"""Nemotron-H-shaped weights for the block of
``paddle_tpu/models/nemotron_h.py``, made on the device from the seed in the
dtype they are served in (bfloat16), under the keys of
``nemotron_h.param_shapes``: the held experts' weights alone
(``n_routed_experts`` of the router's ``n_routed_experts_published``), the
held rows of the embedding and columns of the head.  Nothing is written to
disk: the pair goes to ``DecodeEngine.add_model`` as it is.
"""

# the source's letter for a layer's kind -> the decoder's
LAYER_KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import nemotron_h  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    pattern = config["hybrid_override_pattern"]
    if not config["norm_topk_prob"] or config["n_group"] != 1 \
            or config["topk_group"] != 1 or config["tie_word_embeddings"] \
            or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or config["n_shared_experts"] != 1 \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["mamba_proj_bias"] or config["use_bias"] \
            or not config["use_conv_bias"] \
            or len(pattern) != config["num_hidden_layers"] \
            or set(pattern) - set(LAYER_KINDS) \
            or config["norm_eps"] != config["layer_norm_epsilon"] \
            or config["moe_intermediate_size"] != config["intermediate_size"]:
        raise ValueError(
            "the nemotron_h block is sigmoid scores in one group, "
            "renormalised gates, relu^2 experts of one width beside one "
            "shared expert, SiLU in the mixer, a biased convolution and no "
            "other bias, an untied head, and a letter of M*E a layer")
    return DecoderConfig(
        arch="nemotron_h", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        hidden_size=config["hidden_size"],
        layer_types=[LAYER_KINDS[k] for k in pattern],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_conv=config["conv_kernel"],
        ssm_groups=config["n_groups"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_shared_expert_intermediate_size"],
        experts=config["n_routed_experts_published"],
        experts_held=config["n_routed_experts"],
        expert_first=config["first_expert"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        norm_eps=config["norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    from paddle_tpu.models import nemotron_h

    return nemotron_h.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's held
    experts are 0.32e9 bytes a tensor in bfloat16 and their float32 draw
    twice that, so the draws are not all alive at once).  ``bias``, ``conv``,
    ``a_log`` and ``dt_bias`` are the configuration's ``assumed``:
    ``expert_bias`` normal(0, expert_bias_std), the depthwise convolution
    and its bias uniform in +-1/sqrt(conv_kernel), ``A_log = log(u)``, u
    uniform in [1, 16], and ``dt_bias = softplus^-1(dt)``, dt log-uniform in
    [time_step_min, time_step_max]."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"])}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if kind in scale:
            out = scale[kind] * jax.random.normal(key, shape, jnp.float32)
        elif kind == "conv":
            bound = float(config["conv_kernel"]) ** -0.5
            out = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif kind == "a_log":
            out = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                             1.0, 16.0))
        else:
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32,
                jnp.log(float(config["time_step_min"])),
                jnp.log(float(config["time_step_max"]))))
            out = dt + jnp.log(-jnp.expm1(-dt))
        return out.astype(dtype)

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    with jax.default_device(device):
        for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items())):
            out[name] = draw(jax.random.fold_in(key, i), shape, kind) \
                if kind != "ones" else jnp.ones(shape, dtype)
    return out
