"""Kimi-Linear-shaped weights for the block of
``paddle_tpu/models/kimi_linear.py``, made on the device from the seed in
the dtype they are served in (bfloat16), under the keys of
``kimi_linear.param_shapes``: the held experts' weights alone
(``num_experts`` of the router's ``num_experts_published``), the embedding
and the head whole.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.
"""


def layer_kinds(config):
    """The decoder's kind of each layer, from the source's two 1-indexed
    lists."""
    linear = config["linear_attn_config"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    n = config["num_hidden_layers"]
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError("kda_layers and full_attn_layers name each of the "
                         "%d layers once, from 1" % n)
    return ["kda" if l in kda else "latent" for l in range(1, n + 1)]


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import kimi_linear  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    linear = config["linear_attn_config"]
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None \
            or config["qk_nope_head_dim"] != config["v_head_dim"] \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1 \
            or config["num_shared_experts"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError(
            "the kimi_linear block is MLA with no rotation, no query "
            "compression and keys as wide as values, sigmoid scores in one "
            "group with renormalised gates in every layer after the dense "
            "lead, one shared expert, SiLU, an untied head and no "
            "next-token-prediction layer")
    return DecoderConfig(
        arch="kimi_linear", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        hidden_size=config["hidden_size"], layer_types=layer_kinds(config),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        latent_rank=config["kv_lora_rank"],
        latent_rope=config["qk_rope_head_dim"],
        dense_layers=config["first_k_dense_replace"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_intermediate_size"]
        * config["num_shared_experts"],
        experts=config["num_experts_published"],
        experts_held=config["num_experts"],
        expert_first=config["first_expert"],
        experts_per_token=config["num_experts_per_token"],
        routed_scaling=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    from paddle_tpu.models import kimi_linear

    return kimi_linear.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's held
    experts are 75e6 bytes a tensor in bfloat16 and the embedding 0.75e9, its
    float32 draw twice that, so the draws are not all alive at once).
    ``bias``, ``conv``, ``a_log`` and ``dt_bias`` are the configuration's
    ``assumed``: ``expert_bias`` normal(0, expert_bias_std), the depthwise
    convolutions uniform in +-1/sqrt(short_conv_kernel_size), ``A_log =
    log(u)``, u uniform in [1, 16], and ``dt_bias = softplus^-1(dt)``, dt
    log-uniform in [0.001, 0.1]."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"])}
    taps = config["linear_attn_config"]["short_conv_kernel_size"]

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if kind in scale:
            out = scale[kind] * jax.random.normal(key, shape, jnp.float32)
        elif kind == "conv":
            bound = float(taps) ** -0.5
            out = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        elif kind == "a_log":
            out = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                             1.0, 16.0))
        else:
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
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
