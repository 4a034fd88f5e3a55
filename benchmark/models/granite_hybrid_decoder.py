"""Granite-4.0-H-shaped weights for the hybrid block of
``paddle_tpu/models/granite_hybrid.py``, made on the device from the seed
in the dtype they are served in (bfloat16), under the keys of
``granite_hybrid.param_shapes``.  Nothing is written to disk: the pair goes
to ``DecodeEngine.add_model`` as it is.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import granite_hybrid  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("the granite_hybrid block has no routed experts")
    if config["mamba_n_groups"] != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or config["attention_bias"] \
            or not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope" \
            or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" \
            or config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_expand"] * config["hidden_size"] \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            "the granite_hybrid block is one group of B and C, a biased "
            "convolution and no other bias, a tied head, no position "
            "encoding, SiLU, RMSNorm, and a layer type a layer")
    return DecoderConfig(
        arch="granite_hybrid", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], heads=heads,
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        ffn=config["shared_intermediate_size"],
        max_seq=config["n_positions"], dtype=config["weights_dtype"],
        layer_types=config["layer_types"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        norm_eps=config["rms_norm_eps"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | conv | a_log |
    dt_bias."""
    from paddle_tpu.models import granite_hybrid

    return granite_hybrid.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array.  ``conv``,
    ``a_log`` and ``dt_bias`` are Mamba-2's own start (the configuration's
    ``assumed``): the depthwise convolution and its bias uniform in
    +-1/sqrt(mamba_d_conv), ``A_log = log(u)``, u uniform in [1, 16], and
    ``dt_bias = softplus^-1(dt)``, dt log-uniform in [0.001, 0.1]."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    std = float(config["initializer_range"])

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if kind == "normal":
            out = std * jax.random.normal(key, shape, jnp.float32)
        elif kind == "conv":
            bound = float(config["mamba_d_conv"]) ** -0.5
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
