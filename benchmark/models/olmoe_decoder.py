"""OLMoE-shaped weights for the routed-expert block of
``paddle_tpu/models/olmoe.py``, made on the device from the seed in the
dtype they are served in (bfloat16), under the keys of
``olmoe.param_shapes``.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import olmoe  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if config["num_key_value_heads"] != heads:
        raise ValueError("the olmoe block is multi-head: num_key_value_heads "
                         "must equal num_attention_heads")
    if config["norm_topk_prob"] or config["attention_bias"] \
            or config["tie_word_embeddings"] or config["clip_qkv"] \
            or config["rope_scaling"] or config["hidden_act"] != "silu":
        raise ValueError("the olmoe block has no renormalised gates, biases, "
                         "tied head, qkv clipping or rope scaling")
    return DecoderConfig(
        arch="olmoe", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], heads=heads,
        head_dim=config["hidden_size"] // heads,
        ffn=config["intermediate_size"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"], experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones."""
    from paddle_tpu.models import olmoe

    return olmoe.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array: a layer's
    experts are 0.8e9 bytes in bfloat16 and their float32 draw twice that,
    so the draws are not all alive at once."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    std = float(config["initializer_range"])

    @functools.partial(jax.jit, static_argnums=(1,))
    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    with jax.default_device(device):
        for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items())):
            out[name] = normal(jax.random.fold_in(key, i), shape) \
                if kind == "normal" else jnp.ones(shape, dtype)
    return out
