"""LFM2-MoE-shaped weights for the block of ``paddle_tpu/models/lfm2_moe.py``,
made on the device from the seed in the dtype they are served in
(bfloat16), under the keys of ``lfm2_moe.param_shapes``.  Nothing is written
to disk: the pair goes to ``DecodeEngine.add_model`` as it is.
"""

# the source's names for a layer's kind -> the decoder's
LAYER_KINDS = {"conv": "conv", "full_attention": "attention"}


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import lfm2_moe  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    heads = config["num_attention_heads"]
    if config["hidden_size"] % heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if not config["norm_topk_prob"] or not config["use_expert_bias"] \
            or config["conv_bias"] or not config["tie_word_embeddings"] \
            or config["rope_parameters"]["rope_type"] != "default" \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            "the lfm2_moe block is renormalised gates, a selection bias, a "
            "convolution with no bias, a tied head, default RoPE, and a "
            "layer type a layer")
    return DecoderConfig(
        arch="lfm2_moe", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], heads=heads,
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        layer_types=[LAYER_KINDS[k] for k in config["layer_types"]],
        conv_taps=config["conv_L_cache"],
        dense_layers=config["num_dense_layers"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"], experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        norm_eps=config["norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | conv | bias."""
    from paddle_tpu.models import lfm2_moe

    return lfm2_moe.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's
    experts are 1.2e9 bytes in bfloat16 and their float32 draw twice that,
    so the draws are not all alive at once).  ``conv`` and ``bias`` are the
    configuration's ``assumed``: the depthwise convolution uniform in
    +-1/sqrt(conv_L_cache), ``expert_bias`` normal(0, expert_bias_std)."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"])}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if kind == "conv":
            bound = float(config["conv_L_cache"]) ** -0.5
            out = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        else:
            out = scale[kind] * jax.random.normal(key, shape, jnp.float32)
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
