"""SmallThinker-shaped weights for the block of
``paddle_tpu/models/smallthinker.py``, made on the device from the seed in
the dtype they are served in (bfloat16), under the keys of
``smallthinker.param_shapes``: every expert of every kept layer, the whole
embedding and the untied head.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.
"""

# the source's sliding_window_layout entry -> the decoder's kind of layer
LAYER_KINDS = {0: "attention", 1: "window"}
# ... and the name the derived ``layer_types`` gives it
LAYER_NAMES = {0: "full_attention", 1: "sliding_attention"}

# keys of the family's config.json that would name something the block does
# not compute (the catalog's copy carries none of them; a value that is
# false, 0 or null says the same)
NOT_COMPUTED = ("attention_bias", "use_qk_norm", "qk_norm",
                "moe_num_secondary_experts", "moe_num_shared_experts",
                "num_shared_experts", "first_k_dense_replace",
                "moe_enable_secondary_experts")


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import smallthinker  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    n = config["num_hidden_layers"]
    windowed = list(config["sliding_window_layout"])
    if not config["moe_primary_router_apply_softmax"] \
            or not config["norm_topk_prob"] \
            or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None \
            or len(windowed) != n or set(windowed) - set(LAYER_KINDS) \
            or list(config["rope_layout"]) != windowed \
            or config.get("layer_types",
                          [LAYER_NAMES[w] for w in windowed]) \
            != [LAYER_NAMES[w] for w in windowed] \
            or config.get("sliding_window", config["sliding_window_size"]) \
            != config["sliding_window_size"] \
            or config.get("num_experts", config["moe_num_primary_experts"]) \
            != config["moe_num_primary_experts"] \
            or any(config.get(key) for key in NOT_COMPUTED):
        raise ValueError(
            "the smallthinker block is a softmax router with renormalised "
            "gates ahead of the attention, ReLU-gated primary experts in "
            "every layer and no others, an untied head, plain RoPE on the "
            "window layers and none on the global ones (rope_layout equal "
            "to sliding_window_layout, a 0 or 1 a layer), no attention bias "
            "and no Q/K norm; the derived keys repeat the source's")
    return DecoderConfig(
        arch="smallthinker", vocab=config["vocab_size"], layers=n,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        hidden_size=config["hidden_size"],
        layer_types=[LAYER_KINDS[w] for w in windowed],
        window=config["sliding_window_size"],
        ffn=config["moe_ffn_hidden_size"],
        experts=config["moe_num_primary_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        max_seq=config["n_positions"], dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones."""
    from paddle_tpu.models import smallthinker

    return smallthinker.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's
    experts are three arrays of 0.25e9 B in bfloat16 and their float32 draw
    twice that, so the draws are not all alive at once)."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    std = float(config["initializer_range"])

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    with jax.default_device(device):
        for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items())):
            out[name] = draw(jax.random.fold_in(key, i), shape) \
                if kind != "ones" else jnp.ones(shape, dtype)
    return out
