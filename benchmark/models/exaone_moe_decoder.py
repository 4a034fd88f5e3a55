"""EXAONE-MoE-shaped weights for the block of
``paddle_tpu/models/exaone_moe.py``, made on the device from the seed in the
dtype they are served in (bfloat16), under the keys of
``exaone_moe.param_shapes``: the held experts' weights alone (``num_experts``
of the router's ``num_experts_published``), the held rows of the embedding
and columns of the head.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.
"""

# the source's names for a layer's kind -> the decoder's
LAYER_KINDS = {"sliding_attention": "window", "full_attention": "attention"}


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import exaone_moe  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    n = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    windows = {w for k, w in zip(config["layer_types"],
                                 config["sliding_windows"])
               if k == "sliding_attention"}
    if not config["norm_topk_prob"] or config["scoring_func"] != "sigmoid" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or config["num_shared_experts"] != 1 \
            or config["num_nextn_predict_layers"] \
            or config["rope_parameters"]["rope_type"] != "default" \
            or len(config["layer_types"]) != n \
            or config["mlp_layer_types"] != ["dense"] * dense \
            + ["sparse"] * (n - dense) \
            or windows - {config["sliding_window"]}:
        raise ValueError(
            "the exaone_moe block is sigmoid scores in one group, "
            "renormalised gates, SiLU, one shared expert, default RoPE, an "
            "untied head, no prediction module, a layer type a layer, the "
            "dense layers first and one window for every sliding layer")
    return DecoderConfig(
        arch="exaone_moe", vocab=config["vocab_size"], layers=n,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        hidden_size=config["hidden_size"],
        layer_types=[LAYER_KINDS[k] for k in config["layer_types"]],
        window=config["sliding_window"], dense_layers=dense,
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_intermediate_size"]
        * config["num_shared_experts"],
        experts=config["num_experts_published"],
        experts_held=config["num_experts"],
        expert_first=config["first_expert"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    from paddle_tpu.models import exaone_moe

    return exaone_moe.param_shapes(decoder_config(config))


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's
    held experts are 1.2e9 bytes in bfloat16 and their float32 draw twice
    that, so the draws are not all alive at once).  ``bias`` is the
    configuration's ``assumed``: ``expert_bias`` normal(0,
    expert_bias_std)."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"])}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        return (scale[kind] * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

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
