"""GLM-5-shaped weights for the block of ``paddle_tpu/models/glm_dsa.py``,
made on the device from the seed in the dtype they are served in
(bfloat16), under the keys of ``glm_dsa.param_shapes``: the held experts'
weights alone (``num_experts`` of the router's ``num_experts_published``),
the held slice of the embedding and the head.  Nothing is written to disk:
the pair goes to ``DecodeEngine.add_model`` as it is.

``e_score_correction_bias`` is balanced at set-up on the block's own states,
by ``dots_vlm_decoder.balance`` (the same rule, a router of one group), for
the reason that configuration's ``assumed`` gives.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import glm_dsa  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    rope = config["rope_parameters"]
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["norm_topk_prob"] \
            or config["moe_layer_freq"] != 1 \
            or config["n_shared_experts"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["n_routed_experts"] != config["num_experts"] \
            or rope["rope_type"] != "default" \
            or not config["rope_interleave"] \
            or not config["indexer_rope_interleave"] \
            or not config["q_lora_rank"]:
        raise ValueError(
            "the glm_dsa block is MLA in every layer behind an indexer "
            "(compressed query, no bias, plain interleaved rotation of query, "
            "key and index alike), sigmoid scores chosen without groups "
            "(noaux_tc) with renormalised gates in every layer after the "
            "dense lead, one shared expert, SiLU, an untied head and no "
            "next-token-prediction layer")
    return DecoderConfig(
        arch="glm_dsa", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        v_head_dim=config["v_head_dim"],
        hidden_size=config["hidden_size"],
        layer_types=["latent"] * config["num_hidden_layers"],
        latent_rank=config["kv_lora_rank"],
        latent_rope=config["qk_rope_head_dim"],
        q_rank=config["q_lora_rank"],
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        rope_theta=rope["rope_theta"],
        dense_layers=config["first_k_dense_replace"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        experts=config["num_experts_published"],
        experts_held=config["num_experts"],
        expert_first=config["first_expert"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | zeros | bias."""
    from paddle_tpu.models import glm_dsa

    return glm_dsa.param_shapes(decoder_config(config))


def _dots_builder():
    """``dots_vlm_decoder``, the file beside this one, for its ``balance``."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "dots_vlm_decoder", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "dots_vlm_decoder.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def router_scores(config, params, seed):
    """What the routed layers' routers score on the block's own states:
    ``lanes`` sequences, a seeded first token each, continued greedily for
    ``steps`` positions through ``glm_dsa.token_logits`` (the block the
    engine serves, over a contiguous bfloat16 history of rows as the pool
    holds them) -> sigmoid scores ``[routed layers, lanes * steps,
    experts]`` float32.  ``steps`` is at most ``index_topk``, so a query
    attends every position before it and the indexer has nothing to decide:
    its output is dropped here."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import glm_dsa
    from paddle_tpu.pallas_kernels.paged_attention import masked_latent
    from paddle_tpu.serving.kv_cache import latent_row_of

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    lanes, steps = int(spec["lanes"]), int(spec["steps"])
    if steps > cfg.index_topk:
        raise ValueError("expert_bias_balance.steps %d is past index_topk "
                         "%d: the balancing attends densely"
                         % (steps, cfg.index_topk))
    row = latent_row_of(cfg.latent_width)
    each = jnp.arange(lanes, dtype=jnp.int32)

    def widened(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, row - x.shape[-1])])

    def step(params, carry, t):
        rows, tok = carry
        pos = jnp.full((lanes,), t, jnp.int32)
        held = [rows]

        def attend(l, q, k, _index):
            held[0] = held[0].at[l, each, pos].set(
                widened(k).astype(rows.dtype))
            return masked_latent(widened(q), held[0][l], pos + 1,
                                 cfg.latent_scale, cfg.latent_rank)

        seen = []
        logits, _counts = glm_dsa.token_logits(
            params, cfg, tok, pos, attend, jnp.ones((lanes,), bool),
            seen=seen)
        scores = jnp.stack([jax.nn.sigmoid(jnp.dot(
            h2, params["l%d_router" % l].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
            for l, h2 in zip(cfg.routed_layers, seen)])
        return (held[0], jnp.argmax(logits, axis=-1).astype(jnp.int32)), \
            scores

    @jax.jit
    def run(params, first):
        rows = jnp.zeros((cfg.layers, lanes, steps, row), jnp.bfloat16)
        _carry, scores = jax.lax.scan(
            lambda carry, t: step(params, carry, t), (rows, first),
            jnp.arange(steps, dtype=jnp.int32))
        # [steps, L, lanes, E] -> [L, steps * lanes, E]
        return jnp.swapaxes(scores, 0, 1).reshape(
            scores.shape[1], steps * lanes, scores.shape[3])

    first = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                           (int(seed) >> 31) + (1 << 20)),
        (lanes,), 0, cfg.vocab, jnp.int32)
    return run(params, first)


def balanced(config, params, seed):
    """``params`` with every routed layer's ``expert_bias`` balanced
    (``dots_vlm_decoder.balance``, one group) on the scores of the block's
    own continuation (``router_scores``), starting from the seeded draw: the
    configuration's ``assumed`` ``expert_bias_balance``."""
    import jax.numpy as jnp

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    names = ["l%d_expert_bias" % l for l in cfg.routed_layers]
    bias, _worst, _rms = _dots_builder().balance(
        router_scores(config, params, seed),
        jnp.stack([params[n] for n in names]),
        cfg.experts_per_token, 1, 1,
        int(spec["updates"]), [float(x) for x in spec["speed"]])
    return dict(params, **{n: bias[i].astype(params[n].dtype)
                           for i, n in enumerate(names)})


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's held
    experts are 403e6 bytes a tensor in bfloat16, its float32 draw twice
    that, so the draws are not all alive at once).  ``expert_bias`` is
    normal(0, expert_bias_std), then balanced on the block's own states
    (``balanced``) where the configuration gives ``expert_bias_balance``."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"])}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        return (scale[kind] * jax.random.normal(key, shape, jnp.float32)) \
            .astype(dtype)

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    with jax.default_device(device):
        for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items())):
            out[name] = draw(jax.random.fold_in(key, i), shape, kind) \
                if kind in scale \
                else jnp.full(shape, float(kind == "ones"), dtype)
        if config.get("expert_bias_balance"):
            out = balanced(config, out, seed)
    return out
