"""Xing4.0-shaped weights for the block of ``paddle_tpu/models/xing4.py``,
made on the device from the seed under the keys of ``xing4.param_shapes``:
the projections, embedding and head in the dtype they are served in
(bfloat16), the mixings of the four residual streams (``phi``, ``b``, ``a`` a
sublayer) in float32; the held experts' weights alone (``num_experts`` of the
router's ``num_experts_published``) and the held slice of the embedding and
the head.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.

``e_score_correction_bias`` is balanced at set-up on the block's own states,
by ``dots_vlm_decoder.balance`` (the same rule, a router of one group), for
the reason that configuration's ``assumed`` gives; what the mixings draw is
this configuration's ``assumed.hc_init``.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import xing4  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    scaling = config["rope_scaling"]
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["norm_topk_prob"] \
            or config["moe_layer_freq"] != 1 \
            or config["n_shared_experts"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or config["qk_nope_head_dim"] != config["v_head_dim"] \
            or config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["n_routed_experts"] != config["num_experts"] \
            or (scaling is not None and scaling["type"] != "yarn"):
        raise ValueError(
            "the xing4 block is MLA in every layer (keys as wide as values, "
            "no bias, YaRN or plain rotation), sigmoid scores (noaux_tc) "
            "with renormalised gates in every layer after the dense lead, "
            "one shared expert, SiLU, an untied head and no "
            "next-token-prediction layer, round hc_mult residual streams")
    return DecoderConfig(
        arch="xing4", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        hidden_size=config["hidden_size"],
        layer_types=["latent"] * config["num_hidden_layers"],
        latent_rank=config["kv_lora_rank"],
        latent_rope=config["qk_rope_head_dim"],
        q_rank=config["q_lora_rank"] or 0,
        rope_theta=config["rope_theta"], rope_scaling=scaling,
        dense_layers=config["first_k_dense_replace"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        experts=config["num_experts_published"],
        experts_held=config["num_experts"],
        expert_first=config["first_expert"],
        experts_per_token=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling=config["routed_scaling_factor"],
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(config["mhc_h_res_clamp_min"],
                  config["mhc_h_res_clamp_max"]),
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"], kv_dtype=config.get("kv_dtype"))


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias | hc_phi |
    hc_b | hc_a."""
    from paddle_tpu.models import xing4

    return xing4.param_shapes(decoder_config(config))


def _dots_builder():
    """``dots_vlm_decoder``, the file beside this one, for its ``balance``."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "benchmark_models_dots_vlm_decoder",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "dots_vlm_decoder.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def router_scores(config, params, seed):
    """What the routed layers' routers score on the block's own states:
    ``lanes`` sequences, a seeded first token each, continued greedily for
    ``steps`` positions through the block the engine serves, **a layer at a
    time** (``xing4.layer`` over a contiguous bfloat16 history of rows as the
    pool holds them; ``xing4.streams_in`` and ``logits_out`` at the two
    ends) -> sigmoid scores ``[routed layers, lanes * steps, experts]``
    float32.  A layer at a time because the model whole is a second
    40-layer program beside the step: 170 s of a cold set-up's 562 to trace,
    lower and compile it where one dense and one routed layer take seconds
    (PERF.md section 6, PR 67); the layers' arithmetic is the same."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import xing4
    from paddle_tpu.pallas_kernels.paged_attention import masked_latent
    from paddle_tpu.serving.kv_cache import latent_row_of

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    lanes, steps = int(spec["lanes"]), int(spec["steps"])
    row = latent_row_of(cfg.latent_width)
    each = jnp.arange(lanes, dtype=jnp.int32)
    live = jnp.ones((lanes,), bool)
    f32 = jnp.float32

    def widened(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, row - x.shape[-1])])

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
    def one(dense, p, X, rows, t):
        """A dense or a routed layer's step at position ``t`` over its own
        history ``rows`` [lanes, steps, row] -> (X, rows, the router's
        scores [lanes, experts] or None)."""
        pos = jnp.full((lanes,), t, jnp.int32)
        held = [rows]

        def attend(_l, q, k, _v):
            held[0] = held[0].at[each, pos].set(
                widened(k).astype(rows.dtype))
            return masked_latent(widened(q), held[0], pos + 1,
                                 cfg.latent_scale, cfg.latent_rank)

        seen = []
        X, _chosen, _group = xing4.layer(
            cfg, p.__getitem__, 0 if dense else cfg.dense_layers, X, attend,
            xing4.rotation(cfg, pos), live, seen)
        scores = None if dense else jax.nn.sigmoid(jnp.dot(
            seen[0], p["router"].astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        return X, held[0], scores

    ends = {k: params[k] for k in ("embed", "lnf_g", "head")}
    first = jax.jit(lambda ends, tok: xing4.streams_in(ends, cfg, tok))
    last = jax.jit(lambda ends, X: jnp.argmax(
        xing4.logits_out(ends, cfg, X), axis=-1).astype(jnp.int32))
    layers = []
    for l in range(cfg.layers):
        prefix = "l%d_" % l
        layers.append({k[len(prefix):]: v for k, v in params.items()
                       if k.startswith(prefix)})
    rows = [jnp.zeros((lanes, steps, row), jnp.bfloat16)
            for _ in range(cfg.layers)]
    tok = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                           (int(seed) >> 31) + (1 << 20)),
        (lanes,), 0, cfg.vocab, jnp.int32)
    scores = [[] for _ in cfg.routed_layers]
    for t in range(steps):
        X = first(ends, tok)
        at = jnp.int32(t)
        for l in range(cfg.layers):
            X, rows[l], got = one(l < cfg.dense_layers, layers[l], X,
                                  rows[l], at)
            if got is not None:
                scores[l - cfg.dense_layers].append(got)
        tok = last(ends, X)
    # [L][steps] of [lanes, E] -> [L, steps * lanes, E]
    return jnp.stack([jnp.concatenate(of, axis=0) for of in scores])


def balanced(config, params, seed):
    """``params`` with every routed layer's ``expert_bias`` balanced
    (``dots_vlm_decoder.balance``) on the scores of the block's own
    continuation (``router_scores``), starting from the seeded draw: the
    configuration's ``assumed`` ``expert_bias_balance``."""
    import jax.numpy as jnp

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    names = ["l%d_expert_bias" % l for l in cfg.routed_layers]
    bias, _worst, _rms = _dots_builder().balance(
        router_scores(config, params, seed),
        jnp.stack([params[n] for n in names]),
        cfg.experts_per_token, cfg.n_group, cfg.topk_group,
        int(spec["updates"]), [float(x) for x in spec["speed"]])
    return dict(params, **{n: bias[i].astype(params[n].dtype)
                           for i, n in enumerate(names)})


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array.  Projections,
    embedding and head normal(0, initializer_range) in the served dtype,
    norms at 1, ``expert_bias`` normal(0, expert_bias_std) and then balanced
    on the block's own states (``balanced``) where the configuration gives
    ``expert_bias_balance``; a mixing's ``phi`` normal(0, hc_init.phi_std),
    ``b`` normal(0, hc_init.b_std) with hc_init.b_res_diagonal added on the
    residual map's diagonal, ``a`` hc_init.a, all float32 (the
    configuration's ``assumed.hc_init``)."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        config["weights_dtype"]]
    init, n = config["hc_init"], int(config["hc_mult"])
    scale = {"normal": float(config["initializer_range"]),
             "bias": float(config["expert_bias_std"]),
             "hc_phi": float(init["phi_std"]), "hc_b": float(init["b_std"])}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        x = scale[kind] * jax.random.normal(key, shape, jnp.float32)
        if kind == "hc_b":
            x = x.at[2 * n:].add(float(init["b_res_diagonal"])
                                 * jnp.eye(n, dtype=jnp.float32).reshape(-1))
        return x if kind.startswith("hc_") else x.astype(dtype)

    # a seed may need more than 31 bits
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    with jax.default_device(device):
        for i, (name, (shape, kind)) in enumerate(
                sorted(param_shapes(config).items())):
            if kind == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif kind == "hc_a":
                out[name] = jnp.asarray(init["a"], jnp.float32)
            else:
                out[name] = draw(jax.random.fold_in(key, i), shape, kind)
        if config.get("expert_bias_balance"):
            out = balanced(config, out, seed)
    return out
