"""dots.vlm1-shaped weights for the block of
``paddle_tpu/models/dots_vlm.py``, made on the device from the seed in the
dtype they are served in (bfloat16), under the keys of
``dots_vlm.param_shapes``: the held experts' weights alone (``num_experts``
of the router's ``num_experts_published``), the held slice of the embedding
and the head.  Nothing is written to disk: the pair goes to
``DecodeEngine.add_model`` as it is.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import dots_vlm  # noqa: F401
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
            "the dots_vlm block is MLA in every layer (keys as wide as "
            "values, no bias, YaRN or plain rotation), sigmoid scores "
            "chosen by groups (noaux_tc) with renormalised gates in every "
            "layer after the dense lead, one shared expert, SiLU, an untied "
            "head and no next-token-prediction layer")
    return DecoderConfig(
        arch="dots_vlm", vocab=config["vocab_size"],
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
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    from paddle_tpu.models import dots_vlm

    return dots_vlm.param_shapes(decoder_config(config))


def router_scores(config, params, seed):
    """What the routed layers' routers score on the block's own states:
    ``lanes`` sequences, a seeded first token each, continued greedily for
    ``steps`` positions through ``dots_vlm.token_logits`` (the block the
    engine serves, over a contiguous bfloat16 history of rows as the pool
    holds them) -> sigmoid scores ``[routed layers, lanes * steps,
    experts]`` float32, ``lfm2_moe._route``'s product."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import dots_vlm
    from paddle_tpu.pallas_kernels.paged_attention import masked_latent
    from paddle_tpu.serving.kv_cache import latent_row_of

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    lanes, steps = int(spec["lanes"]), int(spec["steps"])
    row = latent_row_of(cfg.latent_width)
    each = jnp.arange(lanes, dtype=jnp.int32)

    def widened(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, row - x.shape[-1])])

    def step(params, carry, t):
        rows, tok = carry
        pos = jnp.full((lanes,), t, jnp.int32)
        held = [rows]

        def attend(l, q, k, _v):
            held[0] = held[0].at[l, each, pos].set(
                widened(k).astype(rows.dtype))
            return masked_latent(widened(q), held[0][l], pos + 1,
                                 cfg.latent_scale, cfg.latent_rank)

        seen = []
        logits, _counts = dots_vlm.token_logits(
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


def balance(scores, bias, k, n_group, topk_group, updates, speed):
    """DeepSeek-V3's balancing without an auxiliary loss (arXiv 2412.19437
    section 2.1.2: what ``e_score_correction_bias`` is trained by), on fixed
    scores: ``updates`` times every layer's choice is made over ``scores
    [L, N, E]`` with the bias as it stands (the router's rule: a group's
    score its two largest ``score + bias`` summed, the ``topk_group`` best
    groups kept, the ``k`` largest of what they hold chosen), and an
    expert's bias is raised by the update's speed where it was chosen less
    often than the mean and lowered where more often; the speed falls
    geometrically from ``speed[0]`` to ``speed[1]``.  -> (bias [L, E]
    float32, the largest and the root-mean-square relative deviation of an
    expert's load from the mean under the bias returned)."""
    import math

    import jax
    import jax.numpy as jnp

    layers, tokens, experts = scores.shape
    # the tokens along the lanes, a piece of them at a time: a choice is
    # then maxima down the experts' rows, elementwise, and a piece bounds
    # what it holds at once
    piece = math.gcd(tokens, 8192)

    def among_largest(x, n, axis):
        """x >= the n-th largest along ``axis``, by n - 1 removals of the
        largest."""
        rest = x
        for _ in range(n - 1):
            rest = jnp.where(rest >= jnp.max(rest, axis=axis, keepdims=True),
                             -jnp.inf, rest)
        return x >= jnp.max(rest, axis=axis, keepdims=True)

    def chosen(args):
        select, bias = args                     # [E, piece], [E]
        select = select + bias[:, None]
        if n_group > 1:
            grouped = select.reshape(n_group, experts // n_group, piece)
            best2 = jnp.sum(jnp.where(among_largest(grouped, 2, 1), grouped,
                                      0.0), axis=1)
            kept = among_largest(best2, topk_group, 0)
            select = jnp.where(jnp.repeat(kept, experts // n_group, axis=0),
                               select, 0.0)
        return jnp.sum(among_largest(select, k, 0), axis=1,
                       dtype=jnp.float32)

    def loads(pieces, bias):
        counts = jax.lax.map(chosen, (pieces, jnp.repeat(
            bias, tokens // piece, axis=0)))
        return counts.reshape(layers, tokens // piece, experts).sum(axis=1)

    mean = tokens * k / experts
    decay = (speed[1] / speed[0]) ** (1.0 / max(updates - 1, 1))

    @jax.jit
    def run(scores, bias):
        pieces = jnp.moveaxis(scores, 2, 1).reshape(
            layers, experts, tokens // piece, piece)
        pieces = jnp.moveaxis(pieces, 2, 1).reshape(-1, experts, piece)
        bias = jax.lax.fori_loop(
            0, updates, lambda i, bias: bias + speed[0] * decay ** i
            * jnp.sign(mean - loads(pieces, bias)),
            bias.astype(jnp.float32))
        off = loads(pieces, bias) / mean - 1.0
        return bias, jnp.max(jnp.abs(off)), jnp.sqrt(jnp.mean(off * off))

    return run(scores, bias)


def balanced(config, params, seed):
    """``params`` with every routed layer's ``expert_bias`` balanced
    (``balance``) on the scores of the block's own continuation
    (``router_scores``), starting from the seeded draw: the configuration's
    ``assumed`` ``expert_bias_balance``."""
    import jax.numpy as jnp

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    names = ["l%d_expert_bias" % l for l in cfg.routed_layers]
    bias, _worst, _rms = balance(
        router_scores(config, params, seed),
        jnp.stack([params[n] for n in names]),
        cfg.experts_per_token, cfg.n_group, cfg.topk_group,
        int(spec["updates"]), [float(x) for x in spec["speed"]])
    return dict(params, **{n: bias[i].astype(params[n].dtype)
                           for i, n in enumerate(names)})


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a layer's held
    experts are 470e6 bytes a tensor in bfloat16, its float32 draw twice
    that, so the draws are not all alive at once).  ``bias`` is the
    configuration's ``assumed``: ``expert_bias`` normal(0,
    expert_bias_std), then balanced on the block's own states
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
                if kind != "ones" else jnp.ones(shape, dtype)
        if config.get("expert_bias_balance"):
            out = balanced(config, out, seed)
    return out
