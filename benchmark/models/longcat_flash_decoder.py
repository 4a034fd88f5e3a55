"""LongCat-Flash-shaped weights for the block of
``paddle_tpu/models/longcat_flash.py``, made on the device from the seed in
the dtype they are served in (bfloat16), under the keys of
``longcat_flash.param_shapes``: the held experts' weights alone
(``num_experts`` of the ``num_experts_published`` that compute), the router
over all of them and the ``zero_expert_num`` identity experts, the held
slice of the embedding and the head.  Nothing is written to disk: the pair
goes to ``DecodeEngine.add_model`` as it is.

``e_score_correction_bias`` is balanced at set-up on the block's own states
by dots.vlm1's scheme (``benchmark/models/dots_vlm_decoder.py``), with a
routine of its own: that one is written for a sigmoid router with groups,
this router is a softmax over experts and identity experts with no group
stage, and what is balanced is every one of its 768 outputs, so that the
identity experts take their third.
"""


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import longcat_flash  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    if config["attention_method"] != "MLA" \
            or config["zero_expert_type"] != "identity" \
            or config["attention_bias"] \
            or config["n_routed_experts"] != config["num_experts"] \
            or not config["q_lora_rank"]:
        raise ValueError(
            "the longcat_flash block is MLA with a compressed query and no "
            "bias in both sublayers of every layer, round a softmax router "
            "over its experts and identity experts")
    hidden = config["hidden_size"]
    sublayers = 2 * config["num_layers"]
    return DecoderConfig(
        arch="longcat_flash", vocab=config["vocab_size"], layers=sublayers,
        heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        v_head_dim=config["v_head_dim"], hidden_size=hidden,
        layer_types=["latent"] * sublayers,
        latent_rank=config["kv_lora_rank"],
        latent_rope=config["qk_rope_head_dim"],
        q_rank=config["q_lora_rank"],
        latent_q_scale=(hidden / config["q_lora_rank"]) ** 0.5
        if config["mla_scale_q_lora"] else 1.0,
        latent_kv_scale=(hidden / config["kv_lora_rank"]) ** 0.5
        if config["mla_scale_kv_lora"] else 1.0,
        rope_theta=config["rope_theta"],
        dense_ffn=config["ffn_hidden_size"],
        ffn=config["expert_ffn_hidden_size"],
        experts=config["num_experts_published"],
        experts_held=config["num_experts"],
        expert_first=config["first_expert"],
        zero_experts=config["zero_expert_num"],
        experts_per_token=config["moe_topk"],
        routed_scaling=config["routed_scaling_factor"],
        norm_eps=config["rms_norm_eps"], max_seq=config["n_positions"],
        dtype=config["weights_dtype"])


def param_shapes(config):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    from paddle_tpu.models import longcat_flash

    return longcat_flash.param_shapes(decoder_config(config))


def router_probabilities(config, params, seed):
    """What the pairs' routers score on the block's own states: ``lanes``
    sequences, a seeded first token each, continued greedily for ``steps``
    positions through ``longcat_flash.token_logits`` (the block the engine
    serves, over a contiguous bfloat16 history of rows as the pool holds
    them) -> softmax probabilities ``[pairs, lanes * steps, experts +
    identity experts]`` float32, ``longcat_flash._route``'s product."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import longcat_flash
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
        logits, _counts = longcat_flash.token_logits(
            params, cfg, tok, pos, attend, jnp.ones((lanes,), bool),
            seen=seen)
        scores = jnp.stack([jax.nn.softmax(jnp.dot(
            h1, params["l%d_router" % l].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
            for l, h1 in zip(cfg.routed_layers, seen)])
        return (held[0], jnp.argmax(logits, axis=-1).astype(jnp.int32)), \
            scores

    @jax.jit
    def run(params, first):
        rows = jnp.zeros((cfg.layers, lanes, steps, row), jnp.bfloat16)
        _carry, scores = jax.lax.scan(
            lambda carry, t: step(params, carry, t), (rows, first),
            jnp.arange(steps, dtype=jnp.int32))
        # [steps, pairs, lanes, W] -> [pairs, steps * lanes, W]
        return jnp.swapaxes(scores, 0, 1).reshape(
            scores.shape[1], steps * lanes, scores.shape[3])

    first = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                           (int(seed) >> 31) + (1 << 20)),
        (lanes,), 0, cfg.vocab, jnp.int32)
    return run(params, first)


def balance(scores, bias, k, updates, speed):
    """Balancing without an auxiliary loss (DeepSeek-V3, arXiv 2412.19437
    section 2.1.2, which this family's ``e_score_correction_bias`` is trained
    by too), on fixed probabilities: ``updates`` times every pair's choice is
    made over ``scores [L, N, W]`` with the bias as it stands (the ``k``
    largest ``score + bias`` of a token's ``W`` outputs, experts and identity
    experts alike), and an output's bias is raised by the update's speed
    where it was chosen less often than the mean and lowered where more
    often; the speed falls geometrically from ``speed[0]`` to ``speed[1]``.
    -> (bias [L, W] float32, the largest and the root-mean-square relative
    deviation of an output's load from the mean under the bias returned)."""
    import math

    import jax
    import jax.numpy as jnp

    layers, tokens, width = scores.shape
    # the tokens along the lanes, a piece of them at a time: a choice is
    # then maxima down the outputs' rows, elementwise, and a piece bounds
    # what is held at once
    piece = math.gcd(tokens, 8192)

    def chosen(args):
        select, bias = args                     # [W, piece], [W]
        select = select + bias[:, None]
        # >= the k-th largest, by k - 1 removals of the largest
        rest = select
        for _ in range(k - 1):
            rest = jnp.where(rest >= jnp.max(rest, axis=0, keepdims=True),
                             -jnp.inf, rest)
        return jnp.sum(select >= jnp.max(rest, axis=0, keepdims=True),
                       axis=1, dtype=jnp.float32)

    def loads(pieces, bias):
        counts = jax.lax.map(chosen, (pieces, jnp.repeat(
            bias, tokens // piece, axis=0)))
        return counts.reshape(layers, tokens // piece, width).sum(axis=1)

    mean = tokens * k / width
    decay = (speed[1] / speed[0]) ** (1.0 / max(updates - 1, 1))

    @jax.jit
    def run(scores, bias):
        pieces = jnp.moveaxis(scores, 2, 1).reshape(
            layers, width, tokens // piece, piece)
        pieces = jnp.moveaxis(pieces, 2, 1).reshape(-1, width, piece)
        bias = jax.lax.fori_loop(
            0, updates, lambda i, bias: bias + speed[0] * decay ** i
            * jnp.sign(mean - loads(pieces, bias)),
            bias.astype(jnp.float32))
        off = loads(pieces, bias) / mean - 1.0
        return bias, jnp.max(jnp.abs(off)), jnp.sqrt(jnp.mean(off * off))

    return run(scores, bias)


def balanced(config, params, seed):
    """``params`` with every pair's ``expert_bias`` balanced (``balance``)
    on the probabilities of the block's own continuation
    (``router_probabilities``), starting from the seeded draw: the
    configuration's ``assumed`` ``expert_bias_balance``."""
    import jax.numpy as jnp

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    names = ["l%d_expert_bias" % l for l in cfg.routed_layers]
    bias, _worst, _rms = balance(
        router_probabilities(config, params, seed),
        jnp.stack([params[n] for n in names]), cfg.experts_per_token,
        int(spec["updates"]), [float(x) for x in spec["speed"]])
    return dict(params, **{n: bias[i].astype(params[n].dtype)
                           for i, n in enumerate(names)})


def make_params(config, seed, device):
    """Every weight on ``device``, one jitted call per array (a pair's held
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
                if kind != "ones" else jnp.ones(shape, dtype)
        if config.get("expert_bias_balance"):
            out = balanced(config, out, seed)
    return out
