"""Solar-Open2-shaped weights for the block of
``paddle_tpu/models/solar_open2.py``, made on the device from the seed in
the dtype they are served in (bfloat16), under the keys of
``solar_open2.param_shapes``: the held experts' weights alone
(``n_routed_experts`` of the router's ``num_experts_published``), the held
slice of the embedding and the head.  Nothing is written to disk: the pair
goes to ``DecodeEngine.add_model`` as it is.

``e_score_correction_bias`` is balanced at set-up on the block's own states,
by ``dots_vlm_decoder.balance`` (the same rule, a router of one group), for
the reason that configuration's ``assumed`` gives.
"""


def layer_kinds(config):
    """The decoder's kind of each held layer: ``gqa_layers`` (0-indexed, as
    published; those past the depth held name no layer here) are softmax
    layers, every other KDA."""
    gqa = set(config["gqa_layers"])
    return ["attention" if l in gqa else "kda"
            for l in range(config["num_hidden_layers"])]


def decoder_config(config):
    # the block first: a program without it cannot run this configuration,
    # and says so here, before any weight, engine or server exists
    from paddle_tpu.models import solar_open2  # noqa: F401
    from paddle_tpu.serving.decode_model import DecoderConfig

    linear = config["linear_attn_config"]
    if config["use_rope"] or not config["use_gqa_gate"] \
            or config["kda_use_full_proj"] \
            or config["first_k_dense_replace"] \
            or not config["norm_topk_prob"] \
            or config["n_shared_experts"] != 1 \
            or config["tie_word_embeddings"] \
            or linear["num_kv_heads"] is not None \
            or config["n_routed_experts"] != config["num_experts"]:
        raise ValueError(
            "the solar_open2 block is KDA (low-rank decay and gate, as many "
            "key heads as heads) beside gated grouped-query attention with "
            "no rotation, sigmoid scores with renormalised gates in every "
            "layer, one shared expert and an untied head")
    return DecoderConfig(
        arch="solar_open2", vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        hidden_size=config["hidden_size"], layer_types=layer_kinds(config),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        kda_neg_eigval=config["kda_allow_neg_eigval"],
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
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    from paddle_tpu.models import solar_open2

    return solar_open2.param_shapes(decoder_config(config))


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
    """What the layers' routers score on the block's own states: ``lanes``
    sequences, a seeded first token each, continued greedily for ``steps``
    positions through ``solar_open2.token_logits`` (the block the engine
    serves, over a contiguous bfloat16 history of K and V and a window and a
    float32 state a lane, all started from zeros at position 0) -> sigmoid
    scores ``[layers, lanes * steps, experts]`` float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2
    from paddle_tpu.pallas_kernels import kda_update
    from paddle_tpu.pallas_kernels.paged_attention import masked_attention

    cfg = decoder_config(config)
    spec = config["expert_bias_balance"]
    lanes, steps = int(spec["lanes"]), int(spec["steps"])
    each = jnp.arange(lanes, dtype=jnp.int32)
    attn_at = {l: i for i, l in enumerate(cfg.attn_layers)}
    kda_at = {l: i for i, l in enumerate(cfg.kda_layers)}
    taps, width = cfg.kda_conv, 3 * cfg.kda_inner

    def step(params, carry, t):
        held, tok = dict(carry[0]), carry[1]
        pos = jnp.full((lanes,), t, jnp.int32)

        def attend(l, q, k, v):
            i = attn_at[l]
            for name, x in (("k", k), ("v", v)):
                held[name] = held[name].at[i, each, pos].set(
                    x.astype(held[name].dtype))
            return masked_attention(q, held["k"][i], held["v"][i], pos + 1,
                                    None, None)

        class Recur:
            @staticmethod
            def window(l, x):
                i = kda_at[l]
                old = held["window"][i]
                new = jnp.concatenate(
                    [old[:, width:], x.astype(old.dtype)], axis=1)
                held["window"] = held["window"].at[i].set(new)
                return jnp.concatenate([old[:, :width], new], axis=1) \
                    .reshape(lanes, taps, width).astype(jnp.float32)

            @staticmethod
            def delta(l, *operands):
                i = kda_at[l]
                state, o = kda_update.advance(held["state"][i], *operands)
                held["state"] = held["state"].at[i].set(state)
                return o

        seen = []
        logits, _counts = solar_open2.token_logits(
            params, cfg, tok, pos, attend, jnp.ones((lanes,), bool), Recur,
            seen=seen)
        scores = jnp.stack([jax.nn.sigmoid(jnp.dot(
            h2, params["l%d_router" % l].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
            for l, h2 in zip(cfg.routed_layers, seen)])
        return (held, jnp.argmax(logits, axis=-1).astype(jnp.int32)), scores

    @jax.jit
    def run(params, first):
        kv = (len(cfg.attn_layers), lanes, steps, cfg.kv_heads, cfg.head_dim)
        held = {"k": jnp.zeros(kv, jnp.bfloat16),
                "v": jnp.zeros(kv, jnp.bfloat16),
                "window": jnp.zeros((len(cfg.kda_layers), lanes,
                                     (taps - 1) * width), first_dtype),
                "state": jnp.zeros((len(cfg.kda_layers), lanes,
                                    cfg.kda_head_dim, cfg.kda_inner),
                                   jnp.float32)}
        _carry, scores = jax.lax.scan(
            lambda carry, t: step(params, carry, t), (held, first),
            jnp.arange(steps, dtype=jnp.int32))
        # [steps, L, lanes, E] -> [L, steps * lanes, E]
        return jnp.swapaxes(scores, 0, 1).reshape(
            scores.shape[1], steps * lanes, scores.shape[3])

    first_dtype = params["embed"].dtype
    first = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                           (int(seed) >> 31) + (1 << 20)),
        (lanes,), 0, cfg.vocab, jnp.int32)
    scores = run(params, first)
    # the scan's carry (a float32 state a lane a layer) goes with its
    # executable: it is no part of what serves
    run.clear_cache()
    return scores


def balanced(config, params, seed):
    """``params`` with every layer's ``expert_bias`` balanced
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
    experts are 210e6 bytes a tensor in bfloat16, its float32 draw twice
    that, so the draws are not all alive at once).  ``bias``, ``conv``,
    ``a_log`` and ``dt_bias`` are the configuration's ``assumed``:
    ``expert_bias`` normal(0, expert_bias_std), then balanced on the block's
    own states (``balanced``) where the configuration gives
    ``expert_bias_balance``; the depthwise convolutions uniform in
    +-1/sqrt(short_conv_kernel_size), ``A_log = log(u)``, u uniform in [1,
    16], and ``dt_bias = softplus^-1(dt)``, dt log-uniform in [0.001,
    0.1]."""
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
        if config.get("expert_bias_balance"):
            out = balanced(config, out, seed)
    return out
