"""Plain reference for the served Solar-Open2 decoder (upstage/
Solar-Open2-250B, ``model_type`` ``solar_open2``): the whole causal forward
pass of one sequence in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no slots, no
batching and no kernel; the delta-rule recurrence a scan over positions,
attention a causal softmax over all positions, the experts a plain loop with
a mask.  Written from the architecture (the catalog row's ``config`` and
``described_as``, ISSUE 64's equations and the conventions the
configuration's ``assumed`` lists), not from ``paddle_tpu/models/``, of which
it imports nothing.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H; ``gqa_layers`` (0-indexed: the softmax layers; every other
of the ``num_hidden_layers`` is KDA); ``linear_attn_config`` (``num_heads``
SH heads of ``head_dim`` D, ``short_conv_kernel_size`` K);
``num_attention_heads`` heads of ``head_dim`` over ``num_key_value_heads``
KV heads; ``n_routed_experts`` experts *held* of ``moe_intermediate_size``,
``num_experts_per_tok`` a token, ``routed_scaling_factor``;
``n_shared_experts``; ``rms_norm_eps``.  Pre-norm throughout, for the hidden
vectors ``x`` of a sequence (row ``t`` the token at position ``t``)::

    x = x + mixer(rmsnorm(x, input_norm));  x = x + moe(rmsnorm(x, post_norm))
    KDA:  q~, k~, v~ = silu(causal depthwise conv_K(h @ Wq | Wk | Wv))
          q = q~ / |q~| * D^-0.5;  k = k~ / |k~|;  v = v~         # per head
          alpha = exp(-exp(A_log) * softplus(h @ Wfa @ Wfb + dt_bias))
          beta  = 2 sigmoid(h @ Wb)           # kda_allow_neg_eigval: (0, 2)
          S[t] = diag(alpha) S[t-1];  u = v - S^T k;  S[t] += beta outer(k, u)
          o = S[t]^T q;  mixer = (rmsnorm(o, o_norm) * sigmoid(h @ Wga @ Wgb)) @ Wo
    GQA:  q = h @ Wq [heads, D];  k = h @ Wk, v = h @ Wv [KV heads, D]
          score_j(t, s) = q_j(t) . k_{j // (heads / KV)}(s) * D^-0.5,  s <= t
          no rotation, no Q/K norm (use_rope false)
          mixer = (concat_j softmax_s(score_j) v_{j // (heads / KV)}
                   * sigmoid(h @ Wg)) @ Wo                # use_gqa_gate
    moe:  s = sigmoid(h @ gate);  S = the top num_experts_per_tok
          of s + e_score_correction_bias
          w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
          sum_{e in S, e held} w_e E_e(h) + shared(h)
    logits = rmsnorm(x, norm) @ lm_head

**The share.**  ``n_routed_experts`` counts the experts *held* (rows of
``wgate`` / ``wup`` / ``wdown``), ``num_experts_published`` the router's
width and ``first_expert`` the first one held: a share is the held experts'
range.  The router scores all, renormalises over all the chosen, and the sum
runs over the held ones: what an absent expert would add is left out, here
as in the program.  Asked for all of them it is the uncut layer (the share
test, tests/test_solar_open2.py).

**Departures from the plainest form**, each for memory at 6,400 positions
and the published widths, none of them a change of mathematics: attention
goes ``Q_BLOCK`` queries at a time over the keys up to the block's end (a
[heads, T, T] score array would be 10.5e9 B in float32); the head is taken
``HEAD_BLOCK`` columns at a time; ``by_layer`` upcasts one layer's weights at
a time.

Weights are the program's parameter dictionary (``embed``, ``head``,
``lnf_g``; per layer ``ln1_g``, ``ln2_g``; KDA ``wqkv [H, 3 I]`` (q | k |
v), ``conv_w [K, 3 I]`` (row j the tap K - 1 - j tokens back), ``low_a [H,
2 D + SH]`` (Wfa | Wga | Wb), ``f_b``, ``g_b [D, I]``, ``dt_bias [I]``,
``A_log [SH]``, ``o_norm [D]``, ``wo``; GQA ``wq``, ``wg [H, heads D]``,
``wk``, ``wv [H, KV D]``, ``wo``; ``router``, ``expert_bias``, ``wgate``,
``wup [E, H, F]``, ``wdown [E, F, H]``, ``shared_w1``, ``shared_w3``,
``shared_w2``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, as ``olmoe_ref.py`` has it: the served token's
*deficit* at a position is the reference's largest logit less its logit of
the served token, at most twice the served path's logit error.  The runner's
check sends at most 48 positions; ``benchmark/tests/chip_check_solar.py``
compares the step's logits and states themselves at the cell's sizes.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 64: ``benchmark/tests/chip_check_solar.py`` gives
# both statistics for each of 64 sequences' last 48 positions, at contexts of
# 180-6,400; its engine leg for 8,800 tokens by depth; the cell's own check
# for its 64 positions at contexts under 48).  Logits here have a standard
# deviation of 1.28 over 24,576 tokens.  As for the other routed families,
# what sets the readings is less arithmetic error than the routing's
# discontinuity: 8 routers a token over 320 experts each, the closest choice
# at a position won by 4.1e-4 of selection score in the median, so the served
# step and the float32 reference swap an expert in some layer now and then,
# and a swap moves that position's logits (root-mean-square logit error
# 0.095).
#   the share of positions whose served token is not the reference's argmax:
#     served 5-7 of 64 in the cell's checks (0.08-0.11), 0.04-0.42 in any one
#     sequence's 48 positions (median 0.135; the jnp paths 0.02-0.27),
#     0.08-0.17 in every band of depth of the engine leg; with the weights
#     rounded to fp8 (e4m3), the precision next below the stated bfloat16,
#     0.79-1.0 a sequence (median 0.90).  The limit stands between the two,
#     1.44 times the largest served reading and 0.76 of the smallest fp8 one.
#     Also over it: every fault in structure (0.75-1.0); a slot not reset
#     only partly (0.10-0.60 a sequence).
#   the largest deficit: served 0.11-0.23 in the cell's checks, 0.02-0.50 a
#     sequence (median 0.18; the jnp paths up to 0.58), 0.12-0.74 by band in
#     the engine leg (the largest of 3,200 tokens); fp8 2.57-4.67 a sequence
#     (median 3.26).  The limit is twice the largest served reading and 0.58
#     of the smallest fp8 one; a fault in structure reads 1.9-8.7.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits, K and V and states themselves at up to 6,400.
DEFICIT_BOUND = 1.5
DIFFERING_SHARE_BOUND = 0.6

GATE_EPS = 1e-20
L2_EPS = 1e-6
HEAD_BLOCK = 16384          # columns of the head upcast at a time
Q_BLOCK = 256               # queries attended at a time


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def kinds_of(config):
    """``G`` (gated grouped-query attention) or ``K`` (KDA) for each layer:
    ``gqa_layers`` (0-indexed, as published: those past the depth held name
    no layer here) are softmax layers, every other KDA."""
    gqa = set(config["gqa_layers"])
    return "".join("G" if l in gqa else "K"
                   for l in range(config["num_hidden_layers"]))


def _kda(config, p, h, neg_eigval=None, delta=True, qk_norm=True, gate=True):
    """-> (the mixer's output [T, H], the state S [heads, keys, values]
    after the last of the T tokens).  The keywords are the checks' broken
    references: ``beta`` without its factor of 2 (``neg_eigval`` False), the
    delta correction dropped (``S + beta k v^T``), q and k not normalised,
    the output gate dropped."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    linear = config["linear_attn_config"]
    heads, d, taps = linear["num_heads"], linear["head_dim"], \
        linear["short_conv_kernel_size"]
    inner = heads * d
    if neg_eigval is None:
        neg_eigval = bool(config["kda_allow_neg_eigval"])
    qkv = h @ p["wqkv"]
    # depthwise causal convolution as its K-term sum, zeros before position 0
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, 3 * inner), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + t]
                          for j in range(taps)))
    q, k, v = (qkv[:, at:at + inner].reshape(t, heads, d)
               for at in (0, inner, 2 * inner))
    if qk_norm:
        q, k = (x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
                for x in (q, k))
    q = q * d ** -0.5
    low = h @ p["low_a"]
    alpha = jnp.exp(-jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        (low[:, :d] @ p["f_b"] + p["dt_bias"]).reshape(t, heads, d)))
    beta = jax.nn.sigmoid(low[:, 2 * d:])                 # [T, heads]
    if neg_eigval:
        beta = 2.0 * beta

    def one(state, at):
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[:, :, None] * state
        u = v_t - jnp.einsum("hkv,hk->hv", state, k_t) if delta else v_t
        state = state + beta_t[:, None, None] * k_t[:, :, None] \
            * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    last, o = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                           (q, k, v, alpha, beta))
    y = _rmsnorm(o, p["o_norm"], float(config["rms_norm_eps"]))
    if gate:
        y = y * jax.nn.sigmoid((low[:, d:2 * d] @ p["g_b"]).reshape(
            t, heads, d))
    return y.reshape(t, inner) @ p["wo"], last


def _rotated(x, theta):
    """x [T, n, D] turned by position, halves paired (the plain rotation the
    source does NOT apply: the "a rotation applied" fault)."""
    import jax.numpy as jnp

    t, _n, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _gqa(config, p, h, attn_gate=True, rotate=False):
    """-> (the mixer's output [T, H], the K and V a cache would hold, [T, KV
    heads x D] each).  The keywords are the checks' broken references: the
    gate left out, a rotation applied."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kvh, d = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    q = (h @ p["wq"]).reshape(t, heads, d)
    k = (h @ p["wk"]).reshape(t, kvh, d)
    v = (h @ p["wv"]).reshape(t, kvh, d)
    if rotate:
        q, k = (_rotated(x, float(config["rope_theta"])) for x in (q, k))
    # query head j reads KV head j // (heads / kvh)
    q = q.reshape(t, kvh, heads // kvh, d)
    out = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        scores = jnp.einsum("qgrd,kgd->grqk", q[lo:hi], k[:hi]) * d ** -0.5
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("grqk,kgd->qgrd",
                              jax.nn.softmax(scores, axis=-1), v[:hi]))
    o = jnp.concatenate(out, axis=0).reshape(t, heads * d)
    if attn_gate:
        o = o * jax.nn.sigmoid(h @ p["wg"])
    return o @ p["wo"], (k.reshape(t, kvh * d), v.reshape(t, kvh * d))


def gates_of(config, p, x, use_bias=True, renorm=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score).  ``use_bias`` and ``renorm``
    False are the checks' broken references."""
    import jax
    import jax.numpy as jnp

    n_exp = p["router"].shape[1]
    top = config["num_experts_per_tok"]
    score = jax.nn.sigmoid(x @ p["router"])
    select = score + p["expert_bias"] if use_bias else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    if renorm:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                           + GATE_EPS)
    chosen = chosen * float(config["routed_scaling_factor"])
    return chosen, kth - ranked[:, n_exp - top - 1]


def _gated_mlp(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed_sum(config, p, x, gates):
    """sum over the held experts of gate * expert(x): expert ``first_expert
    + i`` of the router is row ``i`` of the weights."""
    import jax.numpy as jnp

    first = int(config.get("first_expert", 0))
    out = jnp.zeros_like(x)
    for i in range(config["n_routed_experts"]):
        y = _gated_mlp(x, p["wgate"][i], p["wup"][i], p["wdown"][i])
        out = out + gates[:, first + i:first + i + 1] * y
    return out


def shared_out(config, p, x):
    if not config["n_shared_experts"]:
        return 0.0
    return _gated_mlp(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def layer(config, kind, p, x, **broken):
    """One block over x [T, H] with its weights ``p`` (upcast here) -> (x,
    what a cache would keep of its mixer: the last state for ``K``, the K
    and V for ``G``; (gates [T, E], margin [T])).  ``broken`` passes the
    checks' faults down (``neg_eigval``, ``delta``, ``qk_norm``, ``gate``;
    ``attn_gate``, ``rotate``; ``use_bias``, ``renorm``, ``shared``)."""
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    eps = float(config["rms_norm_eps"])
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    h = _rmsnorm(x, p["ln1_g"], eps)
    if kind == "K":
        mixed, kept = _kda(config, p, h, **pick("neg_eigval", "delta",
                                                "qk_norm", "gate"))
    else:
        mixed, kept = _gqa(config, p, h, **pick("attn_gate", "rotate"))
    x = x + mixed
    h = _rmsnorm(x, p["ln2_g"], eps)
    routing = gates_of(config, p, h, **pick("use_bias", "renorm"))
    f = routed_sum(config, p, h, routing[0])
    if broken.get("shared", True):
        f = f + shared_out(config, p, h)
    return x + f, kept, routing


def _refuse_other_settings(config):
    if config["use_rope"] or not config["use_gqa_gate"] \
            or config["kda_use_full_proj"] \
            or config["first_k_dense_replace"] \
            or not config["norm_topk_prob"] \
            or config["n_shared_experts"] != 1 \
            or config["tie_word_embeddings"] \
            or config["linear_attn_config"]["num_kv_heads"] is not None:
        raise ValueError(
            "the solar_open2 reference is KDA (low-rank decay and gate, as "
            "many key heads as heads) beside gated grouped-query attention "
            "with no rotation, sigmoid scores with renormalised gates in "
            "every layer, one shared expert and an untied head")


@functools.lru_cache(maxsize=None)
def _head_block(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g, w: _rmsnorm(x, g.astype(jnp.float32), eps)
                   @ w.astype(jnp.float32))


def forward(config, params, tokens, return_kept=False, layer_fn=layer,
            rows=None):
    """Logits [T, vocab] of one sequence of T token ids, or of its positions
    ``rows`` alone (and, asked for, what a cache
    would hold of it: ``states`` each KDA layer's state [heads, keys,
    values] after the last token, ``kv`` each softmax layer's (K, V) [T, KV
    heads x D], ``gates`` [T, E] and ``margins`` [T] of each layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"][tokens].astype(jnp.float32)
    kept = {"states": [], "kv": [], "gates": [], "margins": []}
    for l, kind in enumerate(kinds_of(config)):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, held, routing = layer_fn(config, kind, mine, x)
        kept["states" if kind == "K" else "kv"].append(held)
        kept["gates"].append(routing[0])
        kept["margins"].append(routing[1])
    head = _head_block(float(config["rms_norm_eps"]))
    if rows is not None:
        x = x[np.asarray(rows)]
    logits = jnp.concatenate(
        [head(x, params["lnf_g"], params["head"][:, at:at + HEAD_BLOCK])
         for at in range(0, params["head"].shape[1], HEAD_BLOCK)], axis=1)
    return (logits, kept) if return_kept else logits


def by_layer(config, layer=layer, **broken):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer
    and a length): one layer's float32 weights are all that is alive at
    once."""
    import jax

    @functools.lru_cache(maxsize=None)
    def jitted(kind):
        return jax.jit(functools.partial(layer, config, kind, **broken))

    return functools.partial(
        forward, config,
        layer_fn=lambda _c, kind, p, x: jitted(kind)(p, x))


def check(config, params, cases, pad_to):
    """``cases``: [(prompt ids, served ids)].  -> the number of positions
    compared, how many served tokens differ from the reference's argmax,
    and the largest deficit (see above).  ``ok`` is deficit <= its bound
    and the differing share <= its own."""
    import jax
    import jax.numpy as jnp

    fwd = by_layer(config)
    compared, differing, worst = 0, 0, 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, served in cases:
            seq = list(prompt) + list(served)
            # causal: padding after the sequence cannot reach back into it
            padded = np.zeros(pad_to, np.int32)
            padded[:len(seq)] = seq
            logits = np.asarray(fwd(params, jnp.asarray(padded)))
            for i, tok in enumerate(served):
                row = logits[len(prompt) - 1 + i]
                deficit = float(row.max() - row[int(tok)])
                compared += 1
                differing += deficit > 0
                worst = max(worst, deficit)
    return {"compared": compared, "differing": int(differing),
            "largest_deficit": worst,
            "differing_share_bound": DIFFERING_SHARE_BOUND,
            "ok": worst <= DEFICIT_BOUND
            and differing <= DIFFERING_SHARE_BOUND * compared}
