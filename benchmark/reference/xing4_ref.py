"""Plain reference for the served Xing4.0 decoder (XingChen-AGI/
Xing4.0-29B-A4B, ``model_type`` ``xing4_0``): the whole causal forward pass
of one sequence in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no batching over
lanes and no kernel.  Four residual streams a token, mixed round every
sublayer by manifold-constrained hyper-connections (arXiv 2512.24880, over
arXiv 2409.19606); latent attention in its **expanded** form (every head's
keys and values made from the compressed row, the rotated ``k_pe`` shared by
the heads, the plain causal softmax over all positions); the sigmoid router;
the held experts a plain loop with a mask.  Written from the architecture
(the catalog row's ``config``, ISSUE 67's equations and the points the
configuration's ``assumed`` lists), not from ``paddle_tpu/models/xing4.py``;
it imports nothing of ``paddle_tpu.models`` or ``paddle_tpu.serving``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` C, ``hc_mult`` n streams, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min`` / ``_max``; ``num_attention_heads`` heads of
``qk_nope_head_dim`` + ``qk_rope_head_dim`` (keys) and ``v_head_dim``
(values) over ``kv_lora_rank`` latent values, the query through
``q_lora_rank``; ``rope_theta`` and ``rope_scaling`` (YaRN);
``first_k_dense_replace`` dense layers of ``intermediate_size``; the router
over ``num_experts_published`` experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token, ``routed_scaling_factor``;
``rms_norm_eps``.  For the streams ``X [T, n, C]`` of a sequence (row ``t``
the token at position ``t``; ``X_0`` the embedding repeated n times)::

    a layer, twice: F = mla with input_layernorm, then F = ffn with
    post_attention_layernorm; each sublayer its own phi [n C, 2 n + n^2],
    b [2 n + n^2] and scalars a_pre, a_post, a_res:
        r       = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
        [p | q | R] = r @ phi
        H_pre   = sigmoid(a_pre p + b_pre);  H_post = 2 sigmoid(a_post q + b_post)
        M_0     = exp(clip(a_res mat(R) + b_res, clamp_min, clamp_max))
        M_t     = cols(rows(M_{t-1})), t = 1 .. hc_sinkhorn_iters
                  rows(M) = M / (M 1 + hc_eps), cols alike;  H_res = M_last
        u       = H_pre X;   X' = H_res X + H_post^T F(rmsnorm(u, norm))
    mla:  q = rmsnorm(h @ Wqa, q_a_layernorm) @ Wqb -> per head [q_nope | q_pe]
          [c | k_pe] = h @ Wkva;  c = rmsnorm(c, kv_a_layernorm)
          [k_nope_i | v_i] = c @ Wkvb_i;  q_pe_i, k_pe = rope(., t)
          score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_pe_i(t) . k_pe(s))
                          * (nope + rope)^-0.5 * m^2,  s <= t
          mla = concat_i(softmax_s(score_i) v_i) @ Wo
    rope: x read as interleaved pairs, laid [evens | odds], then
          x * cos + rotate_half(x) * sin with YaRN's frequencies;
          m = 0.1 mscale_all_dim ln(factor) + 1
    ffn:  dense layers  (silu(h @ w1) * (h @ w3)) @ w2
          later    s = sigmoid(h @ gate);  S = the num_experts_per_tok
                   largest of s + e_score_correction_bias
                   w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                   sum_{e in S, e held} w_e E_e(h) + shared(h)
    logits = rmsnorm(sum_i X_i, norm) @ lm_head

**The share.**  ``num_experts`` counts the experts *held* (rows of
``wgate`` / ``wup`` / ``wdown``), ``num_experts_published`` the router's
width and ``first_expert`` the first one held.  The router scores all,
chooses over all, renormalises over all the chosen, and the sum runs over the
held ones: what an absent expert would add is left out, here as in the
program.  Asked for all of them it is the uncut layer (the share test,
tests/test_xing4.py).

Departures from the source: the multi-token-prediction module is no part of
this forward pass (the configuration's ``departures`` say why);
``n_group`` 1 is the only router computed (the source's).  The attention
is computed a block of ``Q_BLOCK`` queries at a time against all the keys:
the same full softmax.  ``by_layer`` upcasts the served bf16 weights a piece
at a time (a mixer, a block of the dense MLP's width, one expert), so float32
copies of a layer are never alive together beside the served model.

Weights are the program's parameter dictionary (``embed``, ``head``,
``lnf_g``; per layer ``ln1_g``, ``ln2_g``, ``wq_a``, ``q_norm``, ``wq_b``,
``wkva``, ``kv_norm``, ``wkvb [rank, heads x (nope | v)]``, ``wo``;
``hc_attn_phi``, ``hc_attn_b``, ``hc_attn_a`` and ``hc_mlp_*`` alike; ``w1``,
``w3``, ``w2``; ``router``, ``expert_bias``, ``wgate``, ``wup [E, H, F]``,
``wdown [E, F, H]``, ``shared_w1``, ``shared_w3``, ``shared_w2``).

The server returns tokens, not logits, so ``check`` is teacher-forced through
the tokens alone, as ``dots_vlm_ref.py`` has it: the served token's *deficit*
at a position is the reference's largest logit less its logit of the served
token.  The runner's check sends at most 48 positions;
``benchmark/tests/chip_check_xing.py`` compares the step's logits, cached
rows, streams and maps themselves at contexts of 200-1,792.
"""

import functools
import math
import types

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (my chip runs, PR 67: ``benchmark/tests/chip_check_xing.py`` gives both
# statistics for each of 32 sequences' last 32 positions a seed and again at
# positions 168-199, call 2, seeds 2147483777 | 2147491696; the cell's own
# check for its 64 positions at contexts under 48, calls 1, 1b and 3; PERF.md
# section 6, PR 67).  Logits here have a standard deviation of 1.20 over
# 16,384 tokens and the served step's root-mean-square logit error is 0.14,
# 0.115 of it (dots.vlm1's 0.053 of 1.69, a third of that share: 40 layers
# deep where its cut is 6, behind 38 routers where it has 5); the best two of
# 16,384 such logits lie 0.25 apart in the median, so the served token is not
# the reference's argmax at an eighth of positions, and neither side is wrong
# there.  As dots.vlm1's: what sets the readings is less arithmetic error
# than the routing's discontinuity.
#   the share of positions whose served token is not the reference's argmax:
#     served 6-16 of 64 in the cell's check over fourteen seeds (0.09-0.25,
#     median 0.17), 0.12-0.15 over a seed's 1,024 positions, 0.0-0.31 in any
#     one sequence's 32 (medians 0.125-0.156); with the weights rounded to fp8 (e4m3), the
#     precision next below the stated bfloat16, 0.715 | 0.727 over 1,024
#     positions and 0.53-0.875 in any one sequence's 32 (median 0.73).  The
#     limit stands between the two, 1.6 times the largest served reading over
#     64 positions and 0.55 of fp8's; every fault in structure reads 0.36
#     (one Sinkhorn iteration: under it, and held by the chip check) to 1.0.
#   the largest deficit: served 0.13-1.53 in the cell's check over fourteen
#     seeds (median 0.49), medians 0.33-0.46 a sequence and 1.29 | 1.82 | 1.87
#     the largest of a run's 1,024 positions (a swapped expert each); fp8 1.22-3.80 a sequence
#     (median 2.05).  The limit is 1.6 times the largest served reading over
#     1,024 positions and 0.8 of fp8's largest: not every fp8 sequence is
#     over it, and every one is over the other limit; a fault in structure
#     reads 5.3-8.2.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits, rows, streams and maps themselves at 200-1,792.
DEFICIT_BOUND = 3.0
DIFFERING_SHARE_BOUND = 0.40

GATE_EPS = 1e-20
Q_BLOCK = 256               # queries attended at a time
MLP_BLOCK = 4608            # columns of a dense MLP upcast at a time
HEAD_BLOCK = 16384          # columns of the head upcast at a time


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- the residual streams ------------------------------------------------------

def hc_maps(config, phi, b, a, X, iters=None, clamp=True, post_two=True,
            flat_norm=True):
    """X [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) by one
    sublayer's ``phi``, ``b`` and ``a``.  The keywords are the tests' broken
    references: another number of Sinkhorn iterations, the clamp left out,
    ``H_post`` without its 2, the flattened norm left out."""
    import jax
    import jax.numpy as jnp

    n = int(config["hc_mult"])
    f32 = jnp.float32
    phi, b, a = phi.astype(f32), b.astype(f32), a.astype(f32)
    r = X.reshape(X.shape[0], -1)
    if flat_norm:
        r = r / jnp.sqrt(jnp.mean(r * r, axis=-1, keepdims=True)
                         + float(config["rms_norm_eps"]))
    z = r @ phi
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    if post_two:
        post = 2.0 * post
    logm = a[2] * z[:, 2 * n:] + b[2 * n:]
    if clamp:
        logm = jnp.clip(logm, float(config["mhc_h_res_clamp_min"]),
                        float(config["mhc_h_res_clamp_max"]))
    m = jnp.exp(logm).reshape(-1, n, n)
    eps = float(config["hc_eps"])
    for _ in range(int(config["hc_sinkhorn_iters"]) if iters is None
                   else iters):
        m = m / (m.sum(axis=2, keepdims=True) + eps)        # rows
        m = m / (m.sum(axis=1, keepdims=True) + eps)        # columns
    return pre, post, m


def hc_read(pre, X):
    """u = H_pre X [T, C]."""
    import jax.numpy as jnp

    return jnp.einsum("tn,tnc->tc", pre, X)


def hc_merge(X, y, post, res):
    """X' = H_res X + H_post^T y [T, n, C]."""
    import jax.numpy as jnp

    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * y[:, None]


# -- the mixer -----------------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(config):
    """The ``qk_rope_head_dim / 2`` inverse frequencies (float32): YaRN's
    blend of ``theta^(-2j/P)`` and that over ``factor``, by the linear ramp
    between the two correction dims; plain RoPE's without ``rope_scaling``."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = config["rope_scaling"]
    if y is None:
        return extra.astype(np.float32)
    inter = extra / y["factor"]

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(config, x):
    """x [T, n, P] with row ``t`` turned by position ``t``."""
    import jax.numpy as jnp

    t, n, dim = x.shape
    y = config["rope_scaling"]
    scale = 1.0 if y is None else yarn_mscale(y["factor"], y["mscale"]) \
        / yarn_mscale(y["factor"], y["mscale_all_dim"])
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(config))[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None]
    cos, sin = jnp.cos(emb) * scale, jnp.sin(emb) * scale
    x = x.reshape(t, n, dim // 2, 2).transpose(0, 1, 3, 2).reshape(t, n, dim)
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + half * sin


def mla(config, p, h, rope=True, mscale=True):
    """-> (the mixer's output [T, H], the rows a latent cache would hold,
    ``[c | rotated k_pe]`` [T, rank + rope]).  Expanded: every head's keys
    and values are made from ``c``.  The keywords are the tests' broken
    references: the rotation left out, ``m^2`` left out of the scale."""
    import jax
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    t = h.shape[0]
    eps = float(config["rms_norm_eps"])
    heads, nope, pe = config["num_attention_heads"], \
        config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    q = (_rmsnorm(h @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(
        t, heads, nope + pe)
    row = h @ p["wkva"]
    c, k_pe = _rmsnorm(row[:, :rank], p["kv_norm"], eps), row[:, rank:]
    q_pe = q[..., nope:]
    if rope:
        q_pe, k_pe = _rope(config, q_pe), _rope(config, k_pe[:, None])[:, 0]
    kv = (c @ p["wkvb"]).reshape(t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = float(nope + pe) ** -0.5
    y = config["rope_scaling"]
    if y is not None and mscale:
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    outs = []
    for at in range(0, t, Q_BLOCK):
        n = min(Q_BLOCK, t - at)
        scores = (jnp.einsum("qhd,khd->hqk", q[at:at + n, :, :nope], k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_pe[at:at + n], k_pe)) * scale
        seen = jnp.arange(t)[None, :] <= (at + jnp.arange(n))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs, axis=0).reshape(t, heads * dv)
    return out @ p["wo"], jnp.concatenate([c, k_pe], axis=1)


# -- the feed-forward ----------------------------------------------------------

def gates_of(config, p, x, use_bias=True, scaled=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score).  ``use_bias`` and ``scaled``
    False are the tests' broken references."""
    import jax
    import jax.numpy as jnp

    router = p["router"].astype(jnp.float32)
    n_exp = router.shape[1]
    top = config["num_experts_per_tok"]
    score = jax.nn.sigmoid(x @ router)
    select = score + p["expert_bias"].astype(jnp.float32) if use_bias \
        else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)
    if scaled:
        chosen = chosen * float(config["routed_scaling_factor"])
    return chosen, kth - ranked[:, n_exp - top - 1]


def gated_mlp(x, w1, w3, w2):
    import jax
    import jax.numpy as jnp

    w1, w3, w2 = (w.astype(jnp.float32) for w in (w1, w3, w2))
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


# the pieces of a layer as they are; ``by_layer`` gives them jitted
_Plain = types.SimpleNamespace(mla=mla, gates_of=gates_of,
                               gated_mlp=gated_mlp, hc_maps=hc_maps)


def routed_sum(config, p, x, gates, pieces=_Plain):
    """sum over the held experts of gate * expert(x): expert ``first_expert
    + i`` of the router is row ``i`` of the weights."""
    import jax.numpy as jnp

    first = int(config.get("first_expert", 0))
    out = jnp.zeros_like(x)
    for i in range(config["num_experts"]):
        y = pieces.gated_mlp(x, p["wgate"][i], p["wup"][i], p["wdown"][i])
        out = out + gates[:, first + i:first + i + 1] * y
    return out


def shared_out(config, p, x, pieces=_Plain):
    """The shared expert's output: the same on every share."""
    return pieces.gated_mlp(x, p["shared_w1"], p["shared_w3"],
                            p["shared_w2"])


def dense_mlp(p, x, pieces=_Plain):
    """The dense layers' MLP, ``MLP_BLOCK`` columns of its width at a time
    (each column's product is whole within its block: the same sum)."""
    width = p["w1"].shape[1]
    return sum(pieces.gated_mlp(x, p["w1"][:, at:at + MLP_BLOCK],
                                p["w3"][:, at:at + MLP_BLOCK],
                                p["w2"][at:at + MLP_BLOCK])
               for at in range(0, width, MLP_BLOCK))


def feed_forward(config, p, h, pieces=_Plain, **broken):
    """A layer's feed-forward over h [T, H] -> (its output, (gates, margin)
    of a routed layer, else None)."""
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    if "w1" in p:
        return dense_mlp(p, h, pieces), None
    routing = pieces.gates_of(
        config, {k: p[k] for k in ("router", "expert_bias")}, h,
        **pick("use_bias", "scaled"))
    f = routed_sum(config, p, h, routing[0], pieces)
    if broken.get("shared", True):
        f = f + shared_out(config, p, h, pieces)
    return f, routing


def layer(config, p, X, pieces=_Plain, **broken):
    """One layer over the streams X [T, n, C] with its weights ``p`` (a
    dense layer has ``w1``, a routed one ``router``) -> (X, what was seen on
    the way: ``rows`` a latent cache would keep, ``routing`` (gates [T, E],
    margin [T]) of a routed layer or None, ``maps`` the two sublayers'
    (H_pre, H_post, H_res)).  ``broken`` passes the tests' faults down
    (``iters``, ``clamp``, ``post_two``, ``flat_norm``; ``rope``,
    ``mscale``; ``use_bias``, ``scaled``, ``shared``)."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    f32 = lambda name: p[name].astype(jnp.float32)
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    seen = {"maps": []}

    def mixed(sub, norm, F, X):
        pre, post, res = pieces.hc_maps(
            config, p["hc_%s_phi" % sub], p["hc_%s_b" % sub],
            p["hc_%s_a" % sub], X,
            **pick("iters", "clamp", "post_two", "flat_norm"))
        seen["maps"].append((pre, post, res))
        y = F(_rmsnorm(hc_read(pre, X), f32(norm), eps))
        return hc_merge(X, y, post, res)

    def attention(h):
        mixer = {k: p[k] for k in ("wq_a", "q_norm", "wq_b", "wkva",
                                   "kv_norm", "wkvb", "wo")}
        out, seen["rows"] = pieces.mla(config, mixer, h,
                                       **pick("rope", "mscale"))
        return out

    def ffn(h):
        out, seen["routing"] = feed_forward(config, p, h, pieces, **broken)
        return out

    X = mixed("attn", "ln1_g", attention, X)
    X = mixed("mlp", "ln2_g", ffn, X)
    return X, seen


def _refuse_other_settings(config):
    y = config["rope_scaling"]
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["norm_topk_prob"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1 \
            or config["n_shared_experts"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or not config["q_lora_rank"] \
            or int(config["hc_mult"]) < 2 \
            or (y is not None and y["type"] != "yarn"):
        raise ValueError(
            "the xing4 reference is hc_mult >= 2 residual streams round MLA "
            "with a compressed query, YaRN or plain rotation and no bias, "
            "sigmoid scores in one group (noaux_tc) with renormalised gates "
            "in every layer after the dense lead, one shared expert, SiLU, "
            "an untied head and no next-token-prediction layer")


@functools.lru_cache(maxsize=None)
def _head_block(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g, w: _rmsnorm(x, g.astype(jnp.float32), eps)
                   @ w.astype(jnp.float32))


def layer_params(params, l):
    """Layer ``l``'s weights under their own names."""
    prefix = "l%d_" % l
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward(config, params, tokens, return_kept=False, layer_fn=layer,
            streams_of=()):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what was seen on the way: ``rows`` each layer's rows [T, rank + rope],
    ``gates`` [T, E] and ``margins`` [T] of each routed layer, ``maps`` each
    layer's two (H_pre, H_post, H_res), and ``streams`` layer -> the streams
    [T, n, C] behind each layer of ``streams_of``)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    n = int(config["hc_mult"])
    x = params["embed"][tokens].astype(jnp.float32)
    X = jnp.repeat(x[:, None, :], n, axis=1)
    kept = {"rows": [], "gates": [], "margins": [], "maps": [],
            "streams": {}}
    for l in range(config["num_hidden_layers"]):
        X, seen = layer_fn(config, layer_params(params, l), X)
        kept["rows"].append(seen["rows"])
        kept["maps"].append(seen["maps"])
        if seen["routing"] is not None:
            kept["gates"].append(seen["routing"][0])
            kept["margins"].append(seen["routing"][1])
        if l in streams_of:
            kept["streams"][l] = X
    x = X.sum(axis=1)
    head = _head_block(float(config["rms_norm_eps"]))
    logits = jnp.concatenate(
        [head(x, params["lnf_g"], params["head"][:, at:at + HEAD_BLOCK])
         for at in range(0, params["head"].shape[1], HEAD_BLOCK)], axis=1)
    return (logits, kept) if return_kept else logits


def by_layer(config, **broken):
    """-> ``forward`` a jitted piece at a time (a sublayer's maps, a mixer,
    the router, one gated MLP: a compile a shape): one piece's float32
    weights are all that is alive at once."""
    import jax

    def jitted(piece, *faults):
        fn = jax.jit(functools.partial(piece, config), static_argnames=faults)
        return lambda _config, *args, **kw: fn(*args, **kw)

    pieces = types.SimpleNamespace(
        mla=jitted(mla, "rope", "mscale"),
        gates_of=jitted(gates_of, "use_bias", "scaled"),
        hc_maps=jitted(hc_maps, "iters", "clamp", "post_two", "flat_norm"),
        gated_mlp=jax.jit(gated_mlp))
    return functools.partial(
        forward, config,
        layer_fn=lambda _c, p, X: layer(config, p, X, pieces, **broken))


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
