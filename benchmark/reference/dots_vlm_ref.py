"""Plain reference for the served dots.vlm1 language model (rednote-hilab/
dots.vlm1.inst, ``model_type`` ``dots_vlm``: the DeepSeek-V3 decoder): the
whole causal forward pass of one sequence in straightforward ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``, with no cache,
no batching and no kernel; latent attention in its **expanded** form (every
head's keys and values made from the compressed row, the rotated ``k_pe``
shared by the heads, the full causal softmax over the sequence), the router
with its groups, the experts a plain loop with a mask.  Written from the
architecture (the catalog row's ``config``, ISSUE 49's equations and the
family's modelling conventions, which the configuration's ``assumed``
lists), not from ``paddle_tpu/models/dots_vlm.py``; it shares no function
with ``paddle_tpu/models/``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H; ``num_attention_heads`` heads of ``qk_nope_head_dim`` +
``qk_rope_head_dim`` (keys) and ``v_head_dim`` (values) over
``kv_lora_rank`` latent values, the query through ``q_lora_rank``;
``rope_theta`` and ``rope_scaling`` (YaRN); ``first_k_dense_replace`` dense
layers of ``intermediate_size``; ``n_routed_experts`` experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token out of
``topk_group`` of ``n_group`` groups, ``routed_scaling_factor``;
``rms_norm_eps``.  Pre-norm throughout, for the hidden vectors ``x`` of a
sequence (row ``t`` the token at position ``t``)::

    x = x + mla(rmsnorm(x, input_norm));  x = x + ffn(rmsnorm(x, post_norm))
    mla:  q = rmsnorm(h @ Wqa, q_a_layernorm) @ Wqb -> per head [q_nope | q_pe]
          [c | k_pe] = h @ Wkva;  c = rmsnorm(c, kv_a_layernorm)
          [k_nope_i | v_i] = c @ Wkvb_i;  q_pe_i, k_pe = rope(., t)
          score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_pe_i(t) . k_pe(s))
                          * (nope + rope)^-0.5 * m^2,  s <= t
          mla = concat_i(softmax_s(score_i) v_i) @ Wo
    rope: the family's: x read as interleaved pairs, laid [evens | odds],
          then x * cos + rotate_half(x) * sin with YaRN's frequencies; cos
          and sin times yarn_mscale(factor, mscale) / yarn_mscale(factor,
          mscale_all_dim);  m = yarn_mscale(factor, mscale_all_dim),
          yarn_mscale(f, a) = 0.1 a ln f + 1
    ffn:  dense layers  (silu(h @ w1) * (h @ w3)) @ w2
          later    s = sigmoid(h @ gate);  s' = s + e_score_correction_bias
                   a group's score = its two largest s' summed; s' of all but
                   the topk_group best groups set to 0; S = the
                   num_experts_per_tok largest of what is left
                   w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                   sum_{e in S, e held} w_e E_e(h) + shared(h)
    logits = rmsnorm(x, norm) @ lm_head

**The share.**  ``num_experts`` counts the experts *held* (rows of
``wgate`` / ``wup`` / ``wdown``), ``num_experts_published`` the router's
width and ``first_expert`` the first one held.  The router scores all,
chooses its groups over all, renormalises over all the chosen, and the sum
runs over the held ones: what an absent expert would add is left out, here
as in the program.  Asked for all of them it is the uncut layer (the share
test, tests/test_dots_vlm.py).

Departures: the multi-token-prediction module and the vision tower are no
part of this forward pass (the configuration's ``departures`` say why).  The
attention is computed a block of ``Q_BLOCK`` queries at a time against all
the keys: the same full softmax, with scores of 128 heads over 4,200
positions a block at a time and not 9e9 B at once.  ``by_layer`` upcasts the
served bf16 weights a piece at a time (a mixer, a block of the dense MLP's
width, one expert) so that float32 copies of a layer's 0.94e9 parameters are
never alive together beside the served model.

Weights are the program's parameter dictionary (``embed``, ``head``,
``lnf_g``; per layer ``ln1_g``, ``ln2_g``, ``wq_a``, ``q_norm``, ``wq_b``
(``wq`` where ``q_lora_rank`` is null), ``wkva``, ``kv_norm``, ``wkvb [rank,
heads x (nope | v)]``, ``wo``; ``w1``, ``w3``, ``w2``; ``router``,
``expert_bias``, ``wgate``, ``wup [E, H, F]``, ``wdown [E, F, H]``,
``shared_w1``, ``shared_w3``, ``shared_w2``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, as ``olmoe_ref.py`` has it: the served token's
*deficit* at a position is the reference's largest logit less its logit of
the served token, at most twice the served path's logit error.  The runner's
check sends at most 48 positions; ``benchmark/tests/chip_check_dots.py``
compares the step's logits and cached rows themselves at some hundreds of
positions and past 4,096.
"""

import functools
import math
import types

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 49: ``benchmark/tests/chip_check_dots.py`` gives
# both statistics for each of 32 sequences' last 64 positions a seed, at
# contexts of 150-324 and, for two of them, past 4,096; its engine leg by
# depth; the cell's own check for its 64 positions at contexts under 48).
# Logits here have a standard deviation of 1.69 over 16,160 tokens.  What sets
# the readings is less arithmetic error (root-mean-square logit error 0.053)
# than the routing's discontinuity: 5 routers a token over 8 groups and 256
# experts, the closest choice at a position won by 8.4e-4 of selection score
# in the median, so the served step and the float32 reference swap an expert
# now and then, and a swap moves that position's logits.
#   the share of positions whose served token is not the reference's argmax:
#     served 3 of 64 in the cell's first check (0.047), 0.0-0.109 in any one
#     sequence's 64 positions (medians 0.039-0.047; the jnp paths at most
#     0.078, every projection's output rounded to bfloat16 at most 0.141);
#     with the weights rounded to fp8 (e4m3), the precision next below the
#     stated bfloat16, 0.375-0.609 (median 0.484).  The limit stands between
#     the two, 1.8 times the largest reading at the stated precision and 0.67
#     of the smallest fp8 one.  Also over it: every fault in structure
#     (0.83-1.0); the groups ignored only sometimes (0.08-0.33).
#   the largest deficit: served 0.28 in the cell's first check, medians 0.06 a
#     sequence and 0.59 the largest of 32 (the jnp paths 0.74); fp8 0.93-2.45
#     a sequence (median 1.26).  The limit is twice the largest served reading
#     and 0.95 of fp8's median: not every fp8 sequence is over it, and every
#     one is over the other limit; a fault in structure reads 3.4-12.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits and rows themselves at 214-324 and past 4,096.
DEFICIT_BOUND = 1.2
DIFFERING_SHARE_BOUND = 0.25

GATE_EPS = 1e-20
Q_BLOCK = 256               # queries attended at a time
MLP_BLOCK = 4608            # columns of a dense MLP upcast at a time
HEAD_BLOCK = 16384          # columns of the head upcast at a time


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


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


def mla(config, p, h, rope=True, mscale=True, q_norm=True, kv_norm=True):
    """-> (the mixer's output [T, H], the rows a latent cache would hold,
    ``[c | rotated k_pe]`` [T, rank + rope]).  Expanded: every head's keys
    and values are made from ``c``.  The keywords are the tests' broken
    references: the rotation left out, ``m^2`` left out of the scale, the
    query's or the row's norm dropped."""
    import jax
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    t = h.shape[0]
    eps = float(config["rms_norm_eps"])
    heads, nope, pe = config["num_attention_heads"], \
        config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    if config["q_lora_rank"]:
        cq = h @ p["wq_a"]
        if q_norm:
            cq = _rmsnorm(cq, p["q_norm"], eps)
        q = cq @ p["wq_b"]
    else:
        q = h @ p["wq"]
    q = q.reshape(t, heads, nope + pe)
    row = h @ p["wkva"]
    c, k_pe = row[:, :rank], row[:, rank:]
    if kv_norm:
        c = _rmsnorm(c, p["kv_norm"], eps)
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


def gates_of(config, p, x, use_bias=True, scaled=True, grouped=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score among the kept groups' experts).
    ``use_bias``, ``scaled`` and ``grouped`` False are the tests' broken
    references (the bias ignored, ``routed_scaling_factor`` dropped, the
    groups ignored: a plain choice over all the experts)."""
    import jax
    import jax.numpy as jnp

    router = p["router"].astype(jnp.float32)
    n_exp = router.shape[1]
    top = config["num_experts_per_tok"]
    score = jax.nn.sigmoid(x @ router)
    select = score + p["expert_bias"].astype(jnp.float32) if use_bias \
        else score
    groups = config["n_group"]
    if grouped and groups > 1:
        by_group = select.reshape(-1, groups, n_exp // groups)
        group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
        kth_group = jnp.sort(group_score, axis=-1)[
            :, groups - config["topk_group"]]
        kept = group_score >= kth_group[:, None]
        select = jnp.where(jnp.repeat(kept, n_exp // groups, axis=1),
                           select, 0.0)
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
                               gated_mlp=gated_mlp)


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
    if not config["n_shared_experts"]:
        return 0.0
    return pieces.gated_mlp(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def dense_mlp(p, x, pieces=_Plain):
    """The dense layers' MLP, ``MLP_BLOCK`` columns of its width at a time
    (each column's product is whole within its block: the same sum)."""
    width = p["w1"].shape[1]
    return sum(pieces.gated_mlp(x, p["w1"][:, at:at + MLP_BLOCK],
                                p["w3"][:, at:at + MLP_BLOCK],
                                p["w2"][at:at + MLP_BLOCK])
               for at in range(0, width, MLP_BLOCK))


def layer(config, p, x, pieces=_Plain, **broken):
    """One block over x [T, H] with its weights ``p`` (a dense layer has
    ``w1``, a routed one ``router``) -> (x, the rows a latent cache would
    keep, (gates [T, E], margin [T]) of a routed layer, else None).
    ``broken`` passes the tests' faults down (``rope``, ``mscale``,
    ``q_norm``, ``kv_norm``; ``use_bias``, ``scaled``, ``grouped``,
    ``shared``)."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    f32 = lambda name: p[name].astype(jnp.float32)
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    mixer = {k: p[k] for k in ("wq_a", "q_norm", "wq_b", "wq", "wkva",
                               "kv_norm", "wkvb", "wo") if k in p}
    mixed, rows = pieces.mla(config, mixer, _rmsnorm(x, f32("ln1_g"), eps),
                             **pick("rope", "mscale", "q_norm", "kv_norm"))
    x = x + mixed
    h = _rmsnorm(x, f32("ln2_g"), eps)
    if "w1" in p:
        return x + dense_mlp(p, h, pieces), rows, None
    routing = pieces.gates_of(
        config, {k: p[k] for k in ("router", "expert_bias")}, h,
        **pick("use_bias", "scaled", "grouped"))
    f = routed_sum(config, p, h, routing[0], pieces)
    if broken.get("shared", True):
        f = f + shared_out(config, p, h, pieces)
    return x + f, rows, routing


def _refuse_other_settings(config):
    y = config["rope_scaling"]
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or not config["norm_topk_prob"] \
            or config["moe_layer_freq"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or (y is not None and y["type"] != "yarn"):
        raise ValueError(
            "the dots_vlm reference is MLA in every layer with YaRN or plain "
            "rotation and no bias, sigmoid scores chosen by groups "
            "(noaux_tc) with renormalised gates in every layer after the "
            "dense lead, SiLU, an untied head and no next-token-prediction "
            "layer")


@functools.lru_cache(maxsize=None)
def _head_block(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g, w: _rmsnorm(x, g.astype(jnp.float32), eps)
                   @ w.astype(jnp.float32))


def forward(config, params, tokens, return_kept=False, layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it and what its routers chose: ``rows`` each
    layer's rows [T, rank + rope], ``gates`` [T, E] and ``margins`` [T] of
    each routed layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"][tokens].astype(jnp.float32)
    kept = {"rows": [], "gates": [], "margins": []}
    for l in range(config["num_hidden_layers"]):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, rows, routing = layer_fn(config, mine, x)
        kept["rows"].append(rows)
        if routing is not None:
            kept["gates"].append(routing[0])
            kept["margins"].append(routing[1])
    head = _head_block(float(config["rms_norm_eps"]))
    logits = jnp.concatenate(
        [head(x, params["lnf_g"], params["head"][:, at:at + HEAD_BLOCK])
         for at in range(0, params["head"].shape[1], HEAD_BLOCK)], axis=1)
    return (logits, kept) if return_kept else logits


def by_layer(config, **broken):
    """-> ``forward`` a jitted piece at a time (a mixer, the router, one
    gated MLP: a compile a shape): one piece's float32 weights are all that
    is alive at once."""
    import jax

    def jitted(piece, *faults):
        fn = jax.jit(functools.partial(piece, config), static_argnames=faults)
        return lambda _config, *args, **kw: fn(*args, **kw)

    pieces = types.SimpleNamespace(
        mla=jitted(mla, "rope", "mscale", "q_norm", "kv_norm"),
        gates_of=jitted(gates_of, "use_bias", "scaled", "grouped"),
        gated_mlp=jax.jit(gated_mlp))
    return functools.partial(
        forward, config,
        layer_fn=lambda _c, p, x: layer(config, p, x, pieces, **broken))


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
