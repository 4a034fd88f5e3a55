"""Plain reference for the served LongCat-Flash language model
(meituan-longcat/LongCat-Flash-Chat, ``model_type`` ``longcat_flash``: a
shortcut-connected mixture of experts): the whole causal forward pass of one
sequence in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no batching and
no kernel; latent attention in its **expanded** form (every head's keys and
values made from the compressed row, the two scales where the source puts
them, the rotated ``k_pe`` shared by the heads, the full causal softmax over
the sequence), the softmax router in float32, the experts a plain loop with
a mask, the identity experts a multiply.  Written from the architecture (the
catalog row's ``config`` and ISSUE 61's equations, which follow HF
``transformers`` ``models/longcat_flash/modeling_longcat_flash.py`` and which
the configuration's ``assumed`` lists), not from
``paddle_tpu/models/longcat_flash.py``; it shares no function with
``paddle_tpu/models/``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H; ``num_layers`` layers, each a PAIR of sublayers round one
mixture; ``num_attention_heads`` heads of ``qk_nope_head_dim`` +
``qk_rope_head_dim`` (keys) and ``v_head_dim`` (values) over
``kv_lora_rank`` latent values, the query through ``q_lora_rank``;
``mla_scale_q_lora`` and ``mla_scale_kv_lora``; ``rope_theta``;
``ffn_hidden_size`` the dense MLPs' width; ``n_routed_experts`` experts of
``expert_ffn_hidden_size`` and ``zero_expert_num`` identity experts,
``moe_topk`` a token, ``routed_scaling_factor``; ``rms_norm_eps``.  For the
hidden vectors ``x`` of a sequence (row ``t`` the token at position ``t``),
layer ``i``, whose sublayers' weights are the program's ``l<2i>_`` and
``l<2i+1>_``::

    h0 = rmsnorm(x, ln1[0]);  x = x + mla_0(h0)
    h1 = rmsnorm(x, ln2[0]);  s = moe(h1)               # read HERE
                              x = x + mlp_0(h1)
    h2 = rmsnorm(x, ln1[1]);  x = x + mla_1(h2)
    h3 = rmsnorm(x, ln2[1]);  x = x + mlp_1(h3) + s     # added HERE
    mla:  q = (rmsnorm(h @ Wqa, q_a_layernorm) @ Wqb) * a_q -> a head [q_nope | q_pe]
          [c | k_pe] = h @ Wkva;  c = rmsnorm(c, kv_a_layernorm) * a_kv
          [k_nope_j | v_j] = c @ Wkvb_j;  q_pe_j, k_pe = rope(., t)
          score_j(t, s) = (q_nope_j(t) . k_nope_j(s) + q_pe_j(t) . k_pe(s))
                          * (nope + rope)^-0.5,  s <= t
          mla = concat_j(softmax_s(score_j) v_j) @ Wo
          a_q = (H / q_lora_rank)^0.5, a_kv = (H / kv_lora_rank)^0.5
    rope: plain: x read as interleaved pairs, laid [evens | odds], then
          x * cos + rotate_half(x) * sin at theta^(-2j/P)
    moe:  p = softmax(h1 @ router) over n_routed_experts + zero_expert_num
          S = the moe_topk largest of p + e_score_correction_bias
          g_e = routed_scaling_factor * p_e, e in S       (not renormalised)
          sum_{e in S, e < E, e held} g_e E_e(h1) + (sum_{e in S, e >= E} g_e) h1
    logits = rmsnorm(x, norm) @ lm_head

**The share.**  ``num_experts`` counts the experts *held* (rows of
``wgate`` / ``wup`` / ``wdown``), ``num_experts_published`` the experts that
compute (the router's first columns; the identity experts' follow) and
``first_expert`` the first one held.  The router scores all and chooses over
all, and the sum runs over the held ones and the identity experts: what an
absent expert would add is left out, here as in the program.  Asked for all
of them it is the uncut layer (the share test, tests/test_longcat_flash.py).

Departures: the multi-token-prediction head is no part of this forward pass
(the configuration's ``departures`` say why).  The attention is computed a
block of ``Q_BLOCK`` queries at a time against all the keys: the same full
softmax, with scores of 64 heads over 4,352 positions a block at a time.
``by_layer`` upcasts the served bf16 weights a piece at a time (a mixer, a
block of a dense MLP's width, one expert) so that float32 copies of a
layer's 1.24e9 parameters are never alive together beside the served model.

Weights are the program's parameter dictionary (``embed``, ``head``,
``lnf_g``; per sublayer ``ln1_g``, ``ln2_g``, ``wq_a``, ``q_norm``, ``wq_b``,
``wkva``, ``kv_norm``, ``wkvb [rank, heads x (nope | v)]``, ``wo``, ``w1``,
``w3``, ``w2``; on a pair's first sublayer ``router [H, E + Z]``,
``expert_bias [E + Z]``, ``wgate``, ``wup [Eh, H, F]``, ``wdown [Eh, F,
H]``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, as ``olmoe_ref.py`` has it: the served token's
*deficit* at a position is the reference's largest logit less its logit of
the served token, at most twice the served path's logit error.  The runner's
check sends at most 48 positions; ``benchmark/tests/chip_check_longcat.py``
compares the step's logits themselves at the cell's sizes, contexts to
4,352.
"""

import functools
import types

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 61: ``benchmark/tests/chip_check_longcat.py``
# gives both statistics for each of 64 sequences' last 64 positions, at
# contexts of 230-640, 2,100-2,300 and 4,288-4,352, served and with the weights
# rounded to fp8; its engine leg by depth; the cell's own check for its 64
# positions at contexts under 48).  Logits here have a standard deviation of
# 1.57 over 16,384 tokens.  As in the other routed cells what sets the readings
# is less arithmetic error (root-mean-square logit error 0.052) than the
# routing's discontinuity: 4 routers a token over 768 outputs, the closest
# choice at a position won by 1.0e-4 of probability in the median, so the
# served step and the float32 reference swap an output now and then, and a
# swap moves that position's logits, though less than in the sigmoid families:
# an output here weighs 6 x 0.011 and not a renormalised eighth.
#   the share of positions whose served token is not the reference's argmax:
#     served 0.047-0.109 of the cell's 64 checked positions over eight runs
#     (3-7 of 64), 0.0-0.156 in any one sequence's 64 positions in the chip
#     check's two seeds (medians 0.0625 and 0.078), 0.0-0.074 by band of depth
#     in the engine leg (contexts to 4,200); with the weights rounded to fp8
#     (e4m3), the precision next below the stated bfloat16, 0.66-0.92 a
#     sequence (medians 0.80 and 0.81).  The limit stands between the two, 2.2
#     times the largest reading at the stated precision and 0.53 of the
#     smallest fp8 one.
#     Also over it: every fault in structure (0.34-1.0 a sequence).
#   the largest deficit: served 0.039-0.172 in the cell's checks, 0.205 and
#     0.230 the largest of 64 sequences in the chip check (medians 0.086 and
#     0.090), 0.220 in the engine leg; fp8 2.25-4.72 a sequence (medians 3.19
#     and 3.21).  The limit is 3.5 times the largest served reading and 0.36
#     of the smallest fp8 one; a
#     fault in structure reads 0.9-12.6.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits and rows themselves at contexts to 4,352.
DEFICIT_BOUND = 0.8
DIFFERING_SHARE_BOUND = 0.35

Q_BLOCK = 256               # queries attended at a time
MLP_BLOCK = 4096            # columns of a dense MLP upcast at a time
HEAD_BLOCK = 16384          # columns of the head upcast at a time


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(config, x):
    """x [T, n, P] with row ``t`` turned by position ``t``: plain RoPE."""
    import jax.numpy as jnp

    t, n, dim = x.shape
    inv = 1.0 / float(config["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    x = x.reshape(t, n, dim // 2, 2).transpose(0, 1, 3, 2).reshape(t, n, dim)
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * cos + half * sin


def mla_scales(config):
    """(a_q, a_kv): what the projected query and the normed compressed K/V
    are multiplied by."""
    h = config["hidden_size"]
    return ((h / config["q_lora_rank"]) ** 0.5
            if config["mla_scale_q_lora"] else 1.0,
            (h / config["kv_lora_rank"]) ** 0.5
            if config["mla_scale_kv_lora"] else 1.0)


def mla(config, p, h, rope=True, a_q=True, a_kv=True):
    """-> (the mixer's output [T, H], the rows a latent cache would hold,
    ``[a_kv c | rotated k_pe]`` [T, rank + rope]).  Expanded: every head's
    keys and values are made from the scaled ``c``.  The keywords are the
    tests' broken references: the rotation or either scale left out."""
    import jax
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    t = h.shape[0]
    eps = float(config["rms_norm_eps"])
    heads, nope, pe = config["num_attention_heads"], \
        config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    scale_q, scale_kv = mla_scales(config)
    q = (_rmsnorm(h @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(
        t, heads, nope + pe)
    row = h @ p["wkva"]
    c, k_pe = _rmsnorm(row[:, :rank], p["kv_norm"], eps), row[:, rank:]
    if a_q:
        q = q * scale_q
    if a_kv:
        c = c * scale_kv
    q_pe = q[..., nope:]
    if rope:
        q_pe, k_pe = _rope(config, q_pe), _rope(config, k_pe[:, None])[:, 0]
    kv = (c @ p["wkvb"]).reshape(t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = float(nope + pe) ** -0.5
    outs = []
    for at in range(0, t, Q_BLOCK):
        n = min(Q_BLOCK, t - at)
        scores = (jnp.einsum("qhd,khd->hqk", q[at:at + n, :, :nope], k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_pe[at:at + n], k_pe)) * scale
        seen = jnp.arange(t)[None, :] <= (at + jnp.arange(n))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0).reshape(t, heads * dv) @ p["wo"], \
        jnp.concatenate([c, k_pe], axis=1)


def gates_of(config, p, x, use_bias=True, scaled=True, renormalised=False):
    """-> (gates [T, E + Z] over the whole router: the chosen outputs'
    weights, 0 elsewhere; margin [T]: by how much the last output chosen
    beat the first one left out, in selection score).  ``use_bias``,
    ``scaled`` False and ``renormalised`` True are the tests' broken
    references (the bias ignored, ``routed_scaling_factor`` dropped, the
    gates divided by their sum)."""
    import jax
    import jax.numpy as jnp

    router = p["router"].astype(jnp.float32)
    width = router.shape[1]
    top = config["moe_topk"]
    score = jax.nn.softmax(x @ router, axis=-1)
    select = score + p["expert_bias"].astype(jnp.float32) if use_bias \
        else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, width - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    if renormalised:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    if scaled:
        chosen = chosen * float(config["routed_scaling_factor"])
    return chosen, kth - ranked[:, width - top - 1]


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


def zero_out(config, x, gates):
    """The identity experts' part: each returns its input, so together they
    add the token's input times their gates' sum.  The same on every share."""
    import jax.numpy as jnp

    return jnp.sum(gates[:, config["num_experts_published"]:], axis=1,
                   keepdims=True) * x


def dense_mlp(p, x, pieces=_Plain):
    """A sublayer's dense MLP, ``MLP_BLOCK`` columns of its width at a time
    (each column's product is whole within its block: the same sum)."""
    width = p["w1"].shape[1]
    return sum(pieces.gated_mlp(x, p["w1"][:, at:at + MLP_BLOCK],
                                p["w3"][:, at:at + MLP_BLOCK],
                                p["w2"][at:at + MLP_BLOCK])
               for at in range(0, width, MLP_BLOCK))


MIXER = ("wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo")


def layer(config, first, second, x, pieces=_Plain, **broken):
    """One layer of the source, a pair of sublayers with the weights
    ``first`` (which holds the router and the experts too) and ``second``,
    over x [T, H] -> (x, (gates [T, E + Z], margin [T]), the rows a latent
    cache would keep of each sublayer).  ``broken`` passes
    the tests' faults down (``rope``, ``a_q``, ``a_kv``; ``use_bias``,
    ``scaled``, ``renormalised``; ``zero`` False: the identity part left out;
    ``read_at`` ``"h3"``: the routed part read where it is added)."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    norm = lambda p, name: _rmsnorm(x, p[name].astype(jnp.float32), eps)
    mixed = lambda p, h: pieces.mla(config, {k: p[k] for k in MIXER}, h,
                                    **pick("rope", "a_q", "a_kv"))

    def moe(h):
        routing = pieces.gates_of(
            config, {k: first[k] for k in ("router", "expert_bias")}, h,
            **pick("use_bias", "scaled", "renormalised"))
        s = routed_sum(config, first, h, routing[0], pieces)
        if broken.get("zero", True):
            s = s + zero_out(config, h, routing[0])
        return s, routing

    mix0, rows0 = mixed(first, norm(first, "ln1_g"))
    x = x + mix0
    h1 = norm(first, "ln2_g")
    early = broken.get("read_at", "h1") == "h1"
    if early:
        s, routing = moe(h1)
    x = x + dense_mlp(first, h1, pieces)
    mix1, rows1 = mixed(second, norm(second, "ln1_g"))
    x = x + mix1
    h3 = norm(second, "ln2_g")
    if not early:
        s, routing = moe(h3)
    return x + dense_mlp(second, h3, pieces) + s, routing, (rows0, rows1)


def _refuse_other_settings(config):
    if config["attention_method"] != "MLA" \
            or config["zero_expert_type"] != "identity" \
            or config["attention_bias"] or not config["q_lora_rank"]:
        raise ValueError(
            "the longcat_flash reference is MLA with a compressed query and "
            "no bias in both sublayers of every layer, round a softmax router "
            "over its experts and identity experts")


@functools.lru_cache(maxsize=None)
def _head_block(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g, w: _rmsnorm(x, g.astype(jnp.float32), eps)
                   @ w.astype(jnp.float32))


def sublayer_params(params, l):
    """The program's ``l<l>_`` weights without their prefix."""
    return {k[len("l%d_" % l):]: v for k, v in params.items()
            if k.startswith("l%d_" % l)}


def forward(config, params, tokens, return_kept=False, layer_fn=layer,
            rows=None):
    """Logits [T, vocab] of one sequence of T token ids, or of its positions
    ``rows`` alone (the layers run over the whole sequence either way), and,
    asked for, what a cache would hold of it and what its routers chose:
    ``rows`` each sublayer's rows [T, rank + rope], ``gates`` [T, E + Z] and
    ``margins`` [T] of each layer."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"][tokens].astype(jnp.float32)
    kept = {"rows": [], "gates": [], "margins": []}
    for i in range(config["num_layers"]):
        x, routing, rows_of = layer_fn(
            config, sublayer_params(params, 2 * i),
            sublayer_params(params, 2 * i + 1), x)
        kept["rows"].extend(rows_of)
        kept["gates"].append(routing[0])
        kept["margins"].append(routing[1])
    if rows is not None:
        x = x[jnp.asarray(rows)]
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
        mla=jitted(mla, "rope", "a_q", "a_kv"),
        gates_of=jitted(gates_of, "use_bias", "scaled", "renormalised"),
        gated_mlp=jax.jit(gated_mlp))
    return functools.partial(
        forward, config,
        layer_fn=lambda _c, first, second, x: layer(
            config, first, second, x, pieces, **broken))


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
