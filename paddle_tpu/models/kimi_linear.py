"""Kimi-Linear decoder block (moonshotai/Kimi-Linear-48B-A3B-Instruct:
``model_type`` ``kimi_linear``) as pure functions of ``(params, cfg, tok,
pos, attend, live, recur)``, called by the decode steps of
``serving/decode_model.py`` under the same contract as the other blocks: one
token per lane through every layer.  The mixers are of two kinds, named by
``cfg.layer_types``, and the feed-forward of two, by the layer's place:

* ``kda``: Kimi Delta Attention, a linear-attention mixer whose memory is a
  matrix a head, moved by a gated delta rule.  What it keeps between tokens,
  the last ``kda_conv - 1`` inputs of the three depthwise convolutions (q, k
  and v side by side) and the state, lives wherever the step maker says:
  ``recur.window(l, qkv)`` pushes this token's input and returns the
  ``kda_conv`` newest, ``recur.delta(l, alpha, beta, k, v, q)`` moves the
  state one token and returns its read-out.
* ``latent``: multi-head latent attention (MLA), served *absorbed*: the
  cache keeps one row a token, ``[c | k_pe]`` (``latent_rank +
  latent_rope`` values), and the key's and the value's up-projections are
  folded into the query and the output.  ``attend(l, q, row, None)`` owns
  the row's write and the history read and returns the probabilities' sum
  of the rows' latent parts.  This family applies **no** position encoding
  there (``mla_use_nope``: the KDA layers carry position) and projects the
  query in one matrix; ``latent_mixer`` takes a rotation of the row's and
  the query's ``latent_rope`` values, a compressed query, a selection and
  two scales as options, for the families that share it (``dots_vlm``,
  ``glm_dsa``, ``longcat_flash``).
* layer 0 (``cfg.dense_layers``) ends in a SiLU-gated MLP of width
  ``cfg.dense_ffn``; every later one in experts of width ``cfg.ffn`` routed
  over ``cfg.experts``, ``cfg.experts_per_token`` a token, beside one shared
  expert of width ``cfg.shared_ffn``: ``exaone_moe``'s routed layer, the
  share it may hold (``experts_held`` from ``expert_first`` on) included.

Pre-norm throughout.  For hidden ``x`` of one token, ``D`` the KDA head's
width, ``SH`` its heads::

    h = rmsnorm(x, ln1_g);  x = x + mixer(h)
    kda:     q~, k~, v~ = split(silu(conv(h @ wqkv)), 3)       # [SH x D] each
             q = q~ / |q~|_2 * D^-0.5;  k = k~ / |k~|_2;  v = v~   # per head
             f, g, b = split(h @ low_a, [R, R, SH])            # R = D
             alpha = exp(-exp(A_log) * softplus(f @ f_b + dt_bias))  # [SH, D]
             beta  = sigmoid(b)                                # [SH]
             S <- diag(alpha) S;  u = v - S^T k;  S <- S + beta outer(k, u)
             o = S^T q                                         # per head
             mixer = (rmsnorm(o, o_norm [D]) * sigmoid(g @ g_b)) @ wo
    latent:  q = h @ wq -> per head [q_nope D | q_pe P]
             [c | k_pe] = h @ wkva;  c = rmsnorm(c, kv_norm)   # the row kept
             q_lat_i = wkvb_i^K q_nope_i                       # [rank]
             score_i(s) = (q_lat_i . c(s) + q_pe_i . k_pe(s)) * (D + P)^-0.5
             o_lat_i = sum_s softmax_s(score_i) c(s)
             mixer = concat(wkvb_i^V^T o_lat_i) @ wo
    h2 = rmsnorm(x, ln2_g);  x = x + ffn(h2)        # dense, or routed + shared

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  ``wkvb [rank,
heads * 2 D]`` holds, a head, the key's up-projection and then the value's.
The expanded form (``k_nope_i(s) = c(s) @ wkvb_i^K``, ``v_i(s) = c(s) @
wkvb_i^V``) is the same mathematics (tests/test_kimi_linear.py).

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the convolution, the gates, the decay, the
state's update and read-out and the residual additions float32; the state
float32 wherever it lives.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``; kda layers
``wqkv [H, 3 I]`` (``I = SH * D``; q, k, v side by side), ``conv_w [K, 3 I]``
(row j the tap on the input K - 1 - j tokens back), ``low_a [H, 2 R + SH]``
(the decay's and the output gate's down-projections and ``b_proj``),
``f_b``, ``g_b [R, I]``, ``dt_bias [I]``, ``A_log [SH]``, ``o_norm [D]``,
``wo [I, H]``; latent layers ``wq [H, heads * (D + P)]``, ``wkva [H, rank +
P]``, ``kv_norm [rank]``, ``wkvb [rank, heads * 2 D]`` (as published; a
decode step holds it as ``laid_out`` leaves it), ``wo [heads * D, H]``; the
dense layer ``w1``, ``w3 [H, F]``, ``w2 [F, H]``; routed layers as
``exaone_moe``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import exaone_moe as _exaone
from . import granite_hybrid as _granite
from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm

__all__ = ["token_logits", "param_shapes", "kda_param_shapes",
           "init_params", "laid_out",
           "routed_part", "shared_part", "BIAS_STD", "FAMILY"]

FAMILY = DecoderFamily(kinds=("kda", "latent"), routes="after_dense",
                       expert_matrices=3, dense_lead=True, holds_share=True,
                       own_stream_width=True, grouped_router=True,
                       shared_expert=True)

# what the L2 norm of a KDA head's q and k adds under the root
L2_EPS = 1e-6
# standard deviation of a seeded ``expert_bias``.  The stream is pre-norm, as
# Nemotron-H's: the router sees rmsnorm(x), its logits (weights normal(0,
# 0.02) over 2,304) have a standard deviation of 0.96, and a token's eighth
# and ninth best of 256 scores lie 0.0046 apart in the median.  Started from
# ``nemotron_h.BIAS_STD`` and read on the CPU before the first chip call
# (tests/test_kimi_linear.py, tokens drawn apart): 0.01 re-decides the
# choice on 57-66% of tokens (a block that ignores it is seen) and leaves the
# held experts a 32-lane step hits at 9.96-10.31 of 16 over six seeds, where
# an even router reads 9.83-10.50 (10.21 expected).  Served whole on the chip
# the cell's step hits 8.2-8.7 (PERF.md section 6, PR 46).
BIAS_STD = 0.01

routed_part = _exaone.routed_part
shared_part = _exaone.shared_part


def kda_param_shapes(cfg):
    """(name, shape, kind) of a kda layer's mixer."""
    h, inner, sh, r = cfg.hidden, cfg.kda_inner, cfg.kda_heads, \
        cfg.kda_head_dim
    return (("wqkv", (h, 3 * inner), "normal"),
            ("conv_w", (cfg.kda_conv, 3 * inner), "conv"),
            ("low_a", (h, 2 * r + sh), "normal"),
            ("f_b", (r, inner), "normal"), ("g_b", (r, inner), "normal"),
            ("dt_bias", (inner,), "dt_bias"), ("A_log", (sh,), "a_log"),
            ("o_norm", (r,), "ones"), ("wo", (inner, h), "normal"))


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    e, held, fe, fd, fs = cfg.experts, cfg.experts_held, cfg.ffn, \
        cfg.dense_ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    mixers = {
        "kda": kda_param_shapes(cfg),
        "latent": (("wq", (h, cfg.heads * (d + cfg.latent_rope)), "normal"),
                   ("wkva", (h, cfg.latent_width), "normal"),
                   ("kv_norm", (cfg.latent_rank,), "ones"),
                   ("wkvb", (cfg.latent_rank, cfg.heads * 2 * d), "normal"),
                   ("wo", (cfg.heads * d, h), "normal")),
    }
    dense = (("w1", (h, fd), "normal"), ("w3", (h, fd), "normal"),
             ("w2", (fd, h), "normal"))
    routed = (("router", (h, e), "normal"), ("expert_bias", (e,), "bias"),
              ("wgate", (held, h, fe), "normal"),
              ("wup", (held, h, fe), "normal"),
              ("wdown", (held, fe, h), "normal"),
              ("shared_w1", (h, fs), "normal"),
              ("shared_w3", (h, fs), "normal"),
              ("shared_w2", (fs, h), "normal"))
    for l, kind in enumerate(cfg.layer_types):
        for name, shape, init in (
                ("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones")
        ) + mixers[kind] + (dense if l < cfg.dense_layers else routed):
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD, shapes=None):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms at 1, ``expert_bias`` normal(0, ``bias_std``), and for
    what sets how much the state holds and how long it remembers the start
    Granite's block gives Mamba-2's (``granite_hybrid.init_params`` says
    why): the depthwise convolutions uniform in +-1/sqrt(taps), ``A_log =
    log(u)``, u uniform in [1, 16], and ``dt_bias = softplus^-1(dt)``, dt
    log-uniform in [0.001, 0.1].  Host-side: tests and demo bundles.
    ``shapes`` is another family's ``param_shapes`` of the same kinds
    (``solar_open2``)."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]

    def make(shape, kind):
        if kind == "bias":
            return r.standard_normal(shape) * bias_std
        if kind == "conv":
            bound = cfg.kda_conv ** -0.5
            return r.uniform(-bound, bound, shape)
        return _granite.draw(r, cfg, shape, kind, std)

    return {name: make(shape, kind).astype(np.float32).astype(dtype)
            for name, (shape, kind) in sorted(
                (shapes or param_shapes)(cfg).items())}


def laid_out(cfg, params):
    """``params`` as a decode step holds them (``decode_model.laid_out``):
    each latent layer's ``wkvb [rank, heads * (D + Dv)]`` gives way to the
    two arrays ``latent_mixer`` multiplies, heads leading: ``wkvb_k [heads,
    rank, D]``, the key's up-projection as ``bhd,hrd->bhr`` reads it, and
    ``wkvb_v [heads, Dv, rank]``, the value's as ``bhr,hdr->bhd`` does
    (``Dv`` is ``cfg.v_head_dim``: ``D`` unless the model says otherwise).
    The same values in the same dtype, turned once on the device; published,
    the head axis lies in the middle and XLA turns the weight in every
    step (PERF.md section 6, PR 52).  A new dict: the caller's keeps the
    published form, which bundles, references and checks read."""
    rank, heads, d = cfg.latent_rank, cfg.heads, cfg.head_dim
    out = dict(params)
    for l in cfg.latent_layers:
        up = jnp.asarray(out.pop("l%d_wkvb" % l)).reshape(
            rank, heads, d + cfg.v_head_dim)
        out["l%d_wkvb_k" % l] = up[:, :, :d].transpose(1, 0, 2)
        out["l%d_wkvb_v" % l] = up[:, :, d:].transpose(1, 2, 0)
    return out


def _l2(x):
    """x [B, SH, D] with each head's values at unit length."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# by name, so that a check can serve the block with one of them taken out
# (benchmark/tests/chip_check_kimi.py, chip_check_dots.py): the KDA output's
# gate, and the norms of the latent row's and of a compressed query's
# compressed part
_out_gate = jax.nn.sigmoid
_kv_norm = _rmsnorm
_q_norm = _rmsnorm


def kda_mixer(cfg, p, l, h, recur):
    """The KDA mixer of layer ``l`` over h [B, H] float32: this family's,
    and ``solar_open2``'s, which reads one more thing of the configuration
    (``cfg.kda_neg_eigval``: ``beta`` twice the sigmoid)."""
    f32 = jnp.float32
    bb = h.shape[0]
    inner, sh, d = cfg.kda_inner, cfg.kda_heads, cfg.kda_head_dim
    by_head = lambda a: a.reshape(bb, sh, d)
    with jax.named_scope("conv"):
        window = recur.window(l, _mm(h, p("wqkv")))        # [B, K, 3 I]
        qkv = jax.nn.silu(
            jnp.sum(p("conv_w").astype(f32)[None] * window, axis=1))
        q, k, v = (by_head(qkv[:, at:at + inner])
                   for at in (0, inner, 2 * inner))
        q = _l2(q) * d ** -0.5
        k = _l2(k)
    with jax.named_scope("state"):
        low = _mm(h, p("low_a"))
        decay = by_head(_mm(low[:, :d], p("f_b")) + p("dt_bias").astype(f32))
        alpha = jnp.exp(-jnp.exp(p("A_log").astype(f32))[None, :, None]
                        * jax.nn.softplus(decay))
        beta = jax.nn.sigmoid(low[:, 2 * d:])
        if cfg.kda_neg_eigval:
            # in (0, 2): I - beta k k^T has the eigenvalue 1 - beta in
            # (-1, 1) along a unit k
            beta = 2.0 * beta
        o = recur.delta(l, alpha, beta, k, v, q)           # [B, SH, D]
    with jax.named_scope("out"):
        gate = _out_gate(by_head(_mm(low[:, d:2 * d], p("g_b"))))
        y = _rmsnorm(o, p("o_norm"), cfg.norm_eps) * gate
        return _mm(y.reshape(bb, inner), p("wo"))


def _held_laid_out(p):
    """The key's and the value's up-projection as ``laid_out`` left them
    (``[heads, rank, D]``, ``[heads, D, rank]``) where the layer's
    parameters hold them so, else None: told apart by the keys the layer
    has, not by a flag."""
    try:
        return p("wkvb_k"), p("wkvb_v")
    except KeyError:
        return None


def latent_mixer(cfg, p, l, h, attend, rotate=None, index=None):
    """The absorbed MLA mixer of layer ``l`` over h [B, H] float32.  Five
    options, for the families that share it (``dots_vlm``, ``glm_dsa``,
    ``longcat_flash``):
    ``cfg.q_rank`` given, the query goes through a low-rank pair with a
    norm between (``wq_a``, ``q_norm``, ``wq_b``) and not through ``wq``;
    ``rotate`` given, ``rotate(x [B, n, latent_rope])`` turns the row's
    shared key and each head's query's last ``latent_rope`` values by the
    lanes' positions, before ``attend`` writes the row and before the
    absorb; ``index`` given, ``index(cq)`` makes of the normed compressed
    query what ``attend`` chooses the attended positions by (an indexer's
    queries, their heads' weights and this token's index key), handed to it
    where a plain latent layer hands None; ``cfg.latent_q_scale`` other than
    1, the projected query (both its parts) is multiplied by it, and
    ``cfg.latent_kv_scale`` other than 1, the normed compressed K/V, before
    the row is written: the cache then holds ``[a_kv c | k_pe]``, from which
    ``wkvb`` makes the scaled keys and values the source makes, so a score
    and an output are the published ones with nothing folded into a weight.
    A head's values are
    ``cfg.v_head_dim`` wide, its own key part ``cfg.head_dim``.  ``wkvb`` is
    read as ``laid_out`` left it or, handed the published array, cut from
    that: the same products of the same values."""
    bb = h.shape[0]
    heads, d, rank = cfg.heads, cfg.head_dim, cfg.latent_rank
    dv = cfg.v_head_dim
    dot = lambda eq, a, b: jnp.einsum(
        eq, a.astype(b.dtype), b, preferred_element_type=jnp.float32)
    laid = _held_laid_out(p)
    # published, a head's columns of wkvb: the key's up-projection, then
    # the value's
    up = None if laid else p("wkvb").reshape(rank, heads, d + dv)
    # (``absorb`` is opened again around each rotation so that ``rope`` is
    # its sibling, and without one the operations come in the order they
    # had before there were options: the lowered step is the same text)
    with jax.named_scope("q_compress" if cfg.q_rank else "absorb"):
        cq = _q_norm(_mm(h, p("wq_a")), p("q_norm"), cfg.norm_eps) \
            if cfg.q_rank else None
        q = _mm(cq, p("wq_b")) if cfg.q_rank else _mm(h, p("wq"))
        q = q.reshape(bb, heads, d + cfg.latent_rope)
        if cfg.latent_q_scale != 1.0:
            q = q * cfg.latent_q_scale
    chosen_by = index(cq) if index is not None else None
    with jax.named_scope("absorb"):
        row = _mm(h, p("wkva"))
        c = _kv_norm(row[:, :rank], p("kv_norm"), cfg.norm_eps)
        if cfg.latent_kv_scale != 1.0:
            c = c * cfg.latent_kv_scale
        k_pe = row[:, rank:]
    if rotate is not None:
        with jax.named_scope("rope"):
            k_pe = rotate(k_pe[:, None])[:, 0]
    with jax.named_scope("absorb"):
        row = jnp.concatenate([c, k_pe], axis=1)           # [c | k_pe]
        q_lat = dot("bhd,hrd->bhr", q[..., :d], laid[0]) if laid \
            else dot("bhd,rhd->bhr", q[..., :d], up[..., :d])
        q_pe = q[..., d:]
    if rotate is not None:
        with jax.named_scope("rope"):
            q_pe = rotate(q_pe)
    with jax.named_scope("absorb"):
        q = jnp.concatenate([q_lat, q_pe], axis=2)         # [q_lat | q_pe]
    o_lat = attend(l, q, row, chosen_by)                   # [B, heads, rank]
    with jax.named_scope("out"):
        o = dot("bhr,hdr->bhd", o_lat, laid[1]) if laid \
            else dot("bhr,rhd->bhd", o_lat, up[..., d:])
        return _mm(o.reshape(bb, heads * dv), p("wo"))


def token_logits(params, cfg, tok, pos, attend, live, recur):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [routed layers, experts]: the tokens of live lanes sent to each expert
    of the whole router this step, a row a layer of ``cfg.routed_layers``
    (``cfg.held_experts`` are the columns computed here).  Scope names:
    ``layer<i>/kda/`` + ``conv``, ``state``, ``out``; ``layer<i>/latent/``
    + ``absorb``, ``kv_write``, ``kv_read`` (``kv_gather`` where the table
    is gathered), ``out``; ``layer<i>/mlp`` on the dense layer,
    ``layer<i>/moe/router``, ``.../moe/experts`` and ``.../moe/shared`` on
    routed ones; ``lm_head``."""
    del pos                             # no position encoding
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l, kind in enumerate(cfg.layer_types):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            with jax.named_scope(kind):
                x = x + (kda_mixer(cfg, p, l, h, recur) if kind == "kda"
                         else latent_mixer(cfg, p, l, h, attend))
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if l < cfg.dense_layers:
                with jax.named_scope("mlp"):
                    x = x + _exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))
            else:
                with jax.named_scope("moe"):
                    f, chosen = routed_part(cfg, p, h2, live)
                    routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                          dtype=jnp.int32))
                    x = x + f + shared_part(p, h2)
    with jax.named_scope("lm_head"):
        logits = _exaone._head(x, params, eps)
    # a cut that keeps the dense layer alone has nothing to count
    return logits, (jnp.stack(routed),) if routed else ()
