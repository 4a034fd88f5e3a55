"""EXAONE-MoE decoder block (LGAI-EXAONE/K-EXAONE-236B-A23B: ``model_type``
``exaone_moe``) as pure functions of ``(params, cfg, tok, pos, attend, live,
recur)``, called by the decode steps of ``serving/decode_model.py`` under
the same contract as the other blocks: one token per lane through every
layer.  The attention is of two kinds, named by ``cfg.layer_types``, and
the feed-forward of two, by the layer's place:

* ``window`` (the source's ``sliding_attention``): grouped-query attention
  over the last ``cfg.window`` positions, q and k rotated (RoPE).
* ``attention`` (``full_attention``): the same over the whole context, with
  **no** position encoding.  Either way ``attend(l, q, k, v)`` owns the KV
  write and the history read; the step maker knows the layer's kind.
* the first ``cfg.dense_layers`` layers end in a SiLU-gated MLP of width
  ``cfg.dense_ffn``; every later one (``cfg.routed_layers``) in experts of
  width ``cfg.ffn`` routed over ``cfg.experts``, ``cfg.experts_per_token``
  a token, beside one shared expert of width ``cfg.shared_ffn`` that every
  token passes through.

The norms sit on each sublayer's **output** (the family's dense half,
``modeling_exaone4.py``), not its input.  For hidden ``x`` of one token at
position ``t``::

    q = rmsnorm(x @ wq [heads x D], q_norm [D])         # per head
    k = rmsnorm(x @ wk [KH x D], k_norm [D]);  v = x @ wv
    window:     q, k = rope(q, t), rope(k, t)   # rotate-half pairs (i, i + D/2)
                a = attention(q, K[t-W+1..t], V[t-W+1..t])
    attention:  a = attention(q, K[0..t], V[0..t])
    x = x + rmsnorm(a @ wo, ln1_g)
    dense:      f = (silu(x @ w1) * (x @ w3)) @ w2
    routed:     s = sigmoid(x @ router)                    # [E], float32
                S = the experts_per_token largest of s + expert_bias
                w_e = routed_scaling * s_e / (sum_{e in S} s_e + 1e-20)
                f = sum_{e in S, e held} w_e * E_e(x) + shared(x)
    x = x + rmsnorm(f, ln2_g)

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head), each ``E_e`` and
``shared`` a SiLU-gated MLP.  The bias chooses and never weighs (the
DeepSeek-V3 router); no capacity.  ``cfg.n_group`` over 1 (K-EXAONE's is 1;
``dots_vlm`` shares ``routed_part``), the experts lie in that many groups of
consecutive ones, a group scores its two largest ``s + expert_bias`` summed,
and ``S`` is chosen among the ``cfg.topk_group`` best groups' experts (the
others' selection scores count as 0).

**The share.**  One chip of a deployment that divides each layer's experts
over several holds ``cfg.experts_held`` of the ``cfg.experts``, from
``cfg.expert_first`` on.  The router keeps its width and its experts a
token, and the gates are renormalised over all the chosen, held or not;
``wgate``/``wup``/``wdown`` are the held experts' alone, and what an absent
expert would add is left out of ``f``: that partial sum is what goes on.
``routed_part`` is one share's routed sum and ``shared_part`` what every
share computes alike; over all shares, the shared part counted once, they
add up to the whole layer (tests/test_exaone_moe.py).  No exchange is stood
in for.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the sigmoid and gates, RoPE and the
residual additions float32.  The routed sum is
``pallas_kernels/moe_experts.py`` ``routed_experts`` over the held experts'
columns of the gates.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``wq [H, heads * D]``, ``wk``, ``wv [H,
KH * D]``, ``wo [heads * D, H]``, ``q_norm``, ``k_norm [D]``, ``ln1_g``, ``ln2_g``; dense
layers ``w1``, ``w3 [H, F]``, ``w2 [F, H]``; routed layers ``router [H,
E]``, ``expert_bias [E]``, ``wgate``, ``wup [Eh, H, Fe]``, ``wdown [Eh, Fe,
H]`` (``Eh`` the experts held) and ``shared_w1``, ``shared_w3 [H, Fs]``,
``shared_w2 [Fs, H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..pallas_kernels import moe_experts as _moe
from . import lfm2_moe as _lfm2
from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm, _rope

__all__ = ["token_logits", "param_shapes", "init_params", "routed_part",
           "shared_part", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention", "window"), grouped_query=True,
                       routes="after_dense", expert_matrices=3,
                       dense_lead=True, holds_share=True,
                       own_stream_width=True, grouped_router=True,
                       shared_expert=True)

# the least the renormalised gates' denominator can be (the DeepSeek-V3
# router adds it to the sum of the chosen scores)
GATE_EPS = 1e-20
# standard deviation of a seeded ``expert_bias``.  Behind norms on the
# sublayers' outputs the stream's entries are 1.4-2.8, so the router's
# logits (weights normal(0, 0.02) over 6,144) have a standard deviation of
# 2-4 and a token's best scores lie within thousandths of each other near 1.
# A bias of 0.001 against that re-decides the choice on 47% of tokens (a
# block that ignores it is seen) and leaves every expert's share of the load
# near even: the held experts a 32-lane step hits stay at 13.7-14.0 of 16
# from seed to seed, where 0.02 reads 8.9-10.4 and a step's time would hang
# on its seed (PERF.md section 6, PR 38; PR 36's refusal).
BIAS_STD = 0.001


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    qw, kv = cfg.heads * d, cfg.kv_heads * d
    e, held, fe, fd, fs = cfg.experts, cfg.experts_held, cfg.ffn, \
        cfg.dense_ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    mixer = (("wq", (h, qw), "normal"), ("wk", (h, kv), "normal"),
             ("wv", (h, kv), "normal"), ("wo", (qw, h), "normal"),
             ("q_norm", (d,), "ones"), ("k_norm", (d,), "ones"),
             ("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones"))
    dense = (("w1", (h, fd), "normal"), ("w3", (h, fd), "normal"),
             ("w2", (fd, h), "normal"))
    routed = (("router", (h, e), "normal"), ("expert_bias", (e,), "bias"),
              ("wgate", (held, h, fe), "normal"),
              ("wup", (held, h, fe), "normal"),
              ("wdown", (held, fe, h), "normal"),
              ("shared_w1", (h, fs), "normal"),
              ("shared_w3", (h, fs), "normal"),
              ("shared_w2", (fs, h), "normal"))
    for l in range(cfg.layers):
        for name, shape, init in mixer + (
                dense if l < cfg.dense_layers else routed):
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD, shapes=None):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms at 1, ``expert_bias`` normal(0, ``bias_std``) (at zero a
    block that ignores it is indistinguishable).  Host-side: tests and demo
    bundles.  ``shapes`` is another family's ``param_shapes`` of the same
    three kinds (``dots_vlm``)."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]

    def make(shape, kind):
        if kind == "ones":
            return np.ones(shape, np.float32)
        return r.standard_normal(shape) * (bias_std if kind == "bias"
                                           else std)

    return {name: make(shape, kind).astype(np.float32).astype(dtype)
            for name, (shape, kind) in sorted(
                (shapes or param_shapes)(cfg).items())}


def _route(x, router, bias, k, scaling, n_group=1, topk_group=1, kept=None):
    """LFM2-MoE's router (sigmoid scores, a bias that chooses and never
    weighs, gates renormalised over the chosen) with this family's
    denominator.  ``n_group`` over 1, the ``k`` are chosen among the
    ``topk_group`` best groups' experts (``_in_kept_groups``), and a list
    given as ``kept`` receives the groups each token kept, [B, n_group]
    bool; one group is the plain choice, the same operations as before
    there were groups."""
    if n_group == 1:
        return _lfm2._route(x, router, bias, k, scaling, GATE_EPS)

    def limit(select):
        select, groups = _in_kept_groups(select, n_group, topk_group)
        if kept is not None:
            kept.append(groups)
        return select

    return _lfm2._route(x, router, bias, k, scaling, GATE_EPS, limit)


def _in_kept_groups(select, n_group, topk_group):
    """The DeepSeek-V3 router's choice of groups (``noaux_tc``): the
    selection scores [B, E] in ``n_group`` groups of consecutive experts, a
    group's score its two largest summed, the ``topk_group`` best groups
    kept -> (the scores with 0 in place of every other group's, the groups
    kept [B, n_group] bool)."""
    bb, e = select.shape
    best2, _idx = jax.lax.top_k(select.reshape(bb, n_group, e // n_group), 2)
    _top, idx = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
    groups = jnp.any(jax.nn.one_hot(idx, n_group, dtype=bool), axis=1)
    return jnp.where(jnp.repeat(groups, e // n_group, axis=1), select,
                     0.0), groups


def _gated_mlp(x, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(x, w1)) * _mm(x, w3), w2)


def _head_norm(x, g, eps):
    """RMSNorm of x [B, heads, D] over each head's D values, one weight
    ``g`` [D] for all heads."""
    return _rmsnorm(x, g, eps)


def _rotated(cfg, l):
    """Is layer ``l``'s q and k rotated?  The window layers' alone."""
    return cfg.layer_types[l] == "window"


def _head(x, params, eps):
    """The final norm and the untied head: float32 logits."""
    return _mm(_rmsnorm(x, params["lnf_g"], eps), params["head"])


def routed_part(cfg, p, x, live, kept=None):
    """-> (this share's routed sum [B, H] float32: the held experts' part
    for the tokens routed to them; ``chosen`` [B, E] bool over the whole
    router).  ``p(name)`` is the layer's parameter; ``kept`` as
    ``_route``'s."""
    with jax.named_scope("router"):
        gates, chosen = _route(x, p("router"), p("expert_bias"),
                               cfg.experts_per_token, cfg.routed_scaling,
                               cfg.n_group, cfg.topk_group, kept)
    with jax.named_scope("experts"):
        y = _moe.routed_experts(x, gates[:, cfg.held_experts], live,
                                p("wgate"), p("wup"), p("wdown"))
    return y, chosen


def shared_part(p, x):
    """The shared expert's output [B, H]: the same on every share."""
    with jax.named_scope("shared"):
        return _gated_mlp(x, p("shared_w1"), p("shared_w3"), p("shared_w2"))


def token_logits(params, cfg, tok, pos, attend, live, recur=None):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [routed layers, experts]: the tokens of live lanes sent to each expert
    of the whole router this step, a row a layer of ``cfg.routed_layers``
    (``cfg.held_experts`` are the columns computed here).  Scope names as
    the other blocks' (``layer<i>/attn``, ``.../kv_write``, ``.../kv_read``
    on both kinds of attention, ``layer<i>/mlp`` on dense layers,
    ``layer<i>/moe/router``, ``.../moe/experts`` and ``.../moe/shared`` on
    routed ones, ``lm_head``)."""
    bb = tok.shape[0]
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            with jax.named_scope("attn"):
                q = _head_norm(_mm(x, p("wq")).reshape(
                    bb, cfg.heads, cfg.head_dim), p("q_norm"), eps)
                k = _head_norm(_mm(x, p("wk")).reshape(
                    bb, cfg.kv_heads, cfg.head_dim), p("k_norm"), eps)
                v = _mm(x, p("wv")).reshape(bb, cfg.kv_heads, cfg.head_dim)
                if _rotated(cfg, l):
                    q = _rope(q, pos, cfg.rope_theta)
                    k = _rope(k, pos, cfg.rope_theta)
                a = attend(l, q, k, v).reshape(bb, cfg.heads * cfg.head_dim)
                x = x + _rmsnorm(_mm(a, p("wo")), p("ln1_g"), eps)
            if l < cfg.dense_layers:
                with jax.named_scope("mlp"):
                    f = _gated_mlp(x, p("w1"), p("w3"), p("w2"))
            else:
                with jax.named_scope("moe"):
                    f, chosen = routed_part(cfg, p, x, live)
                    routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                          dtype=jnp.int32))
                    f = f + shared_part(p, x)
            x = x + _rmsnorm(f, p("ln2_g"), eps)
    with jax.named_scope("lm_head"):
        logits = _head(x, params, eps)
    # a cut that keeps the dense layers alone has nothing to count
    return logits, (jnp.stack(routed),) if routed else ()
