"""Solar-Open2 decoder block (upstage/Solar-Open2-250B: ``model_type``
``solar_open2``) as pure functions of ``(params, cfg, tok, pos, attend,
live, recur)``, called by the decode steps of ``serving/decode_model.py``
under the same contract as the other blocks: one token per lane through
every layer.  The mixers are of two kinds, named by ``cfg.layer_types``, and
every layer's feed-forward is routed (no dense lead):

* ``kda``: Kimi Delta Attention, ``kimi_linear.kda_mixer`` itself (three
  depthwise convolutions, a matrix state a head in a slot, moved by the
  gated delta rule), with what this family declares of it: **negative
  eigenvalues** (``cfg.kda_neg_eigval``), ``beta = 2 sigmoid(b)`` in (0, 2),
  so that ``I - beta k k^T`` has the eigenvalue ``1 - beta`` in (-1, 1)
  along a unit ``k`` and a write may turn what the state holds along ``k``
  about, not only shrink it.
* ``attention``: grouped-query softmax attention over K/V pools
  (``cfg.heads`` query heads over ``cfg.kv_heads``), causal and full, with
  **no position encoding** and no Q/K norm (the KDA layers carry position),
  whose output is **gated**: an elementwise sigmoid of a projection of the
  layer's input, a value a head channel, multiplies the heads' concatenated
  output before ``wo`` (the gated-attention form, arXiv:2505.06708).
  ``attend(l, q, k, v)`` owns the write of this token's K and V and the
  history read.
* every layer ends in experts of width ``cfg.ffn`` routed over
  ``cfg.experts``, ``cfg.experts_per_token`` a token, beside one shared
  expert of width ``cfg.shared_ffn``: ``exaone_moe``'s routed layer (sigmoid
  scores, a bias that chooses and never weighs, gates renormalised over the
  chosen and scaled by ``cfg.routed_scaling``), the share it may hold
  (``experts_held`` from ``expert_first`` on) included.

Pre-norm throughout.  For hidden ``x`` of one token::

    h = rmsnorm(x, ln1_g);  x = x + mixer(h)
    kda:        as ``kimi_linear.kda_mixer`` writes it down, beta = 2 sigmoid
    attention:  q = h @ wq -> [heads, D];  k, v = h @ wk, h @ wv -> [KH, D]
                o_j = sum_{s <= t} softmax_s(q_j . k_{j // (heads / KH)}(s)
                                             * D^-0.5) v_{j // (heads / KH)}(s)
                mixer = (concat_j o_j * sigmoid(h @ wg)) @ wo
    h2 = rmsnorm(x, ln2_g);  x = x + routed(h2) + shared(h2)

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the convolution, the gates, the decay, the
state's update and read-out, the softmax and the residual additions float32;
the state float32 wherever it lives.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``; kda layers
as ``kimi_linear``'s (``wqkv``, ``conv_w``, ``low_a``, ``f_b``, ``g_b``,
``dt_bias``, ``A_log``, ``o_norm``, ``wo``); attention layers ``wq``, ``wg
[H, heads * D]``, ``wk``, ``wv [H, KH * D]``, ``wo [heads * D, H]``; every
layer's routed part as ``exaone_moe``'s.
"""

import jax
import jax.numpy as jnp

from . import exaone_moe as _exaone
from . import kimi_linear as _kimi
from .decoder_family import DecoderFamily
from .olmoe import _mm, _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "gqa_mixer",
           "routed_part", "shared_part", "BIAS_STD", "FAMILY"]

FAMILY = DecoderFamily(kinds=("kda", "attention"), grouped_query=True,
                       routes="after_dense", expert_matrices=3,
                       holds_share=True, own_stream_width=True,
                       grouped_router=True, shared_expert=True,
                       neg_eigval=True)

# standard deviation of a seeded ``expert_bias``.  The router sees
# rmsnorm(x) over 4,096 (logits of standard deviation 1.28 under weights
# normal(0, 0.02)), and a token's eighth and ninth best of 320 sigmoid scores
# lie about 0.003 apart: 0.001 re-decides the choice on a third of the tokens
# (tests/test_solar_open2.py: a block that ignores the bias is seen) and is
# what the benchmark's builder starts its balancing from.
BIAS_STD = 0.001

routed_part = _exaone.routed_part
shared_part = _exaone.shared_part

# by name, so that a check can serve the block with it taken out
# (benchmark/tests/chip_check_solar.py): the attention output's gate
_attn_gate = jax.nn.sigmoid


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    qw, kv = cfg.heads * d, cfg.kv_heads * d
    e, held, fe, fs = cfg.experts, cfg.experts_held, cfg.ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    mixers = {
        "kda": _kimi.kda_param_shapes(cfg),
        "attention": (("wq", (h, qw), "normal"), ("wk", (h, kv), "normal"),
                      ("wv", (h, kv), "normal"), ("wg", (h, qw), "normal"),
                      ("wo", (qw, h), "normal")),
    }
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
        ) + mixers[kind] + routed:
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD):
    """name -> np array in the config's weight dtype, as
    ``kimi_linear.init_params`` draws them (``std``-normal weights, norms at
    1, ``expert_bias`` normal(0, ``bias_std``), the convolutions, ``A_log``
    and ``dt_bias`` by what sets how much the state holds) over this
    family's shapes.  Host-side: tests and demo bundles."""
    return _kimi.init_params(cfg, seed, std, bias_std, shapes=param_shapes)


def gqa_mixer(cfg, p, l, h, attend):
    """The gated, position-free grouped-query mixer of layer ``l`` over h
    [B, H] float32."""
    bb = h.shape[0]
    with jax.named_scope("qkv"):
        q = _mm(h, p("wq")).reshape(bb, cfg.heads, cfg.head_dim)
        k, v = (_mm(h, p(w)).reshape(bb, cfg.kv_heads, cfg.head_dim)
                for w in ("wk", "wv"))
    a = attend(l, q, k, v).reshape(bb, cfg.heads * cfg.head_dim)
    with jax.named_scope("gate"):
        a = a * _attn_gate(_mm(h, p("wg")))
    with jax.named_scope("out"):
        return _mm(a, p("wo"))


def token_logits(params, cfg, tok, pos, attend, live, recur, seen=None):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [layers, experts]: the tokens of live lanes sent to each expert of the
    whole router this step, a row a layer (``cfg.held_experts`` are the
    columns computed here).  Scope names: ``layer<i>/kda/`` + ``conv``,
    ``state``, ``out``; ``layer<i>/attention/`` + ``qkv``, ``kv_write``,
    ``kv_read`` (``kv_gather`` where the table is gathered), ``gate``,
    ``out``; ``layer<i>/moe/router``, ``.../moe/experts`` and
    ``.../moe/shared``; ``lm_head``.  A list given as ``seen`` receives
    what each layer's router read (``h2`` [B, H] float32), as
    ``dots_vlm``'s: the benchmark's builder balances ``expert_bias`` on
    them."""
    del pos                             # no position encoding anywhere
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l, kind in enumerate(cfg.layer_types):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            with jax.named_scope(kind):
                x = x + (_kimi.kda_mixer(cfg, p, l, h, recur)
                         if kind == "kda"
                         else gqa_mixer(cfg, p, l, h, attend))
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if seen is not None:
                seen.append(h2)
            with jax.named_scope("moe"):
                f, chosen = routed_part(cfg, p, h2, live)
                routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                      dtype=jnp.int32))
                x = x + f + shared_part(p, h2)
    with jax.named_scope("lm_head"):
        logits = _exaone._head(x, params, eps)
    return logits, (jnp.stack(routed),)
