"""LongCat-Flash's block (meituan-longcat/LongCat-Flash-Chat: ``model_type``
``longcat_flash``, a shortcut-connected mixture of experts) as pure functions
of ``(params, cfg, tok, pos, attend, live, recur)``, called by the decode
steps of ``serving/decode_model.py`` under the same contract as the other
blocks: one token per lane through every layer.  A layer of the source is a
**pair** of sublayers round one routed part (``FAMILY.routes`` ``"pairs"``):
``cfg.layers`` and ``cfg.layer_types`` count sublayers, every one of kind
``latent``, sublayers ``2 i`` and ``2 i + 1`` are the source's layer ``i``,
and ``cfg.routed_layers`` names the first of each pair, where the routed part
reads the stream and where its router and experts are held.

* ``latent``: ``kimi_linear.latent_mixer`` with the compressed query
  (``cfg.q_rank``), plain rotation of the row's shared key and of each
  head's ``q_pe`` (no ``rope_scaling``) and both scales: the projected query
  times ``cfg.latent_q_scale`` (``(hidden / q_rank)^0.5``), the normed
  compressed K/V times ``cfg.latent_kv_scale`` (``(hidden /
  latent_rank)^0.5``), so the cache keeps ``[a_kv c | k_pe]`` a token a
  sublayer.
* every sublayer ends in a SiLU-gated MLP of width ``cfg.dense_ffn``.
* the routed part: a softmax router over ``cfg.router_width = cfg.experts +
  cfg.zero_experts`` outputs; a bias chooses ``cfg.experts_per_token`` of
  them and never weighs; a chosen output weighs ``cfg.routed_scaling`` times
  its probability, NOT renormalised.  The first ``cfg.experts`` outputs are
  SiLU-gated experts of width ``cfg.ffn``; the last ``cfg.zero_experts`` are
  identity experts, which return their input: a token that chooses one
  computes nothing for it, so a token's routed compute is anything from none
  to ``experts_per_token`` experts, by the router.  No shared expert.

For the stream ``x`` of one token at position ``t``, pair ``i``, sublayers
``a = 2 i`` and ``b = 2 i + 1``, RMSNorm throughout::

    h0 = rmsnorm(x, l<a>_ln1_g);  x = x + mla_a(h0)         # cache layer a
    h1 = rmsnorm(x, l<a>_ln2_g);  s = moe(h1)               # read HERE
                                  x = x + mlp_a(h1)
    h2 = rmsnorm(x, l<b>_ln1_g);  x = x + mla_b(h2)         # cache layer b
    h3 = rmsnorm(x, l<b>_ln2_g);  x = x + mlp_b(h3) + s     # added HERE
    mla:  cq = rmsnorm(h @ wq_a, q_norm);  q = (cq @ wq_b) * a_q
          [c | k_pe] = h @ wkva;  c = rmsnorm(c, kv_norm) * a_kv
          k_pe = rope(k_pe, t);  q_pe_j = rope(q_pe_j, t);  row(t) = [c | k_pe]
          score_j(s) = (wkvb_j^K q_nope_j . c(s) + q_pe_j . k_pe(s)) * (D + P)^-0.5
          mla = concat_j(wkvb_j^V^T sum_s softmax_s(score_j) c(s)) @ wo
    moe:  p = softmax(h1 @ router)                          # float32, [E + Z]
          S = the experts_per_token largest of p + expert_bias
          g_e = routed_scaling * p_e, e in S
          s = sum_{e in S, e < E, e held} g_e E_e(h1) + (sum_{e in S, e >= E} g_e) h1

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  The rotation is
``dots_vlm._rotation``'s without scaling: interleaved pairs, query and key
alike.

**The share.**  One chip of a deployment that divides each pair's experts
holds ``cfg.experts_held`` of the ``cfg.experts``, from ``cfg.expert_first``
on.  The router keeps its width and its outputs a token; this chip adds its
own experts' part and the identity part, which needs no weights and is
computed where the token's stream is, as a shared expert is: every share
computes it alike, and over all shares, the identity part and the dense MLPs
counted once, the parts add up to the whole pair (tests/
test_longcat_flash.py).  What an absent expert would add is left out.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the rotation, the router (its product at
the highest precision), the gates, the identity part and the residual
additions float32.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per sublayer ``l<i>_`` + ``ln1_g``, ``ln2_g``, ``wq_a
[H, Rq]``, ``q_norm [Rq]``, ``wq_b [Rq, heads * (D + P)]``, ``wkva [H, rank
+ P]``, ``kv_norm [rank]``, ``wkvb [rank, heads * 2 D]`` (as published; a
decode step holds it as ``laid_out`` leaves it), ``wo [heads * D, H]``,
``w1``, ``w3 [H, F]``, ``w2 [F, H]``; on the first sublayer of a pair also
``router [H, E + Z]``, ``expert_bias [E + Z]``, ``wgate``, ``wup [Eh, H,
Fe]``, ``wdown [Eh, Fe, H]`` (``Eh`` the experts held).
"""

import jax
import jax.numpy as jnp

from ..pallas_kernels import moe_experts as _moe
from . import dots_vlm as _dots
from . import exaone_moe as _exaone
from . import kimi_linear as _kimi
from .decoder_family import DecoderFamily
from .olmoe import _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "laid_out",
           "routed_part", "BIAS_STD", "FAMILY"]

FAMILY = DecoderFamily(kinds=("latent",), routes="pairs", expert_matrices=3,
                       holds_share=True, own_stream_width=True,
                       rotated_latent=True, zero_experts=True,
                       scaled_latent=True)

# standard deviation of a seeded ``expert_bias``.  The router sees
# rmsnorm(x): its logits (weights normal(0, 0.02) over 6,144) have a standard
# deviation of 1.57, a softmax over 768 of them gives a probability of 0.011
# at the threshold of the choice (the 12 best of 768) and one of 0.0013 in
# the mean, and an output's popularity moves by about 14% for a bias of
# 0.001: a block that ignores the bias is seen (tests/test_longcat_flash.py).
# The benchmark's cell starts from this draw and balances it on the block's
# own states (``benchmark/models/longcat_flash_decoder.py`` ``balanced``,
# through ``token_logits``'s ``seen``), as training does.
BIAS_STD = 0.001

# every sublayer's ``wkvb`` as ``latent_mixer`` multiplies it
laid_out = _kimi.laid_out


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    held, fe, fd = cfg.experts_held, cfg.ffn, cfg.dense_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    sublayer = (("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones"),
                ("wq_a", (h, cfg.q_rank), "normal"),
                ("q_norm", (cfg.q_rank,), "ones"),
                ("wq_b", (cfg.q_rank, cfg.heads * (d + cfg.latent_rope)),
                 "normal"),
                ("wkva", (h, cfg.latent_width), "normal"),
                ("kv_norm", (cfg.latent_rank,), "ones"),
                ("wkvb", (cfg.latent_rank, cfg.heads * (d + cfg.v_head_dim)),
                 "normal"),
                ("wo", (cfg.heads * cfg.v_head_dim, h), "normal"),
                ("w1", (h, fd), "normal"), ("w3", (h, fd), "normal"),
                ("w2", (fd, h), "normal"))
    routed = (("router", (h, cfg.router_width), "normal"),
              ("expert_bias", (cfg.router_width,), "bias"),
              ("wgate", (held, h, fe), "normal"),
              ("wup", (held, h, fe), "normal"),
              ("wdown", (held, fe, h), "normal"))
    for l in range(cfg.layers):
        for name, shape, init in sublayer + (
                routed if l in cfg.routed_layers else ()):
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms at 1, ``expert_bias`` normal(0, ``bias_std``).
    Host-side: tests and demo bundles."""
    return _exaone.init_params(cfg, seed, std, bias_std, param_shapes)


def _route(x, router, bias, k, scaling):
    """-> (gates [B, E + Z] float32: ``scaling`` times the softmax
    probability of each of the token's ``k`` chosen outputs, 0 elsewhere,
    not renormalised; chosen [B, E + Z] bool).  ``bias`` moves the choice
    alone."""
    f32 = jnp.float32
    p = jax.nn.softmax(jnp.dot(x, router.astype(f32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    _top, idx = jax.lax.top_k(p + bias.astype(f32), k)
    chosen = jnp.any(jax.nn.one_hot(idx, p.shape[-1], dtype=bool), axis=1)
    return jnp.where(chosen, p, 0.0) * scaling, chosen


def routed_part(cfg, p, x, live):
    """-> (this share's routed sum [B, H] float32: the held experts' part
    for the tokens routed to them; the identity part [B, H] float32: the
    token's own input times the gates of the identity experts it chose, the
    same on every share; ``chosen`` [B, E + Z] bool over the whole router).
    ``p(name)`` is the pair's first sublayer's parameter."""
    with jax.named_scope("router"):
        gates, chosen = _route(x, p("router"), p("expert_bias"),
                               cfg.experts_per_token, cfg.routed_scaling)
    with jax.named_scope("experts"):
        y = _moe.routed_experts(x, gates[:, cfg.held_experts], live,
                                p("wgate"), p("wup"), p("wdown"))
    with jax.named_scope("zero"):
        z = jnp.sum(gates[:, cfg.experts:], axis=1, keepdims=True) * x
    return y, z, chosen


def token_logits(params, cfg, tok, pos, attend, live, recur=None, seen=None):
    """-> (logits [B, vocab] float32, (routed, real)) with ``routed`` int32
    [pairs, E + Z] the tokens of live lanes sent to each output of the whole
    router this step, a row a pair (``cfg.held_experts`` are the columns
    computed here, the last ``cfg.zero_experts`` the identity experts'), and
    ``real`` int32 [pairs, experts_per_token + 1] the live lanes that chose
    so many real experts.  Scope names: ``layer<l>/latent/`` +
    ``q_compress``, ``absorb``, ``rope``, ``kv_write``, ``kv_read``
    (``kv_gather`` where the table is gathered), ``out`` and ``layer<l>/mlp``
    on every sublayer ``l``; ``layer<l>/moe/router``, ``.../moe/experts`` and
    ``.../moe/zero`` on the first of a pair; ``lm_head``.  A list given as
    ``seen`` receives each pair's router input ``[B, H]`` float32 (what a
    balancing of ``expert_bias`` on the model's own states reads; the decode
    steps pass none)."""
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    rotate = _dots._rotation(cfg, pos)
    counted = lambda mask: jnp.sum(mask & live[:, None], axis=0,
                                   dtype=jnp.int32)
    routed, real = [], []
    shortcut = None
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            with jax.named_scope("latent"):
                x = x + _kimi.latent_mixer(cfg, p, l, h, attend, rotate)
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if l % 2 == 0:
                # the pair's routed part reads the stream here and is added
                # behind the second sublayer's MLP
                with jax.named_scope("moe"):
                    if seen is not None:
                        seen.append(h2)
                    y, z, chosen = routed_part(cfg, p, h2, live)
                    shortcut = y + z
                    routed.append(counted(chosen))
                    real.append(counted(jax.nn.one_hot(
                        jnp.sum(chosen[:, :cfg.experts], axis=1),
                        cfg.experts_per_token + 1, dtype=bool)))
            with jax.named_scope("mlp"):
                x = x + _exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))
            if l % 2:
                x = x + shortcut
    with jax.named_scope("lm_head"):
        logits = _exaone._head(x, params, eps)
    return logits, (jnp.stack(routed), jnp.stack(real))
