"""SmallThinker decoder block (PowerInfer/SmallThinker-21BA3B-Instruct:
``model_type`` ``smallthinker``) as pure functions of ``(params, cfg, tok,
pos, attend, live, recur)``, called by the decode steps of
``serving/decode_model.py`` under the same contract as the other blocks: one
token per lane through every layer.  The attention is of two kinds, named by
``cfg.layer_types`` (the source's ``sliding_window_layout`` and
``rope_layout``, which agree layer by layer):

* ``window``: grouped-query attention over the last ``cfg.window``
  positions, q and k rotated (RoPE).
* ``attention``: the same over the whole context, with **no** position
  encoding.  Either way ``attend(l, q, k, v)`` owns the KV write and the
  history read; the step maker knows the layer's kind.

Every layer's feed-forward is ``cfg.experts`` ReLU-gated experts of width
``cfg.ffn``, ``cfg.experts_per_token`` a token, and **the router reads the
attention's input**: the choice is made from the normed stream *before* the
attention runs (the family's "pre-attention router"), and the chosen experts
then compute on the normed stream *after* it.  For hidden ``x`` of one token
at position ``t``::

    h  = rmsnorm(x, ln1_g)
    r  = h @ router                        # [E], float32: taken HERE
    S  = the experts_per_token largest of r
    w_e = exp(r_e) / sum_{e' in S} exp(r_e')        # softmax, the chosen renormalised
    q, k, v = h @ wq [heads x D], h @ wk [KH x D], h @ wv         # no q/k norm
    window:     q, k = rope(q, t), rope(k, t)   # rotate-half pairs (i, i + D/2)
                a = attention(q, K[t-W+1..t], V[t-W+1..t])
    attention:  a = attention(q, K[0..t], V[0..t])
    x  = x + a @ wo
    h2 = rmsnorm(x, ln2_g)
    x  = x + sum_{e in S} w_e * ((relu(h2 @ wgate_e) * (h2 @ wup_e)) @ wdown_e)

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  No biases, no
shared expert, no dense lead layer, no share of a layer's experts, no
capacity.  ``routed_part``'s one ``x`` (``exaone_moe``) does not fit a block
whose router's input is not its experts' input: here ``_route`` runs under
``layer<i>/moe/router`` ahead of ``layer<i>/attn`` and the experts under
``layer<i>/moe/experts`` behind it.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the router's logits and gates, RoPE and
the residual additions float32.  The routed sum is
``pallas_kernels/moe_experts.py`` ``routed_experts`` with the gate the
family declares (``FAMILY.expert_gate``: ``"relu"``).

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g [H]``, ``wq [H,
heads * D]``, ``wk``, ``wv [H, KH * D]``, ``wo [heads * D, H]``, ``router
[H, E]``, ``wgate``, ``wup [E, H, F]``, ``wdown [E, F, H]``.
"""

import jax
import jax.numpy as jnp

from ..pallas_kernels import moe_experts as _moe
from . import exaone_moe as _exaone
from .decoder_family import DecoderFamily
from .olmoe import _mm, _rmsnorm, _rope

__all__ = ["token_logits", "param_shapes", "init_params", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention", "window"), grouped_query=True,
                       routes="after_dense", expert_matrices=3,
                       own_stream_width=True, expert_gate="relu")


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    qw, kv, e, f = cfg.heads * d, cfg.kv_heads * d, cfg.experts, cfg.ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    for l in range(cfg.layers):
        for name, shape, kind in (
                ("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones"),
                ("wq", (h, qw), "normal"), ("wk", (h, kv), "normal"),
                ("wv", (h, kv), "normal"), ("wo", (qw, h), "normal"),
                ("router", (h, e), "normal"),
                ("wgate", (e, h, f), "normal"), ("wup", (e, h, f), "normal"),
                ("wdown", (e, f, h), "normal")):
            shapes["l%d_%s" % (l, name)] = (shape, kind)
    return shapes


def init_params(cfg, seed=0, std=0.02):
    """name -> np array in the config's weight dtype; ``std``-normal
    weights, norms at 1 (host-side: tests and demo bundles)."""
    return _exaone.init_params(cfg, seed, std, shapes=param_shapes)


def _route(h, router, k):
    """-> (gates [B, E] float32: the softmax over the token's ``k`` largest
    logits, 0 elsewhere (the softmax over all experts with the chosen
    renormalised: the same numbers); chosen [B, E] bool)."""
    logits = jnp.dot(h, router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, k)
    chosen = jnp.any(jax.nn.one_hot(idx, logits.shape[-1], dtype=bool),
                     axis=1)
    e = jnp.where(chosen, jnp.exp(logits - top[:, :1]), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True), chosen


def token_logits(params, cfg, tok, pos, attend, live, recur=None):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [layers, experts]: the tokens of live lanes sent to each expert this
    step.  Scope names as the other blocks', in this block's order:
    ``layer<i>/moe/router`` ahead of ``layer<i>/attn`` (``.../kv_write``,
    ``.../kv_read`` on both kinds), then ``layer<i>/moe/experts``;
    ``lm_head``."""
    bb = tok.shape[0]
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            # the router reads the attention's INPUT: the experts are known
            # before the attention runs
            with jax.named_scope("moe"), jax.named_scope("router"):
                gates, chosen = _route(h, p("router"), cfg.experts_per_token)
                routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                      dtype=jnp.int32))
            with jax.named_scope("attn"):
                q = _mm(h, p("wq")).reshape(bb, cfg.heads, cfg.head_dim)
                k = _mm(h, p("wk")).reshape(bb, cfg.kv_heads, cfg.head_dim)
                v = _mm(h, p("wv")).reshape(bb, cfg.kv_heads, cfg.head_dim)
                if _exaone._rotated(cfg, l):
                    q = _rope(q, pos, cfg.rope_theta)
                    k = _rope(k, pos, cfg.rope_theta)
                a = attend(l, q, k, v).reshape(bb, cfg.heads * cfg.head_dim)
                x = x + _mm(a, p("wo"))
            with jax.named_scope("moe"):
                h2 = _rmsnorm(x, p("ln2_g"), eps)
                with jax.named_scope("experts"):
                    x = x + _moe.routed_experts(
                        h2, gates, live, p("wgate"), p("wup"), p("wdown"),
                        gate=FAMILY.expert_gate)
    with jax.named_scope("lm_head"):
        logits = _mm(_rmsnorm(x, params["lnf_g"], eps), params["head"])
    return logits, (jnp.stack(routed),)
