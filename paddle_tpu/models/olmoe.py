"""OLMoE decoder block (allenai/OLMoE-1B-7B: ``model_type`` ``olmoe``) as
pure functions of ``(params, cfg, tok, pos, attend)``, called by the decode
steps of ``serving/decode_model.py`` under the same contract as the GPT-2
block there: one token per lane through every layer, and ``attend(l, q, k,
v)`` owns the KV write and the history read, so the paged, multi-token,
draft and unpaged steps stay one each and serve both architectures.

The layer, for hidden ``x`` of one token at position ``t``::

    h   = rmsnorm(x, ln1_g)
    q,k = rmsnorm(h @ wq, q_norm), rmsnorm(h @ wk, k_norm)   # over all H*D values
    v   = h @ wv
    q,k = rope(q, t), rope(k, t)          # per head, rotate-half pairs (i, i + D/2)
    x   = x + attention(q, K[0..t], V[0..t]) @ wo           # K cached after rope
    h2  = rmsnorm(x, ln2_g)
    p   = softmax(h2 @ router)            # over all experts, float32
    S   = the experts_per_token largest p                   # weights p_e as they are
    x   = x + sum_{e in S} p_e * ((silu(h2 @ wgate_e) * (h2 @ wup_e)) @ wdown_e)

and ``logits = rmsnorm(x, lnf_g) @ head``.  No biases, no shared expert, no
renormalised gates (``norm_topk_prob`` false), no capacity: every token is
computed by exactly its chosen experts (``parallel/moe.py`` is the
trainer's routing, which drops over capacity and renormalises).

Precision: matmul inputs are cast to the weights' dtype (bfloat16 as
served, float32 in the CPU parity tests) and accumulate in float32
(``preferred_element_type``); the RMSNorms, the router's logits and
softmax, RoPE and the residual additions are float32.  K and V leave here
in float32 and are cast to the pool's dtype by the step's one write.

Routing that drops nothing, shape-static per lane bucket, is
``pallas_kernels/moe_experts.py`` ``routed_experts``: on a TPU one kernel a
layer reads the experts some live lane chose, steered by the list of them,
and nothing of the others (PERF.md section 6, PR 34: it streams this model's
63 of 64 hit experts at 752 GB/s where the einsums read all 64 at 706);
elsewhere every expert runs over every lane (``[E, B, F]``) with the
unchosen weighted zero, which is the same sum.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g [H]`` and per layer ``l<i>_`` + ``ln1_g``, ``wq``, ``wk``,
``wv``, ``wo`` ``[H, H]``, ``q_norm``, ``k_norm`` ``[H]``, ``ln2_g``,
``router [H, E]``, ``wgate``, ``wup`` ``[E, H, F]``, ``wdown [E, F, H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..pallas_kernels import moe_experts as _moe
from .decoder_family import DecoderFamily

__all__ = ["token_logits", "param_shapes", "init_params", "NP_DTYPES",
           "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention",), routes="after_dense",
                       expert_matrices=3)

NP_DTYPES = {"f32": np.dtype(np.float32), "bf16": np.dtype(jnp.bfloat16)}


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones."""
    h, f, e, v = cfg.hidden, cfg.ffn, cfg.experts, cfg.vocab
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    for l in range(cfg.layers):
        for name, shape, kind in (
                ("ln1_g", (h,), "ones"), ("wq", (h, h), "normal"),
                ("wk", (h, h), "normal"), ("wv", (h, h), "normal"),
                ("wo", (h, h), "normal"), ("q_norm", (h,), "ones"),
                ("k_norm", (h,), "ones"), ("ln2_g", (h,), "ones"),
                ("router", (h, e), "normal"),
                ("wgate", (e, h, f), "normal"), ("wup", (e, h, f), "normal"),
                ("wdown", (e, f, h), "normal")):
            shapes["l%d_%s" % (l, name)] = (shape, kind)
    return shapes


def init_params(cfg, seed=0, std=0.02):
    """name -> np array in the config's weight dtype; ``std``-normal
    weights, norms at 1 (host-side: tests and demo bundles)."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]
    return {name: (np.ones(shape, np.float32) if kind == "ones"
                   else r.standard_normal(shape).astype(np.float32) * std
                   ).astype(dtype)
            for name, (shape, kind) in sorted(param_shapes(cfg).items())}


def _rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _mm(x, w):
    """``x @ w`` with the input in the weight's dtype, float32 out."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rope(x, pos, theta):
    """x [B, heads, D] float32 at positions pos [B]: pair (i, i + D/2)
    turns by ``pos * theta ** (-2 i / D)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _route(h2, router, k):
    """-> (gates [B, E] float32: the softmax probability of each of the
    token's k chosen experts, 0 elsewhere; chosen [B, E] bool)."""
    logits = jnp.dot(h2, router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _top, idx = jax.lax.top_k(p, k)
    chosen = jnp.any(jax.nn.one_hot(idx, p.shape[-1], dtype=bool), axis=1)
    return jnp.where(chosen, p, 0.0), chosen


def token_logits(params, cfg, tok, pos, attend, live, recur=None):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed``
    int32 [layers, experts]: the tokens of live lanes sent to each expert
    this step.  Scope names as the GPT-2 block's, plus
    ``layer<i>/moe/router`` and ``layer<i>/moe/experts``."""
    bb = tok.shape[0]
    shape = (bb, cfg.heads, cfg.head_dim)
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            with jax.named_scope("attn"):
                h = _rmsnorm(x, p("ln1_g"), eps)
                q = _rmsnorm(_mm(h, p("wq")), p("q_norm"), eps)
                k = _rmsnorm(_mm(h, p("wk")), p("k_norm"), eps)
                q = _rope(q.reshape(shape), pos, cfg.rope_theta)
                k = _rope(k.reshape(shape), pos, cfg.rope_theta)
                v = _mm(h, p("wv")).reshape(shape)
                a = attend(l, q, k, v).reshape(bb, cfg.hidden)
                x = x + _mm(a, p("wo"))
            with jax.named_scope("moe"):
                h2 = _rmsnorm(x, p("ln2_g"), eps)
                with jax.named_scope("router"):
                    gates, chosen = _route(h2, p("router"),
                                           cfg.experts_per_token)
                    routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                          dtype=jnp.int32))
                with jax.named_scope("experts"):
                    x = x + _moe.routed_experts(
                        h2, gates, live, p("wgate"), p("wup"), p("wdown"))
    with jax.named_scope("lm_head"):
        logits = _mm(_rmsnorm(x, params["lnf_g"], eps), params["head"])
    return logits, (jnp.stack(routed),)
