"""LFM2-MoE decoder block (LiquidAI/LFM2-24B-A2B: ``model_type``
``lfm2_moe``) as pure functions of ``(params, cfg, tok, pos, attend, live,
recur)``, called by the decode steps of ``serving/decode_model.py`` under
the same contract as the GPT-2, OLMoE and Granite blocks: one token per lane
through every layer.  The mixers are of two kinds, named by
``cfg.layer_types``, and the feed-forward of two, by the layer's place:

* ``conv``: a gated short convolution.  What it keeps between tokens, the
  last ``conv_taps - 1`` inputs of its depthwise causal convolution, lives
  wherever the step maker says: ``recur.window(l, g)`` pushes this token's
  input and returns the ``conv_taps`` newest (zeros before position 0).  It
  keeps no state: ``recur.advance`` is not called.
* ``attention``: grouped-query attention, ``cfg.heads`` query heads over
  ``cfg.kv_heads`` KV heads, RMSNorm over each head's values of q and of k
  (OLMoE's is over all heads' at once), then RoPE.  ``attend(l, q, k, v)``
  owns the KV write and the history read, as for the other blocks.
* the first ``cfg.dense_layers`` layers end in a SiLU-gated MLP of width
  ``cfg.dense_ffn``; every later one (``cfg.routed_layers``) in
  ``cfg.experts`` experts of width ``cfg.ffn``, ``cfg.experts_per_token`` a
  token.

The layer, for hidden ``x`` of one token at position ``t``::

    h = rmsnorm(x, ln1_g)
    conv:       B, C, u = split(h @ in_proj, 3)
                g = B * u
                c = sum_j conv_w[j] * window[j]        # window: g[t-K+1 .. t]
                x = x + (C * c) @ out_proj
    attention:  q = rmsnorm(h @ wq [H x D], q_norm [D])    # per head
                k = rmsnorm(h @ wk [KH x D], k_norm [D]);  v = h @ wv
                q, k = rope(q, t), rope(k, t)   # rotate-half pairs (i, i + D/2)
                x = x + attention(q, K[0..t], V[0..t]) @ wo
    h2 = rmsnorm(x, ln2_g)
    dense:      x = x + (silu(h2 @ w1) * (h2 @ w3)) @ w2
    routed:     s = sigmoid(h2 @ router)                   # [E], float32
                S = the experts_per_token largest of s + expert_bias
                w_e = s_e / (sum_{e in S} s_e + 1e-6) * routed_scaling
                x = x + sum_{e in S} w_e * ((silu(h2 @ wgate_e) * (h2 @ wup_e)) @ wdown_e)

and ``logits = rmsnorm(x, lnf_g) @ embed^T`` (a tied head).  The bias
chooses and never weighs; no biases elsewhere, no shared expert, no
activation in the convolution, no capacity: every token is computed by
exactly its chosen experts.

Precision: matmul inputs are cast to the weights' dtype (bfloat16 as
served, float32 in the CPU parity tests) and accumulate in float32; the
norms, the sigmoid and the gates, RoPE, the ``B * u`` and ``C * c``
products, the convolution and the residual additions are float32.  The
convolution's window takes its store's dtype (bfloat16 as served); K and V
leave here in float32 and are cast to the pool's dtype by the step's write.

Routing that drops nothing, shape-static per lane bucket, is OLMoE's:
``pallas_kernels/moe_experts.py`` ``routed_experts``.  At 32 lanes x 4
experts over 64, with a seeded selection bias, 24-26 experts a layer go
unhit, and on a TPU their weights are not read (PERF.md section 6, PR 34:
5.8e9 B a step where the einsums streamed 9.66e9).

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``lnf_g`` and
per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``; conv layers ``in_proj [H, 3
H]``, ``conv_w [K, H]`` (row j the tap on the input K - 1 - j tokens back),
``out_proj [H, H]``; attention layers ``wq [H, H]``, ``wk``, ``wv [H, KH *
D]``, ``wo``, ``q_norm``, ``k_norm [D]``; dense layers ``w1``, ``w3 [H,
F]``, ``w2 [F, H]``; routed layers ``router [H, E]``, ``expert_bias [E]``,
``wgate``, ``wup [E, H, Fe]``, ``wdown [E, Fe, H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..pallas_kernels import moe_experts as _moe
from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm, _rope

__all__ = ["token_logits", "param_shapes", "init_params", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention", "conv"), grouped_query=True,
                       routes="after_dense", expert_matrices=3,
                       dense_lead=True)

# the least the renormalised gates' denominator can be (the family's
# modelling code adds it to the sum of the chosen scores)
GATE_EPS = 1e-6
# standard deviation of a seeded ``expert_bias`` (``init_params`` and the
# benchmark's weights): against sigmoid scores spread over about 0.2 it
# changes the selection on a large share of tokens
BIAS_STD = 0.1


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | conv | bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    kv = cfg.kv_heads * d
    e, fe, fd = cfg.experts, cfg.ffn, cfg.dense_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones")}
    mixers = {
        "attention": (("wq", (h, h), "normal"), ("wk", (h, kv), "normal"),
                      ("wv", (h, kv), "normal"), ("wo", (h, h), "normal"),
                      ("q_norm", (d,), "ones"), ("k_norm", (d,), "ones")),
        "conv": (("in_proj", (h, 3 * h), "normal"),
                 ("conv_w", (cfg.conv_taps, h), "conv"),
                 ("out_proj", (h, h), "normal")),
    }
    dense = (("w1", (h, fd), "normal"), ("w3", (h, fd), "normal"),
             ("w2", (fd, h), "normal"))
    routed = (("router", (h, e), "normal"), ("expert_bias", (e,), "bias"),
              ("wgate", (e, h, fe), "normal"), ("wup", (e, h, fe), "normal"),
              ("wdown", (e, fe, h), "normal"))
    for l, kind in enumerate(cfg.layer_types):
        for name, shape, init in (
                ("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones")
        ) + mixers[kind] + (dense if l < cfg.dense_layers else routed):
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms at 1, the depthwise convolution uniform in
    +-1/sqrt(taps) (its framework's default start: a normal(0, 0.02)
    convolution leaves the conv path at a few hundredths of the residual,
    and a lost or stale window unseen) and ``expert_bias`` normal(0,
    ``BIAS_STD``) (at zero, a block that ignores it is indistinguishable).
    Host-side: tests and demo bundles."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]

    def make(shape, kind):
        if kind == "ones":
            return np.ones(shape, np.float32)
        if kind == "conv":
            bound = cfg.conv_taps ** -0.5
            return r.uniform(-bound, bound, shape)
        return r.standard_normal(shape) * (BIAS_STD if kind == "bias"
                                           else std)

    return {name: make(shape, kind).astype(np.float32).astype(dtype)
            for name, (shape, kind) in sorted(param_shapes(cfg).items())}


def _route(h2, router, bias, k, scaling, eps=GATE_EPS, limit=None):
    """-> (gates [B, E] float32: each of the token's k chosen experts'
    sigmoid score over the chosen scores' sum (and ``eps``), times
    ``scaling``, 0 elsewhere; chosen [B, E] bool).  ``bias`` moves the
    choice alone.  ``limit`` given, the k are chosen among what it leaves
    of the selection scores [B, E] (``exaone_moe``'s choice of groups)."""
    f32 = jnp.float32
    score = jax.nn.sigmoid(jnp.dot(h2, router.astype(f32),
                                   precision=jax.lax.Precision.HIGHEST))
    select = score + bias.astype(f32)
    if limit is not None:
        select = limit(select)
    _top, idx = jax.lax.top_k(select, k)
    chosen = jnp.any(jax.nn.one_hot(idx, score.shape[-1], dtype=bool), axis=1)
    gates = jnp.where(chosen, score, 0.0)
    return gates / (jnp.sum(gates, axis=-1, keepdims=True) + eps) \
        * scaling, chosen


def _head_norm(x, g, eps):
    """RMSNorm of x [B, heads, D] over each head's D values, one weight
    ``g`` [D] for all heads."""
    return _rmsnorm(x, g, eps)


def _short_conv(cfg, p, l, h, recur):
    """The gated short convolution of layer ``l`` over h [B, H] float32."""
    hid = cfg.hidden
    with jax.named_scope("in_proj"):
        bcu = _mm(h, p("in_proj"))
        gate_in, gate_out, u = bcu[:, :hid], bcu[:, hid:2 * hid], \
            bcu[:, 2 * hid:]
    with jax.named_scope("window"):
        window = recur.window(l, gate_in * u)              # [B, K, H]
        c = jnp.sum(p("conv_w").astype(jnp.float32)[None] * window, axis=1)
    with jax.named_scope("out_proj"):
        return _mm(gate_out * c, p("out_proj"))


def token_logits(params, cfg, tok, pos, attend, live, recur):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [routed layers, experts]: the tokens of live lanes sent to each expert
    this step, a row a layer of ``cfg.routed_layers``.  Scope names as the
    other blocks' (``layer<i>/attn``, ``.../kv_write``, ``.../kv_read``,
    ``layer<i>/mlp`` on dense layers, ``layer<i>/moe/router`` and
    ``.../moe/experts`` on routed ones, ``lm_head``), and on conv layers
    ``layer<i>/conv/`` + ``in_proj``, ``window``, ``out_proj``."""
    bb = tok.shape[0]
    eps = cfg.norm_eps
    embed = params["embed"]
    x = jnp.take(embed, tok, axis=0).astype(jnp.float32)
    routed = []
    for l, kind in enumerate(cfg.layer_types):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            if kind == "attention":
                with jax.named_scope("attn"):
                    q = _mm(h, p("wq")).reshape(bb, cfg.heads, cfg.head_dim)
                    k, v = (_mm(h, p(w)).reshape(bb, cfg.kv_heads,
                                                 cfg.head_dim)
                            for w in ("wk", "wv"))
                    q = _rope(_head_norm(q, p("q_norm"), eps), pos,
                              cfg.rope_theta)
                    k = _rope(_head_norm(k, p("k_norm"), eps), pos,
                              cfg.rope_theta)
                    a = attend(l, q, k, v).reshape(bb, cfg.hidden)
                    x = x + _mm(a, p("wo"))
            else:
                with jax.named_scope("conv"):
                    x = x + _short_conv(cfg, p, l, h, recur)
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if l < cfg.dense_layers:
                with jax.named_scope("mlp"):
                    x = x + _mm(jax.nn.silu(_mm(h2, p("w1")))
                                * _mm(h2, p("w3")), p("w2"))
            else:
                with jax.named_scope("moe"):
                    with jax.named_scope("router"):
                        gates, chosen = _route(
                            h2, p("router"), p("expert_bias"),
                            cfg.experts_per_token, cfg.routed_scaling)
                        routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                              dtype=jnp.int32))
                    with jax.named_scope("experts"):
                        x = x + _moe.routed_experts(
                            h2, gates, live, p("wgate"), p("wup"),
                            p("wdown"))
    with jax.named_scope("lm_head"):
        hx = _rmsnorm(x, params["lnf_g"], eps).astype(embed.dtype)
        logits = jax.lax.dot_general(
            hx, embed, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    # a cut that keeps the dense layers alone has nothing to count
    return logits, (jnp.stack(routed),) if routed else ()
