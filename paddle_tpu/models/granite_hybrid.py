"""Granite 4.0-H decoder block (ibm-granite/granite-4.0-h-micro:
``model_type`` ``granitemoehybrid`` with no routed experts) as pure
functions of ``(params, cfg, tok, pos, attend, live, recur)``, called by the
decode steps of ``serving/decode_model.py`` under the same contract as the
GPT-2 and OLMoE blocks: one token per lane through every layer.  Layers are
of two kinds, named by ``cfg.layer_types``:

* ``attention``: grouped-query attention, ``cfg.heads`` query heads over
  ``cfg.kv_heads`` KV heads, no position encoding, scores scaled by
  ``cfg.attention_multiplier``.  ``attend(l, q, k, v)`` owns the KV write
  and the history read, as for the other blocks.
* ``mamba``: a Mamba-2 state-space mixer.  What it keeps between tokens,
  the last ``ssm_conv - 1`` inputs of its causal convolution and the state
  ``S``, lives wherever the step maker says: ``recur.window(l, xbc)``
  pushes this token's convolution input and returns the ``ssm_conv`` newest
  (zeros before position 0), and ``recur.advance(l, decay, dx, b, c)``
  moves the state one token (``S = decay * S + outer(b, dx)``) and returns
  ``c . S``.  The block owns everything else.

The layer, for hidden ``x`` of one token (``rm`` the residual multiplier)::

    h = rmsnorm(x, ln1_g);  x = x + rm * mixer(h)      # by layer_types[l]
    h = rmsnorm(x, ln2_g);  a, b = split(h @ w_in, 2)
    x = x + rm * ((silu(a) * b) @ w_out)

    attention:  q = h @ wq [H x D], k = h @ wk, v = h @ wv [KH x D each]
                mixer = attend(q, k, v) @ wo
    mamba:      z, xBC, dt = split(h @ in_proj, [I, I + 2 N, SH])
                xBC  = silu(conv_b + sum_j conv_w[j] * window[j])
                xs, B, C = split(xBC, [I, N, N])
                dt   = softplus(dt + dt_bias);  A = -exp(A_log)
                S    = exp(dt A) * S + dt * outer(xs, B)   # per head
                y    = S @ C + D * xs
                mixer = rmsnorm(y * silu(z), ssm_norm) @ out_proj

with ``x0 = embedding_multiplier * embed[tok]`` and ``logits = rmsnorm(x,
lnf_g) @ embed^T / logits_scaling`` (a tied head).  ``I = ssm_heads *
ssm_head_dim``, ``N = ssm_state``, ``SH = ssm_heads``, one group of B and C
for all heads (``mamba_mixer`` takes ``cfg.ssm_groups`` of them: the
``nemotron_h`` block's mixer is this one with eight).

Precision: matmul inputs are cast to the weights' dtype (bfloat16 as
served, float32 in the CPU parity tests) and accumulate in float32; norms,
the convolution, softplus, the decay, the state update and its read-out,
and the residual additions are float32.  The state is float32 wherever it
lives; the convolution's window takes its store's dtype (bfloat16 as
served).

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``lnf_g`` and
per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``, ``w_in [H, 2 F]``, ``w_out [F,
H]``; attention layers ``wq [H, H]``, ``wk``, ``wv [H, KH * D]``, ``wo``;
mamba layers ``in_proj [H, 2 I + 2 N + SH]``, ``conv_w [K, I + 2 N]``,
``conv_b``, ``dt_bias``, ``A_log``, ``D [SH]``, ``ssm_norm [I]``,
``out_proj [I, H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "mamba_mixer",
           "mamba_param_shapes", "draw", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention", "mamba"), grouped_query=True)


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | conv | a_log |
    dt_bias."""
    h, f, v = cfg.hidden, cfg.ffn, cfg.vocab
    kv = cfg.kv_heads * cfg.head_dim
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones")}
    kinds = {
        "attention": (("wq", (h, h), "normal"), ("wk", (h, kv), "normal"),
                      ("wv", (h, kv), "normal"), ("wo", (h, h), "normal")),
        "mamba": mamba_param_shapes(cfg),
    }
    for l, kind in enumerate(cfg.layer_types):
        for name, shape, init in (
                ("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones"),
                ("w_in", (h, 2 * f), "normal"), ("w_out", (f, h), "normal")
        ) + kinds[kind]:
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms and ``D`` at 1, and Mamba-2's own start for what sets
    how much the state holds and how long it remembers: the depthwise
    convolution and its bias uniform in +-1/sqrt(taps) (a normal(0, 0.02)
    convolution leaves x, B and C, and with them the state, at a
    thousandth of the skip path), ``A_log = log(u)``, u uniform in [1,
    16], and ``dt_bias = softplus^-1(dt)``, dt log-uniform in [0.001,
    0.1].  Host-side: tests and demo bundles."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]
    return {name: draw(r, cfg, shape, kind, std).astype(np.float32)
            .astype(dtype)
            for name, (shape, kind) in sorted(param_shapes(cfg).items())}


def mamba_mixer(cfg, p, l, h, recur):
    """The Mamba-2 mixer of layer ``l`` over h [B, H] float32, B and C in
    ``cfg.ssm_groups`` groups of heads (one here; ``models/nemotron_h.py``
    calls it with eight): the convolution is ``I + 2 G N`` wide, head ``h``
    reads group ``h // (heads / G)``, and the gated norm is over each
    group's ``I / G`` values."""
    f32 = jnp.float32
    inner, n, groups = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
    bc = groups * n
    with jax.named_scope("in_proj"):
        zxbcdt = _mm(h, p("in_proj"))
        z = zxbcdt[:, :inner]
        xbc = zxbcdt[:, inner:2 * inner + 2 * bc]
        dt = zxbcdt[:, 2 * inner + 2 * bc:]
    with jax.named_scope("conv"):
        window = recur.window(l, xbc)               # [B, K, I + 2 G N]
        xbc = jax.nn.silu(
            p("conv_b").astype(f32)
            + jnp.sum(p("conv_w").astype(f32)[None] * window, axis=1))
        xs = xbc[:, :inner]
        b, c = (xbc[:, at:at + bc].reshape(-1, groups, n)
                for at in (inner, inner + bc))
    with jax.named_scope("state_update"):
        dt = jax.nn.softplus(dt + p("dt_bias").astype(f32))     # [B, SH]
        decay = jnp.exp(dt * -jnp.exp(p("A_log").astype(f32)))
        per_head = lambda a: jnp.repeat(a, cfg.ssm_head_dim, axis=-1)
        y = recur.advance(l, per_head(decay), per_head(dt) * xs, b, c)
        y = y + per_head(p("D").astype(f32)) * xs
    with jax.named_scope("out_proj"):
        by_group = lambda a: a.reshape(a.shape[:-1] + (groups, -1))
        y = _rmsnorm(by_group(y * jax.nn.silu(z)), by_group(p("ssm_norm")),
                     cfg.norm_eps).reshape(y.shape)
        return _mm(y, p("out_proj"))


def mamba_param_shapes(cfg):
    """(name, shape, kind) of a mamba layer's mixer."""
    h, inner, sh = cfg.hidden, cfg.ssm_inner, cfg.ssm_heads
    conv_dim = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return (("in_proj", (h, inner + conv_dim + sh), "normal"),
            ("conv_w", (cfg.ssm_conv, conv_dim), "conv"),
            ("conv_b", (conv_dim,), "conv"),
            ("dt_bias", (sh,), "dt_bias"), ("A_log", (sh,), "a_log"),
            ("D", (sh,), "ones"), ("ssm_norm", (inner,), "ones"),
            ("out_proj", (inner, h), "normal"))


def draw(r, cfg, shape, kind, std):
    """One seeded array of ``init_params``, by its kind (float64)."""
    if kind == "ones":
        return np.ones(shape, np.float32)
    if kind == "conv":
        bound = cfg.ssm_conv ** -0.5
        return r.uniform(-bound, bound, shape)
    if kind == "a_log":
        return np.log(r.uniform(1.0, 16.0, shape))
    if kind == "dt_bias":
        dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), shape))
        return dt + np.log(-np.expm1(-dt))
    return r.standard_normal(shape) * std


def token_logits(params, cfg, tok, pos, attend, live, recur):
    """-> (logits [B, vocab] float32, ()).  Scope names as the other
    blocks' (``layer<i>/attn``, ``.../kv_write``, ``.../kv_read``,
    ``layer<i>/mlp``, ``lm_head``), and on mamba layers ``layer<i>/ssm/``
    + ``in_proj``, ``conv``, ``state_update``, ``out_proj``."""
    del pos, live                       # no position encoding, no routing
    bb = tok.shape[0]
    eps, rm = cfg.norm_eps, cfg.residual_multiplier
    embed = params["embed"]
    x = cfg.embedding_multiplier \
        * jnp.take(embed, tok, axis=0).astype(jnp.float32)
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
                    a = attend(l, q, k, v).reshape(bb, cfg.hidden)
                    x = x + rm * _mm(a, p("wo"))
            else:
                with jax.named_scope("ssm"):
                    x = x + rm * mamba_mixer(cfg, p, l, h, recur)
            with jax.named_scope("mlp"):
                ab = _mm(_rmsnorm(x, p("ln2_g"), eps), p("w_in"))
                x = x + rm * _mm(jax.nn.silu(ab[:, :cfg.ffn])
                                 * ab[:, cfg.ffn:], p("w_out"))
    with jax.named_scope("lm_head"):
        hx = _rmsnorm(x, params["lnf_g"], eps).astype(embed.dtype)
        logits = jax.lax.dot_general(
            hx, embed, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / cfg.logits_scaling
    return logits, ()
