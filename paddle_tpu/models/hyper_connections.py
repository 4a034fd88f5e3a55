"""Manifold-constrained hyper-connections ("mHC", DeepSeek-AI, arXiv
2512.24880, which constrains "Hyper-Connections", Zhu et al., arXiv
2409.19606) as pure functions: what a block does to its residual path when a
token's stream is ``n`` vectors and not one.  A family whose declaration says
``residual_streams`` (``xing4``) carries ``X [B, n, C]`` float32 between its
sublayers (``n = cfg.hc_mult``, ``C = cfg.hidden``) and, round every sublayer
``F``, computes three small maps from the streams themselves, reads the
sublayer's input through one of them and merges its output through the other
two::

    r       = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)          # [n C]
    [p | q | R] = r @ phi                                       # n, n, n^2
    H_pre   = sigmoid(a_pre p + b_pre)                          # [n]
    H_post  = 2 sigmoid(a_post q + b_post)                      # [n]
    M_0     = exp(clip(a_res mat(R) + b_res, clamp))            # [n, n]
    M_t     = cols(rows(M_{t-1})),  t = 1 .. cfg.hc_sinkhorn_iters
              rows(M) = M / (M 1 + hc_eps), cols alike
    H_res   = M_last                      # doubly stochastic to the iterations
    u       = H_pre X                                           # [C]
    X'      = H_res X + H_post^T F(u)                           # [n, C]

``maps`` is the first seven lines, ``read`` the eighth, ``merge`` the ninth;
``start`` repeats an embedding ``n`` times and ``total`` sums the streams
before the final norm.  The flattened norm has no gain (one would fold into
``phi``); rows are normalised before columns; ``hc_eps`` is added to each
sum.  Everything here is float32, the parameters too (``phi [n C, 2 n +
n^2]``, ``b [2 n + n^2]``, ``a [3]``: ``a_pre``, ``a_post``, ``a_res``), the
projection at the highest matmul precision (its 2 n + n^2 columns are far too
few to be worth the matrix unit's bfloat16 passes, and an error in ``R`` is
exponentiated) and the two mixings as sums of products on the vector unit.
The Sinkhorn normalisation is unrolled: ``cfg.hc_sinkhorn_iters`` is a
constant of the model and a step has no loop.

``maps`` picks its path from what it can see, with no flag, as
``ssm_update.state_update`` does: **the kernel**
(``pallas_kernels/hc_maps.py``: a sublayer's maps one device operation) on a
TPU backend where its shape rule admits the step (``maps_path``), and
``maps_reference``, the lines above in jnp, everywhere else.  XLA makes of
the jnp form a reduce, a product and, because it fuses none of the Sinkhorn's
normalisations with the next, two small fusions an iteration and direction:
81 operations a mixing at the published 20 iterations.  The kernel reads
``phi`` as ``[2 n + n^2, n C]``, and is handed the published array turned:
the chip holds ``f32[n C, 2 n + n^2]`` with its long axis minor
(``{0,1:T(8,128)}``: 24 columns would fill a fifth of a 128-lane tile), which
is ``[2 n + n^2, n C]`` row by row, so the turn is a bitcast in the compiled
step and no layout at load is needed (tests/test_tpu_compile.py holds it).

``width``, ``param_shapes``, ``param_bytes`` and ``stream_bytes`` are what
the block's ``param_shapes``, ``decode_model.StepAccount`` and the
benchmark's cost file count by.
"""

import jax
import jax.numpy as jnp

from ..pallas_kernels import adoption
from ..pallas_kernels import hc_maps as _kernel

__all__ = ["width", "param_shapes", "param_bytes", "stream_bytes", "maps",
           "maps_reference", "maps_path", "read", "merge", "start", "total",
           "sinkhorn"]

F32_BYTES = 4


def width(n):
    """The columns of ``phi`` (and entries of ``b``) for ``n`` streams:
    ``H_pre``'s and ``H_post``'s ``n`` each, then ``H_res``'s ``n^2`` row by
    row."""
    return 2 * n + n * n


def param_shapes(cfg, name):
    """(name, shape, kind) of one sublayer's mixing, its parameters named
    ``hc_<name>_phi``, ``_b`` and ``_a`` (kinds ``hc_phi``, ``hc_b``,
    ``hc_a``: float32 whatever the weights' dtype)."""
    n = cfg.hc_mult
    return (("hc_%s_phi" % name, (n * cfg.hidden, width(n)), "hc_phi"),
            ("hc_%s_b" % name, (width(n),), "hc_b"),
            ("hc_%s_a" % name, (3,), "hc_a"))


def param_bytes(cfg, mixings):
    """The bytes of ``mixings`` sublayers' ``phi``, ``b`` and ``a``."""
    n = cfg.hc_mult
    return mixings * (n * cfg.hidden * width(n) + width(n) + 3) * F32_BYTES


def stream_bytes(n, hidden, mixings, lanes):
    """What ``mixings`` mixings have to move of ``lanes`` tokens' streams:
    a token's ``[n, hidden]`` float32 read once for the maps and the
    sublayer's input, read once more for the merge and written once.  The
    sublayer's input and output (a vector each) and the maps themselves are
    left out."""
    return 3 * int(mixings) * int(lanes) * n * hidden * F32_BYTES


def start(x, n):
    """The streams a token starts from: its embedding x [B, C], ``n``
    times -> [B, n, C] float32."""
    x = x.astype(jnp.float32)
    return jnp.broadcast_to(x[:, None], (x.shape[0], n, x.shape[1]))


def total(X):
    """The vector the final norm reads: the streams' sum [B, C]."""
    return jnp.sum(X, axis=1)


def sinkhorn(m, iters, eps):
    """m [B, n, n] positive -> rows then columns normalised ``iters``
    times, each sum with ``eps`` added: doubly stochastic in the limit."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def _flat_norm(X, eps):
    """vec(X) at unit root-mean-square [B, n C]: no gain."""
    flat = X.reshape(X.shape[0], -1)
    return flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)


def maps_path(cfg, lanes):
    """``"pallas"`` where the kernel makes the maps of a step of ``lanes``
    lanes of this model on this backend, else ``"xla"`` (the jnp form);
    None for a model of one stream.  The engine names the step's path by
    it: in the executable's cache key, on the ``serving_prewarm`` event and
    on the step's span."""
    if not cfg.hc_mult:
        return None
    return _kernel.maps_path(cfg.hc_mult, cfg.hidden, lanes)


def maps_reference(cfg, phi, b, a, X):
    """``maps`` in jnp, whatever the backend: the flattened norm, the
    projection at the highest matmul precision and the unrolled
    normalisation."""
    n = cfg.hc_mult
    lo, hi = cfg.hc_clamp
    f32 = jnp.float32
    proj = jnp.dot(_flat_norm(X, cfg.norm_eps), phi.astype(f32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=f32)
    b, a = b.astype(f32), a.astype(f32)
    pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * proj[:, 2 * n:] + b[2 * n:], lo, hi))
    return pre, post, sinkhorn(res.reshape(-1, n, n), cfg.hc_sinkhorn_iters,
                               cfg.hc_eps)


def maps(cfg, phi, b, a, X):
    """X [B, n, C] float32 -> (H_pre [B, n], H_post [B, n], H_res [B, n,
    n]) by one sublayer's ``phi``, ``b`` and ``a`` (float32).  The kernel
    where the shape rule admits it (``adoption.decide`` counts the lowering
    under ``pallas_kernel_used_total`` / ``..._fallback_total{reason}``),
    ``maps_reference`` otherwise."""
    lanes, n, hidden = X.shape
    use, _reason = adoption.decide(
        "hc_maps", _kernel.hc_maps_checks(n, hidden, lanes, X.dtype))
    if not use:
        return maps_reference(cfg, phi, b, a, X)
    return _kernel.maps(cfg, phi.T, b, a, X)


def read(pre, X):
    """``u = H_pre X`` [B, C]: what the sublayer reads."""
    return jnp.sum(pre[:, :, None] * X, axis=1)


def merge(X, y, post, res):
    """``X' = H_res X + H_post^T y`` [B, n, C] with y [B, C] the sublayer's
    output."""
    mixed = jnp.sum(res[:, :, :, None] * X[:, None, :, :], axis=2)
    return mixed + post[:, :, None] * y.astype(jnp.float32)[:, None, :]
