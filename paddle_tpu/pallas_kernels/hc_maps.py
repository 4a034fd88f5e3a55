"""The three maps of one hyper-connection mixing (models/hyper_connections.py
has the equations) as one device operation: the flattened norm, the
projection on ``phi``, the two sigmoids, the clipped exponential and the
Sinkhorn normalisation of the ``n x n`` residual map, for every lane of a
decode step at once.

XLA's form of the same is a reduce, a product and, because it fuses none of
the Sinkhorn's normalisations with the next, two small fusions an iteration
and direction: 81 operations a mixing at the published 20 iterations, 6,482
of a 40-layer step's 7,000 (PERF.md section 6, PRs 67 and 68).  Here a
mixing is one kernel.

Everything is laid with the LANES OF THE STEP ALONG THE 128-WIDE AXIS and
the maps' entries along sublanes: the projection comes out ``[2 n + n^2,
B]``, and the residual map's row ``i`` is an ``[n, B]`` value (entry ``j`` on
sublane ``j``), so a normalisation by rows is a sum over ``n`` sublanes and a
division a row, one by columns the rows' sum and a division a row; nothing is
turned in the kernel.  That is why ``phi`` comes as ``[2 n + n^2, n C]``
(the published ``[n C, 2 n + n^2]`` turned by the caller: the chip holds that
array with its long axis minor, so the turn is a bitcast) and the streams as
``[n, B, C]``: a stream's ``[B, C]`` is what the product contracts against
``phi``'s columns of that stream.  Float32 throughout, the product at the
highest matmul precision, a true division.

The operands are WHOLE ARRAYS IN VMEM, and XLA hands them over where it
keeps them: the merge before writes the streams into VMEM in this layout (the
compiled step holds them there from one mixing to the next, as the jnp form's
did) and ``phi`` arrives by the fetch XLA starts ahead of the call
(``slice-start``), so the kernel moves nothing and is arithmetic: four
products of ``[24, 3584] x [3584, B]`` in six bfloat16 passes, the norm's
sums as adds and one small product, and the normalisations, a dependent chain
(3.7 us a call at the published widths and 32 lanes, of which the 19
iterations past the first are 0.85: PERF.md section 6, PR 68).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["maps", "hc_maps_checks", "maps_path", "KERNEL_NAME"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "hc_maps"

# what a bucket's streams and one mixing's ``phi`` may take of the VMEM a
# kernel gets without asking (Mosaic's scoped limit, 16 MiB of the chip's
# 128): its operands are whole arrays held there, and the product's own
# temporaries come to a third of them on top (compiled for a described v5e:
# 160 lanes of the published 4 x 3,584 fit, 192 do not).  XLA keeps the next
# products' weights in the same VMEM across the call, so the kernel asks for
# no more (``ssm_update`` has the measurement)
_VMEM_BUDGET = 10 << 20

_HIGHEST = jax.lax.Precision.HIGHEST


def _rows(n):
    """``phi``'s rows as the kernel holds it: ``H_pre``'s and ``H_post``'s
    ``n`` each, then ``H_res``'s ``n^2``."""
    return 2 * n + n * n


def hc_maps_checks(n, hidden, lanes, dtype=jnp.float32):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of ``n`` streams of ``hidden`` values at a step of ``lanes``
    lanes."""
    dims = (n, hidden, lanes)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    shaped = static and all(x > 0 for x in dims)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("dtype", jnp.dtype(dtype) == jnp.float32),
        ("empty", shaped),
        # a stream's columns of phi are whole 128-lane tiles
        ("lanes", shaped and hidden % 128 == 0),
        # phi's rows whole sublane tiles, the residual map's too
        ("sublanes", shaped and _rows(n) % 8 == 0),
        # the bucket's streams and one phi within the VMEM a kernel gets
        ("vmem", shaped and 4 * n * hidden * (lanes + _rows(n))
         <= _VMEM_BUDGET),
    ]


def maps_path(n, hidden, lanes, dtype=jnp.float32):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"xla"``: the same rule as ``maps``, counted nowhere."""
    ok = all(ok for _reason, ok in hc_maps_checks(n, hidden, lanes, dtype))
    return "pallas" if ok else "xla"


def _sinkhorn(rows, iters, eps):
    """``rows``: the residual map's rows, ``n`` values ``[n, B]`` (entry
    ``j`` of row ``i`` on sublane ``j`` of value ``i``) -> normalised by
    rows, then by columns, ``iters`` times; ``eps`` in each sum, a true
    division."""
    for _ in range(iters):
        rows = [r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in rows]
        total = functools.reduce(jnp.add, rows) + eps
        rows = [r / total for r in rows]
    return rows


def _kernel(x_ref, phi_ref, ab_ref, out_ref, *, n, hidden, iters, eps,
            norm_eps, lo, hi):
    f32 = jnp.float32
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=f32)
    proj, squares = [], []
    for i in range(n):
        xi = x_ref[i]                                      # [B, C]
        proj.append(dot(phi_ref[:, i * hidden:(i + 1) * hidden], xi))
        # a lane's sum of squares, 128 partial sums of it: adds alone
        sq = xi * xi
        squares += [sq[:, t:t + 128] for t in range(0, hidden, 128)]
    proj = functools.reduce(jnp.add, proj)                 # [2 n + n^2, B]
    # ... which one small product sums and lays along the lanes, as proj is
    ssq = dot(jnp.ones((8, 128), f32),
              functools.reduce(jnp.add, squares))[:1]      # [1, B]
    norm = jax.lax.rsqrt(ssq / (n * hidden) + norm_eps)
    z = ab_ref[:, 0:1] * (proj * norm) + ab_ref[:, 1:2]
    out_ref[:n] = jax.nn.sigmoid(z[:n])
    out_ref[n:2 * n] = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    m = jnp.exp(jnp.clip(z[2 * n:], lo, hi))
    rows = _sinkhorn([m[i * n:(i + 1) * n] for i in range(n)], iters, eps)
    for i, r in enumerate(rows):
        out_ref[2 * n + i * n:2 * n + (i + 1) * n] = r


@functools.lru_cache(maxsize=None)
def _maps_call(lanes, n, hidden, iters, eps, norm_eps, clamp, interpret,
               kernel, sinkhorn):
    """The kernel's call for one set of shapes, traced once, as
    ``paged_attention._latent_call`` is: a model's 80 mixings call one
    ``jit`` whose jaxpr is inlined where it is called.  Everything the
    kernel reads beside its operands is in the key, the kernel and its
    normalisation too (what a test or a check swaps gets another call)."""
    del sinkhorn                        # read by ``kernel`` from the module
    rows = _rows(n)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return jax.jit(pl.pallas_call(
        functools.partial(kernel, n=n, hidden=hidden, iters=iters, eps=eps,
                          norm_eps=norm_eps, lo=clamp[0], hi=clamp[1]),
        in_specs=[vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        name=KERNEL_NAME,
        interpret=interpret,
    ), inline=True)


def maps(cfg, phi_t, b, a, X):
    """X [B, n, C] float32 -> (H_pre [B, n], H_post [B, n], H_res [B, n,
    n]) by one sublayer's ``phi_t [2 n + n^2, n C]`` (the published ``phi``
    turned), ``b`` and ``a``: ``hyper_connections.maps`` on the kernel."""
    f32 = jnp.float32
    lanes, n, hidden = X.shape
    # a row's weight of the dynamic part, a_pre, a_post or a_res: picked
    # by a constant one-hot, so that XLA reads ``a`` whole in one fusion
    part = np.repeat(np.eye(3, dtype=np.float32), (n, n, n * n), axis=0)
    scale = jnp.sum(part * a.astype(f32)[None], axis=1)
    out = _maps_call(
        lanes, n, hidden, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.norm_eps,
        tuple(cfg.hc_clamp), adoption.interpret(), _kernel, _sinkhorn)(
            jnp.transpose(X, (1, 0, 2)), phi_t.astype(f32),
            jnp.stack([scale, b.astype(f32)], axis=1))
    out = out.T
    return out[:, :n], out[:, n:2 * n], out[:, 2 * n:].reshape(lanes, n, n)
