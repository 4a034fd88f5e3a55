"""Fused dropout + residual-add + LayerNorm as one Pallas TPU kernel.

TPU-native answer to the reference's fused_fc_elementwise_layernorm_op.cu
(paddle/fluid/operators/fused/ — the reference fuses the fc epilogue, the
elementwise add and the LayerNorm into one CUDA kernel for exactly the
transformer-encoder epilogue this targets), extended with in-kernel
dropout: z = LayerNorm(x + dropout_upscale(y)) in a single HBM pass.

Why a kernel at all: round-4's profile of the flagship BERT step
(bs256/seq128) left ~23 ms of LayerNorm reduce fusions, ~14 ms of
threefry dropout-mask generation and ~20 ms of layout copies that
XLA-level rewrites could not remove (five measured negatives,
BASELINE.md r4).  The round-3 Pallas LayerNorm LOST in-step because
isolating LN broke XLA's LN-neighbor fusions; this kernel fuses those
neighbors (the residual add and the dropout) so there is nothing left to
break, and draws the dropout mask with the on-core PRNG
(pltpu.prng_random_bits) so no threefry program or mask buffer ever
touches HBM — the backward re-draws the identical mask from the saved
32-bit seed pair instead of reading a saved mask.

Numerics: stats and the normalize are f32-internal regardless of the
carry dtype (the repo-wide LN policy); the keep threshold quantizes the
keep probability to round(q * 2^32)/2^32 — the same realized-probability
contract as ops/common.py bernoulli_bytes, at 2^-33 instead of 2^-9
granularity — and the upscale divides by that realized value so
E[out] = x + y exactly.

Off TPU (CPU test mesh) or for un-tileable shapes, an identical-contract
jnp fallback keyed on the same seed pair runs instead; forward and
backward always agree on the mask because both derive it from the saved
seeds with the same (static) path choice.

On a mesh: Mosaic kernels cannot be partitioned automatically, so in a
program XLA partitions by itself (with_data_parallel) the op-mode entry
points take ``rows_over=(mesh, axis)`` and run their row-wise body inside
a ``shard_map`` over that axis: each shard decides as one chip does on
its own rows (adoption.per_shard), folds its index along the axis into
the seed so the shards draw different masks (forward and backward alike;
the saved seed stays the unfolded pair), and the small gradients are
summed over the axis in the body.  Without ``rows_over`` a call traced in
such a program keeps the jnp composition, counted under ``gspmd_mesh``.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng as _prng

__all__ = ["fused_dropout_add_ln", "fused_ln_fwd", "fused_ln_bwd",
           "ln_stat_shapes"]

_LANES = 128

# shared realized-keep-probability contract (pallas_kernels/prng.py)
_keep_threshold = _prng.keep_threshold
_realized_q = _prng.realized_q


def _pick_rows(n, h, itemsize):
    """Rows per block, bounded by the ~16 MB VMEM scoped-stack limit.

    The backward is the binding constraint (measured: f32 at rows=512,
    h=768 allocates 20.25M — ~52 B per row-element ≈ itemsize*6 + 28 for
    the double-buffered ins/outs plus f32 intermediates).  MUST be a pure
    function of (n, h, itemsize): forward and backward both call it, and
    the dropout mask only replays if both use the same grid blocking.
    """
    # bf16/h=768 -> 512 (measured 24% faster fwd+bwd than 256 at the
    # flagship shape: 0.880 vs 1.165 ms); f32/h=768 -> 256 (512 exceeded
    # the VMEM stack in the pre-r design at 20.25M; the estimate keeps
    # f32 conservative)
    cap = (15 * 1024 * 1024) // (h * (itemsize * 6 + 20))
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand <= cap and n % cand == 0:
            return cand
    return None


def ln_stat_shapes(x_shape, begin_norm_axis):
    """(rows, norm_size) split of x_shape at begin_norm_axis.  The leading
    product may be a SYMBOLIC dim (graph-build shape inference traces ops
    with a symbolic batch — core/registry.py _sym_struct); the trailing
    (normalized) product is always concrete."""
    n = 1
    for d in x_shape[:begin_norm_axis]:
        n = n * d
    h = 1
    for d in x_shape[begin_norm_axis:]:
        h *= int(d)
    return n, h


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _draw_keep(seed_ref, rows, h, thr):
    _prng.seed_block_prng(seed_ref)
    return _prng.draw_keep_bits((rows, h), thr)


def _fwd_kernel(seed_ref, x_ref, y_ref, g_ref, b_ref,
                out_ref, r_ref, mean_ref, var_ref, *, thr, eps, rows, h):
    xv = x_ref[...].astype(jnp.float32)
    yv = y_ref[...].astype(jnp.float32)
    if thr is not None:
        keep = _draw_keep(seed_ref, rows, h, thr)
        yv = jnp.where(keep, yv * (1.0 / _realized_q(thr)), 0.0)
    r = xv + yv
    # r is the ONLY tensor the backward reads (plus dz): saving it instead
    # of x and y halves the residual set — the x,y-residual variant
    # measured 96 MB/epilogue live vs the composed emission's ~73, pushing
    # XLA into rematerializing the f32 gelu intermediates (+47 ms/step)
    r_ref[...] = r.astype(r_ref.dtype)
    mean = jnp.mean(r, axis=1, keepdims=True)
    c = r - mean
    var = jnp.mean(c * c, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    z = c * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    out_ref[...] = z.astype(out_ref.dtype)
    mean_ref[...] = mean
    var_ref[...] = var


def _bwd_kernel(seed_ref, r_ref, g_ref, mean_ref, var_ref, dz_ref,
                dx_ref, dy_ref, dg_ref, db_ref, *, thr, eps, rows, h):
    r = r_ref[...].astype(jnp.float32)
    if thr is not None:
        keep = _draw_keep(seed_ref, rows, h, thr)
        inv_q = 1.0 / _realized_q(thr)
    rstd = jax.lax.rsqrt(var_ref[...] + eps)
    xhat = (r - mean_ref[...]) * rstd
    dz = dz_ref[...].astype(jnp.float32)
    # per-block dgamma/dbeta partials: blocks must be >=8 sublanes, so the
    # row sum lands in row 0 of an 8-row slab (rows 1-7 zero)
    row0 = jax.lax.broadcasted_iota(jnp.int32, (8, h), 0) == 0
    dg_ref[...] = jnp.where(row0, jnp.sum(dz * xhat, axis=0, keepdims=True),
                            0.0)
    db_ref[...] = jnp.where(row0, jnp.sum(dz, axis=0, keepdims=True), 0.0)
    a = dz * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(a, axis=1, keepdims=True)
    m2 = jnp.mean(a * xhat, axis=1, keepdims=True)
    dr = rstd * (a - m1 - xhat * m2)
    dx_ref[...] = dr.astype(dx_ref.dtype)
    if thr is not None:
        dr = jnp.where(keep, dr * inv_q, 0.0)
    dy_ref[...] = dr.astype(dy_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _fwd_pallas(x2, y2, gamma, beta, seed, thr, eps, rows):
    n, h = x2.shape
    grid = (n // rows,)
    row_spec = pl.BlockSpec((rows, h), lambda i, *_: (i, 0))
    vec_spec = pl.BlockSpec((1, h), lambda i, *_: (0, 0))
    stat_spec = pl.BlockSpec((rows, 1), lambda i, *_: (i, 0))
    kernel = functools.partial(_fwd_kernel, thr=thr, eps=eps, rows=rows, h=h)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[row_spec, row_spec, vec_spec, vec_spec],
            out_specs=[row_spec, row_spec, stat_spec, stat_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((n, h), x2.dtype),  # r (backward residual)
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(seed, x2, y2, gamma.reshape(1, h), beta.reshape(1, h))


def _bwd_pallas(r2, gamma, seed, mean, var, dz2, thr, eps, rows):
    n, h = r2.shape
    grid = (n // rows,)
    row_spec = pl.BlockSpec((rows, h), lambda i, *_: (i, 0))
    vec_spec = pl.BlockSpec((1, h), lambda i, *_: (0, 0))
    stat_spec = pl.BlockSpec((rows, 1), lambda i, *_: (i, 0))
    part_spec = pl.BlockSpec((8, h), lambda i, *_: (i, 0))
    kernel = functools.partial(_bwd_kernel, thr=thr, eps=eps, rows=rows, h=h)
    dx, dy, dgp, dbp = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[row_spec, vec_spec, stat_spec, stat_spec, row_spec],
            out_specs=[row_spec, row_spec, part_spec, part_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, h), r2.dtype),
            jax.ShapeDtypeStruct((n, h), r2.dtype),
            jax.ShapeDtypeStruct((n // rows * 8, h), jnp.float32),
            jax.ShapeDtypeStruct((n // rows * 8, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(seed, r2, gamma.reshape(1, h), mean, var, dz2)
    return dx, dy, jnp.sum(dgp, axis=0), jnp.sum(dbp, axis=0)


# ---------------------------------------------------------------------------
# jnp fallback (CPU test mesh / un-tileable shapes) — same seed contract
# ---------------------------------------------------------------------------


def _fallback_keep(seed, thr, shape):
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(0), seed[0].astype(jnp.uint32)),
        seed[1].astype(jnp.uint32))
    bits = jax.random.bits(key, shape, jnp.uint32)
    return bits < jnp.uint32(thr)


def _fwd_fallback(x2, y2, gamma, beta, seed, thr, eps):
    yv = y2.astype(jnp.float32)
    if thr is not None:
        keep = _fallback_keep(seed, thr, y2.shape)
        yv = jnp.where(keep, yv * (1.0 / _realized_q(thr)), 0.0)
    r = x2.astype(jnp.float32) + yv
    mean = jnp.mean(r, axis=1, keepdims=True)
    c = r - mean
    var = jnp.mean(c * c, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    z = c * rstd * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return z.astype(x2.dtype), r.astype(x2.dtype), mean, var


def _bwd_fallback(r2, gamma, seed, mean, var, dz2, thr, eps):
    r = r2.astype(jnp.float32)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (r - mean) * rstd
    dz = dz2.astype(jnp.float32)
    dg = jnp.sum(dz * xhat, axis=0)
    db = jnp.sum(dz, axis=0)
    a = dz * gamma.astype(jnp.float32)
    m1 = jnp.mean(a, axis=1, keepdims=True)
    m2 = jnp.mean(a * xhat, axis=1, keepdims=True)
    dr = rstd * (a - m1 - xhat * m2)
    dx = dr.astype(r2.dtype)
    if thr is not None:
        keep = _fallback_keep(seed, thr, r2.shape)
        dr = jnp.where(keep, dr * (1.0 / _realized_q(thr)), 0.0)
    return dx, dr.astype(r2.dtype), dg, db


# ---------------------------------------------------------------------------
# public custom-vjp entry point
# ---------------------------------------------------------------------------


def fused_ln_checks(n, h, itemsize):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of its ``[n, h]`` operands of ``itemsize`` bytes an element."""
    return [
        ("backend", jax.default_backend() == "tpu"),
        ("symbolic_shape", isinstance(n, int)),
        ("lanes", h % _LANES == 0),
        ("block_rows", isinstance(n, int) and h % _LANES == 0
         and _pick_rows(n, h, itemsize) is not None),
    ]


def _use_pallas(x2, y2):
    # The entry points cast y to x.dtype BEFORE this choice, so the fwd
    # (x2, y2) and bwd (r2, r2 — r stored in x.dtype) calls see the SAME
    # itemsize and pick the SAME rows: a fwd/bwd blocking mismatch would
    # silently desync the re-drawn dropout mask.
    if x2.dtype != y2.dtype:
        raise AssertionError(
            "fused_ln internal: operands must share a dtype by this point")
    from . import adoption

    n, h = x2.shape
    itemsize = x2.dtype.itemsize
    use, _ = adoption.decide("fused_ln", fused_ln_checks(n, h, itemsize))
    return _pick_rows(n, h, itemsize) if use else None


def _fwd_any(x2, y2, gamma, beta, seed, thr, eps):
    rows = _use_pallas(x2, y2)
    if rows is not None:
        return _fwd_pallas(x2, y2, gamma, beta, seed, thr, eps, rows)
    return _fwd_fallback(x2, y2, gamma, beta, seed, thr, eps)


def _bwd_any(r2, gamma, seed, mean, var, dz, thr, eps):
    rows = _use_pallas(r2, r2)
    if rows is not None:
        return _bwd_pallas(r2, gamma, seed, mean, var, dz, thr, eps, rows)
    return _bwd_fallback(r2, gamma, seed, mean, var, dz, thr, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused(x2, y2, gamma, beta, seed, thr, eps):
    z, _, mean, var = _fwd_any(x2, y2, gamma, beta, seed, thr, eps)
    return z, mean, var


def _fused_fwd(x2, y2, gamma, beta, seed, thr, eps):
    # NB: only r (the post-dropout residual sum) is saved — not x or y.
    # dx == dr and dy == mask*dr/q need neither, and halving the residual
    # set is what keeps XLA from rematting neighbors under memory pressure
    z, r, mean, var = _fwd_any(x2, y2, gamma, beta, seed, thr, eps)
    return (z, mean, var), (r, gamma, seed, mean, var)


def _fused_bwd(thr, eps, res, cts):
    # stats are auxiliary (stop-gradded by the wrapper): only dz flows
    dz, _, _ = cts
    r, gamma, seed, mean, var = res
    dx, dy, dg, db = _bwd_any(r, gamma, seed, mean, var, dz, thr, eps)
    return dx, dy, dg.astype(gamma.dtype), db.astype(gamma.dtype), None


_fused.defvjp(_fused_fwd, _fused_bwd)


# odd, so rank -> rank * _RANK_MIX is one-to-one on uint32: with the block
# index folded into word 0 (prng.seed_block_prng) no two (shard, block)
# pairs share a seed
_RANK_MIX = 0x9E3779B1


def _per_shard(body, rows_over, split_in, split_out):
    """`body(*args, axis=axis)` as a shard_map over `rows_over` = (mesh,
    axis).  `split_in` / `split_out`: one flag an argument / a result,
    split over the axis on its leading dimension (rows) or replicated.
    Inside, the funnel decides on the shard's shape (adoption.per_shard)."""
    from jax.sharding import PartitionSpec as P

    from . import adoption

    mesh, axis = rows_over

    def local(*args):
        with adoption.per_shard():
            return body(*args, axis=axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(P(axis) if rows else P() for rows in split_in),
        out_specs=tuple(P(axis) if rows else P() for rows in split_out),
        check_vma=False)


def _shard_seed(seed, axis, thr):
    """The shard's own seed words: its index along `axis` mixed into word
    1.  Forward and backward both come through here, so the mask replays
    per shard."""
    if axis is None or thr is None:
        return seed
    rank = jax.lax.axis_index(axis).astype(jnp.uint32)
    return seed.at[1].set(seed[1] ^ (rank * jnp.uint32(_RANK_MIX)))


def fused_ln_fwd(x, y, gamma, beta, dropout_prob, seed, epsilon,
                 begin_norm_axis, rows_over=None):
    """Op-mode forward (explicit-grad-op integration, cf. the dropout op's
    Mask contract): returns (z, r, mean [N], variance [N]) with NO vjp
    tracking — the program-level grad op calls fused_ln_bwd with the saved
    r/seed/stats instead.  r is the post-dropout residual sum, the only
    large backward residual.  ``rows_over=(mesh, axis)``: per shard of the
    leading dimension (module docstring)."""
    body = functools.partial(_fwd_rows, dropout_prob=dropout_prob,
                             epsilon=epsilon,
                             begin_norm_axis=begin_norm_axis)
    if rows_over is not None:
        body = _per_shard(body, rows_over, (1, 1, 0, 0, 0), (1, 1, 1, 1))
    return body(x, y, gamma, beta, jnp.asarray(seed))


def _fwd_rows(x, y, gamma, beta, seed, *, dropout_prob, epsilon,
              begin_norm_axis, axis=None):
    n, h = ln_stat_shapes(x.shape, begin_norm_axis)
    thr = _keep_threshold(dropout_prob)
    seed = _shard_seed(seed.reshape(2).astype(jnp.uint32), axis, thr)
    # the epilogue computes in x's carry dtype: casting y up front keeps
    # the fwd/bwd block choice a function of ONE dtype (mask replay)
    z, r, mean, var = _fwd_any(x.reshape(n, h),
                               y.astype(x.dtype).reshape(n, h),
                               gamma.reshape(h), beta.reshape(h), seed, thr,
                               float(epsilon))
    return (z.reshape(x.shape), r.reshape(x.shape), mean.reshape(n),
            var.reshape(n))


def fused_ln_bwd(r, gamma, seed, mean, var, dz, dropout_prob, epsilon,
                 begin_norm_axis, rows_over=None):
    """Op-mode backward: (dx, dy, dgamma, dbeta) from the saved residual
    sum r; the dropout mask for dy is re-drawn from the SAME seed and
    grid blocking as the forward.  ``rows_over`` as the forward's: dgamma
    and dbeta are then each shard's sums, added over the axis."""
    body = functools.partial(_bwd_rows, dropout_prob=dropout_prob,
                             epsilon=epsilon,
                             begin_norm_axis=begin_norm_axis)
    if rows_over is not None:
        body = _per_shard(body, rows_over, (1, 1, 1, 1, 0, 0), (1, 1, 0, 0))
    return body(r, dz, mean, var, gamma, jnp.asarray(seed))


def _bwd_rows(r, dz, mean, var, gamma, seed, *, dropout_prob, epsilon,
              begin_norm_axis, axis=None):
    n, h = ln_stat_shapes(r.shape, begin_norm_axis)
    thr = _keep_threshold(dropout_prob)
    seed = _shard_seed(seed.reshape(2).astype(jnp.uint32), axis, thr)
    dx, dy, dg, db = _bwd_any(
        r.reshape(n, h), gamma.reshape(h), seed,
        mean.reshape(n, 1).astype(jnp.float32),
        var.reshape(n, 1).astype(jnp.float32), dz.reshape(n, h), thr,
        float(epsilon))
    if axis is not None:
        dg, db = jax.lax.psum((dg, db), axis)
    return (dx.reshape(r.shape), dy.reshape(r.shape),
            dg.astype(gamma.dtype), db.astype(gamma.dtype))


def fused_dropout_add_ln(x, y, gamma, beta, dropout_prob, seed, epsilon=1e-5,
                         begin_norm_axis=None, return_stats=False):
    """z = LayerNorm(x + dropout_upscale(y)) in one fused pass.

    x, y: same shape, normalized over the trailing dims starting at
    ``begin_norm_axis`` (default: the last dim).  gamma/beta: [H] scale
    and shift.  seed: [2] uint32/int32 array — the dropout mask is a pure
    function of it (the backward re-draws the identical mask; pass the
    same seed to reproduce a step).  dropout_prob <= 0 disables dropout
    (exact LN(x+y)); the training upscale divides by the REALIZED keep
    probability round(q*2^32)/2^32.

    Returns z, or (z, mean, variance) with f32 stats of shape
    [prod(leading)] when return_stats=True.
    """
    if begin_norm_axis is None:
        begin_norm_axis = x.ndim - 1
    n, h = ln_stat_shapes(x.shape, begin_norm_axis)
    thr = _keep_threshold(dropout_prob)
    x2 = x.reshape(n, h)
    # compute in x's carry dtype (see fused_ln_fwd: keeps the fwd/bwd
    # block choice single-dtype so the dropout mask replays)
    y2 = y.astype(x.dtype).reshape(n, h)
    seed = jnp.asarray(seed).reshape(2).astype(jnp.uint32)
    gamma = gamma.reshape(h)
    beta = beta.reshape(h)
    z, mean, var = _fused(x2, y2, gamma, beta, seed, thr, float(epsilon))
    if return_stats:
        return (z.reshape(x.shape),
                jax.lax.stop_gradient(mean).reshape(n),
                jax.lax.stop_gradient(var).reshape(n))
    return z.reshape(x.shape)
