"""Block-sparse embedding-bag gather/sum Pallas kernel.

The recommender path (`distributed/sparse_table.py`) feeds a dense row
buffer ``rows [U, D]`` (the unique rows this step touches, already pulled
from the host-resident sparse table) plus per-sample bags of local ids
``ids [B, K]`` (-1 pads ragged bags).  The generic lowering is
``jnp.take`` into a [B, K, D] intermediate followed by a masked sum —
B*K*D of HBM writes + reads that exist only to be reduced.  This kernel
uses scalar-prefetched ids to steer the input DMA directly: grid step
(b, k) fetches ONE (1, D) row chosen by ``ids[b, k]`` and accumulates it
into the (1, D) output bag in VMEM, so the [B, K, D] intermediate never
materializes.  Invalid (-1) ids are clamped to row 0 for the DMA and
masked to zero in the accumulate.

The backward (row gradients = scatter-add of the bag cotangent over
valid ids) routes through ``jax.vjp`` of the jnp fallback — the ISSUE's
"grads via the fallback VJP" contract; ids are integer inputs and get a
float0 cotangent.

Adoption: FLAGS_use_pallas_embedding_bag + ``bag_checks`` eligibility +
a >= 1.1x tools/probes row, all through adoption.decide().
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["embedding_bag", "embedding_bag_reference", "bag_checks"]


def bag_checks(rows_shape, ids_shape, dtype):
    """Ordered (reason, ok) pairs for adoption.decide()."""
    static = all(isinstance(d, int) and d >= 0
                 for d in tuple(rows_shape) + tuple(ids_shape))
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", len(rows_shape) == 2 and len(ids_shape) == 2),
        ("dtype", jnp.dtype(dtype) == jnp.dtype(jnp.float32)),
        ("row_width", static and len(rows_shape) == 2
         and rows_shape[1] % 128 == 0),
        ("empty", static and all(d > 0 for d in tuple(rows_shape)
                                 + tuple(ids_shape))),
    ]


def embedding_bag_reference(rows, ids):
    """jnp fallback: masked take + sum.  ids < 0 are padding."""
    idx = jnp.maximum(ids, 0)
    g = jnp.take(rows, idx, axis=0)              # [B, K, D]
    mask = (ids >= 0)[..., None]
    return jnp.sum(jnp.where(mask, g, 0.0), axis=1).astype(rows.dtype)


def _bag_kernel(ids_ref, row_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = pl.program_id(0)
    valid = ids_ref[b, k] >= 0
    row = row_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.where(valid, row, 0.0).astype(out_ref.dtype)


def _bag_pallas(rows, ids):
    u, d = rows.shape
    bb, k = ids.shape
    # the prefetched ids steer the row DMA; -1 pads clamp to row 0 (masked
    # to zero inside the kernel before the accumulate)
    row_spec = pl.BlockSpec(
        (1, d), lambda b, j, ids_ref: (jnp.maximum(ids_ref[b, j], 0), 0))
    out_spec = pl.BlockSpec((1, d), lambda b, j, ids_ref: (b, 0))
    call = functools.partial(
        pl.pallas_call,
        _bag_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bb, k),
            in_specs=[row_spec],
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((bb, d), rows.dtype),
        interpret=adoption.interpret(),
    )
    if not adoption.interpret():
        # k must iterate sequentially (the out block accumulates across it)
        call = functools.partial(
            call, compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")))
    return call()(ids.astype(jnp.int32), rows)


@jax.custom_vjp
def embedding_bag(rows, ids):
    """Pallas embedding-bag: out[b] = sum_k rows[ids[b, k]] over ids >= 0.
    Backward differentiates the jnp fallback (scatter-add into rows)."""
    return _bag_pallas(rows, ids)


def _bag_fwd(rows, ids):
    return _bag_pallas(rows, ids), (rows, ids)


def _bag_bwd(res, dout):
    rows, ids = res
    _, vjp = jax.vjp(embedding_bag_reference, rows, ids)
    drows, _ = vjp(dout.astype(rows.dtype))
    import numpy as np

    return drows, np.zeros(ids.shape, dtype=jax.dtypes.float0)


embedding_bag.defvjp(_bag_fwd, _bag_bwd)
