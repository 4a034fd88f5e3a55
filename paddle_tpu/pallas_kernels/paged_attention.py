"""Paged-attention gather kernel for the decode serving path.

The decode step (serving/decode_model.py) attends one query token per
sequence against that sequence's KV history, which lives scattered across
fixed-size cache blocks (serving/kv_cache.py) named by a per-sequence
block table.  The generic lowering gathers the blocks into a contiguous
``[B, S, H, D]`` intermediate (``jnp.take`` over the block axis) and runs
masked attention over it — B*S*H*D of HBM writes + reads that exist only
to be reduced.  This kernel uses the scalar-prefetched block table to
steer the K/V block DMA directly (the embedding-bag idiom): grid step
(b, j) fetches ONE ``(block_size, H, D)`` K block and V block chosen by
``block_tables[b, j]`` and folds them into an online-softmax accumulator
in VMEM, so the gathered intermediate never materializes.

Positions at or beyond ``context_lens[b]`` are masked with a large
negative before the softmax (finite, so a fully-masked idle lane yields a
uniform distribution instead of NaN — the engine discards idle-lane
output anyway).  ``masked_attention`` is the shared jnp core: the paged
reference gathers blocks and calls it, and the UNPAGED reference loop in
decode_model.py calls the very same function on contiguous K/V — that
sharing is what makes paged-vs-unpaged decode bitwise-comparable on the
CPU tier.

Adoption: FLAGS_use_pallas_paged_attention + ``paged_attention_checks``
eligibility + a >= 1.1x tools/probes row, all through adoption.decide()
(interpret mode waives backend + probe for the CPU parity tests).  The
kernel reads pools with the heads split, ``[num_blocks, block_size, H,
D]``; the serving cache keeps them folded, ``[num_blocks, block_size,
H * D]`` (serving/kv_cache.py says why), which the ``rank`` check
declines: the decode step takes the jnp path until the kernel's block
specs read folded rows (splitting the heads on the pool would copy it).
"""

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_checks", "masked_attention", "gather_blocks"]

_MASK = -1e30  # finite: a fully-masked lane softmaxes to uniform, not NaN


def masked_attention(q, k, v, context_lens):
    """Single-token attention over a contiguous history: q [B, H, D],
    k/v [B, S, H, D], context_lens [B] -> [B, H, D].  Positions >= the
    context length are masked.  Shared by the paged gather path AND the
    unpaged reference loop so the two stay bitwise-comparable.

    Both contractions run over the folded minor dimension H * D, against
    a block-diagonal query: row h of ``qx`` holds head h's query in its
    own D columns and zeros elsewhere, so ``qx @ k`` is head h's scores
    and the diagonal blocks of ``p @ v`` are its output.  The history is
    then read as it lies in the pool ([S, H * D] rows): splitting H * D
    into heads on it costs a relayout of everything gathered wherever D
    is under the 128 lanes (D = 64: a second, padded copy of the history
    per layer), while the extra H - 1 zero blocks are matmul work on a
    unit that is otherwise idle.  ``HIGHEST`` keeps the products float32,
    as the elementwise form they replace had them.

    The history's dtype is the matmuls' input dtype: against a float32
    pool nothing is cast; against a bf16 pool (a bf16 model's) the query
    and the probabilities are rounded to bf16, the history is read as it
    is stored, and both contractions accumulate in float32.  Scores,
    mask and softmax are float32 either way."""
    b, h, d = q.shape
    s = k.shape[1]
    dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    eye = jnp.eye(h, dtype=q.dtype)[None, :, :, None]
    qx = (q[:, :, None, :] * eye).reshape(b, h, h * d).astype(k.dtype)
    sc = dot("bhc,bsc->bhs", qx, k.reshape(b, s, h * d)) \
        * (1.0 / math.sqrt(d))
    pos = jnp.arange(s, dtype=jnp.int32)[None, None, :]
    sc = jnp.where(pos < context_lens[:, None, None].astype(jnp.int32),
                   sc, _MASK)
    p = jax.nn.softmax(sc, axis=-1)
    out = dot("bhs,bsc->bhc", p.astype(v.dtype), v.reshape(b, s, h * d))
    return (out.reshape(b, h, h, d) * eye).sum(axis=2)


def gather_blocks(cache, block_tables):
    """A pool ``[num_blocks, block_size, ...]`` read through the tables
    ``[B, MAXB]`` as contiguous history ``[B, MAXB * block_size, ...]``.
    Entries < 0 are unused slots: clamped to block 0 here and masked by
    ``context_lens`` in the attention.  Every index is then in range,
    which ``mode="clip"`` lets the gather rely on: the default mode wraps
    it in a select over everything gathered."""
    bb, maxb = block_tables.shape
    got = jnp.take(cache, jnp.maximum(block_tables, 0), axis=0, mode="clip")
    return got.reshape((bb, maxb * cache.shape[1]) + cache.shape[2:])


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              context_lens):
    """jnp fallback: gather the table's blocks into contiguous K/V, then
    masked_attention.  q [B, H, D]; k_cache/v_cache
    [num_blocks, block_size, H, D], or the serving pool's
    [num_blocks, block_size, H * D]: the heads are split after the
    gather, never on the pool."""
    bb, h, d = q.shape
    with jax.named_scope("kv_gather"):
        k, v = (gather_blocks(c, block_tables).reshape(bb, -1, h, d)
                for c in (k_cache, v_cache))
    return masked_attention(q, k, v, context_lens)


def paged_attention_checks(q_shape, kv_shape, dtype, block_size):
    """Ordered (reason, ok) pairs for adoption.decide()."""
    dims = tuple(q_shape) + tuple(kv_shape)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", len(q_shape) == 3 and len(kv_shape) == 4),
        ("dtype", jnp.dtype(dtype) == jnp.dtype(jnp.float32)),
        ("head_dim", static and len(q_shape) == 3
         and q_shape[2] % 128 == 0),
        ("block_size", isinstance(block_size, int) and block_size > 0
         and block_size % 8 == 0),
        ("empty", static and all(x > 0 for x in dims)),
    ]


def _paged_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    bs = k_ref.shape[1]
    scale = 1.0 / math.sqrt(q_ref.shape[-1])
    q = q_ref[0].astype(jnp.float32)                    # [H, D]
    k = k_ref[0].astype(jnp.float32)                    # [bs, H, D]
    s = jnp.einsum("hd,shd->hs", q, k) * scale          # [H, bs]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(pos < cl_ref[b], s, _MASK)
    # online softmax across the block-table axis (j is sequential)
    m_prev = m_ref[...]                                 # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                              # [H, bs]
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
        "hs,shd->hd", p, v_ref[0].astype(jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_pallas(q, k_cache, v_cache, block_tables, context_lens):
    bb, h, d = q.shape
    bs = k_cache.shape[1]
    maxb = block_tables.shape[1]
    # the prefetched table steers the K/V block DMA; unused (-1) slots
    # clamp to block 0 and are masked off by context_lens in the kernel
    kv_spec = pl.BlockSpec(
        (1, bs, h, d),
        lambda b, j, bt_ref, cl_ref: (jnp.maximum(bt_ref[b, j], 0), 0, 0, 0))
    q_spec = pl.BlockSpec((1, h, d), lambda b, j, bt_ref, cl_ref: (b, 0, 0))
    o_spec = pl.BlockSpec((1, h, d), lambda b, j, bt_ref, cl_ref: (b, 0, 0))
    call = functools.partial(
        pl.pallas_call,
        _paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bb, maxb),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bb, h, d), q.dtype),
        interpret=adoption.interpret(),
    )
    if not adoption.interpret():
        # j accumulates the online softmax, so it must run sequentially
        call = functools.partial(
            call, compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")))
    return call()(block_tables.astype(jnp.int32),
                  context_lens.astype(jnp.int32), q, k_cache, v_cache)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens):
    """Funnel-gated paged attention: the Pallas gather kernel where
    adoption.decide() allows it, the jnp gather reference otherwise."""
    use, _reason = adoption.decide(
        "paged_attention",
        flag="FLAGS_use_pallas_paged_attention",
        checks=paged_attention_checks(q.shape, k_cache.shape, q.dtype,
                                      int(k_cache.shape[1])))
    if use:
        return _paged_pallas(q, k_cache, v_cache, block_tables,
                             context_lens)
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens)
