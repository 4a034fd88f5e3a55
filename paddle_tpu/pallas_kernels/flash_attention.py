"""Blockwise (flash) attention as Pallas TPU kernels.

TPU-native replacement for the reference's fused attention
(paddle/fluid/operators/fused/multihead_matmul_op.cu — inference-only,
single-device) with training support: an online-softmax forward that never
materializes the [Sq, Sk] score matrix in HBM, plus recompute-based backward
kernels for dQ and dK/dV (FlashAttention-style).  Everything is tiled to the
MXU (128-lane blocks), accumulated in f32 VMEM scratch, and differentiable
via jax.custom_vjp.

Layout: q, k, v are [batch, heads, seq, head_dim]; optional additive bias
(attention mask) is [batch, 1 or heads, Sq, Sk].  Outside TPU (or for shapes
the tiling cannot cover) a jnp reference path with identical semantics is
used, so tests run on the CPU mesh unchanged.
"""

import functools

import jax
import jax.numpy as jnp

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_LANES = 128


def _ref_attention(q, k, v, bias, causal, sm_scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        kj = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(kj <= qi, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q, block_k):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: k blocks strictly above the diagonal contribute nothing
    needed = True
    if causal:
        needed = j * block_k <= i * block_q + block_q - 1

    @pl.when(needed)
    def _body():
        qb = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # NB: rows masked everywhere (finite -1e30 bias) degenerate to a
        # uniform softmax (row max contributes p=1, so l >= 1) — output is
        # mean(V), matching the jnp fallback's softmax-over--inf behavior;
        # the l==0 guard is pure belt-and-braces against future NaN masks
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0] = lse


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention recompute scheme)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = True
    if causal:
        needed = j * block_k <= i * block_q + block_q - 1

    @pl.when(needed)
    def _body():
        qb = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        dob = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k):
    j = pl.program_id(2)  # k block (outer)
    i = pl.program_id(3)  # q block (inner, accumulated)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        needed = j * block_k <= i * block_q + block_q - 1

    @pl.when(needed)
    def _body():
        qb = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        dob = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * sm_scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _causal_jmax(i, block_q, block_k):
    """Last k-block index that intersects q-block i's causal band."""
    return (i * block_q + block_q - 1) // block_k


def _causal_imin(j, block_q, block_k):
    """First q-block index that intersects k-block j's causal band."""
    return (j * block_k) // block_q


def _bias_spec(bias, block_q, block_k, causal):
    if bias is None:
        return None
    bh = bias.shape[1]

    def idx(b, h, i, j):
        if causal:
            j = jnp.minimum(j, _causal_jmax(i, block_q, block_k))
        return (b, h if bh > 1 else 0, i, j)

    return pl.BlockSpec((1, 1, block_q, block_k), idx)


def _bias_spec_ji(bias, block_q, block_k, causal):
    if bias is None:
        return None
    bh = bias.shape[1]

    def idx(b, h, j, i):
        if causal:
            i = jnp.maximum(i, _causal_imin(j, block_q, block_k))
        return (b, h if bh > 1 else 0, i, j)

    return pl.BlockSpec((1, 1, block_q, block_k), idx)


def _pick_block(seq, preferred=512):
    for cand in (preferred, 512, 256, 128):
        if cand <= seq and seq % cand == 0:
            return cand
    return None


def _fwd_pallas(q, k, v, bias, causal, sm_scale, block_q, block_k,
                interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k
    grid = (B, H, nq, nk)

    def kv_idx(b, h, i, j):
        # clamping the block index to the causal band makes Pallas's
        # pipeline reuse the previous buffer instead of fetching dead
        # above-diagonal K/V blocks
        if causal:
            j = jnp.minimum(j, _causal_jmax(i, block_q, block_k))
        return (b, h, j, 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, D), kv_idx),
        pl.BlockSpec((1, 1, block_k, D), kv_idx),
    ]
    args = [q, k, v]
    bspec = _bias_spec(bias, block_q, block_k, causal)
    if bias is not None:
        in_specs.append(bspec)
        args.append(bias)

    if bias is not None:
        def kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr):
            _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                        m_scr, l_scr, acc_scr, sm_scale=sm_scale,
                        causal=causal, block_q=block_q, block_k=block_k)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr):
            _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                        m_scr, l_scr, acc_scr, sm_scale=sm_scale,
                        causal=causal, block_q=block_q, block_k=block_k)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return out, lse


def _bwd_pallas(q, k, v, bias, causal, sm_scale, block_q, block_k,
                interpret, out, lse, do):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nq, nk = Sq // block_q, Sk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,Sq,1]

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k)

    # dq: grid (B,H,nq,nk), k-inner
    def kv_idx(b, h, i, j):
        if causal:
            j = jnp.minimum(j, _causal_jmax(i, block_q, block_k))
        return (b, h, j, 0)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D), kv_idx)
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))
    in_specs = [q_spec, k_spec, k_spec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias, block_q, block_k, causal))
        args.append(bias)
    in_specs += [q_spec, row_spec, row_spec]
    args += [do, lse, delta]

    if bias is not None:
        def dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_scr):
            _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                           delta_ref, dq_ref, dq_scr, **common)
    else:
        def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_scr):
            _bwd_dq_kernel(q_ref, k_ref, v_ref, None, do_ref, lse_ref,
                           delta_ref, dq_ref, dq_scr, **common)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)

    # dk/dv: grid (B,H,nk,nq), q-inner
    def qrow_i(j, i):
        if causal:
            i = jnp.maximum(i, _causal_imin(j, block_q, block_k))
        return i

    q_spec_ji = pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, j, i: (b, h, qrow_i(j, i), 0))
    k_spec_ji = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, j, i: (b, h, j, 0))
    row_spec_ji = pl.BlockSpec((1, 1, block_q, 1),
                               lambda b, h, j, i: (b, h, qrow_i(j, i), 0))
    in_specs = [q_spec_ji, k_spec_ji, k_spec_ji]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec_ji(bias, block_q, block_k, causal))
        args.append(bias)
    in_specs += [q_spec_ji, row_spec_ji, row_spec_ji]
    args += [do, lse, delta]

    if bias is not None:
        def dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
            _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                            delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                            **common)
    else:
        def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
            _bwd_dkv_kernel(q_ref, k_ref, v_ref, None, do_ref, lse_ref,
                            delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                            **common)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, nk, nq),
        in_specs=in_specs,
        out_specs=[k_spec_ji, k_spec_ji],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _blocks(q_shape, k_shape):
    Sq, Sk = q_shape[2], k_shape[2]
    return (_pick_block(Sq, preferred=1024 if Sq >= 4096 else 512),
            _pick_block(Sk, preferred=1024))


def flash_attention_checks(q_shape, k_shape, interpret):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of ``[B, H, Sq, D]`` queries and ``[B, H, Sk, D]`` keys."""
    bq, bk = _blocks(q_shape, k_shape)
    return [
        # measured on v5e: below ~1k keys the XLA-fused composition is
        # faster (kernel launch/grid overhead dominates); above it the
        # blockwise kernel wins and, more importantly, never
        # materializes the [Sq, Sk] score matrix
        ("short_keys", k_shape[2] >= 1024),
        ("blocks", bq is not None and bk is not None),
        # off the TPU only a caller that asks for the interpreter gets
        # the kernel: the jnp reference is faster than interpret
        ("backend", interpret is not None
         or jax.default_backend() == "tpu"),
    ]


def _can_use_pallas(q, k, interpret):
    from . import adoption

    use, _ = adoption.decide(
        "flash_attention",
        flash_attention_checks(q.shape, k.shape, interpret))
    if not use:
        return False, None, None
    return True, _blocks(q.shape, k.shape), bool(interpret)


# bias=None routes through the same vjp (None is a valid empty pytree for a
# differentiable argument; bwd returns None for its cotangent)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_b(q, k, v, bias, causal, sm_scale, blocks, interpret):
    out, _ = _fwd_pallas(q, k, v, bias, causal, sm_scale, blocks[0],
                         blocks[1], interpret)
    return out


def _flash_b_fwd(q, k, v, bias, causal, sm_scale, blocks, interpret):
    out, lse = _fwd_pallas(q, k, v, bias, causal, sm_scale, blocks[0],
                           blocks[1], interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_b_bwd(causal, sm_scale, blocks, interpret, res, do):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _bwd_pallas(q, k, v, bias, causal, sm_scale, blocks[0],
                             blocks[1], interpret, out, lse, do)
    return dq, dk, dv, None


_flash_b.defvjp(_flash_b_fwd, _flash_b_bwd)


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    interpret=None):
    """Fused multi-head attention: softmax(q k^T * scale + bias) v.

    q,k,v: [B, H, S, D]; bias: [B, 1|H, Sq, Sk] additive mask or None.
    Uses the Pallas TPU kernel when on TPU with tileable shapes; falls back
    to an identical-semantics jnp composition otherwise (so the same model
    code runs on the CPU test mesh).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    ok, blocks, interp = _can_use_pallas(q, k, interpret)
    if not ok:
        return _ref_attention(q, k, v, bias, causal, sm_scale)
    return _flash_b(q, k, v, bias, causal, sm_scale, blocks, interp)
