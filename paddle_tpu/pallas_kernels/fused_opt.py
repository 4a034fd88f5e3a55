"""Fused optimizer-step Pallas kernel (Adam / momentum) over the flat
fused-group buffer, with the bf16 param-carry cast folded in.

The PR-2 `fuse_optimizer` pass already coalesces per-parameter updates
into one ``fused_adam``/``fused_momentum`` op, so the XLA update is a
single elementwise pass — but under the bf16 param carry
(FLAGS_layout_match_params) the step still streams the parameter set
through HBM three times: moment recurrence + AXPY reads, the f32 master
write, and the separate f32->bf16 carry cast.  This kernel does all of it
in ONE pass per block: each 8x128 tile of the flat group is read once,
the new moments / master / bf16 carry copy are written from VMEM.

**Bitwise contract** (the whole point — enforced by
tests/test_pallas_blocks.py over 3 steps): every elementwise expression
mirrors the unfused ``fused_adam`` lowering verbatim, in the same dtype
and the same operation order (f32 elementwise add/mul/sqrt/div are IEEE
deterministic, so identical expressions are identical bits regardless of
blocking).  Per-member bias correction is preserved: each member's scalar
``lr_t = lr * sqrt(1-b2pow)/(1-b1pow)`` is computed OUTSIDE the kernel
with the exact unfused expression, members are padded to whole 1024-
element blocks so no block straddles two members, and the kernel reads
its block's lr_t from a per-block scalar array.  The bf16 copy is
``p_new.astype(bfloat16)`` — bitwise-identical to the carry cast
build_block_fn would otherwise emit, so correctness never depends on the
kernel engaging; only HBM traffic does.

Adoption is probe-gated like every family (adoption.py):
FLAGS_use_pallas_fused_opt + eligibility + a >=1.1x tools/probes row.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import adoption

__all__ = ["fused_adam_step", "fused_momentum_step", "fused_opt_checks"]

# one grid step = one (8, 128) f32 tile of the flat group
_BLOCK = 8 * 128


def fused_opt_checks(params, grads, moments=()):
    """Ordered (reason, ok) pairs for adoption.decide()."""
    f32 = jnp.dtype(jnp.float32)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("empty_group", len(params) > 0),
        ("dtype", all(p.dtype == f32 for p in params)
         and all(m.dtype == f32 for ms in moments for m in ms)),
    ]


def _pad_flat(tensors):
    """Concat of member flats, each zero-padded to whole blocks.  Returns
    (flat_2d [rows, 128], sizes, block_counts, offsets-in-padded-space)."""
    sizes = [int(np.prod(t.shape)) for t in tensors]
    counts = [max((n + _BLOCK - 1) // _BLOCK, 1) for n in sizes]
    segs, offs, off = [], [], 0
    for t, n, c in zip(tensors, sizes, counts):
        flat = t.reshape(-1)
        pad = c * _BLOCK - n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), t.dtype)])
        segs.append(flat)
        offs.append(off)
        off += c * _BLOCK
    return (jnp.concatenate(segs).reshape(-1, 128), sizes, counts, offs)


def _unpad(flat2d, sizes, counts, offs, shapes, dtype=None):
    flat = flat2d.reshape(-1)
    outs = []
    for n, off, shp in zip(sizes, offs, shapes):
        seg = flat[off:off + n].reshape(shp)
        outs.append(seg if dtype is None else seg.astype(dtype))
    return outs


def _adam_kernel(p_ref, g_ref, m1_ref, m2_ref, lrt_ref,
                 p_out, m1_out, m2_out, bf_out, *, beta1, beta2, epsilon):
    # expression mirrors ops/optimizer_ops.py fused_adam verbatim (bitwise)
    b1 = jnp.float32(beta1)
    b2 = jnp.float32(beta2)
    g = g_ref[...]
    m1n = b1 * m1_ref[...] + (1.0 - b1) * g
    m2n = b2 * m2_ref[...] + (1.0 - b2) * g * g
    u = m1n / (jnp.sqrt(m2n) + epsilon)
    p = p_ref[...] - lrt_ref[0, 0] * u
    p_out[...] = p
    m1_out[...] = m1n
    m2_out[...] = m2n
    bf_out[...] = p.astype(jnp.bfloat16)


def _momentum_kernel(p_ref, g_ref, v_ref, lr_ref, p_out, v_out, bf_out, *,
                     mu, use_nesterov):
    # mirrors ops/optimizer_ops.py fused_momentum verbatim (bitwise)
    g = g_ref[...]
    lr = lr_ref[0, 0]
    v = jnp.float32(mu) * v_ref[...] + g
    if use_nesterov:
        p = p_ref[...] - (g + jnp.float32(mu) * v) * lr
    else:
        p = p_ref[...] - lr * v
    p_out[...] = p
    v_out[...] = v
    bf_out[...] = p.astype(jnp.bfloat16)


def _tile_specs(n_blocks):
    tile = pl.BlockSpec((8, 128), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (i, 0))
    return tile, scalar


def fused_adam_step(params, grads, m1s, m2s, lr, b1pows, b2pows,
                    beta1=0.9, beta2=0.999, epsilon=1e-8):
    """One fused Adam step over the group.  Returns
    (p_news, m1ns, m2ns, b1pow_outs, b2pow_outs, bf16_news) — the last is
    the bf16 carry copies (``p_new.astype(bfloat16)`` per member), emitted
    from the same VMEM tile so the carry never costs an extra HBM pass.

    All scalar algebra (lr_t, beta-pow advance) uses the EXACT unfused
    expressions so the result is bitwise-equal to fused_adam's jnp path."""
    dt = params[0].dtype
    lr_ = lr.reshape(()).astype(dt)
    b1 = jnp.asarray(beta1, dt)
    b2 = jnp.asarray(beta2, dt)
    shapes = [p.shape for p in params]

    p_flat, sizes, counts, offs = _pad_flat(params)
    g_flat, _, _, _ = _pad_flat([g.astype(dt) for g in grads])
    m1_flat, _, _, _ = _pad_flat(m1s)
    m2_flat, _, _, _ = _pad_flat(m2s)

    # per-member scalar lr_t (unfused expression), replicated per block
    lrts = []
    for b1pow, b2pow in zip(b1pows, b2pows):
        b1p = b1pow.reshape(()).astype(dt)
        b2p = b2pow.reshape(()).astype(dt)
        lrts.append(lr_ * jnp.sqrt(1.0 - b2p) / (1.0 - b1p))
    n_blocks = sum(counts)
    lrt_blocks = jnp.repeat(jnp.stack(lrts), np.asarray(counts),
                            total_repeat_length=n_blocks).reshape(-1, 1)

    tile, scalar = _tile_specs(n_blocks)
    rows = n_blocks * 8
    p_new, m1n, m2n, bf = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                          epsilon=epsilon),
        grid=(n_blocks,),
        in_specs=[tile, tile, tile, tile, scalar],
        out_specs=[tile, tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), dt),
                   jax.ShapeDtypeStruct((rows, 128), dt),
                   jax.ShapeDtypeStruct((rows, 128), dt),
                   jax.ShapeDtypeStruct((rows, 128), jnp.bfloat16)],
        interpret=adoption.interpret(),
    )(p_flat, g_flat, m1_flat, m2_flat, lrt_blocks)

    return (_unpad(p_new, sizes, counts, offs, shapes),
            _unpad(m1n, sizes, counts, offs, shapes),
            _unpad(m2n, sizes, counts, offs, shapes),
            [(b.reshape(()) * b1).reshape(b.shape) for b in b1pows],
            [(b.reshape(()) * b2).reshape(b.shape) for b in b2pows],
            _unpad(bf, sizes, counts, offs, shapes))


def fused_momentum_step(params, grads, vels, lr, mu=0.0, use_nesterov=False):
    """One fused momentum step.  Returns (p_news, v_news, bf16_news).
    L2 regularization is pre-applied by the caller on the gradients (the
    unfused lowering folds it into g_flat before the recurrence)."""
    dt = params[0].dtype
    lr_ = lr.reshape(()).astype(dt)
    shapes = [p.shape for p in params]

    p_flat, sizes, counts, offs = _pad_flat(params)
    g_flat, _, _, _ = _pad_flat([g.astype(dt) for g in grads])
    v_flat, _, _, _ = _pad_flat(vels)

    n_blocks = sum(counts)
    lr_blocks = jnp.broadcast_to(lr_.reshape(1, 1), (n_blocks, 1))
    tile, scalar = _tile_specs(n_blocks)
    rows = n_blocks * 8
    p_new, v_new, bf = pl.pallas_call(
        functools.partial(_momentum_kernel, mu=mu,
                          use_nesterov=bool(use_nesterov)),
        grid=(n_blocks,),
        in_specs=[tile, tile, tile, scalar],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), dt),
                   jax.ShapeDtypeStruct((rows, 128), dt),
                   jax.ShapeDtypeStruct((rows, 128), jnp.bfloat16)],
        interpret=adoption.interpret(),
    )(p_flat, g_flat, v_flat, lr_blocks)

    return (_unpad(p_new, sizes, counts, offs, shapes),
            _unpad(v_new, sizes, counts, offs, shapes),
            _unpad(bf, sizes, counts, offs, shapes))


def stash_bf16_carry(ctx, bf16_news):
    """Hand the kernel's bf16 copies to the step function: for every
    carried param in this group (its f32 master lives under
    ``<name>@MASTER``), drop the kernel's cast under
    ``<name>@PALLAS_BF16`` — build_block_fn prefers the stash over
    re-casting the f32 ParamOut (bitwise the same value, one less
    elementwise pass over the parameter bytes)."""
    if ctx is None or ctx.op is None or getattr(ctx, "env", None) is None:
        return
    names = ctx.op.input("Param")
    for n, bf in zip(names, bf16_news):
        if (n + "@MASTER") in ctx.env:
            ctx.env[n + "@PALLAS_BF16"] = bf
