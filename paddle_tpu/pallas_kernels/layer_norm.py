"""Fused LayerNorm forward (Pallas TPU kernel).

The XLA composition reads x for the statistics pass and again for the
normalize pass; this kernel does both in one VMEM-resident pass per row
block — measured 5.44 vs 6.27 ms at BERT-base shapes ([32768, 768] bf16)
on the bench chip, and MORE accurate than the bf16-carry composition
(f32 internal stats: max err 0.015 vs 0.040 against an f64 golden).
In-program it measured -1.5% on full BERT (it breaks XLA's LN-neighbor
fusions), so it ships opt-in: FLAGS_use_pallas_layer_norm.

The backward is a single fused jnp pass (XLA reads x/dy once) using the
saved mean/variance, INCLUDING the mean/variance cotangent contributions
so gradients agree exactly with the differentiable jnp composition.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, m_ref, v_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.mean(x, axis=1, keepdims=True)
    xc = x - m
    v = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(v + eps)
    y = y * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    # stats as [block_r, 1]: 1-D outputs hit XLA/Mosaic tiled-layout
    # mismatches (T(1024) vs T(512)); VARIANCE is emitted directly — the
    # 1/(rstd*rstd)-eps reconstruction catastrophically cancels for
    # near-constant rows and could go negative
    m_ref[...] = m
    v_ref[...] = v


def _pick_block_r(R):
    for b in (512, 256, 128, 64, 32, 16, 8):
        if R % b == 0:
            return b
    return None


def ln_checks(R, C):
    """Ordered (reason, ok) eligibility pairs for adoption.decide() — the
    shared funnel that replaced this module's private copy of the gate
    (fused_ln.py carried a near-duplicate; both now feed adoption.py so a
    fallback is a counted event, not a silent branch)."""
    return [
        ("backend", jax.default_backend() == "tpu"),
        ("lanes", C % 128 == 0),
        ("block_rows", _pick_block_r(R) is not None),
    ]


def can_use_pallas_ln(R, C):
    """Pure eligibility (no flag/probe/telemetry) — tests use this to
    assert the kernel would engage for a shape."""
    return all(ok for _, ok in ln_checks(R, C))


def _fwd_pallas(x, g, b, eps):
    R, C = x.shape
    block_r = _pick_block_r(R)
    y, mean, var = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(R // block_r,),
        in_specs=[pl.BlockSpec((block_r, C), lambda i: (i, 0)),
                  pl.BlockSpec((C,), lambda i: (0,)),
                  pl.BlockSpec((C,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((block_r, C), lambda i: (i, 0)),
                   pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), x.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
    )(x, g, b)
    return y, mean[:, 0], var[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm_2d(x, g, b, eps=1e-5):
    """LN over the last dim: x [R, C], g/b [C] ->
    (y [R, C], mean [R] f32, var [R] f32)."""
    return _fwd_pallas(x, g, b, eps)


def _ln_fwd(x, g, b, eps):
    y, mean, var = _fwd_pallas(x, g, b, eps)
    return (y, mean, var), (x, g, b, mean, var)


def _ln_bwd(eps, res, cts):
    dy, dmean, dvar = cts
    x, g, b, mean, var = res
    C = x.shape[1]
    rstd = jax.lax.rsqrt(var + eps)
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * rstd[:, None]
    dyg = dyf * gf[None, :]
    s1 = jnp.sum(dyg, axis=1, keepdims=True)
    s2 = jnp.sum(dyg * xhat, axis=1, keepdims=True)
    dx = (rstd[:, None] / C) * (C * dyg - s1 - xhat * s2)
    # mean/variance cotangents: the jnp composition is differentiable
    # through its Mean/Variance outputs, so the kernel path must agree —
    # d mean/d x = 1/C; d var/d x = 2 (x - mean)/C
    if dmean is not None:
        dx = dx + dmean.astype(jnp.float32)[:, None] / C
    if dvar is not None:
        dx = dx + (2.0 / C) * dvar.astype(jnp.float32)[:, None] * (
            xf - mean[:, None])
    dg = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    return dx.astype(x.dtype), dg.astype(g.dtype), db.astype(b.dtype)


layer_norm_2d.defvjp(_ln_fwd, _ln_bwd)
