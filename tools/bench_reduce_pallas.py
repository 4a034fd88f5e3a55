"""Microbench: can a Pallas kernel beat XLA's reduction-read bandwidth cap?

Round-3 roofline measured XLA reduction-to-small-output reads at 60-76 GB/s
vs 128-147 GB/s for elementwise streams; BN statistics + wgrad reductions
(the convert_reduce fusion class) are 48% of the ResNet-50 step.  This
measures whether a hand-written Pallas channel reduction reads at the
stream rate, which would halve the dominant slice.

Protocol: dependency-chained repetitions inside ONE jit call (a scalar
carry folds into each iteration so XLA cannot CSE), waited for with
block_until_ready (tools/bench_util.py).

Usage: python tools/bench_reduce_pallas.py [variant ...]
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import sys as _sys, os as _os
_sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
from bench_util import timed as _time
from jax import lax
from jax.experimental import pallas as pl

# BN-stats shape at ResNet-50 bs512: conv output [512, 64, 56, 56] bf16 in
# NHWC view = [N*H*W, C].  c256 is the deeper-stage shape at equal bytes.
SHAPES = {
    "c64": (512 * 56 * 56, 64),
    "c256": (512 * 28 * 28, 256),
}
REP = 64  # chained passes per jit call


def _report(name, shape, t, passes=1.0):
    m, c = shape
    nbytes = m * c * 2 * REP * passes
    gbs = nbytes / t / 1e9
    print(f"{name:30s} {t*1e3/REP:8.3f} ms/pass  {gbs:7.1f} GB/s")
    return gbs


# -- XLA column-reduce chain (the BN-stats emission) -------------------------

def jnp_stats(x):
    def body(c, _):
        xf = x.astype(jnp.float32) + c
        s = jnp.sum(xf, axis=0)
        ss = jnp.sum(xf * xf, axis=0)
        return (jnp.sum(s) + jnp.sum(ss)) * 1e-12, ()

    out, _ = lax.scan(body, jnp.float32(0.0), None, length=REP)
    return (out,)


# -- XLA elementwise stream chain (bandwidth reference) ----------------------

def jnp_stream(x, a):
    def body(y, _):
        return y * a, ()

    y, _ = lax.scan(body, x, None, length=REP)
    return (y[0, 0].astype(jnp.float32), y)


# -- Pallas column-reduce with grid accumulation -----------------------------

def _stats_kernel(x_ref, c_ref, s_ref, ss_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...].astype(jnp.float32) + c_ref[0, 0]
    s_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    ss_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def pallas_stats_one(x, c, block_r):
    m, ch = x.shape
    s, ss = pl.pallas_call(
        _stats_kernel,
        grid=(m // block_r,),
        in_specs=[pl.BlockSpec((block_r, ch), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, ch), lambda i: (0, 0)),
                   pl.BlockSpec((1, ch), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, ch), jnp.float32),
                   jax.ShapeDtypeStruct((1, ch), jnp.float32)],
    )(x, c)
    return s, ss


def pallas_stats(x, block_r):
    def body(c, _):
        s, ss = pallas_stats_one(x, c, block_r)
        return (jnp.sum(s) + jnp.sum(ss)).reshape(1, 1) * 1e-12, ()

    out, _ = lax.scan(body, jnp.zeros((1, 1), jnp.float32), None, length=REP)
    return (out,)


# -- fused affine+stats: y = a*x+b written, stats of y collected -------------
# (models the BN epilogue producer-fusion: the stats pass stops re-reading)

def _affine_stats_kernel(x_ref, a_ref, b_ref, y_ref, s_ref, ss_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...].astype(jnp.float32)
    y = x * a_ref[...] + b_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)
    s_ref[...] += jnp.sum(y, axis=0, keepdims=True)
    ss_ref[...] += jnp.sum(y * y, axis=0, keepdims=True)


def pallas_affine_stats(x, a, b, block_r):
    m, ch = x.shape

    def body(y, _):
        y2, s, ss = pl.pallas_call(
            _affine_stats_kernel,
            grid=(m // block_r,),
            in_specs=[pl.BlockSpec((block_r, ch), lambda i: (i, 0)),
                      pl.BlockSpec((1, ch), lambda i: (0, 0)),
                      pl.BlockSpec((1, ch), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((block_r, ch), lambda i: (i, 0)),
                       pl.BlockSpec((1, ch), lambda i: (0, 0)),
                       pl.BlockSpec((1, ch), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((m, ch), x.dtype),
                       jax.ShapeDtypeStruct((1, ch), jnp.float32),
                       jax.ShapeDtypeStruct((1, ch), jnp.float32)],
        )(y, a, b)
        return y2, jnp.sum(s) + jnp.sum(ss)

    y, stats = lax.scan(body, x, None, length=REP)
    return (stats[-1], y)


# XLA equivalent: y = a*x+b, then stats of y (XLA may or may not
# producer-fuse the reduce into the affine — that is what we measure)

def jnp_affine_stats(x, a, b):
    def body(y, _):
        y2 = y * a[0].astype(y.dtype) + b[0].astype(y.dtype)
        yf = y2.astype(jnp.float32)
        s = jnp.sum(yf, axis=0)
        ss = jnp.sum(yf * yf, axis=0)
        return y2, jnp.sum(s) + jnp.sum(ss)

    y, stats = lax.scan(body, x, None, length=REP)
    return (stats[-1], y)


def main():
    want = set(_sys.argv[1:])
    print(f"device: {jax.devices()[0]} ({jax.devices()[0].device_kind})")
    for sname, shape in SHAPES.items():
        m, c = shape
        print(f"-- shape [{m}, {c}] bf16 ({m*c*2/1e6:.0f} MB), REP={REP}")
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, shape, dtype=jnp.bfloat16)
        a = jnp.ones((1, c), jnp.float32) * 1.0000001
        b = jnp.zeros((1, c), jnp.float32)

        if not want or "stream" in want:
            t = _time(jnp_stream, x, jnp.bfloat16(1.0000001))
            _report("xla stream 1r1w", shape, t, passes=2.0)
        if not want or "jnp" in want:
            t = _time(jnp_stats, x)
            _report("xla sum+sumsq (reduce)", shape, t)
        if not want or "pallas" in want:
            for br in (512, 1024, 2048):
                if m % br:
                    continue
                t = _time(functools.partial(pallas_stats, block_r=br), x)
                _report(f"pallas sum+sumsq br={br}", shape, t)
        if not want or "fused" in want:
            t = _time(jnp_affine_stats, x, a, b)
            _report("xla affine+stats", shape, t, passes=3.0)
            for br in (512, 1024):
                if m % br:
                    continue
                t = _time(
                    functools.partial(pallas_affine_stats, block_r=br),
                    x, a, b)
                _report(f"pallas affine+stats br={br}", shape, t,
                        passes=2.0)


if __name__ == "__main__":
    main()
