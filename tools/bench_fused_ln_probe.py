"""Probe: Pallas fused dropout+add+LN vs the XLA-composed emission.

Flagship BERT shape [32768, 768] bf16 (bs256 x seq128).  The composed
variant reproduces the training emission the ops lower to today:
byte-threshold dropout mask (ops/common.py bernoulli_bytes), residual
add, LayerNorm with f32-internal stats.  Chained+barrier protocol per
bench_util.
"""

import os
import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_util import timed as _time
from paddle_tpu.pallas_kernels.fused_ln import fused_dropout_add_ln
from paddle_tpu.ops.common import bernoulli_bytes, realized_keep_prob

REP = 32
P = 0.1
EPS = 1e-5


def composed(x, y, g, b, key, p):
    if p > 0:
        keep = bernoulli_bytes(key, 1.0 - p, y.shape)
        q = realized_keep_prob(1.0 - p)
        y = jnp.where(keep, y / jnp.asarray(q, y.dtype),
                      jnp.asarray(0.0, y.dtype))
    r = x + y
    rf = r.astype(jnp.float32)
    mean = rf.mean(-1, keepdims=True)
    c = rf - mean
    var = (c * c).mean(-1, keepdims=True)
    z = c * lax.rsqrt(var + EPS) * g + b
    return z.astype(x.dtype)


def chain_fwd(fn, x, y, g, b, rep):
    def body(c, i):
        xb, cb = lax.optimization_barrier((x, c))
        z = fn(xb, y, g, b, i)
        zb = lax.optimization_barrier(z)
        return zb.reshape(-1)[0].astype(jnp.float32) * 1e-9 + cb * 0, ()

    out, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(rep))
    return (out,)


def chain_bwd(fn, x, y, g, b, rep):
    def loss(x, y, g, b, i):
        z = fn(x, y, g, b, i)
        return (z.astype(jnp.float32) ** 2).sum() * 1e-9

    grad = jax.grad(loss, (0, 1, 2, 3))

    def body(c, i):
        xb, cb = lax.optimization_barrier((x, c))
        gs = grad(xb, y, g, b, i)
        gb = lax.optimization_barrier(gs)
        return gb[0].reshape(-1)[0].astype(jnp.float32) * 1e-9 + cb * 0, ()

    out, _ = lax.scan(body, jnp.float32(0.0), jnp.arange(rep))
    return (out,)


def main():
    print(f"device: {jax.devices()[0]} ({jax.devices()[0].device_kind})")
    key = jax.random.PRNGKey(0)
    N, H = 32768, 768
    x = jax.random.normal(key, (N, H), jnp.bfloat16)
    y = jax.random.normal(jax.random.fold_in(key, 1), (N, H), jnp.bfloat16)
    g = jnp.ones((H,), jnp.float32)
    b = jnp.zeros((H,), jnp.float32)

    def run(name, fn, chain):
        t = _time(lambda *a: chain(fn, *a, REP), x, y, g, b)
        dev = t / REP
        # fwd traffic: read x,y write z = 3 passes of N*H*2B
        print(f"{name:44s} {dev*1e3:7.3f} ms")
        return dev

    for p in (0.0, P):
        co = lambda x, y, g, b, i, p=p: composed(
            x, y, g, b, jax.random.fold_in(key, i), p)
        fu = lambda x, y, g, b, i, p=p: fused_dropout_add_ln(
            x, y, g, b, p, jnp.stack([i.astype(jnp.uint32),
                                      jnp.uint32(7)]), EPS)
        a = run(f"composed fwd          p={p}", co, chain_fwd)
        c = run(f"pallas fused fwd      p={p}", fu, chain_fwd)
        print(f"  -> fwd speedup {a/c:.2f}x")
        a = run(f"composed fwd+bwd      p={p}", co, chain_bwd)
        c = run(f"pallas fused fwd+bwd  p={p}", fu, chain_bwd)
        print(f"  -> fwd+bwd speedup {a/c:.2f}x")


if __name__ == "__main__":
    main()
