"""Shared measurement harness for the device probes.

Every probe chains its repetitions inside ONE jit call (lax.scan with
lax.optimization_barrier on loop-invariant operands — XLA otherwise elides
work via slice-of-dot/slice-of-conv/hoisted algebra), so the per-call
dispatch cost is paid once per REP passes and what is timed is the device.
"""

import time

import jax
import numpy as np


def timed(fn, *args, r=5):
    """Median wall time of r calls of jit(fn)(*args), each waited for with
    block_until_ready."""
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(r):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
