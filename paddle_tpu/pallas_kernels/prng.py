"""Shared in-kernel PRNG / dropout-quantization helpers.

The realized-keep-probability contract — keep iff random_u32 <
round(q * 2^32), upscale divided by that REALIZED probability — is
load-bearing for forward/backward mask replay in the fused LN kernel
(fused_ln.py): both passes draw through these helpers.
"""

import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TWO32 = 1 << 32


def keep_threshold(dropout_prob):
    """u32 compare threshold for the keep draw; None = no dropout.
    Clamped to >= 1 so the degenerate draw cannot divide by zero."""
    q = 1.0 - float(dropout_prob)
    thr = int(round(q * _TWO32))
    if thr >= _TWO32:
        return None
    return max(thr, 1)


def realized_q(thr):
    """The keep probability the threshold actually samples with."""
    return thr / _TWO32


def seed_block_prng(seed_ref, grid_axis=0):
    """Seed the on-core PRNG for the current grid block.

    Mosaic caps prng_seed at 2 words, so the block index folds into word
    0 with a Knuth multiplicative hash — every block draws an
    independent stream, and a backward kernel that calls this with the
    SAME seed words and grid blocking replays the forward's stream
    exactly."""
    pid = pl.program_id(grid_axis).astype(jnp.uint32) * jnp.uint32(
        2654435761)
    pltpu.prng_seed(seed_ref[0] ^ pid, seed_ref[1])


def draw_keep_bits(shape, thr):
    """Draw `shape` keep decisions from the seeded on-core PRNG."""
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits < jnp.uint32(thr)
