"""One token's update of a state-space (Mamba-2) layer's recurrent state,
for the decode serving path: the mathematics, and the kernel that applies it
to the slots of a pool where they lie.

A recurrent layer keeps a sequence's state ``S`` as ``[N, I]`` float32:
``N`` the state size and ``I = heads * head_dim`` the inner width, so the
minor dimension is dense in the 128 lanes (a head's ``[head_dim, N]`` matrix
is ``S[:, head's columns].T``).  One token moves it by ::

    S' = decay * S + outer(b, dx)        # decay, dx [I]; b [N]
    y  = c . S'                          # c [N] -> y [I]

with ``decay`` a head's ``exp(dt * A)`` repeated over its columns and ``dx``
its ``dt * x``.  ``advance`` is that on states held as values (the unpaged
reference step, and the jnp path below).

In the paged step the states live in a pool ``[slots, N, I]`` (one slot a
sequence, serving/kv_cache.py) and lane ``b`` of a step holds slot
``slots[b]``.  ``state_update`` picks the path from what it can see, with no
flag, as ``paged_attention`` does:

* **the kernel**, on a TPU backend, for a float32 pool whose ``I`` is a
  multiple of ``COLUMNS`` and whose ``N`` is a multiple of the 8 sublanes:
  a grid over (lane, column chunk) whose blocks of the pool are steered by
  the scalar-prefetched slots, each read into VMEM, updated and written back
  to the same place (the pool is aliased to the output), the next block in
  flight meanwhile.  A slot is read once and written once, and nothing of the
  pool's size is made: XLA's form of the same update splits the pool in
  halves (a whole-pool pass), gathers the lanes' slots, updates them and
  scatters them back, four times the traffic (PERF.md section 6, PR 31).
* **the gather** everywhere else (the CPU tier): the lanes' slots are
  gathered, ``advance`` moves them, a scatter writes them back.

Both start a lane whose ``fresh`` flag is set (position 0 of its sequence)
from zeros, whatever its slot holds.  Idle lanes all name slot 0 and all
write it; nothing reads it.
"""

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["advance", "state_update", "state_update_reference",
           "ssm_update_checks", "started", "KERNEL_NAME"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "ssm_state_update"

# columns of the state a grid step moves: a block of N x COLUMNS float32
# (1 MB at N = 128), double buffered in and out
COLUMNS = 2048

_VMEM_BUDGET = 8 << 20


def advance(state, decay, dx, b, c):
    """``state`` [B, N, I] float32 one token on -> (new state, its read-out
    y [B, I]); ``decay`` and ``dx`` [B, I], ``b`` and ``c`` [B, N].  All
    elementwise and one sum over N, in float32."""
    state = decay[:, None, :] * state + b[:, :, None] * dx[:, None, :]
    return state, jnp.sum(state * c[:, :, None], axis=1)


def started(fresh, x):
    """``x`` [B, ...] with the rows of fresh lanes zeroed."""
    fresh = fresh.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(fresh, jnp.zeros((), x.dtype), x)


def state_update_reference(pool, slots, fresh, decay, dx, b, c):
    """The jnp path: gather the lanes' slots, ``advance``, scatter back (a
    scatter of B slots into a whole donated array, which XLA updates in its
    buffer).  -> (pool, y)."""
    state = started(fresh, jnp.take(pool, slots, axis=0, mode="clip"))
    state, y = advance(state, decay, dx, b, c)
    return pool.at[slots].set(state), y


def ssm_update_checks(pool_shape, pool_dtype, lanes):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of a pool ``[slots, N, I]`` and a step of ``lanes`` lanes."""
    dims = tuple(pool_shape) + (lanes,)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank = len(pool_shape) == 3
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank),
        ("dtype", jnp.dtype(pool_dtype) == jnp.float32),
        ("lanes", static and rank and pool_shape[2] % 128 == 0
         and pool_shape[2] % min(COLUMNS, pool_shape[2]) == 0),
        ("sublanes", static and rank and pool_shape[1] % 8 == 0),
        ("empty", static and all(x > 0 for x in dims)),
        # the pool's block in and out, double buffered, and b and c spread
        # over the lanes
        ("vmem", static and rank and 4 * pool_shape[1] * (
            4 * min(COLUMNS, pool_shape[2]) + 4 * 128) <= _VMEM_BUDGET),
    ]


def _kernel(slots_ref, fresh_ref, pool_ref, decay_ref, dx_ref, b_ref, c_ref,
            out_ref, y_ref):
    del slots_ref                        # steers the blocks, not the body
    lane = pl.program_id(0)
    fresh = fresh_ref[lane] != 0
    b, c = b_ref[...], c_ref[...]        # [N, 128], a value a row
    # 128 columns at a time: every operand is whole (8, 128) tiles, b and c
    # rows broadcast over the lanes, decay and dx columns over the sublanes
    for k in range(pool_ref.shape[1] // 128):
        at = pl.ds(k * 128, 128)
        state = jnp.where(fresh, 0.0, pool_ref[:, at])
        state = decay_ref[:, at] * state + b * dx_ref[:, at]
        out_ref[:, at] = state
        y_ref[:, at] = jnp.sum(state * c, axis=0, keepdims=True)


def _state_update_pallas(pool, slots, fresh, decay, dx, b, c, interpret=None):
    """-> (pool updated in its own buffer, y [B, I])."""
    lanes, inner = decay.shape
    n = pool.shape[1]
    cols = min(COLUMNS, inner)
    if interpret is None:
        interpret = adoption.interpret()
    f32 = jnp.float32
    row = lambda x: x.astype(f32).reshape(lanes, 1, inner)
    # b and c as columns: a value a sublane, repeated over the 128 lanes
    col = lambda x: jnp.broadcast_to(x.astype(f32)[:, :, None],
                                     (lanes, n, 128))
    slot_block = pl.BlockSpec((None, n, cols),
                              lambda i, j, slots, fresh: (slots[i], 0, j))
    lane_row = pl.BlockSpec((None, 1, cols),
                            lambda i, j, slots, fresh: (i, 0, j))
    lane_col = pl.BlockSpec((None, n, 128),
                            lambda i, j, slots, fresh: (i, 0, 0))
    pool, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, inner // cols),
            in_specs=[slot_block, lane_row, lane_row, lane_col, lane_col],
            out_specs=[slot_block, lane_row],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((lanes, 1, inner), f32)],
        # the pool (after the two prefetched scalars) is the first output
        input_output_aliases={2: 0},
        name=KERNEL_NAME,
        interpret=interpret,
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32), pool, row(decay),
      row(dx), col(b), col(c))
    return pool, y.reshape(lanes, inner)


def state_update(pool, slots, fresh, decay, dx, b, c):
    """One token for the lanes of a step, on a recurrent layer's pool
    ``[slots, N, I]``: lane ``i``'s state is slot ``slots[i]``, started from
    zeros where ``fresh[i]``.  -> (pool, y [B, I]).  The kernel where the
    shape rule admits it (``adoption.decide`` counts the lowering under
    ``pallas_kernel_used_total`` / ``..._fallback_total{reason}``), the
    gather otherwise."""
    use, _reason = adoption.decide(
        "ssm_update", ssm_update_checks(pool.shape, pool.dtype,
                                        decay.shape[0]))
    if use:
        return _state_update_pallas(pool, slots, fresh, decay, dx, b, c)
    return state_update_reference(pool, slots, fresh, decay, dx, b, c)
