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
its ``dt * x``.  ``b`` and ``c`` come by group, ``[G, N]``: the heads are
divided evenly over ``G`` groups in order, so group ``g``'s pair moves the
state's columns ``g * I / G .. (g + 1) * I / G - 1`` (``G`` = 1: one pair for
every head, Granite's; 8 for Nemotron-H).  ``advance`` is that on states held
as values (the unpaged reference step, and the jnp path below).

In the paged step the states live in a pool ``[slots, N, I]`` (one slot a
sequence, serving/kv_cache.py) and lane ``b`` of a step holds slot
``slots[b]``.  ``state_update`` picks the path from what it can see, with no
flag, as ``paged_attention`` does:

* **the kernel**, on a TPU backend, for a float32 pool whose ``I`` is a
  multiple of ``COLUMNS``, whose ``N`` is a multiple of the 8 sublanes and
  whose groups are whole 128-column slices that tile a grid step's columns
  (or are tiled by them):
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

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["advance", "state_update", "state_update_reference",
           "ssm_update_checks", "update_path", "started", "KERNEL_NAME"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "ssm_state_update"

# columns of the state a grid step moves: a block of N x COLUMNS float32
# (1 MB at N = 128), double buffered in and out
COLUMNS = 2048

_VMEM_BUDGET = 8 << 20


def advance(state, decay, dx, b, c):
    """``state`` [B, N, I] float32 one token on -> (new state, its read-out
    y [B, I]); ``decay`` and ``dx`` [B, I], ``b`` and ``c`` [B, G, N], group
    ``g`` the pair of columns ``g * I / G`` on.  All elementwise and one sum
    over N, in float32."""
    lanes, n, inner = state.shape
    groups = b.shape[1]
    # columns by group: [B, N, G, I / G] against a pair [B, N, G, 1]
    by_group = lambda x: x.reshape(lanes, -1, groups, inner // groups)
    pair = lambda x: jnp.swapaxes(x, 1, 2)[..., None]
    state = by_group(decay) * by_group(state) + pair(b) * by_group(dx)
    y = jnp.sum(state * pair(c), axis=1)
    return state.reshape(lanes, n, inner), y.reshape(lanes, inner)


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


def _groups_tile(inner, groups):
    """Are the groups' columns whole 128-column slices, and does a group
    tile a grid step's ``min(COLUMNS, inner)`` columns or a step a group?
    Then every slice has one group, known from the step."""
    cols = min(COLUMNS, inner)
    if groups < 1 or inner < 1 or inner % groups \
            or (inner // groups) % 128:
        return False
    per = inner // groups
    return per % cols == 0 or cols % per == 0


def ssm_update_checks(pool_shape, pool_dtype, lanes, groups=1):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of a pool ``[slots, N, I]``, a step of ``lanes`` lanes and ``b``
    and ``c`` in ``groups`` groups."""
    dims = tuple(pool_shape) + (lanes, groups)
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
        # a group is whole 128-column slices, and tiles a grid step's
        # columns or is tiled by them
        ("groups", static and rank and groups <= 128
         and _groups_tile(pool_shape[2], groups)),
        # the pool's block in and out, double buffered, and b and c spread
        # over the lanes
        ("vmem", static and rank and 4 * pool_shape[1] * (
            4 * min(COLUMNS, pool_shape[2]) + 4 * 128) <= _VMEM_BUDGET),
    ]


def update_path(pool_shape, pool_dtype, lanes, groups=1):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"gather"``: the same rule as ``state_update``, counted
    nowhere.  The engine names the step's path by it, in the executable's
    cache key and on the ``serving_prewarm`` event."""
    ok = all(ok for _reason, ok in
             ssm_update_checks(pool_shape, pool_dtype, lanes, groups))
    return "pallas" if ok else "gather"


def _kernel(slots_ref, fresh_ref, pool_ref, decay_ref, dx_ref, b_ref, c_ref,
            out_ref, y_ref, *, groups, per):
    del slots_ref                        # steers the blocks, not the body
    lane = pl.program_id(0)
    fresh = fresh_ref[lane] != 0
    cols = pool_ref.shape[1]
    if groups == 1:
        # [N, 128], a value a row, the same for every column
        first = 0
        pairs = {0: (b_ref[...], c_ref[...])}
    else:
        # [N, 128] with group g's values in lane g: the first group this
        # grid step's columns belong to, and a pair a group as it is met
        first = pl.program_id(1) * cols // per
        pairs = {}

    def pair(g):
        if g not in pairs:
            at = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
            pairs[g] = tuple(
                jnp.broadcast_to(jnp.sum(
                    jnp.where(at == first + g, ref[...], 0.0), axis=1,
                    keepdims=True), ref.shape) for ref in (b_ref, c_ref))
        return pairs[g]

    # 128 columns at a time: every operand is whole (8, 128) tiles, b and c
    # rows broadcast over the lanes, decay and dx columns over the sublanes
    for k in range(cols // 128):
        at = pl.ds(k * 128, 128)
        b, c = pair(k * 128 // per)
        state = jnp.where(fresh, 0.0, pool_ref[:, at])
        state = decay_ref[:, at] * state + b * dx_ref[:, at]
        out_ref[:, at] = state
        y_ref[:, at] = jnp.sum(state * c, axis=0, keepdims=True)


def _state_update_pallas(pool, slots, fresh, decay, dx, b, c, interpret=None):
    """-> (pool updated in its own buffer, y [B, I])."""
    lanes, inner = decay.shape
    n, groups = pool.shape[1], b.shape[1]
    cols = min(COLUMNS, inner)
    if interpret is None:
        interpret = adoption.interpret()
    f32 = jnp.float32
    row = lambda x: x.astype(f32).reshape(lanes, 1, inner)

    # b and c as columns, a value a sublane: one group's repeated over the
    # 128 lanes; several groups' side by side, group g in lane g (the
    # kernel spreads the one a slice of columns belongs to)
    def col(x):
        x = jnp.swapaxes(x.astype(f32), 1, 2)               # [B, N, G]
        if groups == 1:
            return jnp.broadcast_to(x, (lanes, n, 128))
        return jnp.pad(x, ((0, 0), (0, 0), (0, 128 - groups)))

    slot_block = pl.BlockSpec((None, n, cols),
                              lambda i, j, slots, fresh: (slots[i], 0, j))
    lane_row = pl.BlockSpec((None, 1, cols),
                            lambda i, j, slots, fresh: (i, 0, j))
    lane_col = pl.BlockSpec((None, n, 128),
                            lambda i, j, slots, fresh: (i, 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_kernel, groups=groups, per=inner // groups),
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
    zeros where ``fresh[i]``; ``b`` and ``c`` [B, G, N].  -> (pool, y [B,
    I]).  The kernel where the
    shape rule admits it (``adoption.decide`` counts the lowering under
    ``pallas_kernel_used_total`` / ``..._fallback_total{reason}``), the
    gather otherwise."""
    use, _reason = adoption.decide(
        "ssm_update", ssm_update_checks(pool.shape, pool.dtype,
                                        decay.shape[0], b.shape[1]))
    if use:
        return _state_update_pallas(pool, slots, fresh, decay, dx, b, c)
    return state_update_reference(pool, slots, fresh, decay, dx, b, c)
