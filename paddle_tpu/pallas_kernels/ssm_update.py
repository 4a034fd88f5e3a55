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

* **the kernel**, on a TPU backend, for a float32 pool whose ``I`` is whole
  128-column slices, whose ``N`` is a multiple of the 8 sublanes and whose
  groups are whole slices too: a grid over (lane, column chunk) whose unit
  is a lane's whole slot where VMEM allows (``transfer_columns``: ``N x I``
  float32, 2 MiB contiguous at the published sizes) and the widest chunk of
  it that fits otherwise.  The kernel moves the units itself, by async
  copies steered by the scalar-prefetched slots, to and from the same place
  (the pool is aliased to the output), **a batch of units at a time and
  reads and writes in turn** (``in_turns``): a batch is updated in VMEM
  first beside the write of the batch before it, then beside the read of
  the batch after it, so a batch costs ``max(write, first units) +
  max(read, rest)`` and never a read and a write are in flight together.
  The chip's HBM takes reads at 745 GB/s and writes at 653; with both in
  flight everything moves at the writes' rate (a layer's call 204.8 us
  whatever the block or the depth of the queue), in turns each moves at
  its own (195.8 us: PERF.md section 6, PRs 44 and 48).  A
  slot is read once and written once, ``b`` and ``c`` come in as they are
  (``[B, G, N]``, turned into columns in the kernel), and nothing of the
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
           "ssm_update_checks", "update_path", "transfer_columns", "started",
           "in_turns", "units_in_flight", "KERNEL_NAME"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "ssm_state_update"

# what the kernel asks of VMEM (Mosaic's default scoped limit is 16 MiB of
# the chip's 128), and the part of it the units in flight may take: two
# batches of them, one being updated or written while the other is read; the
# rest is the lanes' rows, b and c and Mosaic's own.  XLA keeps the next
# matmuls' weights in the same VMEM across the call: asking for more than
# this costs the step more than longer turns win the kernel (PERF.md
# section 6, PR 44: batches of 8 in 40 MiB are 1.2 us a call faster alone
# and 0.06-0.57 ms a step slower inside Granite's and Nemotron-H's steps)
_VMEM_LIMIT = 24 << 20
_UNIT_BUDGET = 16 << 20

# units read (or written) together at most: 4 whole slots of 2 MiB twice
# fill the budget
BATCH = 4


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


def _whole_groups(inner, groups):
    """Are the groups' columns whole 128-column slices?"""
    return groups >= 1 and inner >= 1 and inner % groups == 0 \
        and (inner // groups) % 128 == 0


def transfer_columns(pool_shape, groups=1):
    """The columns of a slot one transfer moves: ``I`` where two batches of
    two whole slots fit ``_UNIT_BUDGET``, else the widest chunk that does,
    divides ``I`` into whole 128-column slices and lies within one group or
    spans whole groups (so every slice has one group, known from the chunk).
    None where not even a 128-column chunk fits, or the groups are not whole
    slices.  A delta-rule pool's groups are its heads (``kda_update``): a
    slot of 32 heads of 128 (2 MiB) and one of 64 (4 MiB: with ``n`` 128 and
    ``I`` 8192 exactly the budget, in batches of two,
    ``units_in_flight``) both move whole, and past that in whole heads."""
    _slots, n, inner = pool_shape
    if n < 1 or not _whole_groups(inner, groups):
        return None
    per = inner // groups
    for pieces in range(1, inner // 128 + 1):
        cols = inner // pieces
        if inner % pieces or cols % 128 \
                or (per % cols and cols % per):
            continue
        if 2 * 2 * 4 * n * cols <= _UNIT_BUDGET:
            return cols
    return None


def ssm_update_checks(pool_shape, pool_dtype, lanes, groups=1):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of a pool ``[slots, N, I]``, a step of ``lanes`` lanes and ``b``
    and ``c`` in ``groups`` groups."""
    dims = tuple(pool_shape) + (lanes, groups)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank = len(pool_shape) == 3
    shaped = static and rank and all(x > 0 for x in dims)
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank),
        ("dtype", jnp.dtype(pool_dtype) == jnp.float32),
        ("lanes", static and rank and pool_shape[2] % 128 == 0),
        ("sublanes", static and rank and pool_shape[1] % 8 == 0),
        ("empty", shaped),
        # a group is whole 128-column slices
        ("groups", shaped and _whole_groups(pool_shape[2], groups)),
        # two batches of two units, be a unit only 128 columns wide, within
        # what the kernel asks of VMEM
        ("vmem", shaped and transfer_columns(pool_shape, groups) is not None),
    ]


def update_path(pool_shape, pool_dtype, lanes, groups=1):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"gather"``: the same rule as ``state_update``, counted
    nowhere.  The engine names the step's path by it, in the executable's
    cache key and on the ``serving_prewarm`` event."""
    ok = all(ok for _reason, ok in
             ssm_update_checks(pool_shape, pool_dtype, lanes, groups))
    return "pallas" if ok else "gather"


def _columns_of(ref, first):
    """``ref`` [G, N] (``b`` or ``c`` of a lane, as they come) -> ``g`` ->
    [N, 128] with row ``first + g``'s value for state row ``n`` in every
    lane of sublane ``n``: the row picked by a masked sum over the sublanes,
    laid along the diagonal of [N, N] and summed over the lanes (one term
    and zeros: exact)."""
    x = ref[...]
    groups, n = x.shape
    which = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    made = {}

    def column(g):
        if g not in made:
            row = jnp.sum(jnp.where(which == first + g, x, 0.0), axis=0,
                          keepdims=True)
            made[g] = jnp.broadcast_to(
                jnp.sum(jnp.where(diagonal, row, 0.0), axis=1,
                        keepdims=True), (n, 128))
        return made[g]
    return column


def in_turns(slots_ref, out_hbm, buf, rsem, wsem, lanes, chunks, update):
    """The transfers of a kernel whose grid is (lane, chunk) over the slots
    of a pool ``out_hbm`` [slots, N, I] (aliased to its input), around
    ``update(lane, chunk, half, at)``, which moves unit ``lane * chunks +
    chunk`` (columns ``chunk * cols`` on of slot ``slots_ref[lane]``) where
    it lies in ``buf`` [2, K, N, cols]: batch ``unit // K`` in half ``batch
    % 2``, at ``buf[half, at]``.  Reads and writes take turns, R(0), R(1),
    W(0), R(2), W(1), ..., and the core waits out neither: batch ``k``'s
    last step waits for R(k + 1), starts W(k) and returns; batch ``k + 1``'s
    first ``K // 2`` units are updated beside W(k); there the kernel waits
    for W(k) and starts R(k + 2) into the half W(k) has freed; the rest are
    updated beside R(k + 2).  A batch costs ``max(write, first units) +
    max(read, rest)``.  The first step starts R(0) and R(1) at once and
    batch 0's units are updated as each arrives; the last batch, with
    nothing left to read, writes each unit as it is done and waits for
    every write at the end.  Shared by every kernel that updates slots in
    place (``ssm_state_update``, ``kda_state_update``): the grid's
    dimensions are ``arbitrary``, the batches carry over."""
    lane, chunk = pl.program_id(0), pl.program_id(1)
    k_n, cols = buf.shape[1], buf.shape[3]
    total = lanes * chunks
    unit = lane * chunks + chunk
    batch, at = unit // k_n, unit % k_n
    last = batch == (total - 1) // k_n

    def copy(kk, jj, write):
        u = kk * k_n + jj
        where = out_hbm.at[
            slots_ref[u // chunks], :,
            pl.ds(pl.multiple_of(u % chunks * cols, 128), cols)]
        here = buf.at[kk % 2, jj]
        if write:
            return pltpu.make_async_copy(here, where, wsem.at[kk % 2, jj])
        return pltpu.make_async_copy(where, here, rsem.at[kk % 2, jj])

    def each(kk, write, act):
        """``act`` on the copy of every unit batch ``kk`` has (the last
        batch may be short)."""
        for jj in range(k_n):
            go = lambda jj=jj: act(copy(kk, jj, write))
            if total % k_n:
                pl.when(kk * k_n + jj < total)(go)
            else:
                go()

    start, wait = (lambda dma: dma.start()), (lambda dma: dma.wait())

    def written_before():
        """Wait for the write of the batch before this one, if there is
        one."""
        pl.when(batch > 0)(lambda: each(batch - 1, True, wait))

    @pl.when(unit == 0)
    def _first():
        each(0, False, start)

    # the turn from the write before to the read ahead: batch 0 has no
    # write before it
    @pl.when((at == jnp.where(batch == 0, 0, k_n // 2))
             & jnp.logical_not(last))
    def _ahead():
        written_before()
        each(batch + 1, False, start)

    # batch 0 has no batch before it to be updated beside its read: each
    # of its units is updated as it arrives
    @pl.when(batch == 0)
    def _arrived():
        copy(0, at, False).wait()

    update(lane, chunk, batch % 2, at)

    @pl.when(last)
    def _write_now():
        copy(batch, at, True).start()

    @pl.when((at == k_n - 1) & jnp.logical_not(last))
    def _turn():
        each(batch + 1, False, wait)
        each(batch, True, start)

    @pl.when(unit == total - 1)
    def _drain():
        written_before()
        each(batch, True, wait)


def units_in_flight(pool_shape, cols, units):
    """Units read (or written) together, K of ``in_turns``'s ``buf`` [2, K,
    N, cols]: ``BATCH``, fewer where two batches of that many would not fit
    ``_UNIT_BUDGET`` or the call has fewer ``units``."""
    return min(BATCH, _UNIT_BUDGET // (2 * 4 * pool_shape[1] * cols), units)


def _kernel(slots_ref, fresh_ref, pool_hbm, decay_ref, dx_ref, b_ref, c_ref,
            out_hbm, y_ref, buf, rsem, wsem, *, lanes, chunks, per):
    """Grid step (lane, chunk) updates one unit where ``in_turns`` has put
    it."""
    del pool_hbm                         # out_hbm is the same buffer
    cols = buf.shape[3]

    def update(lane, chunk, half, at):
        fresh = fresh_ref[lane] != 0
        # b and c of the groups this chunk's columns belong to, a pair a
        # group as it is met
        first = chunk * cols // per
        b_of, c_of = _columns_of(b_ref, first), _columns_of(c_ref, first)
        # 128 columns at a time: every operand is whole (8, 128) tiles, b
        # and c rows broadcast over the lanes, decay and dx columns over the
        # sublanes
        for k in range(cols // 128):
            sl = pl.ds(k * 128, 128)
            g = k * 128 // per
            state = jnp.where(fresh, 0.0, buf[half, at, :, sl])
            state = decay_ref[:, sl] * state + b_of(g) * dx_ref[:, sl]
            buf[half, at, :, sl] = state
            y_ref[:, sl] = jnp.sum(state * c_of(g), axis=0, keepdims=True)

    in_turns(slots_ref, out_hbm, buf, rsem, wsem, lanes, chunks, update)


def _state_update_pallas(pool, slots, fresh, decay, dx, b, c, interpret=None):
    """-> (pool updated in its own buffer, y [B, I])."""
    lanes, inner = decay.shape
    n, groups = pool.shape[1], b.shape[1]
    cols = transfer_columns(pool.shape, groups)
    chunks = inner // cols
    k_n = units_in_flight(pool.shape, cols, lanes * chunks)
    if interpret is None:
        interpret = adoption.interpret()
    f32 = jnp.float32
    row = lambda x: x.astype(f32).reshape(lanes, 1, inner)
    lane_row = pl.BlockSpec((None, 1, cols),
                            lambda i, j, slots, fresh: (i, 0, j))
    lane_pair = pl.BlockSpec((None, groups, n),
                             lambda i, j, slots, fresh: (i, 0, 0))
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    pool, y = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, chunks=chunks,
                          per=inner // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, chunks),
            in_specs=[in_place, lane_row, lane_row, lane_pair, lane_pair],
            out_specs=[in_place, lane_row],
            scratch_shapes=[pltpu.VMEM((2, k_n, n, cols), f32),
                            pltpu.SemaphoreType.DMA((2, k_n)),
                            pltpu.SemaphoreType.DMA((2, k_n))],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((lanes, 1, inner), f32)],
        # the pool (after the two prefetched scalars) is the first output
        input_output_aliases={2: 0},
        name=KERNEL_NAME,
        interpret=interpret,
        # the batches carry over from one grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32), pool, row(decay),
      row(dx), b.astype(f32), c.astype(f32))
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
