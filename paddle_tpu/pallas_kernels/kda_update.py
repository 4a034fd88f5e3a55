"""One token's update of a Kimi Delta Attention (KDA) layer's matrix state,
for the decode serving path: the mathematics (a gated delta rule whose decay
is a value a key channel), and the kernel that applies it to the slots of a
pool where they lie.

A KDA layer keeps, a sequence and a head, a matrix ``S`` [keys, values]
(``D x D``, ``D`` the head's width), all heads side by side in one slot
``[D, H * D]`` float32: head ``i``'s matrix is the slot's columns ``D i ..
D i + D - 1``, so the minor dimension is dense in the 128 lanes (to the byte
a Mamba-2 slot ``[N, I]`` of the same sizes: serving/kv_cache.py keeps both
alike).  One token moves a head's matrix by ::

    S <- diag(alpha) S                   # alpha [D] in (0, 1), a value a key
    u  = v - S^T k                       # what the decayed state misses of v
    S <- S + beta * outer(k, u)          # = (I - beta k k^T) diag(alpha) S + beta k v^T
    o  = S^T q

with ``k``, ``q`` [D] over the keys, ``v``, ``u``, ``o`` [D] over the values
and ``beta`` a scalar a head.  The rank-one write needs a read of the
decayed state first, which ``ssm_update.advance`` (``S' = decay * S +
outer(b, dx)``) cannot express.  ``advance`` is that on states held as
values (the unpaged reference step, and the jnp path below).

In the paged step the states live in a pool ``[slots, D, H * D]`` and lane
``b`` of a step holds slot ``slots[b]``.  ``state_update`` picks the path
from what it can see, with no flag:

* **the kernel** (executions named ``kda_state_update``), on a TPU backend,
  for a float32 pool whose heads are whole 128-column slices, as many keys
  as values, and whose keys are whole sublane tiles: ``ssm_update``'s grid
  over (lane, column chunk) and its transfers (``ssm_update.in_turns``:
  whole slots where VMEM allows, a batch at a time, reads and writes in
  turn, a batch updated beside the write before it and the read after it,
  which is what hides this rule's long update), with this rule in VMEM, a
  slot read once and written once.  What varies down a head's keys
  (``alpha``, ``k``, ``q`` and ``beta k``) comes in turned, keys down the
  sublanes and a head a lane, **a tile of its own for each transfer a slot
  moves in** (``[B, D, transfers x tile]``, one array: the four columns of
  the transfer's own heads side by side, padded to whole 128-lane tiles),
  so a head's column is one lane spread over the 128; ``v`` and ``o`` cross
  as rows.  The rule puts no bound of its own on the heads: the transfers
  do (``ssm_update.transfer_columns``: two batches of two units within the
  VMEM the kernel asks for), and a transfer's columns are a thirty-second
  of its bytes.  Kimi-Linear's 32 heads are one transfer and one tile
  (``[33, 128, 4096]``: the kernel's text is what it was before a transfer
  had a tile of its own); Solar-Open2's 64 are one transfer of 4 MiB and
  two tiles (``[65, 128, 8192]``, exactly the budget, batches of two: 0.847
  ms a call of 64 lanes on the chip, 634 GB/s, where the same slot in two
  transfers of 32 heads reads 0.863 and the gather 9.06: PERF.md section 6,
  PR 64); 65 heads would move in five transfers of 13.
* **the gather** everywhere else (a head of 64 values, keys unlike values,
  a bfloat16 pool, the CPU tier): the lanes' slots are gathered,
  ``advance`` moves them, a scatter writes them back.

Both start a lane whose ``fresh`` flag is set from zeros, whatever its slot
holds.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption
from . import ssm_update as _ssm

__all__ = ["advance", "state_update", "state_update_reference",
           "kda_update_checks", "update_path", "KERNEL_NAME"]

# the name the kernel's executions carry in a device trace
KERNEL_NAME = "kda_state_update"


def _by_key(x):
    """[B, H, D] over a head's keys -> [B, D, H, 1]: against a state held
    ``[B, D, H, D]`` (keys, heads, values)."""
    return jnp.swapaxes(x, 1, 2)[..., None]


def advance(state, alpha, beta, k, v, q):
    """``state`` [B, D, H * D] float32 one token on -> (new state, its
    read-out o [B, H, D]); ``alpha``, ``k`` and ``q`` [B, H, D] over the
    keys, ``v`` [B, H, D] over the values, ``beta`` [B, H].  All
    elementwise and two sums over the keys, in float32."""
    lanes, dim, inner = state.shape
    heads = inner // dim
    s = state.reshape(lanes, dim, heads, inner // heads)
    kc = _by_key(k)
    s = _by_key(alpha) * s
    u = v[:, None] - jnp.sum(s * kc, axis=1, keepdims=True)
    s = s + (beta[:, None, :, None] * kc) * u
    o = jnp.sum(s * _by_key(q), axis=1)
    return s.reshape(lanes, dim, inner), o


def state_update_reference(pool, slots, fresh, alpha, beta, k, v, q):
    """The jnp path: gather the lanes' slots, ``advance``, scatter back.
    -> (pool, o)."""
    state = _ssm.started(fresh, jnp.take(pool, slots, axis=0, mode="clip"))
    state, o = advance(state, alpha, beta, k, v, q)
    return pool.at[slots].set(state), o


def kda_update_checks(pool_shape, pool_dtype, lanes, heads):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of a pool ``[slots, D, H * D]``, a step of ``lanes`` lanes and
    ``heads`` heads."""
    dims = tuple(pool_shape) + (lanes, heads)
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
        # a head's values are whole 128-column slices, its keys as many as
        # its values.  How many heads is the transfers' to bound: the four
        # columns a head of ONE transfer's heads lie in as many 128-lane
        # tiles as they fill (32 heads one, 64 heads two), a thirty-second
        # of the transfer's own bytes
        ("heads", shaped and pool_shape[2] == heads * pool_shape[1]
         and pool_shape[1] % 128 == 0),
        ("vmem", shaped and _ssm.transfer_columns(pool_shape, heads)
         is not None),
    ]


def update_path(pool_shape, pool_dtype, lanes, heads):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"gather"``: ``state_update``'s rule, counted nowhere."""
    ok = all(ok for _reason, ok in
             kda_update_checks(pool_shape, pool_dtype, lanes, heads))
    return "pallas" if ok else "gather"


def _tile(heads):
    """Lanes the four columns a head of ``heads`` heads take, in whole
    128-lane tiles."""
    return -(-4 * heads // 128) * 128


def _kernel(slots_ref, fresh_ref, pool_hbm, cols_ref, v_ref, out_hbm, o_ref,
            buf, rsem, wsem, *, lanes, chunks, dim):
    """Grid step (lane, chunk) updates one unit where ``ssm_update.in_turns``
    has put it.  ``cols_ref`` [D, tile] is the unit's own: lane ``j * heads
    + i`` holds column ``j`` of (alpha, k, q, beta k) of the ``i``-th of the
    unit's ``heads`` heads down the keys."""
    del pool_hbm                         # out_hbm is the same buffer
    heads = buf.shape[3] // dim

    def update(lane, chunk, half, at):
        del chunk                        # the unit's columns came with it
        fresh = fresh_ref[lane] != 0
        for n in range(heads):
            # a head's column, spread over the lanes
            a_col, k_col, q_col, bk_col = (
                jnp.broadcast_to(
                    cols_ref[:, j * heads + n:j * heads + n + 1], (dim, 128))
                for j in range(4))
            # 128 values at a time: whole (8, 128) tiles, v and o rows
            # broadcast over the sublanes
            for piece in range(dim // 128):
                sl = pl.ds(n * dim + piece * 128, 128)
                state = jnp.where(fresh, 0.0, buf[half, at, :, sl])
                state = a_col * state
                u = v_ref[:, sl] - jnp.sum(state * k_col, axis=0,
                                           keepdims=True)
                state = state + bk_col * u
                buf[half, at, :, sl] = state
                o_ref[:, sl] = jnp.sum(state * q_col, axis=0, keepdims=True)

    _ssm.in_turns(slots_ref, out_hbm, buf, rsem, wsem, lanes, chunks, update)


def _turned(columns, chunks):
    """``columns``, four arrays [B, H, D] over a head's keys -> [B, D,
    chunks * tile]: keys down the sublanes and, for each of the ``chunks``
    transfers a slot moves in, its own heads' four columns side by side
    (alpha | k | q | beta k, a head a lane), padded to whole tiles."""
    lanes, heads, dim = columns[0].shape
    per = heads // chunks
    turned = [jnp.swapaxes(x, 1, 2) for x in columns]
    if chunks > 1:
        turned = [x.reshape(lanes, dim, chunks, per) for x in turned]
    turned = jnp.concatenate(turned, axis=-1)
    turned = jnp.pad(turned, ((0, 0),) * (turned.ndim - 1)
                     + ((0, _tile(per) - 4 * per),))
    return turned.reshape(lanes, dim, -1) if chunks > 1 else turned


def _state_update_pallas(pool, slots, fresh, alpha, beta, k, v, q,
                         interpret=None):
    """-> (pool updated in its own buffer, o [B, H, D])."""
    lanes, heads, dim = v.shape
    inner = heads * dim
    cols = _ssm.transfer_columns(pool.shape, heads)
    chunks = inner // cols
    k_n = _ssm.units_in_flight(pool.shape, cols, lanes * chunks)
    if interpret is None:
        interpret = adoption.interpret()
    f32 = jnp.float32
    turned = _turned([x.astype(f32) for x in (
        alpha, k, q, beta.astype(f32)[..., None] * k)], chunks)
    lane_row = pl.BlockSpec((None, 1, cols),
                            lambda i, j, slots, fresh: (i, 0, j))
    # (a slot moved whole has one tile, named as it was before a transfer
    # had its own: the kernel's text at 32 heads is what it was)
    lane_cols = pl.BlockSpec(
        (None, dim, _tile(heads // chunks)),
        (lambda i, j, slots, fresh: (i, 0, j)) if chunks > 1
        else (lambda i, j, slots, fresh: (i, 0, 0)))
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    pool, o = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, chunks=chunks, dim=dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, chunks),
            in_specs=[in_place, lane_cols, lane_row],
            out_specs=[in_place, lane_row],
            scratch_shapes=[pltpu.VMEM((2, k_n, dim, cols), f32),
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
            vmem_limit_bytes=_ssm._VMEM_LIMIT),
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32), pool, turned,
      v.astype(f32).reshape(lanes, 1, inner))
    return pool, o.reshape(lanes, heads, dim)


def state_update(pool, slots, fresh, alpha, beta, k, v, q):
    """One token for the lanes of a step, on a KDA layer's pool ``[slots,
    D, H * D]``: lane ``i``'s state is slot ``slots[i]``, started from zeros
    where ``fresh[i]``.  -> (pool, o [B, H, D]).  The kernel where the shape
    rule admits it (``adoption.decide`` counts the lowering as
    ``kda_update``), the gather otherwise."""
    use, _reason = adoption.decide(
        "kda_update", kda_update_checks(pool.shape, pool.dtype, v.shape[0],
                                        v.shape[1]))
    if use:
        return _state_update_pallas(pool, slots, fresh, alpha, beta, k, v, q)
    return state_update_reference(pool, slots, fresh, alpha, beta, k, v, q)
