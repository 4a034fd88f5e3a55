"""The routed experts of a mixture-of-experts layer, for the decode serving
path: the mathematics, and the kernel that reads an expert's weights only if
a live lane was routed to it this step.

A routed layer holds ``E`` experts as three tensors, ``wgate`` and ``wup``
``[E, H, F]`` and ``wdown`` ``[E, F, H]``, and the router hands over ``gates
[B, E]`` float32: lane ``b``'s weight on each of its chosen experts, an exact
zero on every other.  The layer's output is ::

    y[b] = sum_e gates[b, e] * ((act(x[b] @ wgate_e) * (x[b] @ wup_e)) @ wdown_e)

``act`` the gate's activation, which the family declares (``GATES``:
``"silu"`` unless its ``FAMILY.expert_gate`` says ``"relu"``; a static
argument of the call and never a flag),
with ``x`` rounded to the weights' dtype, every matmul accumulating in
float32, the activation rounded to the weights' dtype before the down
projection and the gates applied in float32.  No capacity: every (token,
chosen expert) pair is computed.

``routed_experts`` picks the path from what it can see, with no flag, as
``paged_attention`` and ``ssm_update`` do:

* **the kernel**, on a TPU backend, for bfloat16 or float32 weights whose
  ``H`` and ``F`` are multiples of the 128 lanes.  An expert that no live
  lane chose contributes an exact zero to every row, so leaving it out is
  the same sum: from the gates a few small XLA ops derive ``order``, the hit
  experts first and every later entry repeating the last hit one, and
  ``n_hit``.  One ``pallas_call`` walks a grid ``(E, F / f_chunk)`` whose
  weight blocks are steered by the scalar-prefetched ``order``: a grid step
  past ``n_hit`` names the block already in VMEM, fetches nothing and
  computes nothing.  The weights are read as they lie, one expert's column
  chunk of ``wgate`` and ``wup`` and row chunk of ``wdown`` at a time, the
  next in flight meanwhile, into one resident ``[B, H]`` float32 sum.  An
  idle lane hits nothing and its row is zeros (the step discards it).
* **the einsums** everywhere else (the CPU tier, a program XLA partitions
  over a mesh, shapes the kernel does not take): every expert over every
  lane, the unchosen weighted zero, which streams all ``E`` experts whatever
  was hit.  ``experts_reference`` is that path, unchanged.

**The two-matrix form.**  An expert with no gate, ``relu(x @ up)^2 @ down``
(Nemotron-H's), is held as two tensors of ONE shape, ``up`` and ``down``
``[E, F, H]``: ``up`` in its ``nn.Linear`` orientation, so both have ``H``,
a multiple of 128, as the minor dimension and ``F`` is cut on the
second-minor one, in chunks of whole sublane tiles.  That admits widths no
multiple of 128 (1856 = 4 x 464) without padding them: a padded ``F`` would
stream bytes the model does not have.  ``relu2_experts`` is
``routed_experts`` for it, with the same ``hit_order`` steering, the same
rule (``adoption.decide("moe_experts", ...)``, counted under the same name)
and the same fallback (``relu2_reference``); the square is taken per chunk,
which is exact because a chunk holds whole columns of ``x @ up^T``.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import adoption

__all__ = ["routed_experts", "experts_reference", "moe_experts_checks",
           "experts_path", "hit_order", "f_chunk", "KERNEL_NAME",
           "relu2_experts", "relu2_reference", "relu2_checks", "f_rows",
           "RELU2_KERNEL_NAME", "GATES"]

# the names the kernels' executions carry in a device trace
KERNEL_NAME = "moe_routed_experts"
RELU2_KERNEL_NAME = "moe_relu2_experts"

_SUBLANES = {"float32": 8, "bfloat16": 16}   # rows of a dtype's memory tile

# the activations a three-matrix expert's gate may have, by the name a family
# states: the kernel, the einsums and the reference take the same one
# (relu as a select on the sign: XLA:CPU (jaxlib 0.9.0) fuses a bare max(dot,
# 0) into the batched dot's epilogue and then has no bfloat16 thunk for it,
# "Unsupported element type for DotThunk"; the same values)
GATES = {"silu": jax.nn.silu, "relu": lambda g: jnp.where(g > 0, g, 0.0)}

# what the three weight blocks of a grid step may take in VMEM, the next
# step's beside them (double buffered), and the limit the kernel asks
# Mosaic for: the blocks, the lanes' rows in and out and the matmuls'
# temporaries (a v5e core has 128 MiB; the default scoped limit is 16)
_BLOCK_BUDGET = 26 << 20
_VMEM_LIMIT = 40 << 20


def experts_reference(h2, gates, wgate, wup, wdown, gate="silu"):
    """sum_e gates[b, e] * expert_e(h2[b]): all experts over all lanes,
    the unchosen weighted zero.  ``gate`` names the gate's activation
    (``GATES``)."""
    hx = h2.astype(wgate.dtype)
    up = lambda w: jnp.einsum("bh,ehf->ebf", hx, w,
                              preferred_element_type=jnp.float32)
    act = GATES[gate](up(wgate)) * up(wup)
    y = jnp.einsum("ebf,efh->ebh", act.astype(wdown.dtype), wdown,
                   preferred_element_type=jnp.float32)
    return jnp.sum(y * gates.T[:, :, None], axis=0)


def hit_order(gates):
    """-> (order int32 [E], n_hit int32 [1]) of gates [B, E]: the experts
    with a nonzero gate in some row, ascending, then the last of them
    repeated (all zeros where nothing is hit).  Elementwise over [E, E] and
    two sums: no sort, no scatter."""
    e = gates.shape[1]
    hit = jnp.any(gates != 0, axis=0)
    idx = jnp.arange(e, dtype=jnp.int32)
    before = jnp.sum(hit[None, :] & (idx[None, :] < idx[:, None]), axis=1,
                     dtype=jnp.int32)          # hit experts below each one
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    place = jnp.minimum(idx, n_hit - 1)        # the rank each entry names
    order = jnp.sum(jnp.where(hit[None, :]
                              & (before[None, :] == place[:, None]),
                              idx[None, :], 0), axis=1, dtype=jnp.int32)
    return order, n_hit.reshape(1)


def f_chunk(hidden, ffn, itemsize):
    """Columns of ``wgate`` / ``wup`` (rows of ``wdown``) a grid step
    reads: the largest multiple of 128 that divides ``ffn`` and whose three
    blocks, double buffered, fit ``_BLOCK_BUDGET``; 0 where none does."""
    for n in range(1, ffn // 128 + 1):
        fc = ffn // n
        if ffn % n == 0 and fc % 128 == 0 \
                and 6 * hidden * fc * itemsize <= _BLOCK_BUDGET:
            return fc
    return 0


def _vmem_bytes(rows, hidden, fc, itemsize):
    """What a grid step holds: the weight blocks twice, the lanes' rows in
    (twice) and out (twice, float32), and the temporaries of the three
    matmuls in float32."""
    return (6 * hidden * fc * itemsize + 2 * rows * hidden * itemsize
            + 3 * rows * hidden * 4 + 4 * rows * fc * 4)


def moe_experts_checks(rows, w_shape, w_dtype):
    """Ordered (reason, ok) pairs for adoption.decide(): what the kernel
    needs of ``rows`` lanes and of expert weights ``wgate`` ``[E, H, F]``
    in ``w_dtype``."""
    dims = tuple(w_shape) + (rows,)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank = len(w_shape) == 3
    tile = _SUBLANES.get(jnp.dtype(w_dtype).name)
    shaped = static and rank and tile is not None
    fc = f_chunk(w_shape[1], w_shape[2], jnp.dtype(w_dtype).itemsize) \
        if shaped and w_shape[2] % 128 == 0 else 0
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank),
        ("dtype", tile is not None),
        ("lanes", shaped and w_shape[1] % 128 == 0
         and w_shape[2] % 128 == 0),
        ("empty", static and all(x > 0 for x in dims)),
        ("vmem", fc > 0 and _vmem_bytes(
            -(-rows // tile) * tile, w_shape[1], fc,
            jnp.dtype(w_dtype).itemsize) <= _VMEM_LIMIT),
    ]


def experts_path(rows, w_shape, w_dtype, matrices=3):
    """``"pallas"`` where the kernel would serve these shapes on this
    backend, else ``"einsum"``: the same rule as ``routed_experts`` (or,
    ``matrices`` 2, as ``relu2_experts`` for ``up`` ``[E, F, H]``), counted
    nowhere.  The engine names the step's path by it, in the executable's
    cache key and on the ``serving_prewarm`` event."""
    checks = moe_experts_checks if matrices == 3 else relu2_checks
    ok = all(ok for _reason, ok in checks(rows, w_shape, w_dtype))
    return "pallas" if ok else "einsum"


def _kernel(order_ref, n_ref, x_ref, gates_ref, wgate_ref, wup_ref,
            wdown_ref, out_ref, gate="silu"):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _start():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        x = x_ref[...]
        up = lambda w_ref: jnp.dot(x, w_ref[...],
                                   preferred_element_type=jnp.float32)
        act = GATES[gate](up(wgate_ref)) * up(wup_ref)       # [rows, fc]
        y = jnp.dot(act.astype(wdown_ref.dtype), wdown_ref[...],
                    preferred_element_type=jnp.float32)      # [rows, H]
        # this expert's column of the gates, a value a row
        col = jax.lax.broadcasted_iota(jnp.int32, gates_ref.shape, 1)
        weight = jnp.sum(jnp.where(col == order_ref[i], gates_ref[...], 0.0),
                         axis=1, keepdims=True)
        out_ref[...] += weight * y


def _experts_pallas(h2, gates, live, wgate, wup, wdown, fc=None,
                    interpret=None, gate="silu"):
    """``routed_experts`` on the kernel: an idle lane's gates count as
    zeros.  ``fc`` None follows ``f_chunk``; ``interpret`` None follows the
    backend; ``gate`` names the gate's activation (``GATES``)."""
    b, hidden = h2.shape
    e, _h, ffn = wgate.shape
    if fc is None:
        fc = f_chunk(hidden, ffn, wgate.dtype.itemsize)
    if interpret is None:
        interpret = adoption.interpret()
    nj = ffn // fc
    tile = _SUBLANES[wgate.dtype.name]
    pad = (0, -b % tile), (0, 0)
    x = jnp.pad(h2.astype(wgate.dtype), pad)
    gates = jnp.pad(jnp.where(live[:, None], gates.astype(jnp.float32), 0.0),
                    pad)
    order, n_hit = hit_order(gates)
    rows = x.shape[0]

    # past the hit experts every step names the last block fetched
    def chunk(i, j, n):
        return jnp.where(i < n[0], j, nj - 1)

    whole = lambda i, j, order, n: (0, 0)
    columns = pl.BlockSpec((None, hidden, fc), lambda i, j, order, n:
                           (order[i], 0, chunk(i, j, n)))
    out = pl.pallas_call(
        # (the default gate as the bare function: the call every SiLU
        # family lowered to before a gate could be named)
        _kernel if gate == "silu" else functools.partial(_kernel, gate=gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, nj),
            in_specs=[pl.BlockSpec((rows, hidden), whole),
                      pl.BlockSpec((rows, e), whole),
                      columns, columns,
                      pl.BlockSpec((None, fc, hidden), lambda i, j, order, n:
                                   (order[i], chunk(i, j, n), 0))],
            out_specs=pl.BlockSpec((rows, hidden), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=KERNEL_NAME,
        interpret=interpret,
    )(order, n_hit, x, gates, wgate, wup, wdown)
    return out[:b]


def routed_experts(h2, gates, live, wgate, wup, wdown, gate="silu"):
    """A routed layer's experts for the lanes of a step: h2 [B, H] float32,
    gates [B, E] float32 (zero off a lane's chosen experts), live [B] bool
    -> [B, H] float32; ``gate`` (static) names the activation of an expert's
    gate, ``"silu"`` | ``"relu"``.  The kernel where the shape rule admits it
    (``adoption.decide`` counts the lowering under
    ``pallas_kernel_used_total`` / ``..._fallback_total{reason}``): it reads
    the experts some live lane chose and returns zeros for an idle lane.
    The einsums otherwise: every expert, every lane."""
    use, _reason = adoption.decide(
        "moe_experts",
        moe_experts_checks(h2.shape[0], wgate.shape, wgate.dtype))
    if use:
        return _experts_pallas(h2, gates, live, wgate, wup, wdown, gate=gate)
    return experts_reference(h2, gates, wgate, wup, wdown, gate)


# -- the two-matrix form: relu(x @ up^T)^2 @ down ----------------------------

def relu2_reference(h2, gates, up, down):
    """sum_e gates[b, e] * (relu(h2[b] @ up_e^T)^2 @ down_e), ``up`` and
    ``down`` [E, F, H]: all experts over all lanes, the unchosen weighted
    zero.  Rounded as the kernel rounds: inputs in the weights' dtype,
    float32 sums, the squared activation in the weights' dtype."""
    hx = h2.astype(up.dtype)
    h = jnp.einsum("bh,efh->ebf", hx, up, preferred_element_type=jnp.float32)
    # relu(h)^2 as h * relu(h): XLA:CPU (jaxlib 0.9.0) fuses a bare
    # max(dot, 0) into the batched dot's epilogue and then has no bfloat16
    # thunk for it ("Unsupported element type for DotThunk")
    act = h * jax.nn.relu(h)
    y = jnp.einsum("ebf,efh->ebh", act.astype(down.dtype), down,
                   preferred_element_type=jnp.float32)
    return jnp.sum(y * gates.T[:, :, None], axis=0)


def f_rows(hidden, ffn, dtype):
    """Rows of ``up`` and of ``down`` a grid step reads: the largest divisor
    of ``ffn`` that is whole sublane tiles of ``dtype`` and whose two
    blocks, double buffered, fit ``_BLOCK_BUDGET``; 0 where none does."""
    tile = _SUBLANES[jnp.dtype(dtype).name]
    itemsize = jnp.dtype(dtype).itemsize
    for n in range(1, ffn // tile + 1):
        fr = ffn // n
        if ffn % n == 0 and fr % tile == 0 \
                and 4 * hidden * fr * itemsize <= _BLOCK_BUDGET:
            return fr
    return 0


def relu2_checks(rows, w_shape, w_dtype):
    """Ordered (reason, ok) pairs for adoption.decide(): what the two-matrix
    kernel needs of ``rows`` lanes and of ``up`` ``[E, F, H]`` in
    ``w_dtype`` (``down`` has the same shape)."""
    dims = tuple(w_shape) + (rows,)
    static = all(isinstance(x, int) and x >= 0 for x in dims)
    rank = len(w_shape) == 3
    tile = _SUBLANES.get(jnp.dtype(w_dtype).name)
    shaped = static and rank and tile is not None
    fr = f_rows(w_shape[2], w_shape[1], w_dtype) \
        if shaped and min(w_shape) > 0 else 0
    return [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("symbolic_shape", static),
        ("rank", rank),
        ("dtype", tile is not None),
        # H fills whole lanes; F whole sublane tiles (its chunks then do)
        ("lanes", shaped and w_shape[2] % 128 == 0
         and w_shape[1] % tile == 0),
        ("empty", static and all(x > 0 for x in dims)),
        ("vmem", fr > 0 and _vmem_bytes(
            -(-rows // tile) * tile, w_shape[2], fr,
            jnp.dtype(w_dtype).itemsize) <= _VMEM_LIMIT),
    ]


def _relu2_kernel(order_ref, n_ref, x_ref, gates_ref, up_ref, down_ref,
                  out_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _start():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        # [rows, H] x [fr, H]^T: the contraction over H is whole within the
        # chunk, so the activation is this chunk's columns of the expert's
        h = jax.lax.dot_general(
            x_ref[...], up_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [rows, fr]
        act = jnp.square(jnp.maximum(h, 0.0))
        y = jnp.dot(act.astype(down_ref.dtype), down_ref[...],
                    preferred_element_type=jnp.float32)      # [rows, H]
        col = jax.lax.broadcasted_iota(jnp.int32, gates_ref.shape, 1)
        gate = jnp.sum(jnp.where(col == order_ref[i], gates_ref[...], 0.0),
                       axis=1, keepdims=True)
        out_ref[...] += gate * y


def _relu2_pallas(h2, gates, live, up, down, fr=None, interpret=None):
    """``relu2_experts`` on the kernel: an idle lane's gates count as
    zeros.  ``fr`` None follows ``f_rows``; ``interpret`` None follows the
    backend."""
    b, hidden = h2.shape
    e, ffn, _h = up.shape
    if fr is None:
        fr = f_rows(hidden, ffn, up.dtype)
    if interpret is None:
        interpret = adoption.interpret()
    nj = ffn // fr
    tile = _SUBLANES[up.dtype.name]
    pad = (0, -b % tile), (0, 0)
    x = jnp.pad(h2.astype(up.dtype), pad)
    gates = jnp.pad(jnp.where(live[:, None], gates.astype(jnp.float32), 0.0),
                    pad)
    order, n_hit = hit_order(gates)
    rows = x.shape[0]
    whole = lambda i, j, order, n: (0, 0)
    # past the hit experts every step names the last block fetched
    chunk = pl.BlockSpec((None, fr, hidden), lambda i, j, order, n:
                         (order[i], jnp.where(i < n[0], j, nj - 1), 0))
    out = pl.pallas_call(
        _relu2_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, nj),
            in_specs=[pl.BlockSpec((rows, hidden), whole),
                      pl.BlockSpec((rows, e), whole), chunk, chunk],
            out_specs=pl.BlockSpec((rows, hidden), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=RELU2_KERNEL_NAME,
        interpret=interpret,
    )(order, n_hit, x, gates, up, down)
    return out[:b]


def relu2_experts(h2, gates, live, up, down):
    """``routed_experts`` for two-matrix experts, ``up`` and ``down`` [E, F,
    H]: the kernel where ``relu2_checks`` admits the shapes (counted as
    ``moe_experts``, like the three-matrix form), reading the experts some
    live lane chose; ``relu2_reference`` otherwise."""
    use, _reason = adoption.decide(
        "moe_experts", relu2_checks(h2.shape[0], up.shape, up.dtype))
    if use:
        return _relu2_pallas(h2, gates, live, up, down)
    return relu2_reference(h2, gates, up, down)
