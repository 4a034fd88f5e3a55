"""Block capture: lower a whole Block's op list into ONE pure JAX function.

This replaces the reference's op-at-a-time interpreter
(``paddle/fluid/framework/executor.cc:448`` — `for op in ops: op->Run`) with
whole-block staging: every op's registered lowering is traced into a single
XLA computation which `jax.jit` compiles once per (program, shapes) key.  This
is the TPU-idiomatic execution model — XLA fuses across op boundaries, plans
HBM, and overlaps collectives; per-op dispatch only exists in dygraph mode.
"""

import contextlib

import jax
import jax.numpy as jnp

from .registry import get_op_def, _lower_attrs

__all__ = ["LowerCtx", "BlockPlan", "analyze_block", "analyze_param_carry",
           "build_block_fn"]


class LowerCtx:
    """Per-op context handed to lowerings.

    Carries the PRNG key (functional randomness — TPU-native replacement for
    the reference's per-device curand generators), the op desc being lowered,
    and mesh/axis info when lowering inside a shard_map (manual collectives).
    """

    def __init__(self, rng_key=None, op=None, block=None, mesh=None,
                 axis_names=(), mode="traced", runner=None, env=None,
                 data_axis=None):
        self._rng_key = rng_key
        self._rng_n = 0
        self.op = op
        self.block = block
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.mode = mode  # "traced" | "abstract" | "eager"
        # which mesh axis (if any) shards the BATCH dim of feeds —
        # sequence-parallel ops must not mistake it for a sequence axis
        self.data_axis = data_axis
        self.runner = runner  # BlockRunner for ops with sub-blocks
        # live name->value environment of the enclosing block trace; used by
        # control-flow ops (while/conditional_block) whose sub-blocks read
        # outer variables (analog of the reference's kid-scope chain,
        # paddle/fluid/framework/scope.h:46)
        self.env = env

    def run_sub_block(self, block_idx, env, base_key=None):
        """Run every op of a sub-block against `env` (in place)."""
        block = self.block.program.block(block_idx)
        for i, op in enumerate(block.ops):
            key = None
            if base_key is not None:
                key = jax.random.fold_in(base_key, i)
            run_op(op, env, key, mesh=self.mesh, axis_names=self.axis_names)

    def rows_axis(self, lead):
        """The mesh axis a row-wise lowering may make manual (a shard_map
        around its own kernel call) in a program XLA partitions
        automatically, or None: the data axis, where it is the mesh's only
        axis of size over 1 and divides the operand's leading dimension
        ``lead``.  None off a mesh, inside a shard_map (``axis_names``: the
        op runs per shard already), on a dp x tp mesh, for a ragged batch,
        and under FLAGS_deterministic_reduction, which pins the operands
        replicated for one summation order that a per-shard sum of the
        small gradients cannot keep."""
        mesh, axis = self.mesh, self.data_axis
        if mesh is None or self.axis_names or axis is None:
            return None
        from .. import flags as _flags

        n = mesh.shape[axis]
        if (n <= 1 or mesh.size != n or not isinstance(lead, int)
                or lead % n or _flags.flag("deterministic_reduction")):
            return None
        return axis

    def rng(self):
        if self._rng_key is None:
            if self.mode == "abstract":
                return jax.random.key(0)
            raise RuntimeError(
                "op %s requested randomness but no PRNG key is available"
                % (self.op.type if self.op else "?")
            )
        k = jax.random.fold_in(self._rng_key, self._rng_n)
        self._rng_n += 1
        return k

    def amp_bf16(self):
        """True when the program requests the bf16 mixed-precision policy
        (set by paddle_tpu.contrib.mixed_precision.decorate)."""
        blk = self.block
        prog = blk.program if blk is not None else None
        return bool(getattr(prog, "_amp_bf16", False))

    @classmethod
    def abstract(cls, n_rng=0):
        return cls(mode="abstract")


def _iter_runtime_ops(block):
    for op in block.ops:
        if op.type in ("feed", "fetch"):
            continue
        yield op


def analyze_block(block, feed_names):
    """Liveness analysis: which names must come from the scope (external),
    and which persistables are (re)written and must be stored back."""
    feed = set(feed_names)
    written = set()
    external = []
    external_set = set()
    for op in _iter_runtime_ops(block):
        for name in op.input_arg_names:
            if not name:
                continue
            if name in feed or name in written or name in external_set:
                continue
            if name.endswith("@GRAD") or "@GRAD@" in name:
                # grad var not yet produced: implicit zeros (handled by the
                # grad lowering), never an external scope read
                continue
            v = block._find_var_recursive(name)
            if v is not None and getattr(v, "type", None) == "LOD_TENSOR_ARRAY":
                # tensor arrays are trace-local (Python lists in the env),
                # never scope-resident; first write creates them
                continue
            external.append(name)
            external_set.add(name)
        for name in op.output_arg_names:
            if name:
                written.add(name)
    persist_written = []
    for op in _iter_runtime_ops(block):
        for name in op.output_arg_names:
            if not name or name in feed:
                continue
            v = block._find_var_recursive(name)
            if v is not None and v.persistable and name not in persist_written:
                persist_written.append(name)
    return external, written, persist_written


class BlockPlan:
    """Compiled execution plan for one block + feed/fetch signature."""

    def __init__(self, block, feed_names, fetch_names, allow_carry=False):
        self.block = block
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        ext, written, persist_written = analyze_block(block, feed_names)
        self.external = ext
        self.persist_written = persist_written
        # external names that get overwritten -> donatable (read-write)
        self.rw_names = [n for n in ext if n in set(persist_written)]
        rw = set(self.rw_names)
        self.ro_names = [n for n in ext if n not in rw]
        # layout-matched param carry (FLAGS_layout_match_params): persistent
        # f32 weights whose every read is a bf16 matmul/conv consumption
        # enter the compiled step as bf16 arrays pinned across steps
        self.carry_names = (
            analyze_param_carry(block, self.feed_names, fetch_names,
                                self.ro_names, self.rw_names)
            if allow_carry else [])
        if self.carry_names:
            carried = set(self.carry_names)
            # read-only carried params drop out of the f32 argument list
            # entirely: the trace only ever sees their bf16 carry copy
            self.ro_names = [n for n in self.ro_names if n not in carried]


# forward op types whose lowerings consume their (weight) operands in bf16
# under the AMP policy — the set a carried param may be read by.  The
# synthesized `<type>_grad` ops replay the forward via jax.vjp, so they
# consume the same bf16 value and yield a bf16 cotangent (the same value
# the old astype-vjp upcast produced, so the optimizer's astype(f32) is
# bitwise-identical to the per-step-cast scheme).
_CARRY_CONSUMERS = frozenset((
    "mul", "matmul", "matmul_v2", "conv2d", "depthwise_conv2d",
))

# optimizer op types: their "Param" slot must read the f32 MASTER value
# (redirected to <name>@MASTER by _gather_slot), never the bf16 carry
_OPTIMIZER_TYPES = frozenset((
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "lars_momentum", "lamb", "ftrl", "dpsgd",
    "fused_sgd", "fused_momentum", "fused_adam",
))

# ops with sub-blocks read outer vars through ctx.env without appearing in
# the top-level input scan — carry analysis cannot see those reads
_SUBBLOCK_OPS = frozenset((
    "while", "conditional_block", "recurrent", "py_func",
))

_MASTER_SUFFIX = "@MASTER"


def analyze_param_carry(block, feed_names, fetch_names, ro_names, rw_names):
    """Names of persistable f32 params safe to pin in bf16 across steps.

    Eligible: every reader is either (a) an optimizer op reading the param
    via its "Param" slot (redirected to the f32 master inside the trace) or
    (b) one forward op in _CARRY_CONSUMERS plus at most one matching grad
    op (whose vjp replay consumes the identical bf16 value); the only
    writer, if any, is that optimizer's in-place ParamOut.  Feed/fetch
    targets and blocks containing sub-block ops are excluded — a fetched
    param must come back f32, and sub-blocks read outer vars invisibly to
    this scan.  The single-forward-consumer rule keeps gradient
    accumulation out of scope: two bf16 branch grads would sum in bf16
    where the per-step-cast scheme summed their f32 upcasts."""
    import numpy as np

    from ..framework import dtype_to_np

    if any(op.type in _SUBBLOCK_OPS for op in block.ops):
        return []
    prog = block.program
    if not getattr(prog, "_amp_bf16", False):
        return []
    candidates = [n for n in list(ro_names) + list(rw_names)
                  if n not in set(feed_names) and n not in set(fetch_names)]
    readers = {}
    writers = {}
    for op in _iter_runtime_ops(block):
        for n in op.input_arg_names:
            if n:
                readers.setdefault(n, []).append(op)
        for n in op.output_arg_names:
            if n:
                writers.setdefault(n, []).append(op)
    out = []
    for n in candidates:
        v = block._find_var_recursive(n)
        if v is None or not v.persistable or v.shape is None:
            continue
        try:
            if v.dtype is None or dtype_to_np(v.dtype) != np.float32:
                continue
        except Exception:
            continue
        n_fwd = n_grad = 0
        ok = True
        for op in readers.get(n, ()):  # classify every reader
            if (op.type in _OPTIMIZER_TYPES
                    and n in op.input("Param")):
                continue  # master read (redirected inside the trace)
            if op.type in _CARRY_CONSUMERS:
                n_fwd += 1
            elif (op.type.endswith("_grad")
                    and op.type[:-5] in _CARRY_CONSUMERS):
                n_grad += 1
            else:
                ok = False
                break
        if not ok or n_fwd != 1 or n_grad > 1:
            continue
        for op in writers.get(n, ()):  # only in-place optimizer ParamOut
            if not (op.type in _OPTIMIZER_TYPES
                    and n in op.output("ParamOut")):
                ok = False
                break
        if ok:
            out.append(n)
    return out


def _gather_slot(opdef, op, slot, env):
    names = op.input(slot)
    duplicable = slot in opdef.duplicable_inputs
    optional = (
        slot in opdef.optional_inputs
        or slot.startswith("GRAD@")
        or slot.startswith("Out@")
    )
    # layout-matched carry: an optimizer's Param slot must read the f32
    # MASTER value, not the bf16 carry copy the forward/grad ops consume.
    # Only optimizer ops have a "Param" input slot, and carry eligibility
    # already guarantees every other reader wants the bf16 value.
    master = slot == "Param"
    vals = []
    for n in names:
        if not n:
            vals.append(None)
            continue
        if master and (n + _MASTER_SUFFIX) in env:
            vals.append(env[n + _MASTER_SUFFIX])
        elif n in env:
            vals.append(env[n])
        elif optional or n.endswith("@GRAD") or "@GRAD@" in n:
            vals.append(None)
        else:
            raise KeyError(
                "op %s input %s=%r is not initialized (not fed, not in scope, "
                "not produced by a prior op)" % (op.type, slot, n)
            )
    if duplicable:
        return vals
    if not vals:
        return None
    return vals[0]


def _scatter_slot(opdef, op, slot, value, env):
    names = op.output(slot)
    if not names:
        return
    duplicable = slot in opdef.duplicable_outputs
    if duplicable:
        items = list(value) if value is not None else [None] * len(names)
    else:
        items = [value]
    for n, v in zip(names, items):
        if n and v is not None:
            env[n] = v


_AXIS_OPS = frozenset((
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "c_broadcast", "c_allgather", "c_reducescatter",
    "c_shard_slice", "c_allreduce_qsum", "c_reducescatter_q",
    "c_allgather_q",
    "allreduce", "broadcast",
))


def _any_tracer(args):
    for a in args:
        if isinstance(a, (list, tuple)):
            if _any_tracer(a):
                return True
        elif isinstance(a, jax.core.Tracer):
            return True
    return False


def _constrain_replicated(a, sharding):
    """Pin traced op inputs to a replicated layout (deterministic mode).

    Under GSPMD the partitioner picks per-op shardings, and shard-shape-
    dependent kernels (Eigen gemm tiling, fused FMA grouping) reassociate
    f32 sums relative to the single-device program.  Forcing every op to
    consume replicated operands makes the mesh trace reduce in exactly the
    single-device order — bitwise parity, at gather-bandwidth cost.  Only
    tracers are constrained; concrete compile-time constants pass through
    untouched so constant folding keeps working."""
    if isinstance(a, (list, tuple)):
        return type(a)(_constrain_replicated(x, sharding) for x in a)
    if isinstance(a, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(a, sharding)
    return a


def run_op(op, env, rng_key, mesh=None, axis_names=(), runner=None,
           data_axis=None):
    """Lower one op: gather inputs from env, call the lowering, scatter
    outputs back into env."""
    from .registry import record_executed

    opdef = get_op_def(op.type)
    record_executed(op.type)
    args = [_gather_slot(opdef, op, s, env) for s in opdef.input_slots]
    if mesh is not None and not axis_names and op.type not in _AXIS_OPS:
        from .. import flags as _flags

        if _flags.flag("deterministic_reduction"):
            # GSPMD mesh path: replicate every traced operand so sharded
            # and single-device programs sum f32 in the same order (the
            # dp-grad all-reduce becomes gather-then-reduce in canonical
            # order).  Param/feed shardings at the block boundary are
            # untouched — storage stays sharded.
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(mesh, PartitionSpec())
            args = [_constrain_replicated(a, repl) for a in args]
    ctx = LowerCtx(rng_key=rng_key, op=op, block=op.block, mesh=mesh,
                   axis_names=axis_names, runner=runner, env=env,
                   data_axis=data_axis)
    guard = contextlib.nullcontext()
    if mesh is not None and not axis_names and mesh.size > 1:
        # a program XLA partitions automatically: a Pallas kernel is chosen
        # only inside a shard_map its lowering wraps around the call
        # (pallas_kernels/adoption.py, LowerCtx.rows_axis)
        from ..pallas_kernels import adoption

        guard = adoption.auto_partitioned()
    # Constant folding at trace time: ops whose inputs are all trace-time
    # constants evaluate eagerly.  This keeps loop counters / bounds concrete
    # so `while` can unroll and tensor arrays can grow (ops/control_flow.py).
    # Collectives are excluded (lax.axis_index & co. need the enclosing
    # shard_map trace), as are rng-consuming ops when a key is present (the
    # key is usually traced anyway).
    if (op.type not in _AXIS_OPS
            and (opdef.n_rng == 0 or rng_key is None)
            and not _any_tracer(args)
            and jax.process_count() == 1):
        # multi-process excluded: compile-time-eval arrays get committed
        # with shardings spanning non-addressable devices, which cannot be
        # closed over as constants in the per-process trace
        with guard, jax.ensure_compile_time_eval():
            out = opdef.lower(ctx, *args, **_lower_attrs(op.attrs))
    else:
        # the op's type as a scope: metadata only, it reaches the
        # ``op_name`` of every HLO instruction this op lowers to, so a
        # device trace can be grouped by Program op
        with guard, jax.named_scope(op.type):
            out = opdef.lower(ctx, *args, **_lower_attrs(op.attrs))
    if (len(opdef.output_slots) == 1
            and opdef.output_slots[0] in opdef.duplicable_outputs
            and isinstance(out, list)):
        # a bare list from a single-duplicable-output lowering IS the item
        # list — wrap unconditionally so a 1-element list is not mistaken
        # for a positional slot tuple (unstack with num=1, c_sync_comm)
        out = (out,)
    if len(opdef.output_slots) == 1 and not isinstance(out, (tuple, list)):
        out = (out,)
    elif isinstance(out, list):
        out = tuple(out)
    if len(opdef.output_slots) == 1 and len(out) != 1:
        # single duplicable output returned as tuple of items
        out = (list(out),)
    for slot, val in zip(opdef.output_slots, out):
        _scatter_slot(opdef, op, slot, val, env)


def has_collective_ops(block):
    """True if the block contains program-level collectives (fleet/transpiler
    path) that require manual SPMD (shard_map) execution."""
    return any(op.type in _AXIS_OPS for op in block.ops)


def build_spmd_block_fn(plan, mesh, axis="data"):
    """Lower the block for per-rank execution under shard_map: every op runs
    on its shard, collectives (c_*) ride the mesh axis via lax.psum & co.

    This is the TPU-native analog of the reference's one-process-per-GPU
    fleet-collective runtime (transpiler/collective.py + NCCL): rank =
    position along the mesh axis, feeds are batch-sharded, parameters
    replicated.  Fetches come back stacked along the axis (shape [nranks,
    ...] per rank-local value, concatenated on dim 0).
    """
    from jax.sharding import PartitionSpec as P

    block = plan.block
    fetch_names = plan.fetch_names
    persist_written = plan.persist_written

    def _var_spec(name):
        # var-level sharding annotation (tuple of axis names / None per
        # dim, stamped by the ZeRO-1 transpiler) -> PartitionSpec; axis
        # names the mesh does not carry degrade to replicated dims
        v = block._find_var_recursive(name)
        ann = getattr(v, "sharding", None) if v is not None else None
        if not ann:
            return P()
        return P(*[a if a == axis else None for a in ann])

    def local(feeds, params_ro, params_rw, rng):
        # param carry is disabled under SPMD (plan.carry_names empty): the
        # shard_map in/out specs are built per-name and the donation
        # aliasing story differs — carry is a single-process optimization
        env = {}
        env.update(params_ro)
        env.update(params_rw)
        env.update(feeds)
        one_rank = mesh.shape[axis] == 1
        rank = None if one_rank else jax.lax.axis_index(axis)
        for i, op in enumerate(_iter_runtime_ops(block)):
            key = None
            if rng is not None:
                key = jax.random.fold_in(rng, i)
                if rank is not None:  # distinct dropout masks per rank
                    key = jax.random.fold_in(key, rank)
            run_op(op, env, key, mesh=mesh, axis_names=(axis,),
                   data_axis=axis)
        fetches = [env[n] for n in fetch_names]
        updated = {n: env[n] for n in persist_written if n in env}
        return fetches, updated

    nranks = mesh.shape[axis]

    def fn(feeds, params_ro, params_rw, rng):
        feed_specs = {}
        for n, v in feeds.items():
            if v.ndim >= 1 and v.shape[0] % nranks == 0:
                feed_specs[n] = P(axis, *([None] * (v.ndim - 1)))
            else:
                feed_specs[n] = P()  # 0-d / non-divisible: replicate
        param_ro_specs = {n: _var_spec(n) for n in params_ro}
        param_rw_specs = {n: _var_spec(n) for n in params_rw}
        # persist_written defaults to replicated: grads are allreduced before
        # any optimizer write, so params stay bitwise-identical across ranks.
        # Rank-local persistable state (e.g. non-sync batch_norm running
        # stats) resolves to one rank's value — same semantics as the
        # reference's DP, where device-0's copy is the one saved
        # (parallel_executor.cc BCastParamsToDevices / save from scope 0).
        # ZeRO-1 optimizer slots carry a var-level `sharding` annotation
        # (axis-name tuple), which maps straight onto the mesh axis here so
        # each rank holds only its 1/nranks slot shard.
        out_specs = ([P(axis)] * len(fetch_names),
                     {n: _var_spec(n) for n in persist_written})
        sm = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(feed_specs, param_ro_specs, param_rw_specs, P()),
            out_specs=out_specs,
            check_vma=False,
        )
        return sm(feeds, params_ro, params_rw, rng)

    return fn


def build_block_fn(plan, mesh=None, axis_names=(), data_axis=None):
    """Return fn(feeds, params_ro, params_rw, params_carry, rng) ->
    (fetches, updated_rw, updated_carry).

    `data_axis` names the axis of `mesh` that the feeds' batch dimension is
    split over (the GSPMD route; LowerCtx.rows_axis).

    feeds/params are dicts name->array. `rng` is a jax PRNG key; op i uses
    fold_in(rng, i) so randomness is deterministic per (seed, step, op).

    `params_carry` holds the bf16 layout-matched copies of carried params
    (plan.carry_names): inside the trace the f32 master of a carried
    read-write param moves to <name>@MASTER (read only by the optimizer's
    Param slot via _gather_slot) while every forward/grad op reads the bf16
    carry under the original name.  The returned `updated_carry` is the
    next step's carry dict: the f32 ParamOut refreshed to bf16 (the convert
    fuses into the update kernel), or the unchanged donated input for
    read-only carries (aliased, zero-copy)."""
    block = plan.block
    fetch_names = plan.fetch_names
    persist_written = plan.persist_written
    carry_names = list(getattr(plan, "carry_names", ()))

    def fn(feeds, params_ro, params_rw, params_carry, rng):
        env = {}
        env.update(params_ro)
        env.update(params_rw)
        for n in carry_names:
            if n in env:  # rw-carried: keep the f32 master under @MASTER
                env[n + _MASTER_SUFFIX] = env.pop(n)
        env.update(params_carry)
        env.update(feeds)
        for i, op in enumerate(_iter_runtime_ops(block)):
            key = jax.random.fold_in(rng, i) if rng is not None else None
            run_op(op, env, key, mesh=mesh, axis_names=axis_names,
                   data_axis=data_axis)
        fetches = []
        for n in fetch_names:
            if n not in env:
                raise KeyError("fetch target %r was never produced" % n)
            fetches.append(env[n])
        updated = {n: env[n] for n in persist_written if n in env}
        updated_carry = {}
        for n in carry_names:
            v = env[n]  # f32 new master after ParamOut, else the bf16 carry
            if v.dtype != jnp.bfloat16:
                v = v.astype(jnp.bfloat16)
            updated_carry[n] = v
        return fetches, updated, updated_carry

    return fn
