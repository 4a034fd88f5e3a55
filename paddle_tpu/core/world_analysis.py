"""Whole-world static verifier: cross-rank collective-schedule deadlock
analysis + static liveness/peak-HBM estimation (pre-compile).

PR 4's verifier (``core/analysis.py``) checks ONE rank's program in
isolation.  Every distributed failure we have actually shipped since —
elastic re-quorum rewrites, ZeRO-1 reduce-scatter/all-gather chains,
pre-compiled standby worlds — fails *across* ranks: a collective emitted
in a different order, with a different shape/scale/bucket, or on only a
subset of ranks hangs the whole world at runtime with no diagnostic.
This module materializes every rank's transpiled program for a declared
world, extracts each rank's ordered collective trace, and runs a
lockstep matching simulation:

  DL101  cross-rank collective-sequence mismatch (the static deadlock):
         rank r's k-th collective on a ring differs in op type, or r
         runs fewer/more collectives on the ring than the reference
  DL102  matched collectives disagree on shape/dtype/reduction scale or
         quantization geometry (bucket/wire dtype/orig_shape) — not a
         hang but a silent cross-rank corruption
  DL103  collective emitted under control flow whose condition is
         rank-divergent (derived from per-rank data): the branch may
         take different arms on different ranks, so the collective is
         only *conditionally* matched — a latent hang
  DL104  ring/world membership does not cover the declared mesh:
         endpoints/nranks/c_comm_init disagree with the declared world,
         or main-program rings were never initialized in startup

On the same per-block liveness pass the matcher needs, a static memory
estimator attributes per-replica bytes (``Variable.sharding``-aware, so
ZeRO-1 shard slots count 1/nranks) and reports:

  MEM001  static per-replica peak-HBM estimate (informational):
          resident persistable state + feed arguments + the interval-
          liveness peak of transients — cross-checked against
          ``memory_audit``'s compiled ``memory_analysis`` on CPU tier
  MEM002  donation opportunity the executor is not exploiting
          (e.g. ``program._no_donate`` leaves overwritten persistable
          state undonated, doubling its footprint)
  MEM003  predicted peak exceeds ``FLAGS_hbm_budget_bytes`` — the
          on-chip OOM becomes a readable pre-compile diagnostic

Entry points mirror PR 4's three: ``verify_world()`` is called from
``transpiler/collective.py`` (post-transpile, error mode only — warn
mode leaves the cheap single-rank subset to the executor hook) and from
``distributed/elastic.py`` standby pre-verification (a standby world can
never be adopted with a latent deadlock); ``annotate_rank_checks()``
rides the executor's ``check_before_compile`` escalation; and
``tools/proglint.py --world N --mesh dpxtp [--zero1] [--mem-budget]``
runs it standalone over the bundled model zoo.
"""

import threading

from . import analysis
from .analysis import (ERROR, INFO, WARNING, VerifyReport, _COLLECTIVE_OPS,
                       _block_paths, _runtime_ops)

__all__ = [
    "CollectiveEvent",
    "extract_trace",
    "materialize_world",
    "verify_world",
    "check_world_transpiled",
    "annotate_rank_checks",
    "estimate_program_hbm",
]

# collectives whose OUTPUT is bitwise-uniform across ranks (every rank
# reduces/gathers the same global value) — they SCRUB divergence taint
_UNIFORM_OUT = frozenset((
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "c_allreduce_qsum", "c_broadcast", "c_allgather",
    "c_allgather_q", "allreduce", "broadcast",
))

# collectives whose output is a per-rank SHARD (each rank sees different
# values) — they INTRODUCE divergence even from uniform inputs
_DIVERGENT_OUT = frozenset((
    "c_reducescatter", "c_reducescatter_q", "c_shard_slice",
))

# attrs that must agree on a matched collective (DL102); orig_shape /
# bucket / dtype carry the EQuARX quantization geometry, scale the folded
# 1/nranks reduction average, nranks the shard-world
_MATCH_ATTRS = ("scale", "nranks", "bucket", "dtype", "orig_shape")

_DTYPE_BYTES = {
    "bool": 1, "int8": 1, "uint8": 1, "int16": 2, "float16": 2,
    "bfloat16": 2, "int32": 4, "float32": 4, "int64": 8, "float64": 8,
}

# reentrancy guard: verify_world materializes sibling ranks through
# Collective.transpile, which itself hooks back into check_world_transpiled
_tls = threading.local()


def _materializing():
    return bool(getattr(_tls, "active", False))


class _guard:
    def __enter__(self):
        self._prev = getattr(_tls, "active", False)
        _tls.active = True

    def __exit__(self, *exc):
        _tls.active = self._prev


# ---------------------------------------------------------------------------
# collective trace extraction (+ rank-divergence taint)
# ---------------------------------------------------------------------------


class CollectiveEvent:
    """One collective in one rank's execution order: what would be posted
    to the wire, where it sits in the program, and whether control flow
    above it is rank-divergent."""

    __slots__ = ("op_type", "ring", "block_idx", "op_idx", "block_path",
                 "var", "shape", "dtype", "attrs", "divergent", "via")

    def __init__(self, op_type, ring, block_idx, op_idx, block_path, var,
                 shape, dtype, attrs, divergent, via):
        self.op_type = op_type
        self.ring = ring
        self.block_idx = block_idx
        self.op_idx = op_idx
        self.block_path = block_path
        self.var = var
        self.shape = shape
        self.dtype = dtype
        self.attrs = attrs
        self.divergent = divergent
        self.via = via  # condition var that made the context divergent

    def describe(self):
        return "%s(%s%s) ring %s" % (
            self.op_type, self.var or "?",
            "" if self.shape is None else " " + "x".join(
                str(d) for d in self.shape),
            self.ring)


def _sub_block_idx(op):
    sub = op.attr("sub_block")
    if sub is None:
        return None
    return int(getattr(sub, "idx", sub))


def _cond_var(op):
    """The control-flow condition variable of a sub-block op, if any."""
    if op.type == "while":
        names = op.input("Condition")
    elif op.type == "conditional_block":
        names = op.input("Cond")
    else:
        names = ()
    return names[0] if names else None


def divergence_taint(program):
    """Names whose VALUE may differ across ranks: per-rank data feeds
    (``is_data``) and everything dataflow-derived from them, plus shard-
    producing collective outputs.  Uniform-output collectives scrub the
    taint (an allreduced loss is the same number everywhere, so a branch
    on it is rank-uniform).  Two passes reach the fixed point through
    loop-carried vars."""
    tainted = set()
    for blk in program.blocks:
        for name, v in blk.vars.items():
            if getattr(v, "is_data", False):
                tainted.add(name)

    def walk(blk):
        for op in blk.ops:
            if op.type in ("feed", "fetch"):
                continue
            sub = _sub_block_idx(op)
            if sub is not None and sub < len(program.blocks):
                walk(program.blocks[sub])
            if op.type in _UNIFORM_OUT:
                # the reduced/gathered value is identical on every rank:
                # taint does not pass through, and an in-place allreduce
                # (Out aliases X) leaves the name rank-uniform after it
                tainted.difference_update(
                    n for n in op.output_arg_names if n)
                continue
            if (op.type in _DIVERGENT_OUT
                    or any(n in tainted for n in op.input_arg_names if n)):
                tainted.update(n for n in op.output_arg_names if n)

    for _ in range(2):
        walk(program.global_block())
    return tainted


def extract_trace(program):
    """Every collective in one rank's program, in execution order
    (descending into while/cond/recurrent sub-blocks at the point their
    parent op runs), with ring/shape/dtype/quant attrs and the
    rank-divergent-control-flow bit DL103 keys on."""
    paths = _block_paths(program)
    tainted = divergence_taint(program)
    events = []

    def walk(blk, divergent, via):
        for op_idx, op in enumerate(blk.ops):
            if op.type in ("feed", "fetch"):
                continue
            sub = _sub_block_idx(op)
            if sub is not None and sub < len(program.blocks):
                cond = _cond_var(op)
                cond_div = cond is not None and cond in tainted
                walk(program.blocks[sub], divergent or cond_div,
                     via or (cond if cond_div else None))
                continue
            if op.type not in _COLLECTIVE_OPS:
                continue
            x = (op.input("X") or (None,))[0]
            v = blk._find_var_recursive(x) if x else None
            events.append(CollectiveEvent(
                op.type, op.attr("ring_id"), blk.idx, op_idx,
                paths.get(blk.idx) or None, x,
                tuple(int(d) for d in v.shape) if v is not None and v.shape
                else None,
                getattr(v, "dtype", None),
                {k: op.attr(k) for k in _MATCH_ATTRS
                 if op.attr(k) is not None},
                divergent, via))

    walk(program.global_block(), False, None)
    return events


# ---------------------------------------------------------------------------
# world materialization
# ---------------------------------------------------------------------------


def materialize_world(base_main, base_startup, nranks, nrings=1,
                      endpoints=None):
    """Clone the pristine programs and run the flag-selected gradient
    transpiler once per rank — the same rewrite each process would apply
    — returning ``[(main, startup), ...]`` indexed by rank.  Guarded so
    the transpiler's own post-transpile world hook does not recurse."""
    from ..transpiler.collective import select_grad_transpiler

    if endpoints is None:
        endpoints = ["world-check:%d" % (9000 + r) for r in range(nranks)]
    if len(endpoints) != nranks:
        raise ValueError("endpoints %d != nranks %d"
                         % (len(endpoints), nranks))
    out = []
    with _guard():
        for r in range(nranks):
            main = base_main.clone()
            startup = base_startup.clone()
            # clone() rebuilds only IR state; executor-facing side flags
            # like _no_donate must survive or MEM002 goes blind here
            if getattr(base_main, "_no_donate", False):
                main._no_donate = True
            t = select_grad_transpiler(nrings)
            t.transpile(startup_program=startup, main_program=main, rank=r,
                        endpoints=list(endpoints),
                        current_endpoint=endpoints[r], wait_port=False)
            out.append((main, startup))
    return out


# ---------------------------------------------------------------------------
# DL101/DL102: lockstep schedule matching
# ---------------------------------------------------------------------------


def _by_ring(events):
    rings = {}
    for e in events:
        rings.setdefault(e.ring, []).append(e)
    return rings


def _match_schedules(traces, rep):
    """Lockstep simulation: on each ring, every rank must post the same
    collective sequence as rank 0 (the reference).  The first divergence
    per (rank, ring) is the deadlock point; matched pairs are checked for
    shape/dtype/reduction/quant agreement (DL102)."""
    ref_rings = _by_ring(traces[0])
    for r in range(1, len(traces)):
        got_rings = _by_ring(traces[r])
        for ring in sorted(set(ref_rings) | set(got_rings), key=str):
            ref = ref_rings.get(ring, [])
            got = got_rings.get(ring, [])
            diverged = False
            for k, (ea, eb) in enumerate(zip(ref, got)):
                # a matched collective is the same op on the same tensor;
                # a different var at the same position means the SEQUENCE
                # shifted (an exchange lost or gained upstream), which is
                # the deadlock — not an attr disagreement
                if ea.op_type != eb.op_type or ea.var != eb.var:
                    rep.add(ERROR, "DL101",
                            "collective #%d on ring %s is %s on rank %d "
                            "but %s on rank 0 — the world deadlocks at "
                            "this exchange" % (k, ring, eb.describe(), r,
                                               ea.describe()),
                            eb.block_idx, eb.op_idx, rank=r,
                            block_path=eb.block_path,
                            var_names=tuple(n for n in (eb.var, ea.var)
                                            if n),
                            suggestion="re-transpile every rank from the "
                            "same pristine program and flags")
                    diverged = True
                    break
                _match_attrs(ea, eb, r, k, ring, rep)
            if diverged or len(ref) == len(got):
                continue
            if len(got) < len(ref):
                missing = ref[len(got)]
                rep.add(ERROR, "DL101",
                        "rank %d posts only %d collective(s) on ring %s "
                        "but rank 0 posts %d — rank 0 blocks forever in "
                        "collective #%d %s (rank 0 block %d op %d)"
                        % (r, len(got), ring, len(ref), len(got),
                           missing.describe(), missing.block_idx,
                           missing.op_idx),
                        missing.block_idx, missing.op_idx, rank=r,
                        block_path=missing.block_path,
                        var_names=(missing.var,) if missing.var else (),
                        suggestion="rank %d's program lost this exchange "
                        "(stale/tampered transpile) — rebuild it" % r)
            else:
                extra = got[len(ref)]
                rep.add(ERROR, "DL101",
                        "rank %d posts %d collective(s) on ring %s but "
                        "rank 0 posts only %d — rank %d blocks forever "
                        "in its extra collective #%d %s"
                        % (r, len(got), ring, len(ref), r, len(ref),
                           extra.describe()),
                        extra.block_idx, extra.op_idx, rank=r,
                        block_path=extra.block_path,
                        var_names=(extra.var,) if extra.var else (),
                        suggestion="rank %d's program gained an exchange "
                        "no peer posts — rebuild it" % r)


def _match_attrs(ea, eb, rank, k, ring, rep):
    """DL102 on one matched pair: a shape/dtype/scale/quant disagreement
    doesn't hang, it silently corrupts every participating tensor."""
    diffs = []
    if ea.shape != eb.shape:
        diffs.append("shape %s vs %s" % (
            list(eb.shape or ()), list(ea.shape or ())))
    if ea.dtype != eb.dtype:
        diffs.append("dtype %s vs %s" % (eb.dtype, ea.dtype))
    for attr in _MATCH_ATTRS:
        a, b = ea.attrs.get(attr), eb.attrs.get(attr)
        if a != b:
            diffs.append("%s %r vs %r" % (attr, b, a))
    if not diffs:
        return
    rep.add(ERROR, "DL102",
            "collective #%d on ring %s (%s) disagrees between rank %d "
            "and rank 0: %s" % (k, ring, eb.op_type, rank,
                                "; ".join(diffs)),
            eb.block_idx, eb.op_idx, rank=rank, block_path=eb.block_path,
            var_names=(eb.var,) if eb.var else (),
            suggestion="matched collectives must agree on payload "
            "geometry and reduction/quantization attrs on every rank")


# ---------------------------------------------------------------------------
# DL103: collectives under rank-divergent control flow
# ---------------------------------------------------------------------------


def _check_divergent_control_flow(traces, rep):
    seen = set()
    for r, events in enumerate(traces):
        for e in events:
            if not e.divergent:
                continue
            key = (e.block_idx, e.op_idx, e.op_type, e.via)
            if key in seen:
                continue  # identical programs: report once, not per rank
            seen.add(key)
            rep.add(WARNING, "DL103",
                    "collective %s runs under control flow conditioned "
                    "on %r, which is derived from per-rank data — ranks "
                    "may take different arms and the exchange is only "
                    "conditionally matched (latent hang)"
                    % (e.describe(), e.via or "?"),
                    e.block_idx, e.op_idx, rank=r,
                    block_path=e.block_path,
                    var_names=(e.var,) if e.var else (),
                    suggestion="make the condition rank-uniform (e.g. "
                    "allreduce it) or hoist the collective out of the "
                    "branch")


# ---------------------------------------------------------------------------
# DL104: ring/world membership vs the declared mesh
# ---------------------------------------------------------------------------


def _check_world_coverage(worlds, traces, nranks, mesh, rep,
                          declared_world=None):
    if mesh:
        product = 1
        for d in mesh:
            product *= int(d)
        if int(mesh[0]) != int(nranks):
            rep.add(ERROR, "DL104",
                    "declared mesh %s has data axis %d but the "
                    "collective world exchanges across %d rank(s) — "
                    "the rings do not cover the mesh"
                    % ("x".join(str(d) for d in mesh), int(mesh[0]),
                       nranks),
                    suggestion="the mesh's data axis must equal the "
                    "collective world (model/pipeline axes shard within "
                    "a rank)")
        if declared_world is not None and product != int(declared_world):
            rep.add(ERROR, "DL104",
                    "declared mesh %s covers %d device(s) but the world "
                    "declares %d — %d device(s) would never join any "
                    "ring" % ("x".join(str(d) for d in mesh), product,
                              int(declared_world),
                              abs(product - int(declared_world))),
                    suggestion="pick a mesh whose dp*tp product equals "
                    "the world size")
    for r, (main, startup) in enumerate(worlds):
        meta = getattr(main, "_collective_meta", None) or {}
        if meta.get("nranks") and int(meta["nranks"]) != int(nranks):
            rep.add(ERROR, "DL104",
                    "rank %d was transpiled for a %s-rank world but the "
                    "declared world has %d" % (r, meta["nranks"], nranks),
                    rank=r,
                    suggestion="re-transpile for the declared endpoints")
        eps = meta.get("endpoints") or ()
        if eps and len(eps) != int(nranks):
            rep.add(ERROR, "DL104",
                    "rank %d's endpoint list has %d member(s) but the "
                    "declared world has %d" % (r, len(eps), nranks),
                    rank=r)
        init = {}
        for blk in startup.blocks:
            for op_idx, op in _runtime_ops(blk):
                if op.type != "c_comm_init":
                    continue
                ring = op.attr("ring_id")
                init[ring] = (op_idx, op)
                got = op.attr("nranks")
                if got is not None and int(got) != int(nranks):
                    rep.add(ERROR, "DL104",
                            "rank %d initializes ring %s for %d rank(s) "
                            "but the declared world has %d"
                            % (r, ring, int(got), nranks),
                            blk.idx, op_idx, rank=r,
                            suggestion="startup c_comm_init must cover "
                            "the whole declared world")
        used = {e.ring for e in traces[r] if e.ring is not None}
        for ring in sorted(used - set(init), key=str):
            ev = next(e for e in traces[r] if e.ring == ring)
            rep.add(ERROR, "DL104",
                    "rank %d posts collectives on ring %s but startup "
                    "never runs c_comm_init for it — the communicator "
                    "does not exist" % (r, ring),
                    ev.block_idx, ev.op_idx, rank=r,
                    block_path=ev.block_path,
                    suggestion="transpile startup and main together so "
                    "every used ring is initialized")


# ---------------------------------------------------------------------------
# MEM001-003: static liveness / peak-HBM estimator
# ---------------------------------------------------------------------------


def _var_bytes(v, batch, mesh_axes, shape_override=None):
    """Per-replica bytes of one program var.  ``-1`` dims resolve to
    `batch`; a ``Variable.sharding`` annotation divides the sharded dims
    by the mesh axis size (ZeRO-1 state slots, SPMD params); bare data
    feeds are batch-sharded over the data axis."""
    shape = shape_override if shape_override is not None else (v.shape or ())
    dims = [int(batch) if int(d) < 0 else int(d) for d in shape]
    axes = mesh_axes or {}
    sharding = getattr(v, "sharding", None)
    if sharding:
        for i, ax in enumerate(sharding):
            if ax and i < len(dims) and int(axes.get(ax, 1)) > 1:
                dims[i] = -(-dims[i] // int(axes[ax]))
    elif getattr(v, "is_data", False) and dims \
            and shape_override is None and int(axes.get("data", 1)) > 1:
        dims[0] = -(-dims[0] // int(axes["data"]))
    numel = 1
    for d in dims:
        numel *= max(int(d), 0)
    return numel * _DTYPE_BYTES.get(getattr(v, "dtype", None), 4)


# horizontal optimizer fusion (ir.py fuse_optimizer_ops_pass) lowers each
# group through flat concatenated buffers: XLA materializes one
# full-group-size temp per state slot the lowering concatenates
# (ops/optimizer_ops.py: fused_adam flattens Grad and both moments and
# reads each Param where it lies; fused_momentum and fused_sgd flatten
# the params too).  Scalar accumulators (beta pows) don't rate a slot.
_FUSED_FLAT_SLOTS = {
    "adam": ("Grad", "Moment1", "Moment2"),
    "momentum": ("Param", "Grad", "Velocity"),
    "sgd": ("Param", "Grad"),
}


def _fused_optimizer_loads(program, block, nbytes):
    """Point loads [(op_idx, bytes)] for the flat temp buffers of fused
    optimizer updates.  Covers both an already-fused program (the
    executor applies the pass in place before check_before_compile) and
    a pristine one — there the fusion the executor WILL apply is
    predicted with the pass's own grouping rules (per type+LR+dtype,
    rank <= MAX_FUSED_RANK, >= MIN_GROUP members)."""
    loads = []
    fused_seen = False
    for i, op in enumerate(block.ops):
        base = op.type[len("fused_"):] if op.type.startswith("fused_") \
            else None
        if base in _FUSED_FLAT_SLOTS:
            fused_seen = True
            group = sum(nbytes(n) for n in op.input("Param"))
            loads.append((i, group * len(_FUSED_FLAT_SLOTS[base])))
    if fused_seen:
        return loads
    from .. import flags
    from ..ir import FuseOptimizerOpsPass

    if not flags.flag("fuse_optimizer_ops"):
        return loads
    groups = {}
    for i, op in enumerate(block.ops):
        if op.type not in _FUSED_FLAT_SLOTS:
            continue
        pname = op.input("Param")[0]
        pv = block._find_var_recursive(pname)
        if pv is None or pv.shape is None:
            continue
        if len(pv.shape) > FuseOptimizerOpsPass.MAX_FUSED_RANK:
            continue
        lr = (op.input("LearningRate") or [None])[0]
        last_idx, total, count = groups.get((op.type, lr, pv.dtype),
                                            (0, 0, 0))
        groups[(op.type, lr, pv.dtype)] = (i, total + nbytes(pname),
                                           count + 1)
    for (op_type, _lr, _dt), (last_idx, total, count) in groups.items():
        if count >= FuseOptimizerOpsPass.MIN_GROUP:
            loads.append((last_idx,
                          total * len(_FUSED_FLAT_SLOTS[op_type])))
    return loads


def estimate_program_hbm(program, feed_names=None, fetch_names=(), batch=1,
                         mesh_axes=None, feed_shapes=None):
    """Interval-liveness peak-HBM estimate for ONE rank's program,
    pre-compile.  Mirrors what XLA's ``memory_analysis`` budget counts:

      resident   every persistable the step touches (params, optimizer
                 state, bf16 carries) — argument buffers, live end to end
      feeds      data arguments (live end to end: args are not donated)
      transient  interval liveness of every intermediate — def at first
                 write, dead after last read; fetched intermediates stay
                 live to program end (they become output buffers)

    ``peak_bytes = resident + feeds + max_t transient(t)``.  Sub-block
    transient peaks load the parent op's time step.  `feed_shapes` maps
    feed name -> concrete shape (the executor passes the real batch);
    otherwise ``-1`` dims resolve to `batch`."""
    block = program.global_block()
    feed_shapes = dict(feed_shapes or {})
    if feed_names is None:
        feed_names = [n for n, v in sorted(block.vars.items())
                      if getattr(v, "is_data", False)]
    feed_set = set(feed_names)
    fetch_set = set(fetch_names or ())
    if feed_shapes and batch == 1:
        for shp in feed_shapes.values():
            if shp:
                batch = max(batch, int(shp[0]))

    def nbytes(name, blk):
        v = blk._find_var_recursive(name)
        if v is None or getattr(v, "type", None) == "LOD_TENSOR_ARRAY":
            return 0
        return _var_bytes(v, batch, mesh_axes,
                          shape_override=feed_shapes.get(name))

    resident_names, feed_bytes = set(), 0
    for name in feed_set:
        feed_bytes += nbytes(name, block)

    def transient_peak(blk, extra_loads=()):
        ops = [(i, op) for i, op in enumerate(blk.ops)
               if op.type not in ("feed", "fetch")]
        first_write, last_read = {}, {}
        sub_loads = list(extra_loads)
        for i, op in ops:
            sub = _sub_block_idx(op)
            if sub is not None and sub < len(program.blocks):
                sub_loads.append((i, transient_peak(program.blocks[sub])))
            for name in op.input_arg_names:
                if name:
                    last_read[name] = i
            for name in op.output_arg_names:
                if name:
                    first_write.setdefault(name, i)
                    last_read.setdefault(name, i)
        n = len(blk.ops) + 1
        delta = [0] * (n + 1)
        for name, start in first_write.items():
            if name in feed_set:
                continue
            v = blk._find_var_recursive(name)
            if v is None or v.persistable:
                resident_names.add(name)
                continue
            b = nbytes(name, blk)
            if not b:
                continue
            end = n - 1 if name in fetch_set else last_read.get(name, start)
            delta[start] += b
            delta[end + 1] -= b
        for i, load in sub_loads:
            delta[i] += load
            delta[i + 1] -= load
        peak = cur = 0
        for d in delta:
            cur += d
            peak = max(peak, cur)
        return peak

    transient = transient_peak(block, _fused_optimizer_loads(
        program, block, lambda name: nbytes(name, block)))
    # persistables read from the scope (ro/rw args) — including ones only
    # ever read, which the transient scan above never sees
    for blk in program.blocks:
        for op in blk.ops:
            if op.type in ("feed", "fetch"):
                continue
            for name in list(op.input_arg_names) + list(op.output_arg_names):
                if not name or name in feed_set:
                    continue
                v = blk._find_var_recursive(name)
                if v is not None and v.persistable:
                    resident_names.add(name)
    resident = sum(nbytes(name, block) for name in sorted(resident_names))
    out_bytes = sum(nbytes(name, block) for name in sorted(fetch_set))

    # donation audit: overwritten persistable state is normally donated by
    # the executor (the update aliases the argument buffer); _no_donate
    # programs pay for both copies
    from .lowering import analyze_block

    ext, _written, persist_written = analyze_block(block, feed_names)
    rw_names = [n for n in ext if n in set(persist_written)]
    rw_bytes = sum(nbytes(name, block) for name in rw_names)
    no_donate = bool(getattr(program, "_no_donate", False))
    peak = resident + feed_bytes + transient + (rw_bytes if no_donate else 0)
    return {
        "peak_bytes": int(peak),
        "resident_bytes": int(resident),
        "feed_bytes": int(feed_bytes),
        "transient_peak_bytes": int(transient),
        "output_bytes": int(out_bytes),
        "rw_bytes": int(rw_bytes),
        "rw_names": list(rw_names),
        "no_donate": no_donate,
        "batch": int(batch),
        "n_resident": len(resident_names),
    }


def _fmt_mb(b):
    return "%.1f MB" % (b / 1e6)


def check_memory(program, rep, rank=None, budget=None, batch=1,
                 mesh_axes=None, feed_names=None, fetch_names=(),
                 feed_shapes=None):
    """MEM001 estimate + MEM002 donation audit + MEM003 budget gate for
    one rank's program; returns the estimate dict."""
    est = estimate_program_hbm(program, feed_names=feed_names,
                               fetch_names=fetch_names, batch=batch,
                               mesh_axes=mesh_axes, feed_shapes=feed_shapes)
    # engine-owned paged KV pools (serving/kv_cache.py) are allocated
    # OUTSIDE any Program's scope but are just as resident on the chip —
    # fold live caches into the static peak so a decode replica's MEM003
    # budget gate sees them.  The pool bytes already INCLUDE the prefix
    # cache's evictable blocks: cached prefixes live inside the planned
    # pool (zero-ref blocks parked for reuse, reclaimed on demand), so a
    # warm cache never grows the peak beyond this estimate
    try:
        import sys

        _kvmod = sys.modules.get("paddle_tpu.serving.kv_cache")
        kv_bytes = int(_kvmod.engine_owned_kv_bytes()) if _kvmod else 0
        dec_bytes = int(_kvmod.engine_owned_resident_bytes()) \
            if _kvmod else 0
    except Exception:
        kv_bytes = 0
        dec_bytes = 0
    est["kv_cache_bytes"] = kv_bytes
    est["peak_bytes"] += kv_bytes
    # decode-model weights (target + speculative draft params) are
    # engine-resident the same way the KV pools are
    est["decoder_resident_bytes"] = dec_bytes
    est["peak_bytes"] += dec_bytes
    kv_note = " + kv_cache %s" % _fmt_mb(kv_bytes) if kv_bytes else ""
    if dec_bytes:
        kv_note += " + decoder_params %s" % _fmt_mb(dec_bytes)
    rep.add(INFO, "MEM001",
            "static per-replica peak ~%s (resident %s + feeds %s + "
            "transient %s%s, batch %d)"
            % (_fmt_mb(est["peak_bytes"]), _fmt_mb(est["resident_bytes"]),
               _fmt_mb(est["feed_bytes"]),
               _fmt_mb(est["transient_peak_bytes"]), kv_note,
               est["batch"]),
            rank=rank)
    if est["no_donate"] and est["rw_bytes"]:
        rep.add(WARNING, "MEM002",
                "%s of overwritten persistable state is NOT donated "
                "(_no_donate) — the step holds both the argument and the "
                "updated copy live (%d var(s), e.g. %s)"
                % (_fmt_mb(est["rw_bytes"]), len(est["rw_names"]),
                   est["rw_names"][0]),
                rank=rank, var_names=tuple(est["rw_names"][:4]),
                suggestion="clear program._no_donate or split the "
                "overwritten state out of the shared scope")
    if budget is None:
        from .. import flags

        budget = flags.flag("hbm_budget_bytes")
    budget = int(budget or 0)
    if budget > 0 and est["peak_bytes"] > budget:
        rep.add(ERROR, "MEM003",
                "predicted per-replica peak %s (%d bytes) exceeds the "
                "FLAGS_hbm_budget_bytes budget %s (%d bytes) — this world "
                "would trip the HBM band edge on chip"
                % (_fmt_mb(est["peak_bytes"]), est["peak_bytes"],
                   _fmt_mb(budget), budget),
                rank=rank,
                suggestion="shrink the batch, enable BENCH_REMAT=1 "
                "recompute, or shard optimizer state "
                "(FLAGS_collective_mode=zero1)"
                + (", or shrink the paged KV pool "
                   "(FLAGS_kv_cache_blocks / FLAGS_kv_cache_dtype=int8)"
                   if kv_bytes else ""))
    return est


# ---------------------------------------------------------------------------
# top-level entry points
# ---------------------------------------------------------------------------


def verify_world(base_main, base_startup, nranks, mesh=None, nrings=1,
                 feed_names=None, fetch_names=(), label=None, actual=None,
                 batch=1, mem_budget=None, collective_mode=None,
                 wire_dtype=None, quant_bucket=None, endpoints=None,
                 declared_world=None):
    """Materialize every rank of the declared world from the PRISTINE
    programs and run the full cross-rank analysis (DL101-104 +
    MEM001-003).  Returns a VerifyReport whose ``.hbm`` attribute holds
    the per-rank estimate dicts.

    `actual` maps rank -> (main, startup) to substitute a rank's REAL
    transpiled programs (the elastic standby view, the transpiler's own
    output) for the pristine-derived materialization — that is how a
    tampered or stale rank shows up as DL101/DL102 against its
    honestly-derived siblings.  `collective_mode` / `wire_dtype` /
    `quant_bucket` temporarily override the transpile-affecting flags so
    a zero1-int8 world can be checked from any flag state."""
    from .. import flags
    from . import telemetry

    nranks = int(nranks)
    if nranks < 1:
        raise ValueError("nranks must be >= 1, got %d" % nranks)
    overrides = {}
    if collective_mode is not None:
        overrides["FLAGS_collective_mode"] = collective_mode
    if wire_dtype is not None:
        overrides["FLAGS_allreduce_dtype"] = wire_dtype
    if quant_bucket is not None:
        overrides["FLAGS_allreduce_quant_bucket"] = int(quant_bucket)
    saved = flags.get_flags(list(overrides)) if overrides else {}
    if overrides:
        flags.set_flags(overrides)
    try:
        worlds = materialize_world(base_main, base_startup, nranks,
                                   nrings=nrings, endpoints=endpoints)
    finally:
        if overrides:
            flags.set_flags(saved)
    for r, progs in (actual or {}).items():
        r = int(r)
        if not 0 <= r < nranks:
            raise ValueError("actual rank %d outside world of %d"
                             % (r, nranks))
        main, startup = progs
        worlds[r] = (main, startup if startup is not None
                     else worlds[r][1])

    rep = VerifyReport(label=label or ("world of %d rank(s)%s"
                                       % (nranks, " mesh %s" % (
                                           "x".join(str(d) for d in mesh),)
                                          if mesh else "")))
    mesh_axes = {}
    if mesh:
        mesh_axes["data"] = int(mesh[0])
        if len(mesh) > 1:
            mesh_axes["model"] = int(mesh[1])
    else:
        mesh_axes["data"] = nranks

    traces = [extract_trace(main) for main, _startup in worlds]
    with _guard():
        _match_schedules(traces, rep)
        _check_divergent_control_flow(traces, rep)
        _check_world_coverage(worlds, traces, nranks, mesh, rep,
                              declared_world=declared_world)
        rep.hbm = []
        for r, (main, _startup) in enumerate(worlds):
            rep.hbm.append(check_memory(
                main, rep, rank=r, budget=mem_budget, batch=batch,
                mesh_axes=mesh_axes, feed_names=feed_names,
                fetch_names=fetch_names))

    telemetry.inc("static_check_world_runs_total")
    telemetry.set_gauge("static_check_world_ranks", nranks)
    if rep.hbm:
        telemetry.set_gauge("static_check_world_peak_bytes",
                            max(h["peak_bytes"] for h in rep.hbm))
    for d in rep.errors + rep.warnings:
        telemetry.inc("static_check_world_findings", 1, rule=d.rule)
    return rep


def check_world_transpiled(pristine_main, pristine_startup, main, startup,
                           rank, nranks, nrings=1):
    """Post-transpile hook (``Collective.transpile``): in ERROR mode,
    materialize the whole world from the pristine clones and check this
    rank's actual output against its siblings — a stale or divergent
    rewrite raises before anything compiles.  Warn mode skips the
    world-level pass (the executor's compile hook still runs the cheap
    single-rank subset); the materializer's own transpiles never
    recurse."""
    if _materializing():
        return None
    mode = analysis._mode()
    if mode != "error":
        return None
    if pristine_main is None or pristine_startup is None:
        return None
    rep = verify_world(pristine_main, pristine_startup, nranks,
                       nrings=nrings,
                       actual={int(rank): (main, startup)},
                       label="post-transpile world of %d (rank %d)"
                             % (nranks, rank))
    return analysis._dispatch(rep, mode)


def annotate_rank_checks(program, rep, feed_names=(), fetch_names=(),
                         feed_shapes=None):
    """The single-rank subset for the executor's ``check_before_compile``
    escalation: DL103 (divergent control flow over this rank's own
    program) + MEM001-003.  No sibling materialization — the executor
    has no pristine base program — so DL101/102/104 stay with
    verify_world's callers."""
    meta = getattr(program, "_collective_meta", None) or {}
    trace = extract_trace(program)
    _check_divergent_control_flow([trace], rep)
    if meta.get("nranks"):
        used = {e.ring for e in trace if e.ring is not None}
        # DL104-lite: rings are per-world resources; a collective on a
        # ring the transpiler never allocated cannot have a communicator
        nrings = int(meta.get("nrings") or 1)
        for ring in sorted(used, key=str):
            if ring is not None and int(ring) >= nrings:
                ev = next(e for e in trace if e.ring == ring)
                rep.add(ERROR, "DL104",
                        "collective on ring %s but this world only "
                        "initializes rings 0..%d" % (ring, nrings - 1),
                        ev.block_idx, ev.op_idx,
                        block_path=ev.block_path)
    mesh_axes = {"data": int(meta["nranks"])} if meta.get("nranks") else None
    check_memory(program, rep, batch=1, mesh_axes=mesh_axes,
                 feed_names=list(feed_names) or None,
                 fetch_names=fetch_names, feed_shapes=feed_shapes)
    return rep
