"""Executor: runs Programs by compiling whole blocks to XLA.

TPU-native analog of ``paddle/fluid/framework/executor.cc:94`` +
``python/paddle/fluid/executor.py:423``.  Instead of interpreting ops one by
one, `run()` builds (and caches) a single jitted function per
(program-version, feed-signature, fetch-list) key: parameters stream in from
the Scope, get donated when the block overwrites them (optimizer update), and
the updated values are stored back.  Data-parallel / sharded execution reuses
the same path with a `jax.sharding.Mesh` (see paddle_tpu.compiler).
"""

import logging
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import (
    CPUPlace,
    Program,
    Variable,
    default_main_program,
    dtype_to_np,
)
from .lowering import BlockPlan, build_block_fn
from .scope import Scope
from . import telemetry as _telemetry
from . import tracing as _tracing

__all__ = ["Executor", "global_scope", "scope_guard", "CarriedStepFn",
           "aot_compile_cached"]

import contextlib
import threading

_RNG_COUNTER_LOCK = threading.Lock()
_STEADY = contextlib.nullcontext()   # what a cache-hit step runs under

# trace-affecting flags must key the cache: a cached executable baked the
# flag value it was traced under, and flipping the flag without a cache miss
# would silently keep the old lowering
_TRACE_FLAGS = ("FLAGS_check_nan_inf", "FLAGS_bn_stat_subsample",
                "FLAGS_layout_match_params", "FLAGS_deterministic_reduction")

_global_scope = Scope()
# Per-thread scope override (same design as framework's default-program TLS):
# role threads (pserver/worker standing in for separate processes) each
# scope_guard their own Scope without racing on the module global; threads
# that never call scope_guard see the main thread's current scope.
_scope_tls = threading.local()


def _is_main_thread():
    return threading.current_thread() is threading.main_thread()


def global_scope():
    if not _is_main_thread() and getattr(_scope_tls, "scope", None) is not None:
        return _scope_tls.scope
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope):
    global _global_scope
    if _is_main_thread():
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old
    else:
        old = getattr(_scope_tls, "scope", None)
        _scope_tls.scope = scope
        try:
            yield
        finally:
            _scope_tls.scope = old


def _fetch_name(f):
    if isinstance(f, Variable):
        return f.name
    if isinstance(f, str):
        return f
    raise TypeError("bad fetch target %r" % (f,))


def as_numpy(t):
    if isinstance(t, jax.Array) and not t.is_fully_addressable:
        # multi-process fetch: materialize this process's shards only (the
        # reference's nccl2-mode trainers likewise see their local loss).
        # Dedupe by global index (replicated copies on several local
        # devices collapse to one) and order batch shards by their dim-0
        # offset; slice objects themselves are unorderable.
        uniq = {}
        for s in t.addressable_shards:
            key = tuple(sl.start or 0 for sl in s.index)
            uniq.setdefault(key, s)
        if not uniq:
            raise RuntimeError(
                "fetch spans no devices addressable by this process")
        arrs = [np.asarray(s.data) for _, s in sorted(uniq.items())]
        if len(arrs) == 1 or arrs[0].ndim == 0:
            return arrs[0]
        return np.concatenate(arrs, axis=0)
    return np.asarray(t)



def _with_seed_counter(fn):
    """Adapt fn(feeds, ro, rw, carry, key) to take a [seed, counter] uint32
    pair, deriving the key inside the trace (no eager key ops per step)."""

    def wrapped(feeds, params_ro, params_rw, params_carry, sc):
        key = jax.random.fold_in(jax.random.key(sc[0]), sc[1])
        return fn(feeds, params_ro, params_rw, params_carry, key)

    return wrapped


class _CompiledPlan:
    """One cache entry.  ``jfn`` is what run() calls: normally an
    AOT-``Compiled`` executable (eager compile on the miss path, possibly
    deserialized from the tier-B disk cache), or the lazy ``jax.jit``
    wrapper when the eager path had to fall back.  ``jit_fn`` keeps the
    jit wrapper either way for tools that need ``.lower()`` (hbm audit)."""

    __slots__ = ("plan", "jfn", "mesh", "data_axis", "jit_fn",
                 "param_shardings")

    def __init__(self, plan, jfn, mesh=None, data_axis=None, jit_fn=None,
                 param_shardings=None):
        self.plan = plan
        self.jfn = jfn
        self.mesh = mesh
        self.data_axis = data_axis
        self.jit_fn = jit_fn if jit_fn is not None else jfn
        # {persistable name: NamedSharding} on the entry's mesh (None
        # without one): where run() wants each ro/rw input, computed once
        # in _build, so the hit path only compares
        self.param_shardings = param_shardings


class _BuildResult:
    """Stage-1 compile product: the BlockPlan plus the raw python callable
    and jit parameters — everything needed to gather/shard inputs and then
    trace, without having traced anything yet."""

    __slots__ = ("plan", "fn", "donate", "mesh", "data_axis",
                 "out_shardings", "param_shardings")

    def __init__(self, plan, fn, donate, mesh=None, data_axis=None,
                 out_shardings=None, param_shardings=None):
        self.plan = plan
        self.fn = fn
        self.donate = donate
        self.mesh = mesh
        self.data_axis = data_axis
        self.out_shardings = out_shardings
        self.param_shardings = param_shardings


# XLA:CPU only: an executable that jax's persistent cache (tier A) served
# runs, serializes and even loads again, but the serialized copy carries no
# object code and dies at its first call ("Function ... not found").  The
# listener counts tier-A hits so the compile path can tell which
# executables must be compiled afresh before they go to tier B.  TPU
# executables are self-contained either way.
_tier_a_hits = [0]


def _count_tier_a_hit(event, **_kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _tier_a_hits[0] += 1


jax.monitoring.register_event_listener(_count_tier_a_hit)


def _compile_past_tier_a(jfn, args):
    """``jfn.lower(*args).compile()`` with jax's persistent cache bypassed
    for this one compile."""
    from jax._src import compilation_cache as _jcc

    old = jax.config.jax_enable_compilation_cache
    try:
        # jax memoizes the is_cache_used verdict at its first compile, so
        # flipping the flag alone is a no-op — reset_cache() forces the
        # re-check (and again after, so tier A resumes)
        jax.config.update("jax_enable_compilation_cache", False)
        _jcc.reset_cache()
        # the in-memory memo (pxla._cached_compilation) would hand back
        # the same tier-A executable for the identical HLO — drop it too
        jax.clear_caches()
        return jfn.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        _jcc.reset_cache()


def aot_compile_cached(jfn, args, disk_key, devices, meta=None):
    """Produce an AOT ``Compiled`` for ``jfn(*args)`` with tier-B disk
    persistence: disk restore -> eager ``lower().compile()`` (serialized
    back) -> ``(None, cstats)`` when the eager path explodes (the caller
    falls back to the lazy jit wrapper).

    ``devices`` is the executable's device assignment in order — the
    place's one device, or the mesh's devices for a sharded program.  A
    restore loads onto exactly these: ``deserialize_and_load`` otherwise
    spreads a one-device executable over every local device and the next
    call dies with "expected N shards".

    Shared by the Program path (``Executor._finalize_compile``) and the
    decode-serving step path (``CarriedStepFn``) — one implementation of
    the restore/compile/serialize discipline."""
    from . import compile_cache as _cc

    devices = list(devices)

    def mkctx():
        # jax.default_device is a single-use context manager
        return (jax.default_device(devices[0]) if len(devices) == 1
                else contextlib.nullcontext())

    tel = _telemetry.enabled()
    cstats = {"source": "fallback", "compile_ms": 0.0}
    compiled = None
    t0 = time.perf_counter()
    if disk_key is not None:
        rspan = _tracing.start_span("executor.cache_restore",
                                    key=disk_key[:12])
        got = _cc.load(disk_key)
        t_read = time.perf_counter()
        rspan.annotate(read_ms=round((t_read - t0) * 1e3, 3))
        if got is not None:
            rspan.annotate(payload_bytes=len(got["payload"]))
            try:
                from jax.experimental import serialize_executable as _se

                with mkctx(), _tracing.phase("executor.cache_load"):
                    compiled = _se.deserialize_and_load(
                        got["payload"], got["in_tree"], got["out_tree"],
                        execution_devices=devices)
                cstats["source"] = "disk"
                rspan.annotate(
                    load_ms=round((time.perf_counter() - t_read) * 1e3, 3))
            except Exception as e:
                compiled = None
                logging.warning(
                    "compile_cache: deserialize of %s failed (%s); "
                    "recompiling", disk_key[:12], e)
                _telemetry.inc("compile_cache_errors_total",
                               kind="deserialize")
                # crc-valid but unloadable (e.g. XLA build drift):
                # drop it so the store below rewrites the entry
                _cc.invalidate(disk_key)
        rspan.annotate(hit=compiled is not None).end()
    if compiled is None:
        cspan = _tracing.start_span("executor.compile")
        try:
            hits0 = _tier_a_hits[0]
            with mkctx():
                t_tr = time.perf_counter()
                lowered = jfn.lower(*args)
                t_lo = time.perf_counter()
                compiled = lowered.compile()
            t_be = time.perf_counter()
            cstats["source"] = "compiled"
            cspan.annotate(lower_ms=round((t_lo - t_tr) * 1e3, 3),
                           backend_ms=round((t_be - t_lo) * 1e3, 3))
            if tel:
                _telemetry.inc("executor_xla_compile_total")
            if disk_key is not None:
                to_store = compiled
                if (devices[0].platform == "cpu"
                        and _tier_a_hits[0] != hits0):
                    _telemetry.inc("compile_cache_roundtrip_retry_total")
                    with mkctx():
                        to_store = _compile_past_tier_a(jfn, args)
                try:
                    from jax.experimental import \
                        serialize_executable as _se

                    _cc.store(disk_key, *_se.serialize(to_store),
                              meta=meta or {})
                except Exception as e:
                    logging.warning(
                        "compile_cache: serialize failed: %s", e)
                    _telemetry.inc("compile_cache_errors_total",
                                   kind="serialize")
                # serialising, the write and, on the CPU, a compile
                # past tier A
                cspan.annotate(
                    store_ms=round((time.perf_counter() - t_be) * 1e3, 3))
        except Exception as e:
            # the lazy path compiles inside the first call — identical
            # semantics, just conflated timing.  Counted: the chip smoke
            # requires executor_aot_fallback_total == 0.
            logging.warning(
                "executor: eager AOT compile failed (%s); falling back "
                "to lazy jit", e)
            _telemetry.inc("executor_aot_fallback_total")
            compiled = None
        cspan.annotate(source=cstats["source"]).end()
    cstats["compile_ms"] = (time.perf_counter() - t0) * 1e3
    return compiled, cstats


def _step_memory(compiled):
    """{"temp_bytes", "alias_bytes"} of an AOT executable, None each where
    there is no executable (the lazy-jit fallback) or it does not say."""
    try:
        stats = compiled.memory_analysis()
        return {"temp_bytes": int(stats.temp_size_in_bytes),
                "alias_bytes": int(stats.alias_size_in_bytes)}
    except Exception:
        return {"temp_bytes": None, "alias_bytes": None}


class CarriedStepFn:
    """AOT-compiled step function with a persistent donated carry — the
    decode-serving analog of the Program path's bf16 param-carry: the
    carry (the paged KV cache) lives on device across steps, every call
    donates it back in, and each compiled executable is filed under a
    small hashable ``key`` its caller holds (the engine: the lane bucket;
    the multi-token step kinds: ``(bucket, width)``), with tier-B disk
    persistence (``aot_compile_cached``).

    ``key_parts`` is a JSON-able description of everything that affects
    the lowering besides the argument signature (model fingerprint, cache
    geometry, trace flags) — it feeds ``compile_cache.raw_artifact_key``.
    ``warmup(key, *args)`` compiles eagerly for the arguments' signature
    (the serving prewarm: the one place the argument tree is flattened and
    described, for the disk key), files the executable under ``key`` and
    says what it needs beside its arguments (``temp_bytes``) and how much
    of them it updates in their own buffers (``alias_bytes``: a carry
    written in place is aliased whole, and its temporaries stay far under
    it).  ``__call__(key, *args)`` is a dict lookup and the executable's
    own call: what it costs does not grow with the number of leaves in
    the arguments.  A key never warmed compiles on the spot and counts
    ``executor_cache_miss_total``, so "zero runtime compiles under decode
    load" stays provable from the same counter the Program path uses.
    Arguments that do not fit the executable filed under their key raise
    (the executable checks its arguments' tree and avals itself) and
    never run."""

    def __init__(self, fn, donate_argnums=(0,), key_parts=None, name=None):
        self._jfn = jax.jit(fn, donate_argnums=donate_argnums)
        self._key_parts = key_parts
        # labels the hit/miss counters (fn=<name>) so a serving stack
        # running several step kinds per model — decode, draft rollout,
        # speculative verify — can prove flat misses per kind;
        # counter_total() still sums across the labels, so the
        # zero-runtime-compile asserts stay one prefix sum
        self._name = name
        # key -> (executable, the signature it was compiled for, its
        # memory)
        self._compiled = {}

    @staticmethod
    def _sig(args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        return (str(tree),
                tuple((tuple(x.shape), str(x.dtype))
                      if hasattr(x, "shape") else (None, str(type(x)))
                      for x in leaves))

    @staticmethod
    def _devices(args):
        """Where the step runs: the device(s) its already-placed arguments
        live on (host arrays follow them; all-host means the default
        device)."""
        devs = {d for x in jax.tree_util.tree_leaves(args)
                if isinstance(x, jax.Array) for d in x.sharding.device_set}
        return sorted(devs, key=lambda d: d.id) or [jax.local_devices()[0]]

    def _disk_key(self, sig, devices):
        from . import compile_cache as _cc

        if not _cc.enabled() or self._key_parts is None:
            return None
        _cc.enable_xla_cache()
        return _cc.raw_artifact_key(
            "carried_step", {"parts": self._key_parts,
                             "sig": [list(map(str, s)) for s in sig[1]],
                             "tree": sig[0],
                             "devices": [d.id for d in devices]})

    def _lazy(self, sig):
        """What stands in for an executable where the eager compile
        failed: the lazy jit, held to the one signature as an executable
        holds itself (a jit alone would trace another shape and run)."""
        def call(*args):
            if self._sig(args) != sig:
                raise TypeError(
                    "%s: arguments do not fit the signature this key was "
                    "warmed with" % (self._name or "carried step"))
            return self._jfn(*args)

        return call

    def warmup(self, key, *args):
        """Eager-compile for these arguments and file the executable under
        ``key``; {"source", "compile_ms", "key", "temp_bytes",
        "alias_bytes"} (``key`` here is the disk key; the last two from
        the executable's ``memory_analysis()``, None where it has none).
        A key already warmed is free (idempotent prewarm), and refuses
        arguments of another signature."""
        sig = self._sig(args)
        held = self._compiled.get(key)
        if held is not None:
            if held[1] != sig:
                raise TypeError(
                    "%s: key %r is warmed for another signature"
                    % (self._name or "carried step", key))
            return dict(held[2], source="memory", compile_ms=0.0, key=None)
        devices = self._devices(args)
        # one span a key: the disk key's, the restore's and the compile's
        # spans are its children, as they are Executor.warmup's
        with _tracing.span("executor.warmup", fn=self._name,
                           key=str(key)) as wspan:
            with _tracing.span("executor.disk_key"):
                disk_key = self._disk_key(sig, devices)
            compiled, cstats = aot_compile_cached(
                self._jfn, args, disk_key, devices,
                meta={"kind": "carried_step"})
            wspan.annotate(source=cstats["source"]).device_memory()
        memory = _step_memory(compiled)
        self._compiled[key] = (
            compiled if compiled is not None else self._lazy(sig),
            sig, memory)
        if _telemetry.enabled():
            labels = {"fn": self._name} if self._name else {}
            _telemetry.inc("executor_cache_miss_total", **labels)
        return dict(memory, source=cstats["source"],
                    compile_ms=cstats["compile_ms"], key=disk_key)

    def executable(self, key):
        """What ``key`` was warmed to: the AOT executable (``cost_analysis``,
        ``memory_analysis``, ``as_text``)."""
        return self._compiled[key][0]

    def __call__(self, key, *args):
        held = self._compiled.get(key)
        if held is None:
            self.warmup(key, *args)
            held = self._compiled[key]
        elif _telemetry.enabled():
            labels = {"fn": self._name} if self._name else {}
            _telemetry.inc("executor_cache_hit_total", **labels)
            _telemetry.inc("executor_steps_total")
        return held[0](*args)


def _cache_key(program, feed_arrays, fetch_names, mesh):
    """(in-memory cache key, trace-flag tuple) of one program signature —
    run and warmup share it, so a warmed signature is a hit in run."""
    from .. import flags as _flags

    trace_flags = tuple(sorted(_flags.get_flags(_TRACE_FLAGS).items()))
    # mesh keyed by content, not id(): a GC'd Mesh's successor can alias the
    # address exactly like the Program case (program._uid)
    mesh_key = None
    if mesh is not None:
        mesh_key = (tuple(mesh.shape.items()),
                    tuple(d.id for d in mesh.devices.flat))
    key = (
        program._uid,
        program.version,
        tuple(sorted((n, a.shape, str(a.dtype))
                     for n, a in feed_arrays.items())),
        tuple(fetch_names),
        mesh_key,
        trace_flags,
    )
    return key, trace_flags


class Executor:
    """Per-place executor with a program cache."""

    def __init__(self, place=None):
        self.place = place if place is not None else CPUPlace()
        self._cache = {}
        self._fuse_attempted = set()

    def reset_device_state(self):
        """Drop every compiled executable and fusion memo.  The elastic
        re-quorum layer (distributed/elastic.py) calls this after
        re-initializing jax.distributed: cached jfns close over the dead
        world's Mesh/devices and must never run again — the next run()
        recompiles against the new backend."""
        self._cache.clear()
        self._fuse_attempted = set()

    def snapshot_state(self, program, predicate=None):
        """Host-copy snapshot of the program's persistable scope state:
        one D2H device_get per tensor, returning {name: np.ndarray} with
        arrays the caller owns (copy=True — later steps can mutate scope
        tensors without corrupting an in-flight background checkpoint
        write).  This is the only step-path cost of an async
        CheckpointManager.save; serialization/crc/rename happen off-thread
        against this dict."""
        if predicate is None:
            predicate = lambda v: v.persistable and not v.is_data  # noqa: E731
        scope = global_scope()
        t0 = time.perf_counter()
        with _tracing.span("executor.snapshot"):
            out = {}
            for var in program.list_vars():
                if not predicate(var):
                    continue
                sv = scope.find_var(var.name)
                if sv is None or not sv.get_tensor()._is_initialized():
                    continue
                out[var.name] = np.array(sv.get_tensor().numpy(), copy=True)
        if _telemetry.enabled():
            _telemetry.observe("executor_snapshot_ms",
                               (time.perf_counter() - t0) * 1e3)
        return out

    def close(self):
        """Release cached executables and notify pservers this trainer is
        done (reference Executor::Close -> SendComplete, executor.cc:110)."""
        for comm in getattr(self, "_ps_comms", []):
            comm.complete()
        self._ps_comms = []
        self._cache.clear()
        # end-of-run telemetry snapshot (metrics.json/.prom under
        # FLAGS_telemetry_dir; atexit covers executors never closed)
        _telemetry.maybe_dump()

    # -- main entry ----------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
    ):
        from ..compiler import CompiledProgram

        scope = scope if scope is not None else global_scope()

        # unwrap CompiledProgram FIRST so PS metadata on the inner program
        # is seen (a wrapped PS trainer must still send/recv)
        mesh = None
        data_axis = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled._program
            mesh = compiled._mesh()
            data_axis = compiled._data_axis

        # parameter-server program: block in the server loop
        # (listen_and_serv_op.cc:110 RunSyncLoop analog)
        if program is not None and getattr(program, "_ps_server", None):
            from ..distributed.ps import run_pserver

            return run_pserver(self, program, scope)

        # one span for the whole call; it nests under whatever span is
        # active on this thread — the serving dispatcher's
        # serving.execute, or a training loop's root — so cross-process
        # traces reach down to the step
        with _tracing.span("executor.step") as sspan:
            t_run = time.perf_counter()
            try:
                return self._run_step(sspan, program, mesh, data_axis, feed,
                                      fetch_list, scope, return_numpy,
                                      use_program_cache)
            finally:
                phases = sspan.take_phases("executor.")
                if phases:
                    if not sspan.attrs.get("cache_hit", True):
                        sspan.device_memory()
                    # host time of the call: all of it but the two
                    # stretches that hand work to the device and wait for
                    # it
                    run_us = int((time.perf_counter() - t_run) * 1e6)
                    sspan.annotate(
                        host_us=run_us - phases.get("executor.dispatch", 0)
                        - phases.get("executor.fetch", 0))

    def _run_step(self, sspan, program, mesh, data_axis, feed, fetch_list,
                  scope, return_numpy, use_program_cache):
        """``run`` below its span.  The cache-hit path is covered end to
        end by ``tracing.phase`` blocks (prepare, shard_feeds,
        shard_params, dispatch, writeback, fetch), which a profile shows
        as host events and the span carries as ``phases``."""
        with _tracing.phase("executor.prepare"):
            fetch_list = fetch_list or []
            fetch_names = [_fetch_name(f) for f in fetch_list]

            # PS trainer program: ensure comms + initial param pull, and fetch
            # this step's grads for the send/recv exchange after the run
            ps_meta = getattr(program, "_ps_trainer", None) \
                if program else None
            ps_grad_names = []
            if ps_meta is not None:
                if getattr(scope, "_ps_comm", None) is None:
                    from ..distributed.ps import TrainerPSComm

                    scope._ps_comm = TrainerPSComm(ps_meta)
                    scope._ps_comm.pull_initial_params(scope)
                    if not hasattr(self, "_ps_comms"):
                        self._ps_comms = []
                    self._ps_comms.append(scope._ps_comm)
                if not ps_meta.get("geo"):
                    # geo-SGD trains locally (no grad sends) — only the
                    # grad-shipping modes need the per-step grad fetch
                    ps_grad_names = [g for g in ps_meta["param_grad"].values()
                                     if g not in fetch_names]
                    fetch_names = fetch_names + ps_grad_names

            if program is None:
                program = default_main_program()

            if not feed:
                # program-driven input: a started non-iterable DataLoader
                # attached to this program supplies the batch (the reference's
                # py_reader `read` op path; raises core.EOFException at end)
                for loader in program._attached_loaders:
                    if loader._started:
                        feed = loader._next_feed()
                        break
            feed = feed or {}

            feed_arrays = {}
            block = program.global_block()
            for name, value in feed.items():
                if isinstance(value, jax.Array):
                    # device-resident feed: never pull back to host for dtype
                    # coercion (x64-disabled JAX can't hold int64 anyway)
                    feed_arrays[name] = value
                    continue
                arr = np.asarray(value)
                v = block._find_var_recursive(name)
                if v is not None and v.dtype is not None \
                        and arr.dtype != dtype_to_np(v.dtype):
                    arr = np.asarray(arr, dtype=dtype_to_np(v.dtype))
                feed_arrays[name] = arr

            # fuse BEFORE the cache key: the pass bumps the program version,
            # so running it inside _compile would orphan the cache entry and
            # force a full recompile on the next step
            self._maybe_fuse_optimizers(program, program.global_block(),
                                        list(feed_arrays), fetch_names)
            key, trace_flags = _cache_key(program, feed_arrays, fetch_names,
                                          mesh)
            tel = _telemetry.enabled()
            entry = self._cache.get(key) if use_program_cache else None
            cache_hit = entry is not None
            build = None
            build_s = 0.0
            if entry is None:
                # static verifier runs only on the compile path (cache
                # misses), memoized per program signature inside
                # check_before_compile — steady-state steps never pay for
                # it, and FLAGS_static_check=off is a single flag read
                from .analysis import check_before_compile
                from . import compile_cache as _cc

                _cc.enable_xla_cache()
                with _tracing.span("executor.build"):
                    check_before_compile(program, list(feed_arrays),
                                         fetch_names, scope=scope,
                                         feed_shapes={n: tuple(a.shape)
                                                      for n, a in
                                                      feed_arrays.items()})
                    t_build = time.perf_counter()
                    build = self._build(program, list(feed_arrays),
                                        fetch_names, mesh, data_axis)
                    build_s = time.perf_counter() - t_build
                plan = build.plan
                if build.mesh is not None and mesh is None:
                    mesh = build.mesh
                    data_axis = build.data_axis
            else:
                plan = entry.plan
                if entry.mesh is not None and mesh is None:
                    mesh = entry.mesh
                    data_axis = entry.data_axis

            # gather params from scope
            params_ro, params_rw = {}, {}
            for n in plan.ro_names:
                params_ro[n] = self._scope_value(scope, n, block)
            for n in plan.rw_names:
                params_rw[n] = self._scope_value(scope, n, block)
            params_carry, carry_hits, carry_converts = self._gather_carry(
                scope, plan, block)
            # host->device transfer volume: numpy feeds cross the PCIe
            # boundary; device-resident jax.Arrays are already there
            feed_bytes = 0
            if tel:
                feed_bytes = sum(int(a.nbytes) for a in feed_arrays.values()
                                 if not isinstance(a, jax.Array))

            # deterministic functional PRNG: (program seed, per-scope step
            # counter).  Locked: pipeline section workers run concurrently
            # against one scope and must never draw the same key.
            seed = program.random_seed or 0
            with _RNG_COUNTER_LOCK:
                counter = scope._rng_counter
                scope._rng_counter = counter + 1
            # key derivation happens inside the compiled fn (kept out of the
            # eager path: one dispatch per key op, every step)
            rng = np.asarray([seed & 0xFFFFFFFF, counter & 0xFFFFFFFF],
                             dtype=np.uint32)

        params_placed = params_passed = 0
        if mesh is not None:
            with _tracing.phase("executor.shard_feeds"):
                feed_arrays = self._shard_feeds(feed_arrays, mesh,
                                                data_axis)
            with _tracing.phase("executor.shard_params"):
                params_ro, params_rw, params_placed, params_passed = \
                    self._place_params(
                        scope, (entry or build).param_shardings, params_ro,
                        params_rw, place_all=entry is None)

        cstats = None
        if entry is None:
            # eager AOT compile (or tier-B cache restore) with the real
            # first-step inputs — shapes, dtypes AND shardings are exactly
            # what every subsequent call passes, and compile_ms stops being
            # conflated with the first step's wall time
            devices = self._devices(mesh)
            with _tracing.span("executor.disk_key"):
                disk_key = self._disk_key(program, plan, feed_arrays,
                                          fetch_names, trace_flags, mesh,
                                          devices)
            entry, cstats = self._finalize_compile(
                build, feed_arrays, params_ro, params_rw, params_carry,
                rng, disk_key, devices)
            if use_program_cache:
                self._cache[key] = entry
        # a place that names no device raises here, every step — it is
        # never the default device
        ctx = (jax.default_device(self.place.jax_device()) if mesh is None
               else contextlib.nullcontext())

        from ..flags import flag as _flag

        if _flag("hbm_audit"):
            from .memory_audit import maybe_audit

            report = maybe_audit(entry, feed_arrays, params_ro, params_rw,
                                 params_carry, rng)
            if report is not None:
                # fold the HBM report into the telemetry dump so one
                # metrics.json answers both "how slow" and "how big"
                _telemetry.set_info("memory_audit", report)

        sspan.annotate(step=int(counter), cache_hit=cache_hit)
        if mesh is not None:
            sspan.annotate(params_placed=params_placed,
                           params_passed=params_passed)
        # the executable's first call, to its fetched result, is part of
        # set-up (on the chip it loads the program)
        with (_STEADY if cache_hit
              else _tracing.device_span("executor.first_run")):
            t_step = time.perf_counter() if tel else 0.0
            try:
                with ctx, _tracing.phase("executor.dispatch"):
                    fetches, updated, updated_carry = entry.jfn(
                        feed_arrays, params_ro, params_rw, params_carry, rng)
            except Exception:
                if params_carry:
                    # the carry inputs were donated: a failed call may have
                    # consumed them, so drop the cache (next run reconverts
                    # from the still-live f32 masters)
                    cache = scope.__dict__.get("_layout_carry_cache") or {}
                    for n in params_carry:
                        cache.pop(n, None)
                if tel:
                    _telemetry.inc("executor_step_errors_total")
                    _telemetry.event("step_error", step=int(counter))
                raise

            with _tracing.phase("executor.writeback"):
                if tel:
                    step_ms = (time.perf_counter() - t_step) * 1e3
                    fetch_bytes = sum(int(getattr(f, "nbytes", 0))
                                      for f in fetches)
                    no_donate = getattr(program, "_no_donate", False)
                    if cache_hit:
                        compile_ms = None
                    elif cstats is not None and cstats["source"] != "fallback":
                        # eager AOT path: plan build + trace/lower + XLA
                        # compile (or tier-B deserialize) — measured apart
                        # from the step
                        compile_ms = build_s * 1e3 + cstats["compile_ms"]
                    else:
                        # lazy fallback: jit compiles inside the first call,
                        # so the pre-PR conflation is the honest number
                        compile_ms = build_s * 1e3 + step_ms
                    _telemetry.record_step(
                        step_ms, cache_hit,
                        compile_ms=compile_ms,
                        donated=0 if no_donate else
                        len(params_rw) + len(params_carry),
                        feed_bytes=feed_bytes, fetch_bytes=fetch_bytes,
                        carry_hits=carry_hits, carry_converts=carry_converts,
                        params_placed=params_placed,
                        params_passed=params_passed)
                    cmeta = getattr(program, "_collective_meta", None)
                    if cmeta and cmeta.get("wire_bytes_per_step"):
                        # analytic bytes-on-ICI for the step's gradient
                        # exchange (stamped by the collective transpiler; see
                        # transpiler/collective.py _wire_bytes)
                        wire = float(cmeta["wire_bytes_per_step"])
                        _telemetry.inc("collective_wire_bytes_total", wire)
                        _telemetry.set_gauge("collective_wire_bytes_per_step",
                                             wire)

                for n, val in updated.items():
                    scope.var(n).set(val)
                if updated_carry:
                    # refresh the carry cache: pair each bf16 copy with the
                    # scope object it mirrors so staleness is caught by
                    # identity (an external scope.set — checkpoint
                    # restore — forces reconvert)
                    cache = scope.__dict__.setdefault(
                        "_layout_carry_cache", {})
                    for n, bf in updated_carry.items():
                        if n in updated:
                            cache[n] = (scope.var(n).get_tensor().get(), bf)
                        elif n in cache:
                            cache[n] = (cache[n][0], bf)
                        else:
                            cache[n] = (None, bf)
                # the step consumed (donated) these inputs and the scope now
                # holds their successors: drop the last references here, while
                # the device works, not at the function's return, after the
                # wait for it
                del params_rw, params_carry, feed_arrays

            # everything below reads device values on the host: the wait for
            # the device is here
            with _tracing.phase("executor.fetch"):
                if _flag("check_nan_inf"):
                    # reference FLAGS_check_nan_inf (operator.cc:947): scan
                    # outputs; block compilation means we check fetches +
                    # updated state vars
                    for name, val in list(zip(fetch_names, fetches)) + list(
                            updated.items()):
                        arr = np.asarray(val)
                        if np.issubdtype(arr.dtype, np.floating) \
                                and not np.isfinite(arr).all():
                            raise RuntimeError(
                                "Operator output contains NaN/Inf: variable "
                                "%r (FLAGS_check_nan_inf)" % name)

                if ps_meta is not None:
                    # send grads -> barrier -> pull params (the transpiler-
                    # rewritten send/recv op sequence, executed by the runtime
                    # so the compiled step stays pure).  Taken from the FULL
                    # fetch list: a grad the user fetches themselves is still
                    # a grad.
                    all_grads = set(ps_meta["param_grad"].values())
                    grad_vals = {
                        name: np.asarray(v)
                        for name, v in zip(fetch_names, fetches)
                        if name in all_grads
                    }
                    scope._ps_comm.step(scope, grad_vals)
                    n_user = len(fetches) - len(ps_grad_names)
                    fetches = fetches[:n_user]

                if return_numpy:
                    return [as_numpy(f) for f in fetches]
                return list(fetches)

    # -- internals -----------------------------------------------------------
    def _devices(self, mesh):
        """The executable's device assignment, in order: the mesh's devices
        for a sharded program, else the place's one device."""
        if mesh is not None:
            return list(mesh.devices.flat)
        return [self.place.jax_device()]

    def _scope_value(self, scope, name, block):
        var = scope.find_var(name)
        if var is None or not var.get_tensor()._is_initialized():
            raise RuntimeError(
                "variable %r is not initialized in scope — run the startup "
                "program first (fluid.Executor.run(fluid.default_startup_program()))"
                % name
            )
        val = var.get_tensor().get()
        v = block._find_var_recursive(name)
        if (
            v is not None
            and v.dtype is not None
            and not isinstance(val, jax.Array)
        ):
            val = np.asarray(val, dtype=dtype_to_np(v.dtype))
        return val

    def _gather_carry(self, scope, plan, block):
        """bf16 layout-matched copies for plan.carry_names, cached per scope
        and validated against the f32 master by OBJECT IDENTITY: as long as
        the scope still holds the exact array the copy was derived from
        (i.e. only the compiled step has updated it), the cached bf16 array
        is current; any external scope.set (checkpoint restore, manual
        assignment) breaks identity and forces a fresh convert.

        Returns (carry dict, cache hits, fresh converts) — the counts feed
        the telemetry step record."""
        carry_names = getattr(plan, "carry_names", None)
        if not carry_names:
            return {}, 0, 0
        cache = scope.__dict__.setdefault("_layout_carry_cache", {})
        out = {}
        hits = converts = 0
        for n in carry_names:
            master = self._scope_value(scope, n, block)
            ent = cache.get(n)
            if ent is not None and ent[0] is master:
                out[n] = ent[1]
                hits += 1
                continue
            bf = jnp.asarray(master).astype(jnp.bfloat16)
            cache[n] = (master, bf)
            out[n] = bf
            converts += 1
        return out, hits, converts

    def _build(self, program, feed_names, fetch_names, mesh, data_axis,
               devices=None):
        """Stage 1 of a compile: BlockPlan + raw callable + jit params.
        No tracing happens here — run()/warmup() gather and shard the real
        inputs first, then _finalize_compile traces with them.  ``devices``
        overrides the SPMD mesh's device list (elastic standby pre-compiles
        a smaller world over a device prefix of the current backend)."""
        from .lowering import build_spmd_block_fn, has_collective_ops

        from .. import flags as _flags

        block = program.global_block()
        no_donate = getattr(program, '_no_donate', False)
        spmd = mesh is None and has_collective_ops(block)
        # layout-matched param carry: single-process, single-device-program,
        # donated programs only — carry buffers alias across steps via
        # donation, and the SPMD/mesh paths spec params per-name
        allow_carry = (
            bool(_flags.flag("layout_match_params"))
            and mesh is None and not spmd and not no_donate
            and jax.process_count() == 1
        )
        plan = BlockPlan(block, feed_names, fetch_names,
                         allow_carry=allow_carry)
        # pipeline sections share param buffers across concurrently
        # running executors — donation would let one section delete an
        # array another still reads (real on TPU; CPU ignores donation).
        # The bf16 carry dict (arg 3) is donated alongside params_rw so a
        # read-only carry aliases its output and survives step to step.
        donate = () if no_donate else (2, 3)
        if spmd:
            # fleet/transpiler collective path: program-level c_* ops ->
            # manual SPMD over all local devices (reference: one process
            # per GPU + NCCL ring; here: shard_map over the mesh axis).
            # Runs even on 1 device (psum over a size-1 axis is identity)
            # so the transpiler's 1/nranks loss-grad scale stays paired
            # with a real — if degenerate — allreduce.
            from jax.sharding import Mesh

            devs = list(devices) if devices is not None else jax.devices()
            mesh = Mesh(np.array(devs), ("data",))
            sfn = build_spmd_block_fn(plan, mesh, axis="data")

            def fn5(feeds, params_ro, params_rw, params_carry, key,
                    _sfn=sfn):
                fetches, updated = _sfn(feeds, params_ro, params_rw, key)
                return fetches, updated, {}

            return _BuildResult(plan, _with_seed_counter(fn5), donate,
                                mesh, "data",
                                param_shardings=self._param_shardings(
                                    mesh, block, plan))
        fn = _with_seed_counter(build_block_fn(plan, mesh=mesh,
                                               data_axis=data_axis))
        if mesh is None:
            return _BuildResult(plan, fn, donate)
        from jax.sharding import NamedSharding, PartitionSpec as P

        replicated = NamedSharding(mesh, P())
        targets = self._param_shardings(mesh, block, plan)
        # the step hands every written persistable back where it takes it:
        # from the second step on an input already has its target
        out_shardings = ([replicated] * len(fetch_names),
                         {n: targets[n] for n in plan.persist_written},
                         {})
        return _BuildResult(plan, fn, donate, out_shardings=out_shardings,
                            param_shardings=targets)

    def _disk_key(self, program, plan, feed_arrays, fetch_names, trace_flags,
                  mesh, devices):
        """Tier-B content key for this executable, or None when the disk
        cache is off."""
        from . import compile_cache as _cc

        if not _cc.enabled():
            return None
        feed_sig = sorted((n, tuple(a.shape), str(a.dtype))
                          for n, a in feed_arrays.items())
        mesh_sig = None
        if mesh is not None:
            mesh_sig = [[str(k), int(v)] for k, v in mesh.shape.items()]
        extra = {
            "donate": not getattr(program, "_no_donate", False),
            # a serialized executable names its devices by id and loads
            # only onto those: the same program compiled for chip 0, for
            # chip 1 and for the 4-chip mesh are three artifacts
            "devices": [d.id for d in devices],
            "carry": sorted(getattr(plan, "carry_names", None) or ()),
        }
        return _cc.artifact_key(program, feed_sig, fetch_names,
                                trace_flags, mesh_sig=mesh_sig,
                                extra=extra)

    def _finalize_compile(self, build, feeds, params_ro, params_rw,
                          params_carry, rng, disk_key, devices):
        """Stage 2: produce the executable for already-gathered inputs.
        Order: tier-B disk restore -> eager jit(...).lower(...).compile()
        (serialized back to disk) -> lazy jit fallback if either explodes.
        Returns (entry, {"source", "compile_ms"})."""
        if build.out_shardings is not None:
            jfn = jax.jit(build.fn, donate_argnums=build.donate,
                          out_shardings=build.out_shardings)
        else:
            jfn = jax.jit(build.fn, donate_argnums=build.donate)
        compiled, cstats = aot_compile_cached(
            jfn, (feeds, params_ro, params_rw, params_carry, rng),
            disk_key, devices,
            meta={"fetch": list(build.plan.fetch_names),
                  "n_feeds": len(feeds)})
        entry = _CompiledPlan(
            build.plan, compiled if compiled is not None else jfn,
            build.mesh, build.data_axis, jit_fn=jfn,
            param_shardings=build.param_shardings)
        return entry, cstats

    def warmup(self, program=None, feed_specs=None, fetch_list=None,
               scope=None, devices=None):
        """Pre-compile `program` for the given feed signature WITHOUT
        running a step: populates the in-memory executable cache and, when
        FLAGS_compile_cache_dir is set, the on-disk tier-B cache (elastic
        standby / serving-bucket prewarm path).

        ``feed_specs`` maps feed name -> concrete array OR (shape, dtype).
        Parameters must already be initialized in ``scope`` (run the
        startup program first).  ``devices`` overrides the SPMD mesh's
        device list (used by elastic standby to compile a smaller world);
        entries built with an override are only written to disk, never
        into the in-memory cache (their mesh is not this world's).

        Returns {"source": "memory"|"disk"|"compiled"|"fallback",
        "compile_ms": float, "key": tier-B key or None}."""
        from ..compiler import CompiledProgram

        scope = scope if scope is not None else global_scope()
        fetch_list = fetch_list or []
        fetch_names = [_fetch_name(f) for f in fetch_list]
        mesh = None
        data_axis = None
        if isinstance(program, CompiledProgram):
            compiled_prog = program
            program = compiled_prog._program
            mesh = compiled_prog._mesh()
            data_axis = compiled_prog._data_axis
        if program is None:
            program = default_main_program()
        block = program.global_block()
        feed_arrays = {}
        for name, spec in (feed_specs or {}).items():
            if isinstance(spec, (tuple, list)) and len(spec) == 2 and \
                    isinstance(spec[0], (tuple, list)):
                shape, dt = spec
                if dt is None:
                    v = block._find_var_recursive(name)
                    dt = dtype_to_np(v.dtype) if v is not None else np.float32
                feed_arrays[name] = np.zeros(tuple(shape), dtype=np.dtype(dt))
            elif isinstance(spec, jax.Array):
                feed_arrays[name] = spec
            else:
                arr = np.asarray(spec)
                v = block._find_var_recursive(name)
                if v is not None and v.dtype is not None and \
                        arr.dtype != dtype_to_np(v.dtype):
                    arr = np.asarray(arr, dtype=dtype_to_np(v.dtype))
                feed_arrays[name] = arr

        from .analysis import check_before_compile
        from . import compile_cache as _cc

        # the warmup span stacks over the whole build+compile so the
        # build/disk_key/cache_restore/compile child spans nest under it
        with _tracing.span("executor.warmup") as wspan:
            self._maybe_fuse_optimizers(program, block, list(feed_arrays),
                                        fetch_names)
            key, trace_flags = _cache_key(program, feed_arrays, fetch_names,
                                          mesh)
            if devices is None and key in self._cache:
                wspan.annotate(source="memory")
                return {"source": "memory", "compile_ms": 0.0, "key": None}
            _cc.enable_xla_cache()
            with _tracing.span("executor.build"):
                check_before_compile(program, list(feed_arrays), fetch_names,
                                     scope=scope,
                                     feed_shapes={n: tuple(a.shape)
                                                  for n, a in
                                                  feed_arrays.items()})
                t0 = time.perf_counter()
                build = self._build(program, list(feed_arrays), fetch_names,
                                    mesh, data_axis, devices=devices)
            plan = build.plan
            if build.mesh is not None and mesh is None:
                mesh = build.mesh
                data_axis = build.data_axis
            params_ro, params_rw = {}, {}
            for n in plan.ro_names:
                params_ro[n] = self._scope_value(scope, n, block)
            for n in plan.rw_names:
                params_rw[n] = self._scope_value(scope, n, block)
            params_carry, _h, _c = self._gather_carry(scope, plan, block)
            rng = np.asarray([(program.random_seed or 0) & 0xFFFFFFFF, 0],
                             dtype=np.uint32)
            if mesh is not None:
                feed_arrays = self._shard_feeds(feed_arrays, mesh,
                                                data_axis)
                params_ro, params_rw, _placed, _passed = \
                    self._place_params(scope, build.param_shardings,
                                       params_ro, params_rw, place_all=True)
            run_devices = self._devices(mesh)
            with _tracing.span("executor.disk_key"):
                disk_key = self._disk_key(program, plan, feed_arrays,
                                          fetch_names, trace_flags, mesh,
                                          run_devices)
            entry, cstats = self._finalize_compile(
                build, feed_arrays, params_ro, params_rw, params_carry,
                rng, disk_key, run_devices)
            wspan.annotate(source=cstats["source"]).device_memory()
        if devices is None:
            self._cache[key] = entry
        ms = (time.perf_counter() - t0) * 1e3
        _telemetry.inc("executor_warmup_total")
        return {"source": cstats["source"], "compile_ms": ms,
                "key": disk_key}

    def _maybe_fuse_optimizers(self, program, block, feed_names,
                               fetch_names):
        """Horizontal optimizer fusion before lowering (reference
        BuildStrategy fuse_all_optimizer_ops): hundreds of tiny
        per-parameter update fusions each pay a fixed launch cost — ~46 ms
        of a 211 ms ResNet-50 step in the round-3 profile.  Attempted once
        per (program, version): only the vectors fuse (ir.py
        MAX_FUSED_RANK), so many programs form no group, and without
        memoization every step would pay a full pass scan that is
        guaranteed to change nothing."""
        key = (program._uid, program.version)
        if key in self._fuse_attempted:
            return
        self._fuse_attempted.add(key)
        from .. import flags as _flags

        f = _flags.get_flags(["FLAGS_fuse_optimizer_ops",
                              "FLAGS_deterministic_reduction"])
        if not f["FLAGS_fuse_optimizer_ops"]:
            return
        if f["FLAGS_deterministic_reduction"]:
            # the fused flat-buffer update lets XLA regroup FMAs with the
            # surrounding HLO, so the same update computes different last
            # ulps in different programs — incompatible with the bitwise
            # cross-program parity deterministic mode promises
            return
        n_opt = sum(op.type in ("sgd", "momentum", "adam")
                    for op in block.ops)
        if n_opt < 4:
            return
        from .. import ir as _ir

        # an IR pass is part of the build: the first attempt at a version
        # is always under a cache-miss step or a warmup
        with _tracing.span("executor.build", stage="fuse_optimizer_ops"):
            _ir.apply_pass("fuse_optimizer_ops_pass", program, None,
                           protected=set(feed_names) | set(fetch_names))
        # the pass bumps the version when it fuses; mark the new version
        # attempted too so the next run doesn't rescan
        self._fuse_attempted.add((program._uid, program.version))

    def _param_sharding(self, mesh, block, name):
        from jax.sharding import NamedSharding, PartitionSpec as P

        v = block._find_var_recursive(name)
        if v is not None and v.sharding:
            # drop axis names the mesh doesn't have (e.g. a table annotated
            # ("model", None) running on a data-only mesh stays replicated)
            spec = tuple(a if (a is None or a in mesh.axis_names) else None
                         for a in v.sharding)
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    def _param_shardings(self, mesh, block, plan):
        """{name: NamedSharding} of every persistable the step reads or
        writes.  A property of (program version, mesh), which is what the
        executable cache is keyed by: computed once a build and kept with
        the entry."""
        # rw_names are among persist_written, ro_names are not
        return {n: self._param_sharding(mesh, block, n)
                for n in (*plan.ro_names, *plan.persist_written)}

    def _place_params(self, scope, targets, params_ro, params_rw,
                      place_all):
        """One call's persistables, each on its target of ``targets`` (the
        entry's table on a hit, the build's on the compile path, where
        ``place_all``): (ro, rw, arrays placed, arrays passed through)."""
        kept = scope.__dict__.setdefault("_mesh_placed_ro", {})
        ro, placed_ro = self._shard_params(params_ro, targets, kept=kept,
                                           place_all=place_all)
        rw, placed_rw = self._shard_params(params_rw, targets,
                                           place_all=place_all)
        placed = placed_ro + placed_rw
        return ro, rw, placed, len(ro) + len(rw) - placed

    @staticmethod
    def _lies_on(v, target, exact=False):
        """Whether ``v`` is already a device array laid out as ``target``,
        by what the array itself says: the same sharding object, an equal
        one (a step's outputs under ``out_shardings``), or — unless
        ``exact`` — an equivalent one (the transpiled ``shard_map`` route
        declares no ``out_shardings``, the compiler names its outputs'
        layout its own way).  An executable takes all three as they are."""
        if not isinstance(v, jax.Array):
            return False
        sh = v.sharding
        if sh is target or sh == target:
            return True
        return not exact and sh.is_equivalent_to(target, v.ndim)

    def _shard_params(self, params, targets, kept=None, place_all=False):
        """Place each persistable on the mesh once: an array that already
        lies on its target (``_lies_on``) goes into the call untouched;
        anything else — numpy from the startup program or a checkpoint
        restore, a single-device array, an array on another mesh after an
        elastic re-quorum — is placed.  Returns (arrays, number placed).

        ``kept`` (read-only persistables: the step never writes them back,
        so the scope keeps what the user put there) pairs each placed copy
        with the scope object it was made from, by identity, the way
        ``_gather_carry`` keeps its bf16 copies: an external ``scope.set``
        breaks the pair and forces a fresh placement.

        ``place_all`` is the compile path: every array goes through the
        placement call, whose result fixes the executable's input
        shardings (for an array already on its target that call hands back
        its argument)."""
        multi = jax.process_count() > 1
        out = {}
        placed = 0
        for n, v in params.items():
            sh = targets[n]
            if not place_all:
                # multi-process: pass through only what equals the target
                if self._lies_on(v, sh, exact=multi):
                    out[n] = v
                    if kept is not None:
                        # a copy of what the scope held before is of no use
                        kept.pop(n, None)
                    continue
                ent = kept.get(n) if kept is not None else None
                if ent is not None and ent[0] is v \
                        and self._lies_on(ent[1], sh, exact=multi):
                    out[n] = ent[1]
                    continue
            placed += 1
            if not multi or (isinstance(v, jax.Array)
                             and v.sharding.device_set == sh.device_set):
                out[n] = jax.device_put(v, sh)
            else:
                # multi-process (nccl2-mode analog): every process holds
                # the full (identically-seeded) value — locally-committed
                # arrays (e.g. from a single-device startup run) included;
                # assemble the global array from process-local data
                out[n] = jax.make_array_from_process_local_data(
                    sh, np.asarray(v))
            if kept is not None and out[n] is not v:
                kept[n] = (v, out[n])
        return out, placed

    def _shard_feeds(self, feed_arrays, mesh, data_axis):
        from jax.sharding import NamedSharding, PartitionSpec as P

        multi = jax.process_count() > 1
        out = {}
        for n, a in feed_arrays.items():
            batch_ok = (a.ndim >= 1 and data_axis
                        and a.shape[0] % mesh.shape[data_axis] == 0)
            if multi:
                if isinstance(a, jax.Array) and not a.is_fully_addressable:
                    out[n] = a  # already a correctly-assembled global array
                    continue
                # reference nccl2-mode protocol: each trainer process feeds
                # its LOCAL batch shard (numpy or a locally-committed jax
                # array, e.g. from the double-buffered DataLoader); the
                # global batch is the concatenation over processes
                local = np.asarray(a)
                local_dev = max(
                    len([d for d in mesh.devices.flat
                         if d.process_index == jax.process_index()]), 1)
                if local.ndim >= 1 and data_axis \
                        and local.shape[0] % local_dev == 0:
                    spec = P(data_axis, *([None] * (local.ndim - 1)))
                elif local.ndim >= 1 and data_axis and local.shape[0] > 1:
                    # Reference contract (feed_and_split_tensor_into_local_
                    # scopes): every multi-device feed is a batch split
                    # across devices, and an indivisible batch is an error.
                    # Replicating here instead would silently diverge
                    # per-device values when trainers feed distinct shards.
                    # Genuinely replicated constants should be shape
                    # [1, ...] or pre-committed replicated jax.Arrays (the
                    # is_fully_addressable path above).
                    raise ValueError(
                        "multi-process feed '%s': local leading dim %d is "
                        "not divisible by the %d local device(s); pad the "
                        "batch, or feed replicated constants with leading "
                        "dim 1 / as pre-committed jax.Arrays"
                        % (n, local.shape[0], local_dev))
                else:
                    # leading dim 1 (or scalar): broadcast-like feed (lr,
                    # beta_pow) — identical across processes, replicate
                    spec = P()
                out[n] = jax.make_array_from_process_local_data(
                    NamedSharding(mesh, spec), local)
                continue
            spec = (P(data_axis, *([None] * (a.ndim - 1)))
                    if batch_ok else P())
            out[n] = jax.device_put(a, NamedSharding(mesh, spec))
        return out

    # -- dataset/trainer entry points (C++ trainer path analog) --------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           checkpoint_manager=None):
        from ..trainer import train_from_dataset

        return train_from_dataset(self, program, dataset, scope, thread,
                                  fetch_list, fetch_info, print_period,
                                  checkpoint_manager=checkpoint_manager)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from ..trainer import infer_from_dataset

        return infer_from_dataset(self, program, dataset, scope, thread,
                                  fetch_list, fetch_info, print_period)
