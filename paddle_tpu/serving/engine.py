"""Continuous-batching serving engine over AnalysisPredictor.

The reference inference stack answers one request at a time
(AnalysisPredictor::Run); under "heavy traffic from millions of users"
(ROADMAP north star) that wastes the accelerator on batch-1 launches and
recompiles on every new request shape.  The engine closes both gaps:

- **admission queue with deadline-aware backpressure**: ``submit`` sheds
  a request (status "shed" + retry_after_ms) instead of queueing it when
  the projected wait — queue depth x the model's EWMA batch service time —
  already exceeds the request's deadline budget, or when the queue is at
  ``FLAGS_serving_max_queue``.  Queued requests whose deadline expires
  before dispatch complete with status "timeout".
- **shape-bucketed batching**: the dispatcher coalesces queued same-model
  requests for up to ``FLAGS_serving_batch_window_ms`` and pads the
  concatenated batch to the smallest configured bucket that fits
  (``FLAGS_serving_buckets``), so every dispatch hits one of a FIXED set
  of executable shapes.
- **AOT bucket prewarm**: ``prewarm()`` runs ``Executor.warmup`` for every
  (model, bucket) against ``FLAGS_compile_cache_dir`` — all executables
  exist before the first request, and the prewarm manifest records where
  each came from (memory/disk/compiled).  After that, a request can only
  ever hit the in-memory executable cache: zero runtime compiles, provable
  from the ``executor_cache_miss_total`` / ``compile_cache_*`` counters.

Serving control plane (PR 16) on top of that:

- **SLO tiers**: a request carries a tier (``paid``/``free``/``batch``),
  whose configured weight (``FLAGS_serving_tier_weights``) scales its
  admission budget — shed when projected wait exceeds deadline x weight
  — orders batch assembly (higher weight dispatches first), and decides
  queue-full eviction (an arriving higher-weight request evicts the
  lowest-weight queued one instead of being shed itself).  Under
  overload the free tier sheds first and paid p99 never starves.
- **drain hook**: ``drain()`` flips the engine into a shedding-only
  state and waits for the queue to empty — the autoscaler's graceful
  scale-down runs it on the victim so retirement lands at a batch
  boundary with zero dropped requests.
- **versioned routing**: ``add_model("fc@v2", ...)`` registers a second
  version beside ``fc``; ``set_route`` splits base-name traffic between
  active and canary versions by a deterministic per-request hash, so the
  rollout controller (serving/rollout.py) can canary, flip, and roll
  back without touching clients.  Reply phases carry the resolved
  version so per-version p99s fall out of the same attribution.

Telemetry: ``serving_queue_depth`` gauge, ``serving_batch_fill`` +
``serving_latency_ms`` + ``serving_execute_ms`` histograms,
``serving_qps`` gauge (5 s window),
``serving_requests_total{model,tenant}``, ``serving_shed_total{reason}``,
``serving_tier_shed_total{tier}``, ``serving_timeout_total``,
``serving_batches_total{model,bucket}``,
``serving_request_errors_total{model}``.
"""

import functools
import threading
import time
import uuid
import zlib

import numpy as np

from ..core import telemetry as _tm
from ..core import tracing as _tr
from ..core.executor import scope_guard
from ..utils.fault_injection import maybe_fail

__all__ = ["ServingEngine", "DecodeEngine", "InferReply", "parse_buckets",
           "parse_tier_weights", "tier_weight"]

_QPS_WINDOW_S = 5.0

# Machine-readable concurrency contracts (tools/threadlint.py CC101/CC105;
# core/concurrency_analysis.py merges every module's registry).  The
# engine step lock is always OUTERMOST: adopt/seal paths take the cache
# index and allocator locks (and hand frames to the kvxfer sender) while
# holding the engine condition, never the reverse.  The rollout
# controller's state lock wraps engine route mutations.
LOCK_ORDER = (
    ("RolloutController._lock", "ServingEngine._cond"),
    ("DecodeEngine._cond", "PrefixCache._lock", "BlockAllocator._lock"),
    ("DecodeEngine._cond", "KVBlockSender._cond"),
)

# Batch-boundary hooks fire between batches with the queue lock released
# (documented at their assignment sites); CC105 enforces it.  The
# per-step hooks (on_block_sealed / on_handoff) are intentionally NOT
# here: their contract is "fired under the step lock".
UNLOCKED_CALLBACKS = (
    "ServingEngine.on_batch_boundary",
    "DecodeEngine.on_batch_boundary",
    "DecodeEngine.on_preempt",
)


def _flag(name):
    from .. import flags

    return flags.flag(name)


def parse_buckets(spec=None):
    """\"1,4,16\" (or an int sequence) -> sorted unique bucket tuple."""
    if spec is None:
        spec = _flag("serving_buckets")
    if isinstance(spec, str):
        sizes = [int(s) for s in spec.replace(" ", "").split(",") if s]
    else:
        sizes = [int(s) for s in spec]
    if not sizes or any(s <= 0 for s in sizes):
        raise ValueError("serving buckets must be positive ints: %r" % spec)
    return tuple(sorted(set(sizes)))


def parse_tier_weights(spec=None):
    """\"paid:1.0,free:0.45\" -> {tier: weight}; weights in (0, 1]."""
    if spec is None:
        spec = _flag("serving_tier_weights")
    if isinstance(spec, dict):
        out = {str(k): float(v) for k, v in spec.items()}
    else:
        out = {}
        for part in str(spec).replace(" ", "").split(","):
            if not part:
                continue
            name, _, w = part.partition(":")
            if not name or not w:
                raise ValueError("tier weights want tier:weight, got %r"
                                 % part)
            out[name] = float(w)
    if not out or any(w <= 0.0 or w > 1.0 for w in out.values()):
        raise ValueError("tier weights must be in (0, 1]: %r" % spec)
    return out


def tier_weight(weights, tier):
    """(tier label, weight) for one request.  No tier = full budget
    (pre-tier behavior); an unknown tier gets the lowest configured
    weight rather than a free upgrade."""
    if not tier:
        return "default", 1.0
    w = weights.get(tier)
    return (tier, w) if w is not None else (tier, min(weights.values()))


def _route_hash(req_id):
    """Deterministic [0, 1) split point per request (canary routing) —
    stable across replicas so a replayed request lands on the same
    version wherever it fails over to."""
    return (zlib.crc32(req_id.encode("utf-8")) & 0xFFFFFFFF) / 2.0 ** 32


def _prewarm_attrs(span, manifest):
    """What one ``prewarm()`` did, on its ``serving.prewarm`` span:
    (model, bucket) pairs warmed, and their executables by where they came
    from (a speculative bucket holds three)."""
    pairs = [got for per in manifest.values() for got in per.values()]
    sources = [g["source"] for got in pairs
               for g in ([got] if "source" in got else got.values())]
    span.annotate(buckets=len(pairs), compiled=sources.count("compiled"),
                  restored=sources.count("disk")).device_memory()


class InferReply:
    """Terminal state of one request: status ok|shed|timeout|error."""

    __slots__ = ("status", "outputs", "error", "retry_after_ms",
                 "latency_ms", "phases")

    def __init__(self, status, outputs=None, error=None,
                 retry_after_ms=0.0, latency_ms=0.0, phases=None):
        self.status = status
        self.outputs = outputs or {}
        self.error = error
        self.retry_after_ms = float(retry_after_ms)
        self.latency_ms = float(latency_ms)
        # SLO phase attribution (always on, tracing-independent):
        # queue_wait_ms / execute_ms / bucket / rows — the client adds
        # wire_ms as its end-to-end latency minus our latency_ms
        self.phases = phases or {}

    @property
    def ok(self):
        return self.status == "ok"

    def to_meta(self):
        meta = {"status": self.status, "error": self.error,
                "retry_after_ms": round(self.retry_after_ms, 3),
                "latency_ms": round(self.latency_ms, 3),
                "outputs": list(self.outputs)}
        if self.phases:
            meta["phases"] = self.phases
        return meta


class _Pending:
    """Handle returned by submit(): wait() blocks for the InferReply."""

    __slots__ = ("model", "tenant", "feeds", "rows", "deadline",
                 "t_submit", "t_locked", "t_dispatch", "req_id", "callback",
                 "_done",
                 "reply", "traceparent", "span", "qspan", "tier", "weight")

    def __init__(self, model, tenant, feeds, rows, deadline_ms, req_id,
                 callback, traceparent=None, tier="default", weight=1.0):
        self.model = model
        self.tenant = tenant
        self.tier = tier
        self.weight = float(weight)
        self.feeds = feeds
        self.rows = rows
        self.t_submit = time.perf_counter()
        self.t_locked = None   # DecodeEngine.submit: the engine lock taken
        self.t_dispatch = None
        self.deadline = self.t_submit + deadline_ms / 1e3
        self.req_id = req_id
        self.callback = callback
        self._done = threading.Event()
        self.reply = None
        self.traceparent = traceparent  # wire context echoed in the reply
        self.span = None    # serving.request (submit -> complete)
        self.qspan = None   # serving.queue_wait child (submit -> dispatch)

    def complete(self, reply):
        reply.latency_ms = (time.perf_counter() - self.t_submit) * 1e3
        self.reply = reply
        self._done.set()
        if self.callback is not None:
            try:
                self.callback(self)
            except Exception:
                pass

    def wait(self, timeout=None):
        self._done.wait(timeout)
        return self.reply


class _ModelEntry:
    __slots__ = ("name", "predictor", "feed_specs", "svc_ms")

    def __init__(self, name, predictor):
        self.name = name
        self.predictor = predictor
        block = predictor.program().global_block()
        self.feed_specs = {}
        for fname in predictor.get_input_names():
            v = block._find_var_recursive(fname)
            shape = tuple(v.shape)
            if shape and shape[0] in (-1, 0):
                shape = shape[1:]
            self.feed_specs[fname] = (shape, v.dtype)
        # EWMA of one dispatched batch's wall time; seeds pessimistic so
        # the first admission estimates err toward accepting
        self.svc_ms = 0.0


class ServingEngine:
    def __init__(self, buckets=None, max_queue=None, deadline_ms=None,
                 batch_window_ms=None):
        self.buckets = parse_buckets(buckets)
        self.max_queue = int(max_queue if max_queue is not None
                             else _flag("serving_max_queue"))
        self.default_deadline_ms = float(
            deadline_ms if deadline_ms is not None
            else _flag("serving_deadline_ms"))
        self.batch_window_ms = float(
            batch_window_ms if batch_window_ms is not None
            else _flag("serving_batch_window_ms"))
        self.tier_weights = parse_tier_weights()
        self._models = {}
        self._queue = []          # FIFO of _Pending (tiers reorder at
        #                           collect time, not at admission)
        self._routes = {}         # base name -> version route dict
        self._cond = threading.Condition()
        self._running = False
        self._draining = False
        self._thread = None
        self.in_batch = False
        # fleet hook: called (outside the queue lock) after every
        # dispatched batch — the fleet coordinator publishes membership
        # changes here, so a shrink lands at a batch boundary
        self.on_batch_boundary = None
        self._done_times = []     # completion stamps for the QPS gauge

    # -- registry ------------------------------------------------------------

    def add_model(self, name, predictor_or_dir):
        """Register a model under `name`: an AnalysisPredictor, or a
        save_inference_model dir to load one from."""
        from ..inference import AnalysisConfig, AnalysisPredictor

        with _tr.span("serving.add_model", model=name) as span:
            if isinstance(predictor_or_dir, str):
                # AnalysisConfig's default place is TPUPlace(0): a server on
                # a chip machine serves from the chip
                predictor_or_dir = AnalysisPredictor(
                    AnalysisConfig(predictor_or_dir))
            self._models[name] = _ModelEntry(name, predictor_or_dir)
            span.device_memory()
        return self._models[name].predictor

    def models(self):
        return list(self._models)

    def spec(self, model):
        """JSON-able feed/fetch signature for `model` (the __spec__ RPC)."""
        from ..framework import dtype_to_np

        e = self._models[model]
        return {
            "model": model,
            "buckets": list(self.buckets),
            "feeds": {n: {"shape": list(shape),
                          "dtype": np.dtype(dtype_to_np(dt)).str}
                      for n, (shape, dt) in e.feed_specs.items()},
            "outputs": e.predictor.get_output_names(),
        }

    # -- versioned routing (rollout control plane) ---------------------------

    def set_route(self, base, active=None, canary=None, fraction=0.0,
                  state="stable"):
        """Route requests addressed to `base`: `active` serves
        (1 - fraction) of the traffic, `canary` the rest.  Requests
        addressed to a registered version name directly always bypass
        routing.  `state` is bookkeeping for the ``rollout_state`` gauge
        (stable=0, canary=1, flipped=2, rolled_back=3)."""
        active = active or base
        if active not in self._models:
            raise ValueError("unknown active version %r" % active)
        if canary is not None and canary not in self._models:
            raise ValueError("unknown canary version %r" % canary)
        with self._cond:
            self._routes[base] = {
                "active": active,
                "canary": canary,
                "fraction": float(fraction) if canary is not None else 0.0,
                "state": state,
            }
        _tm.set_gauge("rollout_state",
                      {"stable": 0, "canary": 1, "flipped": 2,
                       "rolled_back": 3}.get(state, 0), model=base)

    def clear_route(self, base):
        with self._cond:
            self._routes.pop(base, None)

    def routes(self):
        """{base: route dict} snapshot (the __rollout__ payload)."""
        with self._cond:
            return {b: dict(r) for b, r in self._routes.items()}

    def apply_routes(self, routes):
        """Adopt a broadcast route table wholesale (idempotent; unknown
        version names are skipped so a replica that lacks a model never
        routes into a black hole)."""
        for base, r in (routes or {}).items():
            try:
                self.set_route(base, active=r.get("active"),
                               canary=r.get("canary"),
                               fraction=r.get("fraction", 0.0),
                               state=r.get("state", "stable"))
            except ValueError:
                pass

    def resolve(self, model, req_id):
        """Base name -> version name per the route table; a deterministic
        per-request hash keeps the canary split consistent across
        failover replays."""
        r = self._routes.get(model)
        if not r:
            return model
        if r["canary"] is not None and r["fraction"] > 0.0 \
                and _route_hash(req_id) < r["fraction"]:
            return r["canary"]
        return r["active"]

    # -- AOT bucket prewarm --------------------------------------------------

    def prewarm(self):
        """Executor.warmup every (model, bucket); returns the manifest
        {model: {bucket: {"source", "compile_ms"}}}.  With
        FLAGS_compile_cache_dir set, compiled buckets land in the tier-B
        store and later replicas restore from disk."""
        manifest = {}
        with _tr.span("serving.prewarm") as span:
            for name, e in self._models.items():
                pred = e.predictor
                per = {}
                for b in self.buckets:
                    specs = {n: ((b,) + tuple(shape), None)
                             for n, (shape, _dt) in e.feed_specs.items()}
                    got = pred._exe.warmup(
                        pred.program(), feed_specs=specs,
                        fetch_list=pred._fetch_vars, scope=pred._scope)
                    per[b] = {"source": got["source"],
                              "compile_ms": round(got["compile_ms"], 3)}
                    _tm.inc("serving_prewarm_total", model=name,
                            source=got["source"])
                    _tm.event("serving_prewarm", model=name, bucket=b,
                              source=got["source"],
                              ms=round(got["compile_ms"], 3))
                manifest[name] = per
            _prewarm_attrs(span, manifest)
        return manifest

    # -- admission -----------------------------------------------------------

    def _projected_wait_ms(self, entry, depth):
        """Queue-drain estimate: batches ahead x EWMA batch service time."""
        if entry.svc_ms <= 0.0:
            return 0.0
        batches_ahead = depth // max(self.buckets) + 1
        return batches_ahead * entry.svc_ms

    def _shed(self, req, reason, error, retry_after_ms):
        _tm.inc("serving_shed_total", reason=reason)
        _tm.inc("serving_tier_shed_total", tier=req.tier)
        req.complete(InferReply("shed", error=error,
                                retry_after_ms=retry_after_ms,
                                phases={"tier": req.tier,
                                        "model": req.model}))
        return req

    def submit(self, model, feeds, tenant="default", deadline_ms=None,
               callback=None, req_id=None, traceparent=None, tier=None):
        """Enqueue one request; returns a _Pending (wait() for the reply).
        Shed/timeout/error requests complete immediately.  `tier` scales
        the deadline budget by its configured weight, so under pressure
        low-weight tiers shed first (deadline-weighted admission)."""
        deadline_ms = float(deadline_ms or self.default_deadline_ms)
        req_id = req_id or uuid.uuid4().hex
        tier, weight = tier_weight(self.tier_weights, tier)
        # version routing happens at admission: the resolved name decides
        # the model entry, the metrics labels, and the reply attribution
        model = self.resolve(model, req_id)
        req = _Pending(model, tenant, feeds, 0, deadline_ms, req_id,
                       callback, traceparent=traceparent, tier=tier,
                       weight=weight)
        entry = self._models.get(model)
        if entry is None or not self._running:
            req.complete(InferReply(
                "error", error="unknown model %r" % model if entry is None
                else "engine not running"))
            return req
        try:
            req.feeds, req.rows = self._normalize(entry, feeds)
        except Exception as e:
            req.complete(InferReply("error", error=str(e)))
            return req
        _tm.inc("serving_requests_total", model=model, tenant=tenant)
        with self._cond:
            if self._draining:
                # retiring replica: push traffic to the surviving fleet
                return self._shed(req, "draining", "replica draining",
                                  max(entry.svc_ms, 1.0))
            depth = len(self._queue)
            if depth >= self.max_queue:
                wait_ms = self._projected_wait_ms(entry, depth)
                # tier eviction: a full queue sheds its lowest-weight
                # member instead of the arrival when the arrival
                # outranks it — paid traffic is never blocked behind
                # queued free-tier work
                victim = min(self._queue, key=lambda r: (r.weight,
                                                         -r.t_submit)) \
                    if self._queue else None
                if victim is not None and victim.weight < req.weight:
                    self._queue.remove(victim)
                    if victim.qspan is not None:
                        victim.qspan.annotate(evicted=True).end()
                    if victim.span is not None:
                        victim.span.annotate(status="shed").end()
                    self._shed(victim, "tier_evicted",
                               "evicted by %s-tier arrival" % req.tier,
                               max(wait_ms, entry.svc_ms, 1.0))
                else:
                    return self._shed(
                        req, "queue_full", "queue full (%d)" % depth,
                        max(wait_ms, entry.svc_ms, 1.0))
            wait_ms = self._projected_wait_ms(entry, len(self._queue))
            budget_ms = deadline_ms * req.weight
            if wait_ms > budget_ms:
                return self._shed(
                    req, "deadline_budget",
                    "projected wait %.0fms exceeds %s-tier budget %.0fms"
                    % (wait_ms, req.tier, budget_ms),
                    wait_ms - budget_ms + entry.svc_ms)
            # admitted: open the request span (parents under the server's
            # admission span when submit runs inside it) and its
            # queue-wait child, ended at dispatch or deadline expiry
            req.span = _tr.start_span(
                "serving.request", model=model, tenant=tenant,
                rows=req.rows, req_id=req.req_id, tier=tier)
            req.qspan = _tr.start_span("serving.queue_wait",
                                       parent=req.span, depth=depth)
            self._queue.append(req)
            _tm.set_gauge("serving_queue_depth", len(self._queue))
            self._cond.notify_all()
        return req

    def infer(self, model, feeds, tenant="default", deadline_ms=None):
        """Synchronous submit + wait."""
        req = self.submit(model, feeds, tenant=tenant,
                          deadline_ms=deadline_ms)
        deadline_ms = float(deadline_ms or self.default_deadline_ms)
        reply = req.wait(timeout=deadline_ms / 1e3 + 30.0)
        return reply if reply is not None else InferReply(
            "timeout", error="no reply within deadline")

    def _normalize(self, entry, feeds):
        """Validate + coerce request feeds; returns (feeds, rows)."""
        from ..framework import dtype_to_np

        rows = None
        out = {}
        for name, (shape, dt) in entry.feed_specs.items():
            if name not in feeds:
                raise ValueError("missing feed %r" % name)
            arr = np.ascontiguousarray(feeds[name],
                                       dtype=dtype_to_np(dt))
            if tuple(arr.shape[1:]) != tuple(shape):
                raise ValueError(
                    "feed %r: expected trailing shape %s, got %s"
                    % (name, tuple(shape), tuple(arr.shape[1:])))
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                raise ValueError("inconsistent batch rows across feeds")
            out[name] = arr
        if rows is None or rows == 0:
            raise ValueError("empty request")
        if rows > max(self.buckets):
            raise ValueError("request rows %d exceed largest bucket %d"
                             % (rows, max(self.buckets)))
        return out, rows

    # -- dispatcher ----------------------------------------------------------

    def start(self):
        if self._running:
            return self
        self._running = True
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        name="serving-dispatch", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain_s=5.0):
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(drain_s)
            self._thread = None
        with self._cond:
            for req in self._queue:
                req.complete(InferReply("error", error="engine stopped"))
                if req.qspan is not None:
                    req.qspan.end()
                if req.span is not None:
                    req.span.annotate(status="error").end()
            self._queue.clear()

    @property
    def draining(self):
        return self._draining

    def drain(self, timeout_s=30.0):
        """Graceful retirement: stop admitting (new submits shed with
        reason="draining" so clients fail over), then wait until every
        already-admitted request has dispatched and the in-flight batch
        finished.  Returns True when the queue fully drained — the
        autoscaler's scale-down exits the replica only after that, so a
        retirement never drops an admitted request."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            with self._cond:
                if not self._queue and not self.in_batch:
                    return True
            time.sleep(0.01)
        return False

    def _bucket_for(self, rows):
        for b in self.buckets:
            if rows <= b:
                return b
        return max(self.buckets)

    def _collect(self):
        """Under the lock: wait for work, then coalesce same-model
        requests within the batch window up to the largest bucket."""
        while self._running and not self._queue:
            self._cond.wait(0.2)
        if not self._queue:
            return None, []
        model = self._queue[0].model
        window_end = time.perf_counter() + self.batch_window_ms / 1e3
        max_rows = max(self.buckets)
        while self._running:
            rows = sum(r.rows for r in self._queue if r.model == model)
            if rows >= max_rows:
                break
            left = window_end - time.perf_counter()
            if left <= 0:
                break
            self._cond.wait(min(left, 0.002))
        # tier-priority assembly: among this model's queued requests the
        # highest-weight ones board the batch first (FIFO within a
        # tier), so paid traffic overtakes queued free-tier work instead
        # of waiting behind it
        cands = sorted((r for r in self._queue if r.model == model),
                       key=lambda r: (-r.weight, r.t_submit))
        batch, rows = [], 0
        taken = set()
        for r in cands:
            if rows + r.rows <= max_rows:
                batch.append(r)
                taken.add(id(r))
                rows += r.rows
        self._queue[:] = [r for r in self._queue if id(r) not in taken]
        _tm.set_gauge("serving_queue_depth", len(self._queue))
        return model, batch

    def _dispatch_loop(self):
        while True:
            with self._cond:
                if not self._running:
                    return
                model, batch = self._collect()
            if not batch:
                continue
            now = time.perf_counter()
            live = []
            for r in batch:
                if now > r.deadline:
                    _tm.inc("serving_timeout_total", model=r.model)
                    r.complete(InferReply(
                        "timeout", error="deadline expired in queue",
                        phases={"queue_wait_ms":
                                round((now - r.t_submit) * 1e3, 3),
                                "rows": r.rows}))
                    if r.qspan is not None:
                        r.qspan.annotate(expired=True).end()
                    if r.span is not None:
                        r.span.annotate(status="timeout").end()
                else:
                    r.t_dispatch = now
                    if r.qspan is not None:
                        r.qspan.end()
                    live.append(r)
            if live:
                self.in_batch = True
                try:
                    self._run_batch(self._models[model], live)
                finally:
                    self.in_batch = False
            if self.on_batch_boundary is not None:
                try:
                    self.on_batch_boundary()
                except Exception:
                    pass

    @staticmethod
    def _phases(r, execute_ms, bucket):
        """Per-request SLO phase attribution for the reply meta (always
        on — the client derives wire_ms as e2e minus server latency).
        Carries the tier and the RESOLVED model version so per-tier and
        per-version p99s fall out of the same reply stream."""
        t_d = r.t_dispatch if r.t_dispatch is not None else r.t_submit
        return {"queue_wait_ms": round((t_d - r.t_submit) * 1e3, 3),
                "execute_ms": round(execute_ms, 3),
                "bucket": bucket, "rows": r.rows,
                "tier": r.tier, "model": r.model}

    def _run_batch(self, entry, batch):
        rows = sum(r.rows for r in batch)
        bucket = self._bucket_for(rows)
        pred = entry.predictor
        # a batch serves N requests from (up to) N different traces, so
        # the batch span is a root that LINKS them rather than parenting
        bspan = _tr.start_span("serving.batch", model=entry.name,
                               bucket=bucket, rows=rows,
                               requests=len(batch))
        for r in batch:
            bspan.link(r.span.context if r.span is not None else None)
        with _tr.activate(bspan):
            with _tr.span("serving.pad_to_bucket", rows=rows,
                          bucket=bucket):
                feed = {}
                for name in entry.feed_specs:
                    parts = [r.feeds[name] for r in batch]
                    stacked = np.concatenate(parts, axis=0) \
                        if len(parts) > 1 else parts[0]
                    if rows < bucket:
                        pad = np.zeros(
                            (bucket - rows,) + stacked.shape[1:],
                            dtype=stacked.dtype)
                        stacked = np.concatenate([stacked, pad], axis=0)
                    feed[name] = stacked
            # write-through breadcrumb: if this replica is SIGKILLed
            # mid-execute, flightrec-<pid>.json already names the batch
            _tr.note("batch_start", model=entry.name, bucket=bucket,
                     req_ids=[r.req_id for r in batch])
            t0 = time.perf_counter()
            try:
                # named fault point per model VERSION — a chaos/rollback
                # leg arms e.g. "serving.execute.fc@v2:error:1.0" to
                # seed a bad canary without a genuinely broken model
                if maybe_fail("serving.execute." + entry.name) == "error":
                    raise RuntimeError("injected execute fault (%s)"
                                       % entry.name)
                with _tr.span("serving.execute", bucket=bucket):
                    with scope_guard(pred._scope):
                        vals = pred._exe.run(pred.program(), feed=feed,
                                             fetch_list=pred._fetch_vars)
            except Exception as e:
                ms = (time.perf_counter() - t0) * 1e3
                for r in batch:
                    r.complete(InferReply(
                        "error", error=str(e),
                        phases=self._phases(r, ms, bucket)))
                    if r.span is not None:
                        r.span.annotate(status="error").end()
                _tm.inc("serving_batch_errors_total", model=entry.name)
                _tm.inc("serving_request_errors_total", len(batch),
                        model=entry.name)
                bspan.annotate(error=str(e)[:200]).end()
                return
        ms = (time.perf_counter() - t0) * 1e3
        entry.svc_ms = ms if entry.svc_ms <= 0 else \
            0.7 * entry.svc_ms + 0.3 * ms
        outs = [np.asarray(v) for v in vals]
        names = pred.get_output_names()
        off = 0
        for r in batch:
            sliced = {}
            for n, o in zip(names, outs):
                # slice per-request rows when the output carries the batch
                # dim; batch-free outputs replicate to every request
                sliced[n] = o[off:off + r.rows].copy() \
                    if o.ndim and o.shape[0] == bucket else o
            off += r.rows
            r.complete(InferReply("ok", outputs=sliced,
                                  phases=self._phases(r, ms, bucket)))
            if r.span is not None:
                r.span.annotate(status="ok", bucket=bucket).end()
            _tm.observe("serving_latency_ms", r.reply.latency_ms,
                        model=entry.name)
            # per-version execute p99: the rollout gate's scrape-side
            # signal (phase attribution, not end-to-end latency)
            _tm.observe("serving_execute_ms", ms, model=entry.name)
            # per-tier server-side latency (queue wait + execute, the
            # loadgen "server_ms" attribution) as a MERGEABLE histogram:
            # fleetmon's burn-rate SLO rules window its bucket deltas
            _tm.observe("server_ms",
                        r.reply.phases.get("queue_wait_ms", 0.0) + ms,
                        tier=r.tier)
            # goodput numerator/denominator: a reply that beat its
            # deadline is goodput, a late one is only raw throughput
            met = time.perf_counter() <= r.deadline
            _tm.inc("serving_deadline_met_total" if met
                    else "serving_deadline_missed_total", tier=r.tier)
        _tm.inc("serving_batches_total", model=entry.name,
                bucket=str(bucket))
        _tm.observe("serving_batch_fill", rows / float(bucket),
                    model=entry.name)
        bspan.end()
        now = time.time()
        self._done_times.extend([now] * len(batch))
        cut = now - _QPS_WINDOW_S
        while self._done_times and self._done_times[0] < cut:
            self._done_times.pop(0)
        _tm.set_gauge("serving_qps", len(self._done_times) / _QPS_WINDOW_S)


# ===========================================================================
# Autoregressive decode serving: paged KV-cache + token-level batching
# ===========================================================================

class _DecodeSeq:
    """One autoregressive sequence moving through the decode scheduler.

    Prefill is token-feed: the prompt is fed one token per step through
    the SAME bucketed step executable as generation, so mixed-phase
    batches never force a second compiled shape.  ``n_fed`` counts
    positions whose step the host has read back (confirmed: written to
    the KV cache, their token taken); once it passes the last prompt
    position every step's argmax is a generated token.  ``n_disp`` counts
    positions a step has been *dispatched* for: ``n_fed`` or, while the
    one-ahead loop holds that step's tokens unread on the device,
    ``n_fed + 1``.  The loop plans from ``n_disp`` (position, block, slot
    and context length need no token's value) and everything that reads
    the sequence's history goes by ``n_fed``."""

    __slots__ = ("pending", "prompt", "max_new", "eos_id", "on_token",
                 "blocks", "table", "draft_blocks", "draft_table",
                 "state_slot", "window_ring", "n_fed", "n_disp", "out",
                 "t_admit", "t_first", "token_times", "admit_seq",
                 "aborted", "hashes", "published", "cached_tokens",
                 "handoff", "prefill_upto",
                 "replay_upto", "resume_tail",
                 "hist_hashes", "hist_published")

    def __init__(self, pending, prompt, max_new, eos_id, on_token, maxb):
        self.pending = pending
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.eos_id = int(eos_id)
        self.on_token = on_token
        self.blocks = []                      # allocator block ids held
        self.table = np.full(maxb, -1, np.int32)
        self.draft_blocks = []                # speculative draft KV lanes
        self.draft_table = np.full(maxb, -1, np.int32)
        # the slot holding the recurrent layers' state (a model that has
        # them): taken at admission, given back with the blocks
        self.state_slot = None
        # the blocks held in the window layers' pools (a model that has
        # them): a ring started at admission, moved on as the sequence is
        # dispatched, given back with the blocks
        self.window_ring = None
        self.n_fed = 0
        self.n_disp = 0
        self.out = []
        self.t_admit = None
        self.t_first = None                   # first *generated* token
        self.token_times = []                 # perf_counter per token
        self.admit_seq = 0                    # preemption picks max()
        self.aborted = False
        # prefix-cache state, set at admission: the full-prompt hash
        # chain, how many leading blocks are already indexed (shared hits
        # + this sequence's publishes), and the matched token count
        self.hashes = None
        self.published = 0
        self.cached_tokens = 0
        # disaggregated prefill role: a handoff sequence stops at
        # prefill_upto (the last full-block boundary), streams its sealed
        # blocks to a decode replica, and never generates a token here
        self.handoff = False
        self.prefill_upto = 0
        # replay/resume state: positions below ``replay_upto`` are
        # re-fed from KNOWN history (prompt ++ out) with step outputs
        # discarded — never re-emitted.  A fresh sequence replays
        # exactly its prompt; a resumed (migrated-in) or preempted one
        # replays its already-emitted tokens too, so emission always
        # continues at the next new index.  ``resume_tail`` is an
        # optional migrated partial-block hand-off consumed once at
        # admission; ``hist_hashes``/``hist_published`` extend the
        # prompt hash chain over generated tokens for the history
        # publication that keeps peer prefix indexes warm
        # (FLAGS_session_migration).
        self.replay_upto = len(self.prompt)
        self.resume_tail = None
        self.hist_hashes = []
        self.hist_published = 0

    @property
    def in_prefill(self):
        return self.n_fed < self.replay_upto

    @property
    def known(self):
        """Positions whose input token the host holds: ``prompt ++ out``
        (one past ``n_fed`` in steady decode: the last token emitted is
        fed by the next step)."""
        return len(self.prompt) + len(self.out)

    def feed_tok(self, i):
        """Token fed at position ``i`` — the history ``prompt ++ out``
        (valid for every ``i < known``)."""
        p = len(self.prompt)
        return self.prompt[i] if i < p else self.out[i - p]

    def feed_slice(self, start, span):
        return [self.feed_tok(i) for i in range(start, start + span)]

    @property
    def total(self):
        return len(self.prompt) + self.max_new

    @property
    def feed_limit(self):
        """Positions this replica dispatches a step for: up to the
        hand-off boundary for a prefill-role sequence, else all but the
        last token's (``max_new`` is known at dispatch: the step at
        ``total - 2`` yields the last token, which is never fed)."""
        return self.prefill_upto if self.handoff else self.total - 1

    def reset_for_recompute(self):
        """Preempted (or an aborted migration hand-off): blocks were
        freed; replay known history from scratch.  Emitted tokens are
        KEPT — greedy decode is deterministic, so the replay re-feeds
        ``prompt ++ out`` with outputs discarded and emission resumes
        at the next NEW index, byte-identical to an uninterrupted run.
        (Freed shared blocks only dropped a reference — re-admission
        re-matches the prefix index, now including any published
        history blocks, so the replay usually skips straight past the
        cached prefix again.  A model with recurrent layers has no
        index to match: its replay starts at position 0, in whatever
        slot re-admission hands it; so does a model's with window layers,
        in an empty ring.)"""
        self.state_slot = None
        self.window_ring = None
        self.blocks = []
        self.table.fill(-1)
        self.draft_blocks = []
        self.draft_table.fill(-1)
        self.n_fed = 0
        self.n_disp = 0
        self.replay_upto = len(self.prompt) + len(self.out)
        self.t_first = None
        self.token_times = []
        self.hashes = None
        self.published = 0
        self.cached_tokens = 0
        self.hist_hashes = []
        self.hist_published = 0


class _DecodeModel:
    __slots__ = ("name", "cfg", "params", "kv_config", "cache", "stepfn",
                 "maxb", "account", "step_ms", "prefix",
                 "declines", "slot_bytes", "state_name", "feed0",
                 "columns", "idle_lane", "upload",
                 "__weakref__",
                 # speculative decode (spec_k == 0 means off): the draft
                 # decoder runs k tokens ahead through its own paged pool,
                 # then verifyfn scores all k+1 positions in one target call
                 "spec_k", "draft_cfg", "draft_params", "draft_kv_config",
                 "draft_cache", "rolloutfn", "ingestfn", "verifyfn")

    def __init__(self, name, cfg, params, kv_config, cache, stepfn, account):
        self.name = name
        self.cfg = cfg
        self.params = params        # jnp arrays (device-resident)
        self.kv_config = kv_config
        self.cache = cache
        self.stepfn = stepfn        # CarriedStepFn over make_packed_step
        self.maxb = -(-cfg.max_seq // kv_config.block_size)
        # what the step takes and reads by kind of layer: its paths, its
        # prewarm event's and its span's attributes
        self.account = account      # decode_model.StepAccount
        self.step_ms = 0.0          # EWMA of one decode step
        self.prefix = None          # PrefixCache (FLAGS_prefix_cache)
        # why this model declines what starts or moves a sequence at
        # pos > 0 over K/V blocks alone (prefix reuse, history
        # publication, block adoption, session export), or None:
        # "recurrent_state" (a slot is nowhere but in its sequence),
        # "window_layers" (K and V older than the window are in no block)
        self.declines = None
        # one sequence's recurrent state over all such layers, in bytes,
        # and what its spans, gauge and counter call it (add_model sets
        # both for a model with recurrent layers)
        self.slot_bytes = 0
        self.state_name = None
        # what the first step after a pause takes as "the step before's
        # tokens": zeros on the device, never selected (every src is -1)
        self.feed0 = None
        # where each per-lane integer lies in the one ``int32[bucket, C]``
        # array a step sends up (``decode_model.lane_columns``); a row of
        # it for an idle lane, ``int32[C]``: no token of the step before
        # (``src`` -1), position and length 0, the scratch state slot 0,
        # no block (-1, which reads and writes the scratch block) in the
        # table or the ring; and what starts the upload:
        # ``jax.device_put`` to the device the parameters are on
        # (add_model sets the three)
        self.columns = None
        self.idle_lane = None
        self.upload = None
        self.spec_k = 0
        self.draft_cfg = None
        self.draft_params = None
        self.draft_kv_config = None
        self.draft_cache = None
        self.rolloutfn = None       # draft: k chained proposals per lane
        self.ingestfn = None        # draft: multi-token catch-up writes
        self.verifyfn = None        # target: [B, k+1] multi-token step

    # read-only views of the account for benchmark/tests/chip_check_{dots,
    # exaone,nemotron}.py and tests/test_moe_experts_kernel.py, which read
    # them off the entry: they go when those have a public read (Design 7)
    attn_path = property(lambda self: self.account.attn_path)
    window_path = property(lambda self: self.account.window_path)
    experts_path = property(lambda self: self.account.experts_path)
    state_path = property(lambda self: self.account.state_path)


class _Flight:
    """A decode step that was dispatched and whose tokens the host has
    not read: the lanes it took, the position each fed, and its outputs
    as the device will leave them."""

    __slots__ = ("m", "bucket", "lanes", "pos", "lane_of", "nxt", "extras",
                 "t0")

    def __init__(self, m, bucket, lanes, nxt, extras, t0):
        self.m = m
        self.bucket = bucket
        self.lanes = lanes
        self.pos = [s.n_disp for s in lanes]
        self.lane_of = {id(s): i for i, s in enumerate(lanes)}
        self.nxt = nxt              # int32 [largest bucket], on the device
        self.extras = extras
        self.t0 = t0

    def ready(self):
        """Has the device finished this step?  (Asks, does not wait.)"""
        return self.nxt.is_ready()

    def live(self):
        """[(lane, sequence)] for the lanes whose sequence is still what
        the step took it for: at the position it fed, its dispatched count
        standing (``DecodeEngine._discard_in_flight`` takes it back)."""
        return [(i, s) for i, (s, p) in enumerate(zip(self.lanes, self.pos))
                if s.n_fed == p < s.n_disp]


class DecodeEngine:
    """Token-level continuous batching over an engine-owned paged
    KV-cache.

    Every iteration of the decode loop:

    1. expires deadline-passed sequences, then admits waiting sequences
       into free lanes while the allocator can cover their prompts (in
       ``request`` mode admission only happens when no lane is active —
       the comparison baseline for the token-level win);
    2. picks the smallest configured lane bucket >= the lanes it plans and
       fills ONE ``int32[bucket, C]`` host array for it, whose columns are
       tok | src | pos | context_lens | [state slot] | block table |
       [window ring] (``decode_model.lane_columns``) — idle lanes point at
       the reserved scratch block with context_len 0 — and starts its
       upload at once;
    3. dispatches ONE AOT-compiled step (``CarriedStepFn``, found by the
       bucket: a dict lookup and the executable's own call, whatever the
       model's number of parameters; the paged KV carry is donated and
       swapped back into the cache at dispatch), so mixed-length
       sequences never trigger a runtime compile;
    4. *then* fetches the tokens of the step dispatched an iteration
       before, and appends each live lane's sampled token, finishing
       sequences at max_new/EOS and freeing their blocks in the SAME
       iteration so the next admission sees the space.

    **One step ahead.**  Step n+1 is queued on the device before step n's
    tokens are read, so dispatch, emit, plan and admission run under the
    device's work and not beside it.  The token a lane feeds stays on the
    device: the compiled step (``make_packed_step`` over
    ``make_fed_step``) takes the step before's
    ``next_tokens`` and picks lane i's input from it where ``src[i] >= 0``,
    from the host's ``tok[i]`` where it is -1 (prompt and replayed tokens,
    a lane's first step).  What a step costs the host to start does not
    grow with the model: the executable is filed under its bucket, and
    what the host knows of the lanes goes up as one array (span attribute
    ``uploads``, counter ``serving_step_uploads_total{model}``: 1 a step).  The host plans from ``n_disp`` (positions
    dispatched), which needs no token's value; ``n_fed``, ``out`` and what
    is published go by what has been read back.  Three rules keep that
    exact:

    - *discard*: when a step's tokens are read, lane i's is applied only
      if its sequence is still what the step took it for.  Whatever takes
      a sequence off its lane while a step holds it (end of sequence,
      abort, expiry, preemption) goes through ``_discard_in_flight``, and
      the late token is dropped and counted
      (``serving_lane_steps_discarded_total{model,reason}``).  ``max_new``
      is known at dispatch, so a last token's lane is simply not planned
      again; an ``eos_id`` hit costs one wasted lane-step; a preempted
      sequence's dropped token is recomputed (greedy: the same token).
    - *drain*: whoever needs a sequence's exact position from outside the
      loop calls ``_drain_locked`` first (``export_session``,
      ``abort_migration``, the hand-off sweep, ``drain``'s choice of a
      session to push, ``stop``); it fetches and applies the step in
      flight.  ``abort`` only marks.
    - *device order*: blocks and state slots the host frees while a step
      that names them is in flight are safe, because whoever gets them
      next writes them in a later executable on the same stream, after
      the step in flight has run (see ``_decode_step_locked``).

    A model that speculates (``spec_k > 0``) keeps the synchronous
    iteration: ``_spec_step_locked`` needs the accepted count on the host
    before it can plan the next step.

    Mid-decode allocation failure preempts the youngest active sequence
    (blocks freed, sequence re-queued for deterministic recompute) —
    counted as ``kv_block_evictions_total``.  Admission-time shortage
    sheds with ``retry_after_ms`` derived from the EWMA step time; all
    pressure decisions budget against ``free + evictable`` (a warm
    prefix cache is reclaimable, never a reason to shed).

    Prefix caching (``FLAGS_prefix_cache``): admission matches each
    prompt's hash chain against the model's ``PrefixCache``, seeds the
    block table with shared (refcounted) blocks, and jumps the feed
    pointer so prefill computes only the uncached tail; prefill-completed
    full prompt blocks are sealed + published back.  Outputs are bitwise
    identical cache-on vs cache-off — a hit only skips recomputing KV
    values the reference run would have produced identically.

    ``FLAGS_decode_prefill_token_budget`` caps the prefill tokens mixed
    into one iteration (round-robin across prefilling lanes; decode
    lanes always run), bounding decode ITL under long-prompt bursts
    without adding compiled shapes."""

    def __init__(self, buckets=None, max_queue=None, deadline_ms=None,
                 mode=None):
        self.buckets = parse_buckets(
            buckets if buckets is not None
            else _flag("serving_decode_buckets"))
        self.max_queue = int(max_queue if max_queue is not None
                             else _flag("serving_max_queue"))
        self.default_deadline_ms = float(
            deadline_ms if deadline_ms is not None
            else _flag("serving_deadline_ms"))
        mode = mode if mode is not None else _flag("serving_decode_mode")
        if mode not in ("token", "request"):
            raise ValueError("serving_decode_mode must be token|request, "
                             "got %r" % (mode,))
        self.mode = mode
        self.tier_weights = parse_tier_weights()
        self._draining = False
        self._models = {}
        self._waiting = []          # FIFO of _DecodeSeq
        self._active = []
        self._cond = threading.Condition()
        self._running = False
        self._thread = None
        self._admit_seq = 0
        self._step_no = 0
        self._rr_prefill = 0        # round-robin pointer (token budget)
        # what the next serving.decode_step span reports: (admit wait,
        # lock wait) in ms of each request admitted since the last one,
        # when the device last handed a step's tokens back, when the span
        # before it was opened, and the lane set last written to the
        # flight recorder
        self._admit_waits = []
        self._t_fetched = None
        self._t_step_opened = None
        self._noted_lanes = None
        # the step dispatched and not read back yet (one-ahead loop), and
        # "a step is in flight or being applied"
        self._flight = None
        self.in_batch = False
        self.on_batch_boundary = None
        # ``on_tokens_emitted(step=)`` fires once where an iteration has
        # made its last ``on_token`` call (the end of a step's emit, under
        # the step lock: ``step`` true) and after a terminal chunk emitted
        # outside a step: the server publishes the stream chunks it
        # gathered meanwhile as one store transaction, and returns their
        # number, or from a step's call that number and a dict of
        # attributes for the step's span
        self.on_tokens_emitted = None
        # disaggregated prefill role hooks (serving/disagg.py wires them):
        # on_block_sealed(m, seq, j, digest) fires under the step lock for
        # every sealed full-prompt block of a handoff sequence (including
        # prefix-cache hits at admission — a warm prefill replica still
        # announces the digests); on_handoff(m, seq) fires once the feed
        # pointer reaches prefill_upto, before the blocks are freed
        self.on_block_sealed = None
        self.on_handoff = None
        # live session migration (serving/migrate.py): sequences parked
        # mid-hand-off (export_session -> commit/abort), a bounded ring
        # of recently committed-away req_ids (loud double-migration
        # refusal), and pressure-trigger victims reported at the next
        # batch boundary through ``on_preempt(list of (req_id, model))``
        # — fired with the step lock RELEASED (CC105 contract)
        self._migrating = {}
        self._migrated = []
        self._preempted = []
        self.on_preempt = None

    # -- registry ------------------------------------------------------------

    def add_model(self, name, source, kv_blocks=None, draft=None,
                  speculative_k=None):
        """Register a decode model: `source` is a save_decoder() dir or a
        (DecoderConfig, params) pair.  KV pool size comes from
        kv_blocks / FLAGS_kv_cache_blocks, capped by
        FLAGS_hbm_budget_bytes net of the weights' footprint.

        ``draft`` is an optional (DecoderConfig, params) draft decoder
        (a dir `source` auto-loads its bundled ``<dir>/draft``);
        ``speculative_k`` (default FLAGS_speculative_k) > 0 with a draft
        present turns on speculative decoding: the draft gets its own
        paged pool with the SAME block count as the target (equal token
        capacity keeps the two allocators in lockstep), and three AOT
        step fns replace the single-token one — verify ([B, k+1] target),
        rollout (k chained draft proposals), ingest (draft catch-up)."""
        with _tr.span("serving.add_model", model=name) as span:
            entry = self._add_model(span, name, source, kv_blocks, draft,
                                    speculative_k)
            span.device_memory()
        return entry

    def _add_model(self, span, name, source, kv_blocks, draft,
                   speculative_k):
        """``add_model`` below its span: ``serving.lay_out`` (the weights as
        a step holds them) and ``serving.cache_alloc`` (the pools) are its
        children."""
        import jax

        from . import decode_model as _dm
        from . import kv_cache as _kvc
        from ..core.executor import CarriedStepFn

        if isinstance(source, str):
            cfg, params = _dm.load_decoder(source)
            if draft is None:
                draft = _dm.load_draft(source)
        else:
            cfg, params = source
        k = int(speculative_k if speculative_k is not None
                else _flag("speculative_k") or 0)
        if draft is None:
            k = 0   # no draft bundle -> non-speculative regardless of k
        recurrent = bool(cfg.recurrent_layers)
        windowed = bool(cfg.window_layers)
        if windowed and k > 0:
            # verify's junk-first columns and the draft's rollout write
            # where no ring has a block yet
            raise ValueError(
                "model %r has window layers: speculative decoding is not "
                "planned over their rings (speculative_k=%d)" % (name, k))
        if recurrent and k > 0:
            # verify rolls a rejected proposal back by trimming the block
            # table; a recurrent state that has consumed it cannot be
            raise ValueError(
                "model %r has recurrent layers: speculative decoding needs "
                "state snapshots to roll back to, which the cache does not "
                "keep (speculative_k=%d)" % (name, k))
        # .nbytes of a device array is read without copying it to the host
        resident = sum(int(v.nbytes) for v in params.values())
        draft_resident = 0
        if k > 0:
            dcfg, dparams = draft
            if dcfg.vocab != cfg.vocab:
                raise ValueError("draft vocab %d != target vocab %d"
                                 % (dcfg.vocab, cfg.vocab))
            if dcfg.max_seq != cfg.max_seq:
                raise ValueError("draft max_seq %d != target max_seq %d "
                                 "(block tables must line up)"
                                 % (dcfg.max_seq, cfg.max_seq))
            draft_resident = sum(int(v.nbytes) for v in dparams.values())
        kv_config = _dm.cache_config(
            cfg, int(_flag("kv_block_size")),
            2,  # placeholder; plan_num_blocks decides below
            # the model's own residency (a bf16 model keeps a bf16 cache),
            # else the deployment's flag
            cfg.kv_dtype or str(_flag("kv_cache_dtype")),
            # a sequence holds a state slot, and a ring of the window
            # layers' pools, exactly while it holds a lane: one a lane of
            # the largest bucket, and the scratch
            state_slots=max(self.buckets) + 1 if recurrent or windowed
            else 0)
        n, capped = _kvc.plan_num_blocks(
            kv_config, model_resident_bytes=resident + draft_resident,
            requested=kv_blocks)
        kv_config.num_blocks = n
        span.annotate(blocks=n, budget_capped=capped)
        with _tr.device_span("serving.cache_alloc") as aspan:
            cache = _kvc.PagedKVCache(kv_config)
            aspan.annotate(bytes=cache.nbytes)
        prefix = None
        if bool(_flag("prefix_cache")) and not (recurrent or windowed):
            # content-addressed prefix reuse over the SAME pool: sealed
            # full-prompt blocks park evictable at zero refs, the index
            # revives them on a hash-chain match at admission.  The draft
            # pool (speculation) is deliberately NOT indexed: its blocks
            # only steer acceptance, and a tail-only draft prefill can
            # never change the verified output.
            prefix = _kvc.PrefixCache(cache.allocator,
                                      kv_config.block_size, namespace=name)
        # a model with recurrent layers gets no index (entry.declines): a
        # hit would start a sequence at pos > 0 over shared K/V blocks,
        # where the recurrent layers' state at that position is nowhere,
        # and the window layers' K and V before it in no block
        with _tr.span("serving.lay_out"):
            jparams = _dm.laid_out(cfg, params)
        # the weights held in the family's own layout and not as published
        # (the same bytes: ``resident`` stands)
        laid = {key: v for key, v in jparams.items() if key not in params}
        # the paths the step's kinds of layer take go into the key: an
        # executable compiled for one is never restored for another
        account = _dm.StepAccount(cfg, kv_config, jparams, self.buckets,
                                  model=name, laid=laid)
        stepfn = CarriedStepFn(
            # make_paged_step's step with the token feed on the device and
            # the lanes' integers in one array: still one executable an
            # engine step
            _dm.make_packed_step(cfg, kv_config, max(self.buckets)),
            donate_argnums=(0,), name="decode_step",
            key_parts=dict(account.key_parts, kind="decode_step", model=name,
                           cfg=cfg.to_dict(),
                           kv={"block_size": kv_config.block_size,
                               "num_blocks": kv_config.num_blocks,
                               "window_blocks": kv_config.window_blocks,
                               "dtype": kv_config.dtype}))
        entry = _DecodeModel(name, cfg, jparams, kv_config, cache, stepfn,
                             account)
        entry.prefix = prefix
        entry.columns, width = _dm.lane_columns(kv_config, entry.maxb)
        entry.idle_lane = np.zeros(width, np.int32)
        for column in ("src", "tables", "ring"):
            if column in entry.columns:
                entry.idle_lane[entry.columns[column]] = -1
        entry.upload = functools.partial(
            jax.device_put,
            device=min(next(iter(jparams.values())).sharding.device_set,
                       key=lambda d: d.id))
        entry.feed0 = entry.upload(np.zeros(max(self.buckets), np.int32))
        if windowed:
            entry.declines = "window_layers"
        if recurrent:
            entry.declines = "recurrent_state"
            entry.slot_bytes = _kvc.slot_bytes(kv_config)
            entry.state_name = cfg.state_name
        for gauge, nbytes in account.pool_bytes().items():
            _tm.set_gauge(gauge, nbytes, model=name)
        _tm.set_gauge("decode_weights_laid_out_bytes",
                      sum(int(v.nbytes) for v in laid.values()), model=name)
        if k > 0:
            # draft pool mirrors the target's block COUNT (draft blocks
            # are strictly smaller at fewer layers), so any sequence the
            # target pool can hold, the draft pool can shadow; the budget
            # plan above already counted both param sets, and MEM001
            # reports the exact combined pool bytes afterwards
            draft_kv = _dm.cache_config(dcfg, kv_config.block_size, n,
                                        kv_config.dtype)
            entry.spec_k = k
            entry.draft_cfg = dcfg
            with _tr.span("serving.lay_out", draft=True):
                entry.draft_params = _dm.laid_out(dcfg, dparams)
            base_parts = dict(account.key_parts, model=name, kv={
                "block_size": kv_config.block_size, "num_blocks": n,
                "dtype": kv_config.dtype},
                attention=[account.attn_path, _dm.StepAccount(
                    dcfg, draft_kv, entry.draft_params,
                    self.buckets).attn_path])
            entry.draft_kv_config = draft_kv
            with _tr.device_span("serving.cache_alloc",
                                 draft=True) as aspan:
                entry.draft_cache = _kvc.PagedKVCache(draft_kv)
                aspan.annotate(bytes=entry.draft_cache.nbytes)
            entry.verifyfn = CarriedStepFn(
                _dm.make_paged_step_multi(cfg, kv_config, k + 1),
                donate_argnums=(0,), name="decode_verify",
                key_parts=dict(base_parts, kind="decode_verify",
                               cfg=cfg.to_dict(), width=k + 1))
            entry.rolloutfn = CarriedStepFn(
                _dm.make_draft_rollout(dcfg, draft_kv, k),
                donate_argnums=(0,), name="draft_rollout",
                key_parts=dict(base_parts, kind="draft_rollout",
                               cfg=dcfg.to_dict(), k=k))
            entry.ingestfn = CarriedStepFn(
                _dm.make_paged_step_multi(dcfg, draft_kv, k + 1),
                donate_argnums=(0,), name="draft_ingest",
                key_parts=dict(base_parts, kind="draft_ingest",
                               cfg=dcfg.to_dict(), width=k + 1))
        # engine-owned resident weights (target + draft) fold into the
        # MEM001 static peak beside the KV pool bytes
        _kvc.register_resident_bytes(entry, resident + draft_resident)
        self._models[name] = entry
        return entry

    def models(self):
        return list(self._models)

    def spec(self, model):
        m = self._models[model]
        out = {"model": model, "type": "decode", "arch": m.cfg.arch,
               "vocab": m.cfg.vocab, "max_seq": m.cfg.max_seq,
               "buckets": list(self.buckets), "mode": self.mode,
               "block_size": m.kv_config.block_size,
               "num_blocks": m.kv_config.num_blocks,
               "kv_dtype": m.kv_config.dtype,
               "speculative_k": m.spec_k,
               "prefix_cache": m.prefix is not None,
               "state_slots": m.kv_config.state_slots}
        if m.spec_k > 0:
            out["draft"] = {"layers": m.draft_cfg.layers,
                            "num_blocks": m.draft_kv_config.num_blocks,
                            "kv_bytes": m.draft_cache.nbytes}
        return out

    # -- AOT bucket prewarm --------------------------------------------------

    def prewarm(self):
        """Compile (or restore from the tier-B disk cache) the decode
        step for EVERY lane bucket before the first request.  After
        this, mixed-length continuous batching can only hit the
        in-memory executables: ``executor_cache_miss_total`` stays flat
        under load — the zero-runtime-compile proof."""

        def note(model, bucket, fn, got, **extra):
            # what the executable holds beside its arguments, and how
            # much of them (the KV pool) it updates in their own buffers
            _tm.inc("serving_prewarm_total", model=model,
                    source=got["source"])
            _tm.event("serving_prewarm", model=model, bucket=bucket,
                      source=got["source"], decode=True, fn=fn,
                      ms=round(got["compile_ms"], 3),
                      temp_bytes=got["temp_bytes"],
                      alias_bytes=got["alias_bytes"], **extra,
                      **self._models[model].account.prewarm_attrs(bucket))
            for key in ("temp_bytes", "alias_bytes"):
                if got[key] is not None:
                    _tm.set_gauge("serving_step_" + key, got[key],
                                  model=model, bucket=bucket, fn=fn)
            return {"source": got["source"],
                    "compile_ms": round(got["compile_ms"], 3)}

        manifest = {}
        with _tr.span("serving.prewarm") as span:
            for name, m in self._models.items():
                per = {}
                for b in self.buckets:
                    if m.spec_k > 0:
                        # speculation replaces the single-token step with
                        # three fns; warm each per (model, bucket, k)
                        w = m.spec_k + 1
                        warms = {
                            "verify": m.verifyfn.warmup(
                                (b, w), m.cache.carry(), m.params,
                                np.zeros((b, w), np.int32),
                                np.zeros((b, w), np.int32),
                                np.full((b, m.maxb), -1, np.int32),
                                np.zeros((b, w), np.int32)),
                            "draft_rollout": m.rolloutfn.warmup(
                                (b, m.spec_k), m.draft_cache.carry(),
                                m.draft_params,
                                np.zeros(b, np.int32), np.zeros(b, np.int32),
                                np.full((b, m.maxb), -1, np.int32),
                                np.zeros(b, np.int32), np.zeros(b, np.int32)),
                            "draft_ingest": m.ingestfn.warmup(
                                (b, w), m.draft_cache.carry(), m.draft_params,
                                np.zeros((b, w), np.int32),
                                np.zeros((b, w), np.int32),
                                np.full((b, m.maxb), -1, np.int32),
                                np.zeros((b, w), np.int32)),
                        }
                        per[b] = {kind: note(name, b, kind, got, k=m.spec_k)
                                  for kind, got in warms.items()}
                        continue
                    per[b] = note(name, b, "decode", m.stepfn.warmup(
                        b, *self._step_args(m, np.tile(m.idle_lane, (b, 1)))))
                manifest[name] = per
            _prewarm_attrs(span, manifest)
        return manifest

    @staticmethod
    def _step_args(m, lanes, prev=None):
        """The step's arguments (``make_packed_step``): the carry, the
        parameters, the step before's tokens on the device (None: nothing
        in flight, so no lane's ``src`` names one, as prewarm has it) and
        the lanes' integers in their one array."""
        return (m.cache.carry(), m.params,
                prev if prev is not None else m.feed0, lanes)

    # -- admission -----------------------------------------------------------

    def _retry_after_ms(self, m):
        """Time for roughly one block's worth of tokens to drain."""
        per = m.step_ms if m.step_ms > 0 else 1.0
        return max(per * m.kv_config.block_size, 1.0)

    def handoff_prefill_upto(self, model, prompt_len):
        """Tokens a prefill-role replica computes for this prompt: the
        last full-block boundary below ``len(prompt)`` (the partial tail
        block can never transfer — the prefix chain only keys FULL
        blocks, and ``match`` caps at len-1 so the decode half always
        computes at least one tail token itself).  0 means nothing is
        transferable and the request should be forwarded whole."""
        m = self._models.get(model)
        if m is None or m.prefix is None:
            return 0
        bs = m.kv_config.block_size
        return max(0, ((int(prompt_len) - 1) // bs) * bs)

    def submit(self, model, prompt_ids, max_new_tokens=16, tenant="default",
               deadline_ms=None, eos_id=-1, callback=None, on_token=None,
               req_id=None, traceparent=None, tier=None, handoff=False,
               resume_from=None, resume_tail=None):
        """Enqueue one autoregressive request; returns a _Pending whose
        reply carries outputs={"tokens"} plus TTFT/ITL phases.
        ``on_token(req_id, index, token, done, status)`` fires per
        generated token (the server publishes stream chunks from it);
        the terminal call carries token=None on non-ok completion.

        ``handoff=True`` is the prefill-role mode: the sequence runs
        chunked prefill up to the last full-block boundary, fires the
        ``on_block_sealed``/``on_handoff`` hooks as blocks seal, then
        completes with status "handoff" (never generating a token); the
        paired decode replica owns generation.

        ``resume_from`` (a migrated-in or crash-recovered session) is
        the list of tokens the client already holds: the sequence seeds
        its output with them, admission prefix-matches the full-history
        chain (prompt ++ tokens) instead of the prompt alone, and decode
        resumes at the next NEW index — no received token is ever
        re-emitted.  ``resume_tail`` optionally carries the migrated
        partial tail block ({"digest", "valid", "arrays"}); it is
        validated against the recomputed tail digest and dropped (the
        replay recomputes < 1 block) on any mismatch.  A resume for a
        req_id already live here is loudly refused — double migration
        must never double-run a session."""
        deadline_ms = float(deadline_ms or self.default_deadline_ms)
        prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        tier, weight = tier_weight(self.tier_weights, tier)
        req = _Pending(model, tenant, None, len(prompt_ids), deadline_ms,
                       req_id or uuid.uuid4().hex, callback,
                       traceparent=traceparent, tier=tier, weight=weight)

        def _early(reply):
            """Terminal before admission: also emit the done stream chunk
            so a streaming client unblocks instead of hanging on k=0."""
            if on_token is not None:
                try:
                    on_token(req.req_id, 0, None, True, reply.status)
                except Exception:
                    pass
            req.complete(reply)
            self._tokens_emitted()
            return req

        m = self._models.get(model)
        if m is None or not self._running:
            return _early(InferReply(
                "error", error="unknown decode model %r" % model
                if m is None else "decode engine not running"))
        if not prompt_ids:
            return _early(InferReply("error", error="empty prompt"))
        total = len(prompt_ids) + int(max_new_tokens)
        if total > m.cfg.max_seq:
            return _early(InferReply(
                "error",
                error="prompt+max_new %d exceeds max_seq %d"
                      % (total, m.cfg.max_seq)))
        if any(t < 0 or t >= m.cfg.vocab for t in prompt_ids):
            return _early(InferReply("error", error="token out of vocab"))
        need_cap = m.cache.blocks_for_tokens(total)
        if need_cap > m.cache.allocator.capacity:
            return _early(InferReply(
                "error",
                error="sequence needs %d KV blocks, pool holds %d"
                      % (need_cap, m.cache.allocator.capacity)))
        if m.spec_k > 0 and m.draft_cache.blocks_for_tokens(total) > \
                m.draft_cache.allocator.capacity:
            return _early(InferReply(
                "error",
                error="sequence needs %d draft KV blocks, pool holds %d"
                      % (m.draft_cache.blocks_for_tokens(total),
                         m.draft_cache.allocator.capacity)))
        if handoff:
            upto = self.handoff_prefill_upto(model, len(prompt_ids))
            if upto <= 0:
                return _early(InferReply(
                    "error", error="nothing to hand off: prompt of %d has "
                    "no full %d-token block below its tail"
                    % (len(prompt_ids), m.kv_config.block_size)))
        resume_out = None
        if resume_from is not None:
            toks = [int(t) for t in np.asarray(resume_from).reshape(-1)]
            err = None
            if handoff:
                err = "resume_from resumes decode; handoff is prefill-role"
            elif not toks:
                err = "resume_from carries no tokens"
            elif len(toks) >= int(max_new_tokens):
                err = "resume_from already holds all %d requested " \
                      "tokens" % int(max_new_tokens)
            elif int(eos_id) >= 0 and int(eos_id) in toks:
                err = "resume_from already contains eos"
            elif any(t < 0 or t >= m.cfg.vocab for t in toks):
                err = "resume token out of vocab"
            if err is not None:
                _tm.inc("kv_migrate_resume_total", result="refused",
                        model=model)
                _tm.inc("kv_migrate_refused_total", reason="bad_resume")
                return _early(InferReply("error", error=err))
            resume_out = toks
        _tm.inc("serving_decode_requests_total", model=model, tenant=tenant)
        seq = _DecodeSeq(req, prompt_ids, max_new_tokens, eos_id, on_token,
                         m.maxb)
        if handoff:
            seq.handoff = True
            seq.prefill_upto = upto
        if resume_out is not None:
            seq.out = resume_out
            seq.replay_upto = len(prompt_ids) + len(resume_out)
            seq.resume_tail = resume_tail
        with self._cond:
            req.t_locked = time.perf_counter()
            if resume_out is not None and (
                    req.req_id in self._migrating or any(
                        s.pending.req_id == req.req_id
                        for s in self._active + self._waiting)):
                _tm.inc("kv_migrate_resume_total", result="refused",
                        model=model)
                _tm.inc("kv_migrate_refused_total", reason="duplicate")
                return _early(InferReply(
                    "error", error="req_id %s is already live here "
                    "(double migration refused)" % req.req_id))
            if self._draining:
                _tm.inc("serving_shed_total", reason="draining")
                _tm.inc("serving_tier_shed_total", tier=tier)
                return _early(InferReply(
                    "shed", error="replica draining",
                    retry_after_ms=self._retry_after_ms(m)))
            if len(self._waiting) >= self.max_queue:
                # tier eviction mirrors ServingEngine: a full waiting
                # queue sheds its lowest-weight member when the arrival
                # outranks it
                victim = min(self._waiting,
                             key=lambda s: (s.pending.weight,
                                            -s.pending.t_submit)) \
                    if self._waiting else None
                if victim is not None and victim.pending.weight < weight:
                    self._waiting.remove(victim)
                    _tm.inc("serving_shed_total", reason="tier_evicted")
                    _tm.inc("serving_tier_shed_total",
                            tier=victim.pending.tier)
                    self._finish(victim, InferReply(
                        "shed",
                        error="evicted by %s-tier arrival" % tier,
                        retry_after_ms=self._retry_after_ms(m)))
                else:
                    _tm.inc("serving_shed_total", reason="queue_full")
                    _tm.inc("serving_tier_shed_total", tier=tier)
                    return _early(InferReply(
                        "shed",
                        error="queue full (%d)" % len(self._waiting),
                        retry_after_ms=self._retry_after_ms(m)))
            # admission-time KV pressure: blocks already promised to the
            # queue ahead plus this prompt must fit the RECLAIMABLE pool
            # (free list + zero-ref evictable cached blocks — a warm
            # prefix cache never causes a spurious shed; alloc reclaims
            # evictable LRU-first on demand) — BOTH pools when
            # speculating (the draft shadows every sequence) — else shed
            # with a drain-time hint instead of queueing behind an
            # out-of-memory head-of-line
            promised = sum(
                m.cache.blocks_for_tokens(s.replay_upto)
                for s in self._waiting if s.pending.model == model)
            need_now = promised + m.cache.blocks_for_tokens(seq.replay_upto)
            free_now = m.cache.allocator.reclaimable
            if m.spec_k > 0:
                # equal block geometry -> the same block count applies;
                # the binding pool is whichever could free fewer blocks
                # (the draft pool never seals, so its reclaimable == free)
                free_now = min(free_now,
                               m.draft_cache.allocator.reclaimable)
            if need_now > free_now:
                _tm.inc("serving_shed_total", reason="kv_oom")
                _tm.inc("serving_tier_shed_total", tier=tier)
                return _early(InferReply(
                    "shed",
                    error="KV pool exhausted (%d reclaimable blocks)"
                          % free_now,
                    retry_after_ms=self._retry_after_ms(m)))
            req.span = _tr.start_span(
                "serving.request", model=model, tenant=tenant,
                decode=True, prompt_tokens=len(prompt_ids),
                max_new=int(max_new_tokens), req_id=req.req_id)
            req.qspan = _tr.start_span("serving.queue_wait",
                                       parent=req.span,
                                       depth=len(self._waiting))
            self._waiting.append(seq)
            if resume_out is not None:
                _tm.inc("kv_migrate_resume_total", result="accepted",
                        model=model)
            _tm.set_gauge("serving_queue_depth",
                          len(self._waiting))
            self._cond.notify_all()
        return req

    def generate(self, model, prompt_ids, max_new_tokens=16, **kw):
        """Synchronous submit + wait."""
        deadline_ms = float(kw.get("deadline_ms")
                            or self.default_deadline_ms)
        req = self.submit(model, prompt_ids,
                          max_new_tokens=max_new_tokens, **kw)
        reply = req.wait(timeout=deadline_ms / 1e3 + 30.0)
        return reply if reply is not None else InferReply(
            "timeout", error="no reply within deadline")

    def abort(self, req_id):
        """Drop a sequence by request id (client replay after a timeout
        sends this so an abandoned prefill frees its blocks).  Returns
        True when a waiting/active sequence was found."""
        with self._cond:
            for i, s in enumerate(self._waiting):
                if s.pending.req_id == req_id:
                    self._waiting.pop(i)
                    _tm.set_gauge("serving_queue_depth", len(self._waiting))
                    self._finish(s, InferReply("aborted",
                                               error="aborted by client"))
                    _tm.inc("serving_abort_total", phase="queued")
                    return True
            for s in self._active:
                if s.pending.req_id == req_id and not s.aborted:
                    s.aborted = True   # decode loop frees at next boundary
                    _tm.inc("serving_abort_total",
                            phase="prefill" if s.in_prefill else "decode")
                    return True
        return False

    # -- sealed-block adoption (the decode half of a disaggregated pair) -----

    def adopt_kv_block(self, model, digest, arrays):
        """Adopt one transferred sealed block into ``model``'s pool:
        allocate a private block, install the payload into the carry,
        publish it under ``digest`` and park it evictable — the commit
        frame's ordinary ``submit`` then prefix-matches it exactly like a
        locally-computed cache hit (refcount + hash-chain invariants come
        from the existing machinery, not a parallel path).  Returns
        "adopted", "cached" (digest already indexed — the warm-replica
        skip), or "rejected:<reason>"; rejection is always safe because
        the commit frame carries the full prompt and the engine simply
        recomputes the prefill locally."""
        m = self._models.get(model)
        if m is None:
            return "rejected:unknown model %r" % (model,)
        if m.declines is not None:
            # K/V blocks alone cannot continue a sequence whose other
            # layers hold a state
            _tm.inc("kv_migrate_refused_total", reason=m.declines)
            return "rejected:%s" % m.declines
        if m.prefix is None:
            return "rejected:prefix cache disabled"
        with self._cond:
            if m.prefix.lookup(digest) is not None:
                _tm.inc("kv_xfer_adopt_total", result="cached",
                        model=model)
                return "cached"
            got = m.cache.allocator.alloc(1)
            if got is None:
                _tm.inc("kv_xfer_adopt_total", result="nopool",
                        model=model)
                return "rejected:kv pool exhausted"
            b = got[0]
            try:
                # a step is dispatched, and the carry swapped for its
                # outputs, under self._cond: this update queues behind
                # the step in flight, race-free
                m.cache.import_block(b, arrays)
            except Exception as e:
                m.cache.allocator.free([b])
                _tm.inc("kv_xfer_adopt_total", result="geometry",
                        model=model)
                return "rejected:%s" % e
            if not m.prefix.publish(b, digest):
                # lost a publish race — the digest is resident anyway
                m.cache.allocator.free([b])
                _tm.inc("kv_xfer_adopt_total", result="cached",
                        model=model)
                return "cached"
            # drop our reference: the sealed block parks evictable,
            # resident and revivable until a matching submit arrives
            m.cache.allocator.free([b])
            _tm.inc("kv_xfer_adopt_total", result="adopted", model=model)
            return "adopted"

    def forget_adopted(self, model, digests):
        """Abort reconciliation: un-index + truly free still-evictable
        adopted blocks of a request that died on the prefill half.
        Blocks revived in-use by a live sequence are left to their owner.
        Returns how many index entries existed."""
        m = self._models.get(model)
        if m is None or m.prefix is None:
            return 0
        n = 0
        with self._cond:
            for d in digests:
                if m.prefix.forget(d):
                    n += 1
        if n:
            _tm.inc("kv_xfer_forget_total", n, model=model)
        return n

    # -- live session migration (serving/migrate.py drives these) ------------

    def _refuse_export(self, req_id, reason):
        _tm.inc("kv_migrate_refused_total", reason=reason)
        raise ValueError("cannot migrate %s: %s" % (req_id, reason))

    def export_session(self, req_id):
        """Phase 1 of a migration hand-off: detach a live sequence at
        the current iteration boundary and snapshot everything a peer
        needs to continue it — ``(manifest, payloads)``.

        The manifest is the session descriptor (tokens ride as
        ``_prompt_arr``/``_out_arr`` int32 arrays, stripped onto the
        wire frame's payload by the migrator); ``payloads`` is one
        ``(block_index, digest, arrays, is_tail)`` tuple per shippable
        KV block — every fully-fed history block under its chain
        digest, plus the partial tail block sealed at migration time
        under a domain-separated ``tail_digest``.  The sequence stays
        parked in ``_migrating`` (invisible to the scheduler, blocks
        refcounted) until ``commit_migration`` or ``abort_migration``
        decides its fate — at most one replica ever runs it.

        Refusals raise ValueError and leave the engine unperturbed:
        unknown/finished ids, double migration (parked or recently
        committed away), aborted/handoff sequences, sequences still in
        prefill or replay (re-prefill is cheap and a half-fed block has
        no stable digest), and engines without a prefix cache or with
        ``FLAGS_session_migration`` off."""
        with self._cond:
            # the drain rule: position, ``out`` and the blocks' content
            # are exact only once the step in flight has been read
            self._drain_locked()
            seq, waiting = None, False
            for s in self._active:
                if s.pending.req_id == req_id:
                    seq = s
                    break
            if seq is None:
                for s in self._waiting:
                    if s.pending.req_id == req_id:
                        seq, waiting = s, True
                        break
            if seq is None:
                if req_id in self._migrating:
                    self._refuse_export(req_id, "already_migrating")
                if req_id in self._migrated:
                    self._refuse_export(req_id, "already_migrated")
                self._refuse_export(req_id, "unknown")
            if seq.aborted:
                self._refuse_export(req_id, "aborted")
            if seq.handoff:
                self._refuse_export(req_id, "handoff")
            if not seq.out or (not waiting and seq.in_prefill):
                self._refuse_export(req_id, "in_prefill")
            m = self._model_of(seq)
            if m.declines is not None:
                self._refuse_export(req_id, m.declines)
            if m.prefix is None or not bool(_flag("session_migration")):
                self._refuse_export(req_id, "disabled")
            if m.kv_config.dtype == "bf16":
                # codec.py frames an array by numpy's dtype string, which
                # bfloat16 does not have: refuse, never mis-encode
                self._refuse_export(req_id, "dtype")
            bs = m.kv_config.block_size
            # steady decode keeps n_fed == len(prompt ++ out) - 1 (the
            # last emitted token is fed by the NEXT step); a preempted
            # waiting victim resumes at the same position
            pos = len(seq.prompt) + len(seq.out) - 1 if waiting \
                else seq.n_fed
            nfull = pos // bs
            digests = [self._hist_digest_locked(m, seq, j)
                       for j in range(nfull)]
            payloads = []
            if waiting:
                # preempted victim: its blocks were freed, but published
                # history blocks may still sit evictable — revive what
                # survived and ship that; the destination replays the
                # rest (no tail: the partial block never sealed)
                borrowed = m.prefix.match_digests(digests)
                for j, b in enumerate(borrowed):
                    payloads.append((j, digests[j],
                                     m.cache.export_block(b), False))
                if borrowed:
                    m.cache.allocator.free(borrowed)
            else:
                for j in range(nfull):
                    payloads.append((j, digests[j],
                                     m.cache.export_block(seq.blocks[j]),
                                     False))
                if pos > nfull * bs:
                    from .migrate import tail_digest as _tail_digest
                    td = _tail_digest(
                        digests[-1] if digests else None,
                        seq.feed_slice(nfull * bs, pos - nfull * bs))
                    payloads.append((nfull, td,
                                     m.cache.export_block(seq.blocks[nfull]),
                                     True))
            now = time.perf_counter()
            manifest = {
                "req_id": req_id, "model": seq.pending.model,
                "pos": int(pos), "block_size": int(bs),
                "dtype": str(m.kv_config.dtype), "digests": digests,
                "max_new_tokens": int(seq.max_new),
                "eos_id": int(seq.eos_id),
                "tier": seq.pending.tier, "tenant": seq.pending.tenant,
                "deadline_ms": max(
                    round((seq.pending.deadline - now) * 1e3, 3), 1.0),
                "stream": seq.on_token is not None,
                "spec_k": int(m.spec_k),
                "_prompt_arr": np.asarray(seq.prompt, np.int32),
                "_out_arr": np.asarray(seq.out, np.int32),
            }
            if waiting:
                self._waiting.remove(seq)
                _tm.set_gauge("serving_queue_depth", len(self._waiting))
            else:
                self._active.remove(seq)
            self._migrating[req_id] = seq
            _tm.event("session_export", req_id=req_id, pos=int(pos),
                      model=seq.pending.model, blocks=len(payloads),
                      waiting=waiting)
            return manifest, payloads

    def commit_migration(self, req_id, peer):
        """Phase 3 success: the destination acked "resumed" — free the
        parked victim's blocks and finish it with status "migrated";
        reply phases carry ``migrated_to`` so a waiting client follows
        the session to its new home."""
        with self._cond:
            seq = self._migrating.pop(req_id, None)
            if seq is None:
                return False
            self._migrated.append(req_id)
            del self._migrated[:-256]
            self._free_blocks(seq)
            self._finish(seq, InferReply(
                "migrated", error="session migrated to %s" % peer,
                phases={"migrated_to": peer}))
            self._cond.notify_all()
        _tm.event("session_migrated", req_id=req_id, peer=peer)
        return True

    def abort_migration(self, req_id):
        """Phase 3 failure: the push died or the destination refused —
        re-queue the victim at the FRONT for deterministic local
        recompute.  Its emitted tokens are kept (replay never
        re-emits), so the client sees at most a latency blip.  Zero
        drops, and at most one replica ever runs the session."""
        with self._cond:
            seq = self._migrating.pop(req_id, None)
            if seq is None:
                return False
            # the drain rule (the parked sequence itself has nothing in
            # flight: export_session read it back before parking it)
            self._drain_locked()
            self._free_blocks(seq)
            seq.reset_for_recompute()
            self._waiting.insert(0, seq)
            _tm.set_gauge("serving_queue_depth", len(self._waiting))
            self._cond.notify_all()
        return True

    # -- decode loop ---------------------------------------------------------

    def start(self):
        if self._running:
            return self
        self._running = True
        self._thread = threading.Thread(target=self._decode_loop,
                                        name="serving-decode", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain_s=5.0):
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(drain_s)
            self._thread = None
        with self._cond:
            # the loop read its last step back as it left; this is for a
            # loop that did not leave in time
            self._drain_locked()
            leftovers = self._active + self._waiting + \
                list(self._migrating.values())
            self._active, self._waiting = [], []
            self._migrating = {}
        for s in leftovers:
            self._free_blocks(s)
            self._finish(s, InferReply("error", error="engine stopped"))

    @property
    def draining(self):
        return self._draining

    def drain(self, timeout_s=30.0, migrate=None):
        """Graceful retirement (ServingEngine.drain contract): shed new
        arrivals, wait for every waiting AND active sequence to finish.

        ``migrate`` (``SessionMigrator.drain_push()``) turns the wait
        into drain-by-migration: each live mid-decode session is pushed
        to a surviving peer at a batch boundary instead of being waited
        out — a retiring replica with long generations in flight empties
        in O(transfer), not O(remaining tokens).  A session whose push
        fails (no peer, refusal, wire error) is remembered and simply
        waited out the old way; nothing is ever dropped."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = time.perf_counter() + timeout_s
        failed = set()
        while time.perf_counter() < deadline:
            cand = None
            with self._cond:
                if not self._waiting and not self._active \
                        and not self._migrating:
                    return True
                if migrate is not None:
                    # choose by what has been read back (the drain rule)
                    self._drain_locked()
                    for s in self._active + self._waiting:
                        rid = s.pending.req_id
                        if rid in failed or s.handoff or s.aborted \
                                or not s.out:
                            continue
                        if s in self._active and s.in_prefill:
                            continue
                        cand = (rid, s.pending.model)
                        break
            if cand is not None:
                # the push itself runs OUTSIDE the step lock (it is an
                # RPC); export_session re-checks liveness under the lock
                ok = False
                try:
                    ok = bool(migrate(cand[0], cand[1]))
                except Exception:
                    ok = False
                if not ok:
                    failed.add(cand[0])
                continue
            time.sleep(0.01)
        return False

    def _model_of(self, seq):
        return self._models[seq.pending.model]

    def _free_blocks(self, seq):
        m = self._model_of(seq)
        if seq.blocks:
            m.cache.allocator.free(seq.blocks)
            seq.blocks = []
            seq.table.fill(-1)
        if seq.draft_blocks:
            m.draft_cache.allocator.free(seq.draft_blocks)
            seq.draft_blocks = []
            seq.draft_table.fill(-1)
        if seq.state_slot is not None:
            # with the blocks, wherever they go: finish, abort, timeout,
            # preemption, error.  Nothing is cleared: the next holder's
            # first step starts from zeros
            m.cache.slots.give(seq.state_slot)
            seq.state_slot = None
        if seq.window_ring is not None:
            m.cache.release_ring(seq.window_ring)
            seq.window_ring = None

    def _tokens_emitted(self, step=False):
        """Every ``on_token`` call of this iteration has been made: tell
        whoever gathers them (``on_tokens_emitted``), and whether a step's
        emit ends here.  Returns the step span's attributes from it:
        ``published``, how many stream chunks that stored (0 with no hook
        set), and whatever else the hook hands a step, unread."""
        got = 0
        if self.on_tokens_emitted is not None:
            try:
                got = self.on_tokens_emitted(step=step) or 0
            except Exception:
                pass
        published, attrs = got if isinstance(got, tuple) else (got, {})
        return dict(attrs, published=int(published))

    def _finish(self, seq, reply):
        r = seq.pending
        if reply.ok or reply.status in ("timeout", "migrated"):
            now = time.perf_counter()
            phases = {"queue_wait_ms": round(
                ((seq.t_admit or now) - r.t_submit) * 1e3, 3),
                "tokens": len(seq.out),
                "prompt_tokens": len(seq.prompt),
                "cached_tokens": seq.cached_tokens,
                "tier": r.tier, "model": r.model}
            if seq.replay_upto > len(seq.prompt):
                # resumed/replayed sessions: tokens that were already
                # emitted (re-fed, never re-emitted); with cached_tokens
                # this yields the re-prefill cost of a migration
                phases["resumed_tokens"] = \
                    seq.replay_upto - len(seq.prompt)
            if seq.t_first is not None:
                phases["ttft_ms"] = round(
                    (seq.t_first - r.t_submit) * 1e3, 3)
            if len(seq.token_times) > 1:
                gaps = [(b - a) * 1e3 for a, b in
                        zip(seq.token_times, seq.token_times[1:])]
                phases["itl_ms_samples"] = [round(g, 3) for g in gaps]
            if reply.phases:
                phases.update(reply.phases)
            reply.phases = phases
        out_tokens = np.asarray(seq.out, np.int32)
        if reply.ok:
            reply.outputs = {"tokens": out_tokens}
        elif seq.on_token is not None:
            # terminal stream chunk so a streaming client unblocks even
            # on shed/timeout/abort/error; emitted before the reply, so it
            # is in the store no later than the reply is
            try:
                seq.on_token(r.req_id, len(seq.out), None, True,
                             reply.status)
            except Exception:
                pass
        r.complete(reply)
        if not reply.ok:
            # outside a step's emit nothing else says the chunk is out
            self._tokens_emitted()
        else:
            # fleet-mergeable per-phase histograms: per-tier server_ms
            # (end-to-end on this replica), per-model TTFT and ITL —
            # fleetmon's SLO rules (decode ITL p99) window their bucket
            # deltas; deadline-met replies/tokens are the goodput
            # numerators, raw completions/tokens the denominators
            _tm.observe("server_ms", reply.latency_ms, tier=r.tier)
            if "ttft_ms" in reply.phases:
                _tm.observe("ttft_ms", reply.phases["ttft_ms"],
                            model=r.model)
            _tm.observe_many("itl_ms",
                             reply.phases.get("itl_ms_samples") or (),
                             model=r.model)
            met = time.perf_counter() <= r.deadline
            _tm.inc("serving_deadline_met_total" if met
                    else "serving_deadline_missed_total", tier=r.tier)
            if met:
                _tm.inc("serving_deadline_tokens_total", len(seq.out),
                        tier=r.tier)
        if r.qspan is not None:
            r.qspan.end()
            r.qspan = None
        if r.span is not None:
            r.span.annotate(status=reply.status,
                            tokens=len(seq.out)).end()
            r.span = None

    def _expire_and_admit(self):
        """Under the lock: time out stale waiters, then admit while
        lanes + blocks allow.  Returns the per-model active map."""
        now = time.perf_counter()
        keep = []
        for s in self._waiting:
            if now > s.pending.deadline:
                _tm.inc("serving_timeout_total", model=s.pending.model)
                self._finish(s, InferReply(
                    "timeout", error="deadline expired in queue"))
            else:
                keep.append(s)
        self._waiting[:] = keep
        max_lanes = max(self.buckets)
        while self._waiting and len(self._active) < max_lanes:
            if self.mode == "request" and self._active:
                break  # request-level baseline: no mid-flight joins
            s = self._waiting[0]
            m = self._model_of(s)
            if self._active and self._active[0].pending.model != \
                    s.pending.model:
                break  # one model per step batch
            # reclaimable = free + zero-ref evictable cached blocks: a
            # warm prefix cache never blocks admission (alloc reclaims
            # LRU-first on demand)
            free = m.cache.allocator.reclaimable
            if m.spec_k > 0:
                free = min(free, m.draft_cache.allocator.reclaimable)
            if m.cache.blocks_for_tokens(s.replay_upto) > free:
                break  # head-of-line waits for blocks to free
            self._waiting.pop(0)
            self._admit_seq += 1
            s.admit_seq = self._admit_seq
            if s.t_admit is None:
                # first admission (not a preempted sequence's replay)
                r = s.pending
                self._admit_waits.append(
                    (round((now - r.t_submit) * 1e3, 3),
                     round(((r.t_locked or now) - r.t_submit) * 1e3, 3)))
            s.t_admit = now
            if m.cache.slots is not None:
                s.state_slot = m.cache.slots.take()
            if m.cache.window_allocator is not None:
                s.window_ring = m.cache.new_ring()
            if m.declines is not None and bool(_flag("prefix_cache")):
                # no index to match: the whole prompt (or replay) is fed
                _tm.inc("prefix_cache_declined_total", model=m.name,
                        reason=m.declines)
            if m.prefix is not None and s.replay_upto > len(s.prompt):
                # resumed (migrated-in) or preempted replay: match the
                # full-history chain instead of the prompt alone
                self._admit_resume_locked(m, s)
            elif m.prefix is not None:
                # longest-prefix match: seed the block table with shared
                # (ref-taken) blocks and jump the feed pointer past the
                # cached tokens — prefill computes only the uncached tail.
                # The match is capped at len(prompt)-1 tokens, so there is
                # always a next token to feed and every write this
                # sequence makes lands in a PRIVATE tail block.
                shared, cached, hashes = m.prefix.match(s.prompt)
                s.hashes = hashes
                s.published = len(shared)
                s.cached_tokens = cached
                if cached:
                    s.blocks = list(shared)
                    s.table[:len(shared)] = shared
                    s.n_fed = cached
                if s.handoff and self.on_block_sealed is not None:
                    # a warm prefill replica still announces prefix-hit
                    # digests: the decode peer may be cold (the sender's
                    # per-peer dedupe skips already-shipped ones)
                    want = s.prefill_upto // m.kv_config.block_size
                    for j in range(min(len(shared), want)):
                        self.on_block_sealed(m, s, j, hashes[j])
            if s.pending.span is not None:
                s.pending.span.annotate(cached_tokens=s.cached_tokens)
            if s.pending.qspan is not None:
                s.pending.qspan.end()
                s.pending.qspan = None
            s.n_disp = s.n_fed      # nothing dispatched beyond the match
            self._active.append(s)
        _tm.set_gauge("serving_queue_depth", len(self._waiting))
        # per-model pressure gauges ride the 1s __metrics__ republish:
        # the role-aware autoscaler scales decode replicas on live
        # KV-pool occupancy, routers on the prefix hit rate
        for name, m in self._models.items():
            alloc = m.cache.allocator
            cap = float(alloc.capacity) or 1.0
            _tm.set_gauge("kv_pool_occupancy", alloc.in_use / cap,
                          model=name)
            if m.prefix is not None:
                _tm.set_gauge("prefix_cache_hit_rate", m.prefix.hit_rate(),
                              model=name)

    def _ensure_block(self, seq):
        """Single-token path: cover the position being dispatched."""
        return self._ensure_capacity(seq, seq.n_disp + 1)

    def _ensure_capacity(self, seq, upto, draft_upto=0):
        """Grow seq's block table(s) to cover ``upto`` tokens (and the
        draft's to ``draft_upto`` when speculating) with all-or-nothing
        multi-block allocations; preempt the youngest OTHER active
        sequence on pool exhaustion.  False means seq itself was
        defensively completed (should not happen — submit() capped every
        sequence's total need at pool capacity)."""
        m = self._model_of(seq)
        while True:
            ok = m.cache.ensure_table(seq.table, seq.blocks, upto)
            if ok and draft_upto > 0:
                ok = m.draft_cache.ensure_table(
                    seq.draft_table, seq.draft_blocks, draft_upto)
            if ok:
                return True
            victims = [s for s in self._active if s is not seq]
            if not victims:
                self._discard_in_flight(seq, "error")
                self._active.remove(seq)
                self._free_blocks(seq)
                self._finish(seq, InferReply(
                    "error", error="KV pool exhausted with no victim"))
                return False
            v = max(victims, key=lambda s: s.admit_seq)
            self._discard_in_flight(v, "preempted")
            self._active.remove(v)
            self._free_blocks(v)
            v.reset_for_recompute()
            self._waiting.insert(0, v)
            if v.out:
                # pressure-trigger migration candidate: reported through
                # on_preempt at the next batch boundary (lock released)
                self._preempted.append((v.pending.req_id,
                                        v.pending.model))
            _tm.inc("kv_block_evictions_total",
                    model=v.pending.model)
            _tm.event("decode_preempt", victim=v.pending.req_id,
                      for_req=seq.pending.req_id)

    def _publish_prefix_locked(self, m, s):
        """Publish every newly-completed FULL prompt block of ``s`` into
        the prefix index (first-publisher-wins; a losing duplicate stays
        private and frees normally).  Only blocks whose every position
        holds a prompt token are eligible — decode-written and partially
        fed blocks can never be published, which is what makes a
        mid-prefill abort safe by construction."""
        if m.prefix is None or s.hashes is None:
            return
        bs = m.kv_config.block_size
        done = min(s.n_fed, len(s.prompt)) // bs
        while s.published < min(done, len(s.hashes)):
            j = s.published
            m.prefix.publish(s.blocks[j], s.hashes[j])
            s.published = j + 1
            if s.handoff and self.on_block_sealed is not None \
                    and j < s.prefill_upto // bs:
                self.on_block_sealed(m, s, j, s.hashes[j])

    def _hist_digest_locked(self, m, s, j):
        """``j``-th full-block digest of the prompt ++ out hash chain
        (memoized in ``s.hist_hashes``; the prompt-only prefix of the
        chain is identical to ``s.hashes``, so it is reused)."""
        bs = m.kv_config.block_size
        while len(s.hist_hashes) <= j:
            i = len(s.hist_hashes)
            if s.hashes is not None and i < len(s.hashes):
                s.hist_hashes.append(s.hashes[i])
                continue
            prev = s.hist_hashes[i - 1] if i else None
            s.hist_hashes.append(m.prefix.extend_chain(
                prev, s.feed_slice(i * bs, bs)))
        return s.hist_hashes[j]

    def _publish_history_locked(self, m, s):
        """Publish every newly-completed history block — full blocks
        whose tokens extend past the prompt — under its prompt ++ out
        chain digest (FLAGS_session_migration).  Eligibility mirrors
        ``_publish_prefix_locked``: a block publishes only once every
        one of its positions is fed, so its KV content is final (later
        writes land in later blocks) and any future matcher replays the
        exact tokens that produced it.  This is what makes crash resume
        O(tokens since the last sealed block): a replica that ran the
        same prompt before holds the whole history chain evictable."""
        if m.prefix is None or s.handoff \
                or not bool(_flag("session_migration")):
            return
        bs = m.kv_config.block_size
        first = len(s.prompt) // bs    # prompt-only blocks: see above
        done = s.n_fed // bs
        if s.hist_published < first:
            s.hist_published = first
        while s.hist_published < done:
            j = s.hist_published
            m.prefix.publish(s.blocks[j],
                             self._hist_digest_locked(m, s, j))
            s.hist_published = j + 1

    def _admit_resume_locked(self, m, s):
        """Resume-path admission (``replay_upto > len(prompt)``): match
        the full-history chain — prompt ++ already-emitted tokens —
        instead of the prompt alone, then adopt the migrated tail
        partial block when every full block below it matched.  Serves
        both a migrated-in session and a preempted local replay (whose
        own published history revives here).  Anything unmatched is
        simply replayed: outputs are bitwise identical either way."""
        bs = m.kv_config.block_size
        pos = s.replay_upto - 1      # the last emitted token is re-fed
        nfull = pos // bs
        s.hashes = m.prefix.chain(s.prompt)
        digests = [self._hist_digest_locked(m, s, j)
                   for j in range(nfull)]
        blocks = m.prefix.match_digests(digests)
        if blocks:
            s.blocks = list(blocks)
            s.table[:len(blocks)] = blocks
            s.n_fed = len(blocks) * bs
        s.published = min(len(blocks), len(s.hashes))
        s.hist_published = len(blocks)
        tail, s.resume_tail = s.resume_tail, None
        if tail is not None and len(blocks) == nfull \
                and nfull * bs < pos:
            from .migrate import tail_digest as _tail_digest
            want = _tail_digest(digests[-1] if digests else None,
                                s.feed_slice(nfull * bs, pos - nfull * bs))
            if tail.get("digest") != want \
                    or int(tail.get("valid", -1)) != pos - nfull * bs:
                # a stale/foreign tail is dropped, not trusted: the
                # replay recomputes it (< 1 block of work)
                _tm.inc("kv_migrate_refused_total",
                        reason="tail_mismatch")
            else:
                got = m.cache.allocator.alloc(1)
                if got is not None:
                    b = got[0]
                    try:
                        m.cache.import_block(b, tail["arrays"])
                    except Exception:
                        m.cache.allocator.free([b])
                    else:
                        # PRIVATE tail block owned by the resumed
                        # sequence — never indexed (partial blocks must
                        # not prefix-match)
                        s.blocks.append(b)
                        s.table[nfull] = b
                        s.n_fed = pos
        s.cached_tokens = s.n_fed

    def _prefill_limit(self, s):
        """Last position this replica feeds for ``s``: the known
        history (prompt, plus replayed tokens for a resume), or the
        handoff boundary for a prefill-role sequence."""
        return s.prefill_upto if s.handoff else s.replay_upto

    def _sweep_handoff_locked(self):
        """Complete handoff sequences whose feed pointer reached the
        boundary (lock held, before the step builds lanes): fire
        ``on_handoff`` while the blocks are still owned — the hook
        snapshots nothing, the sealed blocks were already streamed — then
        free and finish with status "handoff" (the prefill replica's
        terminal state; the decode half owns the client-visible reply).
        A sequence dispatched up to its boundary whose last step is still
        in flight is read back first (the drain rule)."""
        handoffs = [s for s in self._active if s.handoff]
        if any(s.n_fed < s.prefill_upto <= s.n_disp for s in handoffs):
            self._drain_locked()
        for s in handoffs:
            if s.n_fed < s.prefill_upto:
                continue
            m = self._model_of(s)
            self._active.remove(s)
            if self.on_handoff is not None:
                try:
                    self.on_handoff(m, s)
                except Exception:
                    pass
            self._free_blocks(s)
            self._finish(s, InferReply("handoff"))
            _tm.inc("serving_handoff_total", model=m.name)

    def _plan_lanes_locked(self, chunk):
        """Token-budget prefill scheduling -> (participants, span_caps).

        With ``FLAGS_decode_prefill_token_budget`` unset every active
        lane participates (legacy order).  With a budget B, decode lanes
        ALWAYS run — bounding decode ITL under a prompt burst is the
        point — and prefilling lanes join round-robin until their summed
        prefill spans (up to ``chunk`` tokens each) reach B; the rest sit
        out this iteration and move to the front of the rotation next
        time.  ``span_caps`` maps id(seq) -> this iteration's prefill
        span cap (spec mode feeds multi-token chunks; non-spec feeds one
        token, so the cap only gates participation).  Pure scheduling:
        participants still pad to a configured lane bucket, so no new
        shape is ever compiled.

        Planning goes by what has been dispatched (``n_disp``): a
        sequence whose every position has a step (its last token, or its
        hand-off boundary, is in flight) sits out until that step is
        read."""
        max_lanes = max(self.buckets)
        budget = int(_flag("decode_prefill_token_budget") or 0)
        ready = [s for s in self._active if s.n_disp < s.feed_limit]
        if budget <= 0:
            return ready[:max_lanes], {}
        decode = [s for s in ready if s.n_disp >= s.replay_upto]
        prefill = [s for s in ready if s.n_disp < s.replay_upto]
        if prefill:
            r = self._rr_prefill % len(prefill)
            prefill = prefill[r:] + prefill[:r]
        chosen, caps, left = [], {}, budget
        for s in prefill:
            if left <= 0 or len(decode) + len(chosen) >= max_lanes:
                break
            span = min(chunk, self._prefill_limit(s) - s.n_disp, left)
            caps[id(s)] = span
            left -= span
            chosen.append(s)
        self._rr_prefill += max(len(chosen), 1)
        return (decode + chosen)[:max_lanes], caps

    def _bucket_for(self, lanes):
        for b in self.buckets:
            if lanes <= b:
                return b
        return max(self.buckets)

    def _decode_loop(self):
        while self._loop_once():
            pass

    def _loop_once(self):
        """One iteration of the decode loop; False once the engine has
        been stopped.  Every stretch of it runs inside a tracing.phase, so
        a profile names what the host did in each device idle gap and the
        step span's ``phases`` attribute accounts for the whole period."""
        # named fault point OUTSIDE the lock: a "delay" spec slows
        # every decode iteration (slow-replica chaos — keeps
        # sessions alive across a drain/kill window in CI) without
        # holding submitters on the cond during the sleep
        with _tr.phase("serving.between_steps"):
            maybe_fail("serving.decode_step")
        with _tr.phase("serving.lock_wait") as lock_wait, self._cond:
            lock_wait.stop()
            if not self._running:
                # stop() finds every sequence at its exact position
                self._drain_locked()
                return False
            with _tr.phase("serving.admit"):
                self._expire_and_admit()
            if not self._active:
                # a step whose every lane has left is read and dropped
                self._drain_locked()
                with _tr.phase("serving.idle"):
                    # for an arrival, at most 50 ms: then round through
                    # admission (and its gauges) again
                    until = time.perf_counter() + 0.05
                    queued = len(self._waiting)
                    while self._running and len(self._waiting) == queued:
                        left = until - time.perf_counter()
                        if left <= 0:
                            break
                        self._cond.wait(left)
                # waiting for work is not the host keeping the
                # device waiting: gap_us counts from here, and the next
                # step's span has no period
                self._t_fetched = time.perf_counter()
                self._t_step_opened = None
                return True
            step_ok = self._decode_step_locked()
            preempted, self._preempted = self._preempted, []
        with _tr.phase("serving.between_steps"):
            if preempted and self.on_preempt is not None:
                # pressure-trigger migration hook (CC105: fired with
                # the lock released; the victims are already back in
                # the waiting queue with their emitted tokens intact)
                try:
                    self.on_preempt(preempted)
                except Exception:
                    pass
            if self.on_batch_boundary is not None:
                try:
                    self.on_batch_boundary()
                except Exception:
                    pass
            if not step_ok:
                time.sleep(0.001)
        return True

    def _open_step_span(self, m, bucket, lanes, **attrs):
        """The iteration's ``serving.decode_step`` span, linked to the
        requests it serves.  A flight-recorder breadcrumb names them when
        the lane set differs from the last one written: a step with the
        lanes of the step before writes nothing to disk."""
        self._step_no += 1
        sspan = _tr.start_span(
            "serving.decode_step", model=m.name, bucket=bucket,
            lanes=len(lanes), step=self._step_no, **attrs)
        for s in lanes:
            sspan.link(s.pending.span.context
                       if s.pending.span is not None else None)
        opened, self._t_step_opened = self._t_step_opened, None
        if _tr.enabled():
            # the loop's whole period, lock, admission and plan with it:
            # from the span before's open to this one's (not on the first
            # span since the flag came on or the loop last idled)
            self._t_step_opened = time.perf_counter()
            if opened is not None:
                sspan.annotate(period_us=int(
                    (self._t_step_opened - opened) * 1e6))
            req_ids = [s.pending.req_id for s in lanes]
            if req_ids != self._noted_lanes:
                self._noted_lanes = req_ids
                _tr.note("decode_step", model=m.name, step=self._step_no,
                         req_ids=req_ids)
        return sspan

    def _dispatch_gap_us(self):
        """Host time since the device handed the last step's tokens back
        (``serving.fetch`` ended), read as this step's dispatch starts
        with nothing running on the device: it has had nothing queued
        since the step read last ended, which is at the latest then.  (A
        step dispatched while the one before still runs has no such gap:
        its span says ``ahead`` and ``gap_us`` 0.)"""
        if self._t_fetched is None:
            return 0
        return int((time.perf_counter() - self._t_fetched) * 1e6)

    def _close_step_span(self, sspan, **attrs):
        waits, self._admit_waits = self._admit_waits, []
        sspan.annotate(admitted=len(waits),
                       admit_wait_ms=[w for w, _ in waits],
                       admit_lock_wait_ms=[w for _, w in waits], **attrs)
        sspan.take_phases("serving.")
        sspan.end()

    def _discard_in_flight(self, s, reason):
        """``s`` leaves its lane (or is reset) for ``reason``: the token a
        step in flight still holds for it will not be applied.  The one
        rule for every late discovery: ``_apply_flight_locked`` takes a
        lane's token only while the sequence's dispatched count stands."""
        if s.n_disp > s.n_fed:
            s.n_disp = s.n_fed
            _tm.inc("serving_lane_steps_discarded_total",
                    model=s.pending.model, reason=reason)

    def _drain_locked(self):
        """Fetch and apply the step in flight, if there is one (call with
        self._cond held).  Whoever needs a sequence's exact position,
        its ``out`` or the cache's content as the host has confirmed it,
        from outside the loop, calls this before it looks."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._apply_flight_locked(flight, spanned=False)
        self.in_batch = False

    def _fail_lanes_locked(self, m, lanes, error):
        for s in lanes:
            self._discard_in_flight(s, "error")
            self._active.remove(s)
            self._free_blocks(s)
            self._finish(s, InferReply("error", error=error))
        _tm.inc("serving_batch_errors_total", model=m.name)

    def _decode_step_locked(self):
        """One loop iteration for a plain decode step (call with
        self._cond held): plan and dispatch the next step, *then* fetch
        and apply the step dispatched an iteration ago.  At most one step
        is in flight beyond the one being read.

        What goes up a step is one ``int32[bucket, C]`` array (idle
        lanes' rows with the live lanes filled in; the plan's ``tok``,
        ``src``, ``pos``, ``lens``, ``slots``, ``tables`` and ``rings`` are
        views of its columns), uploaded as soon as it is filled so that it
        travels while the span is opened; the step itself is found by the
        bucket (``CarriedStepFn``), and is handed nothing that is still
        on the host.

        NOTE: the dispatch and the apply run under the lock — sequences
        can only join/leave at iteration boundaries, which is exactly the
        continuous-batching contract.  submit()/abort() block for at
        most one iteration (milliseconds at serving batch sizes), and in
        exchange the active set and block tables need no second lock.
        The device runs the step *after* the lock is released too, so
        between iterations the host may free blocks and state slots that
        a step in flight still names (a finished, aborted, expired or
        preempted sequence's, or an end-of-sequence lane's wasted write).
        That is safe by device order: the pools chain from step to step
        (the carry is replaced at dispatch with the outputs not yet
        computed, and JAX queues behind them), so whoever gets a freed
        block or slot next writes it in a later executable on the same
        stream: a later step, or ``import_block``'s update of the same
        carry.  Nothing on the host reads a pool without going through
        that carry."""
        m = self._model_of(self._active[0])
        if self._flight is not None and self._flight.m is not m:
            self._drain_locked()    # the last step of another model
        with _tr.phase("serving.plan"):
            # drop client-aborted + deadline-expired actives first, freeing
            # their blocks before this step's allocations
            now = time.perf_counter()
            for s in list(self._active):
                if s.aborted:
                    self._discard_in_flight(s, "aborted")
                    self._active.remove(s)
                    self._free_blocks(s)
                    self._finish(s, InferReply("aborted",
                                               error="aborted by client"))
                elif now > s.pending.deadline:
                    self._discard_in_flight(s, "expired")
                    self._active.remove(s)
                    self._free_blocks(s)
                    _tm.inc("serving_timeout_total", model=s.pending.model)
                    self._finish(s, InferReply(
                        "timeout", error="deadline expired mid-decode"))
        # complete prefill-role sequences whose boundary was reached (by
        # the step read last, or at admission via a warm prefix match)
        self._sweep_handoff_locked()
        if not self._active:
            self._drain_locked()
            return True
        if m.spec_k > 0:
            return self._spec_step_locked(m)
        with _tr.phase("serving.plan"):
            # token-budget prefill scheduling: decode lanes always run;
            # prefilling lanes beyond the budget sit this iteration out
            participants, _caps = self._plan_lanes_locked(1)
            for s in participants:
                if s in self._active and not self._ensure_block(s):
                    pass  # defensively completed inside _ensure_block
            lanes = [s for s in participants if s in self._active]
        if not lanes:
            # every active sequence waits for a token in flight
            self._drain_locked()
            return True
        prev = self._flight
        with _tr.phase("serving.plan"):
            bucket = self._bucket_for(len(lanes))
            # every per-lane integer of the step in ONE host array, so one
            # upload: tok, src, ... are its columns, written through
            at = m.columns
            packed = np.tile(m.idle_lane, (bucket, 1))
            tok, src, pos, lens = (packed[:, at[name].start]
                                   for name in ("tok", "src", "pos", "lens"))
            tables = packed[:, at["tables"]]
            slots = packed[:, at["slot"].start] if "slot" in at else None
            for i, s in enumerate(lanes):
                p = s.n_disp
                if p < s.known:
                    tok[i] = s.feed_tok(p)
                else:
                    # the token the step in flight is computing: lane i
                    # takes it from that step's output, on the device
                    src[i] = prev.lane_of[id(s)]
                pos[i] = p
                tables[i] = s.table
                lens[i] = p + 1  # token valid AFTER this step's write
                if slots is not None:
                    slots[i] = s.state_slot
            windowed = "ring" in at
            released = 0
            if windowed:
                # the windows move on: what left them goes back to the
                # window layers' pools, the blocks this step writes come
                # from them
                rings = packed[:, at["ring"]]
                for i, s in enumerate(lanes):
                    released += m.cache.advance_ring(s.window_ring,
                                                     s.n_disp + 1)
                    rings[i] = s.window_ring.table
            # filled: the upload starts now and travels while the host
            # opens the span; what it counts is every host array this
            # dispatch hands up, the executable's own call included
            args = self._step_args(m, m.upload(packed),
                                   prev.nxt if prev is not None else None)
            uploads = 1 + sum(isinstance(a, np.ndarray) for a in args[2:])
            _tm.inc("serving_step_uploads_total", uploads, model=m.name)
            # what the step's kinds of layer read, and the rings' blocks:
            # counted only while the span is recorded
            read = {}
            if _tr.enabled():
                read = m.account.step_attrs(bucket, lens)
                if windowed:
                    read.update(m.account.window_attrs(
                        lens, released, m.cache.window_allocator.in_use,
                        m.cache.allocator.in_use))
            if slots is not None:
                # lanes at position 0 start their slot from zeros
                resets = int((pos[:len(lanes)] == 0).sum())
                if resets:
                    _tm.inc(m.state_name + "_resets_total", resets,
                            model=m.name)
            sspan = self._open_step_span(m, bucket, lanes, uploads=uploads,
                                         **read)
        # is the device still at work on the step before?  Then it never
        # runs dry between the two, and the host's time since the last
        # fetch kept nothing waiting
        ahead = prev is not None and not prev.ready()
        t0 = time.perf_counter()
        gap_us = 0 if ahead else self._dispatch_gap_us()
        try:
            with _tr.activate(sspan), _tr.phase("serving.dispatch"):
                # threadlint: waive CC102 continuous-batching contract: the step is dispatched under _cond so lane state is frozen while it is planned and queued (see _decode_step_locked docstring); submitters park on the cond, never spin
                carry, nxt, _logits, *extras = m.stepfn(bucket, *args)
                # at dispatch: the next step, and whoever writes a block,
                # queues behind outputs the device has yet to compute
                m.cache.replace_carry(carry)
                # a routed step's counts ride with the tokens, started
                # now, and only while the span is recorded
                if extras and _tr.enabled():
                    for counts in extras:
                        counts.copy_to_host_async()
                else:
                    extras = None
        except Exception as e:
            self._fail_lanes_locked(m, lanes, str(e))
            self._drain_locked()
            self._close_step_span(sspan, gap_us=gap_us, ahead=ahead,
                                  error=str(e)[:200])
            return False
        self._flight = _Flight(m, bucket, lanes, nxt, extras, t0)
        for s in lanes:
            s.n_disp += 1
        self.in_batch = True
        if ahead:
            _tm.inc("serving_steps_ahead_total", model=m.name)
        applied = self._apply_flight_locked(prev) if prev is not None \
            else {"generated": 0, "published": 0}
        self._close_step_span(sspan, gap_us=gap_us, ahead=ahead, **applied)
        return "error" not in applied

    def _apply_flight_locked(self, flight, spanned=True):
        """Fetch a dispatched step's tokens (the wait, if the device is
        still at it) and apply them: one token for every lane that is
        still what the step took it for.  -> the attributes its iteration's
        span reports (``spanned``: there is one to report them)."""
        m = flight.m
        try:
            with _tr.phase("serving.fetch"):
                nxt = np.asarray(flight.nxt)
                moe = m.account.moe_attrs(flight.bucket, flight.extras)
        except Exception as e:
            self._fail_lanes_locked(m, [s for _, s in flight.live()], str(e))
            return {"error": str(e)[:200]}
        t_tok = time.perf_counter()
        # the period: since the step before was read, or since this one's
        # dispatch where nothing was in flight before it
        ms = (t_tok - max(flight.t0, self._t_fetched or 0.0)) * 1e3
        self._t_fetched = t_tok
        m.step_ms = ms if m.step_ms <= 0 else 0.8 * m.step_ms + 0.2 * ms
        n_generated = 0
        with _tr.phase("serving.emit"):
            # a lane that is not live any more was counted where it left
            for i, s in flight.live():
                s.n_fed += 1
                # seal + publish any prompt block this write completed
                # (the boundary-crossing write completes the final full
                # block), then any completed history block (session
                # migration)
                self._publish_prefix_locked(m, s)
                self._publish_history_locked(m, s)
                if s.in_prefill:
                    continue
                token = int(nxt[i])
                s.out.append(token)
                s.token_times.append(t_tok)
                if s.t_first is None:
                    s.t_first = t_tok
                n_generated += 1
                done = (len(s.out) >= s.max_new or token == s.eos_id)
                if s.on_token is not None:
                    try:
                        s.on_token(s.pending.req_id, len(s.out) - 1, token,
                                   done, "ok")
                    except Exception:
                        pass
                if done:
                    # max_new was known at dispatch (nothing in flight);
                    # an eos_id hit was not: its lane ran one step more
                    self._discard_in_flight(s, "eos")
                    self._active.remove(s)
                    self._free_blocks(s)   # same-step free: next admission
                    self._finish(s, InferReply("ok"))
            if n_generated:
                _tm.inc("serving_tokens_generated_total", n_generated,
                        model=m.name)
            _tm.inc("serving_decode_steps_total", model=m.name)
            _tm.observe("decode_batch_occupancy",
                        len(flight.lanes) / float(flight.bucket),
                        model=m.name)
            stream = self._tokens_emitted(step=spanned)
        return dict(moe, generated=n_generated, ms=round(ms, 3), **stream)

    def _spec_step_locked(self, m):
        """One speculative iteration (lock held): the draft decoder
        proposes k tokens per generating lane through its own paged
        pool, ONE bucketed multi-token target step verifies all k+1
        positions, the longest draft prefix matching the target's greedy
        argmax chain is accepted, and over-reserved blocks roll back to
        both free lists in the same iteration.  Prefill lanes ride the
        same verify step as a chunked prefill (up to k+1 prompt tokens
        per iteration, auto-accepted, mirrored into the draft cache).
        Greedy accept keeps the emitted stream bitwise equal to the
        non-speculative engine; draft quality only moves throughput.
        This iteration is synchronous (dispatch, wait, emit): the next
        one cannot be planned before the accepted count is on the host,
        so nothing is ever in flight for a speculating model and
        ``n_disp`` follows ``n_fed``.

        Verify/ingest lane layout is junk-first: a lane with span < k+1
        valid tokens pads the LEADING columns with context_len-0 writes
        aimed at the first valid position, which the first real column
        then overwrites before anything attends — so short lanes never
        touch positions past their reservation and junk never survives
        into attended history."""
        k = m.spec_k
        width = k + 1
        with _tr.phase("serving.plan"):
            # token-budget prefill scheduling: caps[id(s)] trims a prefill
            # lane's chunk span when the budget runs low this iteration
            participants, caps = self._plan_lanes_locked(width)
            plans = {}
            for s in participants:
                if s not in self._active:
                    continue   # preempted by an earlier lane's allocation
                p = s.n_fed
                if s.in_prefill:
                    span = caps.get(id(s),
                                    min(width, self._prefill_limit(s) - p))
                    spec = False
                    # the prompt chunk mirrors into the draft TAIL-ONLY:
                    # with a cached prefix p starts past it, so draft
                    # positions below p stay zero — that can only lower
                    # acceptance, never correctness (verify guards every
                    # emitted token)
                    draft_upto = p + span
                else:
                    span = min(width, s.max_new - len(s.out))
                    spec = span > 1         # last token needs no proposals
                    # rollout writes up to p+k-1 (position-clamped to the
                    # sequence end); a full accept ingests d_k at p+k
                    draft_upto = min(p + k + 1, s.total) if spec else 0
                if not self._ensure_capacity(s, p + span, draft_upto):
                    continue   # defensively completed
                plans[id(s)] = (span, spec)
            lanes = [s for s in participants
                     if s in self._active and id(s) in plans]
            if not lanes:
                return True
            bucket = self._bucket_for(len(lanes))
            tok = np.zeros((bucket, width), np.int32)
            pos = np.zeros((bucket, width), np.int32)
            lens = np.zeros((bucket, width), np.int32)
            tables = np.full((bucket, m.maxb), -1, np.int32)
            rtok = np.zeros(bucket, np.int32)
            rpos = np.zeros(bucket, np.int32)
            rlens = np.zeros(bucket, np.int32)
            rmax = np.zeros(bucket, np.int32)
            rtables = np.full((bucket, m.maxb), -1, np.int32)
            n_spec = 0
            for i, s in enumerate(lanes):
                span, spec = plans[id(s)]
                p = s.n_fed
                pad = width - span
                tables[i] = s.table
                pos[i, :pad] = p
                feed = s.feed_slice(p, span) if s.in_prefill \
                    else [s.feed_tok(p)]
                for j in range(span):
                    pos[i, pad + j] = p + j
                    lens[i, pad + j] = p + j + 1
                for j, t in enumerate(feed):
                    tok[i, pad + j] = t
                if spec:
                    n_spec += 1
                    rtok[i] = s.feed_tok(p)
                    rpos[i] = p
                    rlens[i] = p + 1
                    rmax[i] = s.total - 1
                    rtables[i] = s.draft_table
            sspan = self._open_step_span(m, bucket, lanes,
                                         speculative=True, k=k)
        self.in_batch = True
        t0 = time.perf_counter()
        gap_us = self._dispatch_gap_us()
        props = None
        try:
            with _tr.activate(sspan):
                if n_spec:
                    with _tr.span("serving.draft", lanes=n_spec, k=k), \
                            _tr.phase("serving.dispatch"):
                        # threadlint: waive CC102 draft rollout runs under _cond by the same frozen-lane contract as stepfn in _decode_step_locked
                        dcarry, props = m.rolloutfn(
                            (bucket, k), m.draft_cache.carry(),
                            m.draft_params,
                            rtok, rpos, rtables, rlens, rmax)
                    with _tr.phase("serving.fetch"):
                        m.draft_cache.replace_carry(dcarry)
                        props = np.asarray(props)
                    with _tr.phase("serving.plan"):
                        for i, s in enumerate(lanes):
                            span, spec = plans[id(s)]
                            if spec:
                                for j in range(span - 1):
                                    tok[i, width - span + 1 + j] = \
                                        props[i, j]
                with _tr.span("serving.verify", lanes=len(lanes),
                              width=width), _tr.phase("serving.dispatch"):
                    # threadlint: waive CC102 target-model verify runs under _cond by the same frozen-lane contract as stepfn in _decode_step_locked
                    carry, nxt, _logits, *_extras = m.verifyfn(
                        (bucket, width), m.cache.carry(), m.params, tok,
                        pos, tables, lens)
                with _tr.phase("serving.fetch"):
                    m.cache.replace_carry(carry)
                    nxt = np.asarray(nxt)
        except Exception as e:
            for s in lanes:
                self._active.remove(s)
                self._free_blocks(s)
                self._finish(s, InferReply("error", error=str(e)))
            _tm.inc("serving_batch_errors_total", model=m.name)
            self._close_step_span(sspan, gap_us=gap_us,
                                  error=str(e)[:200])
            self.in_batch = False
            return False
        self.in_batch = False
        self._t_fetched = t_tok = time.perf_counter()
        ms = (t_tok - t0) * 1e3
        m.step_ms = ms if m.step_ms <= 0 else 0.8 * m.step_ms + 0.2 * ms
        n_generated = 0
        k_proposed = 0
        k_accepted = 0
        ingest = []    # (seq, start_pos, tokens) draft catch-up writes
        with _tr.phase("serving.emit"):
            for i, s in enumerate(lanes):
                span, spec = plans[id(s)]
                p = s.n_fed
                pad = width - span
                accepted = 0
                if s.in_prefill:
                    s.n_fed += span
                    s.n_disp = s.n_fed
                    self._publish_prefix_locked(m, s)
                    self._publish_history_locked(m, s)
                    ingest.append((s, p, s.feed_slice(p, span)))
                    if s.in_prefill:
                        continue
                    # chunk crossed the prompt boundary: its last column's
                    # argmax is the first generated token
                    emitted = [int(nxt[i, pad + span - 1])]
                else:
                    # accept-longest-prefix: column j's argmax continues
                    # the chain only while proposal j matched the previous
                    # argmax
                    emitted = [int(nxt[i, pad])]
                    while accepted < span - 1 and \
                            int(props[i, accepted]) == emitted[-1]:
                        emitted.append(int(nxt[i, pad + accepted + 1]))
                        accepted += 1
                    if spec:
                        k_proposed += span - 1
                        k_accepted += accepted
                        _tm.observe("spec_acceptance",
                                    accepted / float(span - 1),
                                    model=m.name)
                    s.n_fed += len(emitted)
                    s.n_disp = s.n_fed
                done = False
                for t in emitted:
                    s.out.append(t)
                    s.token_times.append(t_tok)
                    if s.t_first is None:
                        s.t_first = t_tok
                    n_generated += 1
                    done = (len(s.out) >= s.max_new or t == s.eos_id)
                    if s.on_token is not None:
                        try:
                            s.on_token(s.pending.req_id, len(s.out) - 1, t,
                                       done, "ok")
                        except Exception:
                            pass
                    if done:
                        break
                # history publication must follow the appends: a
                # multi-token accept advances n_fed past tokens that only
                # exist in ``emitted`` until this point, and the chain
                # digest replays them from prompt ++ out
                self._publish_history_locked(m, s)
                if done:
                    self._active.remove(s)
                    self._free_blocks(s)   # same-step free, both pools
                    self._finish(s, InferReply("ok"))
                    continue
                if accepted == k:
                    # full accept: the rollout never wrote position p+k;
                    # its token is d_k (== the target's g_k), caught up
                    # below
                    ingest.append((s, p + k, [int(props[i, k - 1])]))
            # free rollback: every block past the accepted frontier
            # returns to its pool in the SAME iteration (context_lens
            # truncation next step masks the stale writes)
            rolled = 0
            for s in lanes:
                if s not in self._active:
                    continue
                rolled += m.cache.trim_table(s.table, s.blocks, s.n_fed)
                rolled += m.draft_cache.trim_table(
                    s.draft_table, s.draft_blocks, s.n_fed)
            if rolled:
                _tm.inc("spec_blocks_rolled_back_total", rolled,
                        model=m.name)
            # before the draft's catch-up dispatch: the tokens are final
            stream = self._tokens_emitted(step=True)
        ingest = [(s, q, t) for (s, q, t) in ingest if s in self._active]
        if ingest:
            with _tr.phase("serving.plan"):
                itok = np.zeros((bucket, width), np.int32)
                ipos = np.zeros((bucket, width), np.int32)
                ilens = np.zeros((bucket, width), np.int32)
                itables = np.full((bucket, m.maxb), -1, np.int32)
                for r, (s, q, toks) in enumerate(ingest):
                    ipad = width - len(toks)
                    itables[r] = s.draft_table
                    ipos[r, :ipad] = q
                    for j, t in enumerate(toks):
                        ipos[r, ipad + j] = q + j
                        ilens[r, ipad + j] = q + j + 1
                        itok[r, ipad + j] = t
            try:
                with _tr.activate(sspan), \
                        _tr.span("serving.draft_ingest",
                                 lanes=len(ingest)), \
                        _tr.phase("serving.dispatch"):
                    # threadlint: waive CC102 draft-cache ingest runs under _cond by the same frozen-lane contract as stepfn in _decode_step_locked
                    dcarry, _nx, _lg, *_extras = m.ingestfn(
                        (bucket, width), m.draft_cache.carry(),
                        m.draft_params,
                        itok, ipos, itables, ilens)
                m.draft_cache.replace_carry(dcarry)
            except Exception:
                # a stale draft cache only costs acceptance, never
                # correctness — the verify step guards every token
                _tm.inc("spec_ingest_errors_total", model=m.name)
        with _tr.phase("serving.emit"):
            if n_spec:
                _tm.inc("spec_tokens_proposed_total", k_proposed,
                        model=m.name)
                _tm.inc("spec_tokens_accepted_total", k_accepted,
                        model=m.name)
            if n_generated:
                _tm.inc("serving_tokens_generated_total", n_generated,
                        model=m.name)
            _tm.inc("serving_decode_steps_total", model=m.name)
            _tm.observe("decode_batch_occupancy",
                        len(lanes) / float(bucket), model=m.name)
        self._close_step_span(sspan, generated=n_generated, ms=round(ms, 3),
                              gap_us=gap_us, k_proposed=k_proposed,
                              k_accepted=k_accepted, **stream)
        return True
