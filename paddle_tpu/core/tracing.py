"""Distributed tracing: cross-process request/step spans + flight recorder.

PR 3's telemetry registry (core/telemetry.py) answers *how often / how
slow in aggregate*; this layer answers *where one specific request or
step spent its time* across the client -> server -> engine -> executor
chain and across ranks.  Design:

- **spans**: trace_id (32 hex) / span_id (16 hex) / parent_id, wall-clock
  start (``time.time``) + monotonic duration (``perf_counter``), free-form
  ``attrs``, and ``links`` to other spans (a serving batch span links the
  N request spans it serves).  A thread-local span stack parents nested
  spans automatically; ``activate()`` pushes an existing span so work on
  another thread (the serving dispatcher running the executor) nests
  under it.
- **propagation**: W3C-style ``traceparent`` strings
  (``00-<trace>-<span>-01``) ride the serving codec meta and are stamped
  onto native-RPC SEND frame names (native/rpc.py), so one trace_id spans
  client, replicas, trainers, and pservers.  ``remote_parent()`` opens a
  child span under a context received off the wire.
- **sink**: one JSONL stream per process, ``trace-<pid>.jsonl`` under
  ``FLAGS_telemetry_dir``, size-bounded by ``FLAGS_telemetry_max_bytes``
  (same rotate-and-keep-one guard as telemetry's steps.jsonl).
  Recording is an append to an in-memory buffer; records are serialised
  and written by ``flush()``, at exit, and whenever ``_FLUSH_AT`` records
  are waiting — never one write per record.  ``records(name)`` reads the
  newest ``_RECENT_CAP`` records of this process, flushed or not
  (``tracing_dropped_total`` counts what fell out of that window).
  tools/trace_view.py merges the per-process files into a single
  Chrome/Perfetto trace.json with cross-process flow arrows.
- **host phases**: ``phase(name)`` brackets a stretch of a hot loop
  (``serving.dispatch``, ``executor.shard_feeds``...).  It always enters a
  ``jax.profiler.TraceAnnotation`` — inert without a profiler session, a
  host event on the profiler's clock beside the device timeline with one
  (``profiler.start_profiler(device_trace_dir=...)``) — and, when
  ``FLAGS_tracing`` is on, adds its duration to a per-thread tally that
  the loop's step span takes as its ``phases`` attribute
  (``Span.take_phases``).  A phase emits no record of its own.
- **zero-cost off**: ``FLAGS_tracing`` is off by default; every public
  call early-returns after a single flag read, handing back one shared
  inert ``_NULL_SPAN``.  No file, no thread state, no signal handlers.
- **flight recorder**: a bounded ring of the most recent span/instant
  records plus write-through ``note()`` breadcrumbs, dumped to
  ``<telemetry_dir>/flightrec-<pid>.json`` on fault-injection fire,
  unhandled exception, SIGTERM, and atexit — a killed fleet replica
  leaves a postmortem naming its in-flight batch.  Because SIGKILL is
  uncatchable, ``note()`` checkpoints the ring to disk immediately, so
  even a -9'd process leaves its last breadcrumbs behind; callers on a
  hot loop therefore note only what changed (the decode engine: the
  lane set), and a dump serialises only the records new since the last.
"""

import atexit
import collections
import json
import os
import random
import sys
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from . import telemetry as _tm

__all__ = [
    "enabled", "Span", "start_span", "span", "activate", "remote_parent",
    "record_span", "instant", "phase", "device_span", "imported",
    "current_span", "current_context",
    "traceparent", "parse_traceparent", "set_process_name", "note",
    "flight_dump", "flush", "records", "reset",
]

_FLIGHT_CAP = 512          # ring slots kept for the postmortem dump
_RECENT_CAP = 65536        # newest records kept in memory for records()
_FLUSH_AT = 1024           # unwritten records that trigger a flush
_WIRE_SEP = "\x1f"         # RPC frame-name separator for the traceparent

_lock = threading.RLock()  # the buffers below; taken once per record
_io_lock = threading.Lock()  # the sink; taken per flush, never per record
_tls = threading.local()   # .stack = [Span, ...], .phases = {name: s}
_sink = [None, None]       # (path, _RotatingFile) — telemetry's sink idiom
_proc_name = [None]        # explicit process track name (serve.py sets it)
_proc_header_written = [False]
_flight = []               # bounded ring of record dicts
_flight_json = []          # their serialised forms (None until dumped)
_recent = collections.deque(maxlen=_RECENT_CAP)
_unwritten = []            # recorded, not yet in the sink
_handlers_installed = [False]
_import = []               # (wall start s, dur ms) of `import paddle_tpu`,
                           # until the first record takes it with it
_ids = random.Random(os.urandom(16))   # trace/span ids


_flags_mod = [None]        # cached flags module (import once, read often)


def _flags():
    m = _flags_mod[0]
    if m is None:
        from .. import flags as m

        _flags_mod[0] = m
    return m


def enabled():
    """One flag read — the telemetry.enabled() guard pattern."""
    return bool(_flags().flag("tracing"))


def _telemetry_dir():
    return _flags().flag("telemetry_dir") or ""


def _new_id(nbytes):
    # os.urandom per id is measurably slow; draw from a per-process
    # generator seeded from it (re-seeded in a forked child, below)
    return "%0*x" % (2 * nbytes, _ids.getrandbits(8 * nbytes))


def _after_fork_in_child():
    # the parent's ids must not repeat here, and its buffered records are
    # the parent's to write
    _ids.seed(os.urandom(16))
    _drop_buffers()


os.register_at_fork(after_in_child=_after_fork_in_child)


# -- W3C-style context --------------------------------------------------------

def parse_traceparent(tp):
    """``00-<32 hex trace>-<16 hex span>-<flags>`` -> (trace_id, span_id)
    or None on anything malformed (a bad header never breaks a request)."""
    if not isinstance(tp, str):
        return None
    parts = tp.split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None
    return parts[1], parts[2]


def _format_traceparent(trace_id, span_id):
    return "00-%s-%s-01" % (trace_id, span_id)


def current_span():
    """Innermost active span on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def current_context():
    """(trace_id, span_id) of the innermost active span, or None."""
    s = current_span()
    return (s.trace_id, s.span_id) if s is not None else None


def traceparent():
    """Serialized context of the current span for the wire, or None."""
    s = current_span()
    return _format_traceparent(s.trace_id, s.span_id) if s else None


# -- spans --------------------------------------------------------------------

class Span:
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t_wall",
                 "_t0", "dur_ms", "attrs", "links", "thread", "_ended")

    def __init__(self, name, trace_id=None, parent_id=None, **attrs):
        self.name = name
        self.trace_id = trace_id or _new_id(16)
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.t_wall = time.time()
        self._t0 = time.perf_counter()
        self.dur_ms = None
        self.attrs = dict(attrs) if attrs else {}
        self.links = []
        self.thread = threading.current_thread().name
        self._ended = False

    def annotate(self, **attrs):
        self.attrs.update(attrs)
        return self

    def link(self, other):
        """Associate another span (same- or cross-trace) without
        parenting it — e.g. a batch span linking the requests it serves."""
        if isinstance(other, Span):
            self.links.append([other.trace_id, other.span_id])
        elif other:  # (trace_id, span_id) tuple
            self.links.append([other[0], other[1]])
        return self

    @property
    def context(self):
        return (self.trace_id, self.span_id)

    @property
    def traceparent(self):
        return _format_traceparent(self.trace_id, self.span_id)

    def take_phases(self, prefix=""):
        """Move this thread's ``phase()`` tally (names starting with
        ``prefix``) into the ``phases`` attribute, name -> microseconds,
        and return it.  The loop's step span calls this once an
        iteration, so phases that ran before the span opened, or in an
        iteration that opened none, are carried to the next one."""
        tally = getattr(_tls, "phases", None) or {}
        taken = {n: int(tally.pop(n) * 1e6)
                 for n in list(tally) if n.startswith(prefix)}
        self.attrs["phases"] = taken
        return taken

    def device_memory(self):
        """``hbm_in_use_bytes`` and ``hbm_peak_bytes`` of the fullest local
        device as the runtime's allocator counts them now (set-up spans,
        at their end); nothing where the backend keeps no such count (the
        CPU's)."""
        stats = [s for s in (d.memory_stats() for d in jax.local_devices())
                 if s]
        if stats:
            self.attrs.update(
                hbm_in_use_bytes=max(int(s.get("bytes_in_use", 0))
                                     for s in stats),
                hbm_peak_bytes=max(int(s.get("peak_bytes_in_use", 0))
                                   for s in stats))
        return self

    def end(self):
        if self._ended:
            return self
        self._ended = True
        self.dur_ms = (time.perf_counter() - self._t0) * 1e3
        _emit(self._record())
        return self

    def _record(self):
        rec = {"t": "span", "name": self.name, "tid": self.trace_id,
               "sid": self.span_id, "parent": self.parent_id,
               "ts": int(self.t_wall * 1e6),
               "dur": int((self.dur_ms or 0.0) * 1e3),
               "thr": self.thread}
        if self.attrs:
            rec["attrs"] = self.attrs
        if self.links:
            rec["links"] = self.links
        return rec


class _NullSpan:
    """Inert span handed out when FLAGS_tracing is off: every method is a
    cheap no-op so call sites never branch on the flag themselves."""

    __slots__ = ()
    trace_id = span_id = parent_id = None
    context = None
    traceparent = None
    dur_ms = None

    def annotate(self, **attrs):
        return self

    def link(self, other):
        return self

    def device_memory(self):
        return self

    def take_phases(self, prefix=""):
        # a tally left from before the flag went off is stale by the
        # time it comes back on
        tally = getattr(_tls, "phases", None)
        if tally:
            tally.clear()
        return {}

    def end(self):
        return self


_NULL_SPAN = _NullSpan()


def _push(s):
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(s)


def _pop(s):
    stack = getattr(_tls, "stack", None)
    if stack and stack[-1] is s:
        stack.pop()
    elif stack and s in stack:   # out-of-order end: drop it anyway
        stack.remove(s)


def start_span(name, parent=None, **attrs):
    """Open a span (NOT pushed on the thread stack — pair with .end(), or
    use the ``span()`` context manager for stack semantics).  ``parent``
    may be a Span, a (trace_id, span_id) tuple, or None (defaults to the
    current thread's innermost span; a root span otherwise)."""
    if not enabled():
        return _NULL_SPAN
    if parent is None:
        parent = current_span()
    if isinstance(parent, Span):
        return Span(name, trace_id=parent.trace_id,
                    parent_id=parent.span_id, **attrs)
    if isinstance(parent, _NullSpan):
        parent = None
    if parent:  # (trace_id, span_id)
        return Span(name, trace_id=parent[0], parent_id=parent[1], **attrs)
    return Span(name, **attrs)


class _SpanCtx:
    """Context manager that pushes the span on this thread's stack (so
    nested spans parent under it) and ends it on exit."""

    __slots__ = ("span",)

    def __init__(self, s):
        self.span = s

    def __enter__(self):
        if self.span is not _NULL_SPAN:
            _push(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if self.span is not _NULL_SPAN:
            if exc is not None:
                self.span.annotate(error=str(exc)[:200])
            _pop(self.span)
            self.span.end()
        return False


def span(name, parent=None, **attrs):
    """``with tracing.span("serving.execute", bucket=4) as s: ...`` —
    opens, stacks, and ends a span around the block."""
    return _SpanCtx(start_span(name, parent=parent, **attrs))


class _DeviceSpanCtx(_SpanCtx):
    """``_SpanCtx`` inside a ``TraceAnnotation`` of the span's name."""

    __slots__ = ("_annotation",)

    def __init__(self, s, name):
        self.span = s
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        return _SpanCtx.__enter__(self)

    def __exit__(self, exc_type, exc, tb):
        _SpanCtx.__exit__(self, exc_type, exc, tb)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def device_span(name, parent=None, **attrs):
    """``span()`` over a stretch of set-up that waits for the device (an
    executable's first run, the pools' allocation): as a ``phase`` it is
    always a ``jax.profiler.TraceAnnotation`` too, so a profile taken over
    a start names the stretch on the profiler's clock; its duration goes
    into its own record and not into the thread's phase tally, so a step
    span's ``phases`` still sum to no more than the step."""
    return _DeviceSpanCtx(start_span(name, parent=parent, **attrs), name)


class _ActivateCtx:
    """Push an EXISTING span on this thread's stack without ending it on
    exit — used to parent executor spans under the serving batch span
    that lives on the dispatcher thread."""

    __slots__ = ("span",)

    def __init__(self, s):
        self.span = s

    def __enter__(self):
        if isinstance(self.span, Span):
            _push(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if isinstance(self.span, Span):
            _pop(self.span)
        return False


def activate(s):
    return _ActivateCtx(s)


def remote_parent(tp):
    """Context manager: open a span factory under a wire context.  Usage:
    ``with tracing.remote_parent(meta.get("traceparent")): ...`` — spans
    started inside parent under the remote caller's span.  A missing or
    malformed header degrades to local-root semantics."""
    ctx = parse_traceparent(tp) if tp else None
    if not enabled() or ctx is None:
        return _ActivateCtx(_NULL_SPAN)
    anchor = Span.__new__(Span)  # stack anchor only, never emitted
    anchor.name = "<remote>"
    anchor.trace_id, anchor.span_id = ctx
    anchor.parent_id = None
    anchor.t_wall = time.time()
    anchor._t0 = time.perf_counter()
    anchor.dur_ms = None
    anchor.attrs = {}
    anchor.links = []
    anchor.thread = threading.current_thread().name
    anchor._ended = True  # end() can never re-emit it
    return _ActivateCtx(anchor)


def record_span(name, wall_start_s, dur_ms, parent=None, trace_id=None,
                links=None, **attrs):
    """Emit a span RETROACTIVELY from measured timestamps (the elastic
    re-quorum phases are measured as perf_counter deltas first, then laid
    out as a span tree).  ``links`` associates other spans without
    parenting them — each entry a Span or (trace_id, span_id) tuple, e.g.
    the elastic restore phase linking the checkpoint.restore span that
    served it.  Returns the span (already ended)."""
    if not enabled():
        return _NULL_SPAN
    if isinstance(parent, Span):
        trace_id, parent_id = parent.trace_id, parent.span_id
    elif isinstance(parent, (tuple, list)) and len(parent) == 2:
        trace_id, parent_id = parent
    else:
        parent_id = None
    s = Span.__new__(Span)
    s.name = name
    s.trace_id = trace_id or _new_id(16)
    s.span_id = _new_id(8)
    s.parent_id = parent_id
    s.t_wall = float(wall_start_s)
    s._t0 = None
    s.dur_ms = float(dur_ms)
    s.attrs = dict(attrs) if attrs else {}
    s.links = []
    for other in (links or ()):
        if other is not None and not isinstance(other, _NullSpan):
            s.link(other)
    s.thread = threading.current_thread().name
    s._ended = True
    _emit(s._record())
    return s


def imported(wall_start_s, dur_ms):
    """``import paddle_tpu`` took ``dur_ms`` from ``wall_start_s``: the
    package's last line says so, before any caller can have set the flag.
    The pair waits here and becomes the ``setup.import`` span just ahead
    of the first record this process makes, whenever the flag comes on."""
    _import[:] = [(float(wall_start_s), float(dur_ms))]


def _record_import():
    with _lock:
        pair = _import.pop() if _import else None
    if pair is not None:
        record_span("setup.import", *pair)


def instant(name, **attrs):
    """Point-in-time marker on the current trace (folds the profiler's
    mark_instant semantics into the tracing stream)."""
    if not enabled():
        return
    rec = {"t": "inst", "name": name, "ts": int(time.time() * 1e6),
           "thr": threading.current_thread().name}
    ctx = current_context()
    if ctx is not None:
        rec["tid"], rec["sid"] = ctx
    if attrs:
        rec["attrs"] = attrs
    _emit(rec)


class _Phase:
    """Context manager behind ``phase()``; ``stop()`` ends it early (the
    decode loop's lock wait ends inside the ``with`` that takes the
    lock)."""

    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name):
        self.name = name
        self._annotation = TraceAnnotation(name)
        self._t0 = None

    def __enter__(self):
        self._annotation.__enter__()
        if enabled():
            self._t0 = time.perf_counter()
        return self

    def stop(self):
        annotation, self._annotation = self._annotation, None
        if annotation is None:
            return
        annotation.__exit__(None, None, None)
        if self._t0 is not None:
            tally = getattr(_tls, "phases", None)
            if tally is None:
                tally = _tls.phases = {}
            tally[self.name] = tally.get(self.name, 0.0) \
                + time.perf_counter() - self._t0

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        return False


def phase(name):
    """``with tracing.phase("executor.dispatch"): ...`` — a named stretch
    of a hot loop's iteration.  Always a ``jax.profiler.TraceAnnotation``
    (inert without a profiler session; with one, a host event on the
    profiler's clock, which is how a profile taken with ``FLAGS_tracing``
    off names its idle gaps).  With ``FLAGS_tracing`` on its duration is
    also added to this thread's tally for ``Span.take_phases``.  No record
    is emitted."""
    return _Phase(name)


def set_process_name(name):
    """Name this process's track in the merged trace (e.g.
    ``serving-replica-0``); defaults to ``pid-<pid>``."""
    _proc_name[0] = str(name)
    _proc_header_written[0] = False  # re-announce under the new name


# -- sink ---------------------------------------------------------------------

def _proc_header():
    return {"t": "proc", "pid": os.getpid(),
            "name": _proc_name[0] or ("pid-%d" % os.getpid()),
            "ts": int(time.time() * 1e6)}


def _sink_fh(d):
    path = os.path.join(d, "trace-%d.jsonl" % os.getpid())
    if _sink[0] != path:
        if _sink[1] is not None:
            _sink[1].close()
        try:
            os.makedirs(d, exist_ok=True)
            _sink[0] = path
            _sink[1] = _tm._RotatingFile(path)
            _proc_header_written[0] = False
        except OSError:
            _sink[0] = _sink[1] = None
    return _sink[1]


def _drop_buffers():
    with _lock:
        _flight[:] = []
        _flight_json[:] = []
        _recent.clear()
        _unwritten[:] = []


def _emit(rec):
    """Record: an append to the in-memory buffers.  Serialising and
    writing are ``flush()``'s."""
    if not _handlers_installed[0]:
        _install_handlers()
    if _import:
        _record_import()
    with _lock:
        _flight.append(rec)
        _flight_json.append(None)
        if len(_flight) > _FLIGHT_CAP:
            del _flight[: len(_flight) - _FLIGHT_CAP]
            del _flight_json[: len(_flight_json) - _FLIGHT_CAP]
        dropped = len(_recent) == _RECENT_CAP
        _recent.append(rec)
        if _telemetry_dir():
            _unwritten.append(rec)
        full = len(_unwritten) >= _FLUSH_AT
    if _tm.enabled():
        _tm.inc("tracing_records_total", kind=rec["t"])
        if dropped:
            _tm.inc("tracing_dropped_total")
    if full:
        flush()


def flush(wait=True):
    """Write every record made so far to ``trace-<pid>.jsonl`` under
    ``FLAGS_telemetry_dir`` (whatever ``FLAGS_tracing`` says now).
    ``wait=False`` gives up instead of waiting for a flush in progress
    (the SIGTERM handler may have interrupted one on its own thread)."""
    if not _io_lock.acquire(wait):
        return
    try:
        with _lock:
            batch = _unwritten[:]
            _unwritten[:] = []
        d = _telemetry_dir()
        fh = _sink_fh(d) if d and batch else None
        if fh is None:
            return
        if not _proc_header_written[0]:
            _proc_header_written[0] = True
            fh.write(json.dumps(_proc_header()) + "\n")
        for rec in batch:
            fh.write(json.dumps(rec, default=str) + "\n")
        fh.flush()
    finally:
        _io_lock.release()


def records(name):
    """This process's recorded spans of one name, oldest first, in the
    sink's record format, flushed or not: the newest ``_RECENT_CAP``
    records are kept."""
    with _lock:
        return [r for r in _recent
                if r["t"] == "span" and r["name"] == name]


def reset():
    """Tests: drop the sink and every buffer; per-thread stacks are left
    to unwind naturally (they are context-managed)."""
    with _io_lock:
        if _sink[1] is not None:
            _sink[1].close()
        _sink[0] = _sink[1] = None
        _proc_header_written[0] = False
    _drop_buffers()


# -- flight recorder ----------------------------------------------------------

def note(kind, **fields):
    """Write-through breadcrumb: lands in the flight ring AND immediately
    checkpoints the ring to flightrec-<pid>.json.  The serving engine
    notes each batch's req_ids here right before execute — SIGKILL is
    uncatchable, so the postmortem must already be on disk when it hits."""
    if not enabled():
        return
    rec = {"t": "note", "kind": kind, "ts": int(time.time() * 1e6),
           "thr": threading.current_thread().name}
    ctx = current_context()
    if ctx is not None:
        rec["tid"], rec["sid"] = ctx
    if fields:
        rec.update(fields)
    _emit(rec)
    flight_dump(reason="note:" + kind)


def flight_dump(reason="manual"):
    """Atomically write the flight ring to <telemetry_dir>/
    flightrec-<pid>.json.  Returns the path, or None (off / no dir)."""
    if not enabled():
        return None
    d = _telemetry_dir()
    if not d:
        return None
    path = os.path.join(d, "flightrec-%d.json" % os.getpid())
    with _lock:
        # only what is new since the last dump is serialised
        for i, line in enumerate(_flight_json):
            if line is None:
                _flight_json[i] = json.dumps(_flight[i], default=str)
        body = ", ".join(_flight_json)
    head = json.dumps({"proc": _proc_header(), "reason": reason,
                       "dumped_at": int(time.time() * 1e6)})
    tmp = path + ".tmp"
    try:
        os.makedirs(d, exist_ok=True)
        with open(tmp, "w") as f:
            f.write('%s, "records": [%s]}' % (head[:-1], body))
        os.replace(tmp, path)
    except OSError:
        return None
    if _tm.enabled():
        _tm.inc("tracing_flightrec_dumps_total",
                reason=reason.split(":", 1)[0])
    return path


def _install_handlers():
    """Lazy, once: atexit + excepthook always; SIGTERM only from the main
    thread (signal.signal raises elsewhere) and chaining any prior
    handler so serve.py's graceful-shutdown handler still runs."""
    if _handlers_installed[0]:
        return
    with _lock:
        if _handlers_installed[0]:
            return
        _handlers_installed[0] = True
    atexit.register(lambda: (flush(), flight_dump(reason="atexit")))

    prev_hook = sys.excepthook

    def hook(exc_type, exc, tb):
        try:
            flush()
            flight_dump(reason="exception:%s" % exc_type.__name__)
        except Exception:
            pass
        prev_hook(exc_type, exc, tb)

    sys.excepthook = hook
    if threading.current_thread() is threading.main_thread():
        import signal

        try:
            prev = signal.getsignal(signal.SIGTERM)

            def on_term(signum, frame):
                try:
                    flush(wait=False)
                    flight_dump(reason="sigterm")
                except Exception:
                    pass
                if callable(prev):
                    prev(signum, frame)
                elif prev == signal.SIG_DFL:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, on_term)
        except (ValueError, OSError):
            pass


# -- RPC frame-name stamping (native/rpc.py) ----------------------------------

def stamp_wire_name(name):
    """Append the current trace context to an RPC SEND frame name
    (``<name>\\x1f<traceparent>``) — only when tracing is on AND a span is
    active, so heartbeats/control traffic outside any trace stay
    byte-identical on the wire.  The 1024-byte name buffer fits any
    protocol key plus the 55-char header."""
    if not enabled():
        return name
    tp = traceparent()
    if tp is None or len(name) + len(tp) + 1 > 1000:
        return name
    return name + _WIRE_SEP + tp


def strip_wire_name(name):
    """Inverse of stamp_wire_name on the poll side: returns
    (bare_name, traceparent_or_None)."""
    if _WIRE_SEP not in name:
        return name, None
    bare, _, tp = name.partition(_WIRE_SEP)
    return bare, (tp if parse_traceparent(tp) else None)


def wire_received(name, tp):
    """Record receipt of a stamped frame: an instant on the SENDER's
    context (tid/sid from the wire header, not this thread's stack), so
    the merged trace shows where each RPC landed."""
    if not enabled() or tp is None:
        return
    ctx = parse_traceparent(tp)
    if ctx is None:
        return
    rec = {"t": "inst", "name": "rpc.recv", "ts": int(time.time() * 1e6),
           "thr": threading.current_thread().name,
           "tid": ctx[0], "sid": ctx[1], "attrs": {"var": name}}
    _emit(rec)
