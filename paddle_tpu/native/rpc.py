"""Python wrappers over the native tensor-RPC transport (csrc/tensor_rpc.cc).

Analog of the reference's RPCClient/RPCServer interfaces
(paddle/fluid/operators/distributed/rpc_client.h, rpc_server.h) with the
VariableResponse-style tensor framing done in C++.
"""

import ctypes

import numpy as np

from . import load
from ..core import telemetry as _tm
from ..core import tracing as _tr
from ..utils.fault_injection import FaultInjected, maybe_fail

__all__ = ["RpcServer", "RpcClient", "backoff_delay", "probe"]


def probe(endpoint, key="__alive__", timeout=3.0):
    """One bounded GET of `key` against a server, None on any failure.

    The shared liveness-probe idiom of the elastic control plane and the
    serving fleet: connect fast (1 s), GET with a hard deadline, never
    retry — a dead, hung, or not-yet-listening server all read as None,
    and the probing caller decides what that means."""
    try:
        c = RpcClient(endpoint, connect_timeout=1.0, rpc_deadline=timeout,
                      retry_times=0)
    except ConnectionError:
        return None
    try:
        return c.get_var(key)
    except Exception:
        return None
    finally:
        try:
            c.close()
        except Exception:
            pass


def backoff_delay(attempt, base=0.05, cap=2.0, rng=None):
    """Exponential backoff with equal jitter for retry `attempt` (0-based):
    uniformly in [d/2, d] where d = min(cap, base * 2**attempt) — the
    reference client re-queues failed RPCs with a growing delay; jitter
    keeps N trainers retrying a recovered pserver from re-arriving in
    lockstep."""
    import random

    d = min(float(cap), float(base) * (2.0 ** attempt))
    r = (rng or random).random()
    return d * (0.5 + 0.5 * r)

# numpy dtype <-> wire enum
_DTYPES = ["float32", "float64", "int32", "int64", "uint8", "int8",
           "float16", "bool"]
_DT_TO_CODE = {np.dtype(d): i for i, d in enumerate(_DTYPES)}

EV_SEND = 1
EV_BARRIER = 3
EV_COMPLETE = 4

GET_RING = 4096     # timed GET replies the server keeps undrained (kGetRing)
_DRAIN_AT_ONCE = 512    # of them a call of rpcs_drain_gets copies out


class RpcServer:
    def __init__(self, port=0):
        self._lib = load()
        self._h = self._lib.rpcs_create(int(port))
        if not self._h:
            raise OSError("cannot bind RPC server on port %d" % port)
        self.port = self._lib.rpcs_port(self._h)

    def poll(self):
        """Block for the next inbound event.
        Returns (type, name, array_or_None); type 0 => shutdown."""
        c = ctypes
        name = c.create_string_buffer(1024)
        dtype = c.c_ubyte()
        dims = (c.c_longlong * 16)()
        ndim = c.c_int()
        data = c.c_void_p()
        dlen = c.c_longlong()
        if self._h is None:
            return 0, None, None
        t = self._lib.rpcs_poll(self._h, name, 1024, c.byref(dtype), dims, 16,
                                c.byref(ndim), c.byref(data), c.byref(dlen))
        if t == 0:
            return 0, None, None
        arr = None
        if t == EV_SEND:
            shape = tuple(dims[i] for i in range(ndim.value))
            np_dt = np.dtype(_DTYPES[dtype.value])
            buf = ctypes.string_at(data.value, dlen.value)
            arr = np.frombuffer(buf, dtype=np_dt).reshape(shape).copy()
        # SEND frames may carry a trace context appended to the name
        # (tracing.stamp_wire_name); hand callers the bare name always
        bare, tp = _tr.strip_wire_name(name.value.decode())
        if tp is not None:
            _tr.wire_received(bare, tp)
        return t, bare, arr

    def set_vars(self, items, delete=()):
        """Store every ``(name, array)`` of ``items`` and erase the names
        in ``delete`` as ONE transaction of the store: a reader sees all
        of it or none, and only GETs parked on a stored name are woken."""
        # use-after-shutdown must raise, not hand the native layer a NULL
        # handle (a late publisher thread would segfault the process)
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        c = ctypes
        items = list(items)
        names = [name.encode() for name, _ in items]
        arrs = [np.ascontiguousarray(arr) for _, arr in items]
        gone = [name.encode() for name in delete]
        n = len(arrs)
        dims = [d for a in arrs for d in a.shape]
        self._lib.rpcs_set_vars(
            self._h, n, (c.c_char_p * n)(*names),
            (c.c_ubyte * n)(*[_DT_TO_CODE[a.dtype] for a in arrs]),
            (c.c_int * n)(*[a.ndim for a in arrs]),
            (c.c_longlong * len(dims))(*dims),
            (c.c_void_p * n)(*[a.ctypes.data for a in arrs]),
            (c.c_longlong * n)(*[a.nbytes for a in arrs]),
            len(gone), (c.c_char_p * len(gone))(*gone))

    def set_var(self, name, arr):
        """The transaction of one (``rpcs_set_var`` is ``rpcs_set_vars``
        with a single variable)."""
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        arr = np.ascontiguousarray(arr)
        dims = (ctypes.c_longlong * arr.ndim)(*arr.shape)
        self._lib.rpcs_set_var(
            self._h, name.encode(), _DT_TO_CODE[arr.dtype], dims, arr.ndim,
            arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes)

    def del_var(self, name):
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        self._lib.rpcs_del_var(self._h, name.encode())

    def wait_stats(self):
        """GET handlers parked on a variable now, and how often a parked
        handler has woken since the server started."""
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        out = (ctypes.c_longlong * 2)()
        self._lib.rpcs_wait_stats(self._h, out)
        return {"parked": out[0], "wakeups": out[1]}

    def time_gets(self, prefix):
        """Time every reply to a GET of a name that starts with ``prefix``
        from now on (``drain_gets`` reads them); ``None`` turns it off, as
        a new server is.  Either way what was waiting is thrown away."""
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        self._lib.rpcs_time_gets(
            self._h, None if prefix is None else prefix.encode())

    def drain_gets(self):
        """What the store timed of the replies written since the last
        drain, oldest first, in microseconds: ``(deliver, late,
        turnaround, dropped)``.  ``deliver`` has a value a reply: from
        variable and request both there to the reply written; ``late`` one
        for each request that came after its variable was stored: how long
        after; ``turnaround`` one for each reply whose connection's reply
        before was a timed one too: from that one written to this request
        read.  ``dropped`` replies fell out of the ring of ``GET_RING``
        undrained."""
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        parts, dropped = [], 0
        while True:
            # 512 a call: a buffer the allocator hands out of its heap (the
            # whole ring's would be mapped and unmapped at every call)
            out = np.empty(3 + 3 * _DRAIN_AT_ONCE, np.int64)
            n = self._lib.rpcs_drain_gets(self._h, out.ctypes.data,
                                          _DRAIN_AT_ONCE)
            fell, n_late, n_turn = out[:3].tolist()
            dropped += fell
            parts.append((out[3:3 + n], out[3 + n:3 + n + n_late],
                          out[3 + 2 * n:3 + 2 * n + n_turn]))
            if n < _DRAIN_AT_ONCE:
                break
        deliver, late, turnaround = parts[0] if len(parts) == 1 else (
            np.concatenate(col) for col in zip(*parts))
        return deliver, late, turnaround, dropped

    def serve(self, enable=True):
        if self._h is None:
            raise ConnectionError("rpc server already shut down")
        self._lib.rpcs_serve(self._h, 1 if enable else 0)

    def shutdown(self):
        if self._h:
            self._lib.rpcs_destroy(self._h)
            self._h = None


class RpcClient:
    def __init__(self, endpoint, connect_timeout=60.0, rpc_deadline=None,
                 retry_times=None):
        """Retries until the server is up (the reference client's
        wait-for-server behavior; grpc_client.cc connect deadline).

        rpc_deadline: per-REQUEST deadline in seconds; a pserver that hangs
        mid-round raises ConnectionError on the trainer instead of blocking
        forever (reference FLAGS_rpc_deadline + grpc_client.cc deadline
        handling).  None reads FLAGS_rpc_deadline (milliseconds, reference
        units; <=0 disables).  Semantics note: the deadline is enforced as
        a per-syscall IDLE timeout (SO_RCVTIMEO/SO_SNDTIMEO), not an
        elapsed-wall-clock deadline like the reference's gRPC one — a
        server that keeps trickling bytes resets it; a silent one trips it.

        retry_times: bounded reconnect-and-retry on deadline/transport
        failure (reference FLAGS_rpc_retry_times; None reads the flag).
        A failed socket may be mid-frame, so a retry NEVER reuses it:
        the handle is closed and the retry opens a fresh connection after
        an exponential backoff with jitter (backoff_delay).  With
        retry_times=0 the first failure poisons the client (handle
        closed); callers must reconnect with a new RpcClient — the
        pre-retry semantics, still used by tests that assert deadline
        behavior in isolation."""
        import random

        self._lib = load()
        host, port = endpoint.rsplit(":", 1)
        if host in ("localhost", ""):
            host = "127.0.0.1"
        self._host, self._port = host, int(port)
        self.endpoint = endpoint
        self._h = None
        self._rng = random.Random()
        if rpc_deadline is None:
            from .. import flags as _flags

            ms = _flags.get_flags(["FLAGS_rpc_deadline"])[
                "FLAGS_rpc_deadline"]
            rpc_deadline = float(ms) / 1000.0 if ms and ms > 0 else 0.0
        self.rpc_deadline = float(rpc_deadline or 0.0)
        if retry_times is None:
            from .. import flags as _flags

            retry_times = _flags.get_flags(["FLAGS_rpc_retry_times"])[
                "FLAGS_rpc_retry_times"]
        self.retry_times = max(int(retry_times or 0), 0)
        self._connect(connect_timeout)

    def _connect(self, connect_timeout):
        import time

        deadline = time.time() + connect_timeout
        while True:
            self._h = self._lib.rpcc_connect(self._host.encode(), self._port)
            if self._h or time.time() > deadline:
                break
            time.sleep(0.1)
        if not self._h:
            raise ConnectionError("cannot connect to pserver %s within %.0fs"
                                  % (self.endpoint, connect_timeout))
        if self.rpc_deadline > 0:
            self._lib.rpcc_set_deadline(self._h, self.rpc_deadline)

    def _err(self, what):
        hint = (" (deadline %.0fs — pserver hung or connection lost)"
                % self.rpc_deadline if self.rpc_deadline > 0
                else " (connection lost)")
        # a timed-out socket may be mid-frame: reusing this connection
        # would read misaligned frames (silent desync), so every failure
        # closes the handle — retries reconnect fresh
        self.close()
        return ConnectionError("%s to %s failed%s"
                               % (what, self.endpoint, hint))

    def _check_open(self, what):
        if not self._h:
            raise ConnectionError(
                "%s to %s: client closed after a previous deadline/transport "
                "failure — reconnect with a new RpcClient" %
                (what, self.endpoint))

    def _with_retry(self, what, attempt_fn):
        """Run one RPC with up to retry_times reconnect-and-retry rounds.
        Safe for sends because the PS frames are tagged with sequence ids
        and the pserver dedupes replays (distributed/ps.py)."""
        import time

        op = what.split("(", 1)[0]
        last = None
        for i in range(self.retry_times + 1):
            if i:
                _tm.inc("rpc_retry_total", op=op)
                time.sleep(backoff_delay(i - 1, rng=self._rng))
            try:
                if not self._h:
                    # retry_times=0 keeps the poison contract: a closed
                    # client stays closed.  With retries, reconnect —
                    # bounded per attempt so remaining attempts still get
                    # to back off while the server restarts
                    if self.retry_times == 0:
                        self._check_open(what)
                    self._connect(connect_timeout=5.0)
                return attempt_fn()
            except ConnectionError as e:
                last = e
                _tm.inc("rpc_failure_total", op=op)
        _tm.inc("rpc_exhausted_total", op=op)
        raise last

    def send_var(self, name, arr):
        arr = np.ascontiguousarray(arr)
        dims = (ctypes.c_longlong * max(arr.ndim, 1))(*(arr.shape or (0,)))
        what = "send_var(%s)" % name
        # stamp the active trace context onto the frame name — SEND names
        # only surface via server poll (which strips them) and never
        # enter the var store, so GET-by-name semantics are untouched
        wire_name = _tr.stamp_wire_name(name)
        if _tm.enabled():
            _tm.inc("rpc_send_total")
            _tm.inc("rpc_send_bytes_total", int(arr.nbytes))

        def attempt():
            self._check_open(what)
            # fault point rpc.send: "drop" = frame lost before the wire
            # (client sees the same deadline error a lost ACK produces);
            # "error" = transport dies AFTER delivery (ACK lost) — the
            # retry then REPLAYS a frame the server already applied, which
            # is exactly what dedupe-by-sequence must absorb
            kind = maybe_fail("rpc.send")
            if kind == "drop":
                self.close()
                raise FaultInjected("%s to %s: injected frame drop"
                                    % (what, self.endpoint))
            rc = self._lib.rpcc_send_var(
                self._h, wire_name.encode(), _DT_TO_CODE[arr.dtype], dims,
                arr.ndim, arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes)
            if rc != 0:
                raise self._err(what)
            if kind == "error":
                self.close()
                raise FaultInjected("%s to %s: injected transport error "
                                    "after delivery" % (what, self.endpoint))

        return self._with_retry(what, attempt)

    def get_var(self, name):
        what = "get_var(%s)" % name
        _tm.inc("rpc_get_total")

        def attempt():
            self._check_open(what)
            kind = maybe_fail("rpc.get")
            if kind == "drop":
                self.close()
                raise FaultInjected("%s to %s: injected request drop"
                                    % (what, self.endpoint))
            c = ctypes
            dtype = c.c_ubyte()
            dims = (c.c_longlong * 16)()
            ndim = c.c_int()
            data = c.c_void_p()
            n = self._lib.rpcc_get_var(self._h, name.encode(), c.byref(dtype),
                                       dims, 16, c.byref(ndim), c.byref(data))
            if n < 0:
                raise self._err(what)
            shape = tuple(dims[i] for i in range(ndim.value))
            buf = ctypes.string_at(data.value, n)
            self._lib.rpc_free(data)
            if kind == "error":
                # reply lost on the way back: discard it and fail (GET is
                # idempotent — the retry simply re-asks)
                self.close()
                raise FaultInjected("%s to %s: injected reply loss"
                                    % (what, self.endpoint))
            return np.frombuffer(buf, dtype=np.dtype(_DTYPES[dtype.value])) \
                .reshape(shape).copy()

        return self._with_retry(what, attempt)

    def barrier(self, kind):
        what = "barrier(%s)" % kind

        def attempt():
            self._check_open(what)
            if self._lib.rpcc_barrier(self._h, kind.encode()) != 0:
                raise self._err(what)

        return self._with_retry(what, attempt)

    def complete(self):
        if not self._h:
            return  # fire-and-forget; tolerate a poisoned/closed client
        self._lib.rpcc_complete(self._h)

    def close(self):
        if self._h:
            self._lib.rpcc_close(self._h)
            self._h = None
