"""Parameter-server runtime: pserver event loop + trainer comm.

Analog of the reference's PS stack (SURVEY.md §3.4):
- pserver: listen_and_serv_op.cc:110 RunSyncLoop — wait for all trainer
  grad sends, run the optimizer sub-program, publish updated params, repeat;
  exit when every trainer sends COMPLETE (executor.cc:110 SendComplete).
- trainer: send_op/send_barrier/recv_op sequence around each step
  (distribute_transpiler.py's rewritten program), here executed by the
  runtime after the compiled XLA step instead of as graph ops — the compiled
  program stays pure/functional (TPU-idiomatic), communication happens at
  step boundaries over the native C++ transport (native/csrc/tensor_rpc.cc).

Round consistency is VERSION-GATED instead of barrier-gated: round r's
params are published under "name#r" and a GET for that key blocks until the
server finishes round r.  A fast trainer therefore cannot lap the sync
protocol (it blocks in its own round-r GET until every trainer's round-r
grads arrived) — this replaces the reference's fetch_barrier op.

Two consistency modes, selected by the transpiler's sync_mode:
- sync: barrier-gated rounds, mean-aggregated grads (RunSyncLoop).
- async: per-arrival updates with no barriers — each grad immediately runs
  its param's optimizer sub-program and republishes (the reference's
  AsyncCommunicator / RunAsyncLoop, communicator.h:285).  LR-schedule ops
  advance once per logical step (every owned*trainers arrivals), not per
  arrival.

Fault tolerance (this layer owns the at-most-once + liveness contracts; the
transport's retry/backoff lives in native/rpc.py):

- Dedupe-by-sequence: every trainer frame that MUTATES server state (grad
  sends, geo deltas, send-barriers) is tagged ``base@@s<tid>:<nonce>:<seq>``
  with a per-client monotonically increasing seq.  An RPC retry after an
  ACK-lost transport failure replays the frame under the SAME tag, so the
  server applies each logical send at most once (_ReplayFilter).  The nonce
  is drawn fresh per trainer incarnation so a relaunched trainer (seq back
  at 0) is not mistaken for a replay.  Heartbeats/byes stay untagged —
  they are idempotent.
- Eviction / re-quorum (sync mode): the HeartBeatMonitor's checker thread
  EVICTS trainers silent longer than FLAGS_worker_hb_timeout, delivering
  the eviction as a ``__evict__<tid>`` self-RPC so it wakes the round loop
  even when it is parked in poll().  A round's barrier quorum is the LIVE
  set (all - completed - evicted), so rounds keep flowing on survivors.
  Any later contact from an evicted trainer re-admits it.
- Eviction / state reclaim (async mode): the same ``__evict__`` self-RPC
  drops the silent trainer's _ReplayFilter entry and liveness slot, so
  server-side per-trainer state stays bounded by the LIVE trainer set.  A
  relaunched incarnation re-keys under a fresh nonce and its first
  heartbeat re-registers with the monitor — re-admission is automatic.
  Geo mode pushes no heartbeats, so eviction stays disabled there.
- Rejoin: the current round number is published under ``__round__`` and the
  last TWO param versions stay available, so a supervised relaunch
  (distributed/launch.py --restart_failed) can sync its round counter and
  pull a live version despite racing the round it missed.
"""

import collections
import logging

import numpy as np

from ..core import telemetry as _tm
from ..native.rpc import RpcClient, RpcServer, EV_BARRIER, EV_COMPLETE, EV_SEND
from ..utils.fault_injection import maybe_fail

__all__ = ["run_pserver", "TrainerPSComm", "HeartBeatMonitor"]

# pservers running as THREADS of this process (tests; the reference runs
# separate processes).  complete() waits for them to leave the native poll
# so interpreter exit can't abort a daemon thread parked in C++.
_LIVE_SERVERS = set()
_LIVE_LOCK = __import__("threading").Lock()


def _vkey(name, version):
    return "%s#%d" % (name, version)


_HB_PREFIX = "__hb__"
_HB_BYE_PREFIX = "__hb_bye__"
_EVICT_PREFIX = "__evict__"
_ROUND_KEY = "__round__"

_SEQ_SEP = "@@s"


def _untag(name):
    """Split ``base@@s<tid>:<nonce>:<seq>`` -> (base, tid, nonce, seq);
    untagged names come back as (name, None, 0, 0)."""
    i = name.rfind(_SEQ_SEP)
    if i < 0:
        return name, None, 0, 0
    try:
        tid_s, nonce_s, seq_s = name[i + len(_SEQ_SEP):].split(":")
        return name[:i], int(tid_s), int(nonce_s), int(seq_s)
    except ValueError:
        return name, None, 0, 0


class _ReplayFilter:
    """At-most-once filter for tagged trainer frames.  A retry after an
    ACK-lost failure replays the frame under its original tag, and frames
    from one client arrive in send order (sequential client, ordered
    connection), so a frame is a replay iff its seq is <= the last seq seen
    for that (tid, nonce).  A different nonce is a new trainer incarnation:
    accept and re-key."""

    def __init__(self):
        self._last = {}   # tid -> (nonce, last_seq)

    def fresh(self, tid, nonce, seq):
        if tid is None:
            return True
        cur = self._last.get(tid)
        if cur is not None and cur[0] == nonce and seq <= cur[1]:
            return False
        self._last[tid] = (nonce, seq)
        return True

    def evict(self, tid):
        """Forget a trainer's dedupe state (heartbeat eviction): bounds the
        filter to live trainers.  Safe because a relaunched incarnation
        re-keys under a fresh nonce regardless, and the evicted trainer has
        been silent past the heartbeat timeout — far beyond the RPC retry
        budget, so no replayed frame of its old incarnation is in flight."""
        self._last.pop(tid, None)


def _handle_hb(monitor, name):
    """Returns True if `name` was a heartbeat/bye event (consumed)."""
    if name.startswith(_HB_BYE_PREFIX):
        monitor.remove(int(name[len(_HB_BYE_PREFIX):]))
        return True
    if name.startswith(_HB_PREFIX):
        monitor.update(int(name[len(_HB_PREFIX):]))
        return True
    return False


def run_pserver(exe, program, scope):
    """Blocking pserver loop for a transpiled pserver program (the program
    holds one `listen_and_serv` op; metadata on program._ps_server)."""
    from ..core.executor import scope_guard

    meta = program._ps_server
    endpoint = meta["endpoint"]
    port = int(endpoint.rsplit(":", 1)[1])
    params = meta["params"]              # param names owned by this server
    grad_to_param = meta["grad_map"]     # grad name -> param name
    trainers = int(meta["trainers"])
    opt_prog = meta["optimize_program"]

    server = RpcServer(port)
    server.serve(True)
    completed = [0]
    monitor = HeartBeatMonitor(trainers, name="ps:%s" % endpoint)
    # sync mode graduates the monitor from logging to EVICTION: the round
    # loop re-quorums on survivors.  Async mode has no barrier to deadlock,
    # but a dead trainer still pins server state (replay-filter entry +
    # liveness slot), so eviction reclaims those instead.  Geo stays
    # log-only: geo trainers push no heartbeats, so there is no liveness
    # signal to evict on.
    evict_enabled = not meta.get("geo", False)
    # dedicated checker thread (heart_beat_monitor.h runs the monitor in its
    # own thread): a dead trainer in sync mode leaves the server blocked in
    # poll(), so arrival-driven checks alone would never fire.  Evictions
    # are delivered as __evict__ self-RPCs for the same reason — only an
    # inbound event can wake the round loop.
    _mon_stop = __import__("threading").Event()

    def _mon_loop():
        evict_client = [None]
        tick = max(min(monitor.timeout_s / 2.0, 5.0), 0.25)
        while not _mon_stop.wait(tick):
            dead = monitor.check()
            if not dead or not evict_enabled or _mon_stop.is_set():
                continue
            try:
                if evict_client[0] is None:
                    evict_client[0] = RpcClient(
                        "127.0.0.1:%d" % server.port, connect_timeout=5.0,
                        rpc_deadline=5.0, retry_times=0)
                for w in dead:
                    evict_client[0].send_var(_EVICT_PREFIX + str(w),
                                             np.asarray([w], np.int64))
            except Exception:
                # server busy/shutting down — drop the tick, reconnect next
                evict_client[0] = None
        if evict_client[0] is not None:
            evict_client[0].close()

    if not meta.get("geo", False):
        # geo trainers push only sparse param deltas (no heartbeats), so
        # the checker would log false positives there
        __import__("threading").Thread(target=_mon_loop, daemon=True).start()

    def publish(version):
        for p in params:
            server.set_var(
                _vkey(p, version),
                np.asarray(scope.find_var(p).get_tensor().numpy()))
            if version > 1:
                # keep the last TWO versions: a relaunched trainer that just
                # read __round__ == version-1 must still be able to pull it
                # even if this publish races its GETs
                server.del_var(_vkey(p, version - 2))
        # rejoin protocol: relaunched trainers read the round counter to
        # sync TrainerPSComm._round before their first pull
        server.set_var(_ROUND_KEY, np.asarray([version], np.int64))
        # __metrics__ RPC: republish the telemetry snapshot with every
        # round so trainers/tools scrape a fresh view (no-op when off)
        _tm.publish_rpc(server)

    def run_sync():
        import time as _time

        publish(0)  # pserver startup already ran: serve initial params
        version = 0
        replay = _ReplayFilter()
        evicted = set()
        done = set()          # tids that sent __hb_bye__ (clean exit)
        idle_since = [None]   # wall clock when the live set went empty

        def contact(tid):
            """Any frame from a trainer proves liveness and re-admits it."""
            if tid is None or tid in done:
                return
            monitor.update(tid)
            idle_since[0] = None
            if tid in evicted:
                evicted.discard(tid)
                logging.warning("[ps:%s] re-admitted trainer %d",
                                endpoint, tid)
                _tm.inc("ps_readmit_total", ps=endpoint)
                _tm.event("readmit", ps=endpoint, trainer=tid)

        while True:
            t_round = _time.time()
            round_fault = maybe_fail("ps.round")
            if round_fault == "error":
                raise RuntimeError(
                    "injected pserver failure at round %d" % version)
            grads = collections.defaultdict(list)
            barrier_tids = set()
            anon_barriers = [0]   # untagged barriers (raw clients)
            while True:
                live = set(range(trainers)) - done - evicted
                if live and len(barrier_tids & live) + anon_barriers[0] \
                        >= len(live):
                    break
                if not live:
                    # every tracked trainer is done or evicted
                    if completed[0] >= trainers or not evicted:
                        return
                    # supervised relaunch may bring evicted trainers back:
                    # linger for a grace window (woken by the monitor's
                    # periodic __evict__ ticks) before giving up on them
                    now = _time.time()
                    if idle_since[0] is None:
                        idle_since[0] = now
                    elif now - idle_since[0] > 2.0 * monitor.timeout_s:
                        logging.warning(
                            "[ps:%s] all live trainers gone for %.0fs "
                            "(evicted: %s) — shutting down round loop",
                            endpoint, now - idle_since[0], sorted(evicted))
                        return
                t, name, arr = server.poll()
                if t == 0:
                    return
                if t == EV_COMPLETE:
                    completed[0] += 1
                    if completed[0] >= trainers:
                        return
                    continue
                base, tid, nonce, seq = _untag(name)
                if t == EV_BARRIER:
                    if base != "send":
                        continue
                    contact(tid)
                    if not replay.fresh(tid, nonce, seq):
                        _tm.inc("ps_dedupe_drop_total", ps=endpoint)
                        continue
                    if tid is None:
                        anon_barriers[0] += 1
                    else:
                        barrier_tids.add(tid)
                    continue
                if t != EV_SEND:
                    continue
                if base.startswith(_HB_BYE_PREFIX):
                    w = int(base[len(_HB_BYE_PREFIX):])
                    done.add(w)
                    evicted.discard(w)
                    monitor.remove(w)
                    continue
                if base.startswith(_HB_PREFIX):
                    contact(int(base[len(_HB_PREFIX):]))
                    continue
                if base.startswith(_EVICT_PREFIX):
                    w = int(base[len(_EVICT_PREFIX):])
                    if w not in done and w not in evicted:
                        evicted.add(w)
                        logging.warning(
                            "[ps:%s] evicting silent trainer %d — round "
                            "re-quorums on survivors", endpoint, w)
                        _tm.inc("ps_eviction_total", ps=endpoint,
                                mode="sync")
                        _tm.event("eviction", ps=endpoint, trainer=w,
                                  mode="sync", round=version)
                    continue
                contact(tid)
                if not replay.fresh(tid, nonce, seq):
                    _tm.inc("ps_dedupe_drop_total", ps=endpoint)
                    continue
                grads[base].append(arr)
            if round_fault == "drop":
                # injected round drop: lose the round's gradients; params
                # republish unchanged so trainers still make progress
                grads.clear()
            feed = {}
            for gname, parts in grads.items():
                if gname not in grad_to_param:
                    continue
                agg = parts[0].astype(np.float32)
                for p in parts[1:]:
                    agg = agg + p
                feed[gname] = (agg / max(len(parts), 1)).astype(parts[0].dtype)
            if feed:
                with scope_guard(scope):
                    exe.run(opt_prog, feed=feed, fetch_list=[])
            version += 1
            publish(version)
            if _tm.enabled():
                _tm.observe("ps_round_ms", (_time.time() - t_round) * 1e3,
                            ps=endpoint)
                _tm.event("ps_round", ps=endpoint, round=version,
                          grads=len(grads), dropped=round_fault == "drop")

    def run_async():
        """Async mode (reference AsyncCommunicator / RunAsyncLoop,
        communicator.h:285): every grad arrival applies its param's
        optimizer sub-program immediately and republishes — no barriers,
        no versions; trainers always read the freshest params."""
        per_param = meta["optimize_programs"]
        lr_prog = meta.get("lr_program")
        arrivals = [0]
        per_step = max(len(params) * trainers, 1)
        replay = _ReplayFilter()

        def publish_async(p):
            server.set_var(
                _vkey(p, -1),
                np.asarray(scope.find_var(p).get_tensor().numpy()))
            _tm.publish_rpc(server)

        for p in params:
            publish_async(p)
        while True:
            t, name, arr = server.poll()
            if t == 0:
                return
            if t == EV_COMPLETE:
                completed[0] += 1
                if completed[0] >= trainers:
                    return
                continue
            if t != EV_SEND:
                continue
            base, tid, nonce, seq = _untag(name)
            if _handle_hb(monitor, base):
                continue
            if base.startswith(_EVICT_PREFIX):
                # reclaim the silent trainer's server-side state: its
                # replay-filter entry and liveness slot would otherwise
                # live forever.  A relaunched incarnation re-keys under a
                # fresh nonce, and its first heartbeat re-registers with
                # the monitor, so re-admission is automatic.
                w = int(base[len(_EVICT_PREFIX):])
                replay.evict(w)
                monitor.remove(w)
                logging.warning(
                    "[ps:%s] evicted silent trainer %d (async) — "
                    "replay/liveness state reclaimed", endpoint, w)
                _tm.inc("ps_eviction_total", ps=endpoint, mode="async")
                _tm.event("eviction", ps=endpoint, trainer=w, mode="async")
                continue
            if base in grad_to_param:
                if not replay.fresh(tid, nonce, seq):
                    # replayed send: already applied this grad
                    _tm.inc("ps_dedupe_drop_total", ps=endpoint)
                    continue
                pname = grad_to_param[base]
                with scope_guard(scope):
                    exe.run(per_param[pname], feed={base: arr},
                            fetch_list=[])
                    arrivals[0] += 1
                    if (lr_prog is not None
                            and lr_prog.global_block().ops
                            and arrivals[0] % per_step == 0):
                        exe.run(lr_prog, fetch_list=[])
                publish_async(pname)

    def run_geo():
        """Geo-SGD (reference geo_sgd_transpiler.py + GeoSgdCommunicator,
        communicator.h:332): trainers optimize locally and push param
        DELTAS; the server adds each delta to its copy and republishes —
        no optimizer runs server-side.  Deltas are NOT idempotent (the
        server accumulates them), so dedupe matters doubly here."""
        replay = _ReplayFilter()
        applied = {}    # (tid, param) -> the key its last delta is read by

        def publish_geo(p, key=None):
            server.set_var(
                key or _vkey(p, -1),
                np.asarray(scope.find_var(p).get_tensor().numpy()))
            _tm.publish_rpc(server)

        for p in params:
            publish_geo(p)
        param_set = set(params)
        while True:
            t, name, arr = server.poll()
            if t == 0:
                return
            if t == EV_COMPLETE:
                completed[0] += 1
                if completed[0] >= trainers:
                    return
                continue
            if t != EV_SEND:
                continue
            base, tid, nonce, seq = _untag(name)
            if base in param_set:
                if not replay.fresh(tid, nonce, seq):
                    # replayed delta would double-apply
                    _tm.inc("ps_dedupe_drop_total", ps=endpoint)
                    continue
                cur = np.asarray(scope.find_var(base).get_tensor().numpy())
                scope.var(base).set(cur + arr)
                publish_geo(base)
                # read-your-write: the merged value also under the delta's
                # own tag, which the sender's GET blocks on.  A SEND is
                # acknowledged when it is queued, not when it is applied, so
                # a pull of the -1 key could come back without the delta and
                # the next push would then add the same progress twice.
                publish_geo(base, key=name)
                stale = applied.get((tid, base))
                if stale is not None:
                    server.del_var(stale)
                applied[(tid, base)] = name

    with _LIVE_LOCK:
        _LIVE_SERVERS.add(id(server))
    try:
        if meta.get("geo", False):
            run_geo()
        elif meta.get("sync", True):
            run_sync()
        else:
            run_async()
    finally:
        _mon_stop.set()
        server.shutdown()
        with _LIVE_LOCK:
            _LIVE_SERVERS.discard(id(server))


class TrainerPSComm:
    """Per-trainer connections to every pserver + the sync-step protocol."""

    def __init__(self, meta):
        import random

        self.meta = meta
        self.endpoints = meta["endpoints"]
        self.param_to_ep = meta["param_to_ep"]
        self.param_to_grad = meta["param_grad"]
        self.trainer_id = int(meta["trainer_id"])
        self.sync = bool(meta.get("sync", True))
        self.geo = bool(meta.get("geo", False))
        self.geo_push_nums = int(meta.get("geo_push_nums", 100))
        self._clients = {ep: RpcClient(ep) for ep in self.endpoints}
        self._round = 0
        self._step_count = 0
        self._snapshot = {}   # geo: param values at the last push/pull
        self._closed = False
        # dedupe-by-sequence tag state: nonce identifies this incarnation
        # (a relaunched trainer must not look like a replay of its previous
        # life), seq orders this incarnation's state-mutating frames
        self._nonce = random.getrandbits(31)
        self._seq = 0

    def _tag(self, base):
        s = self._seq
        self._seq += 1
        return "%s%s%d:%d:%d" % (base, _SEQ_SEP, self.trainer_id,
                                 self._nonce, s)

    def _pull(self, scope, version):
        for p, ep in self.param_to_ep.items():
            scope.var(p).set(self._clients[ep].get_var(_vkey(p, version)))

    # initial param pull (reference: recv ops in the rewritten startup)
    def pull_initial_params(self, scope):
        if self.sync and not self.geo:
            # rejoin protocol: a relaunched trainer joins at the cluster's
            # CURRENT round, not 0.  Servers publish __round__ with every
            # version; they stay within one round of each other (lockstep),
            # and the laggard completes its in-flight round on the
            # survivors' quorum, so max() is always pullable (servers keep
            # the last two versions).
            self._round = max(
                int(self._clients[ep].get_var(_ROUND_KEY).ravel()[0])
                for ep in self.endpoints)
            self._pull(scope, self._round)
        else:
            self._pull(scope, -1)
        if self.geo:
            self._snapshot = {
                p: np.asarray(scope.find_var(p).get_tensor().numpy()).copy()
                for p in self.param_to_ep}

    def step(self, scope, grad_values):
        """grad_values: grad name -> ndarray for THIS trainer's step."""
        if self.geo:
            return self._geo_step(scope)
        if self._closed:
            raise RuntimeError(
                "PS trainer already completed (Executor.close() was called); "
                "create a new scope/executor to train again")
        # heartbeat: one tiny var per step so the server's HeartBeatMonitor
        # tracks this worker's liveness (heart_beat_monitor.h UPDATE mode).
        # Untagged: heartbeats are idempotent, replays are harmless.
        hb = np.asarray([self.trainer_id], np.int64)
        for c in self._clients.values():
            c.send_var(_HB_PREFIX + str(self.trainer_id), hb)
        for p, g in self.param_to_grad.items():
            if g in grad_values:
                self._clients[self.param_to_ep[p]].send_var(
                    self._tag(g), grad_values[g])
        if not self.sync:
            # async (communicator.h:285): no barrier, read freshest params
            self._pull(scope, -1)
            return
        for c in self._clients.values():
            c.barrier(self._tag("send"))
        self._round += 1
        self._pull(scope, self._round)  # blocks until every trainer's round
        # arrived and the optimizer ran — the sync point

    def _geo_step(self, scope):
        """Local training; every K steps push param deltas vs the last
        snapshot and pull the server's merged params."""
        if self._closed:
            raise RuntimeError("PS trainer already completed")
        self._step_count += 1
        if self._step_count % self.geo_push_nums:
            return
        tags = {}
        for p, ep in self.param_to_ep.items():
            cur = np.asarray(scope.find_var(p).get_tensor().numpy())
            delta = cur - self._snapshot[p]
            tags[p] = self._tag(p)
            self._clients[ep].send_var(tags[p], delta)
        # the merged params as of this trainer's own deltas: the server
        # publishes them under each delta's tag once it has applied it
        for p, ep in self.param_to_ep.items():
            scope.var(p).set(self._clients[ep].get_var(tags[p]))
            self._snapshot[p] = np.asarray(
                scope.find_var(p).get_tensor().numpy()).copy()

    def complete(self):
        if self._closed:
            return
        self._closed = True
        bye = np.asarray([self.trainer_id], np.int64)
        for c in self._clients.values():
            try:
                c.send_var(_HB_BYE_PREFIX + str(self.trainer_id), bye)
                c.complete()
                c.close()
            except Exception:
                pass
        # wait (bounded) for IN-PROCESS pserver threads to leave the
        # native poll: a daemon thread parked in C++ at interpreter exit
        # trips CPython's pthread_exit unwinding (abort).  Costs nothing
        # when pservers run as separate processes (registry empty); with
        # several trainer threads only the last COMPLETE releases the
        # servers, so earlier completers may wait out the bound.
        import time

        deadline = time.time() + 2.0
        while time.time() < deadline:
            with _LIVE_LOCK:
                if not _LIVE_SERVERS:
                    return
            time.sleep(0.01)


class HeartBeatMonitor:
    """Pserver-side worker liveness tracking (parity:
    operators/distributed/heart_beat_monitor.h:54): records each worker's
    last-contact timestamp; `check` returns (and logs once) workers silent
    for longer than `timeout_s`.  The reference runs this only in UPDATE
    mode and only LOGS; here run_pserver's checker thread turns the dead
    list into sync-quorum EVICTIONS (see module docstring) — the monitor
    itself stays a passive bookkeeper.

    timeout_s=None reads FLAGS_worker_hb_timeout.  Workers are seeded at
    construction + startup_grace_s (default: one extra timeout) so a
    worker that dies before its first heartbeat IS eventually caught, but
    a slow start (process spawn + jax import can take tens of seconds)
    is not mistaken for death."""

    def __init__(self, n_workers, timeout_s=None, name="ps",
                 startup_grace_s=None, worker_ids=None):
        import time

        if timeout_s is None:
            from .. import flags as _flags

            timeout_s = float(_flags.flag("worker_hb_timeout") or 60.0)
        self._time = time.time
        if worker_ids is None:
            worker_ids = range(n_workers)
        worker_ids = [int(w) for w in worker_ids]
        self.n_workers = len(worker_ids)
        self.timeout_s = timeout_s
        self.startup_grace_s = (timeout_s if startup_grace_s is None
                                else startup_grace_s)
        self.name = name
        now = self._time()
        self._last_seen = {w: now + self.startup_grace_s
                           for w in worker_ids}
        self._warned = set()
        self._lock = __import__("threading").Lock()

    def update(self, worker_id):
        with self._lock:
            self._last_seen[int(worker_id)] = self._time()
            self._warned.discard(int(worker_id))

    def remove(self, worker_id):
        """Worker exited cleanly (SendComplete) — stop tracking it."""
        with self._lock:
            self._last_seen.pop(int(worker_id), None)
            self._warned.discard(int(worker_id))

    def check(self):
        """Returns the list of currently-dead worker ids (and logs new
        ones once, like the monitor thread's LOG(WARNING))."""
        now = self._time()
        with self._lock:
            dead = [(w, now - t) for w, t in self._last_seen.items()
                    if now - t > self.timeout_s]
            fresh = [wt for wt in dead if wt[0] not in self._warned]
            self._warned.update(w for w, _ in fresh)
        _tm.set_gauge("ps_dead_workers", len(dead), ps=self.name)
        if fresh:
            _tm.inc("ps_heartbeat_miss_total", len(fresh), ps=self.name)
        for w, silent in fresh:
            logging.warning("[%s] worker %d silent for %.0fs",
                            self.name, w, silent)
        return [w for w, _ in dead]
