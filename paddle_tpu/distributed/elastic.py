"""Elastic re-quorum for the collective all-reduce path.

The PS runtime (distributed/ps.py) evicts dead trainers and re-quorums
sync rounds; this module gives collective (NCCL-style all-reduce) jobs the
same survival story.  A quorum/membership layer runs over the
``PADDLE_COORDINATOR`` control channel (native RpcServer/RpcClient, one
port above each member's data endpoint):

  1. every member heartbeats the quorum coordinator (the lowest-rank live
     member); the coordinator tracks liveness with the PS
     ``HeartBeatMonitor`` and declares a member dead after
     ``FLAGS_elastic_hb_timeout`` seconds of silence;
  2. on death (or a pending rejoin) the coordinator aborts the step gate,
     bumps the quorum *epoch*, and publishes a new membership view: the
     dense rank remap, the survivor count, and a fresh jax.distributed
     coordinator port (``base + epoch * FLAGS_elastic_port_stride``);
  3. every survivor re-runs jax.distributed initialization against the new
     view, re-transpiles its pristine main/startup programs with
     ``GradAllReduce`` for the new ``nranks``/endpoints, passes them
     through ``core/analysis.verify_program`` in **error** mode (including
     DL005: the 1/nranks gradient scale must match the new world), restores
     params from ``io.CheckpointManager.latest_valid()``, and resumes;
  4. a member relaunched by ``launch.py --restart_failed`` rejoins at the
     next epoch: it probes the member list for the live coordinator, posts
     ``__ejoin__``, and adopts the first view that includes it.

Why the old jax world is *parked*, not shut down: jaxlib's coordination
service terminates any process that learns of a peer failure
(``LOG(FATAL)`` in xla/pjrt/distributed/client.h — both the missed-
heartbeat callback and the error-polling thread), and the pybind
``missed_heartbeat_callback`` escape hatch is unusable from Python in this
jaxlib (invoking a Python callback from the C++ thread raises
``std::bad_cast``).  So clients/services are constructed directly with
``shutdown_on_destruction=False`` and heartbeat windows far longer than
any job (the control channel above owns failure detection), and on
re-quorum the dead world's client/service objects are kept referenced
forever: their threads idle on a healthy-looking socket and can never
observe an error.  A process that ever *hosted* a coordination service
must exit via ``finalize()`` (``os._exit``) — C++ destructor order at
interpreter teardown would otherwise close the service under its own
pollers and abort.  Survivor ordering at clean exit: members leave first,
the coordinator leaves last, so no live poller ever sees a dead service.
"""

import hashlib
import json
import logging
import os
import socket
import threading
import time

import numpy as np

from ..core import telemetry as _tm
from ..core import tracing as _tr
from ..native.rpc import RpcClient, RpcServer, EV_SEND
from .ps import HeartBeatMonitor

__all__ = ["ElasticMember", "View", "member_env"]

# control-plane variable names (PS-style __dunder__ namespace)
_HB = "__ehb__"          # <rank>: member heartbeat            [rank, epoch]
_READY = "__eready__"    # <rank>: at the step gate            [epoch, step]
_JOIN = "__ejoin__"      # <rank>: admit me at the next epoch  [rank]
_DONE = "__edone__"      # <rank>: clean completion            [rank]
_ALIVE = "__alive__"     # served by every member's local server
_VIEW = "__eview__"      # latest view; __eview__#<epoch> per epoch
_GATE = "__ego__"        # __ego__#<epoch>:<step> -> [1] go | [0] re-quorum
_STATE = "__estate__"    # peer-restore payload: __estate__#<epoch> meta
                         # (json, uint8) + __estate__#<epoch>#<var> arrays

_GO = 1
_ABORT = 0

# parked-world heartbeat windows: long enough that the coordination
# service never declares anyone dead on its own (the control plane owns
# detection), short enough to be a sane int
_JAX_HB_INTERVAL_S = 3600
_JAX_HB_MAX_MISSING = 10000


def _flag(name):
    from .. import flags

    return flags.flag(name)


def _host_port(endpoint):
    host, port = endpoint.rsplit(":", 1)
    if host in ("localhost", ""):
        host = "127.0.0.1"
    return host, int(port)


def _ctrl_endpoint(member_endpoint):
    host, port = _host_port(member_endpoint)
    return "%s:%d" % (host, port + int(_flag("elastic_ctrl_offset") or 1000))


def _port_free(port):
    s = socket.socket()
    try:
        s.bind(("", port))
        return True
    except OSError:
        return False
    finally:
        s.close()


def _world_fingerprint(*programs):
    """Structural hash of program IR (op types, wiring, attrs).

    Taken at standby build time — right after the full world-level verify —
    and checked again at adoption: an equal fingerprint proves the view is
    byte-for-byte the IR that already passed DL101-104, so adoption can
    skip the (expensive) sibling-rank materialization; any mutation in
    between forces the full blocking re-verify instead."""
    h = hashlib.sha1()
    for prog in programs:
        for blk in prog.blocks:
            for op in blk.ops:
                h.update(json.dumps(op.to_dict(), sort_keys=True,
                                    default=repr).encode())
            h.update(b"|")
    return h.hexdigest()


def member_env():
    """(rank, endpoints, restart_count) from the launcher env."""
    eps = [e for e in os.getenv("PADDLE_TRAINER_ENDPOINTS", "").split(",")
           if e]
    rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
    restarts = int(os.getenv("PADDLE_RESTART_COUNT", "0"))
    return rank, eps, restarts


class View:
    """One quorum epoch's membership: which original ranks are in, who
    coordinates, and where the epoch's jax.distributed service lives.

    ``peer_step``/``peer_src`` carry the peer-to-peer restore offer: the
    newest live post-step state any survivor holds and the lowest rank
    holding it.  (0, -1) means no offer — restore from the filesystem.
    They ride at the TAIL of the wire encoding so old decoders (and
    encodings from old coordinators) stay compatible."""

    __slots__ = ("epoch", "coord_rank", "jax_port", "restore_step", "ranks",
                 "peer_step", "peer_src")

    def __init__(self, epoch, coord_rank, jax_port, restore_step, ranks,
                 peer_step=0, peer_src=-1):
        self.epoch = int(epoch)
        self.coord_rank = int(coord_rank)
        self.jax_port = int(jax_port)
        self.restore_step = int(restore_step)
        self.ranks = tuple(int(r) for r in ranks)
        self.peer_step = int(peer_step)
        self.peer_src = int(peer_src)

    def encode(self):
        return np.asarray([self.epoch, self.coord_rank, self.jax_port,
                           self.restore_step, len(self.ranks)]
                          + list(self.ranks)
                          + [self.peer_step, self.peer_src], np.int64)

    @classmethod
    def decode(cls, arr):
        a = np.asarray(arr).reshape(-1).astype(np.int64)
        n = int(a[4])
        tail = a[5 + n:]
        peer_step, peer_src = ((int(tail[0]), int(tail[1]))
                               if len(tail) >= 2 else (0, -1))
        return cls(a[0], a[1], a[2], a[3], [int(x) for x in a[5:5 + n]],
                   peer_step, peer_src)

    def __repr__(self):
        return ("View(epoch=%d, coord=%d, jax_port=%d, restore=%d, "
                "ranks=%s, peer=%d@%d)"
                % (self.epoch, self.coord_rank, self.jax_port,
                   self.restore_step, list(self.ranks), self.peer_step,
                   self.peer_src))


class _JaxWorld:
    """Direct construction of the jax.distributed client/service so peer
    death cannot abort the process (see module docstring).  Old worlds are
    parked in ``_parked`` — never destroyed."""

    _parked = []
    hosted_service = False

    @classmethod
    def reinit(cls, coord_host, coord_port, num_processes, process_id,
               host_service):
        import jax
        from jax._src import distributed as _dist
        from jax._src.lib import _jax as _xe
        from jax.extend import backend as _jexb

        if os.getenv("JAX_PLATFORMS", "").startswith("cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        gs = _dist.global_state
        if gs.client is not None:
            cls._parked.append((gs.client, gs.service,
                                gs.preemption_sync_manager))
            gs.client = None
            gs.service = None
            gs.preemption_sync_manager = None
        _jexb.clear_backends()
        if host_service:
            gs.service = _xe.get_distributed_runtime_service(
                "[::]:%d" % coord_port, num_processes,
                heartbeat_interval=_JAX_HB_INTERVAL_S,
                max_missing_heartbeats=_JAX_HB_MAX_MISSING)
            cls.hosted_service = True
        gs.client = _xe.get_distributed_runtime_client(
            "%s:%d" % (coord_host, coord_port), process_id,
            init_timeout=120, heartbeat_interval=_JAX_HB_INTERVAL_S,
            max_missing_heartbeats=_JAX_HB_MAX_MISSING,
            shutdown_on_destruction=False)
        gs.client.connect()
        gs.process_id = process_id
        gs.num_processes = num_processes
        gs.coordinator_address = "%s:%d" % (coord_host, coord_port)


class _Coordinator(threading.Thread):
    """Quorum state machine; runs inside the coordinator member's process
    on that member's control RpcServer."""

    def __init__(self, member, epoch, ranks, join_window_s=0.0):
        super().__init__(name="elastic-coord", daemon=True)
        self.m = member
        self.srv = member._server
        self.epoch = int(epoch)
        self.live = set(int(r) for r in ranks)
        self.joins = set()
        self.done = set()
        self.ready = {}          # (epoch, step) -> set(ranks)
        self.released = []       # published gate keys (pruned)
        self.aborted = set()     # epochs whose gates answer [0]
        self.state = {}          # rank -> (state_step, has_state) from HB/READY
        self._stop = False
        self._detect_t0 = None
        # a freshly failed-over coordinator waits for survivors to rejoin
        # before forming its first view
        self._join_deadline = (time.time() + join_window_s
                               if join_window_s else None)
        timeout = float(_flag("elastic_hb_timeout") or 5.0)
        self.mon = HeartBeatMonitor(0, timeout_s=timeout, name="elastic",
                                    worker_ids=sorted(self.live))
        self.all_done = threading.Event()
        self._publish_view(View(self.epoch, member.rank,
                                self._pick_port(self.epoch),
                                self._restore_step(), sorted(self.live)))

    # -- helpers ------------------------------------------------------------

    def _restore_step(self):
        ckpt = self.m.ckpt
        if ckpt is None:
            return 0
        try:
            found = ckpt.latest_valid()
        except Exception:
            found = None
        return found[0] if found else 0

    def _peer_fields(self, fs_step):
        """(peer_step, peer_src) offer for the next view: the newest live
        post-step state among surviving members, preferred over the
        filesystem whenever it is at least as fresh as latest_valid() —
        survivors that stepped past the last checkpoint would DIVERGE the
        world if a rejoiner read the stale fs copy.  (0, -1) when p2p
        restore is off (the coordinator's flag decides for the whole world,
        so every member takes the same path) or nobody holds usable state."""
        if not _flag("checkpoint_p2p_restore"):
            return 0, -1
        cands = {r: s for r, (s, h) in self.state.items()
                 if r in self.live and h and s > 0}
        if not cands:
            return 0, -1
        peer_step = max(cands.values())
        if peer_step < int(fs_step):
            return 0, -1
        src = min(r for r, s in cands.items() if s == peer_step)
        return int(peer_step), int(src)

    def _pick_port(self, epoch):
        base = _host_port(self.m.members[self.m.rank])[1]
        stride = int(_flag("elastic_port_stride") or 29)
        port = base + stride * epoch
        for _ in range(32):
            if epoch == 0 or _port_free(port):
                return port
            port += stride
        return port

    def _publish_view(self, view):
        self.view = view
        enc = view.encode()
        self.srv.set_var("%s#%d" % (_VIEW, view.epoch), enc)
        self.srv.set_var(_VIEW, enc)
        self.srv.serve(True)
        _tm.set_gauge("elastic_epoch", view.epoch)
        _tm.set_gauge("elastic_world_size", len(view.ranks))

    def _release(self, epoch, step, value):
        key = "%s#%d:%d" % (_GATE, epoch, step)
        self.srv.set_var(key, np.asarray([value], np.int64))
        if key not in self.released:
            self.released.append(key)
        while len(self.released) > 16:
            self.srv.del_var(self.released.pop(0))

    # -- event handling -----------------------------------------------------

    def run(self):
        while not self._stop:
            t, name, arr = self.srv.poll()
            if t == 0:
                return
            if t == EV_SEND:
                self._on_event(name, arr)
            self._tick()

    def _on_event(self, name, arr):
        if name.startswith(_HB):
            a = np.asarray(arr).reshape(-1)
            r = int(a[0])
            if r in self.live:
                self.mon.update(r)
            if len(a) >= 4:  # extended HB carries (state_step, has_state)
                self.state[r] = (int(a[2]), int(a[3]))
        elif name.startswith(_READY):
            r = int(name[len(_READY):])
            epoch, step = int(arr[0]), int(arr[1])
            if r in self.live:
                self.mon.update(r)
            # a member at the gate holds live state for `step` done steps
            self.state[r] = (step, 1)
            if epoch in self.aborted or epoch < self.epoch:
                self._release(epoch, step, _ABORT)
                return
            got = self.ready.setdefault((epoch, step), set())
            got.add(r)
            if got >= self.live:
                self._release(epoch, step, _GO)
                self.ready.pop((epoch, step), None)
        elif name.startswith(_JOIN):
            r = int(arr[0])
            if r not in self.live:
                self.joins.add(r)
        elif name.startswith(_DONE):
            r = int(arr[0])
            self.done.add(r)
            self.mon.remove(r)
            self.live.discard(r)
            # release any gate the remaining members are parked on
            for (epoch, step), got in list(self.ready.items()):
                if epoch == self.epoch and got >= self.live:
                    self._release(epoch, step, _GO)
                    self.ready.pop((epoch, step), None)
            if not self.live - {self.m.rank}:
                self.all_done.set()

    def _tick(self):
        # the coordinator's own process is trivially alive while this code
        # runs — never let a stalled local HB thread (GIL contention from a
        # standby compile, a wedged shared RPC client) self-evict the
        # quorum's anchor; coordinator death is the members' failover path
        if self.m.rank in self.live:
            self.mon.update(self.m.rank)
        dead = [r for r in self.mon.check() if r in self.live]
        joining = self.joins - self.live
        if dead and self._detect_t0 is None:
            self._detect_t0 = time.perf_counter()
        if self._join_deadline is not None:
            if time.time() < self._join_deadline:
                return
            self._join_deadline = None
            self._requorum(dead)
            return
        if dead or joining:
            self._requorum(dead)

    def _requorum(self, dead):
        t0 = self._detect_t0 or time.perf_counter()
        self._detect_t0 = None
        old_epoch = self.epoch
        evicted = sorted(set(dead) & self.live)
        joined = sorted(self.joins - self.live)
        self.live = (self.live - set(evicted)) | set(joined)
        self.joins.clear()
        self.epoch += 1
        self.aborted.add(old_epoch)
        self.state = {r: v for r, v in self.state.items() if r in self.live}
        # wake every member parked at an old-epoch gate
        for (epoch, step), _ in list(self.ready.items()):
            if epoch <= old_epoch:
                self._release(epoch, step, _ABORT)
                self.ready.pop((epoch, step), None)
        fs_step = self._restore_step()
        peer_step, peer_src = self._peer_fields(fs_step)
        view = View(self.epoch, self.m.rank, self._pick_port(self.epoch),
                    fs_step, sorted(self.live), peer_step, peer_src)
        # grace: a joiner needs time to init jax + transpile + restore
        timeout = float(_flag("elastic_hb_timeout") or 5.0)
        self.mon = HeartBeatMonitor(0, timeout_s=timeout, name="elastic",
                                    worker_ids=sorted(self.live))
        self._publish_view(view)
        ms = (time.perf_counter() - t0) * 1e3
        if evicted:
            _tm.inc("elastic_evictions_total", len(evicted))
        if joined:
            _tm.inc("elastic_rejoins_total", len(joined))
        _tm.observe("elastic_requorum_ms", ms, role="coordinator")
        _tm.event("elastic_epoch", epoch=self.epoch,
                  world=len(view.ranks), evicted=evicted, joined=joined,
                  restore_step=view.restore_step, ms=round(ms, 3),
                  peer_step=view.peer_step, peer_src=view.peer_src)
        logging.warning(
            "[elastic] epoch %d: world=%s evicted=%s joined=%s "
            "jax_port=%d restore_step=%d peer=%d@%d", self.epoch,
            sorted(self.live), evicted, joined, view.jax_port,
            view.restore_step, view.peer_step, view.peer_src)

    def stop(self):
        self._stop = True


class ElasticMember:
    """Member-side elastic runtime for a collective all-reduce job.

    Usage (see tests/dist_elastic_payload.py)::

        member = ElasticMember(main, startup, executor=exe, ckpt=mgr,
                               feed_names=["x", "y"],
                               fetch_names=[loss.name])
        member.start()                    # quorum + jax init + transpile +
        step = member.restore_step        #   verify + restore
        while step < total_steps:
            if not member.gate(step):     # False -> re-quorumed
                step = member.restore_step
                continue
            out = exe.run(member.main_program, feed=shard(step, member),
                          fetch_list=[member.fetch_names[0]])
            step += 1
            ...checkpoint via member.maybe_save(step)...
        member.finalize()

    ``main``/``startup`` are the PRISTINE (un-transpiled) programs; every
    epoch clones them and applies ``GradAllReduce`` for that epoch's world,
    then verifies the rewrite in error mode (DL001-005) before the executor
    may recompile it."""

    def __init__(self, main_program, startup_program, executor=None,
                 ckpt=None, feed_names=(), fetch_names=(), members=None,
                 rank=None, nrings=1, scope=None, feed_specs=None):
        env_rank, env_eps, env_restarts = member_env()
        self.rank = env_rank if rank is None else int(rank)
        self.members = list(members) if members is not None else env_eps
        if not self.members:
            raise ValueError("no member endpoints: pass members= or set "
                             "PADDLE_TRAINER_ENDPOINTS")
        self.restart_count = env_restarts
        self.base_main = main_program
        self.base_startup = startup_program
        self.executor = executor
        self.ckpt = ckpt
        self.scope = scope
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.nrings = int(nrings)
        # feed signature for pre-compilation: {name: (shape, dtype)} or a
        # callable world_size -> that dict (per-member batch shards shrink
        # when the world does).  Enables the standby pre-compile and the
        # post-adopt warmup; without it only transpile+verify are standby.
        self.feed_specs = feed_specs
        self.view = None
        self.main_program = None
        self.startup_program = None
        self.restore_step = 0
        self._server = None
        self._coord = None
        self._ctrl = None        # send-side client to the coordinator
        self._gate_c = None      # blocking-get client to the coordinator
        self._hb_thread = None
        self._stop_hb = threading.Event()
        self._finalized = False
        # standby views: frozenset(ranks) -> pre-transpiled/verified (and,
        # with feed_specs, tier-B pre-compiled) programs for a world this
        # member might shrink into (see _spawn_standby)
        self._standby = {}
        self._standby_lock = threading.Lock()
        self._standby_thread = None
        # last adoption's phase breakdown (ms) + whether a standby view
        # served it — payloads/tests read these after gate() returns False
        self.last_adopt_phases = {}
        self.last_adopt_standby = False
        # where the last adoption's state came from: "peer" | "fs" | None
        self.last_restore_source = None
        # live-state bookkeeping for peer-to-peer restore: how many steps
        # this member has COMPLETED (updated at the gate and after adopt)
        # and whether the scope holds adopted state at all
        self._state_step = 0
        self._has_state = False
        self._published_state = []  # __estate__ keys served for rejoiners

    # -- properties ----------------------------------------------------------

    @property
    def epoch(self):
        return self.view.epoch if self.view else -1

    @property
    def world(self):
        return len(self.view.ranks) if self.view else 0

    @property
    def pid(self):
        """Dense process id in the current view (jax process_id)."""
        return self.view.ranks.index(self.rank)

    def is_coordinator(self):
        return self.view is not None and self.view.coord_rank == self.rank

    def fetch_var(self, name):
        return self.main_program.global_block().var(name)

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        """Form or join the quorum, then adopt the current view (jax init,
        transpile, verify, startup + checkpoint restore)."""
        host, port = _host_port(self.members[self.rank])
        ctrl_port = port + int(_flag("elastic_ctrl_offset") or 1000)
        self._server = RpcServer(port=ctrl_port)
        self._server.set_var(_ALIVE, np.asarray([self.rank, 0, 0], np.int64))
        self._server.serve(True)

        fresh_seed = self.restart_count == 0
        if self.rank == min(range(len(self.members))) and fresh_seed:
            self._become_coordinator(epoch=0,
                                     ranks=range(len(self.members)))
            coord_rank = self.rank
        else:
            coord_rank = self._find_coordinator()
        self._connect_ctrl(coord_rank)
        self._start_heartbeat()

        view = self._wait_view_with_me()
        self._adopt(view)
        return self

    def _become_coordinator(self, epoch, ranks, join_window_s=0.0):
        self._coord = _Coordinator(self, epoch, ranks,
                                   join_window_s=join_window_s)
        self._server.set_var(
            _ALIVE, np.asarray([self.rank, epoch, 1], np.int64))
        self._coord.start()

    def _find_coordinator(self, window_s=90.0):
        """Probe the member list (rank order) for the live coordinator."""
        deadline = time.time() + window_s
        while time.time() < deadline:
            for r, ep in enumerate(self.members):
                if r == self.rank:
                    continue
                got = self._probe_alive(ep)
                if got is not None and got[2] == 1:
                    return r
            time.sleep(0.3)
        raise ConnectionError(
            "[elastic] rank %d: no live coordinator among %s within %.0fs"
            % (self.rank, self.members, window_s))

    def _probe_alive(self, member_endpoint):
        from ..native import rpc as _rpc

        got = _rpc.probe(_ctrl_endpoint(member_endpoint), key=_ALIVE)
        return None if got is None else [int(x) for x in got]

    def _connect_ctrl(self, coord_rank):
        for c in (self._ctrl, self._gate_c):
            if c is not None:
                try:
                    c.close()
                except Exception:
                    pass
        ep = _ctrl_endpoint(self.members[coord_rank])
        self._coord_rank_hint = coord_rank
        self._ctrl = RpcClient(ep, connect_timeout=60.0, rpc_deadline=10.0,
                               retry_times=3)
        self._gate_c = RpcClient(ep, connect_timeout=60.0, rpc_deadline=60.0,
                                 retry_times=1)

    def _start_heartbeat(self):
        if self._hb_thread is not None:
            return

        def loop():
            interval = float(_flag("elastic_hb_interval") or 0.5)
            name = _HB + str(self.rank)
            while not self._stop_hb.wait(interval):
                try:
                    # extended HB: (state_step, has_state) lets the
                    # coordinator compute the next view's peer-restore offer
                    self._ctrl.send_var(name, np.asarray(
                        [self.rank, self.epoch, int(self._state_step),
                         1 if self._has_state else 0], np.int64))
                except Exception:
                    pass  # gate() owns failure handling

        self._hb_thread = threading.Thread(target=loop, name="elastic-hb",
                                           daemon=True)
        self._hb_thread.start()

    def _wait_view_with_me(self, window_s=180.0):
        """Fetch the current view; if this member was evicted (or is a
        rejoiner), post __ejoin__ and wait for an epoch that includes it."""
        deadline = time.time() + window_s
        asked = False
        while time.time() < deadline:
            view = View.decode(self._gate_c.get_var(_VIEW))
            if self.rank in view.ranks:
                return view
            if not asked:
                self._ctrl.send_var(_JOIN + str(self.rank),
                                    np.asarray([self.rank], np.int64))
                _tm.event("elastic_join_request", rank=self.rank,
                          epoch=view.epoch)
                asked = True
            time.sleep(0.3)
        raise TimeoutError("[elastic] rank %d not admitted within %.0fs"
                           % (self.rank, window_s))

    # -- epoch adoption ------------------------------------------------------

    def _numpyify_scope(self):
        """Detach scope tensors from the dying backend: every value becomes
        a host numpy array before clear_backends invalidates jax.Arrays."""
        scope = self.scope
        if scope is None and self.executor is not None:
            from ..core.executor import global_scope

            scope = global_scope()
        if scope is None:
            return
        s = scope
        while s is not None:
            for name in s.local_var_names():
                var = s.find_var(name)
                t = var.get_tensor() if var else None
                if t is not None and t._is_initialized():
                    try:
                        # np.asarray of a CPU jax.Array can alias the XLA
                        # buffer — a real copy is required or the "detached"
                        # value dangles once clear_backends frees the buffer
                        # (the peer-restore path reads these post-reset)
                        t.set(np.array(t.get(), copy=True))
                    except Exception:
                        pass
            s = getattr(s, "parent", None)

    def _adopt(self, view):
        """Make `view` this process's world: jax re-init, then either
        consume a fresh standby view (transpile+verify already done and the
        executable pre-compiled into the tier-B disk cache — re-quorum
        collapses to cache-restore + checkpoint-restore) or re-transpile +
        re-verify from the pristine programs; finally startup + warmup +
        restore.  Each phase lands in the elastic_requorum_phase_ms
        histogram so the breakdown is auditable."""
        t0 = time.perf_counter()
        old_epoch = self.epoch
        self.view = view
        self._server.set_var(_ALIVE, np.asarray(
            [self.rank, view.epoch, 1 if self._coord else 0], np.int64))
        pid = view.ranks.index(self.rank)
        world = len(view.ranks)
        coord_host = _host_port(self.members[view.coord_rank])[0]

        self._numpyify_scope()
        # survivors hold live post-step state right here (numpy, detached
        # from the dying backend) — capture the refs BEFORE run(startup)
        # re-initializes the scope; scope.var().set replaces array objects,
        # so these refs stay intact.  If this member is the view's peer
        # source, serve the state on the ctrl server NOW so a rejoining
        # member can fetch it while we transpile/compile.
        live_state = self._capture_live_state(view) if old_epoch >= 0 else None
        if live_state is not None and view.peer_src == self.rank:
            self._publish_live_state(view, live_state)
        # everything below mutates the scope (run(startup) re-inits, warmup
        # may touch buffers) — if this adoption dies mid-way and another
        # re-quorum follows, a capture against the half-rebuilt scope would
        # serve init values as if they were step-N state.  Invalidate until
        # the adoption completes; live_state above is already detached.
        self._has_state = False
        if self.executor is not None:
            self.executor.reset_device_state()
        _JaxWorld.reinit(coord_host, view.jax_port, world, pid,
                         host_service=self.rank == view.coord_rank)
        phases = {"init": (time.perf_counter() - t0) * 1e3}

        standby = self._take_standby(view) if old_epoch >= 0 else None
        if standby is not None:
            # pre-transpiled + pre-verified in the background after the
            # last adoption: the transpile phase is already paid, and the
            # verify is too IF the IR fingerprint still matches what was
            # hashed right after the standby-build verify.  In error mode
            # a view tampered or staled between build and adoption fails
            # that check and goes through the full world-level re-verify,
            # which raises — it can never be adopted with a latent
            # deadlock.
            main, startup = standby["main"], standby["startup"]
            phases["transpile"] = 0.0
            tampered = (_flag("static_check") == "error"
                        and _world_fingerprint(main, startup)
                        != standby.get("verified_fp"))
            phases["verify"] = 0.0
            if tampered:
                tv = time.perf_counter()
                self._verify(main, startup, world, pid=pid)
                phases["verify"] = (time.perf_counter() - tv) * 1e3
        else:
            # re-transpile pristine programs for the new world + verify the
            # rewrite loudly BEFORE any recompile (DL001-006, error mode)
            endpoints = [self.members[r] for r in view.ranks]
            main = self.base_main.clone()
            startup = self.base_startup.clone()
            # FLAGS_collective_mode-aware: a zero1 job re-shards the
            # optimizer state for the new world here (the re-transpiled
            # shard assignment covers `world` ranks; shard-local slots
            # rematerialize from the full arrays the checkpoint restore
            # puts back into the scope)
            from ..transpiler.collective import select_grad_transpiler

            t1 = time.perf_counter()
            t = select_grad_transpiler(self.nrings)
            t.transpile(startup_program=startup, main_program=main,
                        rank=pid, endpoints=endpoints,
                        current_endpoint=self.members[self.rank],
                        wait_port=False)
            t2 = time.perf_counter()
            self._verify(main, startup, world, pid=pid)
            phases["transpile"] = (t2 - t1) * 1e3
            phases["verify"] = (time.perf_counter() - t2) * 1e3
        # the pool only held subsets of the OLD view; rebuild below
        with self._standby_lock:
            self._standby.clear()
        self.main_program = main
        self.startup_program = startup

        self.restore_step = 0
        self.last_restore_source = None
        phases["compile"] = phases["restore"] = 0.0
        if self.executor is not None:
            tc = time.perf_counter()
            self.executor.run(startup)
            if self.feed_specs is not None and self.fetch_names:
                # pre-compile the training step now so the compile cost is
                # attributed to this phase, not smeared into the first
                # post-restore step; with a pre-compiled standby this is a
                # tier-B disk restore, not an XLA compile
                specs = (self.feed_specs(world) if callable(self.feed_specs)
                         else self.feed_specs)
                try:
                    got = self.executor.warmup(
                        main, feed_specs=specs,
                        fetch_list=list(self.fetch_names))
                    _tm.event("elastic_warmup", rank=self.rank,
                              epoch=view.epoch, source=got["source"],
                              ms=round(got["compile_ms"], 3))
                except Exception as e:
                    logging.warning("[elastic] post-adopt warmup failed: "
                                    "%s", e)
            phases["compile"] = (time.perf_counter() - tc) * 1e3
            tr = time.perf_counter()
            src = None
            if view.peer_step > 0 and live_state is not None \
                    and self._state_step == view.peer_step:
                # survivor: its own pre-requorum state IS the adopted state
                self._set_state(main, live_state)
                self.restore_step = int(view.peer_step)
                src = "peer"
            elif view.peer_step > 0 and 0 <= view.peer_src < len(self.members) \
                    and view.peer_src != self.rank:
                # rejoiner (or a lagging survivor): fetch from the peer
                # source over the native-RPC fabric instead of the fs
                try:
                    self._peer_fetch(view, main)
                    self.restore_step = int(view.peer_step)
                    src = "peer"
                except Exception as e:
                    logging.warning(
                        "[elastic] rank %d: peer restore from rank %d "
                        "failed (%s) — falling back to filesystem",
                        self.rank, view.peer_src, e)
            if src is None and self.ckpt is not None:
                try:
                    self.ckpt.wait()  # drain an in-flight async write
                except Exception as e:
                    logging.warning("[elastic] pending checkpoint write "
                                    "failed: %s", e)
                step, _extra = self.ckpt.restore(self.executor, main)
                self.restore_step = int(step)
                src = "fs"
            if src is not None:
                _tm.inc("checkpoint_restore_source_total", source=src)
                _tm.event("elastic_restore", rank=self.rank,
                          epoch=view.epoch, step=self.restore_step,
                          source=src)
            self.last_restore_source = src
            phases["restore"] = (time.perf_counter() - tr) * 1e3
        ms = (time.perf_counter() - t0) * 1e3
        _tm.observe("elastic_requorum_ms", ms, role="member")
        for ph in ("transpile", "verify", "compile", "restore"):
            _tm.observe("elastic_requorum_phase_ms", phases[ph], phase=ph)
        if _tr.enabled():
            # the phases were measured as perf_counter deltas; lay them
            # out retroactively as one span tree per adoption epoch, the
            # phase children sequential from the adoption's wall start
            wall0 = time.time() - ms / 1e3
            root = _tr.record_span(
                "elastic.requorum", wall0, ms, epoch=view.epoch,
                world=world, rank=self.rank, standby=standby is not None)
            cursor = wall0
            for ph in ("init", "transpile", "verify", "compile",
                       "restore"):
                attrs, links = {}, None
                if ph == "restore" and self.last_restore_source:
                    # flow from the checkpoint span tree into the phase:
                    # the fs path links the checkpoint.restore span that
                    # served it (trace_view renders the arrow)
                    attrs["source"] = self.last_restore_source
                    if (self.last_restore_source == "fs"
                            and self.ckpt is not None):
                        links = [getattr(self.ckpt, "last_restore_span",
                                         None)]
                _tr.record_span("elastic." + ph, cursor, phases[ph],
                                parent=root, links=links, **attrs)
                cursor += phases[ph] / 1e3
        _tm.set_gauge("elastic_epoch", view.epoch)
        if old_epoch >= 0:
            _tm.event("elastic_adopt", rank=self.rank, epoch=view.epoch,
                      world=world, ms=round(ms, 3),
                      standby=standby is not None,
                      phases={k: round(v, 3) for k, v in phases.items()})
        self.last_adopt_phases = dict(phases)
        self.last_adopt_standby = standby is not None
        # adopted state covers restore_step completed steps; gate() keeps
        # _state_step current from here on
        self._state_step = int(self.restore_step)
        self._has_state = self.executor is not None
        logging.info(
            "[elastic] rank %d adopted %r (pid %d/%d) in %.0fms "
            "(standby=%s transpile=%.0f verify=%.0f compile=%.0f "
            "restore=%.0f)", self.rank, view, pid, world, ms,
            standby is not None, phases["transpile"], phases["verify"],
            phases["compile"], phases["restore"])
        self._spawn_standby()

    # -- peer-to-peer state movement ----------------------------------------

    def _live_scope(self):
        if self.scope is not None:
            return self.scope
        from ..core.executor import global_scope

        return global_scope()

    def _persistable_names(self, program):
        return {v.name for v in program.list_vars()
                if v.persistable and not v.is_data}

    def _capture_live_state(self, view):
        """{name: host ndarray} of the persistable scope state, or None when
        this member's progress doesn't match the view's peer offer (it
        crashed behind, or the offer is empty).  Called right after
        _numpyify_scope, so every ref is already a plain numpy array."""
        if (view.peer_step <= 0 or self.executor is None
                or self.main_program is None
                or not self._has_state
                or self._state_step != view.peer_step):
            return None
        scope = self._live_scope()
        out = {}
        for name in self._persistable_names(self.main_program):
            var = scope.find_var(name)
            t = var.get_tensor() if var else None
            if t is None:
                continue
            # ALL-OR-NOTHING: a var whose backend buffer was donated away
            # (deleted jax.Array) or never materialized would silently keep
            # its startup-init value after _set_state — a partial capture
            # restored as if complete diverges the rank bitwise.  Fail the
            # whole capture instead; the adoption falls back to peer-fetch
            # or the filesystem checkpoint, both of which are complete.
            try:
                if not t._is_initialized():
                    raise RuntimeError("uninitialized")
                out[name] = np.array(t.get(), copy=True)
            except Exception as e:
                logging.warning(
                    "[elastic] rank %d: live-state capture failed on %r "
                    "(%s); falling back to peer/fs restore", self.rank,
                    name, e)
                return None
        return out or None

    def _set_state(self, program, state):
        scope = self._live_scope()
        names = self._persistable_names(program)
        for name, arr in state.items():
            if name in names:
                scope.var(name).set(arr)

    def _publish_live_state(self, view, state):
        """Serve this member's live state on its ctrl server for rejoiners:
        one meta var (json describing step/names/shapes/dtypes — the wire
        flattens arrays) plus one var per tensor.  Previous epochs' payload
        is dropped first so state from at most one epoch is ever held."""
        for key in self._published_state:
            try:
                self._server.del_var(key)
            except Exception:
                pass
        self._published_state = []
        meta = {"step": int(view.peer_step),
                "names": sorted(state),
                "shapes": {n: list(np.shape(a)) for n, a in state.items()},
                "dtypes": {n: str(np.asarray(a).dtype)
                           for n, a in state.items()}}
        mkey = "%s#%d" % (_STATE, view.epoch)
        self._server.set_var(mkey, np.frombuffer(
            json.dumps(meta).encode(), np.uint8).copy())
        self._published_state.append(mkey)
        for name, arr in state.items():
            key = "%s#%d#%s" % (_STATE, view.epoch, name)
            self._server.set_var(key, np.asarray(arr))
            self._published_state.append(key)
        _tm.event("elastic_state_published", rank=self.rank,
                  epoch=view.epoch, step=view.peer_step, vars=len(state))

    def _peer_fetch(self, view, program):
        """Pull the peer source's live state over the native-RPC fabric and
        set it into the scope (blocking gets: the publisher serves the
        payload before its own slow adoption phases)."""
        ep = _ctrl_endpoint(self.members[view.peer_src])
        c = RpcClient(ep, connect_timeout=60.0, rpc_deadline=60.0,
                      retry_times=1)
        try:
            raw = np.asarray(c.get_var("%s#%d" % (_STATE, view.epoch)))
            meta = json.loads(raw.astype(np.uint8).tobytes().decode())
            if int(meta["step"]) != int(view.peer_step):
                raise RuntimeError("peer state step %s != offered %d"
                                   % (meta["step"], view.peer_step))
            scope = self._live_scope()
            names = self._persistable_names(program)
            got = 0
            for name in meta["names"]:
                if name not in names:
                    continue
                arr = np.asarray(c.get_var(
                    "%s#%d#%s" % (_STATE, view.epoch, name)))
                arr = arr.reshape(meta["shapes"][name]).astype(
                    meta["dtypes"][name], copy=False)
                scope.var(name).set(arr)
                got += 1
            _tm.event("elastic_state_fetched", rank=self.rank,
                      epoch=view.epoch, src=view.peer_src, vars=got)
        finally:
            try:
                c.close()
            except Exception:
                pass

    def _verify(self, main, startup, world, pid=None):
        from ..core import analysis

        for prog, label in ((main, "main"), (startup, "startup")):
            rep = analysis.verify_program(
                prog, feed_names=self.feed_names if prog is main else (),
                fetch_names=self.fetch_names if prog is main else (),
                label="elastic epoch %d %s" % (self.view.epoch, label),
                expected_nranks=world)
            if rep.errors:
                raise analysis.ProgramVerificationError(rep)
        # whole-world pass: materialize the sibling ranks from the
        # pristine base programs and match THIS view's collective schedule
        # against them in lockstep (DL101-104 + the MEM estimator) — a
        # standby or re-transpiled view carrying a latent cross-rank
        # deadlock can never be adopted
        if pid is not None and int(world) > 1:
            from ..core import world_analysis

            rep = world_analysis.verify_world(
                self.base_main, self.base_startup, world,
                nrings=self.nrings,
                actual={int(pid): (main, startup)},
                feed_names=list(self.feed_names or ()) or None,
                fetch_names=list(self.fetch_names or ()),
                label="elastic epoch %d world of %d"
                      % (self.view.epoch, int(world)))
            if rep.errors:
                raise analysis.ProgramVerificationError(rep)

    # -- standby views -------------------------------------------------------
    #
    # After every adoption a background thread prepares the worlds this
    # member is most likely to shrink into — every single-member loss
    # (world N-1) and the loss of the two highest-ranked peers (world N-2)
    # — by cloning + re-transpiling + verifying the pristine programs NOW,
    # and (when feed_specs is known) pre-compiling the step executable over
    # a device-prefix mesh into the tier-B disk cache.  A later re-quorum
    # that lands on a prepared rank set skips transpile + verify outright
    # and restores the executable from disk instead of recompiling.

    def _standby_flags_sig(self):
        from .. import flags as _flags

        return tuple(sorted(_flags.get_flags(
            ["FLAGS_collective_mode", "FLAGS_allreduce_dtype",
             "FLAGS_allreduce_quant_bucket"]).items()))

    def _standby_candidates(self):
        """Rank subsets (each containing this member) for worlds N-1/N-2,
        by FLAGS_elastic_standby depth."""
        if self.view is None:
            return []
        depth = int(_flag("elastic_standby") or 0)
        ranks = set(self.view.ranks)
        others = sorted(r for r in ranks if r != self.rank)
        cands = []
        if depth >= 1 and len(ranks) >= 2:
            for r in others:
                cands.append(tuple(sorted(ranks - {r})))
        if depth >= 2 and len(ranks) >= 3:
            cands.append(tuple(sorted(ranks - set(others[-2:]))))
        return cands

    def _build_standby(self, ranks):
        """Transpile + verify (error mode) one candidate world; with
        feed_specs, also pre-compile its step into the tier-B cache over
        jax.devices()[:world] (device ids are not part of the tier-B key,
        so the artifact is loadable by the re-initialized backend)."""
        ranks = tuple(sorted(int(r) for r in ranks))
        if self.rank not in ranks:
            raise ValueError("standby ranks %s exclude self (%d)"
                             % (list(ranks), self.rank))
        pid = ranks.index(self.rank)
        world = len(ranks)
        endpoints = [self.members[r] for r in ranks]
        from ..transpiler.collective import select_grad_transpiler

        main = self.base_main.clone()
        startup = self.base_startup.clone()
        t = select_grad_transpiler(self.nrings)
        t.transpile(startup_program=startup, main_program=main, rank=pid,
                    endpoints=endpoints,
                    current_endpoint=self.members[self.rank],
                    wait_port=False)
        self._verify(main, startup, world, pid=pid)
        rec = {"ranks": ranks, "main": main, "startup": startup,
               "flags_sig": self._standby_flags_sig(),
               "base_versions": (self.base_main.version,
                                 self.base_startup.version),
               "compiled": False}
        if self.executor is not None and self.feed_specs is not None \
                and self.fetch_names:
            import jax

            specs = (self.feed_specs(world) if callable(self.feed_specs)
                     else self.feed_specs)
            # jax.devices() is the GLOBAL list: its first `world` entries
            # need not include any device this process can address, and
            # materializing params onto a mesh with zero addressable
            # shards raises a bare StopIteration from deep inside jax.
            # Put our own device at this rank's standby position and fill
            # the rest from the remaining global pool — the tier-B key
            # carries no device ids, so the artifact stays loadable by the
            # re-initialized post-requorum backend either way.
            local = jax.local_devices()[0]
            pool = [d for d in jax.devices() if d != local]
            devs = [local if i == pid else pool.pop(0)
                    for i in range(world)]
            try:
                # the startup program bakes the world size into its
                # c_comm_init nranks attr, so the shrunk world's startup is
                # a distinct executable — pre-compile it too or the
                # re-quorum's executor.run(startup) pays a fresh XLA compile
                self.executor.warmup(startup, feed_specs={}, fetch_list=[],
                                     devices=devs)
            except Exception as e:
                logging.warning("[elastic] standby startup pre-compile for "
                                "world %s failed: %r", list(ranks), e)
            for attempt in (0, 1):
                try:
                    got = self.executor.warmup(
                        main, feed_specs=specs,
                        fetch_list=list(self.fetch_names), devices=devs)
                    rec["compiled"] = got["source"] in ("compiled", "disk")
                    break
                except Exception as e:
                    # racing the training loop: a donated param can vanish
                    # mid-gather — retry once, then settle for
                    # transpile+verify-only standby
                    if attempt:
                        logging.warning("[elastic] standby pre-compile for "
                                        "world %s failed: %r", list(ranks), e)
                        _tm.inc("elastic_standby_errors_total")
        # hash AFTER the warmup pre-compile: the executor may fuse
        # optimizer ops in place there, and the adoption-time check must
        # see the IR exactly as it will be handed over
        rec["verified_fp"] = _world_fingerprint(main, startup)
        with self._standby_lock:
            self._standby[frozenset(ranks)] = rec
        _tm.inc("elastic_standby_built_total")
        _tm.event("elastic_standby", rank=self.rank, world=world,
                  ranks=list(ranks), compiled=rec["compiled"])
        return rec

    def _take_standby(self, view):
        """Fresh standby programs for exactly `view.ranks`, or None.
        Freshness: built from the current base program versions under the
        current transpile-affecting flags."""
        with self._standby_lock:
            rec = self._standby.get(frozenset(view.ranks))
        if rec is None:
            _tm.inc("elastic_standby_miss_total")
            return None
        if (rec["flags_sig"] != self._standby_flags_sig()
                or rec["base_versions"] != (self.base_main.version,
                                            self.base_startup.version)):
            _tm.inc("elastic_standby_stale_total")
            return None
        _tm.inc("elastic_standby_hits_total")
        return rec

    def prepare_standby_views(self, ranks_list=None):
        """Synchronously build standby views (tests / explicit prewarm).
        Defaults to the automatic N-1/N-2 candidate set."""
        built = []
        for ranks in (ranks_list if ranks_list is not None
                      else self._standby_candidates()):
            built.append(self._build_standby(ranks))
        return built

    def _spawn_standby(self):
        if int(_flag("elastic_standby") or 0) <= 0:
            return
        cands = self._standby_candidates()
        if not cands:
            return

        def work():
            for ranks in cands:
                if self._stop_hb.is_set():
                    return
                try:
                    self._build_standby(ranks)
                except Exception as e:
                    logging.warning("[elastic] standby build %s failed: %s",
                                    list(ranks), e)
                    _tm.inc("elastic_standby_errors_total")

        th = threading.Thread(target=work, name="elastic-standby",
                              daemon=True)
        self._standby_thread = th
        th.start()

    def wait_standby(self, timeout=60.0):
        """Block until the background standby builder finishes; -> dict of
        prepared rank tuples -> pre-compiled?  (tests use this to make the
        standby-hit deterministic)."""
        th = self._standby_thread
        if th is not None:
            th.join(timeout)
        with self._standby_lock:
            return {tuple(sorted(k)): v["compiled"]
                    for k, v in self._standby.items()}

    # -- step gate -----------------------------------------------------------

    def gate(self, step):
        """Barrier before `step`.  True -> proceed; False -> the quorum
        re-formed: programs/restore_step were replaced, restart the loop
        from self.restore_step."""
        epoch = self.epoch
        # at the gate for `step`, exactly `step` steps are complete — this
        # is the state a re-quorum's peer-restore offer would broadcast
        self._state_step = int(step)
        try:
            self._ctrl.send_var(_READY + str(self.rank),
                                np.asarray([epoch, step], np.int64))
            verdict = int(self._gate_c.get_var(
                "%s#%d:%d" % (_GATE, epoch, step))[0])
        except Exception:
            self._failover()
            return False
        if verdict == _GO:
            return True
        # re-quorum: the next view may take a moment to publish
        view = View.decode(self._gate_c.get_var("%s#%d" % (_VIEW, epoch + 1)))
        if self.rank not in view.ranks:
            view = self._wait_view_with_me()
        self._adopt(view)
        return False

    def _failover(self):
        """The coordinator stopped answering.  The lowest live rank becomes
        the new coordinator; everyone else rejoins it."""
        logging.warning("[elastic] rank %d: coordinator unreachable — "
                        "failing over", self.rank)
        timeout = float(_flag("elastic_hb_timeout") or 5.0)
        lower_alive = None
        for r in range(self.rank):
            if r >= len(self.members):
                break
            got = self._probe_alive(self.members[r])
            if got is not None:
                lower_alive = r
                break
        if lower_alive is None and self._coord is None:
            self._become_coordinator(epoch=self.epoch + 1,
                                     ranks=[self.rank],
                                     join_window_s=2.0 * timeout)
            self._connect_ctrl(self.rank)
        else:
            coord = (lower_alive if lower_alive is not None
                     else self._find_coordinator())
            self._connect_ctrl(coord)
        view = self._wait_view_with_me()
        self._adopt(view)

    # -- checkpoints ---------------------------------------------------------

    def maybe_save(self, step):
        """Checkpoint from the view's first member only (shared ckpt_dir);
        all members restore the same latest_valid() at re-quorum.  Under a
        sharded zero1 checkpoint every member writes — each rank stages its
        own shard and pid 0 seals the directory (io._write_sharded)."""
        if self.ckpt is None or self.executor is None:
            return None
        sharded = getattr(self.ckpt, "_shard_plan",
                          lambda p: None)(self.main_program)
        if self.pid != 0 and sharded is None:
            return None
        return self.ckpt.maybe_save(self.executor, self.main_program, step)

    # -- teardown ------------------------------------------------------------

    def finalize(self, exit_code=0):
        """Clean completion.  Members leave first (hard-exit right after
        their DONE, so no destructor ever touches a parked client); the
        coordinator waits for every DONE plus a grace period — its process
        holds every epoch's coordination service, so it must be the last
        one whose sockets close (see module docstring).  Does not return
        unless exit_code is None."""
        if self._finalized:
            return
        self._finalized = True
        self._stop_hb.set()
        try:
            self._ctrl.send_var(_DONE + str(self.rank),
                                np.asarray([self.rank], np.int64))
        except Exception:
            pass
        if self._coord is not None:
            self._coord.all_done.wait(timeout=60.0)
            self._coord.stop()
        _tm.event("elastic_finalize", rank=self.rank, epoch=self.epoch)
        _tm.maybe_dump()
        if exit_code is None:
            return
        import sys

        sys.stdout.flush()
        sys.stderr.flush()
        if self._coord is not None or _JaxWorld.hosted_service:
            # members os._exit milliseconds after their DONE lands; ride
            # out interpreter-teardown stragglers before our service
            # sockets vanish under their parked pollers
            time.sleep(2.0)
        # skip interpreter teardown entirely: C++ destructor order would
        # close coordination-service/client sockets under live poll
        # threads -> LOG(FATAL) (client.h:80)
        os._exit(exit_code)
