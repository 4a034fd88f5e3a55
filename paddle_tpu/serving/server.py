"""RPC serving frontend: wire protocol over native/rpc.py.

One ``RpcServer`` per replica carries the whole protocol:

  ``__infer__:<req_id>``  inbound SEND: packed request (serving/codec.py);
                          the reply is published as ``__reply__:<req_id>``
                          and the client's blocking GET picks it up (the
                          transport parks GETs until the var exists)
  ``__alive__``           [rank, epoch, is_coordinator] — same probe
                          contract as the elastic control plane
  ``__metrics__``         telemetry snapshot, republished every second
                          (core/telemetry.start_publisher) for
                          tools/metrics_dump.py --scrape
  ``__spec__:<model>``    feed/fetch signature + buckets, so loadgen can
                          synthesize valid requests without the model dir
  ``__fhb__<rank>``       fleet replica heartbeats (serving/fleet.py)
  ``__generate__:<id>``   inbound SEND: autoregressive request for the
                          paged-KV decode engine; generated tokens stream
                          as ``__stream__:<id>:<k>`` chunks and the final
                          reply still lands on ``__reply__:<id>``
  ``__abort__:<id>``      inbound SEND: drop the sequence, free its KV
                          blocks (client timeout-replay abandonment)
  ``__rollout__``         this replica's applied version-routing state
                          (always published, empty when no rollout —
                          chaos tests GET it from every survivor)
  ``__rollout_set__``     coordinator broadcast: adopt a routing state
  ``__rollout_ctl__:<id>`` admin command for the RolloutController; the
                          reply lands on ``__reply__:<id>``
  ``__retire__``          coordinator order: drain both engines at a
                          batch boundary, then hand off to ``on_retire``
                          (tools/serve.py exits the process); with
                          FLAGS_migrate_on_drain the decode drain pushes
                          live sessions to peers instead of waiting them
                          out (serving/migrate.py)
  ``__resume__:<id>``     inbound SEND: client crash-resume — prompt +
                          already-received tokens; the replica resumes
                          decode at position p, re-prefilling only what
                          its prefix/history index does not hold, and
                          acks under ``__resumeack__:<id>``

Replies are garbage-collected FIFO beyond a bounded ring — a crashed
client can never grow the server's var store unboundedly.

Chaos hooks: the named fault points ``serving.infer`` /
``serving.generate`` / ``serving.reply`` (utils/fault_injection.py,
armed by FLAGS_fault_spec) sit on the wire path — ``drop`` loses the
frame, ``error`` substitutes an error reply — so serving tests inject
faults without SIGKILLing processes.

Disaggregated roles (``role=`` / serving/disagg.py): a ``prefill``-role
replica answers ``__generate__`` by picking a decode peer, publishing
``__pair__:<id>``, and running handoff prefill — sealed blocks stream to
the peer as ``__kvxfer__`` frames and a commit frame delegates
generation; a ``decode``-role replica adopts inbound blocks into its
pool and serves the stream/reply for committed requests.  Either role
still serves plain monolith traffic (the pair var's ``{"decode": None}``
is the no-peers fallback).
"""

import collections
import threading
import time

import numpy as np

from ..core import telemetry as _tm
from ..core import tracing as _tr
from ..native.rpc import EV_SEND, RpcServer
from ..utils.fault_injection import maybe_fail
from . import codec

__all__ = ["ServingServer"]

_REPLY_RING = 1024
# steps whose stream timings enter their histograms together: a call costs
# the decode loop 10-20 us whatever it carries, a value 0.1 us
_OBSERVE_EVERY = 16


class ServingServer:
    def __init__(self, engine, port=0, rank=0, decode_engine=None,
                 role=None, decode_peers=None):
        self.engine = engine
        self.decode_engine = decode_engine
        self.rank = int(rank)
        self.role = role or "serve"
        if self.role not in ("serve", "prefill", "decode"):
            raise ValueError("serving role must be serve|prefill|decode, "
                             "got %r" % (role,))
        self.rpc = RpcServer(port=port)
        self.port = self.rpc.port
        self.fleet = None
        self.rollout = None            # RolloutController (coordinator)
        self.on_retire = None          # callback after a __retire__ drain
        self._retire_thread = None
        # per-request keys in the store, oldest first (the GC ring), and
        # the stream chunks packed since the last store transaction
        self._reply_keys = collections.deque()
        self._chunks = []
        self._reply_lock = threading.Lock()
        # whether the RPC store times its streamed replies: it follows
        # FLAGS_tracing, looked at once a step by the decode loop
        self._gets_timed = False
        # histogram -> a step's values (us) not yet observed, and the steps
        self._stream_seen = {}
        self._stream_steps = 0
        self._thread = None
        self._pub_stop = None
        self._stopped = threading.Event()
        # disaggregation state: the prefill side's sealed-block sender +
        # req -> pair registry; the decode side's adoption tracker
        self._decode_peers_static = list(decode_peers or [])
        self._xfer = None              # KVBlockSender (prefill role)
        self._adopt = None             # AdoptTracker (decode role)
        self._pairs = {}               # req_id -> request meta (prefill)
        self._pair_lock = threading.Lock()
        self._pair_rr = 0
        # live session migration (serving/migrate.py): source-side
        # pusher + destination-side tail/digest holding buffer
        self.migrator = None           # SessionMigrator
        self._resume_buf = None        # ResumeBuffer
        self.fleetmon = None           # FleetMonitor (tools/serve.py)

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        self.engine.start()
        self.rpc.set_var(codec.ALIVE_KEY,
                         np.asarray([self.rank, 0, 0], np.int64))
        # always published (empty before any rollout) so a chaos test's
        # GET never parks forever on a replica that missed every flip
        self.rpc.set_var(codec.ROLLOUT_KEY, codec.pack({"models": {}}))
        for name in self.engine.models():
            self.rpc.set_var(codec.SPEC_KEY + name,
                             codec.pack(self.engine.spec(name)))
        if self.decode_engine is not None:
            self.decode_engine.on_tokens_emitted = self._store
            self.decode_engine.start()
            for name in self.decode_engine.models():
                self.rpc.set_var(codec.SPEC_KEY + name,
                                 codec.pack(self.decode_engine.spec(name)))
        if self.decode_engine is not None and self.role == "prefill":
            from .disagg import KVBlockSender

            self._xfer = KVBlockSender()
            self.decode_engine.on_block_sealed = self._on_block_sealed
            self.decode_engine.on_handoff = self._on_handoff
        if self.decode_engine is not None and self.role == "decode":
            from .disagg import AdoptTracker

            self._adopt = AdoptTracker(self._on_orphan)
        if self.decode_engine is not None:
            from .. import flags

            if flags.flag("session_migration"):
                from .migrate import ResumeBuffer, SessionMigrator

                self._resume_buf = ResumeBuffer()
                self.migrator = SessionMigrator(
                    self.decode_engine, peers_fn=self._migration_peers,
                    occupancy_fn=self._peer_occupancy)
                if flags.flag("migrate_on_pressure"):
                    self.decode_engine.on_preempt = self._on_preempt
        self.rpc.serve(True)
        if _tm.enabled():
            self._pub_stop = _tm.start_publisher(
                self.rpc, interval_s=1.0, on_publish=self._pre_publish)
        self._thread = threading.Thread(target=self._poll_loop,
                                        name="serving-rpc", daemon=True)
        self._thread.start()
        return self

    def _pre_publish(self):
        """Derived per-window gauges, recomputed on every 1s republish
        (runs inside the publisher tick, after series_record):
        per-namespace prefix hit rate from the namespace-labeled token
        counters — the windowed signal the prefix-aware router biases
        on."""
        from .. import flags

        window = float(flags.flag("serving_rate_window"))
        for flat, labels in _tm.label_sets(
                "prefix_cache_ns_lookup_tokens_total"):
            ns = labels.get("namespace", "default")
            lookups = _tm.series_rate(flat, window)
            hits = _tm.series_rate(
                "prefix_cache_ns_hit_tokens_total{namespace=%s}" % ns,
                window)
            _tm.set_gauge("prefix_cache_ns_hit_rate",
                          hits / lookups if lookups > 0 else 0.0,
                          namespace=ns)

    def attach_fleet(self, fleet):
        """Wire a serving fleet: its heartbeats arrive on this server's
        event stream, and membership changes publish at batch boundaries
        via the engine hook."""
        self.fleet = fleet
        self.engine.on_batch_boundary = fleet.tick
        if self.decode_engine is not None:
            self.decode_engine.on_batch_boundary = fleet.tick

    def _poll_loop(self):
        while True:
            try:
                t, name, arr = self.rpc.poll()
            except ConnectionError:
                return             # transport torn down under the loop
            if t == 0:
                return
            if t != EV_SEND or name is None:
                continue
            if self._stopped.is_set():
                return             # late frame raced shutdown(): drop it
            if name.startswith(codec.INFER_KEY):
                self._on_infer(name[len(codec.INFER_KEY):], arr)
            elif name.startswith(codec.GEN_KEY):
                self._on_generate(name[len(codec.GEN_KEY):], arr)
            elif name.startswith(codec.ABORT_KEY):
                rid = name[len(codec.ABORT_KEY):]
                if self.decode_engine is not None:
                    self.decode_engine.abort(rid)
                self._reconcile_abort(rid)
            elif name.startswith(codec.KVXFER_KEY):
                self._on_kvxfer(name[len(codec.KVXFER_KEY):], arr)
            elif name.startswith(codec.RESUME_KEY):
                self._on_resume(name[len(codec.RESUME_KEY):], arr)
            elif name == codec.ROLLOUT_SET_KEY:
                self._on_rollout_set(arr)
            elif name.startswith(codec.ROLLOUT_CTL_KEY):
                self._on_rollout_ctl(
                    name[len(codec.ROLLOUT_CTL_KEY):], arr)
            elif name == codec.RETIRE_KEY:
                self._on_retire()
            elif self.fleet is not None:
                self.fleet.on_event(name, arr)
            if self.fleet is not None:
                self.fleet.tick()

    def _on_infer(self, req_id, arr):
        from .engine import InferReply

        fault = maybe_fail("serving.infer")
        if fault == "drop":
            return                     # frame lost: client replays
        if fault == "error":
            self._publish(req_id, InferReply(
                "error", error="injected fault: serving.infer"))
            return
        try:
            meta, arrays = codec.unpack(arr)
            feeds = dict(zip(meta["feeds"], arrays))
        except Exception as e:
            self._publish(req_id, None)
            _tm.inc("serving_bad_request_total")
            del e
            return
        tp = meta.get(codec.TRACEPARENT)
        # the admission span parents under the client's root span (wire
        # context), and engine.submit opens the request span inside it
        with _tr.remote_parent(tp):
            with _tr.span("serving.admission", req_id=req_id,
                          model=meta.get("model", ""), rank=self.rank):
                self.engine.submit(
                    meta.get("model", ""), feeds,
                    tenant=meta.get("tenant", "default"),
                    deadline_ms=meta.get("deadline_ms"),
                    req_id=req_id,
                    traceparent=tp,
                    tier=meta.get(codec.TIER),
                    callback=lambda pending: self._publish(
                        pending.req_id, pending.reply, pending))

    def _on_generate(self, req_id, arr):
        from .engine import InferReply

        fault = maybe_fail("serving.generate")
        if fault == "drop":
            return
        if fault == "error":
            self._publish(req_id, InferReply(
                "error", error="injected fault: serving.generate"))
            return
        try:
            meta, arrays = codec.unpack(arr)
            prompt = arrays[0]
        except Exception:
            self._publish(req_id, None)
            _tm.inc("serving_bad_request_total")
            return
        if self.decode_engine is None:
            self._publish(req_id, InferReply(
                "error", error="replica has no decode engine"))
            return
        if self.role == "prefill" and self._try_handoff(req_id, meta,
                                                        prompt):
            return
        stream = bool(meta.get("stream"))
        on_token = self._stream_publisher(req_id) if stream else None
        tp = meta.get(codec.TRACEPARENT)
        with _tr.remote_parent(tp):
            with _tr.span("serving.admission", req_id=req_id, decode=True,
                          model=meta.get("model", ""), rank=self.rank):
                self.decode_engine.submit(
                    meta.get("model", ""), prompt,
                    max_new_tokens=int(meta.get("max_new_tokens", 16)),
                    tenant=meta.get("tenant", "default"),
                    deadline_ms=meta.get("deadline_ms"),
                    eos_id=int(meta.get("eos_id", -1)),
                    req_id=req_id,
                    traceparent=tp,
                    tier=meta.get(codec.TIER),
                    on_token=on_token,
                    callback=lambda pending: self._publish(
                        pending.req_id, pending.reply, pending))

# -- disaggregated prefill/decode --------------------------------------------

    def _advertised_ep(self):
        """This replica's endpoint as decode peers should probe it."""
        if self.fleet is not None and self.rank < len(self.fleet.endpoints):
            return self.fleet.endpoints[self.rank]
        return "127.0.0.1:%d" % self.port

    def _pick_decode_peer(self):
        """Round-robin over live decode-role endpoints (fleet view when
        attached, else the static ``decode_peers`` list)."""
        peers = []
        if self.fleet is not None:
            peers = self.fleet.live_role_endpoints("decode")
        if not peers:
            peers = list(self._decode_peers_static)
        if not peers:
            return None
        self._pair_rr += 1
        return peers[self._pair_rr % len(peers)]

    def _migration_peers(self):
        """Candidate endpoints for a session push: every live replica
        that runs a decode engine (decode + monolith roles; prefill-only
        replicas can't resume), minus this one.  Falls back to the
        static decode_peers list when no fleet is attached."""
        me = self._advertised_ep()
        peers = []
        if self.fleet is not None:
            for role in ("decode", "serve"):
                peers.extend(self.fleet.live_role_endpoints(role))
        if not peers:
            peers = list(self._decode_peers_static)
        return [p for p in dict.fromkeys(peers) if p != me]

    def _peer_occupancy(self):
        """endpoint -> windowed KV occupancy from the last fleet doc
        (fleetmon rows), so the migrator prefers the least-loaded
        survivor.  Empty when no monitor is attached."""
        mon = self.fleetmon
        doc = getattr(mon, "last", None) if mon is not None else None
        if not doc:
            return {}
        return {r["endpoint"]: float(r.get("kv_occupancy", 0.0))
                for r in doc.get("replicas", []) if r.get("up")}

    def _wire_dtype(self, model):
        m = self.decode_engine._models.get(model)
        return m.kv_config.dtype if m is not None else "f32"

    def _publish_pair(self, req_id, peer):
        self._store([(codec.PAIR_KEY + req_id,
                      codec.pack({"decode": peer}))])

    def _try_handoff(self, req_id, meta, prompt):
        """Prefill-role admission: pick a decode peer, announce the pair,
        and either run handoff prefill (blocks stream as they seal) or —
        for prompts with no transferable full block — forward the commit
        frame immediately (pure proxy).  Returns False to fall back to
        serving the request locally (no live peer / peer unreachable);
        the published ``{"decode": None}`` pair tells the client so."""
        model = meta.get("model", "")
        peer = self._pick_decode_peer()
        if peer is not None and self._xfer is not None:
            self._xfer.register(req_id, peer, model,
                                self._wire_dtype(model))
            # the expect frame goes out synchronously BEFORE the pair is
            # visible: once a client can learn the pair, the decode half
            # already knows the request (arms its orphan janitor)
            if not self._xfer.send_expect_now(req_id, {
                    "model": model,
                    "prefill_ep": self._advertised_ep(),
                    "deadline_ms": meta.get("deadline_ms")}):
                self._xfer.forget(req_id)
                peer = None
        else:
            peer = None
        self._publish_pair(req_id, peer)
        if peer is None:
            _tm.inc("serving_handoff_fallback_total")
            return False
        prompt_list = [int(t) for t in np.asarray(prompt).reshape(-1)]
        entry = {"decode": peer, "meta": dict(meta),
                 "prompt": prompt_list,
                 "t_arrive": time.perf_counter()}
        with self._pair_lock:
            self._pairs[req_id] = entry
            while len(self._pairs) > _REPLY_RING:
                self._pairs.pop(next(iter(self._pairs)))
        upto = self.decode_engine.handoff_prefill_upto(model,
                                                       len(prompt_list))
        if upto <= 0:
            # nothing transferable below the tail: the commit frame
            # carries the whole prompt and the decode half does all work
            self._xfer.enqueue_commit(req_id, self._commit_meta(
                entry, digests=[],
                phases={"prefill_queue_wait_ms": 0.0, "prefill_ms": 0.0}))
            return True
        tp = meta.get(codec.TRACEPARENT)
        with _tr.remote_parent(tp):
            with _tr.span("serving.admission", req_id=req_id, decode=True,
                          handoff=True, model=model, rank=self.rank):
                self.decode_engine.submit(
                    model, prompt_list,
                    max_new_tokens=int(meta.get("max_new_tokens", 16)),
                    tenant=meta.get("tenant", "default"),
                    deadline_ms=meta.get("deadline_ms"),
                    eos_id=int(meta.get("eos_id", -1)),
                    req_id=req_id, traceparent=tp,
                    tier=meta.get(codec.TIER),
                    handoff=True, callback=self._handoff_done)
        return True

    def _commit_meta(self, entry, digests, phases):
        meta = entry["meta"]
        dl = meta.get("deadline_ms")
        remaining = None
        if dl:
            used = (time.perf_counter() - entry["t_arrive"]) * 1e3
            remaining = max(1.0, float(dl) - used)
        return {"model": meta.get("model", ""), "prompt": entry["prompt"],
                "max_new": int(meta.get("max_new_tokens", 16)),
                "eos_id": int(meta.get("eos_id", -1)),
                "stream": bool(meta.get("stream")),
                "tenant": meta.get("tenant", "default"),
                "tier": meta.get(codec.TIER),
                "deadline_ms": remaining,
                codec.TRACEPARENT: meta.get(codec.TRACEPARENT),
                "digests": list(digests), "phases": dict(phases),
                "sent_unix": time.time(),
                "prefill_ep": self._advertised_ep()}

    def _on_block_sealed(self, m, s, j, digest):
        """Engine hook (step lock held): copy the sealed block's payload
        off the carry and queue the transfer frame."""
        if self._xfer is None:
            return
        if m.kv_config.dtype == "bf16":
            # the frame codec has no bfloat16 (engine.export_session)
            _tm.inc("kv_migrate_refused_total", reason="dtype")
            return
        try:
            arrays = m.cache.export_block(s.blocks[j])
        except Exception:
            _tm.inc("kv_xfer_send_errors_total")
            return
        self._xfer.enqueue_block(s.pending.req_id, j, digest, arrays)

    def _on_handoff(self, m, s):
        """Engine hook (step lock held): the feed pointer reached the
        boundary — queue the commit frame with prefill-side phases."""
        rid = s.pending.req_id
        with self._pair_lock:
            entry = self._pairs.get(rid)
        if entry is None or self._xfer is None:
            return
        now = time.perf_counter()
        t_admit = s.t_admit if s.t_admit is not None else now
        phases = {
            "prefill_queue_wait_ms": round(
                (t_admit - s.pending.t_submit) * 1e3, 3),
            "prefill_ms": round((now - t_admit) * 1e3, 3),
            "prefill_cached_tokens": s.cached_tokens}
        bs = m.kv_config.block_size
        digests = list(s.hashes[:s.prefill_upto // bs]) if s.hashes else []
        self._xfer.enqueue_commit(rid, self._commit_meta(entry, digests,
                                                         phases))

    def _handoff_done(self, pending):
        """Prefill-side completion callback: "handoff" means the commit
        frame already went out; any other terminal (shed / abort /
        timeout / error) relays a cancel so the decode half frees its
        adoptions and publishes the reply the client is parked on."""
        if pending.reply.status == "handoff":
            return
        self._relay_cancel(pending.req_id, pending.reply.to_meta())

    def _relay_cancel(self, rid, reply_meta):
        with self._pair_lock:
            entry = self._pairs.pop(rid, None)
        if entry is not None and self._xfer is not None:
            self._xfer.enqueue_cancel(rid, reply_meta)

    def _reconcile_abort(self, rid):
        """A client ``__abort__`` frees blocks on BOTH halves: the
        prefill side relays a cancel to its pair's decode half; the
        decode side forgets any uncommitted adoptions."""
        self._relay_cancel(rid, {"status": "aborted",
                                 "error": "aborted by client"})
        if self._adopt is not None:
            entry = self._adopt.cancel(rid)
            if entry is not None and entry["digests"] \
                    and self.decode_engine is not None:
                self.decode_engine.forget_adopted(entry["model"],
                                                  entry["digests"])

    def _tracker(self):
        if self._adopt is None:
            from .disagg import AdoptTracker

            self._adopt = AdoptTracker(self._on_orphan)
        return self._adopt

    def _on_kvxfer(self, req_id, arr):
        if self.decode_engine is None:
            return
        try:
            meta, arrays = codec.unpack_kvxfer(arr)
        except ValueError as e:
            _tm.inc("kv_xfer_rejected_total", reason="frame")
            _tr.note("kvxfer_reject", req_id=req_id, error=str(e)[:200])
            return
        kind = meta.get("kind")
        if kind == "session":
            self._on_session(req_id, meta, arrays)
            return
        if kind == "block" and meta.get("session"):
            self._on_session_block(req_id, meta, arrays)
            return
        tracker = self._tracker()
        if kind == "expect":
            tracker.expect(req_id, meta)
        elif kind == "block":
            err = tracker.on_block(req_id, meta)
            if err is not None:
                _tm.inc("kv_xfer_rejected_total", reason="position")
                _tr.note("kvxfer_reject", req_id=req_id, error=err)
                return
            self.decode_engine.adopt_kv_block(
                meta.get("model", ""), meta["digest"], arrays)
        elif kind == "commit":
            self._on_commit(req_id, meta)
        elif kind == "cancel":
            entry = tracker.cancel(req_id)
            if entry is not None and entry["digests"]:
                self.decode_engine.forget_adopted(entry["model"],
                                                  entry["digests"])
            self._publish_cancel(req_id, meta.get("reply") or {})

    def _on_commit(self, req_id, meta):
        """Commit frame: submit through the ordinary engine path — the
        adopted blocks are found by the admission-time prefix match like
        any warm-cache hit — and merge the prefill-side phases into the
        reply so loadgen can attribute TTFT per role."""
        self._tracker().commit(req_id)
        model = meta.get("model", "")
        stream = bool(meta.get("stream"))
        on_token = self._stream_publisher(req_id) if stream else None
        extra = dict(meta.get("phases") or {})
        sent = meta.get("sent_unix")
        if sent:
            extra["xfer_ms"] = round(
                max(0.0, (time.time() - float(sent)) * 1e3), 3)
        extra["role"] = "disagg"
        tp = meta.get(codec.TRACEPARENT)

        def cb(pending):
            rep = pending.reply
            rep.phases.update(extra)
            self._publish(pending.req_id, rep, pending)

        with _tr.remote_parent(tp):
            with _tr.span("serving.adopt_commit", req_id=req_id,
                          model=model, rank=self.rank):
                self.decode_engine.submit(
                    model, meta.get("prompt") or [],
                    max_new_tokens=int(meta.get("max_new", 16)),
                    tenant=meta.get("tenant", "default"),
                    deadline_ms=meta.get("deadline_ms"),
                    eos_id=int(meta.get("eos_id", -1)),
                    req_id=req_id, traceparent=tp,
                    tier=meta.get("tier"),
                    on_token=on_token, callback=cb)

    def _on_session_block(self, req_id, meta, arrays):
        """Session-migration block frame (``kind=block, session=1``):
        sealed history blocks adopt straight into the pool/prefix index
        — warming it whether or not the resume itself lands — while the
        tail partial block is held host-side until the session frame
        consumes it (a partial block must never be indexed)."""
        if self._resume_buf is None or self.decode_engine is None:
            _tm.inc("kv_migrate_refused_total", reason="disabled")
            return
        if meta.get("tail"):
            self._resume_buf.put_tail(req_id, meta.get("digest"),
                                      meta.get("valid", 0), arrays)
            return
        res = self.decode_engine.adopt_kv_block(
            meta.get("model", ""), meta["digest"], arrays)
        if res == "adopted":
            # only freshly-adopted digests are reconciled on refusal —
            # "cached" blocks belong to earlier traffic, not this hand-off
            self._resume_buf.note_adopted(req_id, meta["digest"])

    def _publish_resume_ack(self, req_id, status, error=None):
        doc = {"status": status}
        if error:
            doc["error"] = error
        self._store([(codec.RESUME_ACK_KEY + req_id, codec.pack(doc))])

    def _on_session(self, req_id, meta, arrays):
        """Session manifest (sent LAST on the migration FIFO): consume
        the buffered tail, resume through the ordinary submit path
        (``resume_from`` replays already-emitted tokens without
        re-emitting them), and publish the verdict under
        ``__resumeack__`` — the source only finishes its victim as
        "migrated" after reading "resumed" here."""
        entry = (self._resume_buf.take(req_id)
                 if self._resume_buf is not None else None) or {}
        if self._resume_buf is None or self.decode_engine is None:
            _tm.inc("kv_migrate_refused_total", reason="disabled")
            self._publish_resume_ack(req_id, "refused",
                                     "session migration disabled here")
            return
        try:
            prompt = [int(t) for t in np.asarray(arrays[0]).reshape(-1)]
            resume_out = np.asarray(arrays[1]).reshape(-1)
        except Exception:
            _tm.inc("kv_migrate_refused_total", reason="bad_resume")
            self._publish_resume_ack(req_id, "refused",
                                     "malformed session manifest")
            return
        if int(meta.get("pos", -1)) != len(prompt) + len(resume_out) - 1:
            _tm.inc("kv_migrate_refused_total", reason="pos_mismatch")
            self._publish_resume_ack(
                req_id, "refused",
                "manifest pos %s disagrees with prompt+tokens %d"
                % (meta.get("pos"), len(prompt) + len(resume_out) - 1))
            return
        resume_tail = None
        if entry.get("tail") is not None:
            resume_tail = {"digest": entry.get("tail_digest"),
                           "valid": entry.get("tail_valid", 0),
                           "arrays": entry.get("tail")}
        self._resume_submit(req_id, meta, prompt, resume_out, resume_tail,
                            entry.get("digests") or [])

    def _on_resume(self, req_id, arr):
        """Client crash-resume (``__resume__`` frame): prompt + tokens
        the client already holds.  Any replica resumes; warm history
        blocks — earlier traffic or a prior migration — cap re-prefill
        at O(tokens since last sealed block) instead of O(context)."""
        try:
            meta, arrays = codec.unpack(arr)
            prompt = [int(t) for t in np.asarray(arrays[0]).reshape(-1)]
            resume_out = np.asarray(arrays[1]).reshape(-1)
        except Exception:
            _tm.inc("serving_bad_request_total")
            self._publish_resume_ack(req_id, "refused",
                                     "malformed resume request")
            return
        if self.decode_engine is None:
            self._publish_resume_ack(req_id, "refused",
                                     "replica has no decode engine")
            return
        self._resume_submit(req_id, meta, prompt, resume_out, None, [])

    def _resume_submit(self, req_id, meta, prompt, resume_out,
                       resume_tail, adopted_digests):
        """Shared resume admission: submit with ``resume_from`` and ack
        the synchronous verdict.  An admission-time refusal (bad resume
        state, duplicate req_id, draining) reconciles any blocks this
        hand-off adopted so the destination's pool is left exactly as
        found."""
        model = meta.get("model", "")
        on_token = (self._stream_publisher(req_id)
                    if meta.get("stream") else None)
        tp = meta.get(codec.TRACEPARENT)
        with _tr.remote_parent(tp):
            with _tr.span("serving.resume", req_id=req_id, model=model,
                          rank=self.rank):
                pending = self.decode_engine.submit(
                    model, prompt,
                    max_new_tokens=int(meta.get("max_new_tokens", 16)),
                    tenant=meta.get("tenant", "default"),
                    deadline_ms=meta.get("deadline_ms"),
                    eos_id=int(meta.get("eos_id", -1)),
                    req_id=req_id, traceparent=tp,
                    tier=meta.get("tier"),
                    on_token=on_token,
                    resume_from=resume_out, resume_tail=resume_tail,
                    callback=lambda pending: self._publish(
                        pending.req_id, pending.reply, pending))
        rep = getattr(pending, "reply", None)
        if rep is not None and rep.status in ("error", "shed"):
            if adopted_digests:
                self.decode_engine.forget_adopted(model, adopted_digests)
            self._publish_resume_ack(req_id, "refused", rep.error)
            return False
        self._publish_resume_ack(req_id, "resumed")
        return True

    def _on_preempt(self, victims):
        """Engine preemption hook (fires OUTSIDE the engine lock, on the
        decode-loop thread): push each preempted-youngest session to the
        least-loaded peer on a side thread — the destination-ack wait
        must never block the step loop.  A refused or failed push just
        leaves the victim queued for local deterministic recompute."""
        mig = self.migrator
        if mig is None or not victims:
            return

        def push():
            for rid, model in victims:
                del model
                try:
                    mig.migrate(rid, trigger="pressure")
                except ValueError:
                    pass           # already finished/recomputed: fine

        threading.Thread(target=push, name="serving-migrate-pressure",
                         daemon=True).start()

    def _publish_cancel(self, req_id, reply_meta):
        from .engine import InferReply

        status = reply_meta.get("status") or "aborted"
        if status in ("ok", "handoff"):
            status = "error"
        rep = InferReply(status, error=reply_meta.get("error"),
                         retry_after_ms=reply_meta.get("retry_after_ms")
                         or 0.0)
        # unblock a parked streaming client, then publish the reply
        self._stream_publisher(req_id)(req_id, 0, None, True, rep.status)
        self._publish(req_id, rep)

    def _on_orphan(self, rid, entry):
        """Janitor verdict: the prefill half died before committing this
        request.  Free the adopted blocks and publish a timeout so the
        client's ordinary replay path takes over — no admitted request is
        ever dropped by a prefill SIGKILL."""
        from .engine import InferReply

        if entry.get("digests") and self.decode_engine is not None:
            self.decode_engine.forget_adopted(entry.get("model") or "",
                                              entry["digests"])
        _tr.note("kvxfer_orphan", req_id=rid)
        self._stream_publisher(rid)(rid, 0, None, True, "timeout")
        self._publish(rid, InferReply(
            "timeout",
            error="prefill half died before handoff commit"))

    def _stream_publisher(self, req_id):
        """Per-token chunk publisher: ``__stream__:<id>:<k>`` carries the
        k-th generated token; the final/terminal chunk sets done.  The
        chunk is packed here and waits in ``_chunks``: the decode loop
        says once a step that its tokens are all out
        (``DecodeEngine.on_tokens_emitted`` -> ``_store``), and the step's
        chunks reach the store together."""

        def on_token(rid, index, token, done, status):
            chunk = ("%s%s:%d" % (codec.STREAM_KEY, rid, index), codec.pack(
                {"i": int(index), "done": bool(done), "status": status,
                 "token": None if token is None else int(token)}))
            with self._reply_lock:
                self._chunks.append(chunk)
        return on_token

    def _store(self, items=(), step=False):
        """The one way a per-request key (stream chunk, reply, pair,
        resume ack) enters the RPC store: the waiting stream chunks, then
        ``items``, and the keys the GC ring retires for them, as one
        transaction of the store.  So a step's chunks wake exactly their
        readers under one acquisition of the store's mutex, a request's
        last chunk is there no later than its reply, and the ring bounds
        the store at ``_REPLY_RING`` keys whoever crashed mid-stream.
        Returns the stream chunks handed over.

        ``step`` is the decode loop ending a step's emit
        (``DecodeEngine.on_tokens_emitted``).  While ``FLAGS_tracing`` is
        on, that call also has the store time its streamed replies and
        returns, beside the count, what became of the replies written
        since the step before (``_stream_delivery``), for the step span.
        They are read BEFORE this step's chunks go in: its 30 handler
        threads wake on the transaction and take the cores this thread's
        arithmetic then waits for (drained after it, the same work cost
        ``serving.emit`` 0.19 ms a step on the chip, PERF.md, PR 51)."""
        delivery = self._stream_delivery() \
            if step and self._follow_tracing() else None
        with self._reply_lock:
            chunks, self._chunks = self._chunks, []
            batch = chunks + list(items)
            if batch:
                ring = self._reply_keys
                ring.extend(key for key, _ in batch)
                gone = [ring.popleft()
                        for _ in range(len(ring) - _REPLY_RING)]
                self.rpc.set_vars(batch, delete=gone)
        if chunks:
            _tm.inc("serving_stream_publish_total")
            _tm.inc("serving_stream_chunks_total", len(chunks))
        if delivery is not None:
            return len(chunks), delivery
        return len(chunks)

    def _follow_tracing(self):
        """Turn the store's timing of ``__stream__`` GETs on or off where
        ``FLAGS_tracing`` has changed since the step before (going off,
        what the store had timed is thrown away).  -> the flag."""
        on = _tr.enabled()
        if on != self._gets_timed:
            self.rpc.time_gets(codec.STREAM_KEY if on else None)
            self._gets_timed = on
            if not on:
                self._observe_stream()
        return on

    def _stream_delivery(self):
        """What the store timed of the stream replies written since the
        last call, as step span attributes, microseconds a reply:
        ``deliver_us`` from chunk and request both there to the reply
        written (the server's own: wake, the store's mutex, copy, write);
        ``late_us``, where the request came after its chunk, how long the
        chunk lay waiting for its reader; ``turnaround_us`` from a
        connection's reply written to its next request read (the reader's
        own, with the loopback twice).  They are the replies to the chunks
        of the step BEFORE this one (and to older ones, from readers that
        fell behind): a one-step shift, as ``lanes`` and ``generated`` are a
        step apart.  The same values go to the ``serving_stream_*_ms``
        histograms, ``_OBSERVE_EVERY`` steps' at a time."""
        deliver, late, turnaround, dropped = self.rpc.drain_gets()
        for hist, us in (("serving_stream_deliver_ms", deliver),
                         ("serving_stream_late_ms", late),
                         ("serving_stream_turnaround_ms", turnaround)):
            if us.size:
                self._stream_seen.setdefault(hist, []).append(us)
        out = {"deliver_us": deliver.tolist(), "late_us": late.tolist(),
               "turnaround_us": turnaround.tolist(),
               "stream_replies": deliver.size,
               "stream_records_dropped": dropped}
        self._stream_steps += 1
        if self._stream_steps >= _OBSERVE_EVERY:
            self._observe_stream()
        return out

    def _observe_stream(self):
        """The stream timings gathered since the last call, into their
        histograms (the decode loop's thread: the step that finds
        ``_OBSERVE_EVERY`` of them waiting, or the flag gone off)."""
        seen, self._stream_seen, self._stream_steps = self._stream_seen, {}, 0
        for hist, steps in seen.items():
            _tm.observe_many(hist, np.concatenate(steps) / 1e3)

    def _publish(self, req_id, reply, pending=None):
        from .engine import InferReply

        fault = maybe_fail("serving.reply")
        if fault == "drop":
            return                     # reply lost: client GET times out
        if reply is None:
            reply = InferReply("error", error="malformed request")
        if fault == "error":
            reply = InferReply("error",
                               error="injected fault: serving.reply")
        # runs inside _Pending.complete(), so parent explicitly under the
        # request span rather than whatever is on the completing thread
        with _tr.span("serving.reply_publish",
                      parent=getattr(pending, "span", None),
                      req_id=req_id, status=reply.status):
            meta = reply.to_meta()
            tp = getattr(pending, "traceparent", None)
            if tp:
                meta[codec.TRACEPARENT] = tp
            names = list(reply.outputs)
            buf = codec.pack(meta, [reply.outputs[n] for n in names])
            self._store([(codec.REPLY_KEY + req_id, buf)])

    # -- control plane -------------------------------------------------------

    def apply_rollout(self, doc):
        """Adopt a version-routing state (local command or coordinator
        ``__rollout_set__`` broadcast) and republish this replica's view
        under ``__rollout__`` — the chaos leg asserts every survivor
        converges to the same doc."""
        self.engine.apply_routes(doc.get("models") or {})
        self.rpc.set_var(codec.ROLLOUT_KEY,
                         codec.pack({"models": self.engine.routes()}))

    def _on_rollout_set(self, arr):
        try:
            doc, _ = codec.unpack(arr)
        except Exception:
            _tm.inc("serving_bad_request_total")
            return
        self.apply_rollout(doc)

    def _on_rollout_ctl(self, req_id, arr):
        from .engine import InferReply

        try:
            cmd, _ = codec.unpack(arr)
        except Exception:
            self._publish(req_id, None)
            _tm.inc("serving_bad_request_total")
            return
        if self.rollout is None:
            reply = InferReply("error",
                               error="replica has no rollout controller")
        else:
            meta = self.rollout.handle(cmd)
            reply = InferReply(meta.get("status", "error"),
                               error=meta.get("error"))
            reply.phases = {k: v for k, v in meta.items()
                            if k not in ("status", "error")}
        self._publish(req_id, reply)

    def _on_retire(self):
        """Drain both engines at a batch boundary on a side thread (the
        poll loop must keep serving queued work), then fire on_retire."""
        if self._retire_thread is not None:
            return

        def drain():
            from .. import flags

            self.engine.drain()
            if self.decode_engine is not None:
                mig = None
                if self.migrator is not None \
                        and flags.flag("migrate_on_drain"):
                    mig = self.migrator.drain_push(trigger="drain")
                self.decode_engine.drain(migrate=mig)
            _tm.event("serving_retired", rank=self.rank)
            if self.on_retire is not None:
                self.on_retire()

        self._retire_thread = threading.Thread(
            target=drain, name="serving-retire", daemon=True)
        self._retire_thread.start()

    def set_alive(self, epoch, is_coordinator):
        self.rpc.set_var(codec.ALIVE_KEY, np.asarray(
            [self.rank, int(epoch), 1 if is_coordinator else 0], np.int64))

    def shutdown(self):
        if self._stopped.is_set():
            return
        self._stopped.set()
        if self._pub_stop is not None:
            # stop AND join (idempotent) — a leaked publisher thread
            # would republish __metrics__ into the next test's server
            self._pub_stop.stop()
        if self.rollout is not None:
            self.rollout.stop()
        if self.fleet is not None:
            self.fleet.stop()
        self.engine.stop()
        if self.decode_engine is not None:
            self.decode_engine.stop()
        if self._xfer is not None:
            self._xfer.close()
        if self._adopt is not None:
            self._adopt.close()
        if self.migrator is not None:
            self.migrator.close()
        self.rpc.shutdown()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None
