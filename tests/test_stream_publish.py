"""How a decode step's tokens reach the RPC store (serving/server.py
``_stream_publisher`` / ``_store``, the engine's ``on_tokens_emitted``
hook): a step's chunks together, in one store transaction, under the keys
and payloads a client has always read; a request's last chunk there no
later than its reply, however the request ended; terminal chunks out though
no step follows; the GC ring bounding the store; and the counters and the
span attribute that say the batching engages.
"""

import contextlib
import threading
import time
import uuid

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.native.rpc import RpcClient
from paddle_tpu.serving import (DecodeEngine, ServingClient, ServingEngine,
                                ServingServer, codec)
from paddle_tpu.serving import server as server_mod
from paddle_tpu.serving.decode_model import (DecoderConfig,
                                             init_decoder_params,
                                             unpaged_generate)
from paddle_tpu.utils import fault_injection

CFG = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
PARAMS = init_decoder_params(CFG, seed=7)
BS = 4
LANES = 4
PROMPTS = [[1, 2], [3, 4], [5, 6], [7, 8]]      # one length: lockstep lanes


def _unpaged(prompt, max_new):
    return [int(t) for t in unpaged_generate(CFG, PARAMS, prompt, max_new,
                                             pad_len=48, eos_id=-1)]


@contextlib.contextmanager
def _flags(**kv):
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


class _Wire:
    """A started server over a 4-lane toy engine, with every store
    transaction recorded: [(names stored, names erased)]."""

    def __init__(self):
        self.engine = DecodeEngine(buckets=str(LANES), deadline_ms=30000.0)
        self.engine.add_model("toy", (CFG, PARAMS), kv_blocks=64)
        self.engine.prewarm()
        self.server = ServingServer(ServingEngine(), port=0,
                                    decode_engine=self.engine).start()
        self.ep = "127.0.0.1:%d" % self.server.port
        self.batches = []
        rpc, inner = self.server.rpc, self.server.rpc.set_vars

        def set_vars(items, delete=()):
            items = list(items)
            # recorded first: a reader woken by the store looks here next
            self.batches.append(([k for k, _ in items], list(delete)))
            inner(items, delete=delete)

        rpc.set_vars = set_vars

    def generate(self, req_id, prompt, max_new, deadline_ms=30000.0,
                 model="toy"):
        """What the poll loop does with a streaming ``__generate__`` frame."""
        self.server._on_generate(req_id, codec.pack(
            {"model": model, "req_id": req_id, "deadline_ms": deadline_ms,
             "max_new_tokens": max_new, "eos_id": -1, "stream": True},
            [np.asarray(prompt, np.int32)]))

    def client(self, deadline=20.0):
        return RpcClient(self.ep, rpc_deadline=deadline, retry_times=0)

    def chunks(self, req_id, deadline=20.0):
        """Walk ``__stream__:<id>:<k>`` from 0 to the done chunk, as a
        client does: [(i, token, done, status)]."""
        c, out = self.client(deadline), []
        try:
            while not out or not out[-1][2]:
                meta, _ = codec.unpack(c.get_var(
                    "%s%s:%d" % (codec.STREAM_KEY, req_id, len(out))))
                out.append((meta["i"], meta["token"], meta["done"],
                            meta["status"]))
        finally:
            c.close()
        return out

    def reply(self, req_id):
        c = self.client()
        try:
            return codec.unpack(c.get_var(codec.REPLY_KEY + req_id))[0]
        finally:
            c.close()

    def until(self, what, timeout=30.0):
        end = time.time() + timeout
        while not what():
            assert time.time() < end, "timed out waiting"
            time.sleep(0.005)

    def idle(self):
        self.until(lambda: not self.engine._active
                   and not self.engine._waiting)

    def stored_at(self, key):
        """Index of the first transaction that stored ``key``."""
        return next(i for i, (names, _) in enumerate(self.batches)
                    if key in names)


@pytest.fixture(scope="module")
def wire(tmp_path_factory):
    with _flags(FLAGS_compile_cache_dir=str(tmp_path_factory.mktemp("cc")),
                FLAGS_kv_block_size=BS, FLAGS_kv_cache_dtype="f32"):
        w = _Wire()
    yield w
    w.server.shutdown()


@pytest.fixture()
def fresh(wire):
    wire.idle()
    del wire.batches[:]
    return wire


def _rids(n=LANES):
    return [uuid.uuid4().hex for _ in range(n)]


def _stream_key(rid, k):
    return "%s%s:%d" % (codec.STREAM_KEY, rid, k)


def test_a_steps_chunks_arrive_together_complete_and_in_order(fresh):
    w, rids, n = fresh, _rids(), 12
    with w.engine._cond:                 # one admission: lanes in lockstep
        for rid, prompt in zip(rids, PROMPTS):
            w.generate(rid, prompt, n)
    for rid, prompt in zip(rids, PROMPTS):
        got = w.chunks(rid)
        assert [c[0] for c in got] == list(range(n))
        assert [c[1] for c in got] == _unpaged(prompt, n)
        assert [c[2] for c in got] == [False] * (n - 1) + [True]
        assert {c[3] for c in got} == {"ok"}
        assert w.reply(rid)["status"] == "ok"
    # in the store: token k of all four lanes entered in ONE transaction,
    # for every step in which no lane finished
    for k in range(n - 1):
        step = {_stream_key(rid, k) for rid in rids}
        assert [set(names) for names, _ in w.batches
                if step & set(names)] == [step]
    # and each stream's keys entered in index order
    for rid in rids:
        order = [w.stored_at(_stream_key(rid, k)) for k in range(n)]
        assert order == sorted(order)


def test_an_unmodified_client_streams_token_for_token(fresh):
    w, n = fresh, 10
    seen = {i: [] for i in range(LANES)}
    replies = {}

    def ask(i):
        cli = ServingClient(endpoints=[w.ep])
        replies[i] = cli.generate(
            "toy", PROMPTS[i], max_new_tokens=n, deadline_ms=30000.0,
            stream=True, on_token=lambda k, t: seen[i].append((k, t)))

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(LANES)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    for i in range(LANES):
        want = _unpaged(PROMPTS[i], n)
        assert replies[i].status == "ok"
        assert seen[i] == list(enumerate(want))
        assert [int(t) for t in replies[i].outputs["tokens"]] == want
        assert len(replies[i].phases["client_itl_ms_samples"]) == n - 1


@pytest.mark.parametrize("ending", ["ok", "aborted", "timeout_mid_decode",
                                    "timeout_in_queue", "shed", "error"])
def test_the_last_chunk_is_stored_no_later_than_the_reply(fresh, ending):
    """...and a terminal chunk is flushed though no step follows it: the
    request below is the only one, so after it the loop idles."""
    w, rid = fresh, uuid.uuid4().hex
    blocker = None
    try:
        if ending == "ok":
            w.generate(rid, [1, 2], 6)
        elif ending == "aborted":
            fault_injection.arm("serving.decode_step:delay:1")
            w.generate(rid, [1, 2], 40)
            w.until(lambda: any(_stream_key(rid, 0) in names
                                for names, _ in w.batches))
            assert w.engine.abort(rid)
        elif ending == "timeout_mid_decode":
            fault_injection.arm("serving.decode_step:delay:1")
            w.generate(rid, [1, 2], 40, deadline_ms=450.0)
        elif ending == "timeout_in_queue":
            # the lanes are taken by four long requests; ours waits out
            # its deadline in the queue
            fault_injection.arm("serving.decode_step:delay:1")
            blocker = _rids()
            with w.engine._cond:
                for b, prompt in zip(blocker, PROMPTS):
                    w.generate(b, prompt, 40)
            w.until(lambda: len(w.engine._active) == LANES)
            w.generate(rid, [1, 2], 4, deadline_ms=250.0)
        elif ending == "shed":
            w.engine._draining = True
            w.generate(rid, [1, 2], 4)
        else:
            w.generate(rid, [1, 2], 4, model="no_such_model")
        got = w.chunks(rid)
        meta = w.reply(rid)
    finally:
        fault_injection.disarm()
        w.engine._draining = False
        for b in blocker or ():
            w.engine.abort(b)
    status = ending.split("_")[0]
    assert got[-1][2] and got[-1][3] == status and meta["status"] == status
    if status == "ok":
        assert [c[1] for c in got] == _unpaged([1, 2], 6)
    else:
        assert got[-1][1] is None and got[-1][0] == len(got) - 1
        assert all(c[3] == "ok" and not c[2] for c in got[:-1])
    last = w.stored_at(_stream_key(rid, len(got) - 1))
    reply = w.stored_at(codec.REPLY_KEY + rid)
    assert last <= reply
    if last == reply:
        names = w.batches[reply][0]
        assert names.index(_stream_key(rid, len(got) - 1)) \
            < names.index(codec.REPLY_KEY + rid)


def test_the_ring_bounds_the_store_through_5000_tokens(fresh):
    w = fresh
    srv, ring = w.server, server_mod._REPLY_RING
    rids = _rids()
    pubs = [srv._stream_publisher(rid) for rid in rids]
    live, worst = set(srv._reply_keys), 0
    assert len(live) <= ring
    seen = 0
    for k in range(1250):
        for rid, on_token in zip(rids, pubs):
            on_token(rid, k, k % 31, False, "ok")
        assert srv._store() == LANES
        if k % 100 == 99:                # replies share the ring
            srv._publish_resume_ack(rids[0] + str(k), "resumed")
        for names, gone in w.batches[seen:]:
            live -= set(gone)
            live |= set(names)
            worst = max(worst, len(live))
        seen = len(w.batches)
    assert worst == ring and len(srv._reply_keys) == ring
    assert live == set(srv._reply_keys)
    # the store itself: the oldest chunk is gone, the newest is there
    c = w.client(deadline=0.3)
    with pytest.raises(ConnectionError):
        c.get_var(_stream_key(rids[0], 0))
    c.close()
    c = w.client()
    meta, _ = codec.unpack(c.get_var(_stream_key(rids[-1], 1249)))
    c.close()
    assert meta == {"i": 1249, "done": False, "status": "ok",
                    "token": 1249 % 31}


def test_counters_and_span_attribute_read_the_generating_lanes(fresh,
                                                               tmp_path):
    """Between two looks at a steady 4-lane run, chunks over publishes is
    4; the step spans of those steps carry ``published`` 4."""
    w, rids = fresh, _rids()
    with _flags(FLAGS_telemetry=True, FLAGS_tracing=True,
                FLAGS_telemetry_dir=str(tmp_path)):
        _tm.reset()
        try:
            fault_injection.arm("serving.decode_step:delay:1")
            with w.engine._cond:
                for rid, prompt in zip(rids, PROMPTS):
                    w.generate(rid, prompt, 30)

            def look(at_least):
                """Counters and progress between two steps (the loop
                holds the step lock from plan to publish)."""
                while True:
                    with w.engine._cond:
                        outs = [len(s.out) for s in w.engine._active]
                        if len(outs) == LANES and min(outs) >= at_least:
                            return (_tm.counter_total(
                                "serving_stream_chunks_total"),
                                _tm.counter_total(
                                    "serving_stream_publish_total"),
                                w.engine._step_no)
                    time.sleep(0.01)

            c0, p0, s0 = look(2)
            c1, p1, s1 = look(8)
            fault_injection.disarm()
            for rid in rids:
                assert w.chunks(rid)[-1][2]
            w.idle()
            assert p1 > p0 and (c1 - c0) / (p1 - p0) == LANES
            assert c1 - c0 == LANES * (s1 - s0)
            steps = {s["attrs"]["step"]: s["attrs"]
                     for s in _trc.records("serving.decode_step")}
            assert all(steps[n]["published"] == LANES
                       for n in range(s0 + 1, s1 + 1))
            # prefill steps publish nothing, and say so
            assert min(a["published"] for a in steps.values()) == 0
            assert "serving.emit" in steps[s1]["phases"]
        finally:
            fault_injection.disarm()
            _trc.reset()
            _tm.reset()


def test_an_engine_without_a_server_has_no_hook_and_streams_as_before():
    with _flags(FLAGS_kv_block_size=BS, FLAGS_kv_cache_dtype="f32"):
        e = DecodeEngine(buckets="1", deadline_ms=30000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=32)
    e.start()
    try:
        assert e.on_tokens_emitted is None
        calls = []
        r = e.submit("toy", [1, 2], max_new_tokens=5, deadline_ms=30000.0,
                     on_token=lambda *a: calls.append(a)).wait(60.0)
        assert r.status == "ok"
        assert [(a[1], a[2], a[3]) for a in calls] == [
            (k, t, k == 4) for k, t in enumerate(_unpaged([1, 2], 5))]
        # a terminal chunk comes before the reply completes
        order = []
        p = e.submit("nope", [1], on_token=lambda *a: order.append("chunk"),
                     callback=lambda p: order.append("reply"))
        assert p.wait(10.0).status == "error" and order == ["chunk", "reply"]
    finally:
        e.stop()


# -- what became of a step's chunks on their way out (FLAGS_tracing) ---------

def _spy_on_the_switch(w):
    """Every ``RpcServer.time_gets`` call of ``w``'s server from now on."""
    calls, inner = [], w.server.rpc.time_gets

    def time_gets(prefix):
        calls.append(prefix)
        inner(prefix)

    w.server.rpc.time_gets = time_gets
    return calls


def _read_all(w, rids):
    """Each stream walked to its done chunk on a thread and a connection
    of its own, as clients do: the threads, started."""
    threads = [threading.Thread(target=w.chunks, args=(rid,))
               for rid in rids]
    for t in threads:
        t.start()
    return threads


def _join_all(threads):
    for t in threads:
        t.join(60.0)
    assert not any(t.is_alive() for t in threads), "a reader hung"


def test_the_step_span_says_what_became_of_the_chunks_a_step_late(
        fresh, tmp_path):
    w, rids, n = fresh, _rids(), 8
    # the flag is off: a step's call that finds the switch on (a traced
    # test before this one) turns it off and throws away what was timed
    assert w.server._store(step=True) == 0 and not w.server._gets_timed
    with _flags(FLAGS_tracing=True, FLAGS_telemetry=True,
                FLAGS_telemetry_dir=str(tmp_path)):
        _trc.reset()
        _tm.reset()
        try:
            # slow steps: every reader is parked on its next chunk long
            # before the step that stores it
            fault_injection.arm("serving.decode_step:delay:1")
            with w.engine._cond:
                for rid, prompt in zip(rids, PROMPTS):
                    w.generate(rid, prompt, n)
            _join_all(_read_all(w, rids))
            fault_injection.disarm()
            w.idle()
            # no step follows the last one: its replies are still waiting
            # (a reply reaches its reader before its record the ring)
            time.sleep(0.05)
            published, rest = w.server._store(step=True)
            # the histograms take sixteen steps' values at a time
            w.server._observe_stream()
            steps = sorted((s["attrs"] for s in
                            _trc.records("serving.decode_step")),
                           key=lambda a: a["step"])
            hists = _tm.snapshot()["histograms"]
        finally:
            fault_injection.disarm()
            _trc.reset()
            _tm.reset()
    assert published == 0 and len(steps) >= n
    assert "period_us" not in steps[0]
    assert all(a["period_us"] > 0 for a in steps[1:])
    # the first iteration dispatches and has no step before it to read
    assert "stream_replies" not in steps[0] and steps[0]["published"] == 0
    steps = steps[1:]
    for a in steps + [rest]:
        assert a["stream_records_dropped"] == 0
        assert len(a["deliver_us"]) == a["stream_replies"]
        assert len(a["late_us"]) <= a["stream_replies"]
        assert len(a["turnaround_us"]) <= a["stream_replies"]
        assert min(a["deliver_us"] + a["turnaround_us"] + [0]) >= 0
    # every chunk was read once, and its reply is on the span of the step
    # AFTER the one that stored it: a step's call reads what was timed
    # before its own chunks go in.  But for the ends: the requests for each
    # stream's first chunk were read before the first step's call turned
    # the switch on, and leave no record; the last step is read with no
    # step after it to dispatch, so no span reports its chunks and what was
    # timed after it waits for the next
    assert sum(a["published"] for a in steps) == LANES * (n - 1)
    assert sum(a["stream_replies"] for a in steps + [rest]) \
        == LANES * (n - 1)
    first = next(i for i, a in enumerate(steps) if a["published"])
    assert not any(a["stream_replies"] for a in steps[:first + 2])
    for a, after in zip(steps[first + 1:], steps[first + 2:]):
        assert after["stream_replies"] == a["published"]
    assert rest["stream_replies"] == 2 * LANES
    # parked readers: no chunk waited for its reader, and each reader's
    # first timed reply has no timed reply before it to turn around from
    turns = sum(len(a["turnaround_us"]) for a in steps + [rest])
    assert turns == LANES * (n - 2)
    assert not any(a["late_us"] for a in steps + [rest])
    assert hists["serving_stream_deliver_ms"]["count"] == LANES * (n - 1)
    assert hists["serving_stream_turnaround_ms"]["count"] == turns
    assert "serving_stream_late_ms" not in hists


def test_the_switch_follows_the_flag_on_off_and_on(fresh, tmp_path):
    w, rid, n = fresh, uuid.uuid4().hex, 26

    def at(tokens, do):
        """``do()`` between two steps, once ``rid`` has that many tokens
        (the loop holds the step lock from plan to publish); -> the last
        step's number."""
        while True:
            with w.engine._cond:
                if w.engine._active and \
                        len(w.engine._active[0].out) >= tokens:
                    do()
                    return w.engine._step_no
            time.sleep(0.005)

    # tracing off: the hook's return is the count it was, and the switch
    # goes off if a traced test before this one left it on
    assert w.server._store(step=True) == 0 and not w.server._gets_timed
    calls = _spy_on_the_switch(w)
    off = {}

    def look_while_off():
        *timed, off["dropped"] = w.server.rpc.drain_gets()
        off["recs"] = timed[0]
        off["timed"] = w.server._gets_timed
        fluid.set_flags({"FLAGS_tracing": True})

    try:
        with _flags(FLAGS_tracing=True, FLAGS_telemetry_dir=str(tmp_path)):
            _trc.reset()
            fault_injection.arm("serving.decode_step:delay:1")
            w.generate(rid, [1, 2], n)
            readers = _read_all(w, [rid])
            t_off = time.perf_counter()
            s_off = at(6, lambda: fluid.set_flags({"FLAGS_tracing": False}))
            s_on = at(14, look_while_off)
            off_us = (time.perf_counter() - t_off) * 1e6
            _join_all(readers)
            fault_injection.disarm()
            w.idle()
            steps = {s["attrs"]["step"]: s["attrs"]
                     for s in _trc.records("serving.decode_step")}
    finally:
        fault_injection.disarm()
        _trc.reset()
    assert calls == [codec.STREAM_KEY, None, codec.STREAM_KEY]
    # off: steps ran and chunks were read, nothing was timed or recorded
    assert s_on - s_off >= 7 and not off["timed"]
    assert (len(off["recs"]), off["dropped"]) == (0, 0)
    assert not any(s_off < k <= s_on for k in steps)
    # on again: the GET answered by the first step was read while the switch
    # was off and leaves no record; the next reply has none to turn around
    # from; and nothing after it reaches back over the stretch
    assert "period_us" not in steps[s_on + 1]
    assert steps[s_on + 1]["stream_replies"] == 0
    before = [steps[k] for k in sorted(steps) if k <= s_off]
    after = [steps[k] for k in sorted(steps) if k > s_on]
    # (the request's first span dispatched with nothing to read before it)
    assert sum(a.get("stream_replies", 0) for a in before) >= 3
    assert sum(a["stream_replies"] for a in after) >= 8
    assert sum(len(a["turnaround_us"]) for a in after) \
        == sum(a["stream_replies"] for a in after) - 1
    assert max(t for a in after for t in a["turnaround_us"] + a["late_us"]
               + a["deliver_us"]) < off_us / 2


def test_a_server_that_never_saw_tracing_on_never_asks_for_the_timing(
        tmp_path_factory):
    with _flags(FLAGS_compile_cache_dir=str(tmp_path_factory.mktemp("cc")),
                FLAGS_kv_block_size=BS, FLAGS_kv_cache_dtype="f32"):
        w = _Wire()
    try:
        calls, rid = _spy_on_the_switch(w), uuid.uuid4().hex
        w.generate(rid, [1, 2], 6)
        got = w.chunks(rid)
        w.idle()
        assert [c[1] for c in got] == _unpaged([1, 2], 6)
        # the hook returns the count it always did
        w.server._stream_publisher(rid)(rid, 6, 1, False, "ok")
        assert w.server._store(step=True) == 1
        assert calls == [] and not w.server._gets_timed
        deliver, _late, _turnaround, dropped = w.server.rpc.drain_gets()
        assert (len(deliver), dropped) == (0, 0)
    finally:
        w.server.shutdown()
