"""Distributed tracing layer (paddle_tpu/core/tracing.py).

Covers span nesting/threading semantics, the zero-cost-off contract
(no files, flat counters, inert null span), W3C-style traceparent
round-trips through the serving codec and the RPC frame-name stamping,
one in-process serving request producing the full admission -> execute
-> reply span chain under a single trace_id, the flight-recorder dump
on an injected fault, and the size-bounded JSONL rotation shared with
telemetry.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as tr
from paddle_tpu.utils import fault_injection as fi


@pytest.fixture(autouse=True)
def _clean_tracing():
    tr.reset()
    _tm.reset()
    fi.disarm()
    yield
    tr.reset()
    _tm.reset()
    fi.disarm()
    fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry": False,
                     "FLAGS_telemetry_dir": "",
                     "FLAGS_telemetry_max_bytes": 256 << 20})


def _tracing_on(tmp_path):
    d = str(tmp_path / "tel")
    fluid.set_flags({"FLAGS_tracing": True, "FLAGS_telemetry_dir": d})
    return d


def _read_trace(d):
    tr.flush()   # records are buffered until then
    path = os.path.join(d, "trace-%d.jsonl" % os.getpid())
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- off == inert -------------------------------------------------------------

def test_off_is_inert_no_files_no_counters(tmp_path):
    d = str(tmp_path / "tel")
    fluid.set_flags({"FLAGS_telemetry_dir": d})  # tracing stays off
    s = tr.start_span("x", a=1)
    assert s is tr._NULL_SPAN
    assert s.annotate(b=2) is s and s.link(None) is s and s.end() is s
    assert s.traceparent is None and s.context is None
    with tr.span("y") as y:
        assert y is tr._NULL_SPAN
        assert tr.current_span() is None and tr.traceparent() is None
    tr.instant("i")
    tr.note("n", k=1)
    assert tr.flight_dump() is None
    assert tr.stamp_wire_name("__infer__:r") == "__infer__:r"
    assert not os.path.exists(d)
    assert _tm.snapshot()["counters"] == {}


# -- span semantics -----------------------------------------------------------

def test_span_nesting_and_records(tmp_path):
    d = _tracing_on(tmp_path)
    with tr.span("outer", job="j") as outer:
        assert tr.current_span() is outer
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        tr.instant("mark", step=3)
    recs = _read_trace(d)
    assert recs[0]["t"] == "proc" and recs[0]["pid"] == os.getpid()
    by_name = {r.get("name"): r for r in recs if r["t"] == "span"}
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    assert by_name["outer"]["attrs"] == {"job": "j"}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    inst = [r for r in recs if r["t"] == "inst"]
    assert inst and inst[0]["tid"] == by_name["outer"]["tid"]
    assert _tm.snapshot()["counters"] == {}  # telemetry off: no counters


def test_span_stacks_are_per_thread(tmp_path):
    _tracing_on(tmp_path)
    seen = {}

    def worker():
        # a fresh thread starts with no inherited context...
        seen["bare"] = tr.current_span()
        with tr.span("t2") as s:
            seen["t2"] = s

    with tr.span("t1") as s1:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert tr.current_span() is s1
    assert seen["bare"] is None
    assert seen["t2"].parent_id is None
    assert seen["t2"].trace_id != s1.trace_id

    # ...unless the owning span is explicitly activated over there
    def worker2():
        with tr.activate(s1):
            with tr.span("t3") as s:
                seen["t3"] = s

    t = threading.Thread(target=worker2)
    t.start()
    t.join()
    assert seen["t3"].trace_id == s1.trace_id
    assert seen["t3"].parent_id == s1.span_id


def test_error_annotation_and_links(tmp_path):
    d = _tracing_on(tmp_path)
    with tr.span("a") as a:
        pass
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("xyz")
    root = tr.start_span("batch", parent=None)
    root.link(a).link(("t" * 32, "s" * 16))
    root.end()
    recs = {r.get("name"): r for r in _read_trace(d) if r["t"] == "span"}
    assert "xyz" in recs["boom"]["attrs"]["error"]
    assert recs["batch"]["links"] == [[a.trace_id, a.span_id],
                                      ["t" * 32, "s" * 16]]


# -- W3C context --------------------------------------------------------------

def test_traceparent_parse_and_remote_parent(tmp_path):
    assert tr.parse_traceparent("00-%s-%s-01" % ("a" * 32, "b" * 16)) \
        == ("a" * 32, "b" * 16)
    for bad in (None, 7, "", "00-xy-z-01", "00-%s-%s" % ("a" * 32,
                                                         "b" * 16),
                "00-%s-%s-01" % ("g" * 32, "b" * 16)):
        assert tr.parse_traceparent(bad) is None
    _tracing_on(tmp_path)
    with tr.span("client") as c:
        tp = tr.traceparent()
    assert tr.parse_traceparent(tp) == (c.trace_id, c.span_id)
    with tr.remote_parent(tp):
        child = tr.start_span("server")
        assert child.trace_id == c.trace_id
        assert child.parent_id == c.span_id
        child.end()
    # malformed header degrades to local-root, never raises
    with tr.remote_parent("garbage"):
        s = tr.start_span("orphan")
        assert s.parent_id is None
        s.end()


def test_wire_name_stamp_and_strip(tmp_path):
    _tracing_on(tmp_path)
    assert tr.stamp_wire_name("k") == "k"  # no active span: bare
    with tr.span("s"):
        stamped = tr.stamp_wire_name("__infer__:r9")
        assert stamped != "__infer__:r9"
        bare, tp = tr.strip_wire_name(stamped)
        assert bare == "__infer__:r9" and tp == tr.traceparent()
    assert tr.strip_wire_name("plain") == ("plain", None)


def test_codec_traceparent_roundtrip():
    from paddle_tpu.serving import codec

    meta = {"model": "m", codec.TRACEPARENT:
            "00-%s-%s-01" % ("c" * 32, "d" * 16)}
    got, _ = codec.unpack(codec.pack(meta))
    assert tr.parse_traceparent(got[codec.TRACEPARENT]) \
        == ("c" * 32, "d" * 16)


# -- retroactive spans (elastic phase tree) -----------------------------------

def test_record_span_lays_out_measured_phases(tmp_path):
    d = _tracing_on(tmp_path)
    t0 = time.time() - 0.5
    root = tr.record_span("elastic.requorum", t0, 500.0, epoch=2)
    tr.record_span("elastic.compile", t0, 300.0, parent=root)
    tr.record_span("elastic.restore", t0 + 0.3, 200.0, parent=root)
    spans = [r for r in _read_trace(d) if r["t"] == "span"]
    byn = {r["name"]: r for r in spans}
    assert byn["elastic.compile"]["parent"] == byn["elastic.requorum"]["sid"]
    assert byn["elastic.restore"]["tid"] == byn["elastic.requorum"]["tid"]
    assert byn["elastic.requorum"]["dur"] == 500000  # us
    assert abs(byn["elastic.restore"]["ts"]
               - byn["elastic.compile"]["ts"] - 300000) <= 2


# -- serving chain ------------------------------------------------------------

@pytest.fixture()
def saved_model(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        h = fluid.layers.fc(x, 16, act="relu")
        out = fluid.layers.fc(h, 4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.save_inference_model(str(tmp_path / "model"), ["x"], [out],
                                   exe, main_program=main)
    return str(tmp_path / "model")


def test_serving_request_full_span_chain(saved_model, tmp_path):
    """One wire request must leave the full client.infer ->
    serving.admission -> serving.request (queue_wait) -> batch/execute ->
    serving.reply_publish chain under a SINGLE trace_id, with the batch
    span linking the request span."""
    from paddle_tpu.serving import ServingClient, ServingEngine, \
        ServingServer

    d = _tracing_on(tmp_path)
    eng = ServingEngine(buckets=(1, 4))
    eng.add_model("fc", saved_model)
    eng.prewarm()
    srv = ServingServer(eng, port=0).start()
    try:
        cli = ServingClient(endpoints=["127.0.0.1:%d" % srv.port])
        x = np.ones((2, 8), np.float32)
        r = cli.infer("fc", {"x": x})
        assert r.ok, r.error
        # always-on phase attribution rides the reply even w/o tracing
        assert {"queue_wait_ms", "execute_ms", "bucket", "rows",
                "wire_ms"} <= set(r.phases)
        assert r.phases["bucket"] == 4 and r.phases["rows"] == 2
    finally:
        srv.shutdown()
    tr.flush()
    spans = [x for x in _read_trace(d) if x["t"] == "span"]
    byn = {}
    for s in spans:
        byn.setdefault(s["name"], s)
    need = ["client.infer", "serving.admission", "serving.request",
            "serving.queue_wait", "serving.batch", "serving.pad_to_bucket",
            "serving.execute", "executor.step", "serving.reply_publish"]
    assert set(need) <= set(byn), sorted(byn)
    root = byn["client.infer"]
    # single trace_id across client->server->engine (batch is linked)
    for name in ("serving.admission", "serving.request",
                 "serving.queue_wait", "serving.reply_publish"):
        assert byn[name]["tid"] == root["tid"], name
    assert byn["serving.admission"]["parent"] == root["sid"]
    assert byn["serving.request"]["parent"] \
        == byn["serving.admission"]["sid"]
    assert byn["serving.queue_wait"]["parent"] \
        == byn["serving.request"]["sid"]
    assert byn["serving.reply_publish"]["parent"] \
        == byn["serving.request"]["sid"]
    # batch links the request span; execute/step nest under the batch
    assert [byn["serving.request"]["tid"], byn["serving.request"]["sid"]] \
        in byn["serving.batch"]["links"]
    assert byn["serving.execute"]["parent"] == byn["serving.batch"]["sid"]
    assert byn["executor.step"]["tid"] == byn["serving.batch"]["tid"]
    # the rpc SEND frame was stamped and the server recorded receipt
    recv = [x for x in _read_trace(d)
            if x["t"] == "inst" and x["name"] == "rpc.recv"]
    assert any(x["tid"] == root["tid"] for x in recv)


# -- flight recorder ----------------------------------------------------------

def test_flightrec_dump_on_injected_fault(tmp_path):
    d = _tracing_on(tmp_path)
    with tr.span("work", job="w"):
        tr.note("batch_start", req_ids=["r1", "r2"])
    path = os.path.join(d, "flightrec-%d.json" % os.getpid())
    assert os.path.exists(path)  # note() is write-through
    # an injected (non-kill) fault re-dumps with reason fault
    fi.arm("rpc.send:error:1")
    assert fi.maybe_fail("rpc.send") == "error"
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "note:fault"
    kinds = [r.get("kind") for r in doc["records"] if r["t"] == "note"]
    assert "batch_start" in kinds and "fault" in kinds
    assert any(r.get("req_ids") == ["r1", "r2"] for r in doc["records"]
               if r.get("kind") == "batch_start")


def test_flight_ring_is_bounded(tmp_path):
    _tracing_on(tmp_path)
    for i in range(tr._FLIGHT_CAP + 50):
        tr.instant("i%d" % i)
    assert len(tr._flight) == tr._FLIGHT_CAP
    assert tr._flight[-1]["name"] == "i%d" % (tr._FLIGHT_CAP + 49)


# -- rotation -----------------------------------------------------------------

def test_trace_jsonl_rotation(tmp_path):
    d = _tracing_on(tmp_path)
    fluid.set_flags({"FLAGS_telemetry_max_bytes": 4096})
    for i in range(200):
        tr.instant("filler", i=i, pad="x" * 64)
    tr.flush()
    path = os.path.join(d, "trace-%d.jsonl" % os.getpid())
    assert os.path.exists(path) and os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 4096
    assert os.path.getsize(path + ".1") <= 4096
    # both generations stay parseable JSONL
    for p in (path, path + ".1"):
        with open(p) as f:
            for line in f:
                json.loads(line)


def test_telemetry_events_rotation(tmp_path):
    d = str(tmp_path / "tel")
    fluid.set_flags({"FLAGS_telemetry": True, "FLAGS_telemetry_dir": d,
                     "FLAGS_telemetry_max_bytes": 2048})
    for i in range(200):
        _tm.event("soak", i=i, pad="y" * 32)
    _tm.flush()
    path = os.path.join(d, "steps.jsonl")
    assert os.path.exists(path + ".1"), "steps.jsonl never rotated"
    assert os.path.getsize(path) <= 2048


# -- publisher lifecycle ------------------------------------------------------

def test_publisher_stops_and_joins_on_shutdown(saved_model):
    from paddle_tpu.serving import ServingEngine, ServingServer

    fluid.set_flags({"FLAGS_telemetry": True})
    eng = ServingEngine(buckets=(1, 4))
    eng.add_model("fc", saved_model)
    srv = ServingServer(eng, port=0).start()
    handle = srv._pub_stop
    assert handle is not None and handle.thread.is_alive()
    thread = handle.thread
    srv.shutdown()
    assert not thread.is_alive(), "publisher thread leaked past shutdown"
    # double-stop (and a second shutdown) must be harmless
    handle.stop()
    srv.shutdown()
