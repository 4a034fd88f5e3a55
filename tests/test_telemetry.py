"""Unified runtime telemetry tests (paddle_tpu/core/telemetry.py): registry
semantics, executor step instrumentation, distributed health counters under
an injected fault, the __metrics__ RPC scrape, and the off-by-default
zero-cost contract."""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import telemetry
from paddle_tpu.utils import fault_injection as fi

from dist_utils import free_ports as _free_ports  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    fluid.set_flags({"FLAGS_telemetry": False, "FLAGS_telemetry_dir": "",
                     "FLAGS_fault_spec": ""})
    fi.disarm()
    telemetry.reset()


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.fc(x, 3)
        loss = fluid.layers.reduce_mean(y)
    return main, startup, loss


def test_registry_counters_gauges_histograms():
    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    telemetry.inc("reqs_total")
    telemetry.inc("reqs_total", 2, ep="a")
    telemetry.inc("reqs_total", 3, ep="b")
    telemetry.set_gauge("depth", 7, q="in")
    for v in (1.0, 2.0, 3.0, 4.0):
        telemetry.observe("lat_ms", v)
    snap = telemetry.snapshot()
    assert snap["counters"]["reqs_total"] == 1
    assert snap["counters"]["reqs_total{ep=a}"] == 2
    assert snap["counters"]["reqs_total{ep=b}"] == 3
    assert telemetry.counter_total("reqs_total") == 6.0
    assert snap["gauges"]["depth{q=in}"] == 7.0
    h = snap["histograms"]["lat_ms"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] in (2.0, 3.0)
    prom = telemetry.prometheus_text(snap)
    assert "# TYPE reqs_total counter" in prom
    assert 'reqs_total{ep="a"} 2' in prom
    assert "# TYPE lat_ms summary" in prom
    assert 'lat_ms{quantile="0.5"}' in prom
    assert "lat_ms_count 4" in prom


def test_disabled_is_inert_and_touches_no_files(tmp_path):
    d = str(tmp_path / "telem")
    fluid.set_flags({"FLAGS_telemetry": False, "FLAGS_telemetry_dir": d})
    telemetry.reset()
    telemetry.inc("c_total")
    telemetry.set_gauge("g", 1)
    telemetry.observe("h_ms", 3.0)
    telemetry.event("step", n=1)
    telemetry.record_step(1.0, True)
    telemetry.set_info("k", {"v": 1})
    telemetry.maybe_dump()
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["events_logged"] == {}
    assert "info" not in snap
    # the off path must never create the telemetry dir, let alone write
    assert not os.path.exists(d)

    # three executor steps with telemetry off leave the registry empty
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={"x": np.ones((2, 4), "f")},
                    fetch_list=[loss])
    assert telemetry.snapshot()["counters"] == {}
    assert not os.path.exists(d)


def test_executor_step_instrumentation(tmp_path):
    d = str(tmp_path / "run")
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        # enable AFTER startup so its compile doesn't muddy the counts
        fluid.set_flags({"FLAGS_telemetry": True, "FLAGS_telemetry_dir": d})
        telemetry.reset()
        for _ in range(3):
            exe.run(main, feed={"x": np.ones((2, 4), "f")},
                    fetch_list=[loss])
    snap = telemetry.snapshot()
    c = snap["counters"]
    assert c["executor_steps_total"] == 3
    assert c["executor_cache_miss_total"] == 1  # one compile...
    assert c["executor_cache_hit_total"] == 2   # ...then cache hits
    assert c["executor_feed_bytes_total"] == 3 * 2 * 4 * 4
    assert snap["histograms"]["executor_step_ms"]["count"] == 3
    assert snap["histograms"]["executor_compile_ms"]["count"] == 1
    # JSONL step-event stream: one line per step, hit flags in order
    # (events are buffered; flush() puts them in the file)
    telemetry.flush()
    with open(os.path.join(d, "steps.jsonl")) as f:
        events = [json.loads(line) for line in f]
    assert [e["ev"] for e in events] == ["step"] * 3
    assert [e["cache_hit"] for e in events] == [False, True, True]
    assert "compile_ms" in events[0] and "compile_ms" not in events[1]
    # dump(): prometheus + JSON snapshots land next to the stream
    jpath, ppath = telemetry.dump()
    assert json.load(open(jpath))["counters"]["executor_steps_total"] == 3
    assert "executor_steps_total 3" in open(ppath).read()


def test_ps_fault_rpc_retry_and_dedupe_counters():
    """One sync pserver + one trainer with a single injected ACK-lost fault
    (rpc.send:error): the client retries (rpc_retry_total), the replayed
    tagged frame is dropped by the server's dedupe filter
    (ps_dedupe_drop_total), the fault itself is attributed
    (fault_injected_total), and training still completes."""
    from paddle_tpu.initializer import Constant

    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    # prob=1, count=1, skip=1: each trainer step sends heartbeat first,
    # then tagged grads — skip lets the (untagged, idempotent) heartbeat
    # pass so the one fault lands on the first TAGGED grad send
    fi.arm("rpc.send:error:1:1:1")

    ep = "127.0.0.1:%d" % _free_ports(1)[0]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.data("y", shape=[1])
        pred = fluid.layers.fc(
            x, 1, param_attr=fluid.ParamAttr(initializer=Constant(0.1)),
            bias_attr=fluid.ParamAttr(initializer=Constant(0.0)))
        diff = fluid.layers.elementwise_sub(pred, y)
        loss = fluid.layers.reduce_mean(
            fluid.layers.elementwise_mul(diff, diff))
        fluid.optimizer.SGD(0.1).minimize(loss)

    errs = []

    def run_pserver():
        try:
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=0, program=main, startup_program=startup,
                        pservers=ep, trainers=1)
            prog, sprog = t.get_pserver_programs(ep)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(sprog)
                exe.run(prog, scope=scope)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    th = threading.Thread(target=run_pserver, daemon=True)
    th.start()

    rng = np.random.RandomState(3)
    xs = rng.rand(3, 8, 4).astype("f")
    ys = (xs @ np.array([[1.0], [-2.0], [0.5], [3.0]], "f") + 0.1).astype("f")
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers=ep, trainers=1)
    tp = t.get_trainer_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(3):
            exe.run(tp, feed={"x": xs[i], "y": ys[i]}, fetch_list=[],
                    scope=scope)
        scope._ps_comm.complete()
    th.join(timeout=60)
    assert not errs, errs

    snap = telemetry.snapshot()
    assert telemetry.counter_total("fault_injected_total") == 1
    assert telemetry.counter_total("rpc_retry_total") >= 1
    assert telemetry.counter_total("ps_dedupe_drop_total") >= 1
    assert telemetry.counter_total("rpc_send_total") >= 3  # hb + grads
    assert any(k.startswith("ps_round_ms") for k in snap["histograms"])
    assert snap["events_logged"].get("ps_round", 0) >= 3


def test_metrics_rpc_publish_and_scrape():
    """A server publishes its snapshot under __metrics__; scrape() GETs and
    decodes it over the native transport."""
    from paddle_tpu.native.rpc import RpcServer

    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    telemetry.inc("demo_total", 5, role="server")
    server = RpcServer(port=0)
    try:
        server.serve(True)
        telemetry.publish_rpc(server)
        snap = telemetry.scrape("127.0.0.1:%d" % server.port, timeout=15.0)
        assert snap["counters"]["demo_total{role=server}"] == 5
    finally:
        server.shutdown()


def test_publish_rpc_disabled_publishes_nothing():
    class _FakeServer:
        def __init__(self):
            self.calls = []

        def set_var(self, name, arr):
            self.calls.append(name)

    fluid.set_flags({"FLAGS_telemetry": False})
    s = _FakeServer()
    telemetry.publish_rpc(s)
    assert s.calls == []


def test_heartbeat_monitor_gauge_and_miss_counter():
    from paddle_tpu.distributed.ps import HeartBeatMonitor

    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    m = HeartBeatMonitor(2, timeout_s=0.05, name="t0", startup_grace_s=0.0)
    m.update(0)
    m.update(1)
    time.sleep(0.12)
    m.update(1)  # worker 1 stays alive; worker 0 goes silent
    assert m.check() == [0]
    snap = telemetry.snapshot()
    assert snap["gauges"]["ps_dead_workers{ps=t0}"] == 1.0
    assert telemetry.counter_total("ps_heartbeat_miss_total") == 1
    # already-warned workers don't re-count, the gauge stays current
    assert m.check() == [0]
    assert telemetry.counter_total("ps_heartbeat_miss_total") == 1
    assert telemetry.snapshot()["gauges"]["ps_dead_workers{ps=t0}"] == 1.0
