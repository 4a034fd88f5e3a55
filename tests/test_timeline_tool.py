"""tools/timeline.py exporter tests: the from_profiler path round-trips a
real fluid.profiler capture (including the new ph:"M" metadata and ph:"i"
instant markers), and from_xplane decodes a hand-encoded synthetic
.xplane.pb through the in-repo proto reader."""

import json
import os
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import profiler

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import timeline  # noqa: E402


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.fc(x, 3)
        loss = fluid.layers.reduce_mean(y)
    return main, startup, loss


def test_from_profiler_cli_round_trip(tmp_path):
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    prof_path = str(tmp_path / "prof.json")
    out_path = str(tmp_path / "timeline.json")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        profiler.reset_profiler()
        # the caller's loop marks its own events and step edges (the
        # executor records its runs as executor.step spans in the tracing
        # stream, tests/test_tracing_hot_path.py)
        with profiler.profiler("All", "total", prof_path):
            for step in range(2):
                with profiler.RecordEvent("train::step"):
                    exe.run(main, feed={"x": np.ones((2, 4), "f")},
                            fetch_list=[loss])
                profiler.mark_instant("step", args={"step": step})
    rc = timeline.main(["--profile_path", prof_path,
                        "--timeline_path", out_path])
    assert rc == 0
    with open(out_path) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    runs = [e for e in evs if e["name"] == "train::step"]
    assert len(runs) == 2
    # each step edge is a ph:"i" instant
    insts = [e for e in evs if e.get("ph") == "i"]
    assert [e["name"] for e in insts] == ["step", "step"]
    s0, s1 = (e["args"]["step"] for e in insts)
    assert s1 == s0 + 1
    assert all(e["s"] == "g" for e in insts)
    # ph:"M" process/thread name metadata for chrome://tracing / Perfetto
    meta = {e["name"]: e for e in evs if e.get("ph") == "M"}
    assert meta["process_name"]["args"]["name"] == "paddle_tpu host"
    assert "thread_name" in meta


def test_from_profiler_accepts_bare_event_list(tmp_path):
    prof_path = str(tmp_path / "bare.json")
    out_path = str(tmp_path / "out.json")
    bare = [{"name": "op", "ph": "X", "pid": 0, "tid": 0,
             "ts": 1.0, "dur": 2.0}]
    with open(prof_path, "w") as f:
        json.dump(bare, f)
    assert timeline.main(["--profile_path", prof_path,
                          "--timeline_path", out_path]) == 0
    with open(out_path) as f:
        assert json.load(f)["traceEvents"] == bare


# --- synthetic XSpace proto (matches from_xplane's field numbers) -----------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _msg(num, payload):
    return _varint((num << 3) | 2) + _varint(len(payload)) + payload


def _num(num, value):
    return _varint(num << 3) + _varint(value)


def _make_xspace():
    # XSpace.planes[0]: name + one event_metadata + one line w/ two events
    emeta = _msg(4, _num(1, 7) + _msg(2, _msg(2, b"fusion.1")))
    ev1 = _msg(4, _num(1, 7) + _num(2, 2_000_000) + _num(3, 5_000_000))
    ev2 = _msg(4, _num(1, 7) + _num(2, 9_000_000) + _num(3, 1_000_000))
    line = _msg(3, _msg(2, b"XLA Ops") + _num(3, 1000) + ev1 + ev2)
    plane = _msg(2, b"/device:TPU:0") + emeta + line
    return _msg(1, plane)


def test_from_xplane_synthetic_proto(tmp_path):
    with open(str(tmp_path / "host.xplane.pb"), "wb") as f:
        f.write(_make_xspace())
    trace = timeline.from_xplane(str(tmp_path))
    evs = trace["traceEvents"]
    assert len(evs) == 2
    ev = evs[0]
    assert ev["name"] == "fusion.1"
    assert ev["pid"] == "/device:TPU:0" and ev["tid"] == "XLA Ops"
    # line ts0 is ns, event offset/duration are ps, chrome wants us:
    # 1000 ns + 2_000_000 ps = 3.0 us; dur 5_000_000 ps = 5.0 us
    assert ev["ts"] == 3.0 and ev["dur"] == 5.0
    assert evs[1]["ts"] == 10.0 and evs[1]["dur"] == 1.0


def test_from_xplane_cli_and_missing_dir(tmp_path):
    with open(str(tmp_path / "host.xplane.pb"), "wb") as f:
        f.write(_make_xspace())
    out_path = str(tmp_path / "device_timeline.json")
    assert timeline.main(["--xplane_dir", str(tmp_path),
                          "--timeline_path", out_path]) == 0
    with open(out_path) as f:
        assert len(json.load(f)["traceEvents"]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    try:
        timeline.from_xplane(str(empty))
        raise AssertionError("expected FileNotFoundError")
    except FileNotFoundError:
        pass
