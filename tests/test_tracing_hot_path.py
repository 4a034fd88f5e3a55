"""The recording path the hot loops share (core/tracing.py): buffered
records, ``records()``, ``phase()`` host events on the profiler's clock,
what the decode loop's and the executor's step spans carry, the flight
recorder's lane-set breadcrumbs, and the ``jax.named_scope`` names the
lowered programs carry."""

import collections
import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as tr
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving.decode_model import (DecoderConfig,
                                             init_decoder_params,
                                             make_paged_step)
from paddle_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

CFG = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
PARAMS = init_decoder_params(CFG, seed=7)

SERVING_PHASES = {"serving.between_steps", "serving.lock_wait",
                  "serving.admit", "serving.plan", "serving.dispatch",
                  "serving.fetch", "serving.emit"}
EXECUTOR_PHASES = {"executor.prepare", "executor.dispatch",
                   "executor.writeback", "executor.fetch"}


@pytest.fixture(autouse=True)
def _clean():
    tr.reset()
    _tm.reset()
    yield
    tr.reset()
    _tm.reset()
    fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry": False,
                     "FLAGS_telemetry_dir": ""})


def _tracing_on(tmp_path, **more):
    d = str(tmp_path / "tel")
    fluid.set_flags(dict({"FLAGS_tracing": True, "FLAGS_telemetry_dir": d},
                         **more))
    return d


def _trace_path(d):
    return os.path.join(d, "trace-%d.jsonl" % os.getpid())


def _engine(tmp_path, buckets="2,4"):
    old = fluid.get_flags(["FLAGS_kv_block_size", "FLAGS_kv_cache_dtype",
                           "FLAGS_compile_cache_dir"])
    fluid.set_flags({"FLAGS_kv_block_size": 4, "FLAGS_kv_cache_dtype": "f32",
                     "FLAGS_compile_cache_dir": str(tmp_path / "cc")})
    try:
        e = DecodeEngine(buckets=buckets, deadline_ms=30000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=64)
        e.prewarm()
    finally:
        fluid.set_flags(old)
    return e.start()


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.fc(x, 3)
        loss = fluid.layers.reduce_mean(y)
    return main, startup, loss


# -- buffered recording -------------------------------------------------------

def test_span_reaches_no_file_before_flush_but_records_has_it(tmp_path):
    d = _tracing_on(tmp_path)
    with tr.span("work", job="j"):
        pass
    assert not os.path.exists(_trace_path(d))
    got = tr.records("work")
    assert len(got) == 1 and got[0]["attrs"] == {"job": "j"}
    assert {"t", "name", "tid", "sid", "parent", "ts", "dur", "thr"} \
        <= set(got[0])
    tr.flush()
    with open(_trace_path(d)) as f:
        lines = f.read().splitlines()
    assert len(lines) == 2 and '"proc"' in lines[0] and '"work"' in lines[1]
    # flushed records stay readable, and a second flush writes nothing twice
    tr.flush()
    assert len(tr.records("work")) == 1
    with open(_trace_path(d)) as f:
        assert len(f.read().splitlines()) == 2


def test_records_survive_the_flag_going_off_and_on(tmp_path):
    d = _tracing_on(tmp_path)
    with tr.span("before"):
        pass
    fluid.set_flags({"FLAGS_tracing": False})
    with tr.span("while_off"):
        pass
    fluid.set_flags({"FLAGS_tracing": True})
    with tr.span("after"):
        pass
    assert len(tr.records("before")) == 1 and len(tr.records("after")) == 1
    assert tr.records("while_off") == []
    tr.flush()
    with open(_trace_path(d)) as f:
        text = f.read()
    assert '"before"' in text and '"after"' in text


def _per_call(loop, n=5000, rounds=5):
    """This thread's CPU time a call of ``loop(i)``, the best of five."""
    best = float("inf")
    for _ in range(rounds):
        t0 = time.thread_time()
        for i in range(n):
            loop(i)
        best = min(best, (time.thread_time() - t0) / n)
    return best


def test_recording_costs_a_small_multiple_of_its_dict_work(tmp_path):
    """A span against a bare loop that builds the same record, keeps it in
    a bounded window and serialises it 1,024 at a time as ``flush()`` does:
    both in this thread, both on its CPU clock, so what the other test
    workers do to the machine falls on both sides of the ratio."""
    d = _tracing_on(tmp_path)      # the sink is set: spans pay for flushes
    window, batch = collections.deque(maxlen=tr._RECENT_CAP), []
    thread = threading.current_thread().name
    sink = open(os.path.join(str(tmp_path), "bare.jsonl"), "w")

    def bare(i):
        attrs = dict(i=i, model="m", bucket=4)
        t_wall, t0 = time.time(), time.perf_counter()
        rec = {"t": "span", "name": "cheap", "tid": "0" * 32,
               "sid": "0" * 16, "parent": None, "ts": int(t_wall * 1e6),
               "dur": int((time.perf_counter() - t0) * 1e6),
               "thr": thread, "attrs": attrs}
        window.append(rec)
        batch.append(rec)
        if len(batch) >= 1024:
            for r in batch:
                sink.write(json.dumps(r, default=str) + "\n")
            sink.flush()
            del batch[:]

    def spanned(i):
        with tr.span("cheap", i=i, model="m", bucket=4):
            pass

    with sink:
        floor = _per_call(bare)
        cost = _per_call(spanned)
    assert os.path.exists(_trace_path(d))
    assert cost < 6 * floor, "a span %.1f us, its dict work %.1f us" % (
        cost * 1e6, floor * 1e6)


def test_the_window_is_bounded(tmp_path):
    _tracing_on(tmp_path)
    for i in range(25000):
        with tr.span("cheap", i=i, model="m", bucket=4):
            pass
    assert len(tr.records("cheap")) == 25000
    fluid.set_flags({"FLAGS_telemetry": True})
    # past the window the oldest records fall out, and are counted
    fluid.set_flags({"FLAGS_telemetry_dir": ""})
    for i in range(tr._RECENT_CAP):
        tr.instant("filler")
    assert tr.records("cheap") == []
    assert _tm.counter_total("tracing_dropped_total") == 25000


def test_off_phase_makes_no_record_and_start_span_is_null(tmp_path):
    d = str(tmp_path / "tel")
    fluid.set_flags({"FLAGS_telemetry_dir": d})   # tracing stays off
    with tr.phase("executor.dispatch"):
        pass
    span = tr.start_span("step")
    assert span is tr._NULL_SPAN
    assert span.take_phases("executor.") == {}
    assert not getattr(tr._tls, "phases", None)
    assert len(tr._recent) == 0 and not os.path.exists(d)


def test_phase_tally_is_taken_by_prefix_and_carried(tmp_path):
    _tracing_on(tmp_path)
    with tr.phase("a.one"):
        time.sleep(0.002)
    with tr.phase("b.other"):
        pass
    with tr.phase("a.one") as early:
        early.stop()        # ends here, not at the block's end
        time.sleep(0.01)
    span = tr.start_span("step")
    taken = span.take_phases("a.")
    assert set(taken) == {"a.one"} and 2000 <= taken["a.one"] < 10000
    assert span.attrs["phases"] is taken
    # what was not taken waits for the span that asks for it
    assert set(tr.start_span("other").take_phases("b.")) == {"b.other"}
    assert tr.start_span("again").take_phases("") == {}


# -- the decode loop ----------------------------------------------------------

def test_decode_step_spans_carry_admission_gap_and_phases(tmp_path):
    _tracing_on(tmp_path)
    e = _engine(tmp_path)
    n = 6
    try:
        reqs = [e.submit("toy", [1 + i, 2, 3], max_new_tokens=6)
                for i in range(n)]
        assert all(r.wait(30.0).status == "ok" for r in reqs)
    finally:
        e.stop()
    steps = tr.records("serving.decode_step")
    assert steps
    attrs = [s["attrs"] for s in steps]
    assert sum(a["admitted"] for a in attrs) == n
    waits = [w for a in attrs for w in a["admit_wait_ms"]]
    locks = [w for a in attrs for w in a["admit_lock_wait_ms"]]
    assert len(waits) == n and len(locks) == n
    assert all(0 <= lock <= wait for lock, wait in zip(locks, waits))
    assert all(len(a["admit_wait_ms"]) == a["admitted"] for a in attrs)
    assert all(a["gap_us"] >= 0 for a in attrs)
    # an iteration dispatches a step and then reads the one before it:
    # the first has none to read (no ``ms``); a span that read one has
    # every phase
    read = {"serving.fetch", "serving.emit"}
    for a in attrs:
        assert SERVING_PHASES - read <= set(a["phases"]), a["phases"]
        assert {"lanes", "generated", "bucket", "step", "ahead"} <= set(a)
        if "ms" in a:
            assert SERVING_PHASES <= set(a["phases"]), a["phases"]
    assert attrs[0]["ahead"] is False and "ms" not in attrs[0]
    # (so has one that follows a pause, or an iteration that found every
    # lane waiting for its last token and only read)
    assert sum("ms" in a for a in attrs) > len(attrs) // 2
    # a span's phases are those since the span before it took its own:
    # they sum to no more than the time between the two spans' ends
    ends = [s["ts"] + s["dur"] for s in steps]
    for prev, end, a in zip(ends, ends[1:], attrs[1:]):
        assert sum(a["phases"].values()) <= end - prev + 200, a
    # the wait for work is a phase too, not an unnamed hole
    assert any("serving.idle" in a["phases"] for a in attrs)


def test_unchanged_lane_set_writes_nothing_on_the_engine_thread(
        tmp_path, monkeypatch):
    """With tracing and telemetry on, a decode step whose lanes are those
    of the step before opens, writes, flushes and renames no file; the
    flight record on disk still names the lanes in flight."""
    d = _tracing_on(tmp_path, FLAGS_telemetry=True)
    e = _engine(tmp_path, buckets="1")
    engine_thread = e._thread
    touched = []

    def spy(fn):
        def wrapped(*a, **kw):
            if threading.current_thread() is engine_thread:
                touched.append((e._step_no, fn.__name__))
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(_tm._RotatingFile, "write",
                        spy(_tm._RotatingFile.write))
    monkeypatch.setattr(_tm._RotatingFile, "flush",
                        spy(_tm._RotatingFile.flush))
    monkeypatch.setattr(tr, "flight_dump", spy(tr.flight_dump))
    try:
        r = e.generate("toy", [1, 2, 3], max_new_tokens=12)
        assert r.status == "ok"
    finally:
        e.stop()
    assert e._step_no >= 14
    # one breadcrumb, at the first step, when the lane set appeared
    assert touched == [(1, "flight_dump")]
    with open(os.path.join(d, "flightrec-%d.json" % os.getpid())) as f:
        doc = json.load(f)
    notes = [x for x in doc["records"] if x.get("kind") == "decode_step"]
    assert notes and all(x["req_ids"] for x in notes)


# -- the executor -------------------------------------------------------------

def test_executor_step_spans_carry_phases_and_host_time(tmp_path):
    _tracing_on(tmp_path)
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        tr.reset()
        for _ in range(4):
            exe.run(main, feed={"x": np.ones((2, 4), "f")},
                    fetch_list=[loss])
    steps = tr.records("executor.step")
    assert [s["attrs"]["cache_hit"] for s in steps] \
        == [False, True, True, True]
    # the one span stands for the step marker too: consecutive numbers
    numbers = [s["attrs"]["step"] for s in steps]
    assert numbers == list(range(numbers[0], numbers[0] + 4))
    for s in steps:
        a = s["attrs"]
        assert EXECUTOR_PHASES <= set(a["phases"]), a
        # the span covers the whole call, the phases lie inside it
        assert sum(a["phases"].values()) <= s["dur"] + 200
        assert 0 <= a["host_us"] <= s["dur"] + 200
    assert not [r for r in tr._recent if r["t"] == "inst"]


def test_mesh_step_spans_carry_the_placement_counts_and_phase(tmp_path):
    """A run on a mesh: the span says how many persistables were placed and
    how many passed through, and still times ``executor.shard_params`` (the
    benchmark's ``shard_ms_per_step.train`` reads that phase by name)."""
    _tracing_on(tmp_path)
    main, startup, loss = _tiny_program()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=[fluid.TPUPlace(i) for i in range(4)])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        tr.reset()
        for _ in range(3):
            exe.run(prog, feed={"x": np.ones((8, 4), "f")},
                    fetch_list=[loss])
        exe.run(main, feed={"x": np.ones((8, 4), "f")}, fetch_list=[loss])
    *mesh_steps, plain = tr.records("executor.step")
    for s in mesh_steps:
        a = s["attrs"]
        assert EXECUTOR_PHASES | {"executor.shard_feeds",
                                  "executor.shard_params"} <= set(a["phases"])
        assert a["params_placed"] + a["params_passed"] == 2    # fc w and b
    assert [s["attrs"]["params_placed"] for s in mesh_steps] == [2, 0, 0]
    # no mesh, no placement: neither the counts nor the phase
    assert "params_placed" not in plain["attrs"]
    assert "executor.shard_params" not in plain["attrs"]["phases"]


# -- the profiler's clock -----------------------------------------------------

def test_profile_holds_the_phases_as_host_events_with_tracing_off(tmp_path):
    from jax.profiler import ProfileData

    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    e = _engine(tmp_path, buckets="1")
    trace_dir = str(tmp_path / "profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 4), "f")},
                    fetch_list=[loss])
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                for _ in range(2):
                    exe.run(main, feed={"x": np.ones((2, 4), "f")},
                            fetch_list=[loss])
                assert e.generate("toy", [1, 2], max_new_tokens=2).ok
            finally:
                jax.profiler.stop_trace()
    finally:
        e.stop()
    assert len(tr._recent) == 0      # FLAGS_tracing is off: nothing recorded
    found, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(found).planes:
        for line in plane.lines:
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
    assert names.get("executor.dispatch", 0) >= 2, sorted(names)[:40]
    assert names.get("serving.dispatch", 0) >= 2
    assert {"executor.prepare", "executor.fetch", "serving.plan",
            "serving.fetch", "serving.emit", "serving.lock_wait"} \
        <= set(names)


# -- names in the lowered programs --------------------------------------------

def test_lowered_text_carries_the_scopes():
    # a Program: each op's lowering sits in a scope named for its type
    from paddle_tpu.core.lowering import BlockPlan, build_block_fn

    main, _startup, loss = _tiny_program()
    plan = BlockPlan(main.global_block(), ["x"], [loss.name])
    fn = build_block_fn(plan)
    params = {n: np.zeros([int(d) for d in
                           main.global_block().var(n).shape], "f")
              for n in plan.ro_names + plan.rw_names}
    text = jax.jit(fn).lower(
        {"x": np.ones((2, 4), "f")},
        {n: params[n] for n in plan.ro_names},
        {n: params[n] for n in plan.rw_names}, {},
        None).as_text(debug_info=True)
    for op_type in ("mul", "elementwise_add", "reduce_mean"):
        assert "/%s/" % op_type in text or '%s"' % op_type in text, op_type

    # the decode step: layer, attention, KV write and gather, MLP, head
    kv = KVCacheConfig(CFG.layers, CFG.heads, CFG.head_dim, block_size=4,
                       num_blocks=8)
    step = make_paged_step(CFG, kv)
    text = jax.jit(step).lower(
        PagedKVCache(kv).carry(), PARAMS, np.zeros(2, np.int32),
        np.zeros(2, np.int32),
        np.zeros((2, 12), np.int32), np.ones(2, np.int32)
    ).as_text(debug_info=True)
    for scope in ("layer0/attn", "layer1/attn/kv_write",
                  "layer1/attn/kv_gather", "layer1/mlp", "lm_head"):
        assert scope in text, scope
