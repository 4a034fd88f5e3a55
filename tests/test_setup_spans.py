"""Set-up as a span tree (core/tracing.py, core/executor.py,
serving/engine.py): ``setup.import``, the cache-miss ``executor.step`` and
``executor.warmup`` with their ``build`` / ``disk_key`` / ``cache_restore``
/ ``compile`` / ``first_run`` children, ``serving.add_model`` and
``serving.prewarm``; the benchmark's readers of them
(benchmark/setup_spans.py, eight files under benchmark/layer_metrics/),
``tools/trace_view.py --setup`` and ``bench.py``'s compile story."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as tr
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving.decode_model import (DecoderConfig,
                                             init_decoder_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

from benchmark import run as bench_run          # noqa: E402
from benchmark import setup_spans               # noqa: E402
import trace_view                               # noqa: E402

CFG = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
PARAMS = init_decoder_params(CFG, seed=7)
BUCKETS = (2, 4)
CHILDREN = {"compiled": ["executor.build", "executor.disk_key",
                         "executor.cache_restore", "executor.compile",
                         "executor.first_run"],
            "disk": ["executor.build", "executor.disk_key",
                     "executor.cache_restore", "executor.first_run"]}
READERS = ("import_s.setup", "executor_build_s.train",
           "cache_restore_s.setup", "compile_s.setup",
           "executables_compiled.setup", "add_model_s.serve",
           "prewarm_s.serve", "in_program_s.setup")
SLACK_US = 200     # a span's own open and close, between clocks


@pytest.fixture(autouse=True)
def _clean():
    flags = ["FLAGS_tracing", "FLAGS_telemetry", "FLAGS_telemetry_dir",
             "FLAGS_compile_cache_dir", "FLAGS_kv_block_size",
             "FLAGS_kv_cache_dtype"]
    old = fluid.get_flags(flags)
    tr.reset()
    _tm.reset()
    yield
    tr.reset()
    _tm.reset()
    fluid.set_flags(old)


def _trace(tmp_path, cache="cc"):
    """Recording on, the sink and the compile cache under ``tmp_path``."""
    d = str(tmp_path / "tel")
    fluid.set_flags({"FLAGS_tracing": True, "FLAGS_telemetry_dir": d,
                     "FLAGS_compile_cache_dir": str(tmp_path / cache)})
    return d


def _train(steps=2):
    """A start-up program and ``steps`` steps of a tiny trainer."""
    main, startup = fluid.Program(), fluid.Program()
    # the guard resets the temporary names: a rebuild is the same content
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 3))
        fluid.optimizer.SGD(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(steps):
            exe.run(main, feed={"x": np.ones((2, 4), "f")},
                    fetch_list=[loss])
    return exe, main, loss


def _serve():
    fluid.set_flags({"FLAGS_kv_block_size": 4, "FLAGS_kv_cache_dtype": "f32"})
    engine = DecodeEngine(buckets=",".join(map(str, BUCKETS)),
                          deadline_ms=30000.0)
    engine.add_model("toy", (CFG, PARAMS), kv_blocks=64)
    return engine, engine.prewarm()


def _spans():
    return [r for r in tr._recent if r["t"] == "span"]


def _below(parent, spans):
    return sorted((s for s in spans if s["parent"] == parent["sid"]),
                  key=lambda s: s["ts"])


def _twice(tmp_path, work):
    """``work()`` in a cold cache directory, then again on what it left:
    the second pass's spans by ``source`` ``"disk"``."""
    _trace(tmp_path)
    work()
    first = _spans()
    tr.reset()
    work()
    return {"compiled": first, "disk": _spans()}


# -- the Program path ---------------------------------------------------------

@pytest.mark.parametrize("source", ["compiled", "disk"])
def test_a_missed_step_is_a_tree_of_its_set_up(tmp_path, source):
    spans = _twice(tmp_path, _train)[source]
    steps = [s for s in spans if s["name"] == "executor.step"]
    missed = [s for s in steps if not s["attrs"]["cache_hit"]]
    # the start-up program's run and the step's first: the same tree each
    assert len(missed) == 2 and len(steps) == 3
    for step in missed:
        assert step["parent"] is None
        below = _below(step, spans)
        assert [s["name"] for s in below] == CHILDREN[source]
        assert sum(s["dur"] for s in below) <= step["dur"] + SLACK_US
        assert all(s["tid"] == step["tid"] for s in below)
        restore = below[2]["attrs"]
        assert restore["hit"] is (source == "disk")
        assert restore["read_ms"] >= 0
    # a hit is no set-up: nothing lies under it
    hit, = [s for s in steps if s["attrs"]["cache_hit"]]
    assert not _below(hit, spans)


def test_a_compile_says_where_its_time_went(tmp_path):
    _trace(tmp_path)
    _train(steps=1)
    compiles = tr.records("executor.compile")
    assert len(compiles) == 2
    for c in compiles:
        a = c["attrs"]
        assert a["source"] == "compiled"
        assert min(a["lower_ms"], a["backend_ms"], a["store_ms"]) > 0
        assert (a["lower_ms"] + a["backend_ms"] + a["store_ms"]) * 1e3 \
            <= c["dur"] + SLACK_US


def test_a_restore_says_what_it_read_and_loaded(tmp_path):
    restores = [s for s in _twice(tmp_path, lambda: _train(steps=1))["disk"]
                if s["name"] == "executor.cache_restore"]
    assert len(restores) == 2
    for r in restores:
        a = r["attrs"]
        assert a["hit"] and a["payload_bytes"] > 0 and a["load_ms"] > 0
        assert (a["read_ms"] + a["load_ms"]) * 1e3 <= r["dur"] + SLACK_US
    assert not [s for s in _spans() if s["name"] == "executor.compile"]


def test_a_restores_load_is_a_phase_of_the_step(tmp_path):
    """``deserialize_and_load`` is ``tracing.phase("executor.cache_load")``:
    the missed step's ``phases`` carry it, and still lie inside the step
    (``executor.first_run``, which overlaps dispatch and fetch, is a span
    and no phase)."""
    spans = _twice(tmp_path, lambda: _train(steps=1))["disk"]
    for step in (s for s in spans if s["name"] == "executor.step"):
        phases = step["attrs"]["phases"]
        assert phases["executor.cache_load"] > 0
        assert "executor.first_run" not in phases
        assert sum(phases.values()) <= step["dur"] + SLACK_US


@pytest.mark.parametrize("source", ["compiled", "disk"])
def test_a_programs_warmup_is_the_same_tree(tmp_path, source):
    def work():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4])
            y = fluid.layers.fc(x, 3)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            tr.reset()
            exe.warmup(main, feed_specs={"x": ((2, 4), "float32")},
                       fetch_list=[y])
            # warmed: the run is a hit, and makes no set-up span
            exe.run(main, feed={"x": np.ones((2, 4), "f")}, fetch_list=[y])

    spans = _twice(tmp_path, work)[source]
    warm, = [s for s in spans if s["name"] == "executor.warmup"]
    assert warm["parent"] is None and warm["attrs"]["source"] == source
    below = _below(warm, spans)
    assert [s["name"] for s in below] == CHILDREN[source][:-1]
    assert sum(s["dur"] for s in below) <= warm["dur"] + SLACK_US
    step, = [s for s in spans if s["name"] == "executor.step"]
    assert step["attrs"]["cache_hit"] and not _below(step, spans)


def test_an_ir_pass_is_part_of_the_build(tmp_path):
    """``fuse_optimizer_ops_pass`` runs before the cache is asked; the
    first attempt at a program version is always under the step that then
    misses."""
    _trace(tmp_path)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        h = x
        for _ in range(4):
            h = fluid.layers.fc(h, 4)
        loss = fluid.layers.reduce_mean(h)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), "f")}, fetch_list=[loss])
    spans = _spans()
    fuse, = [s for s in spans
             if s.get("attrs", {}).get("stage") == "fuse_optimizer_ops"]
    step = [s for s in spans if s["name"] == "executor.step"][-1]
    assert fuse["name"] == "executor.build" and fuse["parent"] == step["sid"]
    assert [s["name"] for s in _below(step, spans)][:2] \
        == ["executor.build", "executor.build"]


# -- the engine ---------------------------------------------------------------

@pytest.mark.parametrize("source", ["compiled", "disk"])
def test_the_engines_set_up_is_two_trees(tmp_path, source):
    spans = _twice(tmp_path, _serve)[source]
    add, = [s for s in spans if s["name"] == "serving.add_model"]
    assert add["parent"] is None
    assert add["attrs"] == {"model": "toy", "blocks": 64,
                            "budget_capped": False}
    below = _below(add, spans)
    assert [s["name"] for s in below] == ["serving.cache_alloc",
                                          "serving.lay_out"]
    assert below[0]["attrs"]["bytes"] > 0
    assert sum(s["dur"] for s in below) <= add["dur"] + SLACK_US

    warm, = [s for s in spans if s["name"] == "serving.prewarm"]
    assert warm["parent"] is None
    assert warm["attrs"] == {
        "buckets": len(BUCKETS),
        "compiled": len(BUCKETS) * (source == "compiled"),
        "restored": len(BUCKETS) * (source == "disk")}
    warmups = _below(warm, spans)
    assert [s["name"] for s in warmups] == ["executor.warmup"] * len(BUCKETS)
    assert [s["attrs"]["key"] for s in warmups] == list(map(str, BUCKETS))
    assert sum(s["dur"] for s in warmups) <= warm["dur"] + SLACK_US
    for w in warmups:
        assert w["attrs"]["source"] == source
        assert w["attrs"]["fn"] == "decode_step"
        assert [s["name"] for s in _below(w, spans)] \
            == CHILDREN[source][1:-1]
    # nothing of set-up is a root but the two
    assert {s["name"] for s in spans if s["parent"] is None} \
        == {"serving.add_model", "serving.prewarm"}


def test_a_second_prewarm_warms_from_memory_and_opens_no_warmup(tmp_path):
    _trace(tmp_path)
    engine, _ = _serve()
    tr.reset()
    manifest = engine.prewarm()
    assert {got["source"] for got in manifest["toy"].values()} == {"memory"}
    warm, = tr.records("serving.prewarm")
    assert warm["attrs"] == {"buckets": 2, "compiled": 0, "restored": 0}
    assert not tr.records("executor.warmup")


def test_the_prewarm_event_stays_and_the_added_event_went(tmp_path):
    fluid.set_flags({"FLAGS_telemetry": True,
                     "FLAGS_compile_cache_dir": str(tmp_path / "cc")})
    _serve()
    kinds = [e["ev"] for e in _tm._events]
    assert kinds.count("serving_prewarm") == len(BUCKETS)
    assert "decode_model_added" not in kinds
    hists = _tm.snapshot()["histograms"]
    assert not {"executor_trace_lower_ms", "executor_xla_compile_ms",
                "compile_cache_load_ms"} & set(hists)


# -- the import, and the flag ---------------------------------------------------

def test_the_import_is_recorded_once_with_the_first_record(tmp_path):
    tr.imported(1000.0, 2500.0)
    with tr.span("before.the.flag"):      # off: nothing is made of either
        pass
    assert not tr._recent
    fluid.set_flags({"FLAGS_tracing": True})
    with tr.span("first"):
        pass
    with tr.span("second"):
        pass
    assert [r["name"] for r in tr._recent] \
        == ["setup.import", "first", "second"]
    got, = tr.records("setup.import")
    assert got["ts"] == 1000 * 10 ** 6 and got["dur"] == 2500 * 10 ** 3
    assert got["parent"] is None


def test_a_new_process_times_its_own_import():
    code = ("import time; t0 = time.time(); import paddle_tpu as fluid; "
            "t1 = time.time(); "
            "from paddle_tpu.core import tracing as tr; "
            "fluid.set_flags({'FLAGS_tracing': True}); "
            "tr.instant('go'); import json; "
            "print(json.dumps([t0, t1, list(tr._recent)]))")
    out = subprocess.run(
        [sys.executable, "-c", code], check=True, capture_output=True,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                 FLAGS_tracing="0")).stdout
    t0, t1, records = json.loads(out.decode().strip().splitlines()[-1])
    assert [r["name"] for r in records] == ["setup.import", "go"]
    span = records[0]
    assert t0 - 0.01 <= span["ts"] / 1e6 <= t1
    assert 0 < span["dur"] / 1e6 <= t1 - t0 + 0.01
    # nearly all of the statement is the package's own first line to last
    assert span["dur"] / 1e6 >= 0.5 * (t1 - t0)


def test_with_the_flag_off_set_up_makes_no_record(tmp_path):
    fluid.set_flags({"FLAGS_tracing": False,
                     "FLAGS_compile_cache_dir": str(tmp_path / "cc")})
    tr.imported(1000.0, 2500.0)
    _train()
    _serve()
    assert not tr._recent and not tr._unwritten
    assert tr.device_span("x").span is tr._NULL_SPAN
    assert tr._NULL_SPAN.device_memory() is tr._NULL_SPAN
    assert tr._import       # still waiting for a recorder


def test_a_device_span_is_an_annotation_whatever_the_flag(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    monkeypatch.setattr(tr, "TraceAnnotation", Annotation)
    with tr.device_span("executor.first_run"):
        pass
    fluid.set_flags({"FLAGS_tracing": True})
    with tr.device_span("serving.cache_alloc", draft=True) as s:
        inner = tr.current_span()
    assert seen == [("in", "executor.first_run"),
                    ("out", "executor.first_run"),
                    ("in", "serving.cache_alloc"),
                    ("out", "serving.cache_alloc")]
    assert inner is s and tr.current_span() is None
    got, = tr.records("serving.cache_alloc")
    assert got["attrs"] == {"draft": True}
    # its time is its record's, not the thread's phase tally's
    assert not getattr(tr._tls, "phases", None)


def test_a_set_up_root_says_what_the_fullest_device_holds(tmp_path,
                                                          monkeypatch):
    class Device:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    import jax

    fakes = [Device({"bytes_in_use": 10, "peak_bytes_in_use": 70}),
             Device({"bytes_in_use": 30, "peak_bytes_in_use": 40}),
             Device(None)]
    real = jax.local_devices
    # a place still asks for its backend's devices by name
    monkeypatch.setattr(jax, "local_devices",
                        lambda **kw: real(**kw) if kw else fakes)
    _trace(tmp_path)
    _train(steps=2)
    _serve()
    roots = [s for s in _spans()
             if s["parent"] is None or s["name"] == "executor.warmup"]
    assert len(roots) == 7
    for s in roots:
        full = s["name"] != "executor.step" or not s["attrs"]["cache_hit"]
        assert ("hbm_in_use_bytes" in s["attrs"]) is full, s
        if full:
            assert s["attrs"]["hbm_in_use_bytes"] == 30
            assert s["attrs"]["hbm_peak_bytes"] == 70


def test_a_backend_that_counts_no_memory_adds_no_attribute(tmp_path):
    _trace(tmp_path)
    with tr.span("root") as s:
        s.device_memory()           # the CPU's devices have no statistics
    assert "attrs" not in tr.records("root")[0]


# -- the benchmark's readers ----------------------------------------------------

def _read(name, obs):
    return bench_run.load_module("layer_metrics", name).read(obs)


def _values(kind):
    obs = {"kind": kind}
    return {name: _read(name, obs) for name in READERS}


def _run_of(kind):
    tr.imported(1000.0, 2500.0)
    return _train() if kind == "train" else _serve()


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_the_readers_split_a_cold_and_a_restored_run(tmp_path, kind):
    other = {"train": ("add_model_s.serve", "prewarm_s.serve"),
             "serve": ("executor_build_s.train",)}[kind]
    executables = 2      # start-up and step; two lane buckets
    _trace(tmp_path)
    _run_of(kind)
    cold = _values(kind)
    tr.reset()
    _run_of(kind)
    warm = _values(kind)
    for got in (cold, warm):
        assert {n for n, v in got.items() if v is None} == set(other)
        assert got["import_s.setup"] == 2.5
        # the union holds every part, and the import lies apart from them
        parts = [v for n, v in got.items()
                 if v is not None and n.endswith(("_s.serve", "_s.train"))]
        assert got["in_program_s.setup"] >= 2.5 + sum(parts) - 1e-6
    assert cold["executables_compiled.setup"] == executables
    assert cold["compile_s.setup"] > 0
    assert cold["cache_restore_s.setup"] < cold["compile_s.setup"]
    assert warm["executables_compiled.setup"] == 0
    assert warm["compile_s.setup"] == 0.0
    assert warm["cache_restore_s.setup"] > 0
    assert warm["in_program_s.setup"] < cold["in_program_s.setup"]


def test_the_readers_find_set_up_in_the_sink_once_the_ring_lost_it(tmp_path):
    _trace(tmp_path)
    _run_of("train")
    held = _values("train")
    tr.flush()
    tr._recent.clear()              # a long window pushed set-up out
    assert _values("train") == held
    # and the sink's rotated predecessor is read with it
    path = os.path.join(str(tmp_path / "tel"), "trace-%d.jsonl" % os.getpid())
    tr.reset()
    os.rename(path, path + ".1")
    assert _values("train") == held


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_for_a_program_that_recorded_nothing(name):
    fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry_dir": ""})
    assert _read(name, {"kind": "serve"}) is None
    assert _read(name, {"kind": "train"}) is None


def test_a_program_older_than_the_tree_reads_what_it_has(tmp_path):
    """The parent of this PR records ``executor.cache_restore`` and
    ``executor.compile`` as roots and nothing else of set-up: the three
    metrics that read them report, the others stay out of the line."""
    _trace(tmp_path)
    with tr.span("executor.cache_restore", key="k") as s:
        s.annotate(hit=False)
    with tr.span("executor.compile") as s:
        s.annotate(source="compiled")
    got = _values("serve")
    assert {n for n, v in got.items() if v is not None} == {
        "cache_restore_s.setup", "compile_s.setup",
        "executables_compiled.setup"}
    assert got["executables_compiled.setup"] == 1


@pytest.mark.parametrize("intervals,covered", [
    ([], 0.0),
    ([(0, 5)], 5.0),
    ([(0, 5), (5, 2)], 7.0),            # touching
    ([(0, 5), (1, 2)], 5.0),            # a child inside its parent
    ([(0, 5), (3, 4), (10, 1)], 8.0),   # overlapping, and one apart
])
def test_a_union_counts_no_second_twice(intervals, covered):
    records = [{"ts": int(a * 1e6), "dur": int(d * 1e6)}
               for a, d in intervals]
    assert setup_spans.union_seconds(records) == covered


def test_the_new_metrics_are_declared_with_their_cells():
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    train = [c for c in cells if c.startswith("bert_")]
    serve = [c for c in cells if c not in train]
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(READERS):]] \
        == list(READERS)
    for name in READERS:
        m = declared[name]
        assert (m["source"], m["moves"], m["better"]) \
            == ("program_span", "setup_s", "lower")
        want = {"train": train, "serve": serve}.get(
            name.rsplit(".", 1)[1], cells)
        assert m["workloads"] == want
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))


# -- the operator's view ---------------------------------------------------------

def test_trace_view_prints_the_tree_with_self_times(tmp_path, capsys):
    d = _trace(tmp_path)
    tr.imported(time.time() - 2.5, 2500.0)
    _train(steps=2)
    _serve()
    tr.flush()
    assert trace_view.main(["--telemetry_dir", d, "--setup"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].endswith("(pid %d)" % os.getpid())
    names = [(len(l[13:]) - len(l[13:].lstrip()), l.split()[2])
             for l in lines[1:]]
    assert names == (
        [(0, "setup.import")]
        + 2 * ([(0, "executor.step")]
               + [(2, n) for n in CHILDREN["compiled"]])
        + [(0, "serving.add_model"), (2, "serving.cache_alloc"),
           (2, "serving.lay_out"), (0, "serving.prewarm")]
        + len(BUCKETS) * ([(2, "executor.warmup")]
                          + [(4, n) for n in CHILDREN["compiled"][1:-1]]))
    # a parent's self time is its duration less its children's
    records = trace_view.load_dir(d)[0][2]
    spans = {r["sid"]: r for r in records if r.get("t") == "span"}
    warm, = [s for s in spans.values() if s["name"] == "serving.prewarm"]
    covered = sum(s["dur"] for s in spans.values()
                  if s["parent"] == warm["sid"])
    line, = [l for l in lines if " serving.prewarm " in l]
    assert "self %10.3f s" % ((warm["dur"] - covered) / 1e6) in line
    assert '"restored": 0' in line and "phases" not in "".join(lines)
    # the steps that hit are no part of the view
    assert sum("executor.step" in l for l in lines) == 2


def test_trace_view_still_wants_out_without_setup(tmp_path):
    d = _trace(tmp_path)
    with tr.span("x"):
        pass
    tr.flush()
    with pytest.raises(SystemExit):
        trace_view.main(["--telemetry_dir", d])


def test_bench_reads_its_compile_story_off_the_spans(tmp_path):
    import bench

    _trace(tmp_path)
    fluid.set_flags({"FLAGS_telemetry": True})
    _train(steps=1)
    cold = bench._telemetry_stats()
    assert cold["compile_ms_cold"] > 0 and cold["compile_ms_warm"] == 0
    tr.reset()
    _train(steps=1)
    warm = bench._telemetry_stats()
    assert warm["compile_ms_cold"] == 0 and warm["compile_ms_warm"] > 0
