"""The readers of the metrics that rest on the program's host phases, each
on a hand-made ``obs``: what they compute, and that a program without the
attributes (the parent of the PR that added them) gives nothing and does
not raise."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_module  # noqa: E402


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 2, "generated": 2}, **attrs)}


SERVE = {"kind": "serve", "decode_spans": [
    step(gap_us=9000, admitted=0, admit_wait_ms=[], admit_lock_wait_ms=[]),
    step(gap_us=11000, admitted=2, admit_wait_ms=[300.0, 500.0],
         admit_lock_wait_ms=[250.0, 150.0]),
    step(gap_us=40000, admitted=1, admit_wait_ms=[100.0],
         admit_lock_wait_ms=[20.0])]}
# what the parent's spans look like
SERVE_OLD = {"kind": "serve", "decode_spans": [step(), step()]}


def test_serving_readers():
    assert reader("host_gap_ms_per_step.serve")(SERVE) == 11.0
    assert reader("queue_wait_ms.serve")(SERVE) == 300.0
    assert reader("submit_lock_wait_ms.serve")(SERVE) == 140.0


@pytest.mark.parametrize("name", ["host_gap_ms_per_step.serve",
                                  "queue_wait_ms.serve",
                                  "submit_lock_wait_ms.serve"])
@pytest.mark.parametrize("obs", [SERVE_OLD, {"kind": "serve"},
                                 {"kind": "serve", "decode_spans": []},
                                 {"kind": "train"}])
def test_serving_readers_find_nothing(name, obs):
    assert reader(name)(obs) is None


def test_window_without_an_admission_reports_no_wait():
    quiet = {"kind": "serve", "decode_spans": [
        step(gap_us=5, admitted=0, admit_wait_ms=[], admit_lock_wait_ms=[])]}
    assert reader("queue_wait_ms.serve")(quiet) is None
    assert reader("host_gap_ms_per_step.serve")(quiet) == 0.005


def profile(*gaps):
    return {"window_s": 3.0, "busy_s": 2.5, "idle_gaps": [list(g)
                                                          for g in gaps]}


def test_idle_share_named_by_the_program():
    prof = profile(("serving.plan", 0.06), ("op_to_op_under_20us", 0.5),
                   ("serving.between_steps", 0.02), ("unknown", 0.01),
                   ("PjitFunction", 0.01))
    read = reader("idle_in_program_spans_share.serve")
    assert read({"kind": "serve", "profile": prof}) == pytest.approx(80.0)
    assert read({"kind": "train", "profile": prof}) is None
    assert read({"kind": "serve", "profile": None}) is None
    assert read({"kind": "serve", "profile": profile(
        ("op_to_op_under_20us", 0.5))}) is None
    train = reader("idle_in_program_spans_share.train")
    assert train({"kind": "train", "profile": profile(
        ("bench.exe_run", 0.1))}) == 0.0
    assert train({"kind": "train", "profile": profile(
        ("executor.fetch", 0.3), ("bench.exe_run", 0.1))}) \
        == pytest.approx(75.0)


@pytest.fixture()
def executor_spans(monkeypatch):
    """Stand-ins for the program's in-memory record of ``executor.step``."""
    from paddle_tpu.core import tracing

    def span(hit, host_us, **phases):
        return {"t": "span", "name": "executor.step", "attrs": {
            "cache_hit": hit, "host_us": host_us, "phases": phases}}

    def put(spans):
        monkeypatch.setattr(tracing, "records",
                            lambda name: list(spans) if
                            name == "executor.step" else [], raising=False)

    put.span = span
    return put


def test_training_readers(executor_spans):
    span = executor_spans.span
    executor_spans([
        span(False, 900000, **{"executor.prepare": 800000}),
        span(True, 6000, **{"executor.prepare": 5000}),
        span(True, 8000, **{"executor.prepare": 7000}),
        span(True, 50000, **{"executor.prepare": 7000})])
    assert reader("host_ms_per_step.train")({"kind": "train"}) == 8.0
    # one chip: no sharding phases, nothing to read
    assert reader("shard_ms_per_step.train")({"kind": "train"}) is None
    executor_spans([
        span(True, 50000, **{"executor.shard_feeds": 30000,
                             "executor.shard_params": 10000}),
        span(True, 52000, **{"executor.shard_feeds": 31000,
                             "executor.shard_params": 11000}),
        span(False, 900000, **{"executor.shard_feeds": 500000})])
    assert reader("shard_ms_per_step.train")({"kind": "train"}) == 41.0
    assert reader("host_ms_per_step.train")({"kind": "serve"}) is None


def test_training_readers_on_a_program_without_the_record(monkeypatch):
    from paddle_tpu.core import tracing

    monkeypatch.delattr(tracing, "records", raising=False)
    assert reader("host_ms_per_step.train")({"kind": "train"}) is None
    assert reader("shard_ms_per_step.train")({"kind": "train"}) is None
    # spans that carry no phases (a program older than them)
    monkeypatch.setattr(tracing, "records", lambda name: [
        {"t": "span", "name": name, "attrs": {"cache_hit": True}}],
        raising=False)
    assert reader("host_ms_per_step.train")({"kind": "train"}) is None
    assert reader("shard_ms_per_step.train")({"kind": "train"}) is None
