"""The readers of the two metrics of the one-ahead decode loop, each on a
hand-made ``obs``: what they compute, and that spans without the attribute
they read (the parent of the PR that added ``ahead``; a program older than
the phases) give nothing and do not raise."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_module  # noqa: E402

NAMES = ["dispatch_ahead_share.serve", "dispatch_ms_per_step.serve"]


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 2, "generated": 2}, **attrs)}


def test_ahead_and_dispatch_readers():
    obs = {"kind": "serve", "decode_spans": [
        step(ahead=True, gap_us=0, phases={"serving.dispatch": 3600,
                                           "serving.emit": 1200}),
        step(ahead=True, gap_us=0, phases={"serving.dispatch": 3400}),
        step(ahead=False, gap_us=900, phases={"serving.dispatch": 5000}),
        step(ahead=True, gap_us=0, phases={"serving.dispatch": 3500}),
        # a span closed before its dispatch phase ended (an error path)
        step(ahead=False, gap_us=40, phases={"serving.plan": 50})]}
    assert reader("dispatch_ahead_share.serve")(obs) == 60.0
    assert reader("dispatch_ms_per_step.serve")(obs) == 3.55


def test_the_parent_has_the_phase_and_not_the_attribute():
    # the loop that waits for a step's tokens before it plans the next:
    # its spans carry the phases (since PR 25) and no ``ahead``
    parent = {"kind": "serve", "decode_spans": [
        step(gap_us=1700, phases={"serving.dispatch": 3640}),
        step(gap_us=1800, phases={"serving.dispatch": 3700})]}
    assert reader("dispatch_ahead_share.serve")(parent) is None
    assert reader("dispatch_ms_per_step.serve")(parent) == 3.67


def test_a_window_never_ahead_reads_zero_not_nothing():
    obs = {"kind": "serve", "decode_spans": [step(ahead=False, gap_us=5),
                                             step(ahead=False, gap_us=7)]}
    assert reader("dispatch_ahead_share.serve")(obs) == 0.0
    assert reader("dispatch_ms_per_step.serve")(obs) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    # spans older than the phases and than ``ahead``
    {"kind": "serve", "decode_spans": [step(), step()]},
    {"kind": "serve"},
    {"kind": "serve", "decode_spans": []},
    {"kind": "train"},
    {"kind": "train", "decode_spans": [step(ahead=True, phases={
        "serving.dispatch": 1})]}])
def test_nothing_to_read_gives_none(name, obs):
    assert reader(name)(obs) is None


def test_the_entries_name_the_scheduler_and_the_serving_cells():
    import json

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    serving = [w["name"] for w in bench["workloads"]
               if w["traffic"].startswith("serve_")]
    for name in NAMES:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["layer"] == "scheduler"
        assert entry["moves"] == "itl_p95_ms"
        assert entry["source"] == "program_span"
        assert entry["workloads"] == serving
