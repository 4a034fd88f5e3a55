"""The readers of what became of a step's stream chunks on their way to
their readers (``period_us``, ``deliver_us``, ``turnaround_us``, ``late_us``
and ``stream_replies`` of the ``serving.decode_step`` span), each on a
hand-made ``obs``: what they compute, that a span without the attribute or
an empty window gives nothing and does not raise; and the whole command at
tiny size on the CPU printing all four."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_module  # noqa: E402

NAMES = ["step_period_tail_ms.serve", "stream_deliver_tail_ms.serve",
         "stream_reader_tail_ms.serve", "stream_late_share.serve"]


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 2, "generated": 2}, **attrs)}


def test_the_tails_are_p95_less_p50_over_every_value_in_the_window():
    # 0, 100, ... 10,000 us: p50 5,000, p95 9,500, one value a span
    periods = [step(period_us=100 * k) for k in range(101)]
    assert reader("step_period_tail_ms.serve")(
        {"kind": "serve", "decode_spans": periods}) == pytest.approx(4.5)
    # the same values, several a span and none on some: every reply counts
    # once, whichever span carries it
    spans = [step(deliver_us=[100 * k for k in range(0, 50)],
                  turnaround_us=[200 * k for k in range(0, 40)]),
             step(deliver_us=[], turnaround_us=[]),
             step(),                # the first iteration: nothing to read
             step(deliver_us=[100 * k for k in range(50, 101)],
                  turnaround_us=[200 * k for k in range(40, 101)])]
    obs = {"kind": "serve", "decode_spans": spans}
    assert reader("stream_deliver_tail_ms.serve")(obs) == pytest.approx(4.5)
    assert reader("stream_reader_tail_ms.serve")(obs) == pytest.approx(9.0)
    assert reader("step_period_tail_ms.serve")(obs) is None


def test_the_late_share_is_late_replies_over_replies():
    obs = {"kind": "serve", "decode_spans": [
        step(stream_replies=30, late_us=[12, 800, 2500]),
        step(stream_replies=32, late_us=[]),
        step(),
        step(stream_replies=18, late_us=[40])]}
    assert reader("stream_late_share.serve")(obs) == pytest.approx(5.0)
    none_late = {"kind": "serve", "decode_spans": [
        step(stream_replies=32, late_us=[])]}
    assert reader("stream_late_share.serve")(none_late) == 0.0
    # replies there were none of: no share
    assert reader("stream_late_share.serve")({"kind": "serve", "decode_spans": [
        step(stream_replies=0, late_us=[])]}) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    # the parent's spans: the phases and ``published``, none of these
    {"kind": "serve", "decode_spans": [
        step(published=29, phases={"serving.emit": 1200}), step()]},
    {"kind": "serve"},
    {"kind": "serve", "decode_spans": []},
    {"kind": "train"}])
def test_nothing_to_read_gives_none(name, obs):
    assert reader(name)(obs) is None


def test_every_entry_lists_the_serving_cells_and_has_its_file():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    serving = [w["name"] for w in bench["workloads"] if "_serve_" in w["name"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == serving
        assert entries[name]["moves"] == "itl_p95_ms"
        assert entries[name]["source"] == "program_span"
        assert reader(name) is not None


def test_a_traced_tiny_run_prints_all_four(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2_medium_serve_decode_heavy",
         "--seed", str(2 ** 31 + 5151), "--seconds", "2", "--trace", "1",
         "--rehearse-tiny-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["not_a_chip_result"] is True
    for name in NAMES:
        value = line["metrics"][name]["value"]
        assert value == value and 0.0 <= value < 1e6, (name, value)
    assert line["metrics"]["stream_late_share.serve"]["value"] <= 100.0
    assert line["metrics"]["recompiles.serve"]["value"] == 0.0
