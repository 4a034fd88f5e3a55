"""The cell PR 27 added, rehearsed at tiny size on the CPU through the
whole command, and the routed-expert readers on hand-made ``obs``: what
they compute, and that a program whose step records no routing (the parent
of the PR that added it, or the GPT-2 step) gives nothing and does not
raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import moe_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "olmoe-1b-7b-serve.json")))
MOE = ["moe_experts_hit_per_layer.serve", "moe_load_max_over_mean.serve",
       "moe_stream_floor_share.serve"]


def run_cell(cell, trace, cache, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 4321),
         "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-tiny-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


CELL = "olmoe_1b_7b_serve_decode_heavy"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_carries_the_cells_metrics(trace, tmp_path):
    cell = CELL
    line = run_cell(cell, trace, tmp_path / "cache")
    assert line["correct"] is True and line["failed"] == 0
    assert line["not_a_chip_result"] is True
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[group]
            if cell in m.get("workloads", [cell])
            and (not trace or m["source"] != "device_trace")}
    assert set(line["metrics"]) >= want
    if trace:
        # tiny: 4 lanes x 2 experts over 8
        assert 1 <= line["metrics"][MOE[0]]["value"] <= 8
        assert line["metrics"][MOE[1]]["value"] >= 1


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


ROUTED = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
          "peaks": {"hbm_bytes_per_s": 819e9},
          "profile": {"busy_s": 2.0},
          "decode_spans": [
              step(moe_experts_hit=62.0, moe_load_max=9.0,
                   moe_assignments=256.0),
              step(moe_experts_hit=63.5, moe_load_max=12.0,
                   moe_assignments=256.0),
              step(moe_experts_hit=60.0, moe_load_max=8.0,
                   moe_assignments=248.0)]}


def test_readers_on_routed_spans():
    assert reader(MOE[0])(ROUTED) == 62.0
    assert reader(MOE[1])(ROUTED) == 9.0 * 64 / 256
    # 8 layers x 62 experts x 3 x 2048 x 1024 x 2 B = 6.24e9 B: 7.62 ms at
    # 819 GB/s, of a 20 ms device step
    bytes_a_step = moe_cost.expert_stream_bytes_per_step(CONFIG, 62.0)
    assert bytes_a_step == 8 * 62 * 3 * 2048 * 1024 * 2
    assert reader(MOE[2])(ROUTED) == pytest.approx(
        100 * bytes_a_step / 819e9 / 0.020)
    assert 0 < reader(MOE[2])(ROUTED) < 100


def test_all_experts_hit_is_the_whole_expert_stack():
    assert moe_cost.expert_stream_bytes_per_step(CONFIG, 64) \
        == 8 * 64 * moe_cost.expert_bytes(CONFIG) == 6442450944


@pytest.mark.parametrize("name", MOE)
@pytest.mark.parametrize("obs", [
    dict(ROUTED, decode_spans=[step(), step()]),     # a step without experts
    dict(ROUTED, decode_spans=[]), {"kind": "serve"}, {"kind": "train"}],
    ids=["unrouted", "no_spans", "bare", "train"])
def test_readers_find_nothing(name, obs):
    assert reader(name)(obs) is None


def test_floor_share_needs_a_device_profile():
    assert reader(MOE[2])(dict(ROUTED, profile=None)) is None
    assert reader(MOE[2])(dict(ROUTED, traced_steps=None)) is None
