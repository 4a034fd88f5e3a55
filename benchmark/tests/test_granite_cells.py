"""The cell PR 31 added, rehearsed at tiny size on the CPU through the
whole command, ``ssm_cost``'s bytes against the numbers of ISSUE 31, and the
state-space readers on hand-made ``obs``: what they compute, and that a
program whose step records no state (the parent of the PR that added it, or
the GPT-2 and OLMoE steps) gives nothing and does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import ssm_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "granite-4.0-h-micro-serve.json")))
CELL = "granite_4_0_h_micro_serve_decode_heavy"
FLOOR, ROOFLINE = ("ssm_stream_floor_share.serve",
                   "ssm_update_roofline_share.serve")


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro-serve", "serve_ssm_decode_heavy", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["max_position_embeddings"]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    for kind, name in (("runners", CONFIG["runner"]),
                       ("models", CONFIG["model"]),
                       ("reference", CONFIG["reference"]),
                       ("layer_metrics", FLOOR),
                       ("layer_metrics", ROOFLINE)):
        assert load_module(kind, name) is not None, (kind, name)
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    assert (traffic["clients"], traffic["lane_buckets"], traffic["kv_blocks"],
            traffic["ramp_s"], traffic["check_requests"]) \
        == (32, [32], 2048, 24, 4)
    serve = {m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]
             if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "itl_p95_ms", FLOOR, ROOFLINE,
            "kv_blocks_read_share.serve"} <= serve
    assert not [n for n in serve if n.startswith("moe_")]


def test_the_file_holds_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` under its own key, but
    the one reduced key."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "granite-4.0-h-micro")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert differ == ["max_position_embeddings"]
    assert CONFIG["source"] == row["source_url"]


def test_ssm_cost_gives_the_issues_bytes():
    assert ssm_cost.mamba_layers(CONFIG) == 36
    assert ssm_cost.state_bytes_per_sequence_layer(CONFIG) == 2097152
    assert ssm_cost.state_traffic_bytes_per_step(CONFIG, 32) == 4831838208
    assert ssm_cost.mixer_weight_bytes(CONFIG) == 1861000704
    assert ssm_cost.ssm_stream_bytes_per_step(CONFIG, 32) \
        == 4831838208 + 1861000704
    # 8.2 ms at 819 GB/s
    assert 8.1e-3 < ssm_cost.ssm_stream_bytes_per_step(CONFIG, 32) / 819e9 \
        < 8.3e-3


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


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_carries_the_cells_metrics(trace, tmp_path):
    line = run_cell(CELL, trace, tmp_path / "cache")
    assert line["correct"] is True and line["failed"] == 0
    assert line["not_a_chip_result"] is True
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[group]
            if CELL in m.get("workloads", [CELL])
            and (not trace or m["source"] != "device_trace")}
    assert set(line["metrics"]) >= want
    # no device profile on the CPU: the two state-space shares are absent
    assert FLOOR not in line["metrics"] and ROOFLINE not in line["metrics"]


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


STATE = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
         "peaks": {"hbm_bytes_per_s": 819e9},
         "profile": {"busy_s": 1.7, "op_seconds": {
             "%ssm_state_update.5": 0.4, "ssm_state_update.41": 0.3,
             "%fusion.18": 0.5, "%paged_attention.1": 0.1}},
         "decode_spans": [step(ssm_state_lanes=32, ssm_state_bytes=1),
                          step(ssm_state_lanes=31, ssm_state_bytes=1),
                          step(ssm_state_lanes=32, ssm_state_bytes=1)]}


def test_readers_on_state_spans():
    # 8.17 ms of a 17 ms device step; 5.90 ms of the kernel's 7 ms
    assert reader(FLOOR)(STATE) == pytest.approx(
        100 * (4831838208 + 1861000704) / 819e9 / 0.017)
    assert reader(ROOFLINE)(STATE) == pytest.approx(
        100 * 4831838208 / 819e9 / 0.007)
    assert 30 < reader(FLOOR)(STATE) < 100
    assert 30 < reader(ROOFLINE)(STATE) < 100


@pytest.mark.parametrize("name", [FLOOR, ROOFLINE])
@pytest.mark.parametrize("obs", [
    dict(STATE, decode_spans=[step(), step()]),      # a step with no state
    dict(STATE, decode_spans=[]), dict(STATE, profile=None),
    dict(STATE, traced_steps=None), {"kind": "serve"}, {"kind": "train"}],
    ids=["stateless", "no_spans", "no_profile", "no_steps", "bare", "train"])
def test_readers_find_nothing(name, obs):
    assert reader(name)(obs) is None


def test_roofline_needs_the_kernel_in_the_profile():
    """The parent's step, or the XLA form: no execution under the kernel's
    name."""
    profile = {"busy_s": 1.7, "op_seconds": {"%fusion.18": 0.5}}
    assert reader(ROOFLINE)(dict(STATE, profile=profile)) is None
    assert reader(FLOOR)(dict(STATE, profile=profile)) is not None
