"""The cell PR 33 added, rehearsed at tiny size on the CPU through the
whole command, ``lfm2_cost``'s bytes against the numbers of ISSUE 33, the
configuration's file against the catalog row and its own cut, and the new
reader on hand-made ``obs``: what it computes, and that a program whose
step records no routing, or a configuration without this source's keys
(the parent of the PR that added it, the other cells), gives nothing and
does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import lfm2_cost, moe_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "lfm2-24b-a2b-serve.json")))
OLMOE = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "olmoe-1b-7b-serve.json")))
CELL = "lfm2_24b_a2b_serve_decode_heavy"
FLOOR = "moe_routed_stream_floor_share.serve"
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "max_position_embeddings"]


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-serve", "serve_hybrid_moe_decode_heavy", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    for kind, name in (("runners", CONFIG["runner"]),
                       ("models", CONFIG["model"]),
                       ("reference", CONFIG["reference"]),
                       ("layer_metrics", FLOOR)):
        assert load_module(kind, name) is not None, (kind, name)
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    moe = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "serve_moe_decode_heavy.json")))
    # OLMoE's and Granite's lengths: the cells differ in architecture alone
    same = ("clients", "lane_buckets", "kv_blocks", "deadline_ms", "ramp_s",
            "trace_seconds", "prompt_len", "output_len", "size_set",
            "schedule_seed", "check_requests")
    assert {k: traffic[k] for k in same} == {k: moe[k] for k in same}
    assert (traffic["clients"], traffic["lane_buckets"], traffic["kv_blocks"],
            traffic["ramp_s"], traffic["check_requests"]) \
        == (32, [32], 2048, 24, 4)
    serve = {m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]
             if CELL in m.get("workloads", [])}
    generic = {m["name"] for m in BENCH["per_layer"]
               if m["name"].endswith(".serve")
               and "gpt2_medium_serve_decode_heavy" in m["workloads"]}
    assert {"serve_tokens_per_s", "itl_p95_ms", FLOOR,
            "moe_experts_hit_per_layer.serve",
            "moe_load_max_over_mean.serve"} | generic <= serve
    assert not [n for n in serve if n.startswith("ssm_")
                or n == "moe_stream_floor_share.serve"]
    assert next(m for m in BENCH["per_layer"] if m["name"] == FLOOR) == {
        "name": FLOOR, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model + cache",
        "moves": "itl_p95_ms", "workloads": [CELL]}


def test_the_file_holds_the_catalog_rows_numbers_and_the_stated_cut():
    """Every number of the catalog row's ``config`` under its own key, but
    the reduced keys; the layers kept are published 0 and 2-9."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "LFM2-24B-A2B")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert sorted(differ) == sorted(REDUCED)
    assert CONFIG["source"] == row["source_url"]
    published = row["config"]["layer_types"]
    assert CONFIG["layer_types"] == [published[0]] + published[2:10]
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 9
    assert CONFIG["layer_types"].count("conv") == 7
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert {"tie_word_embeddings", "gate_denominator", "selection",
            "weights"} <= set(CONFIG["assumed"])
    assert CONFIG["departures"] == []
    for said in ("10.36e9 B", "10.5e9 B", "62%", "5.178e9 parameters"):
        assert said in CONFIG["reduced_why"]["num_hidden_layers"], said


def test_lfm2_cost_gives_the_issues_bytes():
    assert lfm2_cost.expert_bytes(CONFIG) == 3 * 2048 * 1536 * 2 == 18874368
    assert lfm2_cost.routed_layers(CONFIG) == 8
    # every expert of every routed layer: the 9.66e9 B the step streams
    assert lfm2_cost.routed_stream_floor_bytes_per_step(CONFIG, 64) \
        == 9663676416
    # the floor at 55.5 experts hit: 10.2 ms at 819 GB/s
    assert 10.1e-3 < lfm2_cost.routed_stream_floor_bytes_per_step(
        CONFIG, 55.5) / 819e9 < 10.3e-3
    # moe_cost reads this source's dense width and all nine layers: 8.6
    # times too much, which is why the cell is not on its metric's list
    assert moe_cost.expert_stream_bytes_per_step(CONFIG, 64) \
        == pytest.approx(9663676416 * (11776 / 1536) * (9 / 8))


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
    if trace:
        # tiny: 4 lanes x 2 experts over 8, means over the 3 routed layers
        assert 1 <= line["metrics"]["moe_experts_hit_per_layer.serve"][
            "value"] <= 8
        # no device profile on the CPU: the floor share is absent
        assert FLOOR not in line["metrics"]


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


ROUTED = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
          "peaks": {"hbm_bytes_per_s": 819e9}, "profile": {"busy_s": 1.5},
          "decode_spans": [step(moe_experts_hit=55.5), step(),
                           step(moe_experts_hit=56.0),
                           step(moe_experts_hit=54.0)]}


def test_reader_on_routed_spans():
    # 10.2 ms of a 15 ms device step
    got = reader(FLOOR)(ROUTED)
    assert got == pytest.approx(
        100 * 8 * 55.5 * 18874368 / 819e9 / 0.015)
    assert 60 < got < 72
    # with every expert hit, a step at the peak itself reads under 100
    full = dict(ROUTED, decode_spans=[step(moe_experts_hit=64.0)],
                profile={"busy_s": 100 * 10355901952 / 819e9})
    assert 90 < reader(FLOOR)(full) < 100


@pytest.mark.parametrize("obs", [
    dict(ROUTED, decode_spans=[step(), step()]),   # a step that routes nothing
    dict(ROUTED, decode_spans=[]), dict(ROUTED, profile=None),
    dict(ROUTED, traced_steps=None), dict(ROUTED, config=OLMOE),
    {"kind": "serve"}, {"kind": "train"}],
    ids=["unrouted", "no_spans", "no_profile", "no_steps", "olmoe_keys",
         "bare", "train"])
def test_reader_finds_nothing(obs):
    assert reader(FLOOR)(obs) is None


def test_chip_check_rehearses_here():
    """benchmark/tests/chip_check_lfm2.py at the tiny sizes: it runs to its
    end, every control is compared, and nothing it prints is a chip
    result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_lfm2.py"),
         "--tiny-on-cpu"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True
    assert set(line["inside_tolerance"]) == {
        "served_bf16", "control_no_expert_bias",
        "control_gates_not_renormalised", "control_stale_window",
        "control_whole_width_qk_norm", "control_fp8_weights"}
    # each control moves what it perturbs, tiny as the sizes are
    served = line["served_bf16"]
    # (at these sizes the tied head repeats a token, and the first layer's
    # inputs with it, so a stale first window reads the same: the later
    # layers' windows tell)
    assert line["control_stale_window"]["later_windows_relative_rms_error"] \
        > 10 * served["later_windows_relative_rms_error"]
    assert line["control_whole_width_qk_norm"][
        "cached_kv_relative_rms_error"] \
        > 5 * served["cached_kv_relative_rms_error"]
    assert line["control_fp8_weights"]["cached_kv_relative_rms_error"] \
        > 5 * served["cached_kv_relative_rms_error"]
    for name in ("no_expert_bias", "gates_not_renormalised"):
        assert line["control_" + name]["rms_logit_error"] \
            > 2 * served["rms_logit_error"]
