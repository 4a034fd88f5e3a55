"""The cell PR 64 added, rehearsed at tiny size on the CPU through the whole
command (and its chip check, all three legs), ``solar_cost``'s bytes against
a count by hand and the numbers of ISSUE 64, the configuration's file against
the catalog row and its own cut, and the four new readers on hand-made
``obs``: what each computes, and that a program whose step records none of
the attributes, a trace with no kernel of the name, or a configuration
without this source's keys (the parent of the PR that added them, the other
cells), gives nothing and does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import solar_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as fp:
        return json.load(fp)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = _config("solar-open2-250b-serve.json")
KIMI = _config("kimi-linear-48b-a3b-serve.json")
CELL = "solar_open2_250b_serve_decode_long"
NEW = ("solar_kda_state_roofline_share.serve",
       "solar_paged_attention_roofline_share.serve",
       "solar_experts_roofline_share.serve",
       "solar_stream_floor_share.serve")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-serve", "serve_linear_gqa_moe_decode_long", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry == BENCH["configs"][-1]
    assert entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    for kind, name in (("runners", CONFIG["runner"]),
                       ("models", CONFIG["model"]),
                       ("reference", CONFIG["reference"])) \
            + tuple(("layer_metrics", n) for n in NEW):
        assert load_module(kind, name) is not None, (kind, name)
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    # ISSUE 64's traffic, to the number
    assert {k: traffic[k] for k in (
        "clients", "lane_buckets", "kv_blocks", "ramp_s", "deadline_ms",
        "trace_seconds", "size_set", "schedule_seed", "check_requests")} == {
        "clients": 64, "lane_buckets": [64], "kv_blocks": 25664,
        "ramp_s": 24, "deadline_ms": 300000, "trace_seconds": 3,
        "size_set": 128, "schedule_seed": 1, "check_requests": 4}
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 32,
                                     "max": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 6144}
    # the longest request on every lane at once, and 64 blocks more
    assert traffic["kv_blocks"] == 64 * (256 + 6144) // 16 + 64
    assert CONFIG["n_positions"] >= 256 + 6144
    # the cell reports tokens/s and set-up (and the inter-token tail where
    # its spread admitted it); every per-layer metric that lists it moves one
    # of those
    reports = [m["name"] for m in BENCH["end_to_end"]
               if CELL in m.get("workloads", [CELL])]
    assert reports in (["serve_tokens_per_s", "setup_s"],
                       ["serve_tokens_per_s", "itl_p95_ms", "setup_s"])
    listed = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert all(m["moves"] in reports for m in listed)
    assert [m["name"] for m in listed][-4:] == list(NEW)
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in listed[-4:])


def test_the_configuration_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Solar-Open2-250B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["max_position_embeddings"],
            CONFIG["n_positions"]) == (8, 20, 24576, 8192, 8192)
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["num_experts_published"] \
        == row["config"]["n_routed_experts"]
    assert CONFIG["expert_parallel_chips"] * CONFIG["n_routed_experts"] \
        == CONFIG["num_experts_published"]
    assert CONFIG["published_layers"] == list(range(8))
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert CONFIG["assumed"] and CONFIG["departures"]
    assert CONFIG["tiny"]["n_positions"] == 64


def test_solar_cost_against_a_count_by_hand():
    """ISSUE 64's arithmetic at the published widths."""
    c = CONFIG
    assert solar_cost.kda_weight_bytes(c, 1) == 137732288 == (
        4096 * 24576 + 4 * 24576 + 4096 * 320 + 2 * 128 * 8192 + 8192 + 64
        + 128 + 8192 * 4096)
    assert solar_cost.gqa_weight_bytes(c, 1) == 109051904 == (
        3 * 33554432 + 2 * 4194304)
    assert solar_cost.expert_bytes(c, 1) == 15728640
    assert solar_cost.routed_layer_fixed_bytes(c, 1) == 4096 * 320 + 15728640
    assert (solar_cost.gqa_layers(c), solar_cost.kda_layers(c),
            solar_cost.routed_layers(c)) == (2, 6, 8)
    assert solar_cost.state_bytes_per_sequence_layer(c) == 4194304
    assert solar_cost.state_traffic_bytes_per_step(c, 64) \
        == 2 * 64 * 6 * 4194304                          # 3.2e9 B
    assert solar_cost.kv_block_bytes(c, 16) == 65536
    assert solar_cost.kv_floor_bytes_per_step(c, 8800, 16) \
        == 2 * 8800 * 65536
    # a step's weights with 16 of 20 experts hit a layer: the issue's 2.6e9
    # of mixers, routers, shared experts and head and 4.0e9 of hit experts
    weights = solar_cost.weight_floor_bytes_per_step(c, 16, 64)
    assert weights == 2 * (
        6 * 137732288 + 2 * 109051904 + 8 * (4096 * 320 + 15728640)
        + 8 * 16 * 15728640 + 4096 * 24576 + 64 * 4096)
    assert 2.55e9 < weights - 2 * 8 * 16 * 15728640 < 2.65e9
    assert 4.0e9 < solar_cost.experts_hit_bytes_per_step(c, 16) < 4.05e9
    assert solar_cost.stream_floor_bytes_per_step(c, 16, 64, 64, 8800, 16) \
        == weights + 2 * 64 * 6 * 4194304 + 2 * 8800 * 65536


def _obs(config=CONFIG, **changed):
    attrs = {"lanes": 64, "kv_block_size": 16, "moe_experts_hit": 16.0,
             "kv_blocks_read": 8800, "kda_state_lanes": 64,
             "moe_local_assignments": 32.0, "moe_absent_assignments": 480.0}
    # 21 steps a second apart: the last two seconds hold three of them
    obs = {
        "kind": "serve", "config": config, "traced_steps": 130.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "decode_spans": [{"ts": 1e6 * i, "attrs": dict(
            attrs, kv_blocks_read=8000 + 40 * i, moe_experts_hit=14.0 + 0.2 * i)}
            for i in range(21)],
        "profile": {"busy_s": 130 * 0.021, "op_seconds": {
            "%kda_state_update.7": 130 * 0.0030,
            "%kda_state_update.9": 130 * 0.0021, "%fusion.99": 130 * 0.008,
            "%paged_attention.3": 130 * 0.0022,
            "%moe_routed_experts.5": 130 * 0.0052}}}
    obs.update(changed)
    return obs


def test_every_new_reader_reads_what_it_says():
    read = {n: load_module("layer_metrics", n).read for n in NEW}
    obs = _obs()
    got = {n: read[n](obs) for n in NEW}
    assert got["solar_kda_state_roofline_share.serve"] == pytest.approx(
        100 * 2 * 64 * 6 * 4194304 / 819e9 / 0.0051)
    # the window's last two seconds: steps 18-20, median blocks 8760
    assert [a["kv_blocks_read"] for a in solar_cost.late_attrs(
        obs, ("kv_blocks_read",))] == [8720, 8760, 8800]
    assert got["solar_paged_attention_roofline_share.serve"] \
        == pytest.approx(100 * 2 * 8760 * 65536 / 819e9 / 0.0022)
    # the whole window's median step hit 16.0
    assert got["solar_experts_roofline_share.serve"] == pytest.approx(
        100 * 8 * 16.0 * 15728640 * 2 / 819e9 / 0.0052)
    assert got["solar_stream_floor_share.serve"] == pytest.approx(
        100 * solar_cost.stream_floor_bytes_per_step(
            CONFIG, 17.8, 64, 64, 8760, 16) / 819e9 / 0.021)
    assert all(0 < v <= 100 for v in got.values()), got


def test_a_step_that_fell_to_the_gather_reads_zero_not_a_share():
    read = load_module("layer_metrics",
                       "solar_kda_state_roofline_share.serve").read
    assert read(_obs(profile={"busy_s": 1.0, "op_seconds": {
        "%fusion.1": 1.0}})) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = load_module("layer_metrics", name).read
    # the parent's spans: none of the attributes
    assert read(_obs(decode_spans=[{"ts": 0, "attrs": {
        "lanes": 32, "kv_block_size": 16}}])) is None
    assert read(_obs(decode_spans=[])) is None
    assert read({"kind": "train"}) is None
    # another configuration, no profile, a CPU rehearsal
    assert read(_obs(config=KIMI)) is None
    assert read(_obs(profile=None)) is None
    assert read(_obs(traced_steps=0)) is None
    if "roofline" in name and "kda" not in name:
        # a trace in which no kernel of the name ran
        assert read(_obs(profile={"busy_s": 1.0, "op_seconds": {
            "%fusion.1": 1.0}})) is None


def _run(*argv):
    out = subprocess.run(
        [sys.executable] + list(argv),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_tiny_rehearsal_prints_a_line_that_is_no_chip_result():
    line = _run(os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                CELL, "--seed", "2600000031", "--seconds", "2", "--trace",
                "1", "--rehearse-tiny-on-cpu")
    assert line["not_a_chip_result"] is True and line["correct"] \
        and line["failed"] == 0
    # no device plane on the CPU: the span-read ones alone
    assert 0 < line["metrics"]["lanes_per_step.serve"]["value"] <= 4
    assert line["metrics"]["prefill_lane_share.serve"]["value"] > 0
    assert not any(n.startswith("solar_") for n in line["metrics"])


@pytest.mark.parametrize("leg", [(), ("--engine",), ("--kernel",)])
def test_the_chip_check_rehearses_tiny_on_the_cpu(leg):
    got = _run(os.path.join(ROOT, "benchmark", "tests",
                            "chip_check_solar.py"), "--tiny-on-cpu", *leg)
    assert got["not_a_chip_result"] is True and got["platform"] == "cpu"
    if leg == ("--engine",):
        # the tiny bucket is 4 lanes: 4 and a quarter more
        assert got["leg"] == "engine" and got["requests"] == 5
    elif leg:
        assert got["leg"] == "kernel" and got["path"] == "gather" \
            and got["ok"]
    else:
        assert set(got["inside_tolerance"]) == {
            "served_bf16", "served_bf16_cut", "jnp_paths"} | {
            "control_" + c for c in (
                "slot_not_reset", "bf16_state", "beta_not_doubled",
                "no_attention_gate", "rotation_applied",
                "gates_not_renormalised", "fp8_weights")}
        # a fault in structure reads several times the served path's error
        # at any size
        served = got["served_bf16_cut"]["rms_logit_error"]
        for name in ("beta_not_doubled", "no_attention_gate",
                     "rotation_applied", "gates_not_renormalised",
                     "slot_not_reset"):
            assert got["control_" + name]["rms_logit_error"] > 3 * served
