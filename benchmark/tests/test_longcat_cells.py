"""The cell PR 61 added, rehearsed at tiny size on the CPU through the whole
command (and its chip check, both legs), ``longcat_cost``'s bytes and
operations against a count by hand and the numbers of ISSUE 61, the
configuration's file against the catalog row and its own cut, and the five
new readers on hand-made ``obs``: what each computes, and that a program
whose step records none of the attributes, a trace with no kernel of the
name, or a configuration without this source's keys (the parent of the PR
that added them, the other cells), gives nothing and does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import longcat_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as fp:
        return json.load(fp)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = _config("longcat-flash-chat-serve.json")
DOTS = _config("dots-vlm1-inst-serve.json")
CELL = "longcat_flash_chat_serve_decode_wide"
NEW = ("moe_zero_assignment_share.serve", "moe_experts_hit_step_spread.serve",
       "longcat_experts_roofline_share.serve",
       "longcat_latent_attention_roofline_share.serve",
       "longcat_stream_floor_share.serve")
REDUCED = ["num_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-chat-serve", "serve_zero_expert_latent_decode_wide", 1)
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
    # ISSUE 61's traffic, to the number
    assert {k: traffic[k] for k in (
        "clients", "lane_buckets", "kv_blocks", "ramp_s", "deadline_ms",
        "trace_seconds", "size_set", "schedule_seed", "check_requests")} == {
        "clients": 64, "lane_buckets": [64], "kv_blocks": 17472,
        "ramp_s": 24, "deadline_ms": 300000, "trace_seconds": 3,
        "size_set": 128, "schedule_seed": 1, "check_requests": 4}
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 32,
                                     "max": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 1024,
                                     "max": 4096}
    # the longest request on every lane at once, and 64 blocks more
    assert traffic["kv_blocks"] == 64 * (256 + 4096) // 16 + 64
    assert CONFIG["n_positions"] == 256 + 4096
    # the cell reports tokens/s and set-up (and the inter-token tail where
    # its spread admitted it); every per-layer metric that lists it moves one
    # of those
    reports = [m["name"] for m in BENCH["end_to_end"]
               if CELL in m.get("workloads", [CELL])]
    assert reports in (["serve_tokens_per_s", "setup_s"],
                       ["serve_tokens_per_s", "itl_p95_ms", "setup_s"])
    listed = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert all(m["moves"] in reports for m in listed)
    assert [m["name"] for m in listed][-5:] == list(NEW)
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in listed[-5:])


def test_the_configuration_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "LongCat-Flash-Chat")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["max_position_embeddings"],
            CONFIG["n_positions"]) == (4, 16, 16384, 4352, 4352)
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["num_experts_published"] \
        == row["config"]["n_routed_experts"]
    assert CONFIG["expert_parallel_chips"] * CONFIG["n_routed_experts"] \
        == CONFIG["num_experts_published"]
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert CONFIG["tiny"]["zero_expert_num"] == 8 \
        and CONFIG["tiny"]["n_positions"] == 64


def test_longcat_cost_against_a_count_by_hand():
    """ISSUE 61's arithmetic at the published widths."""
    c = CONFIG
    assert longcat_cost.latent_weight_bytes(c, 1) == 90572800 == (
        6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512
        + 512 * 64 * 256 + 64 * 128 * 6144)
    assert longcat_cost.dense_mlp_bytes(c, 1) == 226492416
    assert longcat_cost.router_bytes(c, 1) == 6144 * 768
    assert longcat_cost.expert_bytes(c, 1) == 37748736
    assert (longcat_cost.layers(c), longcat_cost.sublayers(c)) == (4, 8)
    assert longcat_cost.latent_block_bytes(c, 16) == 16 * 576 * 2
    assert longcat_cost.latent_floor_bytes_per_step(c, 8800, 16) \
        == 8 * 8800 * 18432
    assert longcat_cost.latent_flops_per_step(c, 1, 16) \
        == 8 * 16 * 64 * 2 * (1024 + 64)
    # a step's weights with 10 of 16 experts hit a layer: the issue's 5.3e9
    # of mixers, dense MLPs and head and 3.0e9 of hit experts
    weights = longcat_cost.weight_floor_bytes_per_step(c, 10, 64)
    assert weights == 2 * (
        8 * (90572800 + 226492416) + 4 * 6144 * 768 + 4 * 10 * 37748736
        + 6144 * 16384 + 64 * 6144)
    assert 5.3e9 < weights - 2 * 4 * 10 * 37748736 < 5.35e9
    assert 3.0e9 < longcat_cost.experts_hit_bytes_per_step(c, 10) < 3.05e9
    assert longcat_cost.stream_floor_bytes_per_step(c, 10, 64, 8800, 16) \
        == weights + 8 * 8800 * 18432


def _obs(config=CONFIG, **changed):
    attrs = {"lanes": 64, "kv_block_size": 16, "moe_experts_hit": 10.0,
             "latent_blocks_read": 8800, "moe_local_assignments": 16.0,
             "moe_absent_assignments": 496.0, "moe_zero_assignments": 256.0}
    hits = [8.0 + 0.25 * i for i in range(21)]            # 8.0 .. 13.0
    obs = {
        "kind": "serve", "config": config, "traced_steps": 200.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "decode_spans": [{"attrs": dict(attrs, moe_experts_hit=h)}
                         for h in hits],
        "profile": {"busy_s": 200 * 0.017, "op_seconds": {
            "%latent_attention.7": 200 * 0.0020,
            "%latent_attention.9": 200 * 0.0010, "%fusion.99": 200 * 0.008,
            "%moe_routed_experts.5": 200 * 0.0050}}}
    obs.update(changed)
    return obs


def test_every_new_reader_reads_what_it_says():
    read = {n: load_module("layer_metrics", n).read for n in NEW}
    obs = _obs()
    got = {n: read[n](obs) for n in NEW}
    assert got["moe_zero_assignment_share.serve"] == pytest.approx(
        100 * 256 / 768)
    # 21 steps from 8.0 to 13.0: the 95th less the 5th percentile
    assert got["moe_experts_hit_step_spread.serve"] == pytest.approx(4.5)
    # the median step hit 10.5
    assert got["longcat_experts_roofline_share.serve"] == pytest.approx(
        100 * 4 * 10.5 * 37748736 * 2 / 819e9 / 0.0050)
    floor = max(8 * 8800 * 18432 / 819e9,
                8 * 8800 * 16 * 64 * 2 * 1088 / 197e12)
    assert floor == 8 * 8800 * 18432 / 819e9          # bound by bytes here
    assert got["longcat_latent_attention_roofline_share.serve"] \
        == pytest.approx(100 * floor / 0.0030)
    assert got["longcat_stream_floor_share.serve"] == pytest.approx(
        100 * longcat_cost.stream_floor_bytes_per_step(
            CONFIG, 10.5, 64, 8800, 16) / 819e9 / 0.017)
    assert all(0 < v <= 100 for n, v in got.items()
               if n != "moe_experts_hit_step_spread.serve"), got


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = load_module("layer_metrics", name).read
    # the parent's spans: none of the attributes
    assert read(_obs(decode_spans=[{"attrs": {"lanes": 32,
                                              "kv_block_size": 16}}])) is None
    assert read(_obs(decode_spans=[])) is None
    assert read({"kind": "train"}) is None
    if name.startswith("longcat_"):
        # another configuration, no profile, a CPU rehearsal
        assert read(_obs(config=DOTS)) is None
        assert read(_obs(profile=None)) is None
        assert read(_obs(traced_steps=0)) is None
    if "roofline" in name:
        # a trace in which no kernel of the name ran
        assert read(_obs(profile={"busy_s": 1.0, "op_seconds": {
            "%fusion.1": 1.0}})) is None
    if name.startswith("moe_"):
        # a routed cell whose every output computes: a share's spans
        share = {"lanes": 32, "moe_experts_hit": 9.0,
                 "moe_local_assignments": 16.0,
                 "moe_absent_assignments": 240.0}
        assert read(_obs(decode_spans=[{"attrs": share}] * 40)) is None


def test_the_spread_wants_twenty_steps():
    read = load_module("layer_metrics",
                       "moe_experts_hit_step_spread.serve").read
    assert read(_obs(decode_spans=_obs()["decode_spans"][:19])) is None
    assert read(_obs(decode_spans=_obs()["decode_spans"][:20])) is not None


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
    assert 10 < line["metrics"]["moe_zero_assignment_share.serve"][
        "value"] < 60
    assert line["metrics"]["moe_experts_hit_step_spread.serve"]["value"] >= 0
    assert not any(n.startswith("longcat_") for n in line["metrics"])


@pytest.mark.parametrize("leg", [(), ("--engine",)])
def test_the_chip_check_rehearses_tiny_on_the_cpu(leg):
    got = _run(os.path.join(ROOT, "benchmark", "tests",
                            "chip_check_longcat.py"), "--tiny-on-cpu", *leg)
    assert got["not_a_chip_result"] is True and got["platform"] == "cpu"
    if leg:
        # the tiny bucket is 4 lanes: 4 and a quarter more
        assert got["leg"] == "engine" and got["requests"] == 5
    else:
        assert set(got["inside_tolerance"]) == {
            "served_bf16", "served_bf16_cut", "jnp_paths"} | {
            "control_" + c for c in (
                "no_q_scale", "no_kv_scale", "no_identity_part",
                "routed_read_at_h3", "gates_renormalised",
                "bf16_accumulation", "fp8_weights")}
        # a fault in structure reads several times the served path's error
        # at any size
        served = got["served_bf16_cut"]["rms_logit_error"]
        for name in ("no_q_scale", "no_kv_scale", "no_identity_part",
                     "routed_read_at_h3", "gates_renormalised"):
            assert got["control_" + name]["rms_logit_error"] > 3 * served
