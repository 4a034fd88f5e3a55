"""The cell PR 56 added, rehearsed at tiny size on the CPU through the whole
command, ``glm_cost``'s bytes and operations against a count by hand and the
numbers of ISSUE 56, the configuration's file against the catalog row and
its own cut, and the six new readers on hand-made ``obs``: what each
computes, and that a program whose step records none of the attributes, a
trace with no kernel of the name, or a configuration without this source's
keys (the parent of the PR that added them, the other cells), gives nothing
and does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import glm_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as fp:
        return json.load(fp)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = _config("glm-5-serve.json")
DOTS = _config("dots-vlm1-inst-serve.json")
CELL = "glm_5_serve_decode_long"
NEW = ("glm_index_scores_roofline_share.serve",
       "glm_sparse_attention_roofline_share.serve",
       "glm_experts_roofline_share.serve", "glm_stream_floor_share.serve",
       "index_sparse_lane_share.serve", "latent_rows_selected_share.serve")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers",
           "max_position_embeddings"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5-serve", "serve_sparse_latent_moe_decode_long", 1)
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
    small = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic",
        "serve_wide_window_moe_decode_long.json")))
    # SmallThinker's long mix to the number, but the deadline
    for key in ("clients", "lane_buckets", "kv_blocks", "ramp_s",
                "trace_seconds", "prompt_len", "output_len", "size_set",
                "schedule_seed", "check_requests"):
        assert traffic[key] == small[key], key
    assert traffic["deadline_ms"] == 300000
    # the cell reports tokens/s and set-up, and every per-layer metric that
    # lists it moves tokens/s
    reports = [m["name"] for m in BENCH["end_to_end"]
               if CELL in m.get("workloads", [CELL])]
    assert reports == ["serve_tokens_per_s", "setup_s"]
    listed = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert all(m["moves"] == "serve_tokens_per_s" for m in listed)
    assert [m["name"] for m in listed][-6:] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in listed[-6:])


def test_the_configuration_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "GLM-5")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"],
            CONFIG["num_nextn_predict_layers"],
            CONFIG["max_position_embeddings"], CONFIG["n_positions"]) == (
        6, 1, 16, 19360, 0, 12544, 12544)
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert CONFIG["tiny"]["index_topk"] == 8 \
        and CONFIG["tiny"]["n_positions"] == 64


def test_glm_cost_against_a_count_by_hand():
    """ISSUE 56's table at the published widths."""
    c = CONFIG
    assert glm_cost.latent_weight_bytes(c, 1) == 165022208 == (
        6144 * 2048 + 2048 + 2048 * 64 * 256 + 6144 * 576 + 512
        + 512 * 64 * (192 + 256) + 64 * 256 * 6144)
    assert glm_cost.indexer_weight_bytes(c, 1) == 9371904 == (
        2048 * 4096 + 6144 * 128 + 2 * 128 + 6144 * 32)
    assert glm_cost.expert_bytes(c, 1) == 37748736
    assert glm_cost.dense_layer_bytes(c, 1) == 226492416
    assert glm_cost.routed_layer_fixed_bytes(c, 1) \
        == 6144 * 256 + 37748736
    assert (glm_cost.layers(c), glm_cost.routed_layers(c)) == (6, 5)
    # a block of 16 tokens: 4,096 B of index keys a layer; a row's values
    # 1,152 B
    assert glm_cost.index_block_bytes(c, 16) == 4096
    assert glm_cost.latent_row_bytes(c) == 1152
    assert glm_cost.index_floor_bytes_per_step(c, 10000, 16) \
        == 6 * 10000 * 4096
    assert glm_cost.index_flops_per_step(c, 1, 16) \
        == 6 * 16 * 32 * (2 * 128 + 3)
    assert glm_cost.selected_floor_bytes_per_step(c, 65536, 32) \
        == 6 * (65536 * 1152 + 32 * 64 * (576 + 512) * 4)
    assert glm_cost.selected_flops_per_step(c, 65536) \
        == 6 * 65536 * 64 * 2 * (1024 + 64)
    # a step's weights with 10 of 16 experts hit a layer: ISSUE 56's 6.95e9
    weights = glm_cost.weight_floor_bytes_per_step(c, 10, 32)
    assert weights == 2 * (
        6 * (165022208 + 9371904) + 226492416
        + 5 * (6144 * 256 + 37748736) + 5 * 10 * 37748736
        + 6144 * 19360 + 32 * 6144)
    assert 6.9e9 < weights < 7.0e9
    assert glm_cost.stream_floor_bytes_per_step(c, 10, 32, 10000, 65536, 16) \
        == weights + 6 * 10000 * 4096 + 6 * 65536 * 1152


def _obs(config=CONFIG, **changed):
    attrs = {"lanes": 32, "kv_block_size": 16, "moe_experts_hit": 10.0,
             "index_blocks_read": 10000, "latent_rows_selected": 60000,
             "latent_rows_in_context": 160000, "sparse_lanes": 24}
    obs = {
        "kind": "serve", "config": config, "traced_steps": 200.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "decode_spans": [{"attrs": dict(attrs)} for _ in range(3)],
        "profile": {"busy_s": 200 * 0.015, "op_seconds": {
            "%index_scores.3": 200 * 0.0010, "%index_scores.4": 200 * 0.0005,
            "%latent_attention.7": 200 * 0.0008, "%sort.11": 200 * 0.0012,
            "%gather_fusion.2": 200 * 0.0010, "%fusion.99": 200 * 0.004,
            "%moe_routed_experts.5": 200 * 0.0050}}}
    obs.update(changed)
    return obs


def test_every_new_reader_reads_what_it_says():
    read = {n: load_module("layer_metrics", n).read for n in NEW}
    obs = _obs()
    got = {n: read[n](obs) for n in NEW}
    assert got["index_sparse_lane_share.serve"] == 75.0
    assert got["latent_rows_selected_share.serve"] == 37.5
    assert got["glm_index_scores_roofline_share.serve"] == pytest.approx(
        100 * 6 * 10000 * 4096 / 819e9 / 0.0015)
    floor = max(glm_cost.selected_floor_bytes_per_step(CONFIG, 60000, 32)
                / 819e9,
                glm_cost.selected_flops_per_step(CONFIG, 60000) / 197e12)
    assert got["glm_sparse_attention_roofline_share.serve"] == pytest.approx(
        100 * floor / (0.0008 + 0.0012 + 0.0010))
    assert got["glm_experts_roofline_share.serve"] == pytest.approx(
        100 * 5 * 10 * 37748736 * 2 / 819e9 / 0.0050)
    assert got["glm_stream_floor_share.serve"] == pytest.approx(
        100 * glm_cost.stream_floor_bytes_per_step(
            CONFIG, 10, 32, 10000, 60000, 16) / 819e9 / 0.015)
    assert all(0 < v <= 100 for v in got.values()), got


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = load_module("layer_metrics", name).read
    # the parent's spans: none of the attributes
    assert read(_obs(decode_spans=[{"attrs": {"lanes": 32,
                                              "kv_block_size": 16}}])) is None
    assert read(_obs(decode_spans=[])) is None
    assert read({"kind": "train"}) is None
    if name.startswith("glm_"):
        # another configuration, no profile, a CPU rehearsal
        assert read(_obs(config=DOTS)) is None
        assert read(_obs(profile=None)) is None
        assert read(_obs(traced_steps=0)) is None
    if "roofline" in name:
        # a trace in which no kernel of the name ran
        assert read(_obs(profile={"busy_s": 1.0, "op_seconds": {
            "%fusion.1": 1.0}})) is None


def test_the_tiny_rehearsal_prints_a_line_that_is_no_chip_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2600000031", "--seconds", "2",
         "--trace", "1", "--rehearse-tiny-on-cpu"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True and line["correct"] \
        and line["failed"] == 0
    # no device plane on the CPU: the span-read ones alone
    assert line["metrics"]["index_sparse_lane_share.serve"]["value"] > 50
    assert 0 < line["metrics"]["latent_rows_selected_share.serve"][
        "value"] < 100
    assert not any(n.startswith("glm_") for n in line["metrics"])
