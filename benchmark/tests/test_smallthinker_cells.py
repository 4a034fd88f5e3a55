"""The cell PR 53 added: its files found by name, the configuration's file
against the catalog row and its own cut, ``smallthinker_cost``'s bytes
against the numbers of ISSUE 53, and the three new readers on hand-made
``obs``: what each computes, and that a program whose step records no
window attributes, a trace with no kernel of the name, or a configuration
without this source's keys (the parent of the PR that added them, the other
cells) gives nothing and does not raise."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import exaone_cost, smallthinker_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "smallthinker-21b-a3b-serve.json")))
EXAONE = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "k-exaone-236b-a23b-serve.json")))
CELL = "smallthinker_21b_a3b_serve_decode_long"
NEW = ("smallthinker_stream_floor_share.serve",
       "smallthinker_experts_roofline_share.serve",
       "kv_window_wrapped_lane_share.serve")
# the catalog row's ``config`` (architectures.jsonl), the layouts' 52 entries
# as their period
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-serve", "serve_wide_window_moe_decode_long", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    for kind, name in (("runners", CONFIG["runner"]),
                       ("models", CONFIG["model"]),
                       ("reference", CONFIG["reference"])) \
            + tuple(("layer_metrics", n) for n in NEW):
        assert load_module(kind, name) is not None, (kind, name)
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    assert (traffic["clients"], traffic["lane_buckets"], traffic["kv_blocks"],
            traffic["ramp_s"], traffic["check_requests"],
            traffic["size_set"], traffic["schedule_seed"],
            traffic["deadline_ms"], traffic["trace_seconds"]) \
        == (32, [32], 25120, 72, 4, 64, 1, 600000, 3)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 32,
                                     "max": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 4096,
                                     "max": 12288}
    # the longest request on every lane at once, and the scratch block
    assert 32 * -(-(256 + 12288) // 16) + 32 == 25120
    assert 256 + 12288 <= CONFIG["n_positions"]
    # the cell reports ``serve_tokens_per_s`` and not ``itl_p95_ms`` (whose
    # spread over seeds is the traffic's own, 4-5% against the 1% half its
    # bound allows: PERF.md section 6, PR 53), so its name is on the lists
    # of the metrics that move tokens/s and on its own three readers'
    listed = {m["name"]: m.get("moves", m["name"])
              for group in ("end_to_end", "per_layer")
              for m in BENCH[group] if CELL in m.get("workloads", [])}
    assert set(listed.values()) == {"serve_tokens_per_s"}
    assert set(listed) == set(NEW) | {
        "serve_tokens_per_s", "lanes_per_step.serve",
        "prefill_lane_share.serve", "ttft_ms_per_prompt_token.serve",
        "device_idle_share.serve", "queue_wait_ms.serve",
        "submit_lock_wait_ms.serve"}


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    assert CONFIG["num_hidden_layers"] == 8 \
        and CONFIG["num_hidden_layers_published"] == 52
    assert CONFIG["rope_layout"] == PUBLISHED["rope_layout"][:8] \
        == CONFIG["sliding_window_layout"]
    # the derived keys repeat the source's
    assert set(CONFIG["derived"]) == {"n_positions", "num_experts",
                                      "layer_types", "sliding_window"}
    assert CONFIG["n_positions"] == CONFIG["max_position_embeddings"]
    assert CONFIG["num_experts"] == CONFIG["moe_num_primary_experts"]
    assert CONFIG["sliding_window"] == CONFIG["sliding_window_size"]
    assert CONFIG["layer_types"] == [
        "sliding_attention" if w else "full_attention"
        for w in CONFIG["sliding_window_layout"]]
    assert {"router_input", "gate", "attention", "initializer_range",
            "weights", "precision", "kv_cache", "deployment"} \
        <= set(CONFIG["assumed"])


def test_the_costs_are_the_issues_bytes():
    cost = smallthinker_cost
    assert cost.attention_weight_bytes(CONFIG) == 2 * 20971520
    assert cost.expert_bytes(CONFIG) == 2 * 5898240
    # 61 of 64 experts hit in each of 8 layers: 5.8e9 B
    assert round(cost.experts_hit_bytes_per_step(CONFIG, 61) / 1e9, 2) == 5.76
    # weights of a step at 32 lanes: 6.9e9 B
    weights = cost.weight_floor_bytes_per_step(CONFIG, 61, 32)
    assert weights == 8 * (2 * 20971520 + 2 * 163840) + 8 * 61 * 11796480 \
        + 2 * 2560 * 151936 + 32 * 5120
    assert round(weights / 1e9, 1) == 6.9
    assert cost.kv_block_bytes(CONFIG, 16) == 32768
    # every lane at 4,096 positions: 256 blocks in each of 8 layers, 2.1e9 B
    kv = cost.kv_floor_bytes_per_step(CONFIG, 32 * 256, 6 * 32 * 256, 16)
    assert kv == 8 * 32 * 256 * 32768 and round(kv / 1e9, 1) == 2.1
    # ... and the accepted attention reader's count of the same blocks, from
    # the derived ``layer_types``
    assert exaone_cost.kv_floor_bytes_per_step(
        CONFIG, 32 * 256, 6 * 32 * 256, 16) == kv


def _obs(config=CONFIG, **attrs):
    base = {"lanes": 32, "kv_block_size": 16, "kv_blocks_read": 8192,
            "kv_window_blocks_read": 6 * 7000, "kv_window_blocks_full": 49152,
            "kv_window_lanes_wrapped": 14, "kv_window_chunks": 440,
            "moe_experts_hit": 61.0}
    base.update(attrs)
    base = {k: v for k, v in base.items() if v is not None}
    return {"kind": "serve", "config": config, "traced_steps": 200,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "profile": {"busy_s": 200 * 0.0125, "op_seconds": {
                "%moe_routed_experts.3": 200 * 0.0078,
                "%paged_attention.1": 200 * 0.003}},
            "decode_spans": [{"attrs": dict(base)} for _ in range(5)]}


def test_the_new_readers_read_the_cell_and_nothing_else():
    floor, experts, wrapped = (load_module("layer_metrics", n) for n in NEW)
    obs = _obs()
    bytes_ = smallthinker_cost.weight_floor_bytes_per_step(CONFIG, 61, 32) \
        + smallthinker_cost.kv_floor_bytes_per_step(CONFIG, 8192, 42000, 16)
    assert abs(floor.read(obs) - 100 * bytes_ / 819e9 / 0.0125) < 1e-9
    assert 70 < floor.read(obs) < 90
    assert abs(experts.read(obs) - 100 * (8 * 61 * 11796480 / 819e9)
               / 0.0078) < 1e-9
    assert wrapped.read(obs) == 100.0 * 14 / 32
    # the accepted readers the cell joins read it too
    attention = load_module("layer_metrics",
                            "paged_attention_roofline_share.serve")
    assert 0 < attention.read(obs) < 100
    assert load_module("layer_metrics", "kv_window_read_share.serve") \
        .read(obs) == 100.0 * 42000 / 49152
    # a program without the attribute (the parent), another model's keys, no
    # kernel of the name, no profile: nothing, and no exception
    assert wrapped.read(_obs(kv_window_lanes_wrapped=None)) is None
    assert floor.read(_obs(kv_window_blocks_read=None)) is None
    assert floor.read(_obs(config=EXAONE)) is None
    assert experts.read(_obs(config=EXAONE)) is None
    no_kernel = _obs()
    no_kernel["profile"]["op_seconds"] = {"%fusion.1": 1.0}
    assert experts.read(no_kernel) is None
    for reader in (floor, experts):
        assert reader.read(dict(_obs(), profile=None)) is None
    for reader in (floor, experts, wrapped):
        assert reader.read({"kind": "train"}) is None
