"""The cell PR 38 added, rehearsed at tiny size on the CPU through the
whole command, ``exaone_cost``'s bytes against the numbers of ISSUE 38, the
configuration's file against the catalog row and its own cut, and the four
new readers on hand-made ``obs``: what each computes, and that a program
whose step records no window or share attributes, a trace with no kernel of
the name, or a configuration without this source's keys (the parent of the
PR that added them, the other cells), gives nothing and does not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import exaone_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "k-exaone-236b-a23b-serve.json")))
LFM2 = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "lfm2-24b-a2b-serve.json")))
CELL = "k_exaone_236b_a23b_serve_decode_long"
NEW = ("kv_window_read_share.serve", "moe_local_assignment_share.serve",
       "exaone_stream_floor_share.serve",
       "paged_attention_roofline_share.serve")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "mtp_layer_types",
           "mtp_sliding_windows", "max_position_embeddings"]


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone-236b-a23b-serve", "serve_window_moe_decode_long", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == REDUCED == CONFIG["reduced"]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
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
        == (32, [32], 12832, 24, 4, 64, 1, 300000, 3)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 32,
                                     "max": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 6144}
    # the longest request on every lane at once, and the scratch block
    assert traffic["kv_blocks"] == 32 * (256 + 6144) // 16 + 32
    assert 256 + 6144 <= CONFIG["n_positions"]
    serve = {m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]
             if CELL in m.get("workloads", [])}
    # end to end the cell reports what the other serving cells do, and every
    # ``.serve`` per-layer metric that all of them report, with the two of
    # the routed ones: ISSUE 38's lists, whatever the spread of itl_p95_ms
    # turns out to be on the driver's runs (PERF.md section 6, PR 38)
    assert {"serve_tokens_per_s", "itl_p95_ms"} <= serve
    shared = {m["name"] for m in BENCH["per_layer"]
              if m["name"].endswith(".serve")
              and "gpt2_medium_serve_decode_heavy" in m["workloads"]}
    assert len(shared) == 16
    assert shared | set(NEW) | {"moe_experts_hit_per_layer.serve",
                                "moe_load_max_over_mean.serve"} \
        == serve - {"serve_tokens_per_s", "itl_p95_ms"}
    for name in ("recompiles.serve", "device_busy_ms_per_step.serve",
                 "decode_step_ms.serve", "kv_blocks_read_share.serve"):
        assert name in shared
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%" \
                and m["layer"] == "model + cache" \
                and m["moves"] == "itl_p95_ms"
            # fewer blocks of the context fetched and fewer assignments on
            # this chip are a shorter step; a share of a floor is better
            # the higher
            assert m["better"] == ("lower" if m["name"] in NEW[:2]
                                   else "higher")
    assert len(BENCH["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_file_holds_the_catalog_rows_numbers_and_the_stated_cut():
    """Every number of the catalog row's ``config`` under its own key, but
    the reduced keys; the layers kept are published 0-4; no width is
    among the reduced."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "K-EXAONE-236B-A23B")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert sorted(differ) == sorted(REDUCED)
    assert CONFIG["source"] == row["source_url"]
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert CONFIG[key] == row["config"][key][:5]
    assert CONFIG["layer_types"].count("sliding_attention") == 4
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    for key, want in (("hidden_size", 6144), ("num_attention_heads", 64),
                      ("num_key_value_heads", 8), ("head_dim", 128),
                      ("sliding_window", 128), ("intermediate_size", 18432),
                      ("moe_intermediate_size", 2048),
                      ("num_experts_per_tok", 8),
                      ("routed_scaling_factor", 2.5)):
        assert CONFIG[key] == row["config"][key] == want
    # the published counts beside the held ones, and the deployment
    assert (CONFIG["num_experts"], CONFIG["num_experts_published"],
            CONFIG["first_expert"], CONFIG["expert_parallel_chips"]) \
        == (16, row["config"]["num_experts"], 0, 8)
    assert (CONFIG["vocab_size"], CONFIG["vocab_size_published"]) \
        == (row["config"]["vocab_size"] // 8, row["config"]["vocab_size"])
    assert CONFIG["num_hidden_layers_published"] == 48
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert {"post_norm", "rope", "router", "expert_bias_std", "weights",
            "deployment"} <= set(CONFIG["assumed"])
    assert "8 chips share each layer" in CONFIG["assumed"]["deployment"]
    assert CONFIG["departures"] == []
    for said in ("7.42e9 B", "43.9%", "3.712e9 parameters"):
        assert said in CONFIG["reduced_why"]["num_hidden_layers"], said


def test_exaone_cost_gives_the_issues_bytes():
    assert exaone_cost.attention_weight_bytes(CONFIG) == 113246208 * 2
    assert exaone_cost.expert_bytes(CONFIG) == 3 * 6144 * 2048 * 2 \
        == 75497472
    assert exaone_cost.sparse_layers(CONFIG) == 4
    assert exaone_cost.kv_block_bytes(CONFIG, 16) == 65536
    # every held expert hit: all the weights, 7.42e9 B, less what a step
    # need not touch (19,168 rows of the embedding, norms and biases)
    whole = exaone_cost.weight_floor_bytes_per_step(CONFIG, 16, 32)
    assert 7.42e9 - 19200 * 6144 * 2 - 1e6 < whole < 7.42e9
    # the issue's reckoning at 13.8 experts hit: experts 4.2e9, attention
    # weights 1.1e9, dense MLP 0.7e9, shared 0.3e9, head 0.24e9
    at = exaone_cost.weight_floor_bytes_per_step(CONFIG, 13.8, 32)
    assert at == pytest.approx(
        4 * 13.8 * 75497472 + 5 * 226492416 + 679477248 + 4 * 75497472
        + 4 * 6144 * 128 * 2 + 6144 * 19200 * 2 + 32 * 6144 * 2)
    assert 6.4e9 < at < 6.7e9
    # K and V: 32 lanes at 4,000 positions in the global layer, 9 blocks a
    # lane in each of the four window layers
    kv = exaone_cost.kv_floor_bytes_per_step(CONFIG, 32 * 250, 4 * 32 * 9, 16)
    assert kv == (8000 + 1152) * 65536
    assert 0.5e9 < kv < 0.7e9


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
        value = lambda name: line["metrics"][name]["value"]
        # tiny, on the gather: a ring of 2 blocks (window 8 in blocks of
        # 16) of a table of 6
        assert value("kv_window_read_share.serve") == pytest.approx(100 / 3)
        # 4 of 16 experts held, 4 a token
        assert 5 < value("moe_local_assignment_share.serve") < 60
        # no device profile on the CPU: the two shares of a peak are absent
        assert not set(NEW[2:]) & set(line["metrics"])


def test_a_program_without_the_block_fails_at_once(tmp_path):
    """The parent of PR 38 has no ``paddle_tpu/models/exaone_moe.py``: the
    model file imports it first, so the command fails before any weight,
    engine or server exists."""
    code = ("import sys, importlib.abc\n"
            "class Gone(importlib.abc.MetaPathFinder):\n"
            "    def find_spec(self, name, path, target=None):\n"
            "        if name == 'paddle_tpu.models.exaone_moe':\n"
            "            raise ModuleNotFoundError(name)\n"
            "sys.meta_path.insert(0, Gone())\n"
            "sys.argv = ['run.py', '--workload', %r, '--seed', '1',\n"
            "            '--seconds', '1', '--rehearse-tiny-on-cpu']\n"
            "import runpy\n"
            "runpy.run_path(%r, run_name='__main__')\n"
            % (CELL, os.path.join(ROOT, "benchmark", "run.py")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "exaone_moe" in out.stderr and "correct" not in out.stdout


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


def served(**attrs):
    return step(**dict(
        {"kv_blocks_read": 8000, "kv_table_slots": 32 * 512,
         "kv_window_blocks_read": 1152, "kv_window_blocks_full": 32000,
         "kv_window_blocks_held": 280, "kv_block_size": 16,
         "moe_experts_hit": 13.75, "moe_assignments": 32.0,
         "moe_local_assignments": 32.0, "moe_absent_assignments": 224.0},
        **attrs))


OBS = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
       "peaks": {"hbm_bytes_per_s": 819e9},
       "profile": {"busy_s": 0.9, "op_seconds": {
           "%paged_attention.1": 0.02, "%paged_attention.7": 0.08,
           "%moe_routed_experts.2": 0.5, "%fusion.3": 0.2}},
       "decode_spans": [served(), step(), served(kv_blocks_read=8008),
                        served(kv_blocks_read=7992)]}


def test_readers_on_served_spans():
    assert reader(NEW[0])(OBS) == pytest.approx(100 * 1152 / 32000)   # 3.6
    assert reader(NEW[1])(OBS) == pytest.approx(12.5)
    bytes_ = exaone_cost.weight_floor_bytes_per_step(CONFIG, 13.75, 32) \
        + (8000 + 1152) * 65536
    # 8.8 ms of a 9 ms device step
    assert reader(NEW[2])(OBS) == pytest.approx(
        100 * bytes_ / 819e9 / 0.009)
    assert 90 < reader(NEW[2])(OBS) < 100
    # 0.73 ms of K and V at the peak in 1 ms of the kernel a step
    assert reader(NEW[3])(OBS) == pytest.approx(
        100 * (8000 + 1152) * 65536 / 819e9 / 0.001)
    assert 70 < reader(NEW[3])(OBS) < 76


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    dict(OBS, decode_spans=[step(), step()]),     # the parent's spans
    dict(OBS, decode_spans=[step(moe_experts_hit=40.0, moe_assignments=128.0,
                                 kv_blocks_read=900, kv_table_slots=3584)]),
    dict(OBS, decode_spans=[]), {"kind": "serve"}, {"kind": "train"}],
    ids=["no_attrs", "lfm2_spans", "no_spans", "bare", "train"])
def test_reader_finds_nothing(name, obs):
    assert reader(name)(obs) is None


@pytest.mark.parametrize("name", NEW[2:])
@pytest.mark.parametrize("obs", [
    dict(OBS, profile=None), dict(OBS, traced_steps=None),
    dict(OBS, config=LFM2), dict(OBS, peaks=None)],
    ids=["no_profile", "no_steps", "lfm2_keys", "no_peaks"])
def test_peak_share_reader_finds_nothing(name, obs):
    assert reader(name)(obs) is None


def test_the_kernels_reader_wants_a_kernel_of_its_name():
    # the parent's kernel carries no name: nothing to read
    unnamed = dict(OBS, profile={"busy_s": 0.9, "op_seconds": {
        "%custom-call.5": 0.1, "%moe_routed_experts.2": 0.5}})
    assert reader(NEW[3])(unnamed) is None


def test_chip_check_rehearses_here():
    """benchmark/tests/chip_check_exaone.py at the tiny sizes: it runs to
    its end, every control is compared and falls outside a tolerance, and
    nothing it prints is a chip result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_exaone.py"),
         "--tiny-on-cpu"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True
    controls = ("window_attends_everything", "rope_on_the_global_layer",
                "no_shared_expert", "bias_ignored", "gates_not_renormalised",
                "whole_width_qk_norm", "bf16_logits", "int8_pool",
                "fp8_weights")
    assert set(line["inside_tolerance"]) == {"served_bf16"} | {
        "control_" + c for c in controls}
    served_ = line["served_bf16"]
    # every sequence is past its window several times over, and no ring
    # ever held more than its blocks
    assert min(line["sequence_lens"]) > 5 * line["window"]
    assert line["window_blocks_held_at_most"] \
        <= line["window_blocks_a_ring_times_lanes"]
    # each control moves what it perturbs, tiny as the sizes are
    for name in ("window_attends_everything", "no_shared_expert",
                 "bias_ignored", "gates_not_renormalised",
                 "rope_on_the_global_layer"):
        assert line["control_" + name]["rms_logit_error"] \
            > 2 * served_["rms_logit_error"], name
    assert line["control_rope_on_the_global_layer"][
        "global_kv_relative_rms_error"] \
        > 3 * served_["global_kv_relative_rms_error"]
    for name in ("whole_width_qk_norm", "int8_pool"):
        assert line["control_" + name]["window_kv_relative_rms_error"] \
            > 2 * served_["window_kv_relative_rms_error"], name
    assert line["control_bf16_logits"]["logits_bf16_exact_share"] == 1.0
    assert served_["logits_bf16_exact_share"] < 0.01


def test_chip_checks_engine_leg_rehearses_here():
    """``--engine`` at the tiny sizes: client, server and engine with the
    traffic's tiny bucket, one sequence past three quarters of the positions
    while the others live beside it and one waits for a lane; the served
    tokens are the reference's in every band of depth, an engine whose
    window layers attend everything is over the limit in every band past the
    ring, and the pools are empty afterwards."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_exaone.py"),
         "--engine", "--tiny-on-cpu"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True and line["ok"] is True
    assert line["requests"] == line["lanes"] + 1
    served_ = line["served"]
    assert max(line["sequence_lens"]) > 72 > 8 * 8       # nine windows deep
    assert served_["window_blocks"]["in_use"] == 0 \
        and served_["global_blocks"]["in_use"] == 0
    assert 0 < served_["window_blocks"]["high_water"] \
        <= line["lanes"] * served_["window_ring"]
    assert all(share == 0.0 for *_x, n, share, _w in served_["by_depth"]
               if n)
    control = line["control_window_attends_everything"]["by_depth"]
    assert all(share > 0.5 for lo, _hi, n, share, _w in control
               if n >= 12 and lo >= 32)
