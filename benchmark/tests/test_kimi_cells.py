"""The cell PR 46 added, rehearsed at tiny size on the CPU through the whole
command, ``kimi_cost``'s bytes against a count by hand and the numbers of
ISSUE 46, the configuration's file against the catalog row and its own cut,
and the three new readers on hand-made ``obs``: what each computes, and that
a program whose step records none of the attributes, a trace with no kernel
of the name, or a configuration without this source's keys (the parent of
the PR that added them, the other cells), gives nothing and does not
raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import kimi_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "kimi-linear-48b-a3b-serve.json")))
NEMOTRON = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-serve.json")))
EXAONE = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "k-exaone-236b-a23b-serve.json")))
CELL = "kimi_linear_48b_a3b_serve_decode_long"
NEW = ("kimi_stream_floor_share.serve",
       "kimi_kda_state_roofline_share.serve",
       "kimi_latent_attention_roofline_share.serve")
REDUCED = ["num_experts", "model_max_length"]


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b-serve", "serve_linear_latent_decode_long", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
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
    # K-EXAONE's long mix to the number
    theirs = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "serve_window_moe_decode_long.json")))
    for key in ("kind", "clients", "lane_buckets", "kv_blocks",
                "deadline_ms", "ramp_s", "trace_seconds", "prompt_len",
                "output_len", "size_set", "schedule_seed", "check_requests"):
        assert traffic[key] == theirs[key], key
    assert (traffic["clients"], traffic["lane_buckets"],
            traffic["kv_blocks"]) == (32, [32], 12832)
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == 6400 <= CONFIG["n_positions"]
    mine = {m["name"]: m for g in ("end_to_end", "per_layer")
            for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "itl_p95_ms"} | set(NEW) <= set(mine)
    for name in NEW:
        m = mine[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%" \
            and m["better"] == "higher" and m["layer"] == "model + cache" \
            and m["moves"] == "itl_p95_ms" and m["source"] == "device_trace"
    # no other source's cost module reads this one's keys
    assert not {n for n in mine if n.split("_")[0] in (
        "nemotron", "exaone", "ssm")}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_file_holds_the_catalog_rows_numbers_and_the_stated_cut():
    """Every number of the catalog row's ``config`` under its own key, but
    the reduced keys; depth, pattern and vocabulary are as published; no
    width is among the reduced."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert sorted(differ) == sorted(REDUCED)
    assert CONFIG["source"] == row["source_url"]
    linear = CONFIG["linear_attn_config"]
    assert linear == row["config"]["linear_attn_config"]
    assert (len(linear["kda_layers"]), len(linear["full_attn_layers"]),
            CONFIG["num_hidden_layers"]) == (20, 7, 27)
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    for key, want in (("hidden_size", 2304), ("num_attention_heads", 32),
                      ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
                      ("qk_rope_head_dim", 64), ("v_head_dim", 128),
                      ("intermediate_size", 9216),
                      ("moe_intermediate_size", 1024),
                      ("num_experts_per_token", 8), ("vocab_size", 163840),
                      ("routed_scaling_factor", 2.446)):
        assert CONFIG[key] == row["config"][key] == want
    assert (CONFIG["num_experts"], CONFIG["num_experts_published"],
            CONFIG["first_expert"], CONFIG["expert_parallel_chips"]) \
        == (16, 256, 0, 16)
    assert row["config"]["num_experts"] == 256
    assert (CONFIG["model_max_length"], CONFIG["n_positions"]) == (8192, 8192)
    assert CONFIG["num_experts"] >= 8              # the guide's floor
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert len(CONFIG["departures"]) == 2 \
        and "float32" in CONFIG["departures"][0] \
        and "640" in CONFIG["departures"][1]
    for key in ("nope", "softmax_scale", "kda", "kda_memory", "router",
                "expert_bias_std", "weights", "precision", "latent_cache",
                "kda_state", "deployment"):
        assert key in CONFIG["assumed"], key
    tiny = CONFIG["tiny"]
    assert len(tiny["linear_attn_config"]["kda_layers"]) == 4 \
        and tiny["linear_attn_config"]["full_attn_layers"] == [4, 6]
    assert tiny["num_experts"] < tiny["num_experts_published"] \
        and tiny["first_expert"] == 4


def test_kimi_cost_against_a_count_by_hand():
    c = kimi_cost
    assert (c.kda_layers(CONFIG), c.latent_layers(CONFIG),
            c.routed_layers(CONFIG)) == (20, 7, 26)
    # ISSUE 46's table, the block norms not the mixers'
    assert c.kda_weight_bytes(CONFIG) == 2 * 39514272
    assert c.latent_weight_bytes(CONFIG) == 2 * 29114880
    assert c.expert_bytes(CONFIG) == 2 * 7077888
    assert c.routed_layer_fixed_bytes(CONFIG) == 2 * (2304 * 256 + 7077888)
    assert c.dense_layer_bytes(CONFIG) == 2 * 63700992
    assert c.state_bytes_per_sequence_layer(CONFIG) == 2097152
    assert c.state_traffic_bytes_per_step(CONFIG, 32) \
        == 2 * 32 * 20 * 2097152 == 2684354560               # 2.68e9
    assert c.latent_block_bytes(CONFIG, 16) == 18432
    assert c.latent_floor_bytes_per_step(CONFIG, 32 * 192, 16) \
        == 7 * 6144 * 18432                                  # 0.79e9
    # the issue's share at 10.1 experts hit: 3.7e9 B of experts
    assert 3.70e9 < c.experts_hit_bytes_per_step(CONFIG, 10.1) < 3.73e9
    # every held expert hit: all the weights, 9.913e9 B, less what a step
    # need not touch (the embedding but 32 rows, block norms, biases)
    whole = c.weight_floor_bytes_per_step(CONFIG, 16, 32)
    assert 9913321216 - 163840 * 2304 * 2 - 1e6 < whole \
        < 9913321216 - 163808 * 2304 * 2
    step = c.stream_floor_bytes_per_step(CONFIG, 10.1, 32, 32, 32 * 192, 16)
    assert step == pytest.approx(
        20 * 2 * 39514272 + 7 * 2 * 29114880 + 2 * 63700992
        + 26 * 2 * (2304 * 256 + 7077888) + 26 * 10.1 * 2 * 7077888
        + 2304 * 163840 * 2 + 32 * 2304 * 2
        + 2684354560 + 7 * 6144 * 18432)
    assert 10.2e9 < step < 10.6e9           # ISSUE 46: about 10.4e9 B a step
    assert 12.4 < 1e3 * step / 819e9 < 13.0


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
        # 4 of 16 experts held, 3 a token
        assert 5 < value("moe_local_assignment_share.serve") < 60
        assert 0 < value("moe_experts_hit_per_layer.serve") <= 4
        assert value("recompiles.serve") == 0
        # no device profile on the CPU: the shares of a peak are absent
        assert not set(NEW) & set(line["metrics"])


def test_a_program_without_the_block_fails_at_once(tmp_path):
    """The parent of PR 46 has no ``paddle_tpu/models/kimi_linear.py``: the
    model file imports it first, so the command fails before any weight,
    engine or server exists."""
    code = ("import sys, importlib.abc\n"
            "class Gone(importlib.abc.MetaPathFinder):\n"
            "    def find_spec(self, name, path, target=None):\n"
            "        if name == 'paddle_tpu.models.kimi_linear':\n"
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
    assert "kimi_linear" in out.stderr and "correct" not in out.stdout


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


def served(**attrs):
    return step(**dict(
        {"kv_blocks_read": 6144, "latent_blocks_read": 6144,
         "kv_table_slots": 32 * 512, "kv_block_size": 16,
         "kda_state_lanes": 32, "kda_state_bytes": 32 * 43417600,
         "moe_experts_hit": 10.0, "moe_assignments": 16.0,
         "moe_local_assignments": 16.0, "moe_absent_assignments": 240.0},
        **attrs))


OBS = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
       "peaks": {"hbm_bytes_per_s": 819e9},
       "profile": {"busy_s": 1.6, "op_seconds": {
           "%kda_state_update.1": 0.2, "%kda_state_update.7": 0.2,
           "%latent_attention.4": 0.12, "%moe_routed_experts.2": 0.5,
           "%ssm_state_update.9": 9.0, "%paged_attention.3": 9.0,
           "%fusion.3": 0.4}},
       "decode_spans": [served(), step(),
                        served(latent_blocks_read=6152),
                        served(latent_blocks_read=6136)]}


def test_readers_on_served_spans():
    c = kimi_cost
    bytes_ = c.stream_floor_bytes_per_step(CONFIG, 10.0, 32, 32, 6144, 16)
    # 12.7 ms of a 16 ms device step
    assert reader(NEW[0])(OBS) == pytest.approx(100 * bytes_ / 819e9 / 0.016)
    assert 75 < reader(NEW[0])(OBS) < 85
    # 3.28 ms of state at the peak in 4 ms of the kernel a step: another
    # kernel's seconds (ssm_state_update) are not this one's
    assert reader(NEW[1])(OBS) == pytest.approx(
        100 * 2684354560 / 819e9 / 0.004)
    assert 80 < reader(NEW[1])(OBS) < 84
    # 0.97 ms of rows at the peak in 1.2 ms of the kernel a step
    assert reader(NEW[2])(OBS) == pytest.approx(
        100 * 7 * 6144 * 18432 / 819e9 / 0.0012)
    assert 78 < reader(NEW[2])(OBS) < 84
    # and the accepted readers the cell joined read the same spans
    assert reader("moe_local_assignment_share.serve")(OBS) \
        == pytest.approx(100 * 16.0 / 256.0)
    assert reader("moe_experts_hit_per_layer.serve")(OBS) == 10.0
    assert reader("kv_blocks_read_share.serve")(OBS) \
        == pytest.approx(100 * 6144 / (32 * 512))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    dict(OBS, decode_spans=[step(), step()]),     # the parent's spans
    dict(OBS, decode_spans=[]), dict(OBS, profile=None),
    dict(OBS, traced_steps=None), dict(OBS, peaks=None),
    dict(OBS, config=NEMOTRON), dict(OBS, config=EXAONE),
    {"kind": "serve"}, {"kind": "train"}],
    ids=["no_attrs", "no_spans", "no_profile", "no_steps", "no_peaks",
         "nemotron_keys", "exaone_keys", "bare", "train"])
def test_reader_finds_nothing(name, obs):
    assert reader(name)(obs) is None


def test_the_kernels_readers_want_a_kernel_of_their_name():
    other = dict(OBS, profile={"busy_s": 1.6, "op_seconds": {
        "%ssm_state_update.2": 0.5, "%paged_attention.5": 0.1}})
    assert reader(NEW[1])(other) is None
    assert reader(NEW[2])(other) is None
    assert reader(NEW[0])(other) is not None      # busy time alone


def test_the_floor_reader_wants_every_attribute():
    for key in ("moe_experts_hit", "kda_state_lanes", "latent_blocks_read",
                "kv_block_size"):
        spans = [served()]
        del spans[0]["attrs"][key]
        assert reader(NEW[0])(dict(OBS, decode_spans=spans)) is None, key


def _chip_check(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_kimi.py"),
         "--tiny-on-cpu"] + list(flags), cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True
    return line


def test_chip_check_rehearses_here():
    """benchmark/tests/chip_check_kimi.py at the tiny sizes: it runs to its
    end, every control is compared and moves what it perturbs, the jnp paths
    are the step's own here, and nothing it prints is a chip result."""
    line = _chip_check()
    chip_check = load_module("tests", "chip_check_kimi")
    assert set(line["inside_tolerance"]) == {"served_bf16", "jnp_paths"} | {
        "control_" + c for c in chip_check.CONTROLS}
    served_ = line["served_bf16"]
    assert line["layers"] == 6 and min(line["sequence_lens"]) > 40
    assert line["jnp_paths"]["largest_difference_from_the_kernels"] == 0.0
    # (at these sizes the scores lie near zero, and what moves them alone
    # is seen, barely: the two scales lie a fifth apart)
    weak = {"scale_128": 1.04, "k_pe_left_out": 1.3}
    for name in set(chip_check.CONTROLS) - {"bf16_state"}:
        assert line["control_" + name]["rms_logit_error"] \
            > weak.get(name, 1.5) * served_["rms_logit_error"], name
        assert line["control_" + name][
            "state_behind_latent_relative_rms_error"] > weak.get(name, 1.5) \
            * served_["state_behind_latent_relative_rms_error"], name
    # (a state rounded to bfloat16 over these 50 steps reads half as much
    # again as the served one, whose error is its inputs' bfloat16)
    for name in ("scalar_decay", "no_delta_correction", "qk_not_normalised",
                 "slot_not_reset", "bf16_state"):
        assert line["control_" + name]["first_state_relative_rms_error"] \
            > (1.3 if name == "bf16_state" else 2) \
            * served_["first_state_relative_rms_error"], name
    for name in ("no_kv_norm", "no_output_gate"):
        assert line["control_" + name]["rows_relative_rms_error"] \
            > 2 * served_["rows_relative_rms_error"], name
    # a fault in the experts leaves the first mixer's state as it was
    # (layer 0 is the dense lead)
    assert line["control_no_shared_expert"][
        "first_state_relative_rms_error"] \
        == served_["first_state_relative_rms_error"]


def test_chip_checks_engine_leg_rehearses_here():
    """``--engine`` at the tiny sizes: client, server and engine with the
    traffic's tiny bucket, one request more than lanes; the served tokens
    are the reference's in every band of depth and the pools and slots are
    empty afterwards."""
    line = _chip_check("--engine")
    assert line["requests"] == line["lanes"] + 1
    served_ = line["served"]
    assert served_["slots_in_use"] == 0 and served_["blocks"]["in_use"] == 0
    rows = served_["by_depth_from_the_start"] \
        + served_["by_depth_after_a_wait"]
    assert all(share <= line["differing_share_bound"]
               for *_x, n, share, _w in rows if n >= 8)
