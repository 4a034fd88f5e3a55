"""The cell PR 41 added, rehearsed at tiny size on the CPU through the whole
command, ``nemotron_cost``'s bytes against a count by hand and the numbers
of ISSUE 41, the configuration's file against the catalog row and its own
cut, and the three new readers on hand-made ``obs``: what each computes, and
that a program whose step records none of the attributes, a trace with no
kernel of the name, or a configuration without this source's keys (the
parent of the PR that added them, the other cells), gives nothing and does
not raise."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import nemotron_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b-serve.json")))
GRANITE = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "granite-4.0-h-micro-serve.json")))
EXAONE = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "k-exaone-236b-a23b-serve.json")))
CELL = "nemotron_3_nano_30b_a3b_serve_decode_heavy"
NEW = ("nemotron_stream_floor_share.serve",
       "nemotron_experts_roofline_share.serve",
       "nemotron_ssm_update_roofline_share.serve")
REDUCED = ["n_routed_experts", "vocab_size", "max_position_embeddings"]
JOINED = {
    "decode_step_ms", "lanes_per_step", "prefill_lane_share",
    "ttft_ms_per_prompt_token", "device_busy_ms_per_step", "recompiles",
    "device_idle_share", "host_gap_ms_per_step", "queue_wait_ms",
    "submit_lock_wait_ms", "kv_blocks_read_share", "emit_ms_per_step",
    "stream_chunks_per_publish", "dispatch_ahead_share",
    "dispatch_ms_per_step", "moe_experts_hit_per_layer",
    "moe_load_max_over_mean", "moe_local_assignment_share"}
# their cost modules read other sources' keys (ISSUE 41); the last finds
# nothing in a traced window whose only idle time is the device's own
# hand-over between operations, as this cell's is on some seeds
NOT_JOINED = {
    "ssm_stream_floor_share", "ssm_update_roofline_share",
    "moe_stream_floor_share", "moe_routed_stream_floor_share",
    "exaone_stream_floor_share", "paged_attention_roofline_share",
    "kv_window_read_share", "idle_in_program_spans_share"}


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert BENCH["workloads"][-1] is cell          # appended, nothing moved
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b-serve", "serve_ssm_moe_decode_heavy", 1)
    entry = BENCH["configs"][-1]
    assert entry["name"] == cell["config"]
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
    # OLMoE's, Granite's and LFM2's mix to the number
    for other in ("serve_moe_decode_heavy", "serve_ssm_decode_heavy",
                  "serve_hybrid_moe_decode_heavy"):
        theirs = json.load(open(os.path.join(
            ROOT, "benchmark", "traffic", other + ".json")))
        for key in ("kind", "clients", "lane_buckets", "kv_blocks",
                    "deadline_ms", "ramp_s", "trace_seconds", "prompt_len",
                    "output_len", "size_set", "schedule_seed",
                    "check_requests"):
            assert traffic[key] == theirs[key], (other, key)
    assert (traffic["clients"], traffic["lane_buckets"],
            traffic["kv_blocks"]) == (32, [32], 2048)
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == 1792 <= CONFIG["n_positions"]
    serve = {m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]
             if CELL in m.get("workloads", [])}
    assert serve == {"serve_tokens_per_s", "itl_p95_ms"} | set(NEW) \
        | {name + ".serve" for name in JOINED}
    assert not serve & {name + ".serve" for name in NOT_JOINED}
    for m in BENCH["per_layer"][-3:]:
        assert m["name"] in NEW and m["workloads"] == [CELL] \
            and m["unit"] == "%" and m["better"] == "higher" \
            and m["layer"] == "model + cache" and m["moves"] == "itl_p95_ms" \
            and m["source"] == "device_trace"
    assert len(BENCH["workloads"]) == 8 and len(BENCH["configs"]) == 7
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert max(len(w["why"]) for w in BENCH["workloads"]) <= 200
    assert max(len(c["why"]) for c in BENCH["configs"]) <= 200


def test_the_file_holds_the_catalog_rows_numbers_and_the_stated_cut():
    """Every number of the catalog row's ``config`` under its own key, but
    the reduced keys; depth and pattern are as published; no width is among
    the reduced."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert sorted(differ) == sorted(REDUCED)
    assert CONFIG["source"] == row["source_url"]
    pattern = CONFIG["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("*"),
            pattern.count("E")) == (52, 23, 6, 23) \
        and CONFIG["num_hidden_layers"] == 52
    for key, want in (("hidden_size", 2688), ("num_attention_heads", 32),
                      ("num_key_value_heads", 2), ("head_dim", 128),
                      ("mamba_num_heads", 64), ("mamba_head_dim", 64),
                      ("ssm_state_size", 128), ("n_groups", 8),
                      ("conv_kernel", 4), ("moe_intermediate_size", 1856),
                      ("moe_shared_expert_intermediate_size", 3712),
                      ("num_experts_per_tok", 6),
                      ("routed_scaling_factor", 2.5)):
        assert CONFIG[key] == row["config"][key] == want
    # the published counts beside the held ones, and the deployment
    assert (CONFIG["n_routed_experts"], CONFIG["n_routed_experts_published"],
            CONFIG["first_expert"], CONFIG["expert_parallel_chips"],
            CONFIG["num_experts"]) == (16, 128, 0, 8, 16)
    assert row["config"]["n_routed_experts"] == 128
    assert (CONFIG["vocab_size"], CONFIG["vocab_size_published"]) \
        == (16384, 131072) and row["config"]["vocab_size"] == 131072
    # the guide's floors: 8 experts a layer, an eighth of the vocabulary
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["vocab_size_published"]
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert CONFIG["departures"] and "float32" in CONFIG["departures"][0]
    for key in ("no_position_encoding", "router", "expert_bias_std",
                "gated_norm_groups", "d_inner", "ssm_memory", "precision",
                "deployment", "chunk_size"):
        assert key in CONFIG["assumed"], key
    tiny = CONFIG["tiny"]
    # all three kinds, G > 1, a share smaller than the router, an F that is
    # no multiple of 128
    assert set(tiny["hybrid_override_pattern"]) == set("M*E")
    assert tiny["n_groups"] > 1 and tiny["moe_intermediate_size"] % 128
    assert tiny["n_routed_experts"] < tiny["n_routed_experts_published"]


def test_nemotron_cost_against_a_count_by_hand():
    c = nemotron_cost
    assert (c.layers_of(CONFIG, "M"), c.layers_of(CONFIG, "*"),
            c.layers_of(CONFIG, "E")) == (23, 6, 23)
    # in_proj 2688 x 10304, conv 6144 x 5, out_proj 4096 x 2688, the gated
    # norm 4096, three values a head (ISSUE 41's 38,744,896 less the block
    # norm, which is not the mixer's)
    assert c.mamba_weight_bytes(CONFIG) == 2 * (
        2688 * 10304 + 6144 * 5 + 4096 * 2688 + 4096 + 3 * 64) \
        == 2 * (38744896 - 2688)
    assert c.attention_weight_bytes(CONFIG) == 2 * (
        2 * 2688 * 4096 + 2 * 2688 * 256) == 2 * (23399040 - 2688)
    assert c.expert_bytes(CONFIG) == 2 * 9977856 == 19955712
    assert c.experts_layer_fixed_bytes(CONFIG) == 2 * (
        2688 * 128 + 19955712)
    assert c.state_bytes_per_sequence_layer(CONFIG) == 2097152
    assert c.state_traffic_bytes_per_step(CONFIG, 32) \
        == 2 * 32 * 23 * 2097152 == 3087007744               # 3.09e9
    assert c.kv_block_bytes(CONFIG, 16) == 16384
    assert c.kv_floor_bytes_per_step(CONFIG, 32 * 63, 16) \
        == 6 * 2016 * 16384                                  # 0.20e9
    # the issue's shares at 12.45 experts hit: 5.71e9 of experts
    assert c.experts_hit_bytes_per_step(CONFIG, 12.45) == pytest.approx(
        23 * 12.45 * 19955712)
    assert 5.70e9 < c.experts_hit_bytes_per_step(CONFIG, 12.45) < 5.72e9
    # every held expert hit: all the weights, 10.52e9 B, less what a step
    # need not touch (16,352 rows of the embedding, block norms, biases)
    whole = c.weight_floor_bytes_per_step(CONFIG, 16, 32)
    assert 10516841088 - 16384 * 2688 * 2 - 1e6 < whole < 10516841088
    step = c.stream_floor_bytes_per_step(CONFIG, 12.45, 32, 32, 32 * 63, 16)
    assert step == pytest.approx(
        23 * 2 * (38744896 - 2688) + 6 * 2 * (23399040 - 2688)
        + 23 * 2 * (2688 * 128 + 19955712) + 23 * 12.45 * 19955712
        + 2688 * 16384 * 2 + 32 * 2688 * 2
        + 3087007744 + 6 * 2016 * 16384)
    assert 12.0e9 < step < 12.2e9             # ISSUE 41: 12.1e9 B, 14.8 ms
    assert 14.6 < 1e3 * step / 819e9 < 14.9


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
    """The parent of PR 41 has no ``paddle_tpu/models/nemotron_h.py``: the
    model file imports it first, so the command fails before any weight,
    engine or server exists."""
    code = ("import sys, importlib.abc\n"
            "class Gone(importlib.abc.MetaPathFinder):\n"
            "    def find_spec(self, name, path, target=None):\n"
            "        if name == 'paddle_tpu.models.nemotron_h':\n"
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
    assert "nemotron_h" in out.stderr and "correct" not in out.stdout


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


def served(**attrs):
    return step(**dict(
        {"kv_blocks_read": 2016, "kv_table_slots": 32 * 128,
         "kv_block_size": 16, "ssm_state_lanes": 32,
         "ssm_state_bytes": 32 * 49082368, "moe_experts_hit": 12.5,
         "moe_assignments": 24.0, "moe_local_assignments": 24.0,
         "moe_absent_assignments": 168.0}, **attrs))


OBS = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
       "peaks": {"hbm_bytes_per_s": 819e9},
       "profile": {"busy_s": 1.8, "op_seconds": {
           "%moe_relu2_experts.1": 0.3, "%moe_relu2_experts.9": 0.5,
           "%ssm_state_update.4": 0.45, "%paged_attention.2": 0.03,
           "%fusion.3": 0.4}},
       "decode_spans": [served(), step(), served(kv_blocks_read=2024),
                        served(kv_blocks_read=2008)]}


def test_readers_on_served_spans():
    c = nemotron_cost
    bytes_ = c.stream_floor_bytes_per_step(CONFIG, 12.5, 32, 32, 2016, 16)
    # 14.8 ms of an 18 ms device step
    assert reader(NEW[0])(OBS) == pytest.approx(100 * bytes_ / 819e9 / 0.018)
    assert 80 < reader(NEW[0])(OBS) < 85
    # 7.0 ms of experts at the peak in 8 ms of the kernel a step
    assert reader(NEW[1])(OBS) == pytest.approx(
        100 * 23 * 12.5 * 19955712 / 819e9 / 0.008)
    assert 85 < reader(NEW[1])(OBS) < 90
    # 3.77 ms of state at the peak in 4.5 ms of the kernel a step
    assert reader(NEW[2])(OBS) == pytest.approx(
        100 * 3087007744 / 819e9 / 0.0045)
    assert 80 < reader(NEW[2])(OBS) < 86
    # and the accepted readers the cell joined read the same spans
    assert reader("moe_local_assignment_share.serve")(OBS) \
        == pytest.approx(12.5)
    assert reader("moe_experts_hit_per_layer.serve")(OBS) == 12.5
    assert reader("moe_load_max_over_mean.serve")(
        dict(OBS, decode_spans=[served(moe_load_max=3.0)])) \
        == pytest.approx(3.0 * 16 / 24.0)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    dict(OBS, decode_spans=[step(), step()]),     # the parent's spans
    dict(OBS, decode_spans=[]), dict(OBS, profile=None),
    dict(OBS, traced_steps=None), dict(OBS, peaks=None),
    dict(OBS, config=GRANITE), dict(OBS, config=EXAONE),
    {"kind": "serve"}, {"kind": "train"}],
    ids=["no_attrs", "no_spans", "no_profile", "no_steps", "no_peaks",
         "granite_keys", "exaone_keys", "bare", "train"])
def test_reader_finds_nothing(name, obs):
    assert reader(name)(obs) is None


def test_the_kernels_readers_want_a_kernel_of_their_name():
    # the three-matrix kernel's name is not this form's; no state kernel ran
    other = dict(OBS, profile={"busy_s": 1.8, "op_seconds": {
        "%moe_routed_experts.2": 0.5, "%custom-call.5": 0.1}})
    assert reader(NEW[1])(other) is None
    assert reader(NEW[2])(other) is None
    assert reader(NEW[0])(other) is not None      # busy time alone


def test_the_floor_reader_wants_every_attribute():
    for key in ("moe_experts_hit", "ssm_state_lanes", "kv_blocks_read",
                "kv_block_size"):
        spans = [served()]
        del spans[0]["attrs"][key]
        assert reader(NEW[0])(dict(OBS, decode_spans=spans)) is None, key


def _chip_check(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_nemotron.py"),
         "--tiny-on-cpu"] + list(flags), cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True
    return line


def test_chip_check_rehearses_here():
    """benchmark/tests/chip_check_nemotron.py at the tiny sizes: it runs to
    its end, every control is compared and moves what it perturbs, the jnp
    paths are the step's own here, and nothing it prints is a chip
    result."""
    line = _chip_check()
    controls = ("bf16_state", "group_0_for_every_head", "relu_for_relu2",
                "routed_scaling_dropped", "no_shared_expert", "bias_ignored",
                "slot_not_reset", "fp8_weights")
    assert set(line["inside_tolerance"]) == {"served_bf16", "jnp_paths"} | {
        "control_" + c for c in controls}
    served_ = line["served_bf16"]
    assert line["layers"] == 6 and min(line["sequence_lens"]) > 40
    assert line["jnp_paths"]["largest_difference_from_the_kernels"] == 0.0
    for name in ("group_0_for_every_head", "relu_for_relu2",
                 "routed_scaling_dropped", "no_shared_expert",
                 "bias_ignored", "slot_not_reset", "fp8_weights"):
        assert line["control_" + name]["rms_logit_error"] \
            > 2 * served_["rms_logit_error"], name
    for name in ("group_0_for_every_head", "slot_not_reset", "bf16_state"):
        assert line["control_" + name]["first_state_relative_rms_error"] \
            > 2 * served_["first_state_relative_rms_error"], name
    # a fault in the experts leaves the first mixer's state as it was
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
    # the control (an engine that never resets a slot, under a name of its
    # own so that no cache hands back the step as served) is seen
    assert line["ok"] is True
    control = line["control_slot_not_reset"]["by_depth_after_a_wait"]
    assert max(share for *_x, n, share, _w in control if n) > 0
