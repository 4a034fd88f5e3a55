"""The cell PR 49 added, rehearsed at tiny size on the CPU through the whole
command, ``dots_cost``'s bytes and operations against a count by hand and the
numbers of ISSUE 49, the configuration's file against the catalog row and
its own cut, and the three new readers on hand-made ``obs``: what each
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

from benchmark import dots_cost  # noqa: E402
from benchmark.run import load_module  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as fp:
        return json.load(fp)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = _config("dots-vlm1-inst-serve.json")
KIMI = _config("kimi-linear-48b-a3b-serve.json")
EXAONE = _config("k-exaone-236b-a23b-serve.json")
CELL = "dots_vlm1_inst_serve_decode_long"
KIMI_CELL = "kimi_linear_48b_a3b_serve_decode_long"
NEW = ("dots_stream_floor_share.serve",
       "dots_latent_attention_roofline_share.serve",
       "dots_experts_roofline_share.serve")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers",
           "max_position_embeddings"]


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots-vlm1-inst-serve", "serve_latent_moe_decode_long", 1)
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
    # K-EXAONE's and Kimi-Linear's long mix to the number
    for other in ("serve_window_moe_decode_long",
                  "serve_linear_latent_decode_long"):
        theirs = json.load(open(os.path.join(
            ROOT, "benchmark", "traffic", other + ".json")))
        for key in ("kind", "clients", "lane_buckets", "kv_blocks",
                    "deadline_ms", "ramp_s", "trace_seconds", "prompt_len",
                    "output_len", "size_set", "schedule_seed",
                    "check_requests", "tiny"):
            assert traffic[key] == theirs[key], key
    assert (traffic["clients"], traffic["lane_buckets"],
            traffic["kv_blocks"]) == (32, [32], 12832)
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == 6400 <= CONFIG["n_positions"]
    mine = {m["name"] for g in ("end_to_end", "per_layer")
            for m in BENCH[g] if CELL in m.get("workloads", [])}
    kimis = {m["name"] for g in ("end_to_end", "per_layer")
             for m in BENCH[g] if KIMI_CELL in m.get("workloads", [])}
    # every list that names Kimi-Linear's cell but its own three, and the
    # three new ones: 20 + 3
    assert mine - set(NEW) == {n for n in kimis if not n.startswith("kimi_")}
    assert len(mine) == 23 and set(NEW) <= mine
    for m in BENCH["per_layer"][-3:]:
        assert m["name"] in NEW and m["workloads"] == [CELL] \
            and m["unit"] == "%" and m["better"] == "higher" \
            and m["layer"] == "model + cache" \
            and m["moves"] == "itl_p95_ms" and m["source"] == "device_trace"
    assert len(BENCH["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_file_holds_the_catalog_rows_numbers_and_the_stated_cut():
    """Every number of the catalog row's ``config`` under its own key, but
    the reduced keys; no width is among the reduced; ``rope_scaling`` is
    whole."""
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(rows))
               if r["name"] == "dots.vlm1.inst")
    differ = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert sorted(differ) == sorted(REDUCED)
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["rope_scaling"] == row["config"]["rope_scaling"]
    for key, want in (("hidden_size", 7168), ("num_attention_heads", 128),
                      ("q_lora_rank", 1536), ("kv_lora_rank", 512),
                      ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                      ("v_head_dim", 128), ("intermediate_size", 18432),
                      ("moe_intermediate_size", 2048),
                      ("num_experts_per_tok", 8), ("n_group", 8),
                      ("topk_group", 4), ("routed_scaling_factor", 2.5),
                      ("rms_norm_eps", 1e-6)):
        assert CONFIG[key] == row["config"][key] == want
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["published_layers"]) == (6, 1, [0, 3, 4, 5, 6, 7])
    assert (CONFIG["n_routed_experts"], CONFIG["num_experts"],
            CONFIG["num_experts_published"], CONFIG["first_expert"],
            CONFIG["expert_parallel_chips"]) == (16, 16, 256, 0, 16)
    assert row["config"]["n_routed_experts"] == 256
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"] == 129280
    assert (CONFIG["max_position_embeddings"], CONFIG["n_positions"],
            CONFIG["num_nextn_predict_layers"]) == (8192, 8192, 0)
    # the guide's floors: four layers behind the lead, 8 experts, an eighth
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["num_experts"] >= 8
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert "11,006,722,560 B" in CONFIG["reduced_why"]["num_hidden_layers"]
    assert len(CONFIG["departures"]) == 3 \
        and "vision tower" in CONFIG["departures"][0] \
        and "multi-token-prediction" in CONFIG["departures"][1] \
        and "640" in CONFIG["departures"][2]
    for key in ("mla", "rope", "router", "expert_bias_std",
                "expert_bias_balance", "weights", "precision",
                "latent_cache", "deployment"):
        assert key in CONFIG["assumed"], key
    tiny = CONFIG["tiny"]
    assert tiny["num_experts"] == tiny["n_routed_experts"] \
        < tiny["num_experts_published"] and tiny["first_expert"] == 4


def test_the_bias_is_balanced_on_the_blocks_own_states():
    """``make_params`` under ``expert_bias_balance`` differs from the plain
    seeded draw in the routed layers' ``expert_bias`` alone, and on the
    scores of the block's own continuation (``router_scores``: lanes x
    steps inputs a routed layer, sigmoid scores over the whole router) the
    experts' load lies nearer the mean than under the seeded draw (tiny
    sizes: 64 inputs over 16 experts leave most of it; the rule at the
    published router is tests/test_dots_vlm.py's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = load_module("models", CONFIG["model"])
    config = dict({k: v for k, v in CONFIG.items() if k != "tiny"},
                  **CONFIG["tiny"])
    plain = {k: v for k, v in config.items() if k != "expert_bias_balance"}
    device = jax.devices()[0]
    seeded = model.make_params(plain, 5, device)
    served = model.make_params(config, 5, device)
    cfg = model.decoder_config(config)
    moved = sorted(k for k in seeded if not np.array_equal(
        np.asarray(seeded[k], np.float32), np.asarray(served[k], np.float32)))
    assert moved == ["l%d_expert_bias" % l for l in cfg.routed_layers]
    spec = config["expert_bias_balance"]
    scores = model.router_scores(config, served, 5)
    assert scores.shape == (len(cfg.routed_layers),
                            spec["lanes"] * spec["steps"], cfg.experts)
    assert float(scores.min()) > 0 and float(scores.max()) < 1

    def off(params):
        bias = jnp.stack([params[k] for k in moved])
        _bias, worst, rms = model.balance(
            model.router_scores(config, params, 5), bias,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group, 0, [1, 1])
        return float(worst), float(rms)

    assert off(served)[1] < 0.9 * off(seeded)[1]


def test_dots_cost_against_a_count_by_hand():
    c = dots_cost
    assert (c.layers(CONFIG), c.routed_layers(CONFIG)) == (6, 5)
    # ISSUE 49's table
    assert c.latent_weight_bytes(CONFIG) == 2 * 187107328
    assert 2 * 187107328 == 2 * (
        7168 * 1536 + 1536 + 1536 * 128 * 192 + 7168 * 576 + 512
        + 512 * 128 * 256 + 128 * 128 * 7168)
    assert c.expert_bytes(CONFIG) == 2 * 44040192
    assert c.routed_layer_fixed_bytes(CONFIG) \
        == 2 * (7168 * 256 + 44040192)
    assert c.dense_layer_bytes(CONFIG) == 2 * 396361728
    assert c.latent_block_bytes(CONFIG, 16) == 18432
    assert c.latent_floor_bytes_per_step(CONFIG, 32 * 192, 16) \
        == 6 * 6144 * 18432                                  # 0.68e9
    # a position a layer: 128 heads x (576 + 512) x 2 = 278,528 operations
    # for 1,152 B: 242 a byte, the chip's ridge (197e12 / 819e9 = 240.5)
    flops = c.latent_flops_per_step(CONFIG, 32 * 192, 16)
    assert flops == 6 * 6144 * 16 * 278528
    assert flops / c.latent_floor_bytes_per_step(CONFIG, 32 * 192, 16) \
        == pytest.approx(278528 / 1152)
    assert 241 < 278528 / 1152 < 242 and 240 < 197e12 / 819e9 < 241
    # the issue's share at 10 experts hit: 0.88e9 B of experts a layer
    assert c.experts_hit_bytes_per_step(CONFIG, 10.0) \
        == 5 * 10 * 2 * 44040192
    # every held expert hit: all the weights, 11,006,722,560 B, less what a
    # step need not touch (the embedding but 32 rows, norms, biases)
    whole = c.weight_floor_bytes_per_step(CONFIG, 16, 32)
    assert 11006722560 - 16160 * 7168 * 2 - 1e6 < whole \
        < 11006722560 - 16128 * 7168 * 2
    step = c.stream_floor_bytes_per_step(CONFIG, 10.0, 32, 32 * 192, 16)
    assert step == pytest.approx(
        6 * 2 * 187107328 + 2 * 396361728
        + 5 * 2 * (7168 * 256 + 44040192) + 5 * 10 * 2 * 44040192
        + 7168 * 16160 * 2 + 32 * 7168 * 2 + 6 * 6144 * 18432)
    assert 8.5e9 < step < 9.0e9         # ISSUE 49: 7.9e9 B + 0.5-0.9e9 B
    assert 10.4 < 1e3 * step / 819e9 < 11.0
    # an uncompressed query is counted whole
    assert c.latent_weight_bytes(dict(CONFIG, q_lora_rank=None)) \
        == 2 * (187107328 - 7168 * 1536 - 1536 - 1536 * 24576
                + 7168 * 24576)


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
    """The parent of PR 49 has no ``paddle_tpu/models/dots_vlm.py``: the
    model file imports it first, so the command fails before any weight,
    engine or server exists."""
    code = ("import sys, importlib.abc\n"
            "class Gone(importlib.abc.MetaPathFinder):\n"
            "    def find_spec(self, name, path, target=None):\n"
            "        if name == 'paddle_tpu.models.dots_vlm':\n"
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
    assert "dots_vlm" in out.stderr and "correct" not in out.stdout


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 32, "generated": 30}, **attrs)}


def served(**attrs):
    return step(**dict(
        {"kv_blocks_read": 6144, "latent_blocks_read": 6144,
         "kv_table_slots": 32 * 512, "kv_block_size": 16,
         "moe_experts_hit": 10.0, "moe_assignments": 16.0,
         "moe_local_assignments": 16.0, "moe_absent_assignments": 240.0,
         "moe_groups_kept": 0.5}, **attrs))


OBS = {"kind": "serve", "config": CONFIG, "traced_steps": 100,
       "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
       "profile": {"busy_s": 1.4, "op_seconds": {
           "%latent_attention.4": 0.1, "%latent_attention.9": 0.1,
           "%moe_routed_experts.2": 0.6, "%moe_relu2_experts.5": 9.0,
           "%kda_state_update.1": 9.0, "%paged_attention.3": 9.0,
           "%fusion.3": 0.4}},
       "decode_spans": [served(), step(),
                        served(latent_blocks_read=6152),
                        served(latent_blocks_read=6136)]}


def test_readers_on_served_spans():
    c = dots_cost
    bytes_ = c.stream_floor_bytes_per_step(CONFIG, 10.0, 32, 6144, 16)
    # 10.7 ms of a 14 ms device step
    assert reader(NEW[0])(OBS) == pytest.approx(100 * bytes_ / 819e9 / 0.014)
    assert 72 < reader(NEW[0])(OBS) < 80
    # the larger of 0.83 ms of rows at the memory's peak and 0.834 ms of
    # operations at the matrix unit's, in 2 ms of the kernel a step: another
    # kernel's seconds (paged_attention) are not this one's
    by_bytes = 6 * 6144 * 18432 / 819e9
    by_flops = 6 * 6144 * 16 * 278528 / 197e12
    assert by_flops > by_bytes
    assert reader(NEW[1])(OBS) == pytest.approx(100 * by_flops / 0.002)
    assert 41 < reader(NEW[1])(OBS) < 42.5
    # with a slower matrix unit's peak the bytes bound it, with a faster
    # memory's the operations still do: the larger of the two, either way
    assert reader(NEW[1])(dict(OBS, peaks=dict(
        OBS["peaks"], bf16_flops_per_s=400e12))) \
        == pytest.approx(100 * by_bytes / 0.002)
    # 5.4 ms of experts at the peak in 6 ms of the kernel a step
    assert reader(NEW[2])(OBS) == pytest.approx(
        100 * 5 * 10 * 2 * 44040192 / 819e9 / 0.006)
    assert 88 < reader(NEW[2])(OBS) < 91
    # and the accepted readers the cell joined read the same spans
    assert reader("moe_local_assignment_share.serve")(OBS) \
        == pytest.approx(100 * 16.0 / 256.0)
    assert reader("moe_experts_hit_per_layer.serve")(OBS) == 10.0
    assert reader("moe_load_max_over_mean.serve")(dict(OBS, decode_spans=[
        served(moe_load_max=3.0)])) == pytest.approx(3.0 * 16 / 16.0)
    assert reader("kv_blocks_read_share.serve")(OBS) \
        == pytest.approx(100 * 6144 / (32 * 512))
    # Kimi-Linear's readers find nothing of theirs in this configuration
    for name in ("kimi_stream_floor_share.serve",
                 "kimi_latent_attention_roofline_share.serve",
                 "kimi_kda_state_roofline_share.serve"):
        assert reader(name)(OBS) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    dict(OBS, decode_spans=[step(), step()]),     # the parent's spans
    dict(OBS, decode_spans=[]), dict(OBS, profile=None),
    dict(OBS, traced_steps=None), dict(OBS, peaks=None),
    dict(OBS, config=KIMI), dict(OBS, config=EXAONE),
    {"kind": "serve"}, {"kind": "train"}],
    ids=["no_attrs", "no_spans", "no_profile", "no_steps", "no_peaks",
         "kimi_keys", "exaone_keys", "bare", "train"])
def test_reader_finds_nothing(name, obs):
    assert reader(name)(obs) is None


def test_the_kernels_readers_want_a_kernel_of_their_name():
    other = dict(OBS, profile={"busy_s": 1.4, "op_seconds": {
        "%moe_relu2_experts.2": 0.5, "%paged_attention.5": 0.1}})
    assert reader(NEW[1])(other) is None
    assert reader(NEW[2])(other) is None
    assert reader(NEW[0])(other) is not None      # busy time alone


def test_the_floor_reader_wants_every_attribute():
    for key in ("moe_experts_hit", "latent_blocks_read", "kv_block_size"):
        spans = [served()]
        del spans[0]["attrs"][key]
        assert reader(NEW[0])(dict(OBS, decode_spans=spans)) is None, key


def _chip_check(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "chip_check_dots.py"),
         "--tiny-on-cpu"] + list(flags), cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["not_a_chip_result"] is True
    return line


def test_chip_check_rehearses_here():
    """benchmark/tests/chip_check_dots.py at the tiny sizes: it runs to its
    end, every control is compared and moves what it perturbs, the jnp paths
    are the step's own here, and nothing it prints is a chip result."""
    line = _chip_check()
    chip_check = load_module("tests", "chip_check_dots")
    assert set(line["inside_tolerance"]) == {"served_bf16", "jnp_paths"} | {
        "control_" + c for c in chip_check.CONTROLS}
    served_ = line["served_bf16"]
    assert line["layers"] == 4 and min(line["sequence_lens"]) > 30
    assert line["jnp_paths"]["largest_difference_from_the_kernels"] == 0.0
    # (sums of 20-64 terms lose little in bfloat16: that control is seen at
    # the published widths, sums of 1,536-18,432 terms, and barely here)
    weak = {"bf16_accumulation": 1.0}
    for name in chip_check.CONTROLS:
        assert line["control_" + name]["rms_logit_error"] \
            > weak.get(name, 1.2) * served_["rms_logit_error"], name
    # the rotation is in the first layer's rows, and in their rotated part
    # alone; a fault in the scale or the router leaves those rows as served
    assert line["control_no_rotation"][
        "first_rows_rotated_part_relative_rms_error"] \
        > 20 * served_["first_rows_rotated_part_relative_rms_error"]
    for name in ("no_yarn_scale", "groups_ignored"):
        assert line["control_" + name]["first_rows_relative_rms_error"] \
            == served_["first_rows_relative_rms_error"], name


def test_chip_checks_engine_leg_rehearses_here():
    """``--engine`` at the tiny sizes: client, server and engine with the
    traffic's tiny bucket, one request more than lanes; the served tokens
    are the reference's in every band of depth, nothing is declined and the
    pool is empty afterwards."""
    line = _chip_check("--engine")
    assert line["requests"] == line["lanes"] + 1
    served_ = line["served"]
    assert served_["declines"] is None and served_["blocks"]["in_use"] == 0
    rows = served_["by_depth_from_the_start"] \
        + served_["by_depth_after_a_wait"]
    assert all(share <= line["differing_share_bound"]
               and worst <= line["deficit_bound"]
               for _lo, _hi, n, share, worst in rows if n)
    assert sum(n for _lo, _hi, n, _s, _w in rows) > 0
