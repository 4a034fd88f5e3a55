"""The cell PR 67 added, rehearsed at tiny size on the CPU through the whole
command (and its chip check), ``xing_cost``'s bytes against a count made
another way (the program's own functions, at the tiny and the published
sizes) and the numbers of ISSUE 67, the configuration's file against the
catalog row and its own cut, and the five new readers on hand-made ``obs``
and a hand-made trace: what each computes, and that a program whose step
records none of the attributes, a trace whose events carry no scope or none
under ``hc``, a trace with no kernel of the name, or a configuration without
this source's keys (the parent of the PR that added them, the other cells)
gives nothing and does not raise."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce, xing_cost  # noqa: E402
from benchmark.run import load_module, with_tiny  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as fp:
        return json.load(fp)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = _config("xing4.0-29b-a4b-serve.json")
DOTS = _config("dots-vlm1-inst-serve.json")
CELL = "xing4_0_29b_a4b_serve_decode_heavy"
NEW = ("xing_hc_step_share.serve", "xing_hc_roofline_share.serve",
       "xing_latent_attention_roofline_share.serve",
       "xing_experts_roofline_share.serve", "xing_stream_floor_share.serve")
HC = NEW[:2]
REDUCED = ["n_routed_experts", "vocab_size", "max_position_embeddings",
           "num_nextn_predict_layers"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_files_are_found_by_name():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b-serve", "serve_hc_latent_moe_decode_heavy", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry == BENCH["configs"][-1] and len(entry["why"]) <= 200
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
    # ISSUE 67's traffic, to the number
    assert {k: traffic[k] for k in (
        "clients", "lane_buckets", "kv_blocks", "ramp_s", "deadline_ms",
        "trace_seconds", "size_set", "schedule_seed", "check_requests")} == {
        "clients": 32, "lane_buckets": [32], "kv_blocks": 3616,
        "ramp_s": 24, "deadline_ms": 300000, "trace_seconds": 3,
        "size_set": 64, "schedule_seed": 1, "check_requests": 4}
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 32,
                                     "max": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1536}
    # the longest request on every lane at once, and 32 blocks more
    assert traffic["kv_blocks"] == 32 * (256 + 1536) // 16 + 32
    assert CONFIG["n_positions"] >= 256 + 1536
    # the cell reports tokens/s and set-up, not the inter-token tail; every
    # per-layer metric that lists it moves one of those
    reports = [m["name"] for m in BENCH["end_to_end"]
               if CELL in m.get("workloads", [CELL])]
    assert reports == ["serve_tokens_per_s", "setup_s"]
    listed = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert all(m["moves"] in reports for m in listed)
    assert [m["name"] for m in listed][-5:] == list(NEW)
    assert all(m["workloads"] == [CELL] and m["unit"] == "%"
               and m["moves"] == "serve_tokens_per_s" for m in listed[-5:])
    # the shared serving metrics Solar-Open2's cell reports (the same two
    # end-to-end metrics), and no other cell's own
    solar = {m["name"] for m in BENCH["per_layer"]
             if "solar_open2_250b_serve_decode_long"
             in m.get("workloads", []) and not m["name"].startswith("solar")}
    assert {m["name"] for m in listed[:-5]} == solar and len(solar) == 6


def test_the_configuration_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"],
            CONFIG["max_position_embeddings"], CONFIG["n_positions"],
            CONFIG["num_nextn_predict_layers"]) == (40, 2, 8, 16384, 2048,
                                                    2048, 0)
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["num_experts_published"] \
        == row["config"]["n_routed_experts"]
    assert CONFIG["expert_parallel_chips"] * CONFIG["n_routed_experts"] \
        == CONFIG["num_experts_published"]
    assert set(CONFIG["reduced_why"]) == set(REDUCED)
    assert CONFIG["departures"] and {
        "hc_norm", "hc_maps", "hc_sinkhorn", "hc_ends", "hc_sublayers",
        "hc_precision", "hc_init"} <= set(CONFIG["assumed"])
    assert CONFIG["kv_dtype"] == "bf16" and CONFIG["weights_dtype"] == "bf16"
    assert CONFIG["tiny"]["n_positions"] == 64


def test_xing_cost_against_counts_made_another_way():
    """ISSUE 67's arithmetic at the published widths, and at the tiny and
    the published sizes the program's own functions: the parameters'
    shapes the benchmark makes weights by, ``hyper_connections``' bytes,
    and what ``StepAccount`` says on a step's span."""
    import numpy as np

    from paddle_tpu.models import hyper_connections as hc
    from paddle_tpu.serving import decode_model as dm

    c = CONFIG
    assert xing_cost.latent_weight_bytes(c, 1) == 28411136
    assert xing_cost.expert_bytes(c, 1) == 11010048
    assert xing_cost.dense_layer_bytes(c, 1) == 99090432
    assert xing_cost.routed_layer_fixed_bytes(c, 1) == 3584 * 64 + 11010048
    assert (xing_cost.layers(c), xing_cost.routed_layers(c),
            xing_cost.mixings(c), xing_cost.hc_width(c)) == (40, 38, 80, 24)
    assert xing_cost.hc_param_bytes(c) == 80 * (14336 * 24 + 24 + 3) * 4 \
        == 110109120
    assert xing_cost.hc_stream_bytes_per_step(c, 32) \
        == 80 * 3 * 32 * 57344 == 440401920
    assert xing_cost.latent_block_bytes(c, 16) == 18432
    assert xing_cost.latent_floor_bytes_per_step(c, 2000, 16) \
        == 40 * 2000 * 18432
    assert xing_cost.latent_flops_per_step(c, 2000, 16) \
        == 40 * 2000 * 16 * 32 * 2 * (2 * 512 + 64)
    # the issue's step: 7 of 8 experts hit a layer, 32 lanes
    weights = xing_cost.weight_floor_bytes_per_step(c, 7, 32)
    assert weights == 2 * (
        40 * 28411136 + 2 * 99090432 + 38 * (3584 * 64 + 11010048)
        + 38 * 7 * 11010048 + 3584 * 16384 + 32 * 3584) + 110109120
    assert 9.4e9 < weights < 9.7e9
    assert xing_cost.stream_floor_bytes_per_step(c, 7, 32, 2000, 16,
                                                 440401920) \
        == weights + 40 * 2000 * 18432 + 440401920
    model = load_module("models", c["model"])
    for config in (c, with_tiny(c, True)):
        config = {k: v for k, v in config.items() if k != "tiny"}
        cfg = model.decoder_config(config)
        shapes = model.param_shapes(config)
        count = lambda keep: sum(
            int(np.prod(s)) for n, (s, _k) in shapes.items() if keep(n))
        h = cfg.hidden
        assert xing_cost.hc_param_bytes(config) \
            == 4 * count(lambda n: "_hc_" in n) \
            == hc.param_bytes(cfg, cfg.mixings)
        assert xing_cost.latent_weight_bytes(config, 1) == count(
            lambda n: n.startswith("l0_") and n[3:] in (
                "wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo"))
        last = "l%d_" % (cfg.layers - 1)
        assert xing_cost.expert_bytes(config, 1) * cfg.experts_held == count(
            lambda n: n.startswith(last) and n[len(last):] in (
                "wgate", "wup", "wdown"))
        assert xing_cost.routed_layer_fixed_bytes(config, 1) == count(
            lambda n: n.startswith(last) and (
                n.endswith("_router") or "shared_w" in n))
        assert xing_cost.dense_layer_bytes(config, 1) == count(
            lambda n: n in ("l0_w1", "l0_w2", "l0_w3"))
        # every weight the step reads, all held experts hit: the whole
        # parameter set less the norms' gains, the selection biases and the
        # embedding's rows no lane feeds
        everything = xing_cost.weight_floor_bytes_per_step(
            config, cfg.experts_held, 0, 1) \
            - xing_cost.hc_param_bytes(config) \
            + count(lambda n: "_hc_" in n)
        left_out = count(lambda n: n.endswith(("ln1_g", "ln2_g", "lnf_g",
                                               "expert_bias"))
                         or n == "embed")
        assert everything + left_out == count(lambda n: True)
        for lanes in (1, 3, 32):
            assert xing_cost.hc_stream_bytes_per_step(config, lanes) \
                == hc.stream_bytes(cfg.hc_mult, h, cfg.mixings, lanes)
        kv = dm.cache_config(cfg, 16, 8)
        held = {n: types.SimpleNamespace(shape=s, dtype=np.dtype("float32"))
                for n, (s, _k) in shapes.items()}
        said = dm.StepAccount(cfg, kv, held, (4,)).step_attrs(
            4, np.asarray([5, 0, 9, 1], np.int32))
        assert said["hc_stream_bytes"] \
            == xing_cost.hc_stream_bytes_per_step(config, 3)
        assert (said["hc_streams"], said["hc_mixings"]) \
            == (config["hc_mult"], xing_cost.mixings(config))


def _obs(config=CONFIG, **changed):
    attrs = {"lanes": 32, "kv_block_size": 16, "moe_experts_hit": 7.0,
             "latent_blocks_read": 2000, "kv_blocks_read": 2000,
             "hc_streams": 4, "hc_mixings": 80,
             "hc_stream_bytes": 440401920}
    # 21 steps a second apart: the last two seconds hold three of them
    obs = {
        "kind": "serve", "config": config, "traced_steps": 100.0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "decode_spans": [{"ts": 1e6 * i, "attrs": dict(
            attrs, latent_blocks_read=1800 + 10 * i,
            moe_experts_hit=6.0 + 0.1 * i)} for i in range(21)],
        "profile": {"busy_s": 100 * 0.030, "op_seconds": {
            "%latent_attention.7": 100 * 0.0012,
            "%latent_attention.9": 100 * 0.0010, "%fusion.99": 100 * 0.008,
            "%moe_routed_experts.5": 100 * 0.0088}}}
    obs.update(changed)
    return obs


def _trace(scoped=True, hc=True, window=1000.0):
    """A trace viewer's events by hand (times in microseconds): one chip, a
    window of 1 ms, four operations a step over two steps, two of them
    under the streams' scopes (``hc`` False: none; ``scoped`` False: no
    event says where it lies), and a host thread's events beside them."""
    def ev(name, start, dur, scope, pid=3, tid=3):
        args = {"device_duration_ps": str(int(dur * 1e6))}
        if scoped:
            args["tf_op"] = "jit(step)/jit(main)/" + scope + ":"
        return {"ph": "X", "pid": pid, "tid": tid, "ts": start, "dur": dur,
                "name": name, "args": args}

    mix = "layer3/hc/attn_maps/div" if hc else "layer3/latent/absorb/dot"
    end = "hc/sum/reduce_sum" if hc else "lm_head/dot_general"
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "main"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": window,
         "name": trace_reduce.WINDOW_ANNOTATION, "args": {}},
        ev("jit_step(1)", 1.0, 200.0, "", tid=2)]
    for step in (0, 1):
        at = 1.0 + step * 400.0
        events += [ev("fusion.1", at, 100.0, mix),
                   ev("fusion.2", at + 100.0, 50.0, "layer3/moe/experts"),
                   ev("fusion.3", at + 150.0, 30.0, end),
                   ev("latent_attention.4", at + 180.0, 20.0,
                      "layer3/latent/kv_read")]
    return events


def _write(trace_dir, events):
    """``events`` as the file the profiler writes beside the .xplane.pb."""
    import gzip

    where = trace_dir / "plugins" / "profile" / "2026_10_05"
    where.mkdir(parents=True, exist_ok=True)
    with gzip.open(where / "host.trace.json.gz", "wb") as fp:
        fp.write(json.dumps({"traceEvents": events}).encode())


@pytest.fixture()
def traced(monkeypatch, tmp_path):
    """-> set(events): what the readers find as the run's trace (None:
    no trace at all)."""
    import shutil

    monkeypatch.setattr(xing_cost, "profile_dir", lambda: str(tmp_path))

    def put(events):
        shutil.rmtree(tmp_path / "plugins", ignore_errors=True)
        xing_cost._hc_share_of.cache_clear()
        if events is not None:
            _write(tmp_path, events)

    yield put
    xing_cost._hc_share_of.cache_clear()


def test_the_scopes_share_is_the_events_under_them(tmp_path):
    # two steps of 100 us under layer3/hc/ and 30 us under hc/sum, of 200
    assert xing_cost.scoped_share(_trace()) == pytest.approx(260 / 400)
    assert xing_cost.is_hc_scope("jit(step)/jit(main)/hc/start/jit(_take):")
    assert xing_cost.is_hc_scope("jit(step)/layer39/hc/mlp_merge/mul:")
    assert not xing_cost.is_hc_scope("jit(step)/layer39/moe/experts/dot:")
    assert not xing_cost.is_hc_scope("jit(step)/layer3/fhc/start_x/mul:")
    # clipped to the window, an operation counts what lies inside it: the
    # second step's first 50 us
    assert xing_cost.scoped_share(_trace(window=451.0)) \
        == pytest.approx(180 / 250)
    # no operation says its scope, or none lies under the streams'
    assert xing_cost.scoped_share(_trace(scoped=False)) is None
    assert xing_cost.scoped_share(_trace(hc=False)) == 0.0
    # the file the profiler writes beside the .xplane.pb, found by name
    assert xing_cost.trace_file(None) is None
    assert xing_cost.trace_file(str(tmp_path)) is None
    _write(tmp_path, _trace())
    assert xing_cost.scoped_share(xing_cost.trace_events(
        xing_cost.trace_file(str(tmp_path)))) == pytest.approx(0.65)


def test_every_new_reader_reads_what_it_says(traced):
    traced(_trace())
    read = {n: load_module("layer_metrics", n).read for n in NEW}
    obs = _obs(traced_steps=2.0, profile=dict(
        _obs()["profile"], busy_s=2 * 200e-6))
    got = {n: read[n](obs) for n in HC}
    assert got["xing_hc_step_share.serve"] == pytest.approx(100 * 0.65)
    assert got["xing_hc_roofline_share.serve"] == pytest.approx(
        100 * (110109120 + 440401920) / 819e9 / 130e-6)
    obs = _obs()
    got = {n: read[n](obs) for n in NEW[2:]}
    # the window's last two seconds: steps 18-20, median blocks 1990
    assert [a["latent_blocks_read"] for a in xing_cost.late_attrs(
        obs, ("latent_blocks_read",))] == [1980, 1990, 2000]
    # 60 operations a byte: the bytes bound it
    assert xing_cost.latent_flops_per_step(CONFIG, 1990, 16) / 197e12 \
        < xing_cost.latent_floor_bytes_per_step(CONFIG, 1990, 16) / 819e9
    assert got["xing_latent_attention_roofline_share.serve"] \
        == pytest.approx(100 * 40 * 1990 * 18432 / 819e9 / 0.0022)
    # the whole window's median step hit 7.0
    assert got["xing_experts_roofline_share.serve"] == pytest.approx(
        100 * 38 * 7.0 * 11010048 * 2 / 819e9 / 0.0088)
    assert got["xing_stream_floor_share.serve"] == pytest.approx(
        100 * xing_cost.stream_floor_bytes_per_step(
            CONFIG, 7.9, 32, 1990, 16, 440401920) / 819e9 / 0.030)
    assert all(0 < v <= 100 for v in got.values()), got


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_returns_none(name, traced):
    read = load_module("layer_metrics", name).read
    traced(_trace())
    if name != "xing_hc_step_share.serve":
        # the parent's spans: none of the attributes
        assert read(_obs(decode_spans=[{"ts": 0, "attrs": {
            "lanes": 32, "kv_block_size": 16}}])) is None
        assert read(_obs(decode_spans=[])) is None
    assert read({"kind": "train"}) is None
    # another configuration, no profile, a CPU rehearsal
    assert read(_obs(config=DOTS)) is None
    assert read(_obs(profile=None)) is None
    assert read(_obs(traced_steps=0)) is None
    if name in HC:
        # the parent of the PR that added the scopes: nothing lies under
        # them; a trace whose events say no scope; no trace at all
        for trace in (_trace(hc=False), _trace(scoped=False), None):
            traced(trace)
            assert read(_obs()) is None
    elif "roofline" in name:
        # a trace in which no kernel of the name ran
        assert read(_obs(profile={"busy_s": 1.0, "op_seconds": {
            "%fusion.1": 1.0}})) is None


def _run(*argv):
    out = subprocess.run(
        [sys.executable] + list(argv),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=1500)
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
    assert not any(n.startswith("xing_") for n in line["metrics"])
    untraced = _run(os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                    CELL, "--seed", "5", "--seconds", "2",
                    "--rehearse-tiny-on-cpu")
    assert untraced["not_a_chip_result"] is True and set(
        untraced["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_chip_check_rehearses_tiny_on_the_cpu():
    got = _run(os.path.join(ROOT, "benchmark", "tests",
                            "chip_check_xing.py"), "--tiny-on-cpu")
    assert got["not_a_chip_result"] is True and got["platform"] == "cpu"
    assert set(got["inside_tolerance"]) == {
        "served_bf16", "served_bf16_first_12"} | {
        "control_" + c for c in (
            "one_sinkhorn_iteration", "post_without_its_two",
            "no_flattened_norm", "no_rotation", "no_yarn_scale",
            "bf16_streams", "bf16_maps", "bf16_phi", "fp8_weights",
            "clamp")}
    served = got["served_bf16_first_12"]
    # a fault in structure reads several times the served path's error at
    # any size; a fault in the mixing's precision shows where it is read
    for name in ("post_without_its_two", "no_flattened_norm", "no_rotation",
                 "no_yarn_scale", "one_sinkhorn_iteration"):
        assert got["control_" + name]["rms_logit_error"] \
            > 3 * served["rms_logit_error"]
    assert got["control_bf16_streams"][
        "first_streams_bfloat16_exact_share"] == 1.0 \
        and served["first_streams_bfloat16_exact_share"] < 0.01
    for name in ("bf16_maps", "bf16_phi"):
        assert got["control_" + name]["first_h_res_largest_difference"] \
            > 100 * served["first_h_res_largest_difference"]
    assert got["control_clamp"]["caught"] \
        and not got["inside_tolerance"]["control_clamp"]
