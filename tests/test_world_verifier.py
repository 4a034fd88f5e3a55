"""Whole-world static verifier (core/world_analysis.py): seeded-defect
fixtures for the cross-rank collective-schedule rules (DL101-DL104) and
the static liveness/peak-HBM estimator (MEM001-MEM003), clean-world runs
over the bundled zoo at dp2 / dp4xtp2 / zero1-int8 / a 2-stage pipeline
world, the elastic standby pre-verification hook, the proglint --world
CLI, and the CPU-tier cross-check of the static peak estimate against
XLA's compiled ``memory_analysis`` (slow tier: it compiles)."""

import contextlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, optimizer
from paddle_tpu.core import analysis, telemetry, world_analysis
from paddle_tpu.core.analysis import ProgramVerificationError
from paddle_tpu.framework import OP_ROLE_KEY, OpRole


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_proglint():
    spec = importlib.util.spec_from_file_location(
        "proglint_under_test", os.path.join(_REPO, "tools", "proglint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def _flags(**kv):
    kv = {("FLAGS_" + k if not k.startswith("FLAGS_") else k): v
          for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


def _fc_world(hidden=8):
    """Tiny trainable model: enough params for several collectives."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.data("y", [-1, 1])
        h = layers.fc(x, size=hidden, act="relu")
        p = layers.fc(h, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


# -- clean worlds ------------------------------------------------------------


def test_clean_world_dp2():
    main, startup, loss = _fc_world()
    rep = world_analysis.verify_world(main, startup, 2,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    assert not rep.errors and not rep.warnings, rep.format()
    assert len(rep.hbm) == 2
    assert rep.hbm[0]["peak_bytes"] > 0


def test_clean_world_dp4_tp2():
    main, startup, loss = _fc_world()
    rep = world_analysis.verify_world(main, startup, 4, mesh=(4, 2),
                                      declared_world=8,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    assert not rep.errors and not rep.warnings, rep.format()
    assert len(rep.hbm) == 4


def test_clean_world_zero1_int8():
    main, startup, loss = _fc_world()
    rep = world_analysis.verify_world(main, startup, 2,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name],
                                      collective_mode="zero1",
                                      wire_dtype="int8")
    assert not rep.errors and not rep.warnings, rep.format()
    # the zero1 rewrite really happened: shard all-gathers in the trace
    with _flags(collective_mode="zero1", allreduce_dtype="int8"):
        worlds = world_analysis.materialize_world(main, startup, 2)
    trace = world_analysis.extract_trace(worlds[0][0])
    assert any(e.op_type.startswith("c_allgather") for e in trace)


def test_clean_world_pipeline_2stage():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 8, act="relu")
        logits = fluid.layers.fc(h, 2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        opt = fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(0.1), cut_list=[[h]],
            place_list=[fluid.CPUPlace(), fluid.CPUPlace()],
            queue_size=4)
        opt.minimize(loss)
    assert len(main._pipeline_opt["sections"]) == 2
    rep = world_analysis.verify_world(main, startup, 2,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    assert not rep.errors and not rep.warnings, rep.format()


@pytest.mark.parametrize("name", ["mnist_mlp", "word2vec"])
def test_clean_world_zoo(name):
    build = models.bundled_builders()[name]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, fetches = build()
        if not any(int(op.attr(OP_ROLE_KEY) or 0) & OpRole.Optimize
                   for op in main.global_block().ops):
            fluid.optimizer.SGD(learning_rate=0.01).minimize(fetches[0])
    rep = world_analysis.verify_world(
        main, startup, 2, feed_names=[v.name for v in feeds],
        fetch_names=[v.name for v in fetches], label=name)
    assert not rep.errors and not rep.warnings, rep.format()


# -- DL101: cross-rank schedule mismatch (static deadlock) -------------------


def test_dl101_rank3_missing_collective():
    main, startup, loss = _fc_world()
    worlds = world_analysis.materialize_world(main, startup, 4)
    m3, s3 = worlds[3]
    blk = m3.global_block()
    drop = next(i for i, op in enumerate(blk.ops)
                if op.type == "c_allreduce_sum")
    del blk.ops[drop]
    rep = world_analysis.verify_world(main, startup, 4,
                                      actual={3: (m3, s3)},
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    hits = rep.by_rule("DL101")
    assert hits, rep.format()
    d = hits[0]
    assert d.severity == analysis.ERROR
    assert d.rank == 3
    # the mismatch anchors at rank 3's first collective, which after the
    # delete is the op that slid into the dropped one's schedule slot
    expected = world_analysis.extract_trace(m3)[0].op_idx
    assert d.op_idx == expected
    assert "rank 3" in d.location()


def test_dl101_missing_tail_allgather_zero1():
    """ISSUE acceptance shape: rank 3 missing one all-gather."""
    main, startup, loss = _fc_world()
    with _flags(collective_mode="zero1", allreduce_dtype="int8"):
        worlds = world_analysis.materialize_world(main, startup, 4)
    m3, s3 = worlds[3]
    blk = m3.global_block()
    drop = max(i for i, op in enumerate(blk.ops)
               if op.type.startswith("c_allgather"))
    dropped_type = blk.ops[drop].type
    del blk.ops[drop]
    rep = world_analysis.verify_world(main, startup, 4,
                                      actual={3: (m3, s3)},
                                      collective_mode="zero1",
                                      wire_dtype="int8")
    hits = rep.by_rule("DL101")
    assert hits, rep.format()
    assert hits[0].rank == 3
    assert dropped_type in hits[0].message


# -- DL102: matched collectives disagree on payload --------------------------


def test_dl102_scale_mismatch():
    main, startup, loss = _fc_world()
    worlds = world_analysis.materialize_world(main, startup, 4)
    m1, s1 = worlds[1]
    op = next(op for op in m1.global_block().ops
              if op.type == "c_allreduce_sum")
    op.attrs["scale"] = 0.5  # stale 1/nranks fold from a 2-rank world
    rep = world_analysis.verify_world(main, startup, 4,
                                      actual={1: (m1, s1)})
    hits = rep.by_rule("DL102")
    assert hits, rep.format()
    assert hits[0].severity == analysis.ERROR
    assert hits[0].rank == 1
    assert "scale" in hits[0].message


# -- DL103: collective under rank-divergent control flow ---------------------


def _cond_collective_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8])
        s = layers.reduce_sum(x)
        pred = layers.less_than(
            layers.fill_constant([1], "float32", 0.0), s)

        def branch():
            t = layers.scale(s, scale=2.0)
            blk = main.current_block()
            blk.append_op(type="c_allreduce_sum", inputs={"X": [t]},
                          outputs={"Out": [t]},
                          attrs={"ring_id": 0,
                                 OP_ROLE_KEY: OpRole.Forward})
            return t

        layers.cond(pred, branch, lambda: layers.scale(s, scale=1.0))
    return main, startup


def test_dl103_collective_under_data_conditioned_branch():
    main, startup = _cond_collective_program()
    rep = world_analysis.verify_world(main, startup, 2, feed_names=["x"])
    hits = rep.by_rule("DL103")
    assert hits, rep.format()
    d = hits[0]
    assert d.severity == analysis.WARNING
    assert d.block_path and "conditional_block" in d.block_path
    assert "less_than" in d.message  # names the divergent condition var


def test_dl103_uniform_condition_is_clean():
    """A condition computed from an allreduced value is rank-uniform:
    the taint scrubs at the collective, so no DL103."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8])
        s = layers.reduce_sum(x)
        blk = main.current_block()
        blk.append_op(type="c_allreduce_sum", inputs={"X": [s]},
                      outputs={"Out": [s]},
                      attrs={"ring_id": 0, OP_ROLE_KEY: OpRole.Forward})
        pred = layers.less_than(
            layers.fill_constant([1], "float32", 0.0), s)

        def branch():
            t = layers.scale(s, scale=2.0)
            b = main.current_block()
            b.append_op(type="c_allreduce_sum", inputs={"X": [t]},
                        outputs={"Out": [t]},
                        attrs={"ring_id": 0, OP_ROLE_KEY: OpRole.Forward})
            return t

        layers.cond(pred, branch, lambda: layers.scale(s, scale=1.0))
    rep = world_analysis.verify_world(main, startup, 2, feed_names=["x"])
    assert not rep.by_rule("DL103"), rep.format()


# -- DL104: ring/world membership --------------------------------------------


def test_dl104_comm_init_nranks_tampered():
    main, startup, loss = _fc_world()
    worlds = world_analysis.materialize_world(main, startup, 4)
    m3, s3 = worlds[3]
    for op in s3.global_block().ops:
        if op.type == "c_comm_init":
            op.attrs["nranks"] = 2
    rep = world_analysis.verify_world(main, startup, 4,
                                      actual={3: (m3, s3)})
    hits = rep.by_rule("DL104")
    assert hits and hits[0].rank == 3, rep.format()
    assert hits[0].op_idx is not None


def test_dl104_ring_never_initialized():
    main, startup, loss = _fc_world()
    worlds = world_analysis.materialize_world(main, startup, 2)
    m0, s0 = worlds[0]
    blk = s0.global_block()
    drop = next(i for i, op in enumerate(blk.ops)
                if op.type == "c_comm_init")
    del blk.ops[drop]
    rep = world_analysis.verify_world(main, startup, 2,
                                      actual={0: (m0, s0)})
    hits = rep.by_rule("DL104")
    assert hits, rep.format()
    assert any(h.rank == 0 for h in hits)


def test_dl104_mesh_does_not_cover_world():
    main, startup, loss = _fc_world()
    rep = world_analysis.verify_world(main, startup, 2, mesh=(2, 2),
                                      declared_world=8)
    hits = rep.by_rule("DL104")
    assert hits, rep.format()
    assert hits[0].severity == analysis.ERROR


# -- MEM001-003: static peak-HBM estimator -----------------------------------


def test_mem001_reports_peak_per_rank():
    main, startup, loss = _fc_world()
    rep = world_analysis.verify_world(main, startup, 2, batch=16,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    hits = rep.by_rule("MEM001")
    assert len(hits) == 2
    assert all(h.severity == analysis.INFO for h in hits)
    est = rep.hbm[0]
    assert est["peak_bytes"] == (est["resident_bytes"] + est["feed_bytes"]
                                 + est["transient_peak_bytes"])
    assert est["batch"] == 16


def test_mem001_batch_scales_feeds_and_transients():
    main, startup, loss = _fc_world()
    small = world_analysis.estimate_program_hbm(
        main, feed_names=["x", "y"], fetch_names=[loss.name], batch=4)
    big = world_analysis.estimate_program_hbm(
        main, feed_names=["x", "y"], fetch_names=[loss.name], batch=64)
    assert big["feed_bytes"] == 16 * small["feed_bytes"]
    assert big["transient_peak_bytes"] > small["transient_peak_bytes"]
    assert big["resident_bytes"] == small["resident_bytes"]


def test_mem001_sharding_divides_per_replica_bytes():
    main, startup, loss = _fc_world()
    whole = world_analysis.estimate_program_hbm(
        main, feed_names=["x", "y"], batch=8)
    # batch-shard the feeds over a 4-way data axis
    quarter = world_analysis.estimate_program_hbm(
        main, feed_names=["x", "y"], batch=8, mesh_axes={"data": 4})
    assert quarter["feed_bytes"] * 4 == whole["feed_bytes"]


def test_mem002_no_donate_flags_rw_state():
    main, startup, loss = _fc_world()
    main._no_donate = True
    try:
        rep = world_analysis.verify_world(main, startup, 2,
                                          feed_names=["x", "y"])
    finally:
        main._no_donate = False
    hits = rep.by_rule("MEM002")
    assert hits, rep.format()
    assert hits[0].severity == analysis.WARNING


def test_mem003_budget_gate_via_flag():
    main, startup, loss = _fc_world()
    with _flags(hbm_budget_bytes=64):
        rep = world_analysis.verify_world(main, startup, 2, batch=8,
                                          feed_names=["x", "y"])
    hits = rep.by_rule("MEM003")
    assert hits, rep.format()
    assert hits[0].severity == analysis.ERROR
    # error-mode dispatch raises on it
    with _flags(hbm_budget_bytes=64, static_check="error"):
        with pytest.raises(ProgramVerificationError):
            rep = world_analysis.verify_world(main, startup, 2, batch=8,
                                              feed_names=["x", "y"])
            analysis._dispatch(rep, "error")
    # generous budget passes
    with _flags(hbm_budget_bytes=10 ** 12):
        rep = world_analysis.verify_world(main, startup, 2, batch=8,
                                          feed_names=["x", "y"])
    assert not rep.by_rule("MEM003")


def test_mem_fused_optimizer_flat_buffers_counted():
    """The fused-adam lowering materializes one flat temp per state slot
    it concatenates, over the group's vectors (ir.py MAX_FUSED_RANK: a
    matrix keeps its plain op and rates none); the estimator must predict
    that plateau on the pristine program whenever FLAGS_fuse_optimizer_ops
    would fuse it."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 64])
        h = x
        for _ in range(4):
            h = layers.relu(h + layers.create_parameter([64], "float32"))
        loss = layers.reduce_mean(h)
        optimizer.Adam(learning_rate=1e-3).minimize(loss)
    with _flags(fuse_optimizer_ops=True):
        fused = world_analysis.estimate_program_hbm(
            main, feed_names=["x"], batch=1)
    with _flags(fuse_optimizer_ops=False):
        plain = world_analysis.estimate_program_hbm(
            main, feed_names=["x"], batch=1)
    assert fused["transient_peak_bytes"] > plain["transient_peak_bytes"]


# -- DL003 block-path reporting (satellite) ----------------------------------


def test_dl003_reports_enclosing_block_path():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        cond_var = layers.less_than(i, n)
        w = layers.While(cond_var)
        with w.block():
            blk = main.current_block()
            blk.append_op(type="c_allreduce_sum", inputs={"X": [x]},
                          outputs={"Out": [x]},
                          attrs={"ring_id": -7,
                                 OP_ROLE_KEY: OpRole.Forward})
            i = layers.increment(i)
            layers.less_than(i, n, cond=cond_var)
    rep = analysis.verify_program(main, feed_names=["x"], label="dl003")
    hits = rep.by_rule("DL003")
    assert hits, rep.format()
    d = hits[0]
    assert d.block_path and d.block_path.startswith("while@block0")
    assert "in while@block0" in d.location()


# -- entry points ------------------------------------------------------------


def test_transpile_hook_clean_in_error_mode():
    """The collective transpiler's post-transpile hook materializes the
    sibling ranks and lockstep-matches them — a healthy transpile must
    come through clean (no recursion, no false DL101)."""
    from paddle_tpu.transpiler.collective import select_grad_transpiler

    main, startup, loss = _fc_world()
    eps = ["127.0.0.1:%d" % (7360 + i) for i in range(2)]
    with _flags(static_check="error"):
        t = select_grad_transpiler(1)
        t.transpile(startup_program=startup, main_program=main, rank=0,
                    endpoints=eps, current_endpoint=eps[0],
                    wait_port=False)
    assert main._collective_meta["nranks"] == 2


def test_elastic_standby_defect_blocks_adoption():
    """A standby view tampered between build and adoption: the adopt-time
    re-verify (the same _verify the standby/adopt paths call) must refuse
    it with DL101 in error mode."""
    from tests.test_elastic_standby import _member

    m = _member(rank=0)
    m.prepare_standby_views([(0, 1)])
    rec = m._standby[frozenset((0, 1))]
    blk = rec["main"].global_block()
    drop = next(i for i, op in enumerate(blk.ops)
                if op.type == "c_allreduce_sum")
    del blk.ops[drop]
    with _flags(static_check="error"):
        with pytest.raises(ProgramVerificationError) as ei:
            m._verify(rec["main"], rec["startup"], 2, pid=0)
    assert any(d.rule == "DL101" for d in ei.value.report.diagnostics)


def test_elastic_standby_clean_passes_world_verify():
    from tests.test_elastic_standby import _member

    m = _member(rank=0)
    m.prepare_standby_views([(0, 1)])
    rec = m._standby[frozenset((0, 1))]
    # does not raise: the world pass ran at build time with pid wired
    m._verify(rec["main"], rec["startup"], 2, pid=0)


def test_elastic_standby_fingerprint_gates_adopt_reverify():
    """Adoption only re-runs the expensive world verify when the standby
    IR changed since the build-time verify: an untouched view hashes to
    the stored fingerprint (re-verify skipped, verify phase stays 0), any
    tamper breaks the hash and routes through the blocking _verify."""
    from paddle_tpu.distributed.elastic import _world_fingerprint
    from tests.test_elastic_standby import _member

    m = _member(rank=0)
    m.prepare_standby_views([(0, 1)])
    rec = m._standby[frozenset((0, 1))]
    assert _world_fingerprint(rec["main"], rec["startup"]) \
        == rec["verified_fp"]
    blk = rec["main"].global_block()
    drop = next(i for i, op in enumerate(blk.ops)
                if op.type == "c_allreduce_sum")
    del blk.ops[drop]
    assert _world_fingerprint(rec["main"], rec["startup"]) \
        != rec["verified_fp"]


def test_world_telemetry_counters():
    main, startup, loss = _fc_world()
    with _flags(telemetry=True):
        telemetry.reset()
        world_analysis.verify_world(main, startup, 2,
                                    feed_names=["x", "y"])
        runs = telemetry.counter_total("static_check_world_runs_total")
        snap = telemetry.snapshot()
    assert runs >= 1
    assert snap["gauges"].get("static_check_world_ranks") == 2.0
    assert snap["gauges"].get("static_check_world_peak_bytes", 0) > 0
    telemetry.reset()


def test_metrics_dump_lint_filter(tmp_path):
    snap = {"counters": {"static_check_world_runs_total": 3,
                         "static_check_warnings{rule=DL101}": 1,
                         "executor_steps_total": 9},
            "gauges": {"static_check_world_ranks": 4,
                       "elastic_world": 4},
            "histograms": {}, "events_logged": {}}
    p = tmp_path / "metrics.json"
    p.write_text(json.dumps(snap))
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "metrics_dump.py"),
         "--json", str(p), "--lint"],
        capture_output=True, text=True, check=True).stdout
    assert "static_check_world_runs_total" in out
    assert "static_check_warnings" in out
    assert "executor_steps_total" not in out
    assert "elastic_world" not in out


def test_proglint_world_cli_seeded_dl101(capsys):
    """Acceptance: proglint --world 4 reports a seeded rank-divergent
    schedule as DL101 with exact rank and op idx."""
    proglint = _load_proglint()
    rc = proglint.main(["--builtin", "mnist_mlp", "--world", "4",
                        "--seed-defect", "dl101"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DL101" in out
    assert "rank 3" in out
    # the dropped op's index is echoed and reported
    m = re.search(r"dropped \S+ at op (\d+) from rank 3", out)
    assert m and ("op %s" % m.group(1)) in out


def test_proglint_world_cli_clean(capsys):
    proglint = _load_proglint()
    rc = proglint.main(["--builtin", "mnist_mlp", "--world", "8",
                        "--mesh", "4x2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "MEM001" in out


# -- CPU-tier cross-check against the compiled memory_analysis ---------------


def _run_and_crosscheck(build_feed):
    main, startup, feed, fetch = build_feed()
    exe = fluid.Executor(fluid.CPUPlace())
    with _flags(hbm_audit=True, telemetry=True):
        telemetry.reset()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[fetch])
        report = telemetry.snapshot().get("info", {}).get("memory_audit")
    telemetry.reset()
    assert report, "hbm audit did not run"
    a = report["analysis"]
    assert "error" not in a, a
    compiled_peak = (a["argument_size_in_bytes"]
                     + a["output_size_in_bytes"]
                     + a["temp_size_in_bytes"]
                     - a["alias_size_in_bytes"])
    est = world_analysis.estimate_program_hbm(
        main, feed_names=list(feed), fetch_names=[fetch.name],
        feed_shapes={n: np.asarray(v).shape for n, v in feed.items()})
    ratio = est["peak_bytes"] / float(compiled_peak)
    assert 0.8 <= ratio <= 1.2, (
        "static peak %d vs compiled %d (ratio %.3f) outside 20%%"
        % (est["peak_bytes"], compiled_peak, ratio))


@pytest.mark.slow
def test_static_peak_within_20pct_of_compiled_bert_tiny():
    from paddle_tpu.models import bert

    def build():
        cfg = bert.BERT_TINY
        seq = 16
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            inputs, loss = bert.build_pretrain(cfg, seq_len=seq, lr=1e-3)
        rng = np.random.RandomState(0)
        B = 2
        feed = {
            "src_ids": rng.randint(0, cfg.vocab_size,
                                   (B, seq, 1)).astype("int64"),
            "pos_ids": np.tile(np.arange(seq).reshape(1, seq, 1),
                               (B, 1, 1)).astype("int64"),
            "sent_ids": np.zeros((B, seq, 1), "int64"),
            "input_mask": np.ones((B, seq, 1), "float32"),
            "mask_pos": np.array([1, 5, seq + 2], "int64"),
            "mask_label": rng.randint(0, cfg.vocab_size,
                                      (3, 1)).astype("int64"),
        }
        return main, startup, feed, loss

    _run_and_crosscheck(build)


@pytest.mark.slow
def test_static_peak_within_20pct_of_compiled_resnet_tiny():
    from paddle_tpu.models import resnet

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img, label, loss, acc = resnet.build_train(
                depth=18, class_dim=10, image_size=32)
        rng = np.random.RandomState(0)
        B = 4
        feed = {"img": rng.rand(B, 3, 32, 32).astype("float32"),
                "label": rng.randint(0, 10, (B, 1)).astype("int64")}
        return main, startup, feed, loss

    _run_and_crosscheck(build)
