"""Profiler, runtime flags, and metric accumulator tests
(reference: test_profiler.py, test_metrics.py patterns)."""

import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import metrics, profiler
from paddle_tpu.core import tracing


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.fc(x, 3)
        loss = fluid.layers.reduce_mean(y)
    return main, startup, loss


def test_profiler_records_and_exports(tmp_path):
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    path = str(tmp_path / "trace.json")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        profiler.reset_profiler()
        fluid.set_flags({"FLAGS_tracing": True})
        tracing.reset()
        try:
            with profiler.profiler("All", "total", path):
                for _ in range(3):
                    with profiler.RecordEvent("train::step"):
                        exe.run(main, feed={"x": np.ones((2, 4), "f")},
                                fetch_list=[loss])
        finally:
            fluid.set_flags({"FLAGS_tracing": False})
    with open(path) as f:
        trace = json.load(f)
    runs = [e for e in trace["traceEvents"] if e["name"] == "train::step"]
    assert len(runs) == 3
    assert all(e["dur"] >= 0 for e in runs)
    # the executor's own record of a run is its executor.step span (the
    # tracing stream), no longer a RecordEvent of its own
    assert not [e for e in trace["traceEvents"]
                if e["name"] == "Executor::Run"]
    steps = tracing.records("executor.step")
    tracing.reset()
    assert len(steps) == 3 and all(s["dur"] >= 0 for s in steps)
    # disabled afterwards: no new events
    n = len(trace["traceEvents"])
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
    profiler.save_chrome_trace(path)
    with open(path) as f:
        assert len(json.load(f)["traceEvents"]) == n


def test_profiler_dygraph_events():
    from paddle_tpu import dygraph

    profiler.reset_profiler()
    with dygraph.guard():
        profiler.start_profiler()
        a = dygraph.to_variable(np.ones((2, 2), "f"))
        b = fluid.layers.elementwise_add(a, a)
        profiler.stop_profiler()
    assert any(e[0] == "elementwise_add" for e in profiler._events)


def test_check_nan_inf_flag_static():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[2])
        y = fluid.layers.elementwise_div(x, fluid.layers.scale(x, scale=0.0))
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with pytest.raises(RuntimeError, match="NaN/Inf"):
                exe.run(main, feed={"x": np.ones((1, 2), "f")},
                        fetch_list=[y])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_flags_get_set_roundtrip():
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    assert fluid.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] is True
    fluid.set_flags({"check_nan_inf": False})  # short name accepted
    assert fluid.get_flags(["check_nan_inf"])["FLAGS_check_nan_inf"] is False
    # inert flags accepted without error
    fluid.set_flags({"FLAGS_eager_delete_tensor_gb": 1.5})
    assert fluid.get_flags("FLAGS_eager_delete_tensor_gb")[
        "FLAGS_eager_delete_tensor_gb"] == 1.5


# -- metrics -----------------------------------------------------------------


def test_precision_recall():
    p, r = metrics.Precision(), metrics.Recall()
    preds = np.array([1, 1, 0, 1, 0])
    labels = np.array([1, 0, 0, 1, 1])
    p.update(preds, labels)
    r.update(preds, labels)
    assert p.eval() == pytest.approx(2 / 3)
    assert r.eval() == pytest.approx(2 / 3)
    p.reset()
    assert p.eval() == 0.0


def test_accuracy_weighted():
    acc = metrics.Accuracy()
    acc.update(0.5, weight=10)
    acc.update(1.0, weight=10)
    assert acc.eval() == pytest.approx(0.75)
    with pytest.raises(ValueError):
        acc.update(0.5, weight=-1)


def test_chunk_evaluator():
    ce = metrics.ChunkEvaluator()
    ce.update(10, 8, 6)
    precision, recall, f1 = ce.eval()
    assert precision == pytest.approx(0.6)
    assert recall == pytest.approx(0.75)
    assert f1 == pytest.approx(2 * 0.6 * 0.75 / 1.35)


def test_edit_distance():
    ed = metrics.EditDistance()
    ed.update(np.array([0.0, 2.0, 1.0]), 3)
    avg, err = ed.eval()
    assert avg == pytest.approx(1.0)
    assert err == pytest.approx(2 / 3)


def test_auc_matches_sklearn_style_reference():
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 2, 200)
    # informative scores
    scores = np.clip(labels * 0.6 + rng.rand(200) * 0.5, 0, 1)
    auc = metrics.Auc()
    auc.update(scores, labels)
    got = auc.eval()

    # exact AUC by rank statistic
    pos = scores[labels == 1]
    neg = scores[labels == 0]
    exact = np.mean([(p > n) + 0.5 * (p == n) for p in pos for n in neg])
    assert got == pytest.approx(exact, abs=2e-3)


def test_composite_metric():
    cm = metrics.CompositeMetric()
    cm.add_metric(metrics.Precision())
    cm.add_metric(metrics.Recall())
    cm.update(np.array([1, 0]), np.array([1, 1]))
    p, r = cm.eval()
    assert p == 1.0 and r == 0.5
