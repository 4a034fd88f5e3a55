"""A persistable is placed on the mesh once (core/executor.py
``_shard_params``): an array that already lies on its target goes into the
compiled step untouched, anything else is placed as before, and the
``executor.step`` span says how many of each.  One parametrised test a
route, on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

import jax
import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as tr

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8 or jax.devices()[0].platform != "cpu",
    reason="needs the 8-device virtual CPU mesh")


@pytest.fixture(autouse=True)
def _recording(tmp_path):
    tr.reset()
    _tm.reset()
    fluid.set_flags({"FLAGS_tracing": True, "FLAGS_telemetry": True,
                     "FLAGS_telemetry_dir": str(tmp_path / "tel")})
    yield
    tr.reset()
    _tm.reset()
    fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry": False,
                     "FLAGS_telemetry_dir": ""})


def _mlp(optimizer="adam", w1_sharding=None, frozen=False, seed=3):
    """x[8] -> fc 16 -> fc 4 -> cross entropy.  ``frozen`` adds a read-only
    persistable (a parameter no optimizer op writes)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        if frozen:
            x = fluid.layers.fc(
                x, 8, bias_attr=False,
                param_attr=fluid.ParamAttr(name="frozen_w", trainable=False))
        h = fluid.layers.fc(
            x, 16, act="relu",
            param_attr=fluid.ParamAttr(name="w1", sharding=w1_sharding))
        logits = fluid.layers.fc(h, 4, param_attr=fluid.ParamAttr(name="w2"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        opt = (fluid.optimizer.Adam(1e-2) if optimizer == "adam"
               else fluid.optimizer.SGD(0.1))
    return main, startup, loss, opt


def _built(**kw):
    main, startup, loss, opt = _mlp(**kw)
    with fluid.program_guard(main, startup):
        opt.minimize(loss)
    return main, startup, loss


def _dp(main, loss, n):
    return fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=[fluid.TPUPlace(i) for i in range(n)])


def _feed(i, n=64):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(n, 8).astype("float32"),
            "y": rng.randint(0, 4, (n, 1)).astype("int64")}


def _step(exe, prog, loss, i):
    out, = exe.run(prog, feed=_feed(i), fetch_list=[loss])
    return np.asarray(out).copy()


def _value(scope, name):
    return scope.find_var(name).get_tensor().get()


def _spans():
    return [s["attrs"] for s in tr.records("executor.step")
            if "params_placed" in s["attrs"]]


def _counts():
    return [(a["params_placed"], a["params_passed"]) for a in _spans()]


def _mesh_entry(exe):
    """The cached executable of the one mesh program ``exe`` has run."""
    entry, = [e for e in exe._cache.values() if e.param_shardings]
    return entry


def _spy_inputs(exe):
    """Record the (ro, rw) dicts the mesh program's executable is called
    with."""
    calls = []
    entry = _mesh_entry(exe)
    jfn = entry.jfn

    def spied(feeds, ro, rw, carry, rng):
        calls.append((dict(ro), dict(rw)))
        return jfn(feeds, ro, rw, carry, rng)

    entry.jfn = spied
    return calls


# -- the cases ----------------------------------------------------------------

def case_dp_adam_passes_through_from_step_2(monkeypatch):
    main, startup, loss = _built()
    prog = _dp(main, loss, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    puts = []
    real_put = jax.device_put

    def counting_put(x, *a, **kw):
        puts.append(np.shape(x))
        return real_put(x, *a, **kw)

    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        _step(exe, prog, loss, 0)
        calls = _spy_inputs(exe)
        monkeypatch.setattr(jax, "device_put", counting_put)
        for i in range(1, 5):
            entry = _mesh_entry(exe)
            before = {n: _value(scope, n) for n in entry.plan.rw_names}
            _step(exe, prog, loss, i)
            _ro, rw = calls[-1]
            assert set(rw) == set(before) and len(rw) > 6
            for n in before:
                assert rw[n] is before[n], n
        monkeypatch.setattr(jax, "device_put", real_put)
    # the compile path places everything, every step after it nothing
    counts = _counts()
    total = sum(counts[0])
    assert counts[0] == (total, 0)
    assert counts[1:] == [(0, total)] * 4
    # two feeds a step and nothing else went through device_put
    assert sorted(puts) == sorted([(64, 8), (64, 1)] * 4)
    assert _tm.counter_total("executor_params_placed_total") == total
    assert _tm.counter_total("executor_params_passed_total") == 4 * total


def case_losses_bitwise_equal_to_forced_placement(monkeypatch):
    def run(force):
        main, startup, loss = _built()
        prog = _dp(main, loss, 4)
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        with fluid.scope_guard(fluid.Scope()):
            scope = fluid.global_scope()
            exe.run(startup)
            for i in range(6):
                if force:
                    # numpy copies in the scope: every array has to be
                    # placed, as it was on every step before
                    for n in scope.local_var_names():
                        v = _value(scope, n)
                        if v is not None:
                            scope.var(n).set(np.array(v))
                losses.append(_step(exe, prog, loss, i))
        return losses

    tr.reset()
    passed = run(False)
    n_passed = _counts()
    tr.reset()
    forced = run(True)
    n_forced = _counts()
    assert all(p == 0 for p, _ in n_passed[1:])
    assert all(q == 0 for _, q in n_forced)
    for a, b in zip(passed, forced):
        assert a.tobytes() == b.tobytes()
    assert len(passed) == 6 and passed[0] != passed[-1]


def case_external_set_is_placed_once_and_used(monkeypatch):
    main, startup, loss = _built(optimizer="sgd")
    prog = _dp(main, loss, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        for i in range(2):
            _step(exe, prog, loss, i)
        # a checkpoint restore: numpy into the scope, mid-run
        restored = np.full(np.shape(_value(scope, "w2")), 0.25, "float32")
        snapshot = {n: np.array(_value(scope, n))
                    for n in scope.local_var_names()
                    if _value(scope, n) is not None}
        scope.var("w2").set(restored)
        got = _step(exe, prog, loss, 2)
        w2_after = np.array(_value(scope, "w2"))
        _step(exe, prog, loss, 3)
        # the same step from the same state, every array placed afresh
        for n, v in snapshot.items():
            scope.var(n).set(v)
        scope.var("w2").set(restored)
        want = _step(exe, prog, loss, 2)
        assert got.tobytes() == want.tobytes()
        assert w2_after.tobytes() == np.array(_value(scope, "w2")).tobytes()
        assert not np.array_equal(w2_after, restored)
    counts = _counts()
    total = sum(counts[0])
    assert counts[1:] == [(0, total), (1, total - 1), (0, total),
                          (total, 0)]


def case_read_only_is_placed_once_until_its_object_changes(monkeypatch):
    main, startup, loss = _built(optimizer="sgd", frozen=True)
    prog = _dp(main, loss, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        host = np.array(_value(scope, "frozen_w"))
        scope.var("frozen_w").set(host)
        _step(exe, prog, loss, 0)
        entry = _mesh_entry(exe)
        assert "frozen_w" in entry.plan.ro_names
        calls = _spy_inputs(exe)
        for i in range(1, 4):
            _step(exe, prog, loss, i)
        # the scope keeps what the user put there; the step got one copy
        assert _value(scope, "frozen_w") is host
        copies = [ro["frozen_w"] for ro, _rw in calls]
        assert all(c is copies[0] for c in copies)
        assert copies[0].sharding == entry.param_shardings["frozen_w"]
        # an equal value in a new object: placed again, once
        scope.var("frozen_w").set(host.copy())
        _step(exe, prog, loss, 4)
        _step(exe, prog, loss, 5)
        assert calls[-1][0]["frozen_w"] is calls[-2][0]["frozen_w"]
        assert calls[-1][0]["frozen_w"] is not copies[0]
    counts = _counts()
    total = sum(counts[0])
    assert [p for p, _ in counts[1:]] == [0, 0, 0, 1, 0]
    assert all(p + q == total for p, q in counts)


def case_dp_x_tp_keeps_the_annotated_spec(monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    main, startup, loss = _built(w1_sharding=(None, "model"))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    prog = fluid.CompiledProgram(main)._with_mesh(mesh, data_axis="data")
    exe = fluid.Executor(fluid.CPUPlace())
    want = NamedSharding(mesh, P(None, "model"))
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        losses = [_step(exe, prog, loss, i) for i in range(4)]
        entry = _mesh_entry(exe)
        assert entry.param_shardings["w1"] == want
        assert entry.param_shardings["w2"] == NamedSharding(mesh, P())
        w1 = _value(scope, "w1")
        assert w1.sharding == want
        assert {s.data.shape for s in w1.addressable_shards} == {(8, 8)}
        # its Adam moments take the parameter's annotation or none: either
        # way they come back where they are taken
        for n in entry.plan.rw_names:
            assert _value(scope, n).sharding == entry.param_shardings[n], n
    counts = _counts()
    assert counts[1:] == [(0, sum(counts[0]))] * 3
    assert losses[-1].item() < losses[0].item()


def case_transpiled_shard_map_route_passes_through(monkeypatch):
    from paddle_tpu.incubate.fleet.base import role_maker
    from paddle_tpu.incubate.fleet.collective import fleet

    fleet.init(role_maker.UserDefinedCollectiveRoleMaker(0))
    main, startup, loss, opt = _mlp(optimizer="sgd")
    with fluid.program_guard(main, startup):
        fleet.distributed_optimizer(opt).minimize(loss)
    assert "c_allreduce_sum" in [op.type for op in main.global_block().ops]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        losses = [float(np.mean(_step(exe, main, loss, i)))
                  for i in range(4)]
        entry = _mesh_entry(exe)
        # the mesh came from the build, the targets with it
        assert entry.mesh is not None and entry.param_shardings
        for n in entry.plan.rw_names:
            v = _value(scope, n)
            assert v.sharding.is_equivalent_to(entry.param_shardings[n],
                                               v.ndim), n
        # an equivalent layout under another spelling is neither the
        # target nor equal to it, and the executable takes it as it is
        from jax.sharding import NamedSharding, PartitionSpec as P

        target = entry.param_shardings["w1"]
        respelt = jax.device_put(
            _value(scope, "w1"), NamedSharding(target.mesh, P(None, None)))
        assert respelt.sharding != target
        assert exe._lies_on(respelt, target)
        assert not exe._lies_on(respelt, target, exact=True)
        scope.var("w1").set(respelt)
        losses.append(float(np.mean(_step(exe, main, loss, 4))))
    counts = _counts()
    assert counts[1:] == [(0, sum(counts[0]))] * 4
    assert losses[-1] < losses[0]


def case_smaller_mesh_re_places_and_trains_on(monkeypatch):
    main, startup, loss = _built()
    exe = fluid.Executor(fluid.CPUPlace())
    four, two = _dp(main, loss, 4), _dp(main, loss, 2)
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        losses = [_step(exe, four, loss, i) for i in range(3)]
        assert len(_value(scope, "w1").sharding.device_set) == 4
        losses += [_step(exe, two, loss, i) for i in range(3, 6)]
        assert len(_value(scope, "w1").sharding.device_set) == 2
        # the entry of the four-device mesh is still cached: back on it a
        # hit finds every array on another mesh and places each
        losses.append(_step(exe, four, loss, 6))
        assert len(_value(scope, "w1").sharding.device_set) == 4
    counts = _counts()
    total = sum(counts[0])
    assert counts == [(total, 0), (0, total), (0, total),
                      (total, 0), (0, total), (0, total), (total, 0)]
    assert _spans()[-1]["cache_hit"]
    assert np.all(np.isfinite(losses))
    assert losses[-1].item() < losses[0].item()


def case_no_donate_program_passes_through(monkeypatch):
    main, startup, loss = _built(optimizer="sgd")
    main._no_donate = True
    prog = _dp(main, loss, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe.run(startup)
        _step(exe, prog, loss, 0)
        calls = _spy_inputs(exe)
        held = []
        for i in range(1, 4):
            before = _value(scope, "w1")
            _step(exe, prog, loss, i)
            assert calls[-1][1]["w1"] is before
            # not donated: the input outlives the call
            held.append(np.asarray(before))
            assert _value(scope, "w1") is not before
    counts = _counts()
    assert counts[1:] == [(0, sum(counts[0]))] * 3
    assert not np.array_equal(held[0], held[-1])


CASES = [
    case_dp_adam_passes_through_from_step_2,
    case_losses_bitwise_equal_to_forced_placement,
    case_external_set_is_placed_once_and_used,
    case_read_only_is_placed_once_until_its_object_changes,
    case_dp_x_tp_keeps_the_annotated_spec,
    case_transpiled_shard_map_route_passes_through,
    case_smaller_mesh_re_places_and_trains_on,
    case_no_donate_program_passes_through,
]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[len("case_"):] for c in CASES])
def test_param_placement(case, monkeypatch):
    case(monkeypatch)
