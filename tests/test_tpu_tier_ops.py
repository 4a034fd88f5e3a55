"""On-chip op-test tier (round-2 verdict item 6): re-instantiate the
whole OpTest corpus (math/nn/manip/longtail modules) against
TPUPlace(0) in f32 AND bf16 — the reference's backend-variant suite
pattern (unittests/mkldnn/: OpTest subclasses re-run with backend flags,
per-place parametrization op_test.py:782) — plus direct on-chip goldens
for the sequence, optimizer and detection families the round-2 verdict
called out as never running on the chip.

Runs only in the TPU tier: PADDLE_TPU_TESTS=1 pytest -m tpu.
"""

import importlib

import numpy as np
import pytest

import paddle_tpu as fluid
from op_test import OpTest

pytestmark = pytest.mark.tpu

_MODULES = ("test_ops_math", "test_ops_nn", "test_ops_manip",
            "test_longtail_ops")

# classes whose contract can't run under the generic per-place re-check
_EXCLUDE = {
    # rng-output ops: goldens are distribution properties, not values
    "TestDropoutOp", "TestUniformRandomOp", "TestGaussianRandomOp",
}


def _collect():
    cases = []
    for mod_name in _MODULES:
        mod = importlib.import_module(mod_name)
        for name in sorted(vars(mod)):
            cls = vars(mod)[name]
            if (isinstance(cls, type) and issubclass(cls, OpTest)
                    and cls is not OpTest
                    and getattr(cls, "op_type", None)
                    and name not in _EXCLUDE):
                cases.append(pytest.param((mod_name, name),
                                          id="%s.%s" % (mod_name, name)))
    return cases


@pytest.mark.parametrize("dtype", [None, "bfloat16"],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", _collect())
def test_op_on_chip(case, dtype):
    mod_name, cls_name = case
    cls = getattr(importlib.import_module(mod_name), cls_name)
    t = cls()
    if hasattr(t, "setup_method"):
        t.setup_method(None)
    no_check = tuple(getattr(t, "tpu_no_check", ()))
    t.check_output_with_place(fluid.TPUPlace(0), dtype=dtype,
                              no_check_set=no_check)


# -- direct on-chip goldens for families absent from the OpTest corpus ------


def _run_on_chip(build_fn, feed, fetch):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetch_vars = build_fn()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        res = exe.run(main, feed=feed,
                      fetch_list=fetch_vars if fetch is None else fetch)
    return [np.asarray(r) for r in res]


class TestSequenceFamilyOnChip:
    @pytest.mark.parametrize("pooltype", ["sum", "average", "max"])
    def test_sequence_pool(self, pooltype):
        rng = np.random.RandomState(0)
        x = rng.uniform(-1, 1, (2, 5, 3)).astype("f")

        def build():
            xv = fluid.layers.data("x", shape=[5, 3])
            return [fluid.layers.sequence_pool(xv, pooltype)]

        out, = _run_on_chip(build, {"x": x}, None)
        want = {"sum": x.sum(1), "average": x.mean(1),
                "max": x.max(1)}[pooltype]
        np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-3)

    def test_sequence_softmax(self):
        rng = np.random.RandomState(1)
        x = rng.uniform(-2, 2, (2, 6, 1)).astype("f")

        def build():
            xv = fluid.layers.data("x", shape=[6, 1])
            return [fluid.layers.sequence_softmax(xv)]

        out, = _run_on_chip(build, {"x": x}, None)
        e = np.exp(x - x.max(1, keepdims=True))
        np.testing.assert_allclose(out, e / e.sum(1, keepdims=True),
                                   rtol=1e-3, atol=1e-3)

    def test_sequence_expand_and_concat(self):
        rng = np.random.RandomState(2)
        a = rng.uniform(-1, 1, (2, 3, 2)).astype("f")
        b = rng.uniform(-1, 1, (2, 2, 2)).astype("f")

        def build():
            av = fluid.layers.data("a", shape=[3, 2])
            bv = fluid.layers.data("b", shape=[2, 2])
            return [fluid.layers.sequence_concat([av, bv])]

        out, = _run_on_chip(build, {"a": a, "b": b}, None)
        np.testing.assert_allclose(out, np.concatenate([a, b], 1),
                                   rtol=1e-5)

    def test_sequence_reverse(self):
        rng = np.random.RandomState(3)
        x = rng.uniform(-1, 1, (2, 4, 3)).astype("f")

        def build():
            xv = fluid.layers.data("x", shape=[4, 3])
            return [fluid.layers.sequence_reverse(xv)]

        out, = _run_on_chip(build, {"x": x}, None)
        np.testing.assert_allclose(out, x[:, ::-1], rtol=1e-5)


class TestOptimizerFamilyOnChip:
    @pytest.mark.parametrize("opt_name", ["sgd", "momentum", "adam",
                                          "adagrad", "rmsprop", "lamb"])
    def test_optimizer_step(self, opt_name):
        """One optimizer step on the chip must track the CPU run of the
        same program (optimizer-family on-chip coverage)."""
        opt_map = {
            "sgd": lambda: fluid.optimizer.SGD(0.1),
            "momentum": lambda: fluid.optimizer.Momentum(0.1, 0.9),
            "adam": lambda: fluid.optimizer.Adam(0.1),
            "adagrad": lambda: fluid.optimizer.Adagrad(0.1),
            "rmsprop": lambda: fluid.optimizer.RMSProp(0.1),
            "lamb": lambda: fluid.optimizer.Lamb(0.01),
        }
        rng = np.random.RandomState(4)
        xb = rng.randn(8, 4).astype("f")
        yb = rng.randn(8, 1).astype("f")

        def run(place):
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = 11
            startup.random_seed = 11
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", shape=[4])
                y = fluid.layers.data("y", shape=[1])
                pred = fluid.layers.fc(
                    x, 1, param_attr=fluid.ParamAttr(name="tw"))
                loss = fluid.layers.mean(
                    fluid.layers.square(pred - y))
                opt_map[opt_name]().minimize(loss)
            exe = fluid.Executor(place)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                for _ in range(3):
                    exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
                return np.asarray(
                    scope.find_var("tw").get_tensor().numpy())

        tpu = run(fluid.TPUPlace(0))
        cpu = run(fluid.CPUPlace())
        np.testing.assert_allclose(tpu, cpu, rtol=2e-3, atol=2e-3)


class TestDetectionFamilyOnChip:
    def test_box_coder_decode(self):
        prior = np.asarray([[0.1, 0.1, 0.5, 0.5],
                            [0.2, 0.2, 0.6, 0.6]], "f")
        target = np.zeros((2, 2, 4), "f")  # zero deltas -> boxes = priors

        def build():
            pv = fluid.layers.data("prior", shape=[2, 4],
                                   append_batch_size=False)
            tv = fluid.layers.data("target", shape=[2, 2, 4],
                                   append_batch_size=False)
            return [fluid.layers.box_coder(
                pv, None, tv, code_type="decode_center_size")]

        out, = _run_on_chip(build, {"prior": prior, "target": target},
                            None)
        np.testing.assert_allclose(
            out, np.broadcast_to(prior, (2, 2, 4)), rtol=1e-3, atol=1e-3)

    def test_multiclass_nms_on_chip(self):
        bboxes = np.asarray([[[0.1, 0.1, 0.4, 0.4],
                              [0.11, 0.1, 0.41, 0.4],
                              [0.6, 0.6, 0.9, 0.9]]], "f")
        scores = np.asarray([[[0.0, 0.0, 0.0],
                              [0.9, 0.8, 0.7]]], "f")

        def build():
            bv = fluid.layers.data("b", shape=[3, 4])
            sv = fluid.layers.data("s", shape=[2, 3])
            return [fluid.layers.multiclass_nms(
                bv, sv, background_label=0, score_threshold=0.1,
                nms_threshold=0.5, keep_top_k=8, nms_top_k=8)]

        out, = _run_on_chip(build, {"b": bboxes, "s": scores}, None)
        kept = out.reshape(-1, 6)
        kept = kept[kept[:, 0] >= 0]
        assert kept.shape[0] == 2
        np.testing.assert_allclose(sorted(kept[:, 1], reverse=True),
                                   [0.9, 0.7], atol=1e-5)


class TestFusionFamilyOnChip:
    def test_fusion_gru_on_chip(self):
        """One fusion-family op exercised on the chip (the round-2 gap:
        no fusion op ever ran on TPU)."""
        from test_op_tail_goldens import _np_gru, run_op

        rng = np.random.RandomState(5)
        B, T, F, D = 2, 5, 6, 4
        x = rng.uniform(-1, 1, (B, T, F)).astype("f")
        wx = rng.uniform(-0.5, 0.5, (F, 3 * D)).astype("f")
        wh = rng.uniform(-0.5, 0.5, (D, 3 * D)).astype("f")
        from paddle_tpu.framework import convert_np_dtype_to_dtype_

        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            block = main.global_block()
            for nm, arr in (("fx", x), ("fwx", wx), ("fwh", wh)):
                block.create_var(name=nm, shape=arr.shape,
                                 dtype=convert_np_dtype_to_dtype_(
                                     arr.dtype))
            for s in ("Hidden",):
                block.create_var(name="out_" + s)
            block.append_op(type="fusion_gru",
                            inputs={"X": ["fx"], "WeightX": ["fwx"],
                                    "WeightH": ["fwh"]},
                            outputs={"Hidden": ["out_Hidden"]}, attrs={})
        exe = fluid.Executor(fluid.TPUPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(main, feed={"fx": x, "fwx": wx, "fwh": wh},
                           fetch_list=["out_Hidden"])
        want = _np_gru(x @ wx, wh)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-3,
                                   atol=2e-3)


def _grad_params():
    """Classes opt into the on-chip grad check by declaring a `tpu_grad`
    dict (inputs_to_check + optional check_grad kwargs) — single source
    of truth next to each class's own test_grad."""
    out = []
    for mod_name in _MODULES:
        mod = importlib.import_module(mod_name)
        for name in sorted(vars(mod)):
            cls = vars(mod)[name]
            if (isinstance(cls, type) and issubclass(cls, OpTest)
                    and getattr(cls, "tpu_grad", None)):
                out.append(pytest.param((mod_name, name),
                                        id="%s.%s" % (mod_name, name)))
    return out


@pytest.mark.parametrize("case", _grad_params())
def test_grad_on_chip(case):
    """Analytic-vs-numeric gradients ON THE CHIP for core training ops
    (check_grad_with_place, reference op_test.py:1033: analytic grads run
    on the TPU, finite differences stay on CPU; the TPU tolerance tier
    applies via the helper's place-aware default)."""
    mod, cls_name = case
    cls = getattr(importlib.import_module(mod), cls_name)
    t = cls()
    if hasattr(t, "setup_method"):
        t.setup_method(None)
    kwargs = dict(cls.tpu_grad)
    inputs = kwargs.pop("inputs_to_check")
    t.check_grad_with_place(fluid.TPUPlace(0), inputs, **kwargs)
