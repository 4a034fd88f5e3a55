"""fused_dropout_add_ln: the fused transformer-encoder epilogue op.

Coverage model per reference op_test.py check_output/check_grad: exact
parity against the composed dropout->add->layer_norm emission at p=0,
mask-replay gradient parity at p>0 (the kernel/fallback re-draws the
mask in the backward from the saved seed — these tests prove the
forward and backward masks agree), and program-level training through
the Executor.  TPU-marked variants exercise the Pallas kernel path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.pallas_kernels import fused_ln as F


def _ref_ln(r, g, b, eps=1e-5):
    rf = r.astype(np.float32)
    m = rf.mean(-1, keepdims=True)
    c = rf - m
    v = (c * c).mean(-1, keepdims=True)
    return c / np.sqrt(v + eps) * g + b


def test_p0_matches_composed_ln():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 6, 16).astype("float32")
    y = rng.randn(4, 6, 16).astype("float32")
    g = (rng.rand(16) + 0.5).astype("float32")
    b = rng.randn(16).astype("float32")
    seed = jnp.array([1, 2], jnp.uint32)
    z = np.asarray(F.fused_dropout_add_ln(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(g), jnp.asarray(b),
        0.0, seed))
    ref = _ref_ln((x + y).reshape(-1, 16), g, b).reshape(4, 6, 16)
    np.testing.assert_allclose(z, ref, atol=2e-5, rtol=2e-5)


def test_dropout_mask_replay_grads():
    """dy==0 exactly where dropped; grads match a mask-replay reference."""
    rng = np.random.RandomState(1)
    N, H = 64, 32
    x = jnp.asarray(rng.randn(N, H), jnp.float32)
    y = jnp.asarray(rng.randn(N, H), jnp.float32)
    g = jnp.asarray(rng.rand(H) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(H), jnp.float32)
    seed = jnp.array([11, 22], jnp.uint32)
    p = 0.4

    loss = lambda x, y, g, b: (
        F.fused_dropout_add_ln(x, y, g, b, p, seed) ** 2).sum()
    dx, dy, dg, db = jax.grad(loss, (0, 1, 2, 3))(x, y, g, b)
    dropped = np.asarray(dy == 0.0)
    assert 0.2 < dropped.mean() < 0.6

    # perturbing a dropped coordinate must not change the output
    zval = F.fused_dropout_add_ln(x, y, g, b, p, seed)
    i, j = np.argwhere(dropped)[0]
    z2 = F.fused_dropout_add_ln(x, y.at[i, j].add(50.0), g, b, p, seed)
    assert bool(jnp.array_equal(z2, zval))

    # mask-replay reference grads
    keep = jnp.asarray(~dropped)
    q = F._realized_q(F._keep_threshold(p))

    def ref(x, y, g, b):
        r = x + jnp.where(keep, y / q, 0.0)
        m = r.mean(-1, keepdims=True)
        v = ((r - m) ** 2).mean(-1, keepdims=True)
        return (((r - m) * jax.lax.rsqrt(v + 1e-5) * g + b) ** 2).sum()

    for got, want in zip((dx, dy, dg, db),
                         jax.grad(ref, (0, 1, 2, 3))(x, y, g, b)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-3, rtol=1e-3)


def test_finite_difference_grads():
    from jax.test_util import check_grads

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.asarray(rng.randn(8, 16), jnp.float32)
    g = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)
    seed = jnp.array([3, 4], jnp.uint32)
    f = lambda x, y, g, b: (
        F.fused_dropout_add_ln(x, y, g, b, 0.25, seed) ** 2).sum()
    check_grads(f, (x, y, g, b), order=1, modes=["rev"], atol=2e-2,
                rtol=2e-2)


def test_program_op_trains_and_matches_composed():
    """Executor path: a program using the fused op trains; at p=0 its
    loss trajectory matches the composed dropout/add/layer_norm program
    exactly (same params, same math)."""

    def build(fused):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            xin = fluid.layers.data("x", shape=[4, 16])
            yv = fluid.layers.fc(xin, 16, num_flatten_dims=2,
                                 param_attr=fluid.ParamAttr(name="w"))
            if fused:
                z = fluid.layers.fused_dropout_add_ln(
                    xin, yv, dropout_prob=0.0, begin_norm_axis=2,
                    param_attr=fluid.ParamAttr(name="ln_g"),
                    bias_attr=fluid.ParamAttr(name="ln_b"))
            else:
                d = fluid.layers.dropout(
                    yv, 0.0, dropout_implementation="upscale_in_train")
                z = fluid.layers.layer_norm(
                    fluid.layers.elementwise_add(xin, d), begin_norm_axis=2,
                    param_attr=fluid.ParamAttr(name="ln_g"),
                    bias_attr=fluid.ParamAttr(name="ln_b"))
            loss = fluid.layers.mean(z * z)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(3)
    feeds = [rng.randn(2, 4, 16).astype("float32") for _ in range(4)]
    curves = []
    for fused in (True, False):
        main, startup, loss = build(fused)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            vals = [float(exe.run(main, feed={"x": f},
                                  fetch_list=[loss])[0][0])
                    for f in feeds]
        curves.append(vals)
    np.testing.assert_allclose(curves[0], curves[1], rtol=1e-5)


def test_program_op_with_dropout_trains():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xin = fluid.layers.data("x", shape=[4, 16])
        yv = fluid.layers.fc(xin, 16, num_flatten_dims=2)
        z = fluid.layers.fused_dropout_add_ln(
            xin, yv, dropout_prob=0.3, begin_norm_axis=2)
        loss = fluid.layers.mean(z * z)
        # reference contract: clone(for_test=True) BEFORE minimize
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(4)
    x = rng.randn(2, 4, 16).astype("float32")
    exe.run(startup)
    for _ in range(3):
        lo, = exe.run(main, feed={"x": x}, fetch_list=[loss])
    assert np.isfinite(lo).all()
    # inference clone: dropout off -> deterministic
    a, = exe.run(test_prog, feed={"x": x}, fetch_list=[loss])
    c, = exe.run(test_prog, feed={"x": x}, fetch_list=[loss])
    np.testing.assert_array_equal(a, c)


def _epilogue_with_grads(p, devices, x, y, w):
    """One fused epilogue and its grad op through the Executor, on one
    device or data-parallel over `devices` (with_data_parallel: the op and
    its grad op then run per shard, pallas_kernels/fused_ln.py).  -> Out,
    R, dX, dY, dScale, dBias."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        xin = fluid.layers.data("x", shape=list(x.shape[1:]))
        yin = fluid.layers.data("y", shape=list(x.shape[1:]))
        win = fluid.layers.data("w", shape=list(x.shape[1:]))
        xin.stop_gradient = yin.stop_gradient = False
        z = fluid.layers.fused_dropout_add_ln(
            xin, yin, dropout_prob=p, begin_norm_axis=2,
            param_attr=fluid.ParamAttr(
                name="ln_g",
                initializer=fluid.initializer.Uniform(0.5, 1.5, seed=3)),
            bias_attr=fluid.ParamAttr(
                name="ln_b",
                initializer=fluid.initializer.Uniform(-1.0, 1.0, seed=4)))
        loss = fluid.layers.reduce_sum(z * win)
        block = main.global_block()
        grads = fluid.gradients(
            [loss], [xin, yin, block.var("ln_g"), block.var("ln_b")])
        op, = [o for o in block.ops if o.type == "fused_dropout_add_ln"]
    program = main
    if devices > 1:
        program = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name,
            places=[fluid.TPUPlace(i) for i in range(devices)])
    exe = fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return exe.run(program, feed={"x": x, "y": y, "w": w},
                       fetch_list=[z.name, op.output("R")[0]]
                       + [g.name for g in grads])


def test_per_shard_op_and_grad_equal_one_device_at_dropout_0():
    """Over 4 devices the wrapped op and its grad op compute what one
    device computes: rows are independent, and dScale / dBias are the
    shards' sums added over the axis (f32 rounding: another order)."""
    rng = np.random.RandomState(6)
    x, y, w = (rng.randn(8, 4, 128).astype("float32") for _ in range(3))
    one = _epilogue_with_grads(0.0, 1, x, y, w)
    four = _epilogue_with_grads(0.0, 4, x, y, w)
    for name, a, b in zip(("Out", "R", "dX", "dY", "dScale", "dBias"),
                          one, four):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5, err_msg=name)


def test_per_shard_masks_differ_and_replay(monkeypatch):
    """At dropout 0.1 over 4 devices: every shard is fed the same rows, so
    R (= 0 + dropout(y)) shows each shard's mask; the shards draw
    different masks (the rank is folded into the seed), each keeps 0.9 of
    its elements within 3 sigma, and dY is zero exactly where the forward
    dropped (the grad op folds the same rank into the same saved seed).
    Control: with the fold taken out, the four shards draw one mask."""
    rng = np.random.RandomState(7)
    rows = rng.uniform(1.0, 2.0, (2, 16, 128)).astype("float32")
    y = np.tile(rows, (4, 1, 1))                    # shard k: y[2k:2k+2]
    x = np.zeros_like(y)
    w = rng.uniform(1.0, 2.0, y.shape).astype("float32")
    _z, r, _dx, dy, _dg, _db = _epilogue_with_grads(0.1, 4, x, y, w)
    kept = (r != 0.0).reshape(4, -1)
    for a in range(4):
        for b in range(a + 1, 4):
            assert (kept[a] != kept[b]).mean() > 0.1, (a, b)
    n = kept.shape[1]
    sigma = (0.9 * 0.1 / n) ** 0.5
    for k in range(4):
        assert abs(kept[k].mean() - 0.9) < 3 * sigma, (k, kept[k].mean())
    np.testing.assert_array_equal((dy != 0.0).reshape(4, -1), kept)
    # what was kept is upscaled by the realised keep probability
    np.testing.assert_allclose(r[r != 0.0], (y / 0.9)[r != 0.0], rtol=1e-6)

    monkeypatch.setattr(F, "_shard_seed", lambda seed, axis, thr: seed)
    _z, r, _dx, dy, _dg, _db = _epilogue_with_grads(0.1, 4, x, y, w)
    same = (r != 0.0).reshape(4, -1)
    assert (same == same[0]).all() and not same.all()


@pytest.mark.tpu
def test_pallas_kernel_parity_tpu():
    """On-chip: the Pallas path vs the jnp fallback math at p=0, and
    mask-replay consistency at p>0 (VERDICT r4 item 5: the bench-critical
    kernels must run in the TPU tier)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs TPU")
    rng = np.random.RandomState(5)
    N, H = 256, 256
    x = jnp.asarray(rng.randn(N, H), jnp.float32)
    y = jnp.asarray(rng.randn(N, H), jnp.float32)
    g = jnp.asarray(rng.rand(H) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(H), jnp.float32)
    seed = jnp.array([7, 8], jnp.uint32)
    assert F._use_pallas(x, y) is not None  # kernel path engaged
    z = F.fused_dropout_add_ln(x, y, g, b, 0.0, seed)
    zf, _, _, _ = F._fwd_fallback(x, y, g, b, seed, None, 1e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zf), atol=2e-5)

    p = 0.2
    dy = jax.grad(lambda y: (
        F.fused_dropout_add_ln(x, y, g, b, p, seed) ** 2).sum())(y)
    dropped = np.asarray(dy == 0.0)
    assert 0.1 < dropped.mean() < 0.3
    zval = F.fused_dropout_add_ln(x, y, g, b, p, seed)
    i, j = np.argwhere(dropped)[0]
    z2 = F.fused_dropout_add_ln(x, y.at[i, j].add(50.0), g, b, p, seed)
    assert bool(jnp.array_equal(z2, zval))


@pytest.mark.tpu
def test_bf16_carry_paths_tpu():
    """bf16-carry AMP dtype path of the fused kernel + byte-threshold
    dropout on the chip (VERDICT r4 item 5: the paths the benches rely
    on must execute in the TPU tier)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs TPU")
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(512, 768), jnp.bfloat16)
    y = jnp.asarray(rng.randn(512, 768), jnp.bfloat16)
    g = jnp.ones((768,), jnp.float32)
    b = jnp.zeros((768,), jnp.float32)
    seed = jnp.array([9, 10], jnp.uint32)
    z = F.fused_dropout_add_ln(x, y, g, b, 0.1, seed)
    assert z.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(z.astype(jnp.float32)).all())
    # backward in bf16 carry
    dx, dyv = jax.grad(lambda x, y: (
        F.fused_dropout_add_ln(x, y, g, b, 0.1, seed)
        .astype(jnp.float32) ** 2).sum(), (0, 1))(x, y)
    assert dx.dtype == jnp.bfloat16 and dyv.dtype == jnp.bfloat16
    from paddle_tpu.ops.common import bernoulli_bytes

    keep = bernoulli_bytes(jax.random.key(0), 0.9, (256, 512))
    frac = float(jnp.mean(keep.astype(jnp.float32)))
    assert 0.85 < frac < 0.95
