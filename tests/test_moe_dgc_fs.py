"""Tests for MoE/expert-parallel, DGC, and the fs shims."""

import numpy as np
import pytest

import paddle_tpu as fluid


# -- MoE ----------------------------------------------------------------------


def test_moe_single_device_matches_dense_routing():
    """With huge capacity every token reaches its top-k experts; the MoE
    output must equal the explicit per-token mixture computed in numpy."""
    import jax.numpy as jnp
    from paddle_tpu.parallel.moe import moe_ffn

    rng = np.random.RandomState(0)
    T, D, H, E, K = 10, 8, 16, 4, 2
    x = rng.randn(T, D).astype("f")
    gw = rng.randn(D, E).astype("f")
    w1 = rng.randn(E, D, H).astype("f") * 0.1
    b1 = rng.randn(E, H).astype("f") * 0.1
    w2 = rng.randn(E, H, D).astype("f") * 0.1
    b2 = rng.randn(E, D).astype("f") * 0.1

    out, aux = moe_ffn(jnp.asarray(x), jnp.asarray(gw), jnp.asarray(w1),
                       jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2),
                       top_k=K, capacity_factor=100.0)
    out = np.asarray(out)

    # numpy reference: softmax gate, top-2, renormalized mixture
    logits = x @ gw
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    exp = np.zeros_like(x)
    for t in range(T):
        top = np.argsort(-probs[t])[:K]
        wsum = probs[t, top].sum()
        for e in top:
            h = np.maximum(x[t] @ w1[e] + b1[e], 0)
            y = h @ w2[e] + b2[e]
            exp[t] += probs[t, e] / wsum * y
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)
    assert np.isfinite(float(aux))


def test_moe_expert_parallel_matches_local():
    """shard_map EP over 4 ranks == single-device result (tokens sharded,
    experts sharded, all_to_all exchange)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.parallel.moe import moe_ffn

    n = 4
    rng = np.random.RandomState(1)
    T, D, H, E, K = 16, 8, 12, 4, 2
    x = rng.randn(T, D).astype("f")
    gw = rng.randn(D, E).astype("f")
    w1 = rng.randn(E, D, H).astype("f") * 0.1
    b1 = rng.randn(E, H).astype("f") * 0.1
    w2 = rng.randn(E, H, D).astype("f") * 0.1
    b2 = rng.randn(E, D).astype("f") * 0.1

    # single-device truth with the SAME per-shard capacity the EP path uses
    # (EP computes dispatch per token-shard: C = ceil(K*(T/n)/E * f))
    import math
    cap = max(int(math.ceil(K * (T // n) / E * 100.0)), 1)

    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))

    def f(xs, gwr, w1s, b1s, w2s, b2s):
        out, aux = moe_ffn(xs, gwr, w1s, b1s, w2s, b2s, top_k=K,
                           capacity_factor=100.0, axis_name="ep")
        return out

    ep = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("ep", None), P(), P("ep", None, None), P("ep", None),
                  P("ep", None, None), P("ep", None)),
        out_specs=P("ep", None), check_vma=False)
    out_ep = np.asarray(ep(jnp.asarray(x), jnp.asarray(gw), jnp.asarray(w1),
                           jnp.asarray(b1), jnp.asarray(w2),
                           jnp.asarray(b2)))

    from paddle_tpu.parallel.moe import moe_ffn as moe_local
    out_local = np.asarray(moe_local(
        jnp.asarray(x), jnp.asarray(gw), jnp.asarray(w1), jnp.asarray(b1),
        jnp.asarray(w2), jnp.asarray(b2), top_k=K, capacity_factor=100.0)[0])
    np.testing.assert_allclose(out_ep, out_local, rtol=1e-4, atol=1e-4)


def test_moe_layer_trains():
    rng = np.random.RandomState(2)
    B, D = 16, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[D])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h, aux = fluid.layers.moe(x, num_experts=4, hidden_size=16)
        logits = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        total = loss + 0.01 * aux
        fluid.optimizer.Adam(5e-3).minimize(total)
    exe = fluid.Executor(fluid.CPUPlace())
    C = rng.randn(3, D).astype("f") * 2
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(60):
            yb = rng.randint(0, 3, (B, 1)).astype("int64")
            xb = (C[yb.ravel()] + 0.3 * rng.randn(B, D)).astype("f")
            lo, = exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
            losses.append(float(np.asarray(lo).reshape(-1)[0]))
    assert losses[-1] < 0.3 < losses[0]


# -- DGC ----------------------------------------------------------------------


def test_dgc_op_semantics():
    """dgc keeps only the top-ratio |v| entries with error feedback."""
    from paddle_tpu.core.registry import get_op_def
    import jax.numpy as jnp

    opdef = get_op_def("dgc")
    g = jnp.asarray(np.array([0.1, -2.0, 0.05, 1.0], "f"))
    u0 = jnp.zeros(4)
    v0 = jnp.zeros(4)
    u, v, enc, gout = opdef.lower(None, u0, v0, g, m=0.5, ratio=0.5)
    # u=g, v=g; top-50% by |v| = entries -2.0 and 1.0
    np.testing.assert_allclose(np.asarray(enc), [0, -2.0, 0, 1.0], atol=1e-6)
    # residual keeps the small entries for the next step
    np.testing.assert_allclose(np.asarray(v), [0.1, 0, 0.05, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(u), [0.1, 0, 0.05, 0], atol=1e-6)


def test_dgc_momentum_optimizer_trains():
    rng = np.random.RandomState(3)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[10])
        y = fluid.layers.data("y", shape=[1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square(pred - y))
        opt = fluid.optimizer.DGCMomentumOptimizer(
            0.05, momentum=0.9, rampup_begin_step=0, sparsity=[0.7])
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    w = rng.randn(10, 1).astype("f")
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(100):
            xb = rng.randn(32, 10).astype("f")
            yb = xb @ w
            lo, = exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
            losses.append(float(np.asarray(lo).reshape(-1)[0]))
    assert losses[-1] < 0.1 * losses[0]


# -- fs shims -----------------------------------------------------------------


def test_local_fs(tmp_path):
    from paddle_tpu.utils.fs import LocalFS

    fs = LocalFS()
    d = str(tmp_path / "sub")
    fs.mkdirs(d)
    assert fs.is_dir(d)
    f = str(tmp_path / "sub" / "a.txt")
    fs.touch(f)
    assert fs.is_file(f)
    assert fs.ls_dir(d) == ["a.txt"]
    fs.mv(f, str(tmp_path / "b.txt"))
    assert fs.is_exist(str(tmp_path / "b.txt"))
    fs.delete(d)
    assert not fs.is_exist(d)


def test_hdfs_client_without_hadoop():
    from paddle_tpu.utils.fs import HDFSClient

    cl = HDFSClient(hadoop_home="/nonexistent")
    with pytest.raises(RuntimeError, match="hadoop binary not found"):
        cl.ls("/foo")
    # import-path parity with the reference package layout
    from paddle_tpu.incubate.fleet.utils.hdfs import HDFSClient as H2
    assert H2 is HDFSClient
