"""Composed multi-axis parallelism on the 8-device CPU mesh.

VERDICT r4 item 3 (r3 item 8) + ADVICE r4 medium: a real pod job
composes data parallelism WITH pipeline/sequence parallelism in one
mesh; these tests pin the (data=2, pp=4) GPipe step — including the
n_chunks>1 gradient-accumulation interaction — and the (data=2, sp=4)
ring-attention leg against single-device references.  Reference
pattern: unittests/test_dist_base.py:500 (mode composition in one job).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel import (make_pipeline_step, reference_step,
                                 stack_stage_params)
from paddle_tpu.parallel.ring_attention import ring_attention
from paddle_tpu.pallas_kernels.flash_attention import _ref_attention


def _need8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")


def _stage(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _loss(outs, labels):
    return jnp.mean((outs - labels) ** 2)


@pytest.mark.parametrize("n_chunks", [1, 2])
def test_dp_x_pp_gpipe_parity(n_chunks):
    """(data=2, pp=4): params stage-sharded over pp, replicated over
    data; microbatches sharded over data; grads/loss pmean'd over data.
    Loss and per-stage grads must match the sequential reference."""
    _need8()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "pp"))
    D, n_micro = 16, 4
    rng = np.random.RandomState(0)
    params = [{"w": rng.randn(D, D).astype("f") * 0.3,
               "b": rng.randn(D).astype("f") * 0.1} for _ in range(4)]
    x = rng.randn(16, D).astype("f")
    y = rng.randn(16, D).astype("f")
    stacked = stack_stage_params(params, mesh, "pp")
    step = make_pipeline_step(_stage, _loss, mesh, n_micro, "pp",
                              n_chunks=n_chunks, data_axis="data")
    loss, grads = step(stacked, x, y)
    ref_loss, ref_grads = reference_step(_stage, _loss, params, x, y,
                                         n_micro)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["w"]),
        np.stack([np.asarray(g["w"]) for g in ref_grads]), rtol=1e-4,
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["b"]),
        np.stack([np.asarray(g["b"]) for g in ref_grads]), rtol=1e-4,
        atol=1e-5)


def test_dp_x_pp_optimizer_updates_match():
    """The composed mesh with an sgd-style optimizer applies the SAME
    update the sequential reference would."""
    _need8()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "pp"))
    D, n_micro, lr = 8, 2, 0.1
    rng = np.random.RandomState(1)
    params = [{"w": rng.randn(D, D).astype("f") * 0.3,
               "b": rng.randn(D).astype("f") * 0.1} for _ in range(4)]
    x = rng.randn(8, D).astype("f")
    y = rng.randn(8, D).astype("f")
    stacked = stack_stage_params(params, mesh, "pp")
    step = make_pipeline_step(_stage, _loss, mesh, n_micro, "pp",
                              optimizer=lambda p, g: p - lr * g,
                              data_axis="data")
    _, new_params = step(stacked, x, y)
    _, ref_grads = reference_step(_stage, _loss, params, x, y, n_micro)
    want_w = np.stack([p["w"] - lr * np.asarray(g["w"])
                       for p, g in zip(params, ref_grads)])
    np.testing.assert_allclose(np.asarray(new_params["w"]), want_w,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_dp_x_sp_ring_attention_parity(causal):
    """(data=2, sp=4): batch sharded over data AND sequence sharded over
    sp in one mesh; ring attention must match dense attention."""
    _need8()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "sp"))
    B, H, S, D = 4, 2, 32, 8
    rng = np.random.RandomState(2)
    q, k, v = (rng.randn(B, H, S, D).astype("f") for _ in range(3))
    spec = P("data", None, "sp", None)
    fn = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = np.asarray(jax.jit(fn)(q, k, v))
    want = np.asarray(_ref_attention(q, k, v, None, causal, D ** -0.5))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_dp_x_sp_ring_attention_grads():
    """Gradients through the composed dp x sp ring match dense-attention
    gradients (the backward rides the same ppermute ring)."""
    _need8()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "sp"))
    B, H, S, D = 2, 2, 16, 8
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(B, H, S, D).astype("f") for _ in range(3))
    spec = P("data", None, "sp", None)
    fn = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=False),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def loss(fn_):
        return lambda a, b, c: (fn_(a, b, c) ** 2).sum()

    got = jax.grad(loss(jax.jit(fn)), (0, 1, 2))(q, k, v)
    want = jax.grad(
        loss(lambda a, b, c: _ref_attention(a, b, c, None, False,
                                            D ** -0.5)), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4)


def test_three_axis_mesh_dp_tp_pp():
    """A 3-axis (data=2, model=2, pp=2) mesh: the pipeline runs over pp
    with microbatches sharded over data while each stage's matmul is
    column-sharded over model via explicit collectives — the full
    composition a pod job uses.  Parity vs the sequential reference."""
    _need8()
    from jax import lax

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "pp"))
    D, n_micro = 8, 2
    rng = np.random.RandomState(4)
    params = [{"w": rng.randn(D, D).astype("f") * 0.3,
               "b": rng.randn(D).astype("f") * 0.1} for _ in range(2)]
    x = rng.randn(8, D).astype("f")
    y = rng.randn(8, D).astype("f")

    def tp_stage(p, h):
        # column-parallel matmul over the model axis: each rank computes
        # a D/2 output slice from ITS slices of w and b (all params
        # consumed pre-collective — the reduce_grad_axes pmean contract),
        # all_gather restores the full width
        i = lax.axis_index("model")
        w_shard = lax.dynamic_slice_in_dim(p["w"], i * (D // 2), D // 2, 1)
        b_shard = lax.dynamic_slice_in_dim(p["b"], i * (D // 2), D // 2, 0)
        part = h @ w_shard + b_shard
        full = lax.all_gather(part, "model", axis=part.ndim - 1,
                              tiled=True)
        return jnp.tanh(full)

    stacked = stack_stage_params(params, mesh, "pp")
    # reduce_grad_axes: each model rank's dw covers only its column
    # slice (zeros elsewhere) — psum over model restores the full grad
    step = make_pipeline_step(tp_stage, _loss, mesh, n_micro, "pp",
                              data_axis="data",
                              reduce_grad_axes=("model",))
    loss, grads = step(stacked, x, y)
    ref_loss, ref_grads = reference_step(_stage, _loss, params, x, y,
                                         n_micro)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["w"]),
        np.stack([np.asarray(g["w"]) for g in ref_grads]), rtol=1e-4,
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["b"]),
        np.stack([np.asarray(g["b"]) for g in ref_grads]), rtol=1e-4,
        atol=1e-5)
