"""Large-scale sparse PS (PSLib/Downpour analog) tests:
distributed/sparse_table.py + the mesh distributed_lookup_table op."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.distributed.sparse_table import (
    SparseTableServer, SparseTableClient, DistributedEmbedding)


@pytest.fixture
def two_shard_table():
    servers = [SparseTableServer(0, dim=8, optimizer="sgd", lr=0.5, seed=s)
               for s in range(2)]
    for s in servers:
        s.start_thread()
    client = SparseTableClient(
        "emb", ["127.0.0.1:%d" % s.port for s in servers])
    yield servers, client
    client.complete()
    client.close()
    for s in servers:
        s.shutdown()


def test_pull_push_roundtrip(two_shard_table):
    servers, client = two_shard_table
    ids = np.array([3, 7, 10, 3], "int64")
    rows = client.pull(ids)
    assert rows.shape == (4, 8)
    # same id pulls the same row; lazily-initialized rows are reproducible
    np.testing.assert_allclose(rows[0], rows[3])
    # push a grad of +1 on id 3 only: sgd lr .5 -> row decreases by .5
    client.push(np.array([3], "int64"), np.ones((1, 8), "f"))
    rows2 = client.pull(np.array([3], "int64"))
    np.testing.assert_allclose(rows2[0], rows[0] - 0.5, atol=1e-6)
    # other ids untouched
    rows7 = client.pull(np.array([7], "int64"))
    np.testing.assert_allclose(rows7[0], rows[1])


def test_distributed_embedding_trains(two_shard_table):
    """DownpourWorker flow: pull -> compiled step -> push; the embedding
    rows must learn to classify which shard-parity their id has."""
    servers, client = two_shard_table
    demb = DistributedEmbedding("emb", dim=8, client=client)

    B, VMAX = 16, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[], dtype="int64")
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        emb = demb.lookup(ids, batch_ids_max=VMAX)
        logits = fluid.layers.fc(emb, 2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.5).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        gv = demb.grad_var(main)
        for step in range(150):
            batch_ids = rng.randint(0, 50, (B,)).astype("int64")
            yb = (batch_ids % 2).reshape(B, 1)
            feed, info = demb.prepare_feed(batch_ids)
            feed["ids"] = batch_ids
            feed["y"] = yb
            lo, g = exe.run(main, feed=feed, fetch_list=[loss, gv])
            demb.push_grads(info, np.asarray(g))
            losses.append(float(np.asarray(lo).reshape(-1)[0]))
    assert losses[-1] < 0.1 < losses[0]


def test_mesh_distributed_lookup_table_op():
    """Manual-SPMD row-sharded lookup: masked partial gathers + psum over
    the mesh axis must equal a plain gather of the full table."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.core.registry import get_op_def

    n = 4
    devs = np.array(jax.devices()[:n])
    mesh = Mesh(devs, ("model",))
    V, D = 32, 6
    rng = np.random.RandomState(0)
    table = rng.randn(V, D).astype("f")
    ids = rng.randint(0, V, (10, 1)).astype("int32")

    opdef = get_op_def("distributed_lookup_table")

    class Ctx:
        axis_names = ("model",)

    def f(w_shard, ids_in):
        return opdef.lower(Ctx(), ids_in, w_shard, ring_id=0)

    sharded = jax.shard_map(
        f, mesh=mesh, in_specs=(P("model", None), P()), out_specs=P(),
        check_vma=False)
    out = np.asarray(sharded(jnp.asarray(table), jnp.asarray(ids)))
    exp = table[ids.reshape(-1)]
    np.testing.assert_allclose(out, exp, rtol=1e-6)


def test_embedding_is_distributed_annotates_sharding():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[1], dtype="int64")
        out = fluid.layers.embedding(ids, size=[100, 16],
                                     is_distributed=True)
    params = main.global_block().all_parameters()
    emb_w = [p for p in params if list(p.shape) == [100, 16]][0]
    assert tuple(emb_w.sharding) == ("model", None)
