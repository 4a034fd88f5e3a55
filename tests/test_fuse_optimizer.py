"""Horizontal optimizer fusion (ir.py fuse_optimizer_ops_pass +
fused_sgd/fused_momentum/fused_adam ops; reference
ir/fuse_optimizer_ops_pass.cc + BuildStrategy fuse_all_optimizer_ops).
Exact numeric parity fused-vs-unfused is the contract."""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import ir


def _train(opt_factory, fuse, steps=4):
    fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": fuse})
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[6])
            y = fluid.layers.data("y", shape=[1])
            h = fluid.layers.fc(x, 8, act="relu")
            h = fluid.layers.fc(h, 8, act="tanh")
            h = fluid.layers.fc(h, 8, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            opt_factory().minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        xb = rng.randn(8, 6).astype("f")
        yb = rng.randn(8, 1).astype("f")
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(steps):
                lo, = exe.run(main, feed={"x": xb, "y": yb},
                              fetch_list=[loss])
                losses.append(float(np.asarray(lo).ravel()[0]))
        types = [op.type for op in main.global_block().ops]
        return losses, types
    finally:
        fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": True})


@pytest.mark.parametrize("name,factory,raw_type", [
    ("sgd", lambda: fluid.optimizer.SGD(0.1), "sgd"),
    ("momentum", lambda: fluid.optimizer.Momentum(0.1, 0.9), "momentum"),
    ("adam", lambda: fluid.optimizer.Adam(0.01), "adam"),
])
def test_fused_matches_unfused(name, factory, raw_type):
    base, t0 = _train(factory, fuse=False)
    fused, t1 = _train(factory, fuse=True)
    assert t0.count(raw_type) == 8          # 4 fc layers: w + b each
    assert t1.count("fused_" + raw_type) == 1   # the 4 biases
    assert t1.count(raw_type) == 4              # the 4 weights
    np.testing.assert_allclose(fused, base, rtol=1e-6, atol=1e-7)


def _ranks_by_op(block, raw_type):
    """Param ranks of the fused op's members and of the plain ops left."""
    def ranks(op):
        return [len(block.var(n).shape) for n in op.input("Param")]

    fused = [r for op in block.ops if op.type == "fused_" + raw_type
             for r in ranks(op)]
    plain = [r for op in block.ops if op.type == raw_type for r in ranks(op)]
    return sorted(fused), sorted(plain)


def test_mixed_ranks_fuse_vectors_only():
    """A group of mixed ranks fuses its vectors only: the fused op's
    members are the rank-1 params (ir.py MAX_FUSED_RANK), every tiled
    param keeps its plain op."""
    main, _startup, _loss = _mixed_rank_net(
        lambda: fluid.optimizer.Momentum(0.1, 0.9))
    ir.apply_pass("fuse_optimizer_ops_pass", main, None)
    fused, plain = _ranks_by_op(main.global_block(), "momentum")
    assert fused == [1] * 5                 # conv and 4 fc biases
    assert plain == [2, 2, 2, 2, 2, 4]      # embedding, 4 fc, conv kernel


def _vector_net(n):
    """A net of ``n`` vector params under SGD: all eligible to fuse."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1])
        h = x
        for _ in range(n):
            h = fluid.layers.tanh(
                h + fluid.layers.create_parameter([8], "float32"))
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.reduce_sum(h, dim=1, keep_dim=True) - y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main


def _fused_types(main):
    ir.apply_pass("fuse_optimizer_ops_pass", main, None)
    return [op.type for op in main.global_block().ops]


def test_mixed_lr_not_fused_together():
    """Different LearningRate vars must not share a fused group."""
    main = _vector_net(6)
    control = _fused_types(main.clone())
    assert control.count("fused_sgd") == 1 and "sgd" not in control
    block = main.global_block()
    # split the sgd ops onto two different LR vars
    block.create_var(name="lr_b", shape=[1], dtype="float32",
                     persistable=True)
    sgds = [op for op in block.ops if op.type == "sgd"]
    for op in sgds[:3]:
        op.inputs["LearningRate"] = ["lr_b"]
    types = _fused_types(main)
    # 3+3 split: neither group reaches MIN_GROUP=4 -> nothing fused
    assert types.count("sgd") == 6
    assert "fused_sgd" not in types


def test_hazard_blocks_fusion():
    """An op between group members that reads a param must block the
    group (ordering hazard)."""
    from paddle_tpu.framework import Operator

    main = _vector_net(6)
    control = _fused_types(main.clone())
    assert control.count("fused_sgd") == 1 and "sgd" not in control
    block = main.global_block()
    sgds = [i for i, op in enumerate(block.ops) if op.type == "sgd"]
    pname = block.ops[sgds[0]].input("Param")[0]
    # reader of an updated param wedged between the sgd ops
    block.create_var(name="hz_out")
    reader = Operator(block, type="assign",
                      inputs={"X": [pname]},
                      outputs={"Out": ["hz_out"]}, attrs={})
    ops = list(block.ops)
    ops.insert(sgds[2], reader)
    block.ops = ops
    types = _fused_types(main)
    assert "fused_sgd" not in types
    assert types.count("sgd") == 6


# -- which params fuse (ir.py FuseOptimizerOpsPass.MAX_FUSED_RANK) -----------

H = 8


def _mixed_rank_net(opt_factory):
    """A net whose parameters are vectors, an [h, h] and an [h, 4h]
    matrix, an embedding and a 4-D conv kernel."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", shape=[1], dtype="int64")
        img = fluid.layers.data("img", shape=[2, 6, 6])
        y = fluid.layers.data("y", shape=[1])
        emb = fluid.layers.embedding(ids, size=[13, H])
        conv = fluid.layers.conv2d(img, num_filters=3, filter_size=3)
        h = fluid.layers.concat(
            [emb, fluid.layers.reshape(conv, [-1, 3 * 4 * 4])], axis=1)
        h = fluid.layers.fc(h, H, act="tanh")
        h = fluid.layers.fc(h, H, act="relu")
        h = fluid.layers.fc(h, 4 * H, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square(pred - y))
        opt_factory().minimize(loss)
    return main, startup, loss


def _state_after(main, startup, loss, steps=5):
    """Every persistable of ``main`` after ``steps`` steps, Adam's beta-pow
    accumulators starting from a value of their own per member."""
    block = main.global_block()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(3)
    feed = {"ids": rng.randint(0, 13, (8, 1)).astype("int64"),
            "img": rng.randn(8, 2, 6, 6).astype("f"),
            "y": rng.randn(8, 1).astype("f")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        pows = sorted(n for op in block.ops
                      if op.type in ("adam", "fused_adam")
                      for slot in ("Beta1Pow", "Beta2Pow")
                      for n in op.input(slot))
        for k, name in enumerate(pows):
            # as after k further steps: a member added mid-training
            was = np.asarray(scope.find_var(name).get_tensor().numpy())
            scope.var(name).set(was ** (1 + k % 4))
        for _ in range(steps):
            exe.run(main, feed=feed, fetch_list=[loss])
        return {n: np.asarray(scope.find_var(n).get_tensor().numpy())
                for n, v in sorted(block.vars.items())
                if v.persistable and scope.find_var(n) is not None}


@pytest.mark.parametrize("raw_type,factory,slots", [
    ("sgd", lambda: fluid.optimizer.SGD(0.1), 0),
    ("momentum", lambda: fluid.optimizer.Momentum(0.1, 0.9), 1),
    ("adam", lambda: fluid.optimizer.Adam(0.01), 4),
])
def test_mixed_rank_group_matches_unfused(raw_type, factory, slots):
    """A program of vectors, matrices, an embedding and a 4-D kernel, its
    vectors fused, equals the per-parameter ops over 5 steps: every
    parameter, every moment and every (divergent) beta-pow accumulator."""
    fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": False})
    try:
        main, startup, loss = _mixed_rank_net(factory)
        fused = main.clone()
        ir.apply_pass("fuse_optimizer_ops_pass", fused, None)
        types = [op.type for op in fused.global_block().ops]
        # 5 biases; 6 tiled: the embedding, the kernel, 4 matrices
        assert types.count("fused_" + raw_type) == 1
        assert types.count(raw_type) == 6
        want = _state_after(main, startup, loss)
        got = _state_after(fused, startup, loss)
    finally:
        fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": True})
    ranks = sorted({a.ndim for a in want.values()})
    assert ranks[-1] == 4 and 2 in ranks and 1 in ranks
    # the learning rate, 11 parameters and `slots` accumulators apiece
    assert len(want) == 1 + 11 * (1 + slots)
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                                   atol=1e-7, err_msg=name)


def _bert_cell_program(tiny):
    """The training cells' own program (benchmark/models/bert_pretrain.py),
    at the configuration's published or ``tiny`` sizes, fused as the
    executor fuses it."""
    from benchmark.models import bert_pretrain
    from benchmark.run import BENCH_DIR, load_json, with_tiny

    config = with_tiny(
        load_json(BENCH_DIR, "configs", "bert-base-pretrain.json"), tiny)
    traffic = with_tiny(
        load_json(BENCH_DIR, "traffic", "train_seq128_bs224.json"), tiny)
    main, startup, loss = bert_pretrain.build_program(config, traffic)
    ir.apply_pass("fuse_optimizer_ops_pass", main, None)
    feeds = bert_pretrain.make_batch(np.random.default_rng(0), config,
                                     traffic, 1)
    return main, startup, loss, feeds


def _adam_member_shapes(main):
    """Param shapes of the one fused_adam's members and of the plain adam
    ops beside it."""
    block = main.global_block()
    fused, = [op for op in block.ops if op.type == "fused_adam"]
    plain = [op for op in block.ops if op.type == "adam"]

    def shapes(names):
        return [tuple(block.var(n).shape) for n in names]

    return (shapes(fused.input("Param")),
            shapes(op.input("Param")[0] for op in plain))


def _lowered_step_text(main, startup, loss, feeds):
    """StableHLO of the whole training step, as the executor traces it."""
    import jax

    from paddle_tpu.core.lowering import BlockPlan, build_block_fn

    block = main.global_block()
    plan = BlockPlan(block, sorted(feeds), [loss.name], allow_carry=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ro, rw = ({n: np.asarray(exe._scope_value(scope, n, block))
                   for n in names}
                  for names in (plan.ro_names, plan.rw_names))
    carry = {n: np.asarray(rw.get(n, ro.get(n))).astype("bfloat16")
             for n in plan.carry_names}
    return jax.jit(build_block_fn(plan)).lower(
        feeds, ro, rw, carry, jax.random.key(0)).as_text()


def _largest_concatenate(text):
    """Elements of the largest ``concatenate`` result in a lowered text."""
    sizes = [int(np.prod([int(d) for d in m.group(1).split("x")[:-1]] or [1]))
             for m in re.finditer(
                 r"stablehlo\.concatenate.*-> tensor<([^>]*)>", text)]
    return max(sizes, default=0)


def test_bert_step_concatenates_no_tiled_member(monkeypatch):
    """The tiny BERT step's lowered text holds no concatenate larger than
    its vectors together: no matrix is copied into a flat buffer.  A
    rank-2 param let into the group is seen."""
    main, startup, loss, feeds = _bert_cell_program(tiny=True)
    vectors, matrices = _adam_member_shapes(main)
    flat_sum = sum(int(np.prod(s)) for s in vectors)
    largest = _largest_concatenate(
        _lowered_step_text(main, startup, loss, feeds))
    assert 0 < largest <= flat_sum
    monkeypatch.setattr(ir.FuseOptimizerOpsPass, "MAX_FUSED_RANK", 2)
    main, startup, loss, feeds = _bert_cell_program(tiny=True)
    planted = _largest_concatenate(
        _lowered_step_text(main, startup, loss, feeds))
    assert planted >= flat_sum + sum(int(np.prod(s)) for s in matrices)


def test_bert_members_by_rank():
    """BERT-base's 203 Adam updates by their shapes alone (nothing run):
    126 vectors in one fused_adam, under 0.2e6 elements together; 77 tiled
    params on plain adam ops.  The cell's tiny program: 16 and 11."""
    main, _startup, _loss, _feeds = _bert_cell_program(tiny=False)
    vectors, matrices = _adam_member_shapes(main)
    assert (len(vectors), len(matrices)) == (126, 77)
    assert {len(s) for s in vectors} == {1}
    assert {len(s) for s in matrices} == {2}
    assert sum(int(np.prod(s)) for s in vectors) < 0.2e6
    assert sum(int(np.prod(s)) for s in matrices) > 132e6

    main, _startup, _loss, _feeds = _bert_cell_program(tiny=True)
    vectors, matrices = _adam_member_shapes(main)
    assert (len(vectors), len(matrices)) == (16, 11)


@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused_program", "pristine_program"])
def test_world_analysis_predicts_fused_vectors_only(fused):
    """The memory check's flat temps are the fused vectors' bytes alone,
    one for each slot fused_adam concatenates (Grad and both moments; it
    reads each Param where it lies), on a program the pass has fused and
    on one it will fuse."""
    from paddle_tpu.core import world_analysis

    main, _startup, _loss = _mixed_rank_net(
        lambda: fluid.optimizer.Adam(0.01))
    block = main.global_block()
    adams = [i for i, op in enumerate(block.ops) if op.type == "adam"]
    vector_adams = [i for i in adams if len(block.var(
        block.ops[i].input("Param")[0]).shape) == 1]
    last = vector_adams[-1]
    if fused:
        ir.apply_pass("fuse_optimizer_ops_pass", main, None)
        last, = [i for i, op in enumerate(block.ops)
                 if op.type == "fused_adam"]

    def nbytes(name):
        return 4 * int(np.prod(block.var(name).shape))

    # the biases: conv 3, fc 8 + 8 + 32 + 1; three flat temps for Adam
    vector_bytes = 4 * (3 + H + H + 4 * H + 1)
    assert world_analysis._fused_optimizer_loads(main, block, nbytes) == [
        (last, 3 * vector_bytes)]
    fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": False})
    try:
        assert world_analysis._fused_optimizer_loads(
            main, block, nbytes) == ([(last, 3 * vector_bytes)]
                                     if fused else [])
    finally:
        fluid.flags.set_flags({"FLAGS_fuse_optimizer_ops": True})
