"""The one rule that chooses a Pallas kernel (pallas_kernels/adoption.py),
and the block ops that lower to plain compositions.

- adoption.decide(kernel, checks): the first failing check is the counted
  reason, an engaged kernel is counted and listed, and each of the two
  default-on training families names the right reason for every way it can
  fall back (paged_attention's own reasons are in
  tests/test_paged_attention_kernel.py::test_shape_rule);
- no flag selects a kernel, and every flag has a reader;
- the conv2d_bn_relu op against conv2d + batch_norm + relu built
  separately, the embedding_bag op against numpy, and the sparse table's
  bagged lookup through the Executor;
- FLAGS_deterministic_reduction: the fixed-order pairwise tree in
  c_allreduce_sum is bit-reproducible against a host-side replay of the
  same tree.
"""

import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import flags as flags_mod
from paddle_tpu.core import telemetry
from paddle_tpu.distributed.sparse_table import DistributedEmbedding
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.ops import collective as coll_ops
from paddle_tpu.ops import manip as manip_ops
from paddle_tpu.ops import nn as nn_ops
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import fused_ln
from paddle_tpu.pallas_kernels import hc_maps
from paddle_tpu.pallas_kernels import kda_update
from paddle_tpu.pallas_kernels.flash_attention import flash_attention_checks
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.pallas_kernels import moe_experts
from paddle_tpu.pallas_kernels import ssm_update

_FLAGS = ("FLAGS_deterministic_reduction", "FLAGS_telemetry")


@pytest.fixture(autouse=True)
def _clean_state():
    """Adoption/telemetry state clean, flags restored."""
    saved = fluid.get_flags(list(_FLAGS))
    adoption.reset()
    telemetry.reset()
    yield
    fluid.set_flags(saved)
    adoption.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


class TestAdoption:
    def test_first_failing_check_is_the_reason(self):
        fluid.set_flags({"FLAGS_telemetry": True})
        use, reason = adoption.decide(
            "fused_ln", [("a", True), ("b", False), ("c", False)])
        assert (use, reason) == (False, "b")
        assert telemetry.counter_total("pallas_kernel_fallback_total") == 1
        assert adoption.active_kernels() == []

    def test_used_counter_and_flagless_kernel(self):
        fluid.set_flags({"FLAGS_telemetry": True})
        assert adoption.decide("fused_ln", [("a", True)]) == (True, "ok")
        assert telemetry.counter_total("pallas_kernel_used_total") == 1
        assert telemetry.counter_total("pallas_kernel_fallback_total") == 0
        assert adoption.active_kernels() == ["fused_ln"]


# shapes each family engages for on the chip: BERT-base's epilogue rows in
# bf16, 1024 keys of attention, GPT-2-medium's folded f32 pool
_ELIGIBLE = {
    "fused_ln": lambda: fused_ln.fused_ln_checks(224 * 128, 768, 2),
    "flash_attention": lambda: flash_attention_checks(
        (2, 4, 1024, 64), (2, 4, 1024, 64), None),
    "paged_attention": lambda: pa.paged_attention_checks(
        (4, 16, 64), (64, 16, 1024), "float32"),
    # granite-4.0-h-micro's state slots: 33 of [128, 64 heads x 64]
    "ssm_update": lambda: ssm_update.ssm_update_checks(
        (33, 128, 4096), "float32", 32),
    # LFM2-24B-A2B's experts at 32 lanes: 64 of [2048, 1536] in bf16
    "moe_experts": lambda: moe_experts.moe_experts_checks(
        32, (64, 2048, 1536), "bfloat16"),
    # Kimi-Linear's latent pool (rows of 576 values held 640 wide, the value
    # the first 512) and its KDA slots: 33 of [128, 32 heads x 128]
    "latent_attention": lambda: pa.latent_attention_checks(
        (32, 32, 640), (12832, 16, 640), "bfloat16", 512),
    "kda_update": lambda: kda_update.kda_update_checks(
        (33, 128, 4096), "float32", 32, 32),
    # GLM-5's index pool (keys of 128) under tables of 784 slots, 32 index
    # heads a lane
    "index_scores": lambda: pa.index_scores_checks(
        (32, 32, 128), (25120, 16, 128), "bfloat16", 784),
    # Xing4.0's mixing: 4 streams of 3,584 at the cell's 32 lanes
    "hc_maps": lambda: hc_maps.hc_maps_checks(4, 3584, 32),
}

RULE = {
    # case: (family, its checks, answer as the chip's backend, reason)
    "fused_ln-backend": (
        "fused_ln", _ELIGIBLE["fused_ln"], False, "backend"),
    "fused_ln-symbolic_shape": (
        "fused_ln", lambda: fused_ln.fused_ln_checks(None, 768, 2), True,
        "symbolic_shape"),
    "fused_ln-lanes": (
        "fused_ln", lambda: fused_ln.fused_ln_checks(256, 100, 2), True,
        "lanes"),
    "fused_ln-block_rows": (
        "fused_ln", lambda: fused_ln.fused_ln_checks(7, 768, 2), True,
        "block_rows"),
    "flash_attention-short_keys": (
        "flash_attention", lambda: flash_attention_checks(
            (2, 4, 512, 64), (2, 4, 512, 64), None), True, "short_keys"),
    "flash_attention-blocks": (
        "flash_attention", lambda: flash_attention_checks(
            (2, 4, 1024, 64), (2, 4, 1088, 64), None), True, "blocks"),
    "flash_attention-backend": (
        "flash_attention", _ELIGIBLE["flash_attention"], False, "backend"),
    "paged_attention-grouped_lanes": (
        # 32 query heads over a pool of 3 KV heads of 64: 192 columns
        "paged_attention", lambda: pa.paged_attention_checks(
            (4, 32, 64), (64, 16, 192), "bfloat16"), True, "lanes"),
    "paged_attention-uneven_groups": (
        # 6 query heads cannot divide over 4 KV heads
        "paged_attention", lambda: pa.paged_attention_checks(
            (4, 6, 64), (64, 16, 256), "bfloat16"), True, "lanes"),
    "ssm_update-backend": (
        "ssm_update", _ELIGIBLE["ssm_update"], False, "backend"),
    "ssm_update-dtype": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 128, 4096), "bfloat16", 32), True, "dtype"),
    "ssm_update-lanes": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 128, 4096 + 64), "float32", 32), True, "lanes"),
    "ssm_update-sublanes": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 100, 4096), "float32", 32), True, "sublanes"),
    "moe_experts-backend": (
        "moe_experts", _ELIGIBLE["moe_experts"], False, "backend"),
    "latent_attention-backend": (
        "latent_attention", _ELIGIBLE["latent_attention"], False, "backend"),
    "latent_attention-selection": (
        # 2,040 chosen rows a lane are no whole blocks of 16: the whole
        # table is gathered and what was not chosen masked
        "latent_attention", lambda: pa._selected_checks(
            (32, 64, 640), (25120, 16, 640), "bfloat16", 512, 2040), True,
        "selection"),
    "index_scores-backend": (
        "index_scores", _ELIGIBLE["index_scores"], False, "backend"),
    "index_scores-lanes": (
        # a key of 64 values is half a tile
        "index_scores", lambda: pa.index_scores_checks(
            (32, 32, 64), (25120, 16, 64), "bfloat16", 784), True, "lanes"),
    "index_scores-dtype": (
        "index_scores", lambda: pa.index_scores_checks(
            (32, 32, 128), (25120, 16, 128), "int8", 784), True, "dtype"),
    "index_scores-vmem": (
        # a lane's scores twice over a table of a million positions
        "index_scores", lambda: pa.index_scores_checks(
            (32, 32, 128), (1 << 17, 16, 128), "bfloat16", 1 << 16), True,
        "vmem"),
    "latent_attention-lanes": (
        # a row of 576 is four and a half tiles: the cache holds it 640 wide
        "latent_attention", lambda: pa.latent_attention_checks(
            (32, 32, 576), (12832, 16, 576), "bfloat16", 512), True, "lanes"),
    "latent_attention-dtype": (
        "latent_attention", lambda: pa.latent_attention_checks(
            (32, 32, 640), (12832, 16, 640), "int8", 512), True, "dtype"),
    "latent_attention-vmem": (
        # 1,024 query rows of 640 + 512 float32 are 4.7e6 B a lane: the
        # grid walks the lanes, and two lanes' do not fit beside the buffers
        "latent_attention", lambda: pa.latent_attention_checks(
            (32, 1024, 640), (12832, 16, 640), "bfloat16", 512), True,
        "vmem"),
    "kda_update-backend": (
        "kda_update", _ELIGIBLE["kda_update"], False, "backend"),
    "kda_update-dtype": (
        "kda_update", lambda: kda_update.kda_update_checks(
            (33, 128, 4096), "bfloat16", 32, 32), True, "dtype"),
    "kda_update-heads": (
        # heads of 64 values: no whole 128-column slice
        "kda_update", lambda: kda_update.kda_update_checks(
            (33, 64, 2048), "float32", 32, 32), True, "heads"),
    "moe_experts-dtype": (
        "moe_experts", lambda: moe_experts.moe_experts_checks(
            32, (64, 2048, 1536), "int8"), True, "dtype"),
    "moe_experts-lanes": (
        "moe_experts", lambda: moe_experts.moe_experts_checks(
            32, (64, 2048, 1536 + 64), "bfloat16"), True, "lanes"),
    "moe_experts-vmem": (
        # no 128-column chunk of a 32768-wide hidden fits twice
        "moe_experts", lambda: moe_experts.moe_experts_checks(
            32, (8, 32768, 1024), "bfloat16"), True, "vmem"),
    # the same families' second forms (PR 41): B and C in groups, and
    # two-matrix experts ``[E, F, H]`` (Nemotron-3-Nano's 16 of 1856 x 2688)
    "ssm_update-groups": (
        # 64 groups of 64 columns: no whole 128-column slice
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 128, 4096), "float32", 32, 64), True, "groups"),
    "ssm_update-groups_uneven": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 128, 4096), "float32", 32, 3), True, "groups"),
    # the state-update kernel's transfers (PR 44): a slot of which not even
    # a 128-column chunk fits four times in what the kernel asks of VMEM
    "ssm_update-vmem": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (3, 1 << 15, 4096), "float32", 2), True, "vmem"),
    "ssm_update-vmem_groups": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (3, 1 << 15, 4096), "float32", 2, 8), True, "vmem"),
    "ssm_update-empty": (
        "ssm_update", lambda: ssm_update.ssm_update_checks(
            (33, 128, 4096), "float32", 0), True, "empty"),
    "hc_maps-backend": (
        "hc_maps", _ELIGIBLE["hc_maps"], False, "backend"),
    "hc_maps-symbolic_shape": (
        "hc_maps", lambda: hc_maps.hc_maps_checks(4, 3584, None), True,
        "symbolic_shape"),
    "hc_maps-dtype": (
        # the streams are float32 in the configuration: a bfloat16 one is
        # another model, and the jnp form's
        "hc_maps", lambda: hc_maps.hc_maps_checks(4, 3584, 32, "bfloat16"),
        True, "dtype"),
    "hc_maps-empty": (
        "hc_maps", lambda: hc_maps.hc_maps_checks(4, 3584, 0), True,
        "empty"),
    "hc_maps-lanes": (
        # a stream's columns of phi are whole 128-lane tiles: 48 is none
        "hc_maps", lambda: hc_maps.hc_maps_checks(4, 48, 32), True, "lanes"),
    "hc_maps-sublanes": (
        # 3 streams make 15 maps' rows: no whole sublane tile
        "hc_maps", lambda: hc_maps.hc_maps_checks(3, 3584, 32), True,
        "sublanes"),
    "hc_maps-vmem": (
        # 1,024 lanes' streams are 59e6 B beside a phi of 1.4e6
        "hc_maps", lambda: hc_maps.hc_maps_checks(4, 3584, 1024), True,
        "vmem"),
    "moe_experts-relu2_backend": (
        "moe_experts", lambda: moe_experts.relu2_checks(
            32, (16, 1856, 2688), "bfloat16"), False, "backend"),
    "moe_experts-relu2_lanes": (
        # H is the minor dimension of both tensors: whole lanes
        "moe_experts", lambda: moe_experts.relu2_checks(
            32, (16, 1856, 2688 + 64), "bfloat16"), True, "lanes"),
    "moe_experts-relu2_sublanes": (
        # F is cut in whole sublane tiles: 1850 is none
        "moe_experts", lambda: moe_experts.relu2_checks(
            32, (16, 1850, 2688), "bfloat16"), True, "lanes"),
    "moe_experts-relu2_vmem": (
        "moe_experts", lambda: moe_experts.relu2_checks(
            32, (8, 1024, 1 << 20), "bfloat16"), True, "vmem"),
}
for _family in adoption.KERNELS:
    for _kind in ("gspmd_mesh", "shape_inference"):
        RULE["%s-%s" % (_family, _kind)] = (
            _family, _ELIGIBLE[_family], True, _kind)


@pytest.mark.parametrize("case", sorted(RULE))
def test_kernel_rule_names_the_fallback(monkeypatch, case):
    """Each way a family can fall back is decided from what the lowering
    observes and counted under one reason: the family's own checks in their
    order, ``gspmd_mesh`` for a bare call in a program XLA partitions by
    itself (since PR 39 the dp4 cell's ``fused_ln`` wraps its call and is
    not one), and nothing at all during build-time shape inference."""
    family, checks, on_chip, expected = RULE[case]
    fluid.set_flags({"FLAGS_telemetry": True})
    if on_chip:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert all(ok for _, ok in _ELIGIBLE[family]()), \
            "the eligible shape must engage on the chip"
    trace = {"gspmd_mesh": adoption.auto_partitioned,
             "shape_inference": adoption.shape_inference}.get(expected)
    if trace is None:
        use, reason = adoption.decide(family, checks())
    else:
        with trace():
            use, reason = adoption.decide(family, checks())
    assert (use, reason) == (False, expected)
    assert adoption.active_kernels() == []
    assert telemetry.counter_total("pallas_kernel_used_total") == 0
    counted = [ls for _flat, ls in
               telemetry.label_sets("pallas_kernel_fallback_total")]
    if expected == "shape_inference":
        assert counted == []
    else:
        assert counted == [{"kernel": family, "reason": expected}]
        assert telemetry.counter_total("pallas_kernel_fallback_total") == 1


TRANSFERS = {
    # case: (pool [slots, N, I], groups, the columns one transfer moves)
    "granite_whole_slot": ((33, 128, 4096), 1, 4096),
    "nemotron_whole_slot_spans_8_groups": ((33, 128, 4096), 8, 4096),
    "three_groups_of_1024": ((9, 128, 3072), 3, 3072),
    # a slot of 4 MiB fits four times in 16 MiB, one of 8 MiB is halved
    "slot_of_4_mib": ((5, 128, 8192), 1, 8192),
    "slot_of_8_mib_in_halves": ((5, 256, 8192), 1, 4096),
    "a_chunk_a_group": ((5, 256, 6144), 2, 3072),
    # thirds (2048) would fit, but a chunk lies in one group or spans whole
    # ones: quarters
    "chunks_keep_to_groups": ((5, 512, 6144), 2, 1536),
    "narrowest_chunk": ((3, 1 << 13, 256), 1, 128),
    "nothing_fits": ((3, 1 << 15, 4096), 1, None),
    "groups_of_64_columns": ((33, 128, 4096), 64, None),
    "groups_uneven": ((33, 128, 4096), 3, None),
}


@pytest.mark.parametrize("case", sorted(TRANSFERS))
def test_state_update_transfer_follows_the_slots_shape(monkeypatch, case):
    """What one transfer of the state-update kernel moves is read from the
    pool's shape and the VMEM the kernel asks for, by no flag: the whole
    slot where two batches of two fit, else the widest chunk that keeps to
    the groups; the family's ``vmem`` check is that some chunk exists."""
    shape, groups, columns = TRANSFERS[case]
    assert ssm_update.transfer_columns(shape, groups) == columns
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    checks = dict(ssm_update.ssm_update_checks(shape, "float32", 2, groups))
    assert all(checks.values()) == (columns is not None)
    assert ssm_update.update_path(shape, "float32", 2, groups) == (
        "pallas" if columns else "gather")
    if columns:
        # at least two units a batch, two batches, inside the budget
        unit = 4 * shape[1] * columns
        assert 4 * unit <= ssm_update._UNIT_BUDGET \
            < ssm_update._VMEM_LIMIT


# names kept so that a Fluid script that sets them still runs; XLA/PJRT owns
# what they governed and nothing reads them
_FLUID_COMPAT = {
    "FLAGS_benchmark", "FLAGS_allocator_strategy",
    "FLAGS_eager_delete_tensor_gb", "FLAGS_fraction_of_gpu_memory_to_use",
    "FLAGS_fuse_parameter_memory_size", "FLAGS_cudnn_deterministic",
    "FLAGS_enable_parallel_graph", "FLAGS_use_system_allocator",
}


def test_no_kernel_switch_and_every_flag_is_read():
    """A kernel is chosen by the rule above, never by a flag; and a flag
    that nothing reads is either a declared compatibility name or dead."""
    names = set(flags_mod._DEFAULTS)
    assert not [n for n in names if n.startswith("FLAGS_use_pallas_")]
    assert _FLUID_COMPAT <= names
    pkg = os.path.dirname(os.path.abspath(fluid.__file__))
    text = []
    for d, _dirs, files in os.walk(pkg):
        for f in files:
            path = os.path.join(d, f)
            if f.endswith(".py") and path != flags_mod.__file__:
                with open(path) as fh:
                    text.append(fh.read())
    text = "\n".join(text)
    unread = sorted(
        n for n in names - _FLUID_COMPAT
        if not re.search(r"[\"'](FLAGS_)?%s[\"']" % re.escape(n[6:]), text))
    assert unread == []


# ---------------------------------------------------------------------------
# conv + bn + relu block
# ---------------------------------------------------------------------------


def _conv_inputs(seed=0, n=2, c=8, h=8, co=8, k=3):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, c, h, h), jnp.float32)
    w = jnp.asarray(rng.randn(co, c, k, k) * 0.1, jnp.float32)
    scale = jnp.asarray(rng.rand(co) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(co) * 0.1, jnp.float32)
    mean = jnp.asarray(rng.randn(co) * 0.1, jnp.float32)
    var = jnp.asarray(rng.rand(co) + 0.5, jnp.float32)
    return x, w, scale, bias, mean, var


def _conv_bn_relu_apart(x, w, scale, bias, mean, var, stride, relu, is_test):
    """conv2d, batch_norm and relu lowered one after the other."""
    conv = nn_ops.conv2d(None, x, w, [stride, stride], [1, 1])
    y, new_mean, new_var, saved_mean, saved_inv, _ = nn_ops.batch_norm(
        None, conv, scale, bias, mean, var, momentum=0.9, epsilon=1e-5,
        is_test=is_test)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y, new_mean, new_var, saved_mean, saved_inv


class TestConvBlock:
    def _check(self, stride, relu, is_test, seed):
        args = _conv_inputs(seed=seed)
        got = nn_ops.conv2d_bn_relu(
            None, *args, strides=[stride, stride], paddings=[1, 1],
            momentum=0.9, epsilon=1e-5, is_test=is_test, with_relu=relu)
        ref = _conv_bn_relu_apart(*args, stride, relu, is_test)
        assert len(got) == 5
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
        y = np.asarray(got[0])
        assert y.shape == (2, 8, 8 // stride, 8 // stride)
        assert (y.min() >= 0.0) == relu
        return args, got

    @pytest.mark.parametrize("stride,relu", [(1, True), (2, True),
                                             (1, False)])
    def test_train_forward_parity(self, stride, relu):
        """Training: all five outputs; SavedVariance holds the INVERSE std
        of the batch, and the running stats move by the momentum."""
        (x, w, _s, _b, mean, var), got = self._check(stride, relu, False, 0)
        conv = np.asarray(nn_ops.conv2d(None, x, w, [stride, stride],
                                        [1, 1]))
        m, v = conv.mean(axis=(0, 2, 3)), conv.var(axis=(0, 2, 3))
        np.testing.assert_allclose(np.asarray(got[3]), m, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[4]),
                                   1.0 / np.sqrt(v + 1e-5), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(got[1]),
                                   0.9 * np.asarray(mean) + 0.1 * m,
                                   atol=1e-5)

    @pytest.mark.parametrize("stride,relu", [(1, True), (2, False)])
    def test_inference_forward_parity(self, stride, relu):
        """Inference: the running statistics normalise and pass through."""
        (_x, _w, _s, _b, mean, var), got = self._check(stride, relu, True, 1)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(mean))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(var))

    def test_program_level_layer(self):
        """layers.conv2d_bn_relu through the Executor against a program of
        layers.conv2d + layers.batch_norm(act="relu") over the same
        filter."""
        rng = np.random.RandomState(7)
        xv = rng.randn(2, 8, 8, 8).astype(np.float32)
        wv = (rng.randn(8, 8, 3, 3) * 0.1).astype(np.float32)
        outs = []
        for fused in (True, False):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", shape=[8, 8, 8], dtype="float32")
                attr = fluid.ParamAttr(
                    initializer=NumpyArrayInitializer(wv))
                if fused:
                    out = fluid.layers.conv2d_bn_relu(
                        x, num_filters=8, filter_size=3, padding=1,
                        param_attr=attr)
                else:
                    conv = fluid.layers.conv2d(
                        x, num_filters=8, filter_size=3, padding=1,
                        param_attr=attr, bias_attr=False)
                    out = fluid.layers.batch_norm(conv, act="relu")
            assert ("conv2d_bn_relu" in [
                op.type for op in main.global_block().ops]) == fused
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                got, = exe.run(main, feed={"x": xv}, fetch_list=[out])
            outs.append(got)
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)
        assert outs[0].min() >= 0.0 and outs[0].max() > 0.0


# ---------------------------------------------------------------------------
# embedding bag
# ---------------------------------------------------------------------------


class TestEmbeddingBag:
    def _data(self, seed=0, u=32, d=128, b=4, k=6, ragged=False):
        rng = np.random.RandomState(seed)
        rows = jnp.asarray(rng.randn(u, d), jnp.float32)
        ids = rng.randint(0, u, size=(b, k)).astype(np.int64)
        if ragged:
            # ragged bags: tail of each bag -1-padded; one bag fully empty
            for i in range(b):
                ids[i, rng.randint(1, k):] = -1
            ids[b - 1, :] = -1
        return rows, jnp.asarray(ids)

    def _expected(self, rows, ids):
        rows, ids = np.asarray(rows), np.asarray(ids)
        out = np.zeros((ids.shape[0], rows.shape[1]), np.float64)
        for bi, row_ids in enumerate(ids):
            for i in row_ids:
                if i >= 0:
                    out[bi] += rows[i]
        return out.astype(np.float32)

    @pytest.mark.parametrize("ragged", [False, True])
    def test_forward_parity(self, ragged):
        rows, ids = self._data(ragged=ragged)
        out = manip_ops.embedding_bag(None, rows, ids)
        assert out.dtype == rows.dtype
        np.testing.assert_allclose(np.asarray(out),
                                   self._expected(rows, ids),
                                   atol=1e-4, rtol=1e-4)
        if ragged:
            # the all-padding bag sums to exactly zero
            np.testing.assert_array_equal(np.asarray(out[-1]),
                                          np.zeros(rows.shape[1],
                                                   np.float32))
        with pytest.raises(ValueError):
            manip_ops.embedding_bag(None, rows, ids, mode="mean")

    def test_grads_scatter_add_over_valid_ids(self):
        rows, ids = self._data(seed=1, ragged=True)
        rng = np.random.RandomState(2)
        ct = rng.randn(ids.shape[0], rows.shape[1]).astype(np.float32)
        # linear loss: each valid id's row receives its bag's cotangent
        got = jax.grad(lambda r: jnp.sum(
            manip_ops.embedding_bag(None, r, ids) * ct))(rows)
        expected = np.zeros(rows.shape, np.float64)
        for bi, row_ids in enumerate(np.asarray(ids)):
            for i in row_ids:
                if i >= 0:
                    expected[i] += ct[bi]
        np.testing.assert_allclose(np.asarray(got), expected, atol=1e-5)


class TestSparseTableBags:
    class _StubClient:
        """pull() returns row i filled with i+1 — sums are predictable."""

        def __init__(self, dim):
            self.dim = dim

        def pull(self, ids):
            ids = np.asarray(ids, np.int64).reshape(-1)
            if not len(ids):
                return np.zeros((0, self.dim), np.float32)
            return np.stack([np.full((self.dim,), float(i + 1), np.float32)
                             for i in ids])

    def test_lookup_bag_end_to_end(self):
        """lookup_bag + prepare_feed_bags through the Executor: the emitted
        embedding_bag op sums the pulled rows of each bag."""
        d = 128
        demb = DistributedEmbedding("tbl", d, client=self._StubClient(d))
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            out = demb.lookup_bag(batch_size=3, bag_size=4, batch_ids_max=8)
        feed, info = demb.prepare_feed_bags([[5, 9], [9], []])
        assert info["n"] == 2 and list(info["uniq"]) == [5, 9]
        local = feed[demb.local_ids_name]
        np.testing.assert_array_equal(
            local, [[0, 1, -1, -1], [1, -1, -1, -1], [-1, -1, -1, -1]])
        expected = np.zeros((3, d), np.float32)
        expected[0] = 6.0 + 10.0   # rows 5 and 9 hold i+1
        expected[1] = 10.0
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            got, = exe.run(main, feed=feed, fetch_list=[out])
        np.testing.assert_allclose(got, expected, atol=1e-5)

    def test_prepare_feed_bags_validates(self):
        d = 128
        demb = DistributedEmbedding("tbl2", d, client=self._StubClient(d))
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            demb.lookup_bag(batch_size=2, bag_size=2, batch_ids_max=3)
        with pytest.raises(ValueError):       # bag longer than bag_size
            demb.prepare_feed_bags([[1, 2, 3], [4]])
        with pytest.raises(ValueError):       # too many unique rows
            demb.prepare_feed_bags([[1, 2], [3, 4]])


# ---------------------------------------------------------------------------
# deterministic collective reduction
# ---------------------------------------------------------------------------


class TestDeterministicReduction:
    def test_tree_reduce_is_bit_reproducible(self):
        ndev = len(jax.devices())
        if ndev < 2:
            pytest.skip("needs >= 2 devices (virtual CPU mesh)")
        ctx = types.SimpleNamespace(axis_names=("dp",), mesh=None)
        rng = np.random.RandomState(0)
        # wildly varying magnitudes make f32 summation order observable
        xs = jnp.asarray(rng.randn(ndev, 4, 3)
                         * (10.0 ** rng.randint(-4, 5, (ndev, 4, 3))),
                         jnp.float32)
        fluid.set_flags({"FLAGS_deterministic_reduction": True})
        out = jax.pmap(lambda x: coll_ops.c_allreduce_sum(ctx, x),
                       axis_name="dp")(xs)
        # host-side replay of the same fixed-order pairwise tree, in f32
        terms = [np.asarray(xs[i]) for i in range(ndev)]
        while len(terms) > 1:
            nxt = [terms[i] + terms[i + 1]
                   for i in range(0, len(terms) - 1, 2)]
            if len(terms) % 2:
                nxt.append(terms[-1])
            terms = nxt
        for r in range(ndev):                 # every rank, identical bits
            np.testing.assert_array_equal(np.asarray(out[r]), terms[0])
        # and the tree agrees with psum up to reassociation error
        fluid.set_flags({"FLAGS_deterministic_reduction": False})
        psum = jax.pmap(lambda x: coll_ops.c_allreduce_sum(ctx, x),
                        axis_name="dp")(xs)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(psum[0]),
                                   rtol=1e-4, atol=1e-4)
