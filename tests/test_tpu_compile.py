"""Compiles for the chip, without the chip: the TPU's compiler is installed
here and compiles for a *described* v5e, so what it makes of the main
path's programs at their real widths is checked on every run of the tests,
at no chip time.  Nothing runs, so nothing here is a time or a result.

All such tests live in this one file, and the topology is described inside
a fixture (never at import): only one process may load the TPU's library,
and it is the worker that is given this file.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.models import gpt2_decoder
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The path rule asks ``jax.default_backend()``, which is the CPU's
    here: answer as the chip would, so that what is compiled for the
    described chip is the program the chip runs (the kernel, not the
    gather)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _kernel_calls(text):
    return len(re.findall(r" custom-call\(.*tpu_custom_call", text))


def _expert_kernels(text):
    """Calls of the routed-expert kernel (pallas_kernels/moe_experts.py)."""
    return len(re.findall(r"%moe_routed_experts\S* = ", text))


def _expert_passes(text, experts, hidden, ffn):
    """Instructions that copy, transpose or convert a whole expert tensor
    (``[E, H, F]`` or ``[E, F, H]``): the experts are read as they lie."""
    whole = re.compile(
        r" = bf16\[%d,(%d,%d|%d,%d)\]\S* (copy|transpose|convert)\("
        % (experts, hidden, ffn, ffn, hidden))
    return [line.strip()[:160] for line in text.splitlines()
            if whole.search(line)]


def _packed_feeds(kv, lanes, maxb):
    """What ``make_packed_step`` takes after the carry and the parameters:
    the step before's tokens and the lanes' integers, ``int32[lanes, C]``
    (``decode_model.lane_columns``)."""
    return [jax.ShapeDtypeStruct((lanes,), jnp.int32),
            jax.ShapeDtypeStruct(
                (lanes, dm.lane_columns(kv, maxb)[1]), jnp.int32)]


def _as_held(cfg, shapes):
    """A bfloat16 model's published ``param_shapes`` as a step holds them
    (``decode_model.laid_out``), shapes alone."""
    return jax.eval_shape(lambda p: dm.laid_out(cfg, p), {
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind) in shapes.items()})


def _weights_relaid(text):
    """The step's own copies of its weights into another layout
    (``tools/decode_step_probe.py`` ``parameter_fed_copies``)."""
    import decoder_families as fam

    return fam.load("tools", "decode_step_probe.py").parameter_fed_copies(
        text)


def _placed(sharding, tree):
    """The tree's shapes, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


_MODULE = """HloModule m, is_scheduled=true

%%fused_computation.1 (param_0: bf16[8,4]) -> bf16[4,8] {
  %%param_0 = bf16[8,4]{1,0} parameter(0)
  ROOT %%transpose.1 = bf16[4,8]{1,0} transpose(%%param_0), dimensions={1,0}
}

%%fused_computation.2 (param_0.1: bf16[8,4], param_1: f32[2,8]) -> f32[2,4] {
  %%param_0.1 = bf16[8,4]{1,0} parameter(0)
  %%param_1 = f32[2,8]{1,0} parameter(1)
  ROOT %%convolution.1 = f32[2,4]{1,0} convolution(%%param_1, %%param_0.1), dim_labels=bf_io->bf
}

%%async_computation.1 (param_0.2: bf16[8,4]) -> bf16[8,4] {
  %%param_0.2 = bf16[8,4]{1,0} parameter(0)
  ROOT %%slice.1 = bf16[8,4]{1,0:S(1)} slice(%%param_0.2), slice={[0:8], [0:4]}
}

ENTRY %%main.1 (kv_carry_0_.1: bf16[8,4], params__l3_wkvb__.1: bf16[8,4], x.1: f32[2,8]) -> f32[2,4] {
  %%kv_carry_0_.1 = bf16[8,4]{1,0} parameter(0)
  %%params__l3_wkvb__.1 = bf16[8,4]{1,0} parameter(1), metadata={op_name="params[\\'l3_wkvb\\']"}
  %%x.1 = f32[2,8]{1,0} parameter(2)
  %%copy.9 = bf16[8,4]{0,1} copy(%%kv_carry_0_.1)
%s
}
"""
_READS = {
    # the weight itself, copied into another layout
    "a_copy": ('  %copy.1 = bf16[8,4]{0,1:T(8,128)(2,1)S(1)} copy('
               '%params__l3_wkvb__.1), metadata={op_name="params"}',
               [["copy.1", "l3_wkvb", 64]]),
    # fetched ahead in two slices, put together, then turned (Kimi-Linear's)
    "a_copy_of_what_was_fetched": (
        "  %slice-start.1 = ((bf16[8,4]{1,0}), bf16[4,4]{1,0:S(1)}, s32[]) "
        "slice-start(%params__l3_wkvb__.1), slice={[0:4], [0:4]}\n"
        "  %slice-start.2 = ((bf16[8,4]{1,0}), bf16[4,4]{1,0:S(1)}, s32[]) "
        "slice-start(%params__l3_wkvb__.1), slice={[4:8], [0:4]}\n"
        "  %slice-done.1 = bf16[4,4]{1,0:S(1)} slice-done(%slice-start.1)\n"
        "  %slice-done.2 = bf16[4,4]{1,0:S(1)} slice-done(%slice-start.2)\n"
        "  %custom-call.1 = bf16[8,4]{1,0:S(1)} custom-call(%slice-done.1, "
        '%slice-done.2), custom_call_target="ConcatBitcast"\n'
        "  %copy.2 = bf16[8,4]{0,1:S(1)} copy(%custom-call.1)",
        [["copy.2", "l3_wkvb", 64]]),
    # the same as a module restored from the compile cache prints it
    "a_copy_of_what_an_async_slice_fetched": (
        "  %slice-start.3 = ((bf16[8,4]{1,0}), bf16[8,4]{1,0:S(1)}, s32[]) "
        "async-start(%params__l3_wkvb__.1), calls=%async_computation.1\n"
        "  %slice-done.3 = bf16[8,4]{1,0:S(1)} async-done(%slice-start.3)\n"
        "  %copy.3 = bf16[8,4]{0,1:S(1)} copy(%slice-done.3)",
        [["copy.3", "l3_wkvb", 64]]),
    # a fusion that only turns it
    "a_fusion_of_a_transpose": (
        "  %fusion.1 = bf16[4,8]{1,0} fusion(%params__l3_wkvb__.1), "
        "kind=kLoop, calls=%fused_computation.1",
        [["fusion.1", "l3_wkvb", 64]]),
    # fetched ahead and read by its product: no copy
    "a_fetch_ahead_of_the_product": (
        "  %copy-start.1 = (bf16[8,4]{1,0:S(1)}, bf16[8,4]{1,0}, u32[]) "
        "copy-start(%params__l3_wkvb__.1)\n"
        "  %copy-done.1 = bf16[8,4]{1,0:S(1)} copy-done(%copy-start.1)\n"
        "  ROOT %fusion.2 = f32[2,4]{1,0} fusion(%copy-done.1, %x.1), "
        "kind=kOutput, calls=%fused_computation.2", []),
}


@pytest.mark.parametrize("case", sorted(_READS))
def test_the_probe_lists_a_weight_the_step_copies_and_no_fetch(case):
    """``parameter_fed_copies`` over a module's text: a weight the entry
    computation copies, transposes or hands to a fusion of nothing else,
    itself or through what only moved it, is listed with its bytes; a fetch
    ahead of the product that reads it is not, nor a copy of the cache's
    carry (no weight)."""
    body, want = _READS[case]
    assert _weights_relaid(_MODULE % body) == want


@pytest.mark.parametrize("kind", ["step", "multi"])
def test_decode_step_keeps_the_pool_in_place_on_v5e(one_chip, kind):
    """GPT-2-medium widths (16 heads of 64, 1024 positions), bucket 32, a
    4.8e8-byte pool a layer pair, on the gather path (the fallback: what
    the backend check picks here): the compiled step aliases the whole pool,
    needs under a quarter of it beside its arguments, and holds no copy,
    select, transpose or slice of a pool's shape (PERF.md section 6, PR 26:
    the parent had four whole-pool layout copies and a slice per layer)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "gpt2-medium-serve.json")) as fp:
        config = dict(json.load(fp), n_layer=4)
    cfg = gpt2_decoder.decoder_config(config)
    assert (cfg.heads, cfg.head_dim, cfg.max_seq) == (16, 64, 1024)
    lanes, block_size, blocks = 32, 16, 1536
    kv = KVCacheConfig(cfg.layers, cfg.heads, cfg.head_dim, block_size,
                       blocks, "f32")

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.float32)
        for name, (shape, _kind) in gpt2_decoder.param_shapes(config).items()})
    per_lane = (lanes,) if kind == "step" else (lanes, 2)
    feeds = on_chip([
        jax.ShapeDtypeStruct(per_lane, jnp.int32),
        jax.ShapeDtypeStruct(per_lane, jnp.int32),
        jax.ShapeDtypeStruct((lanes, cfg.max_seq // block_size), jnp.int32),
        jax.ShapeDtypeStruct(per_lane, jnp.int32)])
    fn = dm.make_paged_step(cfg, kv) if kind == "step" \
        else dm.make_paged_step_multi(cfg, kv, 2)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        carry, params, *feeds).compile()

    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes / 4
    pool_shape = r"f32\[%d,%d,%d\]" % (blocks, block_size, cfg.hidden)
    whole_pool_pass = re.compile(
        r" = %s\S* (copy|select|transpose|slice)\(" % pool_shape)
    found = [line.strip()[:160] for line in compiled.as_text().splitlines()
             if whole_pool_pass.search(line)]
    assert not found, found
    assert re.search(r" = %s\S* scatter\(" % pool_shape, compiled.as_text())


def test_olmoe_step_streams_its_experts_and_keeps_the_pool_in_place(one_chip):
    """OLMoE's published widths (16 heads of 128, 64 experts of 1024, 2048
    positions served), 2 layers, bucket 32, a bf16 pool of 2048 blocks: the
    compiled step aliases the whole pool, holds no copy or transpose of an
    expert tensor (0.27e9 bytes each: the matmuls read the weights as they
    lie), and needs beside its arguments about one layer's gathered K and V
    (PERF.md section 6, PR 27: 0.28e9 for any depth)."""
    from benchmark.models import olmoe_decoder

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b-serve.json")) as fp:
        config = dict(json.load(fp), num_hidden_layers=2)
    cfg = olmoe_decoder.decoder_config(config)
    assert (cfg.heads, cfg.head_dim, cfg.experts, cfg.experts_per_token,
            cfg.ffn, cfg.max_seq, cfg.kv_dtype) == (16, 128, 64, 8, 1024,
                                                    2048, "bf16")
    lanes, block_size, blocks = 32, 16, 2048
    kv = KVCacheConfig(cfg.layers, cfg.heads, cfg.head_dim, block_size,
                       blocks, cfg.kv_dtype)

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in olmoe_decoder.param_shapes(config).items()})
    feeds = on_chip([
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((lanes, cfg.max_seq // block_size), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32)])
    compiled = jax.jit(dm.make_paged_step(cfg, kv), donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()
    out = jax.eval_shape(dm.make_paged_step(cfg, kv), carry, params, *feeds)
    assert out[3].shape == (cfg.layers, cfg.experts)       # routed counts

    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    gathered = lanes * cfg.max_seq * cfg.hidden * 2         # one K or V
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 2.5 * gathered
    # off the TPU's rule (no ``as_on_tpu``) the experts are the einsums,
    # the fallback: no kernel, and still no pass over an expert tensor
    assert _kernel_calls(compiled.as_text()) == 0
    assert not _expert_passes(compiled.as_text(), 64, 2048, 1024)


@pytest.mark.parametrize("kind", ["step", "multi", "packed"])
@pytest.mark.parametrize("model", ["gpt2-medium-serve", "olmoe-1b-7b-serve"])
def test_decode_step_reads_the_pool_through_the_kernel_on_v5e(
        one_chip, as_on_tpu, model, kind):
    """Both serving configurations at their published widths, 2 layers,
    bucket 32, the cells' pools: Mosaic accepts the paged-attention kernel
    inside the whole step (one call a layer and column), the pool is
    aliased whole and written in place between the kernel's reads, and
    beside its arguments the step holds nothing of the table's size: no
    gathered history (``[32, positions, width]``, 0.134e9 / 0.268e9 bytes)
    and no pass over a pool (PERF.md section 6, PR 28).  OLMoE's routed
    layers are the expert kernel, one call a layer and column, its 63 of 64
    experts hit included (PR 34: it streams them faster than the einsums),
    with no copy, transpose or convert of an expert tensor; GPT-2's step
    holds no such call."""
    from benchmark.models import olmoe_decoder

    module, layers_key, blocks, weights = {
        "gpt2-medium-serve": (gpt2_decoder, "n_layer", 1024, jnp.float32),
        "olmoe-1b-7b-serve": (olmoe_decoder, "num_hidden_layers", 2048,
                              jnp.bfloat16)}[model]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           model + ".json")) as fp:
        config = dict(json.load(fp), **{layers_key: 2})
    cfg = module.decoder_config(config)
    lanes, block_size, width = 32, 16, 2 if kind == "multi" else 1
    kv = KVCacheConfig(cfg.layers, cfg.heads, cfg.head_dim, block_size,
                       blocks, cfg.kv_dtype or "f32")
    assert dm.attention_path(cfg, kv) == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, weights)
        for name, (shape, _kind) in module.param_shapes(config).items()})
    per_lane = (lanes,) if kind == "step" else (lanes, width)
    feeds = on_chip([
        jax.ShapeDtypeStruct(per_lane, jnp.int32),
        jax.ShapeDtypeStruct(per_lane, jnp.int32),
        jax.ShapeDtypeStruct((lanes, cfg.max_seq // block_size), jnp.int32),
        jax.ShapeDtypeStruct(per_lane, jnp.int32)])
    fn = dm.make_paged_step(cfg, kv) if kind == "step" \
        else dm.make_paged_step_multi(cfg, kv, width)
    if kind == "packed":
        # the step as the engine compiles it (PR 43): the lanes' integers
        # in one array, cut apart in the same executable
        feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
        fn = dm.make_packed_step(cfg, kv, lanes)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        carry, params, *feeds).compile()

    text = compiled.as_text()
    routed = len(cfg.routed_layers) * width
    assert _expert_kernels(text) == routed
    assert _kernel_calls(text) == cfg.layers * width + routed
    if routed:
        assert not _expert_passes(text, cfg.experts, cfg.hidden, cfg.ffn)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    gathered = lanes * cfg.max_seq * cfg.hidden * carry[0].dtype.itemsize
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < gathered / 8
    big = re.compile(
        r" = \w+\[(%d,%d|%d,%d),%d\]\S* "
        r"(copy|select|transpose|slice|dynamic-slice|gather|concatenate)\("
        % (blocks, block_size, lanes, cfg.max_seq, cfg.hidden))
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_granite_hybrid_step_updates_both_pools_in_place(one_chip, as_on_tpu):
    """granite-4.0-h-micro's published widths (32 query heads over 8 KV
    heads of 64, 64 state-space heads of 64 with state 128), its first 6
    layers (5 mamba, 1 attention), bucket 32, the cell's pools (2048 bf16
    blocks 512 wide, 33 state slots): Mosaic accepts the grouped-query
    paged-attention kernel and the state-update kernel inside the whole
    step, both pools are aliased whole, and beside its arguments the step
    holds nothing of a state pool's size (PERF.md section 6, PR 31: XLA's
    form of the update made a whole-pool pass a layer and 0.15e9 bytes of
    temporaries)."""
    from benchmark.models import granite_hybrid_decoder
    from paddle_tpu.pallas_kernels import ssm_update as ssm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-serve.json")) as fp:
        config = json.load(fp)
    config = dict(config, num_hidden_layers=6,
                  layer_types=config["layer_types"][:6])
    cfg = granite_hybrid_decoder.decoder_config(config)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.ssm_heads,
            cfg.ssm_head_dim, cfg.ssm_state, cfg.attention_multiplier) \
        == (32, 8, 64, 64, 64, 128, 0.015625)
    lanes, block_size, blocks = 32, 16, 2048
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert dm.attention_path(cfg, kv, lanes) == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in granite_hybrid_decoder.param_shapes(config).items()})
    feeds = on_chip([
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((lanes, cfg.max_seq // block_size), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32)])
    compiled = jax.jit(dm.make_paged_step(cfg, kv), donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 6            # 1 attention, 5 state updates
    assert _expert_kernels(text) == 0          # no routed layer, no such call
    assert len(re.findall(r"%ssm_state_update\S* = ", text)) == 5
    # a lane's slot [128, 4096] is one transfer, 4 of them a batch, two
    # batches inside the VMEM the kernel asks for; b and c go in as they
    # are, not a value a lane repeated 128 times
    assert dm.state_update_path(cfg, kv, lanes) == "pallas"
    assert ssm.transfer_columns((lanes + 1, 128, 4096)) == 4096
    assert 2 * ssm.BATCH * 128 * 4096 * 4 <= ssm._UNIT_BUDGET \
        < ssm._VMEM_LIMIT
    assert not re.search(r"f32\[32,128,128\]", text)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    state_pool = (lanes + 1) * 128 * 4096 * 4
    assert pool_bytes == 2 * 2048 * 16 * 512 * 2 + 5 * (
        state_pool + 33 * 13056 * 2)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < state_pool / 2
    big = re.compile(
        r" = f32\[(33|32),128,(4096|2048)\]\S* "
        r"(copy|select|transpose|slice|dynamic-slice|gather|scatter|fusion)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_lfm2_moe_step_updates_pools_and_windows_in_place(one_chip,
                                                          as_on_tpu):
    """LFM2-24B-A2B's published widths (32 query heads over 8 KV heads of
    64, 64 experts of width 1536, the dense MLP of 11776, 3 taps), the first
    three layers of the configuration's cut (conv + dense, attention +
    experts, conv + experts), bucket 32, the cell's pools (2048 bf16 blocks
    512 wide, 33 window slots): Mosaic accepts the grouped-query
    paged-attention kernel inside the whole step at Granite's geometry and
    the routed-expert kernel once a routed layer (PR 34: blocks of
    ``[2048, 768]`` and ``[768, 2048]`` steered by the hit experts' order,
    no copy, transpose or convert of an expert tensor), the KV pools and
    the window slots are aliased whole, and beside its arguments the step
    holds less than one expert's activations would take in float32 for
    every lane and expert."""
    from benchmark.models import lfm2_moe_decoder

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-serve.json")) as fp:
        config = json.load(fp)
    config = dict(config, num_hidden_layers=3,
                  layer_types=config["layer_types"][:3])
    cfg = lfm2_moe_decoder.decoder_config(config)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.conv_taps, cfg.experts,
            cfg.experts_per_token, cfg.ffn, cfg.dense_ffn, cfg.layer_types,
            cfg.routed_layers) == (
        32, 8, 64, 3, 64, 4, 1536, 11776, ("conv", "attention", "conv"),
        (1, 2))
    lanes, block_size, blocks = 32, 16, 2048
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert dm.attention_path(cfg, kv, lanes) == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in lfm2_moe_decoder.param_shapes(config).items()})
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _expert_kernels(text) == 2          # one a routed layer
    assert _kernel_calls(text) == 3            # and the one attention layer
    assert not _expert_passes(text, 64, 2048, 1536)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 2 * 2048 * 16 * 512 * 2 + 2 * 33 * 4096 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 64 * 32 * 1536 * 4
    # nothing of a window pool's shape is copied or rebuilt
    big = re.compile(
        r" = bf16\[33,4096\]\S* "
        r"(copy|select|transpose|slice|dynamic-slice|gather|concatenate)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_exaone_moe_step_compiles_both_attention_kinds_and_its_experts(
        one_chip, as_on_tpu):
    """K-EXAONE-236B-A23B's published widths (64 query heads over 8 KV
    heads of 128 behind a stream of 6144, a window of 128, 16 held experts
    of width 2048 under a router of 128, the dense MLP of 18432), published
    layers 0 and 3 of the configuration's cut (sliding + dense, full +
    sparse), bucket 32, the cell's pools (12,832 bf16 blocks 1024 wide for
    the global layer, 33 rings of 9 blocks for the window layer): Mosaic
    accepts the paged-attention kernel twice inside the whole step, once
    over a ring as one chunk of 144 positions and once over the whole
    context, the query compact in VMEM both times (2.1e6 B of queries and
    outputs where the spread layout asked 16.8e6 and was refused), and the
    routed-expert kernel over the 16 held experts in column chunks of 256
    (22.2e6 B of VMEM, under its 40 MiB limit); both kinds of pool are
    aliased whole."""
    from benchmark.models import exaone_moe_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-236b-a23b-serve.json")) as fp:
        config = json.load(fp)
    keep = [0, 3]
    config = dict(config, num_hidden_layers=2, **{
        key: [config[key][l] for l in keep]
        for key in ("layer_types", "mlp_layer_types", "sliding_windows")})
    cfg = exaone_moe_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.window,
            cfg.experts, cfg.experts_held, cfg.experts_per_token, cfg.ffn,
            cfg.shared_ffn, cfg.dense_ffn, cfg.layer_types,
            cfg.routed_layers, cfg.max_seq) == (
        6144, 64, 8, 128, 128, 128, 16, 8, 2048, 2048, 18432,
        ("window", "attention"), (1,), 8192)
    lanes, block_size, blocks = 32, 16, 12832
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert (kv.window_ring, kv.window_blocks) == (9, 297)
    assert dm.attention_path(cfg, kv, lanes) == "pallas"
    assert dm.attention_path(cfg, kv, lanes, "window") == "pallas"
    q = (lanes, 64, 128)
    assert pa.vmem_bytes(q, (blocks, 16, 1024), jnp.bfloat16) \
        == 4 * 128 * 1024 * 2 + 2 * lanes * 64 * 128 * 4 == 3145728
    assert pa.vmem_bytes(q, (297, 16, 1024), jnp.bfloat16, 9) \
        == 4 * 144 * 1024 * 2 + 2 * lanes * 64 * 128 * 4 == 3276800
    assert moe.f_chunk(6144, 2048, 2) == 256
    assert moe._vmem_bytes(32, 6144, 256, 2) == 22151168 < moe._VMEM_LIMIT
    assert moe.experts_path(lanes, (16, 6144, 2048), jnp.bfloat16) == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in exaone_moe_decoder.param_shapes(config).items()})
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _expert_kernels(text) == 1          # the one sparse layer
    assert _kernel_calls(text) == 3            # and an attention a layer
    assert len(re.findall(r"%paged_attention\S* = ", text)) == 2
    assert not _expert_passes(text, 16, 6144, 2048)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 2 * (12832 + 297) * 16 * 1024 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside its arguments: the dense layer's [32, 18432] activations and
    # the like, nothing of a pool's or of the spread query's size
    assert memory.temp_size_in_bytes < 2 * lanes * 64 * 1024 * 4
    spread = re.compile(r" = f32\[32,64,1024\]")
    found = [line.strip()[:160] for line in text.splitlines()
             if spread.search(line)]
    assert not found, found


def test_smallthinker_step_walks_a_ring_of_257_blocks_in_chunks(
        one_chip, as_on_tpu):
    """SmallThinker-21BA3B's published widths (28 query heads over 4 KV heads
    of 128 behind a stream of 2560, a window of 4,096, 64 ReLU-gated experts
    of width 768), published layers 0 and 1 (global, window), bucket 32, the
    cell's pools (25,120 bf16 blocks 512 wide for the global layer, 33 rings
    of 257 blocks for the window layer) and tables (1,024 slots, the
    published 16,384 positions): Mosaic accepts the paged-attention kernel
    twice inside the whole step, once over the whole context and once over
    a ring walked in chunks of 256 positions (2.1e6 B of VMEM where the ring
    as one chunk asked 16.8e6 of buffers and was refused), 7 query heads a
    KV head, compact, and the routed-expert kernel with its ReLU gate over
    the 64 experts in one column chunk; both kinds of pool are aliased
    whole."""
    from benchmark.models import smallthinker_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-serve.json")) as fp:
        config = json.load(fp)
    config = dict(config, num_hidden_layers=2, **{
        key: config[key][:2]
        for key in ("layer_types", "rope_layout", "sliding_window_layout")})
    cfg = smallthinker_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.window,
            cfg.experts, cfg.experts_per_token, cfg.ffn, cfg.layer_types,
            cfg.routed_layers, cfg.max_seq) == (
        2560, 28, 4, 128, 4096, 64, 6, 768, ("attention", "window"), (0, 1),
        16384)
    lanes, block_size, blocks = 32, 16, 25120
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert (kv.window_ring, kv.window_blocks) == (257, 8481)
    assert dm.attention_path(cfg, kv, lanes) == "pallas"
    assert dm.attention_path(cfg, kv, lanes, "window") == "pallas"
    assert dm.chunk_positions(cfg, kv, lanes) == {"attention": 256,
                                                  "window": 256}
    assert moe.f_chunk(2560, 768, 2) == 768
    assert moe.experts_path(lanes, (64, 2560, 768), jnp.bfloat16) == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in smallthinker_decoder.param_shapes(config).items()})
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    assert feeds[1].shape == (lanes, 4 + 1024 + 257)
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _expert_kernels(text) == 2          # one a layer
    assert _kernel_calls(text) == 4            # and an attention a layer
    assert len(re.findall(r"%paged_attention\S* = ", text)) == 2
    assert not _expert_passes(text, 64, 2560, 768)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 2 * (25120 + 8481) * 16 * 512 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside its arguments: the logits [32, 151936] in float32 and the like,
    # nothing of a ring's size gathered ([32, 4112, 512] is 0.13e9 B)
    assert memory.temp_size_in_bytes < 2 * lanes * 151936 * 4
    gathered = re.compile(r" = (bf16|f32)\[32,4112,")
    found = [line.strip()[:160] for line in text.splitlines()
             if gathered.search(line)]
    assert not found, found


def test_nemotron_h_step_compiles_its_three_kernels_at_published_shapes(
        one_chip, as_on_tpu):
    """NVIDIA-Nemotron-3-Nano-30B-A3B's published widths, its first 6 blocks
    (``M E M E M *``: every kind), bucket 32, the cell's pools (2048 bf16
    blocks 256 wide for 2 KV heads of 128, 33 state slots of [128, 4096]):
    Mosaic accepts, inside the whole step as the engine compiles it
    (``make_packed_step``), the state-update kernel with B and C in 8
    groups (a lane's whole slot one transfer, spanning all 8, batches of 4
    slots in the 24 MiB of VMEM the kernel asks for), the two-matrix expert
    kernel
    over 16 held experts ``[16, 1856, 2688]`` cut on the second-minor axis
    (1856 is no multiple of 128), and the paged-attention kernel for 32
    query heads over 2 (groups of 16, compact); the experts are read as
    they lie and every pool is aliased whole."""
    from benchmark.models import nemotron_h_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.pallas_kernels import ssm_update as ssm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-serve.json")) as fp:
        config = json.load(fp)
    config = dict(config, num_hidden_layers=6,
                  hybrid_override_pattern=config[
                      "hybrid_override_pattern"][:6])
    cfg = nemotron_h_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.ssm_heads,
            cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.experts,
            cfg.experts_held, cfg.experts_per_token, cfg.ffn, cfg.shared_ffn,
            cfg.layer_types, cfg.routed_layers) == (
        2688, 32, 2, 128, 64, 64, 128, 8, 128, 16, 6, 1856, 3712,
        ("mamba", "experts", "mamba", "experts", "mamba", "attention"),
        (1, 3))
    lanes, block_size, blocks = 32, 16, 2048
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert pa._compact(32, 2, 128)
    assert dm.attention_path(cfg, kv, lanes) == "pallas"
    assert dm.state_update_path(cfg, kv, lanes) == "pallas"
    # a lane's whole slot is one transfer: it spans all 8 groups of 512
    assert ssm.transfer_columns(
        (kv.state_slots,) + kv.state_shapes[1][0], 8) == 4096 == 8 * 512
    assert 2 * ssm.BATCH * 128 * 4096 * 4 <= ssm._UNIT_BUDGET \
        < ssm._VMEM_LIMIT
    assert moe.experts_path(lanes, (16, 1856, 2688), jnp.bfloat16,
                            matrices=2) == "pallas"
    assert moe.experts_path(lanes, (16, 2688, 1856), jnp.bfloat16) == "einsum"
    fr = moe.f_rows(2688, 1856, jnp.bfloat16)
    assert 1856 % fr == 0 and fr % 16 == 0
    assert 4 * 2688 * fr * 2 <= moe._BLOCK_BUDGET

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip({
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        for name, (shape, _kind)
        in nemotron_h_decoder.param_shapes(config).items()})
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 6     # 3 state updates, 2 experts, 1 attn
    assert len(re.findall(r"%ssm_state_update\S* = ", text)) == 3
    assert len(re.findall(r"%moe_relu2_experts\S* = ", text)) == 2
    assert len(re.findall(r"%paged_attention\S* = ", text)) == 1
    assert _expert_kernels(text) == 0   # the three-matrix form is not here
    assert not _expert_passes(text, 16, 2688, 1856)
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    state_pool = (lanes + 1) * 128 * 4096 * 4
    assert pool_bytes == 2 * 2048 * 16 * 256 * 2 + 3 * (
        state_pool + 33 * 3 * 6144 * 2)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # b and c cross into the kernel as they are, [32, 8, 128] float32 each
    # (131e3 B): not a value a lane repeated 128 times (2.1e6 B each, until
    # PR 44), nor spread over the groups' columns (16.8e6 B each)
    assert memory.temp_size_in_bytes < state_pool / 4
    assert not re.search(r"f32\[32,128,128\]", text)
    big = re.compile(
        r" = f32\[(33|32),128,(4096|2048|1024)\]\S* "
        r"(copy|select|transpose|slice|dynamic-slice|gather|scatter|fusion)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_kimi_linear_step_compiles_its_three_kernels_at_published_shapes(
        one_chip, as_on_tpu):
    """Kimi-Linear-48B-A3B's published widths, its first 5 layers (``kda``
    under the dense lead, ``kda``, ``kda``, ``latent``, ``kda``: every kind),
    bucket 32, the cell's pools (12,832 bf16 latent blocks whose rows of 576
    values lie 640 wide, 33 state slots of [128, 4096]): Mosaic accepts,
    inside the whole step as the engine compiles it (``make_packed_step``),
    the delta-rule state-update kernel (a lane's whole slot one transfer,
    ``ssm_update``'s batches of 4), the latent form of the paged-attention
    kernel (32 query rows of 640 over one cached head, the value its first
    512 columns) and the routed-expert kernel over 16 held experts of width
    1024; every pool is aliased whole and nothing of a pool's size is made
    beside the arguments."""
    from benchmark.models import kimi_linear_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import ssm_update as ssm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-serve.json")) as fp:
        config = json.load(fp)
    linear = dict(config["linear_attn_config"], kda_layers=[1, 2, 3, 5],
                  full_attn_layers=[4])
    config = dict(config, num_hidden_layers=5, linear_attn_config=linear)
    cfg = kimi_linear_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rank,
            cfg.latent_rope, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
            cfg.experts, cfg.experts_held, cfg.experts_per_token, cfg.ffn,
            cfg.shared_ffn, cfg.dense_ffn, cfg.vocab, cfg.layer_types,
            cfg.routed_layers) == (
        2304, 32, 128, 512, 64, 32, 128, 4, 256, 16, 8, 1024, 1024, 9216,
        163840, ("kda", "kda", "kda", "latent", "kda"), (1, 2, 3, 4))
    lanes, block_size, blocks = 32, 16, 12832
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert (kv.layers, kv.latent_layers, kv.latent_width, kv.latent_row,
            kv.state_layers) == (0, 1, 576, 640, 4)
    assert kv.state_shapes == (((3 * 12288,), "bf16"),
                               ((128, 4096), "f32"))
    assert dm.attention_path(cfg, kv, lanes, "latent") == "pallas"
    assert dm.state_update_path(cfg, kv, lanes) == "pallas"
    assert dm.state_update_columns(cfg, kv) == 4096
    assert moe.experts_path(lanes, (16, 2304, 1024), jnp.bfloat16) \
        == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip(_as_held(cfg, kimi_linear_decoder.param_shapes(config)))
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 9     # 4 state updates, 1 latent, 4 experts
    assert len(re.findall(r"%kda_state_update\S* = ", text)) == 4
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 1
    assert _expert_kernels(text) == 4
    assert not re.findall(r"%(ssm_state_update|paged_attention)\S* = ", text)
    assert not _expert_passes(text, 16, 2304, 1024)
    # the latent layer's up-projection was laid out at load: no weight is
    # copied into another layout by the step (the published ``wkvb`` was
    # fetched into VMEM and turned there, a layer a step)
    assert sorted(n for n in params if "wkvb" in n) == ["l3_wkvb_k",
                                                        "l3_wkvb_v"]
    assert _weights_relaid(text) == []
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    state_pool = (lanes + 1) * 128 * 4096 * 4
    assert pool_bytes == 12832 * 16 * 640 * 2 + 4 * (
        state_pool + 33 * 3 * 12288 * 2)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # what varies down a head's keys crosses into the state kernel turned,
    # one [32, 128, 128] float32 array a layer (2.1e6 B), and nothing else
    # of a slot's size is made (24.4e6 B beside the arguments here, 20.7e6
    # before PR 65: the lanes' windows are relaid between a lane's row and a
    # slot's tiles, [32, 36864] bfloat16, 2.4e6 B; under two fifths of one
    # state pool)
    assert memory.temp_size_in_bytes < 2 * state_pool / 5
    # the latent pool is written a row a lane where it lies (a scatter into
    # the donated array) and never copied into another layout
    big = re.compile(
        r" = (f32\[(33|32),128,(4096|2048|1024)\]\S* (copy|select|transpose"
        r"|slice|dynamic-slice|gather|scatter|fusion)|bf16\[12832,16,640\]"
        r"\S* (copy|transpose|convert))\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_solar_open2_step_compiles_its_three_kernels_at_64_lanes(
        one_chip, as_on_tpu):
    """Solar-Open2-250B's published widths, one period of its layers
    (``attention``, ``kda``, ``kda``, ``kda``: both kinds), bucket 64, the
    cell's pools (25,664 bf16 K/V blocks of 8 heads of 128, 65 state slots
    of [128, 8192]): Mosaic accepts, inside the whole step as the engine
    compiles it (``make_packed_step``), the delta-rule state-update kernel
    at 64 heads (a lane's whole slot of 4 MiB one transfer, batches of 2:
    exactly ``ssm_update._UNIT_BUDGET``; the four columns a head in two
    128-lane tiles), the K/V walk at 64 lanes of 64 query heads over 8 KV
    heads, and the routed-expert kernel over 20 held experts of width 1280
    under a router of 320; every pool is aliased whole and nothing of a
    pool's size is made beside the arguments."""
    from benchmark.models import solar_open2_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import ssm_update as ssm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b-serve.json")) as fp:
        config = dict(json.load(fp), num_hidden_layers=4)
    cfg = solar_open2_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.kda_heads,
            cfg.kda_head_dim, cfg.kda_conv, cfg.kda_neg_eigval, cfg.experts,
            cfg.experts_held, cfg.experts_per_token, cfg.ffn, cfg.shared_ffn,
            cfg.vocab, cfg.layer_types, cfg.routed_layers) == (
        4096, 64, 8, 128, 64, 128, 4, True, 320, 20, 8, 1280, 1280, 24576,
        ("attention", "kda", "kda", "kda"), (0, 1, 2, 3))
    lanes, block_size, blocks = 64, 16, 25664
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=lanes + 1)
    assert (kv.layers, kv.heads, kv.head_dim, kv.latent_layers,
            kv.state_layers) == (1, 8, 128, 0, 3)
    assert kv.state_shapes == (((3 * 24576,), "bf16"),
                               ((128, 8192), "f32"))
    assert dm.attention_path(cfg, kv, lanes) == "pallas"
    assert dm.state_update_path(cfg, kv, lanes) == "pallas"
    assert dm.state_update_columns(cfg, kv) == 8192
    assert ssm.units_in_flight((65, 128, 8192), 8192, lanes) == 2
    assert moe.experts_path(lanes, (20, 4096, 1280), jnp.bfloat16) \
        == "pallas"

    on_chip = functools.partial(_placed, one_chip)

    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip(_as_held(cfg, solar_open2_decoder.param_shapes(config)))
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 8     # 3 state updates, 1 walk, 4 experts
    assert len(re.findall(r"%kda_state_update\S* = ", text)) == 3
    assert len(re.findall(r"%paged_attention\S* = ", text)) == 1
    assert _expert_kernels(text) == 4
    assert not re.findall(r"%(ssm_state_update|latent_attention)\S* = ",
                          text)
    assert not _expert_passes(text, 20, 4096, 1280)
    assert _weights_relaid(text) == []
    # a window's slot is whole tiles, [576, 128] of a pool [65, 576, 128]:
    # the 64 lanes' windows go back as one scatter a layer, not as the loop
    # of 64 one-row ``dynamic-update-slice``s a flat pool's became (PR 65)
    assert carry[2].shape == (65, 576, 128)
    assert " while(" not in text
    assert len(re.findall(r"kda/conv/window/scatter", text)) >= 3
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    state_pool = (lanes + 1) * 128 * 8192 * 4
    assert pool_bytes == 2 * 25664 * 16 * 1024 * 2 + 3 * (
        state_pool + 65 * 3 * 24576 * 2)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # what varies down a head's keys crosses into the state kernel turned,
    # one [64, 128, 256] float32 array a layer (8.4e6 B), and nothing else
    # of a slot's size is made
    assert memory.temp_size_in_bytes < state_pool / 3
    big = re.compile(
        r" = (f32\[(65|64),128,(8192|4096|2048)\]\S* (copy|select|transpose"
        r"|slice|dynamic-slice|gather|scatter|fusion)|bf16\[25664,16,1024\]"
        r"\S* (copy|transpose|convert))\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_dots_vlm_step_compiles_its_two_kernels_at_published_shapes(
        one_chip, as_on_tpu):
    """dots.vlm1's published widths, its dense lead and two routed layers,
    bucket 32, the cell's pools (12,832 bf16 latent blocks whose rows of 576
    values lie 640 wide, nothing else): Mosaic accepts, inside the whole
    step as the engine compiles it (``make_packed_step``), the latent form
    of the paged-attention kernel at 128 query rows of 640 (the grid walking
    the lanes, a lane's query and output in VMEM at a time) and the
    routed-expert kernel over 16 held experts of 7168 x 2048 in chunks of
    256 columns; every pool is aliased whole, no pool and no expert tensor
    is copied, turned or converted."""
    from benchmark.models import dots_vlm_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots-vlm1-inst-serve.json")) as fp:
        config = dict(json.load(fp), num_hidden_layers=3)
    cfg = dots_vlm_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rank,
            cfg.latent_rope, cfg.q_rank, cfg.experts, cfg.experts_held,
            cfg.n_group, cfg.topk_group, cfg.ffn, cfg.dense_ffn,
            cfg.layer_types, cfg.routed_layers) == (
        7168, 128, 128, 512, 64, 1536, 256, 16, 8, 4, 2048, 18432,
        ("latent",) * 3, (1, 2))
    lanes, block_size, blocks = 32, 16, 12832
    kv = dm.cache_config(cfg, block_size, blocks)
    assert (kv.layers, kv.latent_layers, kv.latent_row, kv.state_layers) \
        == (0, 3, 640, 0)
    assert dm.attention_path(cfg, kv, lanes, "latent") == "pallas"
    assert dm.chunk_positions(cfg, kv, lanes) == {"latent": 512}
    assert moe.experts_path(lanes, (16, 7168, 2048), jnp.bfloat16) \
        == "pallas"

    on_chip = functools.partial(_placed, one_chip)
    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip(_as_held(cfg, dots_vlm_decoder.param_shapes(config)))
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 5             # 3 latent, 2 experts
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 3
    assert _expert_kernels(text) == 2
    assert not _expert_passes(text, 16, 7168, 2048)
    # every layer's up-projection was laid out at load: no weight is copied
    # into another layout by the step (the published ``wkvb`` was, 33.5e6 B
    # a layer a step: PERF.md section 6, PR 52)
    assert params["l0_wkvb_k"].shape == (128, 512, 128) \
        and params["l0_wkvb_v"].shape == (128, 128, 512)
    assert _weights_relaid(text) == []
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 3 * 12832 * 16 * 640 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside the arguments: the lanes' queries and outputs of 128 heads
    # (10.5e6 and 8.4e6 B a layer, float32) and activations; under a
    # fifth of one pool
    assert memory.temp_size_in_bytes < 12832 * 16 * 640 * 2 / 5
    big = re.compile(r" = bf16\[12832,16,640\]\S* (copy|transpose|convert)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_xing4_step_compiles_its_mixings_round_two_kernels_at_the_cells_shapes(
        one_chip, as_on_tpu):
    """Xing4.0-29B-A4B's published widths, its dense lead and two routed
    layers (four of its 40 layers: eight mixings), bucket 32, the cell's
    pool (3,616 bf16 latent blocks whose rows of 576 values lie 640 wide):
    Mosaic accepts, inside the whole step as the engine compiles it
    (``make_packed_step``), the latent kernel at 32 heads x 32 lanes (every
    lane's query and output in VMEM at once: no lane grid) and the
    routed-expert kernel over 8 held experts of 3584 x 1024 in chunks of 512
    columns; since PR 68 a mixing's maps are one kernel too (``hc_maps``,
    eight here: the streams ``[4, 32, 3584]`` and a ``phi`` read as ``[24,
    14336]`` whole in VMEM, the product at the highest precision, the
    Sinkhorn iterations unrolled in it), under the scope the benchmark finds
    its time by, and XLA makes no more than ten operations of the rest of a
    mixing's maps (81 before); the read and the merge are XLA's, no loop,
    the parameters float32; every pool is aliased whole and no pool, expert
    tensor or ``phi`` is copied, turned or converted."""
    from benchmark.models import xing4_decoder
    from paddle_tpu.models import hyper_connections as hc
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b-serve.json")) as fp:
        config = dict(json.load(fp), num_hidden_layers=4)
    cfg = xing4_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rank,
            cfg.latent_rope, cfg.q_rank, cfg.experts, cfg.experts_held,
            cfg.experts_per_token, cfg.n_group, cfg.ffn, cfg.dense_ffn,
            cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp,
            cfg.mixings, cfg.layer_types, cfg.routed_layers) == (
        3584, 32, 128, 512, 64, 768, 64, 8, 4, 1, 1024, 9216, 4, 20, 1e-6,
        (-30.0, 30.0), 8, ("latent",) * 4, (2, 3))
    lanes, block_size, blocks = 32, 16, 3616
    kv = dm.cache_config(cfg, block_size, blocks)
    assert (kv.layers, kv.latent_layers, kv.latent_row, kv.state_layers) \
        == (0, 4, 640, 0)
    assert dm.attention_path(cfg, kv, lanes, "latent") == "pallas"
    assert dm.chunk_positions(cfg, kv, lanes) == {"latent": 512}
    assert not pa._latent_lane_grid((32, 32, 640), (3616, 16, 640),
                                    jnp.bfloat16, 512)
    assert moe.experts_path(lanes, (8, 3584, 1024), jnp.bfloat16) == "pallas"
    assert dm.experts_chunk(cfg) == 512

    on_chip = functools.partial(_placed, one_chip)
    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    # the mixings' parameters are float32 whatever the weights' dtype
    shapes = xing4_decoder.param_shapes(config)
    params = on_chip(jax.eval_shape(lambda p: dm.laid_out(cfg, p), {
        name: jax.ShapeDtypeStruct(
            shape, jnp.float32 if kind.startswith("hc_") else jnp.bfloat16)
        for name, (shape, kind) in shapes.items()}))
    assert params["l3_hc_mlp_phi"].shape == (4 * 3584, 24) \
        and params["l3_hc_mlp_phi"].dtype == jnp.float32
    assert hc.maps_path(cfg, lanes) == hc.maps_path(cfg, 64) == "pallas"
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 14    # 4 latent, 2 experts, 8 mixings
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 4
    assert _expert_kernels(text) == 2
    mixed = re.findall(r"%hc_maps\S* = .*?op_name=\"([^\"]*)\"", text)
    assert sorted(re.sub(r".*/(layer\d/hc/\w+_maps)/.*", r"\1", scope)
                  for scope in mixed) == sorted(
        "layer%d/hc/%s_maps" % (l, sub) for l in range(4)
        for sub in ("attn", "mlp"))
    # what XLA still runs under a mixing's maps beside the kernel
    entry = text[text.index("\nENTRY "):]
    under = [line for line in entry.splitlines()
             if re.search(r" (fusion|copy|slice|reduce|transpose)\(", line)
             and re.search(r"layer2/hc/attn_maps/", line)]
    assert 0 < len(under) < 10, under
    # the kernel reads ``phi`` as [24, 14336], and the published [14336, 24]
    # needs no layout at load for it: the chip holds that array with its
    # long axis minor, which is [24, 14336] row by row, so the turn is a
    # bitcast of what XLA fetched ahead of the call
    assert re.findall(r"%params__l2_hc_attn_phi\S* = f32\[14336,24\]"
                      r"\{0,1:T\(8,128\)\} parameter", text)
    call = re.search(r"%hc_maps\S* = f32\[24,32\]\S* custom-call\("
                     r"%[\w.\-]+, %(bitcast[\w.\-]*), ", text)
    assert call, "the kernel's phi is no bitcast"
    turned = re.search(r"%%%s = f32\[24,14336\]\{1,0:T\(8,128\)S\(1\)\} "
                       r"bitcast\(" % re.escape(call.group(1)), text)
    assert turned, "phi is not handed over in VMEM as fetched"
    assert not re.findall(r" while\(", text)
    assert not _expert_passes(text, 8, 3584, 1024)
    assert _weights_relaid(text) == []
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 4 * 3616 * 16 * 640 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 3616 * 16 * 640 * 2 / 2
    big = re.compile(r" = bf16\[3616,16,640\]\S* (copy|transpose|convert)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_glm_dsa_step_compiles_its_three_kernels_at_published_shapes(
        one_chip, as_on_tpu):
    """GLM-5's published widths, its dense lead and two routed layers, bucket
    32, the cell's pools (25,120 bf16 blocks: latent rows of 576 values held
    640 wide and index keys of 128 beside them): Mosaic accepts, inside the
    whole step as the engine compiles it (``make_packed_step``), the kernel
    that scores a lane's cached index keys (32 heads of 128 over chunks of
    1,024 positions under tables of 784 slots), the latent form of the
    paged-attention kernel at 64 query rows of 640 over a lane's own table
    under a mask of its 2,048 chosen positions (25 chunks of 512 a lane; no
    row is gathered), and the routed-expert kernel over 16 held experts of
    6144 x 2048; the mask is made from a layer's 32 x 12,544 scores by an
    exact threshold (``chosen_by_chunk``: the list's ``top_k`` is dead code
    in the served step, so no sort of the scores and no one-hot contraction
    is compiled, and nothing approximates); every pool is aliased whole, and
    no pool is copied, turned or converted."""
    from benchmark.models import glm_dsa_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5-serve.json")) as fp:
        config = dict(json.load(fp), num_hidden_layers=3)
    cfg = glm_dsa_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.v_head_dim,
            cfg.latent_rank, cfg.latent_rope, cfg.q_rank, cfg.index_heads,
            cfg.index_head_dim, cfg.index_topk, cfg.experts,
            cfg.experts_held, cfg.ffn, cfg.dense_ffn, cfg.layer_types,
            cfg.routed_layers, cfg.max_seq) == (
        6144, 64, 192, 256, 512, 64, 2048, 32, 128, 2048, 256, 16, 2048,
        12288, ("latent",) * 3, (1, 2), 12544)
    lanes, block_size, blocks = 32, 16, 25120
    kv = dm.cache_config(cfg, block_size, blocks)
    assert (kv.layers, kv.latent_layers, kv.latent_row, kv.index_layers,
            kv.index_width, kv.state_layers) == (0, 3, 640, 3, 128, 0)
    assert dm.attention_path(cfg, kv, lanes, "latent") == "pallas"
    assert dm.attention_path(cfg, kv, lanes, "selected") == "pallas_masked"
    assert dm.attention_path(cfg, kv, lanes, "index") == "pallas"
    assert dm.chunk_positions(cfg, kv, lanes) == {"latent": 512}
    assert moe.experts_path(lanes, (16, 6144, 2048), jnp.bfloat16) \
        == "pallas"

    on_chip = functools.partial(_placed, one_chip)
    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip(_as_held(cfg, glm_dsa_decoder.param_shapes(config)))
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert len(re.findall(r"%index_scores\S* = ", text)) == 3
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 3
    assert _expert_kernels(text) == 2
    assert _kernel_calls(text) == 8
    assert "ApproxTopK" not in text and "approx" not in text.lower()
    # the only sorts left are the routers' (8 of 256 experts): nothing of a
    # lane's 12,544 scores is sorted, and no list is laid out as a mask
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 2 and all("moe/router" in line for line in sorts)
    assert "latent/select/top_k" not in text and "latent/mask" not in text
    assert "s32[32,2048]" not in text
    assert not _expert_passes(text, 16, 6144, 2048)
    assert params["l0_wkvb_k"].shape == (64, 512, 192) \
        and params["l0_wkvb_v"].shape == (64, 256, 512)
    assert _weights_relaid(text) == []
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 3 * 25120 * 16 * (640 + 128) * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside the arguments: a layer's scores (32 x 12,544 float32) and the
    # mask, as made and as read (32 x 25 x 512 int32); no layer's chosen
    # rows (32 x 2,048 x 640 bfloat16, 84e6 B) are gathered
    assert memory.temp_size_in_bytes < 32 * 2048 * 640 * 2
    assert "bf16[32,2048,640]" not in text and "bf16[65536,640]" not in text
    big = re.compile(r" = bf16\[25120,16,(640|128)\]\S* "
                     r"(copy|transpose|convert)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_longcat_flash_step_compiles_at_published_widths_and_64_lanes(
        one_chip, as_on_tpu):
    """LongCat-Flash's published widths, one layer of the source (a pair:
    two latent sublayers, two dense MLPs of 12288, a router of 768 outputs
    over 16 held experts of 6144 x 2048), the cell's bucket of 64 lanes and
    its pools (17,472 bf16 latent blocks whose rows of 576 values lie 640
    wide, one a sublayer): Mosaic accepts, inside the whole step as the
    engine compiles it (``make_packed_step``), the latent form of the
    paged-attention kernel at 64 heads x 64 lanes of 640 and the
    routed-expert kernel ONCE a pair; every pool is aliased whole, no pool
    and no expert tensor is copied, turned or converted, and no weight is
    laid out again."""
    from benchmark.models import longcat_flash_decoder
    from paddle_tpu.pallas_kernels import moe_experts as moe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-chat-serve.json")) as fp:
        config = dict(json.load(fp), num_layers=1)
    cfg = longcat_flash_decoder.decoder_config(config)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rank,
            cfg.latent_rope, cfg.q_rank, cfg.experts, cfg.experts_held,
            cfg.zero_experts, cfg.router_width, cfg.experts_per_token,
            cfg.ffn, cfg.dense_ffn, cfg.layer_types, cfg.routed_layers,
            cfg.max_seq) == (
        6144, 64, 128, 512, 64, 1536, 512, 16, 256, 768, 12, 2048, 12288,
        ("latent",) * 2, (0,), 4352)
    assert (cfg.latent_q_scale, round(cfg.latent_kv_scale, 4)) == (2.0, 3.4641)
    lanes, block_size, blocks = 64, 16, 17472
    kv = dm.cache_config(cfg, block_size, blocks)
    assert (kv.layers, kv.latent_layers, kv.latent_row, kv.state_layers) \
        == (0, 2, 640, 0)
    assert dm.attention_path(cfg, kv, lanes, "latent") == "pallas"
    assert dm.chunk_positions(cfg, kv, lanes) == {"latent": 512}
    assert moe.experts_path(lanes, (16, 6144, 2048), jnp.bfloat16) \
        == "pallas"

    on_chip = functools.partial(_placed, one_chip)
    carry = on_chip(jax.eval_shape(lambda: PagedKVCache(kv).carry()))
    params = on_chip(_as_held(
        cfg, longcat_flash_decoder.param_shapes(config)))
    feeds = on_chip(_packed_feeds(kv, lanes, cfg.max_seq // block_size))
    compiled = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                       donate_argnums=(0,)
                       ).lower(carry, params, *feeds).compile()

    text = compiled.as_text()
    assert _kernel_calls(text) == 3             # 2 latent, 1 experts
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 2
    assert _expert_kernels(text) == 1
    assert not _expert_passes(text, 16, 6144, 2048)
    assert params["l0_wkvb_k"].shape == (64, 512, 128) \
        and params["l1_wkvb_v"].shape == (64, 128, 512)
    assert _weights_relaid(text) == []
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in carry)
    assert pool_bytes == 2 * 17472 * 16 * 640 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # beside the arguments: 64 lanes' queries and outputs of 64 heads and
    # the dense MLPs' activations; under a fifth of one pool
    assert memory.temp_size_in_bytes < 17472 * 16 * 640 * 2 / 5
    big = re.compile(r" = bf16\[17472,16,640\]\S* (copy|transpose|convert)\(")
    found = [line.strip()[:160] for line in text.splitlines()
             if big.search(line)]
    assert not found, found


def test_glm_dsa_selected_read_gathers_the_rows_under_the_published_table(
        one_chip, as_on_tpu):
    """The other side of the rule: under a table of the published 202,752
    positions (12,672 slots, 99 positions a chosen one) the selected read
    keeps the row form, and Mosaic accepts the latent kernel over the 2,048
    gathered rows a lane (4,096 contiguous blocks of them) at the cell's
    other shapes."""
    from paddle_tpu.pallas_kernels import paged_attention as pa

    lanes, maxb, k = 32, 12672, 2048
    q, pool = (lanes, 64, 640), (25120, 16, 640)
    assert pa.selected_latent_path(q, pool, jnp.bfloat16, 512, k, maxb) \
        == "pallas"
    assert pa.selected_latent_path(q, pool, jnp.bfloat16, 512, k, 784) \
        == "pallas_masked"
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)
    compiled = jax.jit(lambda *a: pa.selected_latent_attention(
        *a, 0.0625, 512)).lower(
        shape(q, jnp.float32), shape(pool, jnp.bfloat16),
        shape((lanes, maxb), jnp.int32), shape((lanes,), jnp.int32),
        shape((lanes, k), jnp.int32), shape((lanes,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%latent_attention\S* = ", text)) == 1
    assert _kernel_calls(text) == 1
    assert "bf16[65536,640]" in text or "bf16[32,2048,640]" in text


def test_data_parallel_bert_layer_runs_fused_ln_per_shard_on_v5e_2x2(
        topo, as_on_tpu):
    """BERT-base's widths (hidden 768, bf16 AMP, dropout 0.1), one layer,
    batch 8 x seq 128 a chip, through ``with_data_parallel``'s route (jit +
    NamedSharding over a ("data",) mesh of the described 2x2): Mosaic
    accepts the fused epilogue inside the program XLA partitions (PR 24:
    "Mosaic kernels cannot be automatically partitioned"; since PR 39 the
    op and its grad op wrap their call in a shard_map), each shard's
    ``[1024, 768]`` rows stay where they lie (nothing of that size is
    gathered or permuted around the kernels), and the funnel counts the
    kernel, not ``gspmd_mesh``."""
    import bench
    import paddle_tpu as fluid
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.core import telemetry
    from paddle_tpu.framework import dtype_to_np
    from paddle_tpu.models import bert

    chips, per, seq = 4, 8, 128
    mesh = Mesh(np.array(topo.devices), ("data",))
    cfg = bert.BertConfig(layers=1, dropout=0.1)
    main, _startup, loss = bench.build_bert_pretrain(cfg, seq, amp=True)
    feed = bench._bert_feed(np.random.RandomState(0), cfg, chips * per, seq)
    old = fluid.get_flags(["FLAGS_telemetry"])
    fluid.set_flags({"FLAGS_telemetry": True})
    telemetry.reset()
    try:
        build = fluid.Executor(fluid.CPUPlace())._build(
            main, sorted(feed), [loss.name], mesh, "data")
        block = main.global_block()

        def persistable(name):
            v = block._find_var_recursive(name)
            return jax.ShapeDtypeStruct(
                tuple(v.shape), dtype_to_np(v.dtype),
                sharding=build.param_shardings[name])

        def fed(a):      # Executor._shard_feeds: split what divides
            spec = P("data") if a.shape[0] % chips == 0 else P()
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=NamedSharding(mesh, spec))

        compiled = jax.jit(
            build.fn, donate_argnums=build.donate,
            out_shardings=build.out_shardings).lower(
                {n: fed(a) for n, a in feed.items()},
                {n: persistable(n) for n in build.plan.ro_names},
                {n: persistable(n) for n in build.plan.rw_names}, {},
                jax.ShapeDtypeStruct((2,), np.uint32,
                                     sharding=NamedSharding(mesh, P()))
            ).compile()
        used = telemetry.counter_total("pallas_kernel_used_total")
        fell = [ls for _flat, ls in
                telemetry.label_sets("pallas_kernel_fallback_total")]
    finally:
        telemetry.reset()
        fluid.set_flags(old)

    text = compiled.as_text()
    # two epilogues a layer, forward and backward
    assert _kernel_calls(text) == 4
    assert (used, fell) == (4, [])
    rows = per * seq * 768
    moved = re.compile(r" (all-gather|collective-permute|all-to-all)"
                       r"(-start)?\(")
    shape = re.compile(r"(?:bf16|f32)\[([0-9,]+)\]")
    found = [line.strip()[:160] for line in text.splitlines()
             if moved.search(line) and any(
                 int(np.prod([int(d) for d in dims.split(",")])) >= rows
                 for dims in shape.findall(line))]
    assert not found, found
    # the gradients still meet in all-reduces, the small ones among them
    assert re.search(r" all-reduce(-start)?\(", text)
