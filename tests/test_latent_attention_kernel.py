"""The latent form of the paged-attention kernel (``latent_attention``:
``pallas_kernels/paged_attention.py`` ``_latent_kernel``) on the CPU tier,
through the Pallas interpreter: against the gather path at every border of
a chunk and of its steps of 128 positions, under a selection's mask, with and without
the grid walking the lanes, and under the interpreter's two models of an
async copy.  The K/V form, the shape rules and the decode steps are in
tests/test_paged_attention_kernel.py (one file a worker: the interpreter
takes 5-25 s a case here); the selected read's forms in
tests/test_glm_dsa.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import paged_attention as pa
from test_paged_attention_kernel import _borders, _unnamed_are_nan


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    fluid.set_flags({"FLAGS_telemetry": True})
    adoption.reset()
    _tm.reset()
    yield
    adoption.reset()
    _tm.reset()
    fluid.set_flags({"FLAGS_telemetry": False})


def _step_borders(block_size, span):
    """``_borders`` and the borders of a last chunk's whole steps of
    ``CHUNK_TOKENS`` positions (where a short last chunk of its own span
    would end: PERF.md section 6, PR 62): a step less one,
    a step, a step and one at every step of a lane of one chunk, the same
    behind one whole chunk and behind two, and two chunks and one block."""
    lens, maxb = _borders(block_size, span)
    steps = range(pa.CHUNK_TOKENS, span, pa.CHUNK_TOKENS)
    ends = [at + by for at in steps for by in (-1, 0, 1)] \
        + [span + at + by for at in steps for by in (-1, 1)] \
        + [2 * span + at for at in steps] + [2 * span + block_size]
    return sorted(set(lens) | {n for n in ends if n <= maxb * block_size}), \
        maxb


def _latent_case(lens, heads, width, block_size, maxb, dtype, seed):
    """A random latent pool, absorbed queries and a table in which each
    lane's blocks are its own and every unused slot is -1 -> (q, pool,
    tables, lens)."""
    rng = np.random.default_rng(seed)
    blocks = 1 + maxb * len(lens)
    pool = jnp.asarray(rng.standard_normal((blocks, block_size, width)),
                       dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), heads, width)),
                    jnp.float32)
    tables = np.full((len(lens), maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, blocks)))
    for b, n in enumerate(lens):
        for j in range(-(-n // block_size)):
            tables[b, j] = next(free)
    return q, pool, tables, np.asarray(lens, np.int32)


@pytest.mark.parametrize("span", [128, 256, 512])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_a_latent_chunk_fetches_the_blocks_a_lane_holds_and_no_others(
        interpreted, monkeypatch, dtype, tol, span):
    """The same of the latent form (12 query rows over one cached head 256
    wide, the value its first 128 columns), whose last chunk fetches the
    lane's last block again and no block it does not hold."""
    heads, width, rank, block_size = 12, 256, 128, 16
    monkeypatch.setattr(pa, "_CHUNK_BYTES",
                        span * width * jnp.dtype(dtype).itemsize)
    lens, maxb = _step_borders(block_size, span)
    q, pool, tables, lens = _latent_case(lens, heads, width, block_size,
                                         maxb, dtype, seed=5)
    assert pa.latent_chunk_positions(q.shape, pool.shape, dtype, rank,
                                     maxb) == span
    ref = np.asarray(pa.latent_attention_reference(q, pool, tables, lens,
                                                   0.1, rank))
    out = np.asarray(pa.latent_attention(
        q, _unnamed_are_nan(pool, tables), tables, lens, 0.1, rank))
    assert adoption.active_kernels() == ["latent_attention"]
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[1:], ref[1:], atol=tol, rtol=tol)
    assert not out[0].any()


@pytest.mark.parametrize("grid", [False, True], ids=["lanes_held", "grid"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_a_latent_chunk_under_a_mask_counts_the_chosen_positions_it_holds(
        interpreted, monkeypatch, dtype, tol, grid):
    """The latent form under a selection's mask, one more
    operand laid out by chunk (``[lanes, chunks, span]`` int32; a lane's
    where the grid walks the lanes): ``masked_latent(..., chosen=)`` over
    the positions the context holds AND the mask marks, at every border of
    a last chunk's steps, idle lanes between live ones, a lane that chose nothing
    of its last chunk.  Every block no table names is NaN."""
    # (blocks of 64: 8 copies a chunk, which the interpreter compiles four
    # times sooner than 32; a step of 128 positions is two of them)
    heads, width, rank, block_size, span = 12, 256, 128, 64, 512
    lens, maxb = _step_borders(block_size, span)
    lens = [0] + lens[:8] + [0, 0] + lens[8:] + [0]
    if grid:
        monkeypatch.setattr(pa, "_VMEM_BUDGET", 90000 + 2 * span * width
                            * jnp.dtype(dtype).itemsize)
    q, pool, tables, lens = _latent_case(lens, heads, width, block_size,
                                         maxb, dtype, seed=7)
    assert pa._latent_lane_grid(q.shape, pool.shape, dtype, rank) == grid
    chunks, by = pa._walk_layout(q.shape, pool.shape, dtype, rank, maxb)
    assert by == span
    chosen = np.random.default_rng(9).random((len(lens), chunks * span)) < .4
    chosen[-2, span:] = False       # nothing of what follows its first chunk
    ref = np.asarray(pa.masked_latent(
        q, pa.gather_blocks(pool, tables), lens, 0.1, rank,
        chosen=jnp.asarray(chosen[:, :maxb * block_size])))
    out = np.asarray(pa._latent_pallas(
        q, _unnamed_are_nan(pool, tables), tables, lens, 0.1, rank,
        chosen=jnp.asarray(chosen.reshape(len(lens), chunks, span),
                           jnp.int32)))
    assert np.isfinite(out).all()
    # (a lane that holds none of its chosen positions is nobody's to read)
    live = np.array([chosen[b, :n].any() for b, n in enumerate(lens)])
    assert live.sum() >= len(lens) - 6
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert not out[lens == 0].any()


# lanes' contexts at the borders of the latent cells' chunk of 512 positions,
# over a table of 32 blocks of 64 (8 copies a chunk, which the interpreter
# compiles four times sooner than the cells' 32): a lane's last chunk fetches
# its last block again for the slots past it, a lane walks its chunks in
# pairs, and a lane's last chunk starts the next live lane's first
LATENT_LANES = {
    "0": [0, 0, 0], "1": [1, 1543, 1], "511": [511, 512, 511],
    "512": [512, 1, 512], "513": [513, 513, 0], "1024": [1024, 1025, 1024],
    "1025": [1025, 1024, 1], "1543": [1543, 511, 1543],
    "whole_table": [2048, 2047, 2048],
    "live_between_idle": [0, 1025, 0], "idle_between_live": [1024, 0, 513],
    # ... and of a last chunk's steps of 128 positions (two blocks of 64)
    "127": [127, 128, 129], "383": [383, 384, 385],
    "a_chunk_and_a_step": [639, 640, 641], "two_chunks_and_a_block": [
        1088, 1152, 1153], "short_ends_between_idle": [129, 0, 1409, 0, 385],
}


# 64 heads with every lane's query held at once (the cells' 64 heads walk
# the lanes, as 128 do here): the last chunk's steps, in bfloat16
_SHORT_ENDS = ["127", "383", "a_chunk_and_a_step", "two_chunks_and_a_block",
          "short_ends_between_idle", "1543", "idle_between_live"]


@pytest.mark.parametrize("heads,dtype,tol,lanes", [
    pytest.param(heads, dtype, tol, lanes,
                 id="%d-%s-%s" % (heads, name, lanes))
    for heads in (32, 128)
    for name, dtype, tol in (("f32", jnp.float32, 2e-5),
                             ("bf16", jnp.bfloat16, 2e-2))
    for lanes in sorted(LATENT_LANES)] + [
    pytest.param(64, jnp.bfloat16, 2e-2, lanes, id="64-bf16-%s" % lanes)
    for lanes in _SHORT_ENDS])
def test_latent_kernel_matches_the_gather_path(interpreted, monkeypatch,
                                               heads, dtype, tol, lanes):
    """The latent kernel against ``latent_attention_reference`` where a
    lane's chunks are all full, all but the last, or only a part of one, and
    where its last chunk ends at a border of its steps of 128; 32 and
    64 query heads (every lane's query in one grid step) and 128 (a lane a
    grid step: the budget is shrunk until three lanes' queries no longer fit
    beside the buffers and two still do).  Every block no table names is
    NaN; an idle lane returns zeros."""
    width, rank, block_size, maxb = 256, 128, 64, 32
    lens = LATENT_LANES[lanes]
    grid = heads == 128
    if grid:
        lane = 4 * heads * (width + rank)
        monkeypatch.setattr(
            pa, "_VMEM_BUDGET",
            2 * 512 * width * jnp.dtype(dtype).itemsize + 2 * lane + lane // 3)
    q, pool, tables, lens = _latent_case(lens, heads, width, block_size,
                                         maxb, dtype, seed=11)
    assert pa._latent_lane_grid(q.shape, pool.shape, dtype, rank) == grid
    assert pa.latent_chunk_positions(q.shape, pool.shape, dtype, rank,
                                     maxb) == 512
    ref = np.asarray(pa.latent_attention_reference(q, pool, tables, lens,
                                                   0.1, rank))
    out = np.asarray(pa.latent_attention(
        q, _unnamed_are_nan(pool, tables), tables, lens, 0.1, rank))
    assert adoption.active_kernels() == ["latent_attention"]
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0
    assert np.isfinite(out).all()
    live = lens > 0
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert not out[~live].any()


# lanes for the two models of an async copy: a chunk of one step (128
# positions in blocks of 16), and a chunk of four (512 in blocks of 64) with
# lanes of one chunk of 1-4 steps, of two and of three chunks that end on 1-4
# steps, idle lanes between them and at both ends
TURNS = {
    "chunks_of_one_step": (16, 24, 128, [0, 300, 0, 0, 128, 129, 1, 384, 0]),
    "one_chunk": (64, 32, 512, [0, 100, 0, 200, 384, 0, 0, 512, 1, 0]),
    "two_chunks": (64, 32, 512, [612, 0, 768, 812, 0, 1024, 513]),
    "three_chunks": (64, 32, 512, [0, 1025, 1224, 0, 1408, 1536, 0, 1100]),
    "all_together": (64, 32, 512, [130, 1300, 0, 640, 2048, 64, 0, 1409]),
}


@pytest.mark.parametrize("lanes", sorted(TURNS))
@pytest.mark.parametrize("order", ["eager", "on_wait"])
def test_the_latent_kernel_keeps_its_turns(interpreted, monkeypatch, order,
                                           lanes):
    """The latent kernel under the TPU interpreter's two models of an async
    copy, done as it is started and only when it is waited for (memory no
    copy has filled reads NaN there): it waits for what it reads, starts
    nothing into a buffer whose
    copies are in flight, and what a chunk that nothing follows fetches for
    nobody (before an idle lane, at the end) is waited out.  Bit for bit the
    plain interpreter's output."""
    from jax.experimental.pallas import tpu as pltpu

    heads, width, rank = 12, 256, 128
    block_size, maxb, span, lens = TURNS[lanes]
    monkeypatch.setattr(pa, "_CHUNK_BYTES", span * width * 4)
    q, pool, tables, lens = _latent_case(lens, heads, width, block_size,
                                         maxb, jnp.float32, seed=3)
    assert pa.latent_chunk_positions(q.shape, pool.shape, jnp.float32, rank,
                                     maxb) == span
    ref = np.asarray(pa.latent_attention_reference(q, pool, tables, lens,
                                                   0.1, rank))
    pool = _unnamed_are_nan(pool, tables)
    plain = np.asarray(pa._latent_pallas(q, pool, tables, lens, 0.1, rank))
    got = np.asarray(pa._latent_pallas(
        q, pool, tables, lens, 0.1, rank,
        interpret=pltpu.InterpretParams(dma_execution_mode=order)))
    assert np.isfinite(got).all()
    assert np.array_equal(got, plain)
    np.testing.assert_allclose(got[lens > 0], ref[lens > 0], atol=2e-5,
                               rtol=2e-5)


def test_a_models_latent_layers_share_one_traced_call(interpreted,
                                                      monkeypatch):
    """``_latent_call`` traces the kernel once a set of shapes: three layers
    of a model (three pools of one shape) inside one jit call one cached
    call; another scale, a mask, another chunk span (a test's shrunk
    ``_CHUNK_BYTES``) or a swapped kernel is another key; and the cached
    call's output is the gather's."""
    heads, width, rank, block_size, maxb = 12, 256, 128, 16, 12
    q, pool, tables, lens = _latent_case([40, 0, 97, 160], heads, width,
                                         block_size, maxb, jnp.float32, 5)
    pa._latent_call.cache_clear()
    pools = [pool, pool * 0.5, pool * 2.0]

    @jax.jit
    def three(q, pools, tables, lens):
        return [pa._latent_pallas(q, p, tables, lens, 0.1, rank)
                for p in pools]

    got = three(q, pools, tables, lens)
    info = pa._latent_call.cache_info()
    assert (info.misses, info.hits) == (1, 2)
    for out, p in zip(got, pools):
        ref = pa.latent_attention_reference(q, p, tables, lens, 0.1, rank)
        np.testing.assert_allclose(np.asarray(out)[lens > 0],
                                   np.asarray(ref)[lens > 0], atol=2e-5,
                                   rtol=2e-5)
    pa._latent_pallas(q, pool, tables, lens, 0.2, rank)
    assert pa._latent_call.cache_info().misses == 2
    monkeypatch.setattr(pa, "_CHUNK_BYTES", 64 * width * 4)
    pa._latent_pallas(q, pool, tables, lens, 0.2, rank)
    assert pa._latent_call.cache_info().misses == 3
    kernel = pa._latent_kernel
    monkeypatch.setattr(pa, "_latent_kernel",
                        lambda *a, **kw: kernel(*a, **kw))
    again = pa._latent_pallas(q, pool, tables, lens, 0.2, rank)
    assert pa._latent_call.cache_info().misses == 4
    np.testing.assert_allclose(
        np.asarray(again)[lens > 0], np.asarray(pa.latent_attention_reference(
            q, pool, tables, lens, 0.2, rank))[lens > 0], atol=2e-5,
        rtol=2e-5)
    pa._latent_call.cache_clear()
