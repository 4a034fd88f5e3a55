"""The paged-attention kernel (pallas_kernels/paged_attention.py) on the CPU
tier, through the Pallas interpreter (``PADDLE_PALLAS_INTERPRET=1``): its
output against the gather path on folded pools, the rule that picks the
path from shapes and backend, and the decode steps built on it against the
unpaged reference loop.  What the chip's compiler makes of it at the real
widths is in tests/test_tpu_compile.py; times are the chip's alone."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as tr
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

# the two pools the serving cells keep, at two heads: GPT-2's (f32, head_dim
# 64, two heads to a lane tile) and OLMoE's (bf16, head_dim 128)
POOLS = {"f32_d64": (jnp.float32, 64, 8, 2e-6),
         "bf16_d128": (jnp.bfloat16, 128, 16, 8e-3)}
HEADS, MAXB = 2, 20          # 20 blocks a lane: two chunks of 128 at 8 a block


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


def _contexts(block_size):
    full = MAXB * block_size
    return {"idle": [0, 5], "one": [1, 0],
            "a_block": [block_size, block_size],
            "a_block_and_one": [block_size + 1, 3],
            "ragged": [0, 1, block_size, 3 * block_size + 5, full - 7, 77,
                       full],
            "full_table": [full, full - 1]}


def _pools(kind, lens, seed=0, maxb=MAXB):
    """Random folded pools and a table: each lane's blocks its own, but the
    first block of lanes 0 and 1 shared (a prefix-cache hit) wherever both
    have one; every unused slot is -1."""
    dtype, head_dim, block_size, _tol = POOLS[kind]
    rng = np.random.RandomState(seed)
    blocks = 1 + maxb * len(lens)
    width = HEADS * head_dim
    q = rng.randn(len(lens), HEADS, head_dim).astype(np.float32)
    k, v = (jnp.asarray(rng.randn(blocks, block_size, width)
                        .astype(np.float32)).astype(dtype) for _ in "kv")
    tables = np.full((len(lens), maxb), -1, np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for i, n in enumerate(lens):
        for j in range(-(-n // block_size)):
            tables[i, j] = free.pop()
    if lens[0] and lens[1]:
        tables[1, 0] = tables[0, 0]
    return q, k, v, tables, np.asarray(lens, np.int32)


@pytest.mark.parametrize("context", sorted(_contexts(8)))
@pytest.mark.parametrize("kind", sorted(POOLS))
def test_kernel_matches_the_gather_path(interpreted, kind, context):
    """Live lanes agree with ``paged_attention_reference`` to the pool
    dtype's rounding; an idle lane returns zeros (the gather path returns
    the mean of whatever block 0 holds: nothing reads either)."""
    lens = _contexts(POOLS[kind][2])[context]
    args = _pools(kind, lens)
    out = np.asarray(pa.paged_attention(*args))
    ref = np.asarray(pa.paged_attention_reference(*args))
    live = np.asarray(lens) > 0
    assert np.isfinite(out).all()
    assert np.abs(out[live] - ref[live]).max() <= POOLS[kind][3]
    assert not out[~live].any()
    assert adoption.active_kernels() == ["paged_attention"]
    assert _tm.counter_total("pallas_kernel_used_total") == 1
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0


def _borders(block_size, span):
    """Contexts at a chunk's borders, and the table they need: nothing, one
    position, a block, a chunk less one, a chunk, a chunk and one, two
    chunks and a block and three, the table's full length."""
    full = 2 * span + 4 * block_size
    return [0, 1, block_size, span - 1, span, span + 1,
            2 * span + block_size + 3, full], full // block_size


def _unnamed_are_nan(pool, tables):
    """``pool`` with NaN in every block no lane's table names (block 0 and
    the free ones): a kernel that fetched one would return it."""
    free = np.ones(pool.shape[0], bool)
    free[np.unique(tables[tables >= 0])] = False
    return jnp.where(jnp.asarray(free)[:, None, None], jnp.nan, pool)


@pytest.mark.parametrize("span", [128, 256])
@pytest.mark.parametrize("kind", sorted(POOLS))
def test_a_chunk_fetches_the_blocks_a_lane_holds_and_no_others(
        interpreted, monkeypatch, kind, span):
    """A position costs 1,024 B in either pool pair here, so ``_CHUNK_BYTES``
    of ``span`` KiB makes a chunk ``span`` positions.  Every block no table
    names is NaN: the outputs are finite and the clean pools' gather, at
    each border of a chunk; an idle lane returns zeros."""
    monkeypatch.setattr(pa, "_CHUNK_BYTES", span * 1024)
    dtype, head_dim, block_size, tol = POOLS[kind]
    lens, maxb = _borders(block_size, span)
    q, k, v, tables, lens = _pools(kind, lens, maxb=maxb)
    assert pa.chunk_positions(q.shape, k.shape, dtype, maxb) == span
    ref = np.asarray(pa.paged_attention_reference(q, k, v, tables, lens))
    out = np.asarray(pa.paged_attention(
        q, _unnamed_are_nan(k, tables), _unnamed_are_nan(v, tables), tables,
        lens))
    assert adoption.active_kernels() == ["paged_attention"]
    assert np.isfinite(out).all()
    assert np.abs(out[1:] - ref[1:]).max() <= tol
    assert not out[0].any()


def test_f32_products_are_f32(interpreted):
    """Against an f32 pool the kernel sits orders below a path whose
    products are rounded to bfloat16: the three-piece split keeps what
    ``Precision.HIGHEST`` keeps."""
    q, k, v, tables, lens = _pools("f32_d64", [150, 160, 9])
    ref = np.asarray(pa.paged_attention_reference(q, k, v, tables, lens))
    out = np.asarray(pa.paged_attention(q, k, v, tables, lens))
    coarse = np.asarray(pa.paged_attention_reference(
        q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), tables, lens))
    assert np.abs(coarse - ref).max() > 100 * np.abs(out - ref).max()


RULE = {
    # name: (q shape, pool shape, pool dtype, where, expected)
    "f32_folded": ((4, 16, 64), (64, 16, 1024), "float32", None, "ok"),
    "bf16_folded": ((4, 16, 128), (64, 16, 2048), "bfloat16", None, "ok"),
    "rank_4": ((4, 16, 64), (64, 16, 16, 64), "float32", None, "rank"),
    "int8": ((4, 16, 64), (64, 32, 1024), "int8", None, "dtype"),
    "width_192": ((4, 3, 64), (64, 16, 192), "float32", None, "lanes"),
    "bf16_block_of_8": ((4, 16, 128), (64, 8, 2048), "bfloat16", None,
                        "block_size"),
    "width_8192": ((4, 64, 128), (64, 16, 8192), "bfloat16", None, "vmem"),
    "symbolic": ((None, 16, 64), (64, 16, 1024), "float32", None,
                 "symbolic_shape"),
    "gspmd_mesh": ((4, 16, 64), (64, 16, 1024), "float32", "mesh",
                   "gspmd_mesh"),
    "off_tpu": ((4, 16, 64), (64, 16, 1024), "float32", "cpu", "backend"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_shape_rule(interpreted, monkeypatch, case):
    """The kernel engages for the folded f32 and bf16 pools and declines,
    under the right reason, everything else; ``attention_path`` says the
    same and counts nothing."""
    q_shape, kv_shape, dtype, where, expected = RULE[case]
    if where == "cpu":
        monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    checks = pa.paged_attention_checks(q_shape, kv_shape, dtype)
    path = pa.attention_path(q_shape, kv_shape, dtype)
    assert _tm.counter_total("pallas_kernel_used_total") == 0
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0
    if where == "mesh":
        with adoption.auto_partitioned():
            use, reason = adoption.decide("paged_attention", checks)
    else:
        use, reason = adoption.decide("paged_attention", checks)
        assert path == ("pallas" if use else "gather")
    assert (use, reason) == (expected == "ok", expected)
    counted = dict(_tm.label_sets("pallas_kernel_fallback_total"))
    if use:
        assert not counted
    else:
        assert list(counted.values()) == [
            {"kernel": "paged_attention", "reason": expected}]


CELLS = {
    # the eight serving cells' attention at 32 lanes and blocks of 16:
    # (q shape, pool shape, dtype, table slots, ring or latent rank,
    #  positions a chunk, bytes of VMEM)
    "gpt2": ((32, 16, 64), (1024, 16, 1024), "float32", 64, 0,
             128, 4 * 128 * 4 * 1024 + 2 * 32 * 4 * 1024),
    "olmoe": ((32, 16, 128), (2048, 16, 2048), "bfloat16", 128, 0,
              128, 4 * 128 * 2 * 2048 + 2 * 32 * 4 * 2048),
    "k_exaone_global": ((32, 64, 128), (12832, 16, 1024), "bfloat16", 512, 0,
                        128, 4 * 128 * 2 * 1024 + 2 * 32 * 4 * 64 * 128),
    "k_exaone_ring": ((32, 64, 128), (297, 16, 1024), "bfloat16", 9, 9,
                      144, 4 * 144 * 2 * 1024 + 2 * 32 * 4 * 64 * 128),
    "granite_lfm2": ((32, 32, 64), (2048, 16, 512), "bfloat16", 128, 0,
                     256, 4 * 256 * 2 * 512 + 2 * 32 * 4 * 32 * 512),
    "nemotron_h": ((32, 32, 128), (2048, 16, 256), "bfloat16", 128, 0,
                   512, 4 * 512 * 2 * 256 + 2 * 32 * 4 * 32 * 128),
    "kimi_latent": ((32, 32, 640), (12832, 16, 640), "bfloat16", 512, 512,
                    512, 2 * 512 * 2 * 640 + 32 * 4 * 32 * (640 + 512)),
    # 128 heads: 18.9e6 B of queries and outputs at 32 lanes, so the grid
    # walks the lanes and holds one lane's (and the next's) at a time
    "dots_latent": ((32, 128, 640), (12832, 16, 640), "bfloat16", 512, 512,
                    512, 2 * 512 * 2 * 640 + 2 * 4 * 128 * (640 + 512)),
    # 64 heads at LongCat-Flash's 64 lanes and at GLM-5's 32: the grid walks
    # the lanes there too (18.9e6 and 9.4e6 B of queries and outputs)
    "longcat_latent": ((64, 64, 640), (17472, 16, 640), "bfloat16", 272, 512,
                       512, 2 * 512 * 2 * 640 + 2 * 4 * 64 * (640 + 512)),
    "glm_latent": ((32, 64, 640), (25120, 16, 640), "bfloat16", 784, 512,
                   512, 2 * 512 * 2 * 640 + 2 * 4 * 64 * (640 + 512)),
    # a table shorter than the rule's chunk is one chunk
    "short_table": ((32, 32, 128), (2048, 16, 256), "bfloat16", 20, 0,
                    320, 4 * 512 * 2 * 256 + 2 * 32 * 4 * 32 * 128),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_chunk_spans_what_its_bytes_are_worth(cell):
    """The span follows the bytes a position costs in the pools fetched: 128
    positions where they hold ``_CHUNK_BYTES`` (512 KiB: K and V rows of
    4,096 B and over), the fewest steps of 128 that do otherwise, 512 at the
    most; a ring is its own chunk; and the ``vmem`` check counts the buffers
    of the span the call uses."""
    q, pool, dtype, maxb, extra, span, vmem = CELLS[cell]
    assert pa._CHUNK_BYTES == 512 << 10
    if cell.endswith("_latent"):
        fetched = jnp.dtype(dtype).itemsize * pool[2]
        held = pa._latent_held_bytes(q, pool, dtype, extra)
        assert pa._latent_lane_grid(q, pool, dtype, extra) \
            == (cell != "kimi_latent")
        assert pa.latent_chunk_positions(q, pool, dtype, extra, maxb) == span
        assert pa.latent_vmem_bytes(q, pool, dtype, extra) == vmem
    else:
        fetched = 2 * jnp.dtype(dtype).itemsize * pool[2]
        held = pa._held_bytes(q, pool)
        assert pa.chunk_positions(q, pool, dtype, maxb, ring=extra) == span
        assert pa.vmem_bytes(q, pool, dtype, ring=extra) == vmem
    if not extra or cell.endswith("_latent"):
        assert pa._chunk_blocks(16, maxb, fetched, held) == span // 16
    assert vmem <= pa._VMEM_BUDGET


def test_a_chunk_shrinks_to_the_vmem_left_and_no_further():
    """Queries and outputs that leave the buffers less room make the chunk
    shorter, by steps of 128 down to 128; under that the ``vmem`` check
    declines, as it did when every chunk was 128 positions: multi-head
    pools over 4096 wide in bfloat16, over 2048 in float32, at 32 lanes."""
    fetched = 1280                                  # Kimi's latent row
    room = lambda held: pa._chunk_positions(fetched, 16, held)
    assert room(0) == 512
    assert room(pa._VMEM_BUDGET - 2 * 512 * fetched) == 512
    assert room(pa._VMEM_BUDGET - 2 * 512 * fetched + 1) == 384
    assert room(pa._VMEM_BUDGET - 2 * 256 * fetched) == 256
    assert room(pa._VMEM_BUDGET) == 128
    for dtype, size, fits, not_ in (("bfloat16", 2, 4096, 8192),
                                    ("float32", 4, 2048, 4096)):
        for width in range(128, 8192 + 128, 128):
            q, pool = (32, width // 64, 64), (64, 16, width)
            ok = dict(pa.paged_attention_checks(q, pool, dtype))["vmem"]
            # what the check said of chunks of 128 positions
            assert ok == (4 * 128 * size * width + 2 * 32 * 4 * width
                          <= pa._VMEM_BUDGET), (dtype, width)
            assert ok or width > fits
            assert not ok or width < not_


def test_blocks_refetched_counts_what_the_latent_kernel_fetches_twice():
    """``blocks_refetched`` at the cells' chunk of 32 blocks of 16: every
    lane's last chunk is fetched whole, its last block again for the slots
    past it, and after every run of live lanes a whole chunk is fetched for
    nobody."""
    lens = np.array([0, 1, 512, 513, 1000, 0, 1024 + 130], np.int32)
    # (32 - 1) + 0 + (64 - 33) + (64 - 63) + (96 - 73), and two runs' ends
    assert pa.blocks_refetched(lens, 16, 80, 512) \
        == 31 + 0 + 31 + 1 + 23 + 2 * 32
    assert pa.blocks_refetched(np.zeros(3, np.int32), 16, 80, 512) == 0
    # ... and what it is beside: the blocks held are counted as before
    assert pa.blocks_read(lens, 16, 80, "pallas") == 1 + 32 + 33 + 63 + 73


def test_blocks_read_counts_live_blocks():
    lens = np.array([0, 1, 128, 129, 1000], np.int32)
    assert pa.blocks_read(lens, 16, 64, "gather") == 5 * 64
    # the blocks each lane holds, whatever the chunk: 0 + 1 + 8 + 9 + 63
    assert pa.blocks_read(lens, 16, 64, "pallas") == 81
    # a table shorter than a chunk: still the blocks held, two of four
    assert pa.blocks_read(np.array([5, 0], np.int32), 4, 12, "pallas") == 2
    # ... and never more than the table names
    assert pa.blocks_read(np.array([100], np.int32), 4, 12, "pallas") == 12


# -- the walk's two bodies ---------------------------------------------------

# queries over KV heads: a head its own K and V; 14 heads over 2 KV heads of
# 128 (compact: the kernel repeats a lane's query itself) and of 64 (spread)
LAYOUTS = {"group1": (2, 2, 64), "compact": (14, 2, 128),
           "spread": (14, 2, 64)}
DTYPES = {"f32": (jnp.float32, 8, 2e-5), "bf16": (jnp.bfloat16, 16, 2e-2)}
# chunks a lane, in two orders: between them a lane of 1, 2, 3, 4 and 5
# chunks starts after an even and after an odd count of chunks, behind an
# idle lane, behind a lane of one chunk and behind a longer one
ORDERS = {"even_first": [0, 1, 5, 0, 2, 4, 1, 3, 0, 3, 1, 1, 4, 2, 5],
          "odd_first": [1, 0, 2, 3, 5, 1, 4, 4, 0, 1, 1, 3, 2, 0, 5, 1]}


def _starts(chunks):
    """(chunks of a lane, parity of the chunks fetched before it)."""
    before = np.concatenate([[0], np.cumsum(chunks)[:-1]])
    return {(int(n), int(g) % 2) for n, g in zip(chunks, before)}


def _walk_case(layout, dtype, lens, maxb, seed=0):
    """Random pools, a query of ``layout`` and each lane's own blocks in the
    leading slots of a table of ``maxb`` (a ring names all its slots from
    the first wrap on)."""
    heads, kv_heads, dim = LAYOUTS[layout]
    dt, block_size, _tol = DTYPES[dtype]
    rng = np.random.RandomState(seed)
    blocks = 1 + maxb * len(lens)
    q = jnp.asarray(rng.randn(len(lens), heads, dim), jnp.float32)
    k, v = (jnp.asarray(rng.randn(blocks, block_size, kv_heads * dim)
                        .astype(np.float32)).astype(dt) for _ in "kv")
    tables = np.full((len(lens), maxb), -1, np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for i, n in enumerate(lens):
        for j in range(min(-(-n // block_size), maxb)):
            tables[i, j] = free.pop()
    lens = np.asarray(lens, np.int32)
    return q, k, v, tables, lens


def _small_chunks(monkeypatch):
    """Chunks of 32 positions: 4 blocks of 8 (two steps of 2) or 2 of 16."""
    monkeypatch.setattr(pa, "CHUNK_TOKENS", 16)
    monkeypatch.setattr(pa, "_MAX_CHUNK_TOKENS", 32)
    return 32


def test_the_orders_cross_every_start():
    """What the two orders are for: every length in either buffer, and a
    lane with a straight chunk behind an idle lane, a lane of one chunk and
    a longer one."""
    assert _starts(ORDERS["even_first"]) | _starts(ORDERS["odd_first"]) \
        >= {(n, parity) for n in range(1, 6) for parity in (0, 1)}
    before = {min(a, 2) for order in ORDERS.values()
              for a, n in zip(order[:-1], order[1:]) if n >= 3}
    assert before == {0, 1, 2}


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_straight_body_meets_the_guarded_one_at_every_seam(
        interpreted, monkeypatch, layout, dtype, order):
    """Lanes of 1 to 5 chunks (all but a lane's last two run the
    straight-line body: 0, 0, 1, 2 and 3 of them), each started in either
    buffer, behind an idle lane, a lane of one chunk and a longer one; a
    lane's last chunk one position, a block less one, a block and one, or
    full.  Every block no table names is NaN: the outputs are finite and
    the clean pools' gather, and the count is the rule's."""
    span = _small_chunks(monkeypatch)
    dt, block_size, tol = DTYPES[dtype]
    ends = [1, block_size - 1, block_size + 1, span]
    lens = [(n - 1) * span + ends[i % 4] if n else 0
            for i, n in enumerate(ORDERS[order])]
    maxb = 5 * span // block_size
    q, k, v, tables, lens = _walk_case(layout, dtype, lens, maxb)
    assert pa.chunk_positions(q.shape, k.shape, dt, maxb) == span
    ref = np.asarray(pa.paged_attention_reference(q, k, v, tables, lens))
    out = np.asarray(pa.paged_attention(
        q, _unnamed_are_nan(k, tables), _unnamed_are_nan(v, tables), tables,
        lens))
    assert adoption.active_kernels() == ["paged_attention"]
    live = lens > 0
    assert np.isfinite(out).all()
    assert np.abs(out[live] - ref[live]).max() <= tol
    assert not out[~live].any()
    chunks = np.asarray(ORDERS[order])
    assert pa.chunks_read(lens, block_size, maxb, span)[0] == chunks.sum()
    assert pa.straight_chunks_read(lens, block_size, maxb, span) \
        == np.maximum(chunks - 2, 0).sum()


@pytest.mark.parametrize("fill", ["part", "full", "wrapped"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("layout", ["compact", "group1"])
def test_a_ring_walked_in_chunks_goes_through_both_bodies(
        interpreted, monkeypatch, layout, dtype, fill):
    """A ring of 16 whole chunks and a block (SmallThinker's 257 blocks, in
    small: 33 slots of 16 positions or 65 of 8 against chunks of 32), part
    filled (7 chunks, the last a block), exactly full and wrapped (17
    chunks, 15 of them straight), in either buffer: behind an idle lane and
    behind a lane of one chunk.  Against the gather under the same mask."""
    span = _small_chunks(monkeypatch)
    dt, block_size, tol = DTYPES[dtype]
    ring = 16 * span // block_size + 1
    ring_len, window = ring * block_size, 16 * span
    ctx = {"part": 6 * span + 3, "full": ring_len,
           "wrapped": 2 * ring_len + span + 5}[fill]
    lens = [0, ctx, 5, ctx + 1 if fill != "full" else ctx]
    q, k, v, tables, lens = _walk_case(layout, dtype, lens, ring)
    assert not pa._ring_whole(ring, block_size)
    assert pa.chunk_positions(q.shape, k.shape, dt, 99, ring) == span
    ref = np.asarray(pa.paged_attention_reference(q, k, v, tables, lens,
                                                  window=window))
    out = np.asarray(pa.paged_attention(
        q, _unnamed_are_nan(k, tables), _unnamed_are_nan(v, tables), tables,
        lens, window=window))
    assert adoption.active_kernels() == ["paged_attention"]
    assert np.isfinite(out).all() and not out[0].any()
    assert np.abs(out[1:] - ref[1:]).max() <= tol
    walked = 7 if fill == "part" else 17
    assert pa.chunks_read(lens, block_size, ring, span)[0] == 2 * walked + 1
    assert pa.straight_chunks_read(lens, block_size, ring, span) \
        == 2 * (walked - 2)


@pytest.mark.parametrize("order", ["eager", "on_wait"])
def test_the_walk_keeps_its_turns(interpreted, monkeypatch, order):
    """Both bodies under the TPU interpreter's two models of an async copy,
    done as it is started and only when it is waited for: a chunk waits for
    what it reads and starts nothing into a buffer it has yet to read.  Bit
    for bit the walk with every chunk guarded (the rule told that no chunk
    is straight), so the bodies differ in the order of issue alone."""
    from jax.experimental.pallas import tpu as pltpu

    span = _small_chunks(monkeypatch)
    lens = [(n - 1) * span + 9 if n else 0 for n in ORDERS["odd_first"]]
    q, k, v, tables, lens = _walk_case("compact", "f32", lens, 20)
    k, v = _unnamed_are_nan(k, tables), _unnamed_are_nan(v, tables)
    walk = lambda **kw: np.asarray(pa._paged_pallas(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens), **kw))
    got = walk(interpret=pltpu.InterpretParams(dma_execution_mode=order))
    assert np.isfinite(got).all()
    assert np.array_equal(got, walk())
    monkeypatch.setattr(pa, "_straight", lambda chunks: chunks * 0)
    assert np.array_equal(got, walk())


def test_straight_chunks_read_counts_all_but_a_lanes_last_two():
    """``straight_chunks_read`` against a hand count, at the shapes the
    cells hold: SmallThinker's ring of 257 blocks in chunks of 256 positions
    (a wrapped lane walks 17 chunks, 15 of them straight), its global table
    of 1,024 slots, GPT-2's lanes of 3-6 chunks of 128, and K-EXAONE's ring
    of one chunk."""
    count = lambda lens, maxb, span: pa.straight_chunks_read(
        np.asarray(lens, np.int32), 16, maxb, span)
    # chunks 0, 1, 2 (19 blocks), 16 (256 blocks), 17, 17, 17
    ring = [0, 16, 300, 4096, 4112, 4113, 12544]
    assert pa.chunks_read(np.asarray(ring, np.int32), 16, 257, 256)[0] \
        == 1 + 2 + 16 + 3 * 17
    assert count(ring, 257, 256) == 0 + 0 + 0 + 14 + 3 * 15
    # chunks 4, 36, 3, 2, 1: a lane of one or two chunks has none
    assert count([1000, 9000, 513, 512, 1], 1024, 256) == 2 + 34 + 1
    # GPT-2: chunks 3, 4, 5, 6, and a lane the table cuts at 8
    assert count([300, 512, 513, 760, 5000], 64, 128) == 1 + 2 + 3 + 4 + 6
    # a ring that is one chunk, and the gather's padded table
    assert count([50, 400, 0], 9, 144) == 0
    assert count([50, 400, 9000], 1024, 1024 * 16) == 0
    assert count([], 64, 128) == 0


# -- the decode steps on the kernel ------------------------------------------

CFG = dm.DecoderConfig(vocab=37, layers=2, heads=2, head_dim=64, max_seq=48)
PARAMS = dm.init_decoder_params(CFG, seed=3)
BS = 8


def _drive(step, kv, prompts, max_new, width=1):
    """Feed each lane its prompt then its own argmax, ``width`` positions a
    call (``width`` 1 is ``make_paged_step``); the last lane stays idle.
    -> per lane (tokens, logits of each generated token)."""
    cache = PagedKVCache(kv)
    lanes, maxb = len(prompts) + 1, CFG.max_seq // BS
    tables = np.full((lanes, maxb), -1, np.int32)
    for i in range(len(prompts)):
        tables[i] = 1 + i * maxb + np.arange(maxb)
    seqs = [list(p) for p in prompts]
    fed = [0] * len(prompts)
    logits = [[] for _ in prompts]
    params = {k: jnp.asarray(v) for k, v in PARAMS.items()}
    fn = jax.jit(step, donate_argnums=(0,))
    total = [len(p) + max_new for p in prompts]
    while any(len(s) < t for s, t in zip(seqs, total)):
        tok = np.zeros((lanes, width), np.int32)
        pos = np.zeros((lanes, width), np.int32)
        lens = np.zeros((lanes, width), np.int32)
        for i, s in enumerate(seqs):
            # this call feeds the known tokens from fed[i] on, at most one
            # past the prompt (what follows has to be generated first)
            n = min(width, len(s) - fed[i])
            for j in range(width):
                real = min(j, n - 1)
                tok[i, j] = s[fed[i] + real]
                pos[i, j] = fed[i] + real
                lens[i, j] = fed[i] + real + 1
        feeds = (tok, pos, tables, lens) if width > 1 \
            else (tok[:, 0], pos[:, 0], tables, lens[:, 0])
        carry, nxt, lg = fn(cache.carry(), params, *feeds)[:3]
        cache.replace_carry(carry)
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        for i, s in enumerate(seqs):
            n = min(width, len(s) - fed[i])
            fed[i] += n
            if fed[i] == len(s) and len(s) < total[i]:
                last = (nxt[i, n - 1], lg[i, n - 1]) if width > 1 \
                    else (nxt[i], lg[i])
                s.append(int(last[0]))
                logits[i].append(last[1])
    return [(s[len(p):], lg) for s, p, lg in zip(seqs, prompts, logits)]


@pytest.mark.parametrize("width", [1, 2], ids=["step", "multi"])
def test_paged_steps_on_the_kernel_match_unpaged(interpreted, width):
    """``make_paged_step`` and ``make_paged_step_multi`` with the kernel
    serving every layer: the tokens of ``unpaged_generate``, logits to
    1e-5 (the softmax's sums run in another order, nothing else differs)."""
    kv = KVCacheConfig(CFG.layers, CFG.heads, CFG.head_dim, BS, 16, "f32")
    assert dm.attention_path(CFG, kv) == "pallas"
    step = dm.make_paged_step(CFG, kv) if width == 1 \
        else dm.make_paged_step_multi(CFG, kv, width)
    prompts = [[5, 6, 7, 8, 9, 10, 11, 12, 13], [3]]
    got = _drive(step, kv, prompts, 6, width)
    assert _tm.counter_total("pallas_kernel_used_total") \
        == CFG.layers * width
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0
    for prompt, (tokens, logits) in zip(prompts, got):
        want, want_logits = dm.unpaged_generate(
            CFG, PARAMS, prompt, 6, pad_len=CFG.max_seq, return_logits=True)
        assert tokens == want
        assert np.abs(np.asarray(logits) - np.asarray(want_logits)).max() \
            < 1e-5


def test_engine_names_the_path_and_counts_the_blocks(interpreted, tmp_path):
    """An engine on the kernel: the prewarm event names the attention path,
    each step's span carries the blocks read beside the table's slots, and
    the tokens are the unpaged loop's."""
    d = str(tmp_path / "tel")
    old = fluid.get_flags(["FLAGS_kv_block_size", "FLAGS_kv_cache_dtype",
                           "FLAGS_compile_cache_dir", "FLAGS_tracing",
                           "FLAGS_telemetry_dir"])
    fluid.set_flags({"FLAGS_kv_block_size": BS, "FLAGS_kv_cache_dtype": "f32",
                     "FLAGS_compile_cache_dir": str(tmp_path / "cc"),
                     "FLAGS_tracing": True, "FLAGS_telemetry_dir": d})
    tr.reset()
    try:
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=16)
        e.prewarm()
        e.start()
        try:
            prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13]
            r = e.generate("toy", prompt, max_new_tokens=4,
                           deadline_ms=60000.0)
        finally:
            e.stop()
        assert r.status == "ok", r.error
        assert list(r.outputs["tokens"]) == dm.unpaged_generate(
            CFG, PARAMS, prompt, 4, pad_len=CFG.max_seq)
        _tm.flush()
        with open(os.path.join(d, "steps.jsonl")) as fp:
            events = [json.loads(line) for line in fp]
        warm = [ev for ev in events if ev["ev"] == "serving_prewarm"]
        assert warm and all(ev["attention"] == "pallas" for ev in warm)
        attrs = [s["attrs"] for s in tr.records("serving.decode_step")]
        maxb = CFG.max_seq // BS
        assert attrs and all(a["kv_table_slots"] == 2 * maxb for a in attrs)
        # one lane of 1..12 positions and an idle one: the one or two
        # blocks the lane holds, of a table of six
        assert {a["kv_blocks_read"] for a in attrs} == {1, 2}
        # ... in the one chunk the table is, which is no straight one
        assert {(a["kv_chunks"], a["kv_straight_chunks"])
                for a in attrs} == {(1, 0)}
        # a model with no latent layer counts no latent chunks
        assert not any(key.startswith("latent_") for a in attrs for key in a)
        # the chunk's span by kind of layer, beside the path's name
        assert all(ev["chunk_positions"] == {"attention": CFG.max_seq}
                   for ev in warm)
    finally:
        fluid.set_flags(old)
        tr.reset()


def test_engine_counts_the_chunks_a_latent_kernel_walks(interpreted,
                                                        monkeypatch,
                                                        tmp_path):
    """An engine whose one layer is latent, on the kernel (a row of 256
    float32 and ``_CHUNK_BYTES`` of 128 rows make a chunk 128 positions):
    each step's span carries the chunks the kernel walks and how many of
    them are full beside the blocks read, as the lane's context passes one
    chunk, and what the kernel fetched beyond the blocks held (the rest of
    the lane's last chunk and a chunk for nobody); the tokens are the jnp
    step's."""
    from paddle_tpu.models import dots_vlm

    monkeypatch.setattr(pa, "_CHUNK_BYTES", 128 * 256 * 4)
    cfg = dm.DecoderConfig(
        arch="dots_vlm", vocab=61, layers=1, heads=4, head_dim=128,
        hidden_size=128, max_seq=320, layer_types=("latent",),
        latent_rank=128, latent_rope=32, q_rank=64, dense_layers=1,
        dense_ffn=64, norm_eps=1e-6,
        # the family routes; its one layer here is the dense lead
        ffn=128, shared_ffn=64, experts=16, experts_per_token=3, n_group=4,
        topk_group=2, routed_scaling=2.5)
    params = dots_vlm.init_params(cfg, seed=5, std=0.1)
    d = str(tmp_path / "tel")
    old = fluid.get_flags(["FLAGS_kv_block_size", "FLAGS_kv_cache_dtype",
                           "FLAGS_compile_cache_dir", "FLAGS_tracing",
                           "FLAGS_telemetry_dir"])
    fluid.set_flags({"FLAGS_kv_block_size": 16, "FLAGS_kv_cache_dtype": "f32",
                     "FLAGS_compile_cache_dir": str(tmp_path / "cc"),
                     "FLAGS_tracing": True, "FLAGS_telemetry_dir": d})
    tr.reset()
    try:
        e = DecodeEngine(buckets="2", deadline_ms=120000.0)
        e.add_model("toy", (cfg, params), kv_blocks=24)
        e.prewarm()
        e.start()
        try:
            prompt = list(np.random.RandomState(2).randint(0, 61, 120))
            r = e.generate("toy", prompt, max_new_tokens=12,
                           deadline_ms=120000.0)
        finally:
            e.stop()
        assert r.status == "ok", r.error
        _tm.flush()
        with open(os.path.join(d, "steps.jsonl")) as fp:
            warm = [ev for ev in map(json.loads, fp)
                    if ev["ev"] == "serving_prewarm"]
        assert warm and all(ev["latent_attention"] == "pallas"
                            and ev["chunk_positions"] == {"latent": 128}
                            for ev in warm)
        attrs = [s["attrs"] for s in tr.records("serving.decode_step")]
        assert len(attrs) >= 131
        # one lane at contexts of 1..131: blocks of 16, chunks of 128
        for a in attrs:
            ctx = -(-a["latent_blocks_read"] // 8) * 128  # its chunks' end
            assert a["latent_blocks_read"] == a["kv_blocks_read"]
            assert a["latent_chunks"] == ctx // 128
            assert a["latent_chunks"] - a["latent_full_chunks"] in (0, 1)
            # the last chunk's slots past the lane's blocks, and a chunk of
            # 8 blocks once more for nobody
            assert a["latent_blocks_refetched"] \
                == 8 * a["latent_chunks"] - a["latent_blocks_read"] + 8
        assert {(a["latent_chunks"], a["latent_full_chunks"])
                for a in attrs} == {(1, 0), (1, 1), (2, 1)}
    finally:
        fluid.set_flags(old)
        tr.reset()


def test_kv_blocks_read_share_reader():
    """The benchmark's reader of the two span attributes: the median share
    over the steps that carry them; nothing from a program that records
    none (the parent), and nothing from a training run."""
    from benchmark.run import load_module

    reader = load_module("layer_metrics", "kv_blocks_read_share.serve")
    spans = [{"attrs": {"kv_blocks_read": n, "kv_table_slots": 2048}}
             for n in (512, 640, 768)] + [{"attrs": {"lanes": 3}}]
    assert reader.read({"kind": "serve", "decode_spans": spans}) \
        == pytest.approx(31.25)
    assert reader.read({"kind": "serve",
                        "decode_spans": [{"attrs": {"lanes": 3}}]}) is None
    assert reader.read({"kind": "serve", "decode_spans": None}) is None
    assert reader.read({"kind": "train", "decode_spans": spans}) is None


def test_latent_full_chunk_share_reader():
    """The benchmark's reader of ``latent_chunks`` / ``latent_full_chunks``:
    the median share over the steps that carry them; nothing from spans
    without them (the parent, the gather path, a model with no latent
    layer) and nothing from a training run."""
    from benchmark.run import load_module

    reader = load_module("layer_metrics", "latent_full_chunk_share.serve")
    spans = [{"attrs": {"latent_chunks": 128, "latent_full_chunks": n}}
             for n in (96, 100, 64)] + [{"attrs": {"lanes": 3}}]
    assert reader.read({"kind": "serve", "decode_spans": spans}) \
        == pytest.approx(75.0)
    # a step of idle lanes walks no chunk, and says nothing
    idle = [{"attrs": {"latent_chunks": 0, "latent_full_chunks": 0}}]
    assert reader.read({"kind": "serve", "decode_spans": idle}) is None
    assert reader.read({"kind": "serve", "decode_spans": [
        {"attrs": {"kv_blocks_read": 5, "latent_blocks_read": 5}}]}) is None
    assert reader.read({"kind": "serve", "decode_spans": None}) is None
    assert reader.read({"kind": "train", "decode_spans": spans}) is None


def test_chunks_read_counts_the_full_chunks():
    """``chunks_read``: a lane's chunks cover the blocks it holds, and a
    chunk is full by positions (a lane at 1,020 holds every block of two
    chunks of 512 and sees 508 positions of the second), at the two latent
    cells' table of 512 slots and chunk of 512 positions."""
    count = lambda *lens: pa.chunks_read(np.array(lens, np.int32), 16, 512,
                                         512)
    assert count(0) == (0, 0)
    assert count(1) == count(511) == (1, 0)
    assert count(512) == (1, 1)
    assert count(513) == (2, 1)
    assert count(1020) == (2, 1)
    assert count(1024) == (2, 2)
    assert count(8192) == count(9000) == (16, 16)
    # the cells' windows: contexts of 1,800-2,050 and of 1,100-2,300
    assert count(1800, 2048, 2050) == (4 + 4 + 5, 3 + 4 + 4)
    assert count(1100, 2300, 0) == (3 + 5, 2 + 4)
    # a chunk of a whole short table
    assert pa.chunks_read(np.array([40, 64], np.int32), 16, 4, 64) == (2, 1)
