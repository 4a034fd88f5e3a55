"""Paged KV-cache pool (serving/kv_cache.py): block-allocator units
(all-or-nothing OOM, LIFO reuse, loud double-free, high-water),
refcounted sharing + the sealed/evictable LRU pool behind prefix
caching, the content-addressed PrefixCache index (hash-chain match,
first-publisher-wins publish, eviction de-indexing), budget-gated
sizing via FLAGS_hbm_budget_bytes / FLAGS_kv_cache_blocks, int8
residency quantization round-trips, and the MEM001 fold of
engine-owned KV bytes into the static per-replica peak estimate."""

import contextlib
import gc

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import world_analysis
from paddle_tpu.serving import kv_cache
from paddle_tpu.serving.kv_cache import (BlockAllocator, KVCacheConfig,
                                         PagedKVCache, PrefixCache,
                                         block_bytes, dequantize_kv,
                                         engine_owned_kv_bytes,
                                         plan_num_blocks, quantize_kv)


@contextlib.contextmanager
def _flags(**kv):
    kv = {"FLAGS_" + k: v for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


def _cfg(**kw):
    base = dict(layers=2, heads=2, head_dim=8, block_size=4, num_blocks=8)
    base.update(kw)
    return KVCacheConfig(**base)


# -- BlockAllocator ----------------------------------------------------------


def test_alloc_free_roundtrip():
    a = BlockAllocator(8, reserve=1)
    assert a.capacity == 7 and a.num_free == 7 and a.in_use == 0
    got = a.alloc(3)
    assert len(got) == 3 and a.in_use == 3 and a.num_free == 4
    # the reserved block never circulates
    assert 0 not in got
    a.free(got)
    assert a.in_use == 0 and a.num_free == 7


def test_alloc_is_all_or_nothing_on_oom():
    a = BlockAllocator(4, reserve=1)
    assert a.alloc(3) is not None
    before = a.stats()
    assert a.alloc(2) is None          # only 0 free: takes NOTHING
    assert a.stats() == before
    assert a.alloc(0) == []


def test_lifo_reuse_locality():
    a = BlockAllocator(8, reserve=1)
    first = a.alloc(2)
    a.free(first)
    again = a.alloc(2)
    # most recently freed block is handed out first
    assert again[0] == first[-1]


def test_double_free_and_foreign_free_raise():
    a = BlockAllocator(4, reserve=1)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got)
    with pytest.raises(ValueError):
        a.free([99])


def test_high_water_tracks_peak_not_current():
    a = BlockAllocator(8, reserve=1)
    g1 = a.alloc(5)
    a.free(g1)
    a.alloc(2)
    assert a.stats()["high_water"] == 5


def test_reserve_validation():
    with pytest.raises(ValueError):
        BlockAllocator(2, reserve=2)


def test_oom_increments_counter():
    fluid.set_flags({"FLAGS_telemetry": True})
    _tm.reset()
    try:
        a = BlockAllocator(3, reserve=1)
        assert a.alloc(5) is None
        assert _tm.counter_total("kv_block_oom_total") == 1
    finally:
        _tm.reset()
        fluid.set_flags({"FLAGS_telemetry": False})


# -- refcounted sharing + the sealed/evictable pool --------------------------


def test_incref_shares_and_free_decrefs():
    a = BlockAllocator(8, reserve=1)
    (b,) = a.alloc(1)
    assert a.refcount(b) == 1
    assert a.incref(b)
    assert a.refcount(b) == 2
    a.free([b])                    # one owner down: block stays in use
    assert a.refcount(b) == 1 and a.in_use == 1 and a.num_free == 6
    a.free([b])                    # last owner: back to the free list
    assert a.refcount(b) == 0 and a.in_use == 0 and a.num_free == 7


def test_sealed_block_parks_evictable_and_revives():
    a = BlockAllocator(8, reserve=1)
    (b,) = a.alloc(1)
    a.seal(b, "tag-b")
    a.free([b])
    # zero-ref but sealed: parked, NOT on the free list
    assert a.in_use == 0 and a.num_evictable == 1 and a.num_free == 6
    assert a.reclaimable == 7
    # revival takes a fresh reference and keeps the seal
    assert a.incref(b)
    assert a.refcount(b) == 1 and a.num_evictable == 0
    a.free([b])
    assert a.num_evictable == 1    # re-parks at zero refs


def test_incref_of_free_or_unknown_block_is_refused():
    a = BlockAllocator(8, reserve=1)
    (b,) = a.alloc(1)
    a.free([b])                    # unsealed: returned to the free list
    assert not a.incref(b)
    assert not a.incref(99)


def test_unsealed_free_keeps_lifo_reuse():
    a = BlockAllocator(8, reserve=1)
    first = a.alloc(2)
    a.free(first)
    assert a.alloc(2)[0] == first[-1]


def test_alloc_reclaims_evictable_lru_first_and_fires_callback():
    a = BlockAllocator(5, reserve=1)   # capacity 4
    evicted = []
    a.on_evict = lambda b, tag: evicted.append((b, tag))
    got = a.alloc(4)
    for i, b in enumerate(got):
        a.seal(b, "t%d" % i)
    a.free(got)                        # all parked, free list empty
    assert a.num_free == 0 and a.num_evictable == 4
    # free list is preferred... there is none, so the LRU victim is the
    # longest-parked block, and the index learns it is gone
    take = a.alloc(2)
    assert take == [got[0], got[1]]    # park order == free order (LRU)
    assert evicted == [(got[0], "t0"), (got[1], "t1")]
    # untouched parked blocks remain revivable
    assert a.incref(got[2])


def test_alloc_all_or_nothing_spans_eviction_reclaim():
    a = BlockAllocator(5, reserve=1)   # capacity 4
    evicted = []
    a.on_evict = lambda b, tag: evicted.append(b)
    keep = a.alloc(2)
    (sealed,) = a.alloc(1)
    a.seal(sealed, "s")
    a.free([sealed])
    assert a.num_free == 1 and a.num_evictable == 1
    # need 3, reclaimable only 2: takes NOTHING — the evictable block
    # survives and no eviction callback fires
    before = a.stats()
    assert a.alloc(3) is None
    assert a.stats() == before and evicted == []
    # need 2 spans free list + eviction reclaim in ONE all-or-nothing
    got = a.alloc(2)
    assert len(got) == 2 and sealed in got and evicted == [sealed]
    a.free(got + keep)


def test_double_free_still_loud_with_refcounts():
    a = BlockAllocator(8, reserve=1)
    (b,) = a.alloc(1)
    a.seal(b, "t")
    a.free([b])
    # parked evictable is NOT owned: freeing it again must raise, not
    # silently double-park
    with pytest.raises(ValueError):
        a.free([b])
    (c,) = a.alloc(1)
    a.free([c])
    with pytest.raises(ValueError):
        a.free([c])


def test_stats_and_high_water_include_evictable():
    a = BlockAllocator(8, reserve=1)
    got = a.alloc(3)
    a.seal(got[0], "t0")
    a.free(got)
    st = a.stats()
    assert st["evictable"] == 1
    assert st["reclaimable"] == st["free"] + st["evictable"] == 7
    # evictable blocks still occupy pool slots: parking never lowers the
    # high-water mark, and occupied (in_use + evictable) peaks count
    a.alloc(4)
    assert a.stats()["high_water"] == 5    # 4 in use + 1 parked


# -- PrefixCache: hash-chain index over sealed blocks ------------------------


def test_hash_chain_commits_to_whole_prefix():
    a = BlockAllocator(8, reserve=1)
    pc = PrefixCache(a, block_size=4, namespace="m")
    base = pc.chain([1, 2, 3, 4, 5, 6, 7, 8])
    assert len(base) == 2                       # full blocks only
    assert len(pc.chain([1, 2, 3])) == 0        # no full block yet
    same_first = pc.chain([1, 2, 3, 4, 9, 9, 9, 9])
    assert same_first[0] == base[0] and same_first[1] != base[1]
    # a different namespace (model) never shares an index key space
    other = PrefixCache(BlockAllocator(8, reserve=1), 4, namespace="n")
    assert other.chain([1, 2, 3, 4])[0] != base[0]


def test_match_publish_roundtrip_with_revival():
    a = BlockAllocator(8, reserve=1)
    pc = PrefixCache(a, block_size=4, namespace="m")
    prompt = list(range(10))                    # 2 full blocks + tail
    blocks, cached, hashes = pc.match(prompt)
    assert (blocks, cached) == ([], 0) and len(hashes) == 2
    owned = a.alloc(3)
    assert pc.publish(owned[0], hashes[0])
    assert pc.publish(owned[1], hashes[1])
    assert len(pc) == 2
    a.free(owned)                               # published pair parks
    assert a.num_evictable == 2
    got, cached, _ = pc.match(prompt)
    assert got == owned[:2] and cached == 8
    assert a.refcount(owned[0]) == 1            # revived on our behalf
    a.free(got)


def test_match_caps_at_len_minus_one_tokens():
    a = BlockAllocator(8, reserve=1)
    pc = PrefixCache(a, block_size=4, namespace="m")
    prompt = list(range(8))                     # exactly 2 full blocks
    owned = a.alloc(2)
    h = pc.chain(prompt)
    pc.publish(owned[0], h[0])
    pc.publish(owned[1], h[1])
    # a full-prompt match would leave prefill NOTHING to feed — the
    # match must stop one block short so at least one tail token runs
    got, cached, _ = pc.match(prompt)
    assert got == [owned[0]] and cached == 4
    a.free(got)
    a.free(owned)


def test_publish_is_first_publisher_wins():
    a = BlockAllocator(8, reserve=1)
    pc = PrefixCache(a, block_size=4, namespace="m")
    h = pc.chain([5, 6, 7, 8])
    b1, b2 = a.alloc(2)
    assert pc.publish(b1, h[0])
    assert not pc.publish(b2, h[0])             # duplicate: stays private
    a.free([b1, b2])
    assert a.num_evictable == 1                 # only the winner parked
    assert a.num_free == 6


def test_eviction_deindexes_and_match_misses():
    a = BlockAllocator(4, reserve=1)            # capacity 3
    pc = PrefixCache(a, block_size=4, namespace="m")
    prompt = [1, 2, 3, 4, 9]
    h = pc.chain(prompt)
    (b,) = a.alloc(1)
    pc.publish(b, h[0])
    a.free([b])
    assert len(pc) == 1
    # pressure reclaims the parked block -> the index must forget it
    a.alloc(3)
    assert len(pc) == 0
    got, cached, _ = pc.match(prompt)
    assert got == [] and cached == 0


# -- sizing (plan_num_blocks) ------------------------------------------------


def test_block_bytes_int8_smaller_than_f32():
    f32 = block_bytes(_cfg())
    i8 = block_bytes(_cfg(dtype="int8"))
    # int8 payload + f32 per-(pos, head) scales: well under half of f32
    assert i8 < f32 / 2
    # exact: 2 sides * layers * block_size * (H*D payload + H scales)
    assert i8 == 2 * 2 * 4 * (2 * 8 * 1 + 2 * 4)
    assert f32 == 2 * 2 * 4 * (2 * 8 * 4)


def test_plan_respects_request_without_budget():
    n, capped = plan_num_blocks(_cfg(), requested=17, budget=0)
    assert (n, capped) == (17, False)


def test_plan_defaults_when_unpinned():
    with _flags(kv_cache_blocks=0, hbm_budget_bytes=0):
        n, capped = plan_num_blocks(_cfg())
    assert (n, capped) == (64, False)


def test_plan_budget_caps_request():
    cfg = _cfg()
    per = block_bytes(cfg)
    n, capped = plan_num_blocks(cfg, model_resident_bytes=per,
                                requested=100, budget=per * 11)
    assert n == 10 and capped


def test_plan_budget_autosizes_fit():
    cfg = _cfg()
    per = block_bytes(cfg)
    n, capped = plan_num_blocks(cfg, requested=0, budget=per * 6 + 1)
    assert n == 6 and not capped


def test_plan_raises_when_budget_cannot_hold_two_blocks():
    cfg = _cfg()
    with pytest.raises(ValueError) as ei:
        plan_num_blocks(cfg, model_resident_bytes=0, requested=8,
                        budget=block_bytes(cfg))
    assert "FLAGS_hbm_budget_bytes" in str(ei.value)


def test_plan_reads_flags():
    with _flags(kv_cache_blocks=9, hbm_budget_bytes=0):
        n, _ = plan_num_blocks(_cfg())
    assert n == 9


# -- int8 residency quantization ---------------------------------------------


def test_quantize_roundtrip_bounded_error():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 4, 2, 8).astype(np.float32)
    q, scale = quantize_kv(x)
    assert np.asarray(q).dtype == np.int8
    back = np.asarray(dequantize_kv(q, scale))
    # symmetric per-[..., H] max-abs: error bounded by half a quant step
    step = np.asarray(scale)[..., None]
    assert np.all(np.abs(back - x) <= step * 0.5 + 1e-7)


def test_quantize_all_zero_block_is_safe():
    q, scale = quantize_kv(np.zeros((2, 4, 2, 8), np.float32))
    assert not np.any(np.isnan(np.asarray(scale)))
    assert np.all(np.asarray(dequantize_kv(q, scale)) == 0.0)


# -- PagedKVCache ------------------------------------------------------------


def test_cache_reserves_scratch_block_and_carry_shapes():
    c = PagedKVCache(_cfg())
    assert c.allocator.reserve == 1 and c.allocator.capacity == 7
    # per-layer pools, K group then V group, heads folded into the minor dim
    carry = c.carry()
    assert len(carry) == 2 * 2
    assert all(a.shape == (8, 4, 2 * 8) and str(a.dtype) == "float32"
               for a in carry)
    (k, v), state = c.config.groups(carry)
    assert state == []
    assert len(k) == len(v) == 2
    assert c.blocks_for_tokens(1) == 1
    assert c.blocks_for_tokens(4) == 1
    assert c.blocks_for_tokens(5) == 2
    assert c.nbytes == block_bytes(c.config) * 8


def test_cache_int8_carry_has_scales():
    c = PagedKVCache(_cfg(dtype="int8"))
    (k, v, ks, vs), _state = c.config.groups(c.carry())
    assert all(str(a.dtype) == "int8" and a.shape == (8, 4, 16)
               for a in k + v)
    assert all(str(a.dtype) == "float32" and a.shape == (8, 4, 2)
               for a in ks + vs)


def test_replace_carry_arity_guard():
    c = PagedKVCache(_cfg())
    with pytest.raises(ValueError):
        c.replace_carry(c.carry() + (c.carry()[0],))


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_export_import_block_roundtrip_is_bitwise(dtype):
    """A block leaves one cache and enters another bit for bit, in the
    wire shapes the disaggregated pair agreed on ([layers, block_size,
    heads, head_dim] per group, scales without head_dim), and the import
    touches no other block."""
    rng = np.random.RandomState(3)
    src, dst = PagedKVCache(_cfg(dtype=dtype)), PagedKVCache(_cfg(dtype=dtype))

    def fill(c):
        return tuple(
            np.asarray(rng.randint(-100, 100, a.shape), str(a.dtype))
            if str(a.dtype) == "int8"
            else rng.standard_normal(a.shape).astype("float32")
            for a in c.carry())

    src.replace_carry([jnp.asarray(a) for a in fill(src)])
    before = fill(dst)
    dst.replace_carry([jnp.asarray(a) for a in before])

    wire = src.export_block(5)
    groups = 4 if dtype == "int8" else 2
    assert [a.shape for a in wire] == (
        [(2, 4, 2, 8)] * 2 + [(2, 4, 2)] * (groups - 2))
    assert [str(a.dtype) for a in wire] == (
        [dtype.replace("f32", "float32")] * 2 + ["float32"] * (groups - 2))
    dst.import_block(3, wire)

    assert all(np.array_equal(a, b) for a, b in
               zip(dst.export_block(3), wire))
    for got, was, sent in zip(dst.carry(), before, src.carry()):
        got = np.asarray(got)
        assert np.array_equal(got[3], np.asarray(sent)[5])
        rest = np.arange(got.shape[0]) != 3
        assert np.array_equal(got[rest], was[rest])

    # a frame cut for another geometry is refused before anything moves
    with pytest.raises(ValueError, match="geometry mismatch"):
        dst.import_block(3, [a[:, :2] for a in wire])
    with pytest.raises(ValueError, match="arity mismatch"):
        dst.import_block(3, wire[:1])
    assert all(np.array_equal(a, b) for a, b in
               zip(dst.export_block(3), wire))


def test_engine_owned_bytes_tracks_live_caches():
    gc.collect()
    base = engine_owned_kv_bytes()
    c = PagedKVCache(_cfg())
    assert engine_owned_kv_bytes() == base + c.nbytes
    del c
    gc.collect()
    assert engine_owned_kv_bytes() == base


# -- multi-token append / rollback (ensure_table, trim_table) ----------------


def test_ensure_table_grows_all_or_nothing():
    c = PagedKVCache(_cfg())          # capacity 7, block_size 4
    table = np.full(8, -1, np.int32)
    blocks = []
    assert c.ensure_table(table, blocks, 5)      # 2 blocks in one call
    assert len(blocks) == 2 and list(table[:2]) == blocks
    assert c.allocator.in_use == 2
    # idempotent when coverage already suffices
    assert c.ensure_table(table, blocks, 8)
    assert len(blocks) == 2 and c.allocator.in_use == 2
    # ask beyond capacity: takes NOTHING, pool state untouched
    before = c.allocator.stats()
    assert not c.ensure_table(table, blocks, 4 * 8)
    assert c.allocator.stats() == before
    assert len(blocks) == 2 and np.all(table[2:] == -1)


def test_trim_table_frees_speculative_overallocation():
    c = PagedKVCache(_cfg())
    table = np.full(8, -1, np.int32)
    blocks = []
    # a k-token speculative reservation out to position 15...
    assert c.ensure_table(table, blocks, 16)
    assert len(blocks) == 4
    # ...rolled back to 6 accepted tokens frees the trailing blocks
    freed = c.trim_table(table, blocks, 6)
    assert freed == 2 and len(blocks) == 2
    assert np.all(table[2:] == -1) and c.allocator.in_use == 2
    # already tight: nothing to free
    assert c.trim_table(table, blocks, 6) == 0
    # full rollback (dead sequence) returns everything
    assert c.trim_table(table, blocks, 0) == 2
    assert c.allocator.in_use == 0 and np.all(table == -1)


def test_trim_then_ensure_reuses_lifo_blocks():
    c = PagedKVCache(_cfg())
    table = np.full(8, -1, np.int32)
    blocks = []
    assert c.ensure_table(table, blocks, 12)
    tail = blocks[-1]
    c.trim_table(table, blocks, 8)
    # re-speculating immediately gets the just-freed block back (LIFO)
    assert c.ensure_table(table, blocks, 12)
    assert blocks[-1] == tail


# -- MEM001 fold: engine-owned KV counted in the static peak -----------------


def _fc_world():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.data("y", [-1, 1])
        p = layers.fc(layers.fc(x, size=8, act="relu"), size=1)
        loss = layers.reduce_mean(layers.square_error_cost(p, y))
        optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_mem001_counts_engine_owned_kv_blocks():
    main, startup, loss = _fc_world()
    gc.collect()
    cache = PagedKVCache(_cfg())
    rep = world_analysis.verify_world(main, startup, 1, batch=4,
                                      feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    est = rep.hbm[0]
    assert est["kv_cache_bytes"] >= cache.nbytes
    assert est["peak_bytes"] >= (est["resident_bytes"] + est["feed_bytes"]
                                 + est["transient_peak_bytes"]
                                 + cache.nbytes)
    hits = rep.by_rule("MEM001")
    assert hits and any("kv_cache" in h.message for h in hits)
    # without a live cache the fold is zero and the message stays clean
    del cache
    gc.collect()
    rep2 = world_analysis.verify_world(main, startup, 1, batch=4,
                                       feed_names=["x", "y"],
                                       fetch_names=[loss.name])
    assert rep2.hbm[0]["kv_cache_bytes"] == 0
    assert all("kv_cache" not in h.message for h in rep2.by_rule("MEM001"))


def test_mem003_suggests_shrinking_kv_pool():
    main, startup, loss = _fc_world()
    cache = PagedKVCache(_cfg())
    with _flags(hbm_budget_bytes=64):
        rep = world_analysis.verify_world(main, startup, 1, batch=4,
                                          feed_names=["x", "y"])
    hits = rep.by_rule("MEM003")
    assert hits, rep.format()
    assert "FLAGS_kv_cache_blocks" in hits[0].suggestion
    del cache
