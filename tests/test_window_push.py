"""A recurrent layer's convolution windows between their slots and the step
(``decode_model.push_windows``): the pool holds a slot as whole rows of 128
(``kv_cache.slot_layout``) and the move is, bit for bit, what the flat pool
did before PR 65: gather the lanes' rows, zero the fresh lanes', drop the
oldest input, append this token's, scatter the rows back.  One rule for the
five families that keep a window; what differs between them is a width, a
tap count and a dtype, which are the cases here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

# family -> (taps, the convolution's width) at the published sizes, and two
# of the tiny configurations the families' own tests serve
WIDTHS = {
    "granite_hybrid": (4, 4096 + 2 * 128),           # 13,056 values, 102 rows
    "nemotron_h": (4, 4096 + 2 * 8 * 128),           # 18,432: 144 rows
    "lfm2_moe": (3, 2048),                           # 4,096: 32 rows
    "kimi_linear": (4, 3 * 4096),                    # 36,864: 288 rows
    "solar_open2": (4, 3 * 8192),                    # 73,728: 576 rows
    "tiny_mamba": (4, 128 + 64),                     # 576: 4.5 rows, padded
    "tiny_conv": (3, 64),                            # 128: one row
}
SLOTS = 9
# lanes 1 and 3 idle (slot 0, position 0), lane 2 fresh on a dirty slot
LANE_SLOTS = np.array([3, 0, 5, 0, 1, 7], np.int32)
FRESH = np.array([False, True, True, True, False, False])
LIVE = np.array([True, False, True, False, True, True])


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _flat_reference(flat, slots, fresh, xbc, taps):
    """The move as it was on a flat pool ``[slots, (K - 1) * W]``, lane by
    lane in numpy -> (pool, [B, K, W] float32)."""
    width = xbc.shape[1]
    old = np.where(fresh[:, None], np.zeros((), flat.dtype), flat[slots])
    new = np.concatenate([old[:, width:], xbc.astype(flat.dtype)], axis=1)
    window = np.concatenate([old[:, :width], new], axis=1).reshape(
        len(slots), taps, width).astype(np.float32)
    out = flat.copy()
    out[slots] = new
    return out, window


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
@pytest.mark.parametrize("family", sorted(WIDTHS))
def test_a_pushed_window_is_the_flat_pools_bit_for_bit(family, dtype):
    taps, width = WIDTHS[family]
    held = (taps - 1) * width
    np_dtype = kvc._PAYLOAD[dtype][0]
    rows, row = kvc.slot_layout((held,))
    assert row == 128 and rows == -(-held // 128)
    assert (rows * row != held) == (family == "tiny_mamba")
    rng = np.random.RandomState(sum(map(ord, family + dtype)))
    # every slot dirty, the rest of a padded last row too
    pool = np.asarray(jnp.asarray(
        rng.randn(SLOTS, rows, row), np.float32).astype(np_dtype))
    xbc = rng.randn(len(LANE_SLOTS), width).astype(np.float32)

    step = jax.jit(lambda pool, slots, fresh, xbc: dm.push_windows(
        pool, slots, fresh, xbc, taps), donate_argnums=(0,))
    got_pool, got_window = step(jnp.asarray(pool), LANE_SLOTS, FRESH, xbc)
    got_pool = np.asarray(got_pool)
    assert got_pool.shape == pool.shape and got_pool.dtype == pool.dtype
    assert got_window.shape == (len(LANE_SLOTS), taps, width) \
        and got_window.dtype == jnp.float32

    flat = pool.reshape(SLOTS, -1)[:, :held]
    want_pool, want_window = _flat_reference(
        flat, LANE_SLOTS, FRESH, np.asarray(jnp.asarray(xbc)), taps)
    # the taps of every lane, the idle ones' too
    assert np.array_equal(_bits(got_window), _bits(want_window))
    # a fresh lane starts from zeros whatever its slot held
    assert not got_window[2, :-1].any() and pool[5].any()
    got_flat = got_pool.reshape(SLOTS, -1)[:, :held]
    named = LANE_SLOTS[LIVE]
    assert np.array_equal(_bits(got_flat[named]), _bits(want_pool[named]))
    # slot 0 holds one of the idle lanes' windows (which, nothing promises)
    assert any(np.array_equal(_bits(got_flat[0]), _bits(
        np.concatenate([np.zeros(held - width, np.float32),
                        xbc[lane]]).astype(np_dtype)))
        for lane in (1, 3))
    # no slot but the lanes' changed, the rest of its last row neither
    others = np.setdiff1d(np.arange(SLOTS), LANE_SLOTS)
    assert np.array_equal(_bits(got_pool[others]), _bits(pool[others]))


def test_a_slot_is_counted_by_the_values_it_holds():
    """A window of 576 values lies in five rows of 128: ``slot_bytes``, the
    cache's ``nbytes`` and the account's ``*_bytes`` gauge count the 576."""
    kv = kvc.KVCacheConfig(
        2, 2, 16, 4, 16, "f32", state_layers=6, state_slots=5,
        state_shapes=(((576,), "bf16"), ((32, 128), "f32")))
    cache = kvc.PagedKVCache(kv)
    _groups, (windows, states) = kv.groups(cache.carry())
    assert all(w.shape == (5, 5, 128) and w.dtype == jnp.bfloat16
               for w in windows)
    assert all(s.shape == (5, 32, 128) for s in states)
    assert kvc.slot_bytes(kv) == 6 * (576 * 2 + 32 * 128 * 4)
    assert kvc.state_bytes(kv) == 5 * kvc.slot_bytes(kv)
    assert cache.nbytes == cache.kv_nbytes + kvc.state_bytes(kv)
    # a matrix lies as it is, a flat array in whole rows
    assert kvc.slot_layout((32, 128)) == (32, 128)
    assert kvc.slot_layout((13056,)) == (102, 128)
    assert kvc.slot_layout((128,)) == (1, 128)
