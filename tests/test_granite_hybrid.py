"""What is the hybrid decoder block's own (paddle_tpu/models/
granite_hybrid.py: Mamba-2 state-space layers beside grouped-query
attention): its logits at every position against its plain reference
(benchmark/reference/granite_hybrid_ref.py, the file the benchmark uses)
and the reference broken, the step's span and prewarm event, the manager's
bytes and budget, and the two kernels under the interpreter.  The contract
it shares with every family (paged against unpaged, the multi-token step,
the engine's lanes, slots, preemption and refusals, the bundle) is
tests/test_decoder_families.py's, over its row of tests/decoder_families.py,
whose tiny sizes these are: 8 layers in two periods of ``mamba, mamba,
attention, mamba``, hidden 64, 4 query heads over 2 (or 1) KV heads of 16, 8
state-space heads of 16 with state 32, vocab 97."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.pallas_kernels import ssm_update as su
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

ref = fam.load("benchmark", "reference", "granite_hybrid_ref.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16), (CFG_G4, PARAMS_G4) = (
    fam.ROWS["granite_hybrid"].configs[k] for k in ("f32", "bf16", "group4"))
_sequences = fam.sequences
_engine = fam.engine
_flags = fam.flags
_alone = fam.alone
_chunked = fam.chunked


def run_paged(*args, **kw):
    return fam.run_paged(*args, **kw)[0]


def _coarse_state(dtype):
    """The control: the state rounded to ``dtype`` at every write."""
    def after_step(kv, carry):
        groups, (windows, states) = kv.groups(carry)
        states = [s.astype(dtype).astype(s.dtype) for s in states]
        return tuple(a for g in groups + [windows, states] for a in g)
    return after_step


def ref_config(cfg):
    """The source's keys, as the reference reads them."""
    return {"num_attention_heads": cfg.heads,
            "num_key_value_heads": cfg.kv_heads,
            "hidden_size": cfg.hidden, "rms_norm_eps": cfg.norm_eps,
            "shared_intermediate_size": cfg.ffn,
            "layer_types": list(cfg.layer_types),
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling}


# float32 rounding over eight layers (measured 2e-5 here); a fault in
# structure is 1e-2 or more (the broken-reference controls below)
TOL_F32 = 5e-4
# bfloat16 as served against the float32 reference on the same (bfloat16)
# weights: the rounding of every matmul's input, of the cached K and V and
# of the convolution's window to 8 bits of mantissa, over eight layers.
# Root-mean-square error over 3 sequences x 40-47 positions x 97 logits
# (standard deviation 0.29): 0.0080-0.0092 on three seeds; the limit is half
# as much again.  At 40 positions a bfloat16 state reads the same (0.0079-
# 0.0107: it is the length of the chip check, 1,024 positions, that tells
# it apart), so the control here is a state rounded to 8 bits (e4m3) at
# every write: 0.032.
RMS_BF16 = 0.014


def _ref_logits(cfg, params, tokens, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            dict(ref_config(cfg), **changed),
            {k: jnp.asarray(v) for k, v in params.items()},
            jnp.asarray(tokens, jnp.int32)))


def _worst(cfg, out, params, **changed):
    return max(float(np.abs(lg - _ref_logits(cfg, params, toks,
                                             **changed)).max())
               for toks, lg in out)


# -- 1. against the reference, and the reference broken ------------------------

@functools.lru_cache(None)
def _f32_out():
    return run_paged(CFG, PARAMS, _sequences(3))


def test_f32_logits_equal_the_reference_at_every_position():
    """Prefill token by token, then decode, three lanes of different
    lengths in shuffled blocks and slots: every position's logits are the
    reference's whole-sequence pass, and the decoded tokens are not the
    tied head repeating its input."""
    out = _f32_out()
    assert _worst(CFG, out, PARAMS) < TOL_F32
    toks, _lg = out[0]
    assert len(set(toks[-8:])) > 2


def test_group_of_four_equals_the_reference():
    out = run_paged(CFG_G4, PARAMS_G4, _sequences(2, seed=4))
    assert _worst(CFG_G4, out, PARAMS_G4) < TOL_F32


def _zeroed(name):
    return lambda p: dict(p, **{k: np.zeros_like(v) for k, v in p.items()
                                if k.endswith(name)})


BREAKS = {
    # what the reference is told, against the block as served
    "attention_multiplier": dict(attention_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=0.3),
    "logits_scaling": dict(logits_scaling=4.0),
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "one_kv_head": dict(num_key_value_heads=4),
}
PARAM_BREAKS = {
    # a reference that forgets: no decay (A_log -> -inf is decay 1: here
    # A_log 0 is A = -1, another memory), no D skip, no convolution bias
    "another_decay": _zeroed("A_log"), "no_skip": _zeroed("_D"),
    "no_conv_bias": _zeroed("conv_b"),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_reference_told_otherwise(how):
    if how == "one_kv_head":
        # the reference reads K and V as 4 heads: only a shape it can split
        params = dict(PARAMS)
        with pytest.raises(Exception):
            _worst(CFG, _f32_out()[:1], params, **BREAKS[how])
        return
    assert _worst(CFG, _f32_out()[:1], PARAMS, **BREAKS[how]) > 20 * TOL_F32


@pytest.mark.parametrize("how", sorted(PARAM_BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    assert _worst(CFG, _f32_out()[:1], PARAM_BREAKS[how](PARAMS)) \
        > 20 * TOL_F32


def test_the_state_is_remembered_across_many_tokens():
    """The check is not blind to a lost state: with Mamba-2's own start of
    A_log and dt_bias a state reset half way moves the logits of every later
    position (the reference on the second half alone differs from the
    reference on the whole)."""
    toks, _lg = _f32_out()[0]
    whole = _ref_logits(CFG, PARAMS, toks)
    cut = len(toks) // 2
    # attention layers alone would also differ; so strip them from both
    only_ssm = dict(ref_config(CFG), layer_types=["mamba"] * CFG.layers)
    p = dict(PARAMS)
    for l, kind in enumerate(CFG.layer_types):
        if kind == "attention":
            for k, v in gh.init_params(CFG.replace(
                    layer_types=("mamba",) * CFG.layers), 7, 0.3).items():
                if k.startswith("l%d_" % l):
                    p[k] = v
    with jax.default_matmul_precision("highest"):
        fwd = lambda t: np.asarray(ref.forward(
            only_ssm, {k: jnp.asarray(v) for k, v in p.items()},
            jnp.asarray(t, jnp.int32)))
        whole, tail = fwd(toks), fwd(toks[cut:])
    assert np.abs(whole[cut + 6:] - tail[6:]).max() > 100 * TOL_F32
    del whole


def _rms(cfg, out, params):
    sq = [np.square(lg - _ref_logits(cfg, params, toks)) for toks, lg in out]
    return float(np.sqrt(sum(x.sum() for x in sq) / sum(x.size for x in sq)))


def test_bf16_logits_within_tolerance_and_a_coarse_state_outside():
    seqs = _sequences(3, seed=1, lo=16, hi=24, n_decode=24)
    served = _rms(CFG16, run_paged(CFG16, PARAMS16, seqs), PARAMS16)
    coarse = _rms(CFG16, run_paged(
        CFG16, PARAMS16, seqs, after_step=_coarse_state(jnp.float8_e4m3fn)),
        PARAMS16)
    assert served < RMS_BF16 < coarse


# -- 2. the engine's span and event -----------------------------------------------


def test_step_span_and_gauge_carry_the_state(cache_dir, telemetry_on,
                                             tmp_path):
    """Traced, the step's span says how many lanes' state it moved and how
    many bytes that is; the gauge holds the slots' bytes; untraced, the span
    attributes are not computed."""
    with _flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = _engine(CFG, PARAMS, 16, buckets="2", name="hy")
        try:
            r = e.generate("hy", [1, 2, 3], max_new_tokens=4,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
    steps = fam.step_spans(tmp_path)
    per_slot = len(CFG.ssm_layers) * (3 * (128 + 64) * 4 + 32 * 128 * 4)
    assert steps and all(s["ssm_state_lanes"] == 1
                         and s["ssm_state_bytes"] == per_slot for s in steps)
    gauges = _tm.snapshot()["gauges"]
    assert gauges["ssm_state_bytes{model=hy}"] == 3 * per_slot


PREWARM = {
    # case: (kernels interpreted?, ssm heads, chunk columns forced,
    #        the event's state_update, its state_update_columns)
    "gather": (False, 8, None, "gather", None),
    "whole_slot": (True, 16, None, "pallas", 256),
    "chunks": (True, 16, 128, "pallas", 128),
}


@pytest.mark.parametrize("case", sorted(PREWARM))
def test_prewarm_event_says_what_a_transfer_moves(case, monkeypatch,
                                                  cache_dir, telemetry_on,
                                                  tmp_path):
    """The ``serving_prewarm`` event of a model with state-space layers
    names the state update's path and, where that is the kernel, the
    columns of a slot one transfer moves: the whole slot, or the chunk a
    slot too large for the VMEM asked falls back to; the served tokens are
    those of the unpaged step either way."""
    kernels, heads, columns, path, said = PREWARM[case]
    cfg = CFG.replace(ssm_heads=heads)
    params = gh.init_params(cfg, seed=3, std=0.3)
    if kernels:
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    if columns:
        _chunked(monkeypatch, cfg.ssm_state, columns)
    adoption.reset()
    try:
        with _flags(telemetry_dir=str(tmp_path)):
            e = _engine(cfg, params, 16, buckets="2", name="hy")
            try:
                e.prewarm()
                r = e.generate("hy", [5, 6, 7], max_new_tokens=5,
                               deadline_ms=60000.0)
            finally:
                e.stop()
            _tm.flush()
    finally:
        adoption.reset()
    assert r.status == "ok"
    assert np.array_equal(r.outputs["tokens"],
                          _alone(cfg, params, [5, 6, 7], 5))
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(ev["state_update"] == path
                        and ev.get("state_update_columns") == said
                        for ev in warm)


# -- 4. the manager: layers by kind, bytes, budget -------------------------------

def test_cache_describes_layers_by_kind():
    kv = dm.cache_config(CFG, BS, 16, state_slots=5)
    assert (kv.layers, kv.heads, kv.head_dim) == (2, 2, 16)
    assert kv.state_layers == 6 and kv.state_slots == 5
    assert kv.state_shapes == (((3 * (128 + 64),), "f32"),
                               ((32, 128), "f32"))
    cache = kvc.PagedKVCache(kv)
    carry = cache.carry()
    groups, (windows, states) = kv.groups(carry)
    assert [len(g) for g in groups] == [2, 2]
    assert all(a.shape == (16, BS, 32) for g in groups for a in g)
    assert len(windows) == len(states) == 6
    # a slot of a window pool is whole rows of 128: 576 values in five
    assert all(w.shape == (5, 5, 128) for w in windows)
    assert all(s.shape == (5, 32, 128) and s.dtype == jnp.float32
               for s in states)
    assert cache.kv_nbytes == 2 * 2 * 16 * BS * 32 * 4
    assert kvc.state_bytes(kv) == 5 * 6 * (576 + 32 * 128) * 4
    assert cache.nbytes == cache.kv_nbytes + kvc.state_bytes(kv)
    assert kvc.engine_owned_kv_bytes() >= cache.nbytes
    with pytest.raises(ValueError, match="carry"):
        kv.groups(carry[:-1])
    # a bf16 model keeps its window in bf16 and its state in float32
    kv16 = dm.cache_config(CFG16, BS, 16, state_slots=5)
    assert [dt for _s, dt in kv16.state_shapes] == ["bf16", "f32"]


def test_published_sizes_give_the_issues_bytes():
    """At the published widths: 8,192 B of K and V a token over 4 layers,
    268,435,456 B for 2048 blocks, and 76,437,504 B of state a sequence."""
    cfg = dm.DecoderConfig(
        arch="granite_hybrid", vocab=100352, layers=40, heads=32, kv_heads=8,
        head_dim=64, ffn=8192, max_seq=2048, dtype="bf16",
        layer_types=[("attention" if l % 10 == 5 else "mamba")
                     for l in range(40)],
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_conv=4)
    kv = dm.cache_config(cfg, 16, 2048, state_slots=33)
    assert kvc.block_bytes(kv) * 2048 == 268435456
    assert kvc.slot_bytes(kv) == 76437504
    assert kvc.state_bytes(kv) == 33 * 76437504
    shapes = gh.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s, _k in shapes.values()) == 3191396096


def test_budget_gate_counts_the_state():
    kv = dm.cache_config(CFG, BS, 2, state_slots=5)
    per, state = kvc.block_bytes(kv), kvc.state_bytes(kv)
    n, capped = kvc.plan_num_blocks(kv, model_resident_bytes=1000,
                                    requested=64,
                                    budget=1000 + state + 10 * per)
    assert (n, capped) == (10, True)
    with pytest.raises(ValueError, match="state bytes"):
        kvc.plan_num_blocks(kv, model_resident_bytes=1000, requested=64,
                            budget=1000 + state + per)
    # the same budget without recurrent layers fits the state's worth more
    plain = kvc.KVCacheConfig(kv.layers, kv.heads, kv.head_dim, BS, 2)
    n2, _ = kvc.plan_num_blocks(plain, model_resident_bytes=1000,
                                requested=0, budget=1000 + state + 10 * per)
    assert n2 == 10 + state // per


def test_slot_allocator_is_loud():
    slots = kvc.SlotAllocator(3)
    a, b = slots.take(), slots.take()
    assert {a, b} == {1, 2} and slots.in_use == 2 and slots.capacity == 2
    with pytest.raises(RuntimeError, match="no recurrent-state slot"):
        slots.take()
    slots.give(a)
    with pytest.raises(ValueError, match="not held"):
        slots.give(a)
    assert slots.take() == a


@pytest.mark.parametrize("arch", ["gpt2", "olmoe"])
def test_attention_only_carries_are_as_they_were(arch):
    """The GPT-2 and OLMoE carries: K then V, a pool a layer, and nothing
    after them; their steps take no slots."""
    cfg = dm.DecoderConfig(vocab=50, layers=3, heads=2, head_dim=16,
                           max_seq=32) if arch == "gpt2" else \
        dm.DecoderConfig(arch="olmoe", vocab=50, layers=3, heads=2,
                         head_dim=16, ffn=16, max_seq=32, experts=4,
                         experts_per_token=2)
    kv = dm.cache_config(cfg, BS, 8, state_slots=5)
    assert (kv.layers, kv.heads, kv.state_layers, kv.state_shapes) \
        == (3, 2, 0, ())
    cache = kvc.PagedKVCache(kv)
    assert cache.slots is None and kvc.state_bytes(kv) == 0
    carry = cache.carry()
    assert len(carry) == 6 and all(a.shape == (8, BS, 32) for a in carry)
    (k, v), state = kv.groups(carry)
    assert state == [] and k == list(carry[:3]) and v == list(carry[3:])
    params = {n: jnp.asarray(a)
              for n, a in dm.init_decoder_params(cfg, 0).items()}
    tables = np.full((2, 8), -1, np.int32)
    tables[0, 0] = 3
    out = jax.jit(dm.make_paged_step(cfg, kv))(
        carry, params, np.array([5, 0]), np.array([0, 0]), tables,
        np.array([1, 0]))
    assert len(out[0]) == 6 and out[1].shape == (2,)
    # and the engine plans no slot for them
    with _flags(kv_block_size=BS):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("m", (cfg, dm.init_decoder_params(cfg, 0)), kv_blocks=8)
    assert e.spec("m")["state_slots"] == 0


def test_config_refuses_what_no_block_computes():
    with pytest.raises(ValueError, match="layer_types"):
        CFG.replace(layer_types=CFG.layer_types[:4])     # 4 names, 8 layers
    with pytest.raises(ValueError, match="layer_types"):
        CFG.replace(layer_types=("window",) * 8)
    with pytest.raises(ValueError, match="multiple of kv_heads"):
        CFG.replace(kv_heads=3)
    with pytest.raises(ValueError, match="multi-head"):
        dm.DecoderConfig(vocab=50, layers=2, heads=4, head_dim=16,
                         kv_heads=2)
    with pytest.raises(ValueError, match="ssm_heads"):
        CFG.replace(ssm_state=0)


# -- 5. the kernels under the interpreter ----------------------------------------


def _pools(heads, kv_heads, dtype, seed=0):
    r = np.random.default_rng(seed)
    blocks, bs, d, lanes, maxb = 12, 16, 64, 3, 4
    q = jnp.asarray(r.standard_normal((lanes, heads, d)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((blocks, bs, kv_heads * d)),
                        jnp.float32).astype(dtype) for _ in range(2))
    tables = np.full((lanes, maxb), -1, np.int32)
    tables[0, :3] = [5, 2, 9]
    tables[1, :1] = [7]
    lens = np.array([40, 9, 0], np.int32)        # the third lane idle
    return q, k, v, jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4), (8, 4)],
                         ids=["group4", "group1", "group2"])
def test_attention_kernel_equals_the_gather(interpreted, heads, kv_heads,
                                            dtype):
    """The kernel under the interpreter against the gather path: grouped
    queries (row r reads KV head r // group) and the multi-head case it had,
    with the scale as an argument."""
    q, k, v, tables, lens = _pools(heads, kv_heads, dtype)
    assert pa.attention_path(q.shape, k.shape, k.dtype) == "pallas"
    for scale in (None, 0.015625):
        got = pa._paged_pallas(q, k, v, tables, lens, scale)
        want = pa.paged_attention_reference(q, k, v, tables, lens, scale)
        assert got.shape == want.shape == q.shape
        # the idle lane: zeros from the kernel, a uniform softmax over
        # masked scores from the gather; nothing reads either
        np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                                   atol=2e-5 if dtype == jnp.float32
                                   else 2e-2)
        assert not np.asarray(got[2]).any()


def test_grouped_attention_is_attention_over_repeated_heads():
    """masked_attention with 8 query heads over 2 KV heads is multi-head
    attention over K and V repeated four times."""
    r = np.random.default_rng(1)
    q = jnp.asarray(r.standard_normal((2, 8, 16)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((2, 12, 2, 16)), jnp.float32)
            for _ in range(2))
    lens = jnp.asarray([12, 5])
    got = pa.masked_attention(q, k, v, lens, 0.3)
    want = pa.masked_attention(q, jnp.repeat(k, 4, axis=2),
                               jnp.repeat(v, 4, axis=2), lens, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # and the default scale is the one it always had
    assert np.array_equal(
        np.asarray(pa.masked_attention(q, k, v, lens)),
        np.asarray(pa.masked_attention(q, k, v, lens, 0.25)))


@pytest.mark.parametrize("inner,columns", [(256, 128), (128, 128),
                                           (256, None)],
                         ids=["two_chunks", "one", "whole_slot"])
def test_state_update_kernel_equals_the_gather(interpreted, monkeypatch,
                                               inner, columns):
    """The state-update kernel under the interpreter against gather, update
    and scatter: the lanes' slots moved one token in place, the others
    untouched, a fresh lane started from zeros, bit for bit."""
    r = np.random.default_rng(0)
    slots_n, n, lanes = 6, 16, 4
    if columns:
        _chunked(monkeypatch, n, columns)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    pool = f(slots_n, n, inner)
    assert su.transfer_columns(pool.shape) == (columns or inner)
    slots = jnp.asarray([3, 5, 1, 0], jnp.int32)
    fresh = jnp.asarray([False, True, False, True])
    decay = jnp.asarray(r.uniform(0.2, 1.0, (lanes, inner)), jnp.float32)
    args = (slots, fresh, decay, f(lanes, inner), f(lanes, 1, n),
            f(lanes, 1, n))
    assert all(ok for _r, ok in su.ssm_update_checks(pool.shape, pool.dtype,
                                                     lanes))
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    got_pool, got_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["ssm_update"]
    assert np.array_equal(np.asarray(got_y), np.asarray(want_y))
    live = [1, 3, 5, 2, 4]                               # all but the scratch
    assert np.array_equal(np.asarray(got_pool)[live],
                          np.asarray(want_pool)[live])
    assert np.array_equal(np.asarray(got_pool)[[2, 4]],
                          np.asarray(pool)[[2, 4]])


# lanes of a step by the slots they name (0: an idle lane's scratch slot)
# and whether they start; pool [slots, N, I]; the chunk to compare with
WHOLE_SLOTS = {
    # case: (slots_n, n, inner, groups, slots, fresh lanes, chunk columns)
    "published_widths": (6, 128, 4096, 1, [3, 5, 1, 4], [1], 2048),
    "small": (6, 16, 256, 1, [3, 5, 1, 4], [1], 128),
    "slots_out_of_order": (10, 16, 256, 1, [9, 2, 7, 1, 8, 3, 6, 4], [],
                           128),
    "every_lane_fresh": (6, 16, 256, 1, [3, 5, 1, 4], [0, 1, 2, 3], 128),
    "idle_lanes_name_slot_0": (8, 16, 256, 1, [0, 5, 0, 2, 0, 0, 7, 0],
                               [3], 128),
    "every_lane_idle": (4, 16, 128, 1, [0, 0, 0, 0], [], 128),
    "one_lane": (3, 16, 256, 1, [2], [], 128),
    # 10 units in batches of 4: the last batch is short
    "short_last_batch": (14, 16, 128, 1,
                         [13, 1, 12, 2, 11, 3, 10, 4, 9, 5], [2, 7],
                         128),
    # five batches: reads of the third go where the first was written from
    "five_batches": (21, 8, 128, 1, list(range(20, 0, -1)), [5], 128),
}


@pytest.mark.parametrize("case", sorted(WHOLE_SLOTS))
def test_state_update_kernel_moves_whole_slots(interpreted, monkeypatch,
                                               case):
    """A lane's slot as one transfer in and one out, batches of slots read
    and written in turn: against gather, update and scatter on every slot a
    live lane names, every other slot (but the scratch one) untouched, and
    bit for bit the kernel that moves the same slots in column chunks."""
    slots_n, n, inner, groups, slots, fresh, columns = WHOLE_SLOTS[case]
    r = np.random.default_rng(len(case))
    lanes = len(slots)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    pool = f(slots_n, n, inner)
    args = (jnp.asarray(slots, jnp.int32),
            jnp.asarray([i in fresh for i in range(lanes)]),
            jnp.asarray(r.uniform(0.2, 1.0, (lanes, inner)), jnp.float32),
            f(lanes, inner), f(lanes, groups, n), f(lanes, groups, n))
    assert su.transfer_columns(pool.shape, groups) == inner
    assert su.update_path(pool.shape, pool.dtype, lanes, groups) == "pallas"
    got_pool, got_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["ssm_update"]
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    live = [i for i, s in enumerate(slots) if s]
    named = sorted(set(slots) - {0})
    others = sorted(set(range(1, slots_n)) - set(named))
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=2e-6,
                               atol=2e-6)
    assert np.array_equal(np.asarray(got_pool)[named],
                          np.asarray(want_pool)[named])
    assert np.array_equal(np.asarray(got_pool)[others],
                          np.asarray(pool)[others])
    for i in fresh:
        # started from zeros: the state is the token's own outer product
        if slots[i]:
            np.testing.assert_allclose(
                np.asarray(got_pool)[slots[i]],
                np.asarray(args[4])[i, 0][:, None]
                * np.asarray(args[3])[i][None, :], rtol=1e-6, atol=1e-6)
    # the same slots in chunks: the same arithmetic a column
    _chunked(monkeypatch, n, columns)
    assert su.transfer_columns(pool.shape, groups) == min(columns, inner)
    chunk_pool, chunk_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert np.array_equal(np.asarray(chunk_y)[live], np.asarray(got_y)[live])
    assert np.array_equal(np.asarray(chunk_pool)[1:],
                          np.asarray(got_pool)[1:])


@pytest.mark.parametrize("order", fam.COPY_ORDERS)
@pytest.mark.parametrize("case", sorted(fam.TURNS))
def test_state_update_kernel_keeps_its_turns(monkeypatch, case, order):
    """The order of the transfers (a batch updated beside the write of the
    batch before it, then beside the read of the batch after it) where it
    is delicate: one batch, whole batches, a short last one, batches of one
    and of three, a slot in chunks, every lane on the scratch slot; under
    the interpreter that runs a copy as it is started and under the one
    that runs it only when it is waited for.  Against gather, update and
    scatter, and bit for bit the plainest order, one unit at a time."""
    n = 16
    slots_n, slots, inner, cols, k_n = fam.turns_case(monkeypatch, case, n)
    r = np.random.default_rng(len(case))
    lanes = len(slots)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    pool = f(slots_n, n, inner)
    args = (jnp.asarray(slots, jnp.int32),
            jnp.asarray([i % 3 == 1 for i in range(lanes)]),
            jnp.asarray(r.uniform(0.2, 1.0, (lanes, inner)), jnp.float32),
            f(lanes, inner), f(lanes, 1, n), f(lanes, 1, n))
    assert su.transfer_columns(pool.shape) == cols
    assert su.units_in_flight(pool.shape, cols,
                              lanes * (inner // cols)) == k_n
    # every idle lane writes the scratch slot, and nothing reads it
    live = [i for i, s in enumerate(slots) if s]
    got_pool, got_y = fam.in_turns_and_plainly(monkeypatch, su, order, pool,
                                               args, live)
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=2e-6,
                               atol=2e-6)
    assert np.array_equal(np.asarray(got_pool)[1:],
                          np.asarray(want_pool)[1:])
    assert np.isfinite(np.asarray(got_pool)).all()


def test_a_slot_too_large_for_the_limit_moves_in_chunks(interpreted,
                                                        monkeypatch,
                                                        telemetry_on):
    """Which form is taken follows from the slot's shape and the VMEM the
    kernel asks for: ``transfer_columns`` says which, ``ssm_update_checks``
    stays whole while some chunk fits and names ``vmem`` when none does
    (the gather then, counted)."""
    assert su._UNIT_BUDGET < su._VMEM_LIMIT
    checks = lambda shape: dict(su.ssm_update_checks(shape, jnp.float32, 4))
    # the published slot: whole, 4 of them a batch
    assert su.transfer_columns((33, 128, 4096)) == 4096
    assert 2 * su.BATCH * 4 * 128 * 4096 <= su._UNIT_BUDGET
    # a slot of 16 MiB: quarters, chosen by nothing but the shape
    assert su.transfer_columns((5, 1024, 4096)) == 1024
    assert all(checks((5, 1024, 4096)).values())
    # less VMEM: the same slot in narrower chunks, then not at all
    _chunked(monkeypatch, 128, 512)
    assert su.transfer_columns((33, 128, 4096)) == 512
    assert all(checks((33, 128, 4096)).values())
    monkeypatch.setattr(su, "_UNIT_BUDGET", 4 * 4 * 128 * 128 - 1)
    assert su.transfer_columns((33, 128, 4096)) is None
    assert not checks((33, 128, 4096))["vmem"]
    assert su.update_path((33, 128, 4096), jnp.float32, 4) == "gather"
    r = np.random.default_rng(1)
    f = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    pool = f(4, 128, 256)
    args = (jnp.asarray([1, 3], jnp.int32), jnp.asarray([False, True]),
            jnp.abs(f(2, 256)), f(2, 256), f(2, 1, 128), f(2, 1, 128))
    got = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    want = jax.jit(su.state_update_reference)(pool, *args)
    assert adoption.active_kernels() == []
    assert [ls for _f, ls in _tm.label_sets(
        "pallas_kernel_fallback_total")] == [
            {"kernel": "ssm_update", "reason": "vmem"}]
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_paged_step_with_both_kernels_equals_the_gather_step(interpreted):
    """The whole hybrid step with both kernels interpreted: the tokens and
    logits of the jnp step."""
    cfg = CFG.replace(head_dim=64, heads=4, kv_heads=2, ssm_head_dim=16)
    params = gh.init_params(cfg, seed=5, std=0.1)
    seqs = [([3, 1, 4, 1, 5], 4), ([9, 2], 5)]
    # 16-token blocks: the attention kernel's sublane tile
    with_kernels = run_paged(cfg, params, seqs, blocks=12, block_size=16)
    assert set(adoption.active_kernels()) == {"paged_attention", "ssm_update"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    plain = run_paged(cfg, params, seqs, blocks=12, block_size=16)
    for (fa, la), (fb, lb) in zip(with_kernels, plain):
        assert fa == fb
        np.testing.assert_allclose(la, lb, atol=1e-5)
