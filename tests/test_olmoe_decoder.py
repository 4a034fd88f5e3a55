"""What is the routed-expert decoder block's own (paddle_tpu/models/
olmoe.py): logits at every position in float32 and in bfloat16 against its
plain reference (benchmark/reference/olmoe_ref.py, the file the benchmark
uses) and the reference broken, the chosen experts, the engine with a
prefix index (mixed lanes, prefix-cache hit, preemption with recompute), the
step's span, the bf16 KV residency and the bundle a GPT-2 wrote before the
config knew architectures.  The contract it shares with every family (paged
against unpaged, the multi-token step, the bundle round trip, the serve
tool) is tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: 2 layers, hidden 64,
4 heads x 16, 8 experts of width 32, top 2, vocab 97, 64 positions."""

import functools
import inspect
import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.utils import fault_injection

ref = fam.load("benchmark", "reference", "olmoe_ref.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["olmoe"].configs[k] for k in ("f32", "bf16"))
run_paged = fam.run_paged
_sequences = fam.sequences
_flags = fam.flags
_fp8_rounded = fam.fp8_rounded
_unpaged = fam.alone


def _engine(cfg, params, kv_blocks, buckets="4"):
    return fam.engine(cfg, params, kv_blocks, buckets, name="moe")


# normal(0, 0.05): at this hidden size the family's 0.02 leaves the layers'
# share of the residual stream, and so a fault's mark on the logits, small
# the reference reads the source's keys
REF_CONFIG = {"num_attention_heads": 4, "hidden_size": 64,
              "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "num_experts": 8,
              "num_experts_per_tok": 2, "num_hidden_layers": 2}

# float32 rounding over two layers (measured 3e-6 here); a fault in
# structure is 1e-2 or more (the broken-reference controls below)
TOL_F32 = 2e-4
# bfloat16 as served against the float32 reference on the same (bfloat16)
# weights: what is left is the rounding of every matmul's input and of the
# cached K and V to 8 bits of mantissa, over two layers.  Measured here over
# 4 sequences x 30-37 positions: largest 0.0068.  Twice that and a little;
# the controls read 0.129 (weights rounded to fp8 e4m3) and 0.026 (an int8
# cache under the bf16 weights).
TOL_BF16 = 0.015


def _ref_logits(params, tokens, return_routing=False):
    with jax.default_matmul_precision("highest"):
        out = ref.forward(REF_CONFIG, {k: jnp.asarray(v)
                                       for k, v in params.items()},
                          jnp.asarray(tokens, jnp.int32), return_routing)
    return jax.tree_util.tree_map(np.asarray, out)


def _worst(out, params):
    return max(float(np.abs(lg - _ref_logits(params, toks)).max())
               for toks, lg in out)


# -- 1. float32 against the reference, and the reference broken ----------------


@functools.lru_cache(None)
def _f32_out():
    return run_paged(CFG, PARAMS, _sequences(3))[0]


def test_f32_logits_equal_the_reference_at_every_position():
    out = _f32_out()
    assert all(len(t) == lg.shape[0] for t, lg in out)
    assert _worst(out, PARAMS) < TOL_F32


BREAKS = {
    "rope_pairing_interleaved": (
        "    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], "
        "axis=-1)\n",
        "    rotated = jnp.stack([-x[..., 1::2], x[..., 0::2]], "
        "axis=-1).reshape(x.shape)\n"),
    "no_q_norm": ('_rmsnorm(h @ p("wq"), p("q_norm"), eps)', '(h @ p("wq"))'),
    "no_k_norm": ('_rmsnorm(h @ p("wk"), p("k_norm"), eps)', '(h @ p("wk"))'),
    "renormalised_gates": (
        "        gate = jnp.where(prob >= kth, prob, 0.0)\n",
        "        gate = jnp.where(prob >= kth, prob, 0.0)\n"
        "        gate = gate / gate.sum(axis=-1, keepdims=True)\n"),
    "dropped_expert": ("for e in range(n_exp):", "for e in range(n_exp - 1):"),
    "no_rope": ("        q, k = _rope(q, theta), _rope(k, theta)\n", ""),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_broken_reference(how):
    """The reference wrong in one way at a time is 1e-2 or more from the
    step: the 2e-4 above is not slack that such a fault fits into."""
    old, new = BREAKS[how]
    source = inspect.getsource(ref)
    assert source.count(old) == 1, how
    broken = types.ModuleType("olmoe_ref_" + how)
    exec(compile(source.replace(old, new), how, "exec"), broken.__dict__)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for toks, lg in _f32_out():
            want = np.asarray(broken.forward(
                REF_CONFIG, {k: jnp.asarray(v) for k, v in PARAMS.items()},
                jnp.asarray(toks, jnp.int32)))
            worst = max(worst, float(np.abs(lg - want).max()))
    print("reference broken (%s): largest logit difference %.4f" % (how, worst))
    assert worst >= 1e-2, (how, worst)


# -- 2. bfloat16 as served, and what falls outside its tolerance ---------------


def test_bf16_logits_within_tolerance_and_lower_precision_outside():
    seqs = _sequences(4, seed=1, n_decode=24)
    out, _ = run_paged(CFG16, PARAMS16, seqs)
    bf16 = _worst(out, PARAMS16)
    assert bf16 < TOL_BF16, bf16
    # controls, each against the reference on the bf16 weights as served:
    # the weights rounded to fp8 on their way into the step, and the cache
    # quantised to int8
    fp8, _ = run_paged(CFG16, _fp8_rounded(PARAMS16), seqs)
    int8, _ = run_paged(CFG16.replace(kv_dtype="int8"), PARAMS16, seqs)
    got = {"bf16": bf16, "fp8_weights": _worst(fp8, PARAMS16),
           "int8_cache": _worst(int8, PARAMS16)}
    print("largest logit error against the f32 reference: %s" % got)
    assert got["fp8_weights"] > 2 * TOL_BF16, got
    assert got["int8_cache"] > TOL_BF16, got


# -- 3. the chosen experts ------------------------------------------------------


def test_chosen_experts_equal_the_references():
    """One live lane, so a step's routed counts are the token's expert set
    in each layer.  Equal to the reference's 2 largest probabilities except
    where its 2nd and 3rd lie within 1e-3 (counted, and rare)."""
    close, compared = 0, 0
    for seq in _sequences(3, seed=2):
        out, routed = run_paged(CFG, PARAMS, [seq])
        toks = out[0][0]
        _, prob = _ref_logits(PARAMS, toks, return_routing=True)
        assert len(routed) == len(toks)
        for t, counts in enumerate(routed):
            assert counts.shape == (CFG.layers, CFG.experts)
            assert (counts.sum(axis=1) == CFG.experts_per_token).all()
            for l in range(CFG.layers):
                srt = np.sort(prob[l, t])[::-1]
                k = CFG.experts_per_token
                compared += 1
                if srt[k - 1] - srt[k] < 1e-3:
                    close += 1
                    continue
                want = set(np.argsort(prob[l, t])[::-1][:k])
                assert set(np.nonzero(counts[l])[0]) == want, (t, l)
    print("expert sets compared %d, skipped as ties within 1e-3: %d"
          % (compared, close))
    assert close < compared // 10


def test_idle_lanes_route_nothing():
    out, routed = run_paged(CFG, PARAMS, [([1, 2, 3], 0), ([4], 0)])
    # lane 1 is done after one step: later steps count lane 0 alone
    assert [int(r[0].sum()) for r in routed] == [4, 2, 2]


# -- 4. paged against unpaged: bitwise -----------------------------------------


# -- 5. the multi-token step ---------------------------------------------------


# -- 6. through the engine ------------------------------------------------------


@pytest.mark.parametrize("cfg,params", [(CFG, PARAMS), (CFG16, PARAMS16)],
                         ids=["f32", "bf16"])
def test_engine_mixed_lanes_prefix_hit_and_preemption(cfg, params, cache_dir,
                                                      telemetry_on):
    e = _engine(cfg, params, 40)
    try:
        e.prewarm()
        assert e.spec("moe")["arch"] == "olmoe"
        assert e.spec("moe")["kv_dtype"] == (cfg.kv_dtype or "f32")
        miss0 = _tm.counter_total("executor_cache_miss_total")
        # mixed: a long prompt still prefilling while short ones decode
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7], [2, 7],
                   [1, 8, 2, 8], [6]]
        with e._cond:
            waits = [e.submit("moe", p, max_new_tokens=9,
                              deadline_ms=60000.0) for p in prompts]
        for p, w in zip(prompts, waits):
            r = w.wait(timeout=120.0)
            assert r is not None and r.status == "ok", r and r.error
            assert np.array_equal(r.outputs["tokens"],
                                  _unpaged(cfg, params, p, 9)), p
        # a prefix-cache hit: the first prompt again skips its 3 full blocks
        r = e.generate("moe", prompts[0], max_new_tokens=9,
                       deadline_ms=60000.0)
        assert r.status == "ok" and r.phases["cached_tokens"] == 12
        assert np.array_equal(r.outputs["tokens"],
                              _unpaged(cfg, params, prompts[0], 9))
        assert _tm.counter_total("executor_cache_miss_total") == miss0
    finally:
        e.stop()
    # preemption with recompute: capacity 3 blocks, A wants 3 and B 2
    e = _engine(cfg, params, 4, buckets="2")
    try:
        with e._cond:
            ra = e.submit("moe", [1, 2, 3, 4], max_new_tokens=8,
                          deadline_ms=60000.0)
            rb = e.submit("moe", [5, 6, 7, 8], max_new_tokens=4,
                          deadline_ms=60000.0)
        a, b = ra.wait(timeout=120.0), rb.wait(timeout=120.0)
        assert a is not None and a.status == "ok", a and a.error
        assert b is not None and b.status == "ok", b and b.error
        assert np.array_equal(a.outputs["tokens"],
                              _unpaged(cfg, params, [1, 2, 3, 4], 8))
        assert np.array_equal(b.outputs["tokens"],
                              _unpaged(cfg, params, [5, 6, 7, 8], 4))
        assert _tm.counter_total("kv_block_evictions_total") >= 1
    finally:
        e.stop()


def test_step_span_carries_routing_only_for_a_routed_block(cache_dir,
                                                           telemetry_on,
                                                           tmp_path):
    """Traced, the olmoe step's span says how many experts were hit, the
    fullest expert's load and the tokens routed; the counters move; a
    GPT-2 step's span has none of it."""
    try:
        with _flags(tracing=True, telemetry_dir=str(tmp_path)):
            for name, (cfg, params) in (("moe", (CFG, PARAMS)),
                                        ("toy", fam.ROWS["gpt2"].f32)):
                e = fam.engine(cfg, params, 16, buckets="2", name=name)
                try:
                    r = e.generate(name, [1, 2, 3], max_new_tokens=4,
                                   deadline_ms=60000.0)
                    assert r.status == "ok", r.error
                finally:
                    e.stop()
            _trc.flush()
        moe, toy = (fam.step_spans(tmp_path, name) for name in ("moe", "toy"))
        assert len(moe) == 6 and len(toy) == 6
        # a span is one iteration of the one-ahead loop: it dispatches a
        # step and reads the step before.  The first reads none, and the
        # sixth step is read by the iteration that has nothing left to
        # dispatch, which opens no span (the counter below has all six)
        routed = [a for a in moe if "moe_experts_hit" in a]
        assert len(routed) == 5 and "moe_experts_hit" not in moe[0]
        for a in routed:
            # one live lane: 2 experts hit a layer, each with one token
            assert a["moe_experts_hit"] == 2 and a["moe_load_max"] == 1
            assert a["moe_assignments"] == 2
        assert not any(k.startswith("moe_") for a in toy for k in a)
        assert _tm.counter_total("moe_tokens_routed_total") \
            == 6 * CFG.layers * CFG.experts_per_token
    finally:
        _trc.reset()


# -- 7. bf16 KV residency -------------------------------------------------------


def test_bf16_cache_bytes_roundtrip_and_geometry_refusal():
    kv = kvc.KVCacheConfig(2, 4, 16, BS, 8, "bf16")
    # K + V x layers x block x hidden x 2 bytes
    assert kvc.block_bytes(kv) == 2 * 2 * BS * 64 * 2
    assert kvc.block_bytes(kvc.KVCacheConfig(2, 4, 16, BS, 8, "f32")) \
        == 2 * kvc.block_bytes(kv)
    n, capped = kvc.plan_num_blocks(kv, model_resident_bytes=1000,
                                    requested=64,
                                    budget=1000 + 10 * kvc.block_bytes(kv))
    assert (n, capped) == (10, True)
    with pytest.raises(ValueError, match="f32.bf16.int8"):
        kvc.KVCacheConfig(2, 4, 16, BS, 8, "fp8")
    cache = kvc.PagedKVCache(kv)
    assert cache.nbytes == 8 * kvc.block_bytes(kv)
    assert all(a.dtype == jnp.bfloat16 and a.shape == (8, BS, 64)
               for a in cache.carry()) and len(cache.carry()) == 4
    rng = np.random.RandomState(0)
    rows = [np.asarray(jnp.asarray(rng.randn(2, BS, 4, 16), jnp.bfloat16))
            for _ in range(2)]
    cache.import_block(3, rows)
    got = cache.export_block(3)
    assert all(g.dtype == rows[0].dtype and np.array_equal(
        g.view(np.uint16), r.view(np.uint16)) for g, r in zip(got, rows))
    assert not np.asarray(cache.carry()[0][2]).any()    # neighbours untouched
    before = [np.asarray(a).view(np.uint16).copy() for a in cache.carry()]
    for bad in ([r.astype(np.float32) for r in rows],          # dtype
                [r[:, :, :2] for r in rows],                   # heads
                rows[:1]):                                     # arity
        with pytest.raises(ValueError, match="kv import"):
            cache.import_block(5, bad)
    assert all(np.array_equal(np.asarray(a).view(np.uint16), b)
               for a, b in zip(cache.carry(), before))


def test_bf16_session_export_is_refused_loudly(cache_dir, telemetry_on):
    """codec.py frames arrays by numpy's dtype string, which bfloat16 does
    not have: the migration export refuses a bf16 pool, counted under
    reason="dtype", before anything is encoded."""
    with _flags(session_migration=True):
        e = _engine(CFG16, PARAMS16, 16, buckets="2")
        try:
            # 1 ms a step keeps the request alive while it is exported
            fault_injection.arm("serving.decode_step:delay:1")
            streamed = threading.Event()
            done = e.submit("moe", [1, 2, 3, 4, 5], max_new_tokens=40,
                            deadline_ms=60000.0,
                            on_token=lambda *a: streamed.set())
            assert streamed.wait(60.0)
            with pytest.raises(ValueError, match="dtype"):
                e.export_session(done.req_id)
            fault_injection.disarm()
            refused = [k for k in _tm.snapshot()["counters"]
                       if k.startswith("kv_migrate_refused_total")]
            assert refused and all("reason=dtype" in k for k in refused)
            assert done.wait(timeout=120.0).status == "ok"
        finally:
            fault_injection.disarm()
            e.stop()


# -- 8. the bundle --------------------------------------------------------------


def test_a_gpt2_bundle_written_before_this_block_still_loads(tmp_path):
    cfg = dm.DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8,
                           max_seq=48)
    params = dm.init_decoder_params(cfg, seed=7)
    d = dm.save_decoder(str(tmp_path / "old"), cfg, params)
    with open(os.path.join(d, "decoder.json"), "w") as fp:
        # the six keys a bundle had before the config knew architectures
        json.dump({"vocab": 31, "layers": 2, "heads": 2, "head_dim": 8,
                   "ffn": 64, "max_seq": 48}, fp)
    got_cfg, got = dm.load_decoder(d)
    assert got_cfg.to_dict() == cfg.to_dict() and got_cfg.arch == "gpt2"
    assert got_cfg.kv_dtype is None
    assert all(np.array_equal(got[k], v) for k, v in params.items())


def test_truncate_decoder_keeps_the_block():
    dcfg, dparams = dm.truncate_decoder(CFG, PARAMS, layers=1)
    assert dcfg.arch == "olmoe" and dcfg.layers == 1
    assert dcfg.experts == CFG.experts
    assert set(dparams) == {k for k in PARAMS
                            if not k.startswith("l1_")}
    out, _ = run_paged(dcfg, dparams, [([1, 2, 3], 2)])
    assert out[0][1].shape == (5, CFG.vocab)


def test_config_refuses_what_no_block_computes():
    with pytest.raises(ValueError, match="arch"):
        dm.DecoderConfig(vocab=9, layers=1, heads=1, head_dim=8, arch="llama")
    with pytest.raises(ValueError, match="experts_per_token"):
        dm.DecoderConfig(vocab=9, layers=1, heads=1, head_dim=8,
                         arch="olmoe", experts=4, experts_per_token=5)
    with pytest.raises(ValueError, match="f32"):
        dm.DecoderConfig(vocab=9, layers=1, heads=1, head_dim=8, dtype="bf16")


