"""The routed-expert decoder block (paddle_tpu/models/olmoe.py) through the
same step makers, cache and engine as the GPT-2 block, against its plain
reference (benchmark/reference/olmoe_ref.py, the file the benchmark uses):
logits at every position in float32 and in bfloat16, the chosen experts,
paged against unpaged, the multi-token step, the engine (mixed lanes,
prefix-cache hit, preemption with recompute), the bf16 KV residency and the
bundle round trip.  Tiny sizes on the CPU: 2 layers, hidden 64, 4 heads x
16, 8 experts of width 32, top 2, vocab 97, 64 positions."""

import contextlib
import glob
import importlib.util
import inspect
import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import olmoe
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc
from paddle_tpu.utils import fault_injection

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_ref():
    spec = importlib.util.spec_from_file_location(
        "olmoe_ref", os.path.join(ROOT, "benchmark", "reference",
                                  "olmoe_ref.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_ref()

BS = 4
CFG = dm.DecoderConfig(arch="olmoe", vocab=97, layers=2, heads=4, head_dim=16,
                       ffn=32, max_seq=64, experts=8, experts_per_token=2)
CFG16 = CFG.replace(dtype="bf16")
# normal(0, 0.05): at this hidden size the family's 0.02 leaves the layers'
# share of the residual stream, and so a fault's mark on the logits, small
PARAMS = olmoe.init_params(CFG, seed=3, std=0.05)
PARAMS16 = olmoe.init_params(CFG16, seed=3, std=0.05)
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


def _sequences(n, seed=0, lo=5, hi=14, n_decode=8):
    rng = np.random.RandomState(seed)
    return [(list(rng.randint(0, CFG.vocab, rng.randint(lo, hi))), n_decode)
            for _ in range(n)]


def run_paged(cfg, params, seqs, width=1, blocks=40, table_seed=5):
    """Every (prompt, n_decode) of ``seqs`` in its own lane through the
    paged step and a real pool with a shuffled block table: the prompt one
    token a step (``width`` a step for the multi-token step), then the
    step's own argmax.  -> per lane (tokens fed, logits [n, vocab] of every
    position fed), and per step the routed-token counts (None for width >
    1 or a block without experts)."""
    kv = kvc.KVCacheConfig(cfg.layers, cfg.heads, cfg.head_dim, BS, blocks,
                           cfg.kv_dtype or "f32")
    cache = kvc.PagedKVCache(kv)
    maxb = cfg.max_seq // BS
    b = len(seqs)
    order = iter(np.random.RandomState(table_seed).permutation(
        np.arange(1, blocks)))
    tables = np.full((b, maxb), -1, np.int32)
    total = [len(p) + n for p, n in seqs]
    for i, t in enumerate(total):
        for j in range(-(-t // BS)):
            tables[i, j] = next(order)
    make = dm.make_paged_step(cfg, kv) if width == 1 \
        else dm.make_paged_step_multi(cfg, kv, width)
    step = jax.jit(make, donate_argnums=(0,))
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    fed = [list(p) for p, _ in seqs]          # grows by the step's argmax
    logits = [[] for _ in seqs]
    routed = []
    while any(len(lg) < t for lg, t in zip(logits, total)):
        tok = np.zeros((b, width), np.int32)
        pos = np.zeros((b, width), np.int32)
        lens = np.zeros((b, width), np.int32)
        cols = []
        for i in range(b):
            at = len(logits[i])
            # a lane feeds what it already knows: prompt tokens, then the
            # one token its last step chose
            n = max(min(width, len(fed[i]) - at, total[i] - at), 0)
            cols.append(n)
            for j in range(width):
                jj = min(j, n - 1)
                if n:
                    tok[i, j] = fed[i][at + jj]
                    pos[i, j] = at + jj
                    lens[i, j] = at + jj + 1
        args = (tok, pos, tables, lens) if width > 1 \
            else (tok[:, 0], pos[:, 0], tables, lens[:, 0])
        carry, nxt, lg, *extras = step(cache.carry(), jparams, *args)
        cache.replace_carry(carry)
        routed.append(np.asarray(extras[0]) if extras else None)
        nxt = np.asarray(nxt).reshape(b, width)
        lg = np.asarray(lg).reshape(b, width, -1)
        for i, n in enumerate(cols):
            for j in range(n):
                logits[i].append(lg[i, j])
            if n and len(logits[i]) == len(fed[i]) < total[i]:
                fed[i].append(int(nxt[i, n - 1]))
    return [(f, np.stack(lg)) for f, lg in zip(fed, logits)], routed


def _worst(out, params):
    return max(float(np.abs(lg - _ref_logits(params, toks)).max())
               for toks, lg in out)


# -- 1. float32 against the reference, and the reference broken ----------------


F32_OUT = {}


def _f32_out():
    if not F32_OUT:
        F32_OUT["out"], F32_OUT["routed"] = run_paged(
            CFG, PARAMS, _sequences(3))
    return F32_OUT["out"]


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


def _fp8_rounded(params):
    return {k: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                          .astype(jnp.bfloat16)) for k, v in params.items()}


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


@pytest.mark.parametrize("cfg,params", [(CFG, PARAMS), (CFG16, PARAMS16)],
                         ids=["f32", "bf16"])
def test_paged_is_bitwise_equal_to_unpaged(cfg, params):
    prompt, n = [7, 3, 9, 1, 4, 4, 8], 10
    toks, hist = dm.unpaged_generate(cfg, params, prompt, n,
                                     pad_len=cfg.max_seq, return_logits=True)
    out, _ = run_paged(cfg, params, [(prompt, n)])
    fed, logits = out[0]
    assert fed[len(prompt):] == toks
    # the logits of the last prompt position and of every decoded one
    assert np.array_equal(logits[len(prompt) - 1:len(prompt) - 1 + n],
                          np.stack(hist))


# -- 5. the multi-token step ---------------------------------------------------


def test_multi_token_step_equals_single():
    seqs = _sequences(3, seed=4)
    single, _ = run_paged(CFG, PARAMS, seqs)
    multi, routed = run_paged(CFG, PARAMS, seqs, width=4)
    for (t1, l1), (t4, l4) in zip(single, multi):
        assert t1 == t4
        assert float(np.abs(l1 - l4).max()) < TOL_F32
    assert _worst(multi, PARAMS) < TOL_F32
    # its routed counts are summed over the columns
    assert routed[0].shape == (CFG.layers, CFG.experts)


# -- 6. through the engine ------------------------------------------------------


@contextlib.contextmanager
def _flags(**kv):
    kv = {"FLAGS_" + k: v for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("cc"))
    old = fluid.get_flags(["FLAGS_compile_cache_dir"])
    fluid.set_flags({"FLAGS_compile_cache_dir": d})
    yield d
    fluid.set_flags(old)


@pytest.fixture()
def telemetry_on():
    fluid.set_flags({"FLAGS_telemetry": True})
    _tm.reset()
    yield
    _tm.reset()
    fluid.set_flags({"FLAGS_telemetry": False})


def _engine(cfg, params, kv_blocks, buckets="4"):
    with _flags(kv_block_size=BS):
        e = DecodeEngine(buckets=buckets, deadline_ms=60000.0)
        e.add_model("moe", (cfg, params), kv_blocks=kv_blocks)
    return e.start()


def _unpaged(cfg, params, prompt, n):
    return np.asarray(dm.unpaged_generate(cfg, params, prompt, n,
                                          pad_len=cfg.max_seq), np.int32)


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
    fluid.set_flags({"FLAGS_tracing": True,
                     "FLAGS_telemetry_dir": str(tmp_path)})
    gcfg = dm.DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8,
                            max_seq=48)
    try:
        for name, cfg, params in (
                ("moe", CFG, PARAMS),
                ("toy", gcfg, dm.init_decoder_params(gcfg, seed=7))):
            with _flags(kv_block_size=BS):
                e = DecodeEngine(buckets="2", deadline_ms=60000.0)
                e.add_model(name, (cfg, params), kv_blocks=16)
            e.start()
            try:
                r = e.generate(name, [1, 2, 3], max_new_tokens=4,
                               deadline_ms=60000.0)
                assert r.status == "ok", r.error
            finally:
                e.stop()
        _trc.flush()
        recs = []
        for path in glob.glob(str(tmp_path / "trace-*.jsonl")):
            with open(path) as fp:
                recs += [json.loads(line) for line in fp if line.strip()]
        steps = [s for s in recs if s.get("t") == "span"
                 and s.get("name") == "serving.decode_step"]
        moe = [s["attrs"] for s in steps if s["attrs"]["model"] == "moe"]
        toy = [s["attrs"] for s in steps if s["attrs"]["model"] == "toy"]
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
        fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry_dir": ""})


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
    with _flags(session_migration=True, kv_block_size=BS):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("moe", (CFG16, PARAMS16), kv_blocks=16)
        e.start()
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


@pytest.mark.parametrize("cfg,params", [(CFG, PARAMS), (CFG16, PARAMS16)],
                         ids=["f32", "bf16"])
def test_bundle_roundtrip(cfg, params, tmp_path):
    d = dm.save_decoder(str(tmp_path / "b"), cfg, params)
    got_cfg, got = dm.load_decoder(d)
    assert got_cfg.to_dict() == cfg.to_dict()
    assert set(got) == set(params)
    for k, v in params.items():
        assert got[k].dtype == v.dtype and got[k].shape == v.shape
        assert np.array_equal(got[k].view(np.uint8), v.view(np.uint8)), k


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


def test_serve_tool_writes_and_serves_an_olmoe_bundle(tmp_path, cache_dir):
    """tools/serve.py builds a demo bundle from a benchmark configuration
    file (its tiny sizes), and the engine serves that directory: tokens
    equal the unpaged loop's."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from serve import save_demo_decoder
    finally:
        sys.path.pop(0)
    d = save_demo_decoder(
        str(tmp_path / "dec"), config=os.path.join(
            ROOT, "benchmark", "configs", "olmoe-1b-7b-serve.json"))
    cfg, params = dm.load_decoder(d)
    assert (cfg.arch, cfg.dtype, cfg.kv_dtype) == ("olmoe", "bf16", "bf16")
    assert (cfg.experts, cfg.experts_per_token, cfg.ffn) == (8, 2, 32)
    assert dm.load_draft(d)[0].arch == "olmoe"
    with _flags(kv_block_size=BS):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("moe", d, kv_blocks=16, speculative_k=0)
    e.start()
    try:
        r = e.generate("moe", [5, 6, 7], max_new_tokens=6,
                       deadline_ms=60000.0)
        assert r.status == "ok", r.error
        assert np.array_equal(r.outputs["tokens"],
                              _unpaged(cfg, params, [5, 6, 7], 6))
    finally:
        e.stop()
