"""Autoregressive decode serving (serving/engine.py DecodeEngine +
decode_model.py + the wire protocol): bitwise parity of the paged step
against the unpaged reference loop, the zero-runtime-compile invariant
under mixed-length continuous batching, token-level join/leave
mid-batch, admission-time KV-pressure shed with a drain-time hint,
deterministic preemption-recompute, client abort, the streaming
``__generate__``/``__stream__`` wire path, client replay on server
timeout, int8 KV residency, the probe-gated Pallas paged-attention
funnel (interpret-mode parity), content-addressed prefix caching
(hit parity, abort safety, evictable-pool admission), and the
token-budget chunked-prefill scheduler."""

import contextlib
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import (DecodeEngine, ServingClient, ServingEngine,
                                ServingServer)
from paddle_tpu.utils import fault_injection
from paddle_tpu.serving.decode_model import (DecoderConfig,
                                             init_decoder_params,
                                             unpaged_generate)

CFG = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
PARAMS = init_decoder_params(CFG, seed=7)
BS = 4                      # FLAGS_kv_block_size for every engine here
PAD = 48                    # maxb(12) * BS: the paged step's context width


def _unpaged(prompt, max_new, eos_id=-1):
    return np.asarray(unpaged_generate(CFG, PARAMS, prompt, max_new,
                                       pad_len=PAD, eos_id=eos_id),
                      np.int32)


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
    """Tier-B disk cache shared by every engine in this module, so
    repeated (cfg, kv geometry) pairs restore instead of recompiling."""
    d = str(tmp_path_factory.mktemp("cc"))
    old = fluid.get_flags(["FLAGS_compile_cache_dir"])
    fluid.set_flags({"FLAGS_compile_cache_dir": d})
    yield d
    fluid.set_flags(old)


@pytest.fixture(scope="module")
def eng(cache_dir):
    """Started token-mode engine with a roomy pool, prewarmed."""
    with _flags(kv_block_size=BS, kv_cache_dtype="f32"):
        e = DecodeEngine(buckets="2,4", deadline_ms=30000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=64)
    e.prewarm()
    e.start()
    yield e
    e.stop()


@pytest.fixture()
def telemetry_on():
    fluid.set_flags({"FLAGS_telemetry": True})
    _tm.reset()
    yield
    _tm.reset()
    fluid.set_flags({"FLAGS_telemetry": False})


def _mkengine(cache_dir, kv_blocks, buckets="1", mode="token",
              source=(CFG, PARAMS), draft=None, speculative_k=None,
              **flag_kw):
    flag_kw.setdefault("kv_block_size", BS)
    with _flags(**flag_kw):
        e = DecodeEngine(buckets=buckets, mode=mode, deadline_ms=30000.0)
        e.add_model("toy", source, kv_blocks=kv_blocks, draft=draft,
                    speculative_k=speculative_k)
    return e.start()


# -- parity ------------------------------------------------------------------


def test_engine_bitwise_parity_vs_unpaged(cache_dir):
    e = _mkengine(cache_dir, 64)
    try:
        for prompt in ([1], [2, 3, 4], [5, 6, 7, 8, 9]):
            r = e.generate("toy", prompt, max_new_tokens=8,
                           deadline_ms=30000.0)
            assert r.status == "ok", r.error
            # greedy paged decode == the unpaged reference, bitwise
            assert np.array_equal(r.outputs["tokens"],
                                  _unpaged(prompt, 8)), prompt
            assert r.phases["prompt_tokens"] == len(prompt)
    finally:
        e.stop()


def test_eos_stops_early(cache_dir, eng):
    full = _unpaged([1, 2], 8)
    eos = int(full[2])
    r = eng.generate("toy", [1, 2], max_new_tokens=8, eos_id=eos,
                     deadline_ms=30000.0)
    assert r.status == "ok"
    assert np.array_equal(r.outputs["tokens"], full[:3])


# -- zero runtime compiles under mixed-length continuous batching ------------


def test_mixed_lengths_share_one_executable(eng, telemetry_on):
    prompts = [[1], [2, 3, 4], [5, 6], [7, 8, 9, 10, 11]]
    miss0 = _tm.counter_total("executor_cache_miss_total")
    reqs = [eng.submit("toy", p, max_new_tokens=6, deadline_ms=30000.0)
            for p in prompts]
    replies = [r.wait(timeout=60.0) for r in reqs]
    assert all(r is not None and r.status == "ok" for r in replies)
    for p, r in zip(prompts, replies):
        assert np.array_equal(r.outputs["tokens"], _unpaged(p, 6)), p
    # the invariant: mixed lengths + mixed phases hit the prewarmed
    # executables only — no runtime XLA compile
    assert _tm.counter_total("executor_cache_miss_total") == miss0
    assert _tm.counter_total("serving_tokens_generated_total") == 24
    snap = _tm.snapshot()
    occ = [v for k, v in snap["histograms"].items()
           if k.startswith("decode_batch_occupancy")]
    assert occ and sum(h["count"] for h in occ) > 0
    # every sequence finished: its blocks went back the same step
    assert eng._models["toy"].cache.allocator.in_use == 0


def test_streaming_phases_and_on_token(eng):
    got = []
    r = eng.generate("toy", [4, 5], max_new_tokens=5,
                     deadline_ms=30000.0,
                     on_token=lambda rid, i, tok, done, st:
                     got.append((i, tok, done, st)))
    assert r.status == "ok"
    assert [g[1] for g in got] == list(r.outputs["tokens"])
    assert got[-1][2] is True and all(g[3] == "ok" for g in got)
    assert r.phases["tokens"] == 5 and r.phases["ttft_ms"] > 0
    assert len(r.phases["itl_ms_samples"]) == 4
    assert r.phases["queue_wait_ms"] >= 0


# -- token-level join/leave --------------------------------------------------


def test_join_and_leave_mid_batch(eng):
    started = threading.Event()
    order = []
    # every iteration takes 100 ms until B is done: A's 40 sub-millisecond
    # steps would otherwise all land before this thread submits B.  (No
    # firing count: every idle engine's loop checks the same fault point.)
    fault_injection.arm("serving.decode_step:delay:1")
    try:
        ra = eng.submit("toy", [1, 2], max_new_tokens=40,
                        deadline_ms=30000.0,
                        callback=lambda r: order.append("A"),
                        on_token=lambda *a: started.set())
        assert started.wait(20.0), "long sequence never produced a token"
        rb = eng.submit("toy", [3], max_new_tokens=2, deadline_ms=30000.0,
                        callback=lambda r: order.append("B"))
        b = rb.wait(timeout=60.0)
    finally:
        fault_injection.disarm()
    a = ra.wait(timeout=60.0)
    assert a.status == "ok" and b.status == "ok"
    # B joined the running batch and LEFT it while A kept decoding
    assert order == ["B", "A"]
    assert len(a.outputs["tokens"]) == 40
    assert np.array_equal(b.outputs["tokens"], _unpaged([3], 2))


def test_abort_queued_and_active(eng, telemetry_on):
    # queued: submit under the scheduler lock so the loop cannot admit
    # it before the abort lands
    with eng._cond:
        rq = eng.submit("toy", [1], max_new_tokens=4, deadline_ms=30000.0)
        assert eng.abort(rq.req_id)
    assert rq.wait(timeout=10.0).status == "aborted"
    # active: abort mid-decode frees the blocks.  The toy step takes well
    # under a millisecond, so all 40 tokens can land before this thread
    # wakes: slow the loop (100 ms per iteration) while the abort races it
    started = threading.Event()
    fault_injection.arm("serving.decode_step:delay:1")
    try:
        ra = eng.submit("toy", [1, 2], max_new_tokens=40,
                        deadline_ms=30000.0,
                        on_token=lambda *a: started.set())
        assert started.wait(20.0)
        assert eng.abort(ra.req_id)
    finally:
        fault_injection.disarm()
    assert ra.wait(timeout=10.0).status == "aborted"
    deadline = time.time() + 5
    while time.time() < deadline and \
            eng._models["toy"].cache.allocator.in_use:
        time.sleep(0.01)
    assert eng._models["toy"].cache.allocator.in_use == 0
    assert _tm.counter_total("serving_abort_total") >= 2


# -- admission control -------------------------------------------------------


def test_submit_validation_errors(eng):
    assert eng.generate("nope", [1]).status == "error"
    assert eng.generate("toy", []).status == "error"
    r = eng.generate("toy", [1], max_new_tokens=99)
    assert r.status == "error" and "max_seq" in r.error
    assert eng.generate("toy", [31]).status == "error"


def test_kv_pressure_sheds_with_retry_hint(cache_dir, telemetry_on):
    e = _mkengine(cache_dir, 3)          # capacity 2 beside the scratch
    try:
        # sequence needing more blocks than the pool holds is an error,
        # not a shed — retrying can never admit it
        r = e.generate("toy", [1] * 9, max_new_tokens=8)
        assert r.status == "error" and "pool holds" in r.error
        # under the lock: A's promised prompt blocks + B's exceed the
        # free pool, so B sheds at admission with a drain-time hint
        with e._cond:
            ra = e.submit("toy", [1] * 5, max_new_tokens=3,
                          deadline_ms=30000.0)
            rb = e.submit("toy", [2] * 4, max_new_tokens=4,
                          deadline_ms=30000.0)
        assert rb.reply.status == "shed"
        assert "KV pool" in rb.reply.error
        assert rb.reply.retry_after_ms >= 1.0
        assert _tm.counter_total("serving_shed_total") == 1
        a = ra.wait(timeout=60.0)
        assert a.status == "ok"
        assert np.array_equal(a.outputs["tokens"], _unpaged([1] * 5, 3))
    finally:
        e.stop()


def test_preemption_recompute_is_deterministic(cache_dir, telemetry_on):
    # capacity 3: A wants 3 blocks (12 tokens), B wants 2 (8 tokens) —
    # 5 > 3 forces mid-decode preemption; greedy recompute must re-emit
    # identical tokens
    e = _mkengine(cache_dir, 4, buckets="2")
    try:
        with e._cond:       # both admitted at the same iteration boundary
            ra = e.submit("toy", [1, 2, 3, 4], max_new_tokens=8,
                          deadline_ms=30000.0)
            rb = e.submit("toy", [5, 6, 7, 8], max_new_tokens=4,
                          deadline_ms=30000.0)
        a = ra.wait(timeout=60.0)
        b = rb.wait(timeout=60.0)
        assert a is not None and a.status == "ok", a and a.error
        assert b is not None and b.status == "ok", b and b.error
        assert np.array_equal(a.outputs["tokens"],
                              _unpaged([1, 2, 3, 4], 8))
        assert np.array_equal(b.outputs["tokens"],
                              _unpaged([5, 6, 7, 8], 4))
        assert _tm.counter_total("kv_block_evictions_total") >= 1
        assert e._models["toy"].cache.allocator.in_use == 0
    finally:
        e.stop()


# -- the step's write ----------------------------------------------------------


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_step_writes_one_row_per_lane_and_nothing_else(dtype):
    """The feed-planning contract, on the pool itself: a step writes row
    ``(block_tables[b, pos // bs], pos % bs)`` of every layer's pools for
    each live lane, row (0, 0) of the scratch block for the idle lanes,
    and leaves every other row of every pool bit-identical."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.decode_model import make_paged_step
    from paddle_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

    kv = KVCacheConfig(CFG.layers, CFG.heads, CFG.head_dim, BS, 16, dtype)
    cache = PagedKVCache(kv)
    rng = np.random.RandomState(11)
    before = [np.asarray(rng.randint(-90, 90, a.shape), str(a.dtype))
              for a in cache.carry()]
    maxb = CFG.max_seq // BS
    tables = np.full((4, maxb), -1, np.int32)
    tables[0, :3] = [5, 9, 2]          # lane 0 at pos 6: block 9, row 2
    tables[1, :1] = [7]                # lane 1 at pos 0: block 7, row 0
    tables[3, :2] = [3, 12]            # lane 3 at pos 7: block 12, row 3
    pos = np.asarray([6, 0, 0, 7], np.int32)          # lane 2 is idle
    lens = np.asarray([7, 1, 0, 8], np.int32)
    step = jax.jit(make_paged_step(CFG, kv), donate_argnums=(0,))
    carry, _nxt, _logits = step(
        tuple(jnp.asarray(a) for a in before),
        {k: jnp.asarray(v) for k, v in PARAMS.items()},
        np.asarray([3, 4, 0, 5], np.int32), pos, tables, lens)
    written = {(9, 2), (7, 0), (12, 3)}
    assert len(carry) == len(before) == (4 if dtype == "int8" else 2) * 2
    for got, was in zip(carry, before):
        got = np.asarray(got)
        changed = {(int(b), int(r)) for b, r in
                   zip(*np.nonzero((got != was).any(axis=-1)))}
        # random rows never equal what the model writes, so every written
        # row shows; the scratch row may or may not differ
        assert written <= changed <= written | {(0, 0)}, changed


# -- int8 KV residency -------------------------------------------------------


def test_int8_residency_generates(cache_dir):
    e = _mkengine(cache_dir, 16, kv_cache_dtype="int8")
    try:
        assert e.spec("toy")["kv_dtype"] == "int8"
        m = e._models["toy"]
        assert len(m.cache.carry()) == 4 * m.cfg.layers
        r = e.generate("toy", [1, 2, 3], max_new_tokens=4,
                       deadline_ms=30000.0)
        assert r.status == "ok"
        toks = r.outputs["tokens"]
        assert len(toks) == 4 and all(0 <= t < 31 for t in toks)
    finally:
        e.stop()


def test_prewarm_reports_the_step_memory(cache_dir, telemetry_on):
    """Prewarm says what each compiled step holds beside its arguments and
    how much of them it updates in their own buffers: in place means the
    whole KV pool is aliased."""
    e = _mkengine(cache_dir, 512, buckets="2", kv_cache_dtype="f32")
    try:
        e.prewarm()
        gauges = _tm.snapshot()["gauges"]
        labels = "{bucket=2,fn=decode,model=toy}"
        alias = gauges["serving_step_alias_bytes" + labels]
        temp = gauges["serving_step_temp_bytes" + labels]
        pool = e._models["toy"].cache.nbytes
        assert gauges["kv_cache_bytes"] == pool
        assert alias >= pool and temp < pool / 4
        # a second prewarm is a memory hit and says the same
        e.prewarm()
        assert _tm.snapshot()["gauges"][
            "serving_step_alias_bytes" + labels] == alias
    finally:
        e.stop()


# -- wire protocol -----------------------------------------------------------


def test_generate_over_the_wire_stream_and_not(cache_dir):
    with _flags(kv_block_size=BS, kv_cache_dtype="f32"):
        e = DecodeEngine(buckets="2", deadline_ms=30000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=64)
    srv = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        cli = ServingClient(endpoints=["127.0.0.1:%d" % srv.port])
        spec = cli.spec("toy")
        assert spec["type"] == "decode" and spec["block_size"] == BS
        want = _unpaged([2, 3], 5)
        r = cli.generate("toy", [2, 3], max_new_tokens=5,
                         deadline_ms=30000.0, stream=False)
        assert r.status == "ok" and np.array_equal(r.outputs["tokens"],
                                                   want)
        seen = []
        r = cli.generate("toy", [2, 3], max_new_tokens=5,
                         deadline_ms=30000.0, stream=True,
                         on_token=lambda i, t: seen.append(t))
        assert r.status == "ok" and seen == list(want)
        # wire-inclusive client-side latency attribution
        assert r.phases["client_ttft_ms"] > 0
        assert len(r.phases["client_itl_ms_samples"]) == 4
        chunks = list(cli.generate_stream("toy", [2, 3], max_new_tokens=5,
                                          deadline_ms=30000.0))
        assert [t for _, t in chunks] == list(want)
        # streaming error terminal chunk: bad model doesn't hang
        assert cli.generate("zzz", [1], deadline_ms=4000.0).status \
            == "error"
    finally:
        srv.shutdown()


def test_client_replays_on_server_timeout(cache_dir):
    """Replica A (request mode) is busy with a long generation, so the
    client's request expires in A's queue; the server's timeout reply
    must trigger replay on replica B, which answers correctly."""
    big = DecoderConfig(vocab=31, layers=6, heads=4, head_dim=32,
                        max_seq=512)
    ea = _mkengine(cache_dir, 140, mode="request",
                   source=(big, init_decoder_params(big, seed=3)))
    eb = _mkengine(cache_dir, 64)
    sa = ServingServer(ServingEngine(), port=0, decode_engine=ea).start()
    sb = ServingServer(ServingEngine(), port=0, decode_engine=eb).start()
    try:
        # request mode runs one sequence at a time: three queued 500-token
        # generations keep A busy for well past the client's deadline
        busy = [ea.submit("toy", [1, 2], max_new_tokens=500,
                          deadline_ms=120000.0) for _ in range(3)]
        deadline = time.time() + 20
        while time.time() < deadline and not ea._active:
            time.sleep(0.01)
        assert ea._active, "busy sequence never admitted"
        cli = ServingClient(endpoints=["127.0.0.1:%d" % sa.port,
                                       "127.0.0.1:%d" % sb.port])
        r = cli.generate("toy", [9, 8, 7], max_new_tokens=4,
                         deadline_ms=300.0)
        assert r.status == "ok", (r.status, r.error)
        assert cli.failovers >= 1
        assert np.array_equal(r.outputs["tokens"], _unpaged([9, 8, 7], 4))
        for b in busy:
            ea.abort(b.req_id)
    finally:
        sa.shutdown()
        sb.shutdown()


# -- Pallas paged-attention funnel -------------------------------------------
# (the kernel's own cases are in tests/test_paged_attention_kernel.py)


def _paged_fixture(rng, bb=2, blocks=4, bs=8, h=1, d=128, maxb=2):
    q = rng.randn(bb, h, d).astype(np.float32)
    k = rng.randn(blocks, bs, h * d).astype(np.float32)
    v = rng.randn(blocks, bs, h * d).astype(np.float32)
    tables = np.array([[1, 3], [2, -1]], np.int32)
    lens = np.array([12, 5], np.int32)
    return q, k, v, tables, lens


def test_paged_attention_interpret_parity(monkeypatch, telemetry_on):
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    adoption.reset()
    try:
        args = _paged_fixture(np.random.RandomState(0))
        out = np.asarray(pa.paged_attention(*args))
        ref = np.asarray(pa.paged_attention_reference(*args))
        # online-softmax accumulation vs one-shot softmax: allclose, and
        # the funnel adopted the kernel with no flag set
        assert np.allclose(out, ref, atol=1e-5), np.abs(out - ref).max()
        assert "paged_attention" in adoption.active_kernels()
        assert _tm.counter_total("pallas_kernel_used_total") >= 1
    finally:
        adoption.reset()


def test_paged_attention_funnel_falls_back_off_tpu(monkeypatch,
                                                   telemetry_on):
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET", raising=False)
    adoption.reset()
    try:
        args = _paged_fixture(np.random.RandomState(1))
        out = np.asarray(pa.paged_attention(*args))
        ref = np.asarray(pa.paged_attention_reference(*args))
        # CPU backend, no interpret: the funnel must refuse the kernel
        # and the jnp fallback is the reference itself
        assert np.array_equal(out, ref)
        assert adoption.active_kernels() == []
        assert [labels for _key, labels in _tm.label_sets(
            "pallas_kernel_fallback_total")] == [
                {"kernel": "paged_attention", "reason": "backend"}]
    finally:
        adoption.reset()


def test_paged_attention_checks_catch_bad_geometry():
    reasons = dict(pa.paged_attention_checks((2, 3, 64), (4, 8, 192),
                                             np.float32))
    assert reasons["lanes"] is False         # 192 % 128 != 0
    reasons = dict(pa.paged_attention_checks((2, 1, 128), (4, 6, 128),
                                             np.float32))
    assert reasons["block_size"] is False    # 6 % 8 != 0
    reasons = dict(pa.paged_attention_checks((2, 1, 128), (4, 8, 128),
                                             np.float16))
    assert reasons["dtype"] is False
    reasons = dict(pa.paged_attention_checks((2, 1, 128), (4, 8, 1, 128),
                                             np.float32))
    assert reasons["rank"] is False          # a head-split pool


# -- speculative decoding ----------------------------------------------------

from paddle_tpu.serving.decode_model import (has_draft, load_draft,  # noqa: E402
                                             save_decoder,
                                             truncate_decoder)

DRAFT = truncate_decoder(CFG, PARAMS, layers=1)


def _spec_engine(cache_dir, kv_blocks=64, buckets="2,4", k=3, **kw):
    return _mkengine(cache_dir, kv_blocks, buckets=buckets, draft=DRAFT,
                     speculative_k=k, **kw)


def test_spec_bitwise_parity_and_eos(cache_dir):
    e = _spec_engine(cache_dir)
    try:
        for prompt in ([1], [2, 3, 4], [5, 6, 7, 8, 9]):
            r = e.generate("toy", prompt, max_new_tokens=8,
                           deadline_ms=30000.0)
            assert r.status == "ok", r.error
            # accept-longest-prefix greedy verification == the plain
            # greedy chain, bitwise — speculation may only change speed
            assert np.array_equal(r.outputs["tokens"],
                                  _unpaged(prompt, 8)), prompt
        # an EOS inside an accepted run must truncate the emission
        full = _unpaged([1, 2], 8)
        eos = int(full[2])
        r = e.generate("toy", [1, 2], max_new_tokens=8, eos_id=eos,
                       deadline_ms=30000.0)
        assert r.status == "ok"
        assert np.array_equal(r.outputs["tokens"], full[:3])
        m = e._models["toy"]
        assert m.cache.allocator.in_use == 0
        assert m.draft_cache.allocator.in_use == 0
    finally:
        e.stop()


def test_spec_mixed_join_leave_parity_and_flat_misses(cache_dir,
                                                      telemetry_on):
    e = _spec_engine(cache_dir)
    try:
        e.prewarm()
        miss0 = _tm.counter_total("executor_cache_miss_total")
        # stagger submissions so sequences join a running speculative
        # batch and leave it at different iterations
        started = threading.Event()
        ra = e.submit("toy", [1, 2], max_new_tokens=12,
                      deadline_ms=30000.0,
                      on_token=lambda *a: started.set())
        assert started.wait(30.0)
        prompts = [[3], [4, 5, 6], [7, 8, 9, 10, 11]]
        reqs = [e.submit("toy", p, max_new_tokens=6, deadline_ms=30000.0)
                for p in prompts]
        a = ra.wait(timeout=60.0)
        replies = [r.wait(timeout=60.0) for r in reqs]
        assert a.status == "ok"
        assert np.array_equal(a.outputs["tokens"], _unpaged([1, 2], 12))
        for p, r in zip(prompts, replies):
            assert r is not None and r.status == "ok", p
            assert np.array_equal(r.outputs["tokens"], _unpaged(p, 6)), p
        # rollout/verify/ingest were all prewarmed per bucket: the
        # mixed join/leave traffic may not compile anything at runtime
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        prop = _tm.counter_total("spec_tokens_proposed_total")
        acc = _tm.counter_total("spec_tokens_accepted_total")
        assert prop > 0 and 0 < acc <= prop
        snap = _tm.snapshot()
        hist = [k for k in snap["histograms"]
                if k.startswith("spec_acceptance")]
        assert hist, "acceptance histogram missing"
    finally:
        e.stop()


def test_spec_rollback_returns_blocks_same_iteration(cache_dir,
                                                     telemetry_on):
    e = _spec_engine(cache_dir, kv_blocks=64, buckets="2")
    try:
        reqs = [e.submit("toy", p, max_new_tokens=10,
                         deadline_ms=30000.0)
                for p in ([1, 2, 3], [9, 8, 7, 6])]
        assert all(r.wait(timeout=60.0).status == "ok" for r in reqs)
        m = e._models["toy"]
        # every over-reserved block came back: nothing leaked in either
        # pool after the accepted-frontier trims + same-step frees
        assert m.cache.allocator.in_use == 0
        assert m.draft_cache.allocator.in_use == 0
        prop = _tm.counter_total("spec_tokens_proposed_total")
        acc = _tm.counter_total("spec_tokens_accepted_total")
        assert prop > 0 and acc <= prop
    finally:
        e.stop()


def test_spec_shed_mid_decode_keeps_decoding(cache_dir, telemetry_on):
    # pool sized so a deep-into-decode speculating A leaves no room for
    # B: B sheds at admission mid-speculation with a drain-time hint,
    # A's stream is untouched
    e = _spec_engine(cache_dir, kv_blocks=10, buckets="1", k=3)
    try:
        deep = threading.Event()

        def on_tok(rid, i, tok, done, st):
            if i >= 20 and not deep.is_set():
                # A holds >= 7 of the 9 usable blocks now; slow its
                # remaining iterations (100 ms each) so B arrives while
                # A still decodes — the toy step alone is sub-millisecond
                fault_injection.arm("serving.decode_step:delay:1")
                deep.set()

        ra = e.submit("toy", [1] * 5, max_new_tokens=30,
                      deadline_ms=30000.0, on_token=on_tok)
        assert deep.wait(60.0)      # A is actively speculating, deep in
        rb = e.submit("toy", [2] * 12, max_new_tokens=4,
                      deadline_ms=30000.0)
        b = rb.wait(timeout=30.0)
        fault_injection.disarm()
        assert b.status == "shed", b.status
        assert b.retry_after_ms >= 1.0
        assert _tm.counter_total("serving_shed_total") >= 1
        a = ra.wait(timeout=60.0)
        assert a.status == "ok"
        assert np.array_equal(a.outputs["tokens"], _unpaged([1] * 5, 30))
    finally:
        fault_injection.disarm()
        e.stop()


def test_spec_preemption_of_speculating_sequence(cache_dir, telemetry_on):
    # two speculating sequences over a pool too small for both peaks:
    # the youngest gets preempted MID-SPECULATION (draft + target blocks
    # freed together) and its deterministic recompute re-emits the
    # identical stream
    e = _spec_engine(cache_dir, kv_blocks=4, buckets="2", k=3)
    try:
        with e._cond:       # both admitted at the same iteration boundary
            ra = e.submit("toy", [1, 2, 3, 4], max_new_tokens=8,
                          deadline_ms=30000.0)
            rb = e.submit("toy", [5, 6, 7, 8], max_new_tokens=4,
                          deadline_ms=30000.0)
        a = ra.wait(timeout=60.0)
        b = rb.wait(timeout=60.0)
        assert a is not None and a.status == "ok", a and a.error
        assert b is not None and b.status == "ok", b and b.error
        assert np.array_equal(a.outputs["tokens"],
                              _unpaged([1, 2, 3, 4], 8))
        assert np.array_equal(b.outputs["tokens"],
                              _unpaged([5, 6, 7, 8], 4))
        assert _tm.counter_total("kv_block_evictions_total") >= 1
        m = e._models["toy"]
        assert m.cache.allocator.in_use == 0
        assert m.draft_cache.allocator.in_use == 0
    finally:
        e.stop()


def test_spec_decode_step_span_has_acceptance_attrs(cache_dir,
                                                    telemetry_on,
                                                    tmp_path):
    import glob
    import json as _json

    from paddle_tpu.core import tracing as _trc
    fluid.set_flags({"FLAGS_tracing": True,
                     "FLAGS_telemetry_dir": str(tmp_path)})
    try:
        e = _spec_engine(cache_dir)
        try:
            r = e.generate("toy", [1, 2, 3], max_new_tokens=8,
                           deadline_ms=30000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        recs = []
        for p in glob.glob(str(tmp_path / "trace-*.jsonl")):
            with open(p) as f:
                recs += [_json.loads(line) for line in f if line.strip()]
        spans = [s for s in recs if s.get("t") == "span"]
        steps = [s for s in spans
                 if s.get("name") == "serving.decode_step"
                 and (s.get("attrs") or {}).get("speculative")]
        assert steps, "no speculative decode_step span recorded"
        assert all("k_proposed" in s["attrs"] and "k_accepted" in s["attrs"]
                   for s in steps)
        step_ids = {x.get("sid") for x in steps}
        kids = {s.get("name") for s in spans
                if s.get("parent") in step_ids}
        # draft and verify phases are children of the step span
        assert "serving.verify" in kids
        assert "serving.draft" in kids
        # the flight recorder's breadcrumb names the lanes in flight, and
        # is written when the lane set changes, not once a step; the host
        # phases of the draft and verify calls are on the step span
        notes = [n for n in recs
                 if n.get("t") == "note" and n.get("kind") == "decode_step"]
        assert notes and all(n["req_ids"] for n in notes)
        assert len(notes) < len(steps)
        assert all({"serving.plan", "serving.dispatch", "serving.fetch",
                    "serving.emit"} <= set(s["attrs"]["phases"])
                   for s in steps)
    finally:
        _trc.reset()
        fluid.set_flags({"FLAGS_tracing": False,
                         "FLAGS_telemetry_dir": ""})


def test_draft_bundle_roundtrip_and_flag_gate(cache_dir, tmp_path):
    d = str(tmp_path / "bundle")
    save_decoder(d, CFG, PARAMS, draft=DRAFT)
    assert has_draft(d)
    dcfg, dparams = load_draft(d)
    assert dcfg.layers == 1 and dcfg.vocab == CFG.vocab
    assert dcfg.max_seq == CFG.max_seq
    assert set(dparams) < set(PARAMS) | {"embed", "pos_embed"}
    # a dir source auto-loads its bundled draft; FLAGS_speculative_k
    # turns speculation on without touching call sites
    with _flags(kv_block_size=BS, speculative_k=2):
        e = DecodeEngine(buckets="1", deadline_ms=30000.0)
        m = e.add_model("toy", d, kv_blocks=32)
    assert m.spec_k == 2 and e.spec("toy")["speculative_k"] == 2
    e.start()
    try:
        r = e.generate("toy", [3, 1, 4], max_new_tokens=6,
                       deadline_ms=30000.0)
        assert r.status == "ok"
        assert np.array_equal(r.outputs["tokens"], _unpaged([3, 1, 4], 6))
    finally:
        e.stop()
    # without a draft bundle, k is ignored: the model decodes plain
    with _flags(kv_block_size=BS, speculative_k=2):
        e2 = DecodeEngine(buckets="1", deadline_ms=30000.0)
        m2 = e2.add_model("toy", (CFG, PARAMS), kv_blocks=32)
    assert m2.spec_k == 0


def test_draft_vocab_mismatch_rejected(tmp_path):
    bad_cfg = DecoderConfig(vocab=7, layers=1, heads=2, head_dim=8,
                            max_seq=48)
    bad = (bad_cfg, init_decoder_params(bad_cfg, seed=1))
    with pytest.raises(ValueError, match="vocab"):
        save_decoder(str(tmp_path / "x"), CFG, PARAMS, draft=bad)
    with _flags(kv_block_size=BS):
        e = DecodeEngine(buckets="1", deadline_ms=30000.0)
        with pytest.raises(ValueError, match="vocab"):
            e.add_model("toy", (CFG, PARAMS), kv_blocks=16, draft=bad,
                        speculative_k=2)


# -- prefix caching ----------------------------------------------------------


def test_prefix_cache_hit_bitwise_parity_and_flat_miss(cache_dir,
                                                       telemetry_on):
    e = _mkengine(cache_dir, 64, buckets="2,4")
    try:
        e.prewarm()
        assert e.spec("toy")["prefix_cache"] is True
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]      # 11 tokens
        want = _unpaged(prompt, 8)
        r1 = e.generate("toy", prompt, max_new_tokens=8,
                        deadline_ms=30000.0)
        assert r1.status == "ok", r1.error
        assert r1.phases["cached_tokens"] == 0
        assert np.array_equal(r1.outputs["tokens"], want)
        # 2 prompt blocks ((11-1)//4) plus 2 history blocks: session
        # migration publishes the prompt ++ out chain too (18 fed // 4)
        assert _tm.counter_total("prefix_cache_blocks_published_total") \
            == 4
        miss0 = _tm.counter_total("executor_cache_miss_total")
        # the repeat skips both cached full prompt blocks, and the cached
        # entry path runs through the SAME prewarmed executables — a hit
        # may never trigger a runtime compile
        r2 = e.generate("toy", prompt, max_new_tokens=8,
                        deadline_ms=30000.0)
        assert r2.status == "ok" and r2.phases["cached_tokens"] == 8
        assert np.array_equal(r2.outputs["tokens"], want)
        assert _tm.counter_total("prefix_cache_hit_tokens_total") == 8
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        # shared prefix, different tail: still a hit, still bitwise
        p3 = prompt[:8] + [7, 7]
        r3 = e.generate("toy", p3, max_new_tokens=8, deadline_ms=30000.0)
        assert r3.status == "ok" and r3.phases["cached_tokens"] == 8
        assert np.array_equal(r3.outputs["tokens"], _unpaged(p3, 8))
        assert _tm.counter_total("executor_cache_miss_total") == miss0
    finally:
        e.stop()


def test_prefix_cache_off_is_bitwise_identical(cache_dir):
    """FLAGS_prefix_cache only changes speed: the same prompts produce
    byte-identical token streams with the index on and off."""
    prompts = ([2, 3, 4, 5, 6, 7], [2, 3, 4, 5, 8, 9], [2, 3, 4, 5, 6, 7])
    outs = []
    for on in (True, False):
        e = _mkengine(cache_dir, 64, buckets="2,4", prefix_cache=on)
        try:
            assert e.spec("toy")["prefix_cache"] is on
            assert (e._models["toy"].prefix is not None) is on
            outs.append([e.generate("toy", list(p), max_new_tokens=6,
                                    deadline_ms=30000.0).outputs["tokens"]
                         for p in prompts])
        finally:
            e.stop()
    for a, b in zip(*outs):
        assert np.array_equal(a, b)


def test_spec_prefix_cache_hit_parity(cache_dir, telemetry_on):
    # prefix hits compose with speculative decoding: the verify chain
    # starts past the cached tokens, parity and pool hygiene hold
    e = _spec_engine(cache_dir)
    try:
        prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13]
        want = _unpaged(prompt, 8)
        for i, want_cached in enumerate((0, 8)):
            r = e.generate("toy", prompt, max_new_tokens=8,
                           deadline_ms=30000.0)
            assert r.status == "ok", (i, r.error)
            assert r.phases["cached_tokens"] == want_cached
            assert np.array_equal(r.outputs["tokens"], want), i
        m = e._models["toy"]
        assert m.cache.allocator.in_use == 0
        assert m.draft_cache.allocator.in_use == 0
        # the draft pool never holds published blocks
        assert m.draft_cache.allocator.num_evictable == 0
    finally:
        e.stop()


def test_abort_mid_prefill_publishes_no_partial_block(cache_dir,
                                                      telemetry_on):
    """A client that disconnects mid-prefill frees its private tail
    blocks, and a partially-filled block is never published into the
    prefix index — only prompt blocks that were COMPLETELY fed before
    the abort may appear."""
    e = _mkengine(cache_dir, 64, buckets="1")
    try:
        m = e._models["toy"]
        prompt = [(i % 29) + 1 for i in range(40)]       # 10 blocks
        # 100 ms per iteration until the abort has landed: the 40
        # sub-millisecond prefill steps would otherwise be over before the
        # poll below catches one
        fault_injection.arm("serving.decode_step:delay:1")
        ra = e.submit("toy", prompt, max_new_tokens=4,
                      deadline_ms=30000.0)
        n_at_abort = None
        deadline = time.time() + 30
        while time.time() < deadline and n_at_abort is None:
            with e._cond:       # scheduler frozen at a step boundary
                for s in e._active:
                    if s.pending.req_id == ra.req_id and s.n_fed > 0:
                        assert s.in_prefill, "prefill already over"
                        n_at_abort = s.n_fed
                        assert e.abort(ra.req_id)
            time.sleep(0.0005)
        fault_injection.disarm()
        assert n_at_abort is not None, "never caught the seq mid-prefill"
        assert ra.wait(timeout=10.0).status == "aborted"
        # the index holds exactly the COMPLETELY fed blocks (mid-prefill
        # n_fed < 40, so at most 9 of the 10) — never a partial one
        assert len(m.prefix) == n_at_abort // BS
        deadline = time.time() + 5
        while time.time() < deadline and m.cache.allocator.in_use:
            time.sleep(0.01)
        # private tail blocks came back to the free list the same step;
        # published ones parked zero-ref in the evictable pool
        assert m.cache.allocator.in_use == 0
        assert m.cache.allocator.num_evictable == len(m.prefix)
    finally:
        fault_injection.disarm()
        e.stop()


def test_evictable_pool_counts_as_reclaimable_no_spurious_shed(
        cache_dir, telemetry_on):
    """Regression: with the free list empty-ish and the pool full of
    zero-ref cached blocks, admission must treat evictable blocks as
    reclaimable capacity instead of shedding."""
    e = _mkengine(cache_dir, 8, buckets="1")             # 7 usable blocks
    try:
        alloc = e._models["toy"].cache.allocator
        # 24-token prompt = 6 full prompt blocks + 1 decode block; on
        # finish all 6 prompt blocks (24//4, every one completely fed)
        # park sealed + evictable, the decode block returns to the free
        # list — free list is down to a single block
        pa_ = list(range(1, 25))
        r = e.generate("toy", pa_, max_new_tokens=2, deadline_ms=30000.0)
        assert r.status == "ok", r.error
        assert np.array_equal(r.outputs["tokens"], _unpaged(pa_, 2))
        deadline = time.time() + 5
        while time.time() < deadline and alloc.in_use:
            time.sleep(0.01)
        assert alloc.in_use == 0
        assert alloc.num_evictable == 6 and alloc.num_free == 1
        assert alloc.reclaimable == 7
        # B promises 3 prompt blocks: more than the free list holds,
        # fewer than free + evictable — the old num_free admission check
        # would shed here; reclaimable-based admission must not
        pb = [29, 28, 27, 26] * 3
        rb = e.generate("toy", pb, max_new_tokens=4, deadline_ms=30000.0)
        assert rb.status == "ok", (rb.status, rb.error)
        assert np.array_equal(rb.outputs["tokens"], _unpaged(pb, 4))
        assert _tm.counter_total("serving_shed_total") == 0
        # the allocation reclaimed LRU cached blocks and de-indexed them
        assert _tm.counter_total("prefix_cache_evictions_total") >= 1
    finally:
        e.stop()


# -- token-budget chunked prefill --------------------------------------------


def test_prefill_token_budget_bitwise_parity_and_flat_miss(cache_dir,
                                                           telemetry_on):
    """Four 20-token prompts admitted at once under a 2-token/iteration
    prefill budget: chunked admission is a pure scheduling change —
    outputs stay bitwise-identical and no new shapes compile."""
    e = _mkengine(cache_dir, 64, buckets="2,4")
    try:
        e.prewarm()
        miss0 = _tm.counter_total("executor_cache_miss_total")
        prompts = [[t] * 20 for t in (1, 2, 3, 4)]
        with _flags(decode_prefill_token_budget=2):
            with e._cond:       # all admitted the same iteration
                reqs = [e.submit("toy", p, max_new_tokens=6,
                                 deadline_ms=30000.0) for p in prompts]
            replies = [r.wait(timeout=60.0) for r in reqs]
        assert all(r is not None and r.status == "ok" for r in replies)
        for p, r in zip(prompts, replies):
            assert np.array_equal(r.outputs["tokens"], _unpaged(p, 6)), p[0]
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        assert e._models["toy"].cache.allocator.in_use == 0
    finally:
        e.stop()


def test_prefill_token_budget_spec_parity(cache_dir, telemetry_on):
    # same scheduling invariant on the speculative path: prefill chunks
    # are capped by the budget, decode lanes keep speculating, parity
    # holds for every stream
    e = _spec_engine(cache_dir, buckets="2,4")
    try:
        prompts = [[t] * 16 for t in (9, 8, 7)]
        with _flags(decode_prefill_token_budget=3):
            with e._cond:
                reqs = [e.submit("toy", p, max_new_tokens=5,
                                 deadline_ms=30000.0) for p in prompts]
            replies = [r.wait(timeout=60.0) for r in reqs]
        assert all(r is not None and r.status == "ok" for r in replies)
        for p, r in zip(prompts, replies):
            assert np.array_equal(r.outputs["tokens"], _unpaged(p, 5)), p[0]
        m = e._models["toy"]
        assert m.cache.allocator.in_use == 0
        assert m.draft_cache.allocator.in_use == 0
    finally:
        e.stop()
