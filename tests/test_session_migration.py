"""Live decode-session migration (serving/migrate.py + the engine's
export/commit/abort primitives and resume-aware admission): manifest
roundtrip including int8 scale payloads and spec-mode state,
adopt-then-resume bitwise parity against the uninterrupted twin, tail
partial-block seal/unseal (domain-separated digest, private install,
loud drop on mismatch), migrate-during-prefill rejected cleanly,
``drain(migrate=...)`` emptying a replica without drops while the
streaming client follows the session to its new home, double migration
loudly refused, and the client-side SIGKILL-between-chunks crash-resume
with index dedupe (no token delivered twice, none skipped)."""

import contextlib
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.serving import (DecodeEngine, ServingClient, ServingEngine,
                                ServingServer, tail_digest)
from paddle_tpu.serving.decode_model import (DecoderConfig,
                                             init_decoder_params,
                                             truncate_decoder,
                                             unpaged_generate)

CFG = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
PARAMS = init_decoder_params(CFG, seed=7)
DRAFT = truncate_decoder(CFG, PARAMS, layers=1)
BS = 4
PAD = 48
PROMPT = [1, 2, 3, 4, 5, 6, 7, 8, 9]


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


def _ctr(name, **labels):
    out = 0.0
    for key, v in _tm.snapshot()["counters"].items():
        if key.split("{")[0] != name:
            continue
        if all(("%s=%s" % (lk, lv)) in key for lk, lv in labels.items()):
            out += v
    return out


def _mkeng(dtype="f32", draft=None, k=None, kv_blocks=64, start=True):
    with _flags(kv_block_size=BS, kv_cache_dtype=dtype):
        e = DecodeEngine(buckets="2,4", deadline_ms=30000.0)
        e.add_model("toy", (CFG, PARAMS), kv_blocks=kv_blocks,
                    draft=draft, speculative_k=k)
    return e.start() if start else e


def _export_live(eng, prompt, max_new, after=5, want_tail=None, tries=10):
    """Submit one generation and export it mid-decode once ``after``
    tokens have streamed.  The export position keeps advancing between
    the trigger and the snapshot, so ``want_tail`` retries (aborting
    the boundary-position export, which re-queues and completes
    harmlessly) until the snapshot carries / omits the tail."""
    for _ in range(tries):
        seen = threading.Event()
        count = [0]

        def on_tok(rid, i, t, done, status):
            count[0] += 1
            if count[0] >= after:
                seen.set()

        pending = eng.submit("toy", prompt, max_new_tokens=max_new,
                             deadline_ms=30000.0, on_token=on_tok)
        assert seen.wait(30.0), "generation never streamed %d tokens" % after
        try:
            manifest, payloads = eng.export_session(pending.req_id)
        except ValueError:
            pending.wait(30.0)         # finished under us — try again
            continue
        has_tail = any(is_tail for _, _, _, is_tail in payloads)
        if want_tail is None or has_tail == want_tail:
            return pending, manifest, payloads
        assert eng.abort_migration(pending.req_id)
        pending.wait(30.0)
    raise AssertionError("no export with want_tail=%s in %d tries"
                         % (want_tail, tries))


def _adopt_and_resume(dst, manifest, payloads, corrupt_tail=False):
    """Destination half of the hand-off, engine-level (what the server's
    ``_on_session_block``/``_on_session`` do over the wire)."""
    resume_tail = None
    for pos, digest, arrays, is_tail in payloads:
        if is_tail:
            resume_tail = {
                "digest": "00" * 32 if corrupt_tail else digest,
                "valid": manifest["pos"] - pos * manifest["block_size"],
                "arrays": arrays}
        else:
            res = dst.adopt_kv_block(manifest["model"], digest, arrays)
            assert res in ("adopted", "cached"), res
    out = [int(t) for t in np.asarray(manifest["_out_arr"]).reshape(-1)]
    prompt = [int(t) for t in np.asarray(manifest["_prompt_arr"]).reshape(-1)]
    reply = dst.generate(manifest["model"], prompt,
                         max_new_tokens=manifest["max_new_tokens"],
                         deadline_ms=30000.0, eos_id=manifest["eos_id"],
                         resume_from=out, resume_tail=resume_tail)
    return reply, len(out)


# -- tail digest -------------------------------------------------------------


def test_tail_digest_domain_separated_from_chain():
    toks = [3, 1, 4, 1]
    seed = tail_digest(None, toks)
    assert len(seed) == 64 and seed != tail_digest(None, toks[:-1])
    prev = "ab" * 32
    chained = tail_digest(prev, toks)
    assert chained != seed
    # deterministic, and never equal for different ancestry
    assert chained == tail_digest(prev, toks)


# -- export manifest ---------------------------------------------------------


def test_export_manifest_fields_and_abort_requeues(cache_dir,
                                                   telemetry_on):
    eng = _mkeng()
    try:
        want = _unpaged(PROMPT, 24)
        pending, manifest, payloads = _export_live(eng, PROMPT, 24)
        pos = manifest["pos"]
        out = np.asarray(manifest["_out_arr"]).reshape(-1)
        assert manifest["req_id"] == pending.req_id
        assert manifest["model"] == "toy"
        assert manifest["block_size"] == BS
        assert manifest["dtype"] == "f32"
        assert manifest["max_new_tokens"] == 24
        assert manifest["eos_id"] == -1
        assert manifest["spec_k"] == 0
        assert manifest["deadline_ms"] > 0
        # position invariant: the last emitted token is always re-fed
        assert pos == len(PROMPT) + len(out) - 1
        assert len(manifest["digests"]) == pos // BS
        # emitted-so-far prefix is already the uninterrupted prefix
        assert np.array_equal(out, want[:len(out)])
        # one payload per full history block (+ tail when off-boundary),
        # each a full-block [k, v] slice pair
        nfull = pos // BS
        full = [p for p in payloads if not p[3]]
        tails = [p for p in payloads if p[3]]
        assert [p[0] for p in full] == list(range(nfull))
        assert [p[1] for p in full] == manifest["digests"]
        assert len(tails) == (1 if pos > nfull * BS else 0)
        for _, _, arrays, _ in payloads:
            assert len(arrays) == 2          # f32 residency: [k, v]
            assert all(a.dtype == np.float32 for a in arrays)
        if tails:
            j, td, _, _ = tails[0]
            assert j == nfull
            hist = (list(PROMPT) + [int(t) for t in out])[
                nfull * BS:pos]
            assert td == tail_digest(
                manifest["digests"][-1] if nfull else None, hist)
        # abort re-queues for deterministic local recompute: the reply
        # completes ok and bitwise-equal, with the kept tokens replayed
        assert eng.abort_migration(pending.req_id)
        reply = pending.wait(60.0)
        assert reply is not None and reply.status == "ok", reply
        assert np.array_equal(reply.outputs["tokens"], want)
        assert reply.phases.get("resumed_tokens") == len(out)
        m = eng._models["toy"]
        assert m.cache.allocator.in_use == 0
    finally:
        eng.stop()


def test_migrate_during_prefill_rejected_cleanly(cache_dir):
    eng = _mkeng()
    try:
        # holding the engine condition (an RLock: same-thread submit /
        # export re-enter) keeps the decode loop from admitting the
        # request, so it is deterministically queued with zero emitted
        # tokens — the snapshot would have no stable position: refuse
        # loudly, engine unperturbed
        with eng._cond:
            pending = eng.submit("toy", PROMPT, max_new_tokens=6,
                                 deadline_ms=30000.0)
            with pytest.raises(ValueError, match="in_prefill"):
                eng.export_session(pending.req_id)
        reply = pending.wait(60.0)
        assert reply is not None and reply.status == "ok", reply
        assert np.array_equal(reply.outputs["tokens"],
                              _unpaged(PROMPT, 6))
        with pytest.raises(ValueError, match="unknown"):
            eng.export_session(pending.req_id)
        with pytest.raises(ValueError, match="unknown"):
            eng.export_session("never-submitted")
    finally:
        eng.stop()


def test_double_migration_loudly_refused(cache_dir, telemetry_on):
    eng = _mkeng()
    try:
        pending, manifest, payloads = _export_live(eng, PROMPT, 24)
        rid = pending.req_id
        with pytest.raises(ValueError, match="already_migrating"):
            eng.export_session(rid)
        assert eng.commit_migration(rid, "127.0.0.1:1")
        reply = pending.wait(30.0)
        assert reply is not None and reply.status == "migrated"
        assert reply.phases.get("migrated_to") == "127.0.0.1:1"
        with pytest.raises(ValueError, match="already_migrated"):
            eng.export_session(rid)
        assert _ctr("kv_migrate_refused_total", reason="already_migrating") \
            == 1
        assert _ctr("kv_migrate_refused_total", reason="already_migrated") \
            == 1
        # a duplicate resume for a LIVE req_id is refused at admission
        live, _, _ = _export_live(eng, PROMPT, 24)
        # under the engine condition (an RLock: abort and submit re-enter)
        # the decode loop cannot finish the re-queued session before the
        # duplicate arrives: a submitter otherwise waits several of the
        # toy's sub-millisecond steps for the lock
        with eng._cond:
            assert eng.abort_migration(live.req_id)   # back in the scheduler
            dup = eng.submit("toy", PROMPT, max_new_tokens=24,
                             deadline_ms=30000.0, req_id=live.req_id,
                             resume_from=[5, 6]).wait(30.0)
        assert dup.status == "error" and "double migration" in dup.error
        assert _ctr("kv_migrate_refused_total", reason="duplicate") == 1
        assert live.wait(60.0).status == "ok"
    finally:
        eng.stop()


# -- adopt-then-resume parity ------------------------------------------------


def test_adopt_then_resume_bitwise_parity(cache_dir, telemetry_on):
    """The tentpole invariant: (manifest, blocks, tail) shipped to a
    cold peer continues the generation bitwise-identically, emitting
    exactly the not-yet-emitted suffix, with re-prefill strictly under
    one block."""
    src, dst = _mkeng(), _mkeng()
    try:
        want = _unpaged(PROMPT, 24)
        pending, manifest, payloads = _export_live(src, PROMPT, 24,
                                                   want_tail=True)
        reply, n_resumed = _adopt_and_resume(dst, manifest, payloads)
        assert reply.status == "ok", (reply.status, reply.error)
        assert np.array_equal(reply.outputs["tokens"], want)
        assert reply.phases["resumed_tokens"] == n_resumed
        # every full block matched AND the tail installed: the resume
        # re-fed exactly one position (the last emitted token)
        assert reply.phases["cached_tokens"] == manifest["pos"]
        assert manifest["pos"] - reply.phases["cached_tokens"] < BS
        assert _ctr("kv_migrate_resume_total", result="accepted") == 1
        src.commit_migration(pending.req_id, "dst")
        assert pending.wait(30.0).status == "migrated"
        for e in (src, dst):
            assert e._models["toy"].cache.allocator.in_use == 0
    finally:
        src.stop()
        dst.stop()


def test_manifest_roundtrip_int8_scales(cache_dir, telemetry_on):
    """int8 residency ships [k, v, k_scales, v_scales] per block and
    the resumed continuation equals the uninterrupted int8 twin."""
    src, dst = _mkeng(dtype="int8"), _mkeng(dtype="int8")
    try:
        ref = src.generate("toy", PROMPT, max_new_tokens=24,
                           deadline_ms=30000.0)
        assert ref.status == "ok", ref.error
        pending, manifest, payloads = _export_live(src, PROMPT, 24,
                                                   want_tail=True)
        assert manifest["dtype"] == "int8"
        for _, _, arrays, _ in payloads:
            assert len(arrays) == 4
            assert arrays[0].dtype == np.int8
            assert arrays[1].dtype == np.int8
        reply, _ = _adopt_and_resume(dst, manifest, payloads)
        assert reply.status == "ok", (reply.status, reply.error)
        assert np.array_equal(reply.outputs["tokens"],
                              ref.outputs["tokens"])
        assert reply.phases["cached_tokens"] == manifest["pos"]
        src.commit_migration(pending.req_id, "dst")
        pending.wait(30.0)
    finally:
        src.stop()
        dst.stop()


def test_spec_mode_state_rides_manifest(cache_dir, telemetry_on):
    """A speculative-decode session migrates mid-flight: the manifest
    carries spec_k, the destination (own draft) continues bitwise (spec
    accept-longest-prefix == greedy chain, so parity is the proof the
    restored state is coherent)."""
    src = _mkeng(draft=DRAFT, k=3)
    dst = _mkeng(draft=DRAFT, k=3)
    try:
        want = _unpaged(PROMPT, 24)
        pending, manifest, payloads = _export_live(src, PROMPT, 24)
        assert manifest["spec_k"] == 3
        reply, _ = _adopt_and_resume(dst, manifest, payloads)
        assert reply.status == "ok", (reply.status, reply.error)
        assert np.array_equal(reply.outputs["tokens"], want)
        src.commit_migration(pending.req_id, "dst")
        pending.wait(30.0)
        for e in (src, dst):
            m = e._models["toy"]
            assert m.cache.allocator.in_use == 0
            assert m.draft_cache.allocator.in_use == 0
    finally:
        src.stop()
        dst.stop()


# -- tail seal/unseal --------------------------------------------------------


def test_tail_mismatch_dropped_and_replayed(cache_dir, telemetry_on):
    """A stale/foreign tail must not be trusted: the resume drops it
    (counted), replays the sub-block suffix, and still lands bitwise."""
    src, dst = _mkeng(), _mkeng()
    try:
        want = _unpaged(PROMPT, 24)
        pending, manifest, payloads = _export_live(src, PROMPT, 24,
                                                   want_tail=True)
        reply, _ = _adopt_and_resume(dst, manifest, payloads,
                                     corrupt_tail=True)
        assert reply.status == "ok", (reply.status, reply.error)
        assert np.array_equal(reply.outputs["tokens"], want)
        nfull = manifest["pos"] // BS
        # full blocks matched, tail refused: re-prefill is the tail span
        assert reply.phases["cached_tokens"] == nfull * BS
        assert _ctr("kv_migrate_refused_total", reason="tail_mismatch") \
            == 1
        src.commit_migration(pending.req_id, "dst")
        pending.wait(30.0)
    finally:
        src.stop()
        dst.stop()


def test_warm_resume_skips_reprefill_via_history_index(cache_dir,
                                                       telemetry_on):
    """History-chain publication makes ANY warmed replica a cheap resume
    target with no transfer at all: a crash-resume (prompt + tokens the
    client holds) on a replica that served the same generation re-feeds
    less than one block."""
    eng = _mkeng()
    try:
        first = eng.generate("toy", PROMPT, max_new_tokens=12,
                             deadline_ms=30000.0)
        assert first.status == "ok", first.error
        toks = [int(t) for t in first.outputs["tokens"]]
        reply = eng.generate("toy", PROMPT, max_new_tokens=12,
                             deadline_ms=30000.0, resume_from=toks[:6])
        assert reply.status == "ok", (reply.status, reply.error)
        assert np.array_equal(reply.outputs["tokens"],
                              first.outputs["tokens"])
        pos = len(PROMPT) + 6 - 1
        assert reply.phases["resumed_tokens"] == 6
        # full history blocks below pos were matched from the replica's
        # own index — only the sub-block suffix was re-fed
        assert reply.phases["cached_tokens"] == (pos // BS) * BS
        assert pos - reply.phases["cached_tokens"] < BS
    finally:
        eng.stop()


# -- drain-by-migration over the wire ----------------------------------------


def _wait_live_decode(eng, timeout=30.0):
    """Block until some sequence is mid-decode (out of prefill, tokens
    emitted) — the earliest instant a migration export can succeed."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        with eng._cond:
            if any(s.out and not s.in_prefill for s in eng._active):
                return True
        time.sleep(0.002)
    return False


def test_drain_migrate_empties_without_drops(cache_dir, telemetry_on):
    """``drain(migrate=...)``: a retiring replica pushes its live
    session over the real ``__kvxfer__`` wire; the destination resumes;
    the STREAMING client follows the terminal "migrated" chunk to the
    new home and sees one gapless, dup-free token sequence, bitwise
    equal to the uninterrupted reference."""
    ea, eb = _mkeng(), _mkeng()
    sb = ServingServer(ServingEngine(), port=0, decode_engine=eb).start()
    sa = ServingServer(ServingEngine(), port=0, decode_engine=ea,
                       decode_peers=["127.0.0.1:%d" % sb.port]).start()
    try:
        assert sa.migrator is not None and sb._resume_buf is not None
        cli = ServingClient(endpoints=["127.0.0.1:%d" % sa.port])
        want = _unpaged(PROMPT, 32)
        got, res = [], {}

        def run():
            gen = cli.generate_stream("toy", PROMPT, max_new_tokens=32,
                                      deadline_ms=30000.0)
            while True:
                try:
                    got.append(next(gen))
                except StopIteration as stop:
                    res["r"] = stop.value
                    return

        th = threading.Thread(target=run, daemon=True)
        th.start()
        assert _wait_live_decode(ea)
        assert ea.drain(timeout_s=60.0,
                        migrate=sa.migrator.drain_push(trigger="drain"))
        th.join(60.0)
        assert not th.is_alive(), "client never finished"
        r = res["r"]
        assert r.status == "ok", (r.status, r.error)
        assert np.array_equal(r.outputs["tokens"], want)
        # gapless, dup-free delivery across the hop
        assert [i for i, _ in got] == list(range(len(got)))
        assert [t for _, t in got] == [int(t) for t in want]
        assert _ctr("kv_migrate_sessions_total", trigger="drain") == 1
        assert _ctr("kv_migrate_resume_total", result="accepted") == 1
        assert _ctr("kv_migrate_failed_total") == 0
        # destination re-prefilled less than one block
        pos = len(PROMPT) + r.phases["resumed_tokens"] - 1
        assert pos - r.phases["cached_tokens"] < BS
        # the source really emptied (nothing waited out, nothing dropped)
        with ea._cond:
            assert not ea._active and not ea._waiting \
                and not ea._migrating
        assert ea._models["toy"].cache.allocator.in_use == 0
    finally:
        sa.shutdown()
        sb.shutdown()


# -- SIGKILL between chunks: crash-resume + stream dedupe --------------------


_DECODE_CHILD = """
import sys, time
import paddle_tpu as fluid
from paddle_tpu.serving import DecodeEngine, ServingEngine, ServingServer
from paddle_tpu.serving.decode_model import DecoderConfig, \\
    init_decoder_params

fluid.set_flags({"FLAGS_kv_block_size": 4, "FLAGS_kv_cache_dtype": "f32",
                 "FLAGS_compile_cache_dir": sys.argv[1]})
cfg = DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
ed = DecodeEngine(buckets="2,4", deadline_ms=30000.0)
ed.add_model("toy", (cfg, init_decoder_params(cfg, seed=7)), kv_blocks=64)
s = ServingServer(ServingEngine(), port=0, decode_engine=ed).start()
print("PORT %d" % s.port, flush=True)
time.sleep(600)
"""


def test_sigkill_between_chunks_resumes_with_index_dedupe(cache_dir):
    """Satellite regression: the replica serving a stream is SIGKILLed
    between chunks.  The client re-submits ``__resume__`` with the
    tokens it holds to the survivor (same req_id, no fresh-prefill
    replay) and keeps delivering — on_token/generate_stream must see
    every index exactly once, in order, bitwise equal to the
    uninterrupted reference."""
    import os
    import signal
    import subprocess
    import sys

    child = subprocess.Popen(
        [sys.executable, "-c", _DECODE_CHILD, cache_dir],
        stdout=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    sv = None
    try:
        line = child.stdout.readline().decode()
        assert line.startswith("PORT "), line
        vport = int(line.split()[1])
        es = _mkeng()
        sv = ServingServer(ServingEngine(), port=0,
                           decode_engine=es).start()
        # victim FIRST: the round-robin lands attempt 0 on the child
        cli = ServingClient(endpoints=["127.0.0.1:%d" % vport,
                                       "127.0.0.1:%d" % sv.port])
        want = _unpaged(PROMPT, 32)
        got = []
        got_first = threading.Event()
        killer = threading.Thread(
            target=lambda: (got_first.wait(60.0),
                            child.send_signal(signal.SIGKILL)),
            daemon=True)
        killer.start()

        def on_token(i, t):
            got.append((i, t))
            got_first.set()

        r = cli.generate("toy", PROMPT, max_new_tokens=32,
                         deadline_ms=30000.0, stream=True,
                         on_token=on_token)
        killer.join(60.0)
        assert got_first.is_set(), "victim never streamed a token"
        assert child.poll() is not None, "victim still alive"
        assert r.status == "ok", (r.status, r.error)
        assert np.array_equal(r.outputs["tokens"], want)
        assert cli.failovers >= 1
        # resume, not blind replay: the reply attributes replayed tokens
        assert r.phases.get("resumed_tokens", 0) >= 1
        # the dedupe contract: every index exactly once, in order
        assert [i for i, _ in got] == list(range(len(got)))
        assert [t for _, t in got] == [int(t) for t in want]
    finally:
        if child.poll() is None:
            child.kill()
        child.stdout.close()
        child.wait(30.0)
        if sv is not None:
            sv.shutdown()
