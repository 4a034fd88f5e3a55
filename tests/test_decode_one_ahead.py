"""The decode loop one step ahead (serving/engine.py): step n+1 is
dispatched before step n's tokens are read, and those tokens stay on the
device (``decode_model.make_fed_step``).  Most tests here drive the loop by
hand (``DecodeEngine._loop_once`` with no thread started), so "while a step
is in flight" is a state the test puts the engine in and asserts, not a race
it hopes to win: streams bitwise equal to ``unpaged_generate`` for requests
that are admitted, finish by ``max_new``, finish by ``eos_id``, are aborted,
expire and are preempted with a step in flight, on the three decoder blocks;
the discard counter; the drain rule (``export_session`` mid-stream); one
executable an engine step and no compile after prewarm; the synchronous path
a speculating model keeps; the span's ``ahead`` and ``gap_us``."""

import glob
import json
import time

import numpy as np
import pytest

import decoder_families as fam
import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.utils import fault_injection

BS = fam.BS
# three blocks: attention alone, routed experts, a recurrent state by slot
MODELS = {arch: fam.ROWS[arch].f32
          for arch in ("gpt2", "olmoe", "granite_hybrid")}
GPT2 = MODELS["gpt2"][0]
pytestmark = pytest.mark.usefixtures("cache_dir")
_flags = fam.flags
PA, PB, PC = [1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11]


def _unpaged(arch, prompt, max_new, eos_id=-1):
    cfg, params = MODELS[arch]
    return [int(t) for t in dm.unpaged_generate(
        cfg, params, prompt, max_new, pad_len=cfg.max_seq, eos_id=eos_id)]


def _ctr(name, **labels):
    out = 0.0
    for key, v in _tm.snapshot()["counters"].items():
        if key.split("{")[0] != name:
            continue
        if all(("%s=%s" % (lk, lv)) in key for lk, lv in labels.items()):
            out += v
    return out


def _discarded(**labels):
    return _ctr("serving_lane_steps_discarded_total", **labels)


def _engine(arch, kv_blocks=64, buckets="2,4", threaded=False, **kw):
    """A decode engine for ``arch``; hand-driven unless ``threaded``: it
    counts as running, and the test makes every iteration itself."""
    cfg, params = MODELS[arch]
    with _flags(kv_block_size=BS, kv_cache_dtype="f32"):
        e = DecodeEngine(buckets=buckets, deadline_ms=60000.0)
        e.add_model("m", (cfg, params), kv_blocks=kv_blocks, **kw)
    if threaded:
        return e.start()
    e._running = True
    return e


class _Stream:
    """A submitted request and the tokens its ``on_token`` saw."""

    def __init__(self, e, prompt, max_new, **kw):
        self.tokens = []
        self.pending = e.submit(
            "m", prompt, max_new_tokens=max_new, deadline_ms=60000.0,
            on_token=lambda rid, i, t, done, status:
                self.tokens.append(t) if t is not None else None, **kw)
        self.req_id = self.pending.req_id

    @property
    def reply(self):
        return self.pending.reply

    def seq(self, e):
        return next(s for s in e._active
                    if s.pending.req_id == self.req_id)

    def in_flight(self, e):
        """Is a step that took this request's lane dispatched and not yet
        read?"""
        f = e._flight
        return f is not None and any(
            s.pending.req_id == self.req_id and s.n_disp > s.n_fed
            for s in f.lanes)


def _drive(e, until, limit=600):
    for _ in range(limit):
        if until():
            return
        assert e._loop_once()
    raise AssertionError("the loop never got there")


def _finish(e, *streams):
    _drive(e, lambda: all(s.reply is not None for s in streams))


def _idle(e):
    m = e._models["m"]
    assert e._flight is None and not e.in_batch
    assert m.cache.allocator.in_use == 0
    if m.cache.slots is not None:
        assert m.cache.slots.in_use == 0


# -- streams with a step in flight ------------------------------------------


def _case_admitted(e, arch):
    a = _Stream(e, PA, 10)
    _drive(e, lambda: len(a.tokens) >= 2 and a.in_flight(e))
    b = _Stream(e, PB, 6)           # arrives while A's step is on the device
    _finish(e, a, b)
    assert a.reply.ok and b.reply.ok
    assert list(a.reply.outputs["tokens"]) == _unpaged(arch, PA, 10)
    assert list(b.reply.outputs["tokens"]) == _unpaged(arch, PB, 6)
    assert a.tokens == _unpaged(arch, PA, 10)
    assert _discarded() == 0


def _case_max_new(e, arch):
    a, b = _Stream(e, PA, 7), _Stream(e, PB, 3)
    _finish(e, a, b)
    assert list(a.reply.outputs["tokens"]) == _unpaged(arch, PA, 7)
    assert list(b.reply.outputs["tokens"]) == _unpaged(arch, PB, 3)
    # max_new is known at dispatch: the last token's lane is not planned
    # again, so nothing was in flight to drop
    assert _discarded() == 0


def _case_eos(e, arch):
    full = _unpaged(arch, PA, 10)
    k = next(i for i in range(2, 9) if full[i] not in full[:i])
    a = _Stream(e, PA, 10, eos_id=full[k])
    b = _Stream(e, PB, 10)
    _drive(e, lambda: a.reply is not None)
    # the step after the one that produced eos was already dispatched
    # with A's lane in it: one wasted lane-step, dropped when it is read
    assert _discarded(reason="eos") == 1
    c = _Stream(e, PC, 5)           # takes over what A freed
    _finish(e, b, c)
    assert list(a.reply.outputs["tokens"]) == full[:k + 1] \
        == _unpaged(arch, PA, 10, eos_id=full[k])
    assert list(b.reply.outputs["tokens"]) == _unpaged(arch, PB, 10)
    assert list(c.reply.outputs["tokens"]) == _unpaged(arch, PC, 5)
    assert _discarded() == 1


def _case_gone(e, arch, reason):
    """A leaves its lane (aborted by its client, or past its deadline)
    while a step that took it is in flight."""
    a, b = _Stream(e, PA, 12), _Stream(e, PB, 8)
    _drive(e, lambda: len(a.tokens) >= 3 and a.in_flight(e))
    seen = list(a.tokens)
    if reason == "aborted":
        assert e.abort(a.req_id)
    else:
        a.seq(e).pending.deadline = time.perf_counter() - 1.0
    assert a.in_flight(e)           # abort only marks
    _drive(e, lambda: a.reply is not None)
    assert a.reply.status == ("aborted" if reason == "aborted"
                              else "timeout")
    # the token in flight was dropped, not streamed after the fact
    assert a.tokens == seen == _unpaged(arch, PA, 12)[:len(seen)]
    assert _discarded(reason=reason) == 1
    c = _Stream(e, PC, 5)
    _finish(e, b, c)
    assert list(b.reply.outputs["tokens"]) == _unpaged(arch, PB, 8)
    assert list(c.reply.outputs["tokens"]) == _unpaged(arch, PC, 5)
    assert _discarded() == 1


def _case_preempted(e, arch):
    # capacity 3: A wants 3 blocks (12 tokens), B wants 2 (8 tokens): the
    # younger is preempted mid-decode with its step in flight, and its
    # recompute re-emits the same tokens
    with e._cond:
        a, b = _Stream(e, PA, 8), _Stream(e, PB, 4)
    _finish(e, a, b)
    assert a.reply.ok and b.reply.ok
    assert list(a.reply.outputs["tokens"]) == a.tokens \
        == _unpaged(arch, PA, 8)
    assert list(b.reply.outputs["tokens"]) == b.tokens \
        == _unpaged(arch, PB, 4)
    assert _tm.counter_total("kv_block_evictions_total") >= 1
    assert _discarded(reason="preempted") >= 1
    assert _discarded() == _discarded(reason="preempted")


CASES = {
    "admitted": (_case_admitted, {}),
    "max_new": (_case_max_new, {}),
    "eos": (_case_eos, {}),
    "aborted": (lambda e, arch: _case_gone(e, arch, "aborted"), {}),
    "expired": (lambda e, arch: _case_gone(e, arch, "expired"), {}),
    "preempted": (_case_preempted, {"kv_blocks": 4, "buckets": "2"}),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("arch", list(MODELS))
def test_streams_bitwise_with_a_step_in_flight(arch, case, telemetry_on):
    run, kw = CASES[case]
    e = _engine(arch, **kw)
    try:
        run(e, arch)
        _drive(e, lambda: e._flight is None and not e._active)
        _idle(e)
    finally:
        e.stop()


def test_one_ahead_is_the_steady_state(telemetry_on):
    """With work to do, every iteration but the first dispatches before
    it reads: a step is in flight whenever the lock is free."""
    e = _engine("gpt2")
    try:
        a = _Stream(e, PA, 10)
        assert e._loop_once() and e._flight is not None
        assert a.tokens == [] and a.seq(e).n_disp == 1 \
            and a.seq(e).n_fed == 0
        while a.reply is None:
            seq = a.seq(e)
            assert e._flight is not None and e.in_batch
            assert seq.n_disp == seq.n_fed + 1
            assert e._loop_once()
        assert a.tokens == _unpaged("gpt2", PA, 10)
        _idle(e)
    finally:
        e.stop()


# -- the drain rule ----------------------------------------------------------


def test_export_session_mid_stream_drains_and_resumes(telemetry_on):
    """``export_session`` with a step in flight reads it back first: the
    manifest's position and tokens are exact, and the session resumes on
    a peer to the same stream."""
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    want = _unpaged("gpt2", prompt, 24)
    src, dst = _engine("gpt2"), _engine("gpt2", threaded=True)
    try:
        a = _Stream(src, prompt, 24)
        _drive(src, lambda: len(a.tokens) >= 6 and a.in_flight(src))
        before = len(a.tokens)
        manifest, payloads = src.export_session(a.req_id)
        assert src._flight is None          # read back, then snapshotted
        out = [int(t) for t in manifest["_out_arr"]]
        assert len(out) == before + 1 and out == want[:len(out)] == a.tokens
        assert manifest["pos"] == len(prompt) + len(out) - 1
        tail = None
        for pos, digest, arrays, is_tail in payloads:
            if is_tail:
                tail = {"digest": digest, "arrays": arrays,
                        "valid": manifest["pos"] - pos * BS}
            else:
                assert dst.adopt_kv_block("m", digest, arrays) == "adopted"
        reply = dst.generate("m", prompt, max_new_tokens=24,
                             deadline_ms=60000.0, resume_from=out,
                             resume_tail=tail)
        assert reply.ok and list(reply.outputs["tokens"]) == want
        assert reply.phases["cached_tokens"] == manifest["pos"]
        assert src.commit_migration(a.req_id, "dst")
        assert a.reply.status == "migrated"
        _idle(src)
        # and the other way out: a refused push re-queues the session
        b = _Stream(src, prompt, 24)
        _drive(src, lambda: len(b.tokens) >= 6 and b.in_flight(src))
        src.export_session(b.req_id)
        c = _Stream(src, PB, 6)
        _drive(src, lambda: c.in_flight(src))
        assert src.abort_migration(b.req_id) and src._flight is None
        _finish(src, b, c)
        assert list(b.reply.outputs["tokens"]) == b.tokens == want
        assert list(c.reply.outputs["tokens"]) == _unpaged("gpt2", PB, 6)
        assert _discarded() == 0
        _idle(src)
    finally:
        src.stop()
        dst.stop()


def test_stop_reads_the_step_in_flight_back(telemetry_on):
    e = _engine("gpt2", threaded=True)
    # 100 ms an iteration: the toy's 40 sub-millisecond steps would
    # otherwise all be over before this thread gets to stop()
    fault_injection.arm("serving.decode_step:delay:1")
    try:
        a = _Stream(e, PA, 40)
        deadline = time.monotonic() + 30.0
        while len(a.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        e.stop()
    finally:
        fault_injection.disarm()
    assert e._flight is None and not e.in_batch
    assert a.reply is not None and a.reply.status == "error"
    assert a.tokens == _unpaged("gpt2", PA, 40)[:len(a.tokens)]
    assert e._models["m"].cache.allocator.in_use == 0


# -- one executable a step, none built after prewarm -------------------------


def test_one_step_call_per_engine_step_and_no_compile(telemetry_on):
    """Lanes come and go over buckets 1, 2 and 4: every engine step is one
    call of the one ``CarriedStepFn`` (the token feed is inside it) under
    its bucket's key, with the lanes' integers in one array of the
    bucket's one shape, and the step before's tokens, padded to the
    largest bucket, never make a new signature."""
    e = _engine("gpt2", buckets="1,2,4")
    m = e._models["m"]
    calls = []
    inner = m.stepfn

    class Counting:
        def __call__(self, key, *args):
            calls.append((key,) + tuple(np.shape(a) for a in args[2:]))
            return inner(key, *args)

        def __getattr__(self, name):
            return getattr(inner, name)

    m.stepfn = Counting()
    try:
        e.prewarm()
        miss0 = _tm.counter_total("executor_cache_miss_total")
        assert miss0 == 3
        a = _Stream(e, PA, 12)
        _drive(e, lambda: len(a.tokens) >= 2)
        b, c = _Stream(e, PB, 5), _Stream(e, PC, 9)
        _drive(e, lambda: b.reply is not None)
        d = _Stream(e, [12, 13], 4)
        _finish(e, a, c, d)
        for s, (p, n) in ((a, (PA, 12)), (b, (PB, 5)), (c, (PC, 9)),
                          (d, ([12, 13], 4))):
            assert list(s.reply.outputs["tokens"]) == _unpaged("gpt2", p, n)
        _drive(e, lambda: e._flight is None)
        assert len(calls) == _tm.counter_total("serving_decode_steps_total")
        assert {key for key, _prev, _lanes in calls} == {1, 2, 4}
        # prev_next is always the largest bucket's width
        assert {prev for _key, prev, _lanes in calls} == {(4,)}
        # tok | src | pos | lens | tables: bucket rows of 4 + MAXB columns
        assert all(lanes == (key, 4 + GPT2.max_seq // BS)
                   for key, _prev, lanes in calls)
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        assert _tm.counter_total("executor_cache_hit_total") == len(calls)
    finally:
        e.stop()


def test_fed_step_selects_on_the_device():
    """``make_fed_step`` is ``make_paged_step`` with the token chosen per
    lane: ``prev_next[src]`` where ``src >= 0``, the host's where -1."""
    import jax

    from paddle_tpu.serving import kv_cache as kvc

    cfg, params = MODELS["gpt2"]
    kv = dm.cache_config(cfg, BS, 8, "f32")
    tables = np.full((2, cfg.max_seq // BS), -1, np.int32)
    tables[:, 0] = (1, 2)
    zeros, ones = np.zeros(2, np.int32), np.ones(2, np.int32)

    def fresh():
        return kvc.PagedKVCache(kv).carry()

    plain = jax.jit(dm.make_paged_step(cfg, kv))
    fed = jax.jit(dm.make_fed_step(cfg, kv, 4))
    want = plain(fresh(), params, np.asarray([7, 9], np.int32), zeros,
                 tables, ones)
    got = fed(fresh(), params, np.asarray([7, 0], np.int32),
              np.asarray([3, 5, 9, 11], np.int32),
              np.asarray([-1, 2], np.int32), zeros, tables, ones)
    assert got[1].shape == (4,)
    assert np.array_equal(got[1][:2], want[1])
    assert np.array_equal(got[1][2:], [0, 0])
    assert np.array_equal(got[2], want[2])
    for x, y in zip(got[0], want[0]):
        assert np.array_equal(x, y)


# -- a speculating model stays synchronous -----------------------------------


def test_spec_model_keeps_the_synchronous_path(telemetry_on):
    cfg, params = MODELS["gpt2"]
    draft = dm.truncate_decoder(cfg, params, layers=1)
    e = _engine("gpt2", draft=draft, speculative_k=3)
    try:
        assert e._models["m"].spec_k == 3
        a, b = _Stream(e, PA, 12), _Stream(e, PB, 7, eos_id=-1)
        while a.reply is None or b.reply is None:
            assert e._loop_once()
            # nothing is ever left on the device between iterations
            assert e._flight is None and not e.in_batch
            assert all(s.n_disp == s.n_fed for s in e._active)
        assert list(a.reply.outputs["tokens"]) == _unpaged("gpt2", PA, 12)
        assert list(b.reply.outputs["tokens"]) == _unpaged("gpt2", PB, 7)
        assert _ctr("serving_steps_ahead_total") == 0 and _discarded() == 0
    finally:
        e.stop()


# -- the span ----------------------------------------------------------------


def _step_spans(tmp_path):
    _trc.flush()
    recs = []
    for path in glob.glob(str(tmp_path / "trace-*.jsonl")):
        with open(path) as fp:
            recs += [json.loads(line) for line in fp if line.strip()]
    return [r["attrs"] for r in recs if r.get("t") == "span"
            and r.get("name") == "serving.decode_step"]


@pytest.mark.parametrize("device_busy", [True, None],
                         ids=["device_still_busy", "as_it_falls"])
def test_span_carries_ahead_and_no_gap_with_it(device_busy, telemetry_on,
                                               tmp_path, monkeypatch):
    """``ahead``: the step before was still on the device as this one was
    dispatched, so the device never ran dry and ``gap_us`` is 0.  The toy
    step is faster than the host, so one leg answers ``is_ready`` as a
    device still at work would."""
    if device_busy:
        monkeypatch.setattr(engine_mod._Flight, "ready", lambda self: False)
    fluid.set_flags({"FLAGS_tracing": True,
                     "FLAGS_telemetry_dir": str(tmp_path)})
    try:
        e = _engine("gpt2")
        try:
            a = _Stream(e, PA, 8)
            _finish(e, a)
            assert a.tokens == _unpaged("gpt2", PA, 8)
        finally:
            e.stop()
        spans = _step_spans(tmp_path)
        # prompt 4 + 8 new: positions 0..10, a span for each dispatch
        assert len(spans) == 11
        assert all(isinstance(a["ahead"], bool) for a in spans)
        assert spans[0]["ahead"] is False       # nothing was in flight
        assert all(a["gap_us"] == 0 for a in spans if a["ahead"])
        assert all("serving.dispatch" in a["phases"] for a in spans)
        n_ahead = sum(a["ahead"] for a in spans)
        assert _ctr("serving_steps_ahead_total", model="m") == n_ahead
        if device_busy:
            assert n_ahead == 10
        assert sum(a["generated"] for a in spans) == 7  # the 8th: drained
    finally:
        _trc.reset()
        fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry_dir": ""})
