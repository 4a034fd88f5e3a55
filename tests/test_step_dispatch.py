"""What starting a decode step costs the host (serving/engine.py
``_decode_step_locked``'s ``serving.dispatch``, core/executor.py
``CarriedStepFn``): the compiled step is found by a key its caller holds
(the lane bucket), not by describing the arguments, and the step's per-lane
integers go up as ONE ``int32[bucket, C]`` array that the same executable
cuts apart (``decode_model.make_packed_step``, ``lane_columns``).

Over the served blocks' tiny configurations (attention only, with state
slots, with window rings, routed): the packed step's carry, ``next_tokens``
and logits equal ``make_fed_step``'s bit for bit on the same integers; a
hand-driven loop (``_loop_once``, as tests/test_decode_one_ahead.py) hands
the step exactly one host array a dispatch, the span's ``uploads`` says 1
and the streams stay those of ``unpaged_generate``; buckets 1, 2, 4 after
prewarm leave ``executor_cache_miss_total`` flat.  And of ``CarriedStepFn``
alone: a warmed key does not flatten its arguments, an unwarmed one compiles
once and counts the miss, arguments of another shape under a warmed key
raise and never run."""

import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
import paddle_tpu as fluid
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.core.executor import CarriedStepFn
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

BS = fam.BS
# what the cache holds beside K/V pools decides the packed columns
MODELS = {"attention_only": fam.ROWS["gpt2"].f32,
          "routed": fam.ROWS["olmoe"].f32,
          "state_slots": fam.ROWS["granite_hybrid"].f32,
          "window_rings": fam.ROWS["exaone_moe"].f32}
pytestmark = pytest.mark.usefixtures("cache_dir")
_flags = fam.flags
KINDS = sorted(MODELS)
FIXED = {"attention_only": ["tok", "src", "pos", "lens", "tables"],
         "routed": ["tok", "src", "pos", "lens", "tables"],
         "state_slots": ["tok", "src", "pos", "lens", "slot", "tables"],
         "window_rings": ["tok", "src", "pos", "lens", "tables", "ring"]}
PA, PB, PC = [1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11]


def _unpaged(kind, prompt, max_new):
    cfg, params = MODELS[kind]
    ring = dm.cache_config(cfg, BS, 2, state_slots=2).window_ring
    return [int(t) for t in dm.unpaged_generate(
        cfg, params, prompt, max_new, pad_len=cfg.max_seq,
        ring_len=ring * BS if ring else None)]


@pytest.fixture()
def traced(tmp_path, telemetry_on):
    fluid.set_flags({"FLAGS_tracing": True,
                     "FLAGS_telemetry_dir": str(tmp_path)})
    yield tmp_path
    _trc.reset()
    fluid.set_flags({"FLAGS_tracing": False, "FLAGS_telemetry_dir": ""})


def _engine(kind, buckets="2,4", kv_blocks=80):
    """A hand-driven decode engine: it counts as running, and the test
    makes every iteration itself (``_loop_once``)."""
    cfg, params = MODELS[kind]
    with _flags(kv_block_size=BS, kv_cache_dtype="f32"):
        e = DecodeEngine(buckets=buckets, deadline_ms=60000.0)
        e.add_model("m", (cfg, params), kv_blocks=kv_blocks)
    e._running = True
    return e


class _Stream:
    def __init__(self, e, prompt, max_new):
        self.pending = e.submit("m", prompt, max_new_tokens=max_new,
                                deadline_ms=60000.0)

    @property
    def reply(self):
        return self.pending.reply


def _finish(e, *streams, limit=600):
    for _ in range(limit):
        if all(s.reply is not None for s in streams) and e._flight is None:
            return
        assert e._loop_once()
    raise AssertionError("the loop never got there")


# -- 1. the packed step is the fed step -------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_lane_columns_follow_what_the_cache_holds(kind):
    cfg, _params = MODELS[kind]
    kv = dm.cache_config(cfg, BS, 16, state_slots=5)
    maxb = cfg.max_seq // BS
    at, width = dm.lane_columns(kv, maxb)
    assert list(at) == FIXED[kind]
    # one column each but the table's and the ring's, side by side
    spans = [at[name] for name in FIXED[kind]]
    assert spans[0].start == 0 and spans[-1].stop == width
    assert all(a.stop == b.start for a, b in zip(spans, spans[1:]))
    assert at["tables"].stop - at["tables"].start == maxb
    if "ring" in at:
        assert at["ring"].stop - at["ring"].start == kv.window_ring == 3
    cache = kvc.PagedKVCache(kv)
    assert ("slot" in at) == (cache.slots is not None)
    assert ("ring" in at) == (cache.window_allocator is not None)


@pytest.mark.parametrize("kind", KINDS)
def test_packed_step_equals_fed_step_bit_for_bit(kind):
    """Three live lanes and an idle one over eight steps, the first three
    fed by the host and the rest from the step before on the device: the
    same integers as separate arrays (``make_fed_step``) and as the columns
    of one (``make_packed_step``) give the same pools, slots and rings, the
    same ``next_tokens``, logits and routed counts."""
    cfg, params = MODELS[kind]
    b, live, width = 4, 3, 4
    kv = dm.cache_config(cfg, BS, 32, state_slots=b + 1)
    maxb = cfg.max_seq // BS
    at, columns = dm.lane_columns(kv, maxb)
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    fed = jax.jit(dm.make_fed_step(cfg, kv, width))
    packed = jax.jit(dm.make_packed_step(cfg, kv, width))
    rs = np.random.RandomState(11)
    blocks = iter(rs.permutation(np.arange(1, 32)))
    tables = np.full((b, maxb), -1, np.int32)
    for i in range(live):
        tables[i, :3] = [next(blocks) for _ in range(3)]
    slots = np.zeros(b, np.int32)
    slots[:live] = rs.permutation(np.arange(1, b + 1))[:live]
    rings = np.full((b, kv.window_ring), -1, np.int32)
    if kv.window_ring:
        rings[:live] = 1 + rs.permutation(
            live * kv.window_ring).reshape(live, -1)
    carries = [kvc.PagedKVCache(kv).carry() for _ in range(2)]
    prevs = [jnp.zeros(width, jnp.int32)] * 2
    for t in range(8):
        tok, pos, lens = (np.zeros(b, np.int32) for _ in range(3))
        src = np.full(b, -1, np.int32)
        pos[:live], lens[:live] = t, t + 1
        if t < 3:
            tok[:live] = rs.randint(0, cfg.vocab, live)
        else:
            src[:live] = np.arange(live)
        more = ([slots] if "slot" in at else []) \
            + ([rings] if "ring" in at else [])
        lanes = np.zeros((b, columns), np.int32)
        for name, value in dict(tok=tok, src=src, pos=pos, lens=lens,
                                slot=slots, tables=tables,
                                ring=rings).items():
            if name in at:
                lanes[:, at[name]] = value.reshape(b, -1)
        want = fed(carries[0], jparams, tok, prevs[0], src, pos, tables,
                   lens, *more)
        got = packed(carries[1], jparams, prevs[1], lanes)
        assert len(got) == len(want)
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert x.dtype == y.dtype and np.array_equal(x, y)
        carries = [want[0], got[0]]
        prevs = [want[1], got[1]]
    assert int(np.asarray(prevs[0])[:live].max()) > 0


# -- 2. one host array a dispatch -------------------------------------------


def _step_spans(tmp_path):
    _trc.flush()
    recs = []
    for path in glob.glob(str(tmp_path / "trace-*.jsonl")):
        with open(path) as fp:
            recs += [json.loads(line) for line in fp if line.strip()]
    return [r["attrs"] for r in recs if r.get("t") == "span"
            and r.get("name") == "serving.decode_step"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_dispatch_hands_up_one_host_array(kind, traced):
    e = _engine(kind)
    m = e._models["m"]
    uploaded, handed = [], []
    upload, inner = m.upload, m.stepfn

    def spy_upload(*host):
        uploaded.append([np.array(a) for a in host])
        return upload(*host)

    class Spy:
        def __call__(self, key, *args):
            carry, params, *feeds = args
            handed.append((key, [type(a) for a in feeds],
                           [np.asarray(a) for a in feeds]))
            return inner(key, *args)

        def __getattr__(self, name):
            return getattr(inner, name)

    try:
        e.prewarm()
        m.upload, m.stepfn = spy_upload, Spy()
        a, b, c = _Stream(e, PA, 9), _Stream(e, PB, 5), _Stream(e, PC, 7)
        _finish(e, a, b, c)
        for s, (p, n) in ((a, (PA, 9)), (b, (PB, 5)), (c, (PC, 7))):
            assert list(s.reply.outputs["tokens"]) == _unpaged(kind, p, n)
    finally:
        e.stop()
    steps = len(handed)
    assert steps == _tm.counter_total("serving_decode_steps_total") > 9
    # one array went up a step, whole and of the bucket's one shape ...
    assert len(uploaded) == steps
    width = m.idle_lane.shape[0]
    for (lanes,), (bucket, kinds, feeds) in zip(uploaded, handed):
        assert lanes.dtype == np.int32 and lanes.shape == (bucket, width)
        # ... and the step itself was handed nothing left on the host:
        # the step before's tokens and that array, both on the device
        assert len(feeds) == 2
        assert not any(issubclass(k, np.ndarray) for k in kinds)
        assert np.array_equal(feeds[1], lanes)
    spans = _step_spans(traced)
    assert len(spans) == steps
    assert [s["uploads"] for s in spans] == [1] * steps
    assert _tm.counter_total("serving_step_uploads_total") == steps


# -- 3. a bucket is one key, warmed once ------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_buckets_after_prewarm_compile_nothing(kind, telemetry_on):
    e = _engine(kind, buckets="1,2,4")
    m = e._models["m"]
    try:
        e.prewarm()
        assert sorted(m.stepfn._compiled) == [1, 2, 4]
        miss0 = _tm.counter_total("executor_cache_miss_total")
        assert miss0 == 3
        a = _Stream(e, PA, 12)
        for _ in range(6):
            assert e._loop_once()           # bucket 1
        b, c = _Stream(e, PB, 4), _Stream(e, PC, 9)     # 4, then 2
        _finish(e, a, b, c)
        d = _Stream(e, [12, 13], 3)
        _finish(e, d)
        for s, (p, n) in ((a, (PA, 12)), (b, (PB, 4)), (c, (PC, 9)),
                          (d, ([12, 13], 3))):
            assert list(s.reply.outputs["tokens"]) == _unpaged(kind, p, n)
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        assert _tm.counter_total("executor_cache_hit_total") \
            == _tm.counter_total("serving_decode_steps_total")
        e.prewarm()                         # idempotent: memory hits
        assert _tm.counter_total("executor_cache_miss_total") == miss0
    finally:
        e.stop()


# -- 4. CarriedStepFn: found by key -----------------------------------------


def _toy_step():
    def step(carry, params, x):
        return carry + params["w"] * x.sum(), x + 1

    return CarriedStepFn(step, donate_argnums=(0,), name="toy")


def _toy_args(n):
    return (jnp.zeros((), jnp.float32), {"w": jnp.ones((), jnp.float32)},
            np.arange(n, dtype=np.int32))


def test_a_warmed_key_is_found_without_describing_the_arguments(
        telemetry_on, monkeypatch):
    fn = _toy_step()
    fn.warmup("a", *_toy_args(3))
    looked = []

    def spy(name):
        real = getattr(jax.tree_util, name)

        def wrapped(*args, **kw):
            looked.append(name)
            return real(*args, **kw)

        monkeypatch.setattr(executor_mod.jax.tree_util, name, wrapped)

    for name in ("tree_flatten", "tree_leaves", "tree_structure"):
        spy(name)
    carry, out = fn("a", *_toy_args(3))
    assert float(carry) == 3.0 and list(np.asarray(out)) == [1, 2, 3]
    assert looked == []
    assert _tm.counter_total("executor_cache_miss_total") == 1
    assert _tm.counter_total("executor_cache_hit_total") == 1
    # the one place that does describe them is warmup
    fn.warmup("b", *_toy_args(5))
    assert "tree_flatten" in looked


def test_an_unwarmed_key_compiles_once_and_counts_the_miss(telemetry_on):
    fn = _toy_step()
    for _ in range(3):
        carry, _out = fn(7, *_toy_args(4))
        assert float(carry) == 6.0
    assert sorted(fn._compiled) == [7]
    assert _tm.counter_total("executor_cache_miss_total") == 1
    assert _tm.counter_total("executor_cache_hit_total") == 2
    assert "reduce" in fn.executable(7).as_text()


@pytest.mark.parametrize("how", ["call", "warmup"])
def test_arguments_of_another_shape_under_a_warmed_key_raise(
        how, telemetry_on):
    fn = _toy_step()
    fn.warmup("a", *_toy_args(3))
    ran = []
    jfn, fn._jfn = fn._jfn, lambda *args: ran.append(args) or jfn(*args)
    with pytest.raises(TypeError):
        (fn if how == "call" else fn.warmup)("a", *_toy_args(5))
    assert ran == [] and sorted(fn._compiled) == ["a"]
    assert _tm.counter_total("executor_cache_miss_total") == 1
    carry, _out = fn("a", *_toy_args(3))    # the key still serves its own
    assert float(carry) == 3.0


def test_the_lazy_stand_in_holds_its_key_to_one_signature(
        telemetry_on, monkeypatch):
    """Where the eager compile fails the key holds the lazy jit: it too
    refuses another shape, where a bare jit would trace it and run."""
    monkeypatch.setattr(
        executor_mod, "aot_compile_cached",
        lambda *args, **kw: (None, {"source": "fallback", "compile_ms": 0.0}))
    fn = _toy_step()
    got = fn.warmup("a", *_toy_args(3))
    assert got["source"] == "fallback" and got["temp_bytes"] is None
    carry, _out = fn("a", *_toy_args(3))
    assert float(carry) == 3.0
    with pytest.raises(TypeError):
        fn("a", *_toy_args(5))
