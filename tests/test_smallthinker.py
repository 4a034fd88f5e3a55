"""The SmallThinker decoder (paddle_tpu/models/smallthinker.py: window layers
of a long ring beside unrotated global ones, 7 query heads a KV head, a
softmax router that reads the attention's input, ReLU-gated experts) against
the plain reference benchmark/reference/smallthinker_ref.py, and what the
family forced: the attention kernel's walk of a ring in chunks, its grouping
at 7, the expert kernel's gate as a static argument, the span's and the
prewarm event's new attributes, the configuration's refusals.  The engine's,
cache's and step's contract runs in tests/test_decoder_families.py over this
family's row of tests/decoder_families.py, whose tiny sizes these are:
window 8, block 4 (a ring of 3 blocks), 8 layers ``attention, window,
window, window`` twice, 14 query heads over 2 KV heads of 8, 16 experts of
width 16, 3 a token."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import smallthinker as st
from paddle_tpu.pallas_kernels import moe_experts as moe
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm

ref = fam.load("benchmark", "reference", "smallthinker_ref.py")
model = fam.load("benchmark", "models", "smallthinker_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["smallthinker"].configs[k] for k in ("f32", "bf16"))
WINDOW, RING = CFG.window, 3            # ceil(8 / 4) + 1
PROMPT = fam.PROMPT
_jnp = fam.as_jnp

# float32 rounding over eight layers (measured 3e-5 here); a fault in
# structure is 1e-2 or more (the references told otherwise below)
TOL_F32 = 5e-4


def ref_config(cfg, **changed):
    """The source's keys, as the reference reads them."""
    windowed = [int(k == "window") for k in cfg.layer_types]
    return dict({
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.layers, "sliding_window_layout": windowed,
        "rope_layout": windowed, "sliding_window_size": cfg.window,
        "moe_ffn_hidden_size": cfg.ffn,
        "moe_num_primary_experts": cfg.experts,
        "moe_num_active_primary_experts": cfg.experts_per_token,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rope_scaling": None,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps},
        **changed)


def _ref(cfg, params, tokens, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(ref_config(cfg, **changed),
                                      _jnp(params),
                                      jnp.asarray(tokens, jnp.int32)))


# -- 1. the block against the reference ------------------------------------------

def _block_logits(cfg, params, n=40):
    """Teacher-forced with its own argmax through the unpaged loop: tokens
    fed and the logits at every position from the prompt's last on."""
    out, logits = fam.generate(cfg, params, PROMPT, n, return_logits=True)
    return PROMPT + out, np.stack(logits)


def test_f32_logits_equal_the_reference_past_two_wraps_of_the_ring():
    fed, got = _block_logits(CFG, PARAMS)
    want = _ref(CFG, PARAMS, fed)[len(PROMPT) - 1:-1]
    assert len(fed) > 4 * RING * BS and got.shape == want.shape
    assert np.abs(got - want).max() < TOL_F32


def test_prefill_then_decode_through_rings_and_pages_equals_the_reference():
    """Three lanes of one paged step over the cache manager's pools and
    rings: a sequence that stays under the window, one past it and one past
    two wraps of the ring (12 positions), every position's logits.  (A
    seed on which no position's sixth expert ties with its seventh to
    float32 rounding: seed 5 has one, and the two sides then swap an
    expert there.)"""
    rng = np.random.RandomState(6)
    seqs = [(list(rng.randint(0, CFG.vocab, n)), m)
            for n, m in ((3, 3), (9, 6), (11, 29))]
    lanes, _routed = fam.run_paged(CFG, PARAMS, seqs)
    for (fed, got), (prompt, n) in zip(lanes, seqs):
        assert len(fed) == len(prompt) + n
        assert np.abs(got - _ref(CFG, PARAMS, fed)).max() < TOL_F32


def _h2_routed(config, sliding, rotated, p, x):
    """The reference's layer with the router fed the EXPERTS' input."""
    eps = float(config["rms_norm_eps"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = ref._rmsnorm(x, p["ln1_g"], eps)
    x = x + ref._attention(config, sliding, rotated, p, h)
    h2 = ref._rmsnorm(x, p["ln2_g"], eps)
    routing = ref.gates_of(config, p, h2)
    return x + ref.routed_sum(config, p, h2, routing[0]), routing


# a reference told otherwise: each is a fault the tolerance has to see
BREAKS = {
    "window_layers_attend_everything": dict(sliding_window_size=10 ** 6),
    "a_window_that_sees_one_position_more": dict(
        sliding_window_size=WINDOW + 1),
    "a_global_layer_rotated": dict(rope_layout=[1] * 8),
    "a_window_layer_not_rotated": dict(rope_layout=[0] * 8),
    "every_layer_global": dict(sliding_window_layout=[0] * 8),
    "fewer_experts_a_token": dict(moe_num_active_primary_experts=2),
}
FORGETFUL = {
    "the_router_reads_h2": lambda mp: mp.setattr(ref, "layer", _h2_routed),
    "silu_for_relu": lambda mp: mp.setattr(
        ref, "routed_sum", lambda c, p, x, g, _f=ref.routed_sum:
        _f(c, p, x, g, act=jax.nn.silu)),
    "gates_not_renormalised": lambda mp: mp.setattr(
        ref, "gates_of", lambda c, p, h, _f=ref.gates_of: (
            _f(c, p, h)[0] * 0.5, _f(c, p, h)[1])),
}


@pytest.mark.parametrize("how", sorted(BREAKS) + sorted(FORGETFUL))
def test_f32_tolerance_catches_a_reference_told_otherwise(how, monkeypatch):
    fed, got = _block_logits(CFG, PARAMS)
    if how in FORGETFUL:
        FORGETFUL[how](monkeypatch)
        # (``forward`` takes ``layer`` as a default argument)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.forward(
                ref_config(CFG), _jnp(PARAMS), jnp.asarray(fed, jnp.int32),
                layer_fn=ref.layer))
    else:
        want = _ref(CFG, PARAMS, fed, **BREAKS[how])
    assert np.abs(got - want[len(PROMPT) - 1:-1]).max() > 20 * TOL_F32, how


def test_the_block_routes_from_the_attentions_input(monkeypatch):
    """A block whose router is fed ``h2`` is seen: the routed counts of the
    step are those of the reference's gates from ``h``, layer by layer, and
    not those from ``h2``."""
    fed = PROMPT + [7, 1, 8, 2, 8]
    with jax.default_matmul_precision("highest"):
        _lg, kept = ref.forward(ref_config(CFG), _jnp(PARAMS),
                                jnp.asarray(fed, jnp.int32),
                                return_kept=True)
        _lg, other = ref.forward(ref_config(CFG), _jnp(PARAMS),
                                 jnp.asarray(fed, jnp.int32),
                                 return_kept=True, layer_fn=_h2_routed)
    want = np.stack([np.asarray(g) > 0 for g in kept["gates"]])   # [L, T, E]
    wrong = np.stack([np.asarray(g) > 0 for g in other["gates"]])
    assert (want != wrong).any()
    (_lane,), routed = fam.run_paged(CFG, PARAMS, [(fed, 0)])
    got = np.stack(routed) > 0                                    # [T, L, E]
    assert (got.sum(axis=2) == CFG.experts_per_token).all()
    assert np.array_equal(got, want.transpose(1, 0, 2))


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """bfloat16 as served against the float32 reference on the same
    weights; with the weights rounded to 8 bits (e4m3) the error is several
    times larger.  The limit stands between."""
    fed, got = _block_logits(CFG16, PARAMS16)
    want = _ref(CFG16, PARAMS16, fed)[len(PROMPT) - 1:-1]
    rms = lambda x: float(np.sqrt(np.mean(np.square(x - want))))
    served = rms(got)
    fp8 = fam.fp8_rounded(PARAMS16)
    rounded = rms(fam.teacher_forced(CFG16, fp8, fed[:-1])[len(PROMPT) - 1:])
    assert served < 0.5 * rounded and served < 0.25, (served, rounded)


# -- 2. the attention kernel: a ring walked in chunks, 7 heads a KV head ---------

def _pools(rng, blocks, bs, width, dtype):
    return [jnp.asarray(rng.randn(blocks, bs, width), dtype)
            for _ in range(2)]


def _ring_tables(rng, lens, bs, ring, window, blocks):
    """Rings as ``PagedKVCache.advance_ring`` leaves them: the slots of the
    blocks still inside the window hold a block, every other slot none."""
    tables = np.stack([rng.permutation(blocks - 1)[:ring] + 1 for _ in lens])
    for b, ctx in enumerate(lens):
        live = {(p // bs) % ring for p in range(max(ctx - window, 0), ctx)}
        tables[b, [s for s in range(ring) if s not in live]] = -1
    return tables


@pytest.mark.parametrize("heads,kv_heads,dim", [(14, 2, 128), (14, 2, 64)],
                         ids=["compact", "spread"])
def test_kernel_walks_a_ring_in_chunks_7_heads_a_kv_head(
        interpreted, monkeypatch, heads, kv_heads, dim):
    """A ring longer than the longest chunk (here 5 slots of 16 against
    chunks of 32 positions: three chunks, the last of one block) is walked
    as a context is: contexts of 1, a block less one, the ring less one, the
    ring, the ring and one, two rings and three, and idle.  Against the
    gather with the same mask and against plain attention over the unrolled
    window; the copies are those of the slots a lane holds."""
    monkeypatch.setattr(pa, "CHUNK_TOKENS", 16)
    monkeypatch.setattr(pa, "_MAX_CHUNK_TOKENS", 32)
    rng = np.random.RandomState(1)
    bs, ring, window = 16, 5, 64
    ring_len = ring * bs
    width = kv_heads * dim
    k, v = _pools(rng, 40, bs, width, jnp.float32)
    lens = np.asarray([0, 1, bs - 1, ring_len - 1, ring_len, ring_len + 1,
                       2 * ring_len + 3], np.int32)
    tables = _ring_tables(rng, lens, bs, ring, window, 40)
    q = jnp.asarray(rng.randn(len(lens), heads, dim), jnp.float32)
    assert not pa._ring_whole(ring, bs)
    assert pa.chunk_positions(q.shape, k.shape, jnp.float32, 99, ring) == 32
    assert pa.attention_path(q.shape, k.shape, jnp.float32, ring) == "pallas"
    # blocks no table names hold NaN: a copy of one would show
    named = np.unique(np.maximum(tables, 0))
    poison = np.ones(40, bool)
    poison[named] = False
    k, v = (jnp.where(poison[:, None, None], jnp.nan, pool)
            for pool in (k, v))
    jt, jlens = jnp.asarray(tables, jnp.int32), jnp.asarray(lens)
    got = np.asarray(pa._paged_pallas(q, k, v, jt, jlens, window=window))
    clean = [jnp.nan_to_num(pool) for pool in (k, v)]
    want = np.asarray(pa.paged_attention_reference(q, *clean, jt, jlens,
                                                   window=window))
    np.testing.assert_allclose(got[1:], want[1:], atol=2e-5, rtol=2e-5)
    assert not got[0].any()
    for b, ctx in enumerate(lens):
        if not ctx:
            continue
        span = range(max(ctx - window, 0), ctx)
        rows = [(int(tables[b, (p // bs) % ring]), p % bs) for p in span]
        kk, vv = (jnp.stack([pool[blk, off] for blk, off in rows])
                  .reshape(1, len(rows), kv_heads, dim) for pool in clean)
        plain = pa.masked_attention(q[b:b + 1], kk, vv,
                                    jnp.asarray([len(rows)], jnp.int32))
        np.testing.assert_allclose(got[b], np.asarray(plain)[0], atol=2e-5,
                                   rtol=2e-5)
    # work follows the slots held: ceil(ctx / 16) leading ones, then all 5
    assert pa.blocks_read(lens, bs, ring, "pallas", ring=True) \
        == 0 + 1 + 1 + 5 + 5 + 5 + 5
    assert pa.chunks_read(lens, bs, ring, 32)[0] == 0 + 1 + 1 + 3 * 4
    # ... and a ring of one chunk costs a live lane the whole ring
    assert pa._ring_whole(2, bs) and pa.blocks_read(
        lens, bs, 2, "pallas", ring=True) == 6 * 2


def test_a_chunks_mask_shifted_by_one_chunk_is_seen(interpreted, monkeypatch):
    """The control the chip check runs at the published sizes: chunk ``c``
    masked as chunk ``c + 1`` would be."""
    monkeypatch.setattr(pa, "CHUNK_TOKENS", 16)
    monkeypatch.setattr(pa, "_MAX_CHUNK_TOKENS", 32)
    rng = np.random.RandomState(2)
    bs, ring, window = 16, 5, 64
    k, v = _pools(rng, 12, bs, 128, jnp.float32)
    lens = np.asarray([70, 200], np.int32)
    tables = jnp.asarray(_ring_tables(rng, lens, bs, ring, window, 12))
    q = jnp.asarray(rng.randn(2, 7, 128), jnp.float32)
    right = np.asarray(pa._paged_pallas(q, k, v, tables, jnp.asarray(lens),
                                        window=window))
    in_window = pa._in_window
    monkeypatch.setattr(pa, "_in_window", lambda ctx, entry, n, w:
                        in_window(ctx, entry + 32, n, w))
    shifted = np.asarray(pa._paged_pallas(q, k, v, tables, jnp.asarray(lens),
                                          window=window))
    assert np.abs(shifted - right).max() > 1e-2


def _text(fn, *shapes):
    return hashlib.sha256(jax.jit(fn).lower(*shapes).as_text().encode()
                          ).hexdigest()[:16]


def test_a_ring_of_one_chunk_and_the_silu_experts_lower_to_the_parents_text(
        interpreted):
    """What this family added to the two kernels moves no other family's
    step: K-EXAONE's ring (9 slots of 16, one chunk) and the SiLU-gated
    experts lower to the text they had before a ring could be walked in
    chunks or a gate named (PR 52's tree, same jax); a global layer's walk
    to the text of its two bodies (PR 63's tree)."""
    shape = jax.ShapeDtypeStruct
    q, pool = shape((4, 64, 128), jnp.float32), \
        shape((40, 16, 1024), jnp.bfloat16)
    lens = shape((4,), jnp.int32)
    assert _text(lambda q, k, v, t, l: pa.paged_attention(
        q, k, v, t, l, window=128), q, pool, pool, shape((4, 9), jnp.int32),
        lens) == "a8be3890b9dfa8ac"
    assert _text(lambda q, k, v, t, l: pa.paged_attention(q, k, v, t, l),
                 q, pool, pool, shape((4, 64), jnp.int32), lens) \
        == "2f3f331fcea4b85e"
    w, wd = shape((8, 256, 128), jnp.bfloat16), \
        shape((8, 128, 256), jnp.bfloat16)
    feeds = (shape((4, 256), jnp.float32), shape((4, 8), jnp.float32),
             shape((4,), jnp.bool_), w, w, wd)
    assert _text(moe.routed_experts, *feeds) == "7712f552a4c4b96a"
    assert _text(lambda *a: moe.routed_experts(*a, gate="silu"), *feeds) \
        == _text(lambda *a: moe.routed_experts(*a), *feeds)
    assert _text(lambda *a: moe.routed_experts(*a, gate="relu"), *feeds) \
        != "7712f552a4c4b96a"


def test_the_cells_shapes_take_the_kernel_and_say_what_the_walk_does(
        interpreted):
    """The published attention at the cell's pools: 28 query heads over 4 KV
    heads of 128, 32 lanes, bf16 rows of 2,048 B.  A ring of 257 blocks as
    one chunk would be 16.8e6 B of buffers; walked in chunks of 256
    positions it is 1 MiB beside 1 MiB of queries and outputs."""
    q, bf16 = (32, 28, 128), jnp.bfloat16
    wpool, gpool = (33 * 257, 16, 512), (25120, 16, 512)
    held = 2 * 32 * 4 * 32 * 128                # rows padded to 32, compact
    assert pa._compact(28, 4, 128) and pa._query_rows(28, 4) == 32
    assert 2 * 257 * 16 * 2048 + held > pa._VMEM_BUDGET
    assert pa.vmem_bytes(q, wpool, bf16, 257) == 4 * 256 * 1024 + held \
        == pa.vmem_bytes(q, gpool, bf16) < pa._VMEM_BUDGET
    assert pa.chunk_positions(q, wpool, bf16, 1024, 257) == 256 \
        == pa.chunk_positions(q, gpool, bf16, 1024)
    assert pa.attention_path(q, wpool, bf16, 257) == "pallas" \
        == pa.attention_path(q, gpool, bf16)
    lens = np.asarray([0, 300, 4096, 4112, 4113, 12544], np.int32)
    assert pa.blocks_read(lens, 16, 257, "pallas", ring=True) \
        == 19 + 256 + 257 + 257 + 257
    assert pa.chunks_read(lens, 16, 257, 256)[0] == 2 + 16 + 17 * 3
    assert pa.blocks_read(lens, 16, 257, "gather", ring=True) == 6 * 257
    # K-EXAONE's ring stays one chunk
    assert pa.chunk_positions((32, 64, 128), (297, 16, 1024), bf16, 512, 9) \
        == 144
    # the expert kernel at width 768 under hidden 2560: one column chunk
    assert moe.f_chunk(2560, 768, 2) == 768
    assert moe.experts_path(32, (64, 2560, 768), bf16) == "pallas"


# -- 3. the expert kernel's gate -------------------------------------------------

@pytest.mark.parametrize("gate", sorted(moe.GATES))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_routed_experts_by_gate_kernel_fallback_and_plain_sum(interpreted,
                                                              gate, dtype,
                                                              tol):
    rng = np.random.RandomState(3)
    e, h, f, b = 8, 128, 256, 5
    x = jnp.asarray(rng.randn(b, h), jnp.float32)
    wgate, wup = (jnp.asarray(rng.randn(e, h, f) * 0.1, dtype)
                  for _ in range(2))
    wdown = jnp.asarray(rng.randn(e, f, h) * 0.1, dtype)
    gates = np.zeros((b, e), np.float32)
    for row in gates:
        row[rng.permutation(e)[:3]] = rng.rand(3)
    live = jnp.asarray([True, True, False, True, True])
    gates = jnp.asarray(gates)
    assert moe.experts_path(b, wgate.shape, dtype) == "pallas"
    got = np.asarray(moe.routed_experts(x, gates, live, wgate, wup, wdown,
                                        gate=gate))
    want = np.asarray(moe.experts_reference(x, gates, wgate, wup, wdown,
                                            gate))
    np.testing.assert_allclose(got[np.asarray(live)],
                               want[np.asarray(live)], atol=tol, rtol=tol)
    assert not got[2].any()
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[gate]
    f32 = lambda w: np.asarray(w, np.float32)
    plain = sum(np.asarray(gates)[:, i:i + 1] * (
        (np.asarray(act(jnp.asarray(f32(x) @ f32(wgate[i]))))
         * (f32(x) @ f32(wup[i]))) @ f32(wdown[i])) for i in range(e))
    np.testing.assert_allclose(want, plain, atol=50 * tol, rtol=50 * tol)
    other = moe.experts_reference(x, gates, wgate, wup, wdown,
                                  "relu" if gate == "silu" else "silu")
    assert np.abs(np.asarray(other) - want).max() > 1e-2


# -- 4. the engine: spans, counters, the prewarm event ---------------------------

def test_step_span_counters_and_prewarm_event(cache_dir, telemetry_on,
                                              tmp_path):
    """Traced, the step's span says what the window layers fetched, how many
    live lanes are past the window and how many chunks a window layer
    walked; the prewarm event names both attention paths, the ring's slots
    and the gate; what the family declines is counted under
    ``window_layers``."""
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path),
                   prefix_cache=True):
        e = fam.engine(CFG, PARAMS, 30, buckets="2", name="st")
        try:
            e.prewarm()
            assert e._models["st"].declines == "window_layers"
            r = e.generate("st", [1, 2, 3], max_new_tokens=30,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "st")
    assert len(steps) >= 32
    maxb = CFG.max_seq // BS
    # the gather reads every slot of the table it is given: the ring's 3 in
    # each of 6 window layers x 2 lanes, against the whole table's 24; a
    # lane's ring is its one chunk
    assert all(s["kv_window_blocks_read"] == 6 * 2 * RING
               and s["kv_window_blocks_full"] == 6 * 2 * maxb
               and s["kv_window_chunks"] == 1
               and s["kv_window_straight_chunks"] == 0
               and 1 <= s["kv_window_blocks_held"] <= RING for s in steps)
    wrapped = [s["kv_window_lanes_wrapped"] for s in steps]
    assert wrapped[:WINDOW] == [0] * WINDOW and set(wrapped[WINDOW:]) == {1}
    routed = [s for s in steps if "moe_experts_hit" in s]
    assert routed and all(s["moe_assignments"] == 3.0
                          and s["moe_experts_hit"] == 3.0 for s in routed)
    assert fam.counters("kv_window_blocks_released_total") == {
        "kv_window_blocks_released_total{model=st}": 6}
    assert fam.counters("prefix_cache_declined_total") == {
        "prefix_cache_declined_total{model=st,reason=window_layers}": 1}
    gauges = _tm.snapshot()["gauges"]
    assert gauges["kv_pool_blocks{kind=window,model=st}"] <= RING
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "st" and ev["attention"] == "gather"
        and ev["window_attention"] == "gather" and ev["window_ring"] == RING
        and ev["experts"] == "einsum" and ev["experts_gate"] == "relu"
        and ev["chunk_positions"] == {} for ev in warm)


# -- 5. the configuration --------------------------------------------------------

def test_config_refuses_what_the_block_does_not_compute():
    base = dict(arch="smallthinker", vocab=31, layers=2, heads=14,
                head_dim=8, kv_heads=2, hidden_size=48, experts=8,
                experts_per_token=2, ffn=16,
                layer_types=["attention", "window"], window=4)
    cfg = dm.DecoderConfig(**base)
    assert cfg.routed_layers == (0, 1) and cfg.window_layers == (1,)
    assert dm.experts_gate(cfg) == "relu" == st.FAMILY.expert_gate
    assert dm.experts_gate(fam.ROWS["olmoe"].f32[0]) == "silu"
    for match, changed in (
            ("a shared expert of width shared_ffn", dict(shared_ffn=16)),
            ("dense_layers leads the", dict(dense_layers=1, dense_ffn=16)),
            ("may hold experts", dict(experts_held=4)),
            ("keep topk_group of n_group", dict(n_group=2)),
            ("the smallthinker block's layers are",
             dict(layer_types=["attention", "conv"], conv_taps=3)),
            ("window layers want window", dict(window=0))):
        with pytest.raises(ValueError, match=match):
            dm.DecoderConfig(**dict(base, **changed))
    # the benchmark's reading of the source refuses a Q/K norm, a bias, a
    # shared or a secondary expert, and layouts that disagree
    with open(fam.config_file("smallthinker-21b-a3b-serve.json")) as fp:
        config = json.load(fp)
    assert model.decoder_config(config).to_dict() == dict(
        dm.DecoderConfig(
            arch="smallthinker", vocab=151936, layers=8, heads=28,
            kv_heads=4, head_dim=128, hidden_size=2560, ffn=768, experts=64,
            experts_per_token=6, window=4096, max_seq=16384, dtype="bf16",
            rope_theta=1.5e6, norm_eps=1e-6,
            layer_types=("attention", "window", "window", "window") * 2
        ).to_dict())
    for changed in (dict(use_qk_norm=True), dict(attention_bias=True),
                    dict(moe_num_shared_experts=1),
                    dict(rope_layout=[1] * 8), dict(norm_topk_prob=False),
                    dict(tie_word_embeddings=True),
                    dict(sliding_window=128)):
        with pytest.raises(ValueError, match="the smallthinker block is"):
            model.decoder_config(dict(config, **changed))
    shapes = model.param_shapes(config)
    count = sum(int(np.prod(shape)) for shape, _kind in shapes.values())
    assert count == 3966937600                  # 7.934e9 B in bfloat16
