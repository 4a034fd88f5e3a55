"""The routed-expert kernel (pallas_kernels/moe_experts.py) on the CPU tier,
through the Pallas interpreter (``PADDLE_PALLAS_INTERPRET=1``): its output
against the einsums over all experts at reduced copies of both routed
configurations' shapes (OLMoE: 8 of 16 by softmax probability, as they are;
LFM2: 4 of 16 by sigmoid score, renormalised), the proof that an expert no
live lane chose is not read (its weights are NaN), the rule that picks the
path, and the decode steps and the engine on the kernel against the same on
the einsums.  What the chip's compiler makes of it at the real widths is in
tests/test_tpu_compile.py; times are the chip's alone."""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.models import lfm2_moe, olmoe
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import moe_experts as me
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

E, H = 16, 128
# model -> (expert width, experts a token, how a lane's chosen scores become
# gates)
MODELS = {"olmoe": (128, 8, "softmax"), "lfm2": (256, 4, "sigmoid")}
# against the einsums on the same weights: float32 differs by the order of
# two sums (measured 5e-7 of outputs up to 0.8); bfloat16 by that and by
# where the activation's rounding to bfloat16 falls beside the chunks
# (measured 4e-3)
TOL = {"float32": 2e-5, "bfloat16": 2e-2}
LANES = 6


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    fluid.set_flags({"FLAGS_telemetry": True})
    adoption.reset()
    _tm.reset()
    yield
    adoption.reset()
    _tm.reset()
    fluid.set_flags({"FLAGS_telemetry": False})


def _weights(ffn, dtype, seed=0):
    rng = np.random.RandomState(seed)
    make = lambda *shape: jnp.asarray(
        rng.randn(*shape).astype(np.float32) * 0.1).astype(dtype)
    return make(E, H, ffn), make(E, H, ffn), make(E, ffn, H)


def _gates(kind, k, allowed, rows, seed=1):
    """gates [rows, E] as the model's router would make them, each row's
    ``k`` experts drawn from ``allowed``."""
    rng = np.random.RandomState(seed)
    k = min(k, len(allowed))
    gates = np.zeros((rows, E), np.float32)
    for b in range(rows):
        chosen = rng.choice(allowed, k, replace=False)
        score = rng.rand(E).astype(np.float32) + 0.05
        if kind == "softmax":          # probabilities over all, as they are
            gates[b, chosen] = (np.exp(score) / np.exp(score).sum())[chosen]
        else:                          # renormalised over the chosen
            gates[b, chosen] = score[chosen] / (score[chosen].sum() + 1e-6)
    return gates


# hit set -> (experts the lanes choose from, live lanes)
HITS = {
    "all": (list(range(E)), [True] * LANES),
    "one_expert": ([5], [True] * LANES),
    "ragged": ([0, 3, 4, 9, 10, 11, 15], [True, True, False, True, True,
                                         True]),
    "every_lane_idle": (list(range(E)), [False] * LANES),
    "one_live_lane": (list(range(E)), [False, False, False, True, False,
                                       False]),
}


@pytest.mark.parametrize("hits", sorted(HITS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_kernel_matches_the_einsums_and_reads_only_what_was_hit(
        interpreted, model, dtype, hits):
    """Live lanes agree with ``experts_reference`` to the weights' rounding;
    an idle lane's row is zeros; and with the weights of every expert that
    no live lane chose poisoned with NaN, the output is finite and the
    same: the kernel did not read them (the einsums would return NaN
    everywhere: 0 x NaN)."""
    ffn, k, kind = MODELS[model]
    allowed, live = HITS[hits]
    wgate, wup, wdown = _weights(ffn, dtype)
    gates = _gates(kind, k, allowed, LANES)
    live = np.asarray(live)
    h2 = jnp.asarray(np.random.RandomState(2).randn(LANES, H), jnp.float32)
    ref = np.asarray(me.experts_reference(h2, jnp.asarray(gates), wgate, wup,
                                          wdown))
    hit = ((gates != 0) & live[:, None]).any(axis=0)
    poison = lambda w: jnp.where(jnp.asarray(hit)[:, None, None], w, jnp.nan)
    if not hit.all():
        assert np.isnan(np.asarray(me.experts_reference(
            h2, jnp.asarray(gates), poison(wgate), wup, wdown))).all()
    out = np.asarray(me.routed_experts(
        h2, jnp.asarray(gates), jnp.asarray(live), poison(wgate),
        poison(wup), poison(wdown)))
    assert np.isfinite(out).all()
    assert not out[~live].any()
    if live.any():
        assert np.abs(ref[live]).max() > 0.05
        assert np.abs(out[live] - ref[live]).max() <= TOL[dtype]
    assert adoption.active_kernels() == ["moe_experts"]
    assert _tm.counter_total("pallas_kernel_used_total") == 1
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0


@pytest.mark.parametrize("rows,fc", [(18, None), (18, 128), (64, 128),
                                     (1, 128)])
def test_rows_of_a_multi_token_step_and_column_chunks(interpreted, rows, fc):
    """``B x width`` rows at once (18: not whole sublane tiles; 64: more
    than a bucket; 1: the smallest bucket), the expert's width walked in
    chunks of 128 or whole: the same sums."""
    ffn, k, kind = MODELS["lfm2"]
    wgate, wup, wdown = _weights(ffn, jnp.bfloat16, seed=3)
    gates = jnp.asarray(_gates(kind, k, [1, 2, 6, 7, 8, 13], rows, seed=4))
    h2 = jnp.asarray(np.random.RandomState(5).randn(rows, H), jnp.float32)
    ref = np.asarray(me.experts_reference(h2, gates, wgate, wup, wdown))
    out = np.asarray(me._experts_pallas(h2, gates, jnp.ones(rows, bool),
                                        wgate, wup, wdown, fc=fc))
    assert out.shape == ref.shape
    assert np.abs(out - ref).max() <= TOL["bfloat16"]


@pytest.mark.parametrize("hit,order,n", [
    ([0, 1, 2, 3, 4, 5], [0, 1, 2, 3, 4, 5], 6),
    ([1, 4], [1, 4, 4, 4, 4, 4], 2),
    ([5], [5] * 6, 1),
    ([], [0] * 6, 0),
])
def test_hit_order_lists_the_hit_experts_then_repeats_the_last(hit, order, n):
    gates = np.zeros((3, 6), np.float32)
    for i, e in enumerate(hit):
        gates[i % 3, e] = 0.5
    got, n_hit = me.hit_order(jnp.asarray(gates))
    assert got.dtype == jnp.int32 and n_hit.dtype == jnp.int32
    assert list(np.asarray(got)) == order and list(np.asarray(n_hit)) == [n]


RULE = {
    # name: (rows, wgate's shape, its dtype, where, expected)
    "bf16": (32, (64, 2048, 1536), "bfloat16", None, "ok"),
    "f32": (32, (16, 128, 256), "float32", None, "ok"),
    "one_row": (1, (64, 2048, 1024), "bfloat16", None, "ok"),
    "rank_2": (32, (2048, 1536), "bfloat16", None, "rank"),
    "int8": (32, (64, 2048, 1536), "int8", None, "dtype"),
    "hidden_64": (4, (8, 64, 128), "float32", None, "lanes"),
    "width_32": (4, (8, 128, 32), "float32", None, "lanes"),
    "no_expert": (4, (0, 128, 128), "float32", None, "empty"),
    # one 128-column chunk of three such blocks, twice, is 50e6 bytes
    "hidden_16384_f32": (32, (8, 16384, 128), "float32", None, "vmem"),
    # the blocks fit; 4096 rows in and out beside them do not
    "rows_4096": (4096, (64, 2048, 1536), "bfloat16", None, "vmem"),
    "symbolic": (None, (64, 2048, 1536), "bfloat16", None, "symbolic_shape"),
    "gspmd_mesh": (32, (64, 2048, 1536), "bfloat16", "mesh", "gspmd_mesh"),
    "off_tpu": (32, (64, 2048, 1536), "bfloat16", "cpu", "backend"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_shape_rule(interpreted, monkeypatch, case):
    """The kernel engages for bfloat16 and float32 experts of whole lane
    tiles and declines, under the right reason, everything else;
    ``experts_path`` says the same and counts nothing."""
    rows, shape, dtype, where, expected = RULE[case]
    if where == "cpu":
        monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    checks = me.moe_experts_checks(rows, shape, dtype)
    path = me.experts_path(rows, shape, dtype)
    assert _tm.counter_total("pallas_kernel_used_total") == 0
    assert _tm.counter_total("pallas_kernel_fallback_total") == 0
    if where == "mesh":
        with adoption.auto_partitioned():
            use, reason = adoption.decide("moe_experts", checks)
    else:
        use, reason = adoption.decide("moe_experts", checks)
    assert (use, reason) == (expected == "ok", expected)
    assert path == ("einsum" if expected not in ("ok", "gspmd_mesh")
                    else "pallas")
    labels = [labels for _key, labels in _tm.label_sets(
        "pallas_kernel_fallback_total")]
    assert labels == ([] if use else [{"kernel": "moe_experts",
                                       "reason": expected}])
    assert "moe_experts" in adoption.KERNELS


def test_f_chunk_fills_the_block_budget():
    """Both cells' experts go through in the widest chunk that leaves room
    for the next one beside it: LFM2's ``[2048, 1536]`` in halves, OLMoE's
    ``[2048, 1024]`` whole."""
    assert me.f_chunk(2048, 1536, 2) == 768
    assert me.f_chunk(2048, 1024, 2) == 1024
    assert me.f_chunk(2048, 1536, 4) == 512
    assert me.f_chunk(128, 256, 4) == 256
    assert me.f_chunk(16384, 128, 4) == 0


# -- the decode steps and the engine on the kernel ------------------------------

BS = 4
CFGS = {
    "olmoe": dm.DecoderConfig(
        arch="olmoe", vocab=97, layers=2, heads=2, head_dim=64, ffn=128,
        max_seq=64, experts=E, experts_per_token=8),
    "lfm2_moe": dm.DecoderConfig(
        arch="lfm2_moe", vocab=97, layers=3, heads=4, kv_heads=2, head_dim=32,
        ffn=256, max_seq=64, layer_types=("conv", "attention", "conv"),
        conv_taps=3, dense_layers=1, dense_ffn=128, experts=E,
        experts_per_token=4, rope_theta=1e6),
}
# what test_olmoe_decoder.py and test_lfm2_moe.py hold the block to against
# its reference in float32, and to spare: here both sides are the step
STEP_TOL = 2e-4


def _params(arch):
    cfg = CFGS[arch]
    init = {"olmoe": olmoe, "lfm2_moe": lfm2_moe}[arch].init_params
    return init(cfg, seed=3, std=0.08)


def _run_step(cfg, params, steps=6, lanes=4):
    """``steps`` steps of the paged step over ``lanes`` lanes, the last one
    idle, each live lane fed its own argmax -> (tokens [steps, lanes],
    logits [steps, lanes, vocab], routed counts)."""
    kv = dm.cache_config(cfg, BS, 24, state_slots=lanes + 1)
    cache = kvc.PagedKVCache(kv)
    step = jax.jit(dm.make_paged_step(cfg, kv), donate_argnums=(0,))
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    tables = np.full((lanes, cfg.max_seq // BS), -1, np.int32)
    for i in range(lanes - 1):
        tables[i, :4] = 1 + 4 * i + np.arange(4)
    live = np.arange(lanes) < lanes - 1
    slots = (np.where(live, np.arange(1, lanes + 1), 0).astype(np.int32),) \
        if cfg.recurrent_layers else ()
    tok = np.where(live, np.arange(7, 7 + lanes), 0).astype(np.int32)
    toks, logits, routed = [], [], []
    for n in range(steps):
        pos = np.where(live, n, 0).astype(np.int32)
        lens = np.where(live, n + 1, 0).astype(np.int32)
        carry, nxt, lg, counts = step(cache.carry(), jparams, tok, pos,
                                      tables, lens, *slots)
        cache.replace_carry(carry)
        tok = np.where(live, np.asarray(nxt), 0).astype(np.int32)
        toks.append(tok)
        logits.append(np.asarray(lg))
        routed.append(np.asarray(counts))
    return np.stack(toks), np.stack(logits), np.stack(routed)


@pytest.mark.parametrize("arch", sorted(CFGS))
def test_paged_step_on_the_kernel_equals_the_step_on_the_einsums(
        interpreted, monkeypatch, arch):
    """The same tokens and, on live lanes, logits to float32 rounding; the
    routed counts are the router's and do not move; one lowering a routed
    layer went to the kernel."""
    cfg, params = CFGS[arch], _params(arch)
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    toks0, logits0, routed0 = _run_step(cfg, params)
    assert _tm.counter_total("pallas_kernel_used_total") == 0
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    _tm.reset()
    toks, logits, routed = _run_step(cfg, params)
    assert _tm.snapshot()["counters"][
        "pallas_kernel_used_total{kernel=moe_experts}"] \
        == len(cfg.routed_layers)
    assert np.array_equal(toks, toks0)
    assert np.array_equal(routed, routed0)
    assert (routed.sum(axis=2) == 3 * cfg.experts_per_token).all()
    assert np.abs(logits[:, :3] - logits0[:, :3]).max() <= STEP_TOL
    assert np.abs(logits0[:, :3]).max() > 0.1


@contextlib.contextmanager
def _flags(**kv):
    kv = {"FLAGS_" + k: v for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


@pytest.mark.parametrize("arch", sorted(CFGS))
def test_engine_on_the_kernel_names_the_path_and_counts_what_it_skipped(
        interpreted, monkeypatch, tmp_path, arch):
    """An engine whose routed layers are the kernel: the stream is the one
    the einsums give, the ``serving_prewarm`` event names the experts' path
    for its bucket, the cache key carries it, the family's counter moved
    once a routed layer a compiled bucket, and while spans are recorded
    ``moe_expert_reads_skipped_total`` counts the experts no token chose."""
    cfg, params = CFGS[arch], _params(arch)
    prompt = [1, 2, 3]
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    expected = dm.unpaged_generate(cfg, params, prompt, 5,
                                   pad_len=cfg.max_seq)
    assert dm.experts_path(cfg, params, 2) == "einsum"
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    assert dm.experts_path(cfg, params, 2) == "pallas"
    _tm.reset()
    with _flags(kv_block_size=BS, tracing=True,
                telemetry_dir=str(tmp_path),
                compile_cache_dir=str(tmp_path / "cc")):
        e = DecodeEngine(buckets="1,2", deadline_ms=60000.0)
        m = e.add_model("moe", (cfg, params), kv_blocks=16)
        assert m.experts_path == {1: "pallas", 2: "pallas"}
        assert m.stepfn._key_parts["experts"] == [(1, "pallas"),
                                                   (2, "pallas")]
        e.start()
        try:
            e.prewarm()
            r = e.generate("moe", prompt, max_new_tokens=5,
                           deadline_ms=60000.0)
            assert r.status == "ok", r.error
        finally:
            e.stop()
        _tm.flush()
    assert list(r.outputs["tokens"]) == list(expected)
    counters = _tm.snapshot()["counters"]
    assert counters["pallas_kernel_used_total{kernel=moe_experts}"] \
        == 2 * len(cfg.routed_layers)
    assert not any("kernel=moe_experts" in key and "fallback" in key
                   for key in counters)
    # one live lane: experts_per_token hit a routed layer, the others not
    # read, on every step whose counts were fetched
    skipped = counters["moe_expert_reads_skipped_total{model=moe}"]
    per_step = len(cfg.routed_layers) * (E - cfg.experts_per_token)
    assert skipped > 0 and skipped % per_step == 0
    with open(os.path.join(tmp_path, "steps.jsonl")) as fp:
        warm = [ev for ev in map(json.loads, fp)
                if ev["ev"] == "serving_prewarm"]
    assert sorted(ev["bucket"] for ev in warm) == [1, 2]
    assert all(ev["experts"] == "pallas" for ev in warm)


def test_an_engine_without_routed_layers_says_nothing_of_experts(tmp_path):
    """A GPT-2 step's cache key and prewarm event are as they were."""
    cfg = dm.DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8,
                           max_seq=48)
    with _flags(kv_block_size=BS, telemetry=True,
                telemetry_dir=str(tmp_path)):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        m = e.add_model("toy", (cfg, dm.init_decoder_params(cfg, seed=7)),
                        kv_blocks=16)
        assert m.experts_path == {} and "experts" not in m.stepfn._key_parts
        assert sorted(m.stepfn._key_parts) == ["attention", "cfg", "kind",
                                               "kv", "model"]
        e.prewarm()
        _tm.flush()
    with open(os.path.join(tmp_path, "steps.jsonl")) as fp:
        warm = [ev for ev in map(json.loads, fp)
                if ev["ev"] == "serving_prewarm"]
    assert warm and not any("experts" in ev for ev in warm)
    _tm.reset()
