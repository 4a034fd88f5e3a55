"""The choice of a latent layer that selects, as the mask its walk reads
(``paged_attention.chosen_by_chunk``: an exact threshold on the scores' order
keys, no sort): its set against ``chosen_mask(*choose(...))``, the exact
``top_k``'s, over scores of every kind and lanes of every length; how the set
crosses to the read (``Choice``, ``chosen_for_read``); and a step that takes
the masked walk, driven through ``benchmark/tests/chip_check_glm.py``'s own
``recording`` and stand-ins, which the change has to leave running unedited:
the list it records marks the set the walk read, and each control still does
what the check wants of it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import glm_dsa as gd
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

# scores [B, S] of a kind, before the lanes' lengths cut them
SCORES = {
    "normal": lambda rng, b, s, k: rng.standard_normal((b, s)),
    # three distinct values: every threshold is tied many times over
    "few_distinct_values": lambda rng, b, s, k: rng.integers(0, 3, (b, s)),
    # k - 3 scores above a plateau of eight equal ones: the plateau
    # straddles the threshold and its three lowest positions are chosen
    "ties_that_straddle_the_threshold": lambda rng, b, s, k: np.stack([
        rng.permutation(np.concatenate([
            np.arange(2, 2 + max(k - 3, 0)), np.ones(8),
            -np.arange(1, 1 + s)])[:s]) for _ in range(b)]),
    "all_equal": lambda rng, b, s, k: np.full((b, s), -2.5),
    "signed_zeros": lambda rng, b, s, k: rng.choice(
        [0.0, -0.0, 1.0, -1.0], (b, s)),
    "denormals": lambda rng, b, s, k: rng.choice(
        [1e-40, -1e-40, 2e-40, 0.0, -0.0, 1e-45, -1e-45], (b, s)),
    "negative": lambda rng, b, s, k: -np.abs(rng.standard_normal((b, s))),
}
# (lanes, positions, k, chunks, span)
SHAPES = {"a_block_of_8": (8, 256, 20, 2, 128),
          "two_blocks": (16, 384, 64, 1, 512),
          "k_is_the_table": (4, 128, 128, 1, 128)}


def _cut(scores, lens):
    scores = np.asarray(scores, np.float32)
    return jnp.asarray(np.where(
        np.arange(scores.shape[1])[None] < np.asarray(lens)[:, None],
        scores, -np.inf).astype(np.float32))


def _lens(rng, lanes, length, k):
    """Every edge a lane's length has, then lengths at random."""
    edges = [0, 1, max(k - 1, 0), k, min(k + 1, length), length]
    more = rng.integers(0, length + 1, max(lanes - len(edges), 0))
    return np.asarray((edges + list(more))[:lanes], np.int32)


def _same_set(scores, lens, k, chunks, span):
    lanes, length = scores.shape
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(pa.chosen_by_chunk(scores, lens, k, chunks, span))
    assert got.shape == (lanes, chunks, span) and got.dtype == np.int32
    assert set(np.unique(got)) <= {0, 1}
    want = np.asarray(pa.chosen_mask(*pa.choose(scores, lens, k), length))
    flat = got.reshape(lanes, -1)
    assert np.array_equal(flat[:, :length] != 0, want)
    assert not flat[:, length:].any()
    assert np.array_equal(flat.sum(1), np.minimum(np.asarray(lens), k))


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", sorted(SCORES))
def test_the_thresholds_mask_is_the_exact_top_ks_set(kind, shape):
    """``chosen_by_chunk`` (a threshold found bit by bit on the scores' order
    keys, ties to the lower position, no sort) marks ``chosen_mask(*choose(
    ...))``'s set exactly, laid out by chunk, the table's tail zeros: normal
    scores, few distinct values, a plateau that straddles the threshold,
    rows all equal, signed zeros and denormals (the plain total order, as
    ``top_k`` has it: ``-0.0`` below ``+0.0``, nothing flushed), negative
    scores; lanes of 0, 1, k-1, k, k+1 positions and the whole table."""
    lanes, length, k, chunks, span = SHAPES[shape]
    rng = np.random.default_rng(sorted(SCORES).index(kind) * 7
                                + sorted(SHAPES).index(shape))
    lens = _lens(rng, lanes, length, k)
    _same_set(_cut(SCORES[kind](rng, lanes, length, k), lens), lens, k,
              chunks, span)


def test_the_mask_at_the_cells_shapes():
    """Once at the cell's own: 32 lanes of 12,544 scores, 2,048 chosen, for
    a walk of 25 chunks of 512 positions (the table's last 256 of those
    zeros), lanes under, at and past ``k`` and idle ones; 46 passes, and no
    sort in what is compiled where ``top_k``'s list is one."""
    rng = np.random.default_rng(60)
    lens = rng.integers(1500, 9500, 32)
    lens[:6] = [0, 1, 2047, 2048, 2049, 12544]
    scores = _cut(np.maximum(rng.standard_normal((32, 12544, 2)), 0).sum(-1),
                  lens)
    _same_set(scores, lens, 2048, 25, 512)
    lens = jnp.asarray(lens, jnp.int32)
    sorts = lambda fn: len(re.findall(
        r"(?i)\bsort\(|topk", jax.jit(fn).lower(scores, lens).compile()
        .as_text()))
    assert sorts(lambda s, n: pa.chosen_by_chunk(s, n, 2048, 25, 512)) == 0
    assert sorts(lambda s, n: tuple(pa.choose(s, n, 2048))) > 0


def test_a_choice_gives_its_set_by_chunk_and_a_plain_pair_cannot(
        interpreted):
    """``choose`` returns its pair as ever (``positions``, ``count``), a
    tuple that also gives the set ``by_chunk``; ``chosen_for_read`` hands the
    walk that mask in the list's place (rank 3), a stand-in's plain pair and
    any read that is no walk the list (rank 2); and ``chosen_mask`` reads
    either as one set."""
    rng = np.random.default_rng(3)
    lens = jnp.asarray([5, 200, 0, 16], jnp.int32)
    scores = _cut(rng.integers(0, 5, (4, 256)), lens)
    choice = pa.choose(scores, lens, 32)
    positions, count = choice
    assert isinstance(choice, tuple) and len(choice) == 2
    assert positions.shape == (4, 32) and count.tolist() == [5, 32, 0, 16]
    # 16 heads of 256 over 128 latent values in blocks of 16: a table of 16
    # slots is 8 positions a chosen one, and walked
    walk = ((4, 16, 256), (40, 16, 256), jnp.float32, 128, 16)
    mask, count2 = pa.chosen_for_read(choice, *walk)
    assert mask.shape == (4, 1, 256) and count2 is count
    plain, _count = pa.chosen_for_read(tuple(choice), *walk)
    assert plain is positions
    want = np.asarray(pa.chosen_mask(positions, count, 256))
    assert np.array_equal(np.asarray(pa.chosen_mask(mask, count, 256)), want)
    # read at another length: cut, or filled with what was not chosen
    assert np.array_equal(np.asarray(pa.chosen_mask(mask, count, 200)),
                          want[:, :200])
    assert np.array_equal(np.asarray(pa.chosen_mask(mask, count, 300)),
                          np.pad(want, ((0, 0), (0, 44))))


# -- a step on the masked walk, through the chip check's own seams ------------

LANES, DEPTH, BLOCK = 2, 40, 16


def _walked():
    """``tests/test_glm_dsa.py``'s block at kernel widths under a table of
    128 positions: 16 chosen, 8 positions a chosen one, so the read walks
    the table under a mask and a lane's scores are one whole tile."""
    cfg = dm.DecoderConfig(
        arch="glm_dsa", vocab=61, layers=3, heads=4, head_dim=96,
        v_head_dim=128, hidden_size=128, max_seq=128,
        layer_types=("latent",) * 3, latent_rank=128, latent_rope=32,
        q_rank=64, index_heads=8, index_head_dim=128, index_topk=16,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, routed_scaling=2.5,
        rope_theta=1e6)
    return cfg, gd.init_params(cfg, seed=5, std=0.1, bias_std=0.05)


@pytest.fixture(scope="module")
def check():
    from benchmark.run import load_module

    return load_module("tests", "chip_check_glm")


def _serve(check, cfg, params, fault=None, record=False):
    """``DEPTH`` positions of seeded tokens in each of ``LANES`` lanes
    through the paged step, traced while ``fault`` (a stand-in of the
    check's ``patched``) stands and, with ``record``, inside its
    ``recording`` -> (logits [DEPTH, LANES, vocab], what each step's
    selecting layers handed out)."""
    kv = dm.cache_config(cfg, BLOCK, 24)
    cache = kvc.PagedKVCache(kv)
    undo = check.patched(fault, cfg) if fault else None
    try:
        base = dm.make_paged_step(cfg, kv)
        step = jax.jit(check.recording(base, dm) if record else base,
                       donate_argnums=(0,))
        rng = np.random.default_rng(8)
        tokens = rng.integers(0, cfg.vocab, (DEPTH, LANES)).astype(np.int32)
        tables = np.full((LANES, cfg.max_seq // BLOCK), -1, np.int32)
        held = [[] for _ in range(LANES)]
        logits, noted = [], []
        for g in range(DEPTH):
            for i in range(LANES):
                assert cache.ensure_table(tables[i], held[i], g + 1)
            carry, _nxt, lg, *rest = step(
                cache.carry(), params, tokens[g],
                np.full(LANES, g, np.int32), tables,
                np.full(LANES, g + 1, np.int32))
            cache.replace_carry(carry)
            logits.append(np.asarray(lg))
            # the block's extras, then a recording step's three tuples
            noted.append([[np.asarray(a) for a in group]
                          for group in rest[2:]])
        return np.stack(logits), noted
    finally:
        if undo:
            undo()


def test_the_recorded_list_marks_the_set_the_walk_read(
        interpreted, telemetry_on, monkeypatch, check):
    """The served step inside the check's ``recording``: the walk is handed
    the threshold's mask, once a selecting layer (the one-hot contraction
    lays nothing out, and the kernels counted are the three the check's
    engine leg names and no other), and the list that ``recording`` hands
    out, ``top_k``'s, marks that mask's set at every step, layer and lane,
    24 steps of them past ``index_topk``."""
    cfg, params = _walked()
    kv = dm.cache_config(cfg, BLOCK, 24)
    assert [dm.attention_path(cfg, kv, LANES, kind)
            for kind in ("selected", "index")] == ["pallas_masked", "pallas"]
    monkeypatch.setattr(pa, "_chunk_mask", lambda *a: pytest.fail(
        "the served walk laid a list out as its mask"))
    made, by_chunk = [], pa.chosen_by_chunk
    monkeypatch.setattr(pa, "chosen_by_chunk", lambda *a: (
        made.append(a[2:]), by_chunk(*a))[1])
    logits, noted = _serve(check, cfg, params, record=True)
    span = dm.chunk_positions(cfg, kv, LANES)["latent"]
    assert span == cfg.max_seq == 128 and made == [(16, 1, span)] * 3
    assert fam.counters("pallas_kernel_") == {
        "pallas_kernel_used_total{kernel=%s}" % k: n for k, n in (
            ("index_scores", 3), ("latent_attention", 3),
            ("moe_experts", 2))}
    assert len(noted) == DEPTH
    for g, (scores, positions, count) in enumerate(noted):
        lens = jnp.full(LANES, g + 1, jnp.int32)
        for l in range(cfg.layers):
            assert count[l].tolist() == [min(g + 1, 16)] * LANES
            mask = by_chunk(jnp.asarray(scores[l]), lens, cfg.index_topk, 1,
                            span)
            assert np.array_equal(
                np.asarray(mask).reshape(LANES, -1) != 0,
                np.asarray(pa.chosen_mask(jnp.asarray(positions[l]),
                                          jnp.asarray(count[l]), span)))
    assert np.isfinite(logits).all()


@pytest.mark.parametrize("stand_in", ["most_recent", "no_selection",
                                      "jnp_paths"])
def test_the_checks_stand_ins_still_bite_on_the_walk(
        interpreted, monkeypatch, check, stand_in):
    """Each of the check's stand-ins on the same step, unedited.
    ``most_recent`` replaces ``choose`` by a function of a plain pair, which
    can give no mask: the one-hot contraction lays its list out, no
    threshold is taken, and the logits past ``index_topk`` are another
    model's.  ``no_selection`` is handed the mask in the positions' place and
    drops it: another model's too.  ``jnp_paths`` reads the mask through
    ``chosen_mask`` (rank 3 there) and gives the served logits."""
    cfg, params = _walked()
    served, _none = _serve(check, cfg, params)
    made, laid, read = [], [], []
    by_chunk, one_hot, as_mask = (pa.chosen_by_chunk, pa._chunk_mask,
                                  pa.chosen_mask)
    monkeypatch.setattr(pa, "chosen_by_chunk", lambda *a: (
        made.append(a[2:]), by_chunk(*a))[1])
    monkeypatch.setattr(pa, "_chunk_mask", lambda *a: (
        laid.append(a[0].shape), one_hot(*a))[1])
    monkeypatch.setattr(pa, "chosen_mask", lambda positions, *a: (
        read.append(positions.ndim), as_mask(positions, *a))[1])
    logits, _none = _serve(check, cfg, params, fault=stand_in)
    far = np.abs(logits[16:] - served[16:]).max()
    assert np.abs(logits[:16] - served[:16]).max() < 2e-4
    if stand_in == "most_recent":
        assert (len(made), laid, read) == (0, [(LANES, 16)] * 3, [])
        assert far > 0.01
    elif stand_in == "no_selection":
        assert (len(made), laid, read) == (3, [], [])
        assert far > 0.01
    else:
        assert (len(made), laid, read) == (3, [], [3] * 3)
        assert far < 2e-4


def test_the_engine_serves_the_walk_on_the_kernels_the_check_names(
        interpreted, cache_dir, telemetry_on, tmp_path):
    """Through the engine with the kernels interpreted, 30 tokens past
    ``index_topk`` on the masked walk: the lowering counted the three
    kernels that ``chip_check_glm.py --engine`` wants counted and no other
    (the threshold is no kernel's: it adds no name there), nothing fell
    back, and the compiled step holds no ``top_k``."""
    from paddle_tpu.serving.engine import DecodeEngine

    cfg, params = _walked()
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path),
                   kv_block_size=BLOCK):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("glm", (cfg, params), kv_blocks=24)
        e.start()
        try:
            e.prewarm()
            r = e.generate("glm", [1, 2, 3], max_new_tokens=30,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["latent_attention"] == "pallas_masked"
        and ev["index_path"] == "pallas"
        and ev["chunk_positions"] == {"latent": 128} for ev in warm)
    assert set(fam.counters("pallas_kernel_")) == {
        "pallas_kernel_used_total{kernel=%s}" % k
        for k in ("index_scores", "latent_attention", "moe_experts")}
    kv = dm.cache_config(cfg, BLOCK, 24)
    text = jax.jit(dm.make_packed_step(cfg, kv, LANES)).lower(
        kvc.PagedKVCache(kv).carry(), params, jnp.zeros(LANES, jnp.int32),
        jnp.zeros((LANES, dm.lane_columns(kv, cfg.max_seq // BLOCK)[1]),
                  jnp.int32)).compile().as_text()
    # the routers' 3 of 16 experts are the step's only top_k
    found = [line for line in text.splitlines()
             if re.search(r"(?i)topk|\bsort\(", line)]
    assert found and all("moe/router" in line for line in found)
