"""The contract every decoder family meets, written once and run over the
rows of ``tests/decoder_families.py`` (the family's name is in each case's
id): the paged step bitwise equal to the unpaged loop; the multi-token step
equal to single steps, or refused over rings; the engine moving lanes up and
reusing what a sequence held; preemption replaying into a fresh slot or an
empty ring; what a family that keeps more than K and V declines (prefix
reuse, speculation, export and adoption), each under its reason, and what a
family whose every layer pages (K and V, or a latent pool's rows) is given
of them; the bundle
round trip; ``tools/serve.py``'s demo bundle served at the defaults; and the
weights as a step holds them (``decode_model.laid_out``): the published
arrays' own logits, no relayout between a laid-out weight and its product,
nothing moved for a family that declares no layout, and an engine that
leaves its caller's arrays alone.  A
family's own file (``tests/test_<family>.py``) holds what is its alone."""

import json
import threading

import jax
import numpy as np
import pytest

import decoder_families as fam
import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.utils import fault_injection

BS = fam.BS
holding = fam.cases(lambda row: row.holds, ["f32"])
# the families whose every layer pages (K and V, or latent rows): nothing
# that starts or moves a sequence at a position is declined for them
paging = fam.cases(lambda row: not row.holds)


@pytest.mark.parametrize("row,key", fam.cases())
def test_paged_is_bitwise_equal_to_unpaged(row, key):
    """A prompt, then 20 decoded tokens (40 over rings: five windows deep)
    through the paged step and the cache manager against the unpaged loop:
    the same tokens and, bit for bit, the same logits.  A ring never holds
    more than ``ceil(window / block) + 1`` blocks, whatever the length: what
    left the window went back."""
    cfg, params = row.configs[key]
    n = 40 if row.holds == "ring" else 20
    log = []
    ((fed, logits),), _routed = fam.run_paged(cfg, params, [(fam.PROMPT, n)],
                                             ring_log=log)
    want, want_logits = fam.generate(cfg, params, fam.PROMPT, n,
                                     return_logits=True)
    at = len(fam.PROMPT)
    assert fed[at:] == want and len(set(want)) > 2
    assert np.array_equal(logits[at - 1:at - 1 + n], np.stack(want_logits))
    if row.holds == "ring":
        ring = fam.ring_blocks(cfg)
        assert at + n > 5 * cfg.window
        assert max(hi - lo for _i, _p, lo, hi, _t, _u in log) == ring
        assert all(used == hi - lo == (table >= 0).sum()
                   for _i, _p, lo, hi, table, used in log)
        _i, pos, lo, hi, _t, _u = log[-1]
        assert (lo, hi) == ((pos + 1 - cfg.window) // BS, pos // BS + 1)


@pytest.mark.parametrize("width", [2, 3])
@pytest.mark.parametrize("row,key", fam.cases(dtypes=["f32"]))
def test_multi_token_step_equals_single(row, key, width):
    """``width`` single steps composed in one call give the single steps'
    logits (bit for bit, or as near as the row says) and route as many
    tokens: whole chunks of prefill for a model with recurrent layers, and
    for the others decode too, a verify's junk columns frozen.  A ring holds
    one write beside its window, so over rings the step is refused."""
    cfg, params = row.configs[key]
    if row.holds == "ring":
        kv = dm.cache_config(cfg, BS, 40, state_slots=3)
        with pytest.raises(ValueError, match="window layers' rings"):
            dm.make_paged_step_multi(cfg, kv, width)
        return
    n = 0 if cfg.recurrent_layers else 4
    seqs = [(list(range(3, 15)), n), (list(range(20, 26)), n)]
    single, routed1 = fam.run_paged(cfg, params, seqs)
    multi, routed = fam.run_paged(cfg, params, seqs, width=width)
    for (t1, l1), (tw, lw) in zip(single, multi):
        assert t1 == tw
        if row.multi_atol is None:
            assert np.array_equal(l1, lw)
        else:
            np.testing.assert_allclose(lw, l1, atol=row.multi_atol)
    if cfg.routed_layers:
        # a step's counts are summed over its columns
        assert routed[0].shape == (len(cfg.routed_layers), cfg.router_width)
        if not n:
            assert sum(int(r.sum()) for r in routed) \
                == sum(int(r.sum()) for r in routed1) \
                == 18 * len(cfg.routed_layers) * cfg.experts_per_token


@pytest.mark.parametrize("row,key", fam.cases(lambda row: row.holds,
                                              ["f32", "bf16"]))
def test_lanes_move_up_and_what_a_sequence_held_is_reused(
        row, key, cache_dir, telemetry_on):
    """Six requests over four lanes, lengths all different and up to six
    windows long, through add_model -> prewarm -> the engine's loop (a step
    ahead of its tokens): sequences finish mid-batch, later lanes move up a
    place, the waiting ones take the freed slots or the freed rings' blocks
    (dirty: nothing clears them, a lane at position 0 starts from zeros),
    and every request's tokens are those of the sequence alone."""
    cfg, params = row.configs[key]
    e = fam.engine(cfg, params, 80)
    try:
        manifest = e.prewarm()
        assert manifest["m"][4]["source"] in ("compiled", "disk")
        m = e._models["m"]
        assert e.spec("m")["arch"] == row.arch
        assert m.prefix is None and m.declines == row.declines
        for name, want in row.entry.items():
            assert getattr(m, name) == (want(cfg) if callable(want)
                                        else want), name
        if row.holds == "slot":
            assert e.spec("m")["state_slots"] == 5
        else:
            assert m.kv_config.window_blocks == 5 * fam.ring_blocks(cfg)
        miss0 = _tm.counter_total("executor_cache_miss_total")
        prompts = [fam.PROMPT, [2, 7], [1, 8, 2, 8], [6],
                   [9, 9, 8, 7, 6, 5], [4, 4]]
        news = [37, 11, 50, 8, 27, 19] if row.holds == "ring" \
            else [5, 11, 3, 8, 7, 6]
        if key == "bf16" and row.batch_dependent_bf16:
            # bfloat16 rounds what float32 sums in another order at another
            # batch: alone, but a lane of the same four-lane step
            want = [e.generate("m", p, max_new_tokens=n,
                               deadline_ms=60000.0).outputs["tokens"]
                    for p, n in zip(prompts, news)]
            _tm.reset()
            miss0 = 0
        else:
            want = [fam.alone(cfg, params, p, n)
                    for p, n in zip(prompts, news)]
        with e._cond:
            waits = [e.submit("m", p, max_new_tokens=n, deadline_ms=60000.0)
                     for p, n in zip(prompts, news)]
        for p, tokens, w in zip(prompts, want, waits):
            r = w.wait(timeout=120.0)
            assert r is not None and r.status == "ok", r and r.error
            assert np.array_equal(r.outputs["tokens"], tokens), p
        assert m.cache.allocator.in_use == 0
        if row.holds == "slot":
            assert m.cache.slots.in_use == 0
            # one reset a sequence: its first step starts the slot from
            # zeros, under the name of the kind of state it holds
            resets = {name: _tm.counter_total(name + "_resets_total")
                      for name in dm.STATE_NAMES.values()}
            assert resets.pop(cfg.state_name) == len(prompts)
            assert not any(resets.values())
        else:
            # four lanes of a ring each at the most, while the global
            # layer's blocks were held to the end: 13 for the longest
            assert m.cache.allocator.high_water >= 13
            assert m.cache.window_allocator.in_use == 0
            assert m.cache.window_allocator.high_water \
                <= 4 * fam.ring_blocks(cfg)
        assert _tm.counter_total("executor_cache_miss_total") == miss0
        assert _tm.counter_total("serving_steps_ahead_total") > 0
    finally:
        e.stop()


@pytest.mark.parametrize("row,key", fam.cases(dtypes=["f32"]))
def test_prewarm_names_the_chunk_by_kind_of_layer(
        row, key, cache_dir, telemetry_on, tmp_path, monkeypatch):
    """The ``serving_prewarm`` event carries ``chunk_positions``: the
    positions a chunk of the attention kernel spans, by kind of layer that
    takes the kernel (the kernel sizes its chunk by the bytes a position
    costs in that kind's pools).  At the family's published widths (its
    benchmark configuration, 32 lanes, its cell's pool) that is the row's
    ``chunk``; at the tests' widths, under the 128 lanes, every kind gathers
    and the engine's event says so: no kind has a chunk."""
    name, blocks, want = row.chunk
    with open(fam.config_file(name)) as fp:
        config = json.load(fp)
    config.pop("tiny", None)
    published = fam.load("benchmark", "models", config["model"] + ".py") \
        .decoder_config(config)
    kv = dm.cache_config(published, 16, blocks, published.kv_dtype or "f32",
                         state_slots=33)
    assert dm.chunk_positions(published, kv, 32) == {}      # the CPU gathers
    with monkeypatch.context() as mp:
        mp.setenv("PADDLE_PALLAS_INTERPRET", "1")           # as a TPU would
        assert dm.chunk_positions(published, kv, 32) == want
    cfg, params = row.configs[key]
    with fam.flags(telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 40, start=False)
        e.prewarm()
        _tm.flush()
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(ev["chunk_positions"] == {} == dm.chunk_positions(
        cfg, e._models["m"].kv_config, ev["bucket"]) for ev in warm)


@pytest.mark.parametrize("row,key", holding)
def test_preemption_replays_into_a_fresh_slot_or_an_empty_ring(
        row, key, cache_dir, telemetry_on):
    """Capacity 7 blocks, A wants 6 and B 4, both several windows long: B is
    preempted, gives its slot or its ring back with its blocks, and replays
    from position 0 (its state reset); both finish with the tokens of the
    sequence alone."""
    cfg, params = row.configs[key]
    e = fam.engine(cfg, params, 8, buckets="2")
    try:
        asks = ([1, 2, 3, 4], 20), ([5, 6, 7, 8], 12)
        with e._cond:
            waits = [e.submit("m", p, max_new_tokens=n, deadline_ms=60000.0)
                     for p, n in asks]
        for (p, n), w in zip(asks, waits):
            r = w.wait(timeout=120.0)
            assert r is not None and r.status == "ok", r and r.error
            assert np.array_equal(r.outputs["tokens"],
                                  fam.alone(cfg, params, p, n))
        assert _tm.counter_total("kv_block_evictions_total") >= 1
        cache = e._models["m"].cache
        if row.holds == "slot":
            assert _tm.counter_total(cfg.state_name + "_resets_total") >= 3
            assert cache.slots.in_use == 0
        else:
            assert cache.window_allocator.in_use == 0
            assert cache.window_allocator.high_water \
                <= 2 * fam.ring_blocks(cfg)
    finally:
        e.stop()


@pytest.mark.parametrize("row,key", holding)
def test_prefix_cache_declines_and_counts(row, key, cache_dir, telemetry_on):
    """FLAGS_prefix_cache is on by default: for a model that keeps more
    than K and V there is no index, each admission is counted under the
    family's reason, and two requests with one prompt give the tokens of
    the prompt alone (a hit would have started the second at pos 12 with no
    state, no window, an empty ring)."""
    assert fluid.get_flags(["FLAGS_prefix_cache"])["FLAGS_prefix_cache"]
    cfg, params = row.configs[key]
    e = fam.engine(cfg, params, 40)
    try:
        prompt = fam.PROMPT + [8, 9, 7]
        want = fam.alone(cfg, params, prompt, 9)
        for _ in range(2):
            r = e.generate("m", prompt, max_new_tokens=9,
                           deadline_ms=60000.0)
            assert r.status == "ok" and r.phases["cached_tokens"] == 0
            assert np.array_equal(r.outputs["tokens"], want)
        # the hand-off of a prefill replica has nothing to transfer
        assert e.handoff_prefill_upto("m", len(prompt)) == 0
        assert fam.counters("prefix_cache_declined_total") == {
            "prefix_cache_declined_total{model=m,reason=%s}"
            % row.declines: 2}
        assert not fam.counters("prefix_cache_hit_tokens_total")
    finally:
        e.stop()


@pytest.mark.parametrize("row,key", holding)
def test_speculation_is_refused(row, key, cache_dir):
    """A draft is a truncation that keeps the family's block; the engine
    refuses to speculate with it for a state that cannot be rolled back or
    a ring that holds one write, and without a draft ignores ``k``."""
    cfg, params = row.configs[key]
    dcfg, _dparams = draft = dm.truncate_decoder(cfg, params, layers=2)
    assert dcfg.layer_types == cfg.layer_types[:2]
    assert dcfg.dense_layers == min(cfg.dense_layers, 2)
    assert dcfg.routed_layers == tuple(l for l in cfg.routed_layers if l < 2)
    e = fam.engine(cfg, params, 16, buckets="2", start=False,
                   speculative_k=2)
    with fam.flags(kv_block_size=BS), pytest.raises(ValueError,
                                                    match=row.refusal):
        e.add_model("m2", (cfg, params), kv_blocks=16, draft=draft,
                    speculative_k=2)
    assert e.spec("m")["speculative_k"] == 0


@pytest.mark.parametrize("row,key", holding)
def test_export_adoption_and_history_are_refused_with_their_reason(
        row, key, cache_dir, telemetry_on):
    cfg, params = row.configs[key]
    with fam.flags(session_migration=True):
        e = fam.engine(cfg, params, 24, buckets="2")
        try:
            # 1 ms a step keeps the request alive while it is exported
            fault_injection.arm("serving.decode_step:delay:1")
            streamed = threading.Event()
            done = e.submit("m", [1, 2, 3, 4, 5], max_new_tokens=40,
                            deadline_ms=60000.0,
                            on_token=lambda *a: streamed.set())
            assert streamed.wait(60.0)
            with pytest.raises(ValueError, match=row.declines):
                e.export_session(done.req_id)
            fault_injection.disarm()
            with e._cond:        # between steps: the carry is donated
                block = e._models["m"].cache.export_block(1)
            assert e.adopt_kv_block("m", "00" * 32, block) \
                == "rejected:" + row.declines
            assert fam.counters("kv_migrate_refused_total") == {
                "kv_migrate_refused_total{reason=%s}" % row.declines: 2}
            r = done.wait(timeout=120.0)
            assert r.status == "ok"
            assert np.array_equal(
                r.outputs["tokens"],
                fam.alone(cfg, params, [1, 2, 3, 4, 5], 40))
            # 45 positions, 11 full blocks: no history block was published
            assert not fam.counters("kv_history_published_total")
        finally:
            fault_injection.disarm()
            e.stop()


@pytest.mark.parametrize("row,key", paging)
def test_a_prefix_hit_and_a_chunked_prompt_give_the_prompts_own_tokens(
        row, key, cache_dir, telemetry_on):
    """Every layer pages, so there is an index and nothing is declined: a
    repeated prompt starts past its cached full blocks (rows a step before
    wrote, rotated by the same positions, for a latent pool as for K and V),
    a prompt that shares its first blocks too, and prompts fed two tokens an
    iteration through the multi-token step (chunked ingest) come out as
    alone."""
    cfg, params = row.configs[key]
    e = fam.engine(cfg, params, 64)
    try:
        m = e._models["m"]
        assert m.declines is None and m.prefix is not None
        assert e.handoff_prefill_upto("m", 14) == 12
        prompt = fam.PROMPT + [8, 9, 7]
        first = e.generate("m", prompt, max_new_tokens=9,
                           deadline_ms=60000.0)
        again = e.generate("m", prompt, max_new_tokens=9,
                           deadline_ms=60000.0)
        assert first.status == again.status == "ok", first.error
        assert (first.phases["cached_tokens"],
                again.phases["cached_tokens"]) == (0, 12)
        assert np.array_equal(first.outputs["tokens"],
                              again.outputs["tokens"])
        if not (key == "bf16" and row.batch_dependent_bf16):
            assert np.array_equal(first.outputs["tokens"],
                                  fam.alone(cfg, params, prompt, 9))
        other = prompt[:8] + [2, 2, 3]
        cold = fam.engine(cfg, params, 64, name="cold")
        try:
            want = cold.generate("cold", other, max_new_tokens=9,
                                 deadline_ms=60000.0)
            with fam.flags(decode_prefill_token_budget=2):
                with cold._cond:        # admitted the same iteration
                    asks = [cold.submit("cold", [t] * 13, max_new_tokens=5,
                                        deadline_ms=60000.0)
                            for t in (1, 2, 3)]
                chunked = [a.wait(timeout=120.0) for a in asks]
            singly = [cold.generate("cold", [t] * 13, max_new_tokens=5,
                                    deadline_ms=60000.0) for t in (1, 2, 3)]
        finally:
            cold.stop()
        hit0 = _tm.counter_total("prefix_cache_hit_tokens_total")
        shared = e.generate("m", other, max_new_tokens=9,
                            deadline_ms=60000.0)
        assert shared.status == "ok" and shared.phases["cached_tokens"] == 8
        assert _tm.counter_total("prefix_cache_hit_tokens_total") == hit0 + 8
        assert np.array_equal(shared.outputs["tokens"],
                              want.outputs["tokens"])
        for a, b in zip(chunked, singly):
            assert a is not None and a.status == b.status == "ok"
            if not (key == "bf16" and row.batch_dependent_bf16):
                assert np.array_equal(a.outputs["tokens"],
                                      b.outputs["tokens"])
        assert not fam.counters("prefix_cache_declined_total")
    finally:
        e.stop()


@pytest.mark.parametrize("row,key", fam.cases(lambda row: not row.holds,
                                              ["f32"]))
def test_speculation_verifies_over_what_the_layers_page(row, key, cache_dir,
                                                        telemetry_on):
    """A one-layer truncation drafts two tokens a step, the multi-token step
    verifies three positions over the target's pools (a latent pool's rows
    beyond the accepted context are rewritten before anything attends them,
    as K and V are), rejected proposals give their blocks back, and the
    tokens are the greedy ones."""
    cfg, params = row.configs[key]
    e = fam.engine(cfg, params, 64, buckets="2",
                   draft=dm.truncate_decoder(cfg, params, layers=1),
                   speculative_k=2)
    try:
        assert e.spec("m")["speculative_k"] == 2
        asks = [(fam.PROMPT, 14), ([4, 4, 2], 9)]
        with e._cond:
            waits = [e.submit("m", p, max_new_tokens=n, deadline_ms=60000.0)
                     for p, n in asks]
        for (p, n), w in zip(asks, waits):
            r = w.wait(timeout=120.0)
            assert r is not None and r.status == "ok", r and r.error
            assert np.array_equal(r.outputs["tokens"],
                                  fam.alone(cfg, params, p, n))
        assert _tm.counter_total("spec_tokens_proposed_total") > 0
        m = e._models["m"]
        assert m.cache.allocator.in_use == 0 \
            and m.draft_cache.allocator.in_use == 0
    finally:
        e.stop()


@pytest.mark.parametrize("row,key", paging)
def test_a_block_is_exported_and_adopted_with_every_layers_rows(
        row, key, cache_dir, telemetry_on):
    """What a block's frame holds is every paging layer's rows of it: K and
    V stacked over the attention layers, a latent pool's rows stacked over
    the latent layers (never read as K and V), and where those select their
    index keys after them.  A float32 session is
    exported with its history blocks and an exported block adopted under a
    digest is matched by the next prompt that hashes to it; bfloat16 has no
    frame (the codec names dtypes as numpy does) and is refused as such."""
    from paddle_tpu.serving import kv_cache as kvc

    cfg, params = row.configs[key]
    with fam.flags(session_migration=True):
        e = fam.engine(cfg, params, 32, buckets="2")
        try:
            fault_injection.arm("serving.decode_step:delay:1")
            streamed = threading.Event()
            done = e.submit("m", [1, 2, 3, 4, 5], max_new_tokens=40,
                            deadline_ms=60000.0,
                            on_token=lambda *a: streamed.set())
            assert streamed.wait(60.0)
            m = e._models["m"]
            kv = m.kv_config
            with e._cond:        # between steps: the carry is donated
                e._drain_locked()
                block = m.cache.export_block(1)
            shapes = [a.shape for a in block]
            assert shapes == [(kv.layers, BS, kv.heads, kv.head_dim)] * (
                2 if kv.layers else 0) + [
                (kv.latent_layers, BS, kv.latent_row)] * bool(
                    kv.latent_layers) + [
                (kv.index_layers, BS, kv.index_width)] * bool(
                    kv.index_layers)
            # the first block of the sequence: its first four rows
            assert all(np.abs(a.astype(np.float32)).sum() > 0 for a in block)
            if key == "bf16":
                with pytest.raises(ValueError, match="dtype"):
                    e.export_session(done.req_id)
                assert fam.counters("kv_migrate_refused_total") == {
                    "kv_migrate_refused_total{reason=dtype}": 1}
            else:
                manifest, payloads = e.export_session(done.req_id)
                assert manifest["pos"] >= 5 and payloads
                assert [a.shape for a in payloads[0][2]] == shapes
                e.abort_migration(done.req_id)
        finally:
            fault_injection.disarm()
            e.stop()
        if key == "bf16":
            return
        # another engine adopts the block under the digest of its tokens
        other = fam.engine(cfg, params, 32, buckets="2", name="o")
        try:
            mo = other._models["o"]
            digest = mo.prefix.chain([1, 2, 3, 4])[0]
            assert other.adopt_kv_block("o", digest, block) == "adopted"
            assert other.adopt_kv_block("o", digest, block) == "cached"
            with other._cond:
                at = mo.prefix.lookup(digest)
                got = mo.cache.export_block(at)
            assert all(np.array_equal(a, b) for a, b in zip(got, block))
            bad = [a[:, :2] for a in block]
            assert other.adopt_kv_block("o", "11" * 32, bad).startswith(
                "rejected:kv import geometry mismatch")
            r = other.generate("o", [1, 2, 3, 4, 5], max_new_tokens=12,
                               deadline_ms=60000.0)
            assert r.status == "ok" and r.phases["cached_tokens"] == 4
            assert np.array_equal(
                r.outputs["tokens"],
                fam.alone(cfg, params, [1, 2, 3, 4, 5], 12))
            assert kvc.block_bytes(mo.kv_config) == sum(
                a.nbytes for a in block)
        finally:
            other.stop()


# the families that declare a layout of their own for a step's weights
laying = fam.cases(lambda row: hasattr(dm._model(row.arch), "laid_out"))


@pytest.mark.parametrize("width", [1, 2])
@pytest.mark.parametrize("row,key", laying)
def test_laid_out_weights_give_the_published_weights_logits(row, key, width):
    """The single-token and the multi-token step over ``laid_out(cfg,
    params)`` against the same steps over the published ``params``: the
    same tokens and, bit for bit, the same logits (on the CPU both forms of
    each ``wkvb`` product contract a head's values in the same order)."""
    cfg, params = row.configs[key]
    held = dm.laid_out(cfg, params)
    assert sorted(set(held) - set(params)) == sorted(
        "l%d_wkvb_%s" % (l, half)
        for l in cfg.latent_layers for half in "kv")
    assert sorted(set(params) - set(held)) == [
        "l%d_wkvb" % l for l in cfg.latent_layers]
    n = 0 if cfg.recurrent_layers and width > 1 else 4
    seqs = [(list(range(3, 15)), n), (list(range(20, 26)), n)]
    published, _ = fam.run_paged(cfg, params, seqs, width=width)
    laid, _ = fam.run_paged(cfg, held, seqs, width=width)
    for (t0, l0), (t1, l1) in zip(published, laid):
        assert t0 == t1 and len(set(t0)) > 2
        assert np.array_equal(l0, l1)


def _readers(jaxpr, var):
    """The primitives that read ``var`` in ``jaxpr``, looked for inside the
    calls it is handed to (``jnp.einsum`` is one)."""
    found = []
    for eqn in jaxpr.eqns:
        for at, operand in enumerate(eqn.invars):
            if operand is not var:
                continue
            inner = eqn.params.get("jaxpr")
            if inner is None:
                found.append(eqn.primitive.name)
            else:
                inner = getattr(inner, "jaxpr", inner)
                found += _readers(inner, inner.invars[at])
    return found


@pytest.mark.parametrize("row,key", laying)
def test_a_laid_out_weight_is_read_by_its_product_and_nothing_else(row, key):
    """In the traced step (the jaxpr: no backend's choice) each half of a
    laid-out ``wkvb`` is an operand of its ``dot_general`` and of nothing
    before it: no transpose, no reshape and slice, no copy.  The published
    array, by contrast, is reshaped first."""
    cfg, params = row.configs[key]
    kv = dm.cache_config(cfg, BS, 40, state_slots=6)
    _columns, ncols = dm.lane_columns(kv, cfg.max_seq // BS)
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    carry = shaped(fam.kvc.PagedKVCache(kv).carry())
    feeds = (jax.ShapeDtypeStruct((4,), np.int32),
             jax.ShapeDtypeStruct((4, ncols), np.int32))

    def readers(weights):
        weights = shaped(weights)
        traced = jax.make_jaxpr(dm.make_packed_step(cfg, kv, 4))(
            carry, weights, *feeds).jaxpr
        names = sorted(weights)           # a dict flattens by its keys
        at = len(jax.tree_util.tree_leaves(carry))
        return {name: _readers(traced, traced.invars[at + i])
                for i, name in enumerate(names) if "_wkvb" in name}

    laid = readers(dm.laid_out(cfg, params))
    assert len(laid) == 2 * len(cfg.latent_layers)
    assert all(read == ["dot_general"] for read in laid.values()), laid
    published = readers(fam.as_jnp(params))
    assert len(published) == len(cfg.latent_layers)
    assert all(read == ["reshape"] for read in published.values()), published


@pytest.mark.parametrize("row,key", fam.cases())
def test_the_engine_lays_out_a_copy_and_counts_it(row, key, cache_dir,
                                                  telemetry_on):
    """``laid_out`` hands a family that declares no layout its own arrays
    back, every one; for one that does, the arrays it does not name.
    ``add_model`` leaves the caller's dict and its arrays as they were, the
    bytes it plans with are the published ones (the two forms weigh the
    same), ``decode_weights_laid_out_bytes{model}`` says what the step
    holds in the family's own layout (nothing, or every latent layer's
    ``wkvb``) and the step's cache key names those weights."""
    cfg, params = row.configs[key]
    mine = fam.as_jnp(params)
    held = dm.laid_out(cfg, mine)
    assert all(held[name] is mine[name] for name in held if name in mine)
    own = list(cfg.latent_layers)
    if not hasattr(dm._model(row.arch), "laid_out"):
        assert not own and sorted(held) == sorted(mine)
    before = dict(mine)
    e = fam.engine(cfg, mine, 40, start=False)
    assert sorted(mine) == sorted(before) \
        and all(mine[name] is before[name] for name in before)
    entry = e._models["m"]
    published = sum(int(v.nbytes) for v in mine.values())
    assert sum(int(v.nbytes) for v in entry.params.values()) == published \
        == fam.kvc._LIVE_RESIDENT[entry]
    laid = sorted("l%d_wkvb_%s" % (l, half) for l in own for half in "kv")
    assert sorted(set(entry.params) - set(mine)) == laid
    # the step's cache key names them, and is as it was where there are none
    assert entry.stepfn._key_parts.get("weights_laid_out", []) == laid \
        and ("weights_laid_out" in entry.stepfn._key_parts) == bool(laid)
    assert _tm.snapshot()["gauges"][
        "decode_weights_laid_out_bytes{model=m}"] == sum(
            int(mine["l%d_wkvb" % l].nbytes) for l in own)


@pytest.mark.parametrize("row,key", fam.cases())
def test_bundle_roundtrip(row, key, tmp_path):
    cfg, params = row.configs[key]
    d = dm.save_decoder(str(tmp_path / "b"), cfg, params)
    got_cfg, got = dm.load_decoder(d)
    assert got_cfg.to_dict() == cfg.to_dict()
    assert got_cfg.layer_types == cfg.layer_types
    assert set(got) == set(params)
    for k, v in params.items():
        assert got[k].dtype == v.dtype and got[k].shape == v.shape
        assert np.array_equal(got[k].view(np.uint8), v.view(np.uint8)), k


@pytest.mark.parametrize("row", [
    pytest.param(row, id=row.arch) for row in fam.ROWS.values() if row.serve])
def test_serve_tool_writes_and_serves_a_demo_bundle(row, tmp_path,
                                                    cache_dir):
    """tools/serve.py builds a demo bundle from the benchmark's
    configuration file (its tiny sizes; GPT-2's from the tool's own six
    numbers), a one-layer draft beside it, and the engine serves that
    directory at the defaults: no speculation, and the tokens of the
    unpaged loop, several windows deep where there is a window."""
    name, says = row.serve
    d = fam.save_demo_decoder(str(tmp_path / "dec"),
                              config=name and fam.config_file(name))
    cfg, params = dm.load_decoder(d)
    served = "bf16" if "bf16" in row.configs else "f32"
    assert (cfg.arch, cfg.dtype, cfg.kv_dtype) \
        == (row.arch, served, "bf16" if served == "bf16" else None)
    for field, want in says.items():
        assert getattr(cfg, field) == want, field
    draft, _params = dm.load_draft(d)
    # a layer's truncation; of a block of pairs, a pair's
    assert (draft.arch, draft.layer_types) == (
        row.arch, cfg.layer_types[:cfg.layers_a_block])
    e = fam.engine(d, None, 24, buckets="2")
    try:
        assert e.spec("m")["arch"] == row.arch \
            and e.spec("m")["kv_dtype"] == (cfg.kv_dtype or "f32") \
            and e.spec("m")["speculative_k"] == 0
        n = 30 if row.holds == "ring" else 20
        r = e.generate("m", [5, 6, 7], max_new_tokens=n, deadline_ms=60000.0)
        assert r.status == "ok", r.error
        again = e.generate("m", [5, 6, 7], max_new_tokens=n,
                           deadline_ms=60000.0)
        assert np.array_equal(r.outputs["tokens"], again.outputs["tokens"])
        assert np.array_equal(r.outputs["tokens"],
                              fam.alone(cfg, params, [5, 6, 7], n))
    finally:
        e.stop()
