"""What is the GLM-5 decoder block's own (paddle_tpu/models/glm_dsa.py:
latent attention that selects, a head's values wider than its own key part,
an index-key pool beside every latent pool): logits at every position
against its plain reference (benchmark/reference/glm_dsa_ref.py, the file
the benchmark uses: expanded attention, the indexer and an exact choice by a
stable sort), prefill then decode through the paged step and the cache
manager with the selection in play (``index_topk`` 8, sequences of 13-22
positions); the reference told otherwise, control by control; the choice
against the reference's on scores with ties; the share; what the cache
manager gives a model with two pools a layer (a block's bytes, the plan, a
prefix hit, export and adoption); the step's span and prewarm event; the
multi-token step; the kernels under the interpreter.  The contract it shares
with every family is tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: 4 ``latent`` layers,
hidden 48 under 4 heads of 12 own key values (+ 8 rotated) and 16 value
values over 24 latent values, the query through 20, an indexer of 4 heads of
16 that keeps 8 positions, a dense lead of width 64, 16 experts of width 24,
3 a token, a shared one of width 24, vocab 97."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import glm_dsa as gd
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

CONFIG_FILE = fam.config_file("glm-5-serve.json")
ref = fam.load("benchmark", "reference", "glm_dsa_ref.py")
model = fam.load("benchmark", "models", "glm_dsa_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["glm_dsa"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(gd.init_params, std=0.3, bias_std=0.05)


def run_paged(cfg, params, seqs, **kw):
    """``fam.run_paged``, every live lane's token counted once by each
    routed layer's router."""
    out, routed = fam.run_paged(cfg, params, seqs, **kw)
    rows = len(cfg.routed_layers)
    assert all(r.shape == (rows, cfg.experts) for r in routed)
    assert sum(int(r.sum()) for r in routed) == rows \
        * cfg.experts_per_token * sum(len(toks) for toks, _lg in out)
    return out


def ref_config(cfg, **changed):
    """The source's keys, as the reference reads them."""
    return dict({
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "num_hidden_layers": cfg.layers, "kv_lora_rank": cfg.latent_rank,
        "q_lora_rank": cfg.q_rank, "qk_nope_head_dim": cfg.head_dim,
        "qk_rope_head_dim": cfg.latent_rope, "v_head_dim": cfg.v_head_dim,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "index_key_norm_eps": gd.INDEX_NORM_EPS,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "rope_interleave": True, "indexer_rope_interleave": True,
        "first_k_dense_replace": cfg.dense_layers,
        "intermediate_size": cfg.dense_ffn,
        "moe_intermediate_size": cfg.ffn, "num_experts": cfg.experts_held,
        "n_routed_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": cfg.routed_scaling,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0, "rms_norm_eps": cfg.norm_eps},
        **changed)


# float32 rounding over four layers (measured 4e-5 here); a fault in
# structure is 0.3 or more (the broken-reference controls below)
TOL_F32 = 2e-4


def _ref(cfg, params, tokens, kept=False, broken=None, **changed):
    layer_fn = functools.partial(ref.layer, **broken) if broken else ref.layer
    with jax.default_matmul_precision("highest"):
        out = ref.forward(ref_config(cfg, **changed), _jnp(params),
                          jnp.asarray(tokens, jnp.int32), kept,
                          layer_fn=layer_fn)
    return jax.tree_util.tree_map(np.asarray, out)


def _worst(cfg, out, params, **kw):
    return max(float(np.abs(lg - _ref(cfg, params, toks, **kw)).max())
               for toks, lg in out)


# -- 1. against the reference, and the reference broken ------------------------

@functools.lru_cache(None)
def _f32_out():
    return run_paged(CFG, PARAMS, fam.sequences(3))


def test_f32_logits_equal_the_reference_at_every_position():
    """Three sequences in three lanes of one paged step, each fed its prompt
    a token a step and then 8 of its own tokens, every one past the 8
    positions the indexer keeps: at every position the step's logits are the
    reference's full forward pass of the sequence so far (expanded attention
    over the set its own exact choice gives), and what every layer's two
    pools hold of a sequence are the reference's ``[c | rotated k_pe]`` rows
    and index keys."""
    out = _f32_out()
    assert len({len(t) for t, _lg in out}) > 1
    assert min(len(t) for t, _lg in out) > CFG.index_topk + 4
    assert _worst(CFG, out, PARAMS) < TOL_F32
    assert all(len(set(t[-8:])) > 2 for t, _lg in out)
    held = {}

    def keep(kv, carry):
        held["rows"] = [np.asarray(p) for p in kv.latent_pools(carry)]
        held["keys"] = [np.asarray(p) for p in kv.index_pools(carry)]
        return carry

    toks = fam.PROMPT + [7, 7, 2]
    fam.run_paged(CFG, PARAMS, [(toks, 0)], after_step=keep)
    _lg, kept = _ref(CFG, PARAMS, toks, kept=True)
    blocks = -(-len(toks) // BS)
    assert len(held["rows"]) == len(held["keys"]) == CFG.layers
    for pool, rows in zip(held["rows"], kept["rows"]):
        # the lane's blocks were handed out in order from block 1
        got = pool[1:1 + blocks].reshape(-1, pool.shape[-1])
        assert not got[:, CFG.latent_width:].any()
        np.testing.assert_allclose(got[:len(toks), :CFG.latent_width], rows,
                                   atol=TOL_F32)
    for pool, keys in zip(held["keys"], kept["keys"]):
        assert pool.shape[-1] == CFG.index_head_dim
        got = pool[1:1 + blocks].reshape(-1, pool.shape[-1])
        np.testing.assert_allclose(got[:len(toks)], keys, atol=TOL_F32)


# the issue's controls, as the reference can be told them: each a part of
# the selection or of the attention over the chosen set left out or changed
BREAKS = {
    "no_selection": dict(select=False),
    "most_recent_instead_of_chosen": dict(recent=True),
    "relu_left_out": dict(relu=False),
    "head_weights_left_out": dict(weighted=False),
    "index_key_unrotated": dict(index_rope=False),
    "v_cut_to_the_keys_width": dict(v_cut=12),
    "bias_ignored": dict(use_bias=False),
    "routed_scaling_dropped": dict(scaled=False),
    "no_shared_expert": dict(shared=False),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    """Each part of the layer's mathematics left out of the reference moves
    the logits a thousand times the tolerance."""
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) > 0.2


def test_a_prefix_hit_without_its_index_rows_is_seen():
    """The latent rows of a sequence's first block alone (its index keys
    zeros, as a frame or a hit that carried one pool would leave them): the
    indexer then scores that block's positions by what a zero key gives,
    chooses other rows, and the logits leave the reference's."""
    def forget(kv, carry):
        carry = list(carry)
        for i in kv.index_places:
            carry[i] = carry[i].at[1].set(0.0)
        return tuple(carry)

    toks = fam.sequences(1)[0][0] + [5, 9, 2, 6, 5, 3, 5, 8]
    (out,), _routed = fam.run_paged(CFG, PARAMS, [(toks, 0)],
                                    after_step=forget)
    whole = np.abs(out[1] - _ref(CFG, PARAMS, toks)).max(axis=1)
    # under index_topk every position is attended whatever its key scores
    assert whole[:CFG.index_topk].max() < TOL_F32 < 0.2 < whole.max()


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """As served (bf16 weights and both pools, float32 accumulation) the
    logits stay within bf16's rounding of the float32 reference on the same
    weights; the same weights rounded to fp8 do not.  Judged by the median
    over positions of a position's largest error, as dots.vlm1's test is:
    a swapped expert, or here a swapped row of the chosen eight, moves one
    position's logits by more than rounding does."""
    seqs = fam.sequences(3)
    out = run_paged(CFG16, PARAMS16, seqs)

    def median_error(runs):
        return float(np.median(np.concatenate([
            np.abs(lg - _ref(CFG16, PARAMS16, toks)).max(axis=1)
            for toks, lg in runs])))

    std = float(np.std(_ref(CFG16, PARAMS16, out[0][0])))
    forced = [(toks, 0) for toks, _lg in out]
    low = run_paged(CFG16, fam.fp8_rounded(PARAMS16), forced)
    err, err8 = median_error(out), median_error(low)
    assert err < 0.2 * std < 0.7 * std < err8, (err, err8, std)


# -- 2. the choice, the share --------------------------------------------------

def test_the_choice_is_the_references_ties_included():
    """``choose`` over scores with many ties (a few distinct values) against
    the reference's stable sort, a query a row at every context length: the
    same set, the lower position of equal scores first, everything the
    context holds while it holds no more than ``k``."""
    rng = np.random.default_rng(0)
    t, k = 40, 8
    scores = rng.integers(0, 5, (t, t)).astype(np.float32)
    lens = jnp.arange(1, t + 1, dtype=jnp.int32)
    seen = np.arange(t)[None, :] < np.arange(1, t + 1)[:, None]
    got = pa.chosen_mask(*pa.choose(
        jnp.where(jnp.asarray(seen), jnp.asarray(scores), -jnp.inf), lens,
        k), t)
    want = ref.chosen_set({"index_topk": k}, jnp.asarray(scores), 0)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got).sum(axis=1),
                          np.minimum(np.arange(1, t + 1), k))
    assert np.array_equal(np.asarray(got)[:k], seen[:k])
    # ties: a row takes the larger scores first and equal ones from the left
    for t_ in (12, 30, 39):
        by_hand = sorted(range(t_ + 1), key=lambda s: (-scores[t_, s], s))[:k]
        assert sorted(by_hand) == list(np.flatnonzero(np.asarray(got)[t_]))


def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One routed layer, 16 experts, 3 a token: sixteen shares of one expert
    each route over all 16 and compute their own expert's part; their sum
    and the shared expert's output, counted once, equal the uncut
    reference's layer.  No share alone does, nor the shared expert counted a
    share."""
    cfg = CFG.replace(layers=1, layer_types=("latent",), dense_layers=0)
    fam.check_shares_add_up(
        cfg, init(cfg, seed=11), gd, ref, ref_config,
        ("wgate", "wup", "wdown"), (2e-5, 5e-5), shares=16)


# -- 3. the configuration as the cell serves it ---------------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_six_latent_layers_get_two_pools_each_and_nothing_else():
    """The configuration as the cell serves it: six latent pools of rows 640
    wide and six index pools of keys 128 wide on the global block tables; no
    K/V pool, no slot, no ring; a block's bytes and the plan count both
    pools."""
    _config, cfg = _published()
    assert cfg.layer_types == ("latent",) * 6 and cfg.routed_layers \
        == (1, 2, 3, 4, 5)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.v_head_dim,
            cfg.latent_rope, cfg.latent_rank, cfg.q_rank, cfg.index_heads,
            cfg.index_head_dim, cfg.index_topk, cfg.dense_ffn, cfg.ffn,
            cfg.shared_ffn, cfg.experts, cfg.experts_held,
            cfg.experts_per_token, cfg.n_group, cfg.routed_scaling,
            cfg.vocab, cfg.norm_eps, cfg.rope_theta, cfg.max_seq) == (
        6144, 64, 192, 256, 64, 512, 2048, 32, 128, 2048, 12288, 2048, 2048,
        256, 16, 8, 1, 2.5, 19360, 1e-5, 1e6, 12544)
    assert cfg.latent_scale == 256 ** -0.5 and cfg.rope_scaling is None
    kv = dm.cache_config(cfg, 16, 25120, state_slots=0)
    assert (kv.layers, kv.latent_layers, kv.latent_width, kv.latent_row,
            kv.index_layers, kv.index_width, kv.state_layers,
            kv.window_layers) == (0, 6, 576, 640, 6, 128, 0, 0)
    assert kvc.latent_block_bytes(kv) == 20480
    assert kvc.index_block_bytes(kv) == 4096
    assert kvc.block_bytes(kv) == 6 * 16 * (640 + 128) * 2 == 147456
    assert kvc.block_bytes(kv) * 25120 == 3704094720
    # the plan takes both pools' bytes off the budget
    resident = 9454681600
    fit, capped = kvc.plan_num_blocks(kv, resident, requested=10 ** 6,
                                      budget=resident + 1000 * 147456 + 5)
    assert (fit, capped) == (1000, True)
    assert set(dm.lane_columns(kv, 784)[0]) \
        == {"tok", "src", "pos", "lens", "tables"}
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(
        dm.cache_config(cfg, 16, 8)).carry())
    assert [a.shape for a in carry] == [(8, 16, 640)] * 6 \
        + [(8, 16, 128)] * 6
    assert all(a.dtype == jnp.bfloat16 for a in carry)


def test_published_sizes_give_the_issues_bytes():
    """The held model, from the shapes the benchmark makes weights by:
    4,727,340,800 parameters (the dense lead 400,898,816, a routed layer's
    share 817,708,032 of which the indexer is 9,371,904, the sliced
    embedding and head with the final norm 237,901,824), 9,454,681,600 B in
    bfloat16."""
    config, _cfg = _published()
    shapes = model.param_shapes(config)
    count = lambda keep: sum(int(np.prod(shape)) for name, (shape, _k)
                             in shapes.items() if keep(name))
    assert count(lambda n: n.startswith("l0_")) == 400898816
    assert count(lambda n: n.startswith("l3_")) == 817708032
    assert count(lambda n: n.startswith("l3_") and "idx" in n) == 9371904
    assert count(lambda n: n.startswith("l3_") and n.split("_", 1)[1] in (
        "wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo")) \
        == 165022208
    assert count(lambda n: not n.startswith("l")
                 or n == "lnf_g") == 237901824
    assert count(lambda n: True) == 4727340800
    assert shapes["l0_wkvb"][0] == (512, 64 * (192 + 256))
    assert shapes["l0_wo"][0] == (64 * 256, 6144)


def test_config_refuses_what_no_block_computes():
    """An indexer's sizes are for the family that selects and for no other;
    that family wants all three and a compressed query; a value width of its
    own is a latent layer's."""
    d = CFG.to_dict()
    for changes in (dict(index_topk=0), dict(index_heads=0), dict(q_rank=0),
                    dict(index_head_dim=4)):
        with pytest.raises(ValueError, match="glm_dsa blocks"):
            dm.DecoderConfig(**dict(d, **changes))
    dots = fam.ROWS["dots_vlm"].f32[0]
    with pytest.raises(ValueError, match="glm_dsa blocks"):
        dots.replace(index_heads=4, index_head_dim=16, index_topk=8)
    assert dots.replace(v_head_dim=8).v_head_dim == 8
    with pytest.raises(ValueError, match="v_head_dim"):
        fam.ROWS["olmoe"].f32[0].replace(v_head_dim=8)
    with pytest.raises(ValueError, match="beside every latent pool"):
        kvc.KVCacheConfig(0, 4, 16, BS, 8, latent_layers=4, latent_width=32,
                          index_layers=3, index_width=16)


def test_a_value_width_of_its_own_is_served_without_a_selection_too():
    """``latent_mixer``'s value width is an option of its own: dots.vlm1's
    row with values 8 wide under keys of 16 serves the tokens of its unpaged
    loop, laid out or as published."""
    cfg = fam.ROWS["dots_vlm"].f32[0].replace(v_head_dim=8)
    params = dict(fam.ROWS["dots_vlm"].f32[1])
    rng = np.random.RandomState(5)
    for l in range(cfg.layers):
        params["l%d_wkvb" % l] = (rng.standard_normal(
            (cfg.latent_rank, cfg.heads * (16 + 8))) * 0.3).astype(np.float32)
        params["l%d_wo" % l] = (rng.standard_normal(
            (cfg.heads * 8, cfg.hidden)) * 0.3).astype(np.float32)
    ((fed, logits),), _r = fam.run_paged(cfg, params, [(fam.PROMPT, 6)])
    want, want_logits = fam.generate(cfg, params, fam.PROMPT, 6,
                                     return_logits=True)
    assert fed[len(fam.PROMPT):] == want
    assert np.array_equal(logits[len(fam.PROMPT) - 1:][:6],
                          np.stack(want_logits))
    laid = dm.laid_out(cfg, params)
    assert laid["l0_wkvb_k"].shape == (4, 24, 16) \
        and laid["l0_wkvb_v"].shape == (4, 8, 24)


def test_the_builder_balances_and_the_reference_checks_at_tiny_sizes():
    """The benchmark's own files at the configuration's tiny sizes: weights
    from a seed past 31 bits with the bias balanced, served tokens that the
    reference's ``check`` passes and a control's that it does not."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.update(config.pop("tiny"))
    config["weights_dtype"] = "f32"
    cfg = model.decoder_config(config)
    assert (cfg.index_topk, cfg.max_seq, cfg.v_head_dim, cfg.head_dim) \
        == (8, 64, 16, 12)
    params = model.make_params(config, 2600000031, jax.devices()[0])
    assert not np.asarray(params["l1_k_idx_b"]).any()
    assert np.asarray(params["l1_expert_bias"]).std() > 0
    host = {k: np.asarray(v) for k, v in params.items()}
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
    served = fam.generate(cfg, host, prompt, 12)
    good = ref.check(config, params, [(prompt, served)], 32)
    assert good["ok"] and good["compared"] == 12 \
        and good["largest_deficit"] < 1e-3
    wrong = [(t + 1) % cfg.vocab for t in served]
    assert not ref.check(config, params, [(prompt, wrong)], 32)["ok"]


# -- 4. prefix hit, export and adoption, the multi-token step -------------------

def test_a_prefix_hit_and_an_adopted_block_carry_both_rows(cache_dir):
    """A prompt of twelve tokens, past the eight the indexer keeps, served
    cold, then again over its cached blocks (three of them: twelve cached
    tokens... less the last), then by another engine that adopted the first
    block's frame: the cold run's tokens each time, and the frame holds the
    index keys beside the latent rows."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]
    want = fam.alone(CFG, PARAMS, prompt, 10)
    e = fam.engine(CFG, PARAMS, 32, buckets="2")
    try:
        m = e._models["m"]
        assert m.prefix is not None and m.declines is None
        cold = e.generate("m", prompt, max_new_tokens=10,
                          deadline_ms=60000.0)
        assert cold.status == "ok" and cold.phases["cached_tokens"] == 0
        hit = e.generate("m", prompt, max_new_tokens=10, deadline_ms=60000.0)
        assert hit.status == "ok" and hit.phases["cached_tokens"] == 12
        assert np.array_equal(cold.outputs["tokens"], want)
        assert np.array_equal(hit.outputs["tokens"], want)
        with e._cond:
            e._drain_locked()
            at = m.prefix.lookup(m.prefix.chain(prompt[:4])[0])
            frame = m.cache.export_block(at)
    finally:
        e.stop()
    assert [a.shape for a in frame] == [(4, BS, 128), (4, BS, 16)]
    assert all(np.abs(a).sum() > 0 for a in frame)
    other = fam.engine(CFG, PARAMS, 32, buckets="2", name="o")
    try:
        mo = other._models["o"]
        assert other.adopt_kv_block(
            "o", mo.prefix.chain(prompt[:4])[0], frame) == "adopted"
        r = other.generate("o", prompt, max_new_tokens=10,
                           deadline_ms=60000.0)
        assert r.status == "ok" and r.phases["cached_tokens"] == 4
        assert np.array_equal(r.outputs["tokens"], want)
        # a frame without the index keys is refused before anything is
        # written
        assert other.adopt_kv_block("o", "22" * 32, frame[:1]).startswith(
            "rejected:kv import arity mismatch")
    finally:
        other.stop()


@pytest.mark.parametrize("width", [2, 3])
def test_the_multi_token_step_selects_a_query(width):
    """The multi-token step is the single step composed: each of its
    ``width`` columns scores, chooses and attends over its own prefix, so a
    chunk fed past ``index_topk`` positions gives the reference's logits
    (a dense verify would not: the no-selection control above)."""
    seqs = [(list(range(3, 19)), 4), (list(range(20, 31)), 4)]
    out, _routed = fam.run_paged(CFG, PARAMS, seqs, width=width)
    assert max(float(np.abs(lg - _ref(CFG, PARAMS, toks)).max())
               for toks, lg in out) < TOL_F32


# -- 5. server and client, the step's span ---------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="glm")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 14), ([9, 2, 6], 7)):
            reply = client.generate("glm", prompt, max_new_tokens=n,
                                    deadline_ms=60000.0)
            assert reply.status == "ok", reply.error
            assert np.array_equal(
                np.asarray(reply.outputs["tokens"]).reshape(-1),
                fam.alone(CFG, PARAMS, prompt, n))
    finally:
        server.shutdown()
        e.stop()


def test_step_span_counters_gauges_and_prewarm_event(cache_dir, telemetry_on,
                                                     tmp_path):
    """Traced, the step's span says what the selection did: the blocks of a
    layer's index pool the scores walked, the rows attention read of those
    in context, the lanes past ``index_topk``; the gauges say what the two
    kinds of pool hold; the prewarm event names the index's path, the
    selected read's (``latent_attention``) and ``index_topk``."""
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(CFG, PARAMS, 24, buckets="2", name="glm")
        try:
            e.prewarm()
            r = e.generate("glm", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "glm")
    assert len(steps) >= 20
    # one live lane of two; the CPU gathers the whole table
    assert all(s["index_blocks_read"] == s["kv_blocks_read"] == 2 * MAXB
               and s["latent_rows_in_context"] >= s["latent_rows_selected"]
               and s["sparse_lanes"] in (0, 1)
               and "latent_blocks_walked" not in s for s in steps)
    by_context = {s["latent_rows_in_context"]: s for s in steps}
    assert set(by_context) >= set(range(1, 22))
    for n, s in by_context.items():
        assert s["latent_rows_selected"] == min(n, 8)
        assert s["sparse_lanes"] == int(n > 8)
    gauges = _tm.snapshot()["gauges"]
    # 4 layers, 24 blocks of 4 rows: 128 values a latent row (32, the tile
    # filled up), 16 an index key, float32
    assert gauges["latent_pool_bytes{model=glm}"] == 4 * 24 * 4 * 128 * 4
    assert gauges["index_pool_bytes{model=glm}"] == 4 * 24 * 4 * 16 * 4
    assert gauges["kv_cache_bytes"] == 4 * 24 * 4 * (128 + 16) * 4
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "glm" and ev["latent_attention"] == "gather"
        and ev["index_path"] == "gather" and ev["index_topk"] == 8 and ev["experts"] == "einsum"
        and ev["chunk_positions"] == {} for ev in warm)
    # a model that does not select says nothing of an index
    dots = fam.ROWS["dots_vlm"].f32
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path / "d")):
        e = fam.engine(*dots, 24, buckets="2", name="dv")
        try:
            e.prewarm()
            assert e.generate("dv", [1, 2, 3], max_new_tokens=3,
                              deadline_ms=60000.0).status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    assert not any(k in s for s in fam.step_spans(tmp_path / "d", "dv")
                   for k in ("index_blocks_read", "sparse_lanes"))
    assert not any("index_path" in ev
                   for ev in fam.prewarm_events(tmp_path / "d"))


# -- 6. the kernels at this family's shapes, under the interpreter ---------------

def _lanes(rng, lens, bs, maxb, blocks):
    tables = np.full((len(lens), maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, blocks)))
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // bs)):
            tables[b, j] = next(free)
    return jnp.asarray(tables), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_index_scores_kernel_walks_a_lanes_live_blocks(interpreted, dtype,
                                                       tol):
    """32 index heads of 128 against keys in blocks of 16, chunks of 1,024
    positions: contexts of one token, of a chunk and a half, of exactly two
    chunks, of nothing (all ``-inf``), tables shuffled: the gather's scores,
    ``-inf`` from the context on."""
    rng = np.random.default_rng(4)
    heads, width, bs, maxb = 32, 128, 16, 140
    lens = [1, 1530, 0, 2048, 77]
    pool = jnp.asarray(rng.standard_normal((360, bs, width)), dtype)
    qi = jnp.asarray(rng.standard_normal((5, heads, width)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, heads)), jnp.float32)
    tables, ctx = _lanes(rng, lens, bs, maxb, 360)
    assert pa.index_path(qi.shape, pool.shape, dtype, maxb) == "pallas"
    got = np.asarray(jax.jit(pa.index_scores)(qi, w, pool, tables, ctx))
    assert adoption.active_kernels() == ["index_scores"]
    want = np.asarray(pa.dense_index_scores(
        qi, w, pa.gather_blocks(pool, tables), ctx))
    assert got.shape == want.shape == (5, maxb * bs)
    for b, n in enumerate(lens):
        assert np.isneginf(got[b, n:]).all()
        scale = np.abs(want[b, :n]).max() if n else 1.0
        np.testing.assert_allclose(got[b, :n] / scale, want[b, :n] / scale,
                                   atol=tol)
    # the choice over either is the same set where no two scores are close
    k = 256
    sets = [np.asarray(pa.chosen_mask(*pa.choose(jnp.asarray(s), ctx, k),
                                      maxb * bs)) for s in (got, want)]
    assert (sets[0] != sets[1]).sum() <= (0 if dtype == jnp.float32 else 8)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_selected_rows_are_gathered_and_the_latent_kernel_walks_them(
        interpreted, monkeypatch, dtype, tol):
    """64 rows of chosen positions a lane, gathered by (block, offset) into
    contiguous blocks, under the latent kernel (the row form, which a table
    of 768 positions, 12 a chosen one, takes): the masked form's numbers
    (the whole table gathered, what was not chosen left out), for lanes
    under the 64, past them, and idle."""
    rng = np.random.default_rng(6)
    heads, width, rank, bs, maxb, k = 16, 256, 128, 16, 48, 64
    lens = [40, 300, 0, 64, 383]
    pool = jnp.asarray(rng.standard_normal((80, bs, width)), dtype)
    q = jnp.asarray(rng.standard_normal((5, heads, width)), jnp.float32)
    tables, ctx = _lanes(rng, lens, bs, maxb, 80)
    scores = jnp.where(jnp.arange(maxb * bs)[None] < ctx[:, None],
                       jnp.asarray(rng.standard_normal((5, maxb * bs)),
                                   jnp.float32), -jnp.inf)
    positions, count = pa.choose(scores, ctx, k)
    assert np.array_equal(np.asarray(count), [40, 64, 0, 64, 64])
    assert pa.selected_latent_path(q.shape, pool.shape, dtype, rank, k,
                                   maxb) == "pallas"
    attend = lambda: jax.jit(
        lambda *a: pa.selected_latent_attention(*a, 0.1, rank))(
        q, pool, tables, ctx, positions, count)
    got = np.asarray(attend())
    assert adoption.active_kernels() == ["latent_attention"]
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    assert pa.selected_latent_path(q.shape, pool.shape, dtype, rank, k,
                                   maxb) == "gather"
    want = np.asarray(attend())
    live = [0, 1, 3, 4]
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    assert not got[2].any()
    # and the masked form is no dense attention: lane 1 chose 64 of 300
    dense = np.asarray(pa.latent_attention_reference(q, pool, tables, ctx,
                                                     0.1, rank))
    assert np.abs(dense[1] - want[1]).max() > 0.05
    np.testing.assert_allclose(dense[0], want[0], atol=tol, rtol=tol)
    # a selection that is no whole blocks is gathered whole and masked
    assert pa.selected_latent_path(q.shape, pool.shape, dtype, rank, 60,
                                   maxb) == "gather"


# lens of the lanes, slots of the table: 16 heads of 256 over rows in blocks
# of 16, 64 positions chosen; a chunk is 512 positions, 32 blocks
WALKS = {
    "under_k": ([40, 7], 36),
    "at_k": ([64, 63], 36),
    "past_k": ([300, 65, 576], 36),
    "an_idle_lane": ([0, 530, 0], 36),
    "a_last_chunk_of_one_block": ([513, 528], 36),
    "a_table_of_one_chunk": ([383, 90], 24),
    "the_grid_walks_the_lanes": ([570, 0, 64, 513], 36),
    # lanes of one chunk of 1, 2, 3 and 4 steps of 128 positions, and a last
    # chunk of one step behind a whole one
    # (tests/test_latent_attention_kernel.py walks longer tables masked)
    "a_lane_ends_on_a_short_chunk": ([127, 129, 300, 0, 530, 385], 36),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(WALKS))
def test_the_masked_walk_reads_the_chosen_positions_of_a_lanes_live_blocks(
        interpreted, monkeypatch, case, dtype, tol):
    """The latent kernel over a lane's own table under a mask of the chosen
    positions (the form a table of at most 9 positions a chosen one takes):
    ``masked_latent``'s numbers over ``chosen_mask``'s set, for lanes under
    ``k``, at it and past it, an idle lane (zeros), a last chunk that holds
    one block, a table of one chunk, lanes that end on a short chunk, and
    with the grid walking the lanes (a lane's chunks of the mask in VMEM at
    a time)."""
    lens, maxb = WALKS[case]
    rng = np.random.default_rng(sorted(WALKS).index(case))
    heads, width, rank, bs, k = 16, 256, 128, 16, 64
    pool = jnp.asarray(rng.standard_normal((120, bs, width)), dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), heads, width)),
                    jnp.float32)
    tables, ctx = _lanes(rng, lens, bs, maxb, 120)
    # scores with ties, so that the choice's order is no position's
    scores = jnp.where(jnp.arange(maxb * bs)[None] < ctx[:, None],
                       jnp.asarray(rng.integers(0, 9, (len(lens), maxb * bs)),
                                   jnp.float32), -jnp.inf)
    positions, count = pa.choose(scores, ctx, k)
    if case == "the_grid_walks_the_lanes":
        # two chunk buffers and these lanes' queries and outputs no longer
        # fit together: a lane a grid step, as at the published shapes
        monkeypatch.setattr(pa, "_VMEM_BUDGET", 90000 + 2 * 512 * width
                            * jnp.dtype(dtype).itemsize)
    assert pa._latent_lane_grid(q.shape, pool.shape, dtype, rank) \
        == (case == "the_grid_walks_the_lanes")
    assert pa.selected_latent_path(q.shape, pool.shape, dtype, rank, k,
                                   maxb) == "pallas_masked"
    assert pa.latent_chunk_positions(q.shape, pool.shape, dtype, rank,
                                     maxb) == min(512, maxb * bs)
    got = np.asarray(jax.jit(
        lambda *a: pa.selected_latent_attention(*a, 0.1, rank))(
        q, pool, tables, ctx, positions, count))
    assert adoption.active_kernels() == ["latent_attention"]
    want = np.asarray(pa.masked_latent(
        q, pa.gather_blocks(pool, tables), ctx, 0.1, rank,
        pa.chosen_mask(positions, count, maxb * bs)))
    for b, n in enumerate(lens):
        if n:
            np.testing.assert_allclose(got[b], want[b], atol=tol, rtol=tol)
        else:
            assert not got[b].any()
    if max(lens) > k:
        # ... and that is no dense attention where a lane chose
        dense = np.asarray(pa.latent_attention_reference(
            q, pool, tables, ctx, 0.1, rank))
        b = int(np.argmax(lens))
        assert np.abs(dense[b] - want[b]).max() > 0.02


@pytest.mark.parametrize("case,chunks,span", [
    ("ties", 3, 128), ("past_count", 2, 256), ("a_span_of_no_128", 4, 24),
    ("a_choice_named_twice_past_count", 2, 128)])
def test_the_mask_laid_out_by_chunk_is_chosen_masks_set(case, chunks, span):
    """``_chunk_mask`` (the one-hot of a position's row against that of its
    column, no scatter) marks ``chosen_mask``'s set exactly, position ``c *
    span + j`` at ``[c, j]``: on scores with ties, on lanes whose trailing
    choices lie past ``count`` (a lane under ``k`` positions), where a chunk
    is no multiple of 128 positions, and where a stand-in for ``choose``
    names a chosen position again past ``count`` (chip_check_glm's
    ``most_recent``), which the scatter leaves to chance."""
    rng = np.random.default_rng(len(case))
    length, k = chunks * span - 3, 20
    lens = jnp.asarray([0, 1, 5, 19, 20, 21, length], jnp.int32)
    scores = rng.integers(0, 3, (7, length)).astype(np.float32) \
        if case == "ties" else rng.standard_normal((7, length))
    scores = jnp.where(jnp.arange(length)[None] < lens[:, None],
                       jnp.asarray(scores, jnp.float32), -jnp.inf)
    positions, count = pa.choose(scores, lens, k)
    want = np.asarray(pa.chosen_mask(positions, count, length))
    if case == "a_choice_named_twice_past_count":
        positions = jnp.where(jnp.arange(k)[None] < count[:, None],
                              positions, positions[:, :1])
    got = np.asarray(pa._chunk_mask(positions, count, chunks, span))
    assert got.shape == (7, chunks, span) and got.dtype == np.int32
    assert set(np.unique(got)) <= {0, 1}
    assert np.array_equal(got.reshape(7, -1)[:, :length] != 0, want)
    assert not got.reshape(7, -1)[:, length:].any()
    assert np.array_equal(want.sum(axis=1), np.minimum(lens, k))


# (heads, row, rank, block, k, table slots) -> the form, under the interpreter
FORMS = {
    "a_table_of_9_positions_a_chosen_one_walks":
        ((4, 16, 256, 128, 16, 64, 36), "pallas_masked"),
    "a_table_of_9_and_a_quarter_gathers_the_rows":
        ((4, 16, 256, 128, 16, 64, 37), "pallas"),
    "the_cells_table_walks": ((32, 64, 640, 512, 16, 2048, 784),
                              "pallas_masked"),
    "the_published_table_gathers_the_rows":
        ((32, 64, 640, 512, 16, 2048, 12672), "pallas"),
    "128_heads_walk_under_the_mask_too": ((32, 128, 640, 512, 16, 2048, 784),
                                          "pallas_masked"),
    "a_selection_of_no_whole_blocks_walks_a_short_table":
        ((4, 16, 256, 128, 16, 60, 24), "pallas_masked"),
    "a_selection_of_no_whole_blocks_gathers_a_wide_table_whole":
        ((4, 16, 256, 128, 16, 60, 48), "gather"),
    "a_row_of_no_whole_tiles_gathers_whole":
        ((4, 16, 160, 128, 16, 64, 24), "gather"),
}


@pytest.mark.parametrize("case", sorted(FORMS))
def test_the_selected_reads_form_follows_from_the_shapes(interpreted,
                                                         monkeypatch, case):
    """``selected_latent_path`` names one of three forms from the shapes
    alone: the masked walk up to ``_WALK_POSITIONS_PER_CHOSEN`` positions of
    the table a chosen one (the cell's 64 heads, and 128 too: the latent
    kernel takes the mask at any head count); the row form past that;
    the whole table gathered where no kernel serves, and everywhere off the
    TPU."""
    (lanes, heads, row, rank, bs, k, maxb), form = FORMS[case]
    shapes = ((lanes, heads, row), (4 * maxb, bs, row), jnp.bfloat16, rank,
              k, maxb)
    assert pa._WALK_POSITIONS_PER_CHOSEN == 9
    assert pa.selected_latent_path(*shapes) == form
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    assert pa.selected_latent_path(*shapes) == "gather"


def test_the_index_and_selected_rules_at_the_published_shapes():
    """What ``adoption.decide`` is given at the cell's shapes passes every
    check but the backend's here: 32 index heads of 128 over the cell's pool
    of keys under tables of 784 slots, and 64 heads of 640 over 2,048
    gathered rows a lane (4,096 blocks of them at 32 lanes)."""
    qi, ipool = (32, 32, 128), (25120, 16, 128)
    checks = dict(pa.index_scores_checks(qi, ipool, jnp.bfloat16, 784))
    assert [k for k, ok in checks.items() if not ok] == ["backend"]
    q, pool = (32, 64, 640), (25120, 16, 640)
    checks = dict(pa._selected_checks(q, pool, jnp.bfloat16, 512, 2048))
    assert [k for k, ok in checks.items() if not ok] == ["backend"]
    # ... and the masked walk under the cell's tables of 784 slots (12,544
    # positions for 2,048 chosen), which a table of the published 202,752
    # positions is too wide for
    checks = dict(pa._walk_checks(q, pool, jnp.bfloat16, 512, 2048, 784))
    assert [k for k, ok in checks.items() if not ok] == ["backend"]
    checks = dict(pa._walk_checks(q, pool, jnp.bfloat16, 512, 2048, 12672))
    assert [k for k, ok in checks.items() if not ok] == ["backend",
                                                         "selection"]
    assert pa.latent_chunk_positions(q, (4096, 16, 640), jnp.bfloat16, 512,
                                     128) == 512
    _config, cfg = _published()
    kv = dm.cache_config(cfg, 16, 25120)
    assert dm.attention_path(cfg, kv, 32, "index") \
        == dm.attention_path(cfg, kv, 32, "latent") \
        == dm.attention_path(cfg, kv, 32, "selected") == "gather"
    assert dm.experts_chunk(cfg) == 256


def _at_kernel_widths():
    """A block the three kernels take under the interpreter: 4 query heads
    of 96 + 32 rotated and values of 128 over 128 latent values, rows of 160
    held 256 wide; 8 index heads of 128 that keep 16 positions, a block of
    them, of the 64 a table names."""
    cfg = dm.DecoderConfig(
        arch="glm_dsa", vocab=61, layers=3, heads=4, head_dim=96,
        v_head_dim=128, hidden_size=128, max_seq=64,
        layer_types=("latent",) * 3, latent_rank=128, latent_rope=32,
        q_rank=64, index_heads=8, index_head_dim=128, index_topk=16,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, routed_scaling=2.5,
        rope_theta=1e6)
    return cfg, gd.init_params(cfg, seed=5, std=0.1, bias_std=0.05)


def test_the_paged_step_on_three_kernels_gives_the_jnp_steps_tokens(
        interpreted, monkeypatch):
    """The whole step with the index, latent-attention and expert kernels
    interpreted (``_at_kernel_widths``): the tokens of the jnp step, 40
    positions deep."""
    cfg, params = _at_kernel_widths()
    kv = dm.cache_config(cfg, 16, 12)
    assert (kv.latent_width, kv.latent_row, kv.index_width) == (160, 256, 128)
    assert dm.attention_path(cfg, kv, 2, "latent") == "pallas"
    # a table of 64 positions for 16 chosen: the kernel walks it, masked
    assert dm.attention_path(cfg, kv, 2, "selected") == "pallas_masked"
    assert dm.attention_path(cfg, kv, 2, "index") == "pallas"
    assert dm.chunk_positions(cfg, kv, 2) == {"latent": 64}

    def run():
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 40), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernels = run()
    assert set(adoption.active_kernels()) == {
        "index_scores", "latent_attention", "moe_experts"}
    monkeypatch.delenv("PADDLE_PALLAS_INTERPRET")
    assert dm.attention_path(cfg, kv, 2, "index") == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=2e-4)


def test_the_step_span_counts_the_blocks_a_masked_walk_fetched(
        interpreted, cache_dir, telemetry_on, tmp_path):
    """Through the engine with the kernels interpreted: the prewarm event
    names the form the selected read took at the bucket
    (``"pallas_masked"``) and the chunk it walks, and a step's span gains
    ``latent_blocks_walked``, the live blocks of the lanes' contexts (what
    ``latent_blocks_read`` then says too), beside ``latent_rows_selected``,
    which stays the rows chosen."""
    from paddle_tpu.serving.engine import DecodeEngine

    cfg, params = _at_kernel_widths()
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path),
                   kv_block_size=16):
        e = DecodeEngine(buckets="2", deadline_ms=60000.0)
        e.add_model("glm", (cfg, params), kv_blocks=12)
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
        and ev["attention"] == ev["index_path"] == "pallas"
        and ev["chunk_positions"] == {"latent": 64} for ev in warm)
    by_context = {s["latent_rows_in_context"]: s
                  for s in fam.step_spans(tmp_path, "glm")}
    assert set(by_context) >= set(range(1, 33))
    for n, s in by_context.items():
        assert s["latent_blocks_walked"] == s["latent_blocks_read"] \
            == s["index_blocks_read"] == -(-n // 16)
        assert s["latent_rows_selected"] == min(n, 16)
        assert s["latent_chunks"] == 1
    assert set(adoption.active_kernels()) == {
        "index_scores", "latent_attention", "moe_experts"}
