"""What is the dots.vlm1 decoder block's own (paddle_tpu/models/dots_vlm.py:
latent attention in every layer, its query compressed and normed, the row's
shared key and the query's last values rotated by position with YaRN's
frequencies, the scores scaled by YaRN's ``m^2``, and experts chosen by
groups beside a shared one behind a dense lead): logits at every position
against its plain reference (benchmark/reference/dots_vlm_ref.py, the file
the benchmark uses, which computes latent attention *expanded*), prefill
then decode through the paged step and the cache manager; the reference told
otherwise; YaRN's numbers worked by hand; the router against a loop; the
share; what the cache manager gives a model whose every layer is latent;
server and client; the step's span and prewarm event; the kernels at this
family's shapes under the interpreter.  The contract it shares with every
family is tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: 4 ``latent`` layers,
hidden 48 under 4 heads of 16 (+ 8 rotated values) over 24 latent values,
the query through 20, a dense lead of width 64, 16 experts of width 24 in 4
groups, 2 groups and 3 experts a token, a shared one of width 24, vocab
97."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import dots_vlm as dv
from paddle_tpu.models import exaone_moe as ex
from paddle_tpu.models import lfm2_moe as lf
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import moe_experts as moe
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm

CONFIG_FILE = fam.config_file("dots-vlm1-inst-serve.json")
ref = fam.load("benchmark", "reference", "dots_vlm_ref.py")
model = fam.load("benchmark", "models", "dots_vlm_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["dots_vlm"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(dv.init_params, std=0.3, bias_std=0.05)


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
        "q_lora_rank": cfg.q_rank or None,
        "qk_nope_head_dim": cfg.head_dim,
        "qk_rope_head_dim": cfg.latent_rope, "v_head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling, type="yarn")
        if cfg.rope_scaling else None,
        "first_k_dense_replace": cfg.dense_layers,
        "intermediate_size": cfg.dense_ffn,
        "moe_intermediate_size": cfg.ffn, "num_experts": cfg.experts_held,
        "n_routed_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0, "rms_norm_eps": cfg.norm_eps},
        **changed)


# float32 rounding over four layers (measured 3e-5 here); a fault in
# structure is 1 or more (the broken-reference controls below)
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
    a token a step and then 8 of its own tokens: at every position the
    step's logits are the expanded reference's full forward pass of the
    sequence so far, and what every layer's pool holds of a sequence is the
    reference's ``[c | rotated k_pe]`` rows."""
    out = _f32_out()
    assert len({len(t) for t, _lg in out}) > 1
    assert _worst(CFG, out, PARAMS) < TOL_F32
    assert all(len(set(t[-8:])) > 2 for t, _lg in out)
    held = {}

    def keep(kv, carry):
        held["pools"] = [np.asarray(p) for p in kv.latent_pools(carry)]
        return carry

    toks = fam.PROMPT + [7, 7, 2]
    fam.run_paged(CFG, PARAMS, [(toks, 0)], after_step=keep)
    _lg, kept = _ref(CFG, PARAMS, toks, kept=True)
    for pool, rows in zip(held["pools"], kept["rows"]):
        # the lane's blocks were handed out in order from block 1
        got = pool[1:1 + -(-len(toks) // BS)].reshape(-1, pool.shape[-1])
        assert not got[:, CFG.latent_width:].any()
        np.testing.assert_allclose(got[:len(toks), :CFG.latent_width], rows,
                                   atol=TOL_F32)


BREAKS = {
    "no_rotation": dict(rope=False),
    "no_yarn_scale": dict(mscale=False),
    "no_q_norm": dict(q_norm=False),
    "no_kv_norm": dict(kv_norm=False),
    "groups_ignored": dict(grouped=False),
    "bias_ignored": dict(use_bias=False),
    "routed_scaling_dropped": dict(scaled=False),
    "no_shared_expert": dict(shared=False),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    """Each part of the layer's mathematics left out of the reference moves
    the logits a thousand times the tolerance."""
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) > 0.2


def test_plain_rope_and_an_uncompressed_query_are_served_too():
    """The two options one by one: without ``rope_scaling`` the rotation is
    plain RoPE and the scale carries no ``m^2``; with ``q_rank`` 0 the query
    is one matrix.  Each against the reference told the same."""
    for changes in (dict(rope_scaling=None), dict(q_rank=0)):
        cfg = CFG.replace(**changes)
        params = init(cfg, seed=5)
        assert ("l0_wq" in params) == (not cfg.q_rank)
        out = run_paged(cfg, params, fam.sequences(2, seed=1))
        assert _worst(cfg, out, params) < TOL_F32
    assert CFG.replace(rope_scaling=None).latent_scale == 24 ** -0.5


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """As served (bf16 weights and cache, float32 accumulation) the logits
    stay within bf16's rounding of the float32 reference on the same
    weights; the same weights rounded to fp8 do not.  Judged by the median
    over positions of a position's largest error: at these sizes two of four
    groups and three of sixteen experts are won by hundredths, bf16 swaps
    one at a tenth of positions, and a swap moves that position's logits by
    1-4 (measured: the median 0.26 and the largest 3.6 in bf16, 3.0 and 6.9
    in fp8, logits of standard deviation 2.1)."""
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


# -- 2. YaRN by hand, the router by a loop, the share ----------------------------

def test_yarn_frequencies_and_scale_for_the_published_group():
    """``rope_scaling`` as published (factor 40 over 4,096 positions,
    beta_fast 32, beta_slow 1) on 64 rotated values, theta 10,000, worked by
    hand: the correction dims are 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) =
    10.47 and 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51, so pairs 0-10
    keep theta^(-j/32), pairs 23-31 have it over 40, and pair j between them
    (1 - (j - 10) / 13) of the one and (j - 10) / 13 of the other;  m = 0.1
    ln 40 + 1 = 1.368888, the scores' scale 192^-0.5 * m^2 = 0.135234, and
    cos and sin are scaled by m / m = 1."""
    config, cfg = _published()
    assert cfg.rope_scaling == {k: float(config["rope_scaling"][k])
                                for k in dm.YARN_KEYS}
    freq = dv.yarn_inv_freq(cfg)
    plain = np.array([1e4 ** (-j / 32) for j in range(32)])
    assert freq.shape == (32,) and freq.dtype == np.float32
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
    for j, want in ((11, 0.0421697 * (12 / 13 + 1 / 13 / 40)),
                    (16, 0.01 * (7 / 13 + 6 / 13 / 40)),
                    (22, 1.778279e-3 * (1 / 13 + 12 / 13 / 40))):
        assert abs(freq[j] / want - 1) < 1e-5, j
    m = dm.yarn_mscale(40, 1)
    assert abs(m - (0.1 * math.log(40) + 1)) < 1e-12 \
        and abs(m - 1.368888) < 1e-6
    assert abs(cfg.latent_scale - 192 ** -0.5 * m * m) < 1e-12 \
        and abs(cfg.latent_scale - 0.135234) < 1e-6
    assert cfg.rope_mscale == 1.0 and dm.yarn_mscale(1, 1) == 1.0
    np.testing.assert_array_equal(freq, ref.yarn_frequencies(config))
    # unscaled: plain RoPE, and no m^2
    bare = cfg.replace(rope_scaling=None)
    np.testing.assert_allclose(dv.yarn_inv_freq(bare), plain, rtol=1e-6)
    assert abs(bare.latent_scale - 192 ** -0.5) < 1e-15
    # the tiny sizes' 8 values: dims 1.3 and 2.8, so the ramp runs from
    # pair 1 to pair 3: two pairs kept, one half blended, one divided
    np.testing.assert_allclose(
        dv.yarn_inv_freq(CFG), [1, 0.1, 0.01 * (1 / 2 + 1 / 2 / 40),
                                1e-3 / 40], rtol=1e-6)


def test_the_rotation_turns_pairs_by_position_and_keeps_a_score():
    """Pair (2j, 2j + 1) of a vector at position t turns by t * f_j and lies
    at (j, P/2 + j); a query at t against a key at s depends on t - s
    alone."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 2, 8), jnp.float32)
    pos = jnp.asarray([0, 5, 40])
    got = np.asarray(dv._rotation(CFG, pos)(x))
    freq = dv.yarn_inv_freq(CFG)
    for b, t in enumerate([0, 5, 40]):
        for j in range(4):
            a, c = np.asarray(x)[b, :, 2 * j], np.asarray(x)[b, :, 2 * j + 1]
            ang = np.float32(t) * freq[j]
            np.testing.assert_allclose(
                got[b, :, j], a * np.cos(ang) - c * np.sin(ang), atol=1e-5)
            np.testing.assert_allclose(
                got[b, :, 4 + j], c * np.cos(ang) + a * np.sin(ang),
                atol=1e-5)
    q, k = x[:1, :1], x[1:2, :1]
    score = lambda t, s: float(jnp.sum(
        dv._rotation(CFG, jnp.asarray([t]))(q)
        * dv._rotation(CFG, jnp.asarray([s]))(k)))
    assert abs(score(9, 4) - score(25, 20)) < 1e-4
    assert abs(score(9, 4) - score(9, 5)) > 1e-3


def _route_by_loop(x, router, bias, k, scaling, n_group, topk_group):
    """The grouped router a token and a group at a time, in numpy float64
    on the float32 scores."""
    score = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)), np.float64)
    gates = np.zeros_like(score)
    size = score.shape[1] // n_group
    for t in range(len(score)):
        select = score[t] + np.asarray(bias, np.float64)
        by_group = [sorted(select[g * size:(g + 1) * size])[-2:]
                    for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -sum(by_group[g])
                      )[:topk_group]
        left = np.zeros_like(select)
        for g in kept:
            left[g * size:(g + 1) * size] = select[g * size:(g + 1) * size]
        chosen = np.argsort(-left)[:k]
        gates[t, chosen] = scaling * score[t, chosen] \
            / (score[t, chosen].sum() + 1e-20)
    return gates


@pytest.mark.parametrize("experts,n_group,topk_group,k", [
    (16, 4, 2, 3), (256, 8, 4, 8), (32, 8, 1, 2)])
def test_the_grouped_router_equals_a_loop(experts, n_group, topk_group, k):
    rng = np.random.RandomState(experts)
    x = jnp.asarray(rng.randn(40, 24), jnp.float32)
    router = jnp.asarray(rng.randn(24, experts) * 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(experts) * 0.1, jnp.float32)
    kept = []
    gates, chosen = ex._route(x, router, bias, k, 2.5, n_group, topk_group,
                              kept)
    want = _route_by_loop(x, router, bias, k, 2.5, n_group, topk_group)
    np.testing.assert_allclose(np.asarray(gates), want, atol=1e-6)
    assert np.array_equal(np.asarray(chosen), want > 0)
    assert (np.asarray(chosen).sum(axis=1) == k).all()
    groups = np.asarray(kept[0])
    assert groups.shape == (40, n_group) \
        and (groups.sum(axis=1) == topk_group).all()
    # every chosen expert lies in a kept group, and the groups matter: a
    # plain choice over all the experts picks otherwise for some token
    size = experts // n_group
    assert all(groups[t, e // size] for t, e in zip(*np.nonzero(
        np.asarray(chosen))))
    _g, plain = ex._route(x, router, bias, k, 2.5)
    assert (np.asarray(plain) != np.asarray(chosen)).any()


def test_one_group_routes_bit_for_bit_as_before_there_were_groups():
    """``n_group`` 1 is LFM2-MoE's router with DeepSeek-V3's denominator,
    what K-EXAONE, Kimi-Linear and this family's ungrouped form serve: the
    same gates and choice to the bit, and the same lowered program."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(32, 48), jnp.float32)
    router = jnp.asarray(rng.randn(48, 64) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.randn(64) * 0.05, jnp.float32)
    before = lambda *a: lf._route(*a, 8, 2.5, ex.GATE_EPS)
    now = lambda *a: ex._route(*a, 8, 2.5, 1, 1, [])
    for a, b in zip(before(x, router, bias), now(x, router, bias)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = lambda fn: jax.jit(fn).lower(x, router, bias).as_text()
    assert text(before) == text(now)
    # the whole step of the families that share it routes in one group
    for arch in ("exaone_moe", "kimi_linear"):
        assert fam.ROWS[arch].f32[0].n_group == 1


def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One routed layer, 16 experts in 4 groups, 2 groups and 3 experts a
    token: sixteen shares of one expert each route over all 16 and compute
    their own expert's part; their sum and the shared expert's output,
    counted once, equal the uncut reference's layer.  No share alone does,
    nor the shared expert counted a share."""
    cfg = CFG.replace(layers=1, layer_types=("latent",), dense_layers=0)
    fam.check_shares_add_up(
        cfg, init(cfg, seed=11), dv, ref, ref_config,
        ("wgate", "wup", "wdown"), (2e-5, 5e-5), shares=16)


def test_the_served_bias_moves_the_choice_and_no_experts_load():
    """At the published router (7,168 x 256, 8 a token in 4 of 8 groups)
    behind the pre-norm (entries of root-mean-square 1), the configuration's
    ``expert_bias_std`` (the draw its balancing starts from: the next test)
    re-decides the choice of experts on more than a tenth of tokens less a
    little (K-EXAONE's measure: a block that ignores
    it is seen), and the 16 held experts (half of group 0) a 32-lane step
    hits stay within 0.3 of an even ungrouped router's 10.2 from seed to
    seed (so a run's time does not hang on its seed: 0.61 ms of a 13 ms step
    a unit, on the chip): group 0 is kept by half the tokens, and a token
    that keeps it chooses its 8 among 128 experts, so the held 16 see 32 x 8
    / 256 = 1 assignment each a step, as without groups.  Measured here: the
    bias re-decides 10-14% of tokens (the groups' choice is re-decided too),
    a step hits 9.96-10.27 of 16, group 0 is kept by 48-54%.  (At 0.01, the
    first value: 74-81%, 9.5-10.2, 44-54%, and on the chip the seed set the
    step's time; at 0.003: 32-38%, 10.08-10.19: PERF.md section 6, PR 49.)"""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    std = config["expert_bias_std"]
    assert std == dv.BIAS_STD
    hits, differ, kept_share = [], [], []
    for seed in range(4):
        rng = np.random.RandomState(seed)
        per_layer = []
        for _layer in range(3):
            router = jnp.asarray(rng.randn(7168, 256) * 0.02, jnp.float32)
            bias = jnp.asarray(rng.randn(256) * std, jnp.float32)
            x = rng.randn(32 * 16, 7168)
            x = jnp.asarray(x / np.sqrt((x * x).mean(1, keepdims=True)),
                            jnp.float32)
            kept = []
            _g, chosen = ex._route(x, router, bias, 8, 2.5, 8, 4, kept)
            _g, plain = ex._route(x, router, jnp.zeros(256), 8, 2.5, 8, 4)
            differ.append(float((np.asarray(chosen) != np.asarray(plain))
                                .any(axis=1).mean()))
            kept_share.append(float(np.asarray(kept[0])[:, 0].mean()))
            held = np.asarray(chosen)[:, :16].reshape(16, 32, 16).sum(axis=1)
            per_layer.append(float((held > 0).sum(axis=1).mean()))
        hits.append(float(np.mean(per_layer)))
    even = 16 * (1 - (1 - 8 / 256) ** 32)
    assert min(differ) > 0.09 and np.mean(differ) > 0.11, differ
    assert max(abs(h - even) for h in hits) < 0.3, hits
    assert 0.4 < np.mean(kept_share) < 0.6, kept_share


def test_balancing_evens_the_load_and_still_moves_the_choice():
    """``dots_vlm_decoder.balance`` (DeepSeek-V3's rule for
    ``e_score_correction_bias``: up where an expert is chosen less than the
    mean, down where more, at a falling speed) at the published router over
    8,192 isotropic inputs: the seeded draw leaves the experts' load where
    the router's rows put it (rows of 7,168 normal values differ in length
    by 0.8%, and at a threshold 1.9 sd out an expert's popularity moves 4
    times that), the balanced bias has every expert within a few
    assignments of the mean on the sample it read, the held sixteen (half
    of group 0) take their sixteenth there, and the choice it makes differs
    from an unbiased one on a visible share of tokens (a block that ignores
    the bias is seen).  On the chip the seed's router moved the held
    sixteen's share by 2.5% and a step's time with it (PERF.md section 6,
    PR 49)."""
    rng = np.random.RandomState(3)
    router = jnp.asarray(rng.randn(7168, 256) * 0.02, jnp.float32)
    seeded = jnp.asarray(rng.randn(1, 256) * dv.BIAS_STD, jnp.float32)
    x = rng.randn(8192, 7168)
    x = jnp.asarray(x / np.sqrt((x * x).mean(1, keepdims=True)), jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST))[None]
    _b, worst0, rms0 = model.balance(scores, seeded, 8, 8, 4, 0, [1, 1])
    bias, worst, rms = model.balance(scores, seeded, 8, 8, 4, 120,
                                     [1e-3, 1e-5])
    assert float(rms0) > 0.05 and float(worst0) > 0.15, (rms0, worst0)
    assert float(rms) < 0.01 and float(worst) < 0.03, (rms, worst)
    _g, chosen = ex._route(x, router, bias[0], 8, 2.5, 8, 4)
    _g, plain = ex._route(x, router, jnp.zeros(256), 8, 2.5, 8, 4)
    chosen, plain = np.asarray(chosen), np.asarray(plain)
    assert abs(chosen[:, :16].sum() / (8192 * 8 / 16) - 1) < 0.01
    assert abs(plain[:, :16].sum() / (8192 * 8 / 16) - 1) > 0.01
    assert (chosen != plain).any(axis=1).mean() > 0.25
    assert 0.001 < float(jnp.std(bias)) < 0.01


# -- 3. the manager: every layer latent ------------------------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_six_latent_layers_get_six_pools_and_nothing_else():
    """The configuration as the cell serves it: six latent pools of rows 640
    wide on the global block tables; no K/V pool, no slot, no ring; a step's
    lanes carry no slot and no ring column; the carry is those six arrays."""
    config, cfg = _published()
    assert cfg.layer_types == ("latent",) * 6 and cfg.routed_layers \
        == (1, 2, 3, 4, 5)
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rope,
            cfg.latent_rank, cfg.q_rank, cfg.dense_ffn, cfg.ffn,
            cfg.shared_ffn, cfg.experts, cfg.experts_held,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling, cfg.vocab, cfg.norm_eps) == (
        7168, 128, 128, 64, 512, 1536, 18432, 2048, 2048, 256, 16, 8, 8, 4,
        2.5, 16160, 1e-6)
    kv = dm.cache_config(cfg, 16, 12832, state_slots=0)
    assert (kv.layers, kv.latent_layers, kv.latent_width, kv.latent_row,
            kv.state_layers, kv.window_layers, kv.state_shapes) \
        == (0, 6, 576, 640, 0, 0, ())
    from paddle_tpu.serving import kv_cache as kvc

    assert kvc.block_bytes(kv) == 6 * 16 * 640 * 2 == 122880
    assert kvc.block_bytes(kv) * 12832 == 1576796160
    assert set(dm.lane_columns(kv, 512)[0]) \
        == {"tok", "src", "pos", "lens", "tables"}
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(
        dm.cache_config(cfg, 16, 8)).carry())
    assert [a.shape for a in carry] == [(8, 16, 640)] * 6
    # the kernels' rules at these shapes: the latent kernel a lane at a
    # time, the experts in chunks of 256 columns
    assert pa._latent_lane_grid((32, 128, 640), (12832, 16, 640),
                                jnp.bfloat16, 512)
    assert dm.experts_chunk(cfg) == moe.f_chunk(7168, 2048, 2) == 256


def test_published_sizes_give_the_issues_bytes():
    """The held model, from the shapes the benchmark makes weights by:
    5,503,361,280 parameters (the dense lead 583,483,392, a routed layer's
    share 937,640,192, embedding, head and final norm 231,676,928),
    11,006,722,560 B in bfloat16, 65.1% of one chip's 16,909,336,064 B; with
    the cell's cache 74.4%."""
    config, _cfg = _published()
    shapes = model.param_shapes(config)
    count = lambda keep: sum(int(np.prod(s)) for n, (s, _k)
                             in shapes.items() if keep(n))
    assert count(lambda n: n.startswith("l0_")) == 583483392
    assert count(lambda n: n.startswith("l3_")) == 937640192
    assert count(lambda n: not n.startswith("l")
                 or n.startswith("lnf")) == 231676928
    mla = count(lambda n: n.startswith("l3_w") and n[3:] in (
        "wq_a", "wq_b", "wkva", "wkvb", "wo")) + 1536 + 512
    assert mla == 187107328
    total = count(lambda n: True)
    assert total == 5503361280
    hbm = 16909336064
    assert round(100 * 2 * total / hbm, 1) == 65.1
    assert round(100 * (2 * total + 1576796160) / hbm, 1) == 74.4


def test_config_refuses_what_no_block_computes():
    tiny = CFG.to_dict()
    bad = lambda **kw: dm.DecoderConfig(**dict(tiny, **kw))
    with pytest.raises(ValueError, match="dots_vlm block's layers are latent"):
        bad(layer_types=("latent", "kda", "latent", "latent"))
    with pytest.raises(ValueError, match="topk_group of n_group"):
        bad(topk_group=5)
    with pytest.raises(ValueError, match="topk_group of n_group"):
        bad(n_group=3)
    with pytest.raises(ValueError, match="turn in pairs"):
        bad(latent_rope=7)
    # what the other families' blocks do not compute
    kimi = fam.ROWS["kimi_linear"].f32[0].to_dict()
    for changes in (dict(q_rank=8), dict(rope_scaling=fam.YARN)):
        with pytest.raises(ValueError,
                           match=r"dots_vlm\|glm_dsa\|longcat_flash\|xing4 "
                           r"blocks' latent"):
            dm.DecoderConfig(**dict(kimi, **changes))
    with pytest.raises(ValueError, match="exaone_moe|kimi_linear|dots_vlm"):
        dm.DecoderConfig(**dict(fam.ROWS["lfm2_moe"].f32[0].to_dict(),
                                n_group=2, topk_group=1))
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    for key, value in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                       ("num_nextn_predict_layers", 1),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="dots_vlm block is MLA"):
            model.decoder_config(dict(config, **{key: value}))
        with pytest.raises(ValueError, match="dots_vlm reference is MLA"):
            ref.forward(dict(config, **{key: value}), {}, [0])


# -- 4. the engine, the server, the client ---------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="dv")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("dv", prompt, max_new_tokens=n,
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
    """Traced, the step's span says the blocks a latent layer fetched with
    their size, what a share's router assigned here and elsewhere and how
    many of the groups that hold a held expert a token kept; the gauge says
    what the latent pools hold; the prewarm event names the paths."""
    cfg = CFG.replace(experts_held=4, expert_first=4)     # group 1, whole
    params = init(cfg, seed=3)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 24, buckets="2", name="dv")
        try:
            e.prewarm()
            r = e.generate("dv", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "dv")
    assert len(steps) >= 20
    assert all(s["kv_block_size"] == BS
               and s["latent_blocks_read"] == s["kv_blocks_read"] == 2 * MAXB
               and "kda_state_lanes" not in s for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 3 experts a token over 16, 4 of them held here: group 1,
    # which the token keeps (1) or not (0) in each of the three layers
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 3.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        and 0.0 <= s["moe_groups_kept"] <= 1.0
        and round(3 * s["moe_groups_kept"], 2) == round(
            3 * s["moe_groups_kept"])
        # an expert is chosen only in a kept group
        and s["moe_local_assignments"] <= 3 * s["moe_groups_kept"]
        for s in routed)
    assert 0 < np.mean([s["moe_groups_kept"] for s in routed]) < 1
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    # 4 latent layers, 24 blocks of 4 rows of 128 (32 values, the tile
    # filled up) in float32
    assert gauges["latent_pool_bytes{model=dv}"] == 4 * 24 * 4 * 128 * 4
    assert not any(k.startswith(("kda_state", "ssm_state")) for k in gauges)
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "dv" and ev["attention"] == "gather"
        and ev["latent_attention"] == "gather"
        and ev["experts"] == "einsum" and "experts_f_chunk" not in ev
        and ev["chunk_positions"] == {} and "state_update" not in ev
        for ev in warm)


# -- 5. the kernels at this family's shapes, under the interpreter ---------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_latent_attention_kernel_at_128_rows_walks_the_lanes(
        interpreted, monkeypatch, dtype, tol):
    """128 query rows over one cached head 256 wide, the value its first 128
    columns, with VMEM for one lane's query and output at a time (as the
    published 128 heads of 640 + 512 leave at 32 lanes): the grid walks the
    lanes, the chunk buffers and the copies in flight pass from a lane to
    the next, and the result is the gather's; contexts of one token, of
    chunks and a half, of nothing (an idle lane returns zeros, and the lane
    behind it starts its own first chunk), tables shuffled."""
    monkeypatch.setattr(pa, "_VMEM_BUDGET", 5 << 18)
    rng = np.random.default_rng(3)
    lanes, heads, width, rank, bs, maxb = 5, 128, 256, 128, 16, 40
    pool = jnp.asarray(rng.standard_normal((200, bs, width)), dtype)
    q = jnp.asarray(rng.standard_normal((lanes, heads, width)), jnp.float32)
    lens = jnp.asarray([1, 590, 0, 77, 512], jnp.int32)
    tables = np.full((lanes, maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, 200)))
    for b, n in enumerate(np.asarray(lens)):
        for j in range(-(-int(n) // bs)):
            tables[b, j] = next(free)
    tables = jnp.asarray(tables)
    assert pa._latent_lane_grid(q.shape, pool.shape, dtype, rank)
    assert pa.latent_path(q.shape, pool.shape, dtype, rank) == "pallas"
    span = pa.latent_chunk_positions(q.shape, pool.shape, dtype, rank, maxb)
    assert span in (256, 384, 512) and 590 > span
    got = jax.jit(lambda *a: pa.latent_attention(*a, 0.1, rank))(
        q, pool, tables, lens)
    assert adoption.active_kernels() == ["latent_attention"]
    want = pa.latent_attention_reference(q, pool, tables, lens, 0.1, rank)
    assert got.shape == (lanes, heads, rank)
    live = [0, 1, 3, 4]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    assert not np.asarray(got)[2].any()
    # every lane's query and output at once: the same numbers
    monkeypatch.undo()
    monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
    assert not pa._latent_lane_grid(q.shape, pool.shape, dtype, rank)
    whole = jax.jit(lambda *a: pa.latent_attention(*a, 0.1, rank))(
        q, pool, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               atol=1e-6 if dtype == jnp.float32 else tol)


def test_the_latent_and_expert_rules_at_the_published_shapes():
    """What ``adoption.decide`` is given at the cell's shapes passes every
    check but the backend's here: 128 heads of 640 over the cell's pool (a
    lane at a time: 2.5e6 B of VMEM, where every lane's query and output
    would be 18.9e6 B), and 16 experts of 7168 x 2048 in chunks of 256
    columns (two buffers of three matrices cost 86,016 B a column: 384 does
    not divide 2048, 512 asks for 44e6 B).  The other cells' chunks stay:
    OLMoE's 1024, LFM2's 768, Kimi-Linear's 512, K-EXAONE's 256 and the 928
    rows of Nemotron-H's two-matrix experts."""
    q, pool = (32, 128, 640), (12832, 16, 640)
    checks = dict(pa.latent_attention_checks(q, pool, jnp.bfloat16, 512))
    assert [k for k, ok in checks.items() if not ok] == ["backend"]
    assert pa.latent_vmem_bytes(q, pool, jnp.bfloat16, 512) \
        == 2 * 512 * 1280 + 2 * 4 * 128 * 1152 <= pa._VMEM_BUDGET
    assert 32 * 4 * 128 * 1152 > pa._VMEM_BUDGET
    assert pa.latent_chunk_positions(q, pool, jnp.bfloat16, 512, 512) == 512
    checks = dict(moe.moe_experts_checks(32, (16, 7168, 2048), jnp.bfloat16))
    assert [k for k, ok in checks.items() if not ok] == ["backend"]
    assert 6 * 7168 * 2 == 86016
    assert moe.f_chunk(7168, 2048, 2) == 256
    assert 6 * 7168 * 512 * 2 > moe._BLOCK_BUDGET >= 6 * 7168 * 256 * 2
    assert moe._vmem_bytes(32, 7168, 256, 2) <= moe._VMEM_LIMIT
    chunks = {}
    for row in fam.ROWS.values():
        with open(fam.config_file(row.chunk[0])) as fp:
            config = json.load(fp)
        config.pop("tiny", None)
        if row.arch != "gpt2":
            chunks[row.arch] = dm.experts_chunk(fam.load(
                "benchmark", "models", config["model"] + ".py"
            ).decoder_config(config))
    assert chunks == {"olmoe": 1024, "granite_hybrid": None, "lfm2_moe": 768,
                      "exaone_moe": 256, "nemotron_h": 928,
                      "kimi_linear": 512, "dots_vlm": 256,
                      "smallthinker": 768, "glm_dsa": 256,
                      "longcat_flash": 256, "solar_open2": 256,
                      "xing4": 512}


def test_routed_experts_kernel_in_chunks_of_an_eighth_of_the_width(
        interpreted):
    """The expert kernel with the width cut in eight chunks, as 2048 is at
    hidden 7168 (here 1024 in chunks of 128 at hidden 256): the einsums'
    sum, experts no live lane chose left unread."""
    rng = np.random.default_rng(7)
    lanes, e, hidden, ffn = 8, 16, 256, 1024
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.float32)
    x, wg, wu, wd = w(lanes, hidden) * 20, w(e, hidden, ffn), \
        w(e, hidden, ffn), w(e, ffn, hidden)
    gates = np.zeros((lanes, e), np.float32)
    for b in range(lanes):
        gates[b, rng.permutation(e)[:3]] = rng.uniform(0.2, 1.0, 3)
    live = jnp.asarray([True] * 6 + [False] * 2)
    got = moe._experts_pallas(x, jnp.asarray(gates), live, wg, wu, wd,
                              fc=128)
    want = moe.experts_reference(
        x, jnp.where(live[:, None], jnp.asarray(gates), 0.0), wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)
    assert not np.asarray(got)[6:].any()


def test_the_paged_step_on_two_kernels_gives_the_jnp_steps_tokens(
        interpreted):
    """The whole step with the latent-attention and expert kernels
    interpreted (4 query heads of 128 + 32 rotated over 96 latent values:
    rows of 128 held 128 wide; experts of width 128 in 4 groups): the tokens
    and logits of the jnp step."""
    cfg = dm.DecoderConfig(
        arch="dots_vlm", vocab=61, layers=3, heads=4, head_dim=128,
        hidden_size=128, max_seq=64, layer_types=("latent",) * 3,
        latent_rank=128, latent_rope=32, q_rank=64, rope_scaling=fam.YARN,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, n_group=4, topk_group=2,
        routed_scaling=2.5, norm_eps=1e-6)
    params = dv.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    kv = dm.cache_config(cfg, 16, 12)
    assert (kv.latent_width, kv.latent_row) == (160, 256)
    assert dm.attention_path(cfg, kv, 2, "latent") == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"
    assert dm.chunk_positions(cfg, kv, 2) == {"latent": 64}

    def run():
        # one lane of a two-lane step, 20 tokens by the step's own argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernels = run()
    assert set(adoption.active_kernels()) == {"latent_attention",
                                              "moe_experts"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.attention_path(cfg, kv, 2, "latent") == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=1e-4, rtol=1e-4)
