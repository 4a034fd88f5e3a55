"""What is the LongCat-Flash decoder block's own (paddle_tpu/models/
longcat_flash.py: a PAIR of latent-attention sublayers and dense MLPs round
one routed part that is read behind the first sublayer's mixer and added
behind the second's MLP, a softmax router wider than its experts whose bias
chooses and never weighs, gates that are not renormalised, identity experts
that return their input, the two scales of the latent mixer): logits at
every position against its plain reference
(benchmark/reference/longcat_flash_ref.py, the file the benchmark uses, which
computes latent attention *expanded* with the scales where the source puts
them), prefill then decode through the paged step and the cache manager; the
reference told otherwise; the router against a loop; a token all of whose
choices are identity experts, and one none of whose are; the share; what
``DecoderConfig`` refuses; what the cache manager gives a model of pairs;
server and client; the step's span, counters and prewarm event; the
benchmark's balancing; the cost functions; the kernels under the
interpreter.  The contract it shares with every family is
tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: 2 pairs (4 ``latent``
sublayers), hidden 48 under 4 heads of 16 (+ 8 rotated values) over 24
latent values, the query through 20, dense MLPs of width 64, a router of 24
outputs (16 experts of width 24 and 8 identity experts), 3 a token, vocab
97."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import longcat_flash as lc
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.serving import decode_model as dm

CONFIG_FILE = fam.config_file("longcat-flash-chat-serve.json")
ref = fam.load("benchmark", "reference", "longcat_flash_ref.py")
model = fam.load("benchmark", "models", "longcat_flash_decoder.py")
cost = fam.load("benchmark", "longcat_cost.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["longcat_flash"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(lc.init_params, std=0.3, bias_std=0.05)


def run_paged(cfg, params, seqs, **kw):
    """``fam.run_paged``, every live lane's token counted once by each
    pair's router, over all its outputs."""
    out, routed = fam.run_paged(cfg, params, seqs, **kw)
    rows = len(cfg.routed_layers)
    assert all(r.shape == (rows, cfg.router_width) for r in routed)
    assert sum(int(r.sum()) for r in routed) == rows \
        * cfg.experts_per_token * sum(len(toks) for toks, _lg in out)
    return out


def ref_config(cfg, **changed):
    """The source's keys, as the reference reads them."""
    return dict({
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "num_layers": cfg.layers // 2, "kv_lora_rank": cfg.latent_rank,
        "q_lora_rank": cfg.q_rank, "qk_nope_head_dim": cfg.head_dim,
        "qk_rope_head_dim": cfg.latent_rope, "v_head_dim": cfg.v_head_dim,
        "mla_scale_q_lora": cfg.latent_q_scale != 1.0,
        "mla_scale_kv_lora": cfg.latent_kv_scale != 1.0,
        "rope_theta": cfg.rope_theta, "ffn_hidden_size": cfg.dense_ffn,
        "expert_ffn_hidden_size": cfg.ffn, "num_experts": cfg.experts_held,
        "n_routed_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first,
        "zero_expert_num": cfg.zero_experts, "zero_expert_type": "identity",
        "moe_topk": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling,
        "attention_method": "MLA", "attention_bias": False,
        "rms_norm_eps": cfg.norm_eps}, **changed)


# float32 rounding over four sublayers (measured 2e-5 here); a fault in
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
    sequence so far, and what every sublayer's pool holds of a sequence is
    the reference's ``[a_kv c | rotated k_pe]`` rows: four pools for two
    pairs."""
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
    assert len(held["pools"]) == len(kept["rows"]) == 4
    for pool, rows in zip(held["pools"], kept["rows"]):
        # the lane's blocks were handed out in order from block 1
        got = pool[1:1 + -(-len(toks) // BS)].reshape(-1, pool.shape[-1])
        assert not got[:, CFG.latent_width:].any()
        np.testing.assert_allclose(got[:len(toks), :CFG.latent_width], rows,
                                   atol=TOL_F32)


def test_the_unpaged_loop_gives_the_reference_too():
    """No pages, no batch: ``make_unpaged_step`` a token at a time."""
    toks = fam.PROMPT + [8, 0, 3]
    got = fam.teacher_forced(CFG, PARAMS, toks)
    assert np.abs(got - _ref(CFG, PARAMS, toks)).max() < TOL_F32


BREAKS = {
    "no_rotation": dict(rope=False),
    "no_q_scale": dict(a_q=False),
    "no_kv_scale": dict(a_kv=False),
    "bias_ignored": dict(use_bias=False),
    "routed_scaling_dropped": dict(scaled=False),
    "gates_renormalised": dict(renormalised=True),
    "no_identity_part": dict(zero=False),
    "routed_part_read_at_h3": dict(read_at="h3"),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    """Each part of the pair's mathematics left out of the reference (a
    scale of the mixer, the unrenormalised x 6, the identity part, "read at
    ``h1``, added after ``mlp_1``") moves the logits a thousand times the
    tolerance."""
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) > 0.2


@pytest.mark.parametrize("gone", ["latent_q_scale", "latent_kv_scale"])
def test_a_step_without_a_scale_is_seen(gone):
    """The served side told otherwise: the block without ``a_q`` or ``a_kv``
    against the whole reference."""
    cfg = CFG.replace(**{gone: 1.0})
    out, _routed = fam.run_paged(cfg, PARAMS, fam.sequences(2, seed=1))
    assert min(float(np.abs(lg - _ref(CFG, PARAMS, toks)).max())
               for toks, lg in out) > 0.2
    # ... and the reference told the same agrees with it
    flag = {"latent_q_scale": "mla_scale_q_lora",
            "latent_kv_scale": "mla_scale_kv_lora"}[gone]
    assert _worst(CFG, out, PARAMS, **{flag: False}) < TOL_F32


def test_a_context_past_one_chunk_of_the_table():
    """A sequence of 61 positions in a table of 16 blocks of 4: every block
    but the last filled, beside a short one."""
    seqs = [(list(np.random.RandomState(4).randint(0, 97, 40)), 21),
            (fam.PROMPT, 5)]
    out = run_paged(CFG, PARAMS, seqs, blocks=24)
    assert [len(t) for t, _lg in out] == [61, 16]
    assert _worst(CFG, out, PARAMS) < TOL_F32


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """As served (bf16 weights and cache, float32 accumulation) the logits
    stay within bf16's rounding of the float32 reference on the same
    weights; the same weights rounded to fp8 do not.  Judged by the median
    over positions of a position's largest error: two routers of 24 outputs
    are won by hundredths, bf16 swaps one now and then, and a swap moves that
    position's logits."""
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
    assert err < 0.2 * std < 0.5 * std < err8, (err, err8, std)


def test_bf16_unpaged_tokens_are_the_paged_lanes():
    """bfloat16 sums depend on the step's lanes; a sequence alone in a
    one-lane paged step is the unpaged loop bit for bit."""
    (fed, _lg), = run_paged(CFG16, PARAMS16, [(fam.PROMPT, 8)])
    assert fed[len(fam.PROMPT):] == list(
        fam.alone(CFG16, PARAMS16, fam.PROMPT, 8))


# -- 2. the router, the identity experts, the share -----------------------------

def _route_by_loop(x, router, bias, k, scaling):
    """The router a token at a time in numpy float64."""
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    gates = np.zeros_like(logits)
    for t, row in enumerate(logits):
        p = np.exp(row - row.max())
        p /= p.sum()
        chosen = np.argsort(-(p + np.asarray(bias, np.float64)),
                            kind="stable")[:k]
        gates[t, chosen] = scaling * p[chosen]
    return gates


@pytest.mark.parametrize("experts,zero,k", [(16, 8, 3), (8, 8, 12), (5, 0, 2),
                                            (512, 256, 12)])
def test_the_router_equals_a_loop(experts, zero, k):
    """Softmax over experts and identity experts alike, the bias in the
    choice and not in the weight, ``routed_scaling`` times the probability
    with no renormalisation: a chosen set's gates sum to what they sum to."""
    rng = np.random.RandomState(experts + k)
    x = rng.randn(9, 48).astype(np.float32)
    router = (rng.randn(48, experts + zero) * 0.3).astype(np.float32)
    bias = (rng.randn(experts + zero) * 0.02).astype(np.float32)
    gates, chosen = lc._route(jnp.asarray(x), jnp.asarray(router),
                              jnp.asarray(bias), k, 6.0)
    want = _route_by_loop(x, router, bias, k, 6.0)
    np.testing.assert_allclose(np.asarray(gates), want, atol=1e-5)
    assert np.array_equal(np.asarray(chosen), want > 0)
    assert (np.asarray(chosen).sum(axis=1) == k).all()
    sums = np.asarray(gates).sum(axis=1)
    # not renormalised: under 6, and differing from token to token
    assert (sums < 6.0).all() and np.ptp(sums) > 1e-3
    # the bias moved some token's choice and no gate's value
    plain, same = lc._route(jnp.asarray(x), jnp.asarray(router),
                            jnp.zeros_like(jnp.asarray(bias)), k, 6.0)
    both = np.asarray(chosen) & np.asarray(same)
    np.testing.assert_array_equal(np.asarray(gates)[both],
                                  np.asarray(plain)[both])


def _pair(params, l=0):
    return {k[len("l%d_" % l):]: jnp.asarray(v) for k, v in params.items()
            if k.startswith("l%d_" % l)}


def test_a_token_all_of_whose_choices_are_identity_experts():
    """A bias that sends every token's 3 choices to identity experts gives
    ``s = (sum g) h1`` to the bit and no expert a token; one that sends none
    there gives no identity part."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(7, CFG.hidden), jnp.float32)
    live = jnp.ones(7, bool)
    p = _pair(PARAMS)
    bias = np.zeros(CFG.router_width, np.float32)
    bias[CFG.experts:] = 10.0
    y, z, chosen = lc.routed_part(
        CFG, dict(p, expert_bias=jnp.asarray(bias)).__getitem__, x, live)
    chosen = np.asarray(chosen)
    assert not chosen[:, :CFG.experts].any() \
        and (chosen[:, CFG.experts:].sum(axis=1) == 3).all()
    assert not np.asarray(y).any()
    gates, _c = lc._route(x, p["router"], jnp.asarray(bias), 3, 6.0)
    want = np.asarray(jnp.sum(gates, axis=1, keepdims=True) * x)
    assert np.array_equal(np.asarray(z), want)
    # ... and the reference's routed part is the same numbers
    with jax.default_matmul_precision("highest"):
        g, _m = ref.gates_of(ref_config(CFG), dict(
            p, expert_bias=jnp.asarray(bias)), x)
        np.testing.assert_allclose(
            np.asarray(ref.zero_out(ref_config(CFG), x, g)), want, atol=1e-5)
        assert not np.asarray(ref.routed_sum(ref_config(CFG), p, x, g)).any()
    y, z, chosen = lc.routed_part(
        CFG, dict(p, expert_bias=jnp.asarray(-bias)).__getitem__, x, live)
    assert not np.asarray(z).any() and np.asarray(y).any() \
        and not np.asarray(chosen)[:, CFG.experts:].any()


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_the_identity_part_and_the_dense_mlps_once_are_the_uncut_layer(
        shares):
    """One layer of the source (a pair) with its 16 experts cut in shares:
    each share routes over all 24 outputs and computes its own experts' part
    (equal to the reference given the same share); the shares' routed parts,
    the identity part counted once and the pair's mixers and dense MLPs
    counted once are the uncut reference's layer output, and neither a share
    alone nor the identity part counted a share is."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(12, CFG.hidden), jnp.float32)
    live = jnp.ones(12, bool)
    first, second = _pair(PARAMS, 0), _pair(PARAMS, 1)
    whole = ref_config(CFG)
    with jax.default_matmul_precision("highest"):
        want, (gates, _margin), _rows = ref.layer(whole, first, second, x)
        # what every share computes alike: the layer with no routed part
        alike, _r, _rows = ref.layer(
            ref_config(CFG, num_experts=0, n_routed_experts=0), first,
            second, x, zero=False)
        eps = CFG.norm_eps
        mixed, _row = ref.mla(whole, {k: first[k] for k in ref.MIXER},
                              ref._rmsnorm(x, first["ln1_g"], eps))
        h1 = ref._rmsnorm(x + mixed, first["ln2_g"], eps)
        parts = []
        for share in range(shares):
            mine = CFG.replace(experts_held=16 // shares,
                               expert_first=16 // shares * share)
            held = dict(first, **{w: first[w][mine.held_experts]
                                  for w in ("wgate", "wup", "wdown")})
            part, zero, chosen = lc.routed_part(mine, held.__getitem__, h1,
                                                live)
            assert chosen.shape == (12, 24) \
                and (np.asarray(chosen).sum(axis=1) == 3).all()
            np.testing.assert_allclose(
                np.asarray(part), np.asarray(ref.routed_sum(
                    ref_config(mine), held, h1, gates)), atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(zero), np.asarray(ref.zero_out(whole, h1, gates)),
                atol=2e-5)
            parts.append(np.asarray(part))
        zero, want, alike = (np.asarray(a) for a in (zero, want, alike))
    np.testing.assert_allclose(alike + sum(parts) + zero, want, atol=1e-4)
    assert np.abs(alike + parts[0] + zero - want).max() > 1e-2
    assert np.abs(alike + sum(parts) + shares * zero - want).max() > 1e-2
    assert np.abs(alike + sum(parts) - want).max() > 1e-2


def test_the_served_bias_moves_the_choice_and_a_lanes_real_count_varies():
    """Through the paged step: the counts' rows are pairs, their columns all
    24 outputs, the identity experts' last; with the bias zeroed some
    token's choice changes; a token's real experts vary from token to
    token."""
    seqs = fam.sequences(4, seed=2)
    out, routed = fam.run_paged(CFG, PARAMS, seqs)
    counts = sum(routed)
    assert counts.shape == (2, 24) and counts[:, 16:].sum() > 0
    forced = [(toks, 0) for toks, _lg in out]
    unbiased = {k: (np.zeros_like(v) if k.endswith("expert_bias") else v)
                for k, v in PARAMS.items()}
    _out, other = fam.run_paged(CFG, unbiased, forced)
    assert not np.array_equal(sum(other), counts)
    x = jnp.asarray(np.random.RandomState(3).randn(64, CFG.hidden),
                    jnp.float32)
    _y, _z, chosen = lc.routed_part(CFG, _pair(PARAMS).__getitem__, x,
                                    jnp.ones(64, bool))
    real = np.asarray(chosen)[:, :16].sum(axis=1)
    assert real.min() < real.max() <= 3


def test_the_block_counts_real_experts_a_lane():
    """The step's second extra: live lanes by the real experts they chose,
    a row a pair; idle lanes are counted nowhere."""
    kv = dm.cache_config(CFG, BS, 12)
    step = jax.jit(dm.make_paged_step(CFG, kv))
    cache = fam.kvc.PagedKVCache(kv)
    tables = np.full((3, MAXB), -1, np.int32)
    tables[0, 0], tables[2, 0] = 1, 2
    _c, _n, _lg, routed, real = step(
        cache.carry(), _jnp(PARAMS), np.asarray([5, 0, 9]), np.zeros(3),
        tables, np.asarray([1, 0, 1]))
    routed, real = np.asarray(routed), np.asarray(real)
    assert real.shape == (2, 4) and (real.sum(axis=1) == 2).all()
    assert (routed.sum(axis=1) == 2 * 3).all()
    # real experts chosen, counted either way
    assert np.array_equal((real * np.arange(4)).sum(axis=1),
                          routed[:, :16].sum(axis=1))


# -- 3. the configuration -------------------------------------------------------

def test_config_refuses_what_no_block_computes():
    kw = CFG.to_dict()
    with pytest.raises(ValueError, match="pair of sublayers"):
        dm.DecoderConfig(**dict(kw, layers=3, layer_types=["latent"] * 3))
    with pytest.raises(ValueError, match="layer_types"):
        dm.DecoderConfig(**dict(kw, layer_types=["attention"] * 4))
    with pytest.raises(ValueError, match="zero_experts"):
        dm.DecoderConfig(**dict(kw, zero_experts=-1))
    with pytest.raises(ValueError, match="shared expert"):
        dm.DecoderConfig(**dict(kw, shared_ffn=8))
    with pytest.raises(ValueError, match="dense_layers"):
        dm.DecoderConfig(**dict(kw, dense_layers=1))
    with pytest.raises(ValueError, match="n_group"):
        dm.DecoderConfig(**dict(kw, n_group=2, topk_group=1))
    with pytest.raises(ValueError, match="experts_per_token"):
        dm.DecoderConfig(**dict(kw, experts_per_token=17))
    assert dm.DecoderConfig(**kw).to_dict() == kw
    assert CFG.routed_layers == (0, 2) and CFG.router_width == 24
    assert CFG.replace(layers=8, layer_types=("latent",) * 8) \
        .routed_layers == (0, 2, 4, 6)


@pytest.mark.parametrize("arch", [a for a in dm.ARCHS
                                  if a != "longcat_flash"])
@pytest.mark.parametrize("field,value", [("zero_experts", 4),
                                         ("latent_q_scale", 2.0),
                                         ("latent_kv_scale", 1.5)])
def test_every_other_family_refuses_the_new_fields(arch, field, value):
    """Identity experts and the two scales are this family's, as ``n_group``
    and ``q_rank`` are their families': a configuration that states one for
    a block that does not compute it is refused, by name."""
    cfg = fam.ROWS[arch].f32[0]
    with pytest.raises(ValueError, match="longcat_flash"):
        dm.DecoderConfig(**dict(cfg.to_dict(), **{field: value}))


def test_a_truncation_keeps_whole_pairs():
    """A draft is the family's block: a one-layer truncation of a model of
    pairs keeps the first pair, router and experts with it."""
    cfg, params = dm.truncate_decoder(CFG, PARAMS, layers=1)
    assert (cfg.layers, cfg.routed_layers) == (2, (0,))
    assert {k for k in params if k.startswith("l")} == {
        k for k in PARAMS if k.startswith(("l0_", "l1_", "lnf"))}
    assert dm.truncate_decoder(CFG, PARAMS, layers=3)[0].layers == 4
    toks = dm.unpaged_generate(cfg, params, fam.PROMPT, 4)
    assert len(toks) == 4


def test_four_sublayers_get_four_pools_and_nothing_else():
    """The cache manager sees latent layers, as many as sublayers: the
    published cut is eight pools of 17,472 blocks of 16 rows held 640 wide,
    163,840 B a block over the eight, 2,862,612,480 B."""
    kv = dm.cache_config(CFG, BS, 10)
    assert (kv.layers, kv.latent_layers, kv.state_layers, kv.window_layers,
            kv.index_layers) == (0, 4, 0, 0, 0)
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    cfg = model.decoder_config(config)
    kv = dm.cache_config(cfg, 16, 17472)
    assert (kv.latent_layers, kv.latent_row, kv.latent_width) == (8, 640, 576)
    carry = jax.eval_shape(lambda: fam.kvc.PagedKVCache(kv).carry())
    assert len(carry) == 8 and all(
        a.shape == (17472, 16, 640) and a.dtype == jnp.bfloat16
        for a in carry)
    assert fam.kvc.latent_block_bytes(kv) * 8 == 163840
    assert sum(int(np.prod(a.shape)) * 2 for a in carry) == 2862612480


def test_published_sizes_give_the_issues_bytes():
    """The cut as ISSUE 61 and the configuration's ``reduced_why`` count it:
    a sublayer's MLA 90,572,800 parameters, a dense MLP 226,492,416, the
    router 4,719,360, an expert 37,748,736; a pair at 32 chips
    1,242,854,144; four and an eighth of the vocabulary 5,172,749,312
    parameters, 10,345,498,624 B; the cost functions read the same."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    shapes = model.param_shapes(config)
    size = lambda *keys: sum(int(np.prod(shapes[k][0])) for k in keys)
    mla = size("l0_wq_a", "l0_q_norm", "l0_wq_b", "l0_wkva", "l0_kv_norm",
               "l0_wkvb", "l0_wo")
    mlp = size("l1_w1", "l1_w3", "l1_w2")
    router = size("l0_router", "l0_expert_bias")
    expert = size("l0_wgate", "l0_wup", "l0_wdown") // 16
    assert (mla, mlp, router, expert) == (90572800, 226492416, 4719360,
                                          37748736)
    pair = sum(int(np.prod(s)) for k, (s, _kind) in shapes.items()
               if k.startswith(("l0_", "l1_")))
    assert pair == 1242854144
    total = sum(int(np.prod(s)) for s, _kind in shapes.values())
    assert total == 5172749312 and 2 * total == 10345498624
    assert "l1_router" not in shapes and "l2_router" in shapes
    assert shapes["l0_router"][0] == (6144, 768)
    # the cost functions: every weight once, all 16 held experts hit, no
    # lanes.  What they leave out: the stream's norms (17 of 6144; q_a's and
    # kv_a's are counted with their mixers), the biases (4 x 768) and the
    # embedding, which is a row a lane
    counted = cost.weight_floor_bytes_per_step(config, 16, 0)
    left_out = (17 * 6144 + 4 * 768) * 2 + 16384 * 6144 * 2
    assert counted == 2 * total - left_out
    assert cost.expert_bytes(config) == 2 * expert
    assert cost.latent_block_bytes(config, 16) == 16 * 576 * 2
    assert cost.latent_flops_per_step(config, 10, 16) \
        == 8 * 10 * 16 * 64 * 2 * (2 * 512 + 64)


def test_the_configuration_states_the_cut_and_the_catalogs_numbers():
    """The file: the catalog row's keys as published but the four it lists
    under ``reduced``, the deployment beside them, and a ``decoder_config``
    that doubles the layers into sublayers."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    published = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA",
        "zero_expert_num": 256, "zero_expert_type": "identity",
        "moe_topk": 12}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"], config["max_position_embeddings"]) \
        == (4, 16, 16384, 4352)
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert (config["expert_parallel_chips"], config["num_experts_published"],
            config["first_expert"]) == (32, 512, 0)
    assert config["assumed"] and config["departures"]
    cfg = model.decoder_config({k: v for k, v in config.items()
                                if k != "tiny"})
    assert (cfg.layers, cfg.routed_layers, cfg.max_seq) \
        == (8, (0, 2, 4, 6), 4352)
    assert (cfg.latent_q_scale, cfg.latent_kv_scale) \
        == (2.0, 12 ** 0.5)
    assert cfg.latent_scale == 192 ** -0.5


# -- 4. the engine ----------------------------------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="lc")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("lc", prompt, max_new_tokens=n,
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
    """Traced, the step's span says the blocks a latent sublayer fetched
    with their size and what a share's router assigned of three kinds: here,
    elsewhere and to identity experts, 3 a lane a pair together; the most
    and the fewest real experts a lane chose; the counter of identity
    assignments; the prewarm event names the routed form and the identity
    experts."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = init(cfg, seed=3)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 24, buckets="2", name="lc")
        try:
            e.prewarm()
            r = e.generate("lc", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "lc")
    assert len(steps) >= 20
    assert all(s["kv_block_size"] == BS
               and s["latent_blocks_read"] == s["kv_blocks_read"] == 2 * MAXB
               for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 3 outputs a token over 24, 4 experts held here, 12 elsewhere
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"]
        + s["moe_zero_assignments"] == 3.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        and 0 <= s["moe_real_per_token_min"] <= s["moe_real_per_token_max"]
        <= 3 and "moe_groups_kept" not in s for s in routed)
    assert sum(s["moe_zero_assignments"] for s in routed) > 0
    assert {s["moe_real_per_token_max"] for s in routed} != {0}
    # a mean over two pairs of whole counts
    assert all(round(2 * s["moe_zero_assignments"], 3)
               == round(2 * s["moe_zero_assignments"]) for s in routed)
    # (the last step's counts are applied after its span has closed)
    assert _tm.counter_total("moe_assignments_zero_total") >= round(
        2 * sum(s["moe_zero_assignments"] for s in routed)) > 0
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    # 4 latent sublayers, 24 blocks of 4 rows of 128 (32 values, the tile
    # filled up) in float32
    assert gauges["latent_pool_bytes{model=lc}"] == 4 * 24 * 4 * 128 * 4
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "lc" and ev["attention"] == "gather"
        and ev["latent_attention"] == "gather" and ev["experts"] == "einsum"
        and ev["routes"] == "pairs" and ev["zero_experts"] == 8
        and ev["chunk_positions"] == {} and "state_update" not in ev
        and "layers" not in ev for ev in warm)


def test_an_untraced_step_fetches_no_counts(cache_dir):
    """The counts ride with the tokens only while the span is recorded: an
    untraced engine asks the account for nothing."""
    e = fam.engine(CFG, PARAMS, 24, buckets="2", name="lc")
    try:
        asked = []
        account = e._models["lc"].account
        whole = account.moe_attrs
        account.moe_attrs = lambda bucket, extras: (
            asked.append(extras), whole(bucket, extras))[1]
        r = e.generate("lc", [1, 2, 3], max_new_tokens=6,
                       deadline_ms=60000.0)
        assert r.status == "ok" and asked and all(x is None for x in asked)
    finally:
        e.stop()


def test_the_account_counts_three_kinds_of_assignment():
    """``StepAccount.moe_attrs`` on counts made by hand: 5 live lanes x 3
    over 2 pairs; held experts 4-7."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, (s, _kind) in lc.param_shapes(cfg).items()}
    account = dm.StepAccount(cfg, dm.cache_config(cfg, BS, 12), params, (8,),
                             model="m")
    counts = np.zeros((2, 24), np.int32)
    counts[0, [4, 5, 9, 17, 23]] = [3, 1, 4, 5, 2]        # 4 here, 4 away, 7
    counts[1, [6, 0, 1, 16]] = [5, 2, 3, 5]               # 5 here, 5 away, 5
    real = np.zeros((2, 4), np.int32)
    real[0, [0, 2, 3]] = [1, 2, 2]
    real[1, [1, 3]] = [2, 3]
    got = account.moe_attrs(8, [counts, real])
    assert got == {"moe_experts_hit": 1.5, "moe_load_max": 4.0,
                   "moe_assignments": 4.5, "moe_local_assignments": 4.5,
                   "moe_absent_assignments": 4.5, "moe_zero_assignments": 6.0,
                   "moe_real_per_token_max": 3, "moe_real_per_token_min": 0}
    assert got["moe_local_assignments"] + got["moe_absent_assignments"] \
        + got["moe_zero_assignments"] == 5 * 3
    idle = account.moe_attrs(8, [np.zeros_like(counts), np.zeros_like(real)])
    assert (idle["moe_real_per_token_max"], idle["moe_real_per_token_min"],
            idle["moe_zero_assignments"]) == (0, 0, 0.0)
    said = account.prewarm_attrs(8)
    assert (said["routes"], said["zero_experts"]) == ("pairs", 8)


# -- 5. the benchmark's builder ---------------------------------------------------

def _tiny_config():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    return dict(config, **config.pop("tiny"))


def test_balancing_gives_the_identity_experts_their_third():
    """``longcat_flash_decoder.balance`` on probabilities drawn uneven: every
    one of the 24 outputs is brought to its share, the 8 identity experts to
    a third of the assignments together, and the bias still moves the
    choice."""
    rng = np.random.RandomState(0)
    logits = rng.randn(2, 4096, 24) + rng.randn(2, 1, 24) * 0.7
    scores = jnp.asarray(jax.nn.softmax(jnp.asarray(logits, jnp.float32),
                                        axis=-1))
    zero = jnp.zeros((2, 24))
    _b, worst0, rms0 = model.balance(scores, zero, 3, 1, [1e-9, 1e-9])
    bias, worst, rms = model.balance(scores, zero, 3, 200, [0.02, 0.0002])
    assert float(worst0) > 0.5 and float(worst) < 0.05 \
        and float(rms) < 0.02 < float(rms0)
    select = np.asarray(scores) + np.asarray(bias)[:, None]
    chosen = select >= np.sort(select, axis=-1)[..., -3][..., None]
    share = chosen[..., 16:].sum() / chosen.sum()
    assert abs(share - 1 / 3) < 0.01
    plain = np.asarray(scores) >= np.sort(
        np.asarray(scores), axis=-1)[..., -3][..., None]
    assert (plain != chosen).any(axis=-1).mean() > 0.2


def test_make_params_balances_on_the_blocks_own_states():
    """The builder at the configuration's tiny sizes: every weight under
    ``param_shapes``'s names in the served dtype, the bias changed from its
    draw on the pairs' first sublayers, the same seed the same weights, a
    seed past 32 bits another."""
    config = _tiny_config()
    device = jax.devices()[0]
    params = model.make_params(config, 5, device)
    shapes = model.param_shapes(config)
    assert set(params) == set(shapes) and all(
        params[k].shape == s and params[k].dtype == jnp.bfloat16
        for k, (s, _kind) in shapes.items())
    drawn = model.make_params(dict(config, expert_bias_balance=None), 5,
                              device)
    assert not np.array_equal(np.asarray(params["l0_expert_bias"]),
                              np.asarray(drawn["l0_expert_bias"]))
    assert np.array_equal(np.asarray(params["l0_wq_a"]),
                          np.asarray(drawn["l0_wq_a"]))
    again = model.make_params(config, 5, device)
    other = model.make_params(config, 5 + (1 << 32), device)
    assert np.array_equal(np.asarray(params["l2_expert_bias"]),
                          np.asarray(again["l2_expert_bias"]))
    assert not np.array_equal(np.asarray(params["embed"]),
                              np.asarray(other["embed"]))
    probs = model.router_probabilities(config, drawn, 5)
    cfg = model.decoder_config(config)
    assert probs.shape == (2, 64, cfg.router_width)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-5)


def test_the_model_file_refuses_another_block():
    config = _tiny_config()
    for key, value in (("attention_method", "GQA"),
                       ("zero_expert_type", "copy"),
                       ("attention_bias", True), ("q_lora_rank", None),
                       ("n_routed_experts", 5)):
        with pytest.raises(ValueError, match="longcat_flash"):
            model.decoder_config(dict(config, **{key: value}))
    with pytest.raises(ValueError, match="longcat_flash"):
        ref.forward(dict(ref_config(CFG), zero_expert_type="copy"),
                    _jnp(PARAMS), jnp.asarray([1, 2]))


def test_the_references_check_reads_the_served_tokens():
    """``check`` teacher-forced through served tokens: the block's own are
    inside both limits, another sequence's are not."""
    prompt = fam.PROMPT
    served = list(fam.alone(CFG, PARAMS, prompt, 8))
    got = ref.check(ref_config(CFG), _jnp(PARAMS), [(prompt, served)], 24)
    assert got["ok"] and got["compared"] == 8 and got["differing"] == 0
    bad = ref.check(ref_config(CFG), _jnp(PARAMS),
                    [(prompt, [(t + 1) % 97 for t in served])], 24)
    assert not bad["ok"] and bad["differing"] >= 7


# -- 6. the kernels at this family's shapes, under the interpreter ---------------

def test_the_paged_step_on_two_kernels_gives_the_jnp_steps_tokens(
        interpreted):
    """The whole step with the latent-attention and expert kernels
    interpreted (4 query heads of 128 + 32 rotated over 96 latent values:
    rows of 128 held 128 wide; experts of width 128, 8 of 16 held beside 8
    identity experts): the tokens and logits of the jnp step."""
    cfg = dm.DecoderConfig(
        arch="longcat_flash", vocab=61, layers=4, heads=4, head_dim=128,
        hidden_size=128, max_seq=64, layer_types=("latent",) * 4,
        latent_rank=128, latent_rope=32, q_rank=64, latent_q_scale=2 ** 0.5,
        latent_kv_scale=1.0, dense_ffn=64, ffn=128, experts=16,
        experts_held=8, zero_experts=8, experts_per_token=3,
        routed_scaling=6.0, rope_theta=1e7)
    params = lc.init_params(cfg, seed=5, std=0.1, bias_std=0.01)
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


def test_the_latent_and_expert_rules_at_the_published_shapes(interpreted):
    """What decides the cell's paths, by shape alone: 64 lanes of 64 heads
    over rows of 640 take the latent kernel in chunks of 512 positions, a
    lane a grid step (64 x 294,912 B of queries and outputs do not fit the
    8 MiB beside the buffers; nor do GLM-5's 32 lanes of them), and 16 held experts of 6144 x 2048 the expert kernel in
    chunks of 256 columns."""
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa

    q, pool = (64, 64, 640), (17472, 16, 640)
    assert pa.latent_path(q, pool, jnp.bfloat16, 512) == "pallas"
    assert pa.latent_chunk_positions(q, pool, jnp.bfloat16, 512, 272) == 512
    assert pa._latent_lane_bytes(q, 512) == 294912
    assert pa._latent_lane_grid(q, pool, jnp.bfloat16, 512)
    assert pa._latent_lane_grid((32, 64, 640), (25120, 16, 640),
                                jnp.bfloat16, 512)
    assert moe.experts_path(64, (16, 6144, 2048), jnp.bfloat16) == "pallas"
    assert moe.f_chunk(6144, 2048, 2) == 256
