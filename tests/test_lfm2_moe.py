"""What is the LFM2-MoE decoder block's own (paddle_tpu/models/lfm2_moe.py:
gated short convolutions beside grouped-query attention, a dense lead layer
and sigmoid-routed experts): logits at every position against its plain
reference (benchmark/reference/lfm2_moe_ref.py, the file the benchmark
uses), prefill then decode through the paged cache and the window slots;
each piece of the routing rule; the per-head Q/K norm; the window's start;
lanes that join and leave; the packed step's feed; the step's span and
prewarm event; the manager's bytes.  The contract it shares with every
family is tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: 5 layers ``conv,
attention, conv, conv, attention``, the first dense (width 48), four routed
(8 experts of width 32, 2 a token), hidden 64, 4 query heads over 2 KV heads
of 16, 3 taps, vocab 97."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import lfm2_moe as lm
from paddle_tpu.models import olmoe
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

ref = fam.load("benchmark", "reference", "lfm2_moe_ref.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["lfm2_moe"].configs[k] for k in ("f32", "bf16"))
KINDS = CFG.layer_types
_jnp = fam.as_jnp
_sequences = fam.sequences
_engine = fam.engine
_flags = fam.flags


def run_paged(cfg, params, seqs, **kw):
    """``fam.run_paged`` -> per lane (tokens fed, logits, and for a sequence
    run alone its routed counts [n, routed layers, experts])."""
    out, routed = fam.run_paged(cfg, params, seqs, **kw)
    return [(f, lg, np.stack(routed) if len(seqs) == 1 else None)
            for f, lg in out]


# normal(0, 0.3): at this hidden size the family's 0.02 leaves the layers'
# share of the residual stream, and so a fault's mark on the logits, small,
# and the tied head would make every token repeat its input


def ref_config(cfg):
    """The source's keys, as the reference reads them."""
    return {"hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
            "num_key_value_heads": cfg.kv_heads,
            "num_hidden_layers": cfg.layers,
            "layer_types": ["full_attention" if k == "attention" else k
                            for k in cfg.layer_types],
            "conv_L_cache": cfg.conv_taps, "conv_bias": False,
            "num_dense_layers": cfg.dense_layers,
            "intermediate_size": cfg.dense_ffn,
            "moe_intermediate_size": cfg.ffn, "num_experts": cfg.experts,
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": True, "use_expert_bias": True,
            "routed_scaling_factor": cfg.routed_scaling,
            "rope_parameters": {"rope_theta": cfg.rope_theta,
                                "rope_type": "default"},
            "norm_eps": cfg.norm_eps}


# float32 rounding over five layers (measured 1e-5 here); a fault in
# structure is 1e-2 or more (the broken-reference controls below)
TOL_F32 = 5e-4
# bfloat16 as served against the float32 reference on the same (bfloat16)
# weights: root-mean-square error over the positions at which the two choose
# the same experts in every layer (RMS_BF16's test says why), logits of
# standard deviation 2.4.  Measured 0.021-0.026 on three seeds; the limit is
# half as much again, and weights rounded to 8 bits (e4m3) read 0.2
RMS_BF16 = 0.04


def _ref(cfg, params, tokens, kept=False, **changed):
    with jax.default_matmul_precision("highest"):
        out = ref.forward(dict(ref_config(cfg), **changed), _jnp(params),
                          jnp.asarray(tokens, jnp.int32), kept)
    return jax.tree_util.tree_map(np.asarray, out)


def _worst(cfg, out, params, **changed):
    return max(float(np.abs(lg - _ref(cfg, params, toks, **changed)).max())
               for toks, lg, _r in out)


# -- 1. against the reference, and the reference broken ------------------------

@functools.lru_cache(None)
def _f32_out():
    return run_paged(CFG, PARAMS, _sequences(3))


def test_f32_logits_equal_the_reference_at_every_position():
    """Prefill token by token, then decode, three lanes of different
    lengths in shuffled blocks and slots: every position's logits are the
    reference's whole-sequence pass, and the decoded tokens are not the
    tied head repeating its input."""
    out = _f32_out()
    assert _worst(CFG, out, PARAMS) < TOL_F32
    toks, _lg, _r = out[0]
    assert len(set(toks[-8:])) > 2


def _zeroed(name):
    return lambda p: dict(p, **{k: np.zeros_like(v) for k, v in p.items()
                                if k.endswith(name)})


def _reversed_taps(p):
    return dict(p, **{k: v[::-1].copy() for k, v in p.items()
                      if k.endswith("conv_w")})


BREAKS = {
    # what the reference is told, against the block as served
    "routed_scaling_factor": dict(routed_scaling_factor=2.0),
    "num_experts_per_tok": dict(num_experts_per_tok=3),
    "rope_theta": dict(rope_parameters={"rope_theta": 1e4,
                                        "rope_type": "default"}),
    "no_dense_lead": dict(num_dense_layers=0),
}
PARAM_BREAKS = {
    # a reference that forgets the bias, or reads the taps the other way
    "no_expert_bias": _zeroed("expert_bias"),
    "reversed_taps": _reversed_taps,
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_reference_told_otherwise(how):
    if how == "no_dense_lead":
        # layer 0 has no router to read
        with pytest.raises(KeyError):
            _worst(CFG, _f32_out()[:1], PARAMS, **BREAKS[how])
        return
    assert _worst(CFG, _f32_out()[:1], PARAMS, **BREAKS[how]) > 20 * TOL_F32


@pytest.mark.parametrize("how", sorted(PARAM_BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    assert _worst(CFG, _f32_out()[:1], PARAM_BREAKS[how](PARAMS)) \
        > 20 * TOL_F32


def test_a_scaling_factor_other_than_one():
    """``routed_scaling`` 2.5 through the block and the reference alike, and
    not the same logits as 1."""
    cfg = CFG.replace(routed_scaling=2.5)
    out = run_paged(cfg, PARAMS, _sequences(1, seed=6))
    assert _worst(cfg, out, PARAMS) < TOL_F32
    assert _worst(cfg, out, PARAMS, routed_scaling_factor=1.0) > 20 * TOL_F32


def test_cached_kv_and_windows_equal_the_reference():
    """What the pools and the slot hold after a sequence: the reference's K
    (after the per-head norm and RoPE) and V of each attention layer, and
    each conv layer's two newest inputs."""
    (prompt, n), = _sequences(1, seed=8)
    kv = dm.cache_config(CFG, BS, 24, state_slots=3)
    cache = kvc.PagedKVCache(kv)
    step = jax.jit(dm.make_paged_step(CFG, kv), donate_argnums=(0,))
    table = np.full((1, CFG.max_seq // BS), -1, np.int32)
    table[0, :6] = [7, 3, 11, 5, 9, 2]
    toks = list(prompt)
    for pos in range(len(prompt) + n):
        carry, nxt, _lg, _c = step(
            cache.carry(), _jnp(PARAMS), np.array([toks[pos]]),
            np.array([pos]), table, np.array([pos + 1]), np.array([2]))
        cache.replace_carry(carry)
        if pos + 1 == len(toks):
            toks.append(int(nxt[0]))
    toks = toks[:len(prompt) + n]
    _logits, kept = _ref(CFG, PARAMS, toks, kept=True)
    groups, (windows,) = kv.groups(cache.carry())
    for i, (k, v) in enumerate(kept["kv"]):
        for pool, want in ((groups[0][i], k), (groups[1][i], v)):
            got = np.asarray(pool[table[0, :6]]).reshape(24, -1)[:len(toks)]
            assert np.abs(got - want.reshape(len(toks), -1)).max() < TOL_F32
    assert len(windows) == len(kept["conv_inputs"]) == 3
    for w, g in zip(windows, kept["conv_inputs"]):
        assert np.abs(np.asarray(w[2]).reshape(2, -1) - g[-2:]).max() \
            < TOL_F32


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """bfloat16 as served against the reference on the same weights, over
    the positions where both choose the same experts: a swapped expert (its
    gate is about a half here) is a consequence of rounding and not an error
    of arithmetic, and moves a position's logits by tenths.  They are found
    by the routed counts of the served step itself."""
    seqs = _sequences(3, seed=1, lo=16, hi=24, n_decode=24)

    def rms(params):
        sq, n, agreed, positions = 0.0, 0, 0, 0
        for prompt, n_dec in seqs:
            (toks, lg, routed), = run_paged(CFG16, params, [(prompt, n_dec)])
            want, kept = _ref(CFG16, PARAMS16, toks, kept=True)
            # [positions, routed layers, experts]: the served choice
            same = np.all((routed > 0) == (np.stack(kept["gates"], 1) > 0),
                          axis=(1, 2))
            positions += len(same)
            agreed += int(same.sum())
            sq += float(np.square(lg[same] - want[same]).sum())
            n += lg[same].size
        return (sq / n) ** 0.5, agreed / positions

    served, share = rms(PARAMS16)
    coarse, _share = rms(fam.fp8_rounded(PARAMS16))
    assert share > 0.7
    assert served < RMS_BF16 < coarse


# -- 2. the pieces of the block -------------------------------------------------

def _route(bias, scaling=1.0, k=2, seed=0):
    rng = np.random.RandomState(seed)
    h2 = jnp.asarray(rng.randn(16, CFG.hidden), jnp.float32)
    router = jnp.asarray(rng.randn(CFG.hidden, CFG.experts) * 0.1,
                         jnp.float32)
    gates, chosen = lm._route(h2, router, jnp.asarray(bias, jnp.float32), k,
                              scaling)
    score = jax.nn.sigmoid(jnp.dot(h2, router, precision="highest"))
    return np.asarray(gates), np.asarray(chosen), np.asarray(score)


def test_the_bias_selects_and_never_weighs():
    """A bias that lifts experts 5 and 6 over every score makes them the
    choice of every token, and their weights are their own sigmoid scores,
    renormalised: nothing of the bias is in them."""
    bias = np.zeros(CFG.experts)
    bias[[5, 6]] = 10.0
    gates, chosen, score = _route(bias)
    assert (chosen == (np.arange(CFG.experts) % 8 >= 5)[None]
            & (np.arange(CFG.experts) < 7)[None]).all()
    want = score[:, [5, 6]] / (score[:, [5, 6]].sum(-1, keepdims=True) + 1e-6)
    assert np.allclose(gates[:, [5, 6]], want, atol=1e-6)
    # and without the bias the choice is another, on most tokens
    _g, plain, _s = _route(np.zeros(CFG.experts))
    assert (plain != chosen).any(axis=1).mean() > 0.5


@pytest.mark.parametrize("scaling", [1.0, 2.5])
def test_gates_sum_to_the_scaling_factor(scaling):
    gates, chosen, _score = _route(
        np.random.RandomState(1).randn(CFG.experts) * 0.1, scaling)
    assert (chosen.sum(-1) == 2).all() and ((gates > 0) == chosen).all()
    assert np.allclose(gates.sum(-1), scaling, rtol=1e-5)


def test_the_dense_lead_layer_has_no_router_and_routed_has_a_row_a_routed_layer():
    shapes = lm.param_shapes(CFG)
    assert "l0_w1" in shapes and "l0_router" not in shapes \
        and "l0_wgate" not in shapes
    assert all("l%d_router" % l in shapes and "l%d_w1" % l not in shapes
               for l in range(1, 5))
    assert shapes["l0_w1"][0] == (64, 48) and shapes["l1_wgate"][0] \
        == (8, 64, 32)
    assert CFG.routed_layers == (1, 2, 3, 4) and CFG.dense_layers == 1
    (_toks, _lg, routed), = run_paged(CFG, PARAMS, _sequences(1, seed=2))
    # one live lane: each routed layer sends its one token to 2 experts
    assert routed.shape[1:] == (4, 8)
    assert (routed.sum(-1) == 2).all() and routed.max() == 1
    # two dense layers: one row fewer, and no router for layer 1 either
    cfg2 = CFG.replace(dense_layers=2)
    assert cfg2.routed_layers == (2, 3, 4)
    assert "l1_router" not in lm.param_shapes(cfg2)
    (_t, _l, routed2), = run_paged(cfg2, lm.init_params(cfg2, 3, 0.3),
                                   _sequences(1, seed=2))
    assert routed2.shape[1:] == (3, 8)


def test_per_head_qk_norm_is_not_olmoes_whole_width_norm():
    """One weight vector of head_dim for all heads, each head normalised by
    its own root-mean-square: heads of different sizes come out alike, which
    a norm over all heads at once keeps apart."""
    assert lm.param_shapes(CFG)["l1_q_norm"][0] == (16,)
    x = jnp.asarray(np.random.RandomState(0).randn(3, 4, 16)
                    * np.array([1.0, 2.0, 4.0, 8.0])[None, :, None],
                    jnp.float32)
    g = jnp.ones(16)
    per_head = np.asarray(lm._head_norm(x, g, 1e-5))
    assert np.allclose(np.sqrt(np.square(per_head).mean(-1)), 1.0, atol=1e-3)
    whole = np.asarray(olmoe._rmsnorm(x.reshape(3, 64), jnp.ones(64), 1e-5)
                       ).reshape(3, 4, 16)
    assert np.abs(np.sqrt(np.square(whole).mean(-1)) - 1.0).max() > 0.5
    # and the reference holds the block to it: a whole-width norm in the
    # block is a fault the f32 tolerance catches
    kept = lm._head_norm
    lm._head_norm = lambda x, g, eps: olmoe._rmsnorm(
        x.reshape(x.shape[0], -1), jnp.tile(g, x.shape[1]), eps
    ).reshape(x.shape)
    try:
        out = run_paged(CFG, PARAMS, _sequences(1, seed=9))
    finally:
        lm._head_norm = kept
    assert _worst(CFG, out, PARAMS) > 20 * TOL_F32


def test_a_lane_at_position_zero_starts_from_a_zero_window():
    """Whatever the slots hold (here 7.0 everywhere), a sequence's first
    token reads zeros before position 0: the same logits, bit for bit, as
    over clean slots."""
    seqs = _sequences(2, seed=3)
    clean = run_paged(CFG, PARAMS, seqs)
    dirty = run_paged(CFG, PARAMS, seqs, dirty=7.0)
    for (_f, a, _r), (_g, b, _s) in zip(clean, dirty):
        assert np.array_equal(a, b)
    assert _worst(CFG, dirty, PARAMS) < TOL_F32


# -- 3. paged against unpaged, the fed step -------------------------------------


def test_joining_and_leaving_lanes_equal_each_alone():
    """Three sequences of different lengths share a step: lanes fall idle
    one by one, and each sequence's logits are those it has with the other
    lanes idle throughout, in other blocks and (but for the first) another
    slot, bit for bit (the CPU tier's gather path)."""
    seqs = _sequences(3, seed=5)
    together = run_paged(CFG, PARAMS, seqs)
    for i, (fed, lg, _r) in enumerate(together):
        alone = run_paged(CFG, PARAMS, [seq if j == i else ([], 0)
                                        for j, seq in enumerate(seqs)])
        assert fed == alone[i][0] and np.array_equal(lg, alone[i][1])


def test_fed_step_feeds_the_step_before_on_the_device():
    """``make_packed_step`` with this model (the step as the engine
    compiles it): decoded tokens chosen on the device from the step
    before's, every lane's integers the columns of one array, give the
    logits of the step the host feeds."""
    seqs = _sequences(2, seed=7)
    host = run_paged(CFG, PARAMS, seqs)
    device = run_paged(CFG, PARAMS, seqs, feed=True)
    for (_f, a, _r), (_g, b, _s) in zip(host, device):
        assert np.array_equal(a, b)


# -- 4. the engine ---------------------------------------------------------------


def test_step_span_gauge_and_prewarm_event(cache_dir, telemetry_on, tmp_path):
    """Traced, the step's span says how many lanes' windows it moved and how
    many bytes that is, and the routing as means over the four layers that
    route (the dense lead layer counts for nothing); the gauge holds the
    slots' bytes; Granite's names are not used; the prewarm event names the
    attention path."""
    with _flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = _engine(CFG, PARAMS, 16, buckets="2", name="lf")
        try:
            e.prewarm()
            r = e.generate("lf", [1, 2, 3], max_new_tokens=4,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path)
    per_slot = 3 * 2 * 64 * 4
    assert steps and all(s["conv_state_lanes"] == 1
                         and s["conv_state_bytes"] == per_slot
                         and "ssm_state_lanes" not in s for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane: 2 experts hit, 2 assignments, the fullest holds 1, in each
    # of the layers that route; a mean over all five would read 1.6
    assert routed and all(
        (s["moe_experts_hit"], s["moe_assignments"], s["moe_load_max"])
        == (2.0, 2.0, 1.0) for s in routed)
    gauges = _tm.snapshot()["gauges"]
    assert gauges["conv_state_bytes{model=lf}"] == 3 * per_slot
    assert "ssm_state_bytes{model=lf}" not in gauges
    assert gauges["moe_experts_hit{model=lf}"] == 2.0
    assert _tm.counter_total("moe_tokens_routed_total") > 0
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(ev["model"] == "lf" and ev["attention"] == "gather"
                        for ev in warm)


# -- 5. the manager: layers by kind, bytes ---------------------------------------

def test_cache_describes_layers_by_kind():
    kv = dm.cache_config(CFG, BS, 16, state_slots=5)
    assert (kv.layers, kv.heads, kv.head_dim) == (2, 2, 16)
    assert kv.state_layers == 3 and kv.state_slots == 5
    # a window and no state
    assert kv.state_shapes == (((2 * 64,), "f32"),)
    cache = kvc.PagedKVCache(kv)
    carry = cache.carry()
    groups, state = kv.groups(carry)
    assert [len(g) for g in groups] == [2, 2] and len(state) == 1
    assert all(w.shape == (5, 1, 128) for w in state[0]) \
        and len(state[0]) == 3
    assert kvc.slot_bytes(kv) == 3 * 128 * 4
    assert cache.nbytes == cache.kv_nbytes + 5 * 3 * 128 * 4
    assert [dt for _s, dt in dm.cache_config(
        CFG16, BS, 16, state_slots=5).state_shapes] == ["bf16"]
    assert CFG.recurrent_layers == CFG.conv_layers == (0, 2, 3)
    assert CFG.ssm_layers == () and CFG.state_name == "conv_state"


def test_published_sizes_give_the_issues_bytes():
    """At the published widths and the configuration's cut: 4,096 B of K and
    V a token over 2 layers, 65,536 B a block, 57,344 B of windows a
    sequence, 5,177,950,976 parameters."""
    model = fam.load("benchmark", "models", "lfm2_moe_decoder.py")
    with open(fam.config_file("lfm2-24b-a2b-serve.json")) as fp:
        config = json.load(fp)
    cfg = model.decoder_config(config)
    assert (cfg.layers, len(cfg.conv_layers), len(cfg.attn_layers),
            cfg.routed_layers) == (9, 7, 2, tuple(range(1, 9)))
    kv = dm.cache_config(cfg, 16, 2048, state_slots=33)
    assert kvc.block_bytes(kv) == 65536
    assert kvc.slot_bytes(kv) == 57344
    assert kvc.state_bytes(kv) == 33 * 57344
    shapes = lm.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s, _k in shapes.values()) == 5177950976
    per = lambda l: sum(int(np.prod(s)) for n, (s, _k) in shapes.items()
                        if n.startswith("l%d_" % l))
    # the dense lead layer, a routed attention layer, a routed conv layer
    # (two norms of 2048 each, and the attention's two of 64)
    assert per(0) == 16783360 + 72351744 + 4096
    assert per(1) == 10485760 + 128 + 603979776 + 131072 + 64 + 4096
    assert per(2) == 16783360 + 603979776 + 131072 + 64 + 4096


def test_config_refuses_what_no_block_computes():
    with pytest.raises(ValueError, match="layers are attention|conv"):
        CFG.replace(layer_types=("mamba",) + KINDS[1:])
    with pytest.raises(ValueError, match="layers are"):
        dm.DecoderConfig(vocab=50, layers=2, heads=4, head_dim=16,
                         layer_types=("conv", "attention"), conv_taps=3)
    with pytest.raises(ValueError, match="conv_taps"):
        CFG.replace(conv_taps=1)
    with pytest.raises(ValueError, match="dense_layers"):
        CFG.replace(dense_layers=6)
    with pytest.raises(ValueError, match="dense_layers"):
        CFG.replace(dense_ffn=0)
    with pytest.raises(ValueError, match="experts_per_token"):
        CFG.replace(experts_per_token=9)
    with pytest.raises(ValueError, match="dense_layers"):
        dm.DecoderConfig(arch="olmoe", vocab=50, layers=2, heads=2,
                         head_dim=16, experts=4, experts_per_token=2,
                         dense_layers=1, dense_ffn=8)


