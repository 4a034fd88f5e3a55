"""What is the Nemotron-H decoder block's own (paddle_tpu/models/
nemotron_h.py: blocks of ONE sublayer each, Mamba-2 mixers with B and C in
groups, grouped-query attention with no position encoding under a stream
narrower than its query heads, and relu^2 experts of two matrices beside a
shared one, in layers that keep nothing in the cache): logits at every
position against its plain reference (benchmark/reference/nemotron_h_ref.py,
the file the benchmark uses), prefill then decode through the paged step and
the cache manager; the reference told otherwise; the share (eight shares'
routed parts and the shared expert once are the uncut layer); what the cache
manager gives the published 52-layer pattern; server and client; the step's
span and prewarm event; the kernels under the interpreter.  The contract it
shares with every family is tests/test_decoder_families.py's, over its row
of tests/decoder_families.py, whose tiny sizes these are: 6 layers ``M E M *
M E``, hidden 48 under 4 query heads over 2 KV heads of 16, 8 state-space
heads of 8 in 2 groups with state 16, 16 experts of width 24 (no multiple of
128) with 3 a token, a shared one of width 40, vocab 97."""

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
from paddle_tpu.models import lfm2_moe as lf
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import moe_experts as me
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.pallas_kernels import ssm_update as su
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

CONFIG_FILE = fam.config_file("nemotron-3-nano-30b-a3b-serve.json")
ref = fam.load("benchmark", "reference", "nemotron_h_ref.py")
model = fam.load("benchmark", "models", "nemotron_h_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["nemotron_h"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
_sequences = fam.sequences
_teacher_forced = fam.teacher_forced
_engine = fam.engine
_flags = fam.flags
_alone = fam.alone
_chunked = fam.chunked


def run_paged(cfg, params, seqs, **kw):
    """``fam.run_paged``, every live lane's token counted once by each
    experts layer's router."""
    out, routed = fam.run_paged(cfg, params, seqs, **kw)
    rows = len(cfg.routed_layers)
    assert all(r.shape == (rows, cfg.experts)
               and int(r.sum()) % (rows * cfg.experts_per_token) == 0
               for r in routed)
    assert sum(int(r.sum()) for r in routed) == rows \
        * cfg.experts_per_token * sum(len(toks) for toks, _lg in out)
    return out


# normal(0, 0.3) and a bias of 0.05: at this hidden size the family's 0.02
# leaves the layers' share of the stream, and so a fault's mark, small
MAXB = CFG.max_seq // BS


def ref_config(cfg, **changed):
    """The source's keys, as the reference reads them."""
    letters = {v: k for k, v in model.LAYER_KINDS.items()}
    return dict({
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.layers,
        "hybrid_override_pattern": "".join(letters[k]
                                           for k in cfg.layer_types),
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.ssm_conv, "moe_intermediate_size": cfg.ffn,
        "moe_shared_expert_intermediate_size": cfg.shared_ffn,
        "n_routed_experts": cfg.experts_held,
        "n_routed_experts_published": cfg.experts,
        "first_expert": cfg.expert_first, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling, "norm_topk_prob": True,
        "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
        "use_bias": False, "use_conv_bias": True,
        "norm_eps": cfg.norm_eps}, **changed)


# float32 rounding over six layers (measured 3e-6 here); a fault in
# structure is 1e-1 or more (the broken-reference controls below)
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
    return run_paged(CFG, PARAMS, _sequences(3))


def test_f32_logits_equal_the_reference_at_every_position():
    """Prefill token by token, then decode, three lanes of different
    lengths through the paged step and the cache manager: every position's
    logits are the reference's whole-sequence pass."""
    out = _f32_out()
    assert _worst(CFG, out, PARAMS) < TOL_F32
    toks, _lg = out[0]
    assert len(set(toks[-8:])) > 2


# a reference told otherwise: each is a fault the tolerance has to see
BREAKS = {
    "every_head_reads_group_0": dict(one_group=True),
    "relu_for_relu_squared": dict(square=False),
    "routed_scaling_dropped": dict(scaled=False),
    "shared_expert_dropped": dict(shared=False),
    "selection_bias_ignored": dict(use_bias=False),
}
CONFIG_BREAKS = {
    "fewer_experts_a_token": dict(num_experts_per_tok=2),
    "another_scaling_factor": dict(routed_scaling_factor=1.0),
    "attention_where_a_mixer_is": dict(hybrid_override_pattern="MEM*EE"),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) \
        > 100 * TOL_F32, how


@pytest.mark.parametrize("how", sorted(CONFIG_BREAKS))
def test_f32_tolerance_catches_a_reference_told_otherwise(how):
    changed = CONFIG_BREAKS[how]
    params = PARAMS
    if "hybrid_override_pattern" in changed:
        # layer 4 an experts layer on both sides' weights, a mixer here
        other = CFG.replace(layer_types=tuple(
            model.LAYER_KINDS[k] for k in changed["hybrid_override_pattern"]))
        params = dict(nh.init_params(other, seed=3, std=0.3, bias_std=0.05),
                      **{k: v for k, v in PARAMS.items()
                         if not k.startswith("l4_")})
        params = {k: v for k, v in params.items()
                  if k in nh.param_shapes(other)}
    assert _worst(CFG, _f32_out(), params, **changed) > 100 * TOL_F32, how


def test_the_state_is_remembered_and_a_slot_not_reset_is_seen():
    """With Mamba-2's own start a state carried over from another sequence
    moves every later logit: the reference on a sequence with five foreign
    tokens before it differs from the reference on the sequence alone."""
    toks, _lg = _f32_out()[0]
    only_mamba = CFG.replace(layer_types=("mamba",) * 6)
    params = nh.init_params(only_mamba, seed=3, std=0.3)
    dirty = _ref(only_mamba, params, [7, 7, 7, 7, 7] + toks)[5:]
    clean = _ref(only_mamba, params, toks)
    assert np.abs(dirty - clean)[6:].max() > 100 * TOL_F32


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """bfloat16 as served against the float32 reference on the same
    weights, logits of standard deviation 2: root-mean-square error
    0.013-0.106 on three seeds (an expert swapped here and there included:
    the largest single error 2.1); with the weights rounded to 8 bits (e4m3)
    0.76-0.88.  The limits stand between."""
    out = run_paged(CFG16, PARAMS16, _sequences(3))
    rms = lambda got: float(np.sqrt(np.mean([np.mean(np.square(
        lg - _ref(CFG16, PARAMS16, toks))) for toks, lg in got])))
    assert rms(out) < 0.25, rms(out)
    fp8 = fam.fp8_rounded(PARAMS16)
    rounded = [(toks, _teacher_forced(CFG16, fp8, toks)) for toks, _ in out]
    assert rms(rounded) > 0.45, rms(rounded)


# -- 2. paged against unpaged ----------------------------------------------------


# -- 3. the share ----------------------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One experts layer, 16 experts, 3 a token: eight shares of 2 experts
    each route over all 16 and compute their own experts' part; their sum
    and the shared expert's output, counted once, equal the uncut
    reference's layer (the reference asked for all 16).  No share alone
    does."""
    cfg = CFG.replace(layers=1, layer_types=("experts",))
    fam.check_shares_add_up(
        cfg, nh.init_params(cfg, seed=11, std=0.3, bias_std=0.05), nh, ref,
        ref_config, ("experts_up", "experts_down"), (2e-5, 5e-5))


def test_a_share_through_the_block_equals_the_reference_given_the_share():
    cfg = CFG.replace(experts_held=4, expert_first=8)
    params = nh.init_params(cfg, seed=3, std=0.3, bias_std=0.05)
    assert params["l1_experts_up"].shape == (4, 24, 48)
    assert params["l1_experts_down"].shape == (4, 24, 48)
    assert params["l1_router"].shape == (48, 16)
    assert params["l3_wq"].shape == (48, 64)
    out = run_paged(cfg, params, _sequences(2, seed=1))
    assert _worst(cfg, out, params) < TOL_F32
    # and not the reference given other experts
    assert _worst(cfg, out, params, first_expert=0) > 100 * TOL_F32


def test_the_served_bias_moves_a_third_of_the_choices_and_no_experts_load():
    """At the published router (2,688 x 128, 6 a token) behind the
    pre-norm (entries of root-mean-square 1), the configuration's
    ``expert_bias_std`` re-decides the choice of experts on more than a
    quarter of tokens (so a block that ignores it is seen), and the 16 held
    experts a 32-lane step hits stay within 0.4 of an even router's 12.56
    from seed to seed (so a run's time does not hang on its seed: PR 36's
    refusal); a token's sixth and seventh best lie thousandths apart."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    std = config["expert_bias_std"]
    assert std == nh.BIAS_STD
    hits, differ, margins = [], [], []
    for seed in range(4):
        rng = np.random.RandomState(seed)
        per_layer = []
        for _layer in range(3):
            router = jnp.asarray(rng.randn(2688, 128) * 0.02, jnp.float32)
            bias = jnp.asarray(rng.randn(128) * std, jnp.float32)
            x = rng.randn(32 * 16, 2688)
            x = jnp.asarray(x / np.sqrt((x * x).mean(1, keepdims=True)),
                            jnp.float32)
            _g, chosen = lf._route(x, router, bias, 6, 2.5, nh.GATE_EPS)
            _g, plain = lf._route(x, router, jnp.zeros(128), 6, 2.5,
                                  nh.GATE_EPS)
            differ.append(float((np.asarray(chosen) != np.asarray(plain))
                                .any(axis=1).mean()))
            held = np.asarray(chosen)[:, :16].reshape(16, 32, 16).sum(axis=1)
            per_layer.append(float((held > 0).sum(axis=1).mean()))
            score = np.sort(np.asarray(jax.nn.sigmoid(x @ router)), axis=1)
            margins.append(float(np.median(score[:, -6] - score[:, -7])))
        hits.append(float(np.mean(per_layer)))
    even = 16 * (1 - (1 - 6 / 128) ** 32)
    assert abs(even - 12.56) < 0.01
    assert min(differ) > 0.25, differ
    assert max(abs(h - even) for h in hits) < 0.45, hits
    assert 0.004 < np.mean(margins) < 0.012, margins


# -- 4. the manager: layers by kind ----------------------------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_the_52_layer_pattern_gets_pools_slots_and_nothing():
    """The published pattern through the cache manager: K and V pools for
    the 6 ``*`` layers, a window and a state for the 23 ``M`` layers, and
    for the 23 ``E`` layers nothing at all."""
    config, cfg = _published()
    pattern = config["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("*"),
            pattern.count("E")) == (52, 23, 6, 23)
    assert len(cfg.attn_layers) == 6 and len(cfg.ssm_layers) == 23
    assert cfg.recurrent_layers == cfg.ssm_layers
    assert cfg.routed_layers == cfg._of_kind("experts") \
        and len(cfg.routed_layers) == 23 and cfg.routed_layers[:3] == (1, 3, 6)
    assert not cfg.window_layers and cfg.state_name == "ssm_state"
    assert (cfg.hidden, cfg.heads * cfg.head_dim, cfg.ssm_inner,
            cfg.ssm_groups) == (2688, 4096, 4096, 8)
    kv = dm.cache_config(cfg, 16, 2048, state_slots=33)
    assert (kv.layers, kv.state_layers, kv.window_layers) == (6, 23, 0)
    assert (kv.heads, kv.head_dim) == (2, 128)
    assert kv.state_shapes == (((3 * 6144,), "bf16"), ((128, 4096), "f32"))
    assert kvc.slot_bytes(kv) == 49082368
    assert kvc.state_bytes(kv) == 33 * 49082368            # 1.62e9 B
    assert kvc.block_bytes(kv) == 98304
    assert kvc.block_bytes(kv) * kv.num_blocks == 201326592  # 0.20e9 B
    # the carry: K and V a pool each of 6 layers, then 23 windows and states
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(kv).carry())
    (k, v), (windows, states) = kv.groups(carry)
    assert len(carry) == 2 * 6 + 2 * 23
    assert len(k) == len(v) == 6 and len(windows) == len(states) == 23
    assert k[0].shape == (2048, 16, 256) and states[0].shape == (33, 128, 4096)
    pool_of = dm._pool_index(cfg)
    assert not set(pool_of) & set(cfg.routed_layers)
    assert sorted(pool_of) == sorted(cfg.attn_layers + cfg.ssm_layers)


def test_published_sizes_give_the_issues_bytes():
    config, cfg = _published()
    shapes = nh.param_shapes(cfg)
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _k) in shapes.items()
                            if n.startswith(pre))
    assert count("l0_") == 38744896                          # a mamba block
    assert count("l5_") == 23399040                          # attention
    assert count("l1_") == 16 * 9977856 + 19955712 + 2688 * 128 + 128 + 2688
    total = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert total == 5258420544                               # 10.52e9 B
    whole = model.param_shapes(dict(config, n_routed_experts=128,
                                    vocab_size=131072))
    assert sum(int(np.prod(s)) for s, _k in whole.values()) == 31577940288
    assert shapes["l1_experts_up"][0] == shapes["l1_experts_down"][0] \
        == (16, 1856, 2688)
    assert me.f_rows(2688, 1856, jnp.bfloat16) in (464, 928)
    assert me.f_chunk(2688, 1856, 2) == 0       # what the old form made of it


def test_config_refuses_what_no_block_computes():
    base = dict(vocab=31, layers=2, heads=4, head_dim=8, kv_heads=2,
                experts=8, experts_per_token=2, ffn=24, shared_ffn=16)
    mamba = dict(ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_conv=4)
    with pytest.raises(ValueError, match="ssm_groups 3 must divide"):
        dm.DecoderConfig(arch="nemotron_h", layer_types=["mamba", "experts"],
                         ssm_groups=3, **dict(base, **mamba))
    with pytest.raises(ValueError, match="the granite_hybrid block's layers"):
        dm.DecoderConfig(arch="granite_hybrid",
                         layer_types=["mamba", "experts"],
                         **dict(mamba, vocab=31, layers=2, heads=4,
                                head_dim=8))
    with pytest.raises(ValueError, match="exaone_moe\\|nemotron_h\\|"
                       "kimi_linear\\|dots_vlm\\|glm_dsa\\|longcat_flash\\|"
                       "solar_open2\\|xing4 blocks may hold experts"):
        dm.DecoderConfig(arch="lfm2_moe", layer_types=["attention"] * 2,
                         experts_held=4, **base)
    with pytest.raises(ValueError, match="exaone_moe\\|nemotron_h\\|"
                       "kimi_linear\\|dots_vlm\\|smallthinker\\|glm_dsa\\|"
                       "longcat_flash\\|solar_open2\\|xing4 may say"):
        dm.DecoderConfig(arch="lfm2_moe", layer_types=["attention"] * 2,
                         hidden_size=24, **base)
    cfg = dm.DecoderConfig(
        arch="nemotron_h", layer_types=["experts", "mamba"], ssm_groups=2,
        experts_held=4, expert_first=4, hidden_size=24,
        **dict(base, **mamba))
    assert cfg.held_experts == slice(4, 8) and cfg.routed_layers == (0,)
    assert cfg.recurrent_layers == (1,) and cfg.attn_layers == ()
    assert dm._conv_window(cfg) == (4, 32 + 2 * 2 * 8)
    # Granite's stay one group wide
    assert dm.DecoderConfig(arch="granite_hybrid", layer_types=["mamba"] * 2,
                            **dict(mamba, vocab=31, layers=2, heads=4,
                                   head_dim=8)).ssm_groups == 1


# -- 5. the engine, the server, the client ---------------------------------------


def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = _engine(CFG, PARAMS, 40, buckets="2", name="nm")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("nm", prompt, max_new_tokens=n,
                                    deadline_ms=60000.0)
            assert reply.status == "ok", reply.error
            assert np.array_equal(
                np.asarray(reply.outputs["tokens"]).reshape(-1),
                _alone(CFG, PARAMS, prompt, n))
    finally:
        server.shutdown()
        e.stop()


def test_step_span_counters_gauges_and_prewarm_event(cache_dir, telemetry_on,
                                                     tmp_path):
    """Traced, the step's span says how many lanes' state it moved, what a
    share's router assigned here and elsewhere (means over the experts
    layers), and the blocks its attention fetched with their size; the
    prewarm event names the three paths and the layers by kind, those that
    keep nothing among them."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = nh.init_params(cfg, seed=3, std=0.3, bias_std=0.05)
    with _flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = _engine(cfg, params, 24, buckets="2", name="nm")
        try:
            e.prewarm()
            r = e.generate("nm", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "nm")
    assert len(steps) >= 20
    per_slot = 3 * (3 * (64 + 2 * 2 * 16) * 4 + 16 * 64 * 4)
    assert all(s["ssm_state_lanes"] == 1 and s["ssm_state_bytes"] == per_slot
               and s["kv_block_size"] == BS and s["kv_blocks_read"] == 2 * MAXB
               for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 3 experts a token over 16, 4 of them held here
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 3.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        and s["moe_experts_hit"] == s["moe_local_assignments"]
        for s in routed)
    assert 0 < sum(s["moe_local_assignments"] for s in routed) \
        < 3 * len(routed)
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    assert gauges["ssm_state_bytes{model=nm}"] == 3 * per_slot
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "nm" and ev["attention"] == "gather"
        and ev["experts"] == "einsum" and ev["state_update"] == "gather"
        and "state_update_columns" not in ev
        and ev["layers"] == {"attention": 1, "experts": 2, "mamba": 3}
        for ev in warm)


# -- 6. the kernels, under the interpreter ---------------------------------------


def _state_args(rng, slots_n, n, inner, groups, lanes):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pool = f(slots_n, n, inner)
    slots = jnp.asarray(rng.permutation(slots_n)[:lanes], jnp.int32)
    fresh = jnp.asarray([i % 3 == 1 for i in range(lanes)])
    decay = jnp.asarray(rng.uniform(0.2, 1.0, (lanes, inner)), jnp.float32)
    return pool, (slots, fresh, decay, f(lanes, inner), f(lanes, groups, n),
                  f(lanes, groups, n))


@pytest.mark.parametrize("groups,columns", [(8, 512), (8, 128), (2, 512),
                                            (4, 1024), (8, None), (2, None),
                                            (1, None), (8, 256)])
def test_state_update_kernel_with_groups_equals_advance_by_group(
        interpreted, monkeypatch, groups, columns):
    """The kernel with B and C by group (a transfer spanning four groups,
    one, half of one; the whole slot and every group, ``columns`` None; two
    groups a chunk) against ``advance`` applied a group at a time with that
    group's pair alone, and against gather, update, scatter bit for bit."""
    if columns:
        _chunked(monkeypatch, 16, columns)
    rng = np.random.default_rng(groups + (columns or 0))
    pool, args = _state_args(rng, 6, 16, 1024, groups, 4)
    slots, fresh, decay, dx, b, c = args
    assert su.transfer_columns(pool.shape, groups) == (columns or 1024)
    assert all(ok for _r, ok in su.ssm_update_checks(pool.shape, pool.dtype,
                                                     4, groups))
    # a jit of its own: a cached trace would decide nothing
    got_pool, got_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["ssm_update"]
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    assert np.array_equal(np.asarray(got_y), np.asarray(want_y))
    assert np.array_equal(np.asarray(got_pool), np.asarray(want_pool))
    # a group at a time: its columns of the state, its pair, one group
    per = 1024 // groups
    start = su.started(fresh, jnp.take(pool, slots, axis=0))
    for g in range(groups):
        at = slice(g * per, (g + 1) * per)
        state, y = su.advance(start[:, :, at], decay[:, at], dx[:, at],
                              b[:, g:g + 1], c[:, g:g + 1])
        np.testing.assert_allclose(np.asarray(got_y)[:, at], np.asarray(y),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got_pool)[np.asarray(slots)][:, :, at],
            np.asarray(state), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", fam.COPY_ORDERS)
@pytest.mark.parametrize("lanes,columns", [(5, None), (9, None), (3, 256),
                                           (5, 512)])
def test_state_update_kernel_with_groups_keeps_its_turns(monkeypatch, lanes,
                                                         columns, order):
    """B and C in 8 groups under ``in_turns``' order: a last batch of one,
    three batches, a slot in four chunks of two groups and in two of four,
    under both of the interpreter's models of a copy, bit for bit gather,
    update, scatter and the plainest order, one unit at a time."""
    if columns:
        _chunked(monkeypatch, 16, columns)
    rng = np.random.default_rng(lanes + (columns or 0))
    pool, args = _state_args(rng, 12, 16, 1024, 8, lanes)
    assert su.transfer_columns(pool.shape, 8) == (columns or 1024)
    got_pool, got_y = fam.in_turns_and_plainly(monkeypatch, su, order, pool,
                                               args, list(range(lanes)))
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-6, atol=2e-6)
    assert np.array_equal(np.asarray(got_pool), np.asarray(want_pool))


@pytest.mark.parametrize("groups,n,inner,columns", [
    (8, 128, 4096, 2048), (2, 128, 4096, 512), (8, 16, 1024, 128),
    (4, 8, 512, 256)], ids=["nemotron_h", "two_groups", "small", "narrow"])
def test_a_whole_slot_spans_every_group(interpreted, monkeypatch, groups, n,
                                        inner, columns):
    """One transfer holds all the groups' columns (Nemotron-H's eight of
    512): against gather, update and scatter, and bit for bit the same
    slots moved in chunks of ``columns`` (PR 41's grid step held 2048),
    with lanes out of order, a fresh one and two idle ones on slot 0."""
    rng = np.random.default_rng(groups + n)
    pool, args = _state_args(rng, 8, n, inner, groups, 6)
    slots = jnp.asarray([5, 0, 7, 2, 0, 3], jnp.int32)
    args = (slots,) + args[1:]
    assert su.transfer_columns(pool.shape, groups) == inner
    got_pool, got_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["ssm_update"]
    want_pool, want_y = jax.jit(su.state_update_reference)(pool, *args)
    live, named = [0, 2, 3, 5], [2, 3, 5, 7]
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=2e-6,
                               atol=2e-6)
    assert np.array_equal(np.asarray(got_pool)[named],
                          np.asarray(want_pool)[named])
    assert np.array_equal(np.asarray(got_pool)[[1, 4, 6]],
                          np.asarray(pool)[[1, 4, 6]])
    _chunked(monkeypatch, n, columns)
    assert su.transfer_columns(pool.shape, groups) == columns
    chunk_pool, chunk_y = jax.jit(lambda *a: su.state_update(*a))(pool, *args)
    assert np.array_equal(np.asarray(chunk_y)[live], np.asarray(got_y)[live])
    assert np.array_equal(np.asarray(chunk_pool)[1:],
                          np.asarray(got_pool)[1:])


def test_one_group_is_bit_identical_to_the_update_as_it_was(interpreted,
                                                            monkeypatch):
    """Granite's G = 1 through the grouped ``advance`` and the kernel: the
    bits of the one-group expressions they replaced (``decay * S + outer(b,
    dx)``, ``c . S`` with ``b``, ``c`` [B, N])."""
    _chunked(monkeypatch, 16, 128)
    rng = np.random.default_rng(0)
    pool, args = _state_args(rng, 6, 16, 256, 1, 4)
    slots, fresh, decay, dx, b, c = args

    @jax.jit
    def as_it_was(pool):
        state = su.started(fresh, jnp.take(pool, slots, axis=0, mode="clip"))
        state = decay[:, None, :] * state \
            + b[:, 0, :, None] * dx[:, None, :]
        return pool.at[slots].set(state), \
            jnp.sum(state * c[:, 0, :, None], axis=1)

    old_pool, old_y = as_it_was(pool)
    for fn in (su.state_update_reference, su.state_update):
        new_pool, new_y = jax.jit(lambda *a, _fn=fn: _fn(*a))(pool, *args)
        assert np.array_equal(np.asarray(new_y), np.asarray(old_y))
        assert np.array_equal(np.asarray(new_pool), np.asarray(old_pool))
    assert adoption.active_kernels() == ["ssm_update"]


def test_groups_the_kernel_cannot_tile_fall_back_counted(interpreted):
    checks = lambda g, inner=4096: dict(su.ssm_update_checks(
        (33, 128, inner), jnp.float32, 32, g))
    assert all(checks(8).values()) and all(checks(1).values())
    assert not checks(3)["groups"]                 # 4096 / 3
    assert not checks(64)["groups"]                # 64 columns a group
    assert not checks(16, 3072)["groups"]          # 192 columns a group
    # 384 columns a group tiled no 2048-column grid step (PR 41); a whole
    # slot spans all eight
    assert all(checks(8, 3072).values())
    assert su.transfer_columns((33, 128, 3072), 8) == 3072
    assert su.update_path((33, 128, 4096), jnp.float32, 32, 8) == "pallas"
    assert su.update_path((33, 128, 4096), jnp.float32, 32, 64) == "gather"


HITS = {
    "some": lambda rng, b, e, k: np.stack(
        [rng.permutation(e)[:k] for _ in range(b)]),
    "one_expert": lambda rng, b, e, k: np.full((b, 1), 5),
    "all": lambda rng, b, e, k: np.tile(np.arange(e), (b, 1)),
}


def _gates(rng, choice, e):
    gates = np.zeros((choice.shape[0], e), np.float32)
    for row, mine in zip(gates, choice):
        row[mine] = rng.uniform(0.1, 1.0, len(mine))
    return jnp.asarray(gates)


@pytest.mark.parametrize("dtype,ffn,tol", [(jnp.float32, 24, 2e-5),
                                           (jnp.bfloat16, 48, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hit", sorted(HITS) + ["nothing"])
def test_relu2_kernel_equals_the_einsums(interpreted, dtype, ffn, tol, hit):
    """The two-matrix kernel against the einsums at a width that is no
    multiple of 128, cut in chunks of one sublane tile: some experts hit,
    one, all, none; two lanes idle (their gates count as zeros, their rows
    are zeros)."""
    rng = np.random.default_rng(7)
    b, e, hidden = 6, 8, 128
    up, down = (jnp.asarray(rng.standard_normal((e, ffn, hidden)) * 0.2,
                            dtype) for _ in range(2))
    x = jnp.asarray(rng.standard_normal((b, hidden)), jnp.float32)
    live = jnp.asarray([True, True, False, True, False, True])
    gates = jnp.zeros((b, e), jnp.float32) if hit == "nothing" \
        else _gates(rng, HITS[hit](rng, b, e, 3), e)
    assert me.experts_path(b, up.shape, dtype, matrices=2) == "pallas"
    assert me.experts_path(b, up.shape, dtype) == "einsum"   # F % 128
    tile = me._SUBLANES[jnp.dtype(dtype).name]
    assert me.f_rows(hidden, ffn, dtype) == ffn
    want = np.asarray(me.relu2_reference(
        x, jnp.where(live[:, None], gates, 0.0), up, down))
    for fr in (None, tile):
        got = np.asarray(me._relu2_pallas(x, gates, live, up, down, fr=fr))
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        assert not got[2].any() and not got[4].any()
    if hit == "nothing":
        assert not got.any()
    got = np.asarray(jax.jit(lambda *a: me.relu2_experts(*a))(
        x, gates, live, up, down))
    assert adoption.active_kernels() == ["moe_experts"]
    np.testing.assert_allclose(got[np.asarray(live)],
                               want[np.asarray(live)], atol=tol, rtol=tol)


def test_relu2_reference_is_the_plain_sum_over_experts():
    rng = np.random.default_rng(1)
    up, down = (jnp.asarray(rng.standard_normal((4, 24, 16)), jnp.float32)
                for _ in range(2))
    x = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
    gates = _gates(rng, HITS["some"](rng, 3, 4, 2), 4)
    want = sum(np.asarray(gates)[:, i:i + 1]
               * (np.square(np.maximum(np.asarray(x) @ np.asarray(up[i]).T,
                                       0)) @ np.asarray(down[i]))
               for i in range(4))
    np.testing.assert_allclose(np.asarray(me.relu2_reference(x, gates, up,
                                                             down)),
                               want, rtol=1e-4, atol=1e-4)


def test_the_paged_step_on_three_kernels_gives_the_jnp_steps_tokens(
        interpreted):
    """The whole step with the attention, state-update and expert kernels
    interpreted (query heads of 128 over a narrower stream, compact; two
    groups of 128 columns; experts of width 24): the tokens and logits of
    the jnp step."""
    cfg = dm.DecoderConfig(
        arch="nemotron_h", vocab=61, layers=4, heads=4, kv_heads=2,
        head_dim=128, hidden_size=128, max_seq=64,
        layer_types=("mamba", "experts", "attention", "mamba"), ssm_heads=8,
        ssm_head_dim=32, ssm_state=16, ssm_conv=4, ssm_groups=2, ffn=24,
        shared_ffn=40, experts=16, experts_held=8, experts_per_token=3,
        routed_scaling=2.5)
    params = nh.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    kv = dm.cache_config(cfg, 16, 12, state_slots=3)
    assert pa._compact(4, 2, 128)
    assert dm.attention_path(cfg, kv, 2) == "pallas"
    assert dm.state_update_path(cfg, kv, 2) == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"

    def run():
        # one lane of a two-lane step, 20 tokens by the step's own argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernels = run()
    assert set(adoption.active_kernels()) == {"paged_attention", "ssm_update",
                                              "moe_experts"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.state_update_path(cfg, kv, 2) == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=1e-4, rtol=1e-4)
