"""What is the Kimi-Linear decoder block's own (paddle_tpu/models/
kimi_linear.py: Kimi Delta Attention layers whose matrix state lives in
slots, multi-head latent attention with no position encoding served absorbed
over a latent paged cache, and sigmoid-routed experts beside a shared one
behind a dense lead layer): logits at every position against its plain
reference (benchmark/reference/kimi_linear_ref.py, the file the benchmark
uses, which computes latent attention *expanded*), prefill then decode
through the paged step and the cache manager; the reference told otherwise;
the share; what the cache manager gives the published 27-layer pattern;
server and client; the step's span and prewarm event; the two kernels under
the interpreter.  The contract it shares with every family is
tests/test_decoder_families.py's, over its row of tests/decoder_families.py,
whose tiny sizes these are: 6 layers ``kda kda kda latent kda latent``,
hidden 48 under 4 latent-attention heads of 16 (+ 8 shared key values) over
24 latent values, 4 KDA heads of 8, a dense lead of width 64, 16 experts of
width 24 with 3 a token, a shared one of width 24, vocab 97."""

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
from paddle_tpu.models import kimi_linear as kl
from paddle_tpu.models import lfm2_moe as lf
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import kda_update as ku
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.pallas_kernels import ssm_update as su
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

CONFIG_FILE = fam.config_file("kimi-linear-48b-a3b-serve.json")
ref = fam.load("benchmark", "reference", "kimi_linear_ref.py")
model = fam.load("benchmark", "models", "kimi_linear_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["kimi_linear"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(kl.init_params, std=0.3, bias_std=0.05)


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
        "num_hidden_layers": cfg.layers,
        "linear_attn_config": {
            "kda_layers": [l + 1 for l in cfg.kda_layers],
            "full_attn_layers": [l + 1 for l in cfg.latent_layers],
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv},
        "kv_lora_rank": cfg.latent_rank, "qk_nope_head_dim": cfg.head_dim,
        "qk_rope_head_dim": cfg.latent_rope, "v_head_dim": cfg.head_dim,
        "q_lora_rank": None, "mla_use_nope": True,
        "first_k_dense_replace": cfg.dense_layers,
        "intermediate_size": cfg.dense_ffn,
        "moe_intermediate_size": cfg.ffn, "num_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first, "num_shared_experts": 1,
        "num_experts_per_token": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0, "rms_norm_eps": cfg.norm_eps},
        **changed)


# float32 rounding over six layers (measured 3e-5 here); a fault in
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
    """Prefill token by token, then decode, three lanes of different lengths
    through the paged step and the cache manager: every position's logits
    are the reference's whole-sequence pass, the absorbed latent attention
    against the expanded one."""
    out = _f32_out()
    assert _worst(CFG, out, PARAMS) < TOL_F32
    toks, _lg = out[0]
    assert len(set(toks[-8:])) > 2


# a reference told otherwise: each is a fault the tolerance has to see
BREAKS = {
    "decay_averaged_over_a_heads_channels": dict(mean_decay=True),
    "delta_correction_dropped": dict(delta=False),
    "q_and_k_not_normalised": dict(qk_norm=False),
    "output_gate_dropped": dict(gate=False),
    "kv_a_layernorm_dropped": dict(kv_norm=False),
    "k_pe_left_out_of_the_score": dict(k_pe=False),
    "the_scale_of_the_nope_part_alone": dict(scale=16 ** -0.5),
    "routed_scaling_dropped": dict(scaled=False),
    "shared_expert_dropped": dict(shared=False),
    "selection_bias_ignored": dict(use_bias=False),
}
CONFIG_BREAKS = {
    "fewer_experts_a_token": dict(num_experts_per_token=2),
    "another_scaling_factor": dict(routed_scaling_factor=1.0),
    # (served from a share of four from expert 8 on, and told otherwise)
    "other_experts_held": dict(first_expert=0),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) \
        > 100 * TOL_F32, how


@pytest.mark.parametrize("how", sorted(CONFIG_BREAKS))
def test_f32_tolerance_catches_a_reference_told_otherwise(how):
    changed = CONFIG_BREAKS[how]
    cfg, params = CFG, PARAMS
    if how == "other_experts_held":
        cfg = CFG.replace(experts_held=4, expert_first=8)
        params = init(cfg, seed=3)
    out = _f32_out() if cfg is CFG else run_paged(cfg, params,
                                                  fam.sequences(2, seed=1))
    assert _worst(cfg, out, params) < TOL_F32
    assert _worst(cfg, out, params, **changed) > 100 * TOL_F32, how


def test_the_state_is_remembered_and_a_slot_not_reset_is_seen():
    """With the decay's own start a state carried over from another
    sequence moves every later logit: the reference on a sequence with five
    foreign tokens before it differs from the reference on the sequence
    alone; and through the paged step a sequence that starts in a slot full
    of another's state reads the same as in a clean one."""
    toks, _lg = _f32_out()[0]
    only_kda = CFG.replace(layer_types=("kda",) * 6)
    params = init(only_kda, seed=3)
    dirty = _ref(only_kda, params, [7, 7, 7, 7, 7] + toks)[5:]
    clean = _ref(only_kda, params, toks)
    assert np.abs(dirty - clean)[6:].max() > 100 * TOL_F32
    seqs = fam.sequences(2, seed=4)
    reused, _r = fam.run_paged(CFG, PARAMS, seqs, dirty=3.0)
    fresh, _r = fam.run_paged(CFG, PARAMS, seqs)
    for (a, la), (b, lb) in zip(reused, fresh):
        assert a == b and np.array_equal(la, lb)


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """bfloat16 as served against the float32 reference on the same
    weights (normal(0, 0.3) here: logits of standard deviation 9): the
    root-mean-square logit error of the served path, 0.61, lies under the
    limit and that of weights rounded to 8 bits (e4m3), 1.66, over it."""
    out = run_paged(CFG16, PARAMS16, fam.sequences(3))
    rms = lambda got: float(np.sqrt(np.mean([np.mean(np.square(
        lg - _ref(CFG16, PARAMS16, toks))) for toks, lg in got])))
    served = rms(out)
    fp8 = fam.fp8_rounded(PARAMS16)
    rounded = rms([(toks, fam.teacher_forced(CFG16, fp8, toks))
                   for toks, _ in out])
    assert served < 0.85 < 1.2 < rounded, (served, rounded)


def test_absorbed_latent_attention_equals_the_expanded_form():
    """One latent layer alone over a sequence: the block's absorbed mixer
    through an ``attend`` that keeps every row (``masked_latent``: the
    query's latent part against the rows, the value their first ``rank``
    columns) against the reference's expanded one, which makes every head's
    keys and values from ``c``; and what the cache would hold is ``[c |
    k_pe]``."""
    cfg = CFG.replace(layers=1, layer_types=("latent",), dense_layers=0)
    params = {k[3:]: jnp.asarray(v) for k, v in init(cfg, seed=5).items()
              if k.startswith("l0_")}
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(9, cfg.hidden), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, rows = ref._mla(ref_config(cfg), params, h)
        got = []
        kept = jnp.zeros((1, 16, cfg.latent_width), jnp.float32)
        for t in range(9):
            def attend(l, q, row, v, _t=t):
                nonlocal kept
                assert v is None and q.shape == (1, 4, cfg.latent_width)
                kept = kept.at[0, _t].set(row[0])
                return pa.masked_latent(q, kept, jnp.asarray([_t + 1]),
                                        cfg.latent_scale, cfg.latent_rank)
            got.append(kl.latent_mixer(cfg, params.__getitem__, 0,
                                       h[t:t + 1], attend)[0])
    np.testing.assert_allclose(np.stack(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(kept[0, :9]), np.asarray(rows),
                               atol=2e-6)
    assert cfg.latent_scale == (16 + 8) ** -0.5


# -- 2. the share ----------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One routed layer, 16 experts, 3 a token: shares of the experts each
    route over all 16 and compute their own experts' part; their sum and
    the shared expert's output, counted once, equal the uncut reference's
    layer.  No share alone does, nor the shared expert counted a share."""
    cfg = CFG.replace(layers=1, layer_types=("kda",), dense_layers=0)
    fam.check_shares_add_up(
        cfg, init(cfg, seed=11), kl, ref, ref_config,
        ("wgate", "wup", "wdown"), (2e-5, 5e-5))


def test_the_served_bias_moves_the_choice_and_no_experts_load():
    """At the published router (2,304 x 256, 8 a token) behind the pre-norm
    (entries of root-mean-square 1), the configuration's ``expert_bias_std``
    re-decides the choice of experts on more than a quarter of tokens (so a
    block that ignores it is seen), and the 16 held experts a 32-lane step
    hits stay within 0.5 of an even router's 10.1 from seed to seed (so a
    run's time does not hang on its seed); a token's eighth and ninth best
    lie thousandths apart."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    std = config["expert_bias_std"]
    assert std == kl.BIAS_STD
    hits, differ, margins = [], [], []
    for seed in range(4):
        rng = np.random.RandomState(seed)
        per_layer = []
        for _layer in range(3):
            router = jnp.asarray(rng.randn(2304, 256) * 0.02, jnp.float32)
            bias = jnp.asarray(rng.randn(256) * std, jnp.float32)
            x = rng.randn(32 * 16, 2304)
            x = jnp.asarray(x / np.sqrt((x * x).mean(1, keepdims=True)),
                            jnp.float32)
            _g, chosen = lf._route(x, router, bias, 8, 2.446, 1e-20)
            _g, plain = lf._route(x, router, jnp.zeros(256), 8, 2.446, 1e-20)
            differ.append(float((np.asarray(chosen) != np.asarray(plain))
                                .any(axis=1).mean()))
            held = np.asarray(chosen)[:, :16].reshape(16, 32, 16).sum(axis=1)
            per_layer.append(float((held > 0).sum(axis=1).mean()))
            score = np.sort(np.asarray(jax.nn.sigmoid(x @ router)), axis=1)
            margins.append(float(np.median(score[:, -8] - score[:, -9])))
        hits.append(float(np.mean(per_layer)))
    even = 16 * (1 - (1 - 8 / 256) ** 32)
    assert abs(even - 10.2) < 0.05
    assert min(differ) > 0.25, differ
    assert max(abs(h - even) for h in hits) < 0.5, hits
    assert 0.001 < np.mean(margins) < 0.01, margins


# -- 3. the manager: layers by kind ----------------------------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_the_27_layer_pattern_gets_latent_pools_and_slots():
    """The published pattern through the cache manager: one pool a layer for
    the 7 latent layers (a row of 576 values a token, held 640 wide), three
    windows and a matrix state for the 20 KDA layers, no K and V pool at
    all."""
    config, cfg = _published()
    assert cfg.layer_types == ("kda", "kda", "kda", "latent") * 6 \
        + ("kda", "kda", "latent")
    assert len(cfg.kda_layers) == 20 and len(cfg.latent_layers) == 7
    assert cfg.recurrent_layers == cfg.kda_layers and not cfg.attn_layers
    assert cfg.routed_layers == tuple(range(1, 27))
    assert cfg.state_name == "kda_state"
    assert (cfg.hidden, cfg.heads * cfg.head_dim, cfg.kda_inner,
            cfg.latent_width, cfg.vocab) == (2304, 4096, 4096, 576, 163840)
    assert abs(cfg.latent_scale - 192 ** -0.5) < 1e-12
    kv = dm.cache_config(cfg, 16, 12832, state_slots=33)
    assert (kv.layers, kv.latent_layers, kv.state_layers,
            kv.window_layers) == (0, 7, 20, 0)
    assert (kv.latent_width, kv.latent_row) == (576, 640)
    assert kv.state_shapes == (((3 * 12288,), "bf16"), ((128, 4096), "f32"))
    assert kvc.slot_bytes(kv) == 20 * (2097152 + 73728) == 43417600
    assert kvc.state_bytes(kv) == 33 * 43417600            # 1.433e9 B
    # a block as held, and the values in it
    assert kvc.latent_block_bytes(kv) == 20480
    assert kvc.block_bytes(kv) == 7 * 20480
    assert kvc.block_bytes(kv) * kv.num_blocks == 1839595520   # 1.840e9 B
    assert 16 * kv.latent_width * 2 == 18432
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(kv).carry())
    pools, (windows, states) = kv.groups(carry)
    assert pools == [] and len(carry) == 7 + 2 * 20
    latent = kv.latent_pools(carry)
    assert len(latent) == 7 and latent[0].shape == (12832, 16, 640) \
        and latent[0].dtype == jnp.bfloat16
    assert len(windows) == len(states) == 20
    assert states[0].shape == (33, 128, 4096) \
        and windows[0].shape == (33, 3 * 12288 // 128, 128)
    pool_of = dm._pool_index(cfg)
    assert [pool_of[l] for l in cfg.latent_layers] == list(range(7))
    assert [pool_of[l] for l in cfg.kda_layers] == list(range(20))
    assert dm.lane_columns(kv, 512)[1] == 4 + 1 + 512


def test_published_sizes_give_the_issues_bytes():
    config, cfg = _published()
    shapes = kl.param_shapes(cfg)
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _k) in shapes.items()
                            if n.startswith(pre))
    ffn = 16 * 7077888 + 7077888 + 2304 * 256 + 256
    assert count("l1_") == 39514272 + 2 * 2304 + ffn          # a KDA layer
    assert count("l3_") == 29114880 + 2 * 2304 + ffn          # an MLA layer
    assert count("l0_") == 39514272 + 2 * 2304 + 63700992     # the dense lead
    total = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert total == 4956660608                               # 9.913e9 B
    whole = model.param_shapes(dict(config, num_experts=256))
    assert sum(int(np.prod(s)) for s, _k in whole.values()) \
        == 49122681728
    assert shapes["l1_wgate"][0] == (16, 2304, 1024)
    assert shapes["l3_wq"][0] == (2304, 6144) \
        and shapes["l3_wkva"][0] == (2304, 576) \
        and shapes["l3_wkvb"][0] == (512, 8192)


def test_config_refuses_what_no_block_computes():
    base = dict(vocab=31, layers=2, heads=4, head_dim=8, experts=8,
                experts_per_token=2, ffn=24, shared_ffn=16)
    kda = dict(kda_heads=2, kda_head_dim=8, kda_conv=4)
    with pytest.raises(ValueError, match="kda layers want kda_heads"):
        dm.DecoderConfig(arch="kimi_linear", layer_types=["kda", "kda"],
                         **base)
    with pytest.raises(ValueError, match="latent layers want latent_rank"):
        dm.DecoderConfig(arch="kimi_linear", layer_types=["kda", "latent"],
                         **dict(base, **kda))
    with pytest.raises(ValueError, match="the kimi_linear block's layers"):
        dm.DecoderConfig(arch="kimi_linear", layer_types=["kda", "attention"],
                         **dict(base, **kda))
    with pytest.raises(ValueError, match="the nemotron_h block's layers"):
        dm.DecoderConfig(arch="nemotron_h", layer_types=["kda", "experts"],
                         **dict(base, **kda))
    cfg = dm.DecoderConfig(
        arch="kimi_linear", layer_types=["kda", "latent"], latent_rank=16,
        latent_rope=4, dense_layers=1, dense_ffn=32, experts_held=4,
        expert_first=4, hidden_size=24, **dict(base, **kda))
    assert cfg.held_experts == slice(4, 8) and cfg.routed_layers == (1,)
    assert cfg.recurrent_layers == (0,) and cfg.latent_layers == (1,)
    assert dm._conv_window(cfg) == (4, 3 * 16)
    assert cfg.replace(attention_multiplier=0.5).latent_scale == 0.5
    # a latent pool has no int8 residency, and says why
    with pytest.raises(ValueError, match="a latent row\\s+has no heads"):
        dm.cache_config(cfg, 4, 8, "int8", state_slots=3)
    source = dict(_published()[0])
    for key, value in (("mla_use_nope", False), ("q_lora_rank", 1536),
                       ("num_expert_group", 8), ("v_head_dim", 64),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="the kimi_linear block is MLA"):
            model.decoder_config(dict(source, **{key: value}))
    with pytest.raises(ValueError, match="name each of the 27 layers once"):
        model.decoder_config(dict(source, linear_attn_config=dict(
            source["linear_attn_config"], full_attn_layers=[4, 8])))


# -- 4. the engine, the server, the client ---------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="km")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("km", prompt, max_new_tokens=n,
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
    """Traced, the step's span says how many lanes' state it moved, the
    blocks a latent layer fetched with their size, and what a share's router
    assigned here and elsewhere; the gauges say what the slots and the
    latent pools hold; the prewarm event names the three paths and the
    layers by kind."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = init(cfg, seed=3)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 24, buckets="2", name="km")
        try:
            e.prewarm()
            r = e.generate("km", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "km")
    assert len(steps) >= 20
    per_slot = 4 * (3 * 3 * 32 * 4 + 8 * 32 * 4)
    assert all(s["kda_state_lanes"] == 1 and s["kda_state_bytes"] == per_slot
               and s["kv_block_size"] == BS
               and s["latent_blocks_read"] == s["kv_blocks_read"] == 2 * MAXB
               for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 3 experts a token over 16, 4 of them held here
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 3.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        for s in routed)
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    assert gauges["kda_state_bytes{model=km}"] == 3 * per_slot
    # 2 latent layers, 24 blocks of 4 rows of 128 (32 values, the tile
    # filled up) in float32
    assert gauges["latent_pool_bytes{model=km}"] == 2 * 24 * 4 * 128 * 4
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "km" and ev["attention"] == "gather"
        and ev["latent_attention"] == "gather"
        and ev["experts"] == "einsum" and ev["state_update"] == "gather"
        and ev["layers"] == {"kda": 4, "latent": 2}
        for ev in warm)


# -- 5. the kernels, under the interpreter ---------------------------------------

def _kda_args(rng, slots_n, dim, heads, lanes):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pool = f(slots_n, dim, heads * dim)
    slots = jnp.asarray(rng.permutation(slots_n)[:lanes], jnp.int32)
    fresh = jnp.asarray([i % 3 == 1 for i in range(lanes)])
    alpha = jnp.asarray(rng.uniform(0.2, 1.0, (lanes, heads, dim)),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (lanes, heads)), jnp.float32)
    k, v, q = (f(lanes, heads, dim) / np.sqrt(dim) for _ in range(3))
    return pool, (slots, fresh, alpha, beta, k, v, q)


def test_advance_is_the_delta_rule_a_head_at_a_time():
    """``kda_update.advance`` on the slot layout against the rule written
    out for one head's [keys, values] matrix in numpy."""
    rng = np.random.default_rng(0)
    pool, (slots, _fresh, alpha, beta, k, v, q) = _kda_args(rng, 3, 8, 4, 3)
    state = jnp.take(pool, slots, axis=0)
    new, o = ku.advance(state, alpha, beta, k, v, q)
    for b in range(3):
        for i in range(4):
            s = np.asarray(state)[b][:, 8 * i:8 * i + 8].astype(np.float64)
            a, kk, vv, qq = (np.asarray(x)[b, i].astype(np.float64)
                             for x in (alpha, k, v, q))
            s = a[:, None] * s
            s = s + float(beta[b, i]) * np.outer(kk, vv - s.T @ kk)
            np.testing.assert_allclose(
                np.asarray(new)[b][:, 8 * i:8 * i + 8], s, atol=1e-5)
            np.testing.assert_allclose(np.asarray(o)[b, i], s.T @ qq,
                                       atol=1e-5)


@pytest.mark.parametrize("heads,columns", [(2, None), (4, None), (4, 256),
                                           (4, 128), (8, 512)])
def test_kda_state_update_kernel_equals_advance(interpreted, monkeypatch,
                                                heads, columns):
    """The kernel (a lane's whole slot one transfer, ``columns`` None; a
    slot in chunks of two heads, of one) against gather, ``advance``,
    scatter: lanes out of order, fresh ones starting from zeros whatever
    their slot holds, the slots no lane names untouched."""
    if columns:
        fam.chunked(monkeypatch, 128, columns)
    rng = np.random.default_rng(heads + (columns or 0))
    pool, args = _kda_args(rng, 7, 128, heads, 5)
    assert su.transfer_columns(pool.shape, heads) == (columns or heads * 128)
    assert all(ok for _r, ok in ku.kda_update_checks(pool.shape, pool.dtype,
                                                     5, heads))
    got_pool, got_o = jax.jit(lambda *a: ku.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["kda_update"]
    want_pool, want_o = jax.jit(ku.state_update_reference)(pool, *args)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_pool), np.asarray(want_pool),
                               rtol=2e-6, atol=2e-6)
    untouched = sorted(set(range(7)) - set(np.asarray(args[0]).tolist()))
    assert np.array_equal(np.asarray(got_pool)[untouched],
                          np.asarray(pool)[untouched])


@pytest.mark.parametrize("order", fam.COPY_ORDERS)
@pytest.mark.parametrize("case", sorted(fam.TURNS))
def test_kda_state_update_kernel_keeps_its_turns(monkeypatch, case, order):
    """``ssm_update.in_turns``' order under the delta rule, whose update is
    the long one (it runs beside the write of the batch before and beside
    the read of the batch after): ``tests/decoder_families.py``'s cases, a
    head a chunk where a slot moves in several, under both of the
    interpreter's models of a copy.  Against gather, ``advance``, scatter,
    and bit for bit the plainest order, one unit at a time."""
    slots_n, slots, inner, cols, k_n = fam.turns_case(monkeypatch, case, 128)
    heads, lanes = inner // 128, len(slots)
    rng = np.random.default_rng(len(case))
    pool, args = _kda_args(rng, slots_n, 128, heads, lanes)
    args = (jnp.asarray(slots, jnp.int32),) + args[1:]
    assert su.transfer_columns(pool.shape, heads) == cols
    assert su.units_in_flight(pool.shape, cols,
                              lanes * (inner // cols)) == k_n
    # every idle lane writes the scratch slot, and nothing reads it
    live = [i for i, s in enumerate(slots) if s]
    got_pool, got_o = fam.in_turns_and_plainly(monkeypatch, ku, order, pool,
                                               args, live)
    want_pool, want_o = jax.jit(ku.state_update_reference)(pool, *args)
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_pool)[1:],
                               np.asarray(want_pool)[1:], rtol=2e-6,
                               atol=2e-6)
    assert np.isfinite(np.asarray(got_pool)).all()


def test_shapes_the_kda_kernel_cannot_tile_fall_back_counted(interpreted):
    checks = lambda shape, heads: dict(ku.kda_update_checks(
        shape, jnp.float32, 32, heads))
    assert all(checks((33, 128, 4096), 32).values())
    assert ku.update_path((33, 128, 4096), jnp.float32, 32, 32) == "pallas"
    assert not checks((33, 64, 2048), 32)["heads"]     # a head of 64 values
    assert not checks((33, 128, 4096), 16)["heads"]    # keys != values
    # (64 heads' columns fill two tiles: tests/test_solar_open2.py)
    assert checks((33, 128, 8192), 64)["heads"]
    assert not dict(ku.kda_update_checks((33, 128, 4096), jnp.bfloat16, 32,
                                         32))["dtype"]
    assert ku.update_path((33, 8, 32), jnp.float32, 4, 4) == "gather"
    pool, args = _kda_args(np.random.default_rng(1), 4, 8, 4, 3)
    jax.jit(lambda *a: ku.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == []


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_latent_attention_kernel_equals_the_gather(interpreted, dtype, tol):
    """The latent form of the paged kernel (12 query rows over one cached
    head 256 wide, the value its first 128 columns) against gather and
    ``masked_latent``: contexts of one token, of a chunk and a half, of
    nothing (an idle lane returns zeros), tables shuffled."""
    rng = np.random.default_rng(3)
    lanes, heads, width, rank, bs, maxb = 4, 12, 256, 128, 16, 12
    pool = jnp.asarray(rng.standard_normal((40, bs, width)), dtype)
    q = jnp.asarray(rng.standard_normal((lanes, heads, width)), jnp.float32)
    lens = jnp.asarray([1, 190, 0, 77], jnp.int32)
    tables = np.full((lanes, maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, 40)))
    for b, n in enumerate(np.asarray(lens)):
        for j in range(-(-int(n) // bs)):
            tables[b, j] = next(free)
    tables = jnp.asarray(tables)
    assert pa.latent_path(q.shape, pool.shape, dtype, rank) == "pallas"
    got = jax.jit(lambda *a: pa.latent_attention(*a, 0.1, rank))(
        q, pool, tables, lens)
    assert adoption.active_kernels() == ["latent_attention"]
    want = pa.latent_attention_reference(q, pool, tables, lens, 0.1, rank)
    assert got.shape == (lanes, heads, rank)
    live = [0, 1, 3]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    assert not np.asarray(got)[2].any()
    # what the kernel's span counts: the blocks each lane holds
    assert pa.blocks_read(np.asarray(lens), bs, maxb, "pallas") == 1 + 12 + 5


def test_a_latent_pool_the_kernel_cannot_read_falls_back():
    checks = lambda q, pool, dt, rank: dict(pa.latent_attention_checks(
        q, pool, dt, rank))
    fine = checks((32, 32, 640), (12832, 16, 640), jnp.bfloat16, 512)
    assert [k for k, ok in fine.items() if not ok] == ["backend"]
    # a row of 576 is no whole tile: the cache holds it 640 wide
    assert not checks((32, 32, 576), (12832, 16, 576), jnp.bfloat16,
                      512)["lanes"]
    assert not checks((32, 32, 640), (12832, 16, 640), jnp.bfloat16,
                      500)["lanes"]
    assert not checks((32, 32, 640), (12832, 8, 640), jnp.bfloat16,
                      512)["block_size"]
    assert not checks((32, 32, 640), (12832, 16, 640), jnp.int8,
                      512)["dtype"]
    # more lanes' queries and outputs than VMEM holds at once go a lane at
    # a time; one lane's alone (twice) can still be too much
    assert checks((256, 64, 640), (12832, 16, 640), jnp.bfloat16,
                  512)["vmem"]
    assert not checks((32, 1024, 640), (12832, 16, 640), jnp.bfloat16,
                      512)["vmem"]


def test_the_paged_step_on_three_kernels_gives_the_jnp_steps_tokens(
        interpreted):
    """The whole step with the latent-attention, state-update and expert
    kernels interpreted (4 query heads of 128 + 32 over 96 latent values:
    rows of 128 held 128 wide; 2 KDA heads of 128; experts of width 128):
    the tokens and logits of the jnp step."""
    cfg = dm.DecoderConfig(
        arch="kimi_linear", vocab=61, layers=4, heads=4, head_dim=128,
        hidden_size=128, max_seq=64,
        layer_types=("kda", "latent", "kda", "latent"), kda_heads=2,
        kda_head_dim=128, kda_conv=4, latent_rank=128, latent_rope=32,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, routed_scaling=2.446)
    params = kl.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    kv = dm.cache_config(cfg, 16, 12, state_slots=3)
    assert (kv.latent_width, kv.latent_row) == (160, 256)
    assert dm.attention_path(cfg, kv, 2, "latent") == "pallas"
    assert dm.state_update_path(cfg, kv, 2) == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"

    def run():
        # one lane of a two-lane step, 20 tokens by the step's own argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernels = run()
    assert set(adoption.active_kernels()) == {"latent_attention",
                                              "kda_update", "moe_experts"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.state_update_path(cfg, kv, 2) == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=1e-4, rtol=1e-4)
