"""What is the Solar-Open2 decoder block's own (paddle_tpu/models/
solar_open2.py: Kimi Delta Attention layers whose delta rule allows negative
eigenvalues and whose matrix state lives in slots, beside gated position-free
grouped-query attention over K/V pools, every layer ending in sigmoid-routed
experts beside a shared one): logits at every position against its plain
reference (benchmark/reference/solar_open2_ref.py, the file the benchmark
uses), unpaged and as prefill then decode through the paged step and the
cache manager; the reference told otherwise; the share; what the cache
manager gives the published pattern; server and client; the step's span and
prewarm event; the state-update kernel at 64 heads under the interpreter.
The contract it shares with every family is tests/test_decoder_families.py's,
over its row of tests/decoder_families.py, whose tiny sizes these are: 8
layers ``attention kda kda kda`` twice, hidden 48 under 8 query heads of 8
over 2 KV heads, 4 KDA heads of 8, 16 experts of width 24 with 3 a token, a
shared one of width 24, vocab 97."""

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
from paddle_tpu.models import solar_open2 as so
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import kda_update as ku
from paddle_tpu.pallas_kernels import ssm_update as su
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

CONFIG_FILE = fam.config_file("solar-open2-250b-serve.json")
ref = fam.load("benchmark", "reference", "solar_open2_ref.py")
model = fam.load("benchmark", "models", "solar_open2_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["solar_open2"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(so.init_params, std=0.3, bias_std=0.05)


def run_paged(cfg, params, seqs, **kw):
    """``fam.run_paged``, every live lane's token counted once by each
    layer's router."""
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
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.layers,
        "gqa_layers": list(cfg.attn_layers), "gqa_interval": 3,
        "linear_attn_config": {
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv, "num_kv_heads": None},
        "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": cfg.kda_neg_eigval, "rope_theta": 10000,
        "first_k_dense_replace": 0, "moe_intermediate_size": cfg.ffn,
        "n_routed_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rms_norm_eps": cfg.norm_eps},
        **changed)


# float32 rounding over eight layers (measured 4e-5 here); a fault in
# structure is 1 or more (the broken-reference controls below)
TOL_F32 = 3e-4


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
    through the paged step and the cache manager (a slot a lane for the six
    KDA layers, K/V blocks for the two softmax layers): every position's
    logits are the reference's whole-sequence pass."""
    out = _f32_out()
    assert _worst(CFG, out, PARAMS) < TOL_F32
    toks, _lg = out[0]
    assert len(set(toks[-8:])) > 2


def test_the_unpaged_step_equals_the_reference_too():
    """The reference step (contiguous K/V, a state a lane) at every position
    of a sequence the paged step fed."""
    toks, _lg = _f32_out()[1]
    got = fam.teacher_forced(CFG, PARAMS, toks)
    assert np.abs(got - _ref(CFG, PARAMS, toks)).max() < TOL_F32


# a reference told otherwise: each is a fault the tolerance has to see
BREAKS = {
    "beta_without_its_factor_of_two": dict(neg_eigval=False),
    "delta_correction_dropped": dict(delta=False),
    "q_and_k_not_normalised": dict(qk_norm=False),
    "kda_output_gate_dropped": dict(gate=False),
    "attention_gate_left_out": dict(attn_gate=False),
    "a_rotation_applied": dict(rotate=True),
    "gates_not_renormalised": dict(renorm=False),
    "shared_expert_dropped": dict(shared=False),
    "selection_bias_ignored": dict(use_bias=False),
}
CONFIG_BREAKS = {
    "fewer_experts_a_token": dict(num_experts_per_tok=2),
    "another_scaling_factor": dict(routed_scaling_factor=2.5),
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


def test_beta_passes_one_and_a_block_with_a_plain_sigmoid_is_seen():
    """``kda_neg_eigval``: the mixer hands the delta rule a ``beta`` in (0,
    2), past 1 on some heads (the eigenvalue ``1 - beta`` is then negative),
    exactly twice the sigmoid a block without the field hands it; and that
    block's logits are not the reference's."""
    cfg = CFG.replace(layers=1, layer_types=("kda",))
    params = {k[3:]: jnp.asarray(v) for k, v in init(cfg, seed=5).items()
              if k.startswith("l0_")}
    h = jnp.asarray(np.random.RandomState(0).randn(6, cfg.hidden),
                    jnp.float32)
    seen = {}

    class Recur:
        @staticmethod
        def window(l, x):
            return jnp.stack([x.astype(jnp.float32)] * cfg.kda_conv, axis=1)

        @staticmethod
        def delta(l, alpha, beta, k, v, q):
            seen[key] = np.asarray(beta)
            return v

    for key, told in (("doubled", cfg),
                      ("plain", cfg.replace(kda_neg_eigval=False))):
        kl.kda_mixer(told, params.__getitem__, 0, h, Recur)
    assert seen["doubled"].shape == (6, cfg.kda_heads)
    np.testing.assert_allclose(seen["doubled"], 2 * seen["plain"], rtol=1e-6)
    assert 1.0 < seen["doubled"].max() < 2.0 and seen["doubled"].min() > 0.0
    assert seen["plain"].max() < 1.0
    plain = CFG.replace(kda_neg_eigval=False)
    out = run_paged(plain, PARAMS, fam.sequences(2, seed=2))
    assert _worst(plain, out, PARAMS) < TOL_F32
    assert _worst(CFG, out, PARAMS) > 100 * TOL_F32


def test_the_state_is_remembered_and_a_slot_not_reset_is_seen():
    """A state carried over from another sequence moves every later logit:
    the reference on a sequence with five foreign tokens before it differs
    from the reference on the sequence alone (KDA layers only: attention
    would see the foreign tokens too); and through the paged step a sequence
    that starts in a slot full of another's state reads the same as in a
    clean one."""
    toks, _lg = _f32_out()[0]
    only_kda = CFG.replace(layer_types=("kda",) * 8)
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
    """bfloat16 as served against the float32 reference on the same weights
    (normal(0, 0.3) here): the root-mean-square logit error of the served
    path lies under the limit and that of weights rounded to 8 bits (e4m3)
    over it."""
    out = run_paged(CFG16, PARAMS16, fam.sequences(3))
    rms = lambda got: float(np.sqrt(np.mean([np.mean(np.square(
        lg - _ref(CFG16, PARAMS16, toks))) for toks, lg in got])))
    served = rms(out)
    fp8 = fam.fp8_rounded(PARAMS16)
    rounded = rms([(toks, fam.teacher_forced(CFG16, fp8, toks))
                   for toks, _ in out])
    assert served < 0.6 < 0.9 < rounded, (served, rounded)


def test_gated_attention_over_kept_keys_equals_the_reference_mixer():
    """One softmax layer alone over a sequence: the block's gated mixer
    through an ``attend`` that keeps every K and V against the reference's
    causal softmax over all positions; what the cache would hold is K and V
    as projected (no rotation, no norm), and a group of four query heads
    reads one KV head."""
    from paddle_tpu.pallas_kernels.paged_attention import masked_attention

    cfg = CFG.replace(layers=1, layer_types=("attention",))
    params = {k[3:]: jnp.asarray(v) for k, v in init(cfg, seed=5).items()
              if k.startswith("l0_")}
    h = jnp.asarray(np.random.RandomState(0).randn(9, cfg.hidden),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, (ref_k, ref_v) = ref._gqa(ref_config(cfg), params, h)
        ks = jnp.zeros((1, 16, cfg.kv_heads, cfg.head_dim), jnp.float32)
        vs, got = ks, []
        for t in range(9):
            def attend(l, q, k, v, _t=t):
                nonlocal ks, vs
                assert q.shape == (1, 8, 8) and k.shape == v.shape == (1, 2,
                                                                       8)
                ks, vs = ks.at[0, _t].set(k[0]), vs.at[0, _t].set(v[0])
                return masked_attention(q, ks, vs, jnp.asarray([_t + 1]))
            got.append(so.gqa_mixer(cfg, params.__getitem__, 0, h[t:t + 1],
                                    attend)[0])
    np.testing.assert_allclose(np.stack(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ks[0, :9]).reshape(9, -1),
                               np.asarray(ref_k), atol=2e-6)
    np.testing.assert_allclose(np.asarray(vs[0, :9]).reshape(9, -1),
                               np.asarray(ref_v), atol=2e-6)


# -- 2. the share ----------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One routed layer, 16 experts, 3 a token: shares of the experts each
    route over all 16 and compute their own experts' part; their sum and
    the shared expert's output, counted once, equal the uncut reference's
    layer.  No share alone does, nor the shared expert counted a share."""
    cfg = CFG.replace(layers=1, layer_types=("kda",))
    fam.check_shares_add_up(
        cfg, init(cfg, seed=11), so, ref, ref_config,
        ("wgate", "wup", "wdown"), (2e-5, 5e-5))


def test_the_320_wide_routers_shares_add_up_and_the_bias_moves_the_choice():
    """At the published router (4,096 x 320, 8 a token) behind the pre-norm
    (entries of root-mean-square 1): sixteen shares of 20 experts partition
    every token's 8 assignments (a token's assignments on the shares add up
    to 8, and a share sees 64 lanes x 8 / 16 of them in the mean); the
    configuration's ``expert_bias_std`` re-decides the choice on a good part
    of the tokens, so a block that ignores it is seen; a token's eighth and
    ninth best lie thousandths apart."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    std = config["expert_bias_std"]
    assert std == so.BIAS_STD
    assert config["num_experts_published"] // config["num_experts"] \
        == config["expert_parallel_chips"] == 16
    differ, margins, local = [], [], []
    for seed in range(3):
        rng = np.random.RandomState(seed)
        router = jnp.asarray(rng.randn(4096, 320) * 0.02, jnp.float32)
        bias = jnp.asarray(rng.randn(320) * std, jnp.float32)
        x = rng.randn(64 * 8, 4096)
        x = jnp.asarray(x / np.sqrt((x * x).mean(1, keepdims=True)),
                        jnp.float32)
        gates, chosen = lf._route(x, router, bias, 8, 1.0, 1e-20)
        _g, plain = lf._route(x, router, jnp.zeros(320), 8, 1.0, 1e-20)
        chosen = np.asarray(chosen)
        assert (chosen.sum(axis=1) == 8).all()
        np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 1.0,
                                   atol=1e-5)
        shares = chosen.reshape(-1, 16, 20).sum(axis=2)
        assert (shares.sum(axis=1) == 8).all()
        local.append(float(shares[:, 0].reshape(8, 64).sum(axis=1).mean()))
        differ.append(float((chosen != np.asarray(plain)).any(axis=1)
                            .mean()))
        score = np.sort(np.asarray(jax.nn.sigmoid(x @ router)), axis=1)
        margins.append(float(np.median(score[:, -8] - score[:, -9])))
    assert min(differ) > 0.1, differ
    assert all(abs(n - 32.0) < 8.0 for n in local), local
    assert 0.001 < np.mean(margins) < 0.01, margins


# -- 3. the manager: layers by kind ----------------------------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_the_published_pattern_gets_kv_pools_and_slots():
    """The held pattern through the cache manager: K and V pools for the two
    softmax layers (8 heads of 128), three windows and a matrix state of 64
    heads for the six KDA layers, in one cache; the cell's bytes are the
    issue's."""
    config, cfg = _published()
    assert cfg.layer_types == ("attention", "kda", "kda", "kda") * 2
    assert cfg.attn_layers == (0, 4) \
        and cfg.kda_layers == (1, 2, 3, 5, 6, 7)
    assert cfg.recurrent_layers == cfg.kda_layers and not cfg.latent_layers
    assert cfg.routed_layers == tuple(range(8)) and cfg.dense_layers == 0
    assert cfg.state_name == "kda_state" and cfg.kda_neg_eigval
    assert (cfg.hidden, cfg.heads * cfg.head_dim, cfg.kv_heads,
            cfg.kda_inner, cfg.vocab) == (4096, 8192, 8, 8192, 24576)
    assert config["gqa_layers"] == list(range(0, 48, 4))
    kv = dm.cache_config(cfg, 16, 25664, state_slots=65)
    assert (kv.layers, kv.latent_layers, kv.state_layers,
            kv.window_layers) == (2, 0, 6, 0)
    assert kv.state_shapes == (((3 * 24576,), "bf16"), ((128, 8192), "f32"))
    assert kvc.slot_bytes(kv) == 6 * (4194304 + 147456) == 26050560
    assert kvc.state_bytes(kv) == 65 * 26050560 == 1693286400
    assert kvc.block_bytes(kv) == 131072
    assert kvc.block_bytes(kv) * kv.num_blocks == 3363831808   # 3.364e9 B
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(kv).carry())
    pools, (windows, states) = kv.groups(carry)
    assert len(carry) == 2 * 2 + 2 * 6 and len(pools[0]) == 2
    assert pools[0][0].shape == (25664, 16, 1024) \
        and pools[0][0].dtype == jnp.bfloat16
    assert len(windows) == len(states) == 6
    assert states[0].shape == (65, 128, 8192) \
        and windows[0].shape == (65, 3 * 24576 // 128, 128)
    pool_of = dm._pool_index(cfg)
    assert [pool_of[l] for l in cfg.attn_layers] == [0, 1]
    assert [pool_of[l] for l in cfg.kda_layers] == list(range(6))
    assert dm.lane_columns(kv, 512)[1] == 4 + 1 + 512
    # resident: weights, slots and pools, 76.0% of a chip's HBM
    assert 7797587200 + 1693286400 + 3363831808 == 12854705408


def test_published_sizes_give_the_issues_bytes():
    config, cfg = _published()
    shapes = so.param_shapes(cfg)
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _k) in shapes.items()
                            if n.startswith(pre))
    experts = 20 * 15728640
    assert count("l1_") == 154780160 + experts            # a KDA layer
    assert count("l0_") == 126099776 + experts            # a softmax layer
    assert 137732288 + 1311040 + 15728640 + 2 * 4096 == 154780160
    assert 109051904 + 1311040 + 15728640 + 2 * 4096 == 126099776
    total = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert total == 3898793600                               # 7.798e9 B
    whole = model.param_shapes(dict(
        config, num_hidden_layers=48, vocab_size=196608, num_experts=320,
        n_routed_experts=320))
    assert round(sum(int(np.prod(s)) for s, _k in whole.values()) / 1e8) \
        == 2503
    assert shapes["l1_wgate"][0] == (20, 4096, 1280)
    assert shapes["l1_wqkv"][0] == (4096, 24576) \
        and shapes["l1_low_a"][0] == (4096, 320) \
        and shapes["l0_wg"][0] == (4096, 8192) \
        and shapes["l0_wk"][0] == (4096, 1024)


def test_config_refuses_what_no_block_computes():
    base = dict(vocab=31, layers=2, heads=4, head_dim=8, experts=8,
                experts_per_token=2, ffn=24, shared_ffn=16)
    kda = dict(kda_heads=2, kda_head_dim=8, kda_conv=4)
    with pytest.raises(ValueError, match="kda layers want kda_heads"):
        dm.DecoderConfig(arch="solar_open2", layer_types=["kda", "kda"],
                         **base)
    with pytest.raises(ValueError, match="the solar_open2 block's layers"):
        dm.DecoderConfig(arch="solar_open2", layer_types=["kda", "latent"],
                         latent_rank=16, latent_rope=4, **dict(base, **kda))
    # beta's range is this family's to declare
    for arch, kinds, more in (
            ("kimi_linear", ["kda", "latent"],
             dict(latent_rank=16, latent_rope=4)),
            ("granite_hybrid", ["attention", "attention"], {}),
            ("gpt2", None, {})):
        given = dict(base, **kda, **more) if arch == "kimi_linear" \
            else dict(vocab=31, layers=2, heads=4, head_dim=8)
        with pytest.raises(ValueError,
                           match="kda_neg_eigval .* solar_open2 blocks"):
            dm.DecoderConfig(arch=arch, layer_types=kinds,
                             kda_neg_eigval=True, **given)
    with pytest.raises(ValueError, match="dense_layers leads"):
        dm.DecoderConfig(arch="solar_open2",
                         layer_types=["attention", "kda"], dense_layers=1,
                         dense_ffn=16, **dict(base, **kda))
    cfg = dm.DecoderConfig(
        arch="solar_open2", layer_types=["attention", "kda"], kv_heads=2,
        experts_held=4, expert_first=4, hidden_size=24, kda_neg_eigval=True,
        **dict(base, **kda))
    assert cfg.held_experts == slice(4, 8) and cfg.routed_layers == (0, 1)
    assert cfg.recurrent_layers == (1,) and cfg.attn_layers == (0,)
    assert dm._conv_window(cfg) == (4, 3 * 16)
    assert cfg.replace(kda_neg_eigval=False).kda_neg_eigval is False
    assert dm.DecoderConfig(**cfg.to_dict()).kda_neg_eigval is True
    source = dict(_published()[0])
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("first_k_dense_replace", 1),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="the solar_open2 block is KDA"):
            model.decoder_config(dict(source, **{key: value}))


# -- 4. the engine, the server, the client ---------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="so")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("so", prompt, max_new_tokens=n,
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
    """Traced, the step's span carries the KDA slots' lanes beside the K/V
    walk's blocks and chunks for the first time, and what a share's router
    assigned here and elsewhere; the gauges say what the slots and the K/V
    pools hold; the prewarm event names the three paths, the KDA heads and
    the layers by kind."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = init(cfg, seed=3)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 24, buckets="2", name="so")
        try:
            e.prewarm()
            r = e.generate("so", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "so")
    assert len(steps) >= 20
    per_slot = 6 * (3 * 3 * 32 * 4 + 8 * 32 * 4)
    assert all(s["kda_state_lanes"] == 1 and s["kda_state_bytes"] == per_slot
               and s["kv_block_size"] == BS
               and s["kv_blocks_read"] == 2 * MAXB
               and s["kv_chunks"] == 1 and s["kv_straight_chunks"] == 0
               and "latent_blocks_read" not in s
               for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 3 experts a token over 16, 4 of them held here
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 3.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        for s in routed)
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    assert gauges["kda_state_bytes{model=so}"] == 3 * per_slot
    assert not [g for g in gauges if g.startswith("latent_pool_bytes")]
    # 2 softmax layers, K and V, 24 blocks of 4 rows of 16 float32
    assert gauges["kv_cache_bytes"] == 2 * 2 * 24 * 4 * 16 * 4
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "so" and ev["attention"] == "gather"
        and "latent_attention" not in ev
        and ev["experts"] == "einsum" and ev["state_update"] == "gather"
        and ev["kda_heads"] == 4
        and ev["layers"] == {"attention": 2, "kda": 6}
        for ev in warm)


# -- 5. the state-update kernel past 32 heads, under the interpreter -------------

def _kda_args(rng, slots_n, dim, heads, lanes):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pool = f(slots_n, dim, heads * dim)
    slots = jnp.asarray(rng.permutation(slots_n)[:lanes], jnp.int32)
    fresh = jnp.asarray([i % 3 == 1 for i in range(lanes)])
    alpha = jnp.asarray(rng.uniform(0.2, 1.0, (lanes, heads, dim)),
                        jnp.float32)
    # (0, 2): the family's range
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (lanes, heads)), jnp.float32)
    k, v, q = (f(lanes, heads, dim) / np.sqrt(dim) for _ in range(3))
    return pool, (slots, fresh, alpha, beta, k, v, q)


@pytest.mark.parametrize("heads,columns,tile", [
    (64, None, 256),        # the published slot, whole: two tiles of columns
    (64, 4096, 128),        # ... in two transfers of 32 heads, a tile each
    (33, None, 256),        # a second tile with one head in it
    (40, 1024, 128),        # five transfers of 8 heads
])
def test_kda_state_update_kernel_past_32_heads_equals_advance(
        interpreted, monkeypatch, heads, columns, tile):
    """The kernel at more heads than one 128-lane tile of columns holds (a
    lane's whole slot one transfer, ``columns`` None: the four columns a
    head lie in as many tiles as they fill; a slot in several transfers:
    each its own tile of its own heads' columns) against gather,
    ``advance``, scatter: lanes out of order, ``beta`` in (0, 2), fresh ones
    starting from zeros whatever their slot holds, the slots no lane names
    untouched."""
    if columns:
        fam.chunked(monkeypatch, 128, columns)
    rng = np.random.default_rng(heads + (columns or 0))
    pool, args = _kda_args(rng, 5, 128, heads, 3)
    assert su.transfer_columns(pool.shape, heads) == (columns or heads * 128)
    # the heads one transfer moves, and the lanes their columns take
    assert ku._tile((columns or heads * 128) // 128) == tile
    assert all(ok for _r, ok in ku.kda_update_checks(pool.shape, pool.dtype,
                                                     3, heads))
    got_pool, got_o = jax.jit(lambda *a: ku.state_update(*a))(pool, *args)
    assert adoption.active_kernels() == ["kda_update"]
    want_pool, want_o = jax.jit(ku.state_update_reference)(pool, *args)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_pool), np.asarray(want_pool),
                               rtol=2e-6, atol=2e-6)
    untouched = sorted(set(range(5)) - set(np.asarray(args[0]).tolist()))
    assert np.array_equal(np.asarray(got_pool)[untouched],
                          np.asarray(pool)[untouched])


def test_the_kda_rule_at_its_edges(interpreted):
    """What the shape rule admits now and what it still leaves to the
    gather: any number of heads of 128 keys and values whose transfers fit
    (32: one tile; 33 and 64: two; the published pool is exactly the budget
    of two batches of two whole slots; 65 heads move in five transfers of
    13), not a head of 64 values, keys unlike values or a bfloat16 pool."""
    checks = lambda shape, heads: dict(ku.kda_update_checks(
        shape, jnp.float32, 64, heads))
    for heads, cols, in_flight in ((32, 4096, 4), (33, 4224, 3),
                                   (64, 8192, 2), (65, 1664, 4)):
        shape = (65, 128, heads * 128)
        assert all(checks(shape, heads).values()), heads
        assert ku.update_path(shape, jnp.float32, 64, heads) == "pallas"
        assert su.transfer_columns(shape, heads) == cols
        assert su.units_in_flight(shape, cols, 64 * (heads * 128 // cols)) \
            == in_flight
    assert 2 * 2 * 4 * 128 * 8192 == su._UNIT_BUDGET
    assert ku._tile(32) == 128 and ku._tile(33) == ku._tile(64) == 256
    assert not checks((65, 64, 4096), 64)["heads"]     # a head of 64 values
    assert not checks((65, 128, 8192), 32)["heads"]    # keys != values
    assert not dict(ku.kda_update_checks((65, 128, 8192), jnp.bfloat16, 64,
                                         64))["dtype"]
    assert ku.update_path((65, 128, 8192), jnp.bfloat16, 64, 64) == "gather"


def test_the_paged_step_on_three_kernels_walks_a_context_past_one_chunk(
        interpreted):
    """The whole step with the K/V walk, the state-update and the expert
    kernels interpreted (16 query heads of 128 over 8 KV heads, the published
    4,096 B a position; 2 KDA heads of 128; experts of width 128), a context of 150 positions over a chunk of
    128: the tokens and logits of the jnp step, which are the reference's."""
    cfg = dm.DecoderConfig(
        arch="solar_open2", vocab=61, layers=2, heads=16, kv_heads=8,
        head_dim=128, hidden_size=128, max_seq=160,
        layer_types=("attention", "kda"), kda_heads=2, kda_head_dim=128,
        kda_conv=4, kda_neg_eigval=True, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3)
    params = so.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    kv = dm.cache_config(cfg, 16, 24, state_slots=3)
    assert dm.attention_path(cfg, kv, 2) == "pallas"
    assert dm.chunk_positions(cfg, kv, 2) == {"attention": 128}
    assert dm.state_update_path(cfg, kv, 2) == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"
    prompt = [int(t) for t in np.random.RandomState(1).randint(0, 61, 140)]

    def run():
        # one lane of a two-lane step: 140 prompt tokens, 10 by its argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [(prompt, 10), ([], 0)], blocks=24, block_size=16)
        return fed, logits

    on_kernels = run()
    assert set(adoption.active_kernels()) == {"paged_attention",
                                              "kda_update", "moe_experts"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.state_update_path(cfg, kv, 2) == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=1e-4, rtol=1e-4)
    assert np.abs(plain[1] - _ref(cfg, params, plain[0])).max() < TOL_F32
