"""What is the Xing4.0 decoder block's own (paddle_tpu/models/xing4.py: four
residual streams a token, mixed round every sublayer by manifold-constrained
hyper-connections, ``models/hyper_connections.py``, round dots.vlm1's latent
mixer and feed-forwards): logits at every position against its plain
reference (benchmark/reference/xing4_ref.py, the file the benchmark uses),
prefill then decode through the paged step and the cache manager; the
reference told otherwise, and the mixing served in a lower precision; the
maps by themselves (doubly stochastic, the clamp reached); ``hc_mult``
refused everywhere else; the share; what the cache manager gives the model at
its whole depth; the published sizes' bytes; server and client; the step's
span, gauge and prewarm event.  The contract it shares with every family is
tests/test_decoder_families.py's, over its row of tests/decoder_families.py,
whose tiny sizes these are: 4 ``latent`` layers (8 mixings), 4 streams of 48
under 4 heads of 16 (+ 8 rotated values) over 24 latent values, the query
through 20, a dense lead of width 64, 16 experts of width 24 in one group, 3
a token, a shared one of width 24, vocab 97."""

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
from paddle_tpu.models import hyper_connections as hc
from paddle_tpu.models import xing4 as xg
from paddle_tpu.pallas_kernels import adoption
from paddle_tpu.pallas_kernels import moe_experts as moe
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm

CONFIG_FILE = fam.config_file("xing4.0-29b-a4b-serve.json")
ref = fam.load("benchmark", "reference", "xing4_ref.py")
model = fam.load("benchmark", "models", "xing4_decoder.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["xing4"].configs[k] for k in ("f32", "bf16"))
_jnp = fam.as_jnp
MAXB = CFG.max_seq // BS
init = functools.partial(xg.init_params, std=0.3, bias_std=0.05, hc_std=0.17)


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
        "hc_mult": cfg.hc_mult, "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
        "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_clamp[0],
        "mhc_h_res_clamp_max": cfg.hc_clamp[1],
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0, "rms_norm_eps": cfg.norm_eps},
        **changed)


# float32 rounding over four layers and eight mixings (measured 4e-5 here);
# a fault in structure is 0.05 or more, a mixing in bfloat16 0.02 or more
# (the controls below)
TOL_F32 = 3e-4


def _ref(cfg, params, tokens, kept=False, broken=None, **kw):
    layer_fn = functools.partial(ref.layer, **broken) if broken else ref.layer
    with jax.default_matmul_precision("highest"):
        out = ref.forward(ref_config(cfg), _jnp(params),
                          jnp.asarray(tokens, jnp.int32), kept,
                          layer_fn=layer_fn, **kw)
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
    step's logits are the reference's full forward pass of the sequence so
    far (four streams, eight mixings, expanded attention), and what every
    layer's pool holds of a sequence is the reference's ``[c | rotated
    k_pe]`` rows."""
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


BREAKS = {
    "one_sinkhorn_iteration": dict(iters=1),
    "five_sinkhorn_iterations": dict(iters=5),
    "no_flattened_norm": dict(flat_norm=False),
    "post_without_its_two": dict(post_two=False),
    "no_rotation": dict(rope=False),
    "no_yarn_scale": dict(mscale=False),
    "bias_ignored": dict(use_bias=False),
    "routed_scaling_dropped": dict(scaled=False),
    "no_shared_expert": dict(shared=False),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_forgetful_reference(how):
    """Each part of the layer's mathematics left out of the reference moves
    the logits by tens of times the tolerance or more (the Sinkhorn
    normalisation stopped after 5 of its 20 iterations the least)."""
    assert _worst(CFG, _f32_out(), PARAMS, broken=BREAKS[how]) \
        > 20 * TOL_F32


def _with_lower_precision(monkeypatch, how):
    """The block with one part of its mixing in bfloat16."""
    # (a convert there and back XLA may drop as excess precision)
    bf16 = lambda x: jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)
    maps, merge, start = hc.maps, hc.merge, hc.start
    if how == "streams":
        # what is carried between sublayers rounded to 8 bits of mantissa
        monkeypatch.setattr(hc, "start", lambda x, n: bf16(start(x, n)))
        monkeypatch.setattr(hc, "merge", lambda *a: bf16(merge(*a)))
    elif how == "maps":
        monkeypatch.setattr(hc, "maps", lambda *a: tuple(
            bf16(m) for m in maps(*a)))
    elif how == "phi":
        monkeypatch.setattr(hc, "maps", lambda cfg, phi, b, a, X: maps(
            cfg, bf16(phi), b, a, X))
    elif how == "projection":
        # the matrix unit's default: one bfloat16 pass
        monkeypatch.setattr(hc, "_flat_norm",
                            lambda X, eps, _f=hc._flat_norm: bf16(_f(X, eps)))


@pytest.mark.parametrize("how", ["streams", "maps", "phi", "projection"])
def test_f32_tolerance_catches_a_mixing_in_bfloat16(monkeypatch, how):
    """The streams, the three maps, ``phi`` or the projection's input
    rounded to bfloat16, everything else float32: the logits leave the
    float32 tolerance by a factor of 30 or more (measured 0.02-0.2)."""
    _with_lower_precision(monkeypatch, how)
    out = run_paged(CFG, PARAMS, [(toks, 0) for toks, _lg in _f32_out()])
    assert _worst(CFG, out, PARAMS) > 30 * TOL_F32


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """As served (bf16 weights and cache, float32 accumulation, the streams
    and their mixing float32) the logits stay within bf16's rounding of the
    float32 reference on the same weights; the same weights rounded to fp8
    do not.  Judged by the median over positions of a position's largest
    error, as dots.vlm1's: three of sixteen experts are won by hundredths
    and bf16 swaps one at some positions."""
    out = run_paged(CFG16, PARAMS16, fam.sequences(3))

    def median_error(runs, params):
        return float(np.median(np.concatenate([
            np.abs(lg - _ref(CFG16, params, toks)).max(axis=1)
            for toks, lg in runs])))

    std = float(np.std(_ref(CFG16, PARAMS16, out[0][0])))
    forced = [(toks, 0) for toks, _lg in out]
    low = {k: v if k.split("_")[1:2] == ["hc"] else fam.fp8_rounded(
        {k: v})[k] for k, v in PARAMS16.items()}
    assert low["l0_hc_attn_phi"].dtype == np.float32
    err = median_error(out, PARAMS16)
    err8 = median_error(run_paged(CFG16, low, forced), PARAMS16)
    assert err < 0.2 * std < 0.7 * std < err8, (err, err8, std)


# -- 2. the maps by themselves ---------------------------------------------------

def _streams(seed=0, tokens=64):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(tokens, CFG.hc_mult, CFG.hidden)
                       * rng.uniform(0.1, 10, (tokens, 1, 1)), jnp.float32)


def _mixing(l=2, sub="mlp", params=PARAMS):
    return tuple(jnp.asarray(params["l%d_hc_%s_%s" % (l, sub, x)])
                 for x in ("phi", "b", "a"))


def test_the_maps_are_the_references_and_h_res_is_doubly_stochastic():
    """On seeded streams of any scale (the flattened norm takes it out):
    ``H_pre`` in (0, 1), ``H_post`` in (0, 2), ``H_res`` positive with
    column sums 1 to ``hc_eps`` and row sums 1 to what 20 iterations leave
    (measured 2e-4 at the worst of 64 tokens; one iteration leaves 0.1-0.5),
    all three the reference's; ``read`` and ``merge`` are ``H_pre X`` and
    ``H_res X + H_post^T y`` by a loop."""
    X = _streams()
    pre, post, res = (np.asarray(m) for m in hc.maps(CFG, *_mixing(), X))
    with jax.default_matmul_precision("highest"):
        want = ref.hc_maps(ref_config(CFG), *_mixing(), X)
        once = np.asarray(ref.hc_maps(ref_config(CFG), *_mixing(), X,
                                      iters=1)[2])
    for got, w in zip((pre, post, res), want):
        np.testing.assert_allclose(got, np.asarray(w), atol=2e-6)
    assert 0 < pre.min() and pre.max() < 1 and 0 < post.min() \
        and post.max() < 2 and post.max() > 1 and res.min() > 0
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-5
    assert np.abs(res.sum(axis=2) - 1).max() < 5e-3
    assert np.abs(once.sum(axis=2) - 1).max() > 0.1
    # far from the identity and from the uniform mixing (xing4.A_INIT ...)
    assert 0.3 < np.mean(np.diagonal(res, axis1=1, axis2=2)) < 0.7
    # a token's scale is taken out: the same maps for streams ten times over
    again = hc.maps(CFG, *_mixing(), 10.0 * X)
    np.testing.assert_allclose(np.asarray(again[2]), res, atol=1e-5)
    y = jnp.asarray(np.random.RandomState(1).randn(64, CFG.hidden),
                    jnp.float32)
    Xn, yn = np.asarray(X), np.asarray(y)
    u = np.asarray(hc.read(jnp.asarray(pre), X))
    merged = np.asarray(hc.merge(X, y, jnp.asarray(post), jnp.asarray(res)))
    for t in (0, 17, 63):
        np.testing.assert_allclose(u[t], pre[t] @ Xn[t], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(
            merged[t], res[t] @ Xn[t] + np.outer(post[t], yn[t]), rtol=1e-5,
            atol=1e-5)
    assert np.array_equal(np.asarray(hc.total(hc.start(y, 4))),
                          np.asarray(4.0 * y))


def test_the_clamp_is_reached_and_holds_the_exponential():
    """A sublayer whose ``b_res`` has an entry of 100 and one of -100: the
    clamp cuts them to +-30 before the exponential, the maps stay finite,
    the entry at +30 takes nearly all of its row and its column (0.96
    after 20 iterations: the others leave slowly), and they are the
    reference's; the reference with the clamp left out overflows float32
    (``exp(100)``) and gives no number."""
    phi, b, a = _mixing()
    b = b.at[2 * 4 + 1].set(100.0).at[2 * 4 + 14].set(-100.0)
    X = _streams(3, 16)
    pre, post, res = (np.asarray(m) for m in hc.maps(CFG, phi, b, a, X))
    assert np.isfinite(res).all() and res[:, 0, 1].min() > 0.9
    with jax.default_matmul_precision("highest"):
        want = ref.hc_maps(ref_config(CFG), phi, b, a, X)
        loose = ref.hc_maps(ref_config(CFG), phi, b, a, X, clamp=False)
    np.testing.assert_allclose(res, np.asarray(want[2]), atol=2e-6)
    assert not np.isfinite(np.asarray(loose[2])).all()
    # ... and a clamp that did not hold would show in the logits
    params = dict(PARAMS, l2_hc_mlp_b=np.asarray(b))
    out = run_paged(CFG, params, fam.sequences(2, seed=4))
    assert _worst(CFG, out, params) < TOL_F32
    assert not np.isfinite(_worst(CFG, out, params,
                                  broken=dict(clamp=False)))


@pytest.mark.parametrize("arch", [a for a in dm.ARCHS if a != "xing4"])
def test_residual_streams_are_refused_by_every_other_family(arch):
    """``hc_mult`` and its three companions are the ``xing4`` block's: a
    family that does not declare ``residual_streams`` refuses each."""
    tiny = fam.ROWS[arch].f32[0].to_dict()
    assert not dm._model(arch).FAMILY.residual_streams
    for changes in (dict(hc_mult=4), dict(hc_sinkhorn_iters=20),
                    dict(hc_eps=1e-6), dict(hc_clamp=(-30, 30)),
                    dict(hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                         hc_clamp=(-30, 30))):
        with pytest.raises(ValueError, match="for the xing4 blocks and for "
                           "no other"):
            dm.DecoderConfig(**dict(tiny, **changes))
    assert dm.DecoderConfig(**tiny).mixings == 0


def test_config_refuses_what_no_block_computes():
    """One stream is not this model and is not built; nor a normalisation
    of no iterations, without its epsilon or its clamp."""
    tiny = CFG.to_dict()
    assert tiny["hc_clamp"] == [-30.0, 30.0] and CFG.mixings == 8
    assert dm.DecoderConfig(**json.loads(json.dumps(tiny))).hc_clamp \
        == (-30.0, 30.0)
    bad = lambda **kw: dm.DecoderConfig(**dict(tiny, **kw))
    for changes in (dict(hc_mult=1), dict(hc_mult=0),
                    dict(hc_sinkhorn_iters=0), dict(hc_eps=0.0),
                    dict(hc_clamp=None), dict(hc_clamp=(30, -30))):
        with pytest.raises(ValueError, match="hc_mult >= 2 residual"):
            bad(**changes)
    with pytest.raises(ValueError, match="xing4 block's layers are latent"):
        bad(layer_types=("latent", "kda", "latent", "latent"))
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    for key, value in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                       ("num_nextn_predict_layers", 1),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="xing4 block is MLA"):
            model.decoder_config(dict(config, **{key: value}))
        with pytest.raises(ValueError, match="xing4 reference is hc_mult"):
            ref.forward(dict(config, **{key: value}), {}, [0])
    with pytest.raises(ValueError, match="xing4 reference is hc_mult"):
        ref.forward(dict(config, hc_mult=1), {}, [0])


# -- 3. the share ------------------------------------------------------------------

def test_the_shares_the_shared_expert_and_the_mixing_once_are_the_uncut_layer():
    """One routed layer, 16 experts, 3 a token: eight shares of two experts
    each route over all 16 and compute their own experts' part; their sum
    and the shared expert's output, counted once, equal the uncut
    reference's feed-forward (``check_shares_add_up``).  Round it the
    mixing, counted once: ``H_res X`` and ``H_post^T`` of that sum are the
    uncut reference's streams behind the layer; a chip that merged its own
    share's part and summed the merged streams would count ``H_res X``
    eight times."""
    cfg = CFG.replace(layers=1, layer_types=("latent",), dense_layers=0)
    params = init(cfg, seed=11)
    fam.check_shares_add_up(cfg, params, xg, ref, ref_config,
                            ("wgate", "wup", "wdown"), (2e-5, 5e-5),
                            shares=8)
    toks = jnp.asarray(fam.PROMPT, jnp.int32)
    whole = {k[3:]: jnp.asarray(v) for k, v in params.items()
             if k.startswith("l0_")}
    with jax.default_matmul_precision("highest"):
        _lg, kept = ref.forward(ref_config(cfg), _jnp(params), toks, True,
                                streams_of=(0,))
        # the streams the feed-forward's mixing read: behind the mixer
        X0 = jnp.repeat(jnp.asarray(params["embed"])[toks][:, None], 4, 1)
        pre, post, res = kept["maps"][0][0]
        mixed, _rows = ref.mla(ref_config(cfg), {k: whole[k] for k in (
            "wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo")},
            ref._rmsnorm(ref.hc_read(pre, X0), whole["ln1_g"], cfg.norm_eps))
        X1 = ref.hc_merge(X0, mixed, post, res)
        pre, post, res = hc.maps(cfg, whole["hc_mlp_phi"], whole["hc_mlp_b"],
                                 whole["hc_mlp_a"], X1)
        h2 = xg._rmsnorm(hc.read(pre, X1), whole["ln2_g"], cfg.norm_eps)
        live = jnp.ones(len(fam.PROMPT), bool)
        parts = []
        for share in range(8):
            mine = cfg.replace(experts_held=2, expert_first=2 * share)
            held = dict(whole, **{w: whole[w][mine.held_experts]
                                  for w in ("wgate", "wup", "wdown")})
            parts.append(xg.routed_part(mine, held.__getitem__, h2, live)[0])
        shared = xg.shared_part(whole.__getitem__, h2)
        once = hc.merge(X1, sum(parts) + shared, post, res)
        each = sum(hc.merge(X1, part, post, res) for part in parts) \
            + post[:, :, None] * shared[:, None]
    want = np.asarray(kept["streams"][0])
    np.testing.assert_allclose(np.asarray(once), want, atol=1e-4)
    assert np.abs(np.asarray(each) - want).max() > 0.1


# -- 4. the manager at the whole depth; the published sizes ------------------------

def _published():
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.pop("tiny")
    return config, model.decoder_config(config)


def test_forty_latent_layers_get_forty_pools_on_one_table():
    """The configuration as the cell serves it: 40 latent pools of rows 640
    wide on the global block tables (the most a model has held: LongCat-
    Flash's 8); no K/V pool, no slot, no ring; 80 mixings; the kernels'
    rules at these shapes: the latent kernel holds every lane's 32 heads at
    once, the experts go in chunks of 512 columns."""
    config, cfg = _published()
    assert cfg.layer_types == ("latent",) * 40 \
        and cfg.routed_layers == tuple(range(2, 40)) and cfg.mixings == 80
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.latent_rope,
            cfg.latent_rank, cfg.q_rank, cfg.dense_ffn, cfg.ffn,
            cfg.shared_ffn, cfg.experts, cfg.experts_held,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling, cfg.vocab, cfg.norm_eps, cfg.max_seq,
            cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp,
            cfg.dtype, cfg.kv_dtype) == (
        3584, 32, 128, 64, 512, 768, 9216, 1024, 1024, 64, 8, 4, 1, 1, 2.0,
        16384, 1e-6, 2048, 4, 20, 1e-6, (-30.0, 30.0), "bf16", "bf16")
    assert abs(cfg.latent_scale - 192 ** -0.5 * 2.004740) < 1e-6
    kv = dm.cache_config(cfg, 16, 3616, state_slots=0)
    assert (kv.layers, kv.latent_layers, kv.latent_width, kv.latent_row,
            kv.state_layers, kv.window_layers, kv.state_shapes) \
        == (0, 40, 576, 640, 0, 0, ())
    from paddle_tpu.serving import kv_cache as kvc

    assert kvc.block_bytes(kv) == 40 * 16 * 640 * 2 == 819200
    assert kvc.block_bytes(kv) * 3616 == 2962227200
    assert 3616 == 32 * (256 + 1536) // 16 + 32
    assert set(dm.lane_columns(kv, 128)[0]) \
        == {"tok", "src", "pos", "lens", "tables"}
    carry = jax.eval_shape(lambda: kvc.PagedKVCache(
        dm.cache_config(cfg, 16, 8)).carry())
    assert [a.shape for a in carry] == [(8, 16, 640)] * 40
    assert not pa._latent_lane_grid((32, 32, 640), (3616, 16, 640),
                                    jnp.bfloat16, 512)
    assert dm.experts_chunk(cfg) == moe.f_chunk(3584, 1024, 2) == 512


def test_published_sizes_give_the_issues_bytes():
    """The held model, from the shapes the benchmark makes weights by:
    5,254,039,536 parameters (a dense layer 128,196,918, a routed layer's
    share 128,426,358, embedding, head and final norm 117,444,096), the
    mixings' 27,527,280 of them float32: 10,563,133,632 B, 62.5% of one
    chip's 16,909,336,064 B; with the cell's cache 80.0%.  The uncut model
    is 29.5e9 parameters."""
    config, cfg = _published()
    shapes = model.param_shapes(config)
    count = lambda keep: sum(int(np.prod(s)) for n, (s, _k)
                             in shapes.items() if keep(n))
    assert count(lambda n: n.startswith("l0_")) == 128196918
    assert count(lambda n: n.startswith("l39_")) == 128426358
    assert count(lambda n: not n.startswith("l")
                 or n.startswith("lnf")) == 117444096
    mla = count(lambda n: n.startswith("l3_") and n[3:] in (
        "wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo"))
    assert mla == 28411136
    mixing = count(lambda n: "_hc_" in n)
    assert mixing == 80 * (14336 * 24 + 24 + 3) == 27527280
    assert hc.param_bytes(cfg, cfg.mixings) == 4 * mixing == 110109120
    total = count(lambda n: True)
    assert total == 5254039536
    nbytes = 2 * (total - mixing) + 4 * mixing
    assert nbytes == 10563133632
    hbm = 16909336064
    assert round(100 * nbytes / hbm, 1) == 62.5
    assert round(100 * (nbytes + 2962227200) / hbm, 1) == 80.0
    whole = 2 * 128196918 + 38 * (128426358 + 56 * 11010048) \
        + 2 * 131072 * 3584 + 3584
    assert whole == 29505505264
    # what a step's 80 mixings move of 32 lanes' streams, and of phi
    assert hc.stream_bytes(4, 3584, 80, 32) == 80 * 3 * 32 * 57344 \
        == 440401920
    # the served weights: the mixings float32, everything else as stated
    params = jax.eval_shape(
        lambda: model.make_params(dict(config, num_hidden_layers=3,
                                       expert_bias_balance=None), 7,
                                  jax.devices()[0]))
    assert {str(v.dtype) for k, v in params.items() if "_hc_" in k} \
        == {"float32"}
    assert {str(v.dtype) for k, v in params.items() if "_hc_" not in k} \
        == {"bfloat16"}


def test_the_benchmarks_seeded_mixing_is_the_assumed_draw():
    """``xing4_decoder.make_params`` at the tiny sizes: ``phi`` normal with
    the configuration's deviation, ``a`` (1, 1, 0.25), ``b_res``'s diagonal
    raised by 1; the host-side ``init_params`` draws the same way."""
    with open(CONFIG_FILE) as fp:
        config = json.load(fp)
    config.update(config.pop("tiny"))
    config["expert_bias_balance"] = None
    got = model.make_params(config, (1 << 31) + 5, jax.devices()[0])
    for params, std in ((got, 0.17), (PARAMS, 0.17)):
        phi = np.concatenate([np.asarray(v).ravel() for k, v in
                              params.items() if k.endswith("_phi")])
        assert abs(phi.std() - std) < 0.01 and params["l1_hc_attn_phi"].shape \
            == (4 * 48, 24)
        assert all(np.array_equal(np.asarray(v), xg.A_INIT)
                   for k, v in params.items() if k.endswith("_hc_attn_a"))
        b = np.stack([np.asarray(v) for k, v in params.items()
                      if "_hc_" in k and k.endswith("_b")])
        diag = b[:, 8:].reshape(-1, 4, 4)[:, np.arange(4), np.arange(4)]
        assert abs(diag.mean() - xg.B_RES_DIAGONAL) < 0.35
        assert abs(b[:, :8].mean()) < 0.25 and 0.3 < b[:, :8].std() < 0.7


# -- 5. the engine, the server, the client -----------------------------------------

def test_server_and_client_serve_the_model_at_defaults(cache_dir):
    """add_model -> prewarm -> ServingServer -> ServingClient.generate, no
    flag beside the tests' block size: the tokens of the sequence alone."""
    from paddle_tpu.serving import ServingClient, ServingEngine, ServingServer

    e = fam.engine(CFG, PARAMS, 40, buckets="2", name="xg")
    e.prewarm()
    server = ServingServer(ServingEngine(), port=0, decode_engine=e).start()
    try:
        client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
        for prompt, n in (([3, 1, 4, 1, 5], 12), ([9, 2, 6], 7)):
            reply = client.generate("xg", prompt, max_new_tokens=n,
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
    """Traced, the step's span says the streams, the sublayers that mix them
    and what those mixings move of the live lanes' streams, beside the
    blocks a latent layer fetched and what a share's router assigned here
    and elsewhere; the gauges say what the latent pools and the mixings'
    parameters hold; the prewarm event names the streams and the paths."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = init(cfg, seed=3)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 24, buckets="2", name="xg")
        try:
            e.prewarm()
            r = e.generate("xg", [1, 2, 3], max_new_tokens=20,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "xg")
    assert len(steps) >= 20
    # one live lane: 8 mixings x 3 passes over 4 x 48 float32
    assert all(s["hc_streams"] == 4 and s["hc_mixings"] == 8
               and s["hc_stream_bytes"] == 8 * 3 * 4 * 48 * 4
               == hc.stream_bytes(4, 48, 8, 1)
               and s["kv_block_size"] == BS
               and s["latent_blocks_read"] == s["kv_blocks_read"] == 2 * MAXB
               for s in steps)
    routed = [s for s in steps if "moe_experts_hit" in s]
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 3.0
        and "moe_groups_kept" not in s for s in routed)
    gauges = _tm.snapshot()["gauges"]
    assert gauges["latent_pool_bytes{model=xg}"] == 4 * 24 * 4 * 128 * 4
    assert gauges["hc_param_bytes{model=xg}"] \
        == 8 * (192 * 24 + 24 + 3) * 4 == hc.param_bytes(cfg, 8)
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "xg" and ev["residual_streams"] == 4
        and ev["hc_sinkhorn_iters"] == 20
        and ev["latent_attention"] == "gather" and ev["experts"] == "einsum"
        for ev in warm)
    # no other family's event or span says any of it
    other = fam.ROWS["dots_vlm"].f32
    kv = dm.cache_config(other[0], BS, 24)
    account = dm.StepAccount(other[0], kv, dm.laid_out(*other), (2,))
    said = set(account.prewarm_attrs(2)) | set(account.key_parts) | set(
        account.step_attrs(2, np.asarray([3, 0], np.int32)))
    assert not [k for k in said if k.startswith(("hc_", "residual"))]
    assert "hc_param_bytes" not in account.pool_bytes()


# -- 6. the two kernels round the mixings, under the interpreter -------------------

def test_the_paged_step_on_two_kernels_gives_the_jnp_steps_tokens(
        interpreted):
    """The whole step with the latent-attention and expert kernels
    interpreted round the float32 mixings (4 streams of 128; 4 query heads
    of 128 + 32 rotated over 96 latent values: rows of 128 held 128 wide;
    experts of width 128): the tokens and logits of the jnp step."""
    cfg = dm.DecoderConfig(
        arch="xing4", vocab=61, layers=3, heads=4, head_dim=128,
        hidden_size=128, max_seq=64, layer_types=("latent",) * 3,
        latent_rank=128, latent_rope=32, q_rank=64, rope_scaling=fam.YARN,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, routed_scaling=2.0,
        norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-30, 30))
    params = xg.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    kv = dm.cache_config(cfg, 16, 12)
    assert (kv.latent_width, kv.latent_row) == (160, 256)
    assert dm.attention_path(cfg, kv, 2, "latent") == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"

    def run():
        # one lane of a two-lane step, 20 tokens by the step's own argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernels = run()
    # (since PR 68 a mixing's maps are a kernel too: its own cases, and
    # this step's twin on that kernel alone, are tests/test_hc_maps.py's)
    assert set(adoption.active_kernels()) == {"latent_attention",
                                              "moe_experts", "hc_maps"}
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.attention_path(cfg, kv, 2, "latent") == "gather"
    plain = run()
    assert on_kernels[0] == plain[0]
    np.testing.assert_allclose(on_kernels[1], plain[1], atol=1e-4, rtol=1e-4)
