"""What is the EXAONE-MoE decoder block's own (paddle_tpu/models/
exaone_moe.py: window layers beside global ones, grouped-query attention
with per-head Q/K norm, norms on the sublayers' outputs, a dense lead layer,
a share of sigmoid-routed experts and a shared expert, an untied head):
logits at every position against its plain reference
(benchmark/reference/exaone_moe_ref.py, the file the benchmark uses) and the
reference told otherwise; the window layers' rings (a ring gives back what
left the window, an int8 pool rings too) and their bytes; the step's span
and prewarm event; the attention kernel in interpret mode against the gather
for 64 query heads over 8 KV heads, compact, with a ring; and the share:
eight shares' routed parts and the shared expert once are the uncut layer,
the sliced head's logits the whole head's rows.  The contract it shares with
every family (paged against unpaged five windows deep, the multi-token step
refused over rings, the engine's lanes, rings, preemption and refusals, the
bundle) is tests/test_decoder_families.py's, over its row of
tests/decoder_families.py, whose tiny sizes these are: window 8, block 4, 5
layers ``window, window, window, attention, window``, the first dense (width
48), four routed (16 experts of width 16, 4 a token, the router 16 wide),
hidden 48 under 8 query heads over 2 KV heads of 8, vocab 61."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import exaone_moe as em
from paddle_tpu.pallas_kernels import paged_attention as pa
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

ref = fam.load("benchmark", "reference", "exaone_moe_ref.py")
BS = fam.BS
(CFG, PARAMS), (CFG16, PARAMS16) = (
    fam.ROWS["exaone_moe"].configs[k] for k in ("f32", "bf16"))
KINDS, WINDOW = CFG.layer_types, CFG.window
RING = 3                # ceil(8 / 4) + 1
MAXB = CFG.max_seq // BS
_jnp = fam.as_jnp
_generate = fam.generate
_teacher_forced = fam.teacher_forced
_engine = fam.engine
_flags = fam.flags
_counters = fam.counters

# normal(0, 0.3) and a bias of 0.05: at this hidden size the family's 0.02
# leaves the router's scores within hundredths of a half, where neither a
# fault's mark nor the bias's would show


def ref_config(cfg, **changed):
    """The source's keys, as the reference reads them."""
    return dict({
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.layers,
        "layer_types": ["full_attention" if k == "attention"
                        else "sliding_attention" for k in cfg.layer_types],
        "mlp_layer_types": ["dense" if l < cfg.dense_layers else "sparse"
                            for l in range(cfg.layers)],
        "sliding_window": cfg.window, "intermediate_size": cfg.dense_ffn,
        "moe_intermediate_size": cfg.ffn, "num_experts": cfg.experts_held,
        "num_experts_published": cfg.experts,
        "first_expert": cfg.expert_first,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_shared_experts": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "routed_scaling_factor": cfg.routed_scaling,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "rms_norm_eps": cfg.norm_eps}, **changed)


# float32 rounding over five layers (measured 2e-5 here); a fault in
# structure is 1e-2 or more (the broken-reference controls below)
TOL_F32 = 5e-4


def _ref(cfg, params, tokens, kept=False, **changed):
    with jax.default_matmul_precision("highest"):
        out = ref.forward(ref_config(cfg, **changed), _jnp(params),
                          jnp.asarray(tokens, jnp.int32), kept)
    return jax.tree_util.tree_map(np.asarray, out)


# -- 1. the block against the reference ------------------------------------------

PROMPT = fam.PROMPT


def _block_logits(cfg, params, n=40):
    """Teacher-forced with its own argmax: tokens fed and the logits at
    every position from the prompt's last on."""
    out, logits = _generate(cfg, params, PROMPT, n, return_logits=True)
    return PROMPT + out, np.stack(logits)


def test_f32_logits_equal_the_reference_past_five_windows():
    fed, got = _block_logits(CFG, PARAMS)
    want = _ref(CFG, PARAMS, fed)[len(PROMPT) - 1:-1]
    assert len(fed) > 5 * WINDOW and got.shape == want.shape
    assert np.abs(got - want).max() < TOL_F32


def _whole_width_norm(config, sliding, p, x):
    # OLMoE's norm: over all heads' values at once
    old = ref._rmsnorm

    def norm(v, g, eps):
        if v.ndim == 3:
            flat = v.reshape(v.shape[0], -1)
            return old(flat, 1.0, eps).reshape(v.shape)
        return old(v, g, eps)

    ref._rmsnorm = norm
    try:
        return ATTENTION(config, sliding, p, x)
    finally:
        ref._rmsnorm = old


ATTENTION = ref._attention

# a reference told otherwise: each is a fault the tolerance has to see
BREAKS = {
    "window_layers_attend_everything": dict(sliding_window=10 ** 6),
    "a_narrower_window": dict(sliding_window=WINDOW - 1),
    "every_layer_global": dict(layer_types=["full_attention"] * 5),
    "rope_on_the_global_layer": dict(
        layer_types=["sliding_attention"] * 5, sliding_window=10 ** 6),
    "no_shared_expert": dict(num_shared_experts=0),
    "another_scaling_factor": dict(routed_scaling_factor=1.0),
    "fewer_experts_a_token": dict(num_experts_per_tok=3),
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_f32_tolerance_catches_a_reference_told_otherwise(how):
    fed, got = _block_logits(CFG, PARAMS)
    want = _ref(CFG, PARAMS, fed, **BREAKS[how])[len(PROMPT) - 1:-1]
    assert np.abs(got - want).max() > 20 * TOL_F32, how


@pytest.mark.parametrize("how", ["bias_ignored", "gates_not_renormalised",
                                 "whole_width_qk_norm"])
def test_f32_tolerance_catches_a_forgetful_reference(how, monkeypatch):
    fed, got = _block_logits(CFG, PARAMS)
    if how == "whole_width_qk_norm":
        monkeypatch.setattr(ref, "_attention", _whole_width_norm)
    else:
        gates_of = ref.gates_of
        kw = {"bias_ignored": dict(use_bias=False),
              "gates_not_renormalised": dict(renormalise=False)}[how]
        monkeypatch.setattr(
            ref, "gates_of", lambda c, p, x: gates_of(c, p, x, **kw))
    want = _ref(CFG, PARAMS, fed)[len(PROMPT) - 1:-1]
    assert np.abs(got - want).max() > 20 * TOL_F32, how


def test_the_bias_selects_and_never_weighs():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 32), jnp.float32)
    router = jnp.asarray(rng.randn(32, 16) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.randn(16) * 0.3, jnp.float32)
    gates, chosen = em._route(x, router, bias, 4, 2.5)
    plain, chosen0 = em._route(x, router, jnp.zeros(16), 4, 2.5)
    score = np.asarray(jax.nn.sigmoid(x @ router))
    assert (np.asarray(chosen) != np.asarray(chosen0)).any(axis=1).mean() > 0.5
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, rtol=1e-5)
    picked = np.where(np.asarray(chosen), score, 0.0)
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-5)


def test_the_served_bias_moves_a_tenth_of_the_choices_and_no_experts_load():
    """At the published router (6,144 x 128, 8 a token) behind streams of
    the four sparse layers' sizes (norms on the sublayers' outputs add unit
    entries twice a layer: root-mean-square 1.4, 2.0, 2.4, 2.8), the
    configuration's ``expert_bias_std`` re-decides the choice of experts on
    more than a tenth of tokens in every such layer (so a block that
    ignores it is seen), and the 16 held experts a 32-lane step hits stay
    within half an expert of an even router's 13.8-14.0 from seed to seed
    (so a run's time does not hang on its seed: PR 36's refusal)."""
    with open(fam.config_file("k-exaone-236b-a23b-serve.json")) as fp:
        config = json.load(fp)
    std = config["expert_bias_std"]
    assert std == em.BIAS_STD
    hits, differ = [], []
    for seed in range(4):
        rng = np.random.RandomState(seed)
        per_layer = []
        for rms in (1.41, 2.0, 2.45, 2.83):
            router = jnp.asarray(rng.randn(6144, 128) * 0.02, jnp.float32)
            bias = jnp.asarray(rng.randn(128) * std, jnp.float32)
            x = jnp.asarray(rng.randn(32 * 24, 6144) * rms, jnp.float32)
            _g, chosen = em._route(x, router, bias, 8, 2.5)
            _g, plain = em._route(x, router, jnp.zeros(128), 8, 2.5)
            differ.append(float((np.asarray(chosen) != np.asarray(plain))
                                .any(axis=1).mean()))
            held = np.asarray(chosen)[:, :16].reshape(24, 32, 16).sum(axis=1)
            per_layer.append(float((held > 0).sum(axis=1).mean()))
        hits.append(float(np.mean(per_layer)))
    assert min(differ) > 0.09 and np.mean(differ) > 0.3, differ
    assert max(hits) - min(hits) < 0.5 and 13.3 < np.mean(hits) < 14.3, hits


def test_bf16_logits_within_tolerance_and_fp8_weights_outside():
    """bfloat16 as served against the float32 reference on the same
    weights, logits of standard deviation 2: root-mean-square error
    0.08-0.11 on three seeds (an expert swapped here and there included);
    with the weights rounded to 8 bits (e4m3) 0.45-0.66.  The limit stands
    between."""
    fed, got = _block_logits(CFG16, PARAMS16)
    want = _ref(CFG16, PARAMS16, fed)[len(PROMPT) - 1:-1]
    rms = lambda x: float(np.sqrt(np.mean(np.square(x - want))))
    assert rms(got) < 0.2, rms(got)
    fp8 = fam.fp8_rounded(PARAMS16)
    rounded = _teacher_forced(CFG16, fp8, fed[:-1])[len(PROMPT) - 1:]
    assert rms(rounded) > 0.3, rms(rounded)


# -- 2. the rings ----------------------------------------------------------------


def test_an_int8_pool_rings_too():
    ((fed, _lg),), _routed = fam.run_paged(CFG.replace(kv_dtype="int8"),
                                           PARAMS, [(PROMPT, 30)])
    out = fed[len(PROMPT):]
    want = _generate(CFG, PARAMS, PROMPT, 30)
    # quantised K and V: the same tokens for a while, not for ever
    assert out[:4] == want[:4]


def test_a_ring_gives_back_what_left_the_window():
    kv = dm.cache_config(CFG, BS, 8, state_slots=3)
    assert kv.window_ring == RING == 3 and kv.window_blocks == 9
    cache = kvc.PagedKVCache(kv)
    assert cache.window_allocator.capacity == 8        # block 0 is scratch
    ring = cache.new_ring()
    released = sum(cache.advance_ring(ring, p + 1) for p in range(30))
    # at position 29 the window is [22, 29]: blocks 5, 6, 7
    assert (ring.lo, ring.hi, ring.held) == (5, 8, 3) and released == 5
    assert cache.window_allocator.in_use == 3
    assert len(set(ring.table)) == 3 and ring.table.min() > 0
    # block 5 goes back a step before a fourth would be taken
    assert cache.advance_ring(ring, 31) == 0
    assert cache.advance_ring(ring, 32) == 1 and (ring.lo, ring.hi) == (6, 8)
    assert cache.advance_ring(ring, 33) == 0 and (ring.lo, ring.hi) == (6, 9)
    assert cache.release_ring(ring) == 3 and ring.held == 0
    assert cache.window_allocator.in_use == 0
    # more sequences than rings is the caller's bug: two rings' worth and
    # two blocks are free, a third sequence's window straddles three
    rings = [cache.new_ring() for _ in range(3)]
    for r in rings[:2]:
        for p in range(11):
            cache.advance_ring(r, p + 1)
        assert r.held == 3
    with pytest.raises(RuntimeError, match="no window block free"):
        for p in range(11):
            cache.advance_ring(rings[2], p + 1)


def test_cache_describes_layers_by_kind_and_counts_the_rings_bytes():
    kv = dm.cache_config(CFG16, BS, 16, state_slots=5)
    assert (kv.layers, kv.window_layers, kv.state_layers) == (1, 4, 0)
    assert (kv.window, kv.window_ring, kv.window_blocks) == (8, 3, 15)
    cache = kvc.PagedKVCache(kv)
    carry = cache.carry()
    (k, v), state = kv.groups(carry)
    wk, wv = kv.window_groups(carry)
    assert len(k) == len(v) == 1 and len(wk) == len(wv) == 4 and not state
    assert k[0].shape == (16, BS, 16) and wk[0].shape == (15, BS, 16)
    per_block = 2 * BS * 16 * 2
    assert kvc.block_bytes(kv) == per_block
    assert kvc.window_bytes(kv) == 4 * 15 * per_block
    assert cache.nbytes == 16 * per_block + 4 * 15 * per_block
    with pytest.raises(ValueError, match="not this cache's"):
        kv.groups(carry[:-1])
    # the rings come off a budget before the blocks do
    n, capped = kvc.plan_num_blocks(kv, requested=1000,
                                    budget=kvc.window_bytes(kv)
                                    + 10 * per_block)
    assert (n, capped) == (10, True)
    # a model without window layers has none of it
    plain = dm.cache_config(dm.DecoderConfig(31, 2, 2, 8), BS, 8)
    assert plain.window_ring == 0 and kvc.window_bytes(plain) == 0
    assert kvc.PagedKVCache(plain).window_allocator is None


def test_published_sizes_give_the_issues_bytes():
    from benchmark.models import exaone_moe_decoder as model

    with open(fam.config_file("k-exaone-236b-a23b-serve.json")) as fp:
        config = json.load(fp)
    config.pop("tiny")
    cfg = model.decoder_config(config)
    shapes = em.param_shapes(cfg)
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _k) in shapes.items()
                            if n.startswith(pre))
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024
    assert count("l0_") == attn + 3 * 6144 * 18432 + 2 * 128 + 2 * 6144
    assert count("l1_") == attn + 16 * 3 * 6144 * 2048 + 3 * 6144 * 2048 \
        + 6144 * 128 + 128 + 2 * 128 + 2 * 6144
    total = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert abs(total - 3.712e9) < 2e6                   # 7.42e9 B in bf16
    kv = dm.cache_config(cfg, 16, 12832, state_slots=33)
    assert kvc.block_bytes(kv) == 65536 and kv.window_ring == 9
    assert kvc.block_bytes(kv) * kv.num_blocks == 840957952    # 0.841e9 B
    assert kvc.window_bytes(kv) == 4 * 297 * 65536             # 0.078e9 B
    # what one table for every layer would hold of four more layers
    assert 4 * 12832 * 65536 == 3363831808


# -- 3. the kernel ---------------------------------------------------------------

def _pools(rng, blocks, bs, width, dtype):
    return [jnp.asarray(rng.randn(blocks, bs, width), dtype)
            for _ in range(2)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_kernel_64_heads_over_8_compact_equals_the_gather(interpreted, dtype,
                                                          tol):
    """The published attention shape: 64 query heads over 8 KV heads of
    128.  Compact, the query crosses into the kernel ``[B, 64, 128]`` and
    the kernel spreads it; 4 lanes of contexts 0 (idle), 1, a chunk's edge
    and several chunks."""
    rng = np.random.RandomState(0)
    bs, maxb = 16, 24
    k, v = _pools(rng, 64, bs, 8 * 128, dtype)
    q = jnp.asarray(rng.randn(4, 64, 128), jnp.float32)
    lens = jnp.asarray([0, 1, 128, 371], jnp.int32)
    tables = jnp.asarray(rng.randint(1, 64, (4, maxb)), jnp.int32)
    assert pa._compact(64, 8, 128) and not pa._compact(32, 8, 64)
    assert pa.attention_path(q.shape, k.shape, dtype) == "pallas"
    got = pa._paged_pallas(q, k, v, tables, lens)
    want = pa.paged_attention_reference(q, k, v, tables, lens)
    assert got.shape == (4, 64, 128)
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:],
                               atol=tol, rtol=tol)
    assert not np.asarray(got)[0].any()                 # an idle lane: zeros


@pytest.mark.parametrize("heads,kv_heads,dim", [(64, 8, 128), (8, 2, 64),
                                                (4, 4, 32)],
                         ids=["compact", "spread", "multi_head"])
def test_kernel_reads_a_ring_and_nothing_before_the_window(interpreted, heads,
                                                           kv_heads, dim):
    """A window layer's call: the table is the ring (9 slots of 16 for a
    window of 128), the kernel's one chunk is the ring as it lies, and a
    lane attends the last 128 positions: contexts inside the first window,
    at its edge, wrapped once and many times, and idle.  Against the gather
    with the same mask, and against plain attention over the unrolled
    window."""
    rng = np.random.RandomState(1)
    bs, ring, window = 16, 9, 128
    width = kv_heads * dim
    k, v = _pools(rng, 40, bs, width, jnp.float32)
    lens = np.asarray([0, 5, 128, 129, 150, 1000], np.int32)
    tables = np.stack([rng.permutation(39)[:ring] + 1 for _ in lens])
    # a slot whose block left the window (or was never reached) holds none
    for b, ctx in enumerate(lens):
        live = {(p // bs) % ring for p in range(max(ctx - window, 0), ctx)}
        tables[b, [s for s in range(ring) if s not in live]] = -1
    q = jnp.asarray(rng.randn(len(lens), heads, dim), jnp.float32)
    tables, jlens = jnp.asarray(tables, jnp.int32), jnp.asarray(lens)
    assert pa.attention_path(q.shape, k.shape, jnp.float32, ring) == "pallas"
    got = np.asarray(pa._paged_pallas(q, k, v, tables, jlens, window=window))
    want = np.asarray(pa.paged_attention_reference(q, k, v, tables, jlens,
                                                   window=window))
    np.testing.assert_allclose(got[1:], want[1:], atol=2e-5, rtol=2e-5)
    assert not got[0].any()
    # the unrolled window, oldest first, through plain masked attention
    for b, ctx in enumerate(lens):
        if not ctx:
            continue
        span = range(max(ctx - window, 0), ctx)
        rows = [(int(tables[b, (p // bs) % ring]), p % bs) for p in span]
        kk, vv = (jnp.stack([pool[blk, off] for blk, off in rows])
                  .reshape(1, len(rows), kv_heads, dim) for pool in (k, v))
        plain = pa.masked_attention(q[b:b + 1], kk, vv,
                                    jnp.asarray([len(rows)], jnp.int32))
        np.testing.assert_allclose(got[b], np.asarray(plain)[0], atol=2e-5,
                                   rtol=2e-5)
    # work follows the ring: 9 blocks a live lane, whatever the context
    assert pa.blocks_read(lens, bs, ring, "pallas", ring=True) == 5 * ring
    # ... where a global layer's kernel fetches the blocks each lane holds
    assert pa.blocks_read(lens, bs, 64, "pallas") == 1 + 8 + 9 + 10 + 63


def test_the_spread_layout_is_over_the_vmem_budget_and_compact_under_it():
    """What paged_attention_checks said of this model before the compact
    layout: 32 lanes x 64 rows x 1024 columns of float32, twice."""
    q, pool = (32, 64, 128), (12832, 16, 1024)
    spread = 4 * 128 * 2 * 1024 + 2 * 32 * 4 * 64 * 1024
    assert spread > pa._VMEM_BUDGET
    assert pa.vmem_bytes(q, pool, jnp.bfloat16) \
        == 4 * 128 * 2 * 1024 + 2 * 32 * 4 * 64 * 128 < pa._VMEM_BUDGET
    assert pa.vmem_bytes(q, (297, 16, 1024), jnp.bfloat16, 9) \
        == 4 * 144 * 2 * 1024 + 2 * 32 * 4 * 64 * 128
    # LFM2's and Granite's shapes keep the spread layout (head_dim 64), and
    # their narrower rows make a chunk 256 positions
    assert pa.vmem_bytes((32, 32, 64), (2048, 16, 512), jnp.bfloat16) \
        == 4 * 256 * 2 * 512 + 2 * 32 * 4 * 32 * 512


def test_the_paged_step_on_the_kernel_gives_the_gathers_tokens(interpreted):
    cfg = dm.DecoderConfig(
        arch="exaone_moe", vocab=61, layers=3, heads=4, kv_heads=2,
        head_dim=128, ffn=128, max_seq=64, layer_types=KINDS[2:],
        window=WINDOW, dense_layers=1, dense_ffn=128, experts=16,
        experts_per_token=4, experts_held=8, shared_ffn=128,
        routed_scaling=2.5, rope_theta=1e6)
    params = em.init_params(cfg, seed=5, std=0.05, bias_std=0.05)
    kv = dm.cache_config(cfg, 8, 24, state_slots=3)
    assert kv.window_ring == 2
    assert dm.attention_path(cfg, kv, 2) == "pallas"
    assert dm.attention_path(cfg, kv, 2, "window") == "pallas"
    assert dm.experts_path(cfg, _jnp(params), 2) == "pallas"

    def run():
        # one lane of a two-lane step, 20 tokens by the step's own argmax
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=24, block_size=8)
        return fed, logits

    on_kernel = run()
    os.environ.pop("PADDLE_PALLAS_INTERPRET")
    assert dm.attention_path(cfg, kv, 2, "window") == "gather"
    gathered = run()
    assert on_kernel[0] == gathered[0]
    np.testing.assert_allclose(on_kernel[1], gathered[1], atol=1e-4,
                               rtol=1e-4)


# -- 4. the share ----------------------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One routed layer, 16 experts, 4 a token: eight shares of 2 experts
    each route over all 16 and compute their own experts' part; their sum
    and the shared expert's output, counted once, equal the uncut
    reference's layer.  No share alone does."""
    cfg = CFG.replace(layers=1, layer_types=KINDS[:1], dense_layers=0)
    fam.check_shares_add_up(
        cfg, em.init_params(cfg, seed=11, std=0.3, bias_std=0.05), em, ref,
        ref_config, ("wgate", "wup", "wdown"), (1e-5, 2e-5))


def test_a_share_through_the_block_equals_the_reference_given_the_share():
    cfg = CFG.replace(experts_held=4, expert_first=8)
    params = em.init_params(cfg, seed=3, std=0.3, bias_std=0.05)
    assert params["l1_wgate"].shape == (4, 48, 16)
    assert params["l1_router"].shape == (48, 16)
    assert params["l1_wq"].shape == (48, 64)
    fed, got = _block_logits(cfg, params, 24)
    want = _ref(cfg, params, fed)[len(PROMPT) - 1:-1]
    assert np.abs(got - want).max() < TOL_F32
    # and not the reference given other experts
    other = _ref(cfg, params, fed, first_expert=0)[len(PROMPT) - 1:-1]
    assert np.abs(got - other).max() > 20 * TOL_F32


def test_the_sliced_heads_logits_are_the_whole_heads_rows():
    """An eighth of the vocabulary: the embedding's and the head's rows of
    the slice.  The slice's logits are the whole head's logits at those
    ids, for tokens drawn from the slice."""
    big = CFG.replace(vocab=8 * 61)
    whole = em.init_params(big, seed=3, std=0.3, bias_std=0.05)
    lo, hi = 2 * 61, 3 * 61
    part = dict(whole, embed=whole["embed"][lo:hi],
                head=whole["head"][:, lo:hi])
    step_whole = jax.jit(dm.make_unpaged_step(big, 32, RING * BS))
    step_part = jax.jit(dm.make_unpaged_step(CFG, 32, RING * BS))
    kv_w = dm._unpaged_carry(big, 1, 32, RING * BS)
    kv_p = dm._unpaged_carry(CFG, 1, 32, RING * BS)
    for pos, tok in enumerate([5, 60, 0, 17, 33, 8]):
        at = [jnp.asarray([pos], jnp.int32), jnp.asarray([pos + 1], jnp.int32)]
        kv_w, _n, lw = step_whole(kv_w, _jnp(whole),
                                  jnp.asarray([lo + tok], jnp.int32), *at)
        kv_p, _n, lp = step_part(kv_p, _jnp(part),
                                 jnp.asarray([tok], jnp.int32), *at)
        assert np.array_equal(np.asarray(lp), np.asarray(lw)[:, lo:hi])


# -- 5. the engine ---------------------------------------------------------------


def test_step_span_counters_gauges_and_prewarm_event(cache_dir, telemetry_on,
                                                     tmp_path):
    """Traced, the step's span says what the window layers fetched beside
    what their whole contexts would have cost and how many blocks their
    pools hold; a share's span splits the router's assignments into those
    computed here and those left out; the counters and the gauge by kind
    ride along; the prewarm event names both attention paths."""
    cfg = CFG.replace(experts_held=4, expert_first=4)
    params = em.init_params(cfg, seed=3, std=0.3, bias_std=0.05)
    with _flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = _engine(cfg, params, 24, buckets="2", name="ex")
        try:
            e.prewarm()
            r = e.generate("ex", [1, 2, 3], max_new_tokens=30,
                           deadline_ms=60000.0)
            assert r.status == "ok"
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    steps = fam.step_spans(tmp_path, "ex")
    assert len(steps) >= 32
    # the gather reads every slot of the table it is given: the ring's 3 in
    # each of 4 window layers x 2 lanes, against the whole table's 24
    assert all(s["kv_window_blocks_read"] == 4 * 2 * RING
               and s["kv_window_blocks_full"] == 4 * 2 * MAXB
               and 1 <= s["kv_window_blocks_held"] <= RING for s in steps)
    assert max(s["kv_window_blocks_held"] for s in steps) == RING
    routed = [s for s in steps if "moe_experts_hit" in s]
    # one lane, 4 experts a token over 16, 4 of them held here
    assert routed and all(
        s["moe_local_assignments"] + s["moe_absent_assignments"] == 4.0
        and s["moe_assignments"] == s["moe_local_assignments"]
        and s["moe_experts_hit"] == s["moe_local_assignments"]
        for s in routed)
    assert 0 < sum(s["moe_local_assignments"] for s in routed) \
        < 4 * len(routed)
    # 32 positions of block 4 with a window of 8: a block goes back every
    # 4 tokens once the window is past it
    assert _counters("kv_window_blocks_released_total") == {
        "kv_window_blocks_released_total{model=ex}": 6}
    assert _tm.counter_total("moe_assignments_absent_total") > 0
    gauges = _tm.snapshot()["gauges"]
    assert gauges["kv_pool_blocks{kind=window,model=ex}"] <= RING
    assert gauges["kv_pool_blocks{kind=global,model=ex}"] >= 8
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(
        ev["model"] == "ex" and ev["attention"] == "gather"
        and ev["window_attention"] == "gather" and ev["experts"] == "einsum"
        for ev in warm)


# -- 6. the configuration --------------------------------------------------------

def test_config_refuses_what_no_block_computes():
    base = dict(vocab=31, layers=2, heads=4, head_dim=8, kv_heads=2,
                experts=8, experts_per_token=2, ffn=16, shared_ffn=16)
    with pytest.raises(ValueError, match="window layers want window"):
        dm.DecoderConfig(arch="exaone_moe", layer_types=["window"] * 2,
                         **base)
    with pytest.raises(ValueError, match="the lfm2_moe block's layers are"):
        dm.DecoderConfig(arch="lfm2_moe", layer_types=["window"] * 2,
                         window=4, conv_taps=3, **base)
    with pytest.raises(ValueError, match="may hold experts"):
        dm.DecoderConfig(arch="exaone_moe", layer_types=["window"] * 2,
                         window=4, experts_held=4, expert_first=6, **base)
    with pytest.raises(ValueError, match="may hold experts"):
        dm.DecoderConfig(arch="olmoe", experts_held=4,
                         **dict(base, kv_heads=4))
    with pytest.raises(ValueError, match="a shared expert of width"):
        dm.DecoderConfig(arch="olmoe", **dict(base, kv_heads=4))
    cfg = dm.DecoderConfig(arch="exaone_moe", layer_types=["window"] * 2,
                           window=4, experts_held=4, expert_first=4, **base)
    assert cfg.held_experts == slice(4, 8) and cfg.routed_layers == (0, 1)
    assert dm.DecoderConfig(**dict(base, arch="olmoe", kv_heads=4,
                                   shared_ffn=0)) \
        .held_experts == slice(0, 8)


