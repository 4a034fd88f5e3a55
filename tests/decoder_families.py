"""The decoder families' table (a helper module, not collected): one row a
family of ``paddle_tpu.serving.decode_model.ARCHS`` with the tiny
configurations it is tested at and what the engine's contract comes to for
it, and the helpers every decode test shares.  ``tests/
test_decoder_families.py`` runs the contract over the rows; a family's own
test file takes its configuration from its row and keeps what is the
family's (its reference numerics, its router, its kernels);
``tests/test_decode_one_ahead.py`` and ``tests/test_step_dispatch.py`` take
their models from here too.  A new family adds its row and nothing else to
the shared tests."""

import collections
import contextlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.models import dots_vlm, exaone_moe, glm_dsa, \
    granite_hybrid, kimi_linear, lfm2_moe, longcat_flash, nemotron_h, olmoe, \
    smallthinker, solar_open2, xing4
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving import kv_cache as kvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4                  # the tests' KV block size
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]      # the suite's: 11 tokens


def load(*parts):
    """A module of the benchmark by its path (``benchmark/`` is no
    package the tests may import by name)."""
    spec = importlib.util.spec_from_file_location(
        parts[-1][:-3], os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config_file(name):
    return os.path.join(ROOT, "benchmark", "configs", name)


def as_jnp(params):
    return {k: jnp.asarray(v) for k, v in params.items()}


def sequences(n, seed=0, lo=5, hi=14, n_decode=8, vocab=97):
    rng = np.random.RandomState(seed)
    return [(list(rng.randint(0, vocab, rng.randint(lo, hi))), n_decode)
            for _ in range(n)]


def fp8_rounded(params):
    """The weights rounded to 8 bits (e4m3) on their way into the step: the
    precision next below bfloat16, which a bf16 tolerance has to catch."""
    return {k: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                          .astype(jnp.bfloat16)) for k, v in params.items()}


def chunked(monkeypatch, n, columns):
    """Leave the state-update kernel VMEM for four units of ``columns``
    columns: a slot wider than that moves in chunks."""
    from paddle_tpu.pallas_kernels import ssm_update

    monkeypatch.setattr(ssm_update, "_UNIT_BUDGET", 4 * 4 * n * columns)


# the TPU interpreter's two models of an async copy: done as it is started,
# or only when it is waited for (memory no copy has filled reads NaN).  A
# kernel right under both waits for what it reads and overwrites nothing a
# copy in flight still has to read
COPY_ORDERS = ("eager", "on_wait")


def in_turns_and_plainly(monkeypatch, module, order, pool, args, live):
    """``module``'s state-update kernel (``ssm_update`` or ``kda_update``)
    with its copies run by ``order``, as ``in_turns`` orders them and then
    one unit at a time, which it has to equal bit for bit on every slot but
    the scratch one and on the ``live`` lanes' read-outs -> (pool,
    read-out)."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.pallas_kernels import ssm_update

    kernel = lambda: jax.jit(lambda *a: module._state_update_pallas(
        *a, interpret=pltpu.InterpretParams(dma_execution_mode=order)))(
            pool, *args)
    got = kernel()
    monkeypatch.setattr(ssm_update, "in_turns", one_at_a_time)
    plain = kernel()
    assert np.array_equal(np.asarray(plain[1])[live],
                          np.asarray(got[1])[live])
    assert np.array_equal(np.asarray(plain[0])[1:], np.asarray(got[0])[1:])
    return got


def one_at_a_time(slots_ref, out_hbm, buf, rsem, wsem, lanes, chunks,
                  update):
    """``ssm_update.in_turns``' contract in the plainest order: a unit is
    read, updated and written back before the next is touched.  What any
    order of the transfers must equal bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lane, chunk = pl.program_id(0), pl.program_id(1)
    k_n, cols = buf.shape[1], buf.shape[3]
    unit = lane * chunks + chunk
    half, at = unit // k_n % 2, unit % k_n
    where = out_hbm.at[slots_ref[lane], :,
                       pl.ds(pl.multiple_of(chunk * cols, 128), cols)]
    read = pltpu.make_async_copy(where, buf.at[half, at], rsem.at[half, at])
    read.start()
    read.wait()
    update(lane, chunk, half, at)
    write = pltpu.make_async_copy(buf.at[half, at], where, wsem.at[half, at])
    write.start()
    write.wait()


# what the order of the transfers makes delicate, for both kernels that share
# it: case -> (lanes, ssm_update.BATCH, chunks a slot, every lane idle)
TURNS = {
    "one_lane": (1, 4, 1, False),
    "one_batch": (4, 4, 1, False),
    "a_last_batch_of_one": (5, 4, 1, False),
    "two_whole_batches": (8, 4, 1, False),
    "three_batches_the_last_short": (9, 4, 1, False),
    "batches_of_one": (5, 1, 1, False),
    "batches_of_three": (7, 3, 1, False),
    "a_slot_in_two_chunks": (5, 4, 2, False),
    "a_slot_in_three_chunks_across_batches": (3, 4, 3, False),
    "a_slot_in_two_chunks_batches_of_one": (3, 1, 2, False),
    "every_lane_on_the_scratch_slot": (6, 4, 1, True),
}


def turns_case(monkeypatch, case, n):
    """Set ``ssm_update`` up for ``TURNS[case]`` on slots of ``n`` rows ->
    (slots in the pool, the lanes' slots, a slot's columns, the columns a
    transfer moves, the units a batch holds)."""
    from paddle_tpu.pallas_kernels import ssm_update

    lanes, batch, pieces, idle = TURNS[case]
    # 256 columns whole or in halves (two a batch: a whole slot is four
    # halves' worth), 384 in thirds (four a batch)
    inner, room = {1: (256, batch), 2: (256, 2), 3: (384, 4)}[pieces]
    cols = inner // pieces
    monkeypatch.setattr(ssm_update, "BATCH", batch)
    if pieces > 1:
        monkeypatch.setattr(ssm_update, "_UNIT_BUDGET",
                            2 * room * 4 * n * cols)
    slots_n = lanes + 3
    rng = np.random.default_rng(lanes + batch)
    slots = [0] * lanes if idle \
        else [int(s) for s in 1 + rng.permutation(slots_n - 1)[:lanes]]
    return slots_n, slots, inner, cols, min(batch, room, lanes * pieces)


# -- the rows ------------------------------------------------------------------

class Row(collections.namedtuple(
        "Row",
        ("arch", "configs", "declines", "holds", "refusal", "multi_atol",
         "batch_dependent_bf16", "entry", "serve", "chunk"),
        defaults=(None, None, None, None, False, {}, None, None))):
    """``configs``: id -> (DecoderConfig, params), ``f32`` first and, where
    the family has one, ``bf16``.  ``declines``: why the engine gives the
    family no prefix reuse, export or adoption (None: it has them).
    ``holds``: what a sequence holds beside blocks (None | ``"slot"`` |
    ``"ring"``).  ``refusal``: the match of its speculation refusal (None:
    it speculates).  ``multi_atol``: how far the multi-token step's logits
    may lie from the single steps' (None: bit for bit).
    ``batch_dependent_bf16``: its bfloat16 sums depend on the step's lanes,
    so a bf16 sequence alone is a lane of the same step, not the unpaged
    loop.  ``entry``: attribute of the engine's entry -> what it has to be
    (a callable takes the configuration).  ``serve``: (the benchmark
    configuration ``tools/serve.py`` builds a demo bundle from, attribute of
    the bundle's configuration -> its value), or None.  ``chunk``: (the
    family's benchmark configuration, its cell's KV blocks, kind of layer ->
    the positions a chunk of the attention kernel spans at those widths)."""

    @property
    def f32(self):
        return self.configs["f32"]


def _both(cfg, init, **kw):
    """The configuration in float32 and as served, on one seed."""
    cfg16 = dm.DecoderConfig(**dict(cfg.to_dict(), dtype="bf16",
                                    kv_dtype=None))
    return {"f32": (cfg, init(cfg, seed=3, **kw)),
            "bf16": (cfg16, init(cfg16, seed=3, **kw))}


_GPT2 = dm.DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=48)
_OLMOE = dm.DecoderConfig(arch="olmoe", vocab=97, layers=2, heads=4,
                          head_dim=16, ffn=32, max_seq=64, experts=8,
                          experts_per_token=2)
_GRANITE = dm.DecoderConfig(
    arch="granite_hybrid", vocab=97, layers=8, heads=4, kv_heads=2,
    head_dim=16, ffn=48, max_seq=64,
    layer_types=("mamba", "mamba", "attention", "mamba") * 2, ssm_heads=8,
    ssm_head_dim=16, ssm_state=32, ssm_conv=4, embedding_multiplier=2.0,
    residual_multiplier=0.22, attention_multiplier=0.25, logits_scaling=8.0)
_LFM2 = dm.DecoderConfig(
    arch="lfm2_moe", vocab=97, layers=5, heads=4, kv_heads=2, head_dim=16,
    ffn=32, max_seq=64,
    layer_types=("conv", "attention", "conv", "conv", "attention"),
    conv_taps=3, dense_layers=1, dense_ffn=48, experts=8, experts_per_token=2,
    rope_theta=1e6)
_EXAONE = dm.DecoderConfig(
    arch="exaone_moe", vocab=61, layers=5, heads=8, kv_heads=2, head_dim=8,
    hidden_size=48, ffn=16, max_seq=96,
    layer_types=("window", "window", "window", "attention", "window"),
    window=8, dense_layers=1, dense_ffn=48, experts=16, experts_per_token=4,
    shared_ffn=16, routed_scaling=2.5, rope_theta=1e6)
_NEMOTRON = dm.DecoderConfig(
    arch="nemotron_h", vocab=97, layers=6, heads=4, kv_heads=2, head_dim=16,
    hidden_size=48, max_seq=64,
    layer_types=("mamba", "experts", "mamba", "attention", "mamba",
                 "experts"), ssm_heads=8, ssm_head_dim=8, ssm_state=16,
    ssm_conv=4, ssm_groups=2, ffn=24, shared_ffn=40, experts=16,
    experts_per_token=3, routed_scaling=2.5)
_KIMI = dm.DecoderConfig(
    arch="kimi_linear", vocab=97, layers=6, heads=4, head_dim=16,
    hidden_size=48, max_seq=64,
    layer_types=("kda", "kda", "kda", "latent", "kda", "latent"),
    kda_heads=4, kda_head_dim=8, kda_conv=4, latent_rank=24, latent_rope=8,
    dense_layers=1, dense_ffn=64, ffn=24, shared_ffn=24, experts=16,
    experts_per_token=3, routed_scaling=2.446)
# the published YaRN group: the two correction dims of 8 rotated values lie
# at 1.3 and 2.8, so pairs 0 and 1 keep their frequency, pair 3 has it
# divided by 40 and pair 2 is half of each
YARN = dict(factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
_DOTS = dm.DecoderConfig(
    arch="dots_vlm", vocab=97, layers=4, heads=4, head_dim=16,
    hidden_size=48, max_seq=64, layer_types=("latent",) * 4, latent_rank=24,
    latent_rope=8, q_rank=20, rope_scaling=YARN, norm_eps=1e-6,
    dense_layers=1, dense_ffn=64, ffn=24, shared_ffn=24, experts=16,
    experts_per_token=3, n_group=4, topk_group=2, routed_scaling=2.5)
# 14 query heads over 2 KV heads: 7 a group, the published ratio (28 over 4)
_SMALLTHINKER = dm.DecoderConfig(
    arch="smallthinker", vocab=61, layers=8, heads=14, kv_heads=2, head_dim=8,
    hidden_size=48, ffn=16, max_seq=96,
    layer_types=("attention", "window", "window", "window") * 2, window=8,
    experts=16, experts_per_token=3, rope_theta=1.5e6, norm_eps=1e-6)
# a head's own key part 12 wide and its value 16 (the published 192 and 256
# in their ratio), an indexer of 4 heads of 16 that keeps 8 positions: the
# suite's prompt of 11 tokens is past the selection from its ninth token on
_GLM = dm.DecoderConfig(
    arch="glm_dsa", vocab=97, layers=4, heads=4, head_dim=12, v_head_dim=16,
    hidden_size=48, max_seq=64, layer_types=("latent",) * 4, latent_rank=24,
    latent_rope=8, q_rank=20, index_heads=4, index_head_dim=16, index_topk=8,
    dense_layers=1, dense_ffn=64, ffn=24, shared_ffn=24, experts=16,
    experts_per_token=3, routed_scaling=2.5, rope_theta=1e6)
# two pairs: four sublayers, every one a latent mixer and a dense MLP, a
# router of 16 experts and 8 identity experts in sublayers 0 and 2; the two
# scales as published, the roots of hidden over the two ranks
_LONGCAT = dm.DecoderConfig(
    arch="longcat_flash", vocab=97, layers=4, heads=4, head_dim=16,
    hidden_size=48, max_seq=64, layer_types=("latent",) * 4, latent_rank=24,
    latent_rope=8, q_rank=20, latent_q_scale=(48 / 20) ** 0.5,
    latent_kv_scale=(48 / 24) ** 0.5, dense_ffn=64, ffn=24, experts=16,
    zero_experts=8, experts_per_token=3, routed_scaling=6.0, rope_theta=1e7)
# two periods [attention, kda, kda, kda], every layer routed: 8 query heads
# over 2 KV heads (4 a group; the published 64 over 8 are 8), 4 KDA heads of
# 8 whose beta lies in (0, 2), a router of 16 of which a share holds 4
_SOLAR = dm.DecoderConfig(
    arch="solar_open2", vocab=97, layers=8, heads=8, kv_heads=2, head_dim=8,
    hidden_size=48, max_seq=64,
    layer_types=("attention", "kda", "kda", "kda") * 2, kda_heads=4,
    kda_head_dim=8, kda_conv=4, kda_neg_eigval=True, ffn=24, shared_ffn=24,
    experts=16, experts_per_token=3)
# dots.vlm1's mixer and feed-forwards (one group of experts, as published)
# round four residual streams: 192 values a token, mixed eight times
_XING = dm.DecoderConfig(
    arch="xing4", vocab=97, layers=4, heads=4, head_dim=16, hidden_size=48,
    max_seq=64, layer_types=("latent",) * 4, latent_rank=24, latent_rope=8,
    q_rank=20, rope_scaling=dict(YARN, factor=64), norm_eps=1e-6,
    dense_layers=1, dense_ffn=64, ffn=24, shared_ffn=24, experts=16,
    experts_per_token=3, routed_scaling=2.0, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, hc_clamp=(-30.0, 30.0))
_GRANITE_G4 = _GRANITE.replace(kv_heads=1)

# Weights are normal(0, 0.3) (OLMoE's 0.05) and a router bias of 0.05: at
# these hidden sizes the families' 0.02 leaves the layers' share of the
# residual stream, and so a fault's mark on the logits, small; a tied head
# would make every token repeat its input, and a router's scores would lie
# within hundredths of a half.
ROWS = {row.arch: row for row in (
    Row("gpt2", {"f32": (_GPT2, dm.init_decoder_params(_GPT2, seed=7))},
        serve=(None, dict(vocab=31, layers=2, max_seq=48)),
        # K and V rows of 1024 float32: 128 positions are 1 MiB
        chunk=("gpt2-medium-serve.json", 1024, {"attention": 128})),
    Row("olmoe", _both(_OLMOE, olmoe.init_params, std=0.05),
        multi_atol=2e-4,
        serve=("olmoe-1b-7b-serve.json",
               dict(experts=8, experts_per_token=2, ffn=32)),
        chunk=("olmoe-1b-7b-serve.json", 2048, {"attention": 128})),
    Row("granite_hybrid",
        dict(_both(_GRANITE, granite_hybrid.init_params, std=0.3),
             group4=(_GRANITE_G4, granite_hybrid.init_params(
                 _GRANITE_G4, seed=3, std=0.3))),
        declines="recurrent_state", holds="slot", refusal="recurrent",
        entry=dict(state_name="ssm_state", state_path={4: "gather"}),
        # 8 KV heads of 64 in bfloat16: 2,048 B a position
        chunk=("granite-4.0-h-micro-serve.json", 2048, {"attention": 256})),
    Row("lfm2_moe", _both(_LFM2, lfm2_moe.init_params, std=0.3),
        declines="recurrent_state", holds="slot", refusal="recurrent",
        entry=dict(state_name="conv_state", slot_bytes=lambda cfg:
                   3 * 2 * 64 * (4 if cfg.dtype == "f32" else 2)),
        serve=("lfm2-24b-a2b-serve.json",
               dict(layer_types=("conv", "attention", "conv", "conv"),
                    dense_layers=1, experts=8, experts_per_token=2,
                    conv_taps=3, rope_theta=1e6)),
        chunk=("lfm2-24b-a2b-serve.json", 2048, {"attention": 256})),
    Row("exaone_moe",
        _both(_EXAONE, exaone_moe.init_params, std=0.3, bias_std=0.05),
        declines="window_layers", holds="ring", refusal="window layers",
        batch_dependent_bf16=True,
        entry=dict(attn_path="gather", window_path="gather"),
        serve=("k-exaone-236b-a23b-serve.json",
               dict(layer_types=_EXAONE.layer_types, dense_layers=1,
                    experts=16, experts_held=4, experts_per_token=4,
                    window=8, hidden=48, heads=8, rope_theta=1e6)),
        # 8 KV heads of 128: 524,288 B in 128 positions; a window layer's
        # chunk is its ring of 9 blocks
        chunk=("k-exaone-236b-a23b-serve.json", 12832,
               {"attention": 128, "window": 144})),
    Row("nemotron_h",
        _both(_NEMOTRON, nemotron_h.init_params, std=0.3, bias_std=0.05),
        declines="recurrent_state", holds="slot", refusal="recurrent",
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(state_name="ssm_state", experts_path={4: "einsum"},
                   state_path={4: "gather"}),
        serve=("nemotron-3-nano-30b-a3b-serve.json",
               dict(layer_types=_NEMOTRON.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    ssm_groups=2, hidden=48, ffn=24)),
        # 2 KV heads of 128: 1,024 B a position
        chunk=("nemotron-3-nano-30b-a3b-serve.json", 2048,
               {"attention": 512})),
    Row("kimi_linear",
        _both(_KIMI, kimi_linear.init_params, std=0.3, bias_std=0.05),
        declines="recurrent_state", holds="slot", refusal="recurrent",
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(state_name="kda_state", attn_path="gather",
                   experts_path={4: "einsum"}, state_path={4: "gather"}),
        serve=("kimi-linear-48b-a3b-serve.json",
               dict(layer_types=_KIMI.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    kda_heads=4, kda_head_dim=8, latent_rank=24,
                    latent_rope=8, hidden=48, ffn=24)),
        # one row of 640 bfloat16 a position: 1,280 B
        chunk=("kimi-linear-48b-a3b-serve.json", 12832, {"latent": 512})),
    # every layer pages (one latent row a token): nothing is declined
    Row("dots_vlm",
        _both(_DOTS, dots_vlm.init_params, std=0.3, bias_std=0.05),
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(attn_path="gather", experts_path={4: "einsum"},
                   state_path={}, declines=None),
        serve=("dots-vlm1-inst-serve.json",
               dict(layer_types=_DOTS.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    n_group=4, topk_group=2, q_rank=20, latent_rank=24,
                    latent_rope=8, hidden=48, ffn=24)),
        # one row of 640 bfloat16 a position: 1,280 B
        chunk=("dots-vlm1-inst-serve.json", 12832, {"latent": 512})),
    Row("smallthinker", _both(_SMALLTHINKER, smallthinker.init_params,
                              std=0.3),
        declines="window_layers", holds="ring", refusal="window layers",
        entry=dict(attn_path="gather", window_path="gather",
                   experts_path={4: "einsum"}),
        serve=("smallthinker-21b-a3b-serve.json",
               dict(layer_types=_SMALLTHINKER.layer_types, experts=16,
                    experts_per_token=3, window=8, hidden=48, heads=14,
                    kv_heads=2, rope_theta=1.5e6)),
        # 4 KV heads of 128: 2,048 B a position, so 256 positions a chunk; a
        # window layer's ring of 257 blocks is walked in such chunks
        chunk=("smallthinker-21b-a3b-serve.json", 25120,
               {"attention": 256, "window": 256})),
    # every layer pages (a latent row and an index key a token): nothing is
    # declined
    Row("glm_dsa",
        _both(_GLM, glm_dsa.init_params, std=0.3, bias_std=0.05),
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(attn_path="gather", experts_path={4: "einsum"},
                   state_path={}, declines=None),
        serve=("glm-5-serve.json",
               dict(layer_types=_GLM.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    q_rank=20, latent_rank=24, latent_rope=8, head_dim=12,
                    v_head_dim=16, index_heads=4, index_head_dim=16,
                    index_topk=8, hidden=48, ffn=24)),
        # the kernel walks the 2,048 chosen rows, gathered: rows of 640
        # bfloat16, 1,280 B a position
        chunk=("glm-5-serve.json", 25120, {"latent": 512})),
    # every sublayer pages (one latent row a token): nothing is declined
    Row("longcat_flash",
        _both(_LONGCAT, longcat_flash.init_params, std=0.3, bias_std=0.05),
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(attn_path="gather", experts_path={4: "einsum"},
                   state_path={}, declines=None),
        serve=("longcat-flash-chat-serve.json",
               dict(layer_types=_LONGCAT.layer_types, experts=16,
                    experts_held=4, expert_first=4, zero_experts=8,
                    experts_per_token=3, q_rank=20, latent_rank=24,
                    latent_rope=8, hidden=48, ffn=24, dense_ffn=64,
                    latent_q_scale=(48 / 20) ** 0.5,
                    latent_kv_scale=(48 / 24) ** 0.5)),
        # one row of 640 bfloat16 a position: 1,280 B
        chunk=("longcat-flash-chat-serve.json", 17472, {"latent": 512})),
    # a kda slot beside K/V pools
    Row("solar_open2",
        _both(_SOLAR, solar_open2.init_params, std=0.3, bias_std=0.05),
        declines="recurrent_state", holds="slot", refusal="recurrent",
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(state_name="kda_state", attn_path="gather",
                   experts_path={4: "einsum"}, state_path={4: "gather"}),
        serve=("solar-open2-250b-serve.json",
               dict(layer_types=_SOLAR.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    kda_heads=4, kda_head_dim=8, kda_neg_eigval=True,
                    heads=8, kv_heads=2, hidden=48, ffn=24)),
        # 8 KV heads of 128 in bfloat16: 4,096 B a position
        chunk=("solar-open2-250b-serve.json", 25664, {"attention": 128})),
    # every layer pages (one latent row a token): nothing is declined.
    # ``phi`` normal(0, 0.17): over 192 values the maps' inputs then have
    # the deviation the published 0.02 gives over 14,336 (2.4)
    Row("xing4",
        _both(_XING, xing4.init_params, std=0.3, bias_std=0.05, hc_std=0.17),
        multi_atol=1e-5, batch_dependent_bf16=True,
        entry=dict(attn_path="gather", experts_path={4: "einsum"},
                   state_path={}, declines=None),
        serve=("xing4.0-29b-a4b-serve.json",
               dict(layer_types=_XING.layer_types, experts=16,
                    experts_held=4, expert_first=4, experts_per_token=3,
                    q_rank=20, latent_rank=24, latent_rope=8, hidden=48,
                    ffn=24, hc_mult=4, hc_sinkhorn_iters=20)),
        # one row of 640 bfloat16 a position: 1,280 B
        chunk=("xing4.0-29b-a4b-serve.json", 3616, {"latent": 512})),
)}
assert tuple(ROWS) == dm.ARCHS


def cases(rows=None, dtypes=None):
    """``pytest.param``s (row, config id), the family's name in the case
    id, over ``rows`` (a filter on the row) and the config ids
    ``dtypes``."""
    return [pytest.param(row, key, id="%s-%s" % (row.arch, key))
            for row in ROWS.values() if rows is None or rows(row)
            for key in row.configs if dtypes is None or key in dtypes]


# -- the step, through the cache manager ----------------------------------------

def ring_blocks(cfg, block_size=BS):
    """The blocks of a window layers' ring (0 for a model with none)."""
    return dm.cache_config(cfg, block_size, 2, state_slots=2).window_ring


def run_paged(cfg, params, seqs, width=1, blocks=40, block_size=BS,
              after_step=None, ring_log=None, dirty=None, feed=False):
    """Every (prompt, n_decode) of ``seqs`` in its own lane through the
    paged step over the cache manager's pools, as the engine moves them:
    blocks from its allocator as a sequence grows, a state slot from its
    slot allocator, a ring advanced a position at a time; the prompt one
    token a step (``width`` a step through the multi-token step: whole
    chunks for a model with recurrent layers, whose state has no junk
    column to hide, and for the others what is known, the later columns
    frozen), then the step's own argmax.  ``after_step(kv_config, carry)
    -> carry`` may change what the pools hold between steps (a control);
    ``ring_log`` collects (lane, position, ring.lo, ring.hi, its table, the
    window blocks in use) a lane-step; ``dirty`` fills every state slot
    before the first step; ``feed`` takes the step as the engine compiles it
    (``make_packed_step``: the lanes' integers in one array, each lane's
    decoded token chosen on the device from the step before's).  -> (per lane (tokens fed, logits [n,
    vocab] of every position fed), per step the block's first extra: the
    routed counts, None for a block without experts)."""
    b = len(seqs)
    kv = dm.cache_config(cfg, block_size, blocks, state_slots=b + 2)
    cache = kvc.PagedKVCache(kv)
    if dirty is not None:
        pools, state = kv.groups(cache.carry())
        cache.replace_carry(tuple(
            a for g in pools + [kv.latent_pools(cache.carry()),
                             kv.index_pools(cache.carry())]
            + kv.window_groups(cache.carry()) for a in g)
            + tuple(jnp.full_like(a, dirty) for g in state for a in g))
    make = dm.make_packed_step(cfg, kv, b) if feed \
        else dm.make_paged_step(cfg, kv) if width == 1 \
        else dm.make_paged_step_multi(cfg, kv, width)
    step = jax.jit(make, donate_argnums=(0,))
    jparams = as_jnp(params)
    tables = np.full((b, cfg.max_seq // block_size), -1, np.int32)
    columns, ncols = dm.lane_columns(kv, tables.shape[1])
    held = [[] for _ in seqs]
    # a slot is taken at a sequence's first step, as the engine takes it
    slots = np.zeros(b, np.int32) if cache.slots else None
    rings = [cache.new_ring() for _ in seqs] if cfg.window_layers else None
    prev = jnp.zeros(b, jnp.int32)
    whole = bool(cfg.recurrent_layers) and width > 1
    total = [len(p) + n for p, n in seqs]
    fed = [list(p) for p, _ in seqs]          # grows by the step's argmax
    logits = [[] for _ in seqs]
    routed = []
    while any(len(lg) < t for lg, t in zip(logits, total)):
        tok, pos, lens = (np.zeros((b, width), np.int32) for _ in range(3))
        cols = []
        for i in range(b):
            at = len(logits[i])
            n = max(min(width, len(fed[i]) - at, total[i] - at), 0)
            cols.append(0 if whole and n < width else n)
            if not cols[i]:
                continue
            assert cache.ensure_table(tables[i], held[i], at + n)
            if slots is not None and not at:
                slots[i] = cache.slots.take()
            if rings:
                cache.advance_ring(rings[i], at + 1)
                if ring_log is not None:
                    ring_log.append((i, at, rings[i].lo, rings[i].hi,
                                     rings[i].table.copy(),
                                     cache.window_allocator.in_use))
            for j in range(width):
                jj = min(j, n - 1)
                tok[i, j], pos[i, j] = fed[i][at + jj], at + jj
                lens[i, j] = at + jj + 1
        live = np.asarray(cols) > 0
        assert live.any(), "no lane can feed a whole chunk"
        args = dict(tok=tok, pos=pos,
                    tables=np.where(live[:, None], tables, -1), lens=lens)
        if width == 1:
            args.update(tok=tok[:, 0], pos=pos[:, 0], lens=lens[:, 0])
        if slots is not None:
            args["slot"] = np.where(live, slots, 0).astype(np.int32)
        if rings:
            args["ring"] = np.stack([
                r.table if on else np.full_like(r.table, -1)
                for r, on in zip(rings, live)])
        if feed:
            # past its prompt a lane feeds the token it made a step ago
            args["src"] = np.asarray([
                i if on and len(lg) >= len(p) else -1
                for i, (on, lg, (p, _n)) in enumerate(zip(live, logits, seqs))])
            args["tok"] = np.where(args["src"] < 0, args["tok"], 0)
            lanes = np.zeros((b, ncols), np.int32)
            for name, value in args.items():
                lanes[:, columns[name]] = value.reshape(b, -1)
            args = dict(prev=prev, lanes=lanes)
        carry, prev, lg, *extras = step(cache.carry(), jparams,
                                        *args.values())
        cache.replace_carry(after_step(kv, carry) if after_step else carry)
        routed.append(np.asarray(extras[0]) if extras else None)
        nxt = np.asarray(prev).reshape(b, width)
        lg = np.asarray(lg).reshape(b, width, -1)
        for i, n in enumerate(cols):
            logits[i].extend(lg[i, :n])
            if n and len(logits[i]) == len(fed[i]) < total[i]:
                fed[i].append(int(nxt[i, n - 1]))
    if rings:
        for ring in rings:
            cache.release_ring(ring)
        assert cache.window_allocator.in_use == 0
    return [(f, np.stack(lg) if lg else None)
            for f, lg in zip(fed, logits)], routed


def generate(cfg, params, prompt, n, **kw):
    """``unpaged_generate`` at the lengths the paged step gathers at the
    tests' block size (the bitwise comparison wants them)."""
    ring = ring_blocks(cfg)
    return dm.unpaged_generate(cfg, params, prompt, n, pad_len=cfg.max_seq,
                               ring_len=ring * BS if ring else None, **kw)


def alone(cfg, params, prompt, n):
    """The tokens of the sequence alone, through the unpaged loop."""
    return np.asarray(generate(cfg, params, prompt, n), np.int32)


def teacher_forced(cfg, params, fed):
    """The unpaged step's logits at every position of ``fed``."""
    ring = ring_blocks(cfg) * BS or None
    step = jax.jit(dm.make_unpaged_step(cfg, cfg.max_seq, ring))
    kv = dm._unpaged_carry(cfg, 1, cfg.max_seq, ring)
    rows = []
    for pos, tok in enumerate(fed):
        kv, _nxt, lg = step(kv, as_jnp(params), jnp.asarray([tok]),
                            jnp.asarray([pos]), jnp.asarray([pos + 1]))
        rows.append(np.asarray(lg[0]))
    return np.stack(rows)


def check_shares_add_up(cfg, params, block, ref, ref_config, held_names,
                        atol, shares=8):
    """One routed layer of ``cfg`` (16 experts) cut in ``shares`` shares of
    as many experts each: each routes over all 16 and computes its own
    experts' part (``block.routed_part``, equal to the reference given the
    same share, to ``atol[0]``); their sum and the shared expert's output
    counted once equal the uncut reference's layer (to ``atol[1]``), and
    neither a share alone nor the shared expert counted a share does."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(12, cfg.hidden), jnp.float32)
    live = jnp.ones(12, bool)
    whole = {k[3:]: jnp.asarray(v) for k, v in params.items()
             if k.startswith("l0_")}
    with jax.default_matmul_precision("highest"):
        gates, _margin = ref.gates_of(ref_config(cfg), whole, x)
        want = np.asarray(ref.routed_sum(ref_config(cfg), whole, x, gates)
                          + ref.shared_out(ref_config(cfg), whole, x))
        parts = []
        for share in range(shares):
            mine = cfg.replace(experts_held=16 // shares,
                               expert_first=16 // shares * share)
            held = dict(whole, **{w: whole[w][mine.held_experts]
                                  for w in held_names})
            part, chosen = block.routed_part(mine, held.__getitem__, x, live)
            assert chosen.shape == (12, 16) \
                and (chosen.sum(axis=1) == cfg.experts_per_token).all()
            np.testing.assert_allclose(
                np.asarray(part), np.asarray(ref.routed_sum(
                    ref_config(mine), held, x, gates)), atol=atol[0])
            parts.append(np.asarray(part))
        shared = np.asarray(block.shared_part(whole.__getitem__, x))
    np.testing.assert_allclose(sum(parts) + shared, want, atol=atol[1])
    assert np.abs(parts[0] + shared - want).max() > 1e-2
    assert np.abs(sum(parts) + shares * shared - want).max() > 1e-2


# -- the engine -----------------------------------------------------------------

@contextlib.contextmanager
def flags(**kv):
    kv = {"FLAGS_" + k: v for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


def engine(cfg, params, kv_blocks, buckets="4", name="m", start=True, **kw):
    """A decode engine serving one model (a (config, params) pair, or a
    bundle's directory with ``params`` None) at the tests' block size."""
    with flags(kv_block_size=BS):
        e = DecodeEngine(buckets=buckets, deadline_ms=60000.0)
        e.add_model(name, (cfg, params) if params is not None else cfg,
                    kv_blocks=kv_blocks, **kw)
    return e.start() if start else e


def counters(prefix):
    return {k: v for k, v in _tm.snapshot()["counters"].items()
            if k.startswith(prefix)}


def step_spans(telemetry_dir, model=None):
    """The attributes of the ``serving.decode_step`` spans flushed under
    ``telemetry_dir`` (of ``model`` alone, if given)."""
    records = [json.loads(line) for fn in sorted(os.listdir(telemetry_dir))
               if fn.startswith("trace-")
               for line in open(os.path.join(telemetry_dir, fn))
               if line.strip()]
    return [s["attrs"] for s in records
            if s.get("name") == "serving.decode_step"
            and model in (None, s["attrs"].get("model"))]


def prewarm_events(telemetry_dir):
    with open(os.path.join(telemetry_dir, "steps.jsonl")) as fp:
        return [ev for ev in map(json.loads, fp)
                if ev["ev"] == "serving_prewarm"]


def save_demo_decoder(dirname, config=None):
    """``tools/serve.py``'s demo bundle (the tool is no package)."""
    serve = load("tools", "serve.py")
    return serve.save_demo_decoder(dirname, config=config)
