"""Tiny pure-JAX transformer decoder for the autoregressive serving path.

The encoder serving stack (PR 10) runs Program-built models through
``AnalysisPredictor``; autoregressive decode instead needs a *step
function over a donated KV carry* — one token in, one token out, cache
updated in place on device.  Threading per-token cache scatters through
the Program op set would rebuild half an interpreter for no modeling
win, so the decode scenario carries its own minimal decoder (pre-LN
transformer: embed + learned positions, per-layer MHA + GELU MLP, tied
vocab head kept separate for clarity) and plugs into the SAME executor
machinery the Program path uses: ``core.executor.CarriedStepFn`` AOT-
compiles the step per lane bucket with tier-B disk persistence and finds
it again by that bucket (the engine's form of the step,
``make_packed_step``, takes the lanes' integers in one array), and the
step's attention is ``pallas_kernels.paged_attention``: on a TPU a kernel
that reads each lane's live KV blocks from the pool where they lie, and
elsewhere (the CPU tier, the int8 residency) a gather of the padded table
into ``masked_attention``, chosen by what the shapes and the backend are.

The decoder's block is its family's, picked by ``DecoderConfig.arch``: a
family is one module, ``models/<arch>.py`` (``ARCHS`` lists them), which
holds the block (``token_logits``), seeded weights (``init_params``) and a
declaration of what the block computes (``FAMILY``, a
``models/decoder_family.py`` ``DecoderFamily``: its kinds of layer, its
weight dtypes, whether and where it routes, ...).  This file names no
family beside that list: ``DecoderConfig`` refuses by the declaration, and
adding a family is its module, its name in ``ARCHS`` and its row in
``tests/decoder_families.py``.  Layers are of seven kinds (``LAYER_KINDS``):
``attention`` (multi-head, or grouped-query with fewer KV heads than query
heads, so pools ``kv_heads * head_dim`` wide), which keeps K and V a token;
``window``, attention over the last ``cfg.window`` positions only, which
keeps K and V in a ring of blocks of its own pools and gives back what
leaves the window; ``mamba``, a Mamba-2 state-space mixer, which keeps a
convolution window and a recurrent state a sequence; ``conv``, a gated
short convolution, which keeps a window and no state; ``experts``, a
layer that is a feed-forward alone (routed experts beside a shared one),
which keeps nothing: the cache manager gives it neither pool nor slot;
``kda``, a Kimi Delta Attention mixer, which keeps three convolution
windows and a matrix state a head a sequence, moved by a gated delta rule
(``pallas_kernels.kda_update``); and ``latent``, multi-head latent
attention served absorbed, which keeps ONE row a token (a compressed K/V
and the key's shared part, ``latent_rank + latent_rope`` values) in a pool
of its own width on the global block tables, read as key and as value both
(``paged_attention.latent_attention``); a latent layer that *selects*
(``cfg.index_topk``) keeps an indexer's key a token in a second pool beside
it and attends the positions the indexer scores highest and no others
(``paged_attention.index_scores``, ``choose``,
``selected_latent_attention``).  A hybrid block names its layers'
kinds one by one, two or three of them in one model (its
``FAMILY.kinds``), in any pairing: a ``kda`` slot lies beside latent pools
in one model's cache (``kimi_linear``) and beside K/V pools in another's
(``solar_open2``, whose ``kda_neg_eigval`` also widens the delta rule's
``beta`` to (0, 2)), as a ``mamba`` slot lies beside K/V pools.  Every step builder below serves every
family through one contract (``_block``), so there is one paged step, one
multi-token step, one draft rollout and one unpaged reference, whatever the
block.

Two step builders share every layer of math through two callbacks, which
own what a layer keeps between tokens: ``attend`` the K and V of an
attention layer, ``recur`` (``_Recurrent``) the convolution window and, for
a kind that has one, the state of a recurrent layer (``_state_shapes`` says
what a kind keeps):

* ``make_paged_step``   — writes this token's K/V rows into the layer's
  own pools of the paged cache (``[num_blocks, block_size, KH * D]``, block
  ids steered by the per-lane block table) and attends through
  ``paged_attention`` over them (scope ``kv_read`` where the kernel
  serves, ``kv_gather`` where the table is gathered); a recurrent layer's
  window and state are read from and written to the slot the step is told
  for each lane (``state_slots``), the window through ``push_windows`` (a
  slot whole tiles, XLA's gather and scatter), the state through
  ``pallas_kernels.ssm_update.state_update`` or, for ``kda`` layers,
  ``pallas_kernels.kda_update.state_update`` (on a TPU a kernel that moves
  each slot in place, elsewhere gather, update, scatter).
* ``make_unpaged_step`` — the reference: contiguous per-lane K/V
  ``[L, B, S, KH, D]`` updated at ``pos`` and attended via the same
  ``masked_attention`` core, window and state a row a lane moved by the
  same ``ssm_update.advance`` (``kda_update.advance``).

Because the gather path and the unpaged loop feed bitwise-identical K/V
values into the identical attention/MLP expressions at identical shapes,
paged decode is bitwise-equal to the unpaged loop on the CPU tier — the
acceptance bar ``unpaged_generate`` exists to prove.  The kernel computes
the same mathematics at the same precision with the softmax's sums in
another order, so there the bar is the same tokens and logits to 1e-5.
"""

import importlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry as _tm
from ..models import hyper_connections as _hc
from ..pallas_kernels import kda_update as _kda
from ..pallas_kernels import moe_experts as _moe
from ..pallas_kernels import paged_attention as _pa
from ..pallas_kernels import ssm_update as _ssm
from ..pallas_kernels.paged_attention import choose, chosen_mask, \
    dense_index_scores, gather_blocks, index_scores, latent_attention, \
    masked_attention, masked_latent, paged_attention, \
    selected_latent_attention
from . import kv_cache as _kv

__all__ = ["DecoderConfig", "init_decoder_params", "save_decoder",
           "load_decoder", "is_decoder_dir", "has_draft", "load_draft",
           "truncate_decoder", "laid_out", "attention_path", "experts_path",
           "state_update_path", "state_update_columns", "experts_chunk",
           "experts_gate", "StepAccount", "push_windows",
           "make_paged_step",
           "make_fed_step", "make_paged_step_multi",
           "make_draft_rollout", "make_unpaged_step", "unpaged_generate",
           "cache_config"]


# the families, a module each: ``models/<arch>.py``
ARCHS = ("gpt2", "olmoe", "granite_hybrid", "lfm2_moe", "exaone_moe",
         "nemotron_h", "kimi_linear", "dots_vlm", "smallthinker", "glm_dsa",
         "longcat_flash", "solar_open2", "xing4")
LAYER_KINDS = ("attention", "mamba", "conv", "window", "experts", "kda",
               "latent")
# recurrent kind -> the name its slot goes by in spans, gauges and counters
# (``ssm_state_lanes``, ``conv_state_bytes{model}``, ...)
STATE_NAMES = {"mamba": "ssm_state", "conv": "conv_state",
               "kda": "kda_state"}


# what ``DecoderConfig.rope_scaling`` holds: the source's YaRN group
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def yarn_mscale(factor, mscale):
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1 for a
    context not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _model(arch):
    """A family's module, ``models/<arch>.py``: its block
    (``token_logits``), ``init_params`` and its declaration (``FAMILY``)."""
    return importlib.import_module("..models." + arch, __package__)


def _declaring(field):
    """The families whose declaration says ``field``, as a refusal names
    them."""
    return "|".join(a for a in ARCHS if getattr(_model(a).FAMILY, field))


class DecoderConfig:
    """What a decode step is built from.  ``arch`` picks the block:
    ``gpt2`` is the pre-LN MHA + GELU block of ``models/gpt2.py`` (learned
    positions), ``olmoe`` the routed-expert block of ``models/olmoe.py``
    (RMSNorm, Q/K norm, RoPE, ``experts`` SiLU-gated experts of width
    ``ffn``, ``experts_per_token`` of them a token), ``granite_hybrid`` the
    block of ``models/granite_hybrid.py``: layers of two kinds named one
    by one in ``layer_types`` (``attention`` | ``mamba``), grouped-query
    attention (``heads`` query heads over ``kv_heads`` KV heads, no
    position encoding) beside Mamba-2 mixers of ``ssm_heads`` heads of
    ``ssm_head_dim``, state ``ssm_state`` and a causal convolution of
    ``ssm_conv`` taps, a gated MLP of width ``ffn``, a tied head, and the
    family's four multipliers (``embedding_multiplier`` on the embedding,
    ``residual_multiplier`` on every branch, ``attention_multiplier`` as
    the scale of the scores, logits divided by ``logits_scaling``).
    ``lfm2_moe`` is the block of ``models/lfm2_moe.py``: ``layer_types`` of
    ``attention`` | ``conv``, grouped-query attention with per-head Q/K norm
    and RoPE beside gated short convolutions of ``conv_taps`` taps over the
    hidden width, and a feed-forward by layer: the first ``dense_layers``
    layers a gated MLP of width ``dense_ffn``, the rest ``experts`` experts
    of width ``ffn`` routed by sigmoid scores (a bias selects
    ``experts_per_token``, the gates are renormalised and scaled by
    ``routed_scaling``), and a tied head.  ``exaone_moe`` is the block of
    ``models/exaone_moe.py``: ``layer_types`` of ``attention`` | ``window``
    (the latter attends the last ``window`` positions and alone is rotated),
    grouped-query attention with per-head Q/K norm, norms on each sublayer's
    output, ``dense_layers`` leading gated MLPs and then experts routed as
    ``lfm2_moe``'s with a shared expert of width ``shared_ffn`` beside them,
    and an untied head.  It may hold a *share* of each routed layer: the
    router scores all ``experts``, the weights are those of the
    ``experts_held`` experts from ``expert_first`` on (0: all of them), and
    what the absent ones would add is left out.  Its stream may be
    narrower than its query heads together (``hidden_size``).
    ``nemotron_h`` is the block of ``models/nemotron_h.py``: every layer ONE
    pre-norm sublayer, of a kind named in ``layer_types`` (``mamba`` |
    ``attention`` | ``experts``): Mamba-2 mixers as Granite's with B and C
    in ``ssm_groups`` groups of heads, grouped-query attention with no
    position encoding, and experts of two matrices (``relu(x @ up)^2 @
    down``, width ``ffn``) routed as ``exaone_moe``'s beside a shared one of
    width ``shared_ffn``, with an untied head.  It too may hold a share and
    have a stream of its own width; its routed layers are its ``experts``
    layers, wherever they lie.  ``kimi_linear`` is the block of
    ``models/kimi_linear.py``: ``layer_types`` of ``kda`` | ``latent``: Kimi
    Delta Attention mixers of ``kda_heads`` heads of ``kda_head_dim`` (keys
    and values alike) behind depthwise convolutions of ``kda_conv`` taps,
    beside latent attention of ``heads`` heads (``head_dim`` their own key
    and value width, ``latent_rope`` key values shared by all heads,
    ``latent_rank`` the compressed K/V's width) with no position encoding;
    ``dense_layers`` leading gated MLPs and then ``exaone_moe``'s routed
    layer (the share it may hold included) under pre-norms, an untied head
    and a stream of its own width.  ``dots_vlm`` is the block of
    ``models/dots_vlm.py``: every layer ``latent``, the query compressed to
    ``q_rank`` values and normed before its up-projection, the row's shared
    key and the query's last ``latent_rope`` values a head rotated by
    position with YaRN's frequencies (``rope_theta``, ``rope_scaling``: the
    source's group, whose ``mscale_all_dim`` also scales the scores), and
    ``kimi_linear``'s feed-forwards with a router that keeps ``topk_group``
    of ``n_group`` groups of experts before it chooses experts.
    ``smallthinker`` is the block of ``models/smallthinker.py``:
    ``exaone_moe``'s two kinds of attention (``window`` layers rotated,
    ``attention`` layers not) with no Q/K norm under pre-norms, and in every
    layer ``experts`` ReLU-gated experts of width ``ffn`` chosen by a softmax
    router that reads the attention's input; no shared expert, no dense
    lead, no share; an untied head and a stream of its own width.
    ``glm_dsa`` is the block of ``models/glm_dsa.py``: ``dots_vlm``'s with
    plain rotation, a head's values ``v_head_dim`` wide beside its
    ``head_dim`` own key values, and latent layers that select: an indexer
    of ``index_heads`` heads of ``index_head_dim`` scores every cached
    position and the attention reads the ``index_topk`` best.
    ``longcat_flash`` is the block of ``models/longcat_flash.py``: a *pair*
    of sublayers a block (``layers`` and ``layer_types`` count sublayers,
    every one ``latent``), each ``dots_vlm``'s mixer with plain rotation,
    the projected query times ``latent_q_scale`` and the normed compressed
    K/V times ``latent_kv_scale``, and a gated MLP of width ``dense_ffn``;
    one routed part a pair, read behind the first sublayer's mixer and added
    behind the second's MLP, whose softmax router scores ``experts +
    zero_experts`` outputs, chooses ``experts_per_token`` of them by a bias
    that never weighs, and weighs the chosen by ``routed_scaling`` times
    their probability, not renormalised: a chosen identity expert (one of
    the last ``zero_experts``) adds the token's own input times its gate.
    No shared expert, no dense lead; it may hold a share.
    ``solar_open2`` is the block of ``models/solar_open2.py``:
    ``layer_types`` of ``kda`` | ``attention``: ``kimi_linear``'s KDA mixer
    whose delta rule allows negative eigenvalues (``kda_neg_eigval``:
    ``beta`` twice the sigmoid, in (0, 2); refused for a family that does
    not declare it) beside grouped-query attention over K/V pools with no
    position encoding, no Q/K norm and a sigmoid gate, a value a head
    channel, on the heads' output; every layer routed as ``exaone_moe``'s
    beside a shared expert, no dense lead; an untied head, a stream of its
    own width, and the share it may hold.  ``xing4`` is the block of
    ``models/xing4.py``: ``dots_vlm``'s mixer and feed-forwards round a
    residual path of ``hc_mult`` streams a token, mixed round every sublayer
    by manifold-constrained hyper-connections: three maps made from the
    streams themselves, the residual one normalised ``hc_sinkhorn_iters``
    times by rows and columns (``hc_eps`` in each sum) from entries clipped
    to ``hc_clamp`` before the exponential (``models/hyper_connections.py``;
    refused for a family that does not declare ``residual_streams``).

    ``kv_heads`` None means ``heads`` (multi-head); ``layer_types`` None
    means ``layers`` attention layers.  ``dtype`` is the weights' (``f32``
    | ``bf16``); ``kv_dtype`` the cache's residency (``f32`` | ``bf16`` |
    ``int8``), and None leaves it to ``FLAGS_kv_cache_dtype``: a bf16
    model keeps a bf16 cache."""

    __slots__ = ("vocab", "layers", "heads", "head_dim", "ffn", "max_seq",
                 "arch", "dtype", "kv_dtype", "experts",
                 "experts_per_token", "rope_theta", "norm_eps",
                 "kv_heads", "layer_types", "ssm_heads", "ssm_head_dim",
                 "ssm_state", "ssm_conv", "embedding_multiplier",
                 "residual_multiplier", "attention_multiplier",
                 "logits_scaling", "conv_taps", "dense_layers", "dense_ffn",
                 "routed_scaling", "window", "experts_held", "expert_first",
                 "shared_ffn", "hidden_size", "ssm_groups", "kda_heads",
                 "kda_head_dim", "kda_conv", "latent_rank", "latent_rope",
                 "q_rank", "n_group", "topk_group", "rope_scaling",
                 "v_head_dim", "index_heads", "index_head_dim",
                 "index_topk", "zero_experts", "latent_q_scale",
                 "latent_kv_scale", "kda_neg_eigval", "hc_mult",
                 "hc_sinkhorn_iters", "hc_eps", "hc_clamp")

    def __init__(self, vocab, layers, heads, head_dim, ffn=None,
                 max_seq=64, arch="gpt2", dtype="f32", kv_dtype=None,
                 experts=0, experts_per_token=0, rope_theta=10000.0,
                 norm_eps=1e-5, kv_heads=None, layer_types=None,
                 ssm_heads=0, ssm_head_dim=0, ssm_state=0, ssm_conv=0,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 attention_multiplier=None, logits_scaling=1.0,
                 conv_taps=0, dense_layers=0, dense_ffn=0,
                 routed_scaling=1.0, window=0, experts_held=0,
                 expert_first=0, shared_ffn=0, hidden_size=None,
                 ssm_groups=1, kda_heads=0, kda_head_dim=0, kda_conv=0,
                 latent_rank=0, latent_rope=0, q_rank=0, n_group=1,
                 topk_group=1, rope_scaling=None, v_head_dim=None,
                 index_heads=0, index_head_dim=0, index_topk=0,
                 zero_experts=0, latent_q_scale=1.0, latent_kv_scale=1.0,
                 kda_neg_eigval=False, hc_mult=0, hc_sinkhorn_iters=0,
                 hc_eps=0.0, hc_clamp=None):
        if arch not in ARCHS:
            raise ValueError("decoder arch must be %s: %r"
                             % ("|".join(ARCHS), arch))
        if dtype not in ("f32", "bf16"):
            raise ValueError("decoder dtype must be f32|bf16: %r" % (dtype,))
        family = _model(arch).FAMILY
        if dtype not in family.dtypes:
            raise ValueError("the %s block is served in %s"
                             % (arch, "|".join(family.dtypes)))
        if family.routes \
                and not 0 < int(experts_per_token) <= int(experts):
            raise ValueError("%s wants 0 < experts_per_token <= experts, "
                             "got %r of %r"
                             % (arch, experts_per_token, experts))
        self.vocab = int(vocab)
        self.layers = int(layers)
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.ffn = int(ffn if ffn is not None else 4 * heads * head_dim)
        self.max_seq = int(max_seq)
        self.arch = arch
        self.dtype = dtype
        self.kv_dtype = kv_dtype if kv_dtype is not None \
            else ("bf16" if dtype == "bf16" else None)
        self.experts = int(experts)
        self.experts_per_token = int(experts_per_token)
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(norm_eps)
        self.kv_heads = int(kv_heads if kv_heads is not None else heads)
        self.layer_types = tuple(
            layer_types if layer_types is not None
            else ("attention",) * self.layers)
        self.ssm_heads = int(ssm_heads)
        self.ssm_head_dim = int(ssm_head_dim)
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_groups = int(ssm_groups)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = None if attention_multiplier is None \
            else float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.conv_taps = int(conv_taps)
        self.dense_layers = int(dense_layers)
        self.dense_ffn = int(dense_ffn)
        self.routed_scaling = float(routed_scaling)
        self.window = int(window)
        self.experts_held = int(experts_held) or self.experts
        self.expert_first = int(expert_first)
        self.shared_ffn = int(shared_ffn)
        self.hidden_size = None if hidden_size is None else int(hidden_size)
        self.kda_heads = int(kda_heads)
        self.kda_head_dim = int(kda_head_dim)
        self.kda_conv = int(kda_conv)
        self.latent_rank = int(latent_rank)
        self.latent_rope = int(latent_rope)
        self.q_rank = int(q_rank)
        self.n_group = int(n_group)
        self.topk_group = int(topk_group)
        self.rope_scaling = None if rope_scaling is None \
            else {k: float(rope_scaling[k]) for k in YARN_KEYS}
        self.v_head_dim = int(v_head_dim or head_dim)
        self.index_heads = int(index_heads)
        self.index_head_dim = int(index_head_dim)
        self.index_topk = int(index_topk)
        self.zero_experts = int(zero_experts)
        self.latent_q_scale = float(latent_q_scale)
        self.latent_kv_scale = float(latent_kv_scale)
        self.kda_neg_eigval = bool(kda_neg_eigval)
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.hc_clamp = None if hc_clamp is None \
            else tuple(float(x) for x in hc_clamp)
        if self.hidden_size not in (None, self.heads * self.head_dim) \
                and not family.own_stream_width:
            raise ValueError("the %s block's stream is heads * head_dim "
                             "wide (%s may say otherwise)"
                             % (arch, _declaring("own_stream_width")))
        if len(self.layer_types) != self.layers or any(
                k not in LAYER_KINDS for k in self.layer_types):
            raise ValueError("layer_types must name each of the %d layers "
                             "%s: %r" % (self.layers, "|".join(LAYER_KINDS),
                                         layer_types))
        if self.heads % self.kv_heads:
            raise ValueError("heads %d must be a multiple of kv_heads %d"
                             % (self.heads, self.kv_heads))
        if any(k not in family.kinds for k in self.layer_types):
            raise ValueError("layer_types: the %s block's layers are %s: %r"
                             % (arch, "|".join(family.kinds), layer_types))
        if not family.grouped_query and self.kv_heads != self.heads:
            raise ValueError("the %s block is multi-head attention in every "
                             "layer" % arch)
        if self.ssm_layers and min(self.ssm_heads, self.ssm_head_dim,
                                   self.ssm_state, self.ssm_conv - 1) < 1:
            raise ValueError("mamba layers want ssm_heads, ssm_head_dim, "
                             "ssm_state >= 1 and ssm_conv >= 2")
        if self.ssm_groups < 1 or (self.ssm_layers
                                   and self.ssm_heads % self.ssm_groups):
            raise ValueError("ssm_groups %d must divide ssm_heads %d"
                             % (self.ssm_groups, self.ssm_heads))
        if self.conv_layers and self.conv_taps < 2:
            raise ValueError("conv layers want conv_taps >= 2")
        if self.kda_layers and min(self.kda_heads, self.kda_head_dim,
                                   self.kda_conv - 1) < 1:
            raise ValueError("kda layers want kda_heads, kda_head_dim >= 1 "
                             "and kda_conv >= 2")
        if self.latent_layers and min(self.latent_rank,
                                      self.latent_rope) < 1:
            raise ValueError("latent layers want latent_rank and "
                             "latent_rope >= 1")
        if (self.q_rank or self.rope_scaling) and not (
                family.rotated_latent and self.latent_rope % 2 == 0):
            raise ValueError(
                "q_rank and rope_scaling are for the %s blocks' latent "
                "layers, whose latent_rope values turn in pairs: %r, %r"
                % (_declaring("rotated_latent"), q_rank, rope_scaling))
        if self.v_head_dim != self.head_dim and set(self.layer_types) - {
                "latent", "kda", "experts"}:
            raise ValueError(
                "v_head_dim is a latent layer's: a head of attention and "
                "window layers has values as wide as its keys: %r for %r"
                % (v_head_dim, head_dim))
        indexer = (self.index_heads, self.index_head_dim, self.index_topk)
        selects = all(x > 0 for x in indexer)
        if bool(family.selects) != selects or any(indexer) and not (
                selects and self.q_rank and self.latent_layers
                and self.index_head_dim >= self.latent_rope):
            raise ValueError(
                "index_heads, index_head_dim and index_topk >= 1 are for "
                "the %s blocks and for no other, whose latent layers select "
                "by an indexer that reads the compressed query (q_rank) and "
                "turns latent_rope of a head's values: %r"
                % (_declaring("selects"), indexer))
        if not 1 <= self.topk_group <= self.n_group or (
                self.n_group > 1 and (
                    not family.grouped_router
                    or self.experts % self.n_group
                    or self.experts // self.n_group < 2)):
            raise ValueError(
                "the %s blocks' routers keep topk_group of n_group groups "
                "of two experts or more: %r of %r over %d experts"
                % (_declaring("grouped_router"), topk_group, n_group,
                   self.experts))
        if not 0 <= self.dense_layers <= self.layers or (
                self.dense_layers and (not family.dense_lead
                                       or self.dense_ffn < 1)):
            raise ValueError(
                "dense_layers leads the %s blocks' %d layers with a "
                "gated MLP of width dense_ffn >= 1: %r of width %r"
                % (_declaring("dense_lead"), self.layers, dense_layers,
                   dense_ffn))
        if self.window_layers and self.window < 1:
            raise ValueError("window layers want window >= 1")
        if not 0 <= self.expert_first \
                <= self.experts - self.experts_held or (
                    self.experts_held != self.experts
                    and not family.holds_share):
            raise ValueError(
                "the %s blocks may hold experts [expert_first, "
                "expert_first + experts_held) of %d: %r from %r"
                % (_declaring("holds_share"), self.experts, experts_held,
                   expert_first))
        if self.layers % self.layers_a_block:
            raise ValueError(
                "the %s block is a pair of sublayers: layers counts "
                "sublayers, two a pair, got %d" % (arch, self.layers))
        if self.zero_experts < 0 or (self.zero_experts
                                     and not family.zero_experts):
            raise ValueError(
                "the %s blocks' routers score zero_experts identity experts "
                "beside their experts: %r"
                % (_declaring("zero_experts"), zero_experts))
        if (self.latent_q_scale, self.latent_kv_scale) != (1.0, 1.0) \
                and not (family.scaled_latent and self.latent_layers):
            raise ValueError(
                "latent_q_scale and latent_kv_scale are for the %s blocks' "
                "latent layers: %r, %r" % (_declaring("scaled_latent"),
                                           latent_q_scale, latent_kv_scale))
        if self.kda_neg_eigval and not family.neg_eigval:
            raise ValueError(
                "kda_neg_eigval (beta in (0, 2)) is for the %s blocks' kda "
                "layers: %r" % (_declaring("neg_eigval"), kda_neg_eigval))
        streams = (self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
                   self.hc_clamp)
        if any(streams) != bool(family.residual_streams) or any(streams) \
                and not (self.hc_mult >= 2 and self.hc_sinkhorn_iters >= 1
                         and self.hc_eps > 0 and self.hc_clamp is not None
                         and len(self.hc_clamp) == 2
                         and self.hc_clamp[0] < self.hc_clamp[1]):
            raise ValueError(
                "hc_mult >= 2 residual streams, mixed by maps normalised "
                "hc_sinkhorn_iters >= 1 times with hc_eps > 0 from entries "
                "clipped to hc_clamp (min < max), are for the %s blocks and "
                "for no other: %r" % (_declaring("residual_streams"),
                                      streams))
        if self.shared_ffn and not family.shared_expert:
            raise ValueError(
                "the %s blocks pass every token through a shared expert of "
                "width shared_ffn: %r" % (_declaring("shared_expert"),
                                          shared_ffn))

    @property
    def hidden(self):
        """The residual stream's width: ``heads * head_dim`` unless the
        model says otherwise (``hidden_size``: a family that declares
        ``own_stream_width`` projects a narrower stream up to its query
        heads)."""
        return self.hidden_size or self.heads * self.head_dim

    def _of_kind(self, kind):
        return tuple(l for l, k in enumerate(self.layer_types) if k == kind)

    @property
    def attn_layers(self):
        """Indices of the layers that hold K and V, in order."""
        return self._of_kind("attention")

    @property
    def window_layers(self):
        """Indices of the layers that hold K and V of the last ``window``
        positions only, in order."""
        return self._of_kind("window")

    @property
    def ssm_layers(self):
        """Indices of the Mamba-2 layers (a window and a state), in order."""
        return self._of_kind("mamba")

    @property
    def conv_layers(self):
        """Indices of the short-convolution layers (a window), in order."""
        return self._of_kind("conv")

    @property
    def kda_layers(self):
        """Indices of the Kimi Delta Attention layers (three convolution
        windows and a matrix state), in order."""
        return self._of_kind("kda")

    @property
    def latent_layers(self):
        """Indices of the layers that hold one latent row a token, in
        order."""
        return self._of_kind("latent")

    @property
    def state_layers(self):
        """Indices of the recurrent layers that keep a state beside their
        window (``mamba`` or ``kda``: a model's are of one kind), in
        order."""
        return self.ssm_layers or self.kda_layers

    @property
    def recurrent_layers(self):
        """Indices of the layers that keep, a sequence, something constant
        in its length (a slot of the cache): the model's ``mamba``,
        ``conv`` or ``kda`` layers, in order."""
        return tuple(l for l, k in enumerate(self.layer_types)
                     if k in STATE_NAMES)

    @property
    def state_name(self):
        """What the recurrent layers' slot goes by in telemetry
        (``ssm_state`` | ``conv_state`` | ``kda_state``); None for a model
        with no such layer."""
        return next((STATE_NAMES[k] for k in self.layer_types
                     if k in STATE_NAMES), None)

    @property
    def routed_layers(self):
        """Indices of the layers whose feed-forward is routed experts, in
        order: the rows of the step's ``routed`` counts.  A block that names
        ``experts`` layers routes in those; a block of pairs once a pair, in
        its first sublayer (where the routed part reads the stream and where
        its router and experts are held); any other routed block in every
        layer after its ``dense_layers``."""
        routes = _model(self.arch).FAMILY.routes
        if routes == "experts_layers":
            return self._of_kind("experts")
        if routes == "pairs":
            return tuple(range(0, self.layers, self.layers_a_block))
        return tuple(range(self.dense_layers, self.layers)) if routes else ()

    @property
    def layers_a_block(self):
        """``layers`` and ``layer_types`` entries one block of the family
        takes: 2 where the block is a pair of sublayers round one routed
        part, else 1."""
        return 2 if _model(self.arch).FAMILY.routes == "pairs" else 1

    @property
    def mixings(self):
        """The sublayers whose residual path is a mixing of ``hc_mult``
        streams: two a layer (the mixer's and the feed-forward's) of a
        family that declares ``residual_streams``, else none."""
        return 2 * self.layers if self.hc_mult else 0

    @property
    def router_width(self):
        """The outputs a routed layer's router scores: its experts and then
        its identity experts."""
        return self.experts + self.zero_experts

    @property
    def held_experts(self):
        """The columns of a routed layer's ``experts`` this model holds the
        weights of (a slice; all of them for a model that holds no share)."""
        return slice(self.expert_first, self.expert_first + self.experts_held)

    @property
    def ssm_inner(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def kda_inner(self):
        return self.kda_heads * self.kda_head_dim

    @property
    def latent_width(self):
        """Values a latent layer keeps a token: the compressed K/V and the
        key's shared part."""
        return self.latent_rank + self.latent_rope

    @property
    def latent_scale(self):
        """The scale of a latent layer's scores: ``attention_multiplier``,
        or one over the root of a key's width (its own ``head_dim`` values
        and the shared ``latent_rope``), times ``m^2`` under
        ``rope_scaling`` (``m = yarn_mscale(factor, mscale_all_dim)``)."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        scale = float(self.head_dim + self.latent_rope) ** -0.5
        if self.rope_scaling:
            # YaRN stretches the rotation and sharpens the softmax for it
            scale *= yarn_mscale(self.rope_scaling["factor"],
                                 self.rope_scaling["mscale_all_dim"]) ** 2
        return scale

    @property
    def rope_mscale(self):
        """What YaRN scales a rotation's cos and sin by (1 without
        ``rope_scaling``, and wherever ``mscale`` is ``mscale_all_dim``)."""
        y = self.rope_scaling
        return yarn_mscale(y["factor"], y["mscale"]) \
            / yarn_mscale(y["factor"], y["mscale_all_dim"]) if y else 1.0

    def to_dict(self):
        d = {s: getattr(self, s) for s in self.__slots__}
        d["layer_types"] = list(self.layer_types)
        if self.hc_clamp is not None:
            d["hc_clamp"] = list(self.hc_clamp)
        if self.v_head_dim == self.head_dim:
            # not a width of its own: it follows ``head_dim`` (``replace``)
            d["v_head_dim"] = None
        return d

    def replace(self, **changes):
        return DecoderConfig(**dict(self.to_dict(), **changes))


def cache_config(cfg, block_size, num_blocks, dtype=None, state_slots=0):
    """The cache geometry a model's step is built over: K and V pools for
    its attention layers (``kv_heads`` wide), for its recurrent layers what
    one sequence's slot holds (``_state_shapes``: a window and a state for
    ``mamba`` and ``kda`` layers, a window alone for ``conv`` layers), in
    ``state_slots`` slots (slot 0 the idle lanes' scratch), for its latent
    layers one pool each, ``latent_width`` values a token (and, where they
    select, an index pool each beside it, ``index_head_dim`` values a
    token), and for its
    window layers as many rings (a sequence holds a ring as it holds a
    slot: one a lane and the scratch), each ``cfg.window`` positions
    long.  A layer of a kind that keeps nothing (``experts``) is counted
    nowhere."""
    return _kv.KVCacheConfig(
        len(cfg.attn_layers), cfg.kv_heads, cfg.head_dim, block_size,
        num_blocks, dtype or cfg.kv_dtype or "f32",
        state_layers=len(cfg.recurrent_layers),
        state_shapes=_state_shapes(cfg),
        state_slots=state_slots,
        window_layers=len(cfg.window_layers), window=cfg.window,
        window_slots=state_slots if cfg.window_layers else 0,
        latent_layers=len(cfg.latent_layers),
        latent_width=cfg.latent_width if cfg.latent_layers else 0,
        index_layers=len(cfg.latent_layers) if cfg.index_topk else 0,
        index_width=cfg.index_head_dim if cfg.index_topk else 0)


def _conv_window(cfg):
    """``(taps, width)`` of the causal convolution of the model's recurrent
    layers: a slot keeps its ``taps - 1`` newest inputs."""
    if cfg.ssm_layers:
        return cfg.ssm_conv, \
            cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    if cfg.kda_layers:
        # q, k and v each pass through a convolution of their own
        return cfg.kda_conv, 3 * cfg.kda_inner
    return cfg.conv_taps, cfg.hidden


def _state_shapes(cfg):
    """``(shape, dtype)`` of each array a recurrent layer keeps a sequence,
    by the kind of layer: always the convolution's window first (``taps -
    1`` inputs, flat, in the weights' dtype; the cache holds a slot of it as
    whole rows of 128, ``kv_cache.slot_layout``, and ``push_windows`` moves
    it so); a ``mamba`` layer then its
    state (``[ssm_state, ssm_inner]`` float32: the heads' ``[head_dim,
    ssm_state]`` matrices, transposed so the minor dimension is the
    128-lane-dense one), a ``kda`` layer its state (``[kda_head_dim,
    kda_inner]`` float32: the heads' ``[keys, values]`` matrices side by
    side), a ``conv`` layer nothing more.  Nothing for a model with no such
    layer."""
    if not cfg.recurrent_layers:
        return ()
    taps, width = _conv_window(cfg)
    window = (((taps - 1) * width,), cfg.dtype)
    if cfg.ssm_layers:
        return (window, ((cfg.ssm_state, cfg.ssm_inner), "f32"))
    if cfg.kda_layers:
        return (window, ((cfg.kda_head_dim, cfg.kda_inner), "f32"))
    return (window,)


def init_decoder_params(cfg, seed=0):
    """name -> np array in the config's weight dtype; 0.02-normal
    weights, identity norms (the family's ``init_params``)."""
    return _model(cfg.arch).init_params(cfg, seed)


_BF16 = np.dtype(jnp.bfloat16)


def _as_stored(a):
    return a.view(np.uint16) if a.dtype == _BF16 else a


def save_decoder(dirname, cfg, params, draft=None):
    """params.npz + decoder.json under `dirname` (tools/serve.py loads
    decode models from such a dir).  ``draft`` — an optional
    (DecoderConfig, params) pair — lands as a nested bundle under
    ``<dirname>/draft`` so the speculative-decode draft ships beside its
    target and the two can never drift apart."""
    os.makedirs(dirname, exist_ok=True)
    # npz has no bfloat16: such arrays are stored as their 16 bits, and
    # load_decoder reads them back by the config's weight dtype
    np.savez(os.path.join(dirname, "params.npz"),
             **{k: _as_stored(np.asarray(v)) for k, v in params.items()})
    with open(os.path.join(dirname, "decoder.json"), "w") as fp:
        json.dump(cfg.to_dict(), fp, indent=1, sort_keys=True)
    if draft is not None:
        dcfg, dparams = draft
        if dcfg.vocab != cfg.vocab:
            raise ValueError("draft vocab %d != target vocab %d"
                             % (dcfg.vocab, cfg.vocab))
        save_decoder(os.path.join(dirname, "draft"), dcfg, dparams)
    return dirname


def load_decoder(dirname):
    with open(os.path.join(dirname, "decoder.json")) as fp:
        cfg = DecoderConfig(**json.load(fp))
    with np.load(os.path.join(dirname, "params.npz")) as z:
        params = {k: z[k] for k in z.files}
    if cfg.dtype == "bf16":
        params = {k: v.view(_BF16) if v.dtype == np.uint16 else v
                  for k, v in params.items()}
    return cfg, params


def is_decoder_dir(dirname):
    return os.path.exists(os.path.join(dirname, "decoder.json"))


def has_draft(dirname):
    return is_decoder_dir(os.path.join(dirname, "draft"))


def load_draft(dirname):
    """The bundled draft decoder, or None when the target ships alone."""
    return load_decoder(os.path.join(dirname, "draft")) \
        if has_draft(dirname) else None


def truncate_decoder(cfg, params, layers=1):
    """A cheap draft from a target: keep the first ``layers`` transformer
    layers plus the embeddings / final LN / head verbatim.  With the
    residual stream dominated by the embedding, the truncated argmax
    tracks the full model's closely — a distillation-free draft for
    demos and smokes (real deployments train one)."""
    # whole blocks: of a family of pairs, whole pairs
    period = cfg.layers_a_block
    layers = min(-(-int(layers) // period) * period, cfg.layers)
    dcfg = cfg.replace(layers=layers, layer_types=cfg.layer_types[:layers],
                       dense_layers=min(cfg.dense_layers, layers))
    dparams = {}
    for k, v in params.items():
        m = re.match(r"l(\d+)_", k)
        if m is None or int(m.group(1)) < layers:
            dparams[k] = np.asarray(v)
    return dcfg, dparams


# -- shared forward ----------------------------------------------------------

def laid_out(cfg, params):
    """``params`` (published names and shapes: ``init_params``, a bundle,
    a benchmark's ``make_params``) as a decode step holds them: every value
    a device array, and where the family's module declares a layout
    (``laid_out(cfg, params)``: ``kimi_linear``, ``dots_vlm``) the weights
    it names in the form its block multiplies, laid out here once so that
    no step lays them out again.  A new dict; a family that declares none
    gets its own arrays back.  Whatever builds a step that is served or
    timed hands it these (the engine for the model and a draft,
    ``unpaged_generate``, ``tools/decode_step_probe.py``); a block handed
    the published arrays computes the same."""
    params = {key: jnp.asarray(v) for key, v in params.items()}
    family = getattr(_model(cfg.arch), "laid_out", None)
    return family(cfg, params) if family else params


def _block(cfg):
    """The architecture's block: ``block(params, cfg, tok, pos, attend,
    live, recur) -> (logits [B, vocab], extras)``.  Two callbacks own what
    a layer keeps between tokens, the only paged/unpaged difference:
    ``attend(l, q, k, v)`` the KV write + history attention of attention
    layer ``l`` (of a ``latent`` layer: ``q`` [B, H, W] the absorbed query,
    ``k`` [B, W] this token's row, ``v`` None -> the probabilities' sum of
    the rows' first ``latent_rank`` columns, [B, H, latent_rank]; of one that
    selects, ``v`` is ``(qi [B, J, E], w [B, J], ki [B, E])``: the indexer's
    queries, their heads' weights and this token's index key, which
    ``attend`` writes beside the row before it scores the lane's keys,
    chooses ``cfg.index_topk`` positions and attends those alone), and
    ``recur`` what the recurrent layers keep
    (``recur.window(l, x)`` pushes this token's convolution input and
    returns the newest ``taps``; for a kind with a state,
    ``recur.advance(l, decay, dx, b, c)`` moves it one token and returns
    its read-out, ``recur.delta(l, alpha, beta, k, v, q)`` a ``kda``
    layer's; None for a model with no such layer).  ``live`` [B] bool
    marks the lanes that hold a sequence; ``extras`` is a tuple of small
    arrays the step returns after its logits (a routed block's tokens sent
    to each expert, a row a layer of ``cfg.routed_layers``; nothing for the
    others).  A block of *pairs* (``FAMILY.routes`` ``"pairs"``) runs two
    sublayers and one routed part a pair: ``cfg.layers`` counts sublayers,
    sublayer ``l`` is pair ``l // 2``'s, ``attend`` gets the sublayer's own
    ``l`` (so every sublayer has its pool, as every layer of another block
    has), and a row of the counts is a pair's, named in
    ``cfg.routed_layers`` by its first sublayer; where the router scores
    identity experts too the counts are ``cfg.router_width`` wide, the
    identity experts' columns last, and a second extra counts the live lanes
    by how many real experts they chose, ``[pairs, experts_per_token + 1]``.
    Every family's block is ``token_logits`` of its module."""
    return _model(cfg.arch).token_logits


# -- paged step --------------------------------------------------------------

def _write_rows(pool, blk_ids, offs, rows):
    """``pool[blk_ids[b], offs[b]] = rows[b]`` for every lane b: the one
    KV write of the step, for payload and scales alike (pool ``[num_blocks,
    block_size, W]``, rows ``[B, W]``).  A scatter of B rows into a whole
    donated array, which XLA:TPU updates in its buffer.  Idle lanes all
    name row (0, 0) of the scratch block, so the indices are not unique
    and nothing is promised about them: which idle lane's row lands there
    is unspecified, and nothing reads it.  Rows take the pool's dtype
    here (a bf16 pool rounds the block's float32 K and V once)."""
    return pool.at[blk_ids, offs].set(
        rows.reshape(rows.shape[0], -1).astype(pool.dtype))


# the form ``push_windows`` moves a window in, as the engine names it (the
# executables' keys, the ``serving_prewarm`` event): whole tiles of a pool
# ``[slots, rows, 128]``
WINDOW_UPDATE = "tiles"


def pushed(old, xbc, taps):
    """One token into convolution windows held as values: ``old`` [B, (K -
    1) * W] the lanes' ``taps - 1`` newest inputs, oldest first, ``xbc`` [B,
    W] this token's -> (the windows one token on, in ``old``'s dtype; the
    ``taps`` newest inputs [B, K, W] float32, oldest first, this one, as
    stored, last)."""
    width = xbc.shape[1]
    new = jnp.concatenate([old[:, width:], xbc.astype(old.dtype)], axis=1)
    return new, jnp.concatenate([old[:, :width], new], axis=1).reshape(
        xbc.shape[0], taps, width).astype(jnp.float32)


def push_windows(pool, slots, fresh, xbc, taps):
    """``pushed`` on the slots of a window pool where they lie: lane ``b``'s
    window is slot ``slots[b]`` of ``pool`` [slots, rows, 128]
    (``kv_cache.slot_layout``: the ``(taps - 1) * W`` values flat in whole
    rows of 128, the last row's rest never read), started from zeros where
    ``fresh[b]`` whatever the slot holds.  -> (pool, [B, K, W] float32).
    A slot is one run of whole tiles, so XLA's own gather and scatter move
    it: B slots out, B slots into the whole donated array, no slot other
    than the lanes' changed.  Idle lanes all name slot 0 and all write it;
    nothing reads it."""
    lanes, held = xbc.shape[0], (taps - 1) * xbc.shape[1]
    rows, row = pool.shape[1:]
    old = jnp.take(pool, slots, axis=0, mode="clip").reshape(
        lanes, rows * row)[:, :held]
    new, window = pushed(_ssm.started(fresh, old), xbc, taps)
    new = jnp.pad(new, ((0, 0), (0, rows * row - held)))
    return pool.at[slots].set(new.reshape(lanes, rows, row)), window


def attention_path(cfg, kv_config, lanes=1, kind="attention"):
    """``"pallas"`` where ``make_paged_step``'s attention is the kernel
    that reads the live blocks in place, for this model and pool on this
    backend at a bucket of ``lanes`` (what holds for a bucket holds for
    every smaller one); ``"gather"`` where it gathers the padded table
    (the int8 residency always does).  ``kind`` ``"window"`` asks it of the
    window layers, whose table is their ring, and ``"latent"`` of the latent
    layers and their form of the kernel (of a model that selects: the kernel
    under either of its forms of the selected read); ``"selected"`` names
    that form (``paged_attention.selected_latent_path``: ``"pallas_masked"``
    where the kernel walks a lane's live blocks under a mask of the chosen
    positions, ``"pallas"`` where it reads the chosen rows, gathered first;
    None for a model that does not select); ``"index"`` of the kernel that
    scores a selecting model's cached index keys."""
    maxb = -(-cfg.max_seq // kv_config.block_size)
    pool_dtype = _kv._PAYLOAD[kv_config.dtype][0]
    if kind == "index":
        return _pa.index_path(
            (lanes, cfg.index_heads, cfg.index_head_dim),
            (kv_config.num_blocks, kv_config.block_size,
             kv_config.index_width), pool_dtype, maxb)
    if kind in ("latent", "selected"):
        q = (lanes, cfg.heads, kv_config.latent_row)
        pool = (kv_config.num_blocks, kv_config.block_size,
                kv_config.latent_row)
        if cfg.index_topk:
            form = _pa.selected_latent_path(
                q, pool, pool_dtype, cfg.latent_rank,
                min(cfg.index_topk, maxb * kv_config.block_size), maxb)
            return form if kind == "selected" or form == "gather" \
                else "pallas"
        return None if kind == "selected" \
            else _pa.latent_path(q, pool, pool_dtype, cfg.latent_rank)
    windowed = kind == "window"
    return _pa.attention_path(
        (lanes, cfg.heads, cfg.head_dim),
        (kv_config.window_blocks if windowed else kv_config.num_blocks,
         kv_config.block_size, kv_config.heads * kv_config.head_dim),
        pool_dtype, ring=kv_config.window_ring if windowed else 0)


def chunk_positions(cfg, kv_config, lanes=1):
    """Positions one chunk of the attention kernel spans, by kind of layer
    that pages a history (``attention``, ``window``, ``latent``) and takes
    the kernel at a bucket of ``lanes``: the kernel sizes a chunk by the
    bytes a position costs in that kind's pools; a window layer's is its
    whole ring where the ring is no longer than the longest chunk, and the
    same span as a context's where the ring is walked in chunks
    (``paged_attention.chunk_positions``).  A latent layer that selects
    walks its own table in such chunks under the mask of its choice, or (a
    table too wide for that: the row form) the ``index_topk`` rows it
    gathered, which may cap the span.  Kinds on the gather path have no
    chunk and no entry."""
    maxb = -(-cfg.max_seq // kv_config.block_size)
    dtype = _kv._PAYLOAD[kv_config.dtype][0]
    q = (lanes, cfg.heads, cfg.head_dim)
    width = kv_config.heads * kv_config.head_dim
    out = {}
    for kind in ("attention", "window", "latent"):
        if not cfg._of_kind(kind) \
                or attention_path(cfg, kv_config, lanes, kind) != "pallas":
            continue
        if kind == "latent":
            row = kv_config.latent_row
            # a model that selects walks its table under a mask, or (the
            # row form) the chosen rows, gathered
            held = min(cfg.index_topk // kv_config.block_size, maxb) \
                if attention_path(cfg, kv_config, lanes, "selected") \
                == "pallas" else maxb
            out[kind] = _pa.latent_chunk_positions(
                (lanes, cfg.heads, row), (kv_config.num_blocks,
                                          kv_config.block_size, row),
                dtype, cfg.latent_rank, held)
        else:
            ring = kv_config.window_ring if kind == "window" else 0
            out[kind] = _pa.chunk_positions(
                q, (kv_config.window_blocks if ring
                    else kv_config.num_blocks, kv_config.block_size, width),
                dtype, maxb, ring)
    return out


def experts_path(cfg, params, lanes=1):
    """``"pallas"`` where a routed layer's experts are the kernel that
    reads the experts hit and no others, for this model's weights on this
    backend at a bucket of ``lanes``; ``"einsum"`` where every expert is
    streamed; None for a model with no routed layer."""
    if not cfg.routed_layers:
        return None
    # three matrices with a gate, or two (``up`` and ``down`` both [E, F, H])
    matrices = _model(cfg.arch).FAMILY.expert_matrices
    w = params["l%d_%s" % (cfg.routed_layers[0],
                           "wgate" if matrices == 3 else "experts_up")]
    return _moe.experts_path(lanes, w.shape, w.dtype, matrices)


def experts_gate(cfg):
    """The activation of an expert's gate in this model's routed layers, as
    ``moe_experts.GATES`` names it (the family's declaration)."""
    return _model(cfg.arch).FAMILY.expert_gate


def experts_chunk(cfg):
    """Columns of an expert's ``wgate`` / ``wup`` (rows of its ``wdown``;
    of a two-matrix expert's ``up`` and ``down``) one grid step of the
    expert kernel reads at this model's widths, by the VMEM its blocks may
    take (``moe_experts.f_chunk``, ``f_rows``); None for a model with no
    routed layer, 0 where no chunk fits."""
    if not cfg.routed_layers:
        return None
    dtype = _kv._PAYLOAD[cfg.dtype][0]
    if _model(cfg.arch).FAMILY.expert_matrices == 3:
        return _moe.f_chunk(cfg.hidden, cfg.ffn, jnp.dtype(dtype).itemsize)
    return _moe.f_rows(cfg.hidden, cfg.ffn, dtype)


def state_update_path(cfg, kv_config, lanes=1):
    """``"pallas"`` where a state-space or delta-rule layer's state is moved
    by the kernel that updates each lane's slot in place, for this model's
    pool on this backend at a bucket of ``lanes``; ``"gather"`` where the
    slots are gathered, moved and scattered back; None for a model with no
    such layer."""
    if not cfg.state_layers:
        return None
    shape, dtype = kv_config.state_shapes[1]
    pool = (kv_config.state_slots,) + shape
    if cfg.kda_layers:
        return _kda.update_path(pool, _kv._PAYLOAD[dtype][0], lanes,
                                cfg.kda_heads)
    return _ssm.update_path(pool, _kv._PAYLOAD[dtype][0], lanes,
                            cfg.ssm_groups)


def state_update_columns(cfg, kv_config):
    """The columns of a slot one transfer of the state-update kernel moves
    for this model's pool: the slot's whole width where the VMEM the kernel
    asks for holds batches of whole slots, else the chunk it falls back to
    (``ssm_update.transfer_columns``); None for a model with no such layer
    or a pool no chunk of which fits.  A delta-rule layer's chunk is whole
    heads, as a state-space layer's is whole groups."""
    if not cfg.state_layers:
        return None
    shape, _dtype = kv_config.state_shapes[1]
    return _ssm.transfer_columns((kv_config.state_slots,) + shape,
                                 cfg.kda_heads or cfg.ssm_groups)


class StepAccount:
    """What one model's decode step takes and reads, by kind of layer: the
    one place outside a family, its kernels and its cache group that knows
    the kinds (the engine keeps lanes, blocks, rings and slots, and asks
    here).  Built once a model from its configuration, its cache's, its
    parameters as the step holds them (``laid_out``, whose own layouts
    ``laid`` names) and the lane buckets; ``model`` labels the counters and
    gauges that ride with the attributes.  Each kind the model has is
    entered once, by its method (``_paged`` ... ``_recurrent``): its path,
    or its form a bucket (None, or empty, for a kind the model has not), and
    what it adds to ``key_parts`` (every ``CarriedStepFn`` of the model is
    keyed by them: an executable compiled for one path is never restored for
    another), to a bucket's ``serving_prewarm`` event and to a step's span.
    A new kind is one more such method."""

    def __init__(self, cfg, kv_config, params, buckets, model=None, laid=()):
        self.cfg, self.kv_config, self.model = cfg, kv_config, model
        self.buckets = tuple(sorted(buckets))
        self.maxb = -(-cfg.max_seq // kv_config.block_size)
        self.attn_path = self.window_path = self.index_path = None
        self.experts_path, self.state_path, self.selected_read = {}, {}, {}
        self.hc_maps_path = {}
        self.key_parts = {}
        # bucket -> the positions a chunk of the attention kernel spans, by
        # kind of layer that takes it
        self._chunks = {b: chunk_positions(cfg, kv_config, b)
                        for b in self.buckets}
        # what the prewarm event says at every bucket, and at each
        self._said, self._said_at = {}, {b: {} for b in self.buckets}
        # what fills a step's span: each a ``(bucket, lens, attrs)``
        self._reads = []
        self._paged()
        if cfg.window_layers:
            self._window()
        if cfg.routed_layers:
            self._routed(params)
        if cfg.recurrent_layers:
            self._recurrent()
        if cfg.mixings:
            self._streams()
        if laid:
            # (the argument shapes tell the two forms of a weight apart too)
            self.key_parts["weights_laid_out"] = sorted(laid)
        kinds = sorted(set(cfg.layer_types))
        if len(kinds) > 1:
            # a hybrid's layers, those that keep nothing in the cache too
            self._said["layers"] = {kind: cfg.layer_types.count(kind)
                                    for kind in kinds}

    def _paged(self):
        """The layers that page a history on the global tables: attention
        layers, or a latent model's latent layers (a block there is one row
        a token, not K and V), which may select: the ``index_topk`` rows
        an indexer scores best are read, in the form ``selected_read`` names
        a bucket (``attention_path``'s ``"index"`` and ``"selected"``)."""
        cfg, kv, top = self.cfg, self.kv_config, self.buckets[-1]
        bs, maxb, topk = kv.block_size, self.maxb, cfg.index_topk
        kind = "latent" if cfg.latent_layers else "attention"
        self.attn_path = path = attention_path(cfg, kv, top, kind)
        latent = {"latent_attention": path} if kind == "latent" else {}
        self.key_parts.update(latent or {"attention": path})
        self._said.update(latent, attention=path)
        if topk:
            self.index_path = attention_path(cfg, kv, top, "index")
            self.key_parts["index_scores"] = self.index_path
            self._said.update(index_path=self.index_path, index_topk=topk)
            for b in self.buckets:
                self.selected_read[b] = self._said_at[b]["latent_attention"] \
                    = attention_path(cfg, kv, b, "selected")

        def read(bucket, lens, attrs):
            # the row form of a selected read fetches a lane's chosen rows,
            # gathered; the masked walk every live block, as the gather
            form = self.selected_read.get(bucket)
            attended = np.minimum(lens, topk) if form == "pallas" else lens
            attrs.update(
                kv_blocks_read=_pa.blocks_read(attended, bs, maxb, path),
                kv_table_slots=bucket * maxb, kv_block_size=bs)
            if latent:
                # each latent layer fetches the same, in so many chunks (on
                # the gather path a lane's padded table is its one chunk)
                attrs["latent_blocks_read"] = attrs["kv_blocks_read"]
                span = self._chunks[bucket].get("latent", maxb * bs)
                attrs["latent_chunks"], attrs["latent_full_chunks"] = \
                    _pa.chunks_read(attended, bs, maxb, span)
                # ... and what the kernel fetched twice, or for nobody
                attrs["latent_blocks_refetched"] = _pa.blocks_refetched(
                    attended, bs, maxb, span) if path == "pallas" else 0
            else:
                # the chunks one layer's kernel walked, and those of them
                # that ran its straight-line body (on the gather path a
                # lane's padded table is its one chunk)
                span = self._chunks[bucket].get("attention", maxb * bs)
                attrs.update(
                    kv_chunks=_pa.chunks_read(lens, bs, maxb, span)[0],
                    kv_straight_chunks=_pa.straight_chunks_read(
                        lens, bs, maxb, span))
            if topk:
                # what the selection did: the rows read, of those the
                # contexts hold, differ on the lanes past ``index_topk`` alone
                attrs.update(
                    index_blocks_read=_pa.blocks_read(lens, bs, maxb,
                                                      self.index_path),
                    latent_rows_selected=int(np.minimum(lens, topk).sum()),
                    latent_rows_in_context=int(lens.sum()),
                    sparse_lanes=int((lens > topk).sum()))
                if form == "pallas_masked":
                    # the blocks a layer's kernel fetched to read them
                    attrs["latent_blocks_walked"] = \
                        attrs["latent_blocks_read"]
        self._reads.append(read)

    def _window(self):
        """The window layers' attention over their rings."""
        self.window_path = path = attention_path(
            self.cfg, self.kv_config, self.buckets[-1], "window")
        self.key_parts["window_attention"] = path
        self._said.update(window_attention=path,
                          window_ring=self.kv_config.window_ring)

    def window_attrs(self, lens, released, window_in_use, global_in_use):
        """What the window layers' attention fetches this step, over all
        such layers, beside what it would fetch of the lanes' whole contexts
        (were they global layers on the same path), and the blocks their
        pools hold (``released``: what the rings gave back at this dispatch);
        asked only while the step span is recorded.  The counter and the
        gauges ride along."""
        cfg, kv = self.cfg, self.kv_config
        n, bs, ring = len(cfg.window_layers), kv.block_size, kv.window_ring
        _tm.inc("kv_window_blocks_released_total", released, model=self.model)
        for kind, held in (("window", window_in_use),
                           ("global", global_in_use)):
            _tm.set_gauge("kv_pool_blocks", held, model=self.model, kind=kind)
        read = lambda maxb, **kw: _pa.blocks_read(
            lens, bs, maxb, self.window_path, **kw)
        span = self._chunks[self.buckets[-1]].get("window", ring * bs)
        return {"kv_window_blocks_read": n * read(ring, ring=True),
                "kv_window_blocks_full": n * read(self.maxb),
                "kv_window_blocks_held": window_in_use,
                # live lanes whose context is past the window (their rings
                # have given blocks back), the chunks ONE window layer's
                # attention walked and those that ran the straight-line body
                "kv_window_lanes_wrapped": int((lens > cfg.window).sum()),
                "kv_window_chunks": _pa.chunks_read(lens, bs, ring, span)[0],
                "kv_window_straight_chunks": _pa.straight_chunks_read(
                    lens, bs, ring, span),
                "kv_block_size": bs}

    def _routed(self, params):
        """The routed layers' experts: how they are read a bucket
        (``"pallas"``: the experts hit alone | ``"einsum"``: all)."""
        cfg = self.cfg
        self.experts_path = {b: experts_path(cfg, params, b)
                             for b in self.buckets}
        self.key_parts["experts"] = sorted(self.experts_path.items())
        for b, path in self.experts_path.items():
            self._said_at[b]["experts"] = path
            if path == "pallas":
                self._said_at[b]["experts_f_chunk"] = experts_chunk(cfg)
        if experts_gate(cfg) != "silu":
            # said only where the family declares another than SiLU
            self._said["experts_gate"] = experts_gate(cfg)
        if cfg.zero_experts:
            # said only where the routed part is not a layer's own
            # feed-forward over experts that all compute
            self._said.update(routes=_model(cfg.arch).FAMILY.routes,
                              zero_experts=cfg.zero_experts)

    def moe_attrs(self, bucket, extras):
        """A routed step's ``extras`` (``_block``: the tokens it sent to
        each expert in each layer that routes, live lanes only, and from a
        router with groups the lanes that kept each group) as its span's
        attributes, means over the layers that route.  The caller hands them
        over only while the span is recorded, so an untraced window pays for
        no transfer; a step with no experts has none.  Where the experts are
        the kernel's, an expert with no token was not read: counted.  An
        assignment is of up to three kinds: to an expert held here, to one
        held elsewhere (a share) or to an identity expert (a router wider
        than its experts), and the three add up to the live lanes times
        ``experts_per_token``; ``moe_assignments`` is the first kind, as it
        has been since shares."""
        if not extras:
            return {}
        cfg = self.cfg
        # the identity experts' columns lie behind the experts'
        scored = np.asarray(extras[0])
        everywhere = scored[:, :cfg.experts]
        routed = everywhere[:, cfg.held_experts]
        hit = float((routed > 0).sum(axis=1).mean())
        _tm.inc("moe_tokens_routed_total", int(routed.sum()), model=self.model)
        _tm.set_gauge("moe_experts_hit", hit, model=self.model)
        if self.experts_path.get(bucket) == "pallas":
            _tm.inc("moe_expert_reads_skipped_total",
                    int((routed == 0).sum()), model=self.model)
        # means over the routed layers: experts with a token, the fullest
        # expert's tokens, and the tokens routed (lanes x experts a token)
        attrs = {"moe_experts_hit": round(hit, 3),
                 "moe_load_max": round(float(routed.max(axis=1).mean()), 3),
                 "moe_assignments": round(float(routed.sum(axis=1).mean()),
                                          3)}
        if routed.shape != everywhere.shape:
            # a share: the assignments computed here, and those left to the
            # experts it does not hold
            absent = int(everywhere.sum() - routed.sum())
            _tm.inc("moe_assignments_absent_total", absent, model=self.model)
            attrs["moe_local_assignments"] = attrs["moe_assignments"]
            attrs["moe_absent_assignments"] = round(
                absent / float(len(routed)), 3)
        if cfg.zero_experts:
            # what the router left uncomputed, and the most and the fewest
            # real experts one live lane chose in any routed layer
            zero = int(scored.sum() - everywhere.sum())
            _tm.inc("moe_assignments_zero_total", zero, model=self.model)
            attrs["moe_zero_assignments"] = round(
                zero / float(len(routed)), 3)
            counts = np.flatnonzero(np.asarray(extras[1]).sum(axis=0))
            most, fewest = (int(counts[-1]), int(counts[0])) \
                if len(counts) else (0, 0)
            attrs.update(moe_real_per_token_max=most,
                         moe_real_per_token_min=fewest)
        if cfg.n_group > 1:
            # of the groups that hold a held expert, how many a token kept
            # (mean over layers)
            size = cfg.experts // cfg.n_group
            held = cfg.held_experts
            mine = np.asarray(extras[1])[
                :, held.start // size:(held.stop - 1) // size + 1]
            lanes = everywhere.sum() / float(cfg.experts_per_token
                                             * len(routed))
            attrs["moe_groups_kept"] = round(
                float(mine.sum()) / (len(routed) * lanes), 3) if lanes \
                else 0.0
        return attrs

    def _recurrent(self):
        """The recurrent layers' slots, named by what they hold
        (``STATE_NAMES``): the state a step reads and writes, a slot a live
        lane; the form their windows move in (``window_update``); and, of
        the kinds that keep a state beside their window, how
        it is moved a bucket (``"pallas"``: each slot in place |
        ``"gather"``), what one transfer of the kernel then moves and in
        how many a slot goes; of ``kda`` layers the heads a slot holds."""
        cfg, kv = self.cfg, self.kv_config
        name, slot = cfg.state_name, _kv.slot_bytes(kv)
        # how a lane's convolution window moves between its slot and the
        # step, whatever the bucket
        self._said["window_update"] = self.key_parts["window_update"] \
            = WINDOW_UPDATE
        if cfg.kda_layers:
            # what the delta-rule kernel's shape rule turns on
            self._said["kda_heads"] = cfg.kda_heads
        for b in self.buckets if cfg.state_layers else ():
            self.state_path[b] = path = state_update_path(cfg, kv, b)
            self._said_at[b]["state_update"] = path
            if path == "pallas":
                # the columns one transfer moves, and the transfers a slot
                # is moved in
                columns = state_update_columns(cfg, kv)
                self._said_at[b].update(
                    state_update_columns=columns,
                    state_update_transfers=kv.state_shapes[1][0][1]
                    // columns)
        if self.state_path:
            self.key_parts["state_update"] = sorted(self.state_path.items())

        def read(bucket, lens, attrs):
            # an idle lane's context is 0, a live one's at least 1
            live = int(np.count_nonzero(lens))
            attrs.update({name + "_lanes": live,
                          name + "_bytes": live * slot})
        self._reads.append(read)

    def _streams(self):
        """The residual path of a block that carries ``hc_mult`` streams a
        token: how many, how often they are normalised, how a sublayer's
        maps are made a bucket (``"pallas"``: one kernel a mixing | ``"xla"``:
        the jnp form), the sublayers that mix them and what those mixings
        move of the live lanes' streams (``hyper_connections.stream_bytes``,
        which the benchmark's cost file is held to)."""
        cfg = self.cfg
        said = {"residual_streams": cfg.hc_mult,
                "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters}
        self._said.update(said)
        self.key_parts.update(said)
        for b in self.buckets:
            self.hc_maps_path[b] = self._said_at[b]["hc_maps_path"] \
                = _hc.maps_path(cfg, b)
        self.key_parts["hc_maps"] = sorted(self.hc_maps_path.items())

        def read(bucket, lens, attrs):
            attrs.update(
                hc_maps_path=self.hc_maps_path[bucket],
                hc_streams=cfg.hc_mult, hc_mixings=cfg.mixings,
                hc_stream_bytes=_hc.stream_bytes(
                    cfg.hc_mult, cfg.hidden, cfg.mixings,
                    int(np.count_nonzero(lens))))
        self._reads.append(read)

    def prewarm_attrs(self, bucket):
        """What the ``serving_prewarm`` event of ``bucket``'s executable
        says of the step's kinds: each one's path, form and chunk."""
        return dict(self._said, **self._said_at[bucket],
                    chunk_positions=self._chunks[bucket])

    def step_attrs(self, bucket, lens):
        """A step's read counts for its span; ``lens`` its context lengths
        (the numpy feed, ``int32[bucket]``, an idle lane's 0).  A traced
        step pays this under the engine's lock: sums over ``lens``."""
        attrs = {}
        for read in self._reads:
            read(bucket, lens, attrs)
        return attrs

    def pool_bytes(self):
        """gauge -> the bytes of the pools beside K and V that the model's
        kinds hold, by the cache's description, and of what its residual
        streams are mixed by (no kind, no gauge)."""
        kv = self.kv_config
        pools = {"latent_pool_bytes": kv.latent_layers
                 * _kv.latent_block_bytes(kv) * kv.num_blocks,
                 "index_pool_bytes": kv.index_layers
                 * _kv.index_block_bytes(kv) * kv.num_blocks,
                 "%s_bytes" % self.cfg.state_name: _kv.state_bytes(kv),
                 # the mixings' parameters of a model of several streams
                 "hc_param_bytes": _hc.param_bytes(self.cfg,
                                                   self.cfg.mixings)}
        return {gauge: n for gauge, n in pools.items() if n}


def _widened(x, row):
    """``x`` [..., latent_width] as a latent pool's row holds it: ``row``
    wide, the rest zeros (they add nothing to a score)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, row - x.shape[-1])])


def _pool_index(cfg):
    """layer -> its place among the layers of its kind (the index of its
    pools, or of its state arrays, in the cache's groups)."""
    return {l: i for kind in (cfg.attn_layers, cfg.latent_layers,
                              cfg.window_layers, cfg.recurrent_layers)
            for i, l in enumerate(kind)}


class _Recurrent:
    """The recurrent layers' callback of a step (``_block``), built by the
    step maker from where it keeps things: ``push(i, fresh, xbc) -> [B, K,
    W]`` moves recurrent layer ``i``'s windows one token on for the step's
    lanes (``pushed``'s rule, on values or on the slots of a pool), and
    ``advance(i, fresh, decay, dx, b, c) -> y`` moves its state
    one token (``ssm_update.advance``'s mathematics, on values or on the
    slots of a pool; for ``kda`` layers ``kda_update.advance``'s, reached
    as ``delta``; None for a kind of layer that keeps a window and no
    state).  A lane at position 0 (``fresh``) starts from zeros whatever is
    stored."""

    def __init__(self, pool_of, pos, push, advance):
        self._at = pool_of
        self._fresh = pos == 0
        self._push, self._advance = push, advance

    def window(self, l, xbc):
        """Push this token's convolution input ``xbc`` [B, W] -> the
        ``taps`` newest inputs [B, K, W] float32, oldest first, this one
        (as stored) last.  The move between the slot and the step has a
        scope of its own inside the mixer's (``kda/conv/window``,
        ``ssm/conv/window``)."""
        with jax.named_scope("window"):
            return self._push(self._at[l], self._fresh, xbc)

    def advance(self, l, decay, dx, b, c):
        """``S = decay * S + outer(b, dx)`` -> ``c . S`` [B, I]: the state
        [N, I] of each lane one token on, ``decay`` and ``dx`` [B, I] (a
        head's decay repeated over its values), ``b`` and ``c`` [B, G, N]
        (a pair a group of heads)."""
        return self._advance(self._at[l], self._fresh, decay, dx, b, c)

    def delta(self, l, alpha, beta, k, v, q):
        """A KDA layer's state one token on by the gated delta rule
        (``kda_update.advance``: decay by ``alpha`` [B, H, D], a value a
        key; write ``beta`` [B, H] times ``outer(k, v - S^T k)``) -> its
        read-out ``S^T q`` [B, H, D]."""
        return self._advance(self._at[l], self._fresh, alpha, beta, k, v, q)


def make_paged_step(cfg, kv_config):
    """-> step(kv_carry, params, tok, pos, block_tables, context_lens[,
    state_slots][, window_tables]) returning (new_kv_carry, next_tokens,
    logits) and then the block's extras, if it has any (``_block``).

    ``kv_carry`` is ``PagedKVCache.carry()``: per-layer pools, K then V
    (then their scales for int8) for the attention layers, the latent
    layers' pools (and their index pools), the window layers' and then the
    recurrent layers' window and state slots, donated by ``CarriedStepFn``
    and written in place.  All shapes are static per lane bucket:
    tok/pos/context_lens [B], block_tables [B, MAXB].  ``context_lens[b]``
    counts the tokens valid AFTER this step's write (pos + 1 for live
    lanes, 0 for idle lanes, whose table points at the reserved scratch
    block 0: an idle lane feeds pos 0, so its write lands on row 0 of that
    block, the only row of the pool a step may change besides the live
    lanes' own).

    A model with recurrent layers takes ``state_slots`` [B] int32 too: the
    slot each lane's sequence holds (idle lanes name the scratch slot 0).
    A lane's window and state are read from its slot and written back to
    it (``push_windows``; ``state_update``), and a lane whose ``pos`` is 0
    starts from zeros whatever the slot holds, so a slot needs no clearing
    between sequences.

    A model with window layers takes, last, ``window_tables`` [B, R] int32:
    each lane's ring in the window layers' pools (``kv_cache.WindowRing``;
    idle lanes' rows are -1, which reads and writes the scratch block 0).
    A window layer writes position ``pos`` into the block of ring slot
    ``(pos // bs) % R`` and attends the ring's entries of the last
    ``cfg.window`` positions; it never looks at ``block_tables``.

    Feed-planning contract (what prefix caching leans on): the step
    WRITES exactly one position — ``pos``, into block
    ``block_tables[b, pos // bs]`` — and only READS every earlier
    position through the table.  The engine may therefore start a
    sequence at any ``pos > 0`` whose history blocks already hold valid
    K/V (shared prefix-cache blocks seeded into the table); those shared
    blocks are read-only by construction because every write lands at
    ``pos >= cached_tokens``, i.e. in a private tail block.  The values a
    cache hit skips recomputing are bitwise the ones this step would
    have produced, so output parity is structural, not numerical.  That
    holds for K and V.  A recurrent state at ``pos`` exists only in the
    slot of the sequence that computed it, so a model with recurrent
    layers starts every sequence at position 0 (the engine declines prefix
    reuse for it).  So does one with window layers: K and V older than the
    window are in no block."""
    bs = kv_config.block_size
    int8 = kv_config.dtype == "int8"
    block = _block(cfg)
    pool_of = _pool_index(cfg)
    taps = _conv_window(cfg)[0]
    windowed = frozenset(cfg.window_layers)
    latent = frozenset(cfg.latent_layers)
    ring = kv_config.window_ring
    # the rule a recurrent layer's state moves by, on the slots of a pool
    move = _kda.state_update if cfg.kda_layers else _ssm.state_update

    def step(kv_carry, params, tok, pos, block_tables, context_lens,
             *more):
        # what a model's kinds of layer add, in this order
        more = list(more)
        state_slots = more.pop(0) if cfg.recurrent_layers else None
        window_tables = more.pop(0).astype(jnp.int32) if windowed else None
        tok = tok.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        block_tables = block_tables.astype(jnp.int32)
        context_lens = context_lens.astype(jnp.int32)
        offs = pos % bs
        pools, state = kv_config.groups(kv_carry)
        lpools = kv_config.latent_pools(kv_carry)
        ipools = kv_config.index_pools(kv_carry)
        wpools = kv_config.window_groups(kv_carry)

        def block_of(tables, slot):
            return jnp.take_along_axis(
                jnp.maximum(tables, 0), slot[:, None], axis=1)[:, 0]

        # a layer's kind -> (its pools, the table that steers them, the
        # block this step writes, its window or None)
        written = block_of(block_tables, pos // bs)
        kinds = {False: (pools, block_tables, written, None)}
        if windowed:
            kinds[True] = (wpools, window_tables,
                           block_of(window_tables, (pos // bs) % ring),
                           cfg.window)

        def attend(l, q, k, v):
            i = pool_of[l]
            if l in latent:
                # one row a token, which is key and value both
                row = kv_config.latent_row
                with jax.named_scope("kv_write"):
                    lpools[i] = _write_rows(lpools[i], written, offs,
                                            _widened(k, row))
                if v is not None:
                    # the layer selects: score the lane's index keys (this
                    # token's among them), choose, attend the chosen rows
                    qi, w, ki = v
                    with jax.named_scope("index"):
                        ipools[i] = _write_rows(ipools[i], written, offs, ki)
                        scores = index_scores(qi, w, ipools[i], block_tables,
                                              context_lens)
                    with jax.named_scope("select"):
                        # the list, or (the masked walk) the set as a mask
                        positions, count = _pa.chosen_for_read(
                            choose(scores, context_lens, cfg.index_topk),
                            q.shape[:2] + (row,), lpools[i].shape,
                            lpools[i].dtype, cfg.latent_rank,
                            block_tables.shape[1])
                    return selected_latent_attention(
                        _widened(q, row), lpools[i], block_tables,
                        context_lens, positions, count, cfg.latent_scale,
                        cfg.latent_rank)
                return latent_attention(_widened(q, row), lpools[i],
                                        block_tables, context_lens,
                                        cfg.latent_scale, cfg.latent_rank)
            mine, tables, blk_ids, window = kinds[l in windowed]

            def write(group, rows):
                mine[group][i] = _write_rows(mine[group][i], blk_ids, offs,
                                             rows)

            if not int8:
                with jax.named_scope("kv_write"):
                    write(0, k)
                    write(1, v)
                return paged_attention(q, mine[0][i], mine[1][i], tables,
                                       context_lens,
                                       cfg.attention_multiplier, window)
            with jax.named_scope("kv_write"):
                for group, x in ((0, k), (1, v)):
                    payload, scale = _kv.quantize_kv(x)
                    write(group, payload)
                    write(group + 2, scale)
            with jax.named_scope("kv_gather"):
                kk, vv = (_kv.dequantize_kv(
                    gather_blocks(mine[g][i], tables).reshape(
                        k.shape[0], -1, *k.shape[1:]),
                    gather_blocks(mine[g + 2][i], tables))
                    for g in (0, 1))
            return masked_attention(q, kk, vv, context_lens,
                                    cfg.attention_multiplier, window)

        recur = None
        if state:
            slots = state_slots.astype(jnp.int32)
            # the window, then (for a kind that has one) the state
            windows, states = state if len(state) == 2 else (state[0], None)

            def push(i, fresh, xbc):
                windows[i], window = push_windows(windows[i], slots, fresh,
                                                  xbc, taps)
                return window

            def advance(i, fresh, *operands):
                states[i], y = move(states[i], slots, fresh, *operands)
                return y

            recur = _Recurrent(pool_of, pos, push,
                               advance if states is not None else None)

        logits, extras = block(params, cfg, tok, pos, attend,
                               context_lens > 0, recur)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tuple(a for group in pools + [lpools, ipools] + wpools
                      + state for a in group), nxt, logits) + tuple(extras)

    return step


def make_fed_step(cfg, kv_config, feed_width):
    """-> step(kv_carry, params, tok, prev_next, src, pos, block_tables,
    context_lens[, state_slots][, window_tables]): ``make_paged_step``'s
    step with each lane's input token chosen on the device, in the same
    executable.

    ``prev_next`` is the ``next_tokens`` of the step before, int32
    [feed_width], as that step returned them: a device array the host need
    not have read.  Lane ``b`` feeds ``prev_next[src[b]]`` where ``src[b]
    >= 0`` (the lane that produced its token a step ago) and the host's
    ``tok[b]`` where it is -1: a prompt or replayed token, a lane's first
    step, an idle lane, the very first step.  So the engine can dispatch
    step n+1 before it has fetched step n's tokens.  ``next_tokens`` comes
    back padded to ``feed_width`` (the engine's largest lane bucket), so it
    feeds the next step whatever the two steps' buckets; lane ``b``'s token
    is still at index ``b``.  Everything else is the inner step's."""
    step = make_paged_step(cfg, kv_config)

    def fed(kv_carry, params, tok, prev_next, src, pos, block_tables,
            context_lens, *more):
        src = src.astype(jnp.int32)
        tok = jnp.where(src >= 0, prev_next[jnp.maximum(src, 0)],
                        tok.astype(jnp.int32))
        carry, nxt, *rest = step(kv_carry, params, tok, pos, block_tables,
                                 context_lens, *more)
        return (carry, jnp.pad(nxt, (0, feed_width - nxt.shape[0])), *rest)

    return fed


def lane_columns(kv_config, maxb):
    """Where each of a step's per-lane integers lies in the one
    ``int32[bucket, C]`` array the host sends up a step (``make_packed_step``
    slices it, ``DecodeEngine._decode_step_locked`` fills it): ({name:
    ``slice`` of columns}, ``C``), in the order ``tok | src | pos | lens |
    [slot] | tables[maxb] | [ring[window_ring]]``.  The bracketed columns
    exist where the cache has what they steer: state slots for recurrent
    layers, rings for window layers."""
    widths = [("tok", 1), ("src", 1), ("pos", 1), ("lens", 1)]
    if kv_config.state_layers:
        widths.append(("slot", 1))
    widths.append(("tables", maxb))
    if kv_config.window_layers:
        widths.append(("ring", kv_config.window_ring))
    columns, at = {}, 0
    for name, width in widths:
        columns[name] = slice(at, at + width)
        at += width
    return columns, at


def make_packed_step(cfg, kv_config, feed_width):
    """-> step(kv_carry, params, prev_next, lanes): ``make_fed_step``'s
    step with every per-lane host array in one ``int32[bucket, C]`` array
    (``lane_columns``), cut by static column offsets in the same
    executable.  The host then uploads one array a step, whatever the
    model's cache holds, and the columns carry the integers the separate
    arrays did: the step's arithmetic is ``make_fed_step``'s."""
    fed = make_fed_step(cfg, kv_config, feed_width)

    def packed(kv_carry, params, prev_next, lanes):
        # the block table is as wide as the other columns leave it
        _, others = lane_columns(kv_config, 0)
        at, _ = lane_columns(kv_config, lanes.shape[1] - others)
        # the one-column quantities turned once, so that each is a row:
        # cut as columns they cost the device a relayout copy apiece
        head = lanes[:, :at["tables"].start].T
        one = lambda name: head[at[name].start]
        more = [one("slot")] if "slot" in at else []
        if "ring" in at:
            more.append(lanes[:, at["ring"]])
        return fed(kv_carry, params, one("tok"), prev_next, one("src"),
                   one("pos"), lanes[:, at["tables"]], one("lens"), *more)

    return packed


# -- multi-token paged step (speculative verify / prefill chunks) ------------

def make_paged_step_multi(cfg, kv_config, width):
    """-> step(kv_carry, params, tok, pos, block_tables, context_lens)
    scoring ``width`` query tokens per lane in ONE call: tok/pos/
    context_lens are [B, width], block_tables stays [B, MAXB]; returns
    (new_kv_carry, next_tokens [B, width], logits [B, width, vocab]) and
    then the block's extras summed over the columns.

    The body is the single-token step composed ``width`` times inside
    one jit — each position runs the IDENTICAL write-then-attend op
    sequence at identical shapes, which is what keeps a speculative
    verify's argmax chain bitwise-equal to ``width`` non-speculative
    steps (the acceptance bar the spec parity tests assert).  Column j
    of pos/context_lens belongs to query j; lanes feeding fewer than
    ``width`` real tokens freeze their later columns' lens so the junk
    columns' (discarded) logits never read an unwritten position, and
    their writes land beyond every lens — overwritten before any later
    step can attend to them.  A recurrent state has no such "beyond": a
    model with recurrent layers takes ``state_slots`` [B] after the lens,
    and every column of a live lane has to be a real token (a chunk of
    prefill; its state cannot be rolled back, which is why the engine
    refuses it speculation).  A ring holds one write beside its window, so
    a model with window layers has no multi-token step.  A latent layer
    that selects does so a query: each column is the single step, which
    scores, chooses and attends over its own prefix."""
    if cfg.window_layers:
        raise ValueError("a multi-token step is not planned over window "
                         "layers' rings")
    base = make_paged_step(cfg, kv_config)

    def step(kv_carry, params, tok, pos, block_tables, context_lens,
             *tail):
        tok = tok.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        context_lens = context_lens.astype(jnp.int32)
        nxts, logits, extras = [], [], None
        for j in range(width):
            tok_j = tok[:, j]
            if nxts:
                # an argmax is never negative, so this is tok[:, j]; what
                # it adds is column j's dependence on column j-1's result.
                # Without it column j's write and column j-1's read of one
                # pool are unordered, and a compiler may keep both by
                # copying the pool (XLA:CPU does) instead of writing in
                # place after the read.
                tok_j = jnp.where(nxts[-1] < 0, nxts[-1], tok_j)
            kv_carry, nxt, lg, *more = base(
                kv_carry, params, tok_j, pos[:, j], block_tables,
                context_lens[:, j], *tail)
            nxts.append(nxt)
            logits.append(lg)
            extras = more if extras is None \
                else [a + b for a, b in zip(extras, more)]
        return (kv_carry, jnp.stack(nxts, axis=1),
                jnp.stack(logits, axis=1)) + tuple(extras)

    return step


# -- draft rollout (speculative proposals) -----------------------------------

def make_draft_rollout(cfg, kv_config, k):
    """-> step(kv_carry, params, tok, pos, block_tables, context_lens,
    max_pos) proposing ``k`` tokens per lane in ONE call: feed tok[b] at
    pos[b], take the argmax, feed it at pos[b]+1, ... — the draft's
    greedy chain, writing its K/V through the draft's own paged lanes as
    it goes.  tok/pos/context_lens/max_pos are [B] (context_lens 0 marks
    an idle lane, whose writes land in the scratch block and whose lens
    stays frozen at 0).  ``max_pos`` clamps the chain's write position:
    a lane whose sequence budget ends before p+k-1 keeps re-writing its
    final reserved position instead of touching blocks it never
    reserved — those clamped writes sit beyond the accepted
    context_lens, so they are re-written before anything attends them.
    Returns (new_kv_carry, proposals [B, k])."""
    base = make_paged_step(cfg, kv_config)

    def step(kv_carry, params, tok, pos, block_tables, context_lens,
             max_pos):
        tok = tok.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        context_lens = context_lens.astype(jnp.int32)
        max_pos = max_pos.astype(jnp.int32)
        live = context_lens > 0
        props = []
        for j in range(k):
            kv_carry, nxt = base(
                kv_carry, params, tok,
                jnp.minimum(pos + j, max_pos), block_tables,
                jnp.where(live,
                          jnp.minimum(context_lens + j, max_pos + 1), 0))[:2]
            props.append(nxt)
            tok = nxt
        return kv_carry, jnp.stack(props, axis=1)

    return step


# -- unpaged reference -------------------------------------------------------

def make_unpaged_step(cfg, pad_len, ring_len=None):
    """Reference step over contiguous per-lane K/V [L, B, pad_len, KH, D]
    (``L`` the attention layers).  Same ``masked_attention`` core at the
    same [B, pad_len, KH, D] shapes as the paged gather path — the bitwise
    comparison target.  A model with window layers carries their K and V
    next, ``[Lw, B, ring_len, KH, D]``, position ``p`` at row ``p %
    ring_len`` (``ring_len`` None: ``cfg.window`` rows; the paged step's
    gathered ring is ``window_ring * block_size`` long, and the bitwise
    comparison wants that).  A model with latent layers carries their rows
    after the global K and V, ``[Ll, B, pad_len, latent_row]``, a row as the
    paged pool holds it, and where they select their index keys after those,
    ``[Ll, B, pad_len, index_head_dim]`` (scored densely, the positions not
    chosen masked: the paged gather path's mathematics).  A model with
    recurrent layers carries their
    window ``[Lr, B, (K - 1) * W]`` and state ``[Lr, B, N, I]`` last, a
    lane a row (the window alone where the layers keep no state), through
    the same ``_Recurrent`` as the paged step."""
    block = _block(cfg)
    pool_of = _pool_index(cfg)
    windowed = frozenset(cfg.window_layers)
    latent = frozenset(cfg.latent_layers)
    advance_values = _kda.advance if cfg.kda_layers else _ssm.advance
    taps = _conv_window(cfg)[0]

    def step(kv_carry, params, tok, pos, context_lens):
        tok = tok.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        context_lens = context_lens.astype(jnp.int32)
        # K and V of the global layers, then the latent layers' rows, then
        # K and V of the window layers
        first = 2 + bool(latent) + bool(latent and cfg.index_topk)
        kv = list(kv_carry[:first + (2 if windowed else 0)])
        state = list(kv_carry[len(kv):])
        lanes = jnp.arange(kv[0].shape[1], dtype=jnp.int32)

        def attend(l, q, k, v):
            i = pool_of[l]
            if l in latent:
                row = kv[2].shape[-1]
                kv[2] = kv[2].at[i, lanes, pos].set(
                    _widened(k, row).astype(kv[2].dtype))
                if v is not None:
                    qi, w, ki = v
                    kv[3] = kv[3].at[i, lanes, pos].set(
                        ki.astype(kv[3].dtype))
                    positions, count = choose(
                        dense_index_scores(qi, w, kv[3][i], context_lens),
                        context_lens, cfg.index_topk)
                    return masked_latent(
                        _widened(q, row), kv[2][i], context_lens,
                        cfg.latent_scale, cfg.latent_rank,
                        chosen_mask(positions, count, kv[2].shape[2]))
                return masked_latent(_widened(q, row), kv[2][i],
                                     context_lens, cfg.latent_scale,
                                     cfg.latent_rank)
            at = first if l in windowed else 0
            row = pos % kv[at].shape[2] if at else pos
            for j, x in ((at, k), (at + 1, v)):
                kv[j] = kv[j].at[i, lanes, row].set(x.astype(kv[j].dtype))
            return masked_attention(q, kv[at][i], kv[at + 1][i],
                                    context_lens, cfg.attention_multiplier,
                                    cfg.window if at else None)

        def push(i, fresh, xbc):
            new, window = pushed(_ssm.started(fresh, state[0][i]), xbc, taps)
            state[0] = state[0].at[i].set(new)
            return window

        def advance(i, fresh, *operands):
            new, y = advance_values(_ssm.started(fresh, state[1][i]),
                                    *operands)
            state[1] = state[1].at[i].set(new)
            return y

        recur = _Recurrent(pool_of, pos, push,
                           advance if len(state) > 1 else None) \
            if state else None
        logits, _extras = block(params, cfg, tok, pos, attend,
                                context_lens > 0, recur)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (*kv, *state), nxt, logits

    return step


def _unpaged_carry(cfg, lanes, pad_len, ring_len=None):
    """Zeros for ``make_unpaged_step``'s carry: K and V in the residency
    the paged pool would have (bf16 for a bf16 model; the f32 default
    otherwise: int8 has no unpaged twin), the window layers' rings, and the
    recurrent layers' window and state as the paged cache would hold
    them."""
    kv_dtype = jnp.bfloat16 if cfg.kv_dtype == "bf16" else jnp.float32
    carry = tuple(jnp.zeros((len(cfg.attn_layers), lanes, pad_len,
                             cfg.kv_heads, cfg.head_dim), kv_dtype)
                  for _ in range(2))
    if cfg.latent_layers:
        carry += (jnp.zeros((len(cfg.latent_layers), lanes, pad_len,
                             _kv.latent_row_of(cfg.latent_width)),
                            kv_dtype),)
        if cfg.index_topk:
            carry += (jnp.zeros((len(cfg.latent_layers), lanes, pad_len,
                                 cfg.index_head_dim), kv_dtype),)
    if cfg.window_layers:
        carry += tuple(jnp.zeros((len(cfg.window_layers), lanes,
                                  ring_len or cfg.window, cfg.kv_heads,
                                  cfg.head_dim), kv_dtype)
                       for _ in range(2))
    return carry + tuple(
        jnp.zeros((len(cfg.recurrent_layers), lanes) + shape,
                  _kv._PAYLOAD[dt][0])
        for shape, dt in _state_shapes(cfg))


def unpaged_generate(cfg, params, prompt_ids, max_new, pad_len=None,
                     eos_id=-1, return_logits=False, ring_len=None):
    """Greedy single-sequence reference loop (no paging, no batching):
    feed the prompt one token per step, then decode ``max_new`` tokens.
    ``pad_len`` must match the paged path's gathered history length
    (MAXB * block_size) for the bitwise comparison, and for a model with
    window layers ``ring_len`` its gathered ring's (``window_ring *
    block_size``)."""
    if pad_len is None:
        pad_len = cfg.max_seq
    step = jax.jit(make_unpaged_step(cfg, pad_len, ring_len),
                   donate_argnums=(0,))
    jparams = laid_out(cfg, params)
    kv = _unpaged_carry(cfg, 1, pad_len, ring_len)
    prompt_ids = [int(t) for t in prompt_ids]
    out, logits_hist = [], []
    tok = prompt_ids[0]
    pos = 0
    while len(out) < max_new:
        kv, nxt, logits = step(
            kv, jparams, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray([pos + 1], jnp.int32))
        pos += 1
        if pos < len(prompt_ids):
            tok = prompt_ids[pos]          # still feeding the prompt
            continue
        tok = int(nxt[0])
        out.append(tok)
        if return_logits:
            logits_hist.append(np.asarray(logits[0]))
        if tok == eos_id:
            break
    return (out, logits_hist) if return_logits else out
