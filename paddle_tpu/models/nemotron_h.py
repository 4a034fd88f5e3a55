"""Nemotron-H decoder block (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16:
``model_type`` ``nemotron_h``) as pure functions of ``(params, cfg, tok,
pos, attend, live, recur)``, called by the decode steps of
``serving/decode_model.py`` under the same contract as the other blocks: one
token per lane through every layer.  A layer here is ONE pre-norm sublayer
and nothing else, of a kind named in ``cfg.layer_types`` (the source's
``hybrid_override_pattern``, a letter a layer):

* ``mamba`` (``M``): a Mamba-2 mixer, Granite's (``granite_hybrid.
  mamba_mixer``, the same function) with B and C in ``cfg.ssm_groups``
  groups: head ``h`` reads group ``h // (heads / G)``, the convolution is
  ``I + 2 G N`` wide and the gated norm is over each group's ``I / G``
  values.  It keeps a window and a state a sequence (``recur``).
* ``attention`` (``*``): grouped-query attention, ``cfg.heads`` query heads
  over ``cfg.kv_heads`` KV heads, **no** position encoding (the mamba layers
  carry position), scores scaled by ``head_dim ** -0.5``; the stream
  (``cfg.hidden``) is narrower than the query heads together.
  ``attend(l, q, k, v)`` owns the KV write and the history read.
* ``experts`` (``E``): a feed-forward alone, which keeps nothing between
  tokens: ``cfg.experts`` experts of two matrices routed by sigmoid scores,
  ``cfg.experts_per_token`` a token, beside one shared expert of width
  ``cfg.shared_ffn`` that every token passes through.

For hidden ``x`` of one token::

    x = x + mixer_l(rmsnorm(x, norm))
    attention:  q = h @ wq [heads x D];  k, v = h @ wk, h @ wv [KH x D]
                mixer = attend(q, k, v) @ wo
    mamba:      granite_hybrid.mamba_mixer (its docstring has the equations)
    experts:    s = sigmoid(h @ router)                      # [E], float32
                S = the experts_per_token largest of s + expert_bias
                w_e = routed_scaling * s_e / (sum_{e in S} s_e + 1e-20)
                mixer = sum_{e in S, e held} w_e * (relu(h @ up_e^T)^2 @ down_e)
                        + relu(h @ shared_up)^2 @ shared_down

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  The router is
``lfm2_moe._route`` with DeepSeek-V3's denominator (``n_group`` 1,
``topk_group`` 1: one group); the bias chooses and never weighs; no
capacity.

**The share**, as ``exaone_moe``'s: one chip of a deployment that divides
each layer's experts over several holds ``cfg.experts_held`` of the
``cfg.experts``, from ``cfg.expert_first`` on.  The router keeps its width
and its experts a token, the gates are renormalised over all the chosen,
held or not, ``experts_up`` / ``experts_down`` are the held experts' alone,
and what an absent expert would add is left out.  ``routed_part`` is one
share's routed sum and ``shared_part`` what every share computes alike; over
all shares, the shared part counted once, they add up to the whole layer
(tests/test_nemotron_h.py).  No exchange is stood in for.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the sigmoid and gates, the convolution,
the state update and the residual additions float32; the state float32
wherever it lives.  The routed sum is ``pallas_kernels/moe_experts.py``
``relu2_experts`` over the held experts' columns of the gates.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``norm [H]``; attention layers
``wq [H, heads * D]``, ``wk``, ``wv [H, KH * D]``, ``wo [heads * D, H]``;
mamba layers as ``granite_hybrid.mamba_param_shapes``; experts layers
``router [H, E]``, ``expert_bias [E]``, ``experts_up``, ``experts_down
[Eh, F, H]`` (``Eh`` the experts held; ``up`` in its ``nn.Linear``
orientation, so both have ``H`` as the minor dimension), ``shared_up [H,
Fs]``, ``shared_down [Fs, H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..pallas_kernels import moe_experts as _moe
from . import granite_hybrid as _granite
from . import lfm2_moe as _lfm2
from .exaone_moe import GATE_EPS
from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "routed_part",
           "shared_part", "BIAS_STD", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention", "mamba", "experts"),
                       grouped_query=True, routes="experts_layers",
                       expert_matrices=2, holds_share=True,
                       own_stream_width=True, shared_expert=True)

# standard deviation of a seeded ``expert_bias``.  This stream is pre-norm:
# the router sees rmsnorm(x), entries of root-mean-square 1, so its logits
# (weights normal(0, 0.02) over 2,688) have a standard deviation of 1.04 and
# the sigmoid scores spread over most of (0, 1); a token's sixth and seventh
# best lie 0.007 apart (median), not 1e-4 as behind K-EXAONE's output norms,
# whose 0.001 would move nothing here.  A bias of 0.01 re-decides the choice
# on 35-49% of tokens (a block that ignores it is seen) and leaves the held
# experts a 32-lane step hits at 12.25-12.53 of 16 over six seeds, where an
# even router reads 12.47-12.71 (12.56 expected); 0.03 reads 11.2-11.8 and
# LFM2's 0.1 reads 7.0-8.3, and a run's time would hang on its seed
# (tests/test_nemotron_h.py derives these from tokens drawn apart).  Served
# whole on the chip a step hits 9.65-10.2: behind 52 blocks of seeded weights
# the lanes' streams share a component and favour the same experts, bias or
# no bias, and that too holds from seed to seed (PERF.md section 6, PR 41).
BIAS_STD = 0.01


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias | conv |
    a_log | dt_bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    qw, kv = cfg.heads * d, cfg.kv_heads * d
    e, held, fe, fs = cfg.experts, cfg.experts_held, cfg.ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    kinds = {
        "attention": (("wq", (h, qw), "normal"), ("wk", (h, kv), "normal"),
                      ("wv", (h, kv), "normal"), ("wo", (qw, h), "normal")),
        "experts": (("router", (h, e), "normal"),
                    ("expert_bias", (e,), "bias"),
                    ("experts_up", (held, fe, h), "normal"),
                    ("experts_down", (held, fe, h), "normal"),
                    ("shared_up", (h, fs), "normal"),
                    ("shared_down", (fs, h), "normal")),
    }
    if cfg.ssm_layers:
        kinds["mamba"] = _granite.mamba_param_shapes(cfg)
    for l, kind in enumerate(cfg.layer_types):
        for name, shape, init in (("norm", (h,), "ones"),) + kinds[kind]:
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms and ``D`` at 1, ``expert_bias`` normal(0, ``bias_std``)
    (at zero a block that ignores it is indistinguishable), and Mamba-2's
    own start for the convolution, ``A_log`` and ``dt_bias``
    (``granite_hybrid.init_params`` says why).  Host-side: tests and demo
    bundles."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]

    def make(shape, kind):
        if kind == "bias":
            return r.standard_normal(shape) * bias_std
        return _granite.draw(r, cfg, shape, kind, std)

    return {name: make(shape, kind).astype(np.float32).astype(dtype)
            for name, (shape, kind) in sorted(param_shapes(cfg).items())}


def _relu2_mlp(x, up, down):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up))), down)


def routed_part(cfg, p, x, live):
    """-> (this share's routed sum [B, H] float32: the held experts' part
    for the tokens routed to them; ``chosen`` [B, E] bool over the whole
    router).  ``p(name)`` is the layer's parameter."""
    with jax.named_scope("router"):
        gates, chosen = _lfm2._route(
            x, p("router"), p("expert_bias"), cfg.experts_per_token,
            cfg.routed_scaling, GATE_EPS)
    with jax.named_scope("experts"):
        y = _moe.relu2_experts(x, gates[:, cfg.held_experts], live,
                               p("experts_up"), p("experts_down"))
    return y, chosen


def shared_part(p, x):
    """The shared expert's output [B, H]: the same on every share."""
    with jax.named_scope("shared"):
        return _relu2_mlp(x, p("shared_up"), p("shared_down"))


def token_logits(params, cfg, tok, pos, attend, live, recur):
    """-> (logits [B, vocab] float32, (routed,)) with ``routed`` int32
    [experts layers, experts]: the tokens of live lanes sent to each expert
    of the whole router this step, a row a layer of ``cfg.routed_layers``
    (``cfg.held_experts`` are the columns computed here).  Scope names as
    the other blocks' (``layer<i>/attn``, ``.../kv_write``, ``.../kv_read``;
    ``layer<i>/ssm/`` + ``in_proj``, ``conv``, ``state_update``,
    ``out_proj``; ``layer<i>/moe/router``, ``.../moe/experts``,
    ``.../moe/shared``; ``lm_head``)."""
    del pos                             # no position encoding
    bb = tok.shape[0]
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    routed = []
    for l, kind in enumerate(cfg.layer_types):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("norm"), eps)
            if kind == "attention":
                with jax.named_scope("attn"):
                    q = _mm(h, p("wq")).reshape(bb, cfg.heads, cfg.head_dim)
                    k, v = (_mm(h, p(w)).reshape(bb, cfg.kv_heads,
                                                 cfg.head_dim)
                            for w in ("wk", "wv"))
                    a = attend(l, q, k, v).reshape(bb, -1)
                    x = x + _mm(a, p("wo"))
            elif kind == "mamba":
                with jax.named_scope("ssm"):
                    x = x + _granite.mamba_mixer(cfg, p, l, h, recur)
            else:
                with jax.named_scope("moe"):
                    f, chosen = routed_part(cfg, p, h, live)
                    routed.append(jnp.sum(chosen & live[:, None], axis=0,
                                          dtype=jnp.int32))
                    x = x + f + shared_part(p, h)
    with jax.named_scope("lm_head"):
        logits = _mm(_rmsnorm(x, params["lnf_g"], eps), params["head"])
    # a cut that keeps no experts layer has nothing to count
    return logits, (jnp.stack(routed),) if routed else ()
