"""Xing4.0's decoder block (XingChen-AGI/Xing4.0-29B-A4B: ``model_type``
``xing4_0``) as pure functions of ``(params, cfg, tok, pos, attend, live,
recur)``, called by the decode steps of ``serving/decode_model.py`` under the
same contract as the other blocks: one token per lane through every layer.
The mixer and the feed-forward are ``dots_vlm``'s to the letter (every layer
``latent``: a compressed query, one cached row ``[c | rotated k_pe]`` a
token, YaRN's frequencies and ``m^2``, served absorbed through
``kimi_linear.latent_mixer``; ``cfg.dense_layers`` leading gated MLPs, then
``exaone_moe``'s routed layer, the share it may hold included, beside one
shared expert).  What is its own is the residual path: a token's stream is
``cfg.hc_mult`` vectors, mixed round every sublayer by manifold-constrained
hyper-connections (``hyper_connections.py`` has the equations)::

    X = embed(tok) repeated hc_mult times                      # [n, C]
    a layer, twice (F the latent mixer with ln1_g, then the feed-forward
    with ln2_g; each sublayer its own phi, b, a):
        H_pre, H_post, H_res = maps(X)        # from the streams themselves
        u  = H_pre X                          # what the sublayer reads
        X  = H_res X + H_post^T F(rmsnorm(u, ln_g))
    logits = rmsnorm(sum_i X_i, lnf_g) @ head

A layer's two sublayers are ``attn`` and ``mlp`` in parameter and scope
names, whatever the feed-forward is.  The multi-token-prediction module of
the source is no part of this block (the configuration's ``departures``).

Precision: the streams, the maps and both mixings float32, their parameters
held in float32 whatever the weights' dtype; everything inside a sublayer as
the other bfloat16 blocks (matmul inputs in the weights' dtype with float32
accumulation; norms, the rotation, the sigmoid and gates float32).

Params (``init_params`` makes seeded ones): ``dots_vlm``'s, and per layer
``l<i>_`` + ``hc_attn_phi [n C, 2 n + n^2]``, ``hc_attn_b [2 n + n^2]``,
``hc_attn_a [3]`` and ``hc_mlp_phi``, ``hc_mlp_b``, ``hc_mlp_a`` alike.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import dots_vlm as _dots
from . import exaone_moe as _exaone
from . import hyper_connections as _hc
from . import kimi_linear as _kimi
from .decoder_family import DecoderFamily
from .olmoe import _rmsnorm

__all__ = ["token_logits", "layer", "streams_in", "logits_out", "rotation",
           "param_shapes", "init_params", "laid_out", "routed_part",
           "shared_part", "SUBLAYERS", "A_INIT", "B_STD", "B_RES_DIAGONAL",
           "FAMILY"]

FAMILY = DecoderFamily(kinds=("latent",), routes="after_dense",
                       expert_matrices=3, dense_lead=True, holds_share=True,
                       own_stream_width=True, grouped_router=True,
                       rotated_latent=True, shared_expert=True,
                       residual_streams=True)

# a layer's sublayers, as its mixings' parameters and scopes name them
SUBLAYERS = ("attn", "mlp")
# what a seeded mixing draws beside ``phi`` (normal, the weights' deviation).
# ``a``: ``a_pre``, ``a_post``, ``a_res``, the dynamic part's weights.  ``r``
# has unit root-mean-square over ``n C`` values, so a column of ``r @ phi``
# has the deviation ``std * sqrt(n C)`` (2.39 at the published 14,336 and
# 0.02): at 1 the sigmoids of ``H_pre`` and ``H_post`` move over most of
# their range from token to token.  ``a_res`` is a quarter: at 1 the entries
# of ``M_0`` would lie ``e^+-5`` apart and the 20 published iterations leave
# 1% of tokens' ``H_res`` with a row sum over 0.02 off 1 (200,000 draws on
# the CPU); at a quarter (log-entries 0.6 apart) the worst of them is 0.002
# off, and a trained model's ``H_res`` is what its iterations make doubly
# stochastic.  ``b``: normal(0, ``B_STD``), and ``b_res`` that plus
# ``B_RES_DIAGONAL`` on the diagonal, so that a seeded ``H_res`` keeps
# 0.38-0.54 of a stream in place in the mean: neither the identity (1, the
# paper's start) nor the uniform mixing (0.25) that would hide a transposed
# or a skipped ``H_res``.
A_INIT = (1.0, 1.0, 0.25)
B_STD = 0.5
B_RES_DIAGONAL = 1.0

routed_part = _exaone.routed_part
shared_part = _exaone.shared_part
# every layer's ``wkvb`` as ``latent_mixer`` multiplies it (a mixing's
# ``phi`` needs no layout of its own: ``hyper_connections`` says why)
laid_out = _kimi.laid_out


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias | hc_phi |
    hc_b | hc_a: ``dots_vlm``'s, and each layer's two mixings."""
    shapes = _dots.param_shapes(cfg)
    for l in range(cfg.layers):
        for sub in SUBLAYERS:
            for name, shape, kind in _hc.param_shapes(cfg, sub):
                shapes["l%d_%s" % (l, name)] = (shape, kind)
    return shapes


def hc_draw(r, n, shape, kind, std):
    """One array of a mixing, float32, from ``r`` (a ``RandomState``):
    ``phi`` normal(0, ``std``); ``b`` normal(0, ``B_STD``) with
    ``B_RES_DIAGONAL`` added on ``b_res``'s diagonal; ``a`` ``A_INIT``."""
    if kind == "hc_phi":
        return (r.standard_normal(shape) * std).astype(np.float32)
    if kind == "hc_a":
        return np.asarray(A_INIT, np.float32)
    b = r.standard_normal(shape) * B_STD
    b[2 * n:] += B_RES_DIAGONAL * np.eye(n).reshape(-1)
    return b.astype(np.float32)


def init_params(cfg, seed=0, std=0.02, bias_std=_dots.BIAS_STD, hc_std=None):
    """name -> np array: ``dots_vlm``'s in the config's weight dtype
    (``std``-normal weights, norms at 1, ``expert_bias`` normal(0,
    ``bias_std``)) and the mixings' in float32 (``hc_draw``, a stream of
    draws of their own; ``phi`` normal(0, ``hc_std``), ``std`` where None).
    Host-side: tests and demo bundles."""
    params = _dots.init_params(cfg, seed, std, bias_std)
    r = np.random.RandomState([seed, cfg.hc_mult])
    for name, (shape, kind) in sorted(param_shapes(cfg).items()):
        if name not in params:
            params[name] = hc_draw(r, cfg.hc_mult, shape, kind,
                                   std if hc_std is None else hc_std)
    return params


def streams_in(params, cfg, tok):
    """The streams a step starts from: each lane's embedding ``cfg.hc_mult``
    times, [B, n, C] float32 (scope ``hc/start``)."""
    with jax.named_scope("hc/start"):
        return _hc.start(jnp.take(params["embed"], tok, axis=0), cfg.hc_mult)


def logits_out(params, cfg, X):
    """The streams' sum through the final norm and the untied head ->
    logits [B, vocab] float32 (scopes ``hc/sum``, ``lm_head``)."""
    with jax.named_scope("hc/sum"):
        x = _hc.total(X)
    with jax.named_scope("lm_head"):
        return _exaone._head(x, params, cfg.norm_eps)


# ``rotation(cfg, pos)``: a step's turn of the rotated values by the lanes'
# positions, made once a step and handed to every layer
rotation = _dots._rotation


def layer(cfg, p, l, X, attend, rotate, live, seen=None, kept=None):
    """Layer ``l`` over the streams X [B, n, C] float32, ``p(name)`` its
    parameters: the latent mixer and the feed-forward (dense where ``l <
    cfg.dense_layers``), each round its mixing -> (X, ``chosen`` [B, E] bool
    over the whole router or None for a dense layer, the groups each token
    kept [B, n_group] bool or None).  Under scope ``layer<l>``; ``seen`` and
    ``kept`` as ``token_logits``'s.  By itself so that what serves a layer
    at a time (the benchmark's balancing of ``expert_bias``) compiles one
    dense and one routed layer and not the model."""
    eps = cfg.norm_eps
    routing = [None, None]

    def mixed(sub, ln_g, F, X):
        """One sublayer ``F`` round its mixing: the streams behind it."""
        with jax.named_scope("hc/%s_maps" % sub):
            phi, b, a = (p("hc_%s_%s" % (sub, x)) for x in ("phi", "b", "a"))
            pre, post, res = _hc.maps(cfg, phi, b, a, X)
        if kept is not None:
            kept[l, sub] = (pre, post, res)
        with jax.named_scope("hc/%s_read" % sub):
            u = _hc.read(pre, X)
        y = F(_rmsnorm(u, p(ln_g), eps))
        with jax.named_scope("hc/%s_merge" % sub):
            return _hc.merge(X, y, post, res)

    def mixer(h):
        with jax.named_scope("latent"):
            return _kimi.latent_mixer(cfg, p, l, h, attend, rotate)

    def dense(h2):
        with jax.named_scope("mlp"):
            return _exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))

    def sparse(h2):
        with jax.named_scope("moe"):
            group = []
            if seen is not None:
                seen.append(h2)
            f, routing[0] = routed_part(cfg, p, h2, live, group)
            # one group: every token keeps it
            routing[1] = group[0] if group else live[:, None]
            return f + shared_part(p, h2)

    with jax.named_scope("layer%d" % l):
        X = mixed("attn", "ln1_g", mixer, X)
        X = mixed("mlp", "ln2_g", dense if l < cfg.dense_layers else sparse,
                  X)
    if kept is not None:
        kept[l, "streams"] = X
    return (X,) + tuple(routing)


def token_logits(params, cfg, tok, pos, attend, live, recur=None, seen=None,
                 kept=None):
    """-> (logits [B, vocab] float32, (routed, groups)) as ``dots_vlm``'s:
    ``routed`` int32 [routed layers, experts] the tokens of live lanes sent
    to each expert of the whole router this step, ``groups`` int32 [routed
    layers, n_group] the live lanes that kept each group.  Scope names:
    ``hc/start`` and ``hc/sum`` at the two ends; in a layer
    ``layer<i>/hc/attn_maps``, ``/hc/attn_read``, ``/hc/attn_merge`` round
    ``layer<i>/latent/*`` (``dots_vlm``'s) and ``/hc/mlp_maps``,
    ``/hc/mlp_read``, ``/hc/mlp_merge`` round ``layer<i>/mlp`` or
    ``layer<i>/moe/router``, ``/experts``, ``/shared``; ``lm_head``.  A list
    given as ``seen`` receives each routed layer's router input ``[B, H]``
    float32 (what a balancing of ``expert_bias`` reads); a dict given as
    ``kept`` receives ``(layer, sublayer) -> (H_pre, H_post, H_res)`` and,
    under ``(layer, "streams")``, the streams behind the layer (what a check
    compares; the decode steps pass neither)."""
    X = streams_in(params, cfg, tok)
    rotate = rotation(cfg, pos)
    counted = lambda mask: jnp.sum(mask & live[:, None], axis=0,
                                   dtype=jnp.int32)
    routed, groups = [], []
    for l in range(cfg.layers):
        def p(name, _l=l):
            return params["l%d_%s" % (_l, name)]

        X, chosen, group = layer(cfg, p, l, X, attend, rotate, live, seen,
                                 kept)
        if chosen is not None:
            routed.append(counted(chosen))
            groups.append(counted(group))
    logits = logits_out(params, cfg, X)
    # a cut that keeps the dense layers alone has nothing to count
    return logits, (jnp.stack(routed), jnp.stack(groups)) if routed else ()
