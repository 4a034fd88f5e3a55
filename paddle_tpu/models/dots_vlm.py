"""dots.vlm1's language-model block (rednote-hilab/dots.vlm1.inst:
``model_type`` ``dots_vlm``, the DeepSeek-V3 decoder) as pure functions of
``(params, cfg, tok, pos, attend, live, recur)``, called by the decode steps
of ``serving/decode_model.py`` under the same contract as the other blocks:
one token per lane through every layer.  Every layer's mixer is of ONE kind,
``latent``, and the feed-forward of two, by the layer's place:

* ``latent``: multi-head latent attention served *absorbed*
  (``kimi_linear.latent_mixer``, with both its options): the query is
  compressed to ``cfg.q_rank`` values, normed and projected up; the cache
  keeps one row a token, ``[c | k_pe]`` (``latent_rank + latent_rope``
  values), whose ``k_pe`` is **rotated by position** before it is written,
  as each head's ``q_pe`` is before the absorb; the scores' scale carries
  YaRN's ``m^2`` (``cfg.latent_scale``).
* the first ``cfg.dense_layers`` layers end in a SiLU-gated MLP of width
  ``cfg.dense_ffn``; every later one in ``exaone_moe``'s routed layer (the
  share it may hold included) beside one shared expert, its router keeping
  ``cfg.topk_group`` of ``cfg.n_group`` groups before it chooses experts.

Pre-norm throughout.  For hidden ``x`` of one token at position ``t``, ``D``
a head's own key and value width, ``P`` the shared rotated part::

    h = rmsnorm(x, ln1_g);  x = x + mla(h)
    mla:  cq = rmsnorm(h @ wq_a, q_norm);  q = cq @ wq_b -> a head [q_nope D | q_pe P]
          [c | k_pe] = h @ wkva;  c = rmsnorm(c, kv_norm)
          k_pe = rope(k_pe, t);  q_pe_i = rope(q_pe_i, t);  row(t) = [c | k_pe]
          q_lat_i = wkvb_i^K q_nope_i
          score_i(s) = (q_lat_i . c(s) + q_pe_i . k_pe(s)) * (D + P)^-0.5 * m^2
          mla = concat(wkvb_i^V^T sum_s softmax_s(score_i) c(s)) @ wo
    rope: pair j = values (2j, 2j + 1) of the P, turned by t * f_j and laid
          [first of each pair | second of each pair] (the family's code);
          f_j YaRN's: 1 / theta^(2j/P) and that over ``factor``, blended by
          the linear ramp between the two correction dims (``yarn_inv_freq``)
    m  =  0.1 * mscale_all_dim * ln(factor) + 1;  cos and sin are scaled by
          yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    h2 = rmsnorm(x, ln2_g);  x = x + ffn(h2)        # dense, or routed + shared

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  The
multi-token-prediction module and the vision tower of the source are no part
of this block: the one is a training head and a self-draft, the other has no
published sizes here; token ids reach every layer of the language model.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the rotation, the sigmoid and gates and the
residual additions float32.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``head [H,
V]``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``, ``wq_a [H,
Rq]``, ``q_norm [Rq]``, ``wq_b [Rq, heads * (D + P)]`` (``wq [H, heads * (D
+ P)]`` where ``q_rank`` is 0), ``wkva [H, rank + P]``, ``kv_norm [rank]``,
``wkvb [rank, heads * 2 D]``, ``wo [heads * D, H]``; dense layers ``w1``,
``w3 [H, F]``, ``w2 [F, H]``; routed layers as ``exaone_moe``'s.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import exaone_moe as _exaone
from . import kimi_linear as _kimi
from .decoder_family import DecoderFamily
from .olmoe import _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "laid_out",
           "routed_part", "shared_part", "yarn_inv_freq", "BIAS_STD",
           "FAMILY"]

FAMILY = DecoderFamily(kinds=("latent",), routes="after_dense",
                       expert_matrices=3, dense_lead=True, holds_share=True,
                       own_stream_width=True, grouped_router=True,
                       rotated_latent=True, shared_expert=True)

# standard deviation of a seeded ``expert_bias``.  The stream is pre-norm:
# the router sees rmsnorm(x), its logits (weights normal(0, 0.02) over
# 7,168) have a standard deviation of 1.69, and a sigmoid score at the
# threshold of the choice has a slope of 0.065: a bias of 0.01 (Kimi-Linear's,
# the first value here) moves an expert's popularity by 18% and gave the
# held sixteen 15.3-17.4 of a step's 256 assignments by the seed on the chip,
# so a run's time hung on its seed (PERF.md section 6, PR 49).  0.001,
# K-EXAONE's, read on the CPU (tests/test_dots_vlm.py, tokens drawn apart, 32
# lanes x 8 of 256 in 4 of 8 groups): it re-decides the choice on 10-14% of
# tokens (the groups' choice is re-decided too: a block that ignores it is
# seen) and group 0 is kept by 48-54% of tokens.  The benchmark's cell
# starts from this draw and balances it on the block's own states
# (``benchmark/models/dots_vlm_decoder.py`` ``balanced``, through
# ``token_logits``'s ``seen``): random router rows are uneven by themselves.
BIAS_STD = 0.001

routed_part = _exaone.routed_part
shared_part = _exaone.shared_part
# every layer's ``wkvb`` as ``latent_mixer`` multiplies it
laid_out = _kimi.laid_out


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    qw = cfg.heads * (d + cfg.latent_rope)
    e, held, fe, fd, fs = cfg.experts, cfg.experts_held, cfg.ffn, \
        cfg.dense_ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    query = (("wq_a", (h, cfg.q_rank), "normal"),
             ("q_norm", (cfg.q_rank,), "ones"),
             ("wq_b", (cfg.q_rank, qw), "normal")) if cfg.q_rank \
        else (("wq", (h, qw), "normal"),)
    mixer = (("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones")) + query + (
        ("wkva", (h, cfg.latent_width), "normal"),
        ("kv_norm", (cfg.latent_rank,), "ones"),
        ("wkvb", (cfg.latent_rank, cfg.heads * 2 * d), "normal"),
        ("wo", (cfg.heads * d, h), "normal"))
    dense = (("w1", (h, fd), "normal"), ("w3", (h, fd), "normal"),
             ("w2", (fd, h), "normal"))
    routed = (("router", (h, e), "normal"), ("expert_bias", (e,), "bias"),
              ("wgate", (held, h, fe), "normal"),
              ("wup", (held, h, fe), "normal"),
              ("wdown", (held, fe, h), "normal"),
              ("shared_w1", (h, fs), "normal"),
              ("shared_w3", (h, fs), "normal"),
              ("shared_w2", (fs, h), "normal"))
    for l in range(cfg.layers):
        for name, shape, init in mixer + (
                dense if l < cfg.dense_layers else routed):
            shapes["l%d_%s" % (l, name)] = (shape, init)
    return shapes


def init_params(cfg, seed=0, std=0.02, bias_std=BIAS_STD):
    """name -> np array in the config's weight dtype: ``std``-normal
    weights, norms at 1, ``expert_bias`` normal(0, ``bias_std``).
    Host-side: tests and demo bundles."""
    return _exaone.init_params(cfg, seed, std, bias_std, param_shapes)


def yarn_inv_freq(cfg):
    """The ``latent_rope / 2`` frequencies a pair turns by a position,
    float32: ``theta^(-2j/P)`` as it is (plain RoPE without
    ``cfg.rope_scaling``), and under YaRN blended with itself over
    ``factor``: a pair that turns more than ``beta_fast`` times in the
    original context keeps its frequency, one that turns less than
    ``beta_slow`` times has it divided, and between the two correction dims
    the blend is linear."""
    p = cfg.latent_rope
    plain = cfg.rope_theta ** (-np.arange(0, p, 2, dtype=np.float64) / p)
    y = cfg.rope_scaling
    if not y:
        return plain.astype(np.float32)

    def correction_dim(turns):
        return p * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), p - 1)
    ramp = np.clip((np.arange(p // 2, dtype=np.float64) - low)
                   / ((high if high > low else low + 0.001) - low), 0, 1)
    return (plain / y["factor"] * ramp + plain * (1 - ramp)) \
        .astype(np.float32)


def _rotation(cfg, pos):
    """-> ``rotate(x [B, n, P])``: each of the ``n`` vectors of lane ``b``
    turned by ``pos[b]``, pair by pair (``kimi_linear.latent_mixer``'s
    option).  The table is YaRN's, built here once a step's trace."""
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(yarn_inv_freq(cfg))                   # [B, 1, P/2]
    cos, sin = (f(ang) * cfg.rope_mscale for f in (jnp.cos, jnp.sin))

    def rotate(x):
        pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1)

    return rotate


def token_logits(params, cfg, tok, pos, attend, live, recur=None, seen=None):
    """-> (logits [B, vocab] float32, (routed, groups)) with ``routed``
    int32 [routed layers, experts] the tokens of live lanes sent to each
    expert of the whole router this step, a row a layer of
    ``cfg.routed_layers`` (``cfg.held_experts`` are the columns computed
    here), and ``groups`` int32 [routed layers, n_group] the live lanes
    that kept each group.  Scope names: ``layer<i>/latent/`` +
    ``q_compress``, ``absorb``, ``rope``, ``kv_write``, ``kv_read``
    (``kv_gather`` where the table is gathered), ``out``; ``layer<i>/mlp``
    on dense layers, ``layer<i>/moe/router``, ``.../moe/experts`` and
    ``.../moe/shared`` on routed ones; ``lm_head``.  A list given as ``seen``
    receives each routed layer's router input ``[B, H]`` float32 (what a
    balancing of ``expert_bias`` on the model's own states reads; the decode
    steps pass none)."""
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    rotate = _rotation(cfg, pos)
    counted = lambda mask: jnp.sum(mask & live[:, None], axis=0,
                                   dtype=jnp.int32)
    routed, groups = [], []
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            with jax.named_scope("latent"):
                x = x + _kimi.latent_mixer(cfg, p, l, h, attend, rotate)
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if l < cfg.dense_layers:
                with jax.named_scope("mlp"):
                    x = x + _exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))
            else:
                with jax.named_scope("moe"):
                    kept = []
                    if seen is not None:
                        seen.append(h2)
                    f, chosen = routed_part(cfg, p, h2, live, kept)
                    routed.append(counted(chosen))
                    # one group: every token keeps it
                    groups.append(counted(kept[0] if kept else live[:, None]))
                    x = x + f + shared_part(p, h2)
    with jax.named_scope("lm_head"):
        logits = _exaone._head(x, params, eps)
    # a cut that keeps the dense layers alone has nothing to count
    return logits, (jnp.stack(routed), jnp.stack(groups)) if routed else ()
