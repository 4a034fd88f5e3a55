"""GLM-5's block (zai-org/GLM-5: ``model_type`` ``glm_moe_dsa``, the
DeepSeek-V3.2 decoder: multi-head latent attention under DeepSeek Sparse
Attention) as pure functions of ``(params, cfg, tok, pos, attend, live,
recur)``, called by the decode steps of ``serving/decode_model.py`` under the
same contract as the other blocks: one token per lane through every layer.
Every layer's mixer is of ONE kind, ``latent``, and **selects**: a learned
indexer scores every cached position and the attention reads the
``cfg.index_topk`` best and no others.  The feed-forward is ``dots_vlm``'s,
with a router of one group (no group stage).

* ``latent``: ``kimi_linear.latent_mixer`` with all three of its options:
  the compressed query (``cfg.q_rank``), the rotation of the row's shared
  key and of each head's ``q_pe`` (plain RoPE: no ``rope_scaling``, so the
  scores' scale is ``(head_dim + latent_rope)^-0.5`` with no ``m^2``), and
  the selection.  A head's own key part is ``cfg.head_dim`` (192) wide and
  its value ``cfg.v_head_dim`` (256).  The cache keeps two rows a token a
  layer: ``[c | k_pe]`` (``latent_rank + latent_rope`` values) in the latent
  pool and the indexer's key ``ki`` (``cfg.index_head_dim`` values) in the
  index pool beside it, on the same block tables.
* the first ``cfg.dense_layers`` layers end in a SiLU-gated MLP of width
  ``cfg.dense_ffn``; every later one in ``exaone_moe``'s routed layer (the
  share it may hold included) beside one shared expert.

Pre-norm throughout, RMSNorm.  For hidden ``x`` of one token at position
``t``, ``h = rmsnorm(x, ln1_g)``, ``D`` a head's own key width, ``Dv`` its
value's, ``P`` the shared rotated part, ``J`` index heads of ``E`` values::

    cq = rmsnorm(h @ wq_a, q_norm);  q = cq @ wq_b -> a head [q_nope D | q_pe P]
    [c | k_pe] = h @ wkva;  c = rmsnorm(c, kv_norm)
    k_pe = rope(k_pe, t);  q_pe_i = rope(q_pe_i, t);  row(t) = [c | k_pe]
    indexer:  qi = cq @ wq_idx -> J heads of E;  ki = layernorm(h @ wk_idx)
              the FIRST P values of each qi_j and of ki turned by t (rope)
              w = (h @ w_idx) * J^-0.5 * E^-0.5
              I(t, s) = sum_j w_j relu(qi_j . ki(s))   s <= t;  index row(t) = ki
              S(t) = the min(index_topk, t + 1) positions s <= t of largest
                     I(t, s)   (ties: the lower s)
    q_lat_i = wkvb_i^K q_nope_i
    score_i(s) = (q_lat_i . c(s) + q_pe_i . k_pe(s)) * (D + P)^-0.5,  s in S(t)
    mla = concat_i(wkvb_i^V^T sum_{s in S} softmax_s(score_i) c(s)) @ wo
    rope: pair j = values (2j, 2j + 1) of the P, turned by t * theta^(-2j/P)
          and laid [first of each pair | second of each pair]
          (``dots_vlm._rotation`` with no scaling; query and key alike, so a
          score does not depend on the laying)
    h2 = rmsnorm(x, ln2_g);  x = x + ffn(h2)        # dense, or routed + shared

and ``logits = rmsnorm(x, lnf_g) @ head`` (an untied head).  Assumed, as the
configuration's ``assumed`` lists: that the rotated ``P`` are the first of an
index head's ``E`` values, that ``ki``'s norm is a LayerNorm (weight and
bias, eps 1e-6) and that the indexer turns interleaved pairs
(``indexer_rope_interleave``) are the DeepSeek-V3.2 inference code's, which
``glm_moe_dsa`` follows.  Departures: that code holds index keys in fp8
behind a Hadamard turn; an orthonormal turn of query and key alike leaves
``qi . ki`` as it is and is left out, and the keys are held in the cache's
dtype (bfloat16 for a bfloat16 model).  The multi-token-prediction module is
no part of this block.

Precision as the other bfloat16 blocks: matmul inputs in the weights' dtype
with float32 accumulation; norms, the rotation, the index scores' ReLU and
weighted sum, the sigmoid and gates and the residual additions float32.

Params (``init_params`` makes seeded ones): ``dots_vlm``'s with ``wkvb
[rank, heads * (D + Dv)]`` and ``wo [heads * Dv, H]``, and a layer's indexer:
``wq_idx [Rq, J * E]``, ``wk_idx [H, E]``, ``k_idx_g``, ``k_idx_b [E]``,
``w_idx [H, J]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import dots_vlm as _dots
from . import exaone_moe as _exaone
from . import kimi_linear as _kimi
from .decoder_family import DecoderFamily
from .olmoe import NP_DTYPES, _mm, _rmsnorm

__all__ = ["token_logits", "param_shapes", "init_params", "laid_out",
           "routed_part", "shared_part", "BIAS_STD", "INDEX_NORM_EPS",
           "FAMILY"]

FAMILY = DecoderFamily(kinds=("latent",), routes="after_dense",
                       expert_matrices=3, dense_lead=True, holds_share=True,
                       own_stream_width=True, rotated_latent=True,
                       shared_expert=True, selects=True)

# dots.vlm1's, for its reason: the stream is pre-norm and the router sees
# rmsnorm(x) over a width of the same order (6,144 for 7,168)
BIAS_STD = _dots.BIAS_STD
# the eps of the index key's LayerNorm (the DeepSeek-V3.2 code's default)
INDEX_NORM_EPS = 1e-6

routed_part = _exaone.routed_part
shared_part = _exaone.shared_part
# every layer's ``wkvb`` as ``latent_mixer`` multiplies it
laid_out = _kimi.laid_out


def param_shapes(cfg):
    """name -> (shape, kind) with kind in normal | ones | zeros | bias."""
    h, v, d = cfg.hidden, cfg.vocab, cfg.head_dim
    e, held, fe, fd, fs = cfg.experts, cfg.experts_held, cfg.ffn, \
        cfg.dense_ffn, cfg.shared_ffn
    shapes = {"embed": ((v, h), "normal"), "lnf_g": ((h,), "ones"),
              "head": ((h, v), "normal")}
    mixer = (("ln1_g", (h,), "ones"), ("ln2_g", (h,), "ones"),
             ("wq_a", (h, cfg.q_rank), "normal"),
             ("q_norm", (cfg.q_rank,), "ones"),
             ("wq_b", (cfg.q_rank, cfg.heads * (d + cfg.latent_rope)),
              "normal"),
             ("wkva", (h, cfg.latent_width), "normal"),
             ("kv_norm", (cfg.latent_rank,), "ones"),
             ("wkvb", (cfg.latent_rank, cfg.heads * (d + cfg.v_head_dim)),
              "normal"),
             ("wo", (cfg.heads * cfg.v_head_dim, h), "normal"),
             ("wq_idx", (cfg.q_rank, cfg.index_heads * cfg.index_head_dim),
              "normal"),
             ("wk_idx", (h, cfg.index_head_dim), "normal"),
             ("k_idx_g", (cfg.index_head_dim,), "ones"),
             ("k_idx_b", (cfg.index_head_dim,), "zeros"),
             ("w_idx", (h, cfg.index_heads), "normal"))
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
    weights, norms at 1 (the index key's LayerNorm bias at 0),
    ``expert_bias`` normal(0, ``bias_std``).  Host-side: tests and demo
    bundles."""
    r = np.random.RandomState(seed)
    dtype = NP_DTYPES[cfg.dtype]

    def make(shape, kind):
        if kind in ("ones", "zeros"):
            return np.full(shape, float(kind == "ones"), np.float32)
        return r.standard_normal(shape) * (bias_std if kind == "bias"
                                           else std)

    return {name: make(shape, kind).astype(np.float32).astype(dtype)
            for name, (shape, kind) in sorted(param_shapes(cfg).items())}


def _layernorm(x, g, b, eps):
    """LayerNorm over the last axis, float32."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)


def _indexer(cfg, p, h, rotate):
    """-> ``index(cq)`` for ``latent_mixer``: the indexer of one layer over
    h [B, H] float32 and the normed compressed query ``cq`` [B, q_rank] ->
    (``qi`` [B, J, E] the index queries, ``w`` [B, J] their heads' weights,
    ``ki`` [B, E] this token's index key), all float32, the first ``latent_rope`` values of each query and of the
    key turned by the lanes' positions."""
    heads, width, turned = cfg.index_heads, cfg.index_head_dim, \
        cfg.latent_rope

    def index(cq):
        with jax.named_scope("index"):
            qi = _mm(cq, p("wq_idx")).reshape(h.shape[0], heads, width)
            ki = _layernorm(_mm(h, p("wk_idx")), p("k_idx_g"), p("k_idx_b"),
                            INDEX_NORM_EPS)
            qi = jnp.concatenate(
                [rotate(qi[..., :turned]), qi[..., turned:]], axis=-1)
            ki = jnp.concatenate(
                [rotate(ki[:, None, :turned])[:, 0], ki[:, turned:]], axis=-1)
            w = _mm(h, p("w_idx")) * (heads ** -0.5 * width ** -0.5)
            return qi, w, ki

    return index


def token_logits(params, cfg, tok, pos, attend, live, recur=None, seen=None):
    """-> (logits [B, vocab] float32, (routed, groups)) as
    ``dots_vlm.token_logits`` (``groups`` one column: every token keeps the
    router's one group).  Scope names: ``layer<i>/latent/`` + ``q_compress``,
    ``index`` (the indexer's projections and rotation, the index row's write
    and the scores), ``absorb``, ``rope``, ``kv_write``, ``select`` (the
    choice), ``mask`` (the chosen positions laid out for the kernel) and
    ``kv_read`` (the kernel over a lane's live blocks under that mask), or,
    under a table too wide for that walk, ``kv_gather`` (the chosen rows'
    gather; the whole table's where no kernel serves) and ``kv_read`` (the
    kernel over the gathered rows), ``out``; ``layer<i>/mlp`` on dense layers,
    ``layer<i>/moe/router``, ``.../moe/experts`` and ``.../moe/shared`` on
    routed ones; ``lm_head``.  ``seen`` as ``dots_vlm``'s."""
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    rotate = _dots._rotation(cfg, pos)
    counted = lambda mask: jnp.sum(mask & live[:, None], axis=0,
                                   dtype=jnp.int32)
    routed, groups = [], []
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            h = _rmsnorm(x, p("ln1_g"), eps)
            with jax.named_scope("latent"):
                x = x + _kimi.latent_mixer(cfg, p, l, h, attend, rotate,
                                           _indexer(cfg, p, h, rotate))
            h2 = _rmsnorm(x, p("ln2_g"), eps)
            if l < cfg.dense_layers:
                with jax.named_scope("mlp"):
                    x = x + _exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))
            else:
                with jax.named_scope("moe"):
                    if seen is not None:
                        seen.append(h2)
                    f, chosen = routed_part(cfg, p, h2, live)
                    routed.append(counted(chosen))
                    groups.append(counted(live[:, None]))
                    x = x + f + shared_part(p, h2)
    with jax.named_scope("lm_head"):
        logits = _exaone._head(x, params, eps)
    # a cut that keeps the dense layers alone has nothing to count
    return logits, (jnp.stack(routed), jnp.stack(groups)) if routed else ()
