"""GPT-2 decoder block (pre-LN transformer: embed + learned positions,
per-layer multi-head attention + GELU MLP, a final LayerNorm and a vocab
head kept separate from the embedding) as pure functions of ``(params, cfg,
tok, pos, attend)``, called by the decode steps of
``serving/decode_model.py`` under the contract every block meets: one token
per lane through every layer, and ``attend(l, q, k, v)`` owns the KV write
and the history read.  Served in float32.

Params (``init_params`` makes seeded ones): ``embed [V, H]``, ``pos_embed
[max_seq, H]``, ``lnf_g``, ``lnf_b [H]``, ``head [H, V]`` and per layer
``l<i>_`` + ``ln1_g``, ``ln1_b``, ``wq``, ``wk``, ``wv``, ``wo [H, H]``,
``ln2_g``, ``ln2_b``, ``w1 [H, F]``, ``b1 [F]``, ``w2 [F, H]``, ``b2 [H]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_family import DecoderFamily

__all__ = ["token_logits", "init_params", "FAMILY"]

FAMILY = DecoderFamily(kinds=("attention",), dtypes=("f32",))


def init_params(cfg, seed=0):
    """name -> np float32 array; 0.02-normal weights, identity norms."""
    r = np.random.RandomState(seed)
    h, f, v = cfg.hidden, cfg.ffn, cfg.vocab

    def w(*shape):
        return (r.standard_normal(shape) * 0.02).astype(np.float32)

    p = {"embed": w(v, h), "pos_embed": w(cfg.max_seq, h),
         "lnf_g": np.ones(h, np.float32), "lnf_b": np.zeros(h, np.float32),
         "head": w(h, v)}
    for l in range(cfg.layers):
        p.update({
            "l%d_ln1_g" % l: np.ones(h, np.float32),
            "l%d_ln1_b" % l: np.zeros(h, np.float32),
            "l%d_wq" % l: w(h, h), "l%d_wk" % l: w(h, h),
            "l%d_wv" % l: w(h, h), "l%d_wo" % l: w(h, h),
            "l%d_ln2_g" % l: np.ones(h, np.float32),
            "l%d_ln2_b" % l: np.zeros(h, np.float32),
            "l%d_w1" % l: w(h, f), "l%d_b1" % l: np.zeros(f, np.float32),
            "l%d_w2" % l: w(f, h), "l%d_b2" % l: np.zeros(h, np.float32),
        })
    return p


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(var + 1e-5) * g + b


def token_logits(params, cfg, tok, pos, attend, live=None, recur=None):
    """-> (logits [B, vocab], ()).  The ``jax.named_scope`` names
    (``layer<i>/attn``, ``.../kv_write``, ``.../kv_read`` or
    ``.../kv_gather``, ``layer<i>/mlp``, ``lm_head``) are metadata: they
    reach each HLO instruction's ``op_name``, so a device trace can be
    grouped by them, and change nothing computed."""
    bb = tok.shape[0]
    x = jnp.take(params["embed"], tok, axis=0) \
        + jnp.take(params["pos_embed"], pos, axis=0)
    for l in range(cfg.layers):
        def p(n, _l=l):
            return params["l%d_%s" % (_l, n)]

        with jax.named_scope("layer%d" % l):
            with jax.named_scope("attn"):
                h = _ln(x, p("ln1_g"), p("ln1_b"))
                q = (h @ p("wq")).reshape(bb, cfg.heads, cfg.head_dim)
                k = (h @ p("wk")).reshape(bb, cfg.heads, cfg.head_dim)
                v = (h @ p("wv")).reshape(bb, cfg.heads, cfg.head_dim)
                a = attend(l, q, k, v).reshape(bb, cfg.hidden)
                x = x + a @ p("wo")
            with jax.named_scope("mlp"):
                h2 = _ln(x, p("ln2_g"), p("ln2_b"))
                x = x + jax.nn.gelu(h2 @ p("w1") + p("b1")) @ p("w2") \
                    + p("b2")
    with jax.named_scope("lm_head"):
        x = _ln(x, params["lnf_g"], params["lnf_b"])
        return x @ params["head"], ()
