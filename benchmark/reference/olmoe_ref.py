"""Plain reference for the served OLMoE decoder (allenai/OLMoE-1B-7B-0125-
Instruct, ``model_type`` ``olmoe``): the whole causal forward pass of one
sequence in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no paging and
no batching, experts as a plain loop with a mask.  Written from the
architecture, not from ``paddle_tpu/models/olmoe.py``.

The layer, for the hidden vector ``x`` of the token at position ``t``::

    h   = rmsnorm(x, input_layernorm)        # x * rsqrt(mean(x^2) + eps) * g
    q,k = rmsnorm(h @ Wq, q_norm), rmsnorm(h @ Wk, k_norm)
                                             # over all heads*head_dim values,
                                             # before the split into heads
    v   = h @ Wv
    q,k = rope(q, t), rope(k, t)             # per head, rotate-half pairing
                                             # (i, i + head_dim/2), theta 10000
    x   = x + attention(q, K[0..t], V[0..t]) @ Wo
                                             # causal, scale 1/sqrt(head_dim)
    h2  = rmsnorm(x, post_attention_layernorm)
    p   = softmax(h2 @ Wr)                   # over all experts
    S   = indices of the num_experts_per_tok largest p
                                             # weights p_e as they are:
                                             # norm_topk_prob is false
    x   = x + sum_{e in S} p_e * ((silu(h2 @ Wgate_e) * (h2 @ Wup_e)) @ Wdown_e)

and ``logits = rmsnorm(x, norm) @ lm_head``.  No biases anywhere, no shared
expert, untied head.  The Q/K RMSNorm is OLMoE's own (its paper and
modelling code), not a key of ``config.json``; the configuration file lists
it under ``assumed``.

Weights are taken as they are served (bfloat16) and upcast to float32 one
layer at a time inside the pass: all of them upcast at once would not fit
beside the engine.  Parameter names are the served ones (``embed``,
``head``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``wq``, ``wk``,
``wv``, ``wo``, ``q_norm``, ``k_norm``, ``ln2_g``, ``router [H, E]``,
``wgate``/``wup [E, H, F]``, ``wdown [E, F, H]``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, exactly as ``gpt2_ref.py`` has it: the served
token's *deficit* at a position is the reference's largest logit less its
logit of the served token, at most twice the served path's logit error.
``benchmark/tests/chip_check_olmoe.py`` compares the step's logits
themselves, outside any window.
"""

import numpy as np

# The largest deficit a correct server may show: three times the largest the
# served step has shown on the chip (0.033 over 5 seeds x 256 positions of
# ``benchmark/tests/chip_check_olmoe.py``, which reads the step's logits;
# PERF.md section 6, PR 27), and under 0.13, twice the largest error of a
# served logit there, which is all the arithmetic allows.  With
# normal(0, 0.02) weights the logits have a standard deviation of 0.9 over
# 50,304 tokens, so a fault in structure (a lost layer, a wrong RoPE
# pairing, a stale cache block, a dropped or renormalised expert) moves the
# argmax almost everywhere and by tenths.  What the tokens alone cannot
# always see is a loss of precision: the same step given weights rounded to
# fp8 showed deficits of 0.108, 0.26 and 0.26 on three seeds (not correct)
# and 0.018 on a fourth, where its argmax happened to agree; the logit check
# tells it apart on every seed (errors of 0.5 against 0.066), so that is
# where the precision is held.
DEFICIT_BOUND = 0.10


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, heads, D], row t at position t."""
    import jax.numpy as jnp

    t, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, D]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def forward(config, params, tokens, return_routing=False):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    the router's probabilities [layers, T, experts])."""
    import jax
    import jax.numpy as jnp

    heads = config["num_attention_heads"]
    dim = config["hidden_size"] // heads
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    n_exp = config["num_experts"]
    top = config["num_experts_per_tok"]
    t = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(params["embed"][tokens])
    causal = jnp.tril(jnp.ones((t, t), bool))
    routing = []
    for l in range(config["num_hidden_layers"]):
        p = lambda n, _l=l: f32(params["l%d_%s" % (_l, n)])
        h = _rmsnorm(x, p("ln1_g"), eps)
        q = _rmsnorm(h @ p("wq"), p("q_norm"), eps).reshape(t, heads, dim)
        k = _rmsnorm(h @ p("wk"), p("k_norm"), eps).reshape(t, heads, dim)
        v = (h @ p("wv")).reshape(t, heads, dim)
        q, k = _rope(q, theta), _rope(k, theta)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dim)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(t, heads * dim) @ p("wo")
        h2 = _rmsnorm(x, p("ln2_g"), eps)
        prob = jax.nn.softmax(h2 @ p("router"), axis=-1)           # [T, E]
        routing.append(prob)
        kth = jnp.sort(prob, axis=-1)[:, n_exp - top][:, None]
        gate = jnp.where(prob >= kth, prob, 0.0)
        wgate, wup, wdown = p("wgate"), p("wup"), p("wdown")
        moe = jnp.zeros_like(x)
        for e in range(n_exp):
            y = (jax.nn.silu(h2 @ wgate[e]) * (h2 @ wup[e])) @ wdown[e]
            moe = moe + gate[:, e:e + 1] * y
        x = x + moe
    logits = _rmsnorm(x, f32(params["lnf_g"]), eps) @ f32(params["head"])
    return (logits, jnp.stack(routing)) if return_routing else logits


def check(config, params, cases, pad_to):
    """``cases``: [(prompt ids, served ids)].  -> the number of positions
    compared, how many served tokens differ from the reference's argmax,
    and the largest deficit (see above).  ``ok`` is deficit <= bound."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda prm, tok: forward(config, prm, tok))
    compared, differing, worst = 0, 0, 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, served in cases:
            seq = list(prompt) + list(served)
            # causal: padding after the sequence cannot reach back into it
            padded = np.zeros(pad_to, np.int32)
            padded[:len(seq)] = seq
            logits = np.asarray(fwd(params, jnp.asarray(padded)))
            for i, tok in enumerate(served):
                row = logits[len(prompt) - 1 + i]
                deficit = float(row.max() - row[int(tok)])
                compared += 1
                differing += deficit > 0
                worst = max(worst, deficit)
    return {"compared": compared, "differing": int(differing),
            "largest_deficit": worst, "ok": worst <= DEFICIT_BOUND}
