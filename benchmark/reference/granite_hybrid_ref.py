"""Plain reference for the served Granite 4.0-H decoder
(ibm-granite/granite-4.0-h-micro, ``model_type`` ``granitemoehybrid``): the
whole causal forward pass of one sequence in straightforward ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``, with no
cache, no batching and no chunking.  Written from the architecture (the
catalog row's ``config`` and ISSUE 31's equations), not from
``paddle_tpu/models/granite_hybrid.py``.

Sizes as the configuration gives them: ``hidden_size`` H, ``layer_types``
(``attention`` | ``mamba``, one a layer), ``num_attention_heads`` query
heads over ``num_key_value_heads`` KV heads of H / heads values,
``shared_intermediate_size`` F (``num_local_experts`` is 0: every layer's
feed-forward is the shared MLP), and for the mamba layers
``mamba_n_heads`` heads of ``mamba_d_head``, ``mamba_d_state`` N,
``mamba_n_groups`` 1, ``mamba_d_conv`` taps.  For the hidden vectors ``x``
of a sequence::

    x0     = embedding_multiplier * embed[tok]
    layer:   r = x;  h = rmsnorm(x, input_layernorm)
             x = r + residual_multiplier * mixer(h)      # by layer_types[l]
             r = x;  h = rmsnorm(x, post_attention_layernorm)
             a, b = split(h @ W_in, 2)                   # W_in [H, 2 F]
             x = r + residual_multiplier * ((silu(a) * b) @ W_out)
    logits = (rmsnorm(x, norm) @ embed^T) / logits_scaling   # tied head

    attention: q = h @ Wq, k = h @ Wk, v = h @ Wv; no position encoding
               (``position_embedding_type`` is ``nope``); K and V repeated
               so that query head j attends KV head j // group; causal;
               scores * attention_multiplier; out @ Wo.

    mamba (Mamba-2): z, xBC, dt = split(h @ W_inproj, [I, I + 2 N, heads])
               xBC_t = silu(conv_bias + sum_{j=0..K-1} conv_w[j] * xBC_{t-K+1+j})
                                    # depthwise, causal, zeros before position 0
               xs, B, C = split(xBC_t, [I, N, N]);  xs -> [heads, d_head]
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t[n] = exp(dt[n] A[n]) S_{t-1}[n] + dt[n] outer(xs[n], B)
               y[n]   = S_t[n] @ C + D[n] xs[n]
               y      = rmsnorm(y.reshape(I) * silu(z), mamba_norm)
                                    # gate first, then the norm over all I
               out    = y @ W_outproj

The recurrence is a plain ``lax.scan`` over positions; the convolution its
K-term sum.  No biases but the convolution's; ``time_step_limit`` is (0,
inf), so dt is not clamped; ``mamba_chunk_size`` belongs to a chunked scan
and changes no value.

Weights are taken as they are served (bfloat16) and upcast to float32 one
layer at a time inside the pass.  Parameter names are the served ones
(``embed``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``,
``w_in``, ``w_out``; ``wq``, ``wk``, ``wv``, ``wo``; ``in_proj``, ``conv_w
[K, I + 2 N]``, ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``ssm_norm``,
``out_proj``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, exactly as ``olmoe_ref.py`` has it: the served
token's *deficit* at a position is the reference's largest logit less its
logit of the served token, at most twice the served path's logit error.
``benchmark/tests/chip_check_granite.py`` compares the step's logits,
cached K and V and recurrent state themselves, outside any window.
"""

import numpy as np

# The largest deficit a correct server may show: about three times the largest
# the served step has shown on the chip (0.0036 in the cell's own check of 64
# positions, 0.0020 over 5 seeds x 320 positions of
# ``benchmark/tests/chip_check_granite.py``, which reads the step's logits;
# PERF.md section 6, PR 31), and under 0.0216, twice the largest error of a
# served logit there, which is all the arithmetic allows.  With normal(0, 0.02)
# weights and the family's multipliers the logits have a standard deviation of
# 0.113 over 100,352 tokens (the tied head divides by ``logits_scaling`` 8), so
# the bound is in those units, an eighth of OLMoE's; a fault in structure (a
# lost layer, a wrong head map, a lost or stale state slot, a multiplier left
# out) moves the argmax almost everywhere and by tenths.  What the tokens alone
# cannot see is a loss of precision: the same step with its state rounded to
# bfloat16 at every write showed deficits of 0.0-0.0013 on five seeds, inside
# any bound, so the cell's ``correct`` admits it.  Precision is held by the chip
# check, which reads the state and the pools themselves; the cell could hold it
# only if the runner handed logits to ``check``, which is a benchmark PR's.
DEFICIT_BOUND = 0.012


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _attention(config, p, h):
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    dim = config["hidden_size"] // heads
    q = (h @ p("wq")).reshape(t, heads, dim)
    k = (h @ p("wk")).reshape(t, kv_heads, dim)
    v = (h @ p("wv")).reshape(t, kv_heads, dim)
    kv = (k, v)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) \
        * float(config["attention_multiplier"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * dim) @ p("wo"), kv


def _mamba(config, p, h):
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, d_head = config["mamba_n_heads"], config["mamba_d_head"]
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    inner = heads * d_head
    zxbcdt = h @ p("in_proj")
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * n]
    dt = zxbcdt[:, 2 * inner + 2 * n:]
    # depthwise causal convolution as its K-term sum, zeros before position 0
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    conv_w = p("conv_w")
    xbc = jax.nn.silu(p("conv_b") + sum(
        conv_w[j] * padded[j:j + t] for j in range(taps)))
    xs = xbc[:, :inner].reshape(t, heads, d_head)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + p("dt_bias"))              # [T, heads]
    a = -jnp.exp(p("A_log"))                             # [heads]

    def one(state, at):
        xs_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + dt_t[:, None, None] * xs_t[:, :, None] * b_t[None, None, :]
        return state, state @ c_t                        # [heads, d_head]

    last, y = jax.lax.scan(
        one, jnp.zeros((heads, d_head, n), jnp.float32), (xs, b, c, dt))
    y = y + p("D")[None, :, None] * xs
    y = _rmsnorm(y.reshape(t, inner) * jax.nn.silu(z), p("ssm_norm"),
                 float(config["rms_norm_eps"]))
    return y @ p("out_proj"), last


def forward(config, params, tokens, return_cached=False):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it: the K and V [T, kv_heads, head_dim] of
    each attention layer, and each mamba layer's state S [heads, d_head, N]
    after the last of the T tokens)."""
    import jax
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    rm = float(config["residual_multiplier"])
    ffn = config["shared_intermediate_size"]
    f32 = lambda a: a.astype(jnp.float32)
    embed = f32(params["embed"])
    x = float(config["embedding_multiplier"]) * embed[tokens]
    cached, states = [], []
    for l, kind in enumerate(config["layer_types"]):
        p = lambda n, _l=l: f32(params["l%d_%s" % (_l, n)])
        h = _rmsnorm(x, p("ln1_g"), eps)
        if kind == "attention":
            mixed, kv = _attention(config, p, h)
            cached.append(kv)
        else:
            mixed, last = _mamba(config, p, h)
            states.append(last)
        x = x + rm * mixed
        ab = _rmsnorm(x, p("ln2_g"), eps) @ p("w_in")
        x = x + rm * ((jax.nn.silu(ab[:, :ffn]) * ab[:, ffn:]) @ p("w_out"))
    logits = (_rmsnorm(x, f32(params["lnf_g"]), eps) @ embed.T) \
        / float(config["logits_scaling"])
    return (logits, cached, states) if return_cached else logits


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
