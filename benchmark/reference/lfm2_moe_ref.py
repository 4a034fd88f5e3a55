"""Plain reference for the served LFM2-MoE decoder (LiquidAI/LFM2-24B-A2B,
``model_type`` ``lfm2_moe``): the whole causal forward pass of one sequence
in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no batching and
no chunking; the convolution is a plain causal sum over the sequence, the
experts a plain loop with a mask.  Written from the architecture (the
catalog row's ``config``, ISSUE 33's equations and the dense family's
modelling code), not from ``paddle_tpu/models/lfm2_moe.py``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H, ``layer_types`` (``conv`` | ``full_attention``, one a
layer), ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
heads of H / heads values, ``conv_L_cache`` taps, ``num_dense_layers``
leading layers with a gated MLP of ``intermediate_size``, the rest with
``num_experts`` experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token, ``routed_scaling_factor``,
``rope_parameters.rope_theta``, ``norm_eps``.  For the hidden vectors ``x``
of a sequence, row ``t`` the token at position ``t``::

    h = rmsnorm(x, operator_norm)
    conv:       B, C, u = split(h @ in_proj, 3)          # in_proj [H, 3 H]
                g = B * u
                c_t = sum_{j=0..K-1} conv_w[j] * g_{t-K+1+j}
                                   # depthwise, causal, zeros before position 0
                x = x + (C * c) @ out_proj               # no activation
    attention:  q = rmsnorm_per_head(h @ Wq, q_layernorm)    # over head_dim
                k = rmsnorm_per_head(h @ Wk, k_layernorm);  v = h @ Wv
                q, k = rope(q), rope(k)    # rotate-half pairs (i, i + D/2)
                K and V repeated so that query head j attends KV head
                j // group; causal; scores / sqrt(head_dim); out @ Wo
    h2 = rmsnorm(x, ffn_norm)
    layer < num_dense_layers:
                x = x + (silu(h2 @ w1) * (h2 @ w3)) @ w2
    else:       s = sigmoid(h2 @ gate)                   # [E]
                S = the num_experts_per_tok largest of s + expert_bias
                                   # the bias selects and never weighs
                w_e = s_e / (sum_{e in S} s_e + 1e-6) * routed_scaling_factor
                x = x + sum_{e in S} w_e * ((silu(h2 @ w1_e) * (h2 @ w3_e)) @ w2_e)
    logits = rmsnorm(x, embedding_norm) @ embed^T        # tied head

``norm_topk_prob`` and ``use_expert_bias`` are true and ``conv_bias`` false
in the source; the reference computes that and refuses another setting.

Weights are taken as they are served (bfloat16) and upcast to float32 one
layer at a time: ``check`` runs the pass a jitted layer at a time
(``by_layer``), so that
one routed layer's float32 copy (2.4e9 B at the published widths) is all
that lives beside the engine.  Parameter names are the served ones
(``embed``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``, ``ln2_g``;
``in_proj``, ``conv_w [K, H]``, ``out_proj``; ``wq``, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm``; ``w1``, ``w3``, ``w2``; ``router [H, E]``,
``expert_bias``, ``wgate``/``wup [E, H, F]``, ``wdown [E, F, H]``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, exactly as ``olmoe_ref.py`` has it: the served
token's *deficit* at a position is the reference's largest logit less its
logit of the served token, at most twice the served path's logit error.
``benchmark/tests/chip_check_lfm2.py`` compares the step's logits, cached K
and V and stored windows themselves, outside any window.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 33).  Logits here have a standard deviation of
# 0.905 over 65,536 tokens.  What sets both readings is not arithmetic error
# but the routing's discontinuity: a renormalised gate is about a quarter, the
# last expert chosen beats the first one left out by under 0.01 of selection
# score somewhere in the 8 routed layers at 96% of positions, and the served
# path's bfloat16 leaves about 0.001 of noise on a score, so the served step
# and the float32 reference swap an expert often, and a swap moves that
# position's logits by tenths.  The served step against this reference:
# largest logit error 1.33-1.55, root-mean-square 0.085-0.092, argmax differing
# at 14.7-16.9% of positions (five seeds x 2,048 positions,
# ``benchmark/tests/chip_check_lfm2.py``).
#   the largest deficit: served 0.21-0.65 in the cell's own check (ten runs of
#     64 positions), 0.85-1.02 over five seeds x 2,048 positions; the limit is
#     one and a half times the largest.  A fault in structure reads over it
#     (gates not renormalised 2.7-4.7 in any 64 positions, a window one token
#     stale 5.0-7.1), an ignored ``expert_bias`` only mostly (1.2-2.4).
#   the share of positions whose served token is not the reference's argmax:
#     served 6-12 of 64 in the cell's ten runs, 0.03-0.31 in any one sequence's
#     64 positions (96 sequences, median 0.156); with weights rounded to fp8
#     0.55-0.81 (median 0.66-0.71), an ignored bias 0.67-0.95.  The limit stands
#     between: what the tokens alone can hold of the precision, by this limit
#     and not by the first (fp8 deficits are 0.86-1.95).
# What neither sees: Q and K normalised over all heads at once (deficits
# 0.24-1.43, share 0.13-0.45): the chip check holds that by the cached K.
DEFICIT_BOUND = 1.5
DIFFERING_SHARE_BOUND = 0.45

GATE_EPS = 1e-6


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


def _short_conv(config, p, h):
    """-> (the mixer's output [T, H], the convolution's inputs g [T, H])."""
    import jax.numpy as jnp

    t, hid = h.shape
    taps = config["conv_L_cache"]
    gate_b, gate_c, u = jnp.split(h @ p["in_proj"], 3, axis=-1)
    g = gate_b * u
    padded = jnp.concatenate([jnp.zeros((taps - 1, hid), jnp.float32), g])
    c = sum(p["conv_w"][j] * padded[j:j + t] for j in range(taps))
    return (gate_c * c) @ p["out_proj"], g


def _attention(config, p, h):
    """-> (the mixer's output [T, H], (K, V) [T, kv_heads, D] as cached)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    dim = config["hidden_size"] // heads
    eps = float(config["norm_eps"])
    theta = float(config["rope_parameters"]["rope_theta"])
    q = _rmsnorm((h @ p["wq"]).reshape(t, heads, dim), p["q_norm"], eps)
    k = _rmsnorm((h @ p["wk"]).reshape(t, kv_heads, dim), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(t, kv_heads, dim)
    q, k = _rope(q, theta), _rope(k, theta)
    kv = (k, v)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * dim) @ p["wo"], kv


def gates_of(config, p, h2):
    """-> (gates [T, E]: the chosen experts' weights, 0 elsewhere; margin
    [T]: by how much the last expert chosen beat the first one left out,
    in selection score: where it is under the served path's rounding noise
    the choice is not the arithmetic's to make)."""
    import jax
    import jax.numpy as jnp

    n_exp, top = config["num_experts"], config["num_experts_per_tok"]
    score = jax.nn.sigmoid(h2 @ p["router"])
    ranked = jnp.sort(score + p["expert_bias"], axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(score + p["expert_bias"] >= kth[:, None], score, 0.0)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS) \
        * float(config["routed_scaling_factor"])
    return gates, kth - ranked[:, n_exp - top - 1]


def layer(config, kind, dense, p, x):
    """One layer over x [T, H] with its float32 weights ``p`` -> (x, what a
    cache would keep of it, (gates [T, E], margin [T]) or None)."""
    import jax
    import jax.numpy as jnp

    eps = float(config["norm_eps"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _rmsnorm(x, p["ln1_g"], eps)
    mixed, kept = (_attention if kind == "full_attention"
                   else _short_conv)(config, p, h)
    x = x + mixed
    h2 = _rmsnorm(x, p["ln2_g"], eps)
    if dense:
        return x + (jax.nn.silu(h2 @ p["w1"]) * (h2 @ p["w3"])) @ p["w2"], \
            kept, None
    gate, margin = gates_of(config, p, h2)
    moe = jnp.zeros_like(x)
    for e in range(config["num_experts"]):
        y = (jax.nn.silu(h2 @ p["wgate"][e]) * (h2 @ p["wup"][e])) \
            @ p["wdown"][e]
        moe = moe + gate[:, e:e + 1] * y
    return x + moe, kept, (gate, margin)


def _refuse_other_settings(config):
    if not config["norm_topk_prob"] or not config["use_expert_bias"] \
            or config["conv_bias"] \
            or config["rope_parameters"]["rope_type"] != "default" \
            or len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            "the lfm2_moe reference is renormalised gates, a selection "
            "bias, a convolution with no bias, default RoPE and a layer "
            "type a layer")


def forward(config, params, tokens, return_kept=False, layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it: ``kv`` the K and V [T, kv_heads,
    head_dim] of each attention layer, ``conv_inputs`` each conv layer's g
    [T, H], of which a slot keeps the newest ``conv_L_cache - 1``,
    ``gates`` [T, E] and ``margins`` [T] of each routed layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    embed = params["embed"].astype(jnp.float32)
    x = embed[tokens]
    kept = {"kv": [], "conv_inputs": [], "gates": [], "margins": []}
    for l, kind in enumerate(config["layer_types"]):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, cached, routing = layer_fn(
            config, kind, l < config["num_dense_layers"], mine, x)
        kept["kv" if kind == "full_attention" else "conv_inputs"].append(
            cached)
        if routing is not None:
            kept["gates"].append(routing[0])
            kept["margins"].append(routing[1])
    logits = _rmsnorm(x, params["lnf_g"].astype(jnp.float32),
                      float(config["norm_eps"])) @ embed.T
    return (logits, kept) if return_kept else logits


def by_layer(config):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer):
    one layer's float32 weights are all that is alive at once."""
    import jax

    @functools.lru_cache(maxsize=None)
    def jitted(kind, dense):
        return jax.jit(functools.partial(layer, config, kind, dense))

    return functools.partial(
        forward, config,
        layer_fn=lambda _c, kind, dense, p, x: jitted(kind, dense)(p, x))


def check(config, params, cases, pad_to):
    """``cases``: [(prompt ids, served ids)].  -> the number of positions
    compared, how many served tokens differ from the reference's argmax,
    and the largest deficit (see above).  ``ok`` is deficit <= its bound
    and the differing share <= its own."""
    import jax
    import jax.numpy as jnp

    fwd = by_layer(config)
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
            "largest_deficit": worst,
            "differing_share_bound": DIFFERING_SHARE_BOUND,
            "ok": worst <= DEFICIT_BOUND
            and differing <= DIFFERING_SHARE_BOUND * compared}
