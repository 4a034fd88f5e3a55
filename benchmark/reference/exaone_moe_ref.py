"""Plain reference for the served EXAONE-MoE decoder (LGAI-EXAONE/
K-EXAONE-236B-A23B, ``model_type`` ``exaone_moe``): the whole causal forward
pass of one sequence in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no cache, no ring, no
batching and no kernel; a window layer is a band mask over the whole ``[T,
T]`` score matrix, the experts a plain loop with a mask.  Written from the
architecture (the catalog row's ``config``, ISSUE 38's equations, the dense
half's modelling code ``models/exaone4/modeling_exaone4.py`` and the
DeepSeek-V3 router ``models/deepseek_v3/modeling_deepseek_v3.py`` of the
installed ``transformers``), not from ``paddle_tpu/models/exaone_moe.py``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H, ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``, ``layer_types``
(``sliding_attention`` | ``full_attention``, one a layer),
``sliding_window`` W, ``mlp_layer_types`` (``dense`` | ``sparse``),
``intermediate_size``, ``moe_intermediate_size``, ``num_shared_experts``,
``num_experts_per_tok``, ``routed_scaling_factor``,
``rope_parameters.rope_theta``, ``rms_norm_eps``.  For the hidden vectors
``x`` of a sequence, row ``t`` the token at position ``t``::

    q = rmsnorm_per_head(x @ Wq, q_norm);  k = rmsnorm_per_head(x @ Wk, k_norm)
    v = x @ Wv
    sliding_attention:  q, k = rope(q), rope(k)   # rotate-half pairs (i, i + D/2)
                        causal, and only positions > t - W
    full_attention:     no position encoding; causal
    query head j attends KV head j // group; scores / sqrt(head_dim)
    x = x + rmsnorm(attn @ Wo, post_attention_layernorm)     # norm on the OUTPUT
    dense:   f = (silu(x @ w1) * (x @ w3)) @ w2
    sparse:  s = sigmoid(x @ gate)                           # [E]
             S = the num_experts_per_tok largest of s + e_score_correction_bias
             w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
             f = sum_{e in S} w_e * expert_e(x) + shared(x)  # shared ungated
    x = x + rmsnorm(f, post_feedforward_layernorm)
    logits = rmsnorm(x, norm) @ lm_head                      # untied

**The share.**  ``num_experts`` counts the experts *held* (16 of the
published ``num_experts_published`` 128: one chip of the 8 that share a
layer), from ``first_expert`` on.  The router scores and chooses over all of
the published count, the gates are renormalised over all the chosen, and
the sum runs over the held experts alone: what an absent expert would add is
left out here as in the program, and that partial result goes on to the
next layer.  ``vocab_size`` is the slice of the embedding and of the head
that is held; logits are over the slice.  With ``num_experts ==
num_experts_published`` this is the uncut layer.

Weights are taken as they are served (bfloat16) and upcast to float32 one
layer at a time: ``check`` runs the pass a jitted layer at a time
(``by_layer``), so that one sparse layer's float32 copy (2.4e9 B at the
published widths) is all that lives beside the engine.  Parameter names are
the served ones (``embed``, ``head``, ``lnf_g`` and per layer ``l<i>_`` +
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``, ``ln1_g``,
``ln2_g``; ``w1``, ``w3``, ``w2``; ``router [H, E]``, ``expert_bias``,
``wgate``/``wup [Eh, H, F]``, ``wdown [Eh, F, H]``, ``shared_w1``,
``shared_w3``, ``shared_w2``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, exactly as ``lfm2_moe_ref.py`` has it: the served
token's *deficit* at a position is the reference's largest logit less its
logit of the served token, at most twice the served path's logit error.
The runner's check sends at most 48 positions, fewer than one window of 128:
it cannot tell a window layer from a global one.
``benchmark/tests/chip_check_exaone.py`` compares the step's logits and
cached K and V themselves past 400 positions, outside any window.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 38: ``benchmark/tests/chip_check_exaone.py`` on
# five seeds gives both statistics for each of 160 sequences' last 64
# positions, at contexts of 340-512; the cell's own check, nine runs, gives
# them for its 64 positions at contexts under 48).  Logits here have a
# standard deviation of 1.57 over 19,200 tokens.  As for LFM2, what sets the
# readings is less arithmetic error than the routing's discontinuity: behind
# norms on the sublayers' outputs the router's best scores lie within 1e-4 of
# each other (the median margin between the 8th expert chosen and the first
# left out is 9.4e-5), the served path's bfloat16 leaves more noise than that
# on a score, so the served step and the float32 reference swap an expert now
# and then, and a swap moves that position's logits.
#   the share of positions whose served token is not the reference's argmax:
#     served 0-3 of 64 in the cell's nine checks, 0-0.109 in any one sequence's
#     64 positions (160 sequences, medians 0.016-0.031); with the weights
#     rounded to fp8 (e4m3), the precision next below the stated bfloat16,
#     0.203-0.516 (128 sequences, medians 0.34-0.39).  The limit stands between
#     the two, 1.3 times the largest served reading and 0.7 of the smallest
#     fp8 one: it is what holds the precision, and fp8 comes out not
#     correct by this limit and not by the next.  Also over it: a window layer
#     attending everything (0.97-1.0), no shared expert (0.78-1.0), gates not
#     renormalised (0.63-0.95), whole-width Q/K norm (0.19-0.58); an ignored
#     bias only mostly (0.05-0.33), RoPE on the global layer mostly (0.08-0.39).
#   the largest deficit: served 0.0-0.34 in the cell's nine checks, medians
#     0.012-0.038 a sequence and 1.36 the largest of 160 sequences (the next
#     0.68); fp8 0.50-1.92 (not held by this limit).  A fault in structure
#     reads over it: a window layer attending everything 6.3-10.9, no shared
#     expert 4.2-7.2, gates not renormalised 1.7-5.2 (medians 3.4-3.8).  The
#     limit is one and a half times the largest served reading.
# What neither sees here: the cell's check sends at most 48 positions, under
# one window of 128, so a window layer that kept its whole history would pass
# it; the chip check holds that past 400 positions.
DEFICIT_BOUND = 2.0
DIFFERING_SHARE_BOUND = 0.14

GATE_EPS = 1e-20


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


def _attention(config, sliding, p, x):
    """-> (the sublayer's output before its norm [T, H], (K, V) [T,
    kv_heads, D] as a cache would hold them)."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    dim = config["head_dim"]
    eps = float(config["rms_norm_eps"])
    q = _rmsnorm((x @ p["wq"]).reshape(t, heads, dim), p["q_norm"], eps)
    k = _rmsnorm((x @ p["wk"]).reshape(t, kv_heads, dim), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(t, kv_heads, dim)
    seen = jnp.tril(jnp.ones((t, t), bool))
    if sliding:
        theta = float(config["rope_parameters"]["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
        # query t sees keys t - W + 1 .. t
        seen = seen & ~jnp.tril(jnp.ones((t, t), bool),
                                -int(config["sliding_window"]))
    kv = (k, v)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dim)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * dim) @ p["wo"], kv


def _gated_mlp(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def gates_of(config, p, x, use_bias=True, renormalise=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score).  ``use_bias`` and
    ``renormalise`` False are the chip check's broken references."""
    import jax
    import jax.numpy as jnp

    n_exp = p["router"].shape[1]
    top = config["num_experts_per_tok"]
    score = jax.nn.sigmoid(x @ p["router"])
    select = score + p["expert_bias"] if use_bias else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    if renormalise:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)
    return chosen * float(config["routed_scaling_factor"]), \
        kth - ranked[:, n_exp - top - 1]


def routed_sum(config, p, x, gates):
    """sum over the held experts of gate * expert(x): expert ``first_expert
    + i`` of the router is row ``i`` of the weights."""
    import jax.numpy as jnp

    first = int(config.get("first_expert", 0))
    out = jnp.zeros_like(x)
    for i in range(config["num_experts"]):
        y = _gated_mlp(x, p["wgate"][i], p["wup"][i], p["wdown"][i])
        out = out + gates[:, first + i:first + i + 1] * y
    return out


def shared_out(config, p, x):
    if not config["num_shared_experts"]:
        return 0.0
    return _gated_mlp(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def layer(config, kind, mlp, p, x):
    """One layer over x [T, H] with its weights ``p`` (upcast here) -> (x,
    (K, V) as a cache would keep them, (gates [T, E], margin [T]) or
    None)."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    mixed, kept = _attention(config, kind == "sliding_attention", p, x)
    x = x + _rmsnorm(mixed, p["ln1_g"], eps)
    if mlp == "dense":
        f, routing = _gated_mlp(x, p["w1"], p["w3"], p["w2"]), None
    else:
        routing = gates_of(config, p, x)
        f = routed_sum(config, p, x, routing[0]) + shared_out(config, p, x)
    return x + _rmsnorm(f, p["ln2_g"], eps), kept, routing


def _refuse_other_settings(config):
    n = config["num_hidden_layers"]
    if not config["norm_topk_prob"] or config["scoring_func"] != "sigmoid" \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or config["rope_parameters"]["rope_type"] != "default" \
            or len(config["layer_types"]) != n \
            or len(config["mlp_layer_types"]) != n \
            or set(config["layer_types"]) - {"sliding_attention",
                                             "full_attention"} \
            or set(config["mlp_layer_types"]) - {"dense", "sparse"}:
        raise ValueError(
            "the exaone_moe reference is sigmoid scores in one group, "
            "renormalised gates, SiLU, default RoPE, an untied head and a "
            "layer type and an MLP type a layer")


def forward(config, params, tokens, return_kept=False, layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it: ``kv`` the K and V [T, kv_heads,
    head_dim] of every layer, ``gates`` [T, E] and ``margins`` [T] of each
    sparse layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"].astype(jnp.float32)[tokens]
    kept = {"kv": [], "gates": [], "margins": []}
    for l, (kind, mlp) in enumerate(zip(config["layer_types"],
                                        config["mlp_layer_types"])):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, cached, routing = layer_fn(config, kind, mlp, mine, x)
        kept["kv"].append(cached)
        if routing is not None:
            kept["gates"].append(routing[0])
            kept["margins"].append(routing[1])
    logits = _rmsnorm(x, params["lnf_g"].astype(jnp.float32),
                      float(config["rms_norm_eps"])) \
        @ params["head"].astype(jnp.float32)
    return (logits, kept) if return_kept else logits


def by_layer(config, layer=layer):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer):
    one layer's float32 weights are all that is alive at once."""
    import jax

    @functools.lru_cache(maxsize=None)
    def jitted(kind, mlp):
        return jax.jit(functools.partial(layer, config, kind, mlp))

    return functools.partial(
        forward, config,
        layer_fn=lambda _c, kind, mlp, p, x: jitted(kind, mlp)(p, x))


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
