"""Plain reference for the served Kimi-Linear decoder (moonshotai/
Kimi-Linear-48B-A3B-Instruct, ``model_type`` ``kimi_linear``): the whole
causal forward pass of one sequence in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``, with no cache, no
slots, no batching and no kernel; the delta-rule recurrence a position at a
time, latent attention in its **expanded** form over the whole sequence (the
program serves the absorbed one), the experts a plain loop with a mask.
Written from the architecture (the catalog row's ``config``, ISSUE 46's
equations and the family's modelling conventions, which the configuration's
``assumed`` lists), not from ``paddle_tpu/models/kimi_linear.py``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H; ``linear_attn_config`` (``kda_layers`` and
``full_attn_layers``, 1-indexed; ``num_heads`` SH heads of ``head_dim`` D;
``short_conv_kernel_size`` K); ``num_attention_heads`` heads of
``qk_nope_head_dim`` + ``qk_rope_head_dim`` (keys) and ``v_head_dim``
(values) over ``kv_lora_rank`` latent values; ``first_k_dense_replace``
dense layers of ``intermediate_size``; ``num_experts`` experts of
``moe_intermediate_size``, ``num_experts_per_token`` a token,
``routed_scaling_factor``; ``rms_norm_eps``.  Pre-norm throughout, for the
hidden vectors ``x`` of a sequence (row ``t`` the token at position ``t``)::

    x = x + mixer(rmsnorm(x, input_norm));  x = x + ffn(rmsnorm(x, post_norm))
    KDA:  q~, k~, v~ = silu(causal depthwise conv_K(h @ Wq | Wk | Wv))
          q = q~ / |q~| * D^-0.5;  k = k~ / |k~|;  v = v~         # per head
          alpha = exp(-exp(A_log) * softplus(h @ Wfa @ Wfb + dt_bias))
          beta  = sigmoid(h @ Wb)
          S[t] = diag(alpha) S[t-1];  u = v - S^T k;  S[t] += beta outer(k, u)
          o = S[t]^T q;  mixer = (rmsnorm(o, o_norm) * sigmoid(h @ Wga @ Wgb)) @ Wo
    MLA:  q = h @ Wq -> per head [q_nope | q_pe];  [c | k_pe] = h @ Wkva
          c = rmsnorm(c, kv_a_layernorm);  [k_nope_i | v_i] = c @ Wkvb_i
          score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_pe_i(t) . k_pe(s))
                          * (nope + rope)^-0.5,  s <= t;  no rotation
          mixer = concat_i(softmax_s(score_i) v_i) @ Wo
    ffn:  layer 0  (silu(h @ w1) * (h @ w3)) @ w2
          later    s = sigmoid(h @ gate);  S = the top num_experts_per_token
                   of s + e_score_correction_bias
                   w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
                   sum_{e in S, e held} w_e E_e(h) + shared(h)
    logits = rmsnorm(x, norm) @ lm_head

**The share.**  ``num_experts`` counts the experts *held* (rows of
``wgate`` / ``wup`` / ``wdown``), ``num_experts_published`` the router's
width and ``first_expert`` the first one held.  The router scores all,
renormalises over all the chosen, and the sum runs over the held ones: what
an absent expert would add is left out, here as in the program.  Asked for
all of them it is the uncut layer (the share test,
tests/test_kimi_linear.py).

Weights are the program's parameter dictionary, upcast here a layer at a
time (``embed``, ``head``, ``lnf_g``; per layer ``ln1_g``, ``ln2_g``; KDA
``wqkv [H, 3 I]`` (q | k | v), ``conv_w [K, 3 I]`` (row j the tap K - 1 - j
tokens back), ``low_a [H, 2 D + SH]`` (Wfa | Wga | Wb), ``f_b``, ``g_b [D,
I]``, ``dt_bias [I]``, ``A_log [SH]``, ``o_norm [D]``, ``wo``; MLA ``wq``,
``wkva``, ``kv_norm``, ``wkvb [rank, heads x (nope | v)]``, ``wo``; ``w1``,
``w3``, ``w2``; ``router``, ``expert_bias``, ``wgate``, ``wup [E, H, F]``,
``wdown [E, F, H]``, ``shared_w1``, ``shared_w3``, ``shared_w2``).  The head
(1.51e9 B in float32 if taken whole) is taken in column blocks.

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, as ``olmoe_ref.py`` has it: the served token's
*deficit* at a position is the reference's largest logit less its logit of
the served token, at most twice the served path's logit error.  The runner's
check sends at most 48 positions; ``benchmark/tests/chip_check_kimi.py``
compares the step's logits, cached rows and states themselves at some
hundreds of positions.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 46: ``benchmark/tests/chip_check_kimi.py`` gives
# both statistics for each of 32 sequences' last 64 positions a seed, at
# contexts of 150-264; its engine leg for 6,600 tokens by depth; the cell's
# own check for its 64 positions at contexts under 48).  Logits here have a
# standard deviation of 0.96 over 163,840 tokens.  As for the other routed
# families, what sets the readings is less arithmetic error than the routing's
# discontinuity: 26 routers a token over 256 experts each, the closest choice
# at a position won by 1.8e-4 of selection score in the median, so the served
# step and the float32 reference swap an expert in some layer now and then,
# and a swap moves that position's logits (root-mean-square logit error
# 0.071-0.078).
#   the share of positions whose served token is not the reference's argmax:
#     served 7-17 of 64 in the cell's seven checks (0.11-0.27), 0.05-0.34 in
#     any one sequence's 64 positions (96 sequences on three seeds, medians
#     0.17-0.18; the largest 0.27, 0.28, 0.34; the jnp paths 0.28, 0.33, 0.30),
#     0.15-0.26 in every band of depth of the engine leg; with the weights
#     rounded to fp8 (e4m3), the precision next below the stated bfloat16,
#     0.75-0.94 (medians 0.86-0.88; the smallest 0.75, 0.78).  The limit
#     stands between the two, 1.45 times the largest served reading and 0.67
#     of the smallest fp8 one.  Also over it: every fault in structure
#     (0.95-1.0), a slot not reset (0.80-0.98); routed_scaling dropped only
#     mostly (0.36-0.58), an ignored bias seldom (0.19-0.52).
#   the largest deficit: served 0.06-0.24 in the cell's seven checks, medians
#     0.16-0.20 a sequence and 0.30, 0.49, 0.36 the largest of 32 sequences a
#     seed (the jnp paths 0.40, 0.30, 0.43), 0.25-0.45 by band in the engine
#     leg; fp8 1.50-2.95 a sequence (medians 2.2-2.3).  The limit is twice the
#     largest served reading and 0.67 of the smallest fp8 one; a fault in
#     structure reads 3.5-8.0.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits, rows and states themselves at 214-264.
DEFICIT_BOUND = 1.0
DIFFERING_SHARE_BOUND = 0.5

GATE_EPS = 1e-20
L2_EPS = 1e-6
HEAD_BLOCK = 16384          # columns of the head upcast at a time


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def kinds_of(config):
    """``K`` (KDA) or ``L`` (latent attention) for each layer, from the
    source's two 1-indexed lists."""
    linear = config["linear_attn_config"]
    kda = set(linear["kda_layers"])
    if kda & set(linear["full_attn_layers"]) or len(kda) + len(
            linear["full_attn_layers"]) != config["num_hidden_layers"]:
        raise ValueError("kda_layers and full_attn_layers name each layer "
                         "once")
    return "".join("K" if l in kda else "L"
                   for l in range(1, config["num_hidden_layers"] + 1))


def _kda(config, p, h, mean_decay=False, delta=True, qk_norm=True, gate=True):
    """-> (the mixer's output [T, H], the state S [heads, keys, values]
    after the last of the T tokens).  The keywords are the chip check's
    broken references: the decay averaged over a head's channels (Gated
    DeltaNet's scalar gate), the delta correction dropped (``S + beta k
    v^T``), q and k not normalised, the output gate dropped."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    linear = config["linear_attn_config"]
    heads, d, taps = linear["num_heads"], linear["head_dim"], \
        linear["short_conv_kernel_size"]
    inner = heads * d
    qkv = h @ p["wqkv"]
    # depthwise causal convolution as its K-term sum, zeros before position 0
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, 3 * inner), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + t]
                          for j in range(taps)))
    q, k, v = (qkv[:, at:at + inner].reshape(t, heads, d)
               for at in (0, inner, 2 * inner))
    if qk_norm:
        q, k = (x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
                for x in (q, k))
    q = q * d ** -0.5
    low = h @ p["low_a"]
    log_decay = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        (low[:, :d] @ p["f_b"] + p["dt_bias"]).reshape(t, heads, d))
    if mean_decay:
        log_decay = jnp.broadcast_to(
            jnp.mean(log_decay, axis=-1, keepdims=True), log_decay.shape)
    alpha = jnp.exp(log_decay)
    beta = jax.nn.sigmoid(low[:, 2 * d:])                 # [T, heads]

    def one(state, at):
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[:, :, None] * state
        u = v_t - jnp.einsum("hkv,hk->hv", state, k_t) if delta else v_t
        state = state + beta_t[:, None, None] * k_t[:, :, None] \
            * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    last, o = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                           (q, k, v, alpha, beta))
    y = _rmsnorm(o, p["o_norm"], float(config["rms_norm_eps"]))
    if gate:
        y = y * jax.nn.sigmoid((low[:, d:2 * d] @ p["g_b"]).reshape(
            t, heads, d))
    return y.reshape(t, inner) @ p["wo"], last


def _mla(config, p, h, kv_norm=True, k_pe=True, scale=None):
    """-> (the mixer's output [T, H], the rows a latent cache would hold,
    ``[c | k_pe]`` [T, rank + rope]).  Expanded: every head's keys and
    values are made from ``c``.  The keywords are the chip check's broken
    references."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, nope, rope = config["num_attention_heads"], \
        config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    q = (h @ p["wq"]).reshape(t, heads, nope + rope)
    row = h @ p["wkva"]
    c, pe = row[:, :rank], row[:, rank:]
    if kv_norm:
        c = _rmsnorm(c, p["kv_norm"], float(config["rms_norm_eps"]))
    kv = (c @ p["wkvb"]).reshape(t, heads, nope + dv)
    scores = jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
    if k_pe:
        scores = scores + jnp.einsum("qhr,kr->hqk", q[..., nope:], pe)
    scores = scores * (scale or float(nope + rope) ** -0.5)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., nope:])
    return out.reshape(t, heads * dv) @ p["wo"], \
        jnp.concatenate([c, pe], axis=1)


def gates_of(config, p, x, use_bias=True, scaled=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score).  ``use_bias`` and ``scaled``
    False are the chip check's broken references."""
    import jax
    import jax.numpy as jnp

    n_exp = p["router"].shape[1]
    top = config["num_experts_per_token"]
    score = jax.nn.sigmoid(x @ p["router"])
    select = score + p["expert_bias"] if use_bias else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)
    if scaled:
        chosen = chosen * float(config["routed_scaling_factor"])
    return chosen, kth - ranked[:, n_exp - top - 1]


def _gated_mlp(x, w1, w3, w2):
    import jax

    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


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


def layer(config, kind, p, x, **broken):
    """One block over x [T, H] with its weights ``p`` (upcast here; a dense
    layer has ``w1``, a routed one ``router``) -> (x, what a cache would
    keep of its mixer: the last state for ``K``, the rows for ``L``; (gates
    [T, E], margin [T]) of a routed layer, else None).  ``broken`` passes
    the chip check's faults down (``mean_decay``, ``delta``, ``qk_norm``,
    ``gate``; ``kv_norm``, ``k_pe``, ``scale``; ``use_bias``, ``scaled``,
    ``shared``)."""
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    eps = float(config["rms_norm_eps"])
    pick = lambda *names: {n: broken[n] for n in names if n in broken}
    h = _rmsnorm(x, p["ln1_g"], eps)
    if kind == "K":
        mixed, kept = _kda(config, p, h, **pick("mean_decay", "delta",
                                                "qk_norm", "gate"))
    else:
        mixed, kept = _mla(config, p, h, **pick("kv_norm", "k_pe", "scale"))
    x = x + mixed
    h = _rmsnorm(x, p["ln2_g"], eps)
    if "w1" in p:
        return x + _gated_mlp(h, p["w1"], p["w3"], p["w2"]), kept, None
    routing = gates_of(config, p, h, **pick("use_bias", "scaled"))
    f = routed_sum(config, p, h, routing[0])
    if broken.get("shared", True):
        f = f + shared_out(config, p, h)
    return x + f, kept, routing


def _refuse_other_settings(config):
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1 \
            or config["hidden_act"] != "silu" \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"]:
        raise ValueError(
            "the kimi_linear reference is MLA with no rotation and no query "
            "compression, sigmoid scores in one group with renormalised "
            "gates in every layer after the dense lead, SiLU, an untied "
            "head and no next-token-prediction layer")


@functools.lru_cache(maxsize=None)
def _head_block(eps):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, g, w: _rmsnorm(x, g.astype(jnp.float32), eps)
                   @ w.astype(jnp.float32))


def forward(config, params, tokens, return_kept=False, layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it: ``states`` each KDA layer's state [heads,
    keys, values] after the last token, ``rows`` each latent layer's rows
    [T, rank + rope], ``gates`` [T, E] and ``margins`` [T] of each routed
    layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"][tokens].astype(jnp.float32)
    kept = {"states": [], "rows": [], "gates": [], "margins": []}
    for l, kind in enumerate(kinds_of(config)):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, held, routing = layer_fn(config, kind, mine, x)
        kept["states" if kind == "K" else "rows"].append(held)
        if routing is not None:
            kept["gates"].append(routing[0])
            kept["margins"].append(routing[1])
    head = _head_block(float(config["rms_norm_eps"]))
    logits = jnp.concatenate(
        [head(x, params["lnf_g"], params["head"][:, at:at + HEAD_BLOCK])
         for at in range(0, params["head"].shape[1], HEAD_BLOCK)], axis=1)
    return (logits, kept) if return_kept else logits


def by_layer(config, layer=layer, **broken):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer
    and of feed-forward): one layer's float32 weights are all that is alive
    at once."""
    import jax

    @functools.lru_cache(maxsize=None)
    def jitted(kind):
        return jax.jit(functools.partial(layer, config, kind, **broken))

    return functools.partial(
        forward, config,
        layer_fn=lambda _c, kind, p, x: jitted(kind)(p, x))


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
