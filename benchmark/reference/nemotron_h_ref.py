"""Plain reference for the served Nemotron-H decoder (nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type`` ``nemotron_h``): the
whole causal forward pass of one sequence in straightforward ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``, with no
cache, no slots, no batching and no kernel; the state-space scan a position
at a time, the experts a plain loop with a mask.  Written from the
architecture (the catalog row's ``config``, ISSUE 41's equations and the
family's ``modeling_nemotron_h.py`` conventions: ``NemotronHBlock``,
``NemotronHMamba2Mixer``, ``MambaRMSNormGated``, ``NemotronHAttention``,
``NemotronHMOE`` with DeepSeek-V3's ``NemotronHTopkRouter``), not from
``paddle_tpu/models/nemotron_h.py``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H, ``hybrid_override_pattern`` (a letter a layer: ``M``
Mamba-2, ``*`` attention, ``E`` experts), ``num_attention_heads`` query
heads over ``num_key_value_heads`` KV heads of ``head_dim``,
``mamba_num_heads`` heads of ``mamba_head_dim`` (``I`` their product),
``ssm_state_size`` N, ``n_groups`` G, ``conv_kernel`` K,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
``n_routed_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``norm_eps``.  Every block is one pre-norm sublayer, for the hidden vectors
``x`` of a sequence (row ``t`` the token at position ``t``)::

    x = x + mixer(rmsnorm(x, norm))                   # NemotronHBlock
    *:  q, k, v = h @ Wq, h @ Wk, h @ Wv;  no position encoding; causal
        query head j attends KV head j // group; scores / sqrt(head_dim)
        mixer = attn @ Wo
    M:  z, xBC, dt = split(h @ in_proj, [I, I + 2 G N, heads])
        xBC = silu(conv_b + causal depthwise conv_K(xBC))
        xs, B, C = split(xBC, [I, G N, G N])
        dt = softplus(dt + dt_bias);  A = -exp(A_log)            # per head
        S_h[t] = exp(dt_h A_h) S_h[t-1] + dt_h outer(xs_h, B_g)  # g = h // (heads / G)
        y_h = S_h[t] C_g + D_h xs_h
        mixer = grouped_rmsnorm(y * silu(z), ssm_norm; groups of I / G) @ out_proj
    E:  s = sigmoid(h @ gate)                                    # [E]
        S = the num_experts_per_tok largest of s + e_score_correction_bias
        w_e = routed_scaling_factor * s_e / (sum_{e in S} s_e + 1e-20)
        mixer = sum_{e in S, e held} w_e * (relu(h @ up_e^T)^2 @ down_e)
                + relu(h @ shared_up)^2 @ shared_down
    logits = rmsnorm(x, norm_f) @ lm_head

Departures from the modelling code, each noted: the scan is the recurrence
itself, a position at a time, not the chunked form (``chunk_size`` is
unused: the two are the same mathematics); ``time_step_limit`` is (0, inf)
there, which clamps nothing, and is left out; the state is float32 here as
everything is (the modelling code keeps it in the model's dtype: the
configuration's ``departures`` has it).  ``n_group`` 1 and ``topk_group`` 1
make DeepSeek-V3's group-limited choice a plain top-k, and anything else is
refused.

**The share.**  ``n_routed_experts`` counts the experts *held* (rows of
``experts_up`` / ``experts_down``), ``n_routed_experts_published`` the
router's width and ``first_expert`` the first one held.  The router scores
all, renormalises over all the chosen, and the sum runs over the held ones:
what an absent expert would add is left out, here as in the program.  Asked
for all of them (``n_routed_experts`` = ``n_routed_experts_published``,
``first_expert`` 0, whole weights), it is the uncut layer (the share test,
tests/test_nemotron_h.py).  A sliced vocabulary is a smaller one.

Weights are the program's parameter dictionary, upcast here (``embed``,
``head``, ``lnf_g``, ``l<i>_norm``; ``wq``, ``wk``, ``wv``, ``wo``;
``in_proj``, ``conv_w [K, I + 2 G N]``, ``conv_b``, ``dt_bias``, ``A_log``,
``D``, ``ssm_norm``, ``out_proj``; ``router``, ``expert_bias``,
``experts_up``, ``experts_down [E, F, H]`` (``up`` as ``nn.Linear`` holds
it), ``shared_up [H, Fs]``, ``shared_down [Fs, H]``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, as ``olmoe_ref.py`` has it: the served token's
*deficit* at a position is the reference's largest logit less its logit of
the served token, at most twice the served path's logit error.  The runner's
check sends at most 48 positions; ``benchmark/tests/chip_check_nemotron.py``
compares the step's logits, cached K and V and recurrent state themselves
at some hundreds of positions.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 41: ``benchmark/tests/chip_check_nemotron.py``
# gives both statistics for each of 32 sequences' last 64 positions a seed, at
# contexts of 200-324; the cell's own check gives them for its 64 positions at
# contexts under 48).  Logits here have a standard deviation of 1.04 over
# 16,384 tokens.  As for LFM2 and K-EXAONE, what sets the readings is less
# arithmetic error than the routing's discontinuity: 23 routers a token, and at
# a position the closest of their choices beats the first expert left out by
# 3.2e-4 of selection score in the median; the served path's bfloat16 leaves
# more noise than that on a score, so the served step and the float32
# reference swap an expert in some layer now and then, and a swap moves that
# position's logits (root-mean-square logit error 0.094).
#   the share of positions whose served token is not the reference's argmax:
#     served 6 and 11 of 64 in the cell's first two checks (0.094, 0.172),
#     0.016-0.31 in any one sequence's 64 positions (96 sequences on three
#     seeds, medians 0.125-0.141; the largest 0.31, 0.22, 0.22), 0.128-0.143
#     in every band of depth of the engine leg's 7,000 tokens; with the weights
#     rounded to fp8 (e4m3), the precision next below the stated bfloat16,
#     0.45-0.77 (medians 0.62-0.64; the smallest 0.45, 0.50, 0.50).  The limit
#     stands between the two, 1.2 times the largest served reading and 0.84
#     of the smallest fp8 one: it is what holds the precision, and fp8 comes
#     out not correct by this limit and not by the next.  Also over it: no
#     shared expert (0.98-1.0), relu for relu^2 (0.92-1.0), every head on
#     group 0 (0.66-0.92), routed_scaling dropped (0.48-0.78); an ignored
#     bias only mostly (0.31-0.63), a slot not reset seldom (0.14-0.47).
#   the largest deficit: served 0.18 and 0.55 in the cell's first two checks,
#     medians 0.28-0.43 a sequence and 1.17, 1.73, 1.08 the largest of 32
#     sequences a seed; fp8 1.0-2.6 (not held by this limit).  A fault in
#     structure reads over it: no shared expert 5.2-7.7, relu for relu^2
#     2.9-5.4 (medians 3.8), every head on group 0 1.6-3.8 (medians 2.2-2.6).
#     The limit is one and a half times the largest served reading.
# What neither sees here: the cell's check sends at most 48 positions; the chip
# check compares logits, K and V and the state themselves at 264-324.
DEFICIT_BOUND = 2.6
DIFFERING_SHARE_BOUND = 0.38

GATE_EPS = 1e-20
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def _rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _attention(config, p, h):
    """-> (the mixer's output [T, H], (K, V) [T, kv_heads, D] as a cache
    would hold them)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    dim = config["head_dim"]
    q = (h @ p["wq"]).reshape(t, heads, dim)
    k = (h @ p["wk"]).reshape(t, kv_heads, dim)
    v = (h @ p["wv"]).reshape(t, kv_heads, dim)
    kv = (k, v)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dim)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * dim) @ p["wo"], kv


def _mamba(config, p, h, one_group=False):
    """-> (the mixer's output [T, H], the state S [heads, d_head, N] after
    the last of the T tokens).  ``one_group`` is the chip check's broken
    reference: every head reads group 0."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, d_head = config["mamba_num_heads"], config["mamba_head_dim"]
    n, groups, taps = config["ssm_state_size"], config["n_groups"], \
        config["conv_kernel"]
    inner, bc = heads * d_head, groups * n
    zxbcdt = h @ p["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * bc]
    dt = zxbcdt[:, 2 * inner + 2 * bc:]
    # depthwise causal convolution as its K-term sum, zeros before position 0
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * padded[j:j + t] for j in range(taps)))
    xs = xbc[:, :inner].reshape(t, heads, d_head)
    # a pair a group, repeated over the group's heads (repeat_interleave)
    of_head = np.zeros(heads, np.int32) if one_group \
        else np.arange(heads) // (heads // groups)
    b = xbc[:, inner:inner + bc].reshape(t, groups, n)[:, of_head]
    c = xbc[:, inner + bc:].reshape(t, groups, n)[:, of_head]
    dt = jax.nn.softplus(dt + p["dt_bias"])              # [T, heads]
    a = -jnp.exp(p["A_log"])                             # [heads]

    def one(state, at):
        xs_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + dt_t[:, None, None] * xs_t[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hdn,hn->hd", state, c_t)

    last, y = jax.lax.scan(
        one, jnp.zeros((heads, d_head, n), jnp.float32), (xs, b, c, dt))
    y = (y + p["D"][None, :, None] * xs).reshape(t, inner)
    # MambaRMSNormGated, norm_before_gate False: gate, then a norm a group
    gated = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = _rmsnorm(gated, p["ssm_norm"].reshape(groups, -1),
                 float(config["norm_eps"])).reshape(t, inner)
    return y @ p["out_proj"], last


def gates_of(config, p, x, use_bias=True, scaled=True):
    """-> (gates [T, E] over the whole router: the chosen experts' weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in selection score).  ``use_bias`` and ``scaled``
    False are the chip check's broken references."""
    import jax
    import jax.numpy as jnp

    n_exp = p["router"].shape[1]
    top = config["num_experts_per_tok"]
    score = jax.nn.sigmoid(x @ p["router"])
    select = score + p["expert_bias"] if use_bias else score
    ranked = jnp.sort(select, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(select >= kth[:, None], score, 0.0)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)
    if scaled:
        chosen = chosen * float(config["routed_scaling_factor"])
    return chosen, kth - ranked[:, n_exp - top - 1]


def _relu2_mlp(x, up, down, square=True):
    import jax

    act = jax.nn.relu(x @ up)
    return (act * act if square else act) @ down


def routed_sum(config, p, x, gates, square=True):
    """sum over the held experts of gate * expert(x): expert ``first_expert
    + i`` of the router is row ``i`` of the weights."""
    import jax.numpy as jnp

    first = int(config.get("first_expert", 0))
    out = jnp.zeros_like(x)
    for i in range(config["n_routed_experts"]):
        y = _relu2_mlp(x, p["experts_up"][i].T, p["experts_down"][i], square)
        out = out + gates[:, first + i:first + i + 1] * y
    return out


def shared_out(config, p, x, square=True):
    if not config["n_shared_experts"]:
        return 0.0
    return _relu2_mlp(x, p["shared_up"], p["shared_down"], square)


def layer(config, kind, p, x, **broken):
    """One block over x [T, H] with its weights ``p`` (upcast here) -> (x,
    what a cache would keep of it: (K, V) for ``*``, the last state for
    ``M``, (gates [T, E], margin [T]) for ``E``).  ``broken`` passes the
    chip check's faults down (``one_group``, ``use_bias``, ``scaled``,
    ``square``, ``shared``)."""
    import jax.numpy as jnp

    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _rmsnorm(x, p["norm"], float(config["norm_eps"]))
    if kind == "*":
        mixed, kept = _attention(config, p, h)
    elif kind == "M":
        mixed, kept = _mamba(config, p, h, broken.get("one_group", False))
    else:
        square = broken.get("square", True)
        kept = gates_of(config, p, h, broken.get("use_bias", True),
                        broken.get("scaled", True))
        mixed = routed_sum(config, p, h, kept[0], square)
        if broken.get("shared", True):
            mixed = mixed + shared_out(config, p, h, square)
    return x + mixed, kept


def _refuse_other_settings(config):
    pattern = config["hybrid_override_pattern"]
    if not config["norm_topk_prob"] or config["n_group"] != 1 \
            or config["topk_group"] != 1 or config["tie_word_embeddings"] \
            or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["mamba_proj_bias"] or config["use_bias"] \
            or not config["use_conv_bias"] \
            or len(pattern) != config["num_hidden_layers"] \
            or set(pattern) - set(KINDS) \
            or config["mamba_num_heads"] % config["n_groups"]:
        raise ValueError(
            "the nemotron_h reference is sigmoid scores in one group, "
            "renormalised gates, relu^2 experts, SiLU in the mixer, a biased "
            "convolution and no other bias, an untied head, and a letter of "
            "M*E a layer")


def forward(config, params, tokens, return_kept=False, layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids (and, asked for,
    what a cache would hold of it: ``kv`` the K and V [T, kv_heads,
    head_dim] of each attention layer, ``states`` each mamba layer's state
    [heads, d_head, N] after the last token, ``gates`` [T, E] and
    ``margins`` [T] of each experts layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"].astype(jnp.float32)[tokens]
    kept = {"kv": [], "states": [], "gates": [], "margins": []}
    for l, kind in enumerate(config["hybrid_override_pattern"]):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, held = layer_fn(config, kind, mine, x)
        if kind == "*":
            kept["kv"].append(held)
        elif kind == "M":
            kept["states"].append(held)
        else:
            kept["gates"].append(held[0])
            kept["margins"].append(held[1])
    logits = _rmsnorm(x, params["lnf_g"].astype(jnp.float32),
                      float(config["norm_eps"])) \
        @ params["head"].astype(jnp.float32)
    return (logits, kept) if return_kept else logits


def by_layer(config, layer=layer, **broken):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer):
    one layer's float32 weights are all that is alive at once."""
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
