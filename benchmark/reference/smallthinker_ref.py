"""Plain reference for the served SmallThinker decoder (PowerInfer/
SmallThinker-21BA3B-Instruct, ``model_type`` ``smallthinker``): the whole
causal forward pass of one sequence in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``, with no cache, no
ring, no batching and no kernel; a window layer is a band mask of
``sliding_window_size`` over the causal score matrix, the experts a plain
loop with a mask.  Written from the architecture (the catalog row's
``config`` and ``described_as`` and ISSUE 53's equations), not from
``paddle_tpu/models/smallthinker.py``.

Sizes as the configuration gives them, under the source's own keys:
``hidden_size`` H, ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``, ``sliding_window_layout``
(1: the layer attends its last ``sliding_window_size`` positions; 0: its
whole context) and ``rope_layout`` (1: q and k rotated; 0: no position
encoding), one entry a layer, ``moe_num_primary_experts`` experts of width
``moe_ffn_hidden_size``, ``moe_num_active_primary_experts`` a token,
``rope_theta``, ``rms_norm_eps``.  For the hidden vectors ``x`` of a
sequence, row ``t`` the token at position ``t``::

    h  = rmsnorm(x, ln1)
    r  = h @ router                                    # [E]: the router reads h
    S  = the moe_num_active_primary_experts largest of softmax(r)
    w_e = softmax(r)_e / sum_{e' in S} softmax(r)_e'   # norm_topk_prob
    q, k, v = h @ Wq, h @ Wk, h @ Wv                   # no bias, no q/k norm
    rope_layout 1:            q, k = rope(q), rope(k)  # rotate-half pairs (j, j + D/2)
    sliding_window_layout 1:  causal, and only positions > t - W
    query head j attends KV head j // group; scores / sqrt(head_dim)
    x  = x + attn @ Wo
    h2 = rmsnorm(x, ln2)
    x  = x + sum_{e in S} w_e * ((relu(h2 @ wgate_e) * (h2 @ wup_e)) @ wdown_e)
    logits = rmsnorm(x, norm) @ lm_head                # untied

Departures from the plainest form, none of the mathematics: the attention
goes ``QUERY_BLOCK`` queries at a time (a sequence of 8,300 positions would
hold 7.7e9 B of scores at once), each block against the keys it can see;
weights are taken as they are served (bfloat16) and upcast to float32 one
layer at a time (``by_layer``: a jitted layer a kind, so that one layer's
float32 copy, 1.6e9 B at the published widths, is all that lives beside the
engine); ``forward(..., rows=...)`` computes the logits of the named
positions alone (the head is 151,936 wide).  Parameter names are the served
ones (``embed``, ``head``, ``lnf_g`` and per layer ``l<i>_`` + ``ln1_g``,
``ln2_g``, ``wq``, ``wk``, ``wv``, ``wo``, ``router [H, E]``,
``wgate``/``wup [E, H, F]``, ``wdown [E, F, H]``).

The server returns tokens, not logits, so ``check`` is teacher-forced
through the tokens alone, exactly as ``exaone_moe_ref.py`` has it: the
served token's *deficit* at a position is the reference's largest logit less
its logit of the served token, at most twice the served path's logit error.
The runner's check sends at most 48 positions, far under one window of
4,096: it cannot tell a window layer from a global one.
``benchmark/tests/chip_check_smallthinker.py`` compares the step's logits
themselves under 4,096 positions, past 4,112 and past 8,224.
"""

import functools

import numpy as np

# Two limits on what a correct server's tokens may show, from readings on the
# chip (PERF.md section 6, PR 53: ``benchmark/tests/chip_check_smallthinker.py``
# gives both statistics for its compared lanes' last 8 positions at contexts of
# 520-8,300, its ``--engine`` leg for six requests' last 64 tokens at 560-8,264
# positions, and the cell's own check, five runs, for its 64 positions at
# contexts under 48).  What sets the readings is, as for the other routed
# models, less arithmetic error than the routing's discontinuity: the served
# path's bfloat16 leaves noise on the router's input, the served step and the
# float32 reference swap a sixth expert now and then, and a swap moves that
# position's logits (standard deviation 1.0 over 151,936 tokens by the head's
# seeded weights: 0.02 x sqrt(2560)).
#   the share of positions whose served token is not the reference's argmax:
#     served 0-3 of 64 in the cell's five checks (at most 0.047), 0-8 of 64 in
#     the engine leg's six requests (0.125 at 4,246 positions, the others at
#     most 0.031), 0-1 of 8 on the chip check's lanes; with the weights rounded
#     to fp8 (e4m3), the precision next below the stated bfloat16, 2 | 2 | 7 of
#     8 (0.25-0.875).  The limit stands between the two, 1.4 times the largest
#     served reading and 0.7 of the smallest fp8 one: it is what holds the
#     precision, and fp8 comes out not correct by this limit and not by the
#     next.  Also over it: the router fed ``h2`` (1.0), a ring chunk's mask
#     shifted by a chunk (0.875-1.0), SiLU for ReLU (0.5), a global layer
#     rotated (0.25).
#   the largest deficit: served at most 0.014 in the cell's checks, 0.087 in
#     the engine leg, 0.007 on the chip check's lanes; fp8 0.10-0.36 (not held
#     by this limit).  A fault in structure that leaves the argmax alone reads
#     over it: a ring chunk's mask shifted 1.33-3.05.  The limit is eleven
#     times the largest served reading, since a swapped expert's mark has a
#     long tail (K-EXAONE's largest of 160 sequences was 1.36), and under the
#     smallest reading of the shifted mask.
# What neither sees here: the cell's check sends at most 48 positions, under
# one chunk of 256 and far under the window of 4,096; the chip check holds
# those.
DEFICIT_BOUND = 1.0
DIFFERING_SHARE_BOUND = 0.18

# queries the attention scores at once
QUERY_BLOCK = 512


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


def _attention(config, sliding, rotated, p, h):
    """The attention of the normed rows h [T, H] -> [T, H] (before the
    residual)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    dim = config["head_dim"]
    q = (h @ p["wq"]).reshape(t, heads, dim)
    k = (h @ p["wk"]).reshape(t, kv_heads, dim)
    v = (h @ p["wv"]).reshape(t, kv_heads, dim)
    if rotated:
        theta = float(config["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    window = int(config["sliding_window_size"])
    key_at = jnp.arange(t)[None, :]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        query_at = jnp.arange(lo, hi)[:, None]
        seen = key_at[:, :hi] <= query_at
        if sliding:
            # query t sees keys t - W + 1 .. t
            seen = seen & (key_at[:, :hi] > query_at - window)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / np.sqrt(dim)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v[:hi]))
    return jnp.concatenate(out).reshape(t, heads * dim) @ p["wo"]


def gates_of(config, p, h):
    """-> (gates [T, E]: the chosen experts' renormalised softmax weights,
    0 elsewhere; margin [T]: by how much the last expert chosen beat the
    first one left out, in probability)."""
    import jax
    import jax.numpy as jnp

    n_exp = p["router"].shape[1]
    top = config["moe_num_active_primary_experts"]
    prob = jax.nn.softmax(h @ p["router"], axis=-1)
    ranked = jnp.sort(prob, axis=-1)
    kth = ranked[:, n_exp - top]
    chosen = jnp.where(prob >= kth[:, None], prob, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True), \
        kth - ranked[:, n_exp - top - 1]


def routed_sum(config, p, h2, gates, act=None):
    """sum over the experts of gate * expert(h2), a ReLU-gated MLP each."""
    import jax
    import jax.numpy as jnp

    act = act or jax.nn.relu
    out = jnp.zeros_like(h2)
    for e in range(config["moe_num_primary_experts"]):
        y = (act(h2 @ p["wgate"][e]) * (h2 @ p["wup"][e])) @ p["wdown"][e]
        out = out + gates[:, e:e + 1] * y
    return out


def layer(config, sliding, rotated, p, x):
    """One layer over x [T, H] with its weights ``p`` (upcast here) -> (x,
    (gates [T, E], margin [T]))."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _rmsnorm(x, p["ln1_g"], eps)
    routing = gates_of(config, p, h)         # from the attention's INPUT
    x = x + _attention(config, sliding, rotated, p, h)
    h2 = _rmsnorm(x, p["ln2_g"], eps)
    return x + routed_sum(config, p, h2, routing[0]), routing


def _refuse_other_settings(config):
    n = config["num_hidden_layers"]
    if not config["moe_primary_router_apply_softmax"] \
            or not config["norm_topk_prob"] \
            or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None \
            or len(config["sliding_window_layout"]) != n \
            or len(config["rope_layout"]) != n \
            or set(config["sliding_window_layout"]) - {0, 1} \
            or set(config["rope_layout"]) - {0, 1}:
        raise ValueError(
            "the smallthinker reference is a softmax router with "
            "renormalised gates, an untied head, plain RoPE and a 0 or 1 a "
            "layer in sliding_window_layout and rope_layout")


def forward(config, params, tokens, rows=None, return_kept=False,
            layer_fn=layer):
    """Logits [T, vocab] of one sequence of T token ids, or of its
    positions ``rows`` alone (and, asked for, ``gates`` [T, E] and
    ``margins`` [T] of every layer)."""
    import jax.numpy as jnp

    _refuse_other_settings(config)
    x = params["embed"].astype(jnp.float32)[tokens]
    kept = {"gates": [], "margins": []}
    for l, (sliding, rotated) in enumerate(zip(
            config["sliding_window_layout"], config["rope_layout"])):
        mine = {k[len("l%d_" % l):]: v for k, v in params.items()
                if k.startswith("l%d_" % l)}
        x, routing = layer_fn(config, bool(sliding), bool(rotated), mine, x)
        kept["gates"].append(routing[0])
        kept["margins"].append(routing[1])
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = _rmsnorm(x, params["lnf_g"].astype(jnp.float32),
                      float(config["rms_norm_eps"])) \
        @ params["head"].astype(jnp.float32)
    return (logits, kept) if return_kept else logits


def by_layer(config, layer=layer):
    """-> ``forward`` a jitted layer at a time (a compile a kind of layer
    and a length): one layer's float32 weights are all that is alive at
    once."""
    import jax

    @functools.lru_cache(maxsize=None)
    def jitted(sliding, rotated):
        return jax.jit(functools.partial(layer, config, sliding, rotated))

    return functools.partial(
        forward, config,
        layer_fn=lambda _c, sliding, rotated, p, x:
        jitted(sliding, rotated)(p, x))


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
            padded = np.zeros(max(pad_to, len(seq)), np.int32)
            padded[:len(seq)] = seq
            rows = np.arange(len(prompt) - 1, len(seq) - 1)
            logits = np.asarray(fwd(params, jnp.asarray(padded), rows=rows))
            for row, tok in zip(logits, served):
                deficit = float(row.max() - row[int(tok)])
                compared += 1
                differing += deficit > 0
                worst = max(worst, deficit)
    return {"compared": compared, "differing": int(differing),
            "largest_deficit": worst,
            "differing_share_bound": DIFFERING_SHARE_BOUND,
            "ok": worst <= DEFICIT_BOUND
            and differing <= DIFFERING_SHARE_BOUND * compared}
