"""Plain reference for the served decoder: the whole causal forward pass in
straightforward ``jax.numpy`` and float32, with no cache, no paging and no
batching, at ``jax.default_matmul_precision("highest")``.  Independent of
``serving/decode_model.py``: written from the architecture (pre-LN
transformer decoder, learned positions, exact GELU, untied head, no
attention biases -- the departures from GPT-2 are listed in the
configuration file and are the served model's, so the reference has them
too).

The server returns tokens, not logits, so the comparison is teacher-forced:
given the prompt and the tokens the server produced, position ``P - 1 + i``
of one forward pass is the reference's logits for the i-th served token.
The served token's *deficit* there is the reference's largest logit less
its logit of the served token: 0 where the two agree, and otherwise at most
twice the served path's logit error (the server preferred the token, the
reference prefers another by that much).  The largest deficit over all
compared positions is therefore a lower bound on twice the served logit
error, read through the tokens alone.
"""

import numpy as np

# The largest deficit a correct server may show: three times the largest the
# served step has shown on the chip (0.009 over 13 runs x 64 positions; PERF.md
# section 6).  With normal(0, 0.02) weights the logits are nearly flat
# (standard deviation near 0.6 over 50,257 tokens), so a fault in structure
# (a lost layer, a wrong mask or position, a stale or misplaced cache block)
# moves the argmax almost everywhere and by tenths.  What the check cannot
# see is a loss of precision as small as bfloat16's: the same model run in
# bfloat16 end to end (``greedy`` below) showed deficits of 0.005 on the chip
# and 0.014 on a CPU, inside the bound and beside the served step's own
# (``benchmark/tests/chip_check_precision.py`` prints both).  Telling those
# apart needs the server's logits, which it does not return.
DEFICIT_BOUND = 0.03


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def forward(config, params, tokens):
    """Logits [T, vocab] of one sequence of T token ids."""
    import jax
    import jax.numpy as jnp

    heads = config["n_head"]
    dim = config["n_embd"] // heads
    eps = float(config["layer_norm_epsilon"])
    t = tokens.shape[0]
    x = params["embed"][tokens] + params["pos_embed"][jnp.arange(t)]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for l in range(config["n_layer"]):
        p = lambda n, _l=l: params["l%d_%s" % (_l, n)]
        h = _layer_norm(x, p("ln1_g"), p("ln1_b"), eps)
        q = (h @ p("wq")).reshape(t, heads, dim)
        k = (h @ p("wk")).reshape(t, heads, dim)
        v = (h @ p("wv")).reshape(t, heads, dim)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dim)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(t, heads * dim) @ p("wo")
        h = _layer_norm(x, p("ln2_g"), p("ln2_b"), eps)
        x = x + jax.nn.gelu(h @ p("w1") + p("b1"), approximate=False) \
            @ p("w2") + p("b2")
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
    return x @ params["head"]


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


def greedy(config, params, prompt, n_out, pad_to, dtype):
    """``n_out`` greedy tokens after ``prompt`` from this file's forward
    pass with weights and activations in ``dtype`` at the default matmul
    precision: a stand-in for a served path of that precision, to show what
    ``check`` does and does not catch.  One full forward pass per token; for a handful of
    short sequences only."""
    import jax
    import jax.numpy as jnp

    cast = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    fwd = jax.jit(lambda prm, tok: forward(config, prm, tok))
    seq = list(prompt)
    for _ in range(n_out):
        padded = np.zeros(pad_to, np.int32)
        padded[:len(seq)] = seq
        logits = fwd(cast, jnp.asarray(padded))
        seq.append(int(jnp.argmax(logits[len(seq) - 1])))
    return seq[len(prompt):]
