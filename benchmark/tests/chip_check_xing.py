"""On the chip, outside any timed window: the served Xing4.0 step's *logits*,
cached latent rows, residual streams and mixing maps against the plain
reference, at the published widths, all 40 layers, the held share and the
cell's lanes, pool and block size.

    chiprun --timeout 3400 -- python benchmark/tests/chip_check_xing.py --seeds 2

Seeded weights as the cell makes them (``expert_bias`` balanced); 32
sequences at once, a lane each of a 32-lane ``make_paged_step`` over the
cache manager's pools (the cell's 3,616 latent blocks of 16 by a shuffled
table): prompts of the traffic's lengths (log-uniform 32-256) fed a token a
step (prefill here is token-feed, as the engine does it) and then decoded,
teacher-forced with the step's own argmax, to total lengths spread over
200-1,792 positions (seven distinct lengths: the reference compiles once a
length).  The step is the served one with more outputs (``xing4.token_logits``
handed ``kept``): the streams behind layer 0 and behind layer 39, layer 0's
first ``H_res``, and the worst row and column sum of any of the step's 80
``H_res``.  At the last ``N_DECODE`` positions of each sequence and at
positions ``SHORT - N_DECODE .. SHORT`` the step's logits and streams are
compared with ``xing4_ref.forward`` of the whole sequence (float32, highest
matmul precision, the served bf16 weights upcast a piece at a time, no cache,
latent attention expanded, a layer and a block of positions at a time), and
what layer 0's and layer 39's pools hold of each sequence with the
reference's ``[c | rotated k_pe]`` rows.

The controls run on the first ``SHORT`` positions of every sequence, the
served run's tokens forced, beside the served path run again on the same
(which every control is paired with).  Each has to fall outside a limit:

* a reference told otherwise, judged against the served step: the Sinkhorn
  normalisation stopped at 1 iteration, ``H_post`` without its 2, the
  flattened norm left out, the rotation left out, ``m^2`` left out;
* a step with one fault in precision, judged by the reference: the streams
  carried in bfloat16, the three maps rounded to bfloat16, ``phi`` rounded
  to bfloat16, and the weights rounded to fp8 (e4m3: the precision next
  below the one the configuration states, what ``xing4_ref.check``'s limits
  are set against);
* the clamp: layer 0's first mixing with a ``b_res`` entry of 100, its maps
  by ``hyper_connections.maps`` on the chip finite and the reference's, the
  reference without the clamp not finite.

Exit code 1 if the served path is outside a limit on any seed, or a control
inside all of them.  ``--tiny-on-cpu`` rehearses here; nothing it prints is a
chip result.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (my chip runs, PR 67, call 2 under
# placeholder limits and call 4, rc 0, under these: seeds 2147483777 |
# 2147491696, first | second below; 32 sequences x 32 positions
# x 16,384 logits of standard deviation 1.20 each, at the sequences' ends
# (200-1,792 positions) and, for the runs the controls are paired with, at
# positions 168-199).  Weights are the same bits on both sides.  What is left
# is the served path's bfloat16 inside a sublayer (the input of every matmul
# and the cached rows rounded to 8 bits of mantissa, 80 sublayers deep; the
# streams between them are float32 on both sides) and what that noise does to
# the routing: 38 routers a token choose 4 of 64 experts, the closest choice
# at a position won by 4.8e-5 of selection score at a tenth of positions, so
# the two sides swap an expert in some layer now and then, and a swap moves
# that position's logits (the largest error is one: 3.4-3.6 of 1.2).  The
# limits on logits and on the last layer's streams therefore hold structure;
# those read before any router (layer 0's rows and first H_res, the streams
# behind layer 0) hold the precision of the mixer and of the mixing:
#   root-mean-square logit error: served 0.136 | 0.138 at the ends, 0.147 |
#     0.144 on the first 200; one Sinkhorn iteration 0.272 | 0.282, fp8
#     weights 0.581 | 0.593, the flattened norm left out 0.795 | 0.921,
#     H_post without its 2 0.948 | 1.01, m^2 left out 1.19 | 1.20, the
#     rotation left out 1.43 | 1.43.  The limit is 1.36 times the largest
#     served reading and 0.74 of the smallest control's.
#   largest logit error: served 3.55 | 3.36 (3.63 | 2.81); one iteration 4.12
#     | 3.65, which a maximum cannot tell from a swap; fp8 4.83 | 5.89; the
#     faults in structure 6.2-8.5.  The limit is 1.38 times the largest served
#     reading and 0.81 of the smallest fault in structure.
#   streams behind layer 0 (two dense sublayers and two mixings deep),
#     root-mean-square error as a share of their own root-mean-square: served
#     0.00703 | 0.00691 (0.00773 | 0.00768); one iteration 0.0285 | 0.0361,
#     fp8 0.149, a fault in structure 0.59-1.16.  The limit is 1.55 times the
#     served reading and 0.42 of one iteration's.  Streams CARRIED in
#     bfloat16 read 0.00814 | 0.00812: their rounding drowns in the sublayers'
#     own bfloat16 products, so they are held by the next reading;
#   the share of those streams' values that bfloat16 holds exactly: served
#     2.4e-5 | 3.0e-5 (1 in 65,536 by chance), streams carried in bfloat16
#     1.0 | 1.0 (Solar-Open2's check holds a bfloat16 state so);
#   streams behind layer 39: served 0.113 | 0.114 (0.122 | 0.119); one
#     iteration 0.242 | 0.255, fp8 0.479 | 0.487.  The limit is 1.4 times the
#     served reading and 0.70 of one iteration's;
#   layer 0's first H_res, largest absolute difference from the reference's
#     (entries in (0, 1)): served 3.0e-7 | 3.3e-7 (4.2e-7 | 3.0e-7); phi
#     rounded to bfloat16 8.1e-4 | 8.2e-4, fp8 weights 0.016 | 0.015 (the
#     streams it reads are off), one iteration 0.23 | 0.21, no flattened norm
#     0.40 | 0.45.  The limit is 48 times the served reading and a fortieth of
#     the bfloat16 phi's;
#   layer 0's rows: served 0.00235 on every run (dots.vlm1's reading to the
#     digit: the same mixer); fp8 0.047, the rotation left out 0.55.  The
#     limit is 2.5 times the served reading and 0.13 of fp8's;
#   every H_res of the step, the worst row sum's distance from 1: served
#     0.00176 | 0.00054 (0.00103 | 0.00157): what 20 iterations leave (one
#     leaves 0.1-0.5, tests/test_xing4.py); the worst column sum's: 1.7e-6
#     (columns are normalised last), maps rounded to bfloat16 0.0034 | 0.0032
#     (and layer 0's H_res 0.00195 off the reference's, call 4).
#   paired with the served path run again on the same 200 positions, tokens
#     and weights: one iteration reads 1.85 | 1.96 of its rms logit error,
#     every fault in structure 5.4-9.9, fp8 3.95 | 4.12; a fault in the
#     mixing's precision 0.99-1.05 (held by the readings above).
# Each control falls outside one limit on every seed, not outside each.  The
# faults in precision round with ``jax.lax.reduce_precision``: a convert to
# bfloat16 and back inside one program XLA may drop as excess precision, and
# did where the maps were rounded so in call 2 (layer 0's H_res came out
# unrounded beside sums that were not).
RMS_TOLERANCE = 0.20
LOGIT_TOLERANCE = 5.0
PAIRED_RMS_TOLERANCE = 1.15
FIRST_ROWS_TOLERANCE = 0.006
FIRST_STREAMS_TOLERANCE = 0.012
LAST_STREAMS_TOLERANCE = 0.17
FIRST_RES_TOLERANCE = 2e-5
ROW_SUM_TOLERANCE = 0.006
COLUMN_SUM_TOLERANCE = 1e-4
STREAMS_EXACT_TOLERANCE = 0.01
N_DECODE = 32
SHORT = 200                 # positions the controls run on
LANES = 32
BLOCK = 16
TOTALS = (200, 456, 712, 968, 1224, 1480, 1792)
BROKEN_REFERENCES = {
    "one_sinkhorn_iteration": dict(iters=1),
    "post_without_its_two": dict(post_two=False),
    "no_flattened_norm": dict(flat_norm=False),
    "no_rotation": dict(rope=False),
    "no_yarn_scale": dict(mscale=False)}
FAULTY_STEPS = ("bf16_streams", "bf16_maps", "bf16_phi")
CONTROLS = tuple(BROKEN_REFERENCES) + FAULTY_STEPS + ("fp8_weights", "clamp")


def observed(token_logits):
    """The family's block with what the check reads returned among its
    extras: the streams behind the first and the last layer, layer 0's first
    ``H_res``, and over all the step's mixings the worst distance of a row
    sum and of a column sum of ``H_res`` from 1, a lane."""
    import jax.numpy as jnp

    def block(params, cfg, tok, pos, attend, live, recur=None):
        kept = {}
        logits, extras = token_logits(params, cfg, tok, pos, attend, live,
                                      recur, kept=kept)
        res = jnp.stack([kept[l, sub][2] for l in range(cfg.layers)
                         for sub in ("attn", "mlp")])        # [M, B, n, n]
        off = lambda axis: jnp.max(jnp.abs(jnp.sum(res, axis=axis) - 1.0),
                                   axis=(0, 2))
        return logits, tuple(extras) + (
            kept[0, "streams"], kept[cfg.layers - 1, "streams"],
            kept[0, "attn"][2], off(3), off(2))

    return block


def patched(name):
    """The block with one fault in its mixing's precision, and the outputs
    the check reads (modules patched) -> undo()."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import hyper_connections as hc
    from paddle_tpu.models import xing4

    saved = [(mod, key, getattr(mod, key)) for mod, key in (
        (hc, "start"), (hc, "merge"), (hc, "maps"),
        (xing4, "token_logits"))]
    # (a convert there and back XLA may drop as excess precision)
    bf16 = lambda x: jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7)
    start, merge, maps = hc.start, hc.merge, hc.maps
    if name == "bf16_streams":
        hc.start = lambda x, n: bf16(start(x, n))
        hc.merge = lambda *a: bf16(merge(*a))
    elif name == "bf16_maps":
        hc.maps = lambda *a: tuple(bf16(m) for m in maps(*a))
    elif name == "bf16_phi":
        hc.maps = lambda cfg, phi, b, a, X: maps(cfg, bf16(phi), b, a, X)
    xing4.token_logits = observed(xing4.token_logits)

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def weights_to_fp8(params):
    """The bfloat16 weights through 8 bits (e4m3) and back, the served set
    given up array by array (two sets do not fit); the mixings' float32
    parameters as they are (their own lower precision is ``bf16_phi``)."""
    import jax
    import jax.numpy as jnp

    down = jax.jit(lambda w: jax.lax.bitcast_convert_type(
        w.astype(jnp.float8_e4m3fn), jnp.uint8))
    up = jax.jit(lambda b, dt: jax.lax.bitcast_convert_type(
        b, jnp.float8_e4m3fn).astype(dt), static_argnums=(1,))
    given = {}
    for key in sorted(params):
        w = params.pop(key)
        given[key] = up(down(w), w.dtype) if w.dtype == jnp.bfloat16 else w
        del w
    return given


def windows(total, n_decode, short):
    """The positions of a sequence the check reads: the last ``n_decode``
    and the last ``n_decode`` of its first ``short``."""
    return sorted(set(range(total - n_decode, total))
                  | set(range(short - n_decode, short)))


def run_batch(step, cache, params, cfg, prompts, totals, n_decode, short,
              forced=None, upto=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended (at ``upto``
    positions, if given).  ``forced`` gives every token to feed; without it
    a sequence feeds its prompt and then the step's own argmax.  -> per
    sequence (tokens fed, position -> what the step gave there: logits, the
    streams behind the first and the last layer, layer 0's first H_res, the
    worst row and column sums; the first and the last layer's cached rows of
    the sequence)."""
    import numpy as np

    from paddle_tpu.pallas_kernels.paged_attention import gather_blocks

    kv = cache.config
    n = len(prompts)
    ends = [min(t, upto) if upto else t for t in totals]
    maxb = cfg.max_seq // BLOCK
    rng = np.random.default_rng(sum(totals))
    lanes = rng.permutation(LANES)[:n]
    free = iter(rng.permutation(np.arange(1, kv.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, end in enumerate(ends):
        for j in range(-(-end // BLOCK)):
            rows[i, j] = next(free)
    fed = [list(forced[i][:ends[i]] if forced else prompts[i])
           for i in range(n)]
    read = [set(p for p in windows(totals[i], n_decode, short)
                if p < ends[i]) for i in range(n)]
    seen = [{} for _ in range(n)]
    for pos in range(max(ends)):
        tok, at, lens = (np.zeros(LANES, np.int32) for _ in range(3))
        tables = np.full((LANES, maxb), -1, np.int32)
        live = [i for i in range(n) if pos < ends[i]]
        for i in live:
            b = lanes[i]
            tok[b], at[b], lens[b] = fed[i][pos], pos, pos + 1
            tables[b] = rows[i]
        carry, nxt, lg, _routed, _groups, *more = step(
            cache.carry(), params, tok, at, tables, lens)
        cache.replace_carry(carry)
        nxt = np.asarray(nxt)
        for i in live:
            if pos + 1 == len(fed[i]) and len(fed[i]) < ends[i]:
                fed[i].append(int(nxt[lanes[i]]))
        keep = [i for i in live if pos in read[i]]
        if keep:
            got = [np.asarray(a) for a in [lg] + more]
            for i in keep:
                seen[i][pos] = [a[lanes[i]] for a in got]
    pools = kv.latent_pools(cache.carry())
    out = []
    for i, end in enumerate(ends):
        table = np.maximum(rows[i], 0)[None]
        held = []
        for pool in (pools[0], pools[-1]):
            got = np.asarray(gather_blocks(pool, table)[0]).astype(
                np.float32)[:end]
            # the pool's rows are ``latent_row`` wide: the values, then zeros
            assert not got[:, kv.latent_width:].any()
            held.append(got[:, :kv.latent_width])
        out.append((fed[i], seen[i], held))
    return out


def reference_of(reference, config, params, runs, totals, n_decode, short,
                 upto=None, **broken):
    """What the reference makes of each served sequence (its first ``upto``
    positions, if given), on the host: position -> (logits, streams behind
    the first and the last layer, layer 0's first H_res) at the positions
    the check reads, the first and the last layer's rows, and the least
    margin of each position's choices of experts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    last = config["num_hidden_layers"] - 1
    fwd = reference.by_layer(config, **broken)
    out = []
    with jax.default_matmul_precision("highest"):
        for (fed, _seen, _held), total in zip(runs, totals):
            toks = fed[:upto] if upto else fed
            logits, kept = fwd(params, jnp.asarray(toks, jnp.int32), True,
                               streams_of=(0, last))
            at = [p for p in windows(total, n_decode, short)
                  if p < len(toks)]
            idx = jnp.asarray(at)
            picked = [np.asarray(a[idx]) for a in (
                logits, kept["streams"][0], kept["streams"][last],
                kept["maps"][0][0][2])]
            margin = np.min([np.asarray(m) for m in kept["margins"]], axis=0)
            out.append(({p: [a[j] for a in picked]
                         for j, p in enumerate(at)},
                        [np.asarray(kept["rows"][0]),
                         np.asarray(kept["rows"][-1])], margin[at]))
            del logits, kept
    return out


def compare(runs, refs, where):
    """The served readings against the reference's at the positions
    ``where(total positions read)`` picks (a sorted list -> the ones
    judged)."""
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               std=0.0, res=0.0, rows_off=0.0, cols_off=0.0, exact=0,
               values=0, per_seq=[], margins=[])
    sums = {k: [0.0, 0.0] for k in ("first_rows", "last_rows",
                                    "first_streams", "last_streams")}

    def add(key, a, b):
        sums[key][0] += float(np.square(a - b).sum())
        sums[key][1] += float(np.square(b).sum())

    for (fed, seen, held), (want, ref_rows, margin) in zip(runs, refs):
        at = where(sorted(p for p in seen if p in want))
        lg = np.stack([seen[p][0] for p in at])
        ref_lg = np.stack([want[p][0] for p in at])
        acc["std"] = float(np.std(ref_lg))
        acc["positions"] += len(at)
        acc["worst"] = max(acc["worst"], float(np.abs(lg - ref_lg).max()))
        acc["sq"] += float(np.square(lg - ref_lg).sum())
        acc["n"] += lg.size
        chosen = lg.argmax(-1)
        differs = chosen != ref_lg.argmax(-1)
        deficit = ref_lg.max(-1) - ref_lg[np.arange(len(at)), chosen]
        acc["differs"] += int(differs.sum())
        acc["deficit"] = max(acc["deficit"], float(deficit.max()))
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        for p in at:
            add("first_streams", seen[p][1], want[p][1])
            add("last_streams", seen[p][2], want[p][2])
            bits = np.ascontiguousarray(seen[p][1], np.float32).view(
                np.uint32)
            acc["exact"] += int(np.count_nonzero(bits & 0xFFFF == 0))
            acc["values"] += bits.size
            acc["res"] = max(acc["res"],
                             float(np.abs(seen[p][3] - want[p][3]).max()))
            acc["rows_off"] = max(acc["rows_off"], float(seen[p][4]))
            acc["cols_off"] = max(acc["cols_off"], float(seen[p][5]))
        n = min(len(held[0]), len(ref_rows[0]))
        add("first_rows", held[0][:n], ref_rows[0][:n])
        add("last_rows", held[1][:n], ref_rows[1][:n])
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    share = lambda key: (sums[key][0] / sums[key][1]) ** 0.5
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "first_rows_relative_rms_error": share("first_rows"),
            "last_rows_relative_rms_error": share("last_rows"),
            "first_streams_relative_rms_error": share("first_streams"),
            "last_streams_relative_rms_error": share("last_streams"),
            "first_streams_bfloat16_exact_share":
                acc["exact"] / acc["values"],
            "first_h_res_largest_difference": acc["res"],
            "h_res_row_sum_largest_distance_from_1": acc["rows_off"],
            "h_res_column_sum_largest_distance_from_1": acc["cols_off"],
            "largest_deficit": acc["deficit"],
            "argmax_differs_share": acc["differs"] / acc["positions"],
            "per_sequence_differs_share_min_median_max":
                spread([d for d, _x in acc["per_seq"]]),
            "per_sequence_largest_deficit_min_median_max":
                spread([x for _d, x in acc["per_seq"]]),
            "selection_margin_quantiles_01_10_50":
                [round(float(np.quantile(np.concatenate(acc["margins"]), q)),
                       6) for q in (0.01, 0.1, 0.5)],
            "positions": acc["positions"], "logit_std": acc["std"]}


def inside(got, served=None):
    """Is a run inside every limit?  ``served`` is the served run's reading
    on the same tokens and weights (the paired limit), where there is
    one."""
    return bool(
        got["largest_logit_error"] <= LOGIT_TOLERANCE
        and got["rms_logit_error"] <= RMS_TOLERANCE
        and (served is None or got["rms_logit_error"]
             <= PAIRED_RMS_TOLERANCE * served["rms_logit_error"])
        and got["first_rows_relative_rms_error"] <= FIRST_ROWS_TOLERANCE
        and got["first_streams_relative_rms_error"]
        <= FIRST_STREAMS_TOLERANCE
        and got["last_streams_relative_rms_error"] <= LAST_STREAMS_TOLERANCE
        and got["first_streams_bfloat16_exact_share"]
        <= STREAMS_EXACT_TOLERANCE
        and got["first_h_res_largest_difference"] <= FIRST_RES_TOLERANCE
        and got["h_res_row_sum_largest_distance_from_1"]
        <= ROW_SUM_TOLERANCE
        and got["h_res_column_sum_largest_distance_from_1"]
        <= COLUMN_SUM_TOLERANCE)


def clamp_leg(cfg, config, reference, params):
    """Layer 0's first mixing with one ``b_res`` entry at 100 and one at
    -100, on the chip by ``hyper_connections.maps``: finite, the
    reference's; the reference with the clamp left out is not finite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import hyper_connections as hc

    n = cfg.hc_mult
    phi, b, a = (params["l0_hc_attn_%s" % x] for x in ("phi", "b", "a"))
    b = b.at[2 * n + 1].set(100.0).at[2 * n + n * n - 2].set(-100.0)
    X = jax.random.normal(jax.random.PRNGKey(3), (LANES, n, cfg.hidden),
                          jnp.float32)
    got = [np.asarray(m) for m in jax.jit(
        lambda *args: hc.maps(cfg, *args))(phi, b, a, X)]
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(m) for m in reference.hc_maps(
            config, phi, b, a, X)]
        loose = np.asarray(reference.hc_maps(config, phi, b, a, X,
                                             clamp=False)[2])
    off = max(float(np.abs(g - w).max()) for g, w in zip(got, want))
    return {"finite": bool(all(np.isfinite(g).all() for g in got)),
            "largest_difference_from_the_reference": off,
            "reference_without_the_clamp_finite":
                bool(np.isfinite(loose).all()),
            "caught": bool(all(np.isfinite(g).all() for g in got)
                           and off <= FIRST_RES_TOLERANCE
                           and not np.isfinite(loose).all())}


def one_seed(seed, config, model, reference, device, tiny, controls,
             kv_blocks):
    import jax
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    rng = np.random.default_rng(seed)
    n_pos = config["n_positions"]
    n_decode = N_DECODE if not tiny else 4
    short = SHORT if not tiny else 12
    spread = TOTALS if not tiny else (12, 20, 31)
    totals = [int(t) for t in rng.permutation(
        np.resize(np.asarray(spread), LANES))]
    lo, hi = (32, 256) if not tiny else (2, 8)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, min(int(np.exp(
        rng.uniform(np.log(lo), np.log(hi)))), total - n_decode))]
        for total in totals]
    assert max(totals) <= n_pos
    kv = dm.cache_config(cfg, BLOCK, kv_blocks)
    steps = {}

    def served(params, forced=None, fault=None, upto=None):
        # the patch has to stand while the step is made and traced
        undo = patched(fault)
        try:
            if fault not in steps:
                steps[fault] = jax.jit(dm.make_paged_step(cfg, kv),
                                       donate_argnums=(0,))
            return run_batch(steps[fault], kvc.PagedKVCache(kv), params, cfg,
                             prompts, totals, n_decode, short, forced, upto)
        finally:
            undo()
            if fault is not None:
                steps.pop(fault).clear_cache()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": kv_blocks,
              "layers": cfg.layers, "mixings": cfg.mixings,
              "sequence_lens": totals,
              "prompt_lens": [len(p) for p in prompts],
              "paths": {"latent_attention": dm.attention_path(
                  cfg, kv, LANES, "latent"),
                  "experts": dm.experts_path(cfg, params, LANES)},
              "limits": {"rms": RMS_TOLERANCE, "logit": LOGIT_TOLERANCE,
                         "paired_rms": PAIRED_RMS_TOLERANCE,
                         "first_rows": FIRST_ROWS_TOLERANCE,
                         "first_streams": FIRST_STREAMS_TOLERANCE,
                         "last_streams": LAST_STREAMS_TOLERANCE,
                         "first_h_res": FIRST_RES_TOLERANCE,
                         "streams_exact": STREAMS_EXACT_TOLERANCE,
                         "row_sum": ROW_SUM_TOLERANCE,
                         "column_sum": COLUMN_SUM_TOLERANCE}}
    note = lambda name: print("chip_check_xing: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    ends = lambda at: [p for p in at if p >= short]     # the last positions
    early = lambda at: [p for p in at if p < short]     # ... of the first
    run = served(params)
    refs = reference_of(reference, config, params, run, totals, n_decode,
                        short)
    # a sequence of exactly ``short`` positions is read once, as early
    last = lambda at: ends(at) or early(at)
    result["served_bf16"] = compare(run, refs, last)
    note("served_bf16")
    verdicts = {"served_bf16": inside(result["served_bf16"])}
    forced = [fed for fed, *_rest in run]
    del run
    again = served(params, forced, upto=short)
    result["served_bf16_first_%d" % short] = base = compare(again, refs,
                                                            early)
    verdicts["served_bf16_first_%d" % short] = inside(base)
    note("served_bf16_first_%d" % short)
    for name in [c for c in BROKEN_REFERENCES if c in controls]:
        wrong = reference_of(reference, config, params, again, totals,
                             n_decode, short, upto=short,
                             **BROKEN_REFERENCES[name])
        result["control_" + name] = compare(again, wrong, early)
        verdicts["control_" + name] = inside(result["control_" + name], base)
        note("control_" + name)
        del wrong
    del again
    given = params
    for name in [c for c in FAULTY_STEPS + ("fp8_weights",)
                 if c in controls]:
        fault = name
        if name == "fp8_weights":
            # the last: the served set is gone
            given, fault = weights_to_fp8(params), None
        got = served(given, forced, fault, upto=short)
        result["control_" + name] = compare(got, refs, early)
        verdicts["control_" + name] = inside(result["control_" + name], base)
        note("control_" + name)
        del got
    if "clamp" in controls:
        result["control_clamp"] = clamp_leg(cfg, config, reference, given)
        # a control "inside" is one not caught
        verdicts["control_clamp"] = not result["control_clamp"]["caught"]
        note("control_clamp")
    result["seconds"] = round(time.time() - t0, 1)
    result["inside_tolerance"] = verdicts
    result["ok"] = all(ok != name.startswith("control_")
                       for name, ok in verdicts.items())
    if device.platform == "tpu":
        result["ok"] = result["ok"] and set(result["paths"].values()) \
            == {"pallas"}
    if tiny:
        result["not_a_chip_result"] = True
    return result


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", type=int, default=1,
                    help="this many seeds, from --seed on, in one process")
    ap.add_argument("--controls", default=",".join(CONTROLS),
                    help="which of the controls to run, comma separated "
                    "(every one by default; '' for none)")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: the configuration's tiny sizes on any "
                    "backend; nothing it prints is a chip result")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny_on_cpu:
        print("chip_check_xing: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "xing4.0-29b-a4b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_hc_latent_moe_decode_heavy.json"), args.tiny_on_cpu)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(args.seeds):
        result = one_seed(args.seed + 7919 * i, config, model, reference,
                          device, args.tiny_on_cpu, controls,
                          int(traffic["kv_blocks"]))
        with open(os.path.join(out_dir, "chip_check_xing.jsonl"), "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
