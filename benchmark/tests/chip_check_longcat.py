"""On the chip, outside any timed window: the served LongCat-Flash step's
*logits* and cached latent rows against the plain reference, at the
configuration's widths, the held share and the cell's sizes.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_longcat.py

Seeded weights as the cell makes them (the bias balanced); 64 sequences at
once, a lane each of a 64-lane ``make_paged_step`` over the cell's pool
(17,472 latent blocks by a shuffled table): fifty-six prompts of 230-576
tokens, four of 2,100-2,300 and four that END at the configuration's last
position, 4,352, fed a token a step (prefill here is token-feed), then 64
decoded tokens each, teacher-forced with the step's own argmax.  The step's
logits at the last 64 positions of each sequence are compared with
``longcat_flash_ref.forward`` of the whole sequence (float32, highest matmul
precision, the served bf16 weights upcast a piece at a time, no cache,
latent attention expanded with the two scales where the source puts them,
the queries in blocks), and what the first and the last sublayer's pools
hold of each sequence with the reference's ``[a_kv c | rotated k_pe]`` rows.

Controls run on the served run's tokens, every sequence cut to its first 640
positions (a control is a fault in structure or precision, which shows at any
depth; the served path alone has to be shown at the cell's depths), each a
server with one fault judged by the same reference on the weights as served,
and each has to fall outside a limit: ``a_q`` left out; ``a_kv`` left out;
the identity part left out; the routed part read at ``h3`` (where it is
added) and not at ``h1``; the gates renormalised; every projection's sum kept
in bfloat16 between pieces of 256 terms (``chip_check_dots._mm_in_bf16``);
the weights rounded to fp8 (e4m3) on their way into the step (the precision
next below the one the configuration states: what
``longcat_flash_ref.check``'s limits are set against).  One more run has to
stay *inside* every limit: the step with its two kernels replaced by their
jnp paths (``jnp_paths``).  Exit code 1 if the served path or ``jnp_paths``
is outside a tolerance on any seed, or a control inside all of them.

``--engine`` goes the cell's own way: ``ServingClient`` -> ``ServingServer``
-> ``DecodeEngine`` with the cell's bucket and pool, 80 requests for 64 lanes
all sent at once (sixteen wait for a lane), 250-700 positions each and four
of 4,200; the comparison is ``longcat_flash_ref.check``'s statistics,
teacher-forced through the tokens, by the depth a token was served at.  What
goes any model's way there (``to_fp8``, ``engine_requests``, ``by_depth``) is
``chip_check_nemotron.py``'s, ``engine_run`` and ``_mm_in_bf16``
``chip_check_dots.py``'s.
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (my chip runs, PR 61, calls 1 and 3: seeds
# 2147483777 and 2147491696, first | second below where they differ; 64
# sequences x 64 positions x 16,384 logits of standard deviation 1.57 each,
# 512 of the positions past 2,048 and 256 of them at 4,288-4,352).  Weights
# are the same bits on both sides.  What is left is the served path's bfloat16
# (the input of every matmul and the cached rows rounded to 8 bits of
# mantissa, through 8 sublayers) and what that noise does to the routing: 4
# routers a token choose 12 of 768 outputs by probabilities whose closest
# choice is won by 1.0e-4 in the median (1.5e-5 at a tenth of positions), so
# the two sides swap an output in some layer now and then; an output here
# weighs 6 x 0.011 and not a renormalised eighth, so a swap moves the logits
# less than in the sigmoid families (the largest error is 0.38, dots.vlm1's
# 1.40).  The readings (the controls on every sequence's first 640 positions,
# where the served path reads the same as on the whole: its own line below):
#   the first sublayer's rows, root-mean-square error as a share of their own
#     root-mean-square: served 0.00235 | 0.00235 (the jnp paths the same); a
#     bfloat16 running sum 0.0063 | 0.0063, fp8 weights 0.047, a_kv left out
#     0.70.  The limit is 1.7 times the served reading and 0.63 of the
#     bfloat16 sum's.
#   the last sublayer's rows (behind 3 routers): served 0.0302 | 0.0303 whole,
#     0.0308 | 0.0308 cut (jnp 0.0313 | 0.0313); a bfloat16 sum 0.082 | 0.082,
#     read at h3 0.247, no identity part 0.250, renormalised 0.46, fp8 0.52,
#     a_kv 1.00, a_q 1.09.  The limit is 1.6 times the served reading and 0.61
#     of the smallest control's.
#   root-mean-square logit error: served 0.0520 | 0.0518 whole (0.0472 | 0.0471
#     past 2,048), 0.0526 | 0.0525 cut (jnp 0.0534 | 0.0532); a bfloat16 sum
#     0.139 | 0.139, read at h3 0.421, no identity part 0.433, renormalised
#     0.778, fp8 0.868, a_q 1.82, a_kv 2.06.  The limit is 1.7 times the served
#     reading and 0.65 of the smallest control's.
#   largest logit error: served 0.375 | 0.386, jnp 0.390 | 0.336; a bfloat16
#     sum 0.894 | 0.848, which the limit leaves to the others; the faults in
#     structure 4.7-13.1, fp8 5.09 | 5.59.  The limit is 3.8 times the largest
#     reading inside and 0.32 of the smallest fault's in structure: a maximum
#     over 6.7e7 logits reads higher on a fresh seed.
#   paired: a run's rms logit error over the served path's on the same tokens,
#     positions and weights: the jnp paths 1.015 | 1.013, every control 2.6
#     (the bfloat16 sum) to 39.
# Each control falls outside one limit on every seed, not outside each.
RMS_TOLERANCE = 0.09
LOGIT_TOLERANCE = 1.5
FIRST_ROWS_TOLERANCE = 0.004
LAST_ROWS_TOLERANCE = 0.05
PAIRED_RMS_TOLERANCE = 1.25
N_DECODE = 64
LANES = 64
BLOCK = 16
MID, LONG = 4, 4            # sequences of 2.1-2.3k positions, and to the end
CUT = 640                   # positions of a sequence a control runs
CONTROLS = ("no_q_scale", "no_kv_scale", "no_identity_part",
            "routed_read_at_h3", "gates_renormalised", "bf16_accumulation",
            "fp8_weights")
# the controls (and the run that must stay inside) whose change is a patch
# of the block or the step: it has to stand while the step is made and traced
PATCHED = ("no_identity_part", "routed_read_at_h3", "gates_renormalised",
           "bf16_accumulation", "jnp_paths")
# ... and those that are another configuration of the same block
CONFIGURED = {
    "no_q_scale": lambda cfg: cfg.replace(latent_q_scale=1.0),
    "no_kv_scale": lambda cfg: cfg.replace(latent_kv_scale=1.0)}


def _sibling(name):
    from benchmark.run import load_module

    return load_module("tests", name)


def _read_at_h3(lc):
    """``longcat_flash.token_logits`` with a pair's routed part read where
    it is added, behind the second sublayer's mixer."""
    import jax
    import jax.numpy as jnp

    def token_logits(params, cfg, tok, pos, attend, live, recur=None,
                     seen=None):
        eps = cfg.norm_eps
        x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
        rotate = lc._dots._rotation(cfg, pos)
        counted = lambda mask: jnp.sum(mask & live[:, None], axis=0,
                                       dtype=jnp.int32)
        routed, real = [], []
        for l in range(cfg.layers):
            p = lambda n, _l=l: params["l%d_%s" % (_l, n)]
            first = lambda n, _l=l - 1: params["l%d_%s" % (_l, n)]
            h = lc._rmsnorm(x, p("ln1_g"), eps)
            x = x + lc._kimi.latent_mixer(cfg, p, l, h, attend, rotate)
            h2 = lc._rmsnorm(x, p("ln2_g"), eps)
            x = x + lc._exaone._gated_mlp(h2, p("w1"), p("w3"), p("w2"))
            if l % 2:
                y, z, chosen = lc.routed_part(cfg, first, h2, live)
                x = x + y + z
                routed.append(counted(chosen))
                real.append(counted(jax.nn.one_hot(
                    jnp.sum(chosen[:, :cfg.experts], axis=1),
                    cfg.experts_per_token + 1, dtype=bool)))
        logits = lc._exaone._head(x, params, eps)
        return logits, (jnp.stack(routed), jnp.stack(real))

    return token_logits


def patched(name):
    """The block or the step with one fault (modules patched): -> undo()."""
    import jax.numpy as jnp

    from paddle_tpu.models import exaone_moe as ex
    from paddle_tpu.models import kimi_linear as kl
    from paddle_tpu.models import longcat_flash as lc
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving import decode_model as dm

    saved = [(lc, "routed_part"), (lc, "token_logits"), (lc, "_route"),
             (kl, "_mm"), (ex, "_mm"), (moe, "routed_experts"),
             (dm, "latent_attention")]
    saved = [(mod, key, getattr(mod, key)) for mod, key in saved]
    if name == "no_identity_part":
        whole = lc.routed_part

        def routed_part(cfg, p, x, live):
            y, z, chosen = whole(cfg, p, x, live)
            return y, jnp.zeros_like(z), chosen

        lc.routed_part = routed_part
    elif name == "routed_read_at_h3":
        lc.token_logits = _read_at_h3(lc)
    elif name == "gates_renormalised":
        plain = lc._route

        def _route(x, router, bias, k, scaling):
            gates, chosen = plain(x, router, bias, k, scaling)
            return gates / jnp.sum(gates, axis=-1, keepdims=True) * scaling, \
                chosen

        lc._route = _route
    elif name == "bf16_accumulation":
        kl._mm = ex._mm = _sibling("chip_check_dots")._mm_in_bf16
    elif name == "jnp_paths":
        moe.routed_experts = lambda h2, gates, live, *w: \
            moe.experts_reference(h2, gates, *w)
        dm.latent_attention = pa.latent_attention_reference

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None,
              cut=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls), ``cut`` the positions of a sequence
    to run at most; without ``forced`` a sequence feeds its prompt and then
    the step's own argmax.  -> per sequence (tokens fed, logits of the last
    n_decode positions run, the first and the last sublayer's cached rows of
    the sequence)."""
    import numpy as np

    from paddle_tpu.pallas_kernels.paged_attention import gather_blocks

    kv = cache.config
    n = len(prompts)
    totals = [len(p) + n_decode for p in prompts]
    if cut:
        totals = [min(t, cut) for t in totals]
    maxb = cfg.max_seq // BLOCK
    rng = np.random.default_rng(sum(totals))
    lanes = rng.permutation(LANES)[:n]
    free = iter(rng.permutation(np.arange(1, kv.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, total in enumerate(totals):
        for j in range(-(-total // BLOCK)):
            rows[i, j] = next(free)
    fed = [list(forced[i][:totals[i]] if forced else prompts[i])
           for i in range(n)]
    logits = [[] for _ in range(n)]
    for pos in range(max(totals)):
        tok, at, lens = (np.zeros(LANES, np.int32) for _ in range(3))
        tables = np.full((LANES, maxb), -1, np.int32)
        live = [i for i in range(n) if pos < totals[i]]
        for i in live:
            b = lanes[i]
            tok[b], at[b], lens[b] = fed[i][pos], pos, pos + 1
            tables[b] = rows[i]
        carry, nxt, lg = step(cache.carry(), params, tok, at, tables,
                              lens)[:3]
        cache.replace_carry(carry)
        keep = [i for i in live if pos >= totals[i] - n_decode]
        grow = [i for i in live
                if pos + 1 == len(fed[i]) and len(fed[i]) < totals[i]]
        if grow:
            nxt = np.asarray(nxt)
            for i in grow:
                fed[i].append(int(nxt[lanes[i]]))
        if keep:
            lg = np.asarray(lg)
            for i in keep:
                logits[i].append(lg[lanes[i]])
    pools = kv.latent_pools(cache.carry())
    out = []
    for i, total in enumerate(totals):
        table = np.maximum(rows[i], 0)[None]
        held = []
        for pool in (pools[0], pools[-1]):
            got = np.asarray(gather_blocks(pool, table)[0]).astype(
                np.float32)[:total]
            # the pool's rows are ``latent_row`` wide: the values, then zeros
            assert not got[:, kv.latent_width:].any()
            held.append(got[:, :kv.latent_width])
        out.append((fed[i], np.stack(logits[i]), held))
    return out


def reference_of(reference, config, params, runs, n_decode, cut):
    """What the reference makes of each served sequence, on the host: per
    sequence {positions run: (logits of its last n_decode positions, the
    first and the last sublayer's rows, the least margin of each of those
    positions' choice of outputs)} for the whole sequence and for its first
    ``cut`` positions (the controls')."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    out = []
    with jax.default_matmul_precision("highest"):
        for fed, *_rest in runs:
            n = len(fed)
            ends = sorted({n, min(n, cut)})
            at = np.concatenate([np.arange(e - n_decode, e) for e in ends])
            # one compile a distinct length (the lengths are drawn from few)
            logits, kept = fwd(params, jnp.asarray(fed, jnp.int32), True,
                               rows=at)
            logits = np.asarray(logits)
            margin = np.min([np.asarray(m) for m in kept["margins"]], axis=0)
            rows = [np.asarray(kept["rows"][0]), np.asarray(kept["rows"][-1])]
            out.append({e: (logits[j * n_decode:(j + 1) * n_decode],
                            [r[:e] for r in rows],
                            margin[e - n_decode:e])
                        for j, e in enumerate(ends)})
            del logits, kept
    return out


def compare(runs, refs):
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               long_sq=0.0, long_n=0, long_positions=0, first_sq=0.0,
               first_ref=0.0, last_sq=0.0, last_ref=0.0, std=0.0,
               per_seq=[], margins=[])
    for (fed, lg, held), ref in zip(runs, refs):
        want, ref_rows, margin = ref[len(fed)]
        acc["std"] = float(np.std(want))
        acc["positions"] += len(lg)
        acc["worst"] = max(acc["worst"], float(np.abs(lg - want).max()))
        acc["sq"] += float(np.square(lg - want).sum())
        acc["n"] += lg.size
        if len(fed) > 2048:
            acc["long_sq"] += float(np.square(lg - want).sum())
            acc["long_n"] += lg.size
            acc["long_positions"] += len(lg)
        chosen = lg.argmax(-1)
        differs = chosen != want.argmax(-1)
        deficit = want.max(-1) - want[np.arange(len(lg)), chosen]
        acc["differs"] += int(differs.sum())
        acc["deficit"] = max(acc["deficit"], float(deficit.max()))
        # what ``longcat_flash_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        for key, a, b in (("first", held[0], ref_rows[0]),
                          ("last", held[1], ref_rows[1])):
            acc[key + "_sq"] += float(np.square(a - b).sum())
            acc[key + "_ref"] += float(np.square(b).sum())
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    share = lambda key: (acc[key + "_sq"] / acc[key + "_ref"]) ** 0.5
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "rms_logit_error_past_2048":
                (acc["long_sq"] / acc["long_n"]) ** 0.5
                if acc["long_n"] else None,
            "first_rows_relative_rms_error": share("first"),
            "last_rows_relative_rms_error": share("last"),
            "largest_deficit": acc["deficit"],
            "argmax_differs_share": acc["differs"] / acc["positions"],
            "per_sequence_differs_share_min_median_max":
                spread([d for d, _x in acc["per_seq"]]),
            "per_sequence_largest_deficit_min_median_max":
                spread([x for _d, x in acc["per_seq"]]),
            "selection_margin_quantiles_01_10_50":
                [float("%.3g" % np.quantile(
                    np.concatenate(acc["margins"]), q))
                 for q in (0.01, 0.1, 0.5)],
            "positions": acc["positions"],
            "positions_past_2048": acc["long_positions"],
            "logit_std": acc["std"]}


def inside(got, served):
    """Is a run inside every limit?  ``served`` is the served path's reading
    on the same tokens, positions and weights (the paired limit; the served
    path itself reads 1 of it)."""
    return bool(got["largest_logit_error"] <= LOGIT_TOLERANCE
                and got["rms_logit_error"]
                <= PAIRED_RMS_TOLERANCE * served["rms_logit_error"]
                and got["rms_logit_error"] <= RMS_TOLERANCE
                and got["first_rows_relative_rms_error"]
                <= FIRST_ROWS_TOLERANCE
                and got["last_rows_relative_rms_error"]
                <= LAST_ROWS_TOLERANCE)


def one_seed(seed, config, model, reference, device, tiny, controls, blocks):
    import jax
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    rng = np.random.default_rng(seed)
    n_pos = config["n_positions"]
    n_decode = min(N_DECODE, n_pos // 4)
    cut = CUT if not tiny else n_pos // 2
    hi = min(640, n_pos * 3 // 4) - n_decode
    # few distinct lengths: the reference compiles once a length
    lens = list(rng.choice(np.linspace(max(hi * 2 // 5, 1), hi, 4).astype(
        int), LANES - MID - LONG))
    lens += [2100, 2236] * (MID // 2) if not tiny else [hi] * MID
    lens += [n_pos - n_decode] * LONG
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    if tiny:
        blocks = LANES * (n_pos // BLOCK) + 8
    kv = dm.cache_config(cfg, BLOCK, blocks)
    steps = {}

    def served(params, forced=None, fault=None, cut=None):
        built = CONFIGURED[fault](cfg) if fault in CONFIGURED else cfg
        # the patch has to stand while the step is made and traced
        key = fault if fault in PATCHED or fault in CONFIGURED else None
        undo = patched(key) if key in PATCHED else None
        try:
            if key not in steps:
                steps[key] = jax.jit(dm.make_paged_step(built, kv),
                                     donate_argnums=(0,))
            return run_batch(steps[key], kvc.PagedKVCache(kv), params, built,
                             prompts, n_decode, forced, cut)
        finally:
            if undo:
                undo()
            if key is not None:
                steps.pop(key).clear_cache()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "sublayers": cfg.layers, "controls_cut_to": cut,
              "sequence_lens": [int(n) + n_decode for n in lens],
              "paths": {"latent_attention": dm.attention_path(
                  cfg, kv, LANES, "latent"),
                  "experts": dm.experts_path(cfg, params, LANES)},
              "chunk_positions": dm.chunk_positions(cfg, kv, LANES),
              "experts_f_chunk": dm.experts_chunk(cfg),
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "first_rows_tolerance": FIRST_ROWS_TOLERANCE,
              "last_rows_tolerance": LAST_ROWS_TOLERANCE,
              "paired_rms_tolerance": PAIRED_RMS_TOLERANCE}
    run = served(params)
    refs = reference_of(reference, config, params, run, n_decode, cut)
    result["served_bf16"] = compare(run, refs)
    # as it goes: a later control that fails leaves these readings behind
    note = lambda name: print("chip_check_longcat: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    note("served_bf16")
    forced = [fed for fed, *_rest in run]
    del run
    # the served path on the controls' positions: what they are paired with
    short = served(params, forced, cut=cut)
    result["served_bf16_cut"] = compare(short, refs)
    note("served_bf16_cut")
    kernel_logits = [lg for _fed, lg, *_rest in short]
    del short
    verdicts = {"served_bf16": inside(result["served_bf16"],
                                      result["served_bf16"]),
                "served_bf16_cut": inside(result["served_bf16_cut"],
                                          result["served_bf16_cut"])}
    within = functools.partial(inside, served=result["served_bf16_cut"])
    if "jnp_paths" in controls:
        got = served(params, forced, "jnp_paths", cut)
        result["jnp_paths"] = dict(
            compare(got, refs),
            largest_difference_from_the_kernels=max(
                float(np.abs(a - lg).max())
                for a, (_f, lg, *_r) in zip(kernel_logits, got)))
        verdicts["jnp_paths"] = within(result["jnp_paths"])
        note("jnp_paths")
        del got
    for name in [c for c in CONTROLS if c in controls]:
        given = params
        if name == "fp8_weights":
            # the last: the served set is gone
            given = _sibling("chip_check_nemotron").to_fp8(params)
        got = served(given, forced, name, cut)
        result["control_" + name] = compare(got, refs)
        verdicts["control_" + name] = within(result["control_" + name])
        note("control_" + name)
        del got, given
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


def engine_leg(seed, config, model, reference, device, tiny, traffic):
    """80 requests for 64 lanes (and four that pass 4,096 positions) through
    client, server and engine: every band of depth with enough tokens inside
    ``longcat_flash_ref.check``'s two limits, for the requests that ran from
    the start and for those that waited for a lane; the step's two kernels
    counted as used and neither as fallen back."""
    import numpy as np

    base = _sibling("chip_check_nemotron")
    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests = base.engine_requests(seed, config, lanes, tiny)
    if not tiny:
        rng = np.random.default_rng([seed, 1 << 23])
        for i in range(LONG):
            requests[i] = ([int(t) for t in rng.integers(
                0, config["vocab_size"], 200)], 4000)
    edges = (0, 64, 256, 2048, 4096) if not tiny else (0, 8)
    judged_from = base.MIN_JUDGED if not tiny else 8
    t0 = time.time()
    cases, said = _sibling("chip_check_dots").engine_run(
        cfg, params, traffic, requests, int(traffic["kv_blocks"]),
        model="longcat_check")
    result = {"leg": "engine", "device": device.device_kind,
              "platform": device.platform, "seed": seed, "lanes": lanes,
              "requests": len(requests),
              "sequence_lens": [len(p) + n for p, n in requests],
              "differing_share_bound": reference.DIFFERING_SHARE_BOUND,
              "deficit_bound": reference.DEFICIT_BOUND}
    ok = said["declines"] is None and said["blocks"]["in_use"] == 0 \
        and all(len(served) == n for (_p, served), (_q, n)
                in zip(cases, requests))
    for name, which in (("by_depth_from_the_start", range(min(8, lanes))),
                        ("by_depth_after_a_wait",
                         range(lanes, len(requests)))):
        rows = base.by_depth(reference, config, params,
                             [cases[i] for i in which], edges)
        said[name] = rows
        judged = [share <= reference.DIFFERING_SHARE_BOUND
                  and worst <= reference.DEFICIT_BOUND
                  for _lo, _hi, n, share, worst in rows if n >= judged_from]
        ok = ok and bool(judged) and all(judged)
    if device.platform == "tpu":
        used = {k for k, v in said["kernels"].items()
                if k.startswith("pallas_kernel_used_total") and v}
        ok = ok and said["paths"]["attention"] == "pallas" and all(
            path == "pallas" for _b, path in said["paths"]["experts"]) \
            and said["prewarm"] == ["compiled"] \
            and used == {"pallas_kernel_used_total{kernel=latent_attention}",
                         "pallas_kernel_used_total{kernel=moe_experts}"} \
            and not any(k.startswith("pallas_kernel_fallback_total")
                        for k in said["kernels"])
    result["served"] = said
    result["ok"] = bool(ok)
    result["seconds"] = round(time.time() - t0, 1)
    if tiny:
        result["not_a_chip_result"] = True
    return result


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", type=int, default=1,
                    help="this many seeds, from --seed on, in one process")
    ap.add_argument("--controls", default=",".join(("jnp_paths",) + CONTROLS),
                    help="which of jnp_paths and the controls to run, comma "
                    "separated (every one by default; '' for none)")
    ap.add_argument("--engine", action="store_true",
                    help="the leg through ServingClient and DecodeEngine, "
                    "and that alone")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: the configuration's tiny sizes on any "
                    "backend; nothing it prints is a chip result")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny_on_cpu:
        print("chip_check_longcat: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "longcat-flash-chat-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_zero_expert_latent_decode_wide.json"), args.tiny_on_cpu)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(args.seeds):
        if args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu, controls,
                              int(traffic["kv_blocks"]))
        with open(os.path.join(out_dir, "chip_check_longcat.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
