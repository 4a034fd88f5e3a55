"""On the chip, outside any timed window: the served GLM-5 step's index
scores, chosen sets and *logits* against the plain reference, at the
configuration's widths and the held share, with the selection in play.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_glm.py

Seeded weights as the cell makes them; 32 sequences at once, a lane each of a
32-lane ``make_packed_step`` over the cache manager's pools (blocks by a
shuffled table), of three bands: twenty under 2,048 positions (180-324),
eight between 2,048 and 4,096, four past 8,192.  Every token is given (seeded
ids, a position a step: prefill here is token-feed) and the lanes start so
that all END together; the cache as it stands ``TAIL`` (8) steps before the
end is kept, and every run below serves those last 8 steps from a copy of it.
The served step also hands out, a layer, the indexer's scores of every
position and the positions it chose.  The reference
(``glm_dsa_ref.by_layer``: float32, highest matmul precision, the served bf16
weights upcast a piece at a time, no cache, attention expanded, the exact
choice by a stable sort) makes the whole sequence's forward pass twice for a
lane past 2,048: over its own chosen sets, and with the served sets imposed
on its last 8 queries in every layer.  Compared, for the last 8 positions of
every lane:

* the first and the last layer's **index scores** (root-mean-square error as
  a share of the reference's root-mean-square over a lane's context);
* the chosen sets' **overlap** with the reference's own (the share of the
  reference's 2,048 that the served set holds), a layer and a position;
* the **logits given the served set** (tight: they hold the attention over
  gathered rows, the absorb at 192 / 256, the rotation, the experts) and
  **given the reference's own set** (looser: the two sets differ at their
  boundary, where bfloat16 keys and queries re-order near-tied scores; a
  boundary row carries about 1/2048 of a head's weight, so sets 1% apart move
  the output by about 1%, and a later layer's scores read that);
* the share of logits that bfloat16 holds exactly (SmallThinker's check: a
  float32 accumulation keeps mantissa below bfloat16's 8 bits).

Controls run the same 8 steps from the same cache, each a server with one
fault, and each has to fall outside a limit: no selection (the latent kernel
over every row); the most recent 2,048 instead of the chosen; the ReLU left
out of a head's index score; the heads' weights left out; the index query
unrotated against the cached keys; ``v`` cut to its first 192 values; a
prefix hit without its index rows (the index keys of the first half of every
lane's blocks zeros); the weights rounded to fp8 (e4m3: the precision next
below the one the configuration states, what ``glm_dsa_ref.check``'s limits
are set against).  One more run has to stay *inside* every limit: the step
with its kernels replaced by their jnp paths (``jnp_paths``: the whole table
gathered, the scores dense, what was not chosen masked: the masked form of
the selected read, which is thereby shown to give the served numbers).  Exit
code 1 if the served path or ``jnp_paths`` is outside a limit, or a control
inside all of them.

``--engine`` goes the cell's own way: ``ServingClient`` -> ``ServingServer``
-> ``DecodeEngine`` with the cell's bucket, 40 requests for 32 lanes all sent
at once (eight wait for a lane), 250-700 positions each and four of 2,800,
past ``index_topk``; the comparison is ``glm_dsa_ref.check``'s statistics,
teacher-forced through the tokens, by the depth a token was served at.
``engine_run`` is ``chip_check_dots.py``'s and ``to_fp8``,
``engine_requests`` and ``by_depth`` ``chip_check_nemotron.py``'s.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (my chip runs, PR 56, calls 2 and 4,
# seeds 2147483777 | 2147491696, first | second below where they differ: 32
# lanes x 8 positions x 19,360 logits of standard deviation 1.568, 12 lanes
# past 2,048; PERF.md section 6, PR 56).  Weights are the same
# bits on both sides; what is left is the served path's bfloat16, what that
# does to five routers a token, and here to the choice: index keys and
# queries rounded to bfloat16 re-order near-tied scores, so a served set and
# the reference's differ at their boundary.  Each control falls outside one
# limit, not outside each.
#   logits given the served set (the reference attends the positions the
#     served step chose), rms error over the logits' standard deviation, the
#     lanes past 2,048: served 0.0287 | 0.0241, which is what the lanes under
#     2,048 read against the plain reference (0.0289 | 0.0230): the gathered
#     rows, the kernel over them, the absorb at 192 / 256 and the rotation
#     hold.  The limit is 2.1 times the larger reading.
GIVEN_SET_RMS_TOLERANCE = 0.06
#   logits given the reference's own set, the same statistic over every lane:
#     served 0.0600 | 0.0670 (0.0905 | 0.1052 on the lanes past 2,048: the
#     sets differ by 0.15-0.5% in the first layer and by 3.6-3.9% in the
#     median, 17% at the most, in the last, whose scores read five layers'
#     streams), the jnp paths 0.0603 | 0.0672; fp8 weights 0.264 | 0.267, the
#     ReLU left out 0.415 | 0.428, no selection 0.517 | 0.531, the index
#     query unrotated 0.543 | 0.557, a hit without its index rows 0.551 |
#     0.577, the most recent 2,048 0.604 | 0.620, the heads' weights left out
#     0.611 | 0.626, ``v`` cut to 192 0.832 | 0.835.  The limit is 1.5 times
#     the larger served reading (fresh seeds read higher) and 0.38 of the
#     smallest control's.
OWN_SET_RMS_TOLERANCE = 0.10
#   the first layer's index scores (before any router; rms error over the
#     reference's rms, a lane's whole context): served 0.0048 | 0.0045,
#     bfloat16 keys and queries.  The limit is 4 times the reading; the last
#     layer's read 0.21 | 0.23 (the streams differ there) and have no limit.
SCORES_RMS_TOLERANCE = 0.02
#   the least overlap of a served set with the reference's own, a lane and a
#     position, first layer: 0.9951 | 0.9956 (the median 0.9985); the last
#     layer's 0.833 | 0.834 (0.964 | 0.961) has no limit.
OVERLAP_FLOOR = 0.97
#   the share of compared logits bfloat16 holds exactly: 3.2e-5 to 6e-5 on
#     every run (a float32 sum keeps mantissa below bfloat16's 8 bits).
BF16_EXACT_TOLERANCE = 0.01
LANES = 32
BLOCK = 16
CONTROLS = ("no_selection", "most_recent", "relu_left_out",
            "head_weights_left_out", "index_query_unrotated", "v_cut_to_192",
            "hit_without_index_rows", "fp8_weights")


def _load(name):
    from benchmark.run import load_module

    return load_module("tests", name)


def lengths(rng, n_pos, topk, tiny):
    """The lanes' sequence lengths, few distinct ones (the reference
    compiles once a length): twenty under ``topk``, eight between ``topk``
    and twice that, four past four times it."""
    import numpy as np

    if tiny:
        short, mid, long_ = [8], [12, 15], [40]
    else:
        short, mid, long_ = [180, 230, 280, 324], [2304, 3500], [8320]
    assert max(short) <= topk < min(mid) and max(mid) <= 2 * topk \
        and 4 * topk < long_[0] <= n_pos
    return [int(x) for x in np.concatenate([
        rng.choice(short, LANES - 12), rng.choice(mid, 8),
        rng.choice(long_, 4)])]


def recording(base, dm):
    """``base`` with what every selecting layer scored and chose handed out
    after its results: (scores, positions, count), a tuple a layer each.
    ``decode_model.choose`` is stood in for while the step is traced."""
    def step(*args):
        seen = []
        choose = dm.choose

        def noted(scores, lens, k):
            out = choose(scores, lens, k)
            seen.append((scores,) + tuple(out))
            return out

        dm.choose = noted
        try:
            out = base(*args)
        finally:
            dm.choose = choose
        return tuple(out) + tuple(zip(*seen))

    return step


def patched(name, cfg):
    """The block or the step with one fault (modules patched): -> undo()."""
    import jax.numpy as jnp

    from paddle_tpu.models import glm_dsa as gd
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving import decode_model as dm

    saved = [(dm, "selected_latent_attention"), (dm, "choose"),
             (dm, "index_scores"), (pa, "_index_act"), (gd, "_indexer"),
             (moe, "routed_experts")]
    saved = [(mod, key, getattr(mod, key)) for mod, key in saved]
    if name == "no_selection":
        dm.selected_latent_attention = (
            lambda q, pool, tables, lens, _positions, _count, scale, rank:
            dm.latent_attention(q, pool, tables, lens, scale, rank))
    elif name == "most_recent":
        def recent(scores, lens, k):
            k = min(int(k), scores.shape[1])
            lens = lens.astype(jnp.int32)
            return jnp.maximum(lens[:, None] - 1 - jnp.arange(
                k, dtype=jnp.int32)[None, :], 0), jnp.minimum(lens, k)

        dm.choose = recent
    elif name == "relu_left_out":
        pa._index_act = lambda x: x
    elif name == "head_weights_left_out":
        scores = dm.index_scores
        even = cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5
        dm.index_scores = lambda qi, w, *rest: scores(
            qi, jnp.full_like(w, even), *rest)
    elif name == "index_query_unrotated":
        indexer = gd._indexer
        gd._indexer = lambda cfg_, p, h, _rotate: indexer(
            cfg_, p, h, lambda x: x)
    elif name == "jnp_paths":
        dm.index_scores = lambda qi, w, pool, tables, lens: \
            pa.dense_index_scores(qi, w, pa.gather_blocks(pool, tables),
                                  lens)

        def masked(q, pool, tables, lens, positions, count, scale, rank):
            rows = pa.gather_blocks(pool, tables)
            return pa.masked_latent(q, rows, lens, scale, rank,
                                    pa.chosen_mask(positions, count,
                                                   rows.shape[1]))

        dm.selected_latent_attention = masked
        moe.routed_experts = lambda h2, gates, live, *w: \
            moe.experts_reference(h2, gates, *w)

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def one_seed(seed, config, model, reference, device, tiny, controls):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    tail = reference.TAIL
    rng = np.random.default_rng(seed)
    totals = lengths(rng, cfg.max_seq, cfg.index_topk, tiny)
    end = max(totals)
    tokens = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in totals]
    blocks = sum(-(-n // BLOCK) for n in totals) + 8
    kv = dm.cache_config(cfg, BLOCK, blocks)
    maxb = cfg.max_seq // BLOCK
    free = iter(rng.permutation(np.arange(1, blocks)))
    tables = np.full((LANES, maxb), -1, np.int32)
    for i, n in enumerate(totals):
        for j in range(-(-n // BLOCK)):
            tables[i, j] = next(free)
    columns, width = dm.lane_columns(kv, maxb)
    sparse = [i for i, n in enumerate(totals) if n > cfg.index_topk]

    def lanes_at(g):
        """The packed lanes of global step ``g``: lane i at position ``g -
        (end - totals[i])``, idle before its start."""
        packed = np.zeros((LANES, width), np.int32)
        packed[:, columns["src"]] = -1
        packed[:, columns["tables"]] = -1
        for i, n in enumerate(totals):
            pos = g - (end - n)
            if pos < 0:
                continue
            packed[i, columns["tok"].start] = tokens[i][pos]
            packed[i, columns["pos"].start] = pos
            packed[i, columns["lens"].start] = pos + 1
            packed[i, columns["tables"]] = tables[i]
        return packed

    feed0 = jnp.zeros((LANES,), jnp.int32)

    def make(weights, fault=None, record=False):
        undo = patched(fault, cfg) if fault else None
        try:
            base = dm.make_packed_step(cfg, kv, LANES)
            fn = jax.jit(recording(base, dm) if record else base,
                         donate_argnums=(0,))
            # traced and compiled while the patch stands
            carry = kvc.PagedKVCache(kv).carry()
            fn.lower(carry, weights, feed0, lanes_at(end - 1)).compile()
            return fn
        finally:
            if undo:
                undo()

    held = dm.laid_out(cfg, params)
    t0 = time.time()
    served_fn = make(held, record=True)
    cache = kvc.PagedKVCache(kv)
    carry = cache.carry()
    for g in range(end - tail):
        carry, *_rest = served_fn(carry, held, feed0, lanes_at(g))
    snapshot = [jnp.copy(a) for a in carry]
    jax.block_until_ready(snapshot)
    prefill_s = time.time() - t0

    def last_steps(fn, given, snap=snapshot):
        """The last ``tail`` steps from a copy of the kept cache -> (logits
        [tail, lanes, vocab], what the selecting layers handed out, a step
        an entry, or None)."""
        carry = [jnp.copy(a) for a in snap]
        logits, noted = [], []
        for g in range(end - tail, end):
            carry, _nxt, lg, *rest = fn(carry, given, feed0, lanes_at(g))
            logits.append(np.asarray(lg))
            # (routed, groups) and then a recording step's three tuples
            noted.append([[np.asarray(a) for a in group]
                          for group in rest[2:]] or None)
        return np.stack(logits), noted

    served, noted = last_steps(served_fn, held)
    # the served sets of every sparse lane's last queries, a layer an entry
    layers = cfg.layers

    def served_sets(i):
        n = totals[i]
        out = np.zeros((layers, tail, n), bool)
        for t, (_scores, positions, count) in enumerate(noted):
            for l in range(layers):
                chosen = positions[l][i, :count[l][i]]
                out[l, t, chosen] = True
        return out

    fwd = reference.by_layer(config)
    own, given, ref_kept = {}, {}, {}
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate(totals):
            ids = jnp.asarray(tokens[i], jnp.int32)
            logits, kept = fwd(params, ids, True)
            own[i] = np.asarray(logits[n - tail:])
            ref_kept[i] = {key: [np.asarray(kept[key][l])
                                 for l in (0, layers - 1)]
                           for key in ("scores", "chosen")}
            del logits, kept
            if i in sparse:
                sets = served_sets(i)
                given[i] = np.asarray(fwd(
                    params, ids, imposed=[jnp.asarray(sets[l])
                                          for l in range(layers)])[n - tail:])
    std = float(np.std(np.concatenate(list(own.values()))))

    def rms(logits, want, lanes):
        err = np.concatenate([logits[:, i] - want[i] for i in lanes])
        return float(np.sqrt(np.mean(np.square(err))) / std)

    def readings(logits):
        rounded = np.asarray(jnp.asarray(logits).astype(jnp.bfloat16)
                             .astype(jnp.float32))
        return {
            "rms_given_the_references_own_set": rms(logits, own,
                                                    range(LANES)),
            "rms_own_set_lanes_past_topk": rms(logits, own, sparse),
            "rms_own_set_lanes_under_topk": rms(
                logits, own, [i for i in range(LANES) if i not in sparse]),
            "largest_logit_error": float(max(
                np.abs(logits[:, i] - own[i]).max() for i in range(LANES))),
            "argmax_differs_share": float(np.mean([
                (logits[:, i].argmax(-1) != own[i].argmax(-1)).mean()
                for i in range(LANES)])),
            # ``glm_dsa_ref.check``'s second statistic: the reference's
            # largest logit less its logit of the served argmax
            "largest_deficit": float(max(
                (own[i].max(-1) - np.take_along_axis(
                    own[i], logits[:, i].argmax(-1)[:, None], 1)[:, 0]).max()
                for i in range(LANES))),
            "bf16_exact": float((rounded == logits).mean())}

    def inside(r):
        return bool(r["rms_given_the_references_own_set"]
                    <= OWN_SET_RMS_TOLERANCE
                    and r["bf16_exact"] <= BF16_EXACT_TOLERANCE)

    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "layers": layers, "sequence_lens": totals,
              "lanes_past_topk": len(sparse), "prefill_seconds":
                  round(prefill_s, 1), "logit_std": std,
              "paths": {"index_scores": dm.attention_path(cfg, kv, LANES,
                                                          "index"),
                        "selected_read": dm.attention_path(cfg, kv, LANES,
                                                           "latent"),
                        "experts": dm.experts_path(cfg, held, LANES)},
              "tolerances": {
                  "given_set_rms": GIVEN_SET_RMS_TOLERANCE,
                  "own_set_rms": OWN_SET_RMS_TOLERANCE,
                  "scores_rms": SCORES_RMS_TOLERANCE,
                  "overlap_floor": OVERLAP_FLOOR,
                  "bf16_exact": BF16_EXACT_TOLERANCE}}
    # the served run: its logits, its scores and its sets
    first = dict(readings(served), rms_given_the_served_set=rms(
        served, given, sparse))
    scores_err = {0: [], layers - 1: []}
    overlap = {0: [], layers - 1: []}
    for i in sparse:
        n = totals[i]
        sets = served_sets(i)
        for at, l in enumerate((0, layers - 1)):
            want = ref_kept[i]["scores"][at]                  # [tail, n]
            got = np.stack([noted[t][0][l][i, :n] for t in range(tail)])
            seen = np.isfinite(want)
            scores_err[l].append(float(np.sqrt(
                np.mean(np.square(got[seen] - want[seen]))
                / np.mean(np.square(want[seen])))))
            theirs = ref_kept[i]["chosen"][at]
            overlap[l] += list((sets[l] & theirs).sum(1) / theirs.sum(1))
    for l, key in ((0, "first"), (layers - 1, "last")):
        first["index_scores_rms_%s_layer" % key] = max(scores_err[l])
        first["overlap_%s_layer_min_median" % key] = [
            float(np.min(overlap[l])), float(np.median(overlap[l]))]
    result["served_bf16"] = first
    note = lambda name: print("chip_check_glm: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    note("served_bf16")
    verdicts = {"served_bf16": inside(first)
                and first["rms_given_the_served_set"]
                <= GIVEN_SET_RMS_TOLERANCE
                and first["index_scores_rms_first_layer"]
                <= SCORES_RMS_TOLERANCE
                and first["overlap_first_layer_min_median"][0]
                >= OVERLAP_FLOOR}
    del served_fn
    for name in [c for c in ("jnp_paths",) + CONTROLS if c in controls]:
        weights, snap, fault = held, snapshot, name
        if name == "v_cut_to_192":
            fault = None
            weights = dict(held, **{
                k: v.at[:, cfg.head_dim:, :].set(0) for k, v in held.items()
                if k.endswith("_wkvb_v")})
        elif name == "hit_without_index_rows":
            fault = None
            lost = np.concatenate([tables[i, :-(-n // BLOCK) // 2]
                                   for i, n in enumerate(totals)])
            snap = [a.at[lost].set(0) if j in kv.index_places else a
                    for j, a in enumerate(snapshot)]
        elif name == "fp8_weights":
            fault = None
            # the last: the served set is given up array by array
            del weights
            held.clear()
            weights = dm.laid_out(cfg, _load("chip_check_nemotron").to_fp8(
                params))
        fn = make(weights, fault)
        logits, _none = last_steps(fn, weights, snap)
        key = name if name == "jnp_paths" else "control_" + name
        result[key] = readings(logits)
        if name == "jnp_paths":
            result[key]["largest_difference_from_the_kernels"] = float(
                np.abs(logits - served).max())
        verdicts[key] = inside(result[key])
        note(key)
        del fn, logits, weights, snap
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
    """40 requests for 32 lanes, four of them past ``index_topk``, through
    client, server and engine: every band of depth with enough tokens inside
    ``glm_dsa_ref.check``'s two limits, for the requests that ran from the
    start and for those that waited for a lane; the step's three kernels
    counted as used and none as fallen back."""
    import numpy as np

    base, dots = _load("chip_check_nemotron"), _load("chip_check_dots")
    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests = base.engine_requests(seed, config, lanes, tiny)
    rng = np.random.default_rng([seed, 1 << 23])
    deep = 2800 if not tiny else 40
    for i in range(4):
        requests[i] = ([int(t) for t in rng.integers(
            0, config["vocab_size"], 200 if not tiny else 6)],
            deep - (200 if not tiny else 6))
    edges = (0, 64, 256, cfg.index_topk) if not tiny else (0, 8)
    judged_from = base.MIN_JUDGED if not tiny else 8
    t0 = time.time()
    cases, said = dots.engine_run(
        cfg, params, traffic, requests,
        lanes * (deep // BLOCK + 2) + 8, model="glm_check")
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
    # the four deep requests ran from the start: their tokens past index_topk
    ok = ok and said["by_depth_from_the_start"][-1][2] >= judged_from
    if device.platform == "tpu":
        used = {k for k, v in said["kernels"].items()
                if k.startswith("pallas_kernel_used_total") and v}
        ok = ok and said["paths"]["attention"] == "pallas" and all(
            path == "pallas" for _b, path in said["paths"]["experts"]) \
            and said["prewarm"] == ["compiled"] \
            and used == {"pallas_kernel_used_total{kernel=%s}" % k for k in (
                "index_scores", "latent_attention", "moe_experts")} \
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
        print("chip_check_glm: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "glm-5-serve.json"), args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_sparse_latent_moe_decode_long.json"), args.tiny_on_cpu)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(args.seeds):
        if args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu, controls)
        with open(os.path.join(out_dir, "chip_check_glm.jsonl"), "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
