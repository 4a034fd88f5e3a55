"""On the chip, outside any timed window: the served K-EXAONE step's
*logits* and cached K and V against the plain reference, at the
configuration's widths, past the window.

    chiprun --timeout 2400 -- python benchmark/tests/chip_check_exaone.py --seeds 3

Seeded weights as the cell makes them; 32 sequences at once, a lane each of
a 32-lane ``make_paged_step`` over the cache manager's pools (the global
layer's blocks by a shuffled table, the window layers' rings moved by
``PagedKVCache.advance_ring`` as the engine moves them, 33 rings of 9
blocks): prompts of 336-448 tokens, fed a token a step (prefill here is
token-feed), then 64 decoded tokens each, teacher-forced with the step's
own argmax, so every sequence ends 400-512 positions long: past the window
of 128 three times over, its rings (144 positions) wrapped twice or more.
The step's logits at the last 64 positions of each sequence are compared
with ``exaone_moe_ref.forward`` of the whole sequence (float32, highest
matmul precision, the served bf16 weights upcast a layer at a time, no
cache, a band mask for the window), what the first layer's ring holds of
each sequence afterwards (a window layer: its last 128 positions' K and V)
with the reference's, and what the global layer's pool holds of the whole
sequence with the reference's.

Nine controls run the same way on the served run's tokens, each a server
with one fault judged by the same reference on the weights as served: a
sliding layer attending its whole context (every layer global, the rotation
kept); RoPE on the global layer too; the shared expert dropped; the
selection bias ignored; gates not renormalised; Q and K normalised over all
heads at once; the logits computed in bfloat16; an int8 pool; the weights
rounded to fp8 (e4m3) on their way into the step (the precision next below
the one the configuration states: what ``exaone_moe_ref.check``'s limits
are set against).  Exit code 1
if the served path is outside a tolerance on any seed, or a control inside
all of them.

``--engine`` adds the leg that goes the cell's own way (``engine_leg``):
``ServingClient`` -> ``ServingServer`` -> ``DecodeEngine`` with the cell's
bucket and pool, so admission, the rings as ``_decode_step_locked`` moves
them, ``make_fed_step`` one step ahead, and the release at a sequence's end
are the timed path's.  40 requests for 32 lanes, all sent at once: four run
past 2,048 positions (16 windows, the ring wrapped 15 times, 137 slots of
the 512-slot table) while the others, 250-2,048 long, are live beside them,
and eight wait for a lane and start in a ring another sequence gave back.
The server returns tokens, so the comparison is ``exaone_moe_ref.check``'s,
teacher-forced through the tokens, by the depth a token was served at: the
share of served tokens that are not the reference's argmax and the largest
deficit, each under the reference's own limit in every band of depth.  One
control goes the same way, an engine whose window layers attend their whole
context: it has to read over the limit in every band past the window.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Five limits, from readings on the chip (PERF.md section 6, PR 38: call 1 read
# one seed and call 2 three more, 32 sequences x 64 positions x 19,200 logits of
# standard deviation 1.57 each, and set the limits; call 4 ran them as committed
# on a fifth seed, whose readings widen two bands below).  Weights are the same bits
# on both sides.  What is left is the served path's bfloat16 (the input of every
# matmul and the cached K and V rounded to 8 bits of mantissa over 5 layers) and
# what that noise does to the routing: the 8th expert chosen beats the first one
# left out by 9.4e-5 of selection score in the median and by under 1.4e-5 at a
# tenth of positions, so the two sides swap an expert now and then.  The limits
# on logits therefore hold structure, and the two read off the cache hold the
# precision:
#   the first layer's ring (a window layer: K and V of a sequence's last 128
#     positions, before any routing), root-mean-square error as a share of their
#     own root-mean-square: served 0.00166 on every seed; an int8 pool
#     0.00645-0.00647; fp8 weights 0.0470-0.0471; Q and K normalised over all
#     heads at once 0.0571-0.0577.  The limit is 1.8 times the served reading and
#     under half the int8 one.
#   the global layer's pool (layer 3, after three sparse layers), the same
#     share over the whole sequence: served 0.0202-0.0216; an int8 pool
#     0.0319-0.0340; the bias ignored 0.0529-0.0559; fp8 0.165; RoPE on the
#     global layer 0.233-0.235; a window layer attending everything 1.03.  The
#     limit is 20% over the largest served reading, 19% under the smallest int8.
#   root-mean-square logit error: served 0.0399-0.0448, a steady statistic; an
#     int8 pool 0.0659-0.0696; the bias ignored 0.152-0.164; RoPE on the global
#     layer 0.203-0.210; fp8 weights 0.304-0.306; whole-width Q/K norm
#     0.318-0.321; gates not renormalised 0.89-0.92; no shared expert 1.31-1.33;
#     a window layer attending everything 1.83-1.84.  The limit is 23% over the
#     largest served reading and 17% under the smallest of any control.
#   largest logit error: served 1.41-1.66 (the largest of 39 million, where an
#     expert was swapped); fp8 2.36-2.61; gates not renormalised 5.7-6.0; an
#     int8 pool 1.59-1.60 and RoPE on the global layer 1.71-1.80, which a
#     maximum cannot tell from the served path.  The limit is 1.2 times the
#     largest served reading.
#   the share of logits that bfloat16 holds exactly: served 4e-5 (a float32 sum
#     keeps mantissa below bfloat16's 8 bits); a bfloat16 logit path 1.0, and
#     nothing else about it differs from the served path's readings.
# Each control falls outside one limit on every seed, not outside each.
LOGIT_TOLERANCE = 2.0
RMS_TOLERANCE = 0.055
WINDOW_KV_TOLERANCE = 0.003
GLOBAL_KV_TOLERANCE = 0.026
BF16_EXACT_TOLERANCE = 0.01
N_DECODE = 64
LANES = 32
BLOCK = 16
BLOCKS = 1100            # 32 lanes x 32 blocks of the global pool, and spare
CONTROLS = ("window_attends_everything", "rope_on_the_global_layer",
            "no_shared_expert", "bias_ignored", "gates_not_renormalised",
            "whole_width_qk_norm", "bf16_logits", "int8_pool", "fp8_weights")
# the controls whose fault is a patch of the block (it has to stand while the
# step is traced)
PATCHED = ("window_attends_everything", "rope_on_the_global_layer",
           "no_shared_expert", "gates_not_renormalised",
           "whole_width_qk_norm", "bf16_logits")


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls); without it a sequence feeds its
    prompt and then the step's own argmax.  -> per sequence (tokens fed,
    logits of the last n_decode positions, the first layer's cached K and V
    of the sequence's last ``window`` positions, the global layer's cached
    K and V of the whole sequence) and the most window blocks the sequences
    held at once."""
    import numpy as np

    from paddle_tpu.pallas_kernels.paged_attention import gather_blocks
    from paddle_tpu.serving import kv_cache as kvc

    kv = cache.config
    n = len(prompts)
    totals = [len(p) + n_decode for p in prompts]
    maxb = cfg.max_seq // BLOCK
    rng = np.random.default_rng(sum(totals))
    lanes = rng.permutation(LANES)[:n]
    free = iter(rng.permutation(np.arange(1, kv.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, total in enumerate(totals):
        for j in range(-(-total // BLOCK)):
            rows[i, j] = next(free)
    rings = [cache.new_ring() for _ in range(n)] if kv.window_layers else None
    fed = [list(forced[i] if forced else prompts[i]) for i in range(n)]
    logits = [[] for _ in range(n)]
    held = 0
    for pos in range(max(totals)):
        tok = np.zeros(LANES, np.int32)
        at = np.zeros(LANES, np.int32)
        lens = np.zeros(LANES, np.int32)
        # an idle lane names the scratch block
        tables = np.full((LANES, maxb), -1, np.int32)
        more = []
        live = [i for i in range(n) if pos < totals[i]]
        if rings is not None:
            wtables = np.full((LANES, kv.window_ring), -1, np.int32)
            # a sequence that has ended keeps its ring until it is read
            for i in live:
                cache.advance_ring(rings[i], pos + 1)
                wtables[lanes[i]] = rings[i].table
            held = max(held, cache.window_allocator.in_use)
            more = [wtables]
        for i in live:
            b = lanes[i]
            tok[b], at[b], lens[b] = fed[i][pos], pos, pos + 1
            tables[b] = rows[i]
        carry, nxt, lg = step(cache.carry(), params, tok, at, tables, lens,
                              *more)[:3]
        cache.replace_carry(carry)
        nxt = np.asarray(nxt)
        keep = [i for i in live if pos >= totals[i] - n_decode]
        lg = np.asarray(lg) if keep else None
        for i in live:
            if pos + 1 == len(fed[i]) and len(fed[i]) < totals[i]:
                fed[i].append(int(nxt[lanes[i]]))
        for i in keep:
            logits[i].append(lg[lanes[i]])
    carry = cache.carry()
    groups, _state = kv.groups(carry)
    wgroups = kv.window_groups(carry)
    if kv.dtype == "int8":
        read = lambda g, j, table: np.asarray(kvc.dequantize_kv(
            gather_blocks(g[j][0], table[None]).reshape(
                1, -1, cfg.kv_heads, cfg.head_dim),
            gather_blocks(g[j + 2][0], table[None])))[0].reshape(
                -1, cfg.kv_heads * cfg.head_dim)
    else:
        read = lambda g, j, table: np.asarray(
            gather_blocks(g[j][0], table[None])[0]).astype(np.float32)
    out = []
    for i, total in enumerate(totals):
        span = np.arange(max(total - cfg.window, 0), total)
        whole = [read(groups, j, np.maximum(rows[i], 0))[:total]
                 for j in (0, 1)]
        if rings is not None:
            # the ring as it lies: position p at row p % (ring blocks x 16)
            ring = [read(wgroups, j, np.maximum(rings[i].table, 0))
                    for j in (0, 1)]
            first = [r[span % len(r)] for r in ring]
            cache.release_ring(rings[i])
        else:
            first = [w[span] for w in whole]
        out.append((fed[i], np.stack(logits[i]), first, whole))
    return out, held


def faulty_block(name):
    """The block with one fault (the model module, patched): -> undo()."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import exaone_moe as em

    names = ("_route", "_head_norm", "_rotated", "shared_part", "_head")
    kept = {key: getattr(em, key) for key in names}
    if name == "gates_not_renormalised":
        def route(x, router, bias, k, scaling):
            # the block's choice, weighted by the scores as they are
            _gates, chosen = kept["_route"](x, router, bias, k, scaling)
            score = jax.nn.sigmoid(jnp.dot(
                x, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return jnp.where(chosen, score, 0.0) * scaling, chosen
        em._route = route
    elif name == "whole_width_qk_norm":
        def head_norm(x, g, eps):
            flat = x.reshape(x.shape[0], -1)
            return em._rmsnorm(
                flat, jnp.tile(g, x.shape[1]), eps).reshape(x.shape)
        em._head_norm = head_norm
    elif name == "rope_on_the_global_layer":
        em._rotated = lambda cfg, l: True
    elif name == "window_attends_everything":
        # the step is built of global layers; the rotation stays where the
        # source has it
        em._rotated = lambda cfg, l, _was=SLIDING: _was[l]
    elif name == "no_shared_expert":
        em.shared_part = lambda p, x: jnp.zeros_like(x)
    elif name == "bf16_logits":
        def head(x, params, eps):
            # the head's sums leave in bfloat16.  reduce_precision and not a
            # pair of converts: inside one executable XLA may keep the
            # excess precision and drop the pair
            return jax.lax.reduce_precision(
                kept["_head"](x, params, eps), exponent_bits=8,
                mantissa_bits=7)
        em._head = head

    def undo():
        for key, fn in kept.items():
            setattr(em, key, fn)

    return undo


SLIDING = []        # layer -> is it a sliding layer in the source (one_seed)


def reference_of(reference, config, params, runs, n_decode, pad):
    """What the reference makes of each served sequence: (logits of the last
    n_decode positions, the first layer's K and V of the last ``window``
    positions, the global layer's K and V of all of them, the least margin
    of each of the last positions' choice of experts), on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    window = config["sliding_window"]
    full = config["layer_types"].index("full_attention")
    out = []
    with jax.default_matmul_precision("highest"):
        for fed, _lg, _first, _whole in runs:
            # causal: padding after the sequence cannot reach back into it
            padded = np.zeros(-(-len(fed) // pad) * pad, np.int32)
            padded[:len(fed)] = fed
            logits, kept = fwd(params, jnp.asarray(padded), True)
            n = len(fed)
            margin = np.min([np.asarray(m[:n]) for m in kept["margins"]],
                            axis=0)
            out.append((
                np.asarray(logits[n - n_decode:n]),
                [np.asarray(a[max(n - window, 0):n]).reshape(
                    min(window, n), -1) for a in kept["kv"][0]],
                [np.asarray(a[:n]).reshape(n, -1) for a in kept["kv"][full]],
                margin[n - n_decode:]))
            del logits, kept
    return out


def compare(runs, refs):
    import jax.numpy as jnp
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               first_sq=0.0, first_ref=0.0, whole_sq=0.0, whole_ref=0.0,
               exact=0, std=0.0, per_seq=[], margins=[])
    for (_fed, lg, first, whole), (want, ref_first, ref_whole, margin) \
            in zip(runs, refs):
        acc["std"] = float(np.std(want))
        acc["positions"] += len(lg)
        acc["worst"] = max(acc["worst"], float(np.abs(lg - want).max()))
        acc["sq"] += float(np.square(lg - want).sum())
        acc["n"] += lg.size
        # a float32 logit path leaves mantissa below bfloat16's 8 bits
        acc["exact"] += int((np.asarray(jnp.asarray(lg).astype(
            jnp.bfloat16).astype(jnp.float32)) == lg).sum())
        chosen = lg.argmax(-1)
        differs = chosen != want.argmax(-1)
        deficit = want.max(-1) - want[np.arange(len(lg)), chosen]
        acc["differs"] += int(differs.sum())
        acc["deficit"] = max(acc["deficit"], float(deficit.max()))
        # what ``exaone_moe_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        for key, got, ref in (("first", first, ref_first),
                              ("whole", whole, ref_whole)):
            for a, b in zip(got, ref):
                acc[key + "_sq"] += float(np.square(a - b).sum())
                acc[key + "_ref"] += float(np.square(b).sum())
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "window_kv_relative_rms_error":
                (acc["first_sq"] / acc["first_ref"]) ** 0.5,
            "global_kv_relative_rms_error":
                (acc["whole_sq"] / acc["whole_ref"]) ** 0.5,
            "logits_bf16_exact_share": acc["exact"] / acc["n"],
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


def routing_of(reference, config, params, runs, pad):
    """How the reference routes the served sequences: the mean number of
    held experts that a step of all lanes hits (a step: the same position
    of every sequence), the fullest held expert over the mean, and the
    share of assignments that fall on held experts, over the sparse
    layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    first, held = config["first_expert"], config["num_experts"]
    shortest = min(len(fed) for fed, *_ in runs)
    chosen = []
    with jax.default_matmul_precision("highest"):
        for fed, *_rest in runs:
            padded = np.zeros(-(-len(fed) // pad) * pad, np.int32)
            padded[:len(fed)] = fed
            _lg, kept = fwd(params, jnp.asarray(padded), True)
            chosen.append(np.stack([np.asarray(g[:shortest]) > 0
                                    for g in kept["gates"]]))
    counts = np.sum(chosen, axis=0)            # [layers, positions, experts]
    mine = counts[:, :, first:first + held]
    return {"held_experts_hit_mean": float((mine > 0).sum(-1).mean()),
            "held_load_max_over_mean": float(
                (mine.max(-1) * held / np.maximum(mine.sum(-1), 1)).mean()),
            "local_assignment_share": float(mine.sum() / counts.sum())}


_STEPS = {}     # a jitted step a block (as served, or with a patched fault)


def one_seed(seed, config, model, reference, device, tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    cfg = model.decoder_config(config)
    SLIDING[:] = [k == "window" for k in cfg.layer_types]
    params = model.make_params(config, seed, device)
    rng = np.random.default_rng(seed)
    n_pos = config["n_positions"]
    n_decode = min(N_DECODE, n_pos // 4)
    # every sequence ends 400-512 positions long (tiny: as long as fits)
    hi = min(512, n_pos) - n_decode
    lens = list(rng.integers(max(hi * 3 // 4, 1), hi + 1, LANES))
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    blocks = BLOCKS if not tiny else LANES * (n_pos // BLOCK) + 8

    def served(params, forced=None, fault=None):
        built = cfg.replace(layer_types=["attention"] * cfg.layers) \
            if fault == "window_attends_everything" else cfg
        kv = dm.cache_config(built, BLOCK, blocks, state_slots=LANES + 1,
                             dtype="int8" if fault == "int8_pool" else None)
        # the patch has to stand while the step is traced: at its first call
        patched = fault if fault in PATCHED else None
        undo = faulty_block(patched) if patched else None
        key = (patched, kv.dtype)
        if key not in _STEPS:
            _STEPS[key] = jax.jit(dm.make_paged_step(built, kv),
                                  donate_argnums=(0,))
        try:
            return run_batch(_STEPS[key], kvc.PagedKVCache(kv), params,
                             built, prompts, n_decode, forced)
        finally:
            if undo:
                undo()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "window": cfg.window, "ring_blocks": dm.cache_config(
                  cfg, BLOCK, blocks, state_slots=LANES + 1).window_ring,
              "sequence_lens": [int(n) + n_decode for n in lens],
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "window_kv_tolerance": WINDOW_KV_TOLERANCE,
              "global_kv_tolerance": GLOBAL_KV_TOLERANCE,
              "bf16_exact_tolerance": BF16_EXACT_TOLERANCE}
    run, held = served(params)
    # the rings' release, seen: 32 sequences of 400-512 positions never held
    # more than a ring each
    result["window_blocks_held_at_most"] = int(held)
    result["window_blocks_a_ring_times_lanes"] = \
        LANES * result["ring_blocks"]
    pad = min(256, n_pos)
    refs = reference_of(reference, config, params, run, n_decode, pad)
    result["served_bf16"] = compare(run, refs)
    result["routing"] = routing_of(reference, config, params, run, pad)
    forced = [fed for fed, _lg, _first, _whole in run]
    del run
    for name in CONTROLS:
        given = params
        if name == "bias_ignored":
            given = {k: jnp.zeros_like(v) if k.endswith("expert_bias") else v
                     for k, v in params.items()}
        elif name == "fp8_weights":
            # two jits with the 8 bits between them: inside one, XLA may keep
            # the excess precision and drop the pair of converts.  The last
            # control: two sets of weights do not fit, so the served set is
            # given up array by array
            to_fp8 = jax.jit(lambda w: jax.lax.bitcast_convert_type(
                w.astype(jnp.float8_e4m3fn), jnp.uint8))
            from_fp8 = jax.jit(lambda b, dt: jax.lax.bitcast_convert_type(
                b, jnp.float8_e4m3fn).astype(dt), static_argnums=(1,))
            given = {}
            for key in sorted(params):
                w = params.pop(key)
                given[key] = from_fp8(to_fp8(w), w.dtype)
                del w
        got, _held = served(given, forced, name)
        result["control_" + name] = compare(got, refs)
        del got, given
    result["seconds"] = round(time.time() - t0, 1)
    inside = {name: bool(
        got["largest_logit_error"] <= LOGIT_TOLERANCE
        and got["rms_logit_error"] <= RMS_TOLERANCE
        and got["window_kv_relative_rms_error"] <= WINDOW_KV_TOLERANCE
        and got["global_kv_relative_rms_error"] <= GLOBAL_KV_TOLERANCE
        and got["logits_bf16_exact_share"] <= BF16_EXACT_TOLERANCE)
        for name, got in result.items()
        if name == "served_bf16" or name.startswith("control_")}
    result["inside_tolerance"] = inside
    result["ok"] = inside == dict(
        {"control_" + name: False for name in CONTROLS}, served_bf16=True) \
        and held <= LANES * result["ring_blocks"]
    if tiny:
        result["not_a_chip_result"] = True
    return result


MODEL = "bench"
DEPTH = 2048             # the deep sequences pass this many positions
# the depth a token was served at (its context's length), by what the path
# has met by then: inside the first window; the ring's first lap; ...; past
# DEPTH.  Bands narrower than this many tokens are reported, not judged
MIN_JUDGED = 64


def engine_requests(seed, config, lanes, tiny):
    """[(prompt ids, tokens to generate)]: ``lanes // 8`` deep ones first,
    then what fills the lanes, then a quarter more that have to wait."""
    import numpy as np

    rng = np.random.default_rng([seed, 1 << 22])
    n_pos, window = config["n_positions"], config["sliding_window"]
    depth = DEPTH if not tiny else n_pos * 3 // 4
    lo, hi = (32, 256) if not tiny else (2, 8)
    deep = max(lanes // 8, 1)
    totals = [int(rng.integers(depth + window + 1,
                               min(depth + 2 * window, n_pos) + 1))
              for _ in range(deep)]
    totals += [int(rng.integers(depth // 4, depth + 1))
               for _ in range(lanes - deep)]
    totals += [int(rng.integers(depth // 8, depth // 4 + 1))
               for _ in range(max(lanes // 4, 1))]
    out = []
    for total in totals:
        n = min(int(np.exp(rng.uniform(np.log(lo), np.log(hi)))), total - 1)
        out.append(([int(t) for t in rng.integers(0, config["vocab_size"],
                                                  n)], total - n))
    return out, depth


def engine_run(cfg, params, traffic, requests, kv_blocks):
    """Every request at once through a client of its own -> ([(prompt,
    served)], what the pools and the prewarm say)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from paddle_tpu.serving import (DecodeEngine, ServingClient,
                                    ServingEngine, ServingServer)

    deadline_ms = float(traffic["deadline_ms"])
    engine = DecodeEngine(buckets=traffic["lane_buckets"],
                          deadline_ms=deadline_ms)
    engine.add_model(MODEL, (cfg, params), kv_blocks=kv_blocks)
    engine.prewarm()
    engine.start()
    server = ServingServer(ServingEngine(), port=0,
                           decode_engine=engine).start()
    endpoint = "127.0.0.1:%d" % server.port

    def ask(request):
        prompt, n_out = request
        reply = ServingClient(endpoints=[endpoint]).generate(
            MODEL, prompt, max_new_tokens=n_out, deadline_ms=deadline_ms)
        if reply.status != "ok":
            raise RuntimeError("engine leg: %s %s"
                               % (reply.status, reply.error))
        return prompt, [int(t) for t in np.asarray(
            reply.outputs["tokens"]).reshape(-1)]

    try:
        with ThreadPoolExecutor(len(requests)) as pool:
            cases = list(pool.map(ask, requests))
        m = engine._models[MODEL]
        walloc = m.cache.window_allocator
        said = {"paths": {"attention": m.attn_path,
                          "window_attention": m.window_path,
                          "experts": sorted(m.experts_path.items())},
                "global_blocks": m.cache.allocator.stats(),
                "window_blocks": walloc.stats() if walloc else None,
                "window_ring": m.kv_config.window_ring}
    finally:
        server.shutdown()
        engine.stop()
    return cases, said


def by_depth(reference, config, params, cases, edges, pad):
    """``exaone_moe_ref.check``'s two statistics of the served tokens, by
    the length of the context each was served from: [(from, to, compared,
    differing share, largest deficit)]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    depth, deficit = [], []
    with jax.default_matmul_precision("highest"):
        for prompt, served in cases:
            seq = list(prompt) + list(served)
            # causal: padding after the sequence cannot reach back into it
            padded = np.zeros(-(-len(seq) // pad) * pad, np.int32)
            padded[:len(seq)] = seq
            logits = np.asarray(fwd(params, jnp.asarray(padded)))
            at = np.arange(len(prompt), len(seq))
            rows = logits[at - 1]
            depth.append(at)
            deficit.append(rows.max(-1) - rows[np.arange(len(at)), served])
            del logits, rows
    depth, deficit = np.concatenate(depth), np.concatenate(deficit)
    out = []
    for lo, hi in zip(edges, edges[1:] + (1 << 30,)):
        mine = deficit[(depth >= lo) & (depth < hi)]
        out.append((int(lo), int(min(hi, depth.max() + 1)), int(mine.size),
                    float((mine > 0).mean()) if mine.size else None,
                    float(mine.max()) if mine.size else None))
    return out


def engine_leg(seed, config, model, reference, device, tiny, traffic):
    cfg = model.decoder_config(config)
    SLIDING[:] = [k == "window" for k in cfg.layer_types]
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests, depth = engine_requests(seed, config, lanes, tiny)
    window = cfg.window
    ring_len = (-(-window // BLOCK) + 1) * BLOCK
    edges = tuple(sorted({e for e in (0, window, ring_len, 4 * window,
                                      depth // 2, depth) if e <= depth}))
    pad = min(256, config["n_positions"])
    t0 = time.time()
    result = {"leg": "engine", "device": device.device_kind,
              "platform": device.platform, "seed": seed, "lanes": lanes,
              "requests": len(requests),
              "sequence_lens": [len(p) + n for p, n in requests],
              "differing_share_bound": reference.DIFFERING_SHARE_BOUND,
              "deficit_bound": reference.DEFICIT_BOUND}
    judged_from = MIN_JUDGED if not tiny else 12
    # the deep ones, and the ones that had to wait for a lane
    deep = max(lanes // 8, 1)
    judged = list(range(deep)) + list(range(lanes, len(requests)))

    def bands(cases):
        rows = by_depth(reference, config, params,
                        [cases[i] for i in judged], edges, pad)
        inside = [share <= reference.DIFFERING_SHARE_BOUND
                  and worst <= reference.DEFICIT_BOUND
                  for _lo, _hi, n, share, worst in rows if n >= judged_from]
        return rows, inside

    cases, said = engine_run(cfg, params, traffic, requests,
                             int(traffic["kv_blocks"]))
    rows, inside = bands(cases)
    result["served"] = dict(said, by_depth=rows)
    held = said["window_blocks"]["high_water"]
    deepest = rows[-1]
    ok = all(inside) and deepest[2] >= judged_from \
        and held <= lanes * said["window_ring"] \
        and said["window_blocks"]["in_use"] == 0 \
        and said["global_blocks"]["in_use"] == 0 \
        and all(len(served) == n for (_p, served), (_q, n)
                in zip(cases, requests))
    if device.platform == "tpu":
        ok = ok and said["paths"]["attention"] == "pallas" \
            and said["paths"]["window_attention"] == "pallas" \
            and all(path == "pallas" for _b, path in said["paths"]["experts"])
    # the control: the same requests to an engine whose window layers keep,
    # and attend, everything (five global pools, sized for these requests)
    undo = faulty_block("window_attends_everything")
    try:
        built = cfg.replace(layer_types=["attention"] * cfg.layers)
        blocks = sum(-(-(len(p) + n) // BLOCK) for p, n in requests) + lanes
        got, _said = engine_run(built, params, traffic, requests, blocks)
    finally:
        undo()
    rows, inside = bands(got)
    result["control_window_attends_everything"] = {"by_depth": rows}
    past = [share > reference.DIFFERING_SHARE_BOUND
            for lo, _hi, n, share, _w in rows
            if n >= judged_from and lo >= ring_len]
    result["ok"] = bool(ok and past and all(past))
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
    ap.add_argument("--engine", action="store_true",
                    help="the leg through ServingClient and DecodeEngine "
                    "past 2,048 positions, and that alone")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: the configuration's tiny sizes on any "
                    "backend; nothing it prints is a chip result")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny_on_cpu:
        print("chip_check_exaone: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "k-exaone-236b-a23b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(ROOT, "benchmark", "traffic",
                                  "serve_window_moe_decode_long.json"),
                        args.tiny_on_cpu)
    ok = True
    for i in range(args.seeds):
        if args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu)
        with open(os.path.join(out_dir, "chip_check_exaone.jsonl"), "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
