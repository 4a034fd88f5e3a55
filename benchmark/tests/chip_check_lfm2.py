"""On the chip, outside any timed window: the served LFM2-MoE step's
*logits*, cached K and V and stored convolution windows against the plain
reference, at the configuration's widths and the cell's sizes.

    chiprun -- python benchmark/tests/chip_check_lfm2.py --seeds 3

Seeded weights as the cell makes them; 32 sequences at once, a lane each of
a 32-lane ``make_paged_step`` over the cell's pool (2048 bf16 KV blocks, 33
window slots), in shuffled lanes, block tables and slots: 31 prompts of
24-200 tokens and one of 448, then 64 decoded tokens each, teacher-forced
with the step's own argmax; a lane whose sequence has ended idles on the
scratch block and slot.  The step's logits at the last 64 positions of each
sequence are compared with ``lfm2_moe_ref.forward`` of the whole sequence
(float32, highest matmul precision, the served bf16 weights upcast a layer
at a time; a sequence at a time), what the first attention layer's pools
hold of each sequence afterwards with the reference's K and V of that
layer, and what each sequence's slot holds after its last step (every conv
layer's two newest inputs) with the reference's.  Printed, and written
under ``chiprun_out/``: the largest absolute logit error, the
root-mean-square error, the cached K and V's and the windows'
root-mean-square error as a share of their own root-mean-square, the share
of positions whose argmax differs and the largest *deficit* of the step's
chosen tokens, which is what ``lfm2_moe_ref.check`` reads through the
tokens alone inside the benchmark's runs.

Five controls run the same way on the served run's tokens, each a server
with one fault judged by the same reference on the weights as served:
``expert_bias`` dropped from the selection; gates not renormalised; the
window one token stale (each step reads the slots as the step before last
left them); Q and K normalised over all heads at once (OLMoE's norm); the
weights rounded to fp8 (e4m3) on their way into the step.  Exit code 1 if
the served path is outside ``LOGIT_TOLERANCE``, ``RMS_TOLERANCE``,
``KV_TOLERANCE`` or ``WINDOW_TOLERANCE`` on any seed, or a control inside
all four.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Four limits, from readings on the chip (PERF.md section 6, PR 33: call 1 read
# two seeds and call 2 three more, 2,048 positions x 65,536 logits of standard
# deviation 0.905 each, and set the limits; call 4 ran them as committed on
# other seeds).  Weights are the same bits on both sides.  What is left is the
# served path's bfloat16 (the input of every matmul, the cached K and V and the
# window rounded to 8 bits of mantissa over 9 layers) and, far larger, what that
# noise does to the routing: a gate is about a quarter, the choice of the 4th
# expert over the 5th hangs on under 0.01 of selection score somewhere in the 8
# routed layers at 96% of positions (``clear_positions_share`` 0.033-0.042), so
# the two sides swap experts often and the logits differ by tenths where they
# do.  The limits on logits therefore hold structure, and the two that are read
# before any routing hold the precision:
#   the first attention layer's cached K and V (after the dense lead layer: no
#     routing before them), root-mean-square error as a share of their own
#     root-mean-square: served 0.00632-0.00633; Q and K normalised over all
#     heads at once 0.0615-0.0617; fp8 weights 0.143.  The limit is 26% over
#     the served band, which is 0.2% wide.
#   the first conv layer's window (its two newest inputs, in the slot after the
#     sequence's last step): served 0.00288-0.00290; fp8 weights 0.0657-0.0663;
#     a window one token stale 1.20-1.25.  The limit is 38% over.
#   root-mean-square logit error: served 0.0855-0.0920, a steady statistic;
#     whole-width Q/K norm 0.137-0.147; fp8 weights 0.344-0.348; ``expert_bias``
#     dropped 0.434-0.445; gates not renormalised 0.811-0.814; a stale window
#     1.22-1.23.  The limit is a quarter over the largest served reading and 16%
#     under the smallest of any control.
#   largest logit error: served 1.33-1.55 (the largest of 134 million, where an
#     expert was swapped); fp8 2.16-2.37; bias dropped 2.69-3.13; whole-width
#     Q/K norm 1.44-1.59, which a maximum cannot tell from the served path.  The
#     limit is 1.3 times the largest served reading.
# Each control falls outside one limit on every seed, not outside each.
LOGIT_TOLERANCE = 2.0
RMS_TOLERANCE = 0.115
KV_TOLERANCE = 0.008
WINDOW_TOLERANCE = 0.004
N_DECODE = 64
LONG = 512
LANES = 32
BLOCK = 16
BLOCKS = 2048
CONTROLS = ("no_expert_bias", "gates_not_renormalised", "stale_window",
            "whole_width_qk_norm", "fp8_weights")


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None,
              after=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls); without it a sequence feeds its
    prompt and then the step's own argmax.  ``after(carry)`` stands between
    a step's carry and the next step.  -> per sequence (tokens fed, logits
    of the last n_decode positions, the first attention layer's cached K
    and V, every conv layer's window in the sequence's slot)."""
    import numpy as np

    n = len(prompts)
    totals = [len(p) + n_decode for p in prompts]
    maxb = cfg.max_seq // BLOCK
    rng = np.random.default_rng(sum(totals))
    lanes = rng.permutation(LANES)[:n]
    slot_of = rng.permutation(np.arange(1, cache.config.state_slots))[:n]
    free = iter(rng.permutation(np.arange(1, cache.config.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, total in enumerate(totals):
        for j in range(-(-total // BLOCK)):
            rows[i, j] = next(free)
    fed = [list(forced[i] if forced else prompts[i]) for i in range(n)]
    logits = [[] for _ in range(n)]
    for pos in range(max(totals)):
        tok = np.zeros(LANES, np.int32)
        at = np.zeros(LANES, np.int32)
        lens = np.zeros(LANES, np.int32)
        slots = np.zeros(LANES, np.int32)
        # an idle lane names the scratch block and the scratch slot
        tables = np.full((LANES, maxb), -1, np.int32)
        live = [i for i in range(n) if pos < totals[i]]
        for i in live:
            b = lanes[i]
            tok[b], at[b], lens[b] = fed[i][pos], pos, pos + 1
            slots[b], tables[b] = slot_of[i], rows[i]
        carry, nxt, lg = step(cache.carry(), params, tok, at, tables, lens,
                              slots)[:3]
        cache.replace_carry(carry if after is None else after(carry))
        nxt = np.asarray(nxt)
        keep = [i for i in live if pos >= totals[i] - n_decode]
        lg = np.asarray(lg) if keep else None
        for i in live:
            if pos + 1 == len(fed[i]) and len(fed[i]) < totals[i]:
                fed[i].append(int(nxt[lanes[i]]))
        for i in keep:
            logits[i].append(lg[lanes[i]])
    groups, (windows,) = cache.config.groups(cache.carry())
    out = []
    for i, total in enumerate(totals):
        blocks = rows[i, :-(-total // BLOCK)]
        kv = [np.asarray(groups[g][0][blocks]).astype(np.float32).reshape(
            len(blocks) * BLOCK, -1)[:total] for g in (0, 1)]
        held = [np.asarray(w[slot_of[i]]).astype(np.float32).reshape(
            cfg.conv_taps - 1, -1) for w in windows]
        out.append((fed[i], np.stack(logits[i]), kv, held))
    return out


def stale_windows(kv_config):
    """-> ``after(carry)``: the windows a step wrote reach the slots a step
    late, so every step reads a window that lacks the newest input."""
    held = []

    def after(carry):
        groups, (windows,) = kv_config.groups(carry)
        held.append(windows)
        late = held.pop(0) if len(held) > 1 else [w * 0 for w in windows]
        return tuple(a for g in groups + [late] for a in g)

    return after


def faulty_block(name):
    """The block with one fault (the model module, patched): -> undo()."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lfm2_moe

    kept = {"_route": lfm2_moe._route, "_head_norm": lfm2_moe._head_norm}
    if name == "gates_not_renormalised":
        def route(h2, router, bias, k, scaling):
            # the block's choice, weighted by the scores as they are
            _gates, chosen = kept["_route"](h2, router, bias, k, scaling)
            score = jax.nn.sigmoid(jnp.dot(
                h2, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return jnp.where(chosen, score, 0.0) * scaling, chosen
        lfm2_moe._route = route
    elif name == "whole_width_qk_norm":
        def head_norm(x, g, eps):
            flat = x.reshape(x.shape[0], -1)
            return lfm2_moe._rmsnorm(
                flat, jnp.tile(g, x.shape[1]), eps).reshape(x.shape)
        lfm2_moe._head_norm = head_norm

    def undo():
        for key, fn in kept.items():
            setattr(lfm2_moe, key, fn)

    return undo


def reference_of(reference, config, params, runs, n_decode, pad):
    """What the reference makes of each served sequence: (logits of the last
    n_decode positions, the first attention layer's K and V, every conv
    layer's two newest inputs, the least margin of each of those positions'
    choice of experts), on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    taps = config["conv_L_cache"]
    out = []
    with jax.default_matmul_precision("highest"):
        for fed, _lg, _kv, _held in runs:
            # causal: padding after the sequence cannot reach back into it;
            # a few padded lengths, so a few compilations
            padded = np.zeros(-(-len(fed) // pad) * pad, np.int32)
            padded[:len(fed)] = fed
            logits, kept = fwd(params, jnp.asarray(padded), True)
            n = len(fed)
            # the least margin of a position's choice of experts over the
            # routed layers
            margin = np.min([np.asarray(m[:n]) for m in kept["margins"]],
                            axis=0)
            out.append((
                np.asarray(logits[n - n_decode:n]),
                [np.asarray(a[:n]).reshape(n, -1) for a in kept["kv"][0]],
                [np.asarray(g[n - taps + 1:n]) for g in kept["conv_inputs"]],
                margin[n - n_decode:]))
            del logits, kept
    return out


# A position's choice of experts is *clear* where, in every routed layer, the
# last expert chosen beats the first one left out by more than this: ten times
# the rounding noise the served path's bfloat16 leaves on a selection score
# (about 0.001).  Elsewhere a swapped expert (its gate is about a quarter) is a
# consequence of the stated precision and not a fault, and moves that
# position's logits by tenths.
CLEAR_MARGIN = 0.01


def compare(runs, refs):
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               kv_sq=0.0, kv_ref=0.0, win_sq=0.0, win_ref=0.0, first_sq=0.0,
               first_ref=0.0, std=0.0, clear=0, clear_sq=0.0, per_seq=[])
    for (_fed, lg, kv, held), (want, ref_kv, ref_held, margin) \
            in zip(runs, refs):
        acc["std"] = float(np.std(want))
        acc["positions"] += len(lg)
        acc["worst"] = max(acc["worst"], float(np.abs(lg - want).max()))
        sq = np.square(lg - want).sum(-1)
        acc["sq"] += float(sq.sum())
        acc["n"] += lg.size
        clear = margin > CLEAR_MARGIN
        acc["clear"] += int(clear.sum())
        acc["clear_sq"] += float(sq[clear].sum())
        chosen = lg.argmax(-1)
        differs = chosen != want.argmax(-1)
        deficit = want.max(-1) - want[np.arange(len(lg)), chosen]
        acc["differs"] += int(differs.sum())
        acc["deficit"] = max(acc["deficit"], float(deficit.max()))
        # what ``lfm2_moe_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        for got, ref in zip(kv, ref_kv):
            acc["kv_sq"] += float(np.square(got - ref).sum())
            acc["kv_ref"] += float(np.square(ref).sum())
        for i, (got, ref) in enumerate(zip(held, ref_held)):
            key = "first" if i == 0 else "win"
            acc[key + "_sq"] += float(np.square(got - ref).sum())
            acc[key + "_ref"] += float(np.square(ref).sum())
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    vocab = acc["n"] // acc["positions"]
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "clear_positions_share": acc["clear"] / acc["positions"],
            "rms_logit_error_clear":
                (acc["clear_sq"] / max(acc["clear"] * vocab, 1)) ** 0.5,
            "cached_kv_relative_rms_error":
                (acc["kv_sq"] / acc["kv_ref"]) ** 0.5,
            "first_window_relative_rms_error":
                (acc["first_sq"] / acc["first_ref"]) ** 0.5,
            "later_windows_relative_rms_error":
                (acc["win_sq"] / acc["win_ref"]) ** 0.5,
            "largest_deficit": acc["deficit"],
            "argmax_differs_share": acc["differs"] / acc["positions"],
            "per_sequence_differs_share_min_median_max":
                spread([d for d, _x in acc["per_seq"]]),
            "per_sequence_largest_deficit_min_median_max":
                spread([x for _d, x in acc["per_seq"]]),
            "positions": acc["positions"], "logit_std": acc["std"]}


_STEPS = {}     # a jitted step a block (as served, or with a patched fault)


def one_seed(seed, config, model, reference, device, tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    rng = np.random.default_rng(seed)
    n_pos = config["n_positions"]
    n_decode = min(N_DECODE, n_pos // 4)
    lens = list(np.minimum(rng.integers(24, 201, LANES - 1), n_pos // 2)) \
        + [min(LONG, n_pos) - n_decode]
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    kv = dm.cache_config(cfg, BLOCK, BLOCKS, state_slots=LANES + 1)

    def served(params, forced=None, fault=None):
        # the patch has to stand while the step is traced: at its first call
        undo = faulty_block(fault) if fault else None
        patched = fault if fault in ("gates_not_renormalised",
                                     "whole_width_qk_norm") else None
        if patched not in _STEPS:
            _STEPS[patched] = jax.jit(dm.make_paged_step(cfg, kv),
                                      donate_argnums=(0,))
        step = _STEPS[patched]
        try:
            return run_batch(
                step, kvc.PagedKVCache(kv), params, cfg, prompts, n_decode,
                forced, stale_windows(kv) if fault == "stale_window"
                else None)
        finally:
            if undo:
                undo()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": BLOCKS,
              "prompt_lens": [int(n) for n in lens],
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "kv_tolerance": KV_TOLERANCE,
              "window_tolerance": WINDOW_TOLERANCE}
    run = served(params)
    refs = reference_of(reference, config, params, run, n_decode,
                        min(256, n_pos))
    result["served_bf16"] = compare(run, refs)
    forced = [fed for fed, _lg, _kv, _held in run]
    del run
    for name in CONTROLS:
        given = params
        if name == "no_expert_bias":
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
        result["control_" + name] = compare(
            served(given, forced, name), refs)
        del given
    result["seconds"] = round(time.time() - t0, 1)
    inside = {name: bool(
        got["largest_logit_error"] <= LOGIT_TOLERANCE
        and got["rms_logit_error"] <= RMS_TOLERANCE
        and got["cached_kv_relative_rms_error"] <= KV_TOLERANCE
        and got["first_window_relative_rms_error"] <= WINDOW_TOLERANCE)
        for name, got in result.items()
        if name == "served_bf16" or name.startswith("control_")}
    result["inside_tolerance"] = inside
    result["ok"] = inside == dict(
        {"control_" + name: False for name in CONTROLS}, served_bf16=True)
    if tiny:
        result["not_a_chip_result"] = True
    return result


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", type=int, default=1,
                    help="this many seeds, from --seed on, in one process")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: the configuration's tiny sizes on any "
                    "backend; nothing it prints is a chip result")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny_on_cpu:
        print("chip_check_lfm2: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "lfm2-24b-a2b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    for i in range(args.seeds):
        result = one_seed(args.seed + 7919 * i, config, model, reference,
                          device, args.tiny_on_cpu)
        with open(os.path.join(out_dir, "chip_check_lfm2.jsonl"), "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
