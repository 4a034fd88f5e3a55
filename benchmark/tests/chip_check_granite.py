"""On the chip, outside any timed window: the served Granite 4.0-H step's
*logits* against the plain reference at the configuration's widths.

    chiprun -- python benchmark/tests/chip_check_granite.py --seeds 5

Seeded weights as the cell makes them; 4 sequences (prompts of 24-200
tokens, then 64 decoded tokens, teacher-forced with the step's own argmax)
and one of 1,024 positions, one at a time through ``make_paged_step``, a real
bf16 KV pool and real state slots (one live lane of a 4-lane step, in a
shuffled block table and a slot of its own).  The step's logits at the last
64 positions of each sequence are compared with
``granite_hybrid_ref.forward`` of the whole sequence (float32, highest
matmul precision, the served bf16 weights upcast layer by layer; a sequence
at a time, so the reference's temporaries fit beside the weights), and what
the first attention layer's pools hold of the sequence afterwards with the
reference's K and V of that layer.  Printed, and written under
``chiprun_out/``: the largest absolute logit error, the root-mean-square
error, the cached K and V's root-mean-square error as a share of their own
root-mean-square, what the longest sequence's state slot holds after its
last step against the reference's state there (the first mamba layer's,
which is held to a limit, and all 36), the share of positions whose argmax
differs and the largest *deficit* of the step's chosen tokens, which is
what ``granite_hybrid_ref.check`` reads through the tokens alone inside the
benchmark's runs.

Two controls run the same way, each a server of a lower precision judged by
the same reference: the SSM state rounded to bfloat16 at every write (the
family's modelling code keeps it in the model's dtype; the configuration
keeps float32 and says so under ``departures``), and the KV pool quantised
to int8.  Exit code 1 if the served path is outside ``STATE_TOLERANCE``,
``KV_TOLERANCE``, ``LOGIT_TOLERANCE`` or ``RMS_TOLERANCE`` on any seed, or
a control inside all four.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Four limits, from readings on the chip (PERF.md section 6, PR 31: call 5 read
# five seeds x 320 positions x 100,352 logits of standard deviation 0.113 and
# set the state's and the cache's limits, call 6 ran them as committed on five
# other seeds, and call 7 this file on five more, each rc 0; ranges below are
# over the fifteen).  Weights are the same bits on both sides, so what is left
# is the served path's bfloat16: the input of every matmul, the cached K and V
# and the convolution's window rounded to 8 bits of mantissa over 40 layers,
# where the reference keeps float32.
#   the first mamba layer's state after the 1,024 positions, a head's
#     root-mean-square error as a share of the head's own root-mean-square,
#     root-mean-square over the 64 heads: served 0.00275-0.00374 (the layer's
#     input is the embedding itself, so this is one matmul's bfloat16 inputs); a
#     bfloat16 state 0.0208-0.0379 (each write's rounding is carried on by the
#     recurrence, the longer the slower a head decays).  The limit is 2.7 times
#     the largest served reading and 2.1 times under the smallest of the
#     control's.  Over all 36 layers the served path reads 0.0095-0.0125 (the
#     hidden state's noise reaches the deeper layers' inputs) against
#     0.0249-0.0336, which is reported and held to nothing.
#   the first attention layer's cached K and V, root-mean-square error as a
#     share of their own root-mean-square: served 0.00682-0.00697 (the hidden
#     state's noise after five layers, and one rounding to bfloat16); an int8
#     cache 0.00888-0.00900.  Both bands are 2% wide and the limit is 15% over
#     the one and 11% under the other.  Attention is 4 layers of 40 and, at the
#     published attention_multiplier, close to a mean over the positions, so
#     only the pool's own content tells an int8 pool from a bfloat16 one.
#   root-mean-square logit error 0.00173-0.00194, limit 1.3 times the largest;
#     largest logit error 0.0101-0.0135 (the largest of 32 million, five to
#     seven standard deviations of the error: it swings by a third from seed to
#     seed), limit 1.5 times the largest.  These two hold the served path's
#     arithmetic as a whole (a fault in structure moves logits by tenths) and
#     are NOT what tells the controls: a bfloat16 state reads 0.0130-0.0189 and
#     0.00200-0.00230, bands that meet the served path's, so no logit limit can
#     stand between them, and an int8 cache reads as the served path.
# Each control falls outside one limit on every seed, not outside each.
STATE_TOLERANCE = 0.01
KV_TOLERANCE = 0.008
LOGIT_TOLERANCE = 0.02
RMS_TOLERANCE = 0.0025
N_DECODE = 64
LONG = 1024
LANES = 4
BLOCK = 16


def run_sequence(step, cache, params, cfg, prompt, n_decode, after=None):
    """One sequence through lane 0 of the step, the others idle.  ->
    (tokens fed, logits of the last n_decode positions, the first attention
    layer's cached K and V, every mamba layer's state in the slot).
    ``after(carry)`` stands between a step's carry and the next step (the
    controls)."""
    import numpy as np

    total = len(prompt) + n_decode
    maxb = cfg.max_seq // BLOCK
    tables = np.full((LANES, maxb), -1, np.int32)
    need = -(-total // BLOCK)
    # a shuffled table: the sequence's blocks lie anywhere in the pool
    rng = np.random.default_rng(len(prompt))
    tables[0, :need] = rng.permutation(
        np.arange(1, cache.config.num_blocks))[:need]
    slots = np.zeros(LANES, np.int32)
    slots[0] = 1 + rng.integers(0, cache.config.state_slots - 1)
    fed = list(prompt)
    logits = []
    for pos in range(total):
        tok = np.zeros(LANES, np.int32)
        at = np.zeros(LANES, np.int32)
        lens = np.zeros(LANES, np.int32)
        tok[0], at[0], lens[0] = fed[pos], pos, pos + 1
        carry, nxt, lg = step(cache.carry(), params, tok, at, tables, lens,
                              slots)
        cache.replace_carry(carry if after is None else after(carry))
        if pos + 1 == len(fed) and len(fed) < total:
            fed.append(int(nxt[0]))
        if pos >= total - n_decode:
            logits.append(np.asarray(lg[0]))
    return fed, np.stack(logits), \
        _cached(cache, tables[0, :need], total), _states(cache, slots[0])


def _states(cache, slot):
    """What the sequence's slot holds after its last step: each mamba
    layer's state [N, heads * d_head] float32."""
    import numpy as np

    _groups, (_windows, states) = cache.config.groups(cache.carry())
    return [np.asarray(s[slot]) for s in states]


def _state_errors(cfg, held, states):
    """The slot's states [N, heads * d_head] against the reference's S
    [heads, d_head, N] a layer -> (the first layer's error, all layers').
    A head's error is the root-mean-square of its difference as a share of
    the reference's own root-mean-square for that head, so that a head that
    decays slowly and holds little weighs as much as one that decays fast
    and holds much; then the root-mean-square over heads."""
    import numpy as np

    def heads(got, ref):
        ref = np.asarray(ref)
        got = got.reshape(got.shape[0], cfg.ssm_heads,
                          cfg.ssm_head_dim).transpose(1, 2, 0)
        return np.square(got - ref).mean((1, 2)) \
            / np.square(ref).mean((1, 2))

    per = np.stack([heads(g, r) for g, r in zip(held, states)])
    return float(np.sqrt(per[0].mean())), float(np.sqrt(per.mean()))


def _cached(cache, blocks, total):
    """What the first attention layer's pools hold of a sequence: K and V
    [total, kv_heads * head_dim] float32, as an attention over them would
    read them (an int8 pool through its scales)."""
    import numpy as np

    groups, _state = cache.config.groups(cache.carry())
    rows = lambda g: np.asarray(groups[g][0][blocks]).astype(
        np.float32).reshape(len(blocks) * BLOCK, -1)[:total]
    out = []
    for g in (0, 1):
        x = rows(g)
        if len(groups) == 4:
            scale = rows(g + 2)
            x = (x.reshape(total, scale.shape[1], -1)
                 * scale[:, :, None]).reshape(total, -1)
        out.append(x)
    return out


_COMPILED = {}      # what every seed shares: a jitted step a variant, ...


def _once(key, make):
    if key not in _COMPILED:
        _COMPILED[key] = make()
    return _COMPILED[key]


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
    lens = list(np.minimum(rng.integers(24, 201, 4), n_pos // 2)) \
        + [min(LONG, n_pos) - n_decode]
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    longest = max(lens) + n_decode

    def served(cfg, state_dtype=None):
        kv = dm.cache_config(cfg, BLOCK, 2 + -(-longest // BLOCK),
                             state_slots=LANES + 1)
        cache = kvc.PagedKVCache(kv)
        step = _once(("step", cfg.kv_dtype), lambda: jax.jit(
            dm.make_paged_step(cfg, kv), donate_argnums=(0,)))
        after = None
        if state_dtype is not None:
            # two jits with the 16 bits between them, as the fp8 control of
            # chip_check_olmoe.py has it: inside one, XLA may keep the
            # excess precision
            to_bits = _once("to_bits", lambda: jax.jit(
                lambda s: jax.lax.bitcast_convert_type(
                    s.astype(state_dtype), jnp.uint16), donate_argnums=(0,)))
            from_bits = _once("from_bits", lambda: jax.jit(
                lambda b: jax.lax.bitcast_convert_type(
                    b, state_dtype).astype(jnp.float32), donate_argnums=(0,)))

            def after(carry):
                groups, (windows, states) = kv.groups(carry)
                states = [from_bits(to_bits(s)) for s in states]
                return tuple(a for g in groups + [windows, states]
                             for a in g)

        return [run_sequence(step, cache, params, cfg, p, n_decode, after)
                for p in prompts]

    fwd = _once("reference", lambda: jax.jit(
        lambda prm, tok: reference.forward(config, prm, tok, True)))

    def against_reference(runs):
        out = {"positions": 0, "argmax_differs": 0, "worst": 0.0,
               "deficit": 0.0, "sq": 0.0, "n": 0, "kv_sq": 0.0,
               "kv_ref_sq": 0.0}
        pad = min(256, n_pos)
        with jax.default_matmul_precision("highest"):
            for fed, lg, cached, held in runs:
                # causal: padding after the sequence cannot reach back
                # into it; a few padded lengths, so a few compilations
                padded = np.zeros(-(-len(fed) // pad) * pad, np.int32)
                padded[:len(fed)] = fed
                logits, kv, states = fwd(params, jnp.asarray(padded))
                want = np.asarray(logits[len(fed) - n_decode:len(fed)])
                for got, ref in zip(cached, kv[0]):
                    ref = np.asarray(ref[:len(fed)]).reshape(len(fed), -1)
                    out["kv_sq"] += float(np.square(got - ref).sum())
                    out["kv_ref_sq"] += float(np.square(ref).sum())
                if len(fed) == longest:
                    # the longest sequence fills its padded length, so the
                    # reference's last state is the state after its last
                    # token, which is what the slot holds
                    assert len(padded) == len(fed)
                    out["state_first"], out["state_all"] = _state_errors(
                        cfg, held, states)
                del logits, kv, states
                out["logit_std"] = float(np.std(want))
                out["positions"] += len(lg)
                out["worst"] = max(out["worst"],
                                   float(np.abs(lg - want).max()))
                out["sq"] += float(np.square(lg - want).sum())
                out["n"] += lg.size
                chosen = lg.argmax(-1)
                out["argmax_differs"] += int(
                    (chosen != want.argmax(-1)).sum())
                out["deficit"] = max(out["deficit"], float(
                    (want.max(-1) - want[np.arange(len(lg)), chosen]).max()))
        return {"largest_logit_error": out["worst"],
                "rms_logit_error": (out["sq"] / out["n"]) ** 0.5,
                "cached_kv_relative_rms_error": (
                    out["kv_sq"] / out["kv_ref_sq"]) ** 0.5,
                "first_state_relative_rms_error": out["state_first"],
                "all_states_relative_rms_error": out["state_all"],
                "largest_deficit": out["deficit"],
                "argmax_differs_share": out["argmax_differs"]
                / out["positions"],
                "positions": out["positions"],
                "logit_std": out["logit_std"]}

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "prompt_lens": [int(n) for n in lens],
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "kv_tolerance": KV_TOLERANCE,
              "state_tolerance": STATE_TOLERANCE}
    runs = {"served_bf16": served(cfg),
            "control_bf16_state": served(cfg, jnp.bfloat16),
            "control_int8_cache": served(cfg.replace(kv_dtype="int8"))}
    for name, got in runs.items():
        result[name] = against_reference(got)
    result["seconds"] = round(time.time() - t0, 1)
    inside = {name: bool(
        result[name]["largest_logit_error"] <= LOGIT_TOLERANCE
        and result[name]["rms_logit_error"] <= RMS_TOLERANCE
        and result[name]["cached_kv_relative_rms_error"] <= KV_TOLERANCE
        and result[name]["first_state_relative_rms_error"]
        <= STATE_TOLERANCE)
        for name in runs}
    result["inside_tolerance"] = inside
    result["ok"] = inside == {"served_bf16": True,
                              "control_bf16_state": False,
                              "control_int8_cache": False}
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
        print("chip_check_granite: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "granite-4.0-h-micro-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    for i in range(args.seeds):
        result = one_seed(args.seed + 7919 * i, config, model, reference,
                          device, args.tiny_on_cpu)
        with open(os.path.join(out_dir, "chip_check_granite.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
