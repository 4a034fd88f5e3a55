"""On the chip, outside any timed window: the served Solar-Open2 step's
*logits*, cached K and V and KDA states against the plain reference, at the
configuration's widths, the held share and the cell's sizes.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_solar.py

Seeded weights as the cell makes them (the bias balanced); 64 sequences at
once, a lane each of a 64-lane ``make_paged_step`` over the cache manager's
pools (K/V blocks by a shuffled table, 65 state slots shuffled): fifty-six
prompts of 200-550 tokens, four of 2,100-2,240 (past several chunks of the
K/V walk) and four that END at 6,400, the cell's longest request, fed a token
a step (prefill here is token-feed), then 48 decoded tokens each,
teacher-forced with the step's own argmax.  The step's logits at the last 48
positions of each sequence are compared with ``solar_open2_ref.forward`` of
the whole sequence (float32, highest matmul precision, the served bf16
weights upcast a layer at a time, no cache, the recurrence a position at a
time, attention a causal softmax in blocks of queries), what the first
softmax layer's pools hold of each sequence with the reference's K and V
(layer 0: before any router, so the served path's precision alone), and what
the first and the last KDA layer's slots hold afterwards with the
reference's final state.

Controls run on the served run's tokens, every sequence cut to its first 640
positions (a control is a fault in structure or precision, which shows at any
depth; the served path alone has to be shown at the cell's depths), each a
server with one fault judged by the same reference on the weights as served,
and each has to fall outside a limit: ``beta`` without its factor of 2; the
attention's gate left out; a rotation applied to q and k; gates not
renormalised; the state rounded to bfloat16 at every step; a slot not reset
at position 0 (the sequences start in the slots the served run left); the
weights rounded to fp8 (e4m3) on their way into the step (the precision next
below the one the configuration states: what ``solar_open2_ref.check``'s
limits are set against).  One more run has to stay *inside* every limit: the
step with its three kernels replaced by their jnp paths (``jnp_paths``).
Exit code 1 if the served path or ``jnp_paths`` is outside a tolerance on any
seed, or a control inside all of them.

``--kernel`` runs the state-update kernel alone at the cell's pool,
``[65, 128, 8192]`` float32 and 64 lanes of 64 heads, against gather,
``kda_update.advance``, scatter: the largest difference of the read-out and
of the pool, and the time of a call chained twenty times in one program, for
the kernel as the rule builds it (a slot one transfer of 4 MiB), with a slot
in two transfers of 32 heads' columns (``ssm_update.transfer_columns`` made
to answer 4096), and for the gather.

``--engine`` goes the cell's own way: ``ServingClient`` -> ``ServingServer``
-> ``DecodeEngine`` with the cell's bucket and pool, 80 requests for 64 lanes
all sent at once (sixteen wait for a lane and start in a slot another
sequence left dirty), 250-700 positions each; the comparison is
``solar_open2_ref.check``'s statistics, teacher-forced through the tokens, by
the depth a token was served at.  What goes any model's way there
(``to_fp8``, ``engine_requests``, ``by_depth``) is
``chip_check_nemotron.py``'s, ``engine_run`` ``chip_check_dots.py``'s.
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (my chip runs, PR 64, calls 4 and 5: seeds
# 2147483777 and 2147491696, first | second below where they differ; 64
# sequences x 48 positions x 24,576 logits of standard deviation 1.28 each,
# 384 of the positions past 2,048 and 192 of them at 6,352-6,400).  Weights
# are the same bits on both sides.  What is left is the served path's
# bfloat16 (the input of every matmul, the cached K and V and the
# convolutions' windows rounded to 8 bits of mantissa, through 8 layers) and
# what that noise does to the routing: 8 routers a token choose 8 of 320 by
# scores whose closest choice is won by 4.1e-4 in the median (6.2e-5 at a
# tenth of positions), so the two sides swap an expert in some layer now and
# then, and a swap moves that position's logits.  The limits on logits
# therefore hold structure, and the ones read off layer 0's K and V (before
# any router) and the states hold the precision.  The readings (the controls
# on every sequence's first 640 positions, where the served path reads the
# same as on the whole: its own line below):
#   layer 0's cached K and V, root-mean-square error as a share of their own
#     root-mean-square: served 0.00235 | 0.00235 (the jnp paths the same); fp8
#     weights 0.0469, a rotation applied 0.789 | 0.798; nothing else moves them.  The limit is
#     2.1 times the served reading and a ninth of fp8's.
#   the first KDA layer's state after the last token (layer 1, behind one
#     router), the same share: served 0.0202 | 0.0210 whole, 0.0195 | 0.0209
#     cut (jnp 0.0191 | 0.0214); a bfloat16 state 0.0215 | 0.0226, which no limit here can tell from the served
#     path (one router's swaps weigh more than 8 bits of mantissa: Kimi-Linear
#     leads with a KDA layer and reads 0.0035 against 0.0103, this model leads
#     with a softmax layer); a slot not reset 0.0413 | 0.0317, fp8 0.30, gates not
#     renormalised 0.63, beta not doubled 0.49, no attention gate 0.93, a
#     rotation 1.36.  The limit is 1.4 times the largest served reading;
#     a slot not reset falls outside the rms and last-state limits on both
#     seeds and outside this one on the first.
#   the share of that state's values that bfloat16 holds exactly: served
#     4.2e-5 (a float32 sum keeps mantissa below bfloat16's 8 bits); a
#     bfloat16 state 1.0, and little else about it differs from the served
#     path's readings (rms logit error 1.048 | 1.039 of it, the state 1.10 |
#     1.08).
#   the last KDA layer's state (layer 7, behind 7 routers): served 0.109 |
#     0.113 whole, 0.108 | 0.113 cut (jnp 0.108 | 0.115); a bfloat16 state
#     0.113 | 0.118, a slot not reset 0.282 | 0.227, beta not doubled 0.79, fp8 0.96, the faults in structure 1.3.
#   root-mean-square logit error: served 0.0948 | 0.0958 whole (0.101 |
#     0.092 on the eight sequences past 2,048), 0.0943 | 0.0967 cut (jnp 0.0946
#     | 0.0983, 1.003 | 1.017 of it; its logits up to 0.71 | 0.89 from the
#     kernels', where an expert was swapped); a bfloat16 state 0.0988 | 0.1005,
#     a slot not reset 0.237 | 0.188, beta not doubled 0.80, fp8 0.89,
#     gates not renormalised 1.38, no attention gate 1.50, a rotation 1.70.
#     The limit is 1.32 times the largest served reading and 0.69 of the
#     smallest control's; paired (a run's rms over the served path's on the
#     same tokens), 1.25 stands between the jnp paths' 1.017 and a slot not
#     reset's 1.94.
#   largest logit error: served 0.96 | 1.00 (the largest of 75 million, where
#     an expert was swapped; jnp 0.94 | 1.02); a bfloat16 state 1.07 | 1.04, a
#     slot not reset 1.89 | 1.75, the faults in structure 4.6-9.6.
# Each control falls outside one limit on every seed, not outside each.
RMS_TOLERANCE = 0.13
LOGIT_TOLERANCE = 1.5
FIRST_KV_TOLERANCE = 0.005
FIRST_STATE_TOLERANCE = 0.03
LAST_STATE_TOLERANCE = 0.16
PAIRED_RMS_TOLERANCE = 1.25
STATE_BF16_EXACT_TOLERANCE = 0.01
N_DECODE = 48
LANES = 64
MID, LONG = 4, 4
CUT = 640
BLOCK = 16
# (in the order they run: the one that needs the served run's cache first,
# the one that gives up the served weights last)
CONTROLS = ("slot_not_reset", "bf16_state", "beta_not_doubled",
            "no_attention_gate", "rotation_applied",
            "gates_not_renormalised", "fp8_weights")
# the controls (and the run that must stay inside) whose change is a patch
# of the block or the step: it has to stand while the step is made and traced
PATCHED = ("no_attention_gate", "rotation_applied", "gates_not_renormalised",
           "slot_not_reset", "jnp_paths")
# ... and those that are another configuration of the same block
CONFIGURED = {"beta_not_doubled": lambda cfg: cfg.replace(
    kda_neg_eigval=False)}


def _sibling(name):
    from benchmark.run import load_module

    return load_module("tests", name)


def patched(name):
    """The block or the step with one fault (modules patched): -> undo()."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import exaone_moe as ex
    from paddle_tpu.models import olmoe
    from paddle_tpu.models import solar_open2 as so
    from paddle_tpu.pallas_kernels import kda_update as kda
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving import decode_model as dm

    saved = [(so, "_attn_gate"), (so, "token_logits"), (ex, "_route"),
             (moe, "routed_experts"), (kda, "state_update"),
             (dm, "paged_attention"), (dm._Recurrent, "__init__")]
    saved = [(mod, key, getattr(mod, key)) for mod, key in saved]
    if name == "no_attention_gate":
        so._attn_gate = jnp.ones_like
    elif name == "rotation_applied":
        block = so.token_logits

        def token_logits(params, cfg, tok, pos, attend, *rest, **kw):
            turned = lambda l, q, k, v: attend(
                l, olmoe._rope(q, pos, cfg.rope_theta),
                olmoe._rope(k, pos, cfg.rope_theta), v)
            return block(params, cfg, tok, pos, turned, *rest, **kw)

        so.token_logits = token_logits
    elif name == "gates_not_renormalised":
        route = ex._route

        def _route(x, router, bias, k, scaling, *rest):
            # the block's choice, weighted by the scores as they are
            _gates, chosen = route(x, router, bias, k, scaling, *rest)
            score = jax.nn.sigmoid(jnp.dot(
                x, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return jnp.where(chosen, score, 0.0) * scaling, chosen

        ex._route = _route
    elif name == "slot_not_reset":
        init = dm._Recurrent.__init__

        def never_fresh(self, pool_of, taps, pos, *rest):
            init(self, pool_of, taps, pos, *rest)
            self._fresh = jnp.zeros_like(self._fresh)

        dm._Recurrent.__init__ = never_fresh
    elif name == "jnp_paths":
        moe.routed_experts = lambda h2, gates, live, *w: \
            moe.experts_reference(h2, gates, *w)
        kda.state_update = kda.state_update_reference
        dm.paged_attention = pa.paged_attention_reference

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None,
              cut=None, round_state=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls), ``cut`` the positions of a sequence
    to run at most; without ``forced`` a sequence feeds its prompt and then
    the step's own argmax.  ``round_state`` rounds the state pools after
    every step (the bf16-state control).  -> per sequence (tokens fed,
    logits of the last n_decode positions run, the first softmax layer's
    cached K and V of the sequence, the state in its slot of the first and
    of the last KDA layer)."""
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
    slots = rng.permutation(np.arange(1, kv.state_slots))[:n]
    free = iter(rng.permutation(np.arange(1, kv.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, total in enumerate(totals):
        for j in range(-(-total // BLOCK)):
            rows[i, j] = next(free)
    fed = [list(forced[i][:totals[i]] if forced else prompts[i])
           for i in range(n)]
    logits = [[] for _ in range(n)]
    for pos in range(max(totals)):
        tok, at, lens, mine = (np.zeros(LANES, np.int32) for _ in range(4))
        tables = np.full((LANES, maxb), -1, np.int32)
        live = [i for i in range(n) if pos < totals[i]]
        for i in live:
            b = lanes[i]
            tok[b], at[b], lens[b], mine[b] = fed[i][pos], pos, pos + 1, \
                slots[i]
            tables[b] = rows[i]
        carry, nxt, lg = step(cache.carry(), params, tok, at, tables, lens,
                              mine)[:3]
        if round_state is not None:
            carry = round_state(carry)
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
    pools, (_windows, states) = kv.groups(cache.carry())
    first, last = (np.asarray(states[i]) for i in (0, len(states) - 1))
    out = []
    for i, total in enumerate(totals):
        table = np.maximum(rows[i], 0)[None]
        held = [np.asarray(gather_blocks(group[0], table)[0]).astype(
            np.float32).reshape(-1, kv.heads * kv.head_dim)[:total]
            for group in pools[:2]]
        out.append((fed[i], np.stack(logits[i]), held,
                    (first[slots[i]], last[slots[i]])))
    return out


def reference_of(reference, config, params, runs, n_decode, cut):
    """What the reference makes of each served sequence, on the host: per
    sequence {positions run: (logits of its last n_decode positions, the
    first softmax layer's K and V, the first and the last KDA layer's final
    state laid out as a slot holds it ``[keys, heads x values]``, the least
    margin of each of those positions' choice of experts)} for the whole
    sequence and for its first ``cut`` positions (the controls')."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    as_slot = lambda s: np.asarray(s).transpose(1, 0, 2).reshape(
        s.shape[1], -1)
    out = []
    with jax.default_matmul_precision("highest"):
        for fed, *_rest in runs:
            mine = {}
            for e in sorted({len(fed), min(len(fed), cut)}):
                # the state after the last token is wanted, so no padding:
                # one compile a distinct length (drawn from few)
                at = np.arange(e - n_decode, e)
                logits, kept = fwd(params, jnp.asarray(fed[:e], jnp.int32),
                                   True, rows=at)
                margin = np.min([np.asarray(m) for m in kept["margins"]],
                                axis=0)
                mine[e] = (np.asarray(logits),
                           [np.asarray(x) for x in kept["kv"][0]],
                           (as_slot(kept["states"][0]),
                            as_slot(kept["states"][-1])), margin[at])
                del logits, kept
            out.append(mine)
    return out


def compare(runs, refs):
    import numpy as np

    keys = ("kv", "first", "last")
    exact = []
    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               long_sq=0.0, long_n=0, long_positions=0, std=0.0, per_seq=[],
               margins=[], **{k + s: 0.0 for k in keys
                              for s in ("_sq", "_ref")})
    for (fed, lg, held, state), ref in zip(runs, refs):
        want, ref_kv, ref_state, margin = ref[len(fed)]
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
        # what ``solar_open2_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        # the low 16 bits of a float32 that bfloat16 holds exactly are zero
        exact.append(float(np.mean(
            np.ascontiguousarray(state[0], np.float32).view(np.uint32)
            & 0xFFFF == 0)))
        for key, a, b in (("kv", np.concatenate(held, 1),
                           np.concatenate(ref_kv, 1)),
                          ("first", state[0], ref_state[0]),
                          ("last", state[1], ref_state[1])):
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
            "first_kv_relative_rms_error": share("kv"),
            "first_state_relative_rms_error": share("first"),
            "last_state_relative_rms_error": share("last"),
            "first_state_bf16_exact_share": float(np.mean(exact)),
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
                and got["first_kv_relative_rms_error"] <= FIRST_KV_TOLERANCE
                and got["first_state_relative_rms_error"]
                <= FIRST_STATE_TOLERANCE
                and got["last_state_relative_rms_error"]
                <= LAST_STATE_TOLERANCE
                and got["first_state_bf16_exact_share"]
                <= STATE_BF16_EXACT_TOLERANCE)


def one_seed(seed, config, model, reference, device, tiny, controls):
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
    longest = 6400 if not tiny else n_pos
    hi = min(600, n_pos * 3 // 4) - n_decode
    # few distinct lengths: the reference compiles once a length
    lens = list(rng.choice(np.linspace(max(hi * 2 // 5, 1), hi, 4).astype(
        int), LANES - MID - LONG))
    lens += [2100, 2192] * (MID // 2) if not tiny else [hi] * MID
    lens += [longest - n_decode] * LONG
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    # the blocks the sequences hold, and as many again: the table is shuffled
    blocks = 2 * sum(-(-(int(n) + n_decode) // BLOCK) for n in lens) + 8
    kv = dm.cache_config(cfg, BLOCK, blocks, state_slots=LANES + 1)
    steps = {}

    # donated: a second copy of the state pools (1.7e9 B) does not fit
    # beside the weights
    @functools.partial(jax.jit, donate_argnums=(0,))
    def to_bf16(carry):
        pools, (windows, states) = kv.groups(carry)
        states = [jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7) for s in states]
        return tuple(a for group in pools for a in group) + tuple(windows) \
            + tuple(states)

    def served(params, forced=None, fault=None, cut=None, cache=None):
        built = CONFIGURED[fault](cfg) if fault in CONFIGURED else cfg
        # the patch has to stand while the step is made and traced
        key = fault if fault in PATCHED or fault in CONFIGURED else None
        undo = patched(key) if key in PATCHED else None
        try:
            if key not in steps:
                steps[key] = jax.jit(dm.make_paged_step(built, kv),
                                     donate_argnums=(0,))
            cache = cache or kvc.PagedKVCache(kv)
            return run_batch(steps[key], cache, params, built, prompts,
                             n_decode, forced, cut,
                             to_bf16 if fault == "bf16_state" else None), \
                cache
        finally:
            if undo:
                undo()
            if key is not None:
                # a control's compiled step goes when it has run
                steps.pop(key).clear_cache()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "layers": cfg.layers, "controls_cut_to": cut,
              "sequence_lens": [int(n) + n_decode for n in lens],
              "paths": {"attention": dm.attention_path(cfg, kv, LANES),
                        "state_update": dm.state_update_path(cfg, kv, LANES),
                        "experts": dm.experts_path(cfg, params, LANES)},
              "state_update_columns": dm.state_update_columns(cfg, kv),
              "chunk_positions": dm.chunk_positions(cfg, kv, LANES),
              "experts_f_chunk": dm.experts_chunk(cfg),
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "first_kv_tolerance": FIRST_KV_TOLERANCE,
              "first_state_tolerance": FIRST_STATE_TOLERANCE,
              "last_state_tolerance": LAST_STATE_TOLERANCE,
              "paired_rms_tolerance": PAIRED_RMS_TOLERANCE,
              "state_bf16_exact_tolerance": STATE_BF16_EXACT_TOLERANCE}
    run, _cache = served(params)
    del _cache
    refs = reference_of(reference, config, params, run, n_decode, cut)
    result["served_bf16"] = compare(run, refs)
    # as it goes: a later control that fails leaves these readings behind
    note = lambda name: print("chip_check_solar: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    note("served_bf16")
    forced = [fed for fed, *_rest in run]
    del run
    # the served path on the controls' positions: what they are paired with
    short, used = served(params, forced, cut=cut)
    result["served_bf16_cut"] = compare(short, refs)
    note("served_bf16_cut")
    kernel_logits = [lg for _fed, lg, *_rest in short]
    del short
    verdicts = {"served_bf16": inside(result["served_bf16"],
                                      result["served_bf16"]),
                "served_bf16_cut": inside(result["served_bf16_cut"],
                                          result["served_bf16_cut"])}
    within = functools.partial(inside, served=result["served_bf16_cut"])

    def control(name, given, cache=None):
        got, _cache = served(given, forced, name, cut, cache)
        result["control_" + name] = compare(got, refs)
        verdicts["control_" + name] = within(result["control_" + name])
        note("control_" + name)

    # first, in the slots as the served run left them; its pools (2.7e9 B)
    # go before any other run makes its own: beside the weights a third set
    # leaves no room for a step that moves the state by gather and scatter
    # (3.8e9 B of temporaries)
    if "slot_not_reset" in controls:
        control("slot_not_reset", params, used)
    del used
    if "jnp_paths" in controls:
        got, _cache = served(params, forced, "jnp_paths", cut)
        result["jnp_paths"] = dict(
            compare(got, refs),
            largest_difference_from_the_kernels=max(
                float(np.abs(a - lg).max())
                for a, (_f, lg, *_r) in zip(kernel_logits, got)))
        verdicts["jnp_paths"] = within(result["jnp_paths"])
        note("jnp_paths")
        del got, _cache
    for name in [c for c in CONTROLS[1:] if c in controls]:
        # fp8 the last: the served set is gone
        control(name, _sibling("chip_check_nemotron").to_fp8(params)
                if name == "fp8_weights" else params)
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


def kernel_leg(device, tiny, repeat=20):
    """The state-update kernel alone at the cell's pool against gather,
    ``advance``, scatter: errors and the time of a call, a form a line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.pallas_kernels import kda_update as ku
    from paddle_tpu.pallas_kernels import ssm_update as su

    slots_n, dim, heads, lanes = (65, 128, 64, 64) if not tiny \
        else (5, 8, 4, 3)
    rng = np.random.default_rng(64)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    slots = jnp.asarray(1 + rng.permutation(slots_n - 1)[:lanes], jnp.int32)
    fresh = jnp.asarray([i % 5 == 1 for i in range(lanes)])
    alpha = jnp.asarray(rng.uniform(0.2, 1.0, (lanes, heads, dim)),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (lanes, heads)), jnp.float32)
    k, v, q = (f(lanes, heads, dim) / np.sqrt(dim) for _ in range(3))
    operands = (alpha, beta, k, v, q)
    shape = (slots_n, dim, heads * dim)
    # the same pool for every form, made anew (a call donates it)
    fill = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(1), shape,
                                             jnp.float32))
    path = ku.update_path(shape, jnp.float32, lanes, heads)
    moved = 2 * lanes * dim * heads * dim * 4

    def chained(update):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(pool, slots, alpha, beta, k, v, q):
            none = jnp.zeros(lanes, bool)

            def body(_i, carry):
                pool, o = carry
                pool, o2 = update(pool, slots, none, alpha, beta, k, v, q)
                return pool, o + o2
            return jax.lax.fori_loop(0, repeat, body, (pool, jnp.zeros_like(
                v)))
        return run

    want_pool, want_o = jax.jit(ku.state_update_reference)(
        fill(), slots, fresh, *operands)
    forms = {"gather": (ku.state_update_reference, None)}
    if path == "pallas":
        forms["kernel_whole_slot"] = (ku.state_update, None)
        forms["kernel_two_transfers"] = (ku.state_update, heads * dim // 2)
    result = {"leg": "kernel", "device": device.device_kind,
              "platform": device.platform, "pool": list(shape),
              "lanes": lanes, "heads": heads, "path": path,
              "transfer_columns": su.transfer_columns(shape, heads),
              "bytes_moved_per_call": moved, "forms": {}}
    ok = path == "pallas" or device.platform != "tpu"
    columns = su.transfer_columns
    for name, (update, cols) in forms.items():
        if cols:
            su.transfer_columns = lambda shape, groups=1, _c=cols: _c
        try:
            got_pool, got_o = jax.jit(update)(fill(), slots, fresh,
                                              *operands)
            err_o = float(jnp.abs(got_o - want_o).max())
            err_pool = float(jnp.abs(got_pool - want_pool).max())
            del got_pool, got_o
            run = chained(update)
            pool, _o = run(fill(), slots, *operands)
            jax.block_until_ready(pool)
            t0 = time.perf_counter()
            pool, _o = run(pool, slots, *operands)
            jax.block_until_ready(pool)
            ms = (time.perf_counter() - t0) * 1e3 / repeat
            del pool, _o
        finally:
            su.transfer_columns = columns
        result["forms"][name] = {
            "largest_read_out_difference": err_o,
            "largest_pool_difference": err_pool, "ms_per_call": ms,
            "bytes_per_s": moved / (ms / 1e3)}
        ok = ok and err_o <= 2e-5 and err_pool <= 2e-5
        print("chip_check_solar: kernel %s %s" % (
            name, json.dumps(result["forms"][name])), file=sys.stderr,
            flush=True)
    result["ok"] = bool(ok)
    if tiny:
        result["not_a_chip_result"] = True
    return result


def engine_leg(seed, config, model, reference, device, tiny, traffic):
    """80 requests for 64 lanes through client, server and engine: every
    band of depth with enough tokens inside ``solar_open2_ref.check``'s two
    limits, for the requests that ran from the start and for those that
    waited for a lane and a slot another sequence left dirty; the step's
    three kernels counted as used and none as fallen back."""
    base = _sibling("chip_check_nemotron")
    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests = base.engine_requests(seed, config, lanes, tiny)
    edges = (0, 64, 256) if not tiny else (0, 8)
    judged_from = base.MIN_JUDGED if not tiny else 8
    t0 = time.time()
    cases, said = _sibling("chip_check_dots").engine_run(
        cfg, params, traffic, requests, int(traffic["kv_blocks"]),
        model="solar_check")
    result = {"leg": "engine", "device": device.device_kind,
              "platform": device.platform, "seed": seed, "lanes": lanes,
              "requests": len(requests),
              "sequence_lens": [len(p) + n for p, n in requests],
              "differing_share_bound": reference.DIFFERING_SHARE_BOUND,
              "deficit_bound": reference.DEFICIT_BOUND}
    ok = said["blocks"]["in_use"] == 0 \
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
            and used == {"pallas_kernel_used_total{kernel=kda_update}",
                         "pallas_kernel_used_total{kernel=paged_attention}",
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
    ap.add_argument("--kernel", action="store_true",
                    help="the state-update kernel alone against advance at "
                    "the cell's pool, and that alone")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: the configuration's tiny sizes on any "
                    "backend; nothing it prints is a chip result")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny_on_cpu:
        print("chip_check_solar: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "solar-open2-250b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_linear_gqa_moe_decode_long.json"), args.tiny_on_cpu)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(1 if args.kernel else args.seeds):
        if args.kernel:
            result = kernel_leg(device, args.tiny_on_cpu)
        elif args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu, controls)
        with open(os.path.join(out_dir, "chip_check_solar.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
