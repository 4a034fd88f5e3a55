"""On the chip, outside any timed window: the served Kimi-Linear step's
*logits*, cached latent rows and KDA states against the plain reference, at
the configuration's widths and its whole depth.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_kimi.py --seeds 2

Seeded weights as the cell makes them; 32 sequences at once, a lane each of
a 32-lane ``make_paged_step`` over the cache manager's pools (2048 latent
blocks by a shuffled table, 33 state slots shuffled): prompts of 150-200
tokens, fed a token a step (prefill here is token-feed), then 64 decoded
tokens each, teacher-forced with the step's own argmax, so every sequence
ends 214-264 positions long through all 27 layers.  The step's logits at the
last 64 positions of each sequence are compared with
``kimi_linear_ref.forward`` of the whole sequence (float32, highest matmul
precision, the served bf16 weights upcast a layer at a time, no cache, the
recurrence a position at a time, latent attention expanded), what the first
latent layer's pool holds of each sequence with the reference's ``[c |
k_pe]`` rows, and what three KDA layers' slots hold afterwards with the
reference's final state: the first (before any router), the first behind a
latent layer (layer 4, behind three routers: what a fault in the attention
alone moves most clearly) and the last.

Controls run the same way on the served run's tokens, each a server with one
fault judged by the same reference on the weights as served, and each has to
fall outside a limit: the state rounded to bfloat16 at every step; the decay
averaged over a head's channels (Gated DeltaNet's scalar gate for KDA's
fine-grained one); the delta correction dropped (``S + beta k v^T``); q and k
not normalised; the output gate dropped; ``kv_a_layernorm`` dropped; ``k_pe``
left out of the score; the scale ``128^-0.5``; ``routed_scaling`` dropped;
the shared expert dropped; the selection bias ignored; a slot not reset at
position 0 (the sequences start in the slots the served run left); the
weights rounded to fp8 (e4m3) on their way into the step (the precision next
below the one the configuration states: what ``kimi_linear_ref.check``'s
limits are set against).  One more run has to stay *inside* every limit: the
step with its three kernels replaced by their jnp paths (``jnp_paths``),
whose logits are also compared with the kernels' directly.  Exit code 1 if
the served path or ``jnp_paths`` is outside a tolerance on any seed, or a
control inside all of them.

``--engine`` goes the cell's own way: ``ServingClient`` -> ``ServingServer``
-> ``DecodeEngine`` with the cell's bucket and pool, 40 requests for 32 lanes
all sent at once (eight wait for a lane and start in a slot another sequence
left dirty), 250-700 positions each; the comparison is
``kimi_linear_ref.check``'s statistics, teacher-forced through the tokens, by
the depth a token was served at.  What goes any model's way there
(``to_fp8``, ``engine_requests``, ``engine_run``, ``by_depth``) is
``chip_check_nemotron.py``'s.
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (my chip runs, PR 46: call 1 read the
# served path, the jnp paths and two controls on seed 2147483777 before it ran
# out of memory, call 3 everything on seeds 2147491696 and 2147499615; 32
# sequences x 64 positions x 163,840 logits of standard deviation 0.96 each).
# Weights are the same bits on both sides.  What is left is the served path's
# bfloat16 (the input of every matmul, the cached rows and the convolutions'
# windows rounded to 8 bits of mantissa, through 27 layers) and what that
# noise does to the routing: at a position the closest of the 26 routers'
# choices over 256 experts beats the first expert left out by 1.8e-4 of
# selection score in the median (2.6e-5 at a tenth of positions), so the two
# sides swap an expert in some layer now and then, and a swap moves that
# position's logits.  The limits on logits therefore hold structure, and the
# ones read off the first mixer's state and the first latent layer's rows
# hold the precision:
#   the first KDA layer's state after the last token (before any router),
#     root-mean-square error as a share of its own root-mean-square: served
#     0.00352-0.00354 (the jnp paths the same to four digits); a bfloat16
#     state 0.0103-0.0104; a slot not reset 0.088-0.101; fp8 weights
#     0.109-0.112; the decay averaged 0.72-0.73, no delta correction
#     0.67-0.68, q and k not normalised 5e12.  The limit is 1.55 times the
#     served reading and 0.53 of the bfloat16-state one.
#   the first latent layer's rows (layer 3, behind three routers), the same
#     share over the whole sequence: served 0.0135-0.0151 (jnp 0.0132-0.0152);
#     kv_a_layernorm dropped 0.0470-0.0503 (which no limit on logits sees:
#     rms 0.076-0.083), a bfloat16 state 0.0206-0.0218, the bias ignored
#     0.033-0.036, routed_scaling dropped 0.047-0.054, fp8 0.222-0.226.  The
#     limit is 1.65 times the largest served reading and 0.53 of the smallest
#     without the norm.
#   the state of the first KDA layer behind a latent layer (layer 4): served
#     0.0227-0.0259 (jnp 0.0220-0.0254); k_pe left out 0.0354-0.0383, a
#     bfloat16 state 0.0374-0.0398, the bias ignored 0.059-0.060.
#   the last KDA layer's state (layer 25, behind 25 routers): served
#     0.101-0.109 (jnp 0.099-0.109); k_pe left out 0.160-0.169, a bfloat16
#     state 0.138-0.143, the bias ignored 0.216, fp8 0.77-0.78.
#   root-mean-square logit error: served 0.0708-0.0775 (jnp 0.0691-0.0775);
#     k_pe left out 0.109-0.118, a bfloat16 state 0.097-0.102, the bias
#     ignored 0.150-0.152, routed_scaling dropped 0.210-0.224, fp8 0.56-0.57,
#     a slot not reset 0.60-0.61, no shared expert 0.95-0.96, no output gate
#     0.99-1.00, no delta correction 1.01-1.02, the decay averaged 1.17-1.18,
#     q and k not normalised 1.36.  The limit is 1.19 times the largest served
#     reading and 0.84 of the smallest with k_pe left out.
#   largest logit error: served 0.65-0.78 (the largest of 1.0e9, where an
#     expert was swapped); routed_scaling dropped 1.38-1.43, fp8 3.6-3.8, the
#     faults in structure 4.0-8.8; the others 0.7-1.1, which a maximum cannot
#     tell from the served path.
#   the scale 128^-0.5 for 192^-0.5 moves every one of these by less than a
#     seed does (rms 0.0708 -> 0.0776 and 0.0775 -> 0.0845: +9.6% and +9.0%;
#     the last state +9.6% and +9.4%; the state behind the latent layer +6%):
#     scores of standard deviation 0.64 over contexts of 214-264 are a flat
#     softmax, and a temperature a fifth off moves it little.  No absolute
#     limit separates that from the seeds' own 0.0708-0.0775, so every run is
#     also judged *paired*: its rms logit error over the served run's on the
#     same tokens and weights.  The jnp paths read 0.976-1.003 of it, the
#     scale 1.090-1.096, every other control 1.07 (kv_a_layernorm) to 17.
# Each control falls outside one limit on every seed, not outside each.
RMS_TOLERANCE = 0.092
LOGIT_TOLERANCE = 1.2
LAST_STATE_TOLERANCE = 0.135
MID_STATE_TOLERANCE = 0.030
FIRST_STATE_TOLERANCE = 0.0055
ROWS_TOLERANCE = 0.025
PAIRED_RMS_TOLERANCE = 1.05
N_DECODE = 64
LANES = 32
BLOCK = 16
# (in the order they run: the one that needs the served run's cache first,
# the one that gives up the served weights last)
CONTROLS = ("slot_not_reset", "bf16_state", "scalar_decay",
            "no_delta_correction", "qk_not_normalised", "no_output_gate",
            "no_kv_norm", "k_pe_left_out", "scale_128",
            "routed_scaling_dropped", "no_shared_expert", "bias_ignored",
            "fp8_weights")
# the controls (and the run that must stay inside) whose change is a patch
# of the block or the step: it has to stand while the step is made and traced
PATCHED = ("scalar_decay", "no_delta_correction", "qk_not_normalised",
           "no_output_gate", "no_kv_norm", "no_shared_expert",
           "slot_not_reset", "jnp_paths")
# ... and those that are another configuration of the same block
CONFIGURED = {"scale_128": lambda cfg: cfg.replace(
    attention_multiplier=float(cfg.head_dim) ** -0.5),
    "routed_scaling_dropped": lambda cfg: cfg.replace(routed_scaling=1.0)}


def _base():
    from benchmark.run import load_module

    return load_module("tests", "chip_check_nemotron")


def patched(name):
    """The block or the step with one fault (modules patched): -> undo()."""
    import jax.numpy as jnp

    from paddle_tpu.models import kimi_linear as kl
    from paddle_tpu.pallas_kernels import kda_update as kda
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.pallas_kernels import ssm_update as ssm
    from paddle_tpu.serving import decode_model as dm

    saved = [(kl, "kda_mixer"), (kl, "_l2"), (kl, "_out_gate"),
             (kl, "_kv_norm"), (kl, "shared_part"), (moe, "routed_experts"),
             (kda, "state_update"), (dm, "latent_attention"),
             (dm._Recurrent, "__init__")]
    saved = [(mod, key, getattr(mod, key)) for mod, key in saved]
    if name == "scalar_decay":
        mixer = kl.kda_mixer

        class Scalar:
            def __init__(self, recur):
                self.window = recur.window
                self._delta = recur.delta

            def delta(self, l, alpha, *rest):
                mean = jnp.exp(jnp.mean(jnp.log(alpha), axis=-1,
                                        keepdims=True))
                return self._delta(l, jnp.broadcast_to(mean, alpha.shape),
                                   *rest)

        kl.kda_mixer = lambda cfg, p, l, h, recur: mixer(
            cfg, p, l, h, Scalar(recur))
    elif name == "no_delta_correction":
        def update(pool, slots, fresh, alpha, beta, k, v, q):
            lanes, heads, dim = v.shape
            state = ssm.started(fresh, jnp.take(pool, slots, axis=0,
                                                mode="clip"))
            s = state.reshape(lanes, dim, heads, dim)
            by_key = lambda x: jnp.swapaxes(x, 1, 2)[..., None]
            s = by_key(alpha) * s \
                + (beta[:, None, :, None] * by_key(k)) * v[:, None]
            o = jnp.sum(s * by_key(q), axis=1)
            return pool.at[slots].set(s.reshape(state.shape)), o

        kda.state_update = update
    elif name == "qk_not_normalised":
        kl._l2 = lambda x: x
    elif name == "no_output_gate":
        kl._out_gate = jnp.ones_like
    elif name == "no_kv_norm":
        kl._kv_norm = lambda x, g, eps: x
    elif name == "no_shared_expert":
        kl.shared_part = lambda p, x: jnp.zeros_like(x)
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
        dm.latent_attention = pa.latent_attention_reference

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None,
              round_state=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls); without it a sequence feeds its
    prompt and then the step's own argmax.  ``round_state`` rounds the state
    pools after every step (the bf16-state control).  -> per sequence (tokens
    fed, logits of the last n_decode positions, the first latent layer's
    cached rows of the sequence, the state in its slot of the first KDA
    layer, of the first one behind a latent layer and of the last)."""
    import numpy as np

    from paddle_tpu.pallas_kernels.paged_attention import gather_blocks

    kv = cache.config
    n = len(prompts)
    totals = [len(p) + n_decode for p in prompts]
    maxb = cfg.max_seq // BLOCK
    rng = np.random.default_rng(sum(totals))
    lanes = rng.permutation(LANES)[:n]
    slots = rng.permutation(np.arange(1, kv.state_slots))[:n]
    free = iter(rng.permutation(np.arange(1, kv.num_blocks)))
    rows = np.full((n, maxb), -1, np.int32)
    for i, total in enumerate(totals):
        for j in range(-(-total // BLOCK)):
            rows[i, j] = next(free)
    fed = [list(forced[i] if forced else prompts[i]) for i in range(n)]
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
        nxt = np.asarray(nxt)
        keep = [i for i in live if pos >= totals[i] - n_decode]
        lg = np.asarray(lg) if keep else None
        for i in live:
            if pos + 1 == len(fed[i]) and len(fed[i]) < totals[i]:
                fed[i].append(int(nxt[lanes[i]]))
        for i in keep:
            logits[i].append(lg[lanes[i]])
    pool = kv.latent_pools(cache.carry())[0]
    _groups, (_windows, states) = kv.groups(cache.carry())
    probes = [np.asarray(states[i]) for i in _probed(cfg)]
    out = []
    for i, total in enumerate(totals):
        table = np.maximum(rows[i], 0)[None]
        held = np.asarray(gather_blocks(pool, table)[0]).astype(
            np.float32)[:total]
        # the pool's rows are ``latent_row`` wide: the values, then zeros
        assert not held[:, kv.latent_width:].any()
        out.append((fed[i], np.stack(logits[i]), held[:, :kv.latent_width],
                    tuple(pool[slots[i]] for pool in probes)))
    return out


def _probed(cfg):
    """Which KDA layers' states are compared, by their place among the KDA
    layers: the first, the first behind a latent layer, the last."""
    behind = next(i for i, l in enumerate(cfg.kda_layers)
                  if l > cfg.latent_layers[0])
    return 0, behind, len(cfg.kda_layers) - 1


def reference_of(reference, config, params, runs, n_decode, probed):
    """What the reference makes of each served sequence: (logits of the last
    n_decode positions, the first latent layer's rows, the ``probed`` KDA
    layers' final states laid out as a slot holds them ``[keys, heads x
    values]``, the least margin of each of the last positions' choice of
    experts), on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    out = []
    with jax.default_matmul_precision("highest"):
        for fed, *_rest in runs:
            n = len(fed)
            # the state after the last token is wanted, so no padding: one
            # compile a distinct length (the lengths are drawn from few)
            logits, kept = fwd(params, jnp.asarray(fed, jnp.int32), True)
            as_slot = lambda s: np.asarray(s).transpose(1, 0, 2).reshape(
                s.shape[1], -1)
            margin = np.min([np.asarray(m) for m in kept["margins"]], axis=0)
            out.append((
                np.asarray(logits[n - n_decode:n]),
                np.asarray(kept["rows"][0]),
                tuple(as_slot(kept["states"][i]) for i in probed),
                margin[n - n_decode:]))
            del logits, kept
    return out


def compare(runs, refs):
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               rows_sq=0.0, rows_ref=0.0, first_sq=0.0, first_ref=0.0,
               mid_sq=0.0, mid_ref=0.0, last_sq=0.0, last_ref=0.0, std=0.0,
               per_seq=[], margins=[])
    for (_fed, lg, held, state), (want, ref_rows, ref_state, margin) \
            in zip(runs, refs):
        acc["std"] = float(np.std(want))
        acc["positions"] += len(lg)
        acc["worst"] = max(acc["worst"], float(np.abs(lg - want).max()))
        acc["sq"] += float(np.square(lg - want).sum())
        acc["n"] += lg.size
        chosen = lg.argmax(-1)
        differs = chosen != want.argmax(-1)
        deficit = want.max(-1) - want[np.arange(len(lg)), chosen]
        acc["differs"] += int(differs.sum())
        acc["deficit"] = max(acc["deficit"], float(deficit.max()))
        # what ``kimi_linear_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        for key, a, b in (("rows", held, ref_rows),
                          ("first", state[0], ref_state[0]),
                          ("mid", state[1], ref_state[1]),
                          ("last", state[2], ref_state[2])):
            acc[key + "_sq"] += float(np.square(a - b).sum())
            acc[key + "_ref"] += float(np.square(b).sum())
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    share = lambda key: (acc[key + "_sq"] / acc[key + "_ref"]) ** 0.5
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "rows_relative_rms_error": share("rows"),
            "first_state_relative_rms_error": share("first"),
            "state_behind_latent_relative_rms_error": share("mid"),
            "last_state_relative_rms_error": share("last"),
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


def inside(got, served):
    """Is a run inside every limit?  ``served`` is the served run's reading
    on the same tokens and weights (the paired limit; the served run itself
    reads 1 of it)."""
    return bool(got["largest_logit_error"] <= LOGIT_TOLERANCE
                and got["rms_logit_error"]
                <= PAIRED_RMS_TOLERANCE * served["rms_logit_error"]
                and got["rms_logit_error"] <= RMS_TOLERANCE
                and got["rows_relative_rms_error"] <= ROWS_TOLERANCE
                and got["first_state_relative_rms_error"]
                <= FIRST_STATE_TOLERANCE
                and got["state_behind_latent_relative_rms_error"]
                <= MID_STATE_TOLERANCE
                and got["last_state_relative_rms_error"]
                <= LAST_STATE_TOLERANCE)


def one_seed(seed, config, model, reference, device, tiny, controls):
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
    hi = min(264, n_pos) - n_decode
    # few distinct lengths: the reference compiles once a length and a kind
    lens = list(rng.choice(np.linspace(max(hi * 3 // 4, 1), hi, 4).astype(
        int), LANES))
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    blocks = 2048 if not tiny else LANES * (n_pos // BLOCK) + 8
    kv = dm.cache_config(cfg, BLOCK, blocks, state_slots=LANES + 1)
    steps = {}

    # donated: a second copy of the state pools (1.4e9 B) does not fit
    # beside the weights
    @functools.partial(jax.jit, donate_argnums=(0,))
    def to_bf16(carry):
        _groups, (windows, states) = kv.groups(carry)
        states = [jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7) for s in states]
        return tuple(kv.latent_pools(carry)) + tuple(windows) + tuple(states)

    def served(params, forced=None, fault=None, cache=None):
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
                             n_decode, forced,
                             to_bf16 if fault == "bf16_state" else None), \
                cache
        finally:
            if undo:
                undo()
            if key is not None:
                # a loaded step holds its temporaries (1.9e9 B where the
                # state moves by gather and scatter): beside 11.6e9 B of
                # weights and pools only a few fit, so a control's goes when
                # it has run; the served step stays for the other controls
                steps.pop(key).clear_cache()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "layers": cfg.layers,
              "sequence_lens": [int(n) + n_decode for n in lens],
              "paths": {"latent_attention": dm.attention_path(
                  cfg, kv, LANES, "latent"),
                  "state_update": dm.state_update_path(cfg, kv, LANES),
                  "experts": dm.experts_path(cfg, params, LANES)},
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "rows_tolerance": ROWS_TOLERANCE,
              "first_state_tolerance": FIRST_STATE_TOLERANCE,
              "state_behind_latent_tolerance": MID_STATE_TOLERANCE,
              "paired_rms_tolerance": PAIRED_RMS_TOLERANCE,
              "last_state_tolerance": LAST_STATE_TOLERANCE}
    run, used = served(params)
    refs = reference_of(reference, config, params, run, n_decode,
                        _probed(cfg))
    result["served_bf16"] = compare(run, refs)
    # as it goes: a later control that fails leaves these readings behind
    note = lambda name: print("chip_check_kimi: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    note("served_bf16")
    forced = [fed for fed, *_rest in run]
    kernel_logits = [lg for _fed, lg, *_rest in run]
    del run
    within = functools.partial(inside, served=result["served_bf16"])
    verdicts = {"served_bf16": within(result["served_bf16"])}
    if "jnp_paths" in controls:
        got, _cache = served(params, forced, "jnp_paths")
        result["jnp_paths"] = dict(
            compare(got, refs), largest_difference_from_the_kernels=max(
                float(np.abs(a - lg).max())
                for a, (_f, lg, *_r) in zip(kernel_logits, got)))
        verdicts["jnp_paths"] = within(result["jnp_paths"])
        note("jnp_paths")
        # a run's pools go before the next run makes its own: beside the
        # weights and the served run's, a third set (1.7e9 B) leaves no room
        # for a step that moves the state by gather and scatter
        del got, _cache
    for name in [c for c in CONTROLS if c in controls]:
        given, cache = params, None
        if name == "bias_ignored":
            given = {k: jnp.zeros_like(v) if k.endswith("expert_bias") else v
                     for k, v in params.items()}
        elif name == "k_pe_left_out":
            # the query's shared-key part at zero: k_pe adds nothing
            d, r = cfg.head_dim, cfg.latent_rope
            keep = jnp.tile(jnp.arange(d + r) < d, cfg.heads)
            given = {k: jnp.where(keep[None], v, 0).astype(v.dtype)
                     if k.endswith("_wq") else v for k, v in params.items()}
        elif name == "slot_not_reset":
            cache = used            # the slots as the served run left them
        elif name == "fp8_weights":
            given = _base().to_fp8(params)  # the last: the served set is gone
        got, _cache = served(given, forced, name, cache)
        result["control_" + name] = compare(got, refs)
        verdicts["control_" + name] = within(result["control_" + name])
        note("control_" + name)
        del got, given, cache, _cache
        if name == "slot_not_reset":
            used = None
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
    """40 requests for 32 lanes through client, server and engine: every
    band of depth with enough tokens inside ``kimi_linear_ref.check``'s two
    limits, for the requests that ran from the start and for those that
    waited for a lane and a slot another sequence left dirty."""
    base = _base()
    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests = base.engine_requests(seed, config, lanes, tiny)
    edges = (0, 64, 256) if not tiny else (0, 8)
    judged_from = base.MIN_JUDGED if not tiny else 8
    t0 = time.time()
    cases, said = base.engine_run(cfg, params, traffic, requests,
                                  int(traffic["kv_blocks"]))
    result = {"leg": "engine", "device": device.device_kind,
              "platform": device.platform, "seed": seed, "lanes": lanes,
              "requests": len(requests),
              "sequence_lens": [len(p) + n for p, n in requests],
              "differing_share_bound": reference.DIFFERING_SHARE_BOUND,
              "deficit_bound": reference.DEFICIT_BOUND}
    ok = said["slots_in_use"] == 0 and said["blocks"]["in_use"] == 0 \
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
        ok = ok and said["paths"]["attention"] == "pallas" and all(
            path == "pallas" for _b, path in said["paths"]["experts"]
            + said["paths"]["state_update"])
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
        print("chip_check_kimi: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "kimi-linear-48b-a3b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_linear_latent_decode_long.json"), args.tiny_on_cpu)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(args.seeds):
        if args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu, controls)
        with open(os.path.join(out_dir, "chip_check_kimi.jsonl"), "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
