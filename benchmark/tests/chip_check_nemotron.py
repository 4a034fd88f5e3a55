"""On the chip, outside any timed window: the served Nemotron-H step's
*logits*, cached K and V and recurrent state against the plain reference, at
the configuration's widths and its whole depth.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_nemotron.py --seeds 2

Seeded weights as the cell makes them; 32 sequences at once, a lane each of
a 32-lane ``make_paged_step`` over the cache manager's pools as the cell
times them (2048 blocks by a shuffled table, 33 state slots shuffled):
prompts of 200-260 tokens, fed a token a step (prefill here is token-feed),
then 64 decoded tokens each, teacher-forced with the step's own argmax, so
every sequence ends 264-324 positions long through all 52 layers.  The
step's logits at the last 64 positions of each sequence are compared with
``nemotron_h_ref.forward`` of the whole sequence (float32, highest matmul
precision, the served bf16 weights upcast a layer at a time, no cache, the
scan a position at a time), what the first attention layer's pool holds of
each sequence with the reference's K and V, and what the first and the last
mamba layer's slot holds afterwards with the reference's final state.

Controls run the same way on the served run's tokens, each a server with
one fault judged by the same reference on the weights as served, and each
has to fall outside a limit: the state rounded to bfloat16 at every step;
every head reading group 0's B and C; relu for relu^2; ``routed_scaling``
dropped; the shared expert dropped; the selection bias ignored; a slot not
reset at position 0 (the sequences start in the slots the served run left);
the weights rounded to fp8 (e4m3) on their way into the step (the precision
next below the one the configuration states: what
``nemotron_h_ref.check``'s limits are set against).  One more run has to
stay *inside* every limit: the step with its three kernels replaced by
their jnp paths (``jnp_paths``), whose logits are also compared with the
kernels' directly.  Exit code 1 if the served path or ``jnp_paths`` is
outside a tolerance on any seed, or a control inside all of them.

``--engine`` goes the cell's own way (``engine_leg``): ``ServingClient`` ->
``ServingServer`` -> ``DecodeEngine`` with the cell's bucket and pool, 40
requests for 32 lanes all sent at once (eight wait for a lane and start in
a slot another sequence left dirty), 250-700 positions each.  The server
returns tokens, so the comparison is ``nemotron_h_ref.check``'s,
teacher-forced through the tokens, by the depth a token was served at.  One
control goes the same way, an engine whose step never resets a slot: the
requests that waited have to read half as much again off the reference's
argmax as they did served, in some band of depth.
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (PERF.md section 6, PR 41: call 2 read one
# seed and call 3 two more, 32 sequences x 64 positions x 16,384 logits of
# standard deviation 1.04 each; the three together set the limits below;
# call 5 ran the engine leg and call 7 this one as committed, on a fourth
# seed).  Weights are the same bits on both sides.
# What is left is the served path's bfloat16 (the input of every matmul, the
# cached K and V and the convolution's window rounded to 8 bits of mantissa,
# through 52 layers) and what that noise does to the routing: at a position
# the closest of the 23 routers' choices beats the first expert left out by
# 3.2e-4 of selection score in the median (5e-5 at a tenth of positions), so
# the two sides swap an expert in some layer now and then, and a swap moves
# that position's logits.  The limits on logits therefore hold structure, and
# the one read off the first mixer's state, before any router, holds the
# precision:
#   the first mamba layer's state after the last token, root-mean-square
#     error as a share of its own root-mean-square: served 0.00296-0.00320
#     (the jnp paths the same bits); a bfloat16 state 0.00505-0.00686; a slot
#     not reset 0.0076-0.066; fp8 weights 0.095-0.105; every head on group 0
#     1.09-1.25.  The limit is 1.3 times the largest served reading and 0.83
#     of the smallest bfloat16-state one.
#   the last mamba layer's state (layer 50, behind 22 routers), the same:
#     served 0.082-0.107, the jnp paths 0.086-0.097, a bfloat16 state
#     0.102-0.113 (not held by this limit), a slot not reset 0.150-0.202, the
#     bias ignored 0.29-0.33, fp8 0.40-0.43.
#   the first attention layer's K and V (layer 5, behind two routers), the
#     same share over the whole sequence: served 0.0254-0.0309, the jnp paths
#     0.0255-0.0307; the bias ignored 0.112-0.124, a slot not reset
#     0.126-0.150, routed_scaling dropped 0.171-0.194, fp8 0.178-0.187.
#   root-mean-square logit error: served 0.0935-0.1052, the jnp paths
#     0.0939-0.1050, a bfloat16 state 0.098-0.106 (not held by this limit); a
#     slot not reset 0.151-0.212, the bias ignored 0.281-0.295, fp8 weights
#     0.401-0.413, routed_scaling dropped 0.397-0.428, every head on group 0
#     0.583-0.646, relu for relu^2 0.952-0.962, no shared expert 1.39.  The
#     limit is 1.24 times the largest served reading and 0.86 of the smallest
#     of those.
#   largest logit error: served 2.16-2.30 (the largest of 33 million, where an
#     expert was swapped); relu 5.1-5.3, no shared expert 7.6-8.0, group 0
#     4.1-4.2, fp8 3.03-3.28; the others 2.2-3.0, which a maximum cannot tell
#     from the served path.
# Each control falls outside one limit on every seed, not outside each.
RMS_TOLERANCE = 0.13
LOGIT_TOLERANCE = 3.0
LAST_STATE_TOLERANCE = 0.14
FIRST_STATE_TOLERANCE = 0.0042
KV_TOLERANCE = 0.045
N_DECODE = 64
LANES = 32
BLOCK = 16
CONTROLS = ("bf16_state", "group_0_for_every_head", "relu_for_relu2",
            "routed_scaling_dropped", "no_shared_expert", "bias_ignored",
            "slot_not_reset", "fp8_weights")
# the controls (and the run that must stay inside) whose change is a patch
# of the block or the step: it has to stand while the step is traced
PATCHED = ("group_0_for_every_head", "relu_for_relu2", "no_shared_expert",
           "slot_not_reset", "jnp_paths")


def patched(name):
    """The block or the step with one fault (modules patched): -> undo()."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import nemotron_h as nh
    from paddle_tpu.pallas_kernels import moe_experts as moe
    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.pallas_kernels import ssm_update as ssm
    from paddle_tpu.serving import decode_model as dm

    saved = [(nh._granite, "mamba_mixer"), (nh, "_relu2_mlp"),
             (nh, "shared_part"), (moe, "relu2_experts"),
             (ssm, "state_update"), (dm, "paged_attention"),
             (dm._Recurrent, "__init__")]
    saved = [(mod, key, getattr(mod, key)) for mod, key in saved]
    if name == "group_0_for_every_head":
        mixer = nh._granite.mamba_mixer

        class Group0:
            def __init__(self, recur):
                self.window = recur.window
                self._advance = recur.advance

            def advance(self, l, decay, dx, b, c):
                first = lambda x: jnp.broadcast_to(x[:, :1], x.shape)
                return self._advance(l, decay, dx, first(b), first(c))

        nh._granite.mamba_mixer = lambda cfg, p, l, h, recur: mixer(
            cfg, p, l, h, Group0(recur))
    elif name == "relu_for_relu2":
        def experts(h2, gates, live, up, down):
            hx = h2.astype(up.dtype)
            h = jnp.einsum("bh,efh->ebf", hx, up,
                           preferred_element_type=jnp.float32)
            # relu, exactly, in a form XLA:CPU does not fuse into the dot
            # (relu2_reference says why): the rehearsal runs there
            act = 0.5 * (h + jnp.abs(h))
            y = jnp.einsum("ebf,efh->ebh", act.astype(down.dtype), down,
                           preferred_element_type=jnp.float32)
            return jnp.sum(y * gates.T[:, :, None], axis=0)

        moe.relu2_experts = experts
        nh._relu2_mlp = lambda x, up, down: nh._mm(
            jax.nn.relu(nh._mm(x, up)), down)
    elif name == "no_shared_expert":
        nh.shared_part = lambda p, x: jnp.zeros_like(x)
    elif name == "slot_not_reset":
        init = dm._Recurrent.__init__

        def never_fresh(self, pool_of, taps, pos, *rest):
            init(self, pool_of, taps, pos, *rest)
            self._fresh = jnp.zeros_like(self._fresh)

        dm._Recurrent.__init__ = never_fresh
    elif name == "jnp_paths":
        moe.relu2_experts = lambda h2, gates, live, up, down: \
            moe.relu2_reference(h2, gates, up, down)
        ssm.state_update = ssm.state_update_reference
        dm.paged_attention = pa.paged_attention_reference

    def undo():
        for mod, key, fn in saved:
            setattr(mod, key, fn)

    return undo


def run_batch(step, cache, params, cfg, prompts, n_decode, forced=None,
              round_state=None):
    """Every sequence in a lane of its own through the step, all started
    together; a lane idles once its sequence has ended.  ``forced`` gives
    every token to feed (the controls); without it a sequence feeds its
    prompt and then the step's own argmax.  ``round_state`` rounds the
    state pools after every step (the bf16-state control).  -> per sequence
    (tokens fed, logits of the last n_decode positions, the first attention
    layer's cached K and V of the sequence, the first and the last mamba
    layer's state in its slot)."""
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
    (k, v), (_windows, states) = kv.groups(cache.carry())
    first_state = np.asarray(states[0])
    last_state = np.asarray(states[-1])
    out = []
    for i, total in enumerate(totals):
        table = np.maximum(rows[i], 0)[None]
        whole = [np.asarray(gather_blocks(pool[0], table)[0]).astype(
            np.float32)[:total] for pool in (k, v)]
        out.append((fed[i], np.stack(logits[i]), whole,
                    (first_state[slots[i]], last_state[slots[i]])))
    return out


def reference_of(reference, config, params, runs, n_decode):
    """What the reference makes of each served sequence: (logits of the last
    n_decode positions, the first attention layer's K and V, the first and
    the last mamba layer's final state laid out as a slot holds it ``[N,
    I]``, the least margin of each of the last positions' choice of
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
            as_slot = lambda s: np.asarray(s).transpose(2, 0, 1).reshape(
                s.shape[2], -1)
            margin = np.min([np.asarray(m) for m in kept["margins"]], axis=0)
            out.append((
                np.asarray(logits[n - n_decode:n]),
                [np.asarray(a).reshape(n, -1) for a in kept["kv"][0]],
                (as_slot(kept["states"][0]), as_slot(kept["states"][-1])),
                margin[n - n_decode:]))
            del logits, kept
    return out


def compare(runs, refs):
    import numpy as np

    acc = dict(positions=0, differs=0, worst=0.0, deficit=0.0, sq=0.0, n=0,
               kv_sq=0.0, kv_ref=0.0, first_sq=0.0, first_ref=0.0,
               last_sq=0.0, last_ref=0.0, std=0.0, per_seq=[], margins=[])
    for (_fed, lg, whole, state), (want, ref_kv, ref_state, margin) \
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
        # what ``nemotron_h_ref.check`` would read of this sequence alone
        acc["per_seq"].append((float(differs.mean()), float(deficit.max())))
        acc["margins"].append(margin)
        for a, b in zip(whole, ref_kv):
            acc["kv_sq"] += float(np.square(a - b).sum())
            acc["kv_ref"] += float(np.square(b).sum())
        for key, a, b in (("first", state[0], ref_state[0]),
                          ("last", state[1], ref_state[1])):
            acc[key + "_sq"] += float(np.square(a - b).sum())
            acc[key + "_ref"] += float(np.square(b).sum())
    spread = lambda xs: [round(float(np.quantile(xs, q)), 4)
                         for q in (0.0, 0.5, 1.0)]
    share = lambda key: (acc[key + "_sq"] / acc[key + "_ref"]) ** 0.5
    return {"largest_logit_error": acc["worst"],
            "rms_logit_error": (acc["sq"] / acc["n"]) ** 0.5,
            "kv_relative_rms_error": share("kv"),
            "first_state_relative_rms_error": share("first"),
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


def inside(got):
    return bool(got["largest_logit_error"] <= LOGIT_TOLERANCE
                and got["rms_logit_error"] <= RMS_TOLERANCE
                and got["kv_relative_rms_error"] <= KV_TOLERANCE
                and got["first_state_relative_rms_error"]
                <= FIRST_STATE_TOLERANCE
                and got["last_state_relative_rms_error"]
                <= LAST_STATE_TOLERANCE)


def to_fp8(params):
    """The weights through 8 bits (e4m3) and back, the served set given up
    array by array (two sets do not fit).  Two jits with the 8 bits between
    them: inside one, XLA may keep the excess precision and drop the pair
    of converts."""
    import jax
    import jax.numpy as jnp

    down = jax.jit(lambda w: jax.lax.bitcast_convert_type(
        w.astype(jnp.float8_e4m3fn), jnp.uint8))
    up = jax.jit(lambda b, dt: jax.lax.bitcast_convert_type(
        b, jnp.float8_e4m3fn).astype(dt), static_argnums=(1,))
    given = {}
    for key in sorted(params):
        w = params.pop(key)
        given[key] = up(down(w), w.dtype)
        del w
    return given


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
    hi = min(324, n_pos) - n_decode
    # few distinct lengths: the reference compiles once a length and a kind
    lens = list(rng.choice(np.linspace(max(hi * 3 // 4, 1), hi, 4).astype(
        int), LANES))
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, n)] for n in lens]
    blocks = 2048 if not tiny else LANES * (n_pos // BLOCK) + 8
    kv = dm.cache_config(cfg, BLOCK, blocks, state_slots=LANES + 1)
    steps = {}

    # donated: a second copy of the state pools (1.5e9 B) does not fit
    # beside the weights
    @functools.partial(jax.jit, donate_argnums=(0,))
    def to_bf16(carry):
        groups, (windows, states) = kv.groups(carry)
        states = [jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7) for s in states]
        return tuple(a for g in groups + [windows, states] for a in g)

    def served(params, forced=None, fault=None, cache=None):
        built = cfg.replace(routed_scaling=1.0) \
            if fault == "routed_scaling_dropped" else cfg
        # the patch has to stand while the step is traced: at its first call
        key = fault if fault in PATCHED + ("routed_scaling_dropped",) \
            else None
        undo = patched(key) if key in PATCHED else None
        if key not in steps:
            steps[key] = jax.jit(dm.make_paged_step(built, kv),
                                 donate_argnums=(0,))
        cache = cache or kvc.PagedKVCache(kv)
        try:
            return run_batch(steps[key], cache, params, built, prompts,
                             n_decode, forced,
                             to_bf16 if fault == "bf16_state" else None), \
                cache
        finally:
            if undo:
                undo()

    t0 = time.time()
    result = {"device": device.device_kind, "platform": device.platform,
              "seed": seed, "lanes": LANES, "blocks": blocks,
              "layers": cfg.layers,
              "sequence_lens": [int(n) + n_decode for n in lens],
              "paths": {"attention": dm.attention_path(cfg, kv, LANES),
                        "state_update": dm.state_update_path(cfg, kv, LANES),
                        "experts": dm.experts_path(cfg, params, LANES)},
              "tolerance": LOGIT_TOLERANCE, "rms_tolerance": RMS_TOLERANCE,
              "kv_tolerance": KV_TOLERANCE,
              "first_state_tolerance": FIRST_STATE_TOLERANCE,
              "last_state_tolerance": LAST_STATE_TOLERANCE}
    run, used = served(params)
    refs = reference_of(reference, config, params, run, n_decode)
    result["served_bf16"] = compare(run, refs)
    # as it goes: a later control that fails leaves these readings behind
    note = lambda name: print("chip_check_nemotron: %s %s" % (
        name, json.dumps(result[name])), file=sys.stderr, flush=True)
    note("served_bf16")
    forced = [fed for fed, *_rest in run]
    kernel_logits = [lg for _fed, lg, *_rest in run]
    del run
    verdicts = {"served_bf16": inside(result["served_bf16"])}
    if "jnp_paths" in controls:
        got, _cache = served(params, forced, "jnp_paths")
        result["jnp_paths"] = dict(
            compare(got, refs), largest_difference_from_the_kernels=max(
                float(np.abs(a - lg).max())
                for a, (_f, lg, *_r) in zip(kernel_logits, got)))
        verdicts["jnp_paths"] = inside(result["jnp_paths"])
        note("jnp_paths")
        del got
    for name in [c for c in CONTROLS if c in controls]:
        given, cache = params, None
        if name == "bias_ignored":
            given = {k: jnp.zeros_like(v) if k.endswith("expert_bias") else v
                     for k, v in params.items()}
        elif name == "slot_not_reset":
            cache = used            # the slots as the served run left them
        elif name == "fp8_weights":
            given = to_fp8(params)  # the last control: the served set is gone
        got, _cache = served(given, forced, name, cache)
        result["control_" + name] = compare(got, refs)
        verdicts["control_" + name] = inside(result["control_" + name])
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


MODEL = "bench"
MIN_JUDGED = 64


def engine_requests(seed, config, lanes, tiny):
    """[(prompt ids, tokens to generate)]: what fills the lanes, then a
    quarter more that have to wait for a lane and a slot."""
    import numpy as np

    rng = np.random.default_rng([seed, 1 << 22])
    n_pos = config["n_positions"]
    lo, hi = (250, 700) if not tiny else (n_pos // 3, n_pos * 3 // 4)
    plo, phi = (32, 256) if not tiny else (2, 8)
    out = []
    for _ in range(lanes + max(lanes // 4, 1)):
        total = int(rng.integers(lo, hi + 1))
        n = min(int(np.exp(rng.uniform(np.log(plo), np.log(phi)))), total - 1)
        out.append(([int(t) for t in rng.integers(0, config["vocab_size"],
                                                  n)], total - n))
    return out


def engine_run(cfg, params, traffic, requests, kv_blocks, model=MODEL):
    """Every request at once through a client of its own -> ([(prompt,
    served)], what the pools and the prewarm say).  ``model`` is the name
    the engine serves it under, and a part of its executables' cache key:
    a patched step goes under a name of its own, or the compile cache
    hands back the step as served."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from paddle_tpu.serving import (DecodeEngine, ServingClient,
                                    ServingEngine, ServingServer)

    deadline_ms = float(traffic["deadline_ms"])
    engine = DecodeEngine(buckets=traffic["lane_buckets"],
                          deadline_ms=deadline_ms)
    engine.add_model(model, (cfg, params), kv_blocks=kv_blocks)
    engine.prewarm()
    engine.start()
    server = ServingServer(ServingEngine(), port=0,
                           decode_engine=engine).start()
    endpoint = "127.0.0.1:%d" % server.port

    def ask(request):
        prompt, n_out = request
        reply = ServingClient(endpoints=[endpoint]).generate(
            model, prompt, max_new_tokens=n_out, deadline_ms=deadline_ms)
        if reply.status != "ok":
            raise RuntimeError("engine leg: %s %s"
                               % (reply.status, reply.error))
        return prompt, [int(t) for t in np.asarray(
            reply.outputs["tokens"]).reshape(-1)]

    try:
        with ThreadPoolExecutor(len(requests)) as pool:
            cases = list(pool.map(ask, requests))
        m = engine._models[model]
        said = {"paths": {"attention": m.attn_path,
                          "state_update": sorted(m.state_path.items()),
                          "experts": sorted(m.experts_path.items())},
                "blocks": m.cache.allocator.stats(),
                "slots_in_use": m.cache.slots.in_use,
                "state_slots": m.kv_config.state_slots}
    finally:
        server.shutdown()
        engine.stop()
    return cases, said


def by_depth(reference, config, params, cases, edges):
    """``nemotron_h_ref.check``'s two statistics of the served tokens, by
    the length of the context each was served from: [(from, to, compared,
    differing share, largest deficit)]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = reference.by_layer(config)
    pad = 128
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
    params = model.make_params(config, seed, device)
    lanes = max(traffic["lane_buckets"])
    requests = engine_requests(seed, config, lanes, tiny)
    edges = (0, 64, 256) if not tiny else (0, 8)
    t0 = time.time()
    result = {"leg": "engine", "device": device.device_kind,
              "platform": device.platform, "seed": seed, "lanes": lanes,
              "requests": len(requests),
              "sequence_lens": [len(p) + n for p, n in requests],
              "differing_share_bound": reference.DIFFERING_SHARE_BOUND,
              "deficit_bound": reference.DEFICIT_BOUND}
    judged_from = MIN_JUDGED if not tiny else 8
    # the ones that ran from the start, and the ones that had to wait
    first, waited = list(range(lanes)), list(range(lanes, len(requests)))

    def bands(cases, which):
        rows = by_depth(reference, config, params,
                        [cases[i] for i in which], edges)
        return rows, [share <= reference.DIFFERING_SHARE_BOUND
                      and worst <= reference.DEFICIT_BOUND
                      for _lo, _hi, n, share, worst in rows
                      if n >= judged_from]

    cases, said = engine_run(cfg, params, traffic, requests,
                             int(traffic["kv_blocks"]))
    rows_first, in_first = bands(cases, first[:8])
    rows_waited, in_waited = bands(cases, waited)
    result["served"] = dict(said, by_depth_from_the_start=rows_first,
                            by_depth_after_a_wait=rows_waited)
    ok = all(in_first) and all(in_waited) and in_waited \
        and said["slots_in_use"] == 0 and said["blocks"]["in_use"] == 0 \
        and all(len(served) == n for (_p, served), (_q, n)
                in zip(cases, requests))
    if device.platform == "tpu":
        ok = ok and said["paths"]["attention"] == "pallas" and all(
            path == "pallas" for _b, path in said["paths"]["experts"]
            + said["paths"]["state_update"])
    # the control: the same requests to an engine whose step never resets a
    # slot; the requests that waited start in what another sequence left
    undo = patched("slot_not_reset")
    try:
        got, _said = engine_run(cfg, params, traffic, requests,
                                int(traffic["kv_blocks"]),
                                model=MODEL + "_slot_not_reset")
    finally:
        undo()
    rows, _inside = bands(got, waited)
    result["control_slot_not_reset"] = {"by_depth_after_a_wait": rows}
    # tokens alone do not hold this fault to the absolute limit (the step's
    # control does, by the state itself): a state left by another sequence
    # fades, and what is left of it moves one token in four.  It has to read
    # half as much again as the served path in some band of depth
    seen = [share > 1.5 * was[3] for (_lo, _hi, n, share, _w), was
            in zip(rows, rows_waited) if n >= judged_from]
    result["ok"] = bool(ok and seen and any(seen))
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
        print("chip_check_nemotron: no TPU", file=sys.stderr)
        return 2
    config = with_tiny(load_json(ROOT, "benchmark", "configs",
                                 "nemotron-3-nano-30b-a3b-serve.json"),
                       args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    traffic = with_tiny(load_json(ROOT, "benchmark", "traffic",
                                  "serve_ssm_moe_decode_heavy.json"),
                        args.tiny_on_cpu)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for i in range(args.seeds):
        if args.engine:
            result = engine_leg(args.seed + 7919 * i, config, model,
                                reference, device, args.tiny_on_cpu, traffic)
        else:
            result = one_seed(args.seed + 7919 * i, config, model,
                              reference, device, args.tiny_on_cpu, controls)
        with open(os.path.join(out_dir, "chip_check_nemotron.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
