"""On the chip, outside any timed window: the served SmallThinker step's
*logits* against the plain reference, at the configuration's widths and
eight layers, under the window, past the ring's first wrap and past its
second.

    chiprun --timeout 3000 -- python benchmark/tests/chip_check_smallthinker.py
    chiprun --timeout 3000 -- python benchmark/tests/chip_check_smallthinker.py --engine

Seeded weights as the cell makes them; 32 sequences of seeded tokens at
once, a lane each of a 32-lane ``make_packed_step`` (the engine's form of
the step) over the cache manager's pools: the global layers' blocks by a
shuffled table of 1,024 slots, the window layers' rings of 257 blocks moved
by ``PagedKVCache.advance_ring`` as the engine moves them.  Every token is
fed a token a step (prefill here is token-feed), teacher-forced.  The lanes'
lengths are of three bands (``BANDS``): under 4,096 positions (rings part
filled: the chunked walk fetches a lane's leading slots), just past 4,112
(the ring's first wrap: slot 0 is written again) and past 8,224 (its
second).  The step's logits at the last ``N_COMPARED`` positions of
``PER_BAND`` lanes a band are compared with ``smallthinker_ref.forward`` of
the whole sequence (float32, highest matmul precision, the served bf16
weights upcast a layer at a time, the attention ``QUERY_BLOCK`` queries at a
time, no cache, a band mask of 4,096 for the window).

Controls, each of which has to fall outside a limit.  Three are a reference
told otherwise, judged against the served logits: the router fed ``h2``,
SiLU for ReLU, a global layer rotated.  Three are a step with one fault on a
short run (``SHORT`` positions), judged by the true reference: a ring
chunk's mask shifted by one chunk, bfloat16 accumulation in every projection
(which the logits' errors cannot tell from the served path, whose next
matmul rounds its input to bfloat16 anyway: what tells it is the share of
logits that bfloat16 holds exactly) and the weights rounded to fp8 (e4m3) on
their way into the step (the precision next below the one the configuration
states: what ``smallthinker_ref.check``'s limits are set against).

**A window layer that sees position ``t - 4096``** cannot be judged by the
logits either: one key more among 4,096 under seeded weights moves them by
less than the served path's own noise (a reference with a window of 4,097
reads 0.0286 where the true one reads 0.0272 on the same lane: PR 53, call
1).  It is held where it can be seen, on the kernel alone at the cell's
shapes (``window_edge``): q ``[32, 28, 128]`` over rings of 257 blocks of a
bfloat16 pool, contexts of 4,097 to three rings, every slot of a ring held
so that the position just outside the window lies in the ring and only the
mask keeps it out, and that position's key planted to win any softmax that
sees it.  The kernel has to equal plain attention over the unrolled 4,096
positions and to differ from it over 4,097.

Exit code 1 if the served path is outside a limit, a control inside all of
them, or a kind of layer not on the kernel.

``--engine`` instead goes the cell's own way: ``ServingClient`` ->
``ServingServer`` -> ``DecodeEngine`` with the cell's bucket and pool, 36
requests for 32 lanes sent at once, two of them past 4,112 positions and one
past 8,224 while the others, 200-2,000 long, come and go beside them; the
served tokens against ``smallthinker_ref.check``'s two limits by band, the
prewarm event and ``pallas_kernel_used_total`` / ``_fallback_total`` read
for both kinds of attention and the experts.  ``--tiny-on-cpu`` rehearses
either here at the configuration's ``tiny`` sizes (not a chip result).
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Limits, from readings on the chip (PERF.md section 6, PR 53: call 1 read one
# seed and set them, call 2 ran them as committed).  Weights are the same bits
# on both sides; what is left is the served path's bfloat16 (every matmul's
# input and the cached K and V rounded to 8 bits of mantissa over 8 layers) and
# what that noise does to the routing: where a token's sixth and seventh
# experts lie within it the two sides swap an expert, which moves that
# position's logits and, through K and V, those behind it.  Logits here have a
# standard deviation of 1.0 over 151,936 tokens (0.02 x sqrt(2560)).
#   root-mean-square logit error over a lane's 8 compared rows, as a share of
#     the logits' own standard deviation: served 0.0039-0.0272 over nine lanes
#     (under 4,096: 0.0042 | 0.0114 | 0.0046; past 4,112: 0.0272 | 0.0039 |
#     0.0040; past 8,224: 0.0128 | 0.0039 | 0.0166) and 0.0071-0.0159 on the
#     short run; fp8 weights 0.136-0.139; SiLU for ReLU 0.155; a global layer
#     rotated 0.217; the router fed ``h2`` 0.446; a chunk's mask shifted
#     0.74-0.83.  The limit is 2.9 times the largest served reading and 0.59 of
#     the smallest of any control's.
#   the largest logit error of a lane: served 0.019-0.235; the router fed
#     ``h2`` 2.36; a chunk's mask shifted 3.7-3.9; fp8 0.68-0.75, SiLU 0.86 and
#     a rotated global layer 1.09, which a maximum of 1.2 million logits cannot
#     tell from an expert swapped.  The limit is 6 times the largest served
#     reading.
#   the share of a lane's compared logits that bfloat16 holds exactly: served
#     4e-5 to 5e-5 (a float32 sum keeps mantissa below bfloat16's 8 bits);
#     every projection's sum kept to bfloat16 1.0, and nothing else about it
#     differs from the served path (0.0050-0.0111 by root-mean-square: the
#     next matmul rounds its input to bfloat16 anyway).
#   the window's edge on the kernel alone (``window_edge``): against plain
#     attention over the unrolled 4,096 positions 0.00033 (bfloat16 pools), over
#     4,097 positions 50.06 in every lane; the limits 0.05 and 5.
# Each control falls outside one limit, not outside each.
RMS_TOLERANCE = 0.08
LOGIT_TOLERANCE = 1.5
BF16_EXACT_TOLERANCE = 0.01
LANES = 32
BLOCK = 16
N_COMPARED = 8           # the last positions of a compared lane
PER_BAND = 3             # lanes compared a band
# band -> (shortest, longest sequence, the length the reference pads to: a
# compile a length); the published window 4,096 and ring 4,112
BANDS = {"under_4096": (1500, 4000, 4096),
         "past_4112": (4113, 4300, 4608),
         "past_8224": (8225, 8300, 8704)}
SHORT = (520, 600, 1024)    # the faulty steps' runs: three chunks of a ring
REFERENCE_CONTROLS = ("router_reads_h2", "silu_for_relu",
                      "a_global_layer_rotated")
STEP_CONTROLS = ("chunk_mask_shifted", "bf16_accumulation", "fp8_weights")


def lengths(seed, bands, tiny):
    """A length a lane: ``PER_BAND`` compared lanes a band first, the rest
    spread over the bands (more of them short: the batch a step sees)."""
    import numpy as np

    rng = np.random.default_rng([seed, 53])
    out = []
    for name, (lo, hi, _pad) in bands.items():
        out += [(name, int(n)) for n in rng.integers(lo, hi + 1, PER_BAND)]
    names = list(bands)
    spare = LANES - len(out) if not tiny else 1
    for i in range(spare):
        name = names[0] if i % 2 else names[i // 2 % len(names)]
        lo, hi, _pad = bands[name]
        out.append((None, int(rng.integers(lo, hi + 1))))
    return out


def run_lanes(step, cache, params, cfg, seqs, keep):
    """Every sequence of ``seqs`` (token lists) in a lane of its own through
    the packed step, all started together, a token a step; a lane idles once
    its sequence has ended.  ``keep[i]``: how many of sequence i's last
    positions' logits to return.  -> per sequence its kept logits [keep,
    vocab] (None where keep is 0)."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import decode_model as dm

    kv = cache.config
    n = len(seqs)
    maxb = cfg.max_seq // kv.block_size
    columns, width = dm.lane_columns(kv, maxb)
    idle = np.zeros(width, np.int32)
    for name in ("src", "tables", "ring"):
        idle[columns[name]] = -1
    packed = np.tile(idle, (n, 1))
    tables = packed[:, columns["tables"]]
    held = [[] for _ in seqs]
    rings = [cache.new_ring() for _ in seqs]
    at = lambda name: columns[name].start
    prev = jnp.zeros(n, jnp.int32)
    rows = [[] for _ in seqs]
    for pos in range(max(map(len, seqs))):
        for i, seq in enumerate(seqs):
            if pos >= len(seq):
                if pos == len(seq):
                    packed[i] = idle
                continue
            assert cache.ensure_table(tables[i], held[i], pos + 1)
            cache.advance_ring(rings[i], pos + 1)
            packed[i, at("tok")], packed[i, at("pos")] = seq[pos], pos
            packed[i, at("lens")] = pos + 1
            packed[i, columns["ring"]] = rings[i].table
        carry, prev, logits = step(cache.carry(), params, prev,
                                   packed.copy())[:3]
        cache.replace_carry(carry)
        for i, seq in enumerate(seqs):
            if len(seq) - keep[i] <= pos < len(seq):
                rows[i].append(logits[i])
    for ring in rings:
        cache.release_ring(ring)
    return [np.stack([np.asarray(r) for r in got]) if got else None
            for got in rows]


def faulty(name):
    """Patch the program for a control that is a step with one fault (it has
    to stand while the step is traced) -> undo."""
    import jax

    from paddle_tpu.models import smallthinker
    from paddle_tpu.pallas_kernels import paged_attention as pa

    if name == "chunk_mask_shifted":
        # chunk c of a ring masked as chunk c + 1 would be
        old = pa._in_window
        span = 256
        pa._in_window = lambda ctx, entry, n, w: old(ctx, entry + span, n, w)
        return lambda: setattr(pa, "_in_window", old)
    if name == "bf16_accumulation":
        old = smallthinker._mm
        # every projection's sum kept to bfloat16's 8 bits of mantissa
        # (said with reduce_precision: a convert there and back is a pair
        # XLA:CPU's simplifier drops)
        smallthinker._mm = lambda x, w: jax.lax.reduce_precision(
            old(x, w), exponent_bits=8, mantissa_bits=7)
        return lambda: setattr(smallthinker, "_mm", old)
    return lambda: None


def told_otherwise(name, reference, config):
    """-> (config, layer function) of a reference with one fault."""
    import jax
    import jax.numpy as jnp

    layer = reference.layer
    if name == "a_global_layer_rotated":
        config = dict(config,
                      rope_layout=[1] * len(config["rope_layout"]))
    elif name == "silu_for_relu":
        def layer(config, sliding, rotated, p, x):
            old = reference.routed_sum
            reference.routed_sum = lambda c, p, h, g: old(
                c, p, h, g, act=jax.nn.silu)
            try:
                return reference.layer(config, sliding, rotated, p, x)
            finally:
                reference.routed_sum = old
    elif name == "router_reads_h2":
        def layer(config, sliding, rotated, p, x):
            eps = float(config["rms_norm_eps"])
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            h = reference._rmsnorm(x, p["ln1_g"], eps)
            x = x + reference._attention(config, sliding, rotated, p, h)
            h2 = reference._rmsnorm(x, p["ln2_g"], eps)
            routing = reference.gates_of(config, p, h2)
            return x + reference.routed_sum(config, p, h2, routing[0]), \
                routing
    return config, layer


# (configuration, layer function) -> the reference's jitted forward pass
_FORWARDS = {}


def reference_rows(reference, config, params, seq, n_rows, pad, layer=None):
    """The reference's logits at the last ``n_rows`` positions of ``seq``,
    the pass padded to ``pad`` positions (causal: padding cannot reach
    back)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cached = _FORWARDS
    key = (json.dumps(config, sort_keys=True), layer)
    if key not in cached:
        cached[key] = reference.by_layer(config, layer or reference.layer)
    padded = np.zeros(max(pad, len(seq)), np.int32)
    padded[:len(seq)] = seq
    rows = np.arange(len(seq) - n_rows, len(seq))
    with jax.default_matmul_precision("highest"):
        return np.asarray(cached[key](params, jnp.asarray(padded),
                                      rows=rows))


def readings(got, want):
    """What a lane's compared rows show: root-mean-square error as a share
    of the logits' standard deviation, the largest error, and the two
    statistics ``smallthinker_ref.check`` limits (the share of rows whose
    served argmax is not the reference's, the largest deficit)."""
    import jax.numpy as jnp
    import numpy as np

    served = got.argmax(axis=1)
    deficit = want.max(axis=1) - want[np.arange(len(want)), served]
    rounded = np.asarray(jnp.asarray(got).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    return {"rms": float(np.sqrt(np.mean(np.square(got - want)))
                         / want.std()),
            "max": float(np.abs(got - want).max()),
            "bf16_exact": float((rounded == got).mean()),
            "differing": float((deficit > 0).mean()),
            "deficit": float(deficit.max())}


def inside(r):
    return r["rms"] <= RMS_TOLERANCE and r["max"] <= LOGIT_TOLERANCE \
        and r["bf16_exact"] <= BF16_EXACT_TOLERANCE


def window_edge(cfg, lanes, block, seed):
    """The window kernel alone at the model's shapes: does a lane attend
    its last ``window`` positions and not one more?  -> readings: the
    largest error against plain attention over the unrolled window, and
    against the same over one position more, whose planted key wins any
    softmax that sees it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.pallas_kernels import paged_attention as pa

    window, dim, heads, kv_heads = cfg.window, cfg.head_dim, cfg.heads, \
        cfg.kv_heads
    ring = -(-window // block) + 1
    ring_len = ring * block
    rng = np.random.default_rng([seed, 57])
    edges = [window + 1, ring_len - 1, ring_len, ring_len + 1,
             2 * ring_len + 3, 3 * window + 5, window + block, 3 * ring_len]
    lens = np.asarray([edges[i % len(edges)] + i // len(edges)
                       for i in range(lanes)], np.int32)
    pools = [rng.standard_normal((1 + lanes * ring, block, kv_heads * dim))
             .astype(np.float32) for _ in range(2)]
    q = rng.standard_normal((lanes, heads, dim)).astype(np.float32)
    # every slot held: lane b's ring is blocks 1 + b * ring onward
    tables = 1 + np.arange(lanes * ring, dtype=np.int32).reshape(lanes, ring)
    group = heads // kv_heads

    def rows_of(b, positions):
        entry = np.asarray(positions) % ring_len
        return tables[b, entry // block], entry % block

    for b, ctx in enumerate(lens):
        # the position just outside the window: its key the first query of
        # each group, scaled to a score of 4 |q|^2 / sqrt(D), its value 50
        blk, off = rows_of(b, [ctx - 1 - window])
        pools[0][blk, off] = 4.0 * q[b, ::group].reshape(-1)
        pools[1][blk, off] = 50.0
    k, v = (jnp.asarray(pool, jnp.bfloat16) for pool in pools)
    path = pa.attention_path(q.shape, k.shape, k.dtype, ring)
    got = np.asarray(jax.jit(lambda *a: pa.paged_attention(
        *a, window=window))(jnp.asarray(q), k, v, jnp.asarray(tables),
                            jnp.asarray(lens)))
    # the largest error over the lanes against the window, the smallest
    # against one position more (every lane has to differ from that)
    right, wide = 0.0, float("inf")
    for b, ctx in enumerate(lens):
        for extra in (0, 1):
            blk, off = rows_of(b, np.arange(ctx - window - extra, ctx))
            kk, vv = (pool[blk, off].reshape(1, -1, kv_heads, dim)
                      for pool in (k, v))
            plain = np.asarray(pa.masked_attention(
                jnp.asarray(q[b:b + 1]), kk, vv,
                jnp.asarray([window + extra], jnp.int32)))[0]
            err = float(np.abs(got[b] - plain).max())
            if extra:
                wide = min(wide, err)
            else:
                right = max(right, err)
    return {"path": path, "ring": ring, "contexts": sorted(set(lens.tolist())),
            "against_the_window": right, "against_one_position_more": wide}


def step_leg(seed, config, model, reference, device, tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    t_leg = time.monotonic()
    cfg = model.decoder_config(config)
    window = cfg.window
    ring_len = (-(-window // BLOCK) + 1) * BLOCK
    bands = BANDS if not tiny else {
        "under_window": (3, window - 1, 16),
        "past_the_ring": (ring_len + 1, ring_len + 6, 32),
        "past_two_rings": (2 * ring_len + 1, 2 * ring_len + 6, 48)}
    short = SHORT if not tiny else (12, 14, 16)
    block = BLOCK if not tiny else 4
    params = dm.laid_out(cfg, model.make_params(config, seed, device))
    lens = lengths(seed, bands, tiny)
    rng = np.random.default_rng([seed, 54])
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab, n)]
            for _band, n in lens]
    n_cmp = N_COMPARED if not tiny else 3
    keep = [n_cmp if band else 0 for band, _n in lens]
    lanes = len(seqs)

    def served(weights, which, keeps, blocks, fault=None):
        kv = dm.cache_config(cfg, block, blocks, state_slots=lanes + 1)
        paths = {kind: dm.attention_path(cfg, kv, lanes, kind)
                 for kind in ("attention", "window")}
        paths["experts"] = dm.experts_path(cfg, weights, lanes)
        undo = faulty(fault) if fault else (lambda: None)
        try:
            step = jax.jit(dm.make_packed_step(cfg, kv, lanes),
                           donate_argnums=(0,))
            cache = kvc.PagedKVCache(kv)
            t0 = time.monotonic()
            out = run_lanes(step, cache, weights, cfg, which, keeps)
        finally:
            undo()
        steps = max(map(len, which))
        return out, paths, (time.monotonic() - t0) / steps

    report = {"seed": seed, "lanes": lanes, "lengths": [n for _b, n in lens],
              "chunk_positions": None, "bands": {}, "controls": {}}
    blocks = sum(-(-n // block) for _b, n in lens) + 2
    got, paths, per_step = served(params, seqs, keep, blocks)
    report["paths"], report["s_per_step"] = paths, round(per_step, 5)
    kv = dm.cache_config(cfg, block, blocks, state_slots=lanes + 1)
    report["chunk_positions"] = dm.chunk_positions(cfg, kv, lanes)
    report["window_ring"] = kv.window_ring
    ok = tiny or (set(paths.values()) == {"pallas"}
                  and kv.window_ring * block
                  > report["chunk_positions"]["window"])
    wants = {}
    for i, (band, _n) in enumerate(lens):
        if not band:
            continue
        wants[i] = reference_rows(reference, config, params, seqs[i], n_cmp,
                                  bands[band][2])
        r = readings(got[i], wants[i])
        report["bands"].setdefault(band, []).append(
            dict(r, length=len(seqs[i])))
        ok &= inside(r)
    print("served (%.0f s so far): %s" % (time.monotonic() - t_leg,
                                          json.dumps(report)), flush=True)
    edge = window_edge(cfg, lanes, block, seed)
    report["window_edge"] = edge
    sharp = (tiny or edge["path"] == "pallas") \
        and edge["against_the_window"] < 0.05 \
        and edge["against_one_position_more"] > 5.0
    print("window edge on the kernel: %s %s" % (
        "held" if sharp else "NOT HELD", json.dumps(edge)), flush=True)
    ok &= sharp

    # a reference told otherwise, against the served logits of one compared
    # lane: the shortest band's first (a compile a fault and a kind of
    # layer), but past the first wrap for the window one position wider,
    # which shows past the window alone
    first = {band: i for i, (band, _n) in reversed(list(enumerate(lens)))
             if band}
    for name in REFERENCE_CONTROLS:
        faulty_config, layer = told_otherwise(name, reference, config)
        band = list(bands)[name == "window_sees_t_minus_4096"]
        i = first[band]
        r = readings(got[i], reference_rows(
            reference, faulty_config, params, seqs[i], n_cmp, bands[band][2],
            layer))
        report["controls"][name] = dict(r, band=band)
        print("control %s (%.0f s so far): %s %s" % (
            name, time.monotonic() - t_leg,
            "MISSED" if inside(r) else "caught", json.dumps(r)), flush=True)
        ok &= not inside(r)
    del got

    # a step with one fault, on a short run, against the true reference
    lo, hi, pad = short
    few = [[int(t) for t in rng.integers(0, cfg.vocab, n)]
           for n in rng.integers(lo, hi + 1, lanes)]
    few_keep = [n_cmp] * PER_BAND + [0] * (lanes - PER_BAND)
    few_blocks = sum(-(-len(s) // block) for s in few) + 2
    few_want = [reference_rows(reference, config, params, few[i], n_cmp, pad)
                for i in range(PER_BAND)]
    if tiny:
        # (the CPU tier gathers: neither kernel's mask is on the path)
        controls = ("bf16_accumulation", "fp8_weights")
    else:
        controls = STEP_CONTROLS
    for name in (None,) + tuple(controls):
        weights = params
        if name == "fp8_weights":
            # in place, an array at a time: two copies of the weights do not
            # fit the chip (the true reference's rows are already computed)
            for key in list(params):
                params[key] = params[key].astype(jnp.float8_e4m3fn).astype(
                    params[key].dtype)
        out, _paths, _t = served(weights, few, few_keep, few_blocks, name)
        rows = [readings(out[i], few_want[i]) for i in range(PER_BAND)]
        if name is None:
            report["short_served"] = rows
            good = all(inside(r) for r in rows)
            print("short run served: %s %s" % (
                "inside" if good else "OUTSIDE", json.dumps(rows)),
                flush=True)
        else:
            report["controls"][name] = rows
            good = all(not inside(r) for r in rows)
            print("control %s (%.0f s so far): %s %s" % (
                name, time.monotonic() - t_leg,
                "caught" if good else "MISSED", json.dumps(rows)),
                flush=True)
        ok &= good
    return ok, report


def engine_leg(seed, config, model, reference, device, tiny, traffic):
    """The cell's own way, served tokens against the reference by band."""
    import numpy as np

    from concurrent.futures import ThreadPoolExecutor

    import paddle_tpu as fluid
    from paddle_tpu import telemetry
    from paddle_tpu.serving import DecodeEngine, ServingClient, \
        ServingEngine, ServingServer

    cfg = model.decoder_config(config)
    params = model.make_params(config, seed, device)
    rng = np.random.default_rng([seed, 55])
    if tiny:
        sizes = [(4, 6), (5, 30), (6, 60)] + [(3, 10)] * 3
        pads = {6: 16, 30: 48, 60: 80}
    else:
        sizes = [(64, 8200), (64, 4100), (96, 4150)] \
            + [(int(p), int(o)) for p, o in zip(
                rng.integers(32, 257, 33), rng.integers(200, 2001, 33))]
        pads = {}
    tmp = os.path.join(ROOT, "chiprun_out", "smallthinker_engine_%d" % seed)
    os.makedirs(tmp, exist_ok=True)
    fluid.set_flags({"FLAGS_telemetry": True, "FLAGS_telemetry_dir": tmp})
    engine = DecodeEngine(buckets=traffic["lane_buckets"],
                          deadline_ms=float(traffic["deadline_ms"]))
    engine.add_model("bench", (cfg, params),
                     kv_blocks=int(traffic["kv_blocks"]))
    engine.prewarm()
    engine.start()
    server = ServingServer(ServingEngine(), port=0,
                           decode_engine=engine).start()
    endpoint = "127.0.0.1:%d" % server.port

    def ask(i):
        p, o = sizes[i]
        r = np.random.default_rng([seed, 56, i])
        prompt = [int(t) for t in r.integers(0, cfg.vocab, p)]
        reply = ServingClient(endpoints=[endpoint]).generate(
            "bench", prompt, max_new_tokens=o,
            deadline_ms=float(traffic["deadline_ms"]))
        assert reply.status == "ok", (i, reply.status, reply.error)
        return prompt, [int(t) for t in np.asarray(
            reply.outputs["tokens"]).reshape(-1)]

    t0 = time.monotonic()
    try:
        with ThreadPoolExecutor(len(sizes)) as pool:
            cases = list(pool.map(ask, range(len(sizes))))
    finally:
        server.shutdown()
        engine.stop()
    took = time.monotonic() - t0
    telemetry.flush()
    counters = {k: v for k, v in telemetry.snapshot()["counters"].items()
                if k.startswith("pallas_kernel_")}
    with open(os.path.join(tmp, "steps.jsonl")) as fp:
        warm = [ev for ev in map(json.loads, fp)
                if ev["ev"] == "serving_prewarm"]
    report = {"seed": seed, "requests": len(sizes), "seconds": round(took, 1),
              "counters": counters, "prewarm": warm[-1:], "cases": []}
    ok = bool(warm)
    if not tiny:
        ev = warm[-1]
        ok &= ev["attention"] == "pallas" \
            and ev["window_attention"] == "pallas" \
            and ev["experts"] == "pallas" and ev["experts_gate"] == "relu" \
            and ev["window_ring"] == 257 \
            and ev["chunk_positions"] == {"attention": 256, "window": 256}
        used = lambda k: sum(v for name, v in counters.items()
                             if name.startswith("pallas_kernel_used_total")
                             and "kernel=%s" % k in name)
        # a lowering is what the counters count: an executable restored
        # from the compile cache (``source`` ``disk``) lowered nothing, and
        # the event's paths are then all there is to read
        lowered = ev["source"] != "compiled" or (
            used("paged_attention") > 0 and used("moe_experts") > 0)
        ok &= lowered and not any(
            name.startswith("pallas_kernel_fallback_total")
            for name in counters)
    # the three long requests and three of the others, each by its own
    # length: the reference's two limits
    for i in list(range(3)) + list(range(3, len(sizes), 11))[:3]:
        prompt, served = cases[i]
        total = len(prompt) + len(served)
        pad = pads.get(sizes[i][1], -(-total // 512) * 512)
        # the last 64 served tokens: the deepest the request got
        tail = min(64, len(served))
        got = reference.check(
            config, params,
            [(prompt + served[:-tail], served[-tail:])], pad)
        report["cases"].append(dict(got, positions=total))
        ok &= got["ok"]
    return ok, report


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2500000011)
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--tiny-on-cpu", action="store_true",
                    help="TEST ONLY: tiny sizes on any backend")
    args = ap.parse_args(argv)

    import jax

    from benchmark.run import load_json, load_module, with_tiny

    device = jax.devices()[0]
    if not args.tiny_on_cpu and device.platform != "tpu":
        print("chip_check_smallthinker: needs a TPU; JAX found %s"
              % device.platform, file=sys.stderr)
        return 2
    config = with_tiny(load_json(
        ROOT, "benchmark", "configs", "smallthinker-21b-a3b-serve.json"),
        args.tiny_on_cpu)
    traffic = with_tiny(load_json(
        ROOT, "benchmark", "traffic",
        "serve_wide_window_moe_decode_long.json"), args.tiny_on_cpu)
    model = load_module("models", config["model"])
    reference = load_module("reference", config["reference"])
    all_ok, reports = True, []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        if args.engine:
            ok, report = engine_leg(seed, config, model, reference, device,
                                    args.tiny_on_cpu, traffic)
        else:
            ok, report = step_leg(seed, config, model, reference, device,
                                  args.tiny_on_cpu)
        all_ok &= ok
        reports.append(report)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = "chip_check_smallthinker%s.json" % (
        "_engine" if args.engine else "")
    with open(os.path.join(out, name), "w") as fp:
        json.dump(reports, fp, indent=1)
    line = {"ok": bool(all_ok), "device": {"platform": device.platform,
                                           "kind": device.device_kind},
            "limits": {"rms": RMS_TOLERANCE, "max": LOGIT_TOLERANCE,
                       "bf16_exact": BF16_EXACT_TOLERANCE},
            "reports": reports}
    if args.tiny_on_cpu:
        line["not_a_chip_result"] = True
    print(json.dumps(line), flush=True)
    # (the rehearsal's sizes are not the limits': it has only to run)
    return 0 if all_ok or args.tiny_on_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
