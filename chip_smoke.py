"""chip_smoke.py — one command that proves the main path runs on the chip.

    python chip_smoke.py            # on a machine with a TPU; exit 0 = up

It drives the system through the entry points a user calls, at the full
width of one model the repo supports, and checks what comes out by the
repo's own means.  No gain is claimed and nothing here is a benchmark: the
only timing printed is the dispatch+fetch round trip of a trivial jit.

  leg A  trainer   BERT-base (12 x 768, seq 128, bf16 AMP, Adam) exactly as
                   bench.py builds it, batch 128, through
                   fluid.Executor(fluid.TPUPlace(0)): startup + 8 steps on
                   one seeded batch, then the same two steps again on
                   executables restored from the tier-B compile cache.
  leg B  server    tools/serve.py's pieces in this process: demo decoder ->
                   DecodeEngine -> prewarm -> ServingServer ->
                   ServingClient.generate over the native RPC wire.  The
                   decoder is the toy the repo has (2 layers, max_seq 48):
                   this leg brings up the serving STACK, not a model.
  leg C  kernels   the Pallas flash-attention kernel, forward + backward at
                   [2, 12, 4096, 64] bf16, against the jnp reference
                   (fused_ln, the other default-on family, is gated in leg A).
  leg D  n chips   only with >= 4 devices: leg A's program (4 layers, dropout
                   off), data-parallel through
                   CompiledProgram.with_data_parallel and through the
                   transpiled c_allreduce_sum route, against one chip on the
                   same global batch.

Every gate raises: there is no try/except that logs and carries on.  The
script exits non-zero, and prints no result line, unless JAX's first device
is a TPU.  One process touches JAX and nothing here starts another: a chip
belongs to one process at a time.  Last line of stdout on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--test-tiny-on-cpu`` is for tests/test_chip_smoke.py and for debugging
the script's own control flow: tiny widths on whatever backend JAX has,
gates that need Mosaic skipped by name, and a result line that says
``"not_a_chip_result": true``.
"""

import argparse
import gc
import json
import os
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(_HERE, "tools"))

import numpy as np

# full-size defaults / the tiny set --test-tiny-on-cpu swaps in
SIZES = {
    "chip": dict(bert_layers=12, bert_batch=128, bert_steps=8,
                 flash_shape="2,12,4096,64", serve_requests=16,
                 dp_layers=4, dp_batch_per_chip=64, dp_steps=3),
    "tiny": dict(bert_layers=1, bert_batch=4, bert_steps=4,
                 flash_shape="1,1,1024,64", serve_requests=8,
                 dp_layers=1, dp_batch_per_chip=2, dp_steps=2),
}
SEED = 20260926


class GateFailed(AssertionError):
    pass


def gate(ok, what):
    """One pass/fail line per gate; the first failure ends the run."""
    print("  [%s] %s" % ("ok" if ok else "FAIL", what), flush=True)
    if not ok:
        raise GateFailed(what)


def skip(what, why):
    print("  [skipped: %s] %s" % (why, what), flush=True)


def counter(name, **labels):
    """Sum of a telemetry counter over the label sets that include
    ``labels``."""
    from paddle_tpu import telemetry

    counters = telemetry.snapshot()["counters"]
    return sum(counters.get(flat, 0)
               for flat, ls in telemetry.label_sets(name)
               if all(ls.get(k) == v for k, v in labels.items()))


def fallbacks(kernel):
    """{reason: count} of the adoption funnel's fallbacks for `kernel`."""
    from paddle_tpu import telemetry

    counters = telemetry.snapshot()["counters"]
    return {ls["reason"]: counters[flat] for flat, ls in
            telemetry.label_sets("pallas_kernel_fallback_total")
            if ls.get("kernel") == kernel and counters.get(flat)}


def mosaic_calls(exe):
    """tpu_custom_call sites in the executables `exe` holds, read from the
    compiled modules themselves — so it says the same for an executable
    restored from disk (nothing lowered, no counter moved) as for one
    compiled a moment ago."""
    return sum(e.jfn.as_text().count("tpu_custom_call")
               for e in exe._cache.values())


# ---------------------------------------------------------------------------
# leg A: trainer


def bert_cfg(args, layers, dropout=0.1):
    """BERT-base at full width (the BertConfig defaults), cut in depth."""
    from paddle_tpu.models import bert

    if args.test_tiny_on_cpu:
        return bert.BertConfig(vocab_size=512, hidden=128, layers=layers,
                               heads=2, ffn=256, max_pos=128,
                               dropout=dropout)
    return bert.BertConfig(layers=layers, dropout=dropout)


def build_bert(cfg, seq_len):
    import bench

    main, startup, loss = bench.build_bert_pretrain(cfg, seq_len, amp=True)
    main.random_seed = startup.random_seed = SEED
    return main, startup, loss


def leg_a(args, dev):
    import jax

    import bench
    import paddle_tpu as fluid

    seq = 128
    cfg = bert_cfg(args, args.bert_layers)
    print("leg A: trainer — BERT %d x %d, heads %d, ffn %d, vocab %d, seq %d,"
          " batch %d, bf16 AMP + Adam, %d steps"
          % (cfg.layers, cfg.hidden, cfg.heads, cfg.ffn, cfg.vocab_size, seq,
             args.bert_batch, args.bert_steps), flush=True)
    main, startup, loss = build_bert(cfg, seq)
    feed = bench._bert_feed(np.random.RandomState(SEED), cfg,
                            args.bert_batch, seq)

    def train(steps):
        """Fresh Executor + Scope: startup, then `steps` steps on the one
        batch.  -> (losses, where the train step's executable came from,
        scope, cache-miss counter after the first step, Mosaic call sites
        in the executables)"""
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            # warmup() names where the executable came from; the step that
            # follows hits it in memory
            src = exe.warmup(main, feed_specs=feed, fetch_list=[loss])
            miss1 = None
            for i in range(steps):
                out, = exe.run(main, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(out).reshape(-1)[0]))
                if i == 0:
                    miss1 = counter("executor_cache_miss_total")
        return losses, src, scope, miss1, mosaic_calls(exe)

    losses, src, scope, miss1, calls = train(args.bert_steps)
    print("  train step executable: source=%s compile_ms=%.0f"
          % (src["source"], src["compile_ms"]))
    print("  losses: %s" % " ".join("%.4f" % x for x in losses))
    gate(all(np.isfinite(losses)), "every fetched loss is finite")
    gate(losses[-1] < losses[0], "last loss %.4f below the first %.4f"
         % (losses[-1], losses[0]))
    bad = []
    n_persist = 0
    for var in main.list_vars():
        if not var.persistable or var.is_data:
            continue
        sv = scope.find_var(var.name)
        if sv is None or not sv.get_tensor()._is_initialized():
            continue
        n_persist += 1
        val = sv.get_tensor().get()
        if not (isinstance(val, jax.Array)
                and {d.platform for d in val.devices()} == {dev.platform}):
            bad.append(var.name)
    gate(n_persist > 0 and not bad,
         "all %d persistables in the scope are jax.Arrays on %s%s"
         % (n_persist, dev.platform, " (not: %s)" % bad[:5] if bad else ""))
    gate(counter("executor_cache_miss_total") == miss1,
         "executor_cache_miss_total flat after the first step")
    gate(counter("executor_aot_fallback_total") == 0,
         "executor_aot_fallback_total == 0")
    gate(counter("compile_cache_errors_total") == 0,
         "compile_cache_errors_total == 0")
    used = counter("pallas_kernel_used_total", kernel="fused_ln")
    fell = fallbacks("fused_ln")
    what = ("fused_ln compiled by Mosaic: %d tpu_custom_call sites in the "
            "step's executables" % calls)
    what2 = ("fused_ln engaged at every lowering (used=%d, fallbacks=%s)"
             % (used, fell or 0))
    if dev.platform != "tpu":
        skip(what, "Mosaic needs a TPU")
        skip(what2, "Mosaic needs a TPU")
    else:
        gate(calls > 0, what)
        if src["source"] == "compiled":
            gate(used > 0 and not fell, what2)
        else:
            skip(what2, "the executable came from tier B: nothing was "
                 "lowered in this process")

    # restore path: nothing compiled may survive in memory, then the same
    # seeded two steps must come back from tier B with the same losses
    del scope
    gc.collect()
    jax.clear_caches()
    xla0 = counter("executor_xla_compile_total")
    again, src2, _scope, _, calls2 = train(2)
    print("  restored: source=%s load+build ms=%.0f losses: %s"
          % (src2["source"], src2["compile_ms"],
             " ".join("%.4f" % x for x in again)))
    gate(src2["source"] == "disk",
         "tier B answered the train step after the in-memory executables "
         "were dropped (source == disk)")
    gate(counter("executor_xla_compile_total") == xla0,
         "no XLA compile on the restore path (startup came from tier B too)")
    gate(again == losses[:2] and calls2 == calls,
         "restored executables reproduce the first ones' losses exactly "
         "(and hold the same %d Mosaic call sites)" % calls2)
    return {"losses": losses, "first_source": src["source"]}


# ---------------------------------------------------------------------------
# leg B: server


def leg_b(args, dev):
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from serve import save_demo_decoder

    from paddle_tpu.serving import (DecodeEngine, ServingClient,
                                    ServingEngine, ServingServer)
    from paddle_tpu.serving.decode_model import (load_decoder,
                                                 unpaged_generate)

    n = args.serve_requests
    print("leg B: server — demo decoder (toy: 2 layers, 2 heads x 8, vocab "
          "31, max_seq 48; this leg brings up the serving stack, not a "
          "model), %d requests over the native RPC wire" % n, flush=True)
    rng = np.random.RandomState(SEED)
    shared = [int(t) for t in rng.randint(1, 31, 16)]  # one full KV block
    prompts = []
    for i in range(n):
        tail = [int(t) for t in rng.randint(1, 31, 1 + i % 7)]
        # every other request opens with the shared 16-token block
        prompts.append((shared + tail) if i % 2 else tail)
    max_new = [4 + i % 5 for i in range(n)]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_dec_") as tmp:
        model_dir = save_demo_decoder(os.path.join(tmp, "dec"))
        cfg, params = load_decoder(model_dir)
        engine = DecodeEngine(deadline_ms=60000.0)
        engine.add_model("toy", model_dir)
        manifest = engine.prewarm()
        print("  prewarm: %s" % json.dumps(manifest))
        miss0 = counter("executor_cache_miss_total")
        engine.start()
        server = ServingServer(ServingEngine(), port=0,
                               decode_engine=engine).start()
        def ask(i):
            client = ServingClient(endpoints=["127.0.0.1:%d" % server.port])
            return client.generate("toy", prompts[i],
                                   max_new_tokens=max_new[i],
                                   deadline_ms=60000.0)

        try:
            # requests 0 and 1 alone (1 seals the shared block), the rest
            # four at a time so sequences join and leave a running batch
            replies = [ask(0), ask(1)]
            with ThreadPoolExecutor(4) as pool:
                replies += list(pool.map(ask, range(2, n)))
        finally:
            server.shutdown()
            engine.stop()
    gate(all(r.status == "ok" for r in replies),
         "all %d requests ok (%s)"
         % (n, sorted({r.status for r in replies})))
    got = [np.asarray(r.outputs["tokens"]).reshape(-1) for r in replies]
    gate([len(t) for t in got] == max_new,
         "every reply carries the requested token count")
    m = engine._models["toy"]
    placed = [x for x in list(m.params.values()) + list(m.cache.carry())]
    gate(all(isinstance(x, jax.Array)
             and {d.platform for d in x.devices()} == {dev.platform}
             for x in placed),
         "the KV carry and the %d params live on %s"
         % (len(m.params), dev.platform))
    gate(counter("executor_cache_miss_total") == miss0,
         "zero executables built under traffic (cache-miss counter flat "
         "after prewarm)")
    hits = counter("prefix_cache_hit_tokens_total")
    gate(hits > 0, "prefix_cache_hit_tokens_total = %d > 0" % hits)

    # reported, not gated: greedy agreement with the unpaged reference.  On
    # the chip f32 matmuls run as bf16 passes, so an argmax may flip.
    pad = m.maxb * m.kv_config.block_size
    diverged = []
    for i, (p, k, t) in enumerate(zip(prompts, max_new, got)):
        want = np.asarray(unpaged_generate(cfg, params, p, k, pad_len=pad))
        if not np.array_equal(want, t):
            first = int(np.argmax(want != t))
            diverged.append((i, first))
    agreement = ("exact on %d/%d requests" % (n - len(diverged), n)
                 + ("; first diverging (request, position): %s"
                    % diverged[:4] if diverged else ""))
    print("  token agreement with unpaged_generate (reported): %s"
          % agreement)
    return {"token_agreement": agreement}


# ---------------------------------------------------------------------------
# leg C: kernels


def leg_c(args, dev):
    import importlib

    import jax
    import jax.numpy as jnp

    # the package re-exports the function under the module's name
    fa = importlib.import_module("paddle_tpu.pallas_kernels.flash_attention")
    B, H, S, D = (int(x) for x in args.flash_shape.split(","))
    on_tpu = dev.platform == "tpu"
    print("leg C: kernels — flash attention fwd+bwd at [%d, %d, %d, %d] bf16"
          % (B, H, S, D), flush=True)
    rng = np.random.RandomState(SEED)
    q, k, v = (jnp.asarray(rng.uniform(-1, 1, (B, H, S, D)), jnp.bfloat16)
               for _ in range(3))
    # off the TPU the kernel only runs when asked to interpret
    interp = None if on_tpu else True

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    for causal in (False, True):
        def flash(q, k, v, causal=causal):
            return fa.flash_attention(q, k, v, causal=causal,
                                      interpret=interp)

        def ref(q, k, v, causal=causal):
            return fa._ref_attention(q, k, v, None, causal, D ** -0.5)

        used0 = counter("pallas_kernel_used_total", kernel="flash_attention")
        lowered = jax.jit(jax.grad(loss_of(flash), argnums=(0, 1, 2))).lower(
            q, k, v)
        gate(counter("pallas_kernel_used_total", kernel="flash_attention")
             > used0, "causal=%s: adoption funnel counted the kernel as used"
             % causal)
        if on_tpu:
            gate("tpu_custom_call" in lowered.as_text(),
                 "causal=%s: the lowered fwd+bwd module holds a "
                 "tpu_custom_call (Mosaic, not interpret)" % causal)
        else:
            skip("causal=%s: tpu_custom_call in the lowered module" % causal,
                 "Mosaic needs a TPU")
        out = jax.jit(flash)(q, k, v)
        grads = lowered.compile()(q, k, v)
        want = jax.jit(ref)(q, k, v)
        want_grads = jax.jit(jax.grad(loss_of(ref), argnums=(0, 1, 2)))(
            q, k, v)
        # Tolerance: inputs and outputs are bf16 (8 significand bits, ulp
        # 2^-8 at 1.0); both paths accumulate in f32 but round the
        # probabilities to bf16 at different points, so two correct
        # results differ by a few ulp of the largest element.  2^-6
        # (4 ulp) of the reference's max magnitude, per tensor.
        for name, a, b in [("out", out, want)] + [
                ("d" + n, g, w) for n, g, w in zip("qkv", grads, want_grads)]:
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            gate(np.isfinite(a).all(), "causal=%s %s finite" % (causal, name))
            err, scale = float(np.max(np.abs(a - b))), float(np.max(np.abs(b)))
            gate(err <= 2.0 ** -6 * scale,
                 "causal=%s %s matches _ref_attention: max|d| %.3g <= "
                 "2^-6 * max|ref| %.3g" % (causal, name, err, scale))
    return {}


# ---------------------------------------------------------------------------
# leg D: data parallel over every chip of the host


def leg_d(args, dev):
    import jax

    import bench
    import paddle_tpu as fluid

    devs = jax.devices()
    n = len(devs)
    seq, per = 128, args.dp_batch_per_chip
    batch = n * per
    # Depth cut so that the one-chip reference holds the whole global batch
    # in HBM; dropout off so that the three runs draw nothing and can be
    # compared.  Width is leg A's.
    cfg = bert_cfg(args, args.dp_layers, dropout=0.0)
    print("leg D: %d chips — leg A's program at %d layers, dropout off, "
          "global batch %d x %d, %d steps"
          % (n, cfg.layers, n, per, args.dp_steps), flush=True)

    # One logical batch, two spellings of mask_pos: the single-program
    # routes index the global [batch*seq] token axis; under the transpiled
    # route each rank sees only its own [per*seq] tokens.
    rng = np.random.RandomState(SEED)
    shards = [bench._bert_feed(rng, cfg, per, seq) for _ in range(n)]
    local = {k: np.concatenate([s[k] for s in shards]) for k in shards[0]}
    glob = dict(local)
    glob["mask_pos"] = np.concatenate(
        [s["mask_pos"] + r * per * seq for r, s in enumerate(shards)])

    def run(main, startup, loss, program, feed):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(args.dp_steps):
                out, = exe.run(program, feed=feed, fetch_list=[loss],
                               return_numpy=False)
                # the transpiled route fetches one loss per rank
                losses.append(float(np.mean(np.asarray(out))))
        return losses, out, scope, mosaic_calls(exe)

    def in_use():
        stats = [d.memory_stats() for d in devs]
        return None if any(s is None for s in stats) else \
            [int(s["bytes_in_use"]) for s in stats]

    main, startup, loss = build_bert(cfg, seq)
    ref, _out, scope, _ = run(main, startup, loss, main, glob)
    print("  %-26s losses: %s"
          % ("1 chip, same global batch", " ".join("%.5f" % x for x in ref)))
    del scope, _out
    gc.collect()

    results = {}
    for route in ("with_data_parallel", "transpiled c_allreduce_sum"):
        base = in_use()
        used0 = counter("pallas_kernel_used_total", kernel="fused_ln")
        fell0 = fallbacks("fused_ln")
        main, startup, loss = build_bert(cfg, seq)
        if route == "with_data_parallel":
            program = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
            feed = glob
        else:
            from paddle_tpu.transpiler.collective import \
                select_grad_transpiler

            eps = ["local:%d" % i for i in range(n)]
            select_grad_transpiler().transpile(
                startup_program=startup, main_program=main, rank=0,
                endpoints=eps, current_endpoint=eps[0], wait_port=False)
            program, feed = main, local
        got, out, scope, calls = run(main, startup, loss, program, feed)
        print("  %-26s losses: %s"
              % (route, " ".join("%.5f" % x for x in got)))
        gate(len({s.device for s in out.addressable_shards}) == n,
             "%s: the fetched loss has shards on %d distinct devices"
             % (route, n))
        pname = next(v.name for v in main.list_vars()
                     if v.persistable and v.name.endswith("_q_w"))
        pval = scope.find_var(pname).get_tensor().get()
        gate(len(pval.sharding.device_set) == n,
             "%s: updated parameter %s lives on %d devices"
             % (route, pname, n))
        now = in_use()
        if now is None:
            skip("%s: per-device bytes_in_use" % route,
                 "this backend reports no memory_stats")
        else:
            delta = [b - a for a, b in zip(base, now)]
            print("  %s: bytes_in_use per device %s (this route added %s)"
                  % (route, now, delta))
            gate(min(now) > 0, "%s: memory in use on all %d devices"
                 % (route, n))
            # nothing piled on device 0: each device holds a replica of
            # the state plus its own shard of the batch, so what the route
            # added is the same everywhere up to allocator rounding
            gate(max(delta) <= 1.25 * min(delta),
                 "%s: not lopsided (max added %.0f MiB <= 1.25 x min added "
                 "%.0f MiB)" % (route, max(delta) / 2**20,
                                min(delta) / 2**20))
        # Tolerance: the loss is an f32 mean of thousands of
        # cross-entropies whose logits come out of bf16 matmuls; n chips
        # reduce the batch in a different order than one.  Adam's first
        # updates are sign-like (lr * g/|g|), so a missing or wrong
        # gradient exchange moves the second loss by far more than
        # rounding does.
        tol = 1e-3
        worst = max(abs(a - b) for a, b in zip(got, ref))
        gate(worst <= tol,
             "%s: %d-chip losses equal the 1-chip losses within %.0e "
             "(bf16 matmuls, f32 loss; worst |d| %.2e)"
             % (route, n, tol, worst))
        # XLA cannot partition a Mosaic kernel automatically: under
        # with_data_parallel the fused epilogue's two lowerings wrap their
        # call in a shard_map over the data axis (pallas_kernels/
        # fused_ln.py), under the transpiled route the whole block is one,
        # and on both the kernel runs per shard with no fallback.
        # Read from the executables (true on a warm cache too); the
        # funnel's counters, which move only when something is lowered,
        # are printed beside it.
        used = counter("pallas_kernel_used_total", kernel="fused_ln") - used0
        fell = {r: c - fell0.get(r, 0) for r, c in fallbacks("fused_ln").items()
                if c - fell0.get(r, 0)}
        what = ("%s: %d tpu_custom_call sites in its executables (fused_ln "
                "lowerings this process: Pallas %d, fallbacks %s)"
                % (route, calls, used, fell or 0))
        if dev.platform != "tpu":
            skip(what, "Mosaic needs a TPU")
        else:
            gate(calls > 0 and not fell, what)
        results[route] = got
        del scope, out, pval
        gc.collect()
    gate(counter("executor_aot_fallback_total") == 0
         and counter("compile_cache_errors_total") == 0,
         "executor_aot_fallback_total == 0 and compile_cache_errors_total "
         "== 0")
    return {"one_chip": ref, **results}


# ---------------------------------------------------------------------------


def round_trip(dev):
    """Median dispatch+fetch round trip of a trivial jit (reported)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda s: s + 1.0)
    s = jnp.float32(0.0)
    np.asarray(f(s))
    ts = []
    for _ in range(50):
        t0 = time.perf_counter()
        np.asarray(f(s))
        ts.append(time.perf_counter() - t0)
    ms = float(np.median(ts)) * 1e3
    print("round trip: median dispatch+fetch of a trivial jit on %s: %.3f ms"
          " (50 calls)" % (dev.platform, ms), flush=True)
    return ms


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--test-tiny-on-cpu", action="store_true",
                    help="TEST ONLY: tiny sizes, any backend, Mosaic gates "
                    "skipped; the result is labelled not a chip result")
    ap.add_argument("--legs", default="A,B,C,D",
                    help="comma-separated legs to run (default all; D runs "
                    "only with >= 4 devices)")
    for name, val in SIZES["chip"].items():
        ap.add_argument("--" + name.replace("_", "-"), default=None,
                        type=type(val),
                        help="default %r (tiny: %r)"
                        % (val, SIZES["tiny"][name]))
    args = ap.parse_args(argv)
    preset = SIZES["tiny" if args.test_tiny_on_cpu else "chip"]
    for name, val in preset.items():
        if getattr(args, name) is None:
            setattr(args, name, val)
    legs = [x.strip().upper() for x in args.legs.split(",") if x.strip()]

    if os.environ.get("PADDLE_PALLAS_INTERPRET") and \
            not args.test_tiny_on_cpu:
        print("chip_smoke: PADDLE_PALLAS_INTERPRET is set — a smoke whose "
              "kernels run interpreted proves nothing about the chip",
              file=sys.stderr)
        return 2
    # flags read FLAGS_* from the environment at import
    os.environ["FLAGS_telemetry"] = "1"

    from importlib.metadata import version

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("chip_smoke: jax %s jaxlib %s libtpu %s | %s"
          % (jax.__version__, version("jaxlib"), version("libtpu"),
             json.dumps(device)), flush=True)
    if dev.platform != "tpu" and not args.test_tiny_on_cpu:
        print("chip_smoke: JAX's first device is %r, not a tpu — nothing "
              "to prove here" % dev.platform, file=sys.stderr)
        return 2
    if args.test_tiny_on_cpu:
        print("chip_smoke: --test-tiny-on-cpu: tiny sizes on %s; NOT A CHIP "
              "RESULT" % dev.platform, flush=True)

    from paddle_tpu.core import compile_cache

    print("chip_smoke: compile cache at %s" % compile_cache.place(),
          flush=True)

    report = {"round_trip_ms": round_trip(dev)}
    todo = {"A": leg_a, "B": leg_b, "C": leg_c, "D": leg_d}
    for name in legs:
        if name == "D" and device["count"] < 4:
            print("leg D: not run (%d device)" % device["count"], flush=True)
            continue
        t0 = time.perf_counter()
        report[name] = todo[name](args, dev)
        gc.collect()  # the next leg gets this one's device memory back
        print("leg %s: passed in %.1f s" % (name, time.perf_counter() - t0),
              flush=True)
    print("chip_smoke: report %s" % json.dumps(report), flush=True)
    result = {"ok": True, "device": device}
    if args.test_tiny_on_cpu:
        result["not_a_chip_result"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
