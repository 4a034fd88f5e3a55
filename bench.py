"""Benchmarks for the BASELINE.md target configs, driver-visible as JSON.

Default (driver) metric: ResNet-50 training throughput on one chip
(BASELINE config 2).  `BENCH_CONFIG` selects the others:

    BENCH_CONFIG=resnet50  (default)   images/sec/chip + MFU
    BENCH_CONFIG=bert                  seqs/sec/chip + model TF/s (config 3)
    BENCH_CONFIG=nmt                   tokens/sec (config 4)
    BENCH_CONFIG=scaling               1->N chip scaling efficiency (config 5;
                                       on a 1-chip host this runs the 8-way
                                       virtual CPU mesh as a smoke + emits
                                       the single-chip reference number)
    BENCH_CONFIG=longctx               long-context flash attention fwd+bwd
                                       tokens/s vs the XLA-composed path
                                       (BENCH_SEQ selects sequence length;
                                       vs_baseline=-1 = composed path OOMs)

Each run prints ONE JSON line {"metric","value","unit","vs_baseline",
"platform","device_kind","device_count",...}: every number names the device
it came from, and a device that is not in DEVICE_PEAKS is an error — there
is no MFU "vs the v5e" for anything but a v5e.  One process does the whole
run (a chip belongs to one process; nothing here starts a child).

Anchors: H100 ResNet-50 train ~3000 img/s/chip (NVIDIA NGC MLPerf-era
mixed-precision single-GPU; the former 2400 figure was generous), BERT-base
seq128 pretrain ~2300 seqs/s/chip (NGC LAMB phase-1 class), Transformer-base
NMT ~200k tokens/s/chip (see bench_nmt for the derivation).
Protocol per BASELINE.md: warmup, then median of timed chunks.
"""

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

import numpy as np

H100_RESNET50_IMG_PER_SEC = 3000.0
H100_BERT_SEQ_PER_SEC = 2300.0

# Published per-chip peaks, keyed by jax.devices()[0].device_kind as the
# chip reports it.  Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbytes_per_sec": 819.0,
                    "hbm_gbytes": 16.0},
}


def _device():
    """The chip under test.  TPUPlace raises when JAX finds no accelerator
    (framework.py), so a chip-less `python bench.py` fails here instead of
    timing the CPU under device-metric names."""
    import paddle_tpu as fluid

    return fluid.TPUPlace(0).jax_device()


def _device_fields():
    """platform / device_kind / device_count for every JSON line."""
    import jax

    dev = _device()
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _emit(rec):
    print(json.dumps(dict(rec, **_device_fields())))


def _peaks():
    kind = _device().device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            "bench: no published peak for device_kind %r (known: %s); add "
            "it to DEVICE_PEAKS with its source before reporting an MFU"
            % (kind, sorted(DEVICE_PEAKS)))
    return DEVICE_PEAKS[kind]


def _mfu(model_tflops_per_sec):
    return model_tflops_per_sec / _peaks()["bf16_tflops"]


# executor_cache_miss_total delta across the TIMED region of the last
# _timed_loop (post-warmup).  This is the BENCH "recompiles" number: any
# miss after warmup means an executable was built inside the timed window
# and the median is invalid (BASELINE.md round-8 protocol).  A second
# same-process run of a config trivially reports 0 — the in-memory cache
# serves every step — and with FLAGS_compile_cache_dir armed a second
# PROCESS reports compile_ms_cold ~0 as well (tier-B restore).
_TIMED_RECOMPILES = None


def _miss_total():
    from paddle_tpu import telemetry

    return int(telemetry.counter_total("executor_cache_miss_total"))


def _telemetry_stats():
    """Step stats from the runtime metrics registry (core/telemetry.py).

    The executor records per-step wall time and compile time into the
    registry; the headline seqs/img numbers stay on _timed_loop's chunked
    host timing, and these registry keys ride along so a BENCH JSON also
    says how much was spent compiling, whether anything RECOMPILED
    mid-run (a recompile inside the timed region invalidates the median),
    and what the per-step distribution looked like.  Empty when
    FLAGS_telemetry is off.

    Compile latency splits two ways (the persistent-cache story):
    ``compile_ms_cold`` is real trace+lower+XLA time paid this process;
    ``compile_ms_warm`` is tier-B disk-restore time.  A cold process
    reports (cold>0, warm=0); re-running the same config against the same
    FLAGS_compile_cache_dir flips it to (cold~0, warm=restore-ms)."""
    from paddle_tpu import telemetry

    if not telemetry.enabled():
        return {}
    snap = telemetry.snapshot()
    hists = snap.get("histograms", {})
    out = {"recompiles": int(_TIMED_RECOMPILES
                             if _TIMED_RECOMPILES is not None
                             else telemetry.counter_total(
                                 "executor_cache_miss_total"))}
    # the durations live on the set-up spans (main() arms the recorder
    # beside the registry): trace + lower + XLA on ``executor.compile``,
    # the read and ``deserialize_and_load`` on ``executor.cache_restore``
    from paddle_tpu.core import tracing

    cold = sum(s["attrs"].get("lower_ms", 0.0)
               + s["attrs"].get("backend_ms", 0.0)
               for s in tracing.records("executor.compile"))
    warm = sum(s["dur"] / 1e3
               for s in tracing.records("executor.cache_restore")
               if s["attrs"].get("hit"))
    out["compile_ms_cold"] = round(cold, 1)
    out["compile_ms_warm"] = round(warm, 1)
    comp = hists.get("executor_compile_ms")
    if comp:
        out["compile_ms"] = round(comp["sum"], 1)
    step = hists.get("executor_step_ms")
    if step:
        out["step_ms_p50"] = step["p50"]
        out["step_ms_p90"] = step["p90"]
        out["step_ms_p99"] = step["p99"]
    return out


def _timed_loop(run_step, sync, warmup, iters, chunk=None):
    # One host sync (dispatch + fetch round trip) per chunk of steps; the
    # chunk sizes were chosen on an earlier installation and have not been
    # re-fit to this one (chip_smoke.py prints the round trip).  Numbers
    # are only comparable at the same chunk.
    if chunk is None:
        chunk = int(os.environ.get("BENCH_CHUNK", "30"))
    out = None
    for _ in range(warmup):
        out = run_step()
    if out is not None:
        sync(out)
    miss0 = _miss_total()
    times = []
    for _ in range(max(iters // chunk, 1)):
        t0 = time.perf_counter()
        for _ in range(chunk):
            out = run_step()
        sync(out)
        times.append((time.perf_counter() - t0) / chunk)
    global _TIMED_RECOMPILES
    _TIMED_RECOMPILES = _miss_total() - miss0
    return float(np.median(times)), out


def bench_resnet(batch=512, image_size=224, warmup=5, iters=30, depth=50,
                 amp=True, data_format="NCHW", chunk=None):
    """The headline config measures at chunk=120 (set by main): the
    steady-state device number a real training loop (which syncs rarely)
    sees.  Numbers are only comparable at matched chunk."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img, label, loss, acc = resnet.build_train(
            depth=depth, class_dim=1000, image_size=image_size, lr=0.1,
            amp=amp, data_format=data_format)

    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    use_pipeline = os.environ.get("BENCH_PIPELINE", "0") == "1"
    with fluid.scope_guard(scope):
        exe.run(startup)
        if use_pipeline:
            # full reference workflow: host batches ride the DataLoader's
            # native queue + double buffering (VERDICT r1 weak #8 — the
            # headline number with the input pipeline engaged)
            loader = fluid.io.DataLoader.from_generator(
                feed_list=[img, label], capacity=8, use_double_buffer=True)
            xs = rng.rand(batch, 3, image_size, image_size).astype("float32")
            ys = rng.randint(0, 1000, (batch, 1)).astype("int32")

            def gen():
                while True:
                    yield [xs, ys]

            loader.set_batch_generator(gen, places=[fluid.TPUPlace(0)])
            it = iter(loader)

            def step():
                feed = next(it)
                out, = exe.run(main, feed=feed, fetch_list=[loss],
                               return_numpy=False)
                return out
        else:
            xb = jax.device_put(
                rng.rand(batch, 3, image_size, image_size).astype("float32"),
                _device())
            yb = jax.device_put(
                rng.randint(0, 1000, (batch, 1)).astype("int32"), _device())
            feed = {"img": xb, "label": yb}

            def step():
                out, = exe.run(main, feed=feed, fetch_list=[loss],
                               return_numpy=False)
                return out

        med, out = _timed_loop(step, lambda o: np.asarray(o), warmup,
                               iters, chunk=chunk)
    return batch / med, float(np.asarray(out).reshape(-1)[0])


def _resnet50_train_flops_per_image(image_size=224):
    # fwd ~4.09 GFLOP/img at 224 (canonical count, MACs*2); train = fwd +
    # dgrad + wgrad ~ 3x fwd
    return 3 * 4.089e9 * (image_size / 224.0) ** 2


def _bert_feed(rng, cfg, batch, seq_len, mask_frac=0.15):
    n_mask = max(int(batch * seq_len * mask_frac), 1)
    return {
        "src_ids": rng.randint(0, cfg.vocab_size,
                               (batch, seq_len, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq_len).reshape(1, seq_len, 1),
                           (batch, 1, 1)).astype("int64"),
        "sent_ids": np.zeros((batch, seq_len, 1), "int64"),
        "input_mask": np.ones((batch, seq_len, 1), "float32"),
        "mask_pos": rng.randint(0, batch * seq_len, (n_mask,)).astype("int64"),
        "mask_label": rng.randint(0, cfg.vocab_size,
                                  (n_mask, 1)).astype("int64"),
    }


# analytic ICI wire bytes per step of the last bench_bert program —
# stamped by the collective transpiler into _collective_meta (0.0 when the
# bench ran single-device / untranspiled)
_BERT_WIRE_BYTES = 0.0


def build_bert_pretrain(cfg, seq_len, amp=True, remat=False):
    """models/bert.build_pretrain's structure with an AMP-decorated Adam;
    returns (main, startup, loss).  chip_smoke.py trains this same
    program."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        enc = bert.bert_encoder(cfg, seq_len, return_checkpoints=remat)
        if remat:
            inputs, seq_out, ckpts = enc
        else:
            inputs, seq_out = enc
        mask_pos = fluid.layers.data("mask_pos", shape=[1], dtype="int64")
        mask_label = fluid.layers.data("mask_label", shape=[1],
                                       dtype="int64")
        flat = fluid.layers.reshape(seq_out, [-1, cfg.hidden])
        picked = fluid.layers.gather(flat, mask_pos)
        trans = fluid.layers.fc(picked, cfg.hidden, act="gelu")
        trans = fluid.layers.layer_norm(trans, begin_norm_axis=1)
        logits = fluid.layers.fc(trans, cfg.vocab_size)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, mask_label))
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        if remat:
            # remat wraps OUTSIDE the AMP decorator: RecomputeOptimizer
            # records the checkpoints on the program before delegating, and
            # the decorated minimize drives backward (which consumes them)
            opt = fluid.optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(ckpts)
        opt.minimize(loss)
    return main, startup, loss


def bench_bert(batch=224, seq_len=128, warmup=3, iters=15, amp=True,
               remat=False):
    """BERT-base pretrain step at exactly the requested batch; returns
    (seqs/s, loss).  An out-of-memory batch fails the run: the process
    that holds the chip cannot hand it to a retry child, and a number from
    a silently smaller batch is not the number that was asked for."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = bert.BERT_BASE
    main, startup, loss = build_bert_pretrain(cfg, seq_len, amp, remat)

    # BENCH_COLLECTIVE=1: run the data-parallel exchange path (GradAllReduce
    # or, under FLAGS_collective_mode=zero1, ShardedGradAllReduce +
    # quantized wire per FLAGS_allreduce_dtype) over the local mesh and
    # report the transpiler's analytic bytes-on-ICI per step
    global _BERT_WIRE_BYTES
    _BERT_WIRE_BYTES = 0.0
    if os.environ.get("BENCH_COLLECTIVE", "0") == "1":
        n = len(jax.devices())
        if n > 1:
            from paddle_tpu.transpiler.collective import \
                select_grad_transpiler

            eps = ["local:%d" % i for i in range(n)]
            select_grad_transpiler().transpile(
                startup_program=startup, main_program=main, rank=0,
                endpoints=eps, current_endpoint=eps[0], wait_port=False)
            _BERT_WIRE_BYTES = float(
                main._collective_meta.get("wire_bytes_per_step", 0.0))

    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = _bert_feed(rng, cfg, batch, seq_len)
    feed = {k: jax.device_put(v, _device()) for k, v in feed.items()}
    with fluid.scope_guard(scope):
        exe.run(startup)

        def step():
            out, = exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            return out

        med, out = _timed_loop(step, lambda o: np.asarray(o), warmup, iters)
    return batch / med, float(np.asarray(out).reshape(-1)[0])


def _bert_train_flops_per_seq(seq_len=128, layers=12, hidden=768,
                              vocab=30522):
    # encoder matmul flops/seq fwd: 12 * (4*h^2*2 (qkv+proj) + 2*4h*h*2
    # (ffn)) * s + attention 2*2*s^2*h; head: s*h*vocab*2; train = 3x
    per_layer = (4 * hidden * hidden * 2 + 2 * 4 * hidden * hidden * 2)
    enc = layers * (per_layer * seq_len + 2 * 2 * seq_len * seq_len * hidden)
    head = seq_len * hidden * vocab * 2
    return 3 * (enc + head)


def _nmt_train_flops_per_token(src_len=64, tgt_len=64, d=512, ffn=2048,
                               enc_layers=6, dec_layers=6, vocab=30000):
    # transformer-base matmul flops per batch element, fwd; train = 3x.
    # enc layer/token: qkv+proj 4*d^2*2, ffn 2*(d*ffn*2); dec layer adds
    # cross-attention projections (another 4*d^2*2); head: d*vocab*2 per
    # TARGET token; attention scores 2*2*span*d per token, where the span
    # is tgt_len for decoder self-attention but SRC_len for
    # cross-attention (the decoder attends over the encoder sequence).
    enc_tok = 4 * d * d * 2 + 2 * d * ffn * 2 + 2 * 2 * src_len * d
    dec_tok = (8 * d * d * 2 + 2 * d * ffn * 2
               + 2 * 2 * tgt_len * d + 2 * 2 * src_len * d)
    fwd = (src_len * enc_layers * enc_tok + tgt_len * dec_layers * dec_tok
           + tgt_len * d * vocab * 2)
    return 3 * fwd / (src_len + tgt_len)


# H100 transformer-base NMT anchor, derived (BASELINE.md config 4 note):
# the recorded H100 BERT anchor implies 2300 seqs/s * 85 GFLOP/seq =
# ~196 TF/s = ~20% MFU of the 989 TF/s bf16 peak; applying that SAME MFU
# to transformer-base's train FLOPs/token gives the tokens/s an H100
# would post on this config.  This is generous to the H100 (small d=512 /
# seq-64 models run at LOWER MFU than BERT-base), hence an honest upper
# anchor.  Note the physics: H100:v5e peak ratio is ~5:1, so any
# compute-bound config on ONE chip is bounded near vs_baseline ~0.2
# at matched MFU (the BERT r1 note; ResNet escapes it by being
# bandwidth-bound on the H100).
H100_NMT_TOKENS_PER_SEC = (H100_BERT_SEQ_PER_SEC * _bert_train_flops_per_seq()
                           / _nmt_train_flops_per_token())


def bench_nmt(batch=128, src_len=64, tgt_len=64, warmup=3, iters=15):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    # transformer-base (config 4 as recorded in BASELINE.md r1)
    cfg = transformer.TransformerConfig(
        src_vocab=30000, trg_vocab=30000, d_model=512, heads=8,
        enc_layers=6, dec_layers=6, ffn=2048, max_len=max(src_len, tgt_len))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, loss = transformer.build_train(cfg, src_len, tgt_len)

    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(2, cfg.src_vocab,
                               (batch, src_len)).astype("int64"),
        "trg_ids": rng.randint(2, cfg.trg_vocab,
                               (batch, tgt_len)).astype("int64"),
        "trg_next": rng.randint(2, cfg.trg_vocab,
                                (batch, tgt_len)).astype("int64"),
        "trg_weight": np.ones((batch, tgt_len), "float32"),
    }
    feed = {k: jax.device_put(v, _device()) for k, v in feed.items()}
    with fluid.scope_guard(scope):
        exe.run(startup)

        def step():
            out, = exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            return out

        med, out = _timed_loop(step, lambda o: np.asarray(o), warmup, iters)
    tokens = batch * (src_len + tgt_len)
    return tokens / med, float(np.asarray(out).reshape(-1)[0])


def bench_longctx(seq_len=4096, batch=1, heads=12, head_dim=64, warmup=3,
                  iters=12, causal=True):
    """Long-context attention (the new-capability tier, SURVEY §5): fwd+bwd
    through the Pallas flash kernel at long sequence vs the XLA-composed
    reference path; reports tokens/s and the speedup."""
    import importlib
    import time as _time

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.pallas_kernels.flash_attention")
    rng = np.random.RandomState(0)
    shape = (batch, heads, seq_len, head_dim)
    q, k, v = (jax.device_put(rng.uniform(-1, 1, shape).astype("float32"),
                              _device()).astype(jnp.bfloat16)
               for _ in range(3))

    def make_loss(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, causal=causal).astype(jnp.float32)
                           ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def timeit(fn):
        g = fn(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(g)[0].ravel()[0:1])
        for _ in range(warmup):
            g = fn(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(g)[0].ravel()[0:1])
        t0 = _time.perf_counter()
        for _ in range(iters):
            g = fn(q, k, v)
        np.asarray(jax.tree_util.tree_leaves(g)[0].ravel()[0:1])
        return (_time.perf_counter() - t0) / iters

    t_flash = timeit(make_loss(
        lambda q, k, v, causal: fa.flash_attention(q, k, v, causal=causal)))
    try:
        t_ref = timeit(make_loss(
            lambda q, k, v, causal: fa._ref_attention(
                q, k, v, None, causal, q.shape[-1] ** -0.5)))
        speedup = t_ref / t_flash
    except Exception as e:
        # the composed path materializes the [S, S] score matrix and OOMs
        # at long sequence — the capability gap the flash kernel closes.
        # Anything that is NOT an out-of-memory failure is a real bug and
        # must surface.
        msg = str(e)
        if not ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg
                or "Ran out of memory" in msg):
            raise
        speedup = float("inf")
    toks = batch * seq_len / t_flash
    return toks, speedup, seq_len


def bench_scaling(batch_per_chip=512, warmup=3, iters=9):
    """Config 5: data-parallel ResNet-50 scaling efficiency across the local
    mesh (with_data_parallel over every chip of the host).  On a 1-chip
    machine this measures 1-chip throughput and emits efficiency=1.0 with
    n_devices=1; on a four-chip host it measures 1 vs 4.  A CPU-mesh smoke
    of the same path runs in tests/test_collective.py."""
    import jax

    _device()  # no chip, no scaling number
    n = len(jax.devices())

    def run(nchips):
        import paddle_tpu as fluid
        from paddle_tpu.models import resnet

        batch = batch_per_chip * nchips
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img, label, loss, acc = resnet.build_train(
                depth=50, class_dim=1000, image_size=224, lr=0.1, amp=True)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name,
            places=[fluid.TPUPlace(i) for i in range(nchips)])
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        # stage once, already split over the mesh the executor will use:
        # staging the global batch on chip 0 would pile nchips batches
        # onto one device before the first step
        from jax.sharding import NamedSharding, PartitionSpec

        split = NamedSharding(cp._mesh(), PartitionSpec("data"))
        feed = {"img": jax.device_put(
                    rng.rand(batch, 3, 224, 224).astype("float32"), split),
                "label": jax.device_put(
                    rng.randint(0, 1000, (batch, 1)).astype("int32"),
                    split)}
        with fluid.scope_guard(scope):
            exe.run(startup)

            def step():
                out, = exe.run(cp, feed=feed, fetch_list=[loss],
                               return_numpy=False)
                return out

            # chunk must equal bench_resnet's: the two are compared
            med, _ = _timed_loop(step, lambda o: np.asarray(o), warmup,
                                 iters)
        return batch / med

    one = run(1)
    if n == 1:
        return 1.0, one, 1, one
    full = run(n)
    return full / (one * n), full, n, one


def bench_serving(requests=300, qps=80.0, buckets="1,4,16"):
    """Continuous-batching serving under open-loop Poisson load
    (serving/engine.py behind the RPC frontend, driven by
    tools/loadgen.py).  Measures end-to-end request latency through the
    admission queue + bucketed batcher, not bare executor dispatch; all
    buckets AOT-prewarm first, so `recompiles` counts executables built
    under TRAFFIC — the round-10 capture protocol marks any nonzero
    value invalid."""
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    import loadgen
    from serve import save_demo_model

    from paddle_tpu.serving import ServingEngine, ServingServer

    _device()  # no chip, no serving number
    # the model and the report go to the (git-ignored) output directory,
    # never to the repo root
    out_dir = os.path.join(_HERE, "chiprun_out", "bench_serving")
    os.makedirs(out_dir, exist_ok=True)
    model_dir = save_demo_model(os.path.join(out_dir, "model"))
    engine = ServingEngine(buckets=buckets)
    engine.add_model("fc", model_dir)
    manifest = engine.prewarm()
    miss0 = _miss_total()
    server = ServingServer(engine, port=0).start()
    out_json = os.path.join(out_dir, "BENCH_serving.json")
    try:
        rc = loadgen.main([
            "--endpoints", "127.0.0.1:%d" % server.port, "--model", "fc",
            "--requests", str(requests), "--qps", str(qps),
            "--batch-mix", "1,1,2,4,8", "--out", out_json])
        if rc != 0:
            raise RuntimeError("loadgen failed (rc=%d)" % rc)
    finally:
        server.shutdown()
    with open(out_json) as f:
        report = json.load(f)
    report["recompiles"] = _miss_total() - miss0
    report["prewarm"] = manifest
    report.update(_device_fields())
    with open(out_json, "w") as f:
        json.dump(report, f, indent=1)
    return report


def main():
    # arm the metrics registry before the lazy paddle_tpu import (flags
    # read FLAGS_* env at import time).  BENCH_TELEMETRY=0 opts out.
    if os.environ.get("BENCH_TELEMETRY", "1") == "1":
        os.environ.setdefault("FLAGS_telemetry", "1")
        # the compile story's durations are span attributes; with no
        # FLAGS_telemetry_dir the records stay in memory
        os.environ.setdefault("FLAGS_tracing", "1")
    # persistent two-tier compilation cache, placed by the one resolver:
    # $JAX_COMPILATION_CACHE_DIR, else FLAGS_compile_cache_dir, else the
    # fixed in-checkout path — so a repeat of the same config pays
    # compile_ms_cold ~0 (restore from disk instead of XLA)
    from paddle_tpu.core import compile_cache

    compile_cache.place()
    print("bench: %s" % json.dumps(_device_fields()), file=sys.stderr)
    _peaks()  # an unknown device fails before the run, not after it
    cfg = os.environ.get("BENCH_CONFIG", "resnet50")
    iters = int(os.environ.get("BENCH_ITERS", "60"))
    if cfg == "bert":
        batch = int(os.environ.get("BENCH_BATCH", "224"))
        seqs, _loss = bench_bert(
            batch=batch, iters=max(iters // 2, 5),
            remat=os.environ.get("BENCH_REMAT", "0") == "1")
        tfs = seqs * _bert_train_flops_per_seq() / 1e12
        rec = {
            "metric": "bert_base_pretrain_seqs_per_sec_per_chip",
            "value": round(seqs, 2),
            "unit": "seqs/sec",
            "vs_baseline": round(seqs / H100_BERT_SEQ_PER_SEC, 4),
            "model_tflops_per_sec": round(tfs, 1),
            "mfu_vs_peak": round(_mfu(tfs), 4),
            "batch": batch,
            # analytic per-rank ICI wire bytes per step of the gradient
            # exchange (BENCH_COLLECTIVE=1 + multi-device; else 0.0).
            # FLAGS_allreduce_dtype=int8 should read ~0.25x the f32 row;
            # FLAGS_collective_mode=zero1 at f32 matches replicated (the
            # RS+AG pair costs exactly one ring allreduce)
            "bytes_on_ici_per_step": round(_BERT_WIRE_BYTES, 1),
        }
        rec.update(_telemetry_stats())
        _emit(rec)
    elif cfg == "nmt":
        batch = int(os.environ.get("BENCH_BATCH", "128"))
        toks, _loss = bench_nmt(batch=batch, iters=max(iters // 2, 5))
        tfs = toks * _nmt_train_flops_per_token() / 1e12
        _emit(dict({
            "metric": "transformer_nmt_tokens_per_sec_per_chip",
            "value": round(toks, 2),
            "unit": "tokens/sec",
            # anchor: H100 at its BERT-anchor MFU applied to this model's
            # FLOPs/token (derivation at H100_NMT_TOKENS_PER_SEC; ~0.2 is
            # the peak-ratio bound for compute-bound 1-chip configs)
            "vs_baseline": round(toks / H100_NMT_TOKENS_PER_SEC, 4),
            "model_tflops_per_sec": round(tfs, 1),
            "mfu_vs_peak": round(_mfu(tfs), 4),
        }, **_telemetry_stats()))
    elif cfg == "serving":
        requests = int(os.environ.get("BENCH_REQUESTS", "300"))
        qps = float(os.environ.get("BENCH_QPS", "80"))
        rep = bench_serving(requests=requests, qps=qps)
        _emit({
            "metric": "serving_p99_latency_ms",
            "value": rep["latency_ms_p99"],
            "unit": "ms",
            # under open-loop load the server must sustain what was
            # offered: achieved/offered QPS is the health ratio
            "vs_baseline": round(rep["achieved_qps"] / qps, 4),
            "latency_ms_p50": rep["latency_ms_p50"],
            "qps_under_load": rep["achieved_qps"],
            "batch_fill": rep["batch_fill"],
            "shed_rate": rep["shed_rate"],
            "dropped": rep["dropped"],
            "recompiles": rep["recompiles"],
        })
    elif cfg == "longctx":
        seq = int(os.environ.get("BENCH_SEQ", "4096"))
        toks, speedup, seq = bench_longctx(seq_len=seq)
        _emit({
            "metric": "flash_attention_fwdbwd_tokens_per_sec_seq%d" % seq,
            "value": round(toks, 1),
            "unit": "tokens/sec",
            # vs XLA-composed attention; inf-> -1 = composed path OOMs
            "vs_baseline": (round(speedup, 3)
                            if speedup != float("inf") else -1),
        })
    elif cfg == "scaling":
        eff, ips, n, one_chip = bench_scaling(iters=15)
        # single-chip shard_map vs plain-executor parity (round-2 verdict
        # perf item: on a pod the shard_map path IS the execution path, so
        # its 1-chip throughput must match the plain executor's).  Both
        # legs use the same _timed_loop harness (chunk=5, 3 chunks) — a
        # mismatched chunking previously read as a phantom 7-15% overhead
        plain_ips, _ = bench_resnet(batch=512, warmup=3, iters=15)
        _emit(dict({
            "metric": "resnet50_dp_scaling_efficiency",
            "value": round(eff, 4),
            "unit": "fraction_linear_%dchips" % n,
            "vs_baseline": round(eff / 0.90, 4),  # gate: >=90% linear
            "images_per_sec_total": round(ips, 2),
            "plain_images_per_sec": round(plain_ips, 2),
            "spmd_over_plain": round(one_chip / plain_ips, 4),
        }, **_telemetry_stats()))
    else:
        batch = int(os.environ.get("BENCH_BATCH", "512"))
        amp = os.environ.get("BENCH_AMP", "1") == "1"
        data_format = os.environ.get("BENCH_DATA_FORMAT", "NCHW")
        img_per_sec, _loss = bench_resnet(batch=batch,
                                          iters=max(iters, 240), amp=amp,
                                          chunk=int(os.environ.get(
                                              "BENCH_CHUNK", "120")),
                                          data_format=data_format)
        tfs = img_per_sec * _resnet50_train_flops_per_image() / 1e12
        from paddle_tpu.pallas_kernels import adoption

        _emit(dict({
            "metric": "resnet50_train_images_per_sec_per_chip",
            "value": round(img_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(img_per_sec / H100_RESNET50_IMG_PER_SEC, 4),
            "model_tflops_per_sec": round(tfs, 1),
            "mfu_vs_peak": round(_mfu(tfs), 4),
            # which Pallas kernels actually engaged during the run
            "pallas_kernels_active": adoption.active_kernels(),
        }, **_telemetry_stats()))


if __name__ == "__main__":
    main()
