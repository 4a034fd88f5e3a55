"""Capture a jax.profiler trace of the BERT bench step and print the
per-fusion device-time decomposition (the round-4/5 optimization loop's
measurement tool), plus an optional HBM footprint audit.

Usage: python tools/profile_bert_step.py [steps] [--steps N] [--audit]
                                         [--tiny] [--no-trace]

  --steps N    profiled steps (default 3; bare positional N still works)
  --audit      print the compiled step's memory_analysis with per-var
               attribution (core/memory_audit.py; same report as
               FLAGS_hbm_audit=1) before the timing trace
  --tiny       BERT_TINY config at batch 8 — a seconds-long CPU dry pass
               (the run_ci.sh --layout-smoke leg)
  --no-trace   skip the jax.profiler trace (audit/step-run only; the
               profiler needs a real TPU to produce XLA-Ops lanes)

Env: PROFILE_BATCH (default 192), PROFILE_TOP_OPS=1 for per-op listing.
"""

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_args(argv):
    steps, audit, tiny, trace = 3, False, False, True
    it = iter(argv)
    for a in it:
        if a == "--steps":
            steps = int(next(it))
        elif a.startswith("--steps="):
            steps = int(a.split("=", 1)[1])
        elif a == "--audit":
            audit = True
        elif a == "--tiny":
            tiny = True
        elif a == "--no-trace":
            trace = False
        elif a.lstrip("-").isdigit():
            steps = int(a)
        else:
            raise SystemExit("unknown arg %r (see module docstring)" % a)
    return steps, audit, tiny, trace


def _print_telemetry(fluid):
    """Host-side step stats from the metrics registry — complements the
    device-time decomposition below (which only a real TPU trace gives)."""
    tel = fluid.telemetry
    if not tel.enabled():
        return
    snap = tel.snapshot()
    hists = snap.get("histograms", {})
    step = hists.get("executor_step_ms") or {}
    comp = hists.get("executor_compile_ms") or {}
    print("telemetry: steps=%d recompiles=%d cache_hits=%d "
          "compile_ms=%.1f step_ms p50=%.2f p90=%.2f p99=%.2f" % (
              tel.counter_total("executor_steps_total"),
              tel.counter_total("executor_cache_miss_total"),
              tel.counter_total("executor_cache_hit_total"),
              comp.get("sum", 0.0),
              step.get("p50", 0.0), step.get("p90", 0.0),
              step.get("p99", 0.0)))


def main():
    import jax
    import numpy as np

    steps, audit, tiny, do_trace = _parse_args(sys.argv[1:])

    # build the bench step exactly as bench_bert does, but hand-run it
    import paddle_tpu as fluid
    from paddle_tpu.models import bert as bert_model

    # host-side step stats ride the same run (core/telemetry.py); the
    # jax.profiler trace below still owns the device-time story
    fluid.set_flags({"FLAGS_telemetry": True})

    if tiny:
        batch, seq = 8, 32
        cfg = bert_model.BERT_TINY
    else:
        batch, seq = int(os.environ.get("PROFILE_BATCH", "192")), 128
        cfg = bert_model.BERT_BASE
    # AMP like bench_bert — the f32 and bf16-carry programs have entirely
    # different fusion structures, so profiling the wrong one misleads
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        inputs, seq_out = bert_model.bert_encoder(cfg, seq)
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
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    if audit:
        # route the executor's first-run audit hook to stdout
        fluid.flags.set_flags({"FLAGS_hbm_audit": True})
        import logging as _logging

        _logging.basicConfig()
        _logging.getLogger().setLevel(_logging.WARNING)
    place = fluid.CPUPlace() if jax.default_backend() == "cpu" \
        else fluid.TPUPlace(0)
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    n_mask = batch * int(seq * 0.15)
    feed = {
        "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq)[None, :, None], (batch, 1, 1)).astype("int64"),
        "sent_ids": rng.randint(0, 2, (batch, seq, 1)).astype("int64"),
        "input_mask": np.ones((batch, seq, 1), "float32"),
        "mask_pos": rng.randint(0, batch * seq, (n_mask, 1)).astype("int64"),
        "mask_label": rng.randint(0, cfg.vocab_size, (n_mask, 1)).astype("int64"),
    }
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    with fluid.scope_guard(scope):
        exe.run(startup)

        def step():
            out, = exe.run(main_p, feed=feed, fetch_list=[loss],
                           return_numpy=False)
            return out

        for _ in range(max(min(3, steps), 1)):
            out = step()
        np.asarray(out)
        print("profile_bert_step: cfg=%s batch=%d seq=%d backend=%s "
              "loss=%.4f" % ("tiny" if tiny else "base", batch, seq,
                             jax.default_backend(),
                             float(np.asarray(out).reshape(-1)[0])))

        if not do_trace:
            for _ in range(steps):
                out = step()
            np.asarray(out)
            print("profile_bert_step: %d steps ran (trace skipped)" % steps)
            _print_telemetry(fluid)
            return

        from timeline import from_xplane

        # a fixed (git-ignored) output directory: a chip call brings
        # chiprun_out/ back, a temp dir dies with the machine
        tmpd = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chiprun_out", "bert_prof")
        import shutil

        shutil.rmtree(tmpd, ignore_errors=True)  # from_xplane reads all
        os.makedirs(tmpd)
        with jax.profiler.trace(tmpd):
            for _ in range(steps):
                out = step()
            np.asarray(out)

    trace = from_xplane(tmpd)
    # device lane "XLA Ops"; async -start/-done spans cover their whole
    # in-flight window and OVERLAP compute, so they are not device time —
    # excluded from the totals
    buckets = defaultdict(float)
    total = 0.0
    for ev in trace["traceEvents"]:
        if "XLA Ops" not in ev["tid"]:
            continue
        name = ev["name"]
        if ("-start" in name or "-done" in name or "slice-s" in name
                or "copy-s" in name or "copy-d" in name):
            continue
        key = name.split(".")[0].split("(")[0].split("=")[0].strip()
        buckets[key] += ev["dur"] / 1e3  # ms
        total += ev["dur"] / 1e3
    _print_telemetry(fluid)
    print("total sync device ms over %d steps: %.1f (%.1f ms/step)" %
          (steps, total, total / steps))
    for k, v in sorted(buckets.items(), key=lambda kv: -kv[1])[:28]:
        print("  %-46s %8.2f ms/step" % (k, v / steps))
    if os.environ.get("PROFILE_TOP_OPS") == "1":
        per_op = defaultdict(float)
        for ev in trace["traceEvents"]:
            if "XLA Ops" not in ev["tid"]:
                continue
            name = ev["name"]
            if ("-start" in name or "-done" in name):
                continue
            per_op[name] += ev["dur"] / 1e3
        print("\ntop individual ops:")
        for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])[:40]:
            print("  %9.3f ms/step  %s" % (v / steps, k))


if __name__ == "__main__":
    main()
