"""Runner for training cells: a Fluid ``Program`` through
``fluid.Executor``, one chip or data-parallel over the chips of the host
(``CompiledProgram.with_data_parallel``).

The loop is a Fluid user's: every step feeds a fresh host batch (numpy,
from a seeded pool) through ``exe.run(feed=...)`` and fetches the loss as a
numpy value.  The window opens after the warm-up steps (the first of which
compiles, or restores the step from the compile cache) and closes on the
first fetched loss at or after ``--seconds``.
"""

import time

import numpy as np


def persistables_off_device(main, scope, platform):
    """Names of initialised persistables that are not jax.Arrays on
    ``platform`` (there should be none), and how many were looked at."""
    import jax

    bad, seen = [], 0
    for var in main.list_vars():
        if not var.persistable or var.is_data:
            continue
        sv = scope.find_var(var.name)
        if sv is None or not sv.get_tensor()._is_initialized():
            continue
        seen += 1
        val = sv.get_tensor().get()
        if not (isinstance(val, jax.Array)
                and {d.platform for d in val.devices()} == {platform}):
            bad.append(var.name)
    return bad, seen


def run(ctx):
    import jax

    import paddle_tpu as fluid

    config, traffic, chips = ctx.config, ctx.traffic, ctx.chips
    model = ctx.load("models", config["model"])
    main, startup, loss = model.build_program(config, traffic)
    main.random_seed = startup.random_seed = ctx.seed31
    pool = [model.make_batch(np.random.default_rng([ctx.seed, i]), config,
                             traffic, chips)
            for i in range(int(traffic["pool_batches"]))]
    program = main
    if chips > 1:
        program = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name,
            places=[fluid.TPUPlace(i) for i in range(chips)])
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    losses = []

    def step():
        out, = exe.run(program, feed=pool[len(losses) % len(pool)],
                       fetch_list=[loss])
        losses.append(float(np.mean(np.asarray(out))))

    def steps_for(seconds, annotate=None):
        """Step until ``seconds`` have passed; -> (elapsed, step times)."""
        times = []
        t0 = t = time.monotonic()
        while t - t0 < seconds:
            if annotate is None:
                step()
            else:
                with annotate("bench.exe_run"):
                    step()
            now = time.monotonic()
            times.append(now - t)
            t = now
        return t - t0, times

    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(int(traffic["warmup_steps"])):
            step()
        compiles0 = ctx.compiles()
        miss0 = ctx.counter("executor_cache_miss_total")
        ctx.open_window()
        elapsed, times = steps_for(ctx.seconds)
        compiles = ctx.compiles() - compiles0
        misses = ctx.counter("executor_cache_miss_total") - miss0
        traced_steps = 0
        if ctx.trace:
            with ctx.profiled():
                _t, traced = steps_for(float(traffic["trace_seconds"]),
                                       jax.profiler.TraceAnnotation)
            traced_steps = len(traced)
        bad, seen = persistables_off_device(
            main, scope, jax.devices()[0].platform)

    n_pool = len(pool)
    first = model.expected_first_loss(config)
    tolerance = float(traffic["first_loss_tolerance"])
    checks = {
        "every fetched loss is finite": bool(np.all(np.isfinite(losses))),
        "first loss %.4f within %.2f of ln(vocab) %.4f"
        % (losses[0], tolerance, first): abs(losses[0] - first) < tolerance,
        "no executable built inside the window (%d)" % compiles:
            compiles == 0,
        "all %d persistables on the device (not: %s)" % (seen, bad[:3]):
            seen > 0 and not bad,
    }
    if len(losses) >= 2 * n_pool:
        a, b = np.mean(losses[:n_pool]), np.mean(losses[-n_pool:])
        checks["mean loss of the last pool pass %.4f below the first %.4f"
               % (b, a)] = bool(b < a)
    tokens = model.tokens_per_step(traffic, chips)
    rate = tokens * len(times) / elapsed
    notes = ["[%s] %s" % ("ok" if ok else "FAIL", what)
             for what, ok in checks.items()]
    notes.append("steps %d in %.3f s, median step %.3f ms, losses %.4f -> "
                 "%.4f" % (len(times), elapsed, 1e3 * float(np.median(times)),
                           losses[0], losses[-1]))
    return {
        "correct": all(checks.values()),
        "attempted": len(times),
        "failed": int(sum(not np.isfinite(x) for x in losses)),
        "end_to_end": {"train_tokens_per_s": rate},
        "notes": notes,
        "obs": {"kind": "train", "step_s": times, "tokens_per_s": rate,
                "flops_per_token": model.flops_per_token(config, traffic),
                "recompiles": misses if ctx.trace else None,
                "traced_steps": traced_steps},
    }
