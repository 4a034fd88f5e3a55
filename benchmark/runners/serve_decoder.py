"""Runner for serving cells: the repo's decoder behind ``DecodeEngine`` and
``ServingServer`` in this process (which holds the chip), and the traffic
from a child process (``benchmark/loadgen.py``) over the native RPC wire.

Set-up, in order: weights on the device from the seed, the engine with the
deployment's lane buckets, KV blocks and deadline (from the traffic file),
prewarm of those buckets (compile, or restore from the compile cache), the
server, the correctness check against the plain reference, the child and
its ramp.  Then the window: the parent only waits; every number comes from
the child's record of what its clients saw.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

MODEL = "bench"
CHILD_START_S = 0.5     # from the child's "ready" to its first client
TRACE_LEAD_S = 4.0      # load kept up beyond the traced seconds


def check_cases(ctx, endpoint, config, traffic):
    """A few short seeded requests, sent together from this process outside
    the window: [(prompt ids, served ids)]."""
    from concurrent.futures import ThreadPoolExecutor

    from paddle_tpu.serving import ServingClient

    n_out = 16
    count = int(traffic["check_requests"])
    longest = min(32, config["n_positions"] - n_out - 1)

    def ask(i):
        rng = np.random.default_rng([ctx.seed, 1 << 21, i])
        n = max(longest * (i + 1) // count, 1)
        prompt = [int(t) for t in rng.integers(0, config["vocab_size"], n)]
        reply = ServingClient(endpoints=[endpoint]).generate(
            MODEL, prompt, max_new_tokens=n_out,
            deadline_ms=float(traffic["deadline_ms"]))
        if reply.status != "ok":
            raise RuntimeError("check request %d: %s %s"
                               % (i, reply.status, reply.error))
        return prompt, [int(t) for t in np.asarray(
            reply.outputs["tokens"]).reshape(-1)]

    with ThreadPoolExecutor(count) as pool:
        cases = list(pool.map(ask, range(count)))
    return cases, longest + n_out


def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q))


def client_metrics(rows, t0, t1, deadline_s):
    """What the clients saw in the window [t0, t1)."""
    tokens, itl, ttft, failed = 0, [], [], 0
    sent = [r for r in rows if r["t_send"] is not None
            and t0 <= r["t_send"] < t1]
    for r in rows:
        times = r["token_times"]
        tokens += sum(t0 <= t < t1 for t in times)
        itl += [b - a for a, b in zip(times, times[1:]) if t0 <= b < t1]
    for r in sent:
        if r["token_times"]:
            ttft.append(r["token_times"][0] - r["t_send"])
        else:
            # failed or never answered: it missed any limit
            ttft.append(deadline_s)
            failed += 1
        if r["token_times"] and r["status"] not in (None, "ok"):
            failed += 1
    return {"tokens": tokens, "itl_s": itl, "ttft_s": ttft,
            "prompt_lens": [r["prompt_len"] for r in sent],
            "attempted": len(sent), "failed": failed}


def run(ctx):
    import jax

    from paddle_tpu.serving import DecodeEngine, ServingEngine, ServingServer

    config, traffic = ctx.config, ctx.traffic
    model = ctx.load("models", config["model"])
    reference = ctx.load("reference", config["reference"])
    device = jax.devices()[0]
    deadline_ms = float(traffic["deadline_ms"])

    dcfg = model.decoder_config(config)
    params = model.make_params(config, ctx.seed, device)
    engine = DecodeEngine(buckets=traffic["lane_buckets"],
                          deadline_ms=deadline_ms)
    engine.add_model(MODEL, (dcfg, params),
                     kv_blocks=int(traffic["kv_blocks"]))
    manifest = engine.prewarm()
    engine.start()
    server = ServingServer(ServingEngine(), port=0,
                           decode_engine=engine).start()
    endpoint = "127.0.0.1:%d" % server.port
    child = None
    try:
        cases, pad_to = check_cases(ctx, endpoint, config, traffic)
        ref = reference.check(config, params, cases, pad_to)

        extra = (float(traffic["trace_seconds"]) + TRACE_LEAD_S
                 if ctx.trace else 0.0)
        child = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "loadgen.py"),
             "--endpoint", endpoint, "--model", MODEL,
             "--traffic", json.dumps(traffic), "--seed", str(ctx.seed),
             "--vocab", str(config["vocab_size"]),
             "--max-seq", str(config["n_positions"]),
             "--seconds", repr(ctx.seconds), "--extra", repr(extra)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        if child.stdout.readline().strip() != b"ready":
            raise RuntimeError("loadgen did not start")
        t0 = time.monotonic() + CHILD_START_S + float(traffic["ramp_s"])
        t1 = t0 + ctx.seconds
        child.stdin.write(b"%r\n" % t0)
        child.stdin.flush()
        time.sleep(max(t0 - time.monotonic(), 0.0))
        compiles0 = ctx.compiles()
        miss0 = ctx.counter("executor_cache_miss_total")
        ctx.open_window()
        time.sleep(max(t1 - time.monotonic(), 0.0))
        compiles = ctx.compiles() - compiles0
        misses = ctx.counter("executor_cache_miss_total") - miss0
        pool = engine._models[MODEL].cache.allocator.stats()
        if ctx.trace:
            with ctx.profiled():
                time.sleep(float(traffic["trace_seconds"]))
        out, _err = child.communicate(timeout=deadline_ms / 1e3 + 60.0)
        if child.returncode != 0:
            raise RuntimeError("loadgen exited %d" % child.returncode)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.shutdown()
        engine.stop()

    rows = json.loads(out.decode().strip().splitlines()[-1])["requests"]
    seen = client_metrics(rows, t0, t1, deadline_ms / 1e3)
    done = [r for r in rows if r["status"] is not None]
    not_ok = [r for r in done if r["status"] != "ok"
              or r["n_tokens"] != r["max_new"]]
    checks = {
        "every completed request ok with its token count (%d of %d not)"
        % (len(not_ok), len(done)): bool(done) and not not_ok,
        "no request sent in the window failed or went unanswered (%d of %d)"
        % (seen["failed"], seen["attempted"]): seen["failed"] == 0,
        "no executable built inside the window (%d)" % compiles:
            compiles == 0,
        "no served token is more than %.2f below the reference's largest "
        "logit (%s)" % (reference.DEFICIT_BOUND, json.dumps(ref)): ref["ok"],
    }
    notes = ["[%s] %s" % ("ok" if ok else "FAIL", what)
             for what, ok in checks.items()]
    notes.append("prewarm %s" % json.dumps(manifest))
    spans = ctx.spans("serving.decode_step") if ctx.trace else []
    in_window = [s for s in spans if t0 <= _span_monotonic(s) < t1]
    end_to_end = {}
    if seen["tokens"]:
        end_to_end["serve_tokens_per_s"] = seen["tokens"] / ctx.seconds
    if seen["ttft_s"]:
        end_to_end["ttft_p95_ms"] = 1e3 * percentile(seen["ttft_s"], 95)
    if seen["itl_s"]:
        end_to_end["itl_p95_ms"] = 1e3 * percentile(seen["itl_s"], 95)
    median_ms = lambda xs: 1e3 * percentile(xs, 50) if xs else float("nan")
    notes.append(
        "window: %d requests sent, %d tokens, ttft p50 %.1f ms p95 %.1f ms "
        "(n=%d), itl p50 %.3f ms (n=%d)"
        % (seen["attempted"], seen["tokens"], median_ms(seen["ttft_s"]),
           end_to_end.get("ttft_p95_ms", float("nan")), len(seen["ttft_s"]),
           median_ms(seen["itl_s"]), len(seen["itl_s"])))
    notes.append("kv pool at the window's end: %d of %d blocks held by "
                 "sequences, high water %d (with cached prompt blocks)"
                 % (pool["in_use"], pool["capacity"], pool["high_water"]))
    return {
        "correct": all(checks.values()),
        "attempted": seen["attempted"],
        "failed": seen["failed"],
        "end_to_end": end_to_end,
        "notes": notes,
        "obs": {"kind": "serve", "decode_spans": in_window,
                "ttft_s": seen["ttft_s"], "prompt_lens": seen["prompt_lens"],
                "recompiles": misses if ctx.trace else None,
                # under load every executable started in the profiled
                # seconds is one engine step
                "traced_steps": (ctx.profile or {}).get("module_launches")},
    }


def _span_monotonic(span):
    """A span's start on this process's monotonic clock: spans carry wall
    time in microseconds."""
    return span["ts"] / 1e6 - (time.time() - time.monotonic())
