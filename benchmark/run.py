"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from ``BENCHMARK.json``
at the root of the checkout: the cell's configuration
(``benchmark/configs/<config>.json``), its traffic mix
(``benchmark/traffic/<traffic>.json``), the runner the configuration names
(``benchmark/runners/<runner>.py``) and, in a traced run, one reader per
per-layer metric (``benchmark/layer_metrics/<metric>.py``).  Adding a cell,
a configuration, a mix or a per-layer metric is adding files and entries;
no file that is here needs an edit.

The last line of standard output is the result, one JSON object.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, measured with
the program at its defaults; with ``--trace 1`` they are the cell's
per-layer metrics, and the run turns on the program's own telemetry and
span recording (both off by default) and profiles a few seconds after the
window.

Without a TPU, or with fewer chips than the cell asks for, the command
exits 2 and prints no result.  ``--rehearse-tiny-on-cpu`` is for the
benchmark's own tests: tiny sizes from the ``tiny`` groups of the data
files, on whatever backend JAX has, and a result labelled
``"not_a_chip_result": true``.
"""

import time

_T_PROCESS = time.monotonic()

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as fp:
        return json.load(fp)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` by file name (a metric's name may hold
    dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def with_tiny(data, tiny):
    """The data file as it is run: under ``--rehearse-tiny-on-cpu`` its
    ``tiny`` group overrides the real sizes."""
    data = dict(data)
    overrides = data.pop("tiny", {})
    if tiny:
        data.update(overrides)
    return data


def metrics_of(bench, group, cell):
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Context:
    """What a runner is given, and the measuring tools runners share."""

    def __init__(self, args, bench, cell):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.tiny = bool(args.rehearse_tiny_on_cpu)
        self.cell = cell
        self.chips = int(cell["chips"])
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.config = with_tiny(load_json(ROOT, entry["file"]), self.tiny)
        self.traffic = with_tiny(load_json(
            BENCH_DIR, "traffic", cell["traffic"] + ".json"), self.tiny)
        self.tmp = tempfile.mkdtemp(prefix="paddle_tpu_bench_")
        self.setup_s = None
        self.profile = None
        self._compiles = 0

    def load(self, kind, name):
        module = load_module(kind, name)
        if module is None:
            raise FileNotFoundError("benchmark/%s/%s.py" % (kind, name))
        return module

    # a seed may need more than 31 bits; the program's seeds are 32-bit
    @property
    def seed31(self):
        return (self.seed ^ (self.seed >> 31)) & 0x7FFFFFFF

    def count_compiles(self):
        """Count every executable XLA builds (or fetches from its cache)
        from now on: inside a window there must be none."""
        import jax.monitoring

        def on_event(event, _secs, **_kw):
            if event == COMPILE_EVENT:
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def compiles(self):
        return self._compiles

    def open_window(self):
        """Set-up ends here: process start to the start of the window."""
        self.setup_s = time.monotonic() - _T_PROCESS

    def instrument(self):
        """Traced run only: the program's telemetry counters and spans are
        off by default; a per-layer metric can only read them switched on."""
        import paddle_tpu as fluid

        fluid.set_flags({"FLAGS_telemetry": True, "FLAGS_tracing": True,
                         "FLAGS_telemetry_dir": self.tmp})

    def counter(self, name):
        from paddle_tpu import telemetry

        return float(telemetry.counter_total(name))

    def spans(self, name):
        """The program's recorded spans of one name (traced run)."""
        from paddle_tpu.core import tracing

        tracing.flush()
        out = []
        for fn in sorted(os.listdir(self.tmp)):
            if not (fn.startswith("trace-") and fn.endswith(".jsonl")):
                continue
            with open(os.path.join(self.tmp, fn)) as fp:
                for line in fp:
                    if '"%s"' % name not in line:
                        continue
                    rec = json.loads(line)
                    if rec.get("t") == "span" and rec.get("name") == name:
                        out.append(rec)
        return out

    @contextlib.contextmanager
    def profiled(self):
        """Profile the enclosed work; afterwards ``self.profile`` holds the
        trace's reduction (``trace_reduce.reduce_profile``).  The program's
        own instrumentation is off meanwhile: it slows the host, and the
        idle share read here has to be the uninstrumented system's."""
        import jax

        import paddle_tpu as fluid
        from benchmark import trace_reduce

        fluid.set_flags({"FLAGS_telemetry": False, "FLAGS_tracing": False})
        trace_dir = os.path.join(self.tmp, "profile")
        options = jax.profiler.ProfileOptions()
        # the Python tracer would slow the host it is meant to observe
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
                yield
        finally:
            jax.profiler.stop_trace()
            self.instrument()
        self.profile = trace_reduce.reduce_dir(trace_dir)

    def close(self):
        shutil.rmtree(self.tmp, ignore_errors=True)


def device_report(ctx, devices):
    """Two counters of the runtime's allocator, as it reports them after
    the run, on the fullest chip.  ``peak_bytes_in_use`` holds the buffers
    the program owns (weights, optimizer state, the KV pool, feeds); on this
    backend (TPU v5e, libtpu 0.0.34) it does not move for the temporaries an
    executable is given while it runs, which the runtime counts under
    ``peak_bytes_reserved`` instead (PERF.md section 6 has the probe).  The
    chip's peak is the two together; each is also given alone."""
    best = (0, 0, 0)
    for d in devices[:ctx.chips]:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        best = max(best, (in_use + reserved, in_use, reserved))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": best[0],
           "peak_bytes_in_use": best[1], "peak_bytes_reserved": best[2]}
    if ctx.trace and ctx.profile is not None:
        out["busy_s"] = ctx.profile["busy_s"]
        out["window_s"] = ctx.profile["window_s"]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse-tiny-on-cpu", action="store_true",
                    help="TEST ONLY: tiny sizes on any backend; the result "
                    "is labelled not a chip result")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print("run.py: no workload %r in BENCHMARK.json (has: %s)"
              % (args.workload, [w["name"] for w in bench["workloads"]]),
              file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = bench["run_seconds"]

    sys.path.insert(0, ROOT)
    import jax

    devices = jax.devices()
    if not args.rehearse_tiny_on_cpu and (
            devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print("run.py: cell %s needs %d TPU chip(s); JAX found %d %s "
              "device(s)" % (cell["name"], cell["chips"], len(devices),
                             devices[0].platform), file=sys.stderr)
        return 2
    peaks = load_json(BENCH_DIR, "peaks.json")
    if not args.rehearse_tiny_on_cpu and devices[0].device_kind not in peaks:
        print("run.py: no published peak for device_kind %r in "
              "benchmark/peaks.json" % devices[0].device_kind,
              file=sys.stderr)
        return 2

    from paddle_tpu.core import compile_cache

    print("run.py: %s seed %d, %.0f s, trace %d, on %d x %s; compile cache "
          "at %s" % (cell["name"], args.seed, args.seconds, args.trace,
                     len(devices), devices[0].device_kind,
                     compile_cache.place()), flush=True)

    ctx = Context(args, bench, cell)
    try:
        if ctx.trace:
            ctx.instrument()
        ctx.count_compiles()
        runner = ctx.load("runners", ctx.config["runner"])
        result = runner.run(ctx)
        obs = result["obs"]
        # a profile with no device plane (a CPU rehearsal) is nothing to read
        seen = ctx.profile if ctx.profile and ctx.profile["chips"] else None
        obs.update(profile=seen, chips=ctx.chips, config=ctx.config,
                   traffic=ctx.traffic, device_kind=devices[0].device_kind,
                   peaks=peaks.get(devices[0].device_kind))
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        if ctx.trace:
            values = {}
            for metric in metrics_of(bench, "per_layer", cell["name"]):
                reader = load_module("layer_metrics", metric["name"])
                value = reader.read(obs) if reader is not None else None
                if value is not None:
                    values[metric["name"]] = float(value)
        group = "per_layer" if ctx.trace else "end_to_end"
        units = {m["name"]: m["unit"] for m in bench[group]}
        wanted = [m["name"] for m in metrics_of(bench, group, cell["name"])]
        line = {
            "correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": {n: {"value": values[n], "unit": units[n]}
                        for n in wanted if values.get(n) is not None},
            "device": device_report(ctx, devices),
        }
        if ctx.trace and ctx.profile is not None:
            line["breakdown"] = {"device_ops": ctx.profile["device_ops"],
                                 "idle_gaps": ctx.profile["idle_gaps"]}
        if ctx.tiny:
            line["not_a_chip_result"] = True
        for note in result.get("notes", []):
            print("run.py: " + note, flush=True)
        print(json.dumps(line), flush=True)
    finally:
        ctx.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
