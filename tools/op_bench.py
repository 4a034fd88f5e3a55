"""Config-driven per-op micro-benchmark harness (parity:
paddle/fluid/operators/benchmark/op_tester.cc + op_tester_config.h).

Config: a JSON file (or inline dict) describing one or more ops::

    [
      {"op_type": "matmul",
       "inputs": {"X": {"dims": [64, 1024], "dtype": "fp32",
                        "initializer": "random"},
                  "Y": {"dims": [1024, 1024]}},
       "attrs": {"transpose_X": false},
       "repeat": 100, "device": "tpu"}
    ]

dtypes: fp32/fp64/int32/int64 (reference spellings accepted).
initializers: random | natural | zeros (op_tester_config.h:33-40).

Usage: python tools/op_bench.py <config.json> [--device cpu|tpu]
Prints one JSON line per op: {"op_type", "device", "repeat",
"mean_ms", "p50_ms", "min_ms"}.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DTYPES = {"fp32": "float32", "float": "float32", "fp64": "float64",
           "double": "float64", "int32": "int32", "int": "int32",
           "int64": "int64", "long": "int64",
           "float32": "float32", "float64": "float64"}


def _make_input(spec, rng):
    dims = [int(d) for d in spec["dims"]]
    dtype = _DTYPES[spec.get("dtype", "fp32")]
    init = spec.get("initializer", "random")
    if init == "random":
        a = rng.rand(*dims) if dtype.startswith("float") else rng.randint(
            0, spec.get("max_value", 10), dims)
    elif init == "natural":
        a = np.arange(int(np.prod(dims))).reshape(dims)
    elif init == "zeros":
        a = np.zeros(dims)
    elif init == "file":
        a = np.load(spec["filename"])
    else:
        raise ValueError("unknown initializer %r" % init)
    return np.asarray(a, dtype)


def bench_op(cfg, device=None, repeat=None, warmup=None):
    import paddle_tpu as fluid
    from paddle_tpu.core.registry import get_op_def

    op_type = cfg["op_type"]
    opdef = get_op_def(op_type)
    repeat = int(repeat if repeat is not None else cfg.get("repeat", 50))
    warmup = int(warmup if warmup is not None else cfg.get("warmup", 5))
    dev = device or cfg.get("device", "cpu")

    rng = np.random.RandomState(int(cfg.get("seed", 0)))
    feeds = {}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        inputs = {}
        for slot, spec in cfg.get("inputs", {}).items():
            name = "in_%s" % slot
            arr = _make_input(spec, rng)
            v = fluid.layers.data(name, shape=list(arr.shape[1:]),
                                  dtype=str(arr.dtype))
            feeds[name] = arr
            inputs[slot] = [v]
        block = main.global_block()
        outs = {}
        fetch = []
        for oslot in opdef.output_slots:
            ov = block.create_var(
                name="out_%s" % oslot,
                dtype=next(iter(feeds.values())).dtype.name
                if feeds else "float32")
            outs[oslot] = [ov]
            fetch.append(ov)
        from paddle_tpu.layer_helper import LayerHelper

        helper = LayerHelper(op_type)
        helper.append_op(type=op_type, inputs=inputs, outputs=outs,
                         attrs=dict(cfg.get("attrs", {})))

    place = fluid.TPUPlace(0) if dev == "tpu" else fluid.CPUPlace()
    exe = fluid.Executor(place)
    times = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(max(warmup, 1)):  # >=1: the first run compiles
            o = exe.run(main, feed=feeds, fetch_list=fetch[:1],
                        return_numpy=False)
        np.asarray(o[0])
        for _ in range(repeat):
            t0 = time.perf_counter()
            o = exe.run(main, feed=feeds, fetch_list=fetch[:1],
                        return_numpy=False)
            np.asarray(o[0])  # sync
            times.append((time.perf_counter() - t0) * 1e3)
    times = np.asarray(times)
    return {"op_type": op_type, "device": dev, "repeat": repeat,
            "mean_ms": round(float(times.mean()), 4),
            "p50_ms": round(float(np.median(times)), 4),
            "min_ms": round(float(times.min()), 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--device", default=None, choices=[None, "cpu", "tpu"])
    ap.add_argument("--repeat", type=int, default=None,
                    help="override every config row's repeat count")
    ap.add_argument("--warmup", type=int, default=None,
                    help="override every config row's warmup count")
    args = ap.parse_args()
    with open(args.config) as f:
        cfgs = json.load(f)
    if isinstance(cfgs, dict):
        cfgs = [cfgs]
    for cfg in cfgs:
        print(json.dumps(bench_op(cfg, device=args.device,
                                  repeat=args.repeat, warmup=args.warmup)))


if __name__ == "__main__":
    main()
