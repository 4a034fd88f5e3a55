"""Probe of the decode step alone, on the chip: no server, no wire, no
scheduler.  One serving configuration of the benchmark (``--config``: a
name under ``benchmark/configs/`` or a path; GPT-2-medium by default) at
its own widths, weight dtype and KV residency, at one lane bucket and one
pool size, through the same ``CarriedStepFn`` the engine uses.  Prints, and
writes under ``chiprun_out/``, one JSON object:

* ``memory``: the compiled step's ``temp_bytes`` / ``alias_bytes`` beside the
  pool's bytes, and the pool-sized instructions left in its HLO;
* ``step_ms``: host clock over steps that end in ``block_until_ready``;
* ``scope_ms_per_step``: device time per step by ``jax.named_scope``
  (``layer<i>`` folded to ``layerN``), from a profile of ``--steps`` steps
  reduced by ``benchmark/trace_reduce.py`` and keyed through the HLO's
  ``op_name`` metadata; ``top_ops`` names the dearest single instructions.

    chiprun -- python tools/decode_step_probe.py --blocks 1024 --bucket 32
    chiprun -- python tools/decode_step_probe.py --config olmoe-1b-7b-serve \
        --blocks 2048

For a routed-expert configuration the result also gives ``moe/experts``'
achieved bytes/s (``benchmark/moe_cost.py`` over the scope's device time),
and ``--experts ragged`` swaps the block's expert matmuls for this file's
``experts_ragged`` (tokens sorted by expert, ``jax.lax.ragged_dot``): the
comparison the block's choice was made by, not an option of the program.

It needs the TPU for a time; ``--compile-only`` stops after ``memory`` (it
then says what the local backend's compiler made, which is not the chip's).
"""

import argparse
import json
import math
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WARM_STEPS = 5


def hlo_index(text):
    """instruction name -> (opcode, shape, op_name scope) of a compiled
    module's entry computation and fusions."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if not m:
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = (m.group(3), m.group(2),
                           scope.group(1) if scope else "")
    return out


def scope_of(op_name):
    """``jit(step)/layer3/attn/kv_write/scatter`` -> ``layerN/attn/kv_write``."""
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    parts = [re.sub(r"^layer\d+$", "layerN", p) for p in parts]
    keep = [p for p in parts
            if p in ("layerN", "attn", "mlp", "moe", "router", "experts",
                     "lm_head", "kv_write", "kv_gather")]
    return "/".join(keep) or "other"


def pool_sized(index, pool_elems):
    """Instructions whose result is at least one layer pool, by opcode."""
    found = {}
    for _name, (op, shape, _scope) in index.items():
        if op in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        dims = re.findall(r"\[([\d,]+)\]", shape)
        elems = max((math.prod(map(int, d.split(","))) for d in dims),
                    default=0)
        if elems >= pool_elems:
            found[op] = found.get(op, 0) + 1
    return found


def experts_ragged(k):
    """``models/olmoe.py`` ``_experts`` by sorting: each lane's ``k``
    chosen experts become ``B * k`` rows ordered by expert, and one
    ``ragged_dot`` per projection runs each expert over its own rows."""
    import jax
    import jax.numpy as jnp

    def experts(h2, gates, wgate, wup, wdown):
        weight, idx = jax.lax.top_k(gates, k)
        order = jnp.argsort(idx.reshape(-1))
        lane = order // k
        sizes = jnp.bincount(idx.reshape(-1), length=gates.shape[1]
                             ).astype(jnp.int32)
        dot = lambda x, w: jax.lax.ragged_dot(
            x.astype(w.dtype), w, sizes,
            preferred_element_type=jnp.float32)
        x = h2[lane]
        y = dot(jax.nn.silu(dot(x, wgate)) * dot(x, wup), wdown)
        y = y * weight.reshape(-1)[order][:, None]
        return jnp.zeros(h2.shape, jnp.float32).at[lane].add(y)

    return experts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2-medium-serve",
                    help="a serving configuration of the benchmark: a name "
                    "under benchmark/configs/ or a path to such a file")
    ap.add_argument("--experts", default="block",
                    choices=("block", "ragged"))
    ap.add_argument("--blocks", type=int, default=1024)
    ap.add_argument("--bucket", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--dtype", default=None,
                    help="KV residency (default: the model's own, else f32)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--label", default="probe")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import moe_cost, trace_reduce
    from benchmark.run import load_module
    from paddle_tpu.core.executor import CarriedStepFn
    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    path = args.config if os.path.exists(args.config) else os.path.join(
        ROOT, "benchmark", "configs", args.config + ".json")
    with open(path) as fp:
        config = json.load(fp)
    config.pop("tiny", None)
    if args.layers:
        config["n_layer" if "n_layer" in config
               else "num_hidden_layers"] = args.layers
    device = jax.devices()[0]
    model = load_module("models", config["model"])
    cfg = model.decoder_config(config)
    params = model.make_params(config, args.seed, device)
    if args.experts == "ragged":
        from paddle_tpu.models import olmoe

        olmoe._experts = experts_ragged(cfg.experts_per_token)
    args.dtype = args.dtype or cfg.kv_dtype or "f32"
    kv = kvc.KVCacheConfig(cfg.layers, cfg.heads, cfg.head_dim,
                           args.block_size, args.blocks, args.dtype)
    cache = kvc.PagedKVCache(kv)
    b, maxb = args.bucket, cfg.max_seq // args.block_size

    # every lane mid-sequence, its blocks its own, as in the cell's window
    rng = np.random.default_rng(args.seed)
    grow = WARM_STEPS + 2 * args.steps
    longest = min(700, cfg.max_seq - grow,
                  (args.blocks - 1) // b * args.block_size - grow)
    lens = rng.integers(min(200, longest - 1), longest, b).astype(np.int32)
    tables = np.full((b, maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, args.blocks)))
    for i in range(b):
        for j in range(-(-int(lens[i] + grow) // args.block_size)):
            tables[i, j] = next(free)
    tok = rng.integers(0, cfg.vocab, b).astype(np.int32)

    stepfn = CarriedStepFn(dm.make_paged_step(cfg, kv), donate_argnums=(0,),
                           name="probe")
    feed = lambda n: (cache.carry(), params, tok, lens + n - 1, tables,
                      lens + n)
    warm = stepfn.warmup(*feed(0))
    compiled = stepfn._compiled[stepfn._sig(feed(0))]
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo_out:
        os.makedirs(os.path.dirname(args.hlo_out) or ".", exist_ok=True)
        with open(args.hlo_out, "w") as fp:
            fp.write(text)
    index = hlo_index(text)
    pool_elems = args.blocks * args.block_size * cfg.hidden
    result = {
        "label": args.label, "config": config["name"],
        "experts": args.experts, "device": device.device_kind,
        "platform": device.platform, "blocks": args.blocks,
        "bucket": b, "dtype": args.dtype, "layers": cfg.layers,
        "memory": {"temp_bytes": int(memory.temp_size_in_bytes),
                   "alias_bytes": int(memory.alias_size_in_bytes),
                   "pool_bytes": cache.nbytes,
                   "compile_ms": round(warm["compile_ms"], 1),
                   "pool_sized_instructions": pool_sized(index, pool_elems)},
    }
    if not args.compile_only:
        if device.platform != "tpu":
            print("decode_step_probe: no TPU, so no time "
                  "(use --compile-only)", file=sys.stderr)
            return 2

        def run(n0, n):
            for i in range(n0, n0 + n):
                carry, nxt = stepfn(*feed(i))[:2]
                cache.replace_carry(carry)
                nxt.block_until_ready()

        run(0, WARM_STEPS)
        t0 = time.perf_counter()
        run(WARM_STEPS, args.steps)
        result["step_ms"] = (time.perf_counter() - t0) * 1e3 / args.steps
        trace_dir = tempfile.mkdtemp(prefix="decode_step_probe_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            run(WARM_STEPS + args.steps, args.steps)
        jax.profiler.stop_trace()
        prof = trace_reduce.reduce_dir(trace_dir, top=12)
        scopes = {}
        short = lambda name: trace_reduce._short(name).lstrip("%_")
        for name, secs in prof["op_seconds"].items():
            key = scope_of(index.get(short(name), ("", "", ""))[2])
            scopes[key] = scopes.get(key, 0.0) + secs * 1e3 / args.steps
        result["busy_ms_per_step"] = prof["busy_s"] * 1e3 / args.steps
        result["scope_ms_per_step"] = dict(
            sorted(scopes.items(), key=lambda kv: -kv[1]))
        result["top_ops"] = [
            [n, round(s * 1e3 / args.steps, 4),
             "/".join(index.get(short(n), ("", "", ""))[::2])[:120]]
            for n, s in prof["device_ops"]]
        moe_ms = sum(v for k, v in scopes.items() if k.endswith("experts"))
        if cfg.arch == "olmoe" and moe_ms:
            # every expert of every layer, read once a step (32 lanes x 8
            # over 64 experts leave none unread), over the scope's time
            moved = moe_cost.expert_stream_bytes_per_step(
                config, config["num_experts"])
            result["moe_experts_bytes_per_step"] = moved
            result["moe_experts_bytes_per_s"] = moved / (moe_ms / 1e3)
        stats = device.memory_stats() or {}
        result["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        result["peak_bytes_reserved"] = stats.get("peak_bytes_reserved")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "decode_step_probe.jsonl"), "a") as fp:
        fp.write(json.dumps(result) + "\n")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
