"""A program for the chip, run by hand: what the serving cell's correctness
check can tell apart at the real size.

    python3 benchmark/tests/chip_check_precision.py [--seed N] [--tiny]

It makes the configuration's weights from the seed, produces greedy tokens
for the check's four prompts from the reference's own forward pass run in
float32 at the device's default matmul precision (on a TPU: bfloat16
multiplies of float32 operands, which is how the served step computes) and
again with weights and activations in bfloat16 end to end, and prints what
``gpt2_ref.check`` says of each.  The first has to pass.  The second passes
too (PERF.md section 6): the check reads tokens, and bfloat16 moves these
logits by less than the check's bound.  The exit code is the first's.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--tiny", action="store_true",
                    help="the configuration's tiny sizes (a CPU rehearsal)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as bench_run

    config = bench_run.with_tiny(bench_run.load_json(
        ROOT, "benchmark", "configs", "gpt2-medium-serve.json"), args.tiny)
    model = bench_run.load_module("models", config["model"])
    ref = bench_run.load_module("reference", config["reference"])
    device = jax.devices()[0]
    params = model.make_params(config, args.seed, device)
    n_out, count = 16, 4
    longest = min(32, config["n_positions"] - n_out - 1)
    prompts = []
    for i in range(count):
        rng = np.random.default_rng([args.seed, 1 << 21, i])
        n = max(longest * (i + 1) // count, 1)
        prompts.append([int(t) for t in
                        rng.integers(0, config["vocab_size"], n)])
    pad_to = longest + n_out
    verdicts = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        cases = [(p, ref.greedy(config, params, p, n_out, pad_to, dtype))
                 for p in prompts]
        verdicts[name] = ref.check(config, params, cases, pad_to)
        print("%s on %s: %s" % (name, device.device_kind,
                                json.dumps(verdicts[name])), flush=True)
    return 0 if verdicts["float32"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
