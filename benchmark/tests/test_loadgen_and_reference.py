"""The traffic generator's schedule, and the serving check's verdicts on a
right and a wrong model, at tiny size on the CPU."""

import collections
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import loadgen  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

MIX = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "serve_decode_heavy.json")))


def test_lengths_cover_the_mixs_ranges():
    pairs = loadgen.size_set(MIX, 1024)
    prompts, outputs = zip(*pairs)
    assert len(pairs) == MIX["size_set"]
    assert MIX["prompt_len"]["min"] <= min(prompts) <= 17
    assert 120 <= max(prompts) <= MIX["prompt_len"]["max"]
    assert MIX["output_len"]["min"] <= min(outputs) <= 264
    assert 760 <= max(outputs) <= MIX["output_len"]["max"]
    # log-uniform: the median prompt is the geometric middle, not the mean
    assert 40 <= sorted(prompts)[len(prompts) // 2] <= 50
    assert len(set(outputs)) == len(outputs)


def test_every_seed_sends_the_same_set_in_another_order():
    a = loadgen.Schedule(MIX, 1024, 50257, 2 ** 31 + 11)
    b = loadgen.Schedule(MIX, 1024, 50257, 7)
    assert a.pairs == b.pairs
    n = a.clients
    rounds = len(a.pairs) // n
    walk = lambda s: [s.sizes(i, k) for k in range(1, rounds + 1)
                      for i in range(n)]
    assert collections.Counter(walk(a)) == collections.Counter(a.pairs)
    assert collections.Counter(walk(b)) == collections.Counter(a.pairs)
    assert walk(a) != walk(b)
    # a caller's first request is one already under way: same prompt, a
    # share of the output, the shares spread evenly over the callers
    firsts = [a.sizes(i, 0) for i in range(n)]
    fulls = [a.pairs[a.order[i]] for i in range(n)]
    assert all(f[0] == g[0] and 1 <= f[1] <= g[1]
               for f, g in zip(firsts, fulls))
    assert sorted(a.first_share) == [(i + 0.5) / n for i in range(n)]
    assert a.prompt(3, 1, 20) == a.prompt(3, 1, 20) != b.prompt(3, 1, 20)


def test_check_passes_the_model_and_fails_a_wrong_one():
    import jax
    import jax.numpy as jnp

    config = bench_run.with_tiny(bench_run.load_json(
        ROOT, "benchmark", "configs", "gpt2-medium-serve.json"), True)
    model = bench_run.load_module("models", config["model"])
    ref = bench_run.load_module("reference", config["reference"])
    # weights wide enough that the tiny model's logits are not all alike
    params = model.make_params(dict(config, initializer_range=0.5), 5,
                               jax.devices()[0])
    prompts = [[int(t) for t in np.random.default_rng(i).integers(
        0, config["vocab_size"], 6)] for i in range(3)]
    served = [(p, ref.greedy(config, params, p, 8, 16, jnp.float32))
              for p in prompts]
    verdict = ref.check(config, params, served, 16)
    assert verdict["ok"] and verdict["compared"] == 24
    # a server that lost its last layer's MLP
    wrong = dict(params)
    wrong["l%d_w2" % (config["n_layer"] - 1)] = jnp.zeros_like(
        params["l%d_w2" % (config["n_layer"] - 1)])
    bad = [(p, ref.greedy(config, wrong, p, 8, 16, jnp.float32))
           for p in prompts]
    verdict = ref.check(config, params, bad, 16)
    assert not verdict["ok"] and verdict["differing"] > 0
