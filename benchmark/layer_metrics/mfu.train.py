"""Lowering + kernels: model FLOP/s utilisation.  The benchmark's own count
of forward + backward matmul operations per token (``flops.py``, nothing
recomputed) times the window's tokens per second, over chips x the
published bf16 peak (``peaks.json``)."""


def read(obs):
    if obs.get("kind") != "train" or not obs.get("peaks"):
        return None
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * obs["flops_per_token"] * obs["tokens_per_s"] / peak
