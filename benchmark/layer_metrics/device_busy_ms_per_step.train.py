"""Lowering + kernels: device busy time (union of the chip's operation
intervals, averaged over chips) per step of the profiled seconds."""


def read(obs):
    prof = obs.get("profile")
    if obs.get("kind") != "train" or not prof or not obs.get("traced_steps"):
        return None
    return 1e3 * prof["busy_s"] / obs["traced_steps"]
