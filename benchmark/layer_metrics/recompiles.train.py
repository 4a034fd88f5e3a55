"""Executor: ``executor_cache_miss_total`` over the window (telemetry is on
in the traced run).  Anything but 0 is an executable built under load."""


def read(obs):
    if obs.get("kind") != "train" or obs.get("recompiles") is None:
        return None
    return obs["recompiles"]
