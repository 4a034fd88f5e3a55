"""Executor: median host time of one ``exe.run`` with its feed and loss
fetch, over the steps of the (unprofiled) window."""

import statistics


def read(obs):
    if obs.get("kind") != "train" or not obs.get("step_s"):
        return None
    return 1e3 * statistics.median(obs["step_s"])
