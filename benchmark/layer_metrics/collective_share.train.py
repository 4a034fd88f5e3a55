"""Parallelism: device time of the collective operations (all-reduce and
kin, self time, averaged over chips) over the profiled window."""


def read(obs):
    prof = obs.get("profile")
    if obs.get("kind") != "train" or not prof or obs["chips"] < 2 \
            or not prof["window_s"]:
        return None
    return 100.0 * prof["collective_s"] / prof["window_s"]
