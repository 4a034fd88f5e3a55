"""Step function: ``executor_cache_miss_total`` from the window's start
(after prewarm and ramp) to its end.  Anything but 0 is a compile stall
under traffic."""


def read(obs):
    if obs.get("kind") != "serve" or obs.get("recompiles") is None:
        return None
    return obs["recompiles"]
