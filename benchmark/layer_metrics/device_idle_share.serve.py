"""Device: share of the profiled window in which no operation ran."""


def read(obs):
    prof = obs.get("profile")
    if obs.get("kind") != "serve" or not prof or not prof["window_s"]:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / prof["window_s"])
