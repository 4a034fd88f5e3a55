"""Model + cache: device busy time per engine step in the profiled seconds
(steps counted as the executables the profile saw start)."""


def read(obs):
    prof = obs.get("profile")
    if obs.get("kind") != "serve" or not prof or not obs.get("traced_steps"):
        return None
    return 1e3 * prof["busy_s"] / obs["traced_steps"]
