"""Scheduler: what one prompt token costs a caller before anything appears:
the sum of send -> first token over the requests sent in the window, over
the sum of their prompt lengths.  In a closed loop a caller's lane is free
when it sends, so this is the prefill cost per token (one engine step
today) and not queueing.  A request that never answered counts as the
deadline."""


def read(obs):
    ttft, lens = obs.get("ttft_s"), obs.get("prompt_lens")
    if obs.get("kind") != "serve" or not ttft or not sum(lens):
        return None
    return 1e3 * sum(ttft) / sum(lens)
