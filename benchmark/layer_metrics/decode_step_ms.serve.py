"""Scheduler: median duration of the program's ``serving.decode_step`` span
over the window (host time of one engine iteration's device step)."""

import statistics


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    return statistics.median(s["dur"] for s in spans) / 1e3
