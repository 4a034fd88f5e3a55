"""Scheduler: median ``gap_us`` of the window's ``serving.decode_step``
spans: host time from one step's tokens coming back from the device to the
next step's dispatch, in which the device has nothing queued.  With
``decode_step_ms.serve`` it makes the step period."""

import statistics


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    gaps = [s["attrs"]["gap_us"] for s in spans if "gap_us" in s["attrs"]]
    return statistics.median(gaps) / 1e3 if gaps else None
