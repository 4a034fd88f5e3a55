"""Scheduler: p95 less p50 of the ``period_us`` attribute of the window's
``serving.decode_step`` spans: the decode loop's whole period on the
monotonic clock, from one span's open to the next one's (lock, admission and
plan with it, which the span's ``dur`` leaves out).  It is the part of the
client's ``itl_p95_ms`` over its median that the loop itself makes.  A
program whose spans carry no such attribute (the parent of the PR that added
it) gives nothing to read."""

import numpy as np


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [s["attrs"]["period_us"] for s in obs.get("decode_spans") or []
           if "period_us" in s.get("attrs", {})]
    if not got:
        return None
    p50, p95 = np.percentile(got, [50, 95])
    return (p95 - p50) / 1e3
