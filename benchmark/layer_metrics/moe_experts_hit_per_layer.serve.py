"""Model + cache: experts that at least one token was routed to, a layer,
median over the window's steps (the ``moe_experts_hit`` attribute of
``serving.decode_step`` spans: its mean over the step's layers).  A program
whose step routes nothing records no such attribute, and this reads
nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [s["attrs"]["moe_experts_hit"]
           for s in obs.get("decode_spans") or []
           if "moe_experts_hit" in s.get("attrs", {})]
    return statistics.median(got) if got else None
