"""Model + cache: how far the held experts a step hits swing from step to
step: the 95th less the 5th percentile of ``moe_experts_hit`` (the mean over
the step's layers that route) over the window's ``serving.decode_step``
spans.  Where the router decides how many real experts a token computes (0
to ``moe_topk``: identity experts take the rest), the experts a step streams
vary with its tokens and not only with where they fall, and a step's time
with them, 0.6 ms an expert a layer at these widths: this is what variable
compute does to the expert stream.  Reads nothing where the spans carry no
``moe_zero_assignments`` (a router whose every output computes: their cells
read ``moe_experts_hit_per_layer.serve``, the median), or from fewer than
twenty steps."""

import statistics

MIN_STEPS = 20


def read(obs):
    if obs.get("kind") != "serve":
        return None
    hit = [s["attrs"]["moe_experts_hit"]
           for s in obs.get("decode_spans") or []
           if "moe_experts_hit" in s.get("attrs", {})
           and "moe_zero_assignments" in s["attrs"]]
    if len(hit) < MIN_STEPS:
        return None
    cuts = statistics.quantiles(hit, n=20, method="inclusive")
    return cuts[-1] - cuts[0]
