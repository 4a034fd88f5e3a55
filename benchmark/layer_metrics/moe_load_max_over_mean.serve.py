"""Model + cache: how unevenly a step's tokens fall on the experts: the
fullest expert's tokens over the mean expert's, median over the window's
steps (``moe_load_max * num_experts / moe_assignments`` of
``serving.decode_step`` spans; 1 is even).  Reads nothing where the spans
carry no such attributes."""

import statistics


def read(obs):
    spans = obs.get("decode_spans") or []
    if obs.get("kind") != "serve":
        return None
    experts = obs.get("config", {}).get("num_experts")
    got = [s["attrs"]["moe_load_max"] * experts
           / s["attrs"]["moe_assignments"]
           for s in spans if s.get("attrs", {}).get("moe_assignments")]
    return statistics.median(got) if got and experts else None
