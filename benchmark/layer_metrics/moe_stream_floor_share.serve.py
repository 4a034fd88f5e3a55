"""Model + cache: the time the step's expert weights need at the chip's
memory bandwidth (``moe_cost.expert_stream_bytes_per_step`` of the experts
hit, the median ``moe_experts_hit`` of the window's ``serving.decode_step``
spans, over ``peaks.hbm_bytes_per_s``) as a share of the device's busy time
a step (profiled seconds): how close the step is to what its expert layers
alone must cost.  Over 100% would mean the bytes are counted too high.
Reads nothing without the spans' routing attributes or without a device
profile."""

import statistics


def read(obs):
    from benchmark import moe_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps"):
        return None
    hit = [s["attrs"]["moe_experts_hit"] for s in obs.get("decode_spans", [])
           if "moe_experts_hit" in s.get("attrs", {})]
    if not hit:
        return None
    floor_s = moe_cost.expert_stream_bytes_per_step(
        obs["config"], statistics.median(hit)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (prof["busy_s"] / obs["traced_steps"])
