"""Model + cache: the time the expert weights a step's tokens were routed to
need at the chip's memory bandwidth
(``lfm2_cost.routed_stream_floor_bytes_per_step`` of the experts hit, the
median ``moe_experts_hit`` of the window's ``serving.decode_step`` spans: a
mean over the layers that route, over ``peaks.hbm_bytes_per_s``) as a share
of the device's busy time a step (profiled seconds).  A step that reads
every expert, hit or not, is bounded by hit / experts of its stream's share
of the peak; over 100% would mean the bytes are counted too high.  Reads
nothing without the spans' routing attributes, without a device profile, or
for a configuration without the keys ``lfm2_cost`` reads."""

import statistics


def read(obs):
    from benchmark import lfm2_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or "moe_intermediate_size" not in config \
            or "num_dense_layers" not in config:
        return None
    hit = [s["attrs"]["moe_experts_hit"] for s in obs.get("decode_spans", [])
           if "moe_experts_hit" in s.get("attrs", {})]
    if not hit:
        return None
    floor_s = lfm2_cost.routed_stream_floor_bytes_per_step(
        config, statistics.median(hit)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (prof["busy_s"] / obs["traced_steps"])
