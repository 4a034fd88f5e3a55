"""Model + cache: the time the step's state-space mixers need at the chip's
memory bandwidth (``ssm_cost.ssm_stream_bytes_per_step``: their weights
once, and the state of the lanes that held a sequence, the median
``ssm_state_lanes`` of the window's ``serving.decode_step`` spans, read and
written once, over ``peaks.hbm_bytes_per_s``) as a share of the device's
busy time a step (profiled seconds): how close the step is to what its
recurrent layers alone must cost.  Over 100% would mean the bytes are
counted too high.  Reads nothing without the spans' state attributes (a
program or a model with no recurrent layers) or without a device
profile."""

import statistics


def read(obs):
    from benchmark import ssm_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps"):
        return None
    lanes = [s["attrs"]["ssm_state_lanes"]
             for s in obs.get("decode_spans", [])
             if "ssm_state_lanes" in s.get("attrs", {})]
    if not lanes:
        return None
    floor_s = ssm_cost.ssm_stream_bytes_per_step(
        obs["config"], statistics.median(lanes)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (prof["busy_s"] / obs["traced_steps"])
