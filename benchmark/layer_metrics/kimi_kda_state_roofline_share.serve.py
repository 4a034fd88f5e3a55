"""Model + cache: the KDA state-update kernel's share of its roofline.  The
kernel (``paddle_tpu/pallas_kernels/kda_update.py``, executions named
``kda_state_update*`` in the device trace) is bound by memory: the least
time it could take is the state of the lanes that held a sequence, read and
written once in every KDA layer (``kimi_cost.state_traffic_bytes_per_step``
of the median ``kda_state_lanes`` of the window's ``serving.decode_step``
spans), at ``peaks.hbm_bytes_per_s``; the share is that over the profile's
``op_seconds`` under the kernel's name, a step.  The kernel moves idle
lanes' scratch slot too and reads each lane's decay, keys, values and
queries, which the numerator leaves out, so the share cannot pass 100 unless
the bytes are counted too high.  Reads nothing where no such kernel ran
(another model's keys, the parent of the PR that added this, a CPU
rehearsal) or without a device profile."""

import statistics

KERNEL = "kda_state_update"


def read(obs):
    from benchmark import kimi_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or "linear_attn_config" not in config \
            or "kv_lora_rank" not in config:
        return None
    kernel_s = sum(s for name, s in prof.get("op_seconds", {}).items()
                   if name.lstrip("%").startswith(KERNEL))
    lanes = [s["attrs"]["kda_state_lanes"]
             for s in obs.get("decode_spans") or []
             if "kda_state_lanes" in s.get("attrs", {})]
    if not kernel_s or not lanes:
        return None
    floor_s = kimi_cost.state_traffic_bytes_per_step(
        config, statistics.median(lanes)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
