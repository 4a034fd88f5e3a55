"""Model + cache: the routed-expert kernel's share of its roofline at hidden
6144.  The kernel (``paddle_tpu/pallas_kernels/moe_experts.py``
``routed_experts``, executions named ``moe_routed_experts*`` in the device
trace) is bound by memory at 32 lanes: the least time it could take is the
held experts that were hit, read once in every routed layer
(``glm_cost.experts_hit_bytes_per_step`` of the median ``moe_experts_hit`` of
the window's ``serving.decode_step`` spans), at ``peaks.hbm_bytes_per_s``;
the share is that over the profile's ``op_seconds`` under the kernel's name,
a step.  The kernel also reads the lanes' rows and gates and writes their
sum, which the numerator leaves out, so the share cannot pass 100 unless the
bytes are counted too high.  Reads nothing where no kernel of that name ran
(another model, the einsum path, the parent of the PR that added it, a CPU
rehearsal), without a device profile, or for a configuration without the
keys ``glm_cost`` reads."""

import statistics

KERNEL = "moe_routed_experts"


def read(obs):
    from benchmark import glm_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or any(key not in config for key in glm_cost.KEYS):
        return None
    kernel_s = sum(s for name, s in prof.get("op_seconds", {}).items()
                   if name.lstrip("%_").startswith(KERNEL))
    hit = [s["attrs"]["moe_experts_hit"]
           for s in obs.get("decode_spans") or []
           if "moe_experts_hit" in s.get("attrs", {})]
    if not kernel_s or not hit:
        return None
    floor_s = glm_cost.experts_hit_bytes_per_step(
        config, statistics.median(hit)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
