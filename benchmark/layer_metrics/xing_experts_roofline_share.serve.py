"""Model + cache: the routed-expert kernel's share of its roofline at hidden
3584, width 1024 and 32 lanes, 8 experts held of a router of 64.  The kernel
(``paddle_tpu/pallas_kernels/moe_experts.py`` ``routed_experts``, executions
named ``moe_routed_experts*`` in the device trace) is bound by memory: the
least time it could take is the held experts that were hit, read once in
each of the 38 routed layers (``xing_cost.experts_hit_bytes_per_step`` of the
median ``moe_experts_hit`` of the window's ``serving.decode_step`` spans: the
traffic is a closed loop of 32 callers, so the hits are stationary), at
``peaks.hbm_bytes_per_s``; the share is that over the profile's
``op_seconds`` under the kernel's name, a step.  The kernel also reads the
lanes' rows and gates and writes their sum, which the numerator leaves out,
so the share cannot pass 100 unless the bytes are counted too high.  Reads
nothing where no kernel of that name ran (the einsum path, a CPU rehearsal),
for another configuration, without the spans' attribute or without a device
profile."""

import statistics

KERNEL = "moe_routed_experts"


def read(obs):
    from benchmark import xing_cost

    if not xing_cost.profiled(obs):
        return None
    kernel_s = xing_cost.kernel_seconds(obs, KERNEL)
    hit = [s["attrs"]["moe_experts_hit"]
           for s in obs.get("decode_spans") or []
           if "moe_experts_hit" in s.get("attrs", {})]
    if not kernel_s or not hit:
        return None
    floor_s = xing_cost.experts_hit_bytes_per_step(
        obs["config"], statistics.median(hit)) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
