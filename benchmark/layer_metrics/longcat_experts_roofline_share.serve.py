"""Model + cache: the routed-expert kernel's share of its roofline at hidden
6144 and 64 lanes.  The kernel (``paddle_tpu/pallas_kernels/moe_experts.py``
``routed_experts``, executions named ``moe_routed_experts*`` in the device
trace) is bound by memory: the least time it could take is the held experts
that were hit, read once in every layer of the source (a pair of sublayers
has ONE routed part: ``longcat_cost.experts_hit_bytes_per_step`` of the
median ``moe_experts_hit`` of the window's ``serving.decode_step`` spans), at
``peaks.hbm_bytes_per_s``; the share is that over the profile's
``op_seconds`` under the kernel's name, a step.  The kernel also reads the
lanes' rows and gates and writes their sum, which the numerator leaves out,
so the share cannot pass 100 unless the bytes are counted too high.  Reads
nothing where no kernel of that name ran (another model, the einsum path, the
parent of the PR that added it, a CPU rehearsal), without a device profile,
or for a configuration without the keys ``longcat_cost`` reads."""

import statistics

KERNEL = "moe_routed_experts"
KEYS = ("num_layers", "ffn_hidden_size", "expert_ffn_hidden_size",
        "zero_expert_num", "num_experts_published")


def read(obs):
    from benchmark import longcat_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or any(key not in config for key in KEYS):
        return None
    kernel_s = sum(s for name, s in prof.get("op_seconds", {}).items()
                   if name.lstrip("%").startswith(KERNEL))
    hit = [s["attrs"]["moe_experts_hit"]
           for s in obs.get("decode_spans") or []
           if "moe_experts_hit" in s.get("attrs", {})]
    if not kernel_s or not hit:
        return None
    floor_s = longcat_cost.experts_hit_bytes_per_step(
        config, statistics.median(hit)) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
