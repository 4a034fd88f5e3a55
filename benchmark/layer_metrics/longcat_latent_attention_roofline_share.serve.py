"""Model + cache: the latent-attention kernel's share of its roofline at 64
query heads and 64 lanes over one cached row a sublayer.  The kernel (the
latent form of ``paddle_tpu/pallas_kernels/paged_attention.py``, executions
named ``latent_attention*`` in the device trace) reads a lane's rows once
for its 64 heads, 121 operations a byte, half the chip's ridge: the least
time it could take is the LARGER of the rows it fetched at
``peaks.hbm_bytes_per_s`` (``longcat_cost.latent_floor_bytes_per_step``: a
row's 576 values and not the 640 its pool holds it in, in each of the eight
sublayers) and the absorbed form's operations over them at
``peaks.bf16_flops_per_s`` (``longcat_cost.latent_flops_per_step``), as
dots.vlm1's share has it; the share is that over the profile's ``op_seconds``
under the kernel's name, a step.  The kernel also reads every lane's query
and writes its output, which the numerator leaves out, so the share cannot
pass 100 unless the bytes or the operations are counted too high.

The caveat of PERF.md section 7: the runner does not record spans while it
profiles, so the numerator is the median ``latent_blocks_read`` of the
WINDOW's ``serving.decode_step`` spans and the denominator the kernel's time
in the steps profiled just after it.  The traffic is stationary (a closed
loop of 64 callers whose requests end and begin all through the run), so the
two see the same contexts to a few percent; a reading is that much
uncertain, in either direction.

Reads nothing where no kernel of that name ran (the gather path, the parent
of the PR that added this, a CPU rehearsal), without the spans' attribute,
without a device profile, or for a configuration without the keys
``longcat_cost`` reads."""

import statistics

KERNEL = "latent_attention"
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
    attrs = [a for a in (s.get("attrs", {})
                         for s in obs.get("decode_spans") or [])
             if "latent_blocks_read" in a and a.get("kv_block_size")]
    if not kernel_s or not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    blocks, size = median("latent_blocks_read"), median("kv_block_size")
    floor_s = max(
        longcat_cost.latent_floor_bytes_per_step(config, blocks, size)
        / peaks["hbm_bytes_per_s"],
        longcat_cost.latent_flops_per_step(config, blocks, size)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
