"""Model + cache: the latent-attention kernel's share of its roofline at 32
query heads x 32 lanes over one cached row, in each of 40 layers.  The kernel
(the latent form of ``paddle_tpu/pallas_kernels/paged_attention.py``,
executions named ``latent_attention*`` in the device trace) does 60
operations a byte here (a quarter of dots.vlm1's 242: a quarter of its
heads), so the least time it could take is the LARGER of the rows it fetched
at ``peaks.hbm_bytes_per_s`` (``xing_cost.latent_floor_bytes_per_step``: a
row's 576 values and not the 640 its pool holds it in) and the absorbed
form's operations over them at ``peaks.bf16_flops_per_s``
(``xing_cost.latent_flops_per_step``); the share is that over the profile's
``op_seconds`` under the kernel's name, a step.  The kernel also reads every
lane's query and writes its output, which the numerator leaves out, so the
share cannot pass 100 unless the bytes or the operations are counted too
high.  The blocks are the median ``latent_blocks_read`` of the window's last
two seconds of ``serving.decode_step`` spans (``xing_cost.late_attrs``: the
runner records no span while it profiles, PERF.md section 7; the traffic is
a closed loop of 32 callers whose requests end and begin all through the
run, so the contexts are stationary).  Reads nothing where no kernel of that
name ran (the gather path, a CPU rehearsal), without the spans' attribute,
without a device profile, or for another configuration."""

import statistics

KERNEL = "latent_attention"


def read(obs):
    from benchmark import xing_cost

    if not xing_cost.profiled(obs):
        return None
    config, peaks = obs["config"], obs["peaks"]
    kernel_s = xing_cost.kernel_seconds(obs, KERNEL)
    attrs = [a for a in xing_cost.late_attrs(
        obs, ("latent_blocks_read", "kv_block_size")) if a["kv_block_size"]]
    if not kernel_s or not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    blocks, size = median("latent_blocks_read"), median("kv_block_size")
    floor_s = max(
        xing_cost.latent_floor_bytes_per_step(config, blocks, size)
        / peaks["hbm_bytes_per_s"],
        xing_cost.latent_flops_per_step(config, blocks, size)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
