"""Model + cache: the K/V walk's share of its roofline at 64 lanes of 64
query heads over 8 KV heads of 128.  The kernel
(``paddle_tpu/pallas_kernels/paged_attention.py`` ``_kernel``, executions
named ``paged_attention*`` in the device trace) is bound by memory: the least
time it could take is the K and V blocks it fetched, 65,536 B a block a layer
(``solar_cost.kv_floor_bytes_per_step`` of the median ``kv_blocks_read`` in
each of the softmax layers), at ``peaks.hbm_bytes_per_s``; the share is that
over the profile's ``op_seconds`` under the kernel's name, a step.  The
kernel also reads every lane's query and writes its output, which the
numerator leaves out, so the share cannot pass 100 unless the bytes are
counted too high.

Numerator and kernel time from the same steps, as nearly as the harness lets
them: the runner profiles the seconds after the window and records no span
meanwhile, and in this cell a request outlasts ramp and window, so contexts
grow all through the run; the blocks are the median of the window's LAST two
seconds of spans (``solar_cost.late_attrs``), not of the whole window's (the
long cells' known mismatch, PERF.md section 7).  What growth is left between
those and the profiled steps makes the share read low, never high.

Reads nothing where no kernel of that name ran (the gather path, a CPU
rehearsal), for another model's keys, without the spans' attributes or
without a device profile."""

import statistics

KERNEL = "paged_attention"


def read(obs):
    from benchmark import solar_cost

    if not solar_cost.profiled(obs):
        return None
    config, peaks = obs["config"], obs["peaks"]
    kernel_s = solar_cost.kernel_seconds(obs, KERNEL)
    attrs = [a for a in solar_cost.late_attrs(
        obs, ("kv_blocks_read", "kv_block_size")) if a["kv_block_size"]]
    if not kernel_s or not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_s = solar_cost.kv_floor_bytes_per_step(
        config, median("kv_blocks_read"), median("kv_block_size")) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
