"""Model + cache: the index-scores kernel's share of its roofline.  The
kernel (``paddle_tpu/pallas_kernels/paged_attention.py`` ``index_scores``,
executions named ``index_scores*`` in the device trace) reads every index key
of every live block of a lane in every layer and is bound by memory (32 heads
of 128 against rows of 256 B: 33 operations a byte, a seventh of the chip's
ridge): the least time it could take is those keys
(``glm_cost.index_floor_bytes_per_step`` of the median ``index_blocks_read``
of the window's ``serving.decode_step`` spans) at ``peaks.hbm_bytes_per_s``,
or its operations at ``peaks.bf16_flops_per_s`` where that is longer; the
share is that over the profile's ``op_seconds`` under the kernel's name, a
step.  The kernel also reads the lanes' queries and writes their scores,
which the numerator leaves out, so the share cannot pass 100 unless the
bytes are counted too high.

The profile is reduced by operation name, not by ``named_scope``
(``benchmark/trace_reduce.py``), so the time is the kernel's own: the
indexer's projections, the rotation and the index key's write, which share
its scope (``layerN/latent/index``), are XLA fusions under names of their
own and are not in it (``tools/decode_step_probe.py`` gives the scope whole).

The caveat of PERF.md section 7: the numerator is the window's median, the
denominator the kernel's time in the steps profiled just after it, whose
contexts are a few hundred positions longer; a reading is that much low.

Reads nothing where no kernel of that name ran (the gather path, another
model, the parent of the PR that added this, a CPU rehearsal), without the
spans' attribute, without a device profile, or for a configuration without
the keys ``glm_cost`` reads."""

import statistics

KERNEL = "index_scores"


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
    attrs = [a for a in (s.get("attrs", {})
                         for s in obs.get("decode_spans") or [])
             if "index_blocks_read" in a and a.get("kv_block_size")]
    if not kernel_s or not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    blocks, size = median("index_blocks_read"), median("kv_block_size")
    floor_s = max(
        glm_cost.index_floor_bytes_per_step(config, blocks, size)
        / peaks["hbm_bytes_per_s"],
        glm_cost.index_flops_per_step(config, blocks, size)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
