"""Model + cache: the latent-attention kernel's share of its roofline.  The
kernel (the latent form of ``paddle_tpu/pallas_kernels/paged_attention.py``,
executions named ``latent_attention*`` in the device trace) is bound by
memory: the least time it could take is the rows it fetched
(``kimi_cost.latent_floor_bytes_per_step`` of the medians of the window's
``serving.decode_step`` spans: ``latent_blocks_read`` blocks of
``kv_block_size`` tokens in each latent layer, a row's 576 values and not
the 640 its pool holds it in) at ``peaks.hbm_bytes_per_s``; the share is
that over the profile's ``op_seconds`` under the kernel's name, a step.  The
kernel also reads every lane's query and writes its output, which the
numerator leaves out, so the share cannot pass 100 unless the bytes are
counted too high.  Reads nothing where no kernel of that name ran (the
gather path, the parent of the PR that added this, a CPU rehearsal), without
the spans' attribute, or without a device profile."""

import statistics

KERNEL = "latent_attention"


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
    attrs = [a for a in (s.get("attrs", {})
                         for s in obs.get("decode_spans") or [])
             if "latent_blocks_read" in a and a.get("kv_block_size")]
    if not kernel_s or not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_s = kimi_cost.latent_floor_bytes_per_step(
        config, median("latent_blocks_read"), median("kv_block_size")) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
