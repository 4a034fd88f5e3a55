"""Model + cache: the time a Xing4.0 decode step's bytes need at the chip's
memory bandwidth, as a share of the device's busy time a step (profiled
seconds): the share of the whole step.  The bytes are ``xing_cost``'s: every
weight the step reads once (40 mixers, 80 mixings' float32 parameters, the
dense lead, 38 routers and shared experts, the held experts *hit*, the median
``moe_experts_hit``, not the experts held, the head), the latent rows its
attention fetched (``latent_blocks_read`` a layer, blocks of
``kv_block_size`` tokens) and the live lanes' four streams three times a
mixing (``hc_stream_bytes``), over ``peaks.hbm_bytes_per_s``.  The attributes
are the medians of the window's last two seconds of ``serving.decode_step``
spans (``xing_cost.late_attrs``).  Only what must move is counted, so the
share cannot pass 100 unless the bytes are counted too high; what is left
under 100 is time the step spends on something else than streaming (its
mixings are bound by latency).  Reads nothing without those attributes
(another model, the parent of the PR that added them), without a device
profile, or for another configuration."""

import statistics

NEEDS = ("moe_experts_hit", "latent_blocks_read", "kv_block_size", "lanes",
         "hc_stream_bytes")


def read(obs):
    from benchmark import xing_cost

    if not xing_cost.profiled(obs):
        return None
    attrs = [a for a in xing_cost.late_attrs(obs, NEEDS)
             if a["kv_block_size"]]
    if not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_bytes = xing_cost.stream_floor_bytes_per_step(
        obs["config"], median("moe_experts_hit"), median("lanes"),
        median("latent_blocks_read"), median("kv_block_size"),
        median("hc_stream_bytes"))
    return 100.0 * floor_bytes / obs["peaks"]["hbm_bytes_per_s"] \
        / (obs["profile"]["busy_s"] / obs["traced_steps"])
