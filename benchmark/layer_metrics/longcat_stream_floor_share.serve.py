"""Model + cache: the time a LongCat-Flash decode step's bytes need at the
chip's memory bandwidth, as a share of the device's busy time a step
(profiled seconds).  The bytes are ``longcat_cost``'s: every weight the step
reads once (eight mixers and eight dense MLPs, four routers, the held experts
*hit*, the median ``moe_experts_hit`` of the window's ``serving.decode_step``
spans, not the experts held; an identity expert has no weight) and the latent
rows its attention fetched (``latent_blocks_read`` a sublayer, blocks of
``kv_block_size`` tokens), over ``peaks.hbm_bytes_per_s``.  Only what must
move is counted, so the share cannot pass 100 unless the bytes are counted
too high; what is left under 100 is time the step spends on something else
than streaming.  Reads nothing without those attributes (another model, the
parent of the PR that added them), without a device profile, or for a
configuration without the keys ``longcat_cost`` reads."""

import statistics

NEEDS = ("moe_experts_hit", "latent_blocks_read", "kv_block_size", "lanes")
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
    attrs = [a for a in (s.get("attrs", {})
                         for s in obs.get("decode_spans") or [])
             if all(key in a for key in NEEDS) and a["kv_block_size"]]
    if not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_bytes = longcat_cost.stream_floor_bytes_per_step(
        config, median("moe_experts_hit"), median("lanes"),
        median("latent_blocks_read"), median("kv_block_size"))
    return 100.0 * floor_bytes / peaks["hbm_bytes_per_s"] \
        / (prof["busy_s"] / obs["traced_steps"])
