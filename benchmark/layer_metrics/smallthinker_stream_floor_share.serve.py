"""Model + cache: the time a SmallThinker decode step's bytes need at the
chip's memory bandwidth, as a share of the device's busy time a step
(profiled seconds).  The bytes are ``smallthinker_cost``'s: every weight the
step reads once (the experts *hit*, the median ``moe_experts_hit`` of the
window's ``serving.decode_step`` spans, not the experts held) and the K and
V blocks its attention fetched, by the layer's kind (the spans'
``kv_blocks_read`` a global layer, ``kv_window_blocks_read`` over the window
layers, blocks of ``kv_block_size`` tokens), over ``peaks.hbm_bytes_per_s``.
Only what must move is counted, so the share cannot pass 100 unless the
bytes are counted too high; what is left under 100 is time the step spends
on something else than streaming.  Reads nothing without the spans' window
attributes (another model, a program without them), without a device
profile, or for a configuration without the keys ``smallthinker_cost``
reads."""

import statistics


def read(obs):
    from benchmark import smallthinker_cost

    prof, peaks = obs.get("profile"), obs.get("peaks")
    config = obs.get("config") or {}
    if obs.get("kind") != "serve" or not prof or not peaks \
            or not obs.get("traced_steps") \
            or any(key not in config for key in smallthinker_cost.KEYS):
        return None
    attrs = [s.get("attrs", {}) for s in obs.get("decode_spans") or []]
    attrs = [a for a in attrs if "kv_window_blocks_read" in a
             and "kv_blocks_read" in a and "moe_experts_hit" in a
             and a.get("kv_block_size")]
    if not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_bytes = smallthinker_cost.weight_floor_bytes_per_step(
        config, median("moe_experts_hit"), median("lanes")) \
        + smallthinker_cost.kv_floor_bytes_per_step(
            config, median("kv_blocks_read"),
            median("kv_window_blocks_read"), median("kv_block_size"))
    return 100.0 * floor_bytes / peaks["hbm_bytes_per_s"] \
        / (prof["busy_s"] / obs["traced_steps"])
