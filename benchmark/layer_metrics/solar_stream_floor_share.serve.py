"""Model + cache: the time a Solar-Open2 decode step's bytes need at the
chip's memory bandwidth, as a share of the device's busy time a step
(profiled seconds).  The bytes are ``solar_cost``'s: every weight the step
reads once (six KDA mixers, two gated softmax mixers, eight routers and
shared experts, the held experts *hit*, the median ``moe_experts_hit``, not
the experts held, the head), the live lanes' KDA state in and out
(``kda_state_lanes``) and the K and V its attention fetched
(``kv_blocks_read`` a softmax layer, blocks of ``kv_block_size`` tokens),
over ``peaks.hbm_bytes_per_s``.  The attributes are the medians of the
window's last two seconds of ``serving.decode_step`` spans
(``solar_cost.late_attrs``: the steps nearest the profiled ones; contexts
grow all through this cell's run).  Only what must move is counted, so the
share cannot pass 100 unless the bytes are counted too high; what is left
under 100 is time the step spends on something else than streaming.  Reads
nothing without those attributes (another model, the parent of the PR that
added them), without a device profile, or for a configuration without the
keys ``solar_cost`` reads."""

import statistics

NEEDS = ("moe_experts_hit", "kda_state_lanes", "kv_blocks_read",
         "kv_block_size", "lanes")


def read(obs):
    from benchmark import solar_cost

    if not solar_cost.profiled(obs):
        return None
    config, peaks = obs["config"], obs["peaks"]
    attrs = [a for a in solar_cost.late_attrs(obs, NEEDS)
             if a["kv_block_size"]]
    if not attrs:
        return None
    median = lambda key: statistics.median(a[key] for a in attrs)
    floor_bytes = solar_cost.stream_floor_bytes_per_step(
        config, median("moe_experts_hit"), median("lanes"),
        median("kda_state_lanes"), median("kv_blocks_read"),
        median("kv_block_size"))
    return 100.0 * floor_bytes / peaks["hbm_bytes_per_s"] \
        / (obs["profile"]["busy_s"] / obs["traced_steps"])
