"""Model + cache: the KDA state-update kernel's share of its roofline at 64
heads and 64 lanes.  The kernel (``paddle_tpu/pallas_kernels/kda_update.py``,
executions named ``kda_state_update*`` in the device trace) is bound by
memory: the least time it could take is the state of the lanes that held a
sequence, 4,194,304 B a layer, read and written once in every KDA layer
(``solar_cost.state_traffic_bytes_per_step`` of the median
``kda_state_lanes`` of the window's last ``serving.decode_step`` spans,
``solar_cost.late_attrs``), at ``peaks.hbm_bytes_per_s``; the share is that
over the profile's ``op_seconds`` under the kernel's name, a step.  The
kernel moves idle lanes' scratch slot too and reads each lane's decay, keys,
values and queries, which the numerator leaves out, so the share cannot pass
100 unless the bytes are counted too high.  A step that fell to the gather
runs no kernel of that name: this then reads 0, not a share of something
else.  Reads nothing for another model's keys, for a program without the
spans' attribute (the parent of the PR that added this), on a CPU rehearsal
or without a device profile."""

import statistics

KERNEL = "kda_state_update"


def read(obs):
    from benchmark import solar_cost

    if not solar_cost.profiled(obs):
        return None
    config, peaks = obs["config"], obs["peaks"]
    attrs = solar_cost.late_attrs(obs, ("kda_state_lanes",))
    if not attrs:
        return None
    kernel_s = solar_cost.kernel_seconds(obs, KERNEL)
    if not kernel_s:
        return 0.0
    floor_s = solar_cost.state_traffic_bytes_per_step(
        config, statistics.median(a["kda_state_lanes"] for a in attrs)) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / obs["traced_steps"])
