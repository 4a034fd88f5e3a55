"""Model + cache: how far from its floor the mixing of a Xing4.0 step's
residual streams runs.  The least time the mechanism could take is what it
must move at ``peaks.hbm_bytes_per_s``: every sublayer's ``phi``, ``b`` and
scalars once (``xing_cost.hc_param_bytes``, float32) and the live lanes'
streams three times a mixing (the span's ``hc_stream_bytes``, the median of
the window's last two seconds of ``serving.decode_step`` spans, which the
program counts by ``hyper_connections.stream_bytes`` and
``xing_cost.hc_stream_bytes_per_step`` another way); the share is that over
the device's time under the ``hc`` scopes a step
(``xing_cost.hc_seconds_per_step``: their share of the operations' time
times the busy time a step).  The mixing also reads each sublayer's
output and writes its input, which the numerator leaves out, so the share
cannot pass 100 unless the bytes are counted too high.  Reads nothing where
``xing_hc_step_share.serve`` reads nothing, or without the span's
attribute."""

import statistics


def read(obs):
    from benchmark import xing_cost

    seconds = xing_cost.hc_seconds_per_step(obs)
    attrs = xing_cost.late_attrs(obs, ("hc_stream_bytes",)) if seconds \
        else []
    if not attrs:
        return None
    floor_bytes = xing_cost.hc_param_bytes(obs["config"]) \
        + statistics.median(a["hc_stream_bytes"] for a in attrs)
    return 100.0 * floor_bytes / obs["peaks"]["hbm_bytes_per_s"] / seconds
