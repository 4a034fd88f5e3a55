"""Model + cache: what the window layers' attention fetches as a share of
what it would fetch of the lanes' whole contexts: 100 x
``kv_window_blocks_read`` / ``kv_window_blocks_full`` of the window's
``serving.decode_step`` spans (both over all such layers), the median over
its steps.  A layer that attends its last 128 positions through a ring of 9
blocks reads 9 a live lane whatever the context; the same layer on the
global layers' table would read the context's blocks, rounded up to the
kernel's chunk.  100 would say the window layers read their history.  A
program whose spans carry no such attributes (a model with no window layer,
the parent of the PR that added them) records nothing here, and this reads
nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [100.0 * a["kv_window_blocks_read"] / a["kv_window_blocks_full"]
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if a.get("kv_window_blocks_full")
           and "kv_window_blocks_read" in a]
    return statistics.median(got) if got else None
