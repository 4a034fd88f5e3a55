"""Model + cache: the share of the block table's slots that the step's
attention fetches, a layer: 100 x ``kv_blocks_read`` / ``kv_table_slots`` of
the window's ``serving.decode_step`` spans, the median over its steps.  It
reads 100 where the step gathers the whole padded table and the live
context's share (rounded up to the kernel's chunk) where the kernel reads
blocks in place: engagement, and what the traffic leaves to gain, in one
number.  A program whose spans carry no such attributes records nothing
here, and this reads nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [100.0 * a["kv_blocks_read"] / a["kv_table_slots"]
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if a.get("kv_table_slots") and "kv_blocks_read" in a]
    return statistics.median(got) if got else None
