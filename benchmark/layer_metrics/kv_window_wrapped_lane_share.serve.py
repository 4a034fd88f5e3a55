"""Model + cache: the share of a step's lanes whose context is past the
window: 100 x ``kv_window_lanes_wrapped`` / ``lanes`` of the window's
``serving.decode_step`` spans, the median over its steps.  A window layer
differs from a global one only on such a lane (its ring has wrapped and
gives back a block for every block it takes; under the window a ring is a
context's leading blocks), so this says whether the traffic reached the
mechanism the cell is there for: 0 is a cell of global layers with another
table.  A program whose spans carry no such attribute (a model with no
window layer, the parent of the PR that added it) records nothing here, and
this reads nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [100.0 * a["kv_window_lanes_wrapped"] / a["lanes"]
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if "kv_window_lanes_wrapped" in a and a.get("lanes")]
    return statistics.median(got) if got else None
