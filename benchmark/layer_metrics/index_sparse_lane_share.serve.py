"""Model + cache: the share of live lane-steps whose context is past
``index_topk``: 100 x the sum of ``sparse_lanes`` over the sum of ``lanes``
of the window's ``serving.decode_step`` spans.  A latent layer that selects
differs from a dense one only on such a lane (under the threshold every
position is chosen), so this says whether the traffic reached the mechanism
the cell is there for: 0 is dots.vlm1's cell with an indexer that decides
nothing.  A program whose spans carry no such attribute (a model that does
not select, the parent of the PR that added it) records nothing here, and
this reads nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [(a["sparse_lanes"], a["lanes"])
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if "sparse_lanes" in a and a.get("lanes")]
    if not got:
        return None
    return 100.0 * sum(s for s, _n in got) / sum(n for _s, n in got)
