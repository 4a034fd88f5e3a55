"""Model + cache: the share of the chunks the latent attention kernel walks
that are full, a layer: 100 x ``latent_full_chunks`` / ``latent_chunks`` of
the window's ``serving.decode_step`` spans, the median over its steps.  A
chunk is full when its lane sees every position of it.  The kernel pays a
whole chunk's arithmetic for every chunk (unseen positions are masked), and
where it runs its straight-line body (128 heads) a whole chunk's copies too
(a lane's last fetches its last block again for the slots past it), so this
says how much of what it pays for is of use: 0 where every
context is under one chunk (and on the gather path, where a lane's whole
padded table is its one chunk), 75 where a lane walks four.  A program whose
spans carry no such attributes (a model with no latent layer, the parent)
records nothing here, and this reads nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [100.0 * a["latent_full_chunks"] / a["latent_chunks"]
           for a in (s.get("attrs", {}) for s in obs.get("decode_spans") or [])
           if a.get("latent_chunks") and "latent_full_chunks" in a]
    return statistics.median(got) if got else None
