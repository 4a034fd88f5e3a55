"""Server: mean of the ``published`` attribute of the window's
``serving.decode_step`` spans: the stream chunks that the step's one store
transaction carried to the RPC store.  It reads the generating, streaming
lanes of a step where a step's tokens are published together, and 1 where
each token is stored alone.  A program whose spans carry no such attribute
(the parent of the PR that added it) gives nothing to read."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [s["attrs"]["published"] for s in obs.get("decode_spans") or []
           if "published" in s.get("attrs", {})]
    return statistics.fmean(got) if got else None
