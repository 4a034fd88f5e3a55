"""Server: p95 less p50 of every ``deliver_us`` on the window's
``serving.decode_step`` spans: for each streamed reply the RPC store timed,
from the moment chunk and request were both there to the reply written (the
handler's wake, the store's mutex, the copy, the write).  It is the part of
the client's ``itl_p95_ms`` over its median that the server's own delivery
makes.  A step's span carries the replies written since the step before, so
the values are a step late, which a percentile over the window does not see.
A program whose spans carry no such attribute (the parent of the PR that
added it) gives nothing to read."""

import numpy as np


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [us for s in obs.get("decode_spans") or []
           for us in s.get("attrs", {}).get("deliver_us") or ()]
    if not got:
        return None
    p50, p95 = np.percentile(got, [50, 95])
    return (p95 - p50) / 1e3
