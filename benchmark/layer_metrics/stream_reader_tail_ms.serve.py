"""Client / wire: p95 less p50 of every ``turnaround_us`` on the window's
``serving.decode_step`` spans: as the RPC store saw it, from a connection's
streamed reply written to its next request read (the loopback twice, the
reader's ``recv``, ``codec.unpack``, its callback and its next ``get_var``).
It is the part of the client's ``itl_p95_ms`` over its median that the
readers make: the program's own ``serving/client.py`` under the load
generator's threads.  A program whose spans carry no such attribute (the
parent of the PR that added it) gives nothing to read."""

import numpy as np


def read(obs):
    if obs.get("kind") != "serve":
        return None
    got = [us for s in obs.get("decode_spans") or []
           for us in s.get("attrs", {}).get("turnaround_us") or ()]
    if not got:
        return None
    p50, p95 = np.percentile(got, [50, 95])
    return (p95 - p50) / 1e3
