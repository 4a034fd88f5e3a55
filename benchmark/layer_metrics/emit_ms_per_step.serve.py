"""Server: median time the decode loop spends in its ``serving.emit`` phase
a step (the ``phases`` attribute of the window's ``serving.decode_step``
spans, microseconds): taking the step's tokens, calling every generating
lane's ``on_token`` and handing the step's stream chunks to the RPC store.
The device has nothing queued meanwhile, so this is part of
``host_gap_ms_per_step.serve``.  Spans without the attribute (a program
older than the phases) give nothing to read."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    emit = [phases["serving.emit"]
            for phases in (s.get("attrs", {}).get("phases") or {}
                           for s in obs.get("decode_spans") or [])
            if "serving.emit" in phases]
    return statistics.median(emit) / 1e3 if emit else None
