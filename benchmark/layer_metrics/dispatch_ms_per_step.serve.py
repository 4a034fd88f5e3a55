"""Scheduler: median time the decode loop spends in its ``serving.dispatch``
phase a step (the ``phases`` attribute of the window's ``serving.decode_step``
spans, microseconds): the host's side of starting one executable, from
``CarriedStepFn.__call__`` (the signature of the step's argument tree, the
executable's own call with its hundreds of buffers) to the carry being
swapped for its outputs.  It is host time whether or not the device is at
work meanwhile; where the host is the longer side of the loop it is the
largest part of the period.  Spans without the attribute (a program older
than the phases) give nothing to read."""

import statistics


def read(obs):
    if obs.get("kind") != "serve":
        return None
    dispatch = [phases["serving.dispatch"]
                for phases in (s.get("attrs", {}).get("phases") or {}
                               for s in obs.get("decode_spans") or [])
                if "serving.dispatch" in phases]
    return statistics.median(dispatch) / 1e3 if dispatch else None
