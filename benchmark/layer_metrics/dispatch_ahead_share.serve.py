"""Scheduler: share of the window's ``serving.decode_step`` spans whose
``ahead`` attribute is true: the step before was still running on the device
as this one was dispatched (the loop asked ``is_ready`` of its tokens just
before the dispatch), so the device went from one step to the next with no
host time between them.  100 where the device's step is the longer side of
the loop, falling as the host becomes it.  Spans without the attribute (a
program whose loop waits for a step's tokens before it plans the next, and
the synchronous iterations a speculating model keeps) give nothing to
read."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    ahead = [s["attrs"]["ahead"] for s in obs.get("decode_spans") or []
             if "ahead" in s.get("attrs", {})]
    return 100.0 * sum(bool(a) for a in ahead) / len(ahead) if ahead else None
