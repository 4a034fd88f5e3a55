"""Scheduler: mean number of live lanes per engine step (the ``lanes``
attribute of ``serving.decode_step`` spans) over the window."""


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    return sum(s["attrs"]["lanes"] for s in spans) / len(spans)
