"""Scheduler: share of lane-steps that fed a prompt token instead of
generating one (``lanes`` less ``generated`` of the decode-step spans)."""


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    lanes = sum(s["attrs"]["lanes"] for s in spans)
    generated = sum(s["attrs"].get("generated", 0) for s in spans)
    return 100.0 * (lanes - generated) / lanes if lanes else None
