"""Server: mean time from ``DecodeEngine.submit`` being entered to the
request's admission to a lane, over the requests admitted in the window
(the ``admit_wait_ms`` lists of its ``serving.decode_step`` spans)."""


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    waits = [w for s in spans for w in s["attrs"].get("admit_wait_ms", ())]
    return sum(waits) / len(waits) if waits else None
