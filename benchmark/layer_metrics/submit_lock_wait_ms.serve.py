"""Server: mean time ``DecodeEngine.submit`` waited for the engine's lock,
over the requests admitted in the window (``admit_lock_wait_ms`` of its
``serving.decode_step`` spans): the part of ``queue_wait_ms.serve`` that is
the submitting thread losing the lock to the decode loop."""


def read(obs):
    spans = obs.get("decode_spans")
    if obs.get("kind") != "serve" or not spans:
        return None
    waits = [w for s in spans
             for w in s["attrs"].get("admit_lock_wait_ms", ())]
    return sum(waits) / len(waits) if waits else None
