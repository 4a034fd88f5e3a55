"""Client / wire: of the streamed replies the RPC store timed in the window
(``stream_replies`` of the ``serving.decode_step`` spans), the share whose
request was read only after its chunk was stored (a ``late_us``): the chunk
lay in the store waiting for its reader, where otherwise the reader is
parked waiting for the chunk.  A program whose spans carry no such attribute
(the parent of the PR that added it), or a window with no timed reply, gives
nothing to read."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    attrs = [s.get("attrs", {}) for s in obs.get("decode_spans") or []]
    replies = sum(a.get("stream_replies", 0) for a in attrs)
    if not replies:
        return None
    return 100.0 * sum(len(a.get("late_us") or ()) for a in attrs) / replies
