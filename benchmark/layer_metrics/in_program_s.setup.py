"""Executor: seconds of set-up spent inside the program, the union of every
set-up span's interval (``setup_spans.NAMES``: the import, the steps
that missed the cache with their build, key, restore, compile and first run,
the warm-ups, ``serving.add_model`` and ``serving.prewarm``): how much of
``setup_s`` a change to the program can move.  The rest is the runtime's
start, the benchmark's weights, check, child and ramp.  A program without
``setup.import`` (older than the spans) gives nothing, since a union of the
two spans it does record would not be this quantity."""


def read(obs):
    from benchmark import setup_spans

    held = setup_spans.spans(obs)
    if not held["setup.import"]:
        return None
    return setup_spans.union_seconds(
        [s for found in held.values() for s in found])
