"""Executor: median ``host_us`` of the run's cache-hit ``executor.step``
spans: the time of one ``Executor.run`` outside ``executor.dispatch`` and
``executor.fetch`` (feed conversion, cache key, scope gathers, sharding,
write-back).  The runner's ``obs`` holds no spans, so they are read from
the program's in-memory record; a program without one gives nothing."""

import statistics


def read(obs):
    if obs.get("kind") != "train":
        return None
    from paddle_tpu.core import tracing

    records = getattr(tracing, "records", None)
    spans = records("executor.step") if records is not None else []
    host = [s["attrs"]["host_us"] for s in spans
            if s.get("attrs", {}).get("cache_hit")
            and "host_us" in s["attrs"]]
    return statistics.median(host) / 1e3 if host else None
