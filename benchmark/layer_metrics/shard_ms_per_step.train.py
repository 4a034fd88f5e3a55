"""Parallelism: median time one ``Executor.run`` spends placing its feeds
and its parameter dicts over the mesh (the ``executor.shard_feeds`` and
``executor.shard_params`` phases of the cache-hit ``executor.step`` spans,
read from the program's in-memory record).  Only the mesh route has these
phases; elsewhere, and in a program without them, there is nothing to
read."""

import statistics


def read(obs):
    if obs.get("kind") != "train":
        return None
    from paddle_tpu.core import tracing

    records = getattr(tracing, "records", None)
    spans = records("executor.step") if records is not None else []
    shard = []
    for s in spans:
        attrs = s.get("attrs", {})
        phases = attrs.get("phases", {})
        if attrs.get("cache_hit") and "executor.shard_feeds" in phases:
            shard.append(phases["executor.shard_feeds"]
                         + phases.get("executor.shard_params", 0))
    return statistics.median(shard) / 1e3 if shard else None
