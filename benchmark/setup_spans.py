"""The run's set-up spans, for the per-layer metrics that split ``setup_s``
(``import_s.setup``, ``executor_build_s.train``, ``cache_restore_s.setup``,
``compile_s.setup``, ``executables_compiled.setup``, ``add_model_s.serve``,
``prewarm_s.serve``, ``in_program_s.setup``).

A traced run records set-up already: ``run.py`` turns the recorder on before
the runner starts, and ``import paddle_tpu``, which precedes the flag, times
itself and hands the recorder its span with the first record
(``setup.import``).  The spans are the program's own
(``paddle_tpu/core/tracing.py``): ``executor.build``, ``.disk_key``,
``.cache_restore``, ``.compile``, ``.first_run`` under an ``executor.step``
that missed the cache or an ``executor.warmup``; ``serving.add_model`` and
``serving.prewarm`` round the engine's.

``spans(obs)`` is ``{name: [records]}`` of this process, read once a run and
kept on ``obs``: from the recorder's memory (``tracing.records``) while it
still holds the process's first record, which ``setup.import`` is, and
otherwise (a long window pushed set-up out of the ring of 65,536, or the
program is older than the span) from the sink's ``trace-<pid>.jsonl`` under
``FLAGS_telemetry_dir`` after a flush.  A program that records none of a
name gives an empty list, and the readers then report nothing.
"""

import json
import os
import re

# their union is the set-up a change to the program can move (children lie
# inside their parents: a union counts nothing twice)
NAMES = ("setup.import", "executor.step", "executor.warmup",
         "executor.build", "executor.disk_key", "executor.cache_restore",
         "executor.compile", "executor.first_run", "serving.add_model",
         "serving.prewarm")
_WANTED = re.compile('"name": "(%s)"' % "|".join(map(re.escape, NAMES)))


def _from_sink():
    """The process's own sink file, and the one it was rotated from."""
    import paddle_tpu as fluid
    from paddle_tpu.core import tracing

    root = fluid.get_flags(["FLAGS_telemetry_dir"]).get(
        "FLAGS_telemetry_dir")
    if not root:
        return []
    tracing.flush()
    path = os.path.join(root, "trace-%d.jsonl" % os.getpid())
    out = []
    for part in (path + ".1", path):
        if not os.path.exists(part):
            continue
        with open(part) as fp:
            for line in fp:
                if _WANTED.search(line) is None:
                    continue
                rec = json.loads(line)
                if rec.get("t") == "span" and rec.get("name") in NAMES:
                    out.append(rec)
    return out


def _is_set_up(span):
    """An ``executor.step`` is set-up where it built its executable."""
    return span["name"] != "executor.step" \
        or span.get("attrs", {}).get("cache_hit") is False


def spans(obs):
    """``{name: [span records]}`` of the run's set-up, oldest first; of
    ``executor.step`` only the steps that missed the cache."""
    held = obs.get("setup_spans")
    if held is None:
        from paddle_tpu.core import tracing

        records = getattr(tracing, "records", None)
        if records is not None and records("setup.import"):
            found = [s for name in NAMES for s in records(name)]
        else:
            found = _from_sink()
        held = {name: [] for name in NAMES}
        for span in sorted(found, key=lambda s: s["ts"]):
            if _is_set_up(span):
                held[span["name"]].append(span)
        obs["setup_spans"] = held
    return held


def seconds(obs, *names):
    """The summed duration of the run's spans of these names, or None where
    it recorded none of them."""
    found = [s for name in names for s in spans(obs)[name]]
    return sum(s["dur"] for s in found) / 1e6 if found else None


def compiled_or_restored(obs):
    """Whether the program put an executable through the recorded path at
    all: where it did, a run that only restored reads 0.0 s of compiling
    (and one that only compiled 0.0 s of restoring), not nothing."""
    held = spans(obs)
    return bool(held["executor.cache_restore"] or held["executor.compile"])


def union_seconds(records):
    """Seconds covered by at least one of the spans (wall-clock start,
    monotonic duration, both in microseconds)."""
    covered, end = 0, None
    for start, stop in sorted((s["ts"], s["ts"] + s["dur"])
                              for s in records):
        if end is None or start > end:
            covered += stop - start
            end = stop
        elif stop > end:
            covered += stop - end
            end = stop
    return covered / 1e6
