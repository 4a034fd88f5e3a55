"""Merge per-process tracing JSONL into one Chrome/Perfetto trace.json.

Every traced process writes ``trace-<pid>.jsonl`` under
``FLAGS_telemetry_dir`` (core/tracing.py); a multi-process run — fleet
replicas + client, launch.py trainers/pservers — therefore leaves one
file per process.  This tool merges them into a single chrome-trace
document:

- each process is a named track (the ``proc`` header record carries the
  name set via ``tracing.set_process_name``; threads become sub-tracks)
- ``span`` records become ``ph:"X"`` slices, ``inst``/``note`` records
  become instant markers (flight-recorder ``flightrec-*.json`` dumps are
  folded in as process-scoped instants so a postmortem shows up on the
  dead replica's track)
- a parent->child span edge or a span link whose two ends live in
  DIFFERENT processes becomes a flow arrow (``ph:"s"``/``"f"``) keyed by
  trace_id, so one request's client.infer -> serving.admission -> ... ->
  serving.reply_publish chain reads as one connected line across tracks
- a ``link``-kind edge between two span trees of the SAME process also
  becomes an arrow (same-process parent edges stay implicit in the slice
  nesting): the elastic re-quorum's restore phase links the
  ``checkpoint.save``/``checkpoint.restore`` tree that produced its
  state, so recovery reads as checkpoint I/O flowing into the re-quorum

Usage:
    python tools/trace_view.py --telemetry_dir /tmp/tel --out trace.json
    python tools/trace_view.py --telemetry_dir ... --out ... --require-flow
    python tools/trace_view.py --telemetry_dir /tmp/tel --setup

``--require-flow`` exits non-zero unless at least one cross-process flow
was emitted (the --trace-smoke CI gate).  Open the output in
https://ui.perfetto.dev or chrome://tracing.

``--setup`` writes no file: it prints each process's set-up tree, the
view to read after a slow start.  The roots are ``setup.import``, the
``executor.step``s that missed the cache, ``executor.warmup``,
``serving.add_model`` and ``serving.prewarm``; under each its children
by parent id (``executor.build``, ``.disk_key``, ``.cache_restore``,
``.compile``, ``.first_run``, ``serving.lay_out``, ``.cache_alloc``), a
line a span: when it began after the process's first span, its duration,
its self time (the duration less what its children cover) and its
attributes.
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from timeline import track_meta  # noqa: E402


def read_jsonl(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # torn tail line from a killed process
    return out


def load_dir(telemetry_dir):
    """-> list of (pid, proc_name, records) per trace-*.jsonl, with any
    flightrec-*.json records folded into the matching process (or their
    own synthetic process when no JSONL exists for that pid)."""
    procs = {}
    for path in sorted(glob.glob(os.path.join(telemetry_dir,
                                              "trace-*.jsonl"))):
        # include a rotated predecessor so a long soak still merges
        recs = read_jsonl(path + ".1") if os.path.exists(path + ".1") \
            else []
        recs += read_jsonl(path)
        pid = int(os.path.basename(path)[len("trace-"):-len(".jsonl")])
        name = "pid-%d" % pid
        for r in recs:
            if r.get("t") == "proc" and r.get("name"):
                name = r["name"]
        procs[pid] = (name, recs)
    for path in sorted(glob.glob(os.path.join(telemetry_dir,
                                              "flightrec-*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except ValueError:
            continue
        pid = int(doc.get("proc", {}).get("pid", 0) or
                  os.path.basename(path)[len("flightrec-"):-len(".json")])
        name, recs = procs.get(pid) or (
            doc.get("proc", {}).get("name") or "pid-%d" % pid, [])
        recs = list(recs)
        recs.append({"t": "note", "kind": "flightrec",
                     "ts": doc.get("dumped_at", 0),
                     "reason": doc.get("reason", "?"), "thr": "flightrec"})
        # only add ring records the JSONL does not already carry (a live
        # process logs both; a SIGKILLed one may only have the dump)
        seen = {(r.get("t"), r.get("sid"), r.get("ts")) for r in recs}
        for r in doc.get("records", []):
            if (r.get("t"), r.get("sid"), r.get("ts")) not in seen:
                recs.append(r)
        procs[pid] = (name, recs)
    return [(pid, nm, rc) for pid, (nm, rc) in sorted(procs.items())]


# chrome://tracing reserved color names for span families whose phases
# should be tellable apart at a glance: the speculative-decode children
# (draft work yellow-ish, the target verify step green, so accept/reject
# economics show up visually) and the checkpoint tree (the foreground
# D2H snapshot + save stall vs the background write — the async-save
# contract is precisely that the yellow I/O slice leaves the step track)
_SPAN_COLORS = {"serving.draft": "thread_state_iowait",
                "serving.draft_ingest": "thread_state_iowait",
                "serving.verify": "thread_state_running",
                "executor.snapshot": "thread_state_runnable",
                "checkpoint.save": "rail_response",
                "checkpoint.write": "thread_state_iowait",
                "checkpoint.restore": "rail_load"}


def merge(procs):
    """-> (chrome trace dict, number of cross-process flows)."""
    events = []
    span_home = {}   # span_id -> (pid, tid, ts_us, name)
    edges = []       # (child_pid, child_tid, child_ts, trace_id,
                     #  parent_sid, child_sid, kind)
    tid_maps = {}
    for sort, (pid, name, recs) in enumerate(procs):
        events.extend(track_meta(pid, name, sort_index=sort))
        tids = tid_maps.setdefault(pid, {})

        def tid_of(thr):
            if thr not in tids:
                tids[thr] = len(tids) + 1
                events.extend(track_meta(pid, name, tid=tids[thr],
                                         thread_name=thr)[1:])
            return tids[thr]

        for r in recs:
            t = r.get("t")
            ts = r.get("ts", 0)
            tid = tid_of(r.get("thr", "main"))
            if t == "span":
                args = dict(r.get("attrs") or {})
                args["trace_id"] = r.get("tid")
                args["span_id"] = r.get("sid")
                if r.get("parent"):
                    args["parent_id"] = r["parent"]
                ev = {"name": r.get("name", "?"), "ph": "X",
                      "pid": pid, "tid": tid, "ts": ts,
                      "dur": max(r.get("dur", 0), 1),
                      "cat": "span", "args": args}
                cname = _SPAN_COLORS.get(ev["name"])
                if cname:
                    ev["cname"] = cname
                events.append(ev)
                span_home[r.get("sid")] = (pid, tid, ts,
                                           r.get("name", "?"))
                if r.get("parent"):
                    edges.append((pid, tid, ts, r.get("tid"),
                                  r["parent"], r.get("sid"), "parent"))
                for ltid, lsid in r.get("links") or []:
                    # link arrow points batch -> linked request: start at
                    # the LINKED span, finish at this one
                    edges.append((pid, tid, ts, ltid, lsid,
                                  r.get("sid"), "link"))
            elif t in ("inst", "note"):
                nm = r.get("name") if t == "inst" else \
                    "note:%s" % r.get("kind", "?")
                args = {k: v for k, v in r.items()
                        if k not in ("t", "ts", "thr", "name")}
                events.append({"name": nm, "ph": "i", "pid": pid,
                               "tid": tid, "ts": ts,
                               "s": "t" if t == "inst" else "p",
                               "cat": t, "args": args})
    flows = local_flows = 0
    for cpid, ctid, cts, trace_id, psid, csid, kind in edges:
        home = span_home.get(psid)
        if home is None:
            continue
        if home[0] == cpid and kind != "link":
            # same-process parent edge: the slice nesting already shows it
            continue
        ppid, ptid, pts, pname = home
        fid = "%s:%s" % (trace_id, csid)
        events.append({"name": "trace", "cat": "flow", "ph": "s",
                       "id": fid, "pid": ppid, "tid": ptid,
                       "ts": pts + 1})
        events.append({"name": "trace", "cat": "flow", "ph": "f",
                       "bp": "e", "id": fid, "pid": cpid, "tid": ctid,
                       "ts": max(cts + 1, pts + 2)})
        if home[0] == cpid:
            # link between two span TREES of one process — e.g. the
            # elastic restore phase pointing back at the checkpoint
            # save/restore tree that produced its state; without the
            # arrow they read as unrelated tracks
            local_flows += 1
        else:
            flows += 1
    events.sort(key=lambda e: (e.get("ts", 0), e.get("ph") != "M"))
    return {"traceEvents": events,
            "displayTimeUnit": "ms"}, flows, local_flows


_SETUP_ROOTS = ("setup.import", "executor.warmup", "serving.add_model",
                "serving.prewarm")


def _starts_set_up(r):
    return r["name"] in _SETUP_ROOTS or (
        r["name"] == "executor.step"
        and r.get("attrs", {}).get("cache_hit") is False)


def setup_roots(records):
    """The spans a set-up tree starts from, oldest first: the named roots
    and every ``executor.step`` that missed the cache, where none of them
    lies above (a bucket's ``executor.warmup`` is its prewarm's child)."""
    spans = {r["sid"]: r for r in records if r.get("t") == "span"}

    def nested(r):
        above = spans.get(r.get("parent"))
        return above is not None and (_starts_set_up(above) or nested(above))

    return sorted((r for r in spans.values()
                   if _starts_set_up(r) and not nested(r)),
                  key=lambda r: r["ts"])


def setup_tree(records):
    """-> lines: each root of ``setup_roots`` and what lies under it."""
    spans = [r for r in records if r.get("t") == "span"]
    children = {}
    for r in spans:
        children.setdefault(r.get("parent"), []).append(r)
    t0 = min((r["ts"] for r in spans), default=0)
    lines = []

    def walk(r, depth):
        below = sorted(children.get(r["sid"], ()), key=lambda c: c["ts"])
        covered = sum(c["dur"] for c in below)
        # a step's phase tally is left out: the tree says it
        attrs = {k: v for k, v in r.get("attrs", {}).items()
                 if k != "phases"}
        lines.append("%9.3f s  %-34s %10.3f s  self %10.3f s  %s"
                     % ((r["ts"] - t0) / 1e6, "  " * depth + r["name"],
                        r["dur"] / 1e6, max(r["dur"] - covered, 0) / 1e6,
                        json.dumps(attrs, sort_keys=True) if attrs else ""))
        for c in below:
            walk(c, depth + 1)

    for root in setup_roots(records):
        walk(root, 0)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge per-process trace-*.jsonl into trace.json")
    ap.add_argument("--telemetry_dir", required=True,
                    help="FLAGS_telemetry_dir of the traced run")
    ap.add_argument("--out", help="output trace.json path")
    ap.add_argument("--setup", action="store_true",
                    help="print each process's set-up tree (durations and "
                    "self times) instead of writing trace.json")
    ap.add_argument("--require-flow", action="store_true",
                    help="exit 1 unless >=1 cross-process flow merged")
    args = ap.parse_args(argv)
    procs = load_dir(args.telemetry_dir)
    if not procs:
        print("no trace-*.jsonl under %s" % args.telemetry_dir,
              file=sys.stderr)
        return 1
    if args.setup:
        for pid, name, records in procs:
            print("%s (pid %d)" % (name, pid))
            print("\n".join(setup_tree(records)))
        return 0
    if not args.out:
        ap.error("--out is required unless --setup is given")
    trace, flows, local_flows = merge(procs)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    print("merged %d processes, %d events, %d cross-process + %d "
          "same-process link flows -> %s"
          % (len(procs), len(trace["traceEvents"]), flows, local_flows,
             args.out))
    if args.require_flow and flows == 0:
        print("--require-flow: no cross-process flow found",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
