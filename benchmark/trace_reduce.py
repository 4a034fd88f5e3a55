"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, the window, time per device operation,
and the idle gaps labelled by what the host was doing.

The yardstick lives here, with the benchmark, so that every PR computes the
same number the same way.  Only ``jax.profiler.ProfileData`` is needed to
read the file.

What the trace of this installation looks like (TPU v5e, JAX 0.9.0, read by
hand before this was written):

* one plane per chip, named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
  one event per executed HLO operation (nested for ``while``/``conditional``
  bodies), ``XLA Modules`` one event per executable launch, ``Steps`` the
  step groups;
* host planes (``/host:CPU`` and friends), one line per thread, holding the
  runtime's own events and every ``jax.profiler.TraceAnnotation``.

Busy time is the union of the ``XLA Ops`` intervals of a chip, clipped to
the window; the window is the ``bench.window`` annotation the runner wraps
around the traced work (else the extent of the device events).  A gap is a
maximal interval inside the window in which no operation ran on the chip.
"""

import bisect
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.window"
# operations that move data between chips (HLO opcode prefixes as the trace
# prints them; fusions never carry these names)
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")
# host events that only say "a thread exists": never a gap's label
_HOST_NOISE = ("$", "Thread", "process_", "ProfilerSession")
SHORT_GAP_NS = 20e3
LABELLED_GAPS = 2000


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler.start_trace`` dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    """[(start_ns, end_ns, name)] of one line, sorted by start."""
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def _union(intervals):
    """Merged, sorted, disjoint intervals of [(start, end)]."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _self_times(events):
    """name -> self nanoseconds: an event's duration less what the events
    nested inside it cover (a ``while`` is not charged its body)."""
    out = {}
    stack = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for start, end, name in events:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return out


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def _window(data, device_events):
    """(start_ns, end_ns) of the traced window."""
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_ANNOTATION:
                    start = float(ev.start_ns)
                    return start, start + float(ev.duration_ns)
    starts = [evs[0][0] for evs in device_events.values() if evs]
    ends = [max(e for _s, e, _n in evs) for evs in device_events.values()
            if evs]
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def _host_events(data):
    out = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for start, end, name in _events(line):
                if name == WINDOW_ANNOTATION or name.startswith(_HOST_NOISE):
                    continue
                out.append((start, end, name))
    out.sort()
    return out


LONG_HOST_EVENT_NS = 1e6


class _HostIndex:
    """Answers "which host event covers this instant": the shortest one,
    looked up among the few long events and the latest short ones."""

    def __init__(self, events):
        self.long = [e for e in events if e[1] - e[0] >= LONG_HOST_EVENT_NS]
        self.short = [e for e in events if e[1] - e[0] < LONG_HOST_EVENT_NS]
        self.starts = [e[0] for e in self.short]

    def label(self, at):
        # a short event that covers `at` began less than LONG_HOST_EVENT_NS
        # before it
        lo = bisect.bisect_left(self.starts, at - LONG_HOST_EVENT_NS)
        hi = bisect.bisect_right(self.starts, at)
        best = None
        for start, end, name in self.short[lo:hi] + self.long:
            if start <= at <= end and (best is None
                                       or end - start < best[0]):
                best = (end - start, name)
        return _short(best[1]) if best else "unknown"


def _short(name):
    """A label without spaces, commas or arguments, at most 48 characters."""
    name = name.split("(")[0].split(" ")[0].strip() or "unknown"
    return name[:48]


def reduce_profile(data, top=10):
    """The trace's numbers, as a dict:

    ``window_s``, ``busy_s`` (averaged over the chips that ran anything),
    ``chips``, ``module_launches`` (executables started, per chip),
    ``op_seconds`` (operation -> self seconds, summed over chips
    and divided by their number), ``collective_s`` (same, for collectives),
    ``device_ops`` / ``idle_gaps`` (the ``top`` largest, for the breakdown).
    """
    device_events, launches = {}, {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                device_events[plane.name] = _events(line)
            elif line.name == MODULES_LINE:
                launches[plane.name] = _events(line)
    lo, hi = _window(data, device_events)
    used = {name: _clip(evs, lo, hi) for name, evs in device_events.items()}
    used = {name: evs for name, evs in used.items() if evs}
    chips = len(used)
    out = {"window_s": (hi - lo) / 1e9, "busy_s": 0.0, "chips": chips,
           "op_seconds": {}, "collective_s": 0.0, "module_launches": 0.0,
           "device_ops": [], "idle_gaps": []}
    if not chips:
        return out
    host = _HostIndex(_host_events(data))
    ops, gaps, found = {}, {}, []
    busy = 0.0
    for evs in used.values():
        merged = _union([(s, e) for s, e, _n in evs])
        busy += sum(e - s for s, e in merged)
        for name, ns in _self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + ns
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        found += [(e - s, s) for s, e in zip(edges[0::2], edges[1::2])
                  if e > s]
    # the device's own hand-over from one operation to the next is not the
    # host's doing; only the longest of the longer gaps are worth a label
    found.sort(reverse=True)
    for rank_, (length, start) in enumerate(found):
        if length < SHORT_GAP_NS:
            label = "op_to_op_under_20us"
        elif rank_ < LABELLED_GAPS:
            label = host.label(start + 0.5 * length)
        else:
            label = "unlabelled_short_gaps"
        gaps[label] = gaps.get(label, 0.0) + length
    out["busy_s"] = busy / chips / 1e9
    # executables that began inside the window, per chip
    out["module_launches"] = sum(
        sum(lo <= start < hi for start, _e, _n in launches.get(name, []))
        for name in used) / chips
    out["op_seconds"] = {n: ns / chips / 1e9 for n, ns in ops.items()}
    out["collective_s"] = sum(
        s for n, s in out["op_seconds"].items()
        if n.lstrip("%").startswith(COLLECTIVE_PREFIXES))
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    out["device_ops"] = [[_short(n), s] for n, s in rank(out["op_seconds"])]
    out["idle_gaps"] = [[n, ns / chips / 1e9] for n, ns in rank(gaps)]
    return out


def reduce_dir(trace_dir, top=10):
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_profile(load(path), top=top)
