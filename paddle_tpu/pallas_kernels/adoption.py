"""Probe-gated Pallas kernel adoption — one funnel for every kernel family.

Every kernel family under ``pallas_kernels/`` (``KERNELS``) decides here
whether it engages; before this module each carried its own copy of the
shape/dtype eligibility checks and fell back SILENTLY — a misconfigured flag
or an off-by-128 channel count ran the jnp composition with no trace in the
metrics.  This module centralizes:

* **eligibility** — ``decide()`` walks an ordered check list; the first
  failing check becomes the fallback *reason*.
* **telemetry** — every decision increments
  ``pallas_kernel_used_total{kernel}`` or
  ``pallas_kernel_fallback_total{kernel,reason}`` in the PR-3 registry
  (no-ops when FLAGS_telemetry is off), so a silent fallback is now a
  countable event.
* **the probe gate** — the hierarchical-systems cost-model discipline
  (PAPERS.md arXiv 2110.10548): a kernel may be *written* optimistically
  but is *adopted* only where a measured ``tools/op_bench.py --pallas``
  probe shows >= 1.1x over its own fallback on the target device.  Probe
  rows are JSON files (BASELINE.md round-9 protocol);
  ``PADDLE_PALLAS_PROBE_DIR`` points at the archive (default:
  ``tools/probes/results/``, which holds no row from the current
  installation).

Flag-off is INERT: no counters move, so a default-configured run pays one
dict lookup per decision and nothing else.

``PADDLE_PALLAS_INTERPRET=1`` forces interpret-mode execution (kernels run
through the Pallas interpreter on CPU) and waives the backend + probe
checks — the CI ``--kernel-smoke`` leg and the parity tests ride this.  On
any backend but the CPU's it raises.

In a program that XLA partitions automatically over several devices no
kernel engages (``auto_partitioned``, reason ``gspmd_mesh``).
"""

import contextlib
import json
import os
import threading

__all__ = ["decide", "active_kernels", "probe_speedup", "register_probe",
           "reset", "interpret_mode", "interpret", "auto_partitioned",
           "shape_inference", "KERNELS", "MIN_SPEEDUP"]

# the kernel families sharing this funnel
KERNELS = ("layer_norm", "fused_ln", "flash_attention", "conv_block",
           "fused_opt", "embedding_bag", "paged_attention")

# adoption threshold: a probe row below this keeps the fallback
MIN_SPEEDUP = 1.1

_lock = threading.Lock()
_active = set()          # kernels that engaged >= 1 time this process
_probe_overrides = {}    # kernel -> speedup (register_probe: tests/op_bench)
_probe_cache = None      # kernel -> speedup loaded from the archive dir


def interpret_mode():
    """True when PADDLE_PALLAS_INTERPRET forces the Pallas interpreter
    (CPU parity tests / the --kernel-smoke probe leg).  The interpreter is
    for the CPU backend only: leaked into a chip run, the switch would
    waive every probe gate and run each kernel interpreted while counting
    it as engaged, so there it is an error."""
    if os.environ.get("PADDLE_PALLAS_INTERPRET", "") not in ("1", "true"):
        return False
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "PADDLE_PALLAS_INTERPRET is set but the backend is %r: the "
            "Pallas interpreter is for the CPU test tier, unset it"
            % jax.default_backend())
    return True


def interpret():
    """The ``interpret=`` argument of a pallas_call: compiled by Mosaic on
    the TPU, interpreted on the CPU test mesh."""
    import jax

    return interpret_mode() or jax.default_backend() != "tpu"


# what kind of trace is running op lowerings on this thread, when it is one
# in which no kernel may engage
_trace = threading.local()


@contextlib.contextmanager
def _tracing(kind):
    old = getattr(_trace, "kind", None)
    _trace.kind = kind
    try:
        yield
    finally:
        _trace.kind = old


def shape_inference():
    """Entered by core/registry.py around build-time shape inference
    (``jax.eval_shape`` of the lowering with a symbolic batch).  Nothing is
    being lowered, so no kernel is chosen and NOTHING is counted — the
    used/fallback counters describe real lowerings only."""
    return _tracing("shape_inference")


def auto_partitioned():
    """Entered by core/lowering.py while it lowers the ops of a program that
    XLA partitions automatically over a mesh of several devices
    (CompiledProgram.with_data_parallel: jit + NamedSharding, no shard_map).
    Mosaic kernels cannot be partitioned automatically — JAX raises
    "wrap the call in a shard_map" at lowering — so there every family falls
    back, counted under reason ``gspmd_mesh``.  Inside a shard_map (the
    transpiled collective route) kernels run per shard and this is not
    entered."""
    return _tracing("gspmd_mesh")


def _probe_dir():
    d = os.environ.get("PADDLE_PALLAS_PROBE_DIR", "")
    if d:
        return d
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, "tools", "probes", "results")


def _load_probes():
    """kernel -> best measured speedup across every archived probe row.

    A row is any JSON object (one per file, or one per line) with
    ``kernel`` and ``speedup`` keys — exactly what
    ``op_bench.py --pallas --save-probe`` writes.  Unreadable files are
    skipped: a corrupt archive must degrade to "no probe" (fallback),
    never to a crash in the hot path."""
    out = {}
    d = _probe_dir()
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(d, name)) as f:
                text = f.read()
        except OSError:
            continue
        rows = []
        try:
            obj = json.loads(text)
            rows = obj if isinstance(obj, list) else [obj]
        except ValueError:
            for line in text.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass
        for row in rows:
            if not isinstance(row, dict):
                continue
            k = row.get("kernel")
            try:
                sp = float(row.get("speedup"))
            except (TypeError, ValueError):
                continue
            if k in KERNELS:
                out[k] = max(out.get(k, 0.0), sp)
    return out


def probe_speedup(kernel):
    """Best archived probe speedup for `kernel`, or None if never probed.
    In-memory registrations (register_probe) win over the disk archive."""
    global _probe_cache
    with _lock:
        if kernel in _probe_overrides:
            return _probe_overrides[kernel]
        cache = _probe_cache
    if cache is None:
        # read the archive outside the lock — disk I/O must not stall
        # register_probe()/decide() callers on other threads.  Two racing
        # loaders both read the same files; first publish wins and the
        # loser adopts it, so every caller sees one consistent cache.
        loaded = _load_probes()
        with _lock:
            if _probe_cache is None:
                _probe_cache = loaded
            cache = _probe_cache
    return cache.get(kernel)


def register_probe(kernel, speedup):
    """Record an in-process probe result (op_bench --pallas runs this after
    measuring; tests use it to exercise the gate without touching disk)."""
    with _lock:
        _probe_overrides[kernel] = float(speedup)


def reset():
    """Clear the active set, probe overrides, and the disk cache (tests)."""
    global _probe_cache
    with _lock:
        _active.clear()
        _probe_overrides.clear()
        _probe_cache = None


def _inc(name, **labels):
    from ..core import telemetry

    telemetry.inc(name, **labels)


def decide(kernel, flag=None, checks=(), require_probe=True):
    """Single adoption decision.  Returns (use: bool, reason: str).

    `flag`: the FLAGS_use_pallas_* name gating this family; when the flag
    is off the decision is inert — (False, "flag_off") with NO telemetry,
    so default-configured runs cost one flag read.  `checks` is an ordered
    iterable of (reason, ok) pairs; the first falsy `ok` is the recorded
    fallback reason (eligibility stays next to the kernel that owns it —
    this funnel owns the ordering, counting, and the probe gate).
    `require_probe=False` is for kernels whose adoption predates the probe
    protocol and is pinned by in-step BASELINE numbers instead (fused_ln:
    the round-3 LN lesson is that a microbench win is necessary but not
    sufficient, so an in-step capture outranks the probe row;
    paged_attention: no flag either, pinned by the serving cells of
    BENCHMARK.json)."""
    from .. import flags as _flags

    if flag is not None and not _flags.flag(flag):
        return False, "flag_off"
    kind = getattr(_trace, "kind", None)
    if kind == "shape_inference":
        return False, kind
    if kind == "gspmd_mesh":
        _inc("pallas_kernel_fallback_total", kernel=kernel, reason=kind)
        return False, kind
    for reason, ok in checks:
        if not ok:
            _inc("pallas_kernel_fallback_total", kernel=kernel,
                 reason=reason)
            return False, reason
    if require_probe and not interpret_mode():
        sp = probe_speedup(kernel)
        if sp is None:
            _inc("pallas_kernel_fallback_total", kernel=kernel,
                 reason="no_probe")
            return False, "no_probe"
        if sp < MIN_SPEEDUP:
            _inc("pallas_kernel_fallback_total", kernel=kernel,
                 reason="probe_below_min")
            return False, "probe_below_min"
    _inc("pallas_kernel_used_total", kernel=kernel)
    with _lock:
        _active.add(kernel)
    return True, "ok"


def active_kernels():
    """Sorted kernels that engaged at least once this process — bench.py
    prints this as `pallas_kernels_active` so a capture records which
    kernels actually ran (a kernel adopted without a probe row is an
    invalid capture, BASELINE.md round-9)."""
    with _lock:
        return sorted(_active)
