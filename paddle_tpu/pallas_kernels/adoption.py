"""One rule for choosing a Pallas kernel — one funnel for every family.

Every kernel family under ``pallas_kernels/`` (``KERNELS``) decides here
whether it engages.  A family is chosen from what the lowering can observe
(shapes, dtypes, the backend, the kind of trace), never by a flag:

* **eligibility** — ``decide(kernel, checks)`` walks the family's ordered
  check list; the first failing check becomes the fallback *reason*.  The
  checks live beside the kernel that owns them (``fused_ln_checks``,
  ``flash_attention_checks``, ``paged_attention_checks``,
  ``ssm_update_checks``, ``moe_experts_checks``,
  ``latent_attention_checks``, ``kda_update_checks``, ``hc_maps_checks``).
* **telemetry** — every decision increments
  ``pallas_kernel_used_total{kernel}`` or
  ``pallas_kernel_fallback_total{kernel,reason}`` in the telemetry
  registry (no-ops when FLAGS_telemetry is off), so a fallback is a
  countable event and never silent.
* **the trace kind** — build-time shape inference chooses nothing and
  counts nothing (``shape_inference``); in a program that XLA partitions
  automatically over several devices a bare kernel call cannot lower
  (``auto_partitioned``, counted under reason ``gspmd_mesh``), so a
  lowering that wants its kernel there wraps the call in a ``shard_map``
  over the axis its rows are split on and enters ``per_shard`` inside
  the body: there the family's own checks decide on the shard's shape,
  as they do on one chip.  ``fused_ln`` does (ops/nn.py, on a mesh whose
  only axis of size over 1 is the data axis); the other families
  and any ``fused_ln`` call outside such a wrap still decline.

``PADDLE_PALLAS_INTERPRET=1`` forces interpret-mode execution (kernels run
through the Pallas interpreter on the CPU test tier).  On any backend but
the CPU's it raises.

A new kernel enters through ``decide`` with its checks, a benchmark cell
that runs it, and a case in tests/test_tpu_compile.py.
"""

import contextlib
import os
import threading

__all__ = ["decide", "active_kernels", "reset", "interpret_mode",
           "interpret", "auto_partitioned", "per_shard", "shape_inference",
           "KERNELS"]

# the kernel families sharing this funnel
KERNELS = ("fused_ln", "flash_attention", "paged_attention", "ssm_update",
           "moe_experts", "latent_attention", "kda_update", "index_scores",
           "hc_maps")

_lock = threading.Lock()
_active = set()          # kernels that engaged >= 1 time this process


def interpret_mode():
    """True when PADDLE_PALLAS_INTERPRET forces the Pallas interpreter
    (the CPU parity tests).  The interpreter is for the CPU backend only:
    leaked into a chip run, the switch would run each kernel interpreted
    while counting it as engaged, so there it is an error."""
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
    "wrap the call in a shard_map" at lowering — so a kernel call that is
    not wrapped falls back there, counted under reason ``gspmd_mesh``.  A
    lowering that has wrapped its call enters ``per_shard`` inside the
    body.  Inside the transpiled collective route's shard_map kernels run
    per shard and this is not entered."""
    return _tracing("gspmd_mesh")


def per_shard():
    """Entered by a lowering inside the body of a ``shard_map`` it put
    around its own kernel call in an auto-partitioned program: the axis the
    rows are split over is manual there, Mosaic sees one shard's operands,
    and ``decide`` runs the family's checks on the shard's shape instead of
    declining under ``gspmd_mesh``."""
    return _tracing(None)


def reset():
    """Clear the active set (tests)."""
    with _lock:
        _active.clear()


def _inc(name, **labels):
    from ..core import telemetry

    telemetry.inc(name, **labels)


def decide(kernel, checks):
    """Single adoption decision.  Returns (use: bool, reason: str).

    `checks` is an ordered iterable of (reason, ok) pairs; the first falsy
    `ok` is the recorded fallback reason (eligibility stays next to the
    kernel that owns it — this funnel owns the trace kind, the ordering
    and the counting)."""
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
    _inc("pallas_kernel_used_total", kernel=kernel)
    with _lock:
        _active.add(kernel)
    return True, "ok"


def active_kernels():
    """Sorted kernels that engaged at least once this process — bench.py
    prints this as `pallas_kernels_active` so a capture records which
    kernels actually ran."""
    with _lock:
        return sorted(_active)
