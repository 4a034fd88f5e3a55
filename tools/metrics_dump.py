"""Inspect paddle_tpu telemetry: pretty-print a dumped snapshot or scrape
a live pserver's ``__metrics__`` RPC.

Usage:
    python tools/metrics_dump.py --json  RUN_DIR/metrics.json
    python tools/metrics_dump.py --scrape HOST:PORT [--timeout SECS]
    python tools/metrics_dump.py ... --prom          # Prometheus text
    python tools/metrics_dump.py ... --raw           # raw JSON passthrough

``--json`` reads what ``telemetry.dump()`` / the Executor end-of-run hook
wrote under FLAGS_telemetry_dir; ``--scrape`` asks a running pserver
(distributed/ps.py publishes a fresh snapshot every round).  The default
output is a human table; --prom re-renders either source in Prometheus
exposition format for scrapers.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _filter_snap(snap, prefix):
    """Keep only metric families whose FLAT name starts with `prefix`
    (label suffixes ride along)."""
    kept = dict(snap)
    for fam in ("counters", "gauges", "histograms"):
        kept[fam] = {k: v for k, v in snap.get(fam, {}).items()
                     if k.startswith(prefix)}
    kept["events_logged"] = {k: v
                             for k, v in snap.get("events_logged",
                                                  {}).items()
                             if k.startswith(prefix)}
    kept["info"] = {}
    return kept


def render_table(snap, out=sys.stdout):
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})
    if counters:
        out.write("counters:\n")
        for k in sorted(counters):
            out.write("  %-52s %g\n" % (k, counters[k]))
    if gauges:
        out.write("gauges:\n")
        for k in sorted(gauges):
            out.write("  %-52s %g\n" % (k, gauges[k]))
    if hists:
        out.write("histograms (ms unless the name says otherwise):\n")
        for k in sorted(hists):
            h = hists[k]
            out.write("  %-40s n=%-6d sum=%-10g p50=%-8g p90=%-8g "
                      "p99=%g\n" % (k, h["count"], h["sum"], h["p50"],
                                    h["p90"], h["p99"]))
    ev = snap.get("events_logged", {})
    if ev:
        out.write("events logged: %s\n"
                  % ", ".join("%s=%d" % kv for kv in sorted(ev.items())))
    info = snap.get("info", {})
    if info:
        out.write("info payloads: %s\n" % ", ".join(sorted(info)))
    if not (counters or gauges or hists or ev):
        out.write("(empty snapshot — was FLAGS_telemetry on?)\n")


def render_fleet(doc, out=sys.stdout):
    """Human view of a ``__fleet__`` aggregate (serving/fleetmon.py):
    per-replica rows, fleet-merged histograms, windowed rates, goodput,
    and SLO burn state."""
    out.write("fleet @ t=%.3f epoch=%s replicas_up=%s\n"
              % (doc.get("t", 0.0), doc.get("epoch", "?"),
                 doc.get("replicas_up", "?")))
    for r in doc.get("replicas", []):
        p99 = r.get("p99_ms", {})
        out.write("  %-22s role=%-8s up=%-5s q=%-5g kv=%-5.2f "
                  "hit=%-5.2f server_p99=%-8g itl_p99=%g\n"
                  % (r.get("endpoint", "?"), r.get("role", "?"),
                     r.get("up"), r.get("queue_depth", 0.0),
                     r.get("kv_occupancy", 0.0),
                     r.get("prefix_hit_rate", 0.0),
                     p99.get("server_ms", 0.0), p99.get("itl_ms", 0.0)))
    hists = doc.get("histograms", {})
    if hists:
        out.write("fleet-merged histograms:\n")
        for k in sorted(hists):
            h = hists[k]
            out.write("  %-40s n=%-6d p50=%-8g p90=%-8g p99=%g\n"
                      % (k, h.get("count", 0), h.get("p50", 0.0),
                         h.get("p90", 0.0), h.get("p99", 0.0)))
    rates = doc.get("rates", {})
    if rates:
        out.write("windowed rates (/s over %gs):\n"
                  % doc.get("rate_window_s", 0.0))
        for k in sorted(rates):
            if rates[k]:
                out.write("  %-52s %g\n" % (k, rates[k]))
    gp = doc.get("goodput", {})
    if gp:
        out.write("goodput: %s\n"
                  % ", ".join("%s=%g" % kv for kv in sorted(gp.items())))
    for s in doc.get("slo", []):
        out.write("slo %-14s %s p%d obj=%gms burn fast=%.2f slow=%.2f "
                  "%s\n" % (s["name"], s["metric"],
                            round(s["quantile"] * 100),
                            s["objective_ms"], s["burn_fast"],
                            s["burn_slow"],
                            "FIRING" if s["active"] else "ok"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--json", dest="json_path",
                     help="metrics.json written by telemetry.dump()")
    src.add_argument("--scrape", dest="endpoint",
                     help="live pserver HOST:PORT (__metrics__ RPC)")
    ap.add_argument("--timeout", type=float, default=10.0,
                    help="scrape connect/RPC deadline in seconds")
    ap.add_argument("--fleet", action="store_true", dest="fleet_doc",
                    help="with --scrape: GET the coordinator's merged "
                    "__fleet__ aggregate (serving/fleetmon.py) instead "
                    "of one replica's __metrics__ snapshot; with --json "
                    "render the file as a fleet doc (merged histograms "
                    "include migration_ms, rates include kv_migrate_*)")
    ap.add_argument("--prom", action="store_true",
                    help="emit Prometheus exposition text")
    ap.add_argument("--raw", action="store_true",
                    help="emit the raw JSON snapshot")
    ap.add_argument("--elastic", action="store_true",
                    help="show only the elastic re-quorum health metrics "
                    "(elastic_epoch/world gauges, eviction/rejoin "
                    "counters, re-quorum duration histogram)")
    ap.add_argument("--collective", action="store_true",
                    help="show only the collective-exchange metrics "
                    "(collective_nranks/wire_bytes gauges+counters and "
                    "the zero1_* shard accounting)")
    ap.add_argument("--compile", action="store_true", dest="compile_only",
                    help="show only compilation metrics: the two-tier "
                    "cache (compile_cache_* hit/miss/store/eviction/error "
                    "counters) and the executor's compile, miss, warmup "
                    "and fallback counts (the durations are the set-up "
                    "spans': tools/trace_view.py --setup)")
    ap.add_argument("--kernels", action="store_true", dest="kernels_only",
                    help="show only Pallas kernel-adoption metrics: the "
                    "pallas_kernel_used_total{kernel} / "
                    "pallas_kernel_fallback_total{kernel,reason} counters "
                    "(pallas_kernels/adoption.py)")
    ap.add_argument("--serving", action="store_true", dest="serving_only",
                    help="show only inference-serving metrics: queue "
                    "depth / qps / fleet gauges, request / shed / timeout "
                    "/ batch counters, latency + batch-fill histograms, "
                    "plus the control plane — per-tier shed counters "
                    "(serving_tier_shed_total{tier}), autoscaler events "
                    "(autoscale_events_total{dir}), rollout_state gauge "
                    "and rollback counters, client shed retries, and "
                    "injected wire faults (serving/engine.py + fleet.py "
                    "+ rollout.py)")
    ap.add_argument("--decode", action="store_true", dest="decode_only",
                    help="show only autoregressive-decode metrics: paged "
                    "KV pool counters/gauges (kv_block_*, kv_blocks_in_use"
                    ", kv_cache_bytes, kv_block_evictions_total), "
                    "serving_decode_* / serving_tokens_generated_total, "
                    "speculative-decode spec_* counters and acceptance "
                    "histogram, prefix_cache_* hit/publish/eviction "
                    "counters, the decode_batch_occupancy histogram, "
                    "disaggregated sealed-block transfer counters "
                    "(kv_xfer_*, serving_handoff_fallback_total), live "
                    "session-migration counters and timing (kv_migrate_*"
                    ", migration_ms, client_resume/*follow/*dup) and the "
                    "kv_pool_occupancy / prefix_cache_hit_rate gauges")
    ap.add_argument("--tracing", action="store_true", dest="tracing_only",
                    help="show only distributed-tracing health metrics: "
                    "tracing_records_total{kind}, "
                    "tracing_dropped_total and "
                    "tracing_flightrec_dumps_total{reason} "
                    "(core/tracing.py)")
    ap.add_argument("--checkpoint", action="store_true", dest="ckpt_only",
                    help="show only checkpoint I/O metrics: the "
                    "checkpoint_save_stall_ms vs checkpoint_write_ms "
                    "split, restore timings/sources "
                    "(checkpoint_restore_source_total{source}), overlap "
                    "drops, temp-GC sweeps, and the executor's D2H "
                    "snapshot histogram (io.py + core/executor.py)")
    ap.add_argument("--lint", action="store_true", dest="lint_only",
                    help="show only static-checker metrics: per-rule "
                    "static_check_warnings counters, the whole-world "
                    "verifier's static_check_world_* run/finding counters "
                    "and rank/peak-HBM gauges, and the concurrency "
                    "lint's static_check_concurrency_total / "
                    "static_check_waivers_total per-rule counters")
    args = ap.parse_args(argv)

    if args.json_path:
        with open(args.json_path) as f:
            snap = json.load(f)
    elif args.fleet_doc:
        from paddle_tpu import telemetry
        from paddle_tpu.serving.fleetmon import FLEET_RPC_KEY

        snap = telemetry.scrape(args.endpoint, timeout=args.timeout,
                                key=FLEET_RPC_KEY)
    else:
        from paddle_tpu import telemetry

        snap = telemetry.scrape(args.endpoint, timeout=args.timeout)

    if args.fleet_doc:
        if args.raw:
            json.dump(snap, sys.stdout, indent=1)
            sys.stdout.write("\n")
        else:
            render_fleet(snap)
        return 0

    if args.elastic:
        snap = _filter_snap(snap, "elastic_")
    if args.collective:
        # str.startswith takes a tuple: both metric families in one pass
        snap = _filter_snap(snap, ("collective_", "zero1_"))
    if args.compile_only:
        snap = _filter_snap(snap, ("compile_cache_", "executor_compile",
                                   "executor_xla_", "executor_cache_",
                                   "executor_aot_", "executor_warmup"))
    if args.kernels_only:
        snap = _filter_snap(snap, "pallas_kernel_")
    if args.serving_only:
        # serving_* plus the PR 16 control-plane families (autoscaler,
        # rollout gate, client shed retries, injected wire faults)
        snap = _filter_snap(snap, ("serving_", "autoscale_", "rollout_",
                                   "client_shed_", "fault_injected_"))
    if args.decode_only:
        snap = _filter_snap(snap, ("kv_block", "kv_cache_",
                                   "kv_blocks_in_use", "serving_decode_",
                                   "serving_tokens_", "serving_abort_",
                                   "decode_batch_occupancy", "spec_",
                                   "prefix_cache_", "kv_xfer_", "kv_pool_",
                                   "serving_handoff_", "kv_migrate_",
                                   "migration_ms", "client_resume_",
                                   "client_migrate_", "client_stream_"))
    if args.tracing_only:
        snap = _filter_snap(snap, "tracing_")
    if args.ckpt_only:
        # checkpoint_* covers save/write/restore/overlap/tmp-GC; the D2H
        # snapshot cost lives under the executor family
        snap = _filter_snap(snap, ("checkpoint_", "executor_snapshot"))
    if args.lint_only:
        # covers static_check_warnings{rule=}, static_check_world_*, and
        # the threadlint static_check_concurrency_total /
        # static_check_waivers_total families
        snap = _filter_snap(snap, "static_check")

    if args.raw:
        json.dump(snap, sys.stdout, indent=1)
        sys.stdout.write("\n")
    elif args.prom:
        from paddle_tpu import telemetry

        sys.stdout.write(telemetry.prometheus_text(snap))
    else:
        render_table(snap)
    return 0


if __name__ == "__main__":
    sys.exit(main())
