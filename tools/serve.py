"""Continuous-batching inference server entry point (serving/ subsystem).

Usage:
    # single replica, two models, AOT-compile the buckets, serve
    python tools/serve.py --model fc=/path/to/model \
        --model bert=/path/to/bert --port 9000 --buckets 1,4,16 \
        --cache-dir /tmp/cc

    # CI-style: compile every (model, bucket) into the cache and exit
    python tools/serve.py --model fc=/path --prewarm-only --cache-dir /tmp/cc

    # elastic fleet of N replicas: run once per replica with the SAME
    # --fleet list; the coordinator (lowest live rank) maintains
    # --endpoints-file for client failover
    python tools/serve.py --model fc=/path --rank 0 \
        --fleet 127.0.0.1:9000,127.0.0.1:9001 \
        --endpoints-file /tmp/eps.json

    # elastic fleet + autoscaling: the coordinator watches queue depth /
    # shed rate and forks prewarmed standbys into dead --fleet slots on
    # sustained pressure, retires the highest rank on sustained idle
    python tools/serve.py --model fc=/path --rank 0 \
        --fleet 127.0.0.1:9000,127.0.0.1:9001 --cache-dir /tmp/cc \
        --endpoints-file /tmp/eps.json --autoscale --max-replicas 2

    # disaggregated prefill/decode fleet: role column parallels --fleet;
    # prefill replicas stream sealed KV blocks to decode replicas and
    # clients route __generate__ by the published roles
    python tools/serve.py --model toy=/tmp/dec --rank 0 \
        --fleet 127.0.0.1:9000,127.0.0.1:9001,127.0.0.1:9002,127.0.0.1:9003 \
        --roles prefill,prefill,decode,decode --endpoints-file /tmp/eps.json

    # helper for smoke tests: save a tiny fc inference model and exit
    python tools/serve.py --save-demo-model /tmp/model

    # autoregressive decode serving: a --model DIR holding a
    # save_decoder() bundle (decoder.json + params.npz) is routed to the
    # paged-KV DecodeEngine instead; helper to create one:
    python tools/serve.py --save-demo-decoder /tmp/dec
    python tools/serve.py --model toy=/tmp/dec --decode-buckets 4,8

The prewarm manifest prints one JSON line (PREWARM {...}) so harnesses
can assert every bucket exists before traffic starts; "READY port=N" on
stdout marks the server accepting requests.
"""

import argparse
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def save_demo_model(dirname, in_dim=8, out_dim=4):
    """Tiny fc softmax model via save_inference_model (smoke tests)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[in_dim])
        h = fluid.layers.fc(x, 16, act="relu")
        out = fluid.layers.fc(h, out_dim, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=main)
    return dirname


def demo_decoder_config(path):
    """A DecoderConfig from a file: a bundle's ``decoder.json`` (the
    config's own keys), or a serving configuration of the benchmark
    (``benchmark/configs/<name>.json``), read through the model module it
    names at its ``tiny`` sizes: a demo bundle is written to disk, and the
    published widths are gigabytes."""
    from benchmark.run import load_module, with_tiny
    from paddle_tpu.serving.decode_model import DecoderConfig

    with open(path) as fp:
        data = json.load(fp)
    if "model" not in data:
        return DecoderConfig(**data)
    return load_module("models", data["model"]).decoder_config(
        with_tiny(data, True))


def save_demo_decoder(dirname, vocab=31, layers=2, heads=2, head_dim=8,
                      max_seq=48, seed=7, config=None):
    """Tiny decode model via serving.decode_model.save_decoder, bundled
    with a first-layer-truncation draft so FLAGS_speculative_k > 0 can
    speculate out of the box.  ``config`` (``demo_decoder_config``) names
    the architecture and sizes instead of the six numbers."""
    from paddle_tpu.serving.decode_model import (DecoderConfig,
                                                 init_decoder_params,
                                                 save_decoder,
                                                 truncate_decoder)

    cfg = demo_decoder_config(config) if config else DecoderConfig(
        vocab=vocab, layers=layers, heads=heads, head_dim=head_dim,
        max_seq=max_seq)
    params = init_decoder_params(cfg, seed=seed)
    return save_decoder(dirname, cfg, params,
                        draft=truncate_decoder(cfg, params, layers=1))


def _open_device(rank):
    """Open this replica's JAX device before anything else needs it.  A
    chip belongs to one process: a replica started beside a process that
    already holds the chip (a standby forked by a coordinator that serves
    from it, for one) fails here, or hangs inside the driver — so say
    what is going on first, and turn a hang into an exit."""
    import faulthandler

    import jax

    print("serve[rank %d]: opening the JAX device (replicas on one host are "
          "one process per chip; a chip another process holds cannot be "
          "opened)" % rank, file=sys.stderr, flush=True)
    faulthandler.dump_traceback_later(180, exit=True)
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        raise SystemExit(
            "serve[rank %d]: no JAX device for this replica: %s" % (rank, e))
    finally:
        faulthandler.cancel_dump_traceback_later()
    print("serve[rank %d]: serving from %s (%s)"
          % (rank, dev, dev.device_kind), file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", action="append", default=[],
                    metavar="NAME=DIR",
                    help="register a model (repeatable): serving name = "
                    "save_inference_model directory")
    ap.add_argument("--port", type=int, default=0,
                    help="RPC port (0 = ephemeral; printed on READY)")
    ap.add_argument("--buckets", default=None,
                    help="batch buckets, e.g. 1,4,16,64 "
                    "(default FLAGS_serving_buckets)")
    ap.add_argument("--cache-dir", default=None,
                    help="compile cache for AOT bucket artifacts "
                    "(JAX_COMPILATION_CACHE_DIR, when set, wins)")
    ap.add_argument("--prewarm-only", action="store_true",
                    help="compile every (model, bucket), print the "
                    "manifest, exit")
    ap.add_argument("--rank", type=int, default=0,
                    help="this replica's rank in --fleet")
    ap.add_argument("--fleet", default=None,
                    help="comma list of ALL replica endpoints (host:port); "
                    "enables fleet membership")
    ap.add_argument("--endpoints-file", default=None,
                    help="coordinator-maintained live-endpoints file "
                    "(client failover)")
    ap.add_argument("--save-demo-model", metavar="DIR", default=None,
                    help="write a tiny fc inference model to DIR and exit")
    ap.add_argument("--save-demo-decoder", metavar="DIR", default=None,
                    help="write a tiny autoregressive decoder to DIR "
                    "and exit")
    ap.add_argument("--demo-decoder-config", metavar="FILE", default=None,
                    help="with --save-demo-decoder: the decoder's "
                    "architecture and sizes from a decoder.json or a "
                    "benchmark configuration file (its tiny sizes)")
    ap.add_argument("--decode-buckets", default=None,
                    help="decode lane buckets, e.g. 4,8 "
                    "(default FLAGS_serving_decode_buckets)")
    ap.add_argument("--decode-mode", default=None,
                    choices=("token", "request"),
                    help="token-level continuous batching (default) or "
                    "the request-level baseline")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV pool size in blocks "
                    "(default FLAGS_kv_cache_blocks / HBM budget)")
    ap.add_argument("--speculative-k", type=int, default=None,
                    help="draft-model speculation depth for decode "
                    "models with a bundled draft (default "
                    "FLAGS_speculative_k; 0 = off)")
    ap.add_argument("--role", default=None,
                    choices=("serve", "prefill", "decode"),
                    help="disaggregated serving role for THIS replica "
                    "(default: this rank's --roles column entry, else "
                    "monolith \"serve\")")
    ap.add_argument("--roles", default=None,
                    help="comma role column parallel to --fleet "
                    "(serve|prefill|decode per slot); the coordinator "
                    "publishes it in the endpoints file so clients "
                    "route __generate__ to prefill replicas")
    ap.add_argument("--decode-peers", default=None,
                    help="comma list of decode-role endpoints a prefill "
                    "replica streams sealed KV blocks to when no fleet "
                    "role column is in play (tests / static pairs)")
    ap.add_argument("--autoscale", action="store_true",
                    help="coordinator only: watch queue depth / shed "
                    "rate and launch prewarmed standby replicas into "
                    "dead --fleet slots on sustained pressure, drain + "
                    "retire the highest rank on sustained idle")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscaler floor (default "
                    "FLAGS_serving_min_replicas)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling (default "
                    "FLAGS_serving_max_replicas; also clamped by the "
                    "--fleet slot count)")
    args = ap.parse_args(argv)

    if args.save_demo_model:
        print("saved demo model:", save_demo_model(args.save_demo_model))
        return 0
    if args.save_demo_decoder:
        print("saved demo decoder:",
              save_demo_decoder(args.save_demo_decoder,
                                config=args.demo_decoder_config))
        return 0

    from paddle_tpu.core import tracing
    from paddle_tpu.serving import ServingEngine, ServingFleet, ServingServer

    _open_device(args.rank)
    if args.cache_dir:
        from paddle_tpu.core import compile_cache

        compile_cache.place(args.cache_dir)
    # names this replica's track in the merged trace_view.py output
    tracing.set_process_name("serving-replica-%d" % args.rank)
    if not args.model:
        ap.error("at least one --model NAME=DIR is required")

    from paddle_tpu.serving import DecodeEngine
    from paddle_tpu.serving.decode_model import is_decoder_dir

    engine = ServingEngine(buckets=args.buckets)
    decode_engine = None
    for spec in args.model:
        name, _, dirname = spec.partition("=")
        if not dirname:
            ap.error("--model wants NAME=DIR, got %r" % spec)
        if is_decoder_dir(dirname):
            if decode_engine is None:
                decode_engine = DecodeEngine(buckets=args.decode_buckets,
                                             mode=args.decode_mode)
            decode_engine.add_model(name, dirname,
                                    kv_blocks=args.kv_blocks,
                                    speculative_k=args.speculative_k)
        else:
            engine.add_model(name, dirname)

    manifest = engine.prewarm()
    if decode_engine is not None:
        manifest.update(decode_engine.prewarm())
    print("PREWARM " + json.dumps(manifest), flush=True)
    if args.prewarm_only:
        return 0

    if args.fleet:
        endpoints = [e.strip() for e in args.fleet.split(",") if e.strip()]
        port = args.port or int(endpoints[args.rank].rsplit(":", 1)[1])
    else:
        endpoints, port = None, args.port

    roles = None
    if args.roles:
        roles = [r.strip() for r in args.roles.split(",") if r.strip()]
        if endpoints is None or len(roles) != len(endpoints):
            ap.error("--roles must parallel --fleet")
    role = args.role or (roles[args.rank] if roles else None)
    decode_peers = [e.strip() for e in (args.decode_peers or "").split(",")
                    if e.strip()]
    server = ServingServer(engine, port=port, rank=args.rank,
                           decode_engine=decode_engine, role=role,
                           decode_peers=decode_peers).start()
    fleet = None
    if endpoints:
        fleet = ServingFleet(args.rank, endpoints, server,
                             endpoints_file=args.endpoints_file,
                             roles=roles).start()

    # rollout controller: serves __rollout_ctl__ admin commands and runs
    # the canary metrics gate (auto-rollback); with a fleet, state
    # changes broadcast to peers and ride the epoch-bumped endpoints file
    from paddle_tpu.serving import RolloutController

    server.rollout = RolloutController(server, fleet).start()

    # fleet observability plane (PR 18): scrape every live replica each
    # tick, merge histograms / window rates / evaluate burn-rate SLOs,
    # republish the merged doc under __fleet__ on the coordinator.  The
    # autoscaler closures below prefer its fleet-windowed view.
    monitor = None
    from paddle_tpu.core import telemetry as _tmon

    if _tmon.enabled() and (fleet is not None or args.endpoints_file):
        from paddle_tpu.serving import FleetMonitor

        monitor = FleetMonitor(server=server, fleet=fleet,
                               endpoints_file=args.endpoints_file).start()
    server.fleetmon = monitor

    done = threading.Event()
    # a drained __retire__ order exits the process like a SIGTERM would
    server.on_retire = done.set

    scalers = []
    if args.autoscale and fleet is not None:
        from paddle_tpu import flags as _flags
        from paddle_tpu.core import telemetry as _tm
        from paddle_tpu.serving import AutoScaler

        def child_argv(rank):
            """Re-exec this invocation for a standby slot (the child
            shares --cache-dir, so its prewarm is restore-dominated);
            the child never autoscales itself and takes its role from
            its --roles column slot."""
            out, it = [sys.executable, os.path.abspath(__file__)], \
                iter(sys.argv[1:])
            for a in it:
                if a == "--autoscale":
                    continue
                if a in ("--rank", "--min-replicas", "--max-replicas",
                         "--role"):
                    next(it, None)
                    continue
                out.append(a)
            return out + ["--rank", str(rank)]

        def local_depth():
            depth = len(engine._queue)
            if decode_engine is not None:
                depth += len(decode_engine._waiting)
            return depth

        def scale_up_for(want_role):
            def fn():
                import subprocess

                if not fleet.is_coordinator():
                    return
                dead = [r for r in range(len(fleet.endpoints))
                        if r not in fleet.live
                        and (want_role is None
                             or fleet.role_of(r) == want_role)]
                if not dead:
                    return
                rank = dead[0]
                fleet.notice_relaunch(rank)
                subprocess.Popen(child_argv(rank), start_new_session=True)
            return fn

        def scale_down_for(want_role):
            def fn():
                if not fleet.is_coordinator():
                    return
                cands = [r for r in sorted(fleet.live)
                         if r != fleet.rank
                         and (want_role is None
                              or fleet.role_of(r) == want_role)]
                if cands:
                    fleet.retire(cands[-1])
            return fn

        if roles is None:
            def metrics():
                # fleet-windowed view when the monitor has a doc (queue
                # depth summed across replicas, shed/s over the rate
                # window); local instants only until its first tick
                if monitor is not None:
                    m = monitor.autoscale_metrics()
                    if m is not None and m.get("replicas_up"):
                        return m
                return {"queue_depth": local_depth(),
                        "shed_total": _tm.counter_total(
                            "serving_shed_total")}

            scalers.append(AutoScaler(
                metrics, scale_up_for(None), scale_down_for(None),
                replicas_fn=lambda: len(fleet.live),
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas).start())
        else:
            # disaggregated fleet: one controller per role, each with a
            # role-specific pressure signal — prefill chases admission
            # queue depth (TTFT pressure), decode chases KV-pool
            # occupancy (ITL pressure).  Peer replicas are scraped over
            # __metrics__; this replica contributes locally.
            def role_metrics(want_role):
                def fn():
                    if monitor is not None:
                        m = monitor.autoscale_metrics(want_role)
                        if m is not None and m.get("replicas_up"):
                            return m
                    depth = occ = shed = 0.0
                    for ep in fleet.live_role_endpoints(want_role):
                        if ep == fleet.endpoints[fleet.rank]:
                            continue
                        try:
                            snap = _tm.scrape(ep, timeout=2.0)
                        except Exception:
                            continue
                        g = snap.get("gauges", {})
                        depth += max(
                            (v for k, v in g.items()
                             if k.startswith("serving_queue_depth")),
                            default=0.0)
                        occ = max(occ, max(
                            (v for k, v in g.items()
                             if k.startswith("kv_pool_occupancy")),
                            default=0.0))
                        shed += sum(
                            v for k, v in
                            snap.get("counters", {}).items()
                            if k.startswith("serving_shed_total"))
                    if fleet.role_of(fleet.rank) == want_role:
                        depth += local_depth()
                        shed += _tm.counter_total("serving_shed_total")
                        if decode_engine is not None:
                            for m in decode_engine._models.values():
                                alloc = m.cache.allocator
                                occ = max(occ, alloc.in_use /
                                          (float(alloc.capacity) or 1.0))
                    return {"queue_depth": depth, "shed_total": shed,
                            "kv_occupancy": occ}
                return fn

            up_depth = float(_flags.flag("serving_scale_up_depth"))

            def prefill_pressure(m):
                d = float(m.get("queue_depth", 0.0))
                return d >= up_depth, d <= 0.0

            def decode_pressure(m):
                occ = float(m.get("kv_occupancy", 0.0))
                return occ >= 0.85, occ <= 0.30

            for want_role, pfn in (("prefill", prefill_pressure),
                                   ("decode", decode_pressure)):
                if want_role not in roles:
                    continue
                scalers.append(AutoScaler(
                    role_metrics(want_role), scale_up_for(want_role),
                    scale_down_for(want_role),
                    replicas_fn=(lambda wr=want_role:
                                 len(fleet.live_role_ranks(wr))),
                    min_replicas=args.min_replicas,
                    max_replicas=args.max_replicas,
                    pressure_fn=pfn).start())

    print("READY port=%d" % server.port, flush=True)

    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    for scaler in scalers:
        scaler.stop()
    if monitor is not None:
        monitor.stop()
    if fleet is not None:
        fleet.stop()
    server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
