"""Runtime flags (analog of the reference's gflags surface,
paddle/fluid/platform/flags.cc + __bootstrap__ env reading in
python/paddle/fluid/__init__.py:132-220 + the pybind
global_value_getter_setter).

Flags whose semantics dissolve into XLA/PJRT (allocator strategy, GPU memory
fractions, eager deletion thresholds) are accepted as inert for API
compatibility; behavioral ones (check_nan_inf, benchmark) are honored by the
executor/dygraph paths.
"""

import os

__all__ = ["set_flags", "get_flags"]

_DEFAULTS = {
    # honored
    "FLAGS_check_nan_inf": False,       # flags.cc:44 — scan outputs for NaN/Inf
    # ghost-batch BN statistics: estimate batch stats from every k-th
    # sample (1 = exact reference semantics); read at layer-build time
    "FLAGS_bn_stat_subsample": 1,
    # capacity of tensor arrays carried through data-dependent while loops
    # (XLA needs a static bound; reference while_op grows arrays freely)
    "FLAGS_tensor_array_max_len": 256,
    # horizontal optimizer-update fusion (reference BuildStrategy
    # fuse_all_optimizer_ops / ir/fuse_optimizer_ops_pass.cc): coalesce
    # the per-parameter sgd/momentum/adam ops of the vectors (biases,
    # LayerNorm and BN scales) into one flat update — round 3: ~46 ms of a
    # 211 ms ResNet-50 step was the launches of 315 tiny per-weight
    # updates.  Matrices and conv kernels keep their plain ops: copying
    # tiled arrays into a flat buffer cost BERT-base 22 ms of a 228 ms
    # step on the chip (PR 54; ir.py FuseOptimizerOpsPass.MAX_FUSED_RANK)
    "FLAGS_fuse_optimizer_ops": True,
    # per-request PS RPC deadline in MILLISECONDS (reference units —
    # paddle/fluid/operators/distributed/ FLAGS_rpc_deadline, default
    # 180000): a pserver that hangs mid-round raises ConnectionError on
    # the trainer instead of blocking its recv() forever.  <=0 disables.
    "FLAGS_rpc_deadline": 180000,
    # bounded reconnect-and-retry on RPC deadline/transport failures
    # (reference FLAGS_rpc_retry_times, grpc_client.cc): each retry opens
    # a FRESH connection (a timed-out socket may be mid-frame) after an
    # exponential backoff with jitter.  0 restores poison-on-first-failure.
    "FLAGS_rpc_retry_times": 3,
    # fault-injection spec "point:kind:prob[:count[:skip]];..." checked by
    # utils/fault_injection.maybe_fail at named runtime fault points
    # (rpc.send, rpc.get, ps.round, ckpt.write).  Empty = disarmed.
    "FLAGS_fault_spec": "",
    # pserver-side worker liveness timeout in SECONDS
    # (heart_beat_monitor.h): a trainer silent this long is EVICTED from
    # the sync quorum (rounds re-quorum on survivors) until it re-contacts.
    "FLAGS_worker_hb_timeout": 60.0,
    # layout-matched persistent params (core/lowering.py param carry): AMP
    # programs pin eligible weights in their bf16 compute dtype ACROSS steps
    # (the scope keeps the f32 master for the optimizer), so the compiled
    # step stops re-materializing f32->bf16 converts + layout copies of
    # ~85 MB of encoder weights every iteration.  Safe default-on: carry
    # engages only where it is bitwise-identical to the per-step cast
    # (single-consumer matmul/conv weights, single-process, no mesh).
    "FLAGS_layout_match_params": True,
    # unified runtime telemetry (core/telemetry.py): process-wide metrics
    # registry (counters/gauges/histograms) + JSONL step-event log.  Zero
    # cost when off (every mutator early-returns on this flag, the
    # profiler.is_profiler_enabled guard pattern).
    "FLAGS_telemetry": False,
    # where telemetry streams steps.jsonl and dump() writes metrics.json /
    # metrics.prom; empty = in-memory only (snapshot()/__metrics__ RPC
    # still work, nothing touches disk)
    "FLAGS_telemetry_dir": "",
    # size bound (bytes) for the append-only JSONL streams under
    # FLAGS_telemetry_dir (steps.jsonl + the tracing trace-<pid>.jsonl):
    # when a stream exceeds it, the file is rotated to <name>.1 (one
    # previous generation kept) so long fleet soaks stay disk-bounded.
    # <=0 disables rotation.
    "FLAGS_telemetry_max_bytes": 256 << 20,
    # distributed tracing (core/tracing.py): cross-process request/step
    # spans (trace_id/span_id/parent_id, W3C-style traceparent propagated
    # through the serving meta + RPC SEND frames) streamed as JSONL
    # (trace-<pid>.jsonl under FLAGS_telemetry_dir) and merged by
    # tools/trace_view.py into one Chrome/Perfetto trace.json.  Zero cost
    # when off: every span call early-returns on this one flag read, and
    # no trace file is ever created.
    "FLAGS_tracing": False,
    # static Program verifier (core/analysis.py): off | warn | error.
    # "warn" (default) runs the four rule families (well-formedness,
    # type/shape flow, donation/aliasing, distributed lint) on every
    # executor cache-miss compile and post-transpile, logging a
    # ProgramVerifyWarning + counting static_check_warnings into telemetry;
    # "error" raises one readable ProgramVerificationError report instead
    # of an opaque XLA traceback; "off" costs a single flag read
    "FLAGS_static_check": "warn",
    # HBM footprint auditor (core/memory_audit.py): after each compile, log
    # the executable's memory_analysis (arg/output/temp/alias bytes) with
    # per-variable attribution of the argument footprint.  Diagnostic; adds
    # one extra AOT compile per cache entry, so default-off.
    "FLAGS_hbm_audit": False,
    # per-replica HBM budget (bytes) for the static peak estimator
    # (core/world_analysis.py MEM003): when > 0, a predicted peak above
    # the budget becomes a MEM003 diagnostic pre-compile (error mode
    # raises) instead of an on-chip band-edge trip.  0 disables the gate;
    # MEM001 (the estimate itself) is always reported at info level.
    "FLAGS_hbm_budget_bytes": 0,
    # deterministic collective reduction order (ops/collective.py
    # c_allreduce_sum): replace lax.psum with all_gather + a fixed-order
    # pairwise tree-reduce, so the cross-rank gradient sum reassociates
    # identically regardless of ring schedule — the dp-sharded
    # reduction-reassociation item (ROADMAP; test_dp4_tp2 step-2 drift).
    # Costs gather bandwidth over psum, so default off.
    "FLAGS_deterministic_reduction": False,
    # two-tier persistent compilation cache (core/compile_cache.py).
    # Non-empty = enabled: <dir>/xla holds JAX's native persistent XLA
    # cache (jax_compilation_cache_dir, tier A — dedupes identical HLO
    # even across different programs); <dir>/aot holds framework-level
    # serialized executables keyed by (program content hash, trace-flag
    # fingerprint, collective world, feed shapes/dtypes) (tier B — a hit
    # skips trace + lower + compile entirely).  Empty = both tiers off —
    # unless JAX_COMPILATION_CACHE_DIR is set: the machine placed the
    # cache, both tiers live under it and this flag is not consulted
    # (compile_cache.cache_dir is the one resolver).
    "FLAGS_compile_cache_dir": "",
    # tier-B size cap in bytes; least-recently-used entries are evicted
    # after each store once the total exceeds it.  <=0 disables eviction.
    "FLAGS_compile_cache_max_bytes": 1 << 30,
    # elastic standby worlds (distributed/elastic.py): after each epoch
    # adoption, a background thread pre-transpiles + pre-verifies views
    # for worlds N-1 and N-2 (every single-member loss, plus the
    # two-member loss) and pre-compiles them into the tier-B cache, so a
    # re-quorum becomes cache-restore + checkpoint-restore.  0 disables.
    "FLAGS_elastic_standby": 2,
    # collective gradient-exchange strategy (transpiler/collective.py):
    # "allreduce" = replicated GradAllReduce (every rank updates every
    # param); "zero1" = ShardedGradAllReduce, the ZeRO-1 weight-update
    # sharding pass (arXiv 2004.13336): reduce-scatter the gradients,
    # each rank runs the optimizer only on its 1/nranks param shard
    # (optimizer-state HBM drops by nranks), then all-gather the updated
    # params.  Params whose dim 0 does not divide the world, or whose
    # optimizer is not elementwise (lamb/lars need global norms), fall
    # back per-param to the replicated update.
    "FLAGS_collective_mode": "allreduce",
    # wire dtype for the gradient exchange (EQuARX, arXiv 2506.17615):
    # f32 = bitwise-parity escape hatch (plain psum / psum_scatter);
    # bf16 / int8 = bucketed per-tensor-scale quantization before the
    # wire, dequant after.  int8 cuts bytes-on-ICI per step to ~0.25x of
    # the f32 ring all-reduce (payload + per-bucket f32 scales).
    "FLAGS_allreduce_dtype": "f32",
    # quantization bucket (elements) for FLAGS_allreduce_dtype=int8:
    # one f32 max-abs scale per bucket per destination rank.  Smaller =
    # tighter scales (less quant error) but more scale bytes on the wire.
    "FLAGS_allreduce_quant_bucket": 512,
    # async snapshot-to-host checkpointing (io.CheckpointManager): save()
    # costs the step path ONE D2H host snapshot; serialization, crc32 and
    # the atomic _SUCCESS-sealed directory write run on a background
    # writer thread (at most one snapshot in flight — a save arriving
    # while one is writing is dropped LOUDLY via
    # checkpoint_save_overlap_total + a warning).  The telemetry split
    # checkpoint_save_stall_ms (foreground) vs checkpoint_write_ms
    # (background) proves the stall left the step path.
    "FLAGS_checkpoint_async": False,
    # shard-aware checkpoints under FLAGS_collective_mode=zero1: each
    # rank writes only its own dim-0 slice of the sharded optimizer
    # state (__shard_<r>of<w>__.npz; the _SUCCESS manifest records the
    # layout exported by the transpiler), rank 0 writes the replicated
    # vars once and seals.  restore() reassembles from whatever world
    # the checkpoint was written by, so world changes re-shard for free.
    # Off = every saver writes the full state (pre-sharding format,
    # still readable by restore).
    "FLAGS_checkpoint_sharded": True,
    # peer-to-peer elastic restore (distributed/elastic.py): on
    # re-quorum the adopted view prefers live post-step state held by
    # survivors — their own scope, or an RPC fetch over the control
    # fabric for a rejoining member — over re-reading the filesystem;
    # latest_valid() remains the fallback when no survivor has state
    # (checkpoint_restore_source_total{peer|fs}).  The COORDINATOR's
    # flag decides for the whole world (the chosen resume step rides
    # the published view), so members can never disagree on where to
    # resume.
    "FLAGS_checkpoint_p2p_restore": True,
    # elastic collective re-quorum (distributed/elastic.py): member
    # heartbeat period over the PADDLE_COORDINATOR control channel, and how
    # long a member may stay silent before the quorum evicts it and the
    # survivors re-form the world (seconds)
    "FLAGS_elastic_hb_interval": 0.5,
    "FLAGS_elastic_hb_timeout": 5.0,
    # control-channel port = member endpoint port + this offset (the member
    # endpoint port itself belongs to jax.distributed / the data plane)
    "FLAGS_elastic_ctrl_offset": 1000,
    # each quorum epoch moves the jax.distributed coordinator to
    # base_port + epoch * stride (the old world's sockets are parked, not
    # closed — see elastic.py on why tearing them down is fatal)
    "FLAGS_elastic_port_stride": 29,
    # continuous-batching inference serving (paddle_tpu/serving/):
    # shape buckets the batcher pads request batches to — every bucket is
    # AOT-compiled at startup (Executor.warmup against
    # FLAGS_compile_cache_dir) so no request ever pays an XLA compile
    "FLAGS_serving_buckets": "1,4,16,64",
    # admission-queue depth cap; beyond it requests are shed with a
    # retry-after instead of queued
    "FLAGS_serving_max_queue": 256,
    # default per-tenant deadline budget (ms): admission sheds a request
    # when projected queue wait already exceeds it
    "FLAGS_serving_deadline_ms": 2000.0,
    # how long the batcher waits to coalesce more same-model requests
    # toward the next larger bucket before dispatching (ms)
    "FLAGS_serving_batch_window_ms": 2.0,
    # serving-fleet replica heartbeat period / silence-eviction timeout
    # (seconds) — the serving analog of the elastic quorum knobs; the
    # fleet coordinator rewrites the endpoints file when a replica dies
    "FLAGS_serving_hb_interval": 0.3,
    "FLAGS_serving_hb_timeout": 2.0,
    # where the fleet coordinator publishes the live endpoints JSON
    # (clients re-read it to fail over); empty = no file
    "FLAGS_serving_endpoints_file": "",
    # -- serving control plane (tiers / autoscale / rollout) -----------------
    # SLO tiers: "tier:weight" comma list.  A request's tier scales its
    # admission deadline budget (shed when projected wait > deadline x
    # weight) and orders both batch assembly and queue-full eviction, so
    # under overload the lowest-weight tier sheds first.  Requests with
    # no tier get weight 1.0 (pre-tier behavior); an unknown tier name
    # defensively gets the lowest configured weight.
    "FLAGS_serving_tier_weights": "paid:1.0,free:0.45,batch:0.15",
    # ServingClient: how many times a shed reply is retried client-side
    # after its retry_after_ms hint (with backoff+jitter) before the shed
    # is surfaced to the caller; 0 restores the old return-immediately
    "FLAGS_serving_client_shed_retries": 2,
    # replica autoscaler (serving/fleet.py AutoScaler, tools/serve.py
    # --autoscale): poll period (s); consecutive pressure/idle polls
    # before scaling (hysteresis); post-action cooldown polls; the mean
    # queue depth that counts as pressure; and the replica count clamp
    "FLAGS_serving_autoscale_interval": 0.5,
    "FLAGS_serving_scale_up_ticks": 3,
    "FLAGS_serving_scale_down_ticks": 8,
    "FLAGS_serving_autoscale_cooldown": 6,
    "FLAGS_serving_scale_up_depth": 4.0,
    "FLAGS_serving_min_replicas": 1,
    "FLAGS_serving_max_replicas": 4,
    # versioned rollout (serving/rollout.py): default canary traffic
    # fraction, and the auto-rollback gate — trips when the canary's
    # phase p99 exceeds ratio x the baseline version's, or its per-
    # request error rate exceeds the cap, judged only after min_samples
    # canary requests have completed
    "FLAGS_serving_canary_fraction": 0.25,
    "FLAGS_rollout_gate_p99_ratio": 2.0,
    "FLAGS_rollout_gate_error_rate": 0.05,
    "FLAGS_rollout_gate_min_samples": 20,
    # -- fleet observability (serving/fleetmon.py FleetMonitor) --------------
    # scrape/aggregate cadence (s) and the trailing horizon (s) used for
    # windowed rates derived from the per-process time-series ring
    # (per-tier shed/s on the 1s republish, autoscaler fleet rates)
    "FLAGS_serving_fleetmon_interval": 1.0,
    "FLAGS_serving_rate_window": 30.0,
    # burn-rate SLO rules: ";"-separated "name:metric:pQQ:objective_ms".
    # metric is a histogram flat key or prefix (label sets merge), e.g.
    # "paid_server:server_ms{tier=paid}:p99:500" alerts when the paid
    # tier's windowed server-side p99 burns past 500 ms.  Each rule is
    # evaluated over a fast AND a slow trailing window (multi-window
    # burn-rate alerting): the alert FIRES when both windows' burn
    # (windowed pQQ / objective) reach the threshold, and CLEARS with
    # hysteresis once the fast window drops below threshold x clear_ratio
    "FLAGS_serving_slo_rules":
        "paid_server:server_ms{tier=paid}:p99:500;decode_itl:itl_ms:p99:250",
    "FLAGS_serving_slo_fast_window": 60.0,
    "FLAGS_serving_slo_slow_window": 900.0,
    "FLAGS_serving_slo_burn_threshold": 1.0,
    "FLAGS_serving_slo_clear_ratio": 0.5,
    # bounded length of the in-process telemetry time-series ring (one
    # sample per publisher tick; 1024 ~= 17 min of 1s samples)
    "FLAGS_telemetry_series_cap": 1024,
    # -- autoregressive decode serving (serving/kv_cache.py + DecodeEngine) --
    # decode-lane buckets: the running token batch pads to the smallest
    # bucket that fits the live sequences; one decode-step executable is
    # AOT-compiled per bucket at prewarm, so mixed-length traffic never
    # triggers a runtime XLA compile
    "FLAGS_serving_decode_buckets": "4,8",
    # "token" = continuous batching at token granularity (sequences
    # join/leave the running batch at every decode step); "request" =
    # request-level static batching (the batch drains fully before new
    # sequences join) — kept as the loadgen comparison baseline
    "FLAGS_serving_decode_mode": "token",
    # paged KV-cache geometry: tokens per block, and how many blocks the
    # engine owns per model.  0 blocks = size from FLAGS_hbm_budget_bytes
    # (kv_cache.plan_num_blocks), falling back to 64 when no budget is set.
    "FLAGS_kv_block_size": 16,
    "FLAGS_kv_cache_blocks": 0,
    # KV-block residency dtype: f32 (bitwise parity with the unpaged
    # reference) or int8 (quantize-for-the-residency, EQuARX idiom: per
    # (block, position, head) max-abs scales; ~4x the f32 capacity per
    # byte of HBM at a small accuracy cost)
    "FLAGS_kv_cache_dtype": "f32",
    # draft-model speculative decoding on the paged decode path
    # (DecodeEngine): 0 = off; k > 0 runs the model's bundled draft
    # decoder (save_decoder(draft=...) / <model_dir>/draft) k tokens
    # ahead per sequence through its own paged KV lanes, then verifies
    # all k+1 positions with ONE bucketed multi-token target step.
    # Greedy verification accepts the longest draft prefix matching the
    # target argmax chain, so output stays bitwise-equal to k=0;
    # rollback is free (context_lens truncation + same-iteration block
    # free).  Requires a draft bundle — a model without one decodes
    # non-speculatively regardless of k.
    "FLAGS_speculative_k": 0,
    # content-addressed KV prefix caching over the paged pool: admission
    # matches each prompt's hash chain against sealed full-prompt blocks,
    # seeds the block table with the shared prefix, and prefill computes
    # only the uncached tail.  Zero-ref cached blocks park in an LRU
    # evictable pool (reclaimed on demand), so residency is free under
    # pressure; outputs stay bitwise-identical cache-on vs cache-off.
    "FLAGS_prefix_cache": True,
    # live decode-session migration (serving/migrate.py): on, the engine
    # publishes each COMPLETED decode-history block into the prefix index
    # under the full-history hash chain (prompt ++ emitted tokens), so a
    # crash-resume (`__resume__`) or migrated session re-prefills only
    # the tokens since the last sealed block; the server also accepts
    # kind=session `__kvxfer__` frames and resume submissions.  Off, the
    # wire rejects session frames and resume falls back to full replay.
    "FLAGS_session_migration": True,
    # drain-by-migration: a retiring replica (autoscale-down, rollout
    # flip) pushes its live decode sessions to peers at a batch boundary
    # instead of waiting out long generations.  Off by default — flipped
    # on by the --migrate-smoke CI leg and opt-in deployments.
    "FLAGS_migrate_on_drain": False,
    # pressure-trigger migration: mid-decode preemption may migrate the
    # youngest (preempted) sequence to the least-loaded peer (fleetmon's
    # windowed occupancy signal) instead of deterministic local
    # recompute.  Off by default; recompute is always the fallback.
    "FLAGS_migrate_on_pressure": False,
    # seconds a migration source waits for the destination's
    # __resumeack__ before aborting the hand-off and resuming locally
    "FLAGS_migrate_ack_timeout": 10.0,
    # cap on total prefill tokens mixed into one decode iteration
    # (0 = unlimited).  Under a long-prompt burst, unbudgeted prefill
    # chunks crowd every iteration and inflate decode ITL p99; the budget
    # round-robins prefilling lanes so decode lanes always run.  Pure
    # scheduling: compiles nothing new (misses stay flat).
    "FLAGS_decode_prefill_token_budget": 0,
    # accepted no-ops (XLA/PJRT owns these concerns; benchmark's per-op
    # sync has no meaning under whole-block compilation)
    "FLAGS_benchmark": False,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_eager_delete_tensor_gb": 0.0,
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_fuse_parameter_memory_size": -1,
    "FLAGS_cudnn_deterministic": False,
    "FLAGS_enable_parallel_graph": False,
    "FLAGS_use_system_allocator": False,
}

_flags = {}


def _coerce(cur_default, value):
    if isinstance(cur_default, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes")
        return bool(value)
    if isinstance(cur_default, float):
        return float(value)
    if isinstance(cur_default, int):
        return int(value)
    return value


def _init_from_env():
    for k, dflt in _DEFAULTS.items():
        env = os.environ.get(k)
        _flags[k] = _coerce(dflt, env) if env is not None else dflt


_init_from_env()


def _norm(name):
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def set_flags(flags):
    """fluid.set_flags({'FLAGS_check_nan_inf': True}).  Unknown names raise
    (matching the reference's gflags registry check) so typos can't silently
    disable a debug flag."""
    for k, v in flags.items():
        k = _norm(k)
        if k not in _DEFAULTS:
            raise ValueError(
                "unknown flag %r (known: %s)" % (k, ", ".join(sorted(_DEFAULTS))))
        _flags[k] = _coerce(_DEFAULTS[k], v)


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {(_norm(n)): _flags.get(_norm(n)) for n in names}


def flag(name):
    """Internal fast read."""
    return _flags.get(_norm(name))
