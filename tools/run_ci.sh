#!/usr/bin/env bash
# CI harness (reference paddle/scripts/paddle_build.sh analog): build the
# native pieces, run the full test pyramid, smoke the bench + graft entry.
# Usage: tools/run_ci.sh [quick|full|tpu|--layout-smoke|--obs-smoke|--lint|--elastic-smoke|--zero1-smoke|--cache-smoke|--serve-smoke|--fleetmon-smoke|--trace-smoke|--decode-smoke|--disagg-smoke|--migrate-smoke|--ckpt-smoke]
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"

if [ "$MODE" = "--lint" ]; then
  # static-analysis leg: verifier unit tests, then proglint over every
  # bundled model (+ grad programs + a transpiled 2-pserver split) with
  # FLAGS_static_check=error — any error/warning diagnostic fails the leg
  echo "== lint: program verifier tests =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_program_verifier.py -q
  echo "== lint: proglint over bundled models (FLAGS_static_check=error) =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/proglint.py --grad --transpile 2
  echo "== lint: world verifier tests =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_world_verifier.py -q
  echo "== lint: whole-world checks (dp2 / dp4xtp2 / zero1) =="
  # every rank of each world is materialized and its collective schedule
  # lockstep-matched (DL101-DL104) + peak-HBM-estimated (MEM001-MEM003);
  # keep to the two fast zoo models so the leg stays O(seconds)
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/proglint.py --builtin mnist_mlp --builtin word2vec --world 2
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/proglint.py --builtin mnist_mlp --builtin word2vec \
    --world 8 --mesh 4x2
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/proglint.py --builtin mnist_mlp --builtin word2vec \
    --world 2 --zero1
  echo "== lint: concurrency lint tests (CC1xx) =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_threadlint.py -q
  echo "== lint: threadlint over paddle_tpu/ (must be clean mod waivers) =="
  JAX_PLATFORMS=cpu python tools/threadlint.py
  echo "== lint: threadlint seeded-defect self-test (must exit 1) =="
  # the planted CC101 inversion MUST be detected: exit 1 is the success
  # path here, anything else (0 = missed, 2 = misattributed) fails CI
  set +e
  JAX_PLATFORMS=cpu python tools/threadlint.py --seed-defect cc101
  seed_rc=$?
  set -e
  if [ "$seed_rc" -ne 1 ]; then
    echo "CI --lint: FAIL (seed-defect cc101 exit=$seed_rc, want 1)"
    exit 1
  fi
  echo "CI --lint: PASS"
  exit 0
fi

if [ "$MODE" = "--elastic-smoke" ]; then
  # elastic re-quorum leg: DL005 verifier units + the full 3-member
  # SIGKILL/evict/restore/rejoin subprocess scenario, everything under
  # FLAGS_static_check=error so any post-requorum rewrite that fails the
  # verifier kills the run instead of limping into XLA
  echo "== elastic smoke: DL005 + evict/rejoin subprocess scenario =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_dist_elastic_subprocess.py -q
  echo "CI --elastic-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--zero1-smoke" ]; then
  # ZeRO-1 + quantized-allreduce leg: the sharding/parity/DL006 unit
  # tests, then an 8-device dryrun of the sharded int8 path with the
  # static verifier in error mode (a stale shard table or drifted
  # dequant scale kills the run instead of limping into XLA)
  echo "== zero1 smoke: sharding + quantized allreduce tests =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_zero1_sharding.py -q
  echo "== zero1 smoke: 8-device int8 sharded dryrun =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error FLAGS_collective_mode=zero1 \
    FLAGS_allreduce_dtype=int8 python tools/zero1_smoke.py
  echo "CI --zero1-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--cache-smoke" ]; then
  # persistent-compilation-cache leg: the cache + standby unit/subprocess
  # tests, then a two-process reuse dryrun through the CLI — process 1
  # prewarms a bundled model, process 2 must restore it from disk (the
  # "disk" source assertion) — all under FLAGS_static_check=error
  echo "== cache smoke: compile cache + elastic standby tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_compile_cache.py \
    tests/test_elastic_standby.py -q
  echo "== cache smoke: two-process prewarm -> restore dryrun =="
  CC_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/compile_cache.py --dir "$CC_DIR" prewarm --model mnist_mlp
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python tools/compile_cache.py --dir "$CC_DIR" prewarm --model mnist_mlp \
    | grep -q " disk "
  python tools/compile_cache.py --dir "$CC_DIR" stats
  rm -rf "$CC_DIR"
  echo "CI --cache-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--serve-smoke" ]; then
  # continuous-batching serving leg: the engine/wire/clone unit tests,
  # then a live 2-replica fleet — prewarm both buckets AOT, stream 200
  # open-loop requests through the endpoints file while one replica is
  # SIGKILLed mid-stream — 0 dropped requests is the hard invariant, and
  # the scraped serving_* metrics must answer over the survivor
  echo "== serve smoke: serving + threaded-clone tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_serving.py \
    tests/test_serving_fleet_subprocess.py tests/test_inference.py -q
  echo "== serve smoke: 2-replica fleet + SIGKILL under load =="
  SRV_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-model "$SRV_DIR/model"
  SRV_ENV=(JAX_PLATFORMS=cpu FLAGS_static_check=error FLAGS_telemetry=1
           FLAGS_serving_hb_interval=0.2 FLAGS_serving_hb_timeout=1.5
           FLAGS_compile_cache_dir="$SRV_DIR/cc")
  env "${SRV_ENV[@]}" python tools/serve.py --model fc="$SRV_DIR/model" \
    --rank 0 --fleet 127.0.0.1:9460,127.0.0.1:9461 --buckets 1,4 \
    --endpoints-file "$SRV_DIR/eps.json" > "$SRV_DIR/r0.log" 2>&1 &
  R0=$!
  env "${SRV_ENV[@]}" python tools/serve.py --model fc="$SRV_DIR/model" \
    --rank 1 --fleet 127.0.0.1:9460,127.0.0.1:9461 --buckets 1,4 \
    --endpoints-file "$SRV_DIR/eps.json" > "$SRV_DIR/r1.log" 2>&1 &
  R1=$!
  trap 'kill -9 $R0 $R1 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$SRV_DIR/r0.log" && grep -q READY "$SRV_DIR/r1.log" \
      && break
    sleep 1
  done
  grep -q READY "$SRV_DIR/r0.log" && grep -q READY "$SRV_DIR/r1.log"
  # both buckets must be present in rank 0's prewarm manifest
  grep -q '"1"' "$SRV_DIR/r0.log" && grep -q '"4"' "$SRV_DIR/r0.log"
  ( sleep 2; kill -9 $R1 2>/dev/null || true ) &
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$SRV_DIR/eps.json" --model fc --requests 200 \
    --qps 50 --out "$SRV_DIR/BENCH_serving.json" --assert-no-drops
  # grep -c (not -q): -q's early exit SIGPIPEs the dump under pipefail
  python tools/metrics_dump.py --scrape 127.0.0.1:9460 --serving \
    | grep -c serving_batches_total > /dev/null
  kill $R0 2>/dev/null || true
  trap - EXIT

  echo "== serve smoke: SLO-tiered admission under overload =="
  # single replica, tiny queue, one 4-row bucket.  The armed delay fault
  # point (satellite: FLAGS_fault_spec on the execute path) makes every
  # batch take 50-150 ms, so qps 75 of one-row requests is a genuine
  # ~2x overload of the ~36/s capacity.  The 150 ms batch window makes
  # the paid-p99 bound meaningful: the uncontended baseline pays a full
  # coalescing window per solo request, and under overload a paid
  # arrival evicts queued free work and boards the NEXT dispatch, so
  # its wait is the in-flight remainder — bounded by that same window —
  # while free-tier traffic queues behind it and sheds
  env "${SRV_ENV[@]}" FLAGS_serving_max_queue=4 \
    FLAGS_serving_batch_window_ms=150 \
    FLAGS_fault_spec="serving.execute.fc:delay:1.0" \
    python tools/serve.py --model fc="$SRV_DIR/model" --port 9462 \
    --buckets 4 > "$SRV_DIR/tier.log" 2>&1 &
  R2=$!
  trap 'kill -9 $R2 2>/dev/null || true' EXIT
  for _ in $(seq 60); do grep -q READY "$SRV_DIR/tier.log" && break; sleep 1; done
  grep -q READY "$SRV_DIR/tier.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9462 \
    --model fc --requests 40 --qps 5 --batch-mix 1 --tier-mix paid:1.0 \
    --out "$SRV_DIR/BENCH_tier_base.json" --assert-no-drops
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9462 \
    --model fc --requests 240 --qps 75 --batch-mix 1 \
    --tier-mix paid:0.12,free:0.88 \
    --out "$SRV_DIR/BENCH_tier_overload.json"
  python tools/metrics_dump.py --scrape 127.0.0.1:9462 --serving \
    | grep -c serving_tier_shed_total > /dev/null
  kill -9 $R2 2>/dev/null || true
  trap - EXIT
  python - "$SRV_DIR/BENCH_tier_base.json" \
    "$SRV_DIR/BENCH_tier_overload.json" <<'EOF'
import json, sys
base = json.load(open(sys.argv[1]))["tiers"]["paid"]
over = json.load(open(sys.argv[2]))["tiers"]
paid, free = over["paid"], over["free"]
shed = paid["shed"] + free["shed"]
assert shed > 0, "overload run never shed — not actually overloaded"
frac_free = free["shed"] / shed
b, p = base["server_ms_p99"], paid["server_ms_p99"]
# 1.2x with a small absolute floor: at ms-scale baselines the in-flight
# batch alone exceeds 1.2x, so the bound is max(1.2x, +20ms)
bound = max(1.2 * b, b + 20.0)
print("TIER paid server p99 %.1f ms under overload (uncontended %.1f, "
      "bound %.1f); %d shed, %.0f%% free-tier"
      % (p, b, bound, shed, frac_free * 100))
assert paid["ok"] > 0, "no paid request survived overload"
assert p <= bound, "paid p99 %.1f ms blew the %.1f ms bound" % (p, bound)
assert frac_free >= 0.90, \
    "shed load only %.0f%% free-tier (< 90%%)" % (frac_free * 100)
EOF

  echo "== serve smoke: chaos canary flip (SIGKILL mid-flip under load) =="
  # 3 replicas serving fc AND fc@v2 (same weights, both prewarmed); a
  # 50% canary starts, then the flip lands while rank 1 is SIGKILLed
  # under open-loop load — 0 drops, and every survivor must converge on
  # the flipped version (the monitor's re-broadcast heals missed sends).
  # The metrics gate is parked (huge min_samples): the same-weights
  # canary must never spuriously roll back mid-chaos
  CHS_ENV=("${SRV_ENV[@]}" FLAGS_rollout_gate_min_samples=1000000)
  CFLEET=127.0.0.1:9463,127.0.0.1:9464,127.0.0.1:9465
  for r in 0 1 2; do
    env "${CHS_ENV[@]}" python tools/serve.py \
      --model fc="$SRV_DIR/model" --model fc@v2="$SRV_DIR/model" \
      --rank $r --fleet "$CFLEET" --buckets 1,4 \
      --endpoints-file "$SRV_DIR/ceps.json" > "$SRV_DIR/c$r.log" 2>&1 &
    eval "C$r=\$!"
  done
  trap 'kill -9 $C0 $C1 $C2 2>/dev/null || true' EXIT
  for _ in $(seq 90); do
    grep -q READY "$SRV_DIR/c0.log" && grep -q READY "$SRV_DIR/c1.log" \
      && grep -q READY "$SRV_DIR/c2.log" && break
    sleep 1
  done
  grep -q READY "$SRV_DIR/c2.log"
  JAX_PLATFORMS=cpu python - "$SRV_DIR/ceps.json" <<'EOF'
import sys
from paddle_tpu.serving import ServingClient
c = ServingClient(endpoints_file=sys.argv[1])
r = c.rollout({"op": "start", "model": "fc", "active": "fc",
               "canary": "fc@v2", "fraction": 0.5})
assert r.get("status") == "ok", r
print("canary started:", r["phases"]["routes"])
EOF
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$SRV_DIR/ceps.json" --model fc --requests 240 \
    --qps 60 --out "$SRV_DIR/BENCH_chaos_flip.json" --assert-no-drops &
  LG=$!
  sleep 1.5
  # the flip and the SIGKILL race each other mid-stream
  ( JAX_PLATFORMS=cpu python - "$SRV_DIR/ceps.json" <<'EOF'
import sys
from paddle_tpu.serving import ServingClient
r = ServingClient(endpoints_file=sys.argv[1]).rollout(
    {"op": "flip", "model": "fc"})
assert r.get("status") == "ok", r
print("flipped:", r["phases"]["routes"])
EOF
  ) &
  FLIP=$!
  kill -9 $C1 2>/dev/null || true
  wait $FLIP
  wait $LG   # 0 dropped requests through the kill + flip
  # every survivor must agree on the flipped version
  JAX_PLATFORMS=cpu python - <<'EOF'
import sys, time
from paddle_tpu.serving import ServingClient
c = ServingClient(endpoints=["127.0.0.1:9463", "127.0.0.1:9465"])
deadline = time.time() + 30
while True:
    docs = [c.rollout_state(ep) for ep in ("127.0.0.1:9463",
                                           "127.0.0.1:9465")]
    routes = [d.get("models", {}).get("fc") for d in docs]
    if all(r and r["state"] == "flipped" and r["active"] == "fc@v2"
           for r in routes):
        print("survivors agree: fc -> fc@v2 (flipped) on both replicas")
        break
    if time.time() > deadline:
        sys.exit("survivors never converged: %s" % routes)
    time.sleep(0.3)
EOF
  # post-flip traffic must be served ~entirely by fc@v2
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$SRV_DIR/ceps.json" --model fc --requests 80 \
    --qps 80 --out "$SRV_DIR/BENCH_postflip.json" --assert-no-drops \
    --canary-assert fc@v2:0.99
  kill -9 $C0 $C2 2>/dev/null || true
  trap - EXIT

  echo "== serve smoke: canary rollback gate (seeded bad v2) =="
  # single replica; every fc@v2 execution raises via the armed fault
  # point, so the canary's error rate trips the gate and the monitor
  # rolls back on its own.  GATE-VERDICT printed beside the BENCH rows
  # is the BASELINE.md round-16 validity requirement
  env "${SRV_ENV[@]}" FLAGS_rollout_gate_min_samples=5 \
    FLAGS_fault_spec="serving.execute.fc@v2:error:1.0" \
    python tools/serve.py --model fc="$SRV_DIR/model" \
    --model fc@v2="$SRV_DIR/model" --port 9466 --buckets 1,4 \
    > "$SRV_DIR/gate.log" 2>&1 &
  R6=$!
  trap 'kill -9 $R6 2>/dev/null || true' EXIT
  for _ in $(seq 60); do grep -q READY "$SRV_DIR/gate.log" && break; sleep 1; done
  grep -q READY "$SRV_DIR/gate.log"
  JAX_PLATFORMS=cpu python - <<'EOF'
from paddle_tpu.serving import ServingClient
c = ServingClient(endpoints=["127.0.0.1:9466"])
r = c.rollout({"op": "start", "model": "fc", "active": "fc",
               "canary": "fc@v2", "fraction": 0.5})
assert r.get("status") == "ok", r
EOF
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9466 \
    --model fc --requests 60 --qps 60 \
    --out "$SRV_DIR/BENCH_rollback.json"
  JAX_PLATFORMS=cpu python - <<'EOF'
import sys, time
from paddle_tpu.serving import ServingClient
c = ServingClient(endpoints=["127.0.0.1:9466"])
deadline = time.time() + 30
while True:
    doc = c.rollout_state("127.0.0.1:9466").get("models", {}).get("fc")
    if doc and doc["state"] == "rolled_back":
        break
    if time.time() > deadline:
        sys.exit("gate never rolled the canary back: %s" % doc)
    time.sleep(0.3)
st = c.rollout({"op": "status"})
gate = st["phases"]["gates"].get("fc", {})
print("GATE-VERDICT model=fc verdict=%s reason=%r (state=rolled_back)"
      % (gate.get("verdict"), gate.get("reason")))
assert gate.get("verdict") == "trip", gate
EOF
  python tools/metrics_dump.py --scrape 127.0.0.1:9466 --serving \
    | grep -c rollout_rollbacks_total > /dev/null
  kill -9 $R6 2>/dev/null || true
  trap - EXIT

  echo "== serve smoke: autoscaler (prewarmed standby up, drain down) =="
  # rank 0 alone holds a 2-slot fleet; sustained overload must fork the
  # prewarmed standby into slot 1 (endpoints file grows), sustained idle
  # must drain + retire it (file shrinks) — hysteresis ticks shortened
  # for CI wall time
  env "${SRV_ENV[@]}" FLAGS_serving_max_queue=4 \
    FLAGS_serving_autoscale_interval=0.25 FLAGS_serving_scale_up_ticks=2 \
    FLAGS_serving_scale_down_ticks=4 FLAGS_serving_autoscale_cooldown=4 \
    python tools/serve.py --model fc="$SRV_DIR/model" --rank 0 \
    --fleet 127.0.0.1:9467,127.0.0.1:9468 --buckets 1 \
    --endpoints-file "$SRV_DIR/aeps.json" --autoscale --max-replicas 2 \
    > "$SRV_DIR/a0.log" 2>&1 &
  A0=$!
  trap 'kill -9 $A0 2>/dev/null || true; pkill -9 -f "127.0.0.1:9467,127.0.0.1:9468" 2>/dev/null || true' EXIT
  for _ in $(seq 60); do grep -q READY "$SRV_DIR/a0.log" && break; sleep 1; done
  grep -q READY "$SRV_DIR/a0.log"
  # wait out the eviction of the never-started slot 1 (live must be [0])
  python - "$SRV_DIR/aeps.json" <<'EOF'
import json, sys, time
deadline = time.time() + 30
while time.time() < deadline:
    try:
        if len(json.load(open(sys.argv[1]))["endpoints"]) == 1:
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("fleet never settled to 1 live replica")
EOF
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9467 \
    --model fc --requests 800 --qps 500 --batch-mix 1 \
    --out "$SRV_DIR/BENCH_autoscale.json" &
  ALG=$!
  # sustained pressure -> standby forked into slot 1 (cold start is
  # restore-dominated via the shared compile cache)
  python - "$SRV_DIR/aeps.json" <<'EOF'
import json, sys, time
deadline = time.time() + 90
while time.time() < deadline:
    try:
        if len(json.load(open(sys.argv[1]))["endpoints"]) == 2:
            print("scaled UP to 2 replicas")
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("autoscaler never scaled up under overload")
EOF
  wait $ALG || true
  # sustained idle -> the standby drains at a batch boundary and retires
  python - "$SRV_DIR/aeps.json" <<'EOF'
import json, sys, time
deadline = time.time() + 90
while time.time() < deadline:
    try:
        if len(json.load(open(sys.argv[1]))["endpoints"]) == 1:
            print("scaled DOWN to 1 replica")
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("autoscaler never retired the idle standby")
EOF
  python - <<'EOF'
from paddle_tpu.core import telemetry
snap = telemetry.scrape("127.0.0.1:9467")
c = snap.get("counters", {})
up = c.get("autoscale_events_total{dir=up}", 0)
down = c.get("autoscale_events_total{dir=down}", 0)
assert up >= 1 and down >= 1, \
    "autoscale_events_total up=%s down=%s" % (up, down)
print("autoscale_events_total: up=%d down=%d" % (up, down))
EOF
  kill -9 $A0 2>/dev/null || true
  pkill -9 -f "127.0.0.1:9467,127.0.0.1:9468" 2>/dev/null || true
  trap - EXIT
  rm -rf "$SRV_DIR"
  echo "CI --serve-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--fleetmon-smoke" ]; then
  # fleet observability leg (PR 18): the mergeable-histogram / windowed-
  # rate / burn-alert unit tests plus the live fleet_top schema test,
  # then a 2-replica fleet where rank 1 carries an injected ~100ms
  # execute delay — the coordinator's FleetMonitor must publish a
  # fleet-merged server_ms p99 that REFLECTS the slow replica (the
  # healthy replica's local p99 stays fast), the multi-window burn-rate
  # alert must FIRE under the seeded Poisson load and CLEAR after the
  # fault window drains, and a trimmed PR-16 autoscale pass must still
  # scale 1->2 with pressure now sourced from the monitor's windowed
  # fleet rates
  echo "== fleetmon smoke: metrics plane + live fleet_top tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_fleetmon.py \
    tests/test_fleetmon_subprocess.py -q
  echo "== fleetmon smoke: 2-replica fleet, one slow replica =="
  FM_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-model "$FM_DIR/model"
  FM_ENV=(JAX_PLATFORMS=cpu FLAGS_static_check=error FLAGS_telemetry=1
          FLAGS_serving_hb_interval=0.2 FLAGS_serving_hb_timeout=1.5
          FLAGS_serving_fleetmon_interval=0.5
          FLAGS_serving_rate_window=10
          FLAGS_serving_slo_fast_window=6
          FLAGS_serving_slo_slow_window=15
          FLAGS_serving_slo_rules="srv:server_ms:p99:60"
          FLAGS_compile_cache_dir="$FM_DIR/cc")
  env "${FM_ENV[@]}" python tools/serve.py --model fc="$FM_DIR/model" \
    --rank 0 --fleet 127.0.0.1:9470,127.0.0.1:9471 --buckets 1,4 \
    --endpoints-file "$FM_DIR/eps.json" > "$FM_DIR/f0.log" 2>&1 &
  F0=$!
  env "${FM_ENV[@]}" FLAGS_fault_spec="serving.execute.fc:delay:1.0" \
    python tools/serve.py --model fc="$FM_DIR/model" \
    --rank 1 --fleet 127.0.0.1:9470,127.0.0.1:9471 --buckets 1,4 \
    --endpoints-file "$FM_DIR/eps.json" > "$FM_DIR/f1.log" 2>&1 &
  F1=$!
  trap 'kill -9 $F0 $F1 2>/dev/null || true; pkill -9 -f "127.0.0.1:9470,127.0.0.1:9471" 2>/dev/null || true' EXIT
  for _ in $(seq 90); do
    grep -q READY "$FM_DIR/f0.log" && grep -q READY "$FM_DIR/f1.log" \
      && break
    sleep 1
  done
  grep -q READY "$FM_DIR/f0.log" && grep -q READY "$FM_DIR/f1.log"
  # seeded Poisson load, half landing on the delayed replica; runs in
  # the background while the monitor's windows fill
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$FM_DIR/eps.json" --model fc --requests 300 \
    --qps 40 --seed 7 --deadline-ms 5000 --batch-mix 1 \
    --out "$FM_DIR/BENCH_fleetmon.json" &
  FLG=$!
  # the merged p99 must reflect the slow replica while the healthy
  # replica's own row stays fast, and the burn alert must fire
  python - <<'EOF'
import sys, time
from paddle_tpu.core import telemetry
deadline = time.time() + 60
fired = reflected = False
while time.time() < deadline and not (fired and reflected):
    try:
        doc = telemetry.scrape("127.0.0.1:9470", timeout=3.0,
                               key="__fleet__")
    except Exception:
        time.sleep(0.5)
        continue
    merged = [h for k, h in doc["histograms"].items()
              if k.split("{", 1)[0] == "server_ms"]
    if merged and max(h["p99"] for h in merged) >= 60.0:
        rows = {r["endpoint"]: r for r in doc["replicas"]}
        fast = rows.get("127.0.0.1:9470", {}).get("p99_ms", {})
        if fast.get("server_ms", 1e9) < max(h["p99"] for h in merged):
            reflected = True
    if any(s["active"] for s in doc.get("slo", [])):
        fired = True
    time.sleep(0.5)
if not reflected:
    sys.exit("fleet-merged p99 never reflected the slow replica")
if not fired:
    sys.exit("burn-rate alert never fired under the injected delay")
print("fleet p99 reflects slow replica; SLO alert FIRED")
EOF
  wait $FLG
  # load is over: the fast window drains and the alert must clear
  python - <<'EOF'
import sys, time
from paddle_tpu.core import telemetry
deadline = time.time() + 60
while time.time() < deadline:
    try:
        doc = telemetry.scrape("127.0.0.1:9470", timeout=3.0,
                               key="__fleet__")
        snap = telemetry.scrape("127.0.0.1:9470", timeout=3.0)
    except Exception:
        time.sleep(0.5)
        continue
    c = snap.get("counters", {})
    fires = sum(v for k, v in c.items()
                if k.startswith("slo_alerts_total{event=fire"))
    clears = sum(v for k, v in c.items()
                 if k.startswith("slo_alerts_total{event=clear"))
    # the __metrics__ snapshot republishes on its own 1s cadence, so
    # the clear counter can lag the doc's active flag by one tick —
    # wait for BOTH
    if not any(s["active"] for s in doc.get("slo", [])) \
            and fires >= 1 and clears >= 1:
        print("SLO alert CLEARED (fires=%d clears=%d)"
              % (fires, clears))
        sys.exit(0)
    time.sleep(0.5)
sys.exit("burn-rate alert never cleared after the fault window")
EOF
  # operator surface against the live fleet: fleet_top --once --json
  # must emit the full schema, goodput included
  env "${FM_ENV[@]}" python tools/fleet_top.py --scrape 127.0.0.1:9470 \
    --once --json > "$FM_DIR/fleet_top.json"
  python - "$FM_DIR/fleet_top.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
need = {"t", "replicas", "replicas_up", "histograms", "counters",
        "rates", "goodput", "slo", "bucket_bounds"}
missing = need - set(doc)
assert not missing, "fleet_top doc missing %s" % missing
assert doc["replicas_up"] == 2, doc["replicas_up"]
assert doc["goodput"]["raw_replies_per_s"] >= 0.0
print("fleet_top schema OK: %d replicas, %d merged histograms"
      % (len(doc["replicas"]), len(doc["histograms"])))
EOF
  kill -9 $F0 $F1 2>/dev/null || true
  pkill -9 -f "127.0.0.1:9470,127.0.0.1:9471" 2>/dev/null || true
  trap - EXIT

  echo "== fleetmon smoke: autoscale 1->2 from windowed fleet rates =="
  # trimmed PR-16 leg on fresh ports: with the FleetMonitor running,
  # the coordinator's AutoScaler reads autoscale_metrics() (fleet
  # queue depth + windowed shed/s) instead of local instants — the
  # standby must still fork into slot 1 under sustained overload
  env "${FM_ENV[@]}" FLAGS_serving_max_queue=4 \
    FLAGS_serving_autoscale_interval=0.25 FLAGS_serving_scale_up_ticks=2 \
    FLAGS_serving_scale_down_ticks=4 FLAGS_serving_autoscale_cooldown=4 \
    python tools/serve.py --model fc="$FM_DIR/model" --rank 0 \
    --fleet 127.0.0.1:9477,127.0.0.1:9478 --buckets 1 \
    --endpoints-file "$FM_DIR/aeps.json" --autoscale --max-replicas 2 \
    > "$FM_DIR/a0.log" 2>&1 &
  FA0=$!
  trap 'kill -9 $FA0 2>/dev/null || true; pkill -9 -f "127.0.0.1:9477,127.0.0.1:9478" 2>/dev/null || true' EXIT
  for _ in $(seq 60); do grep -q READY "$FM_DIR/a0.log" && break; sleep 1; done
  grep -q READY "$FM_DIR/a0.log"
  python - "$FM_DIR/aeps.json" <<'EOF'
import json, sys, time
deadline = time.time() + 30
while time.time() < deadline:
    try:
        if len(json.load(open(sys.argv[1]))["endpoints"]) == 1:
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("fleet never settled to 1 live replica")
EOF
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9477 \
    --model fc --requests 800 --qps 500 --batch-mix 1 --seed 7 \
    --out "$FM_DIR/BENCH_fm_autoscale.json" &
  FALG=$!
  python - "$FM_DIR/aeps.json" <<'EOF'
import json, sys, time
deadline = time.time() + 90
while time.time() < deadline:
    try:
        if len(json.load(open(sys.argv[1]))["endpoints"]) == 2:
            print("scaled UP to 2 replicas")
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("autoscaler never scaled up under overload")
EOF
  wait $FALG || true
  # the monitor was live (fleet_replicas_up published) and the scale-up
  # event fired — the unit tests pin that the pressure values came from
  # autoscale_metrics()'s windowed view
  python - <<'EOF'
from paddle_tpu.core import telemetry
snap = telemetry.scrape("127.0.0.1:9477")
up = snap.get("counters", {}).get("autoscale_events_total{dir=up}", 0)
assert up >= 1, "autoscale_events_total{dir=up}=%s" % up
assert snap.get("gauges", {}).get("fleet_replicas_up", 0) >= 1, \
    "FleetMonitor never ticked on the coordinator"
print("autoscale up=%d with fleet_replicas_up=%g" % (
    up, snap["gauges"]["fleet_replicas_up"]))
EOF
  kill -9 $FA0 2>/dev/null || true
  pkill -9 -f "127.0.0.1:9477,127.0.0.1:9478" 2>/dev/null || true
  trap - EXIT
  rm -rf "$FM_DIR"
  echo "CI --fleetmon-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--decode-smoke" ]; then
  # autoregressive decode leg: paged-KV allocator + decode engine units,
  # then a live replica serving token-level continuous batching under a
  # mixed-length burst — zero runtime compiles after the bucket prewarm
  # is the hard invariant (flat executor_cache_miss_total), and the same
  # traffic against a request-level replica must be >=1.5x slower in
  # generated tokens/sec (the continuous-batching win); a third replica
  # with --speculative-k 3 replays the identical seeded traffic and must
  # produce bitwise-equal outputs (outputs_sha256) with its own flat
  # miss count (buckets x 3 speculative stepfn kinds); a prefix leg then
  # replays seeded shared-prefix traffic (--prefix-share 0.75) against a
  # cache-on and a cache-off replica — bitwise-equal outputs_sha256 is
  # the parity gate, hit rate >= 0.5 and a flat miss count prove the hit
  # path reuses blocks without compiling; a final leg reruns the token
  # traffic under FLAGS_decode_prefill_token_budget and must stay
  # bitwise-identical (budgeted prefill is scheduling only)
  echo "== decode smoke: paged KV cache + decode serving tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_kv_cache.py tests/test_decode_serving.py \
    tests/test_decode_fleet_subprocess.py -q
  echo "== decode smoke: token-level replica under mixed-length burst =="
  DEC_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-decoder "$DEC_DIR/dec"
  DEC_ENV=(JAX_PLATFORMS=cpu FLAGS_telemetry=1
           FLAGS_kv_block_size=8 FLAGS_kv_cache_blocks=64
           FLAGS_compile_cache_dir="$DEC_DIR/cc")
  env "${DEC_ENV[@]}" python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9480 --decode-buckets 4,8 --decode-mode token \
    > "$DEC_DIR/token.log" 2>&1 &
  D0=$!
  trap 'kill -9 $D0 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/token.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/token.log"
  # near-simultaneous arrivals (open-loop qps >> service rate) so the
  # scheduler, not the arrival schedule, is the bottleneck; high prompt
  # length variance is what request-level batching wastes lanes on
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9480 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,24 --max-new 8 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_token.json" --assert-no-drops
  # zero runtime XLA compiles under mixed-length decode: the miss
  # counter must still equal the 2 prewarmed lane buckets
  python - <<'EOF'
from paddle_tpu.core import telemetry
snap = telemetry.scrape("127.0.0.1:9480")
miss = sum(v for k, v in snap["counters"].items()
           if k.startswith("executor_cache_miss_total"))
steps = sum(v for k, v in snap["counters"].items()
            if k.startswith("serving_decode_steps_total"))
assert steps > 0, "no decode steps recorded"
assert miss == 2, "runtime compiles under decode: miss=%s != 2" % miss
print("flat executor_cache_miss_total OK: %d over %d decode steps"
      % (miss, steps))
EOF
  python tools/metrics_dump.py --scrape 127.0.0.1:9480 --decode \
    | grep -c kv_blocks_in_use > /dev/null
  python tools/metrics_dump.py --scrape 127.0.0.1:9480 --decode \
    | grep -c decode_batch_occupancy > /dev/null
  kill -9 $D0 2>/dev/null || true
  echo "== decode smoke: request-level baseline, same traffic =="
  env "${DEC_ENV[@]}" python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9481 --decode-buckets 4,8 --decode-mode request \
    > "$DEC_DIR/request.log" 2>&1 &
  D1=$!
  trap 'kill -9 $D1 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/request.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/request.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9481 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,24 --max-new 8 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_request.json" --assert-no-drops
  kill -9 $D1 2>/dev/null || true
  trap - EXIT
  python - "$DEC_DIR/BENCH_decode_token.json" \
    "$DEC_DIR/BENCH_decode_request.json" <<'EOF'
import json, sys
tok = json.load(open(sys.argv[1]))
req = json.load(open(sys.argv[2]))
rt, rr = tok["tokens_per_sec"], req["tokens_per_sec"]
ratio = rt / max(rr, 1e-9)
print("token-level %.1f tok/s vs request-level %.1f tok/s -> %.2fx"
      % (rt, rr, ratio))
print("token-level TTFT p50/p99 = %s/%s ms, ITL p50/p99 = %s/%s ms"
      % (tok["ttft_ms_p50"], tok["ttft_ms_p99"],
         tok["itl_ms_p50"], tok["itl_ms_p99"]))
assert tok["ttft_ms_p50"] > 0, "no TTFT samples"
assert ratio >= 1.5, "continuous-batching win %.2fx < 1.5x" % ratio
EOF
  echo "== decode smoke: speculative decoding, same traffic =="
  # third replica: same bundle (save_demo_decoder ships a draft), same
  # seeded traffic, FLAGS_speculative_k=3 — greedy accept-longest-prefix
  # must be BITWISE identical to the non-speculative token run
  # (outputs_sha256), and the miss counter must stay flat at
  # 2 buckets x 3 stepfn kinds (verify + draft rollout + draft ingest)
  env "${DEC_ENV[@]}" python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9482 --decode-buckets 4,8 --decode-mode token \
    --speculative-k 3 > "$DEC_DIR/spec.log" 2>&1 &
  D2=$!
  trap 'kill -9 $D2 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/spec.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/spec.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9482 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,24 --max-new 8 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_spec.json" --assert-no-drops
  python - <<'EOF'
from paddle_tpu.core import telemetry
snap = telemetry.scrape("127.0.0.1:9482")
miss = sum(v for k, v in snap["counters"].items()
           if k.startswith("executor_cache_miss_total"))
assert miss == 6, \
    "runtime compiles under speculation: miss=%s != 2 buckets x 3" % miss
print("flat executor_cache_miss_total OK under speculation: %d" % miss)
EOF
  python tools/metrics_dump.py --scrape 127.0.0.1:9482 --decode \
    | grep -c spec_tokens_proposed_total > /dev/null
  kill -9 $D2 2>/dev/null || true
  trap - EXIT
  python - "$DEC_DIR/BENCH_decode_spec.json" \
    "$DEC_DIR/BENCH_decode_token.json" <<'EOF'
import json, sys
spec = json.load(open(sys.argv[1]))
base = json.load(open(sys.argv[2]))
assert spec["speculative_k"] == 3, spec["speculative_k"]
assert spec["outputs_sha256"] == base["outputs_sha256"], \
    "speculative outputs differ from greedy baseline: %s != %s" \
    % (spec["outputs_sha256"], base["outputs_sha256"])
assert spec["spec_tokens_proposed"] > 0, "speculation never ran"
acc = spec["spec_acceptance_rate"]
assert acc is not None and 0.0 < acc <= 1.0, acc
rs, rb = spec["tokens_per_sec"], base["tokens_per_sec"]
ratio = rs / max(rb, 1e-9)
print("speculative %.1f tok/s vs greedy %.1f tok/s -> %.2fx "
      "(acceptance %.0f%%)" % (rs, rb, ratio, acc * 100))
print("bitwise-equal outputs OK (%d distinct prompts)"
      % spec["outputs_distinct"])
if ratio < 1.3:
    # the 1-layer toy draft on a loaded CI box can miss the perf bar
    # even with high acceptance; parity + flat-miss asserted above are
    # the correctness gates, so the throughput bar alone degrades to a
    # loud notice instead of a hard failure
    print("SKIP-NOTICE: speculative speedup %.2fx < 1.3x target "
          "(acceptance %.0f%%) — correctness gates passed"
          % (ratio, acc * 100))
EOF
  echo "== decode smoke: prefix caching, cache-on vs cache-off =="
  # two replicas, identical seeded shared-prefix traffic (75% of
  # requests open with one of two 24-token prefixes = 3 full blocks at
  # FLAGS_kv_block_size=8): the cache-on replica must emit bitwise the
  # same streams as the cache-off one while skipping cached prefill
  # work.  Pool sized so the WHOLE burst's promised prompt blocks fit
  # (48 x 5 <= 255): admission sheds would complete different request
  # sets on the two replicas and void the sha comparison
  env "${DEC_ENV[@]}" FLAGS_prefix_cache=1 FLAGS_kv_cache_blocks=256 \
    python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9483 --decode-buckets 4,8 --decode-mode token \
    > "$DEC_DIR/prefix_on.log" 2>&1 &
  D3=$!
  trap 'kill -9 $D3 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/prefix_on.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/prefix_on.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9483 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,8 --max-new 8 \
    --prefix-share 0.75 --prefix-tokens 24 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_prefix_on.json" --assert-no-drops
  # the hit path feeds from mid-prompt through the SAME prewarmed
  # executables: the miss counter must still equal the 2 lane buckets
  python - <<'EOF'
from paddle_tpu.core import telemetry
snap = telemetry.scrape("127.0.0.1:9483")
miss = sum(v for k, v in snap["counters"].items()
           if k.startswith("executor_cache_miss_total"))
hits = sum(v for k, v in snap["counters"].items()
           if k.startswith("prefix_cache_hit_tokens_total"))
assert hits > 0, "prefix cache never hit under 0.75 shared-prefix traffic"
assert miss == 2, "runtime compiles on the hit path: miss=%s != 2" % miss
print("flat executor_cache_miss_total OK with %d prefix-cached tokens"
      % hits)
EOF
  python tools/metrics_dump.py --scrape 127.0.0.1:9483 --decode \
    | grep -c prefix_cache_hit_tokens_total > /dev/null
  kill -9 $D3 2>/dev/null || true
  env "${DEC_ENV[@]}" FLAGS_prefix_cache=0 FLAGS_kv_cache_blocks=256 \
    python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9484 --decode-buckets 4,8 --decode-mode token \
    > "$DEC_DIR/prefix_off.log" 2>&1 &
  D4=$!
  trap 'kill -9 $D4 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/prefix_off.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/prefix_off.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9484 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,8 --max-new 8 \
    --prefix-share 0.75 --prefix-tokens 24 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_prefix_off.json" --assert-no-drops
  kill -9 $D4 2>/dev/null || true
  trap - EXIT
  python - "$DEC_DIR/BENCH_decode_prefix_on.json" \
    "$DEC_DIR/BENCH_decode_prefix_off.json" <<'EOF'
import json, sys
on = json.load(open(sys.argv[1]))
off = json.load(open(sys.argv[2]))
assert on["outputs_sha256"] == off["outputs_sha256"], \
    "prefix-cached outputs differ from cache-off baseline: %s != %s" \
    % (on["outputs_sha256"], off["outputs_sha256"])
assert on["prefix_cache_hit_tokens"] > 0, "no prefix-cache hits scraped"
assert on["prefix_cache_hit_rate"] >= 0.5, \
    "hit rate %.2f < 0.5 at 0.75 prefix share" % on["prefix_cache_hit_rate"]
assert off["prefix_cache_hit_tokens"] == 0
assert off["prefix_cache_hit_rate"] == 0.0
rt_on, rt_off = on["ttft_ms_p50"], off["ttft_ms_p50"]
ratio = rt_off / max(rt_on, 1e-9)
print("prefix cache: hit rate %.0f%%, %d cached tokens, TTFT p50 "
      "%.1f ms (on) vs %.1f ms (off) -> %.2fx"
      % (on["prefix_cache_hit_rate"] * 100, on["prefix_cache_hit_tokens"],
         rt_on, rt_off, ratio))
print("bitwise-equal outputs OK (%d distinct prompts)"
      % on["outputs_distinct"])
if ratio < 1.3:
    # parity + hit rate + flat miss are the correctness gates; the TTFT
    # bar on a loaded CI box degrades to a loud notice, the real capture
    # lives in BASELINE.md round 15
    print("SKIP-NOTICE: prefix-cache TTFT win %.2fx < 1.3x target — "
          "correctness gates passed" % ratio)
EOF
  echo "== decode smoke: token-budget chunked prefill, same traffic =="
  # the token leg's exact seeded traffic replayed with an 8-token/iter
  # prefill budget: chunked admission may only change scheduling, never
  # tokens — outputs_sha256 must match BENCH_decode_token.json.  The
  # bigger pool keeps the slower queue drain from shedding (a shed
  # would change the completed set, not the tokens)
  env "${DEC_ENV[@]}" FLAGS_decode_prefill_token_budget=8 \
    FLAGS_kv_cache_blocks=256 \
    python tools/serve.py --model dec="$DEC_DIR/dec" \
    --port 9485 --decode-buckets 4,8 --decode-mode token \
    > "$DEC_DIR/budget.log" 2>&1 &
  D5=$!
  trap 'kill -9 $D5 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$DEC_DIR/budget.log" && break; sleep 1
  done
  grep -q READY "$DEC_DIR/budget.log"
  JAX_PLATFORMS=cpu python tools/loadgen.py --endpoints 127.0.0.1:9485 \
    --model dec --requests 48 --qps 400 --prompt-mix 2,4,24 --max-new 8 \
    --deadline-ms 30000 --retry-shed 4 \
    --out "$DEC_DIR/BENCH_decode_budget.json" --assert-no-drops
  kill -9 $D5 2>/dev/null || true
  trap - EXIT
  python - "$DEC_DIR/BENCH_decode_budget.json" \
    "$DEC_DIR/BENCH_decode_token.json" <<'EOF'
import json, sys
bud = json.load(open(sys.argv[1]))
base = json.load(open(sys.argv[2]))
assert bud["outputs_sha256"] == base["outputs_sha256"], \
    "budgeted outputs differ from unbudgeted baseline: %s != %s" \
    % (bud["outputs_sha256"], base["outputs_sha256"])
ri_b, ri_u = bud["itl_ms_p99"], base["itl_ms_p99"]
ratio = ri_b / max(ri_u, 1e-9)
print("budgeted ITL p99 %.1f ms vs unbudgeted %.1f ms -> %.2fx"
      % (ri_b, ri_u, ratio))
if ratio > 0.7:
    # decode-lane tail protection is the point of the budget, but the
    # ratio on a loaded CI box is noisy — parity above is the hard gate
    print("SKIP-NOTICE: budgeted ITL p99 ratio %.2fx > 0.7x target — "
          "parity gate passed" % ratio)
EOF
  rm -rf "$DEC_DIR"
  echo "CI --decode-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--disagg-smoke" ]; then
  # disaggregated prefill/decode leg: the __kvxfer__ codec / handoff /
  # reconciliation unit tests, then a 2-prefill+2-decode fleet replaying
  # the decode leg's round-15 mixed burst against a 4-monolith twin —
  # bitwise-equal outputs_sha256 is the hard gate, the per-role phase
  # p99s print beside it (TTFT/ITL p99 over ~1.10x the monolith twin
  # degrades to a loud SKIP-NOTICE on a loaded CI box); then a prefill
  # replica is SIGKILLed mid-transfer under load (zero admitted requests
  # dropped; the victim's flight recorder must name the in-flight
  # transfer frames); finally compact 1-prefill+1-decode pairs move the
  # same long-prompt traffic in f32 and int8 residency — the int8 pair
  # must be output-equal to an int8 monolith while moving <= 0.55x the
  # f32 pair's scraped kv_xfer_bytes_total
  echo "== disagg smoke: kvxfer codec + handoff + reconciliation tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_disagg_serving.py -q
  echo "== disagg smoke: 2-prefill+2-decode vs 4-monolith, same burst =="
  DSG_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-decoder "$DSG_DIR/dec"
  DSG_ENV=(JAX_PLATFORMS=cpu FLAGS_telemetry=1
           FLAGS_kv_block_size=8 FLAGS_kv_cache_blocks=256
           FLAGS_serving_hb_interval=0.2 FLAGS_serving_hb_timeout=1.5
           FLAGS_compile_cache_dir="$DSG_DIR/cc")
  # wait for the coordinator to publish the fleet's endpoints file —
  # clients learn the role column from THIS file, so traffic fired
  # before it lands would treat a handing-off prefill as a monolith
  dsg_wait_eps() {
    python - "$1" "$2" "$3" <<'EOF'
import json, sys, time
path, want_n, roles_csv = sys.argv[1], int(sys.argv[2]), sys.argv[3]
want_roles = [r for r in roles_csv.split(",") if r] or None
deadline = time.time() + 30
while time.time() < deadline:
    try:
        doc = json.load(open(path))
        if len(doc.get("endpoints", [])) == want_n and \
                (want_roles is None or doc.get("roles") == want_roles):
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("%s never published %d endpoints (roles=%s)"
         % (path, want_n, roles_csv or None))
EOF
  }
  MFLEET=127.0.0.1:9420,127.0.0.1:9421,127.0.0.1:9422,127.0.0.1:9423
  for r in 0 1 2 3; do
    env "${DSG_ENV[@]}" python tools/serve.py --model dec="$DSG_DIR/dec" \
      --rank $r --fleet "$MFLEET" --decode-buckets 4,8 \
      --decode-mode token --endpoints-file "$DSG_DIR/meps.json" \
      > "$DSG_DIR/m$r.log" 2>&1 &
    eval "M$r=\$!"
  done
  trap 'kill -9 $M0 $M1 $M2 $M3 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$DSG_DIR/m0.log" && grep -q READY "$DSG_DIR/m1.log" \
      && grep -q READY "$DSG_DIR/m2.log" && grep -q READY "$DSG_DIR/m3.log" \
      && break
    sleep 1
  done
  grep -q READY "$DSG_DIR/m3.log"
  dsg_wait_eps "$DSG_DIR/meps.json" 4 ""
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$DSG_DIR/meps.json" --model dec --requests 48 \
    --qps 400 --prompt-mix 2,4,24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_disagg_mono.json" \
    --assert-no-drops
  kill -9 $M0 $M1 $M2 $M3 2>/dev/null || true
  trap - EXIT
  DFLEET=127.0.0.1:9424,127.0.0.1:9425,127.0.0.1:9426,127.0.0.1:9427
  for r in 0 1 2 3; do
    env "${DSG_ENV[@]}" python tools/serve.py --model dec="$DSG_DIR/dec" \
      --rank $r --fleet "$DFLEET" --roles prefill,prefill,decode,decode \
      --decode-buckets 4,8 --decode-mode token \
      --endpoints-file "$DSG_DIR/deps.json" > "$DSG_DIR/d$r.log" 2>&1 &
    eval "D$r=\$!"
  done
  trap 'kill -9 $D0 $D1 $D2 $D3 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$DSG_DIR/d0.log" && grep -q READY "$DSG_DIR/d1.log" \
      && grep -q READY "$DSG_DIR/d2.log" && grep -q READY "$DSG_DIR/d3.log" \
      && break
    sleep 1
  done
  grep -q READY "$DSG_DIR/d3.log"
  dsg_wait_eps "$DSG_DIR/deps.json" 4 "prefill,prefill,decode,decode"
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$DSG_DIR/deps.json" --model dec --requests 48 \
    --qps 400 --prompt-mix 2,4,24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_disagg_pair.json" \
    --assert-no-drops
  # satellite: replicas republish the transfer counters and the
  # per-model cache-pressure gauges over the 1 s __metrics__ publish —
  # the role-aware autoscaler's decode signal rides kv_pool_occupancy
  python tools/metrics_dump.py --scrape 127.0.0.1:9424 --decode \
    | grep -c kv_xfer_blocks_total > /dev/null
  python tools/metrics_dump.py --scrape 127.0.0.1:9426 --decode \
    | grep -c kv_pool_occupancy > /dev/null
  python tools/metrics_dump.py --scrape 127.0.0.1:9426 --decode \
    | grep -c prefix_cache_hit_rate > /dev/null
  kill -9 $D0 $D1 $D2 $D3 2>/dev/null || true
  trap - EXIT
  python - "$DSG_DIR/BENCH_disagg_pair.json" \
    "$DSG_DIR/BENCH_disagg_mono.json" <<'EOF'
import json, sys
dis = json.load(open(sys.argv[1]))
mono = json.load(open(sys.argv[2]))
assert dis["outputs_sha256"] == mono["outputs_sha256"], \
    "disagg outputs differ from the monolith twin: %s != %s" \
    % (dis["outputs_sha256"], mono["outputs_sha256"])
rp = dis.get("role_phases")
assert rp and rp["disagg_requests"] > 0, \
    "no reply carried role=disagg phase attribution: %r" % (rp,)
print("disagg per-role p99: prefill queue %.1f ms, prefill %.1f ms, "
      "xfer %.1f ms, decode queue %.1f ms, decode exec %.1f ms "
      "(%d disagg requests)"
      % (rp["prefill"]["queue_wait_ms_p99"], rp["prefill"]["prefill_ms_p99"],
         rp["xfer"]["xfer_ms_p99"], rp["decode"]["queue_wait_ms_p99"],
         rp["decode"]["execute_ms_p99"], rp["disagg_requests"]))
for k in ("ttft_ms_p99", "itl_ms_p99"):
    d, m = dis[k], mono[k]
    ratio = d / max(m, 1e-9)
    print("%s: disagg %.1f ms vs monolith %.1f ms -> %.2fx" % (k, d, m, ratio))
    if ratio > 1.10:
        # sha parity + no-drops above are the hard gates; tail latency
        # on a loaded CI box degrades to a loud notice (the real capture
        # lives in BASELINE.md round 17)
        print("SKIP-NOTICE: disagg %s %.2fx > 1.10x of the monolith twin "
              "— parity gates passed" % (k, ratio))
print("bitwise-equal outputs OK (%d distinct prompts)"
      % dis["outputs_distinct"])
EOF
  echo "== disagg smoke: SIGKILL a prefill replica mid-transfer =="
  KFLEET=127.0.0.1:9428,127.0.0.1:9429,127.0.0.1:9430,127.0.0.1:9431
  for r in 0 1 2 3; do
    env "${DSG_ENV[@]}" FLAGS_tracing=1 \
      FLAGS_telemetry_dir="$DSG_DIR/tel" \
      python tools/serve.py --model dec="$DSG_DIR/dec" \
      --rank $r --fleet "$KFLEET" --roles prefill,prefill,decode,decode \
      --decode-buckets 4,8 --decode-mode token \
      --endpoints-file "$DSG_DIR/keps.json" > "$DSG_DIR/k$r.log" 2>&1 &
    eval "K$r=\$!"
  done
  trap 'kill -9 $K0 $K1 $K2 $K3 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$DSG_DIR/k0.log" && grep -q READY "$DSG_DIR/k1.log" \
      && grep -q READY "$DSG_DIR/k2.log" && grep -q READY "$DSG_DIR/k3.log" \
      && break
    sleep 1
  done
  grep -q READY "$DSG_DIR/k3.log"
  dsg_wait_eps "$DSG_DIR/keps.json" 4 "prefill,prefill,decode,decode"
  # long prompts keep sealed-block transfers in flight when the kill
  # lands; the surviving prefill absorbs the replays — zero drops
  ( sleep 1; kill -9 $K0 2>/dev/null || true ) &
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$DSG_DIR/keps.json" --model dec --requests 96 \
    --qps 40 --prompt-mix 24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_disagg_kill.json" \
    --assert-no-drops
  kill -9 $K1 $K2 $K3 2>/dev/null || true
  trap - EXIT
  # the victim's write-through flight recorder must already name its
  # in-flight transfer frames on disk (SIGKILL is uncatchable)
  grep -q kvxfer "$DSG_DIR/tel/flightrec-$K0.json"
  echo "flight recorder OK: victim flightrec-$K0.json names kvxfer frames"
  echo "== disagg smoke: int8 wire residency, pair vs pair vs monolith =="
  env "${DSG_ENV[@]}" python tools/serve.py --model dec="$DSG_DIR/dec" \
    --rank 0 --fleet 127.0.0.1:9432,127.0.0.1:9433 \
    --roles prefill,decode --decode-buckets 4,8 --decode-mode token \
    --endpoints-file "$DSG_DIR/f32eps.json" > "$DSG_DIR/f32p.log" 2>&1 &
  F0=$!
  env "${DSG_ENV[@]}" python tools/serve.py --model dec="$DSG_DIR/dec" \
    --rank 1 --fleet 127.0.0.1:9432,127.0.0.1:9433 \
    --roles prefill,decode --decode-buckets 4,8 --decode-mode token \
    --endpoints-file "$DSG_DIR/f32eps.json" > "$DSG_DIR/f32d.log" 2>&1 &
  F1=$!
  env "${DSG_ENV[@]}" FLAGS_kv_cache_dtype=int8 python tools/serve.py \
    --model dec="$DSG_DIR/dec" \
    --rank 0 --fleet 127.0.0.1:9434,127.0.0.1:9435 \
    --roles prefill,decode --decode-buckets 4,8 --decode-mode token \
    --endpoints-file "$DSG_DIR/i8eps.json" > "$DSG_DIR/i8p.log" 2>&1 &
  I0=$!
  env "${DSG_ENV[@]}" FLAGS_kv_cache_dtype=int8 python tools/serve.py \
    --model dec="$DSG_DIR/dec" \
    --rank 1 --fleet 127.0.0.1:9434,127.0.0.1:9435 \
    --roles prefill,decode --decode-buckets 4,8 --decode-mode token \
    --endpoints-file "$DSG_DIR/i8eps.json" > "$DSG_DIR/i8d.log" 2>&1 &
  I1=$!
  env "${DSG_ENV[@]}" FLAGS_kv_cache_dtype=int8 python tools/serve.py \
    --model dec="$DSG_DIR/dec" --port 9436 --decode-buckets 4,8 \
    --decode-mode token > "$DSG_DIR/i8m.log" 2>&1 &
  I2=$!
  trap 'kill -9 $F0 $F1 $I0 $I1 $I2 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$DSG_DIR/f32p.log" && grep -q READY "$DSG_DIR/f32d.log" \
      && grep -q READY "$DSG_DIR/i8p.log" && grep -q READY "$DSG_DIR/i8d.log" \
      && grep -q READY "$DSG_DIR/i8m.log" && break
    sleep 1
  done
  grep -q READY "$DSG_DIR/i8m.log"
  dsg_wait_eps "$DSG_DIR/f32eps.json" 2 "prefill,decode"
  dsg_wait_eps "$DSG_DIR/i8eps.json" 2 "prefill,decode"
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$DSG_DIR/f32eps.json" --model dec --requests 24 \
    --qps 200 --prompt-mix 24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_xfer_f32.json" --assert-no-drops
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$DSG_DIR/i8eps.json" --model dec --requests 24 \
    --qps 200 --prompt-mix 24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_xfer_int8.json" --assert-no-drops
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints 127.0.0.1:9436 --model dec --requests 24 \
    --qps 200 --prompt-mix 24 --max-new 8 --deadline-ms 60000 \
    --retry-shed 4 --out "$DSG_DIR/BENCH_xfer_int8_mono.json" \
    --assert-no-drops
  # scrape the wire counters off both prefill replicas BEFORE teardown
  python - <<'EOF'
import time
from paddle_tpu.core import telemetry
time.sleep(1.2)   # one __metrics__ publish period
def xfer_bytes(ep, dtype):
    snap = telemetry.scrape(ep)
    return sum(v for k, v in snap.get("counters", {}).items()
               if k.startswith("kv_xfer_bytes_total")
               and "dtype=%s" % dtype in k)
f32 = xfer_bytes("127.0.0.1:9432", "f32")
i8 = xfer_bytes("127.0.0.1:9434", "int8")
assert f32 > 0, "f32 pair never moved a sealed block"
assert i8 > 0, "int8 pair never moved a sealed block"
ratio = i8 / f32
print("kv_xfer_bytes_total: int8 %d B vs f32 %d B on the same traffic "
      "-> %.2fx" % (i8, f32, ratio))
assert ratio <= 0.55, \
    "int8 wire transfer %.2fx > 0.55x of f32 bytes" % ratio
EOF
  kill -9 $F0 $F1 $I0 $I1 $I2 2>/dev/null || true
  trap - EXIT
  python - "$DSG_DIR/BENCH_xfer_int8.json" \
    "$DSG_DIR/BENCH_xfer_int8_mono.json" <<'EOF'
import json, sys
pair = json.load(open(sys.argv[1]))
mono = json.load(open(sys.argv[2]))
assert pair["outputs_sha256"] == mono["outputs_sha256"], \
    "int8 pair outputs differ from the int8 monolith: %s != %s" \
    % (pair["outputs_sha256"], mono["outputs_sha256"])
print("int8 pair == int8 monolith outputs OK (%d distinct prompts)"
      % pair["outputs_distinct"])
EOF
  rm -rf "$DSG_DIR"
  echo "CI --disagg-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--migrate-smoke" ]; then
  # live decode-session migration leg: the export/adopt/resume unit
  # tests, then two fleet scenarios.  Crash: a 3-replica fleet is
  # warmed per-replica with the SAME seeded Poisson traffic (every
  # replica then holds the full prompt ++ out history chain of every
  # generation, evictable in its prefix index), one replica is
  # SIGKILLed mid-decode under load — every request must answer, the
  # resumed outputs_sha256 must equal the uninterrupted twin's, the
  # worst resumed session re-feeds under one KV block (the chain
  # matched instead of re-prefilling), the victim's write-through
  # flight recorder names its in-flight sessions, and
  # executor_cache_miss_total stays flat on the survivors.  Drain: an
  # autoscale-down __retire__ with FLAGS_migrate_on_drain pushes the
  # victim's live sessions to its peers over __kvxfer__ — zero drops,
  # parity again, and the victim exits promptly: the resumed sessions
  # prove hand-off, not completion-wait
  echo "== migrate smoke: session-migration unit tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_session_migration.py -q
  MIG_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-decoder "$MIG_DIR/dec"
  MIG_ENV=(JAX_PLATFORMS=cpu FLAGS_telemetry=1
           FLAGS_kv_block_size=8 FLAGS_kv_cache_blocks=768
           FLAGS_serving_hb_interval=0.2 FLAGS_serving_hb_timeout=1.5
           FLAGS_compile_cache_dir="$MIG_DIR/cc")
  mig_wait_eps() {
    python - "$1" "$2" <<'EOF'
import json, sys, time
path, want_n = sys.argv[1], int(sys.argv[2])
deadline = time.time() + 30
while time.time() < deadline:
    try:
        if len(json.load(open(path)).get("endpoints", [])) == want_n:
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.3)
sys.exit("%s never published %d endpoints" % (path, want_n))
EOF
  }
  echo "== migrate smoke: SIGKILL a replica mid-decode, clients resume =="
  CFLEET=127.0.0.1:9490,127.0.0.1:9491,127.0.0.1:9492
  for r in 0 1 2; do
    env "${MIG_ENV[@]}" FLAGS_tracing=1 \
      FLAGS_telemetry_dir="$MIG_DIR/tel" \
      python tools/serve.py --model dec="$MIG_DIR/dec" \
      --rank $r --fleet "$CFLEET" --decode-buckets 4,8 \
      --decode-mode token --endpoints-file "$MIG_DIR/ceps.json" \
      > "$MIG_DIR/c$r.log" 2>&1 &
    eval "C$r=\$!"
  done
  trap 'kill -9 $C0 $C1 $C2 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$MIG_DIR/c0.log" && grep -q READY "$MIG_DIR/c1.log" \
      && grep -q READY "$MIG_DIR/c2.log" && break
    sleep 1
  done
  grep -q READY "$MIG_DIR/c2.log"
  mig_wait_eps "$MIG_DIR/ceps.json" 3
  # warmth: replay the same seeded traffic against EACH replica
  # individually, so whichever survivor a crashed stream fails over to
  # already holds the session's full history chain; the last pass
  # doubles as the uninterrupted parity twin (same seed, same prompts)
  for port in 9490 9491 9492; do
    JAX_PLATFORMS=cpu python tools/loadgen.py \
      --endpoints 127.0.0.1:$port --model dec --requests 48 --qps 60 \
      --prompt-mix 8,16,24 --max-new 16 --deadline-ms 60000 \
      --retry-shed 4 --seed 20 --out "$MIG_DIR/BENCH_migrate_twin.json" \
      --assert-no-drops
  done
  # survivor compile-cache baseline: crash resume must reuse the
  # prewarmed lane buckets, so the miss counter may not move again
  python - "$MIG_DIR/miss0.json" <<'EOF'
import json, sys, time
from paddle_tpu.core import telemetry
time.sleep(1.2)   # one __metrics__ publish period
out = {}
for ep in ("127.0.0.1:9491", "127.0.0.1:9492"):
    snap = telemetry.scrape(ep)
    out[ep] = sum(v for k, v in snap.get("counters", {}).items()
                  if k.startswith("executor_cache_miss_total"))
json.dump(out, open(sys.argv[1], "w"))
EOF
  ( sleep 1.5; kill -9 $C0 2>/dev/null || true ) &
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$MIG_DIR/ceps.json" --model dec --requests 48 \
    --qps 30 --prompt-mix 8,16,24 --max-new 16 --deadline-ms 60000 \
    --retry-shed 4 --seed 20 --out "$MIG_DIR/BENCH_migrate_kill.json" \
    --assert-no-drops
  # the victim's write-through flight recorder must already name its
  # in-flight decode sessions on disk (req_ids ride the decode_step
  # notes; SIGKILL is uncatchable)
  grep -q decode_step "$MIG_DIR/tel/flightrec-$C0.json"
  echo "flight recorder OK: victim flightrec-$C0.json names live sessions"
  { python tools/metrics_dump.py --scrape 127.0.0.1:9491 --decode;
    python tools/metrics_dump.py --scrape 127.0.0.1:9492 --decode; } \
    | grep -c kv_migrate_resume_total > /dev/null
  python - "$MIG_DIR/BENCH_migrate_kill.json" \
    "$MIG_DIR/BENCH_migrate_twin.json" "$MIG_DIR/miss0.json" <<'EOF'
import json, sys, time
from paddle_tpu.core import telemetry
kill = json.load(open(sys.argv[1]))
twin = json.load(open(sys.argv[2]))
miss0 = json.load(open(sys.argv[3]))
assert kill["statuses"].get("ok") == kill["requests"], \
    "not every request answered across the SIGKILL: %s" % kill["statuses"]
assert kill["outputs_sha256"] == twin["outputs_sha256"], \
    "resumed outputs differ from the uninterrupted twin: %s != %s" \
    % (kill["outputs_sha256"], twin["outputs_sha256"])
res = kill.get("resume")
assert res and res["resumed_requests"] >= 1, \
    "no stream crash-resumed across the kill: %r" % (res,)
assert res["reprefill_tokens_max"] < 8, \
    "a resumed session re-fed %d tokens (>= one 8-token KV block): %r" \
    % (res["reprefill_tokens_max"], res["rows"])
time.sleep(1.2)   # one __metrics__ publish period
for ep, before in miss0.items():
    snap = telemetry.scrape(ep)
    after = sum(v for k, v in snap.get("counters", {}).items()
                if k.startswith("executor_cache_miss_total"))
    assert after == before, \
        "executor_cache_miss_total moved on %s: %s -> %s" \
        % (ep, before, after)
print("crash leg OK: %d resumed sessions, worst re-feed %d tokens, "
      "sha parity with the twin, survivor compile caches flat"
      % (res["resumed_requests"], res["reprefill_tokens_max"]))
EOF
  kill -9 $C1 $C2 2>/dev/null || true
  trap - EXIT
  echo "== migrate smoke: autoscale-down retirement drains by migration =="
  EFLEET=127.0.0.1:9494,127.0.0.1:9495,127.0.0.1:9496
  for r in 0 1 2; do
    # the retirement victim (rank 2) decodes with an injected 100 ms
    # per-iteration delay — its sessions are deterministically still
    # live when the drain scans, so the leg proves hand-off, not luck
    FS=""
    if [ "$r" = 2 ]; then FS="serving.decode_step:delay:1"; fi
    env "${MIG_ENV[@]}" FLAGS_migrate_on_drain=1 FLAGS_fault_spec="$FS" \
      python tools/serve.py --model dec="$MIG_DIR/dec" \
      --rank $r --fleet "$EFLEET" --decode-buckets 4,8 \
      --decode-mode token --endpoints-file "$MIG_DIR/eeps.json" \
      > "$MIG_DIR/e$r.log" 2>&1 &
    eval "E$r=\$!"
  done
  trap 'kill -9 $E0 $E1 $E2 2>/dev/null || true' EXIT
  for _ in $(seq 120); do
    grep -q READY "$MIG_DIR/e0.log" && grep -q READY "$MIG_DIR/e1.log" \
      && grep -q READY "$MIG_DIR/e2.log" && break
    sleep 1
  done
  grep -q READY "$MIG_DIR/e2.log"
  mig_wait_eps "$MIG_DIR/eeps.json" 3
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$MIG_DIR/eeps.json" --model dec --requests 60 \
    --qps 40 --prompt-mix 16,24 --max-new 24 --deadline-ms 60000 \
    --retry-shed 6 --seed 21 --out "$MIG_DIR/BENCH_drain_twin.json" \
    --assert-no-drops
  # retire rank 2 mid-flight: the coordinator stays up, the victim
  # drains by PUSHING its live sessions to the surviving peers
  ( sleep 0.7; python - <<'EOF'
import numpy as np
from paddle_tpu.native import rpc
from paddle_tpu.serving import codec
c = rpc.RpcClient("127.0.0.1:9496", connect_timeout=2.0,
                  rpc_deadline=5.0, retry_times=0)
try:
    c.send_var(codec.RETIRE_KEY, np.asarray([0], np.int64))
finally:
    c.close()
EOF
  ) &
  JAX_PLATFORMS=cpu python tools/loadgen.py \
    --endpoints-file "$MIG_DIR/eeps.json" --model dec --requests 60 \
    --qps 40 --prompt-mix 16,24 --max-new 24 --deadline-ms 60000 \
    --retry-shed 6 --seed 21 --out "$MIG_DIR/BENCH_migrate_drain.json" \
    --assert-no-drops
  # zero completion-wait stalls: the drained victim must exit promptly
  # (its 24-token generations moved, they were not waited out)
  for _ in $(seq 80); do
    kill -0 $E2 2>/dev/null || break
    sleep 0.5
  done
  if kill -0 $E2 2>/dev/null; then
    echo "CI --migrate-smoke: FAIL (retired replica never exited)"
    exit 1
  fi
  python - "$MIG_DIR/BENCH_migrate_drain.json" \
    "$MIG_DIR/BENCH_drain_twin.json" <<'EOF'
import json, sys, time
from paddle_tpu.core import telemetry
drain = json.load(open(sys.argv[1]))
twin = json.load(open(sys.argv[2]))
assert drain["statuses"].get("ok") == drain["requests"], \
    "not every request answered across the retirement: %s" \
    % drain["statuses"]
assert drain["outputs_sha256"] == twin["outputs_sha256"], \
    "post-drain outputs differ from the uninterrupted twin: %s != %s" \
    % (drain["outputs_sha256"], twin["outputs_sha256"])
res = drain.get("resume")
assert res and res["resumed_requests"] >= 1, \
    "retirement migrated no live session (completion-wait drain?): %r" \
    % (res,)
time.sleep(1.2)   # one __metrics__ publish period
accepted = 0
for ep in ("127.0.0.1:9494", "127.0.0.1:9495"):
    snap = telemetry.scrape(ep)
    accepted += sum(
        v for k, v in snap.get("counters", {}).items()
        if k.startswith("kv_migrate_resume_total")
        and "result=accepted" in k)
assert accepted >= 1, "no survivor admitted a migrated session"
print("drain leg OK: %d sessions followed the hand-off, %d resume "
      "admissions on the survivors, sha parity with the twin"
      % (res["resumed_requests"], accepted))
EOF
  kill -9 $E0 $E1 2>/dev/null || true
  trap - EXIT
  rm -rf "$MIG_DIR"
  echo "CI --migrate-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--ckpt-smoke" ]; then
  # checkpoint leg: manager unit tests (async writer, sharded layout,
  # crash consistency, temp GC, validity cache), then the stall probe —
  # an async save may not stall the step loop more than 5% of a step
  # (the BASELINE validity bar) — and the telemetry round trip through
  # the metrics_dump --checkpoint CLI filter
  echo "== ckpt smoke: checkpoint manager tests =="
  JAX_PLATFORMS=cpu FLAGS_static_check=error \
    python -m pytest tests/test_checkpoint_resume.py -q
  echo "== ckpt smoke: async save stall probe (<5% of step) =="
  CKPT_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu FLAGS_telemetry=1 FLAGS_telemetry_dir="$CKPT_DIR/tel" \
    python tools/ckpt_stall_probe.py --steps 16 --save-every 4 \
      --batch 4096 --hidden 512 --ckpt-dir "$CKPT_DIR/ckpt" \
      --assert-stall-frac 0.05 --out "$CKPT_DIR/probe.json"
  echo "== ckpt smoke: metrics_dump --checkpoint round trip =="
  python tools/metrics_dump.py --json "$CKPT_DIR/tel/metrics.json" \
    --checkpoint --prom | grep -q checkpoint_save_stall_ms
  rm -rf "$CKPT_DIR"
  echo "CI --ckpt-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--trace-smoke" ]; then
  # distributed-tracing leg: the tracing unit tests, then a live
  # 2-replica fleet under FLAGS_tracing=1 — the per-process trace JSONL
  # files must merge into one Perfetto-loadable trace.json containing at
  # least one cross-process flow (client span -> replica span)
  echo "== trace smoke: tracing tests =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q
  echo "== trace smoke: 2-replica fleet under FLAGS_tracing=1 =="
  TRC_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu python tools/serve.py --save-demo-model "$TRC_DIR/model"
  TRC_ENV=(JAX_PLATFORMS=cpu FLAGS_tracing=1 FLAGS_telemetry=1
           FLAGS_telemetry_dir="$TRC_DIR/tel"
           FLAGS_serving_hb_interval=0.2 FLAGS_serving_hb_timeout=1.5
           FLAGS_compile_cache_dir="$TRC_DIR/cc")
  env "${TRC_ENV[@]}" python tools/serve.py --model fc="$TRC_DIR/model" \
    --rank 0 --fleet 127.0.0.1:9470,127.0.0.1:9471 --buckets 1,4 \
    --endpoints-file "$TRC_DIR/eps.json" > "$TRC_DIR/r0.log" 2>&1 &
  T0=$!
  env "${TRC_ENV[@]}" python tools/serve.py --model fc="$TRC_DIR/model" \
    --rank 1 --fleet 127.0.0.1:9470,127.0.0.1:9471 --buckets 1,4 \
    --endpoints-file "$TRC_DIR/eps.json" > "$TRC_DIR/r1.log" 2>&1 &
  T1=$!
  trap 'kill -9 $T0 $T1 2>/dev/null || true' EXIT
  for _ in $(seq 60); do
    grep -q READY "$TRC_DIR/r0.log" && grep -q READY "$TRC_DIR/r1.log" \
      && break
    sleep 1
  done
  grep -q READY "$TRC_DIR/r0.log" && grep -q READY "$TRC_DIR/r1.log"
  env "${TRC_ENV[@]}" python tools/loadgen.py \
    --endpoints-file "$TRC_DIR/eps.json" --model fc --requests 40 \
    --qps 40 --out "$TRC_DIR/BENCH_serving.json" --assert-no-drops
  kill $T0 $T1 2>/dev/null || true
  wait $T0 $T1 2>/dev/null || true
  trap - EXIT
  # one trace.json over client + both replicas, >=1 cross-process flow
  python tools/trace_view.py --telemetry_dir "$TRC_DIR/tel" \
    --out "$TRC_DIR/trace.json" --require-flow
  python -c "import json,sys; json.load(open(sys.argv[1]))" \
    "$TRC_DIR/trace.json"
  rm -rf "$TRC_DIR"
  echo "CI --trace-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--obs-smoke" ]; then
  # observability fast leg: telemetry + timeline-tool tests, then a tiny
  # telemetry-on executor run dumped and re-read through the CLI
  echo "== obs smoke: telemetry + timeline tests =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py \
    tests/test_timeline_tool.py tests/test_profiler_metrics.py -q
  echo "== obs smoke: dump -> metrics_dump round trip =="
  OBS_DIR="$(mktemp -d)"
  JAX_PLATFORMS=cpu FLAGS_telemetry=1 FLAGS_telemetry_dir="$OBS_DIR" \
    python tools/profile_bert_step.py --steps 2 --tiny --no-trace
  python tools/metrics_dump.py --json "$OBS_DIR/metrics.json"
  python tools/metrics_dump.py --json "$OBS_DIR/metrics.json" --prom \
    | grep -q executor_steps_total
  rm -rf "$OBS_DIR"
  echo "CI --obs-smoke: PASS"
  exit 0
fi

if [ "$MODE" = "--layout-smoke" ]; then
  # layout/carry fast leg: the HLO-level regression test (compiled AMP
  # step has no per-step f32 converts of carried params) plus a tiny
  # 2-step CPU dry pass of the profiler harness with the HBM audit on
  echo "== layout smoke: HLO regression test =="
  JAX_PLATFORMS=cpu python -m pytest tests/test_layout_match.py -q
  echo "== layout smoke: profile_bert_step CPU dry pass =="
  JAX_PLATFORMS=cpu python tools/profile_bert_step.py --steps 2 --tiny \
    --audit --no-trace
  echo "CI --layout-smoke: PASS"
  exit 0
fi

echo "== native build (compiles on import) =="
python -c "import paddle_tpu.native; print('native OK')"

echo "== unit + integration tests (virtual 8-device CPU mesh) =="
case "$MODE" in
  quick)
    python -m pytest tests/ -x -q -k "not subprocess and not torch_parity" ;;
  tpu)
    # real-chip tier (needs a TPU host)
    PADDLE_TPU_TESTS=1 python -m pytest tests/ -m tpu -q ;;
  *)
    python -m pytest tests/ -x -q ;;
esac

echo "== multichip dryrun (8-device virtual mesh) =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

if [ "$MODE" = "tpu" ]; then
  echo "== bench (real chip) =="
  python bench.py
fi

echo "CI $MODE: PASS"
