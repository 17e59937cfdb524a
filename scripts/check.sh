#!/usr/bin/env bash
# Repo check gate: the ROADMAP.md tier-1 pytest run plus a live
# /metrics scrape smoke test, so telemetry regressions fail fast.
# Usage: scripts/check.sh [--smoke-only]
set -u -o pipefail

cd "$(dirname "$0")/.."

rc=0

echo "== pio-tpu lint (static analysis gate, docs/static_analysis.md) =="
# AST-based concurrency/compilation-discipline analyzer: lock-order
# cycles, blocking-under-lock, wall-clock misuse, device syncs on the
# dispatch path, jit retrace hazards, mesh/PartitionSpec hygiene,
# donated-buffer reuse, thread lifecycle, telemetry hygiene. Pure
# stdlib (no jax), so it runs first and fails fast; findings outside
# scripts/lint_baseline.txt are NEW and block the gate. On GitHub
# Actions the findings double as ::error workflow annotations inline
# on the PR diff (--format github).
lint_fmt=()
if [ -n "${GITHUB_ACTIONS:-}" ]; then
    lint_fmt=(--format github)
fi
lint_start=$SECONDS
# the linted surface includes the test CHILD processes (tests/*_child.py
# run as real separate processes in the smokes, so they participate in
# the wire contract) but not the rest of tests/
if ! timeout -k 10 120 python -m predictionio_tpu.cli.main lint \
    predictionio_tpu scripts tests/*_child.py \
    ${lint_fmt[@]+"${lint_fmt[@]}"}; then
    echo "pio-tpu lint FAILED (new findings — fix, suppress with a"
    echo "reason, or accept via: pio-tpu lint --write-baseline)"
    rc=1
fi
lint_dur=$((SECONDS - lint_start))
# the rule set keeps growing; a lint gate that creeps past 30 s stops
# being the "fails fast" first step (per-checker timingsMs is in
# `pio-tpu lint --json`, alongside the parse/index cache hit rate —
# find the regressing checker there)
if [ "$lint_dur" -gt 30 ]; then
    echo "pio-tpu lint exceeded the 30 s CI budget (${lint_dur}s) —"
    echo "check timingsMs in: pio-tpu lint --json"
    rc=1
fi

echo "== lint policy gate (empty baseline + reasoned suppressions) =="
# the empty-baseline policy is a GATE, not a convention: the shipped
# scripts/lint_baseline.txt must have zero entries, and every inline
# `# pio-lint: disable...` must carry a `-- <reason>` tail
if ! timeout -k 10 60 python scripts/lint_policy_gate.py; then
    echo "lint policy gate FAILED (see docs/static_analysis.md)"
    rc=1
fi

if [ "${1:-}" != "--smoke-only" ]; then
    echo "== tier-1 pytest (ROADMAP.md) =="
    rm -f /tmp/_t1.log
    timeout -k 10 870 env JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
    t1_rc=${PIPESTATUS[0]}
    echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
    if [ "$t1_rc" -ne 0 ]; then
        echo "tier-1 pytest FAILED (rc=$t1_rc)"
        rc=1
    fi
fi

echo "== telemetry smoke test (live /metrics scrape) =="
# also asserts per-tenant cost attribution under mixed-tenant traffic
# (summed pio_tenant_device_seconds_total == the batcher's measured
# device time within 1%, locally AND in the router's fleet merge) and
# the federated incident timeline (/debug/timeline.json time-ordered
# across 2 replicas with one SIGKILLed mid-run: stale, not absent) --
# docs/observability.md "Cost attribution" / "Incident timeline"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python scripts/metrics_smoke.py; then
    echo "telemetry smoke test FAILED"
    rc=1
fi

echo "== chaos smoke test (resilience layer, docs/robustness.md) =="
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python scripts/chaos_smoke.py; then
    echo "chaos smoke test FAILED"
    rc=1
fi

echo "== store HA smoke test (replicated tier, docs/storage.md) =="
# kill -9 the primary store node under continuous ingest: zero
# ack'd-write loss through the W-of-N quorum, a generation published
# during the outage loads from a replica, and the restarted node
# converges via hinted handoff + anti-entropy (merged timeline shows
# the repair)
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/store_ha_smoke.py; then
    echo "store HA smoke test FAILED"
    rc=1
fi

echo "== serving pipeline bench (closed + open loop) =="
# BENCH-format JSON lands on stdout AND is appended to
# SERVING_BENCH.json (serving-bench/v1) so the perf trajectory is
# recorded, not just printed
if ! timeout -k 10 180 env JAX_PLATFORMS=cpu \
    python scripts/serving_bench.py --smoke; then
    echo "serving pipeline bench FAILED"
    rc=1
fi

echo "== multichip scaling bench (sharded ALS, docs/parallelism.md) =="
# 1->2->4->8 simulated host devices: fused sharded epoch + two-phase
# sharded serving step, weak+strong curves appended to MULTICHIP.json.
# Always gated: worker health + sharded-vs-replicated factor equality;
# the >=1.6x strong floor at 4 devices gates only on runners with the
# cores to show it (virtual devices time-share cores otherwise). The
# outer bound leaves headroom over the bench's own 4x150s per-worker
# budgets so a hang is attributed to a WORKER (diagnostic + persisted
# error record), not a bare outer SIGTERM
if ! timeout -k 10 780 env JAX_PLATFORMS=cpu \
    python scripts/multichip_bench.py --smoke; then
    echo "multichip scaling bench FAILED"
    rc=1
fi

echo "== overload smoke test (admission control plane, docs/robustness.md) =="
# baseline collapse vs admission-controlled goodput at 2x saturation
# (recorded into SERVING_BENCH.json) + the HTTP wiring: computed
# Retry-After on sheds, criticality ordering, limiter gauges
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python scripts/overload_smoke.py; then
    echo "overload smoke test FAILED"
    rc=1
fi

echo "== router smoke test (scale-out tier, docs/scale_out.md) =="
# 2 real replicas behind the router: SIGKILL + respawn chaos, rolling
# generation swap, one trace ID spanning router→replica→store
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/router_smoke.py; then
    echo "router smoke test FAILED"
    rc=1
fi

echo "== fleet smoke test (crash-safe fleet control plane, docs/scale_out.md) =="
# kill -9 matrix: router mid-gate (abort to old generation) and
# mid-watch (resume to new), promotion driver mid-promotion (token
# idempotency: ONE fleet gate per generation), staged replica
# mid-canary (gate veto) — all under continuous traffic with zero
# non-200 final outcomes and convergence to exactly one serving
# generation
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/fleet_smoke.py; then
    echo "fleet smoke test FAILED"
    rc=1
fi

echo "== fleet autoscaling ramp bench (docs/scale_out.md) =="
# open-loop offered QPS doubles mid-run against the real router +
# autoscaler: replicas scale 2->4, goodput follows, QPS-per-replica
# stays within 25% across phases ($/QPS flat) — recorded to
# SERVING_BENCH.json as serving_fleet_ramp
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python scripts/serving_bench.py --ramp --smoke; then
    echo "fleet ramp bench FAILED"
    rc=1
fi

echo "== serving density bench (multi-tenant model pool, docs/serving.md) =="
# models-resident x QPS per chip, int8 vs f32 under one byte budget:
# int8 must hold >= 2x the tenants at goodput parity with the recall
# gate met — recorded to SERVING_BENCH.json as serving-density/v1;
# each pass also records per-tenant attributed device-seconds
# (attributed_device_s + per_tenant) so the density record doubles as
# a cost-attribution fixture.
# QPS parity is recorded-not-gated when the f32 baseline is degenerate
# on the runner (< 5 QPS); capacity and recall always gate
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python scripts/serving_bench.py --density --smoke; then
    echo "serving density bench FAILED"
    rc=1
fi

echo "== density smoke test (pooled multi-tenant serving, docs/serving.md) =="
# 2 pooled 3-tenant replicas behind the router under a budget that
# forces LRU thrash: tenant-keyed answers stay correct through
# evictions racing in-flight queries, a SIGKILL'd pooled replica
# rides through losslessly, and per-tenant /reload bumps only its
# tenant's generation
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/density_smoke.py; then
    echo "density smoke test FAILED"
    rc=1
fi

echo "== serving cache bench (generation-keyed cache, docs/serving.md) =="
# skewed traffic (shared Zipf keys, alpha 0.9 and 1.1) with the cache
# on vs off: at alpha=1.1 cached QPS must beat uncached by the floor
# with hit-path p99 under the uncached p50, and EVERY answer must be
# byte-identical cache-on vs cache-off (equality always gates; the
# speedup gate is recorded-not-gated when the uncached baseline is
# degenerate on the runner, < 5 QPS) — recorded to SERVING_BENCH.json
# as serving-cache/v1
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python scripts/serving_bench.py --skew --smoke; then
    echo "serving cache bench FAILED"
    rc=1
fi

echo "== cache smoke test (generation-keyed serving cache, docs/serving.md) =="
# every swap path flushes: immediate /reload, canary promotion,
# automatic rollback (the OLD generation's answers come back), and
# trainer fold-in each land a cache_flush{reason} timeline event with
# zero stale answers under continuous traffic; Cache-Control: no-cache
# bypasses; eviction bursts emit cache_pressure; X-PIO-Cache crosses
# the router and federated pio_cache_* counters conserve
# (fleet == sum of replicas)
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/cache_smoke.py; then
    echo "cache smoke test FAILED"
    rc=1
fi

echo "== trainer smoke test (crash-safe continuous training, docs/training.md) =="
# supervised trainer killed -9 mid-epoch resumes from checkpoint;
# fold-in freshness recorded to SERVING_BENCH.json; corrupt artifact
# quarantined with last-good serving; NaN generation rejected at the
# canary gate; post-promotion regression auto-rolls-back — zero
# non-200s under continuous traffic throughout
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/trainer_smoke.py; then
    echo "trainer smoke test FAILED"
    rc=1
fi

exit $rc
