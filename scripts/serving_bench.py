"""Serving-pipeline bench: the micro-batcher on a synthetic device.

A CPU rig, not a measurement of the chip (that is ``benchmarks/chip``):
a ``TwoPhaseBatchFn`` whose ``dispatch`` pays a host enqueue cost and
reserves a window on a simulated serial accelerator, and whose
``collect`` blocks until that window elapses (the "device barrier")
then pays a host decode cost. The batcher's collector enqueues batch
N+1 while its completer is still inside batch N, so a cycle costs
~max(device, host). That the two overlap is held by
``tests/test_batching_pipeline.py`` on events; this script gates what
needs load: a sustained open-loop rate and the overload discipline.

Load comes in two shapes:

* **closed loop**: one submitter keeps ``--window`` requests in flight
  (done-callbacks refill the window), which saturates the batcher
  without the GIL thrash of a thread per simulated client. Its QPS is
  the capacity the other passes are offered against;
* **open loop** (``--open-rate``, on by default): requests arrive on a
  FIXED schedule (request i at ``t0 + i/rate``) regardless of how fast
  earlier ones complete — the shape real traffic has, and the one
  closed loops systematically flatter (coordinated omission: a slow
  server slows its own offered load). Reports achieved QPS and
  p50/p95/p99 under the offered rate, and fails when less than 90% of
  the offered rate is sustained.

**Overload mode** (on by default, ``--no-overload`` to skip): open-loop
load at 2× the measured pipelined capacity, twice. The *baseline* pass
is the pre-admission stack (unbounded queue, no deadlines, no
controller) — the PR 6 collapse: the queue grows without bound and
almost nothing completes inside its nominal deadline. The *admitted*
pass runs the same offered load through
:class:`~predictionio_tpu.serving.admission.AdmissionController` with
propagated deadlines and a 20/60/20 critical/default/sheddable mix:
the adaptive limit tracks capacity, the lowest class sheds first, and
goodput (completions inside the deadline) stays ≥80% of capacity while
critical-class p99 stays inside the deadline. Both passes land in the
record (``extra.overload``) so the collapse-vs-controlled contrast is
a recorded number, not a claim.

The last stdout line is a BENCH-format JSON record
(``{"metric": "serving_closed_loop_qps", ...}``), and every run is also APPENDED to
``SERVING_BENCH.json`` at the repo root (schema ``serving-bench/v1``:
``{"schema": ..., "runs": [record + recordedAtUtc, ...]}``, last 100
kept) so serving-tier scaling claims cite recorded numbers, not one-off
stdout. ``--smoke`` shrinks the run for CI (scripts/check.sh wires it
in); ``--out ''`` disables persistence.

**Density mode** (``--density``): the multi-tenant model-pool proof,
measured as models-resident × aggregate QPS per chip. N synthetic
tenants' factor tables are served through a byte-budgeted
:class:`~predictionio_tpu.serving.modelpool.ModelPool` twice — f32
tables, then per-row int8 (``ops/quantize``) — under a skewed tenant
mix. Gates: int8 fits ≥2× the f32 tenant count in the SAME byte budget
(deterministic byte math, hard), int8 recall@k against the f32 ranking
stays above the floor (hard), and aggregate QPS holds goodput parity
(gated with a recorded-not-gated degenerate escape when the runner
itself collapses). The dequantizing Pallas kernel is timed against the
jitted XLA fallback and recorded labeled with ``interpret`` — on CPU
the kernel runs in interpreter mode, so that latency is recorded for
trend only, never gated. Lands in SERVING_BENCH.json as a
``serving-density/v1`` record.

**Skew mode** (``--skew``): the generation-keyed serving-cache proof
under Zipf-skewed traffic (shared key generator, scripts/bench_keys.py
— the same distribution --density uses for its tenant mix). Each
α ∈ {0.9, 1.1} runs a closed-loop pass twice over one key sequence —
cache OFF (every request pays a batcher slot on the simulated device)
and cache ON (a real :class:`~predictionio_tpu.serving.querycache
.QueryCache`, byte-budgeted to hold only ~a quarter of the key space
so the LRU must keep the Zipf head) — and records QPS, hit/miss/
coalesced counts, and hit-path p50/p99. Gates: byte-identical answers
per key across BOTH passes (always — same generation ⇒ same bytes),
and at α=1.1 cached QPS ≥ ``--skew-floor``× uncached with hit-path
p99 below the uncached p50 (the speedup floor takes the same
recorded-not-gated degenerate-runner escape as --density when the
uncached baseline itself collapses). Lands in SERVING_BENCH.json as a
``serving-cache/v1`` record.

No jax import outside ``--density`` — the pipeline modes exercise the
batcher itself, so they run in seconds on any CPU-only runner.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # the package itself (no install required)

from predictionio_tpu.obs import MetricRegistry  # noqa: E402
from predictionio_tpu.serving import admission  # noqa: E402
from predictionio_tpu.serving import resilience  # noqa: E402
from predictionio_tpu.serving.batching import (  # noqa: E402
    MicroBatcher,
    TwoPhaseBatchFn,
)


class SimDevice:
    """A serial accelerator: one compute queue, fixed per-batch time.

    ``dispatch`` models JAX async dispatch — it spins for the host
    enqueue cost (CPU work, holds the GIL like a real enqueue),
    reserves the device's next free window, and returns immediately.
    ``collect`` models the barrier — it blocks until the reserved
    window has elapsed, then sleeps for the host decode cost (stage
    occupancy is what the pipeline overlaps; a sleep keeps the
    measurement deterministic on small CI runners).
    """

    def __init__(self, device_s: float, enqueue_s: float, decode_s: float):
        self.device_s = device_s
        self.enqueue_s = enqueue_s
        self.decode_s = decode_s
        self._lock = threading.Lock()
        self._free_at = 0.0
        self.batches = 0

    def dispatch(self, items):
        end = time.perf_counter() + self.enqueue_s
        while time.perf_counter() < end:
            pass
        with self._lock:
            start = max(time.monotonic(), self._free_at)
            done_at = start + self.device_s
            self._free_at = done_at
            self.batches += 1
        return done_at, list(items)

    def collect(self, handle):
        done_at, items = handle
        delay = done_at - time.monotonic()
        if delay > 0:
            time.sleep(delay)  # the device barrier
        time.sleep(self.decode_s)  # host result materialization
        return [i * 2 for i in items]


def run_mode(
    *, pipeline_depth: int, window: int, requests: int,
    max_batch: int, max_wait_ms: float, device_ms: float,
    enqueue_ms: float, decode_ms: float,
) -> dict:
    dev = SimDevice(
        device_ms / 1000.0, enqueue_ms / 1000.0, decode_ms / 1000.0
    )
    batcher = MicroBatcher(
        TwoPhaseBatchFn(dev.dispatch, dev.collect),
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        max_queue=0,  # the window bounds in-flight work; don't shed
        pipeline_depth=pipeline_depth,
        name=f"bench-depth{pipeline_depth}",
    )
    sem = threading.Semaphore(window)
    latencies: list[float] = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    for i in range(requests):
        sem.acquire()
        submitted = time.perf_counter()

        def refill(fut, submitted=submitted):
            with lock:
                latencies.append(time.perf_counter() - submitted)
            sem.release()

        batcher.submit(i).add_done_callback(refill)
    for _ in range(window):  # wait for the tail of the window
        sem.acquire()
    elapsed = time.perf_counter() - t0
    batcher.close()
    latencies.sort()
    n = len(latencies)
    return {
        "qps": round(n / elapsed, 1),
        "p50_ms": round(latencies[n // 2] * 1000, 3),
        "p99_ms": round(latencies[min(n - 1, int(n * 0.99))] * 1000, 3),
        "occupancy": round(n / max(1, dev.batches), 1),
        "batches": dev.batches,
        "requests": n,
        "elapsed_s": round(elapsed, 3),
    }


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(len(sorted_vals) * q))
    return sorted_vals[idx]


def run_open_loop(
    *, rate_qps: float, duration_s: float, pipeline_depth: int,
    max_batch: int, max_wait_ms: float, device_ms: float,
    enqueue_ms: float, decode_ms: float,
) -> dict:
    """Fixed-arrival-rate load: request i is submitted at
    ``t0 + i/rate`` whether or not earlier requests finished, and its
    latency is measured from its SCHEDULED time — late submission
    (harness backpressure) counts against the server, not the clock.
    That is the open-loop discipline closed loops can't give: achieved
    QPS below the offered rate, or a p99 blowup, means the
    configuration cannot sustain the load."""
    dev = SimDevice(
        device_ms / 1000.0, enqueue_ms / 1000.0, decode_ms / 1000.0
    )
    batcher = MicroBatcher(
        TwoPhaseBatchFn(dev.dispatch, dev.collect),
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        max_queue=0,
        pipeline_depth=pipeline_depth,
        name=f"bench-open-depth{pipeline_depth}",
    )
    total = max(1, int(rate_qps * duration_s))
    interval = 1.0 / rate_qps
    latencies: list[float] = []
    done = threading.Semaphore(0)
    lock = threading.Lock()
    t0 = time.perf_counter()
    for i in range(total):
        scheduled = t0 + i * interval
        delay = scheduled - time.perf_counter()
        if delay > 0:
            time.sleep(delay)

        def record(fut, scheduled=scheduled):
            with lock:
                latencies.append(time.perf_counter() - scheduled)
            done.release()

        batcher.submit(i).add_done_callback(record)
    for _ in range(total):
        done.acquire()
    elapsed = time.perf_counter() - t0
    batcher.close()
    latencies.sort()
    n = len(latencies)
    return {
        "offered_qps": round(rate_qps, 1),
        "achieved_qps": round(n / elapsed, 1),
        "p50_ms": round(_percentile(latencies, 0.50) * 1000, 3),
        "p95_ms": round(_percentile(latencies, 0.95) * 1000, 3),
        "p99_ms": round(_percentile(latencies, 0.99) * 1000, 3),
        "requests": n,
        "elapsed_s": round(elapsed, 3),
    }


#: 20% critical / 60% default / 20% sheddable, cycled per request
_CLASS_MIX = (
    admission.CRITICAL,
    admission.DEFAULT, admission.DEFAULT, admission.DEFAULT,
    admission.SHEDDABLE,
)


def run_overload(
    *, capacity_qps: float, duration_s: float, deadline_ms: float,
    pipeline_depth: int, max_batch: int, max_wait_ms: float,
    device_ms: float, enqueue_ms: float, decode_ms: float,
    admit: bool,
) -> dict:
    """Open-loop load at 2× ``capacity_qps`` with a criticality mix.

    ``admit=False`` is the pre-admission stack: unbounded queue, no
    deadline propagation, no controller — latency grows with the
    backlog and goodput (completion within ``deadline_ms`` of the
    SCHEDULED time) collapses. ``admit=True`` runs the same offered
    load through an :class:`AdmissionController` with per-request
    deadlines: the limiter tracks capacity, sheds carry the class that
    was refused, and goodput holds near capacity."""
    dev = SimDevice(
        device_ms / 1000.0, enqueue_ms / 1000.0, decode_ms / 1000.0
    )
    batcher = MicroBatcher(
        TwoPhaseBatchFn(dev.dispatch, dev.collect),
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        max_queue=0,  # the controller (when on) is the bound under test
        pipeline_depth=pipeline_depth,
        name=f"bench-overload-{'adm' if admit else 'base'}",
    )
    controller = (
        admission.AdmissionController(
            "bench-overload",
            registry=MetricRegistry(),
            config=admission.AdmissionConfig(
                # same floor the engine server applies: one full
                # pipeline of batches stays admissible, or the limiter
                # starves the device without helping latency
                min_limit=float(max_batch * (pipeline_depth + 1)),
            ),
        )
        if admit
        else None
    )
    deadline_s = deadline_ms / 1000.0
    rate = capacity_qps * 2.0
    total = max(1, int(rate * duration_s))
    interval = 1.0 / rate
    # the first quarter is warm-up (threads spinning up, limiter
    # settling): exercised but excluded from the goodput accounting
    warmup_s = duration_s * 0.25
    stats = {
        cls: {"offered": 0, "shed": 0, "good": 0, "latencies": []}
        for cls in (
            admission.CRITICAL, admission.DEFAULT, admission.SHEDDABLE
        )
    }
    completions = [0]
    lock = threading.Lock()
    done = threading.Semaphore(0)
    submitted = 0
    # the baseline pass must not inherit a deadline/class left in the
    # submitter thread's context by an earlier pass
    resilience.set_deadline(None)
    admission.set_criticality(admission.DEFAULT)
    t0 = time.perf_counter()
    for i in range(total):
        scheduled = t0 + i * interval
        delay = scheduled - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        cls = _CLASS_MIX[i % len(_CLASS_MIX)]
        counted = scheduled - t0 >= warmup_s
        if counted:
            stats[cls]["offered"] += 1
        if admit:
            resilience.set_deadline(resilience.Deadline.after(deadline_s))
            admission.set_criticality(cls)
            try:
                controller.try_acquire(cls)
            except admission.AdmissionRejected:
                if counted:
                    stats[cls]["shed"] += 1
                continue
        try:
            future = batcher.submit(i)
        except Exception:  # DeadlineExceeded / BatcherOverloaded
            if admit:
                controller.release(0.0, admission.OUTCOME_DROP)
            if counted:
                stats[cls]["shed"] += 1
            continue

        def record(fut, scheduled=scheduled, cls=cls, counted=counted):
            latency = time.perf_counter() - scheduled
            served = fut.exception() is None
            with lock:
                completions[0] += 1
                if counted:
                    stats[cls]["latencies"].append(latency)
                    if served and latency <= deadline_s:
                        stats[cls]["good"] += 1
            if admit:
                # served-in-budget is a latency sample; a miss (late or
                # dropped pre-dispatch) is the AIMD backoff signal
                controller.release(
                    latency,
                    admission.OUTCOME_OK
                    if served and latency <= deadline_s
                    else admission.OUTCOME_DROP,
                )
            done.release()

        future.add_done_callback(record)
        submitted += 1
    for _ in range(submitted):
        done.acquire()
    elapsed = time.perf_counter() - t0
    batcher.close()
    resilience.set_deadline(None)
    admission.set_criticality(admission.DEFAULT)

    counted_window = max(0.001, elapsed - warmup_s)
    out = {
        "offered_qps": round(rate, 1),
        "goodput_qps": round(
            sum(s["good"] for s in stats.values()) / counted_window, 1
        ),
        # raw completion throughput regardless of lateness: for the
        # baseline pass this IS the rig's measured capacity (the device
        # stays saturated), which self-normalizes the goodput ratio
        # against machine noise between passes
        "served_qps": round(completions[0] / elapsed, 1),
        "elapsed_s": round(elapsed, 3),
    }
    if admit:
        out["limit"] = round(controller.limiter.limit, 1)
    for cls, s in stats.items():
        lat = sorted(s["latencies"])
        out[cls] = {
            "offered": s["offered"],
            "shed": s["shed"],
            "good": s["good"],
            "shed_ratio": round(s["shed"] / max(1, s["offered"]), 3),
            "good_ratio": round(s["good"] / max(1, s["offered"]), 3),
            "p99_ms": round(_percentile(lat, 0.99) * 1000, 3),
        }
    return out


def run_ramp(
    *, base_rate: float, phase_s: float, capacity: int,
    service_ms: float, min_replicas: int = 2, max_replicas: int = 4,
    deadline_s: float = 1.0,
) -> dict:
    """Open-loop fleet ramp over the REAL control plane: an in-process
    :class:`~predictionio_tpu.serving.router.ServingRouter` with the
    replica autoscaler spawning jax-free replica processes
    (``tests/fleet_replica_child.py``, ``capacity`` concurrent ×
    ``service_ms`` each — a hard per-replica throughput ceiling).

    Phase A offers ``base_rate`` QPS (inside 2 replicas' capacity);
    phase B DOUBLES it mid-run, pushing the fleet past saturation —
    replicas shed 503+Retry-After, the router marks them saturated,
    the autoscaler scales out, and goodput follows the offered load.
    Per-phase goodput, replica count, and QPS-per-replica land in the
    record: the $/QPS-stays-flat claim (replica count IS the cost
    axis) cites these numbers, not a narrative. The accounting window
    for each phase is its second half, so scale-out reaction time is
    exercised but does not blur the steady-state comparison."""
    import concurrent.futures
    import logging
    import urllib.error
    import urllib.request

    from predictionio_tpu.obs import MetricRegistry
    from predictionio_tpu.serving.autoscaler import (
        AutoscalerConfig,
        ReplicaAutoscaler,
        ReplicaSpawner,
    )
    from predictionio_tpu.serving.router import ServingRouter

    # per-request INFO access/failover log lines are real CPU on the
    # 2-core CI rig the bench shares with its own fleet — the ramp
    # measures the fleet, not json.dumps
    logging.getLogger("predictionio_tpu").setLevel(logging.WARNING)
    child = os.path.join(REPO, "tests", "fleet_replica_child.py")
    router = ServingRouter(
        probe_interval_s=0.1,
        failover_retries=1,
        proxy_timeout_s=10.0,
        registry=MetricRegistry(),
    )
    autoscaler = ReplicaAutoscaler(
        router,
        ReplicaSpawner(
            [
                sys.executable, child,
                "--port", "{port}",
                "--generation", "{generation}",
                "--capacity", str(capacity),
                "--service-ms", str(service_ms),
            ],
        ),
        config=AutoscalerConfig(
            min_replicas=min_replicas,
            max_replicas=max_replicas,
            interval_s=0.2,
            shrink_after_ticks=10_000,  # the ramp only scales OUT
        ),
        registry=MetricRegistry(),
    ).start()
    http = router.serve(host="127.0.0.1", port=0)
    http.start()
    base = f"http://127.0.0.1:{http.port}"
    body = json.dumps({"x": 7}).encode()

    def one_query(scheduled: float) -> tuple[int, float]:
        req = urllib.request.Request(
            base + "/queries.json", data=body, method="POST"
        )
        req.add_header("Content-Type", "application/json")
        try:
            with urllib.request.urlopen(req, timeout=5) as resp:
                resp.read()
                status = resp.status
        except urllib.error.HTTPError as e:
            e.read()
            status = e.code
        except OSError:
            status = -1
        return status, time.perf_counter() - scheduled

    try:
        # wait for the autoscaler to reach its floor
        deadline_boot = time.monotonic() + 60
        while time.monotonic() < deadline_boot:
            if router.autoscaler_signals()["healthy"] >= min_replicas:
                break
            time.sleep(0.1)

        phases = []
        pool = concurrent.futures.ThreadPoolExecutor(max_workers=64)
        # client warm-up at a gentle rate: thread spin-up and
        # interpreter warm-up otherwise burst the very first arrivals,
        # shed, and scale the pool out before phase A even starts
        warm_deadline = time.monotonic() + 2.0
        while time.monotonic() < warm_deadline:
            pool.submit(one_query, time.perf_counter())
            time.sleep(4.0 / max(base_rate, 1.0))
        try:
            for name, rate in (("base", base_rate),
                               ("doubled", base_rate * 2.0)):
                results: list[tuple[int, float, bool]] = []
                replica_samples: list[int] = []
                lock = threading.Lock()
                stop_sampling = threading.Event()

                def sample_replicas():
                    while not stop_sampling.wait(0.1):
                        replica_samples.append(
                            router.autoscaler_signals()["healthy"]
                        )

                sampler = threading.Thread(
                    target=sample_replicas, daemon=True
                )
                total = max(1, int(rate * phase_s))
                # steady-state accounting: the last third of the phase
                # (spawning a replica process + its warmup admission
                # takes seconds on a small runner — that reaction time
                # is exercised, not measured)
                counted_after = phase_s * (2.0 / 3.0)
                t0 = time.perf_counter()
                pending = []

                def record_result(status, latency, counted):
                    with lock:
                        results.append((status, latency, counted))

                sampler_started = False
                for i in range(total):
                    scheduled = t0 + i / rate
                    delay = scheduled - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    counted = scheduled - t0 >= counted_after
                    if counted and not sampler_started:
                        sampler_started = True
                        sampler.start()

                    def run(scheduled=scheduled, counted=counted):
                        status, latency = one_query(scheduled)
                        record_result(status, latency, counted)

                    pending.append(pool.submit(run))
                for fut in pending:
                    fut.result(timeout=60)
                stop_sampling.set()
                if sampler_started:
                    sampler.join(timeout=5)
                counted_results = [r for r in results if r[2]]
                good = [
                    r for r in counted_results
                    if r[0] == 200 and r[1] <= deadline_s
                ]
                shed = [r for r in counted_results if r[0] == 503]
                window_s = max(0.001, phase_s - counted_after)
                replicas = (
                    sum(replica_samples) / len(replica_samples)
                    if replica_samples
                    else 0.0
                )
                goodput = len(good) / window_s
                phases.append({
                    "phase": name,
                    "offered_qps": round(rate, 1),
                    "goodput_qps": round(goodput, 1),
                    "shed": len(shed),
                    "requests_counted": len(counted_results),
                    "replicas": round(replicas, 2),
                    "replicas_end": (
                        replica_samples[-1] if replica_samples else 0
                    ),
                    "qps_per_replica": round(
                        goodput / max(replicas, 0.01), 1
                    ),
                })
                print(f"  ramp {name}: {phases[-1]}")
        finally:
            pool.shutdown(wait=False)
    finally:
        http.shutdown()
        router.close()
        autoscaler.close(terminate=True, grace_s=10.0)
    a, b = phases
    per_replica = [p["qps_per_replica"] for p in phases]
    spread = (
        abs(per_replica[0] - per_replica[1])
        / max(max(per_replica), 0.01)
    )
    return {
        "base": a,
        "doubled": b,
        "scaled_out": b["replicas_end"] > a["replicas_end"],
        "goodput_ratio": round(
            b["goodput_qps"] / max(a["goodput_qps"], 0.01), 3
        ),
        "qps_per_replica_spread": round(spread, 3),
        "params": {
            "capacity": capacity,
            "service_ms": service_ms,
            "min_replicas": min_replicas,
            "max_replicas": max_replicas,
            "phase_s": phase_s,
            "deadline_s": deadline_s,
        },
    }


def ramp_main(args) -> int:
    """``--ramp``: the fleet-autoscaling proof, recorded to
    SERVING_BENCH.json as ``serving_fleet_ramp``. Gates: the fleet
    scaled out under the doubled offered load, goodput followed it
    (≥1.4× the base phase), and QPS-per-replica stayed within 25%
    across phases — the $/QPS-flat claim of ROADMAP item 3."""
    # sized for small CI runners: the whole rig (client threads,
    # router, 2-4 replica processes) shares a couple of cores, so the
    # offered load must stress the REPLICAS' capacity, not the
    # harness. Long service times keep the request RATE (= Python/
    # proxy overhead) low while the offered CONCURRENCY still
    # saturates: 12.5 qps x 240 ms = 3 in flight over 2 replicas x 2
    # slots (comfortable); doubled = 6 in flight over those 4 slots
    # (sheds until the pool reaches 4 replicas = 8 slots)
    phase_s = args.ramp_phase_s or (12.0 if args.smoke else 18.0)
    rate = args.ramp_rate or 12.5

    def degenerate_reason(ramp: dict) -> str:
        """Harness (not fleet) failure modes on tiny shared runners —
        recorded, never gated on. A REAL control-plane failure looks
        different: a broken autoscaler leaves the doubled phase pinned
        at base capacity with a LARGE shed ratio (refusals), which the
        gates below still catch."""
        base_phase, doubled = ramp["base"], ramp["doubled"]
        if base_phase["goodput_qps"] < 0.5 * base_phase["offered_qps"]:
            return (
                f"base phase collapsed (goodput "
                f"{base_phase['goodput_qps']} of "
                f"{base_phase['offered_qps']} offered)"
            )
        if base_phase["replicas_end"] >= ramp["params"]["max_replicas"]:
            # runner hiccups early in the base phase shed enough to
            # scale the pool to max before the doubled load ever came:
            # the 2->4 premise is void (over-triggering, not a
            # control-plane fault — the fleet still served the load)
            return (
                "base phase scaled out prematurely "
                f"(replicas already {base_phase['replicas_end']})"
            )
        shed_ratio = doubled["shed"] / max(
            1, doubled["requests_counted"]
        )
        if (
            doubled["goodput_qps"] < 0.5 * base_phase["goodput_qps"]
            and shed_ratio < 0.1
        ):
            # requests were SERVED, just late: the client/runner fell
            # behind, the fleet did not refuse work
            return (
                f"doubled phase served-but-late (goodput "
                f"{doubled['goodput_qps']}, shed ratio "
                f"{shed_ratio:.2f}) — harness, not fleet, saturated"
            )
        return ""

    ramp = None
    failures: list[str] = []
    for attempt in range(2):
        print(
            f"serving_bench --ramp: {rate:.0f} qps then "
            f"{2 * rate:.0f} qps, {phase_s:.0f}s per phase, "
            f"replicas 2..4 (attempt {attempt + 1})"
        )
        ramp = run_ramp(
            base_rate=rate,
            phase_s=phase_s,
            capacity=2,
            service_ms=240.0,
            min_replicas=2,
            max_replicas=4,
        )
        failures = []
        reason = degenerate_reason(ramp)
        if reason:
            ramp["degenerate"] = reason
            print(
                f"serving_bench --ramp: degenerate run ({reason}); "
                "gate skipped",
                file=sys.stderr,
            )
            break
        base_phase = ramp["base"]
        if not ramp["scaled_out"]:
            failures.append(
                f"fleet did not scale out under 2x load "
                f"(replicas {base_phase['replicas_end']} -> "
                f"{ramp['doubled']['replicas_end']})"
            )
        if ramp["goodput_ratio"] < 1.4:
            failures.append(
                f"goodput did not follow offered load "
                f"(ratio {ramp['goodput_ratio']} < 1.4)"
            )
        if ramp["qps_per_replica_spread"] > 0.25:
            failures.append(
                "QPS-per-replica drifted "
                f"{ramp['qps_per_replica_spread']:.0%} across phases "
                "(> 25%): $/QPS did not stay flat"
            )
        if not failures:
            break
        if attempt == 0:
            print(
                "serving_bench --ramp: gates failed, one retry "
                "(shared-runner noise shield): " + "; ".join(failures),
                file=sys.stderr,
            )
    base_phase = ramp["base"]
    record = {
        "metric": "serving_fleet_ramp",
        "value": ramp["goodput_ratio"],
        "unit": "x",
        "extra": ramp,
    }
    if failures:
        record["error"] = failures
    if args.out:
        persist_record(record, args.out)
    print(json.dumps(record))
    if failures:
        print(
            "serving_bench --ramp: FAILED: " + "; ".join(failures),
            file=sys.stderr,
        )
        return 1
    print(
        f"serving_bench --ramp: replicas "
        f"{base_phase['replicas_end']} -> "
        f"{ramp['doubled']['replicas_end']}, goodput x"
        f"{ramp['goodput_ratio']}, per-replica spread "
        f"{ramp['qps_per_replica_spread']:.0%} — ok"
    )
    return 0


def density_main(args) -> int:
    """``--density``: models-resident × aggregate QPS under one pool
    byte budget, f32 vs int8 — the multi-tenant capacity claim as a
    recorded number (serving-density/v1)."""
    import numpy as np  # noqa: PLC0415 - density-only deps
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.obs import MetricRegistry
    from predictionio_tpu.ops import quantize, similarity
    from predictionio_tpu.ops.pallas_topk import fused_top_k_dot
    from predictionio_tpu.serving.modelpool import ModelPool

    n_tenants = args.density_tenants or (12 if args.smoke else 16)
    n_items = args.density_items or (3000 if args.smoke else 20000)
    k_dim = 32
    topk = 10
    batch = 8
    requests = args.requests or (240 if args.smoke else 1200)
    min_capacity = args.density_min_capacity
    recall_floor = args.density_recall_floor
    parity_floor = args.density_parity_floor

    rng = np.random.default_rng(0)
    tables = {
        f"t{i}": rng.standard_normal((n_items, k_dim)).astype(
            np.float32
        )
        for i in range(n_tenants)
    }
    f32_bytes = n_items * k_dim * 4
    # a budget that fits ~2.5 f32 tenants: small enough that f32
    # thrashes under the mix, big enough that int8 (~0.26x) holds most
    # of the tenant set resident
    budget = int(2.5 * f32_bytes)
    # skewed tenant mix (Zipf alpha=1.0, weight ∝ 1/rank): the shape
    # multi-tenant traffic actually has — LRU keeps the head hot, the
    # tail faults. Shared generator (bench_keys) with --skew; passing
    # this rng keeps the draws identical to the old hand-rolled code.
    import bench_keys

    sequence = bench_keys.zipf_sequence(
        n_tenants, requests, alpha=1.0, rng=rng
    )
    queries = jnp.asarray(
        rng.standard_normal((batch, k_dim)).astype(np.float32)
    )

    def loader_for(name: str, mode: str):
        def load():
            t = tables[name]
            if mode == "f32":
                staged = similarity.stage_factors(jnp.asarray(t))
                return staged, int(staged.size) * 4, None
            qf = quantize.stage_quantized(
                quantize.quantize_factors(t, mode)
            )
            return qf, qf.nbytes, None

        return load

    def run_pass(mode: str) -> dict:
        # cost attribution rides the same shared tenant families the
        # batcher registers (identical kind + labels): each request's
        # timed device seconds are charged to the tenant it served,
        # and pool residency accrues byte-seconds — the density record
        # carries the per-tenant cost split, not just the aggregate
        registry = MetricRegistry()
        device_seconds = registry.counter(
            "pio_tenant_device_seconds_total",
            "Measured device time (enqueue + sync) apportioned to the "
            "tenant's slots, by slot count per coalesced batch",
            ("tenant",),
        )
        pool = ModelPool(budget_bytes=budget, registry=registry)
        try:
            # capacity: cycle every tenant once; what stays resident
            # is the budget's tenant count for this precision
            for name in tables:
                with pool.pin(name, loader_for(name, mode)):
                    pass
            resident = pool.stats()["tenantsResident"]
            # warm the jitted top-k (compile outside the timed window)
            with pool.pin("t0", loader_for("t0", mode)) as table:
                jax.block_until_ready(
                    similarity.top_k_dot(queries, table, topk)[1]
                )
            t0 = time.perf_counter()
            for idx in sequence:
                name = f"t{int(idx)}"
                req_t0 = time.perf_counter()
                with pool.pin(name, loader_for(name, mode)) as table:
                    jax.block_until_ready(
                        similarity.top_k_dot(queries, table, topk)[1]
                    )
                device_seconds.labels(name).inc(
                    time.perf_counter() - req_t0
                )
            elapsed = time.perf_counter() - t0
            stats = pool.stats()  # settles residency byte-seconds too
            qps = round(requests / elapsed, 1)

            def by_tenant(metric_name):
                family = registry.to_dict().get(metric_name) or {}
                return {
                    s["labels"]["tenant"]: s["value"]
                    for s in family.get("samples") or []
                    if s.get("labels", {}).get("tenant")
                }

            attributed = by_tenant("pio_tenant_device_seconds_total")
            byte_seconds = by_tenant(
                "pio_tenant_resident_byte_seconds_total"
            )
            per_tenant = {
                t: {
                    "device_s": round(dev, 4),
                    "byte_s": round(byte_seconds.get(t, 0.0), 1),
                }
                for t, dev in sorted(
                    attributed.items(), key=lambda kv: -kv[1]
                )[:5]
            }
            return {
                "mode": mode,
                "tenants_resident": resident,
                "per_tenant_bytes": (
                    stats["residentBytes"] // max(1, resident)
                ),
                "qps": qps,
                "density": round(resident * qps, 1),
                "evictions": stats["evictions"],
                "elapsed_s": round(elapsed, 3),
                "attributed_device_s": round(
                    sum(attributed.values()), 3
                ),
                "per_tenant": per_tenant,
            }
        finally:
            pool.close()

    print(
        f"serving_bench --density: {n_tenants} tenants x "
        f"[{n_items}, {k_dim}] f32, budget {budget} B "
        f"(~2.5 f32 tables), {requests} requests, batch {batch}"
    )
    f32 = run_pass("f32")
    print(f"  f32 : {f32}")
    int8 = run_pass("int8")
    print(f"  int8: {int8}")

    # recall@k of the int8 ranking against the f32 ranking on the
    # hottest tenant, over a bigger probe batch for a stable estimate
    probe = jnp.asarray(
        rng.standard_normal((64, k_dim)).astype(np.float32)
    )
    t0_table = jnp.asarray(tables["t0"])
    qf0 = quantize.quantize_factors(tables["t0"], "int8")
    _, i_ref = similarity.top_k_dot(probe, t0_table, topk)
    _, i_q = similarity.top_k_dot(probe, qf0, topk)
    recall = round(quantize.recall_at_k(i_ref, i_q), 4)

    # dequantizing Pallas kernel vs the jitted XLA fallback, recorded
    # labeled with interpret: on CPU the kernel runs interpreted
    # (orders slower — trend data, never a gate); on TPU it's the real
    # fused path
    backend = jax.default_backend()
    interpret = backend != "tpu"
    kernel_items = min(n_items, 1024) if interpret else n_items
    kq = qf0.data[:kernel_items]
    kscale = qf0.scale[:kernel_items]

    def timed(fn, iters):
        jax.block_until_ready(fn())  # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn())
        return round((time.perf_counter() - t0) / iters * 1000.0, 3)

    kernel_ms = timed(
        lambda: fused_top_k_dot(
            queries, kq, topk, block=512, interpret=interpret,
            scale=kscale,
        )[1],
        2 if interpret else 20,
    )
    xla_ms = timed(
        lambda: quantize._top_k_dot_quant_xla(
            queries, kq, kscale, topk
        )[1],
        20,
    )
    kernel_vs_jit = {
        "pallas_ms": kernel_ms,
        "xla_ms": xla_ms,
        "interpret": interpret,
        "backend": backend,
        "n_items": kernel_items,
    }
    print(f"  recall@{topk}: {recall}  kernel_vs_jit: {kernel_vs_jit}")

    capacity_ratio = round(
        int8["tenants_resident"] / max(1, f32["tenants_resident"]), 3
    )
    parity = round(int8["qps"] / max(1e-9, f32["qps"]), 3)
    failures: list[str] = []
    degenerate = ""
    if f32["qps"] < 5.0:
        # the runner itself collapsed (shared-CI noise): the parity
        # comparison would measure the harness, not the pool. The
        # capacity and recall gates are deterministic and still hold.
        degenerate = (
            f"f32 pass served only {f32['qps']} req/s — runner, not "
            "pool, saturated; parity gate skipped"
        )
        print(
            f"serving_bench --density: degenerate run ({degenerate})",
            file=sys.stderr,
        )
    if capacity_ratio < min_capacity:
        failures.append(
            f"int8 fit only {capacity_ratio}x the f32 tenant count "
            f"in the same budget (< {min_capacity}x)"
        )
    if recall < recall_floor:
        failures.append(
            f"int8 recall@{topk} {recall} below the "
            f"{recall_floor} floor against the f32 ranking"
        )
    if not degenerate and parity < parity_floor:
        failures.append(
            f"int8 aggregate QPS {int8['qps']} is {parity}x f32's "
            f"{f32['qps']} (< {parity_floor}x: goodput parity lost)"
        )

    record = {
        "metric": "serving_density",
        "record": "serving-density/v1",
        "value": capacity_ratio,
        "unit": "x",
        "extra": {
            "f32": f32,
            "int8": int8,
            "budget_bytes": budget,
            "capacity_ratio": capacity_ratio,
            "qps_parity": parity,
            "recall_at_k": recall,
            "topk": topk,
            "kernel_vs_jit": kernel_vs_jit,
            "params": {
                "tenants": n_tenants,
                "n_items": n_items,
                "k_dim": k_dim,
                "batch": batch,
                "requests": requests,
                "min_capacity": min_capacity,
                "recall_floor": recall_floor,
                "parity_floor": parity_floor,
                "smoke": args.smoke,
            },
        },
    }
    if degenerate:
        record["extra"]["degenerate"] = degenerate
    if failures:
        record["error"] = failures
    if args.out:
        persist_record(record, args.out)
    print(json.dumps(record))
    if failures:
        print(
            "serving_bench --density: FAILED: " + "; ".join(failures),
            file=sys.stderr,
        )
        return 1
    print(
        f"serving_bench --density: int8 holds {capacity_ratio}x the "
        f"f32 tenant count (recall@{topk} {recall}, QPS parity "
        f"{parity}x) — ok"
    )
    return 0


def _skew_prediction(k: int) -> dict:
    """Deterministic per-key 'model answer' — the stand-in for
    ``serving.serve`` so byte equality across passes is checkable."""
    return {
        "user": f"u{k}",
        "itemScores": [
            {"item": f"i{j}", "score": (k * 131 + j * 17) % 997}
            for j in range(10)
        ],
    }


def run_skew_pass(
    sequence, *, use_cache: bool, cache_budget: int, workers: int,
    max_batch: int, max_wait_ms: float, device_ms: float,
    enqueue_ms: float, decode_ms: float,
) -> dict:
    """One closed-loop pass over a skewed key sequence. ``use_cache``
    interposes a real QueryCache exactly where the engine server does:
    after 'admission' (the worker picked the request up), before the
    batcher (hits never submit). Returns rates, state counts, hit-path
    percentiles, and the per-key answer bytes for equality gating."""
    from predictionio_tpu.serving.querycache import (
        QueryCache,
        canonical_query_bytes,
    )

    dev = SimDevice(
        device_ms / 1000.0, enqueue_ms / 1000.0, decode_ms / 1000.0
    )
    batcher = MicroBatcher(
        TwoPhaseBatchFn(dev.dispatch, dev.collect),
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        max_queue=0,
        pipeline_depth=2,
        name=f"bench-skew-{'on' if use_cache else 'off'}",
    )
    cache = (
        QueryCache(cache_budget, shards=4, registry=None)
        if use_cache
        else None
    )
    n_keys = int(max(sequence)) + 1
    canon = [
        canonical_query_bytes({"user": f"u{k}", "num": 10})
        for k in range(n_keys)
    ]
    lock = threading.Lock()
    answers: dict[int, bytes] = {}
    mismatched: list[int] = []
    counts = {"hit": 0, "miss": 0, "coalesced": 0}
    all_lat: list[float] = []
    hit_lat: list[float] = []
    next_idx = {"i": 0}
    errors: list[str] = []

    def compute(k: int) -> bytes:
        # the uncached tail: one batcher slot on the simulated device,
        # then the same single json.dumps the engine server's leader
        # path uses
        batcher.submit(k).result(timeout=30)
        return json.dumps(_skew_prediction(k)).encode("utf-8")

    def one(k: int) -> None:
        t_req = time.perf_counter()
        if cache is None:
            body = compute(k)
            state = "miss"
        else:
            claim = cache.claim("", "g1", canon[k])
            if claim.hit:
                body = claim.value
                state = "hit"
            elif claim.leader:
                try:
                    body = compute(k)
                except BaseException as exc:
                    cache.abort(claim, exc)
                    raise
                cache.fill(claim, body)
                state = "miss"
            else:
                body = cache.join(claim, 30.0)
                state = "coalesced"
        dt = time.perf_counter() - t_req
        with lock:
            counts[state] += 1
            all_lat.append(dt)
            if state == "hit":
                hit_lat.append(dt)
            prev = answers.setdefault(k, body)
            if prev != body and k not in mismatched:
                mismatched.append(k)

    def worker() -> None:
        while True:
            with lock:
                i = next_idx["i"]
                next_idx["i"] += 1
            if i >= len(sequence):
                return
            try:
                one(int(sequence[i]))
            except Exception as exc:  # noqa: BLE001 - recorded, fails pass
                with lock:
                    errors.append(f"key {int(sequence[i])}: {exc}")

    threads = [
        threading.Thread(target=worker, name=f"skew-{w}", daemon=True)
        for w in range(workers)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    batcher.close()
    all_lat.sort()
    hit_lat.sort()
    n = len(all_lat)
    return {
        "cache": "on" if use_cache else "off",
        "qps": round(n / max(1e-9, elapsed), 1),
        "p50_ms": round(_percentile(all_lat, 0.50) * 1000, 3),
        "p99_ms": round(_percentile(all_lat, 0.99) * 1000, 3),
        "hit_p50_ms": round(_percentile(hit_lat, 0.50) * 1000, 3),
        "hit_p99_ms": round(_percentile(hit_lat, 0.99) * 1000, 3),
        "hits": counts["hit"],
        "misses": counts["miss"],
        "coalesced": counts["coalesced"],
        "hit_rate": round(counts["hit"] / max(1, n), 3),
        "batches": dev.batches,
        "requests": n,
        "elapsed_s": round(elapsed, 3),
        "errors": errors,
        "answers": answers,
    }


def skew_main(args) -> int:
    """Generation-keyed serving cache under Zipf-skewed traffic:
    cache-off vs cache-on at α ∈ {0.9, 1.1}, gated on byte-identical
    answers (always) and the α=1.1 hit-path speedup."""
    import bench_keys

    n_keys = args.skew_keys or (200 if args.smoke else 400)
    requests = args.requests or (2400 if args.smoke else 8000)
    floor = args.skew_floor
    workers = 8
    # budget ≈ a quarter of the key space resident: the LRU must earn
    # its hit rate by keeping the Zipf head, not by caching everything
    sample_value = json.dumps(_skew_prediction(0)).encode("utf-8")
    entry_bytes = len(sample_value) + 64 + 256
    cache_budget = max(4096, (n_keys // 4) * entry_bytes)
    print(
        f"serving_bench --skew: {n_keys} keys, {requests} requests/"
        f"pass, {workers} workers, cache budget {cache_budget} B "
        f"(~{n_keys // 4} of {n_keys} keys resident)"
    )

    common = dict(
        workers=workers, cache_budget=cache_budget,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        device_ms=args.device_ms, enqueue_ms=args.enqueue_ms,
        decode_ms=args.decode_ms,
    )
    failures: list[str] = []
    degenerate = ""
    by_alpha: dict[str, dict] = {}
    speedup_at_gate = 0.0
    for alpha in (0.9, 1.1):
        sequence = bench_keys.zipf_sequence(
            n_keys, requests, alpha=alpha, seed=int(alpha * 10)
        )
        off = run_skew_pass(sequence, use_cache=False, **common)
        on = run_skew_pass(sequence, use_cache=True, **common)
        # exact-equality gate, both directions: every key answered in
        # both passes must have produced byte-identical responses
        # (same generation ⇒ same bytes, hit or miss)
        unequal = [
            k for k, body in on.pop("answers").items()
            if off["answers"].get(k, body) != body
        ]
        off.pop("answers")
        speedup = round(on["qps"] / max(1e-9, off["qps"]), 3)
        result = {"off": off, "on": on, "speedup": speedup}
        by_alpha[f"{alpha}"] = result
        print(
            f"  alpha={alpha}: off {off['qps']} qps p50 "
            f"{off['p50_ms']}ms | on {on['qps']} qps "
            f"(hit rate {on['hit_rate']}, hit p99 "
            f"{on['hit_p99_ms']}ms) | speedup {speedup}x"
        )
        for label, p in (("off", off), ("on", on)):
            if p["errors"]:
                failures.append(
                    f"alpha={alpha} cache-{label} pass errored: "
                    f"{p['errors'][:3]}"
                )
        if unequal:
            failures.append(
                f"alpha={alpha}: {len(unequal)} key(s) answered "
                f"non-identically cache-on vs cache-off "
                f"(e.g. {sorted(unequal)[:5]})"
            )
        if alpha == 1.1:
            speedup_at_gate = speedup
            if off["qps"] < 5.0:
                # the runner itself collapsed: the speedup would
                # measure harness noise. Equality above still gates.
                degenerate = (
                    f"uncached pass served only {off['qps']} req/s — "
                    "runner, not cache, saturated; speedup gate "
                    "skipped"
                )
                print(
                    f"serving_bench --skew: degenerate run "
                    f"({degenerate})",
                    file=sys.stderr,
                )
            else:
                if speedup < floor:
                    failures.append(
                        f"alpha=1.1 cached QPS {on['qps']} is only "
                        f"{speedup}x uncached {off['qps']} "
                        f"(< {floor}x)"
                    )
                if on["hits"] and not (
                    on["hit_p99_ms"] < off["p50_ms"]
                ):
                    failures.append(
                        f"alpha=1.1 hit-path p99 {on['hit_p99_ms']}ms "
                        f"not below uncached p50 {off['p50_ms']}ms"
                    )
                if not on["hits"]:
                    failures.append(
                        "alpha=1.1 cached pass recorded zero hits"
                    )

    record = {
        "metric": "serving_cache_speedup",
        "record": "serving-cache/v1",
        "value": speedup_at_gate,
        "unit": "x",
        "extra": {
            "by_alpha": by_alpha,
            "params": {
                "keys": n_keys,
                "requests": requests,
                "workers": workers,
                "cache_budget_bytes": cache_budget,
                "speedup_floor": floor,
                "smoke": args.smoke,
            },
        },
    }
    if degenerate:
        record["extra"]["degenerate"] = degenerate
    if failures:
        record["error"] = failures
    if args.out:
        persist_record(record, args.out)
    print(json.dumps(record))
    if failures:
        print(
            "serving_bench --skew: FAILED: " + "; ".join(failures),
            file=sys.stderr,
        )
        return 1
    print(
        f"serving_bench --skew: cached serving holds "
        f"{speedup_at_gate}x uncached QPS at alpha=1.1 with "
        f"byte-identical answers — ok"
    )
    return 0


def persist_record(record: dict, out_path: str) -> None:
    """Append the run to the stable serving-bench trajectory file
    (schema serving-bench/v1), mirroring how the training bench's
    BENCH_*.json rounds persist — scaling claims cite these (shared
    bench_record helper)."""
    from bench_record import append_run

    append_run(record, out_path, "serving-bench/v1", "serving_bench")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small, CI-safe run")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests of the closed loop")
    ap.add_argument("--window", type=int, default=64,
                    help="in-flight requests at load (closed loop)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--device-ms", type=float, default=4.0,
                    help="simulated device time per batch")
    ap.add_argument("--enqueue-ms", type=float, default=0.2,
                    help="simulated host enqueue cost per batch")
    ap.add_argument("--decode-ms", type=float, default=4.0,
                    help="simulated host decode cost per batch")
    ap.add_argument("--pipeline-depth", type=int, default=2)
    ap.add_argument("--open-rate", type=float, default=None,
                    help="open-loop offered arrival rate in QPS "
                         "(default: 60%% of the closed-loop "
                         "capacity; 0 disables the open-loop pass)")
    ap.add_argument("--open-duration", type=float, default=None,
                    help="open-loop run length in seconds "
                         "(default 4, smoke 2)")
    ap.add_argument("--no-overload", dest="overload",
                    action="store_false",
                    help="skip the 2x-saturation overload passes "
                         "(baseline collapse vs admission-controlled)")
    ap.add_argument("--overload-duration", type=float, default=None,
                    help="overload pass length in seconds "
                         "(default 3, smoke 1.5)")
    ap.add_argument("--overload-deadline-ms", type=float, default=150.0,
                    help="per-request deadline for overload goodput "
                         "accounting")
    ap.add_argument("--ramp", action="store_true",
                    help="run ONLY the fleet-autoscaling ramp: open-"
                         "loop offered QPS doubles mid-run against a "
                         "real router + autoscaler, replicas scale "
                         "2->4, per-phase goodput + QPS-per-replica "
                         "recorded (docs/scale_out.md 'Autoscaling')")
    ap.add_argument("--ramp-rate", dest="ramp_rate", type=float,
                    default=None,
                    help="phase-A offered QPS (default 12.5; "
                         "phase B doubles it)")
    ap.add_argument("--ramp-phase-s", dest="ramp_phase_s", type=float,
                    default=None,
                    help="seconds per ramp phase (default 6 smoke, 12)")
    ap.add_argument("--density", action="store_true",
                    help="run ONLY the multi-tenant model-pool density "
                         "bench: models-resident x aggregate QPS under "
                         "one byte budget, f32 vs int8 quantized "
                         "tables (docs/serving.md 'Multi-tenant "
                         "serving')")
    ap.add_argument("--density-tenants", type=int, default=None,
                    help="synthetic tenant count (default 12 smoke, "
                         "16)")
    ap.add_argument("--density-items", type=int, default=None,
                    help="catalog rows per tenant (default 3000 "
                         "smoke, 20000)")
    ap.add_argument("--density-min-capacity", type=float, default=2.0,
                    help="hard floor on int8/f32 resident-tenant "
                         "ratio in the same byte budget")
    ap.add_argument("--density-recall-floor", type=float, default=0.9,
                    help="hard floor on int8 recall@k against the f32 "
                         "ranking")
    ap.add_argument("--density-parity-floor", type=float, default=0.6,
                    help="int8 aggregate QPS as a fraction of f32's "
                         "(goodput parity; skipped on a degenerate "
                         "runner, recorded either way)")
    ap.add_argument("--skew", action="store_true",
                    help="run ONLY the serving-cache skewed-traffic "
                         "bench: cache-off vs cache-on under Zipf "
                         "alpha in {0.9, 1.1}, gated on byte-equal "
                         "answers + the alpha=1.1 hit-path speedup "
                         "(docs/serving.md 'Serving query cache')")
    ap.add_argument("--skew-keys", type=int, default=None,
                    help="distinct query keys (default 200 smoke, "
                         "400); the cache budget holds ~a quarter")
    ap.add_argument("--skew-floor", type=float, default=1.5,
                    help="cached/uncached QPS floor at alpha=1.1 "
                         "(recorded-not-gated on a degenerate runner)")
    ap.add_argument("--out", default=os.path.join(
                        REPO, "SERVING_BENCH.json"),
                    help="append the run record to this trajectory "
                         "file ('' disables persistence)")
    args = ap.parse_args()

    if args.ramp:
        return ramp_main(args)
    if args.density:
        return density_main(args)
    if args.skew:
        return skew_main(args)

    total = args.requests or (2000 if args.smoke else 8000)
    common = dict(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        device_ms=args.device_ms, enqueue_ms=args.enqueue_ms,
        decode_ms=args.decode_ms,
    )

    print(
        f"serving_bench: device={args.device_ms}ms "
        f"decode={args.decode_ms}ms enqueue={args.enqueue_ms}ms "
        f"max_batch={args.max_batch} window={args.window} "
        f"requests={total}"
    )
    # warm one tiny round first so thread startup noise stays out of
    # the measured windows
    run_mode(
        pipeline_depth=args.pipeline_depth, window=8, requests=32,
        **common,
    )
    piped = run_mode(
        pipeline_depth=args.pipeline_depth, window=args.window,
        requests=total, **common,
    )
    print(f"  closed loop (depth={args.pipeline_depth}): {piped}")

    # open loop: offered load at a fraction of the closed-loop
    # capacity, so the pass asserts SUSTAINED rate + tails, not peak
    # throughput
    open_loop = None
    if args.open_rate is None or args.open_rate > 0:
        rate = args.open_rate or max(100.0, piped["qps"] * 0.6)
        duration = args.open_duration or (2.0 if args.smoke else 4.0)
        open_loop = run_open_loop(
            rate_qps=rate, duration_s=duration,
            pipeline_depth=args.pipeline_depth, **common,
        )
        print(f"  open loop ({rate:.0f} qps offered): {open_loop}")

    # overload: 2x the measured closed-loop capacity, baseline stack vs
    # admission-controlled (docs/robustness.md "Overload & backpressure")
    overload = None
    if args.overload:
        offered_anchor = piped["qps"]
        dur = args.overload_duration or (1.5 if args.smoke else 3.0)
        base = run_overload(
            capacity_qps=offered_anchor, duration_s=dur,
            deadline_ms=args.overload_deadline_ms,
            pipeline_depth=args.pipeline_depth, admit=False, **common,
        )
        print(f"  overload baseline (2x, no admission): {base}")
        adm = run_overload(
            capacity_qps=offered_anchor, duration_s=dur,
            deadline_ms=args.overload_deadline_ms,
            pipeline_depth=args.pipeline_depth, admit=True, **common,
        )
        print(f"  overload admitted (2x, controller)  : {adm}")
        # measured capacity = what the rig actually served while fully
        # saturated in the baseline pass (it never sheds, so its raw
        # completion rate is the device ceiling on THIS run)
        capacity = base["served_qps"]
        overload = {
            "capacity_qps": capacity,
            "offered_qps": adm["offered_qps"],
            "deadline_ms": args.overload_deadline_ms,
            "goodput_ratio": round(adm["goodput_qps"] / capacity, 3),
            "baseline_goodput_ratio": round(
                base["goodput_qps"] / capacity, 3
            ),
            "critical_p99_ms": adm[admission.CRITICAL]["p99_ms"],
            "critical_shed_ratio": adm[admission.CRITICAL]["shed_ratio"],
            "sheddable_shed_ratio": adm[
                admission.SHEDDABLE
            ]["shed_ratio"],
            "baseline": base,
            "admitted": adm,
        }

    failures = []
    if open_loop is not None:
        sustained = open_loop["achieved_qps"]
        offered = open_loop["offered_qps"]
        # 10% slack absorbs scheduler noise on shared CI runners; a
        # real capacity shortfall shows up far below that
        if sustained < offered * 0.9:
            failures.append(
                f"open loop: sustained {sustained} qps of "
                f"{offered} offered (<90%)"
            )
    if overload is not None and (
        overload["offered_qps"] < 1.5 * overload["capacity_qps"]
    ):
        # the offered-rate anchor (the closed-loop measurement) came
        # out below the rig's real capacity — the "2x saturation"
        # premise is void, so the overload assertions would measure
        # harness noise, not the controller. Record the numbers, skip
        # the gate.
        overload["anchor_degenerate"] = True
        print(
            "serving_bench: overload anchor degenerate "
            f"(offered {overload['offered_qps']} < 1.5x capacity "
            f"{overload['capacity_qps']}); overload gate skipped",
            file=sys.stderr,
        )
    elif overload is not None:
        # the overload proof (ISSUE 8 acceptance): at 2x saturation,
        # goodput >= 80% of capacity, critical p99 inside the deadline,
        # and sheddable sheds first
        if overload["goodput_ratio"] < 0.8:
            failures.append(
                f"overload: goodput {overload['goodput_ratio']} of "
                "capacity (<0.8) under admission"
            )
        # "bounded": within 2x the deadline (p99 includes late-served
        # stragglers, and harness GIL bursts count against the server
        # in this in-process rig) — versus the uncontrolled baseline
        # collapsing to >10x the deadline as the queue grows
        if (
            overload["critical_p99_ms"]
            > 2.0 * args.overload_deadline_ms
        ):
            failures.append(
                f"overload: critical p99 "
                f"{overload['critical_p99_ms']}ms past 2x the "
                f"{args.overload_deadline_ms}ms deadline"
            )
        if (
            overload["critical_shed_ratio"]
            > overload["sheddable_shed_ratio"]
        ):
            failures.append(
                "overload: critical shed "
                f"{overload['critical_shed_ratio']} above sheddable "
                f"{overload['sheddable_shed_ratio']} — class order "
                "violated"
            )
        if overload["goodput_ratio"] <= overload["baseline_goodput_ratio"]:
            failures.append(
                "overload: admission goodput "
                f"{overload['goodput_ratio']} not above the "
                f"uncontrolled baseline "
                f"{overload['baseline_goodput_ratio']}"
            )

    record = {
        "metric": "serving_closed_loop_qps",
        "value": piped["qps"],
        "unit": "qps",
        "extra": {
            "closed_loop": piped,
            "open_loop": open_loop,
            "overload": overload,
            "params": {
                "device_ms": args.device_ms,
                "decode_ms": args.decode_ms,
                "enqueue_ms": args.enqueue_ms,
                "max_batch": args.max_batch,
                "window": args.window,
                "pipeline_depth": args.pipeline_depth,
                "smoke": args.smoke,
            },
        },
    }
    if failures:
        record["error"] = failures
    if args.out:
        persist_record(record, args.out)
    print(json.dumps(record))
    if failures:
        print("serving_bench: FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    print(
        f"serving_bench: closed loop {piped['qps']} qps on the "
        "simulated device, open loop and overload gates hold — ok"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
