"""Benchmark — prints ONE JSON line.

Headline metric (BASELINE.md north star): implicit-ALS epoch time on a
synthetic MovieLens-class workload, measured in this process on the
accelerator JAX finds. There is no fallback: without an accelerator the
script exits non-zero and prints no number.

Workloads:

* default — 49,152 users × 8,192 items, ~2M nnz, rank 32 (ml-1m/10m
  territory; whole bench < a couple of minutes including compiles).
* ``--large`` / PIO_BENCH_SCALE=ml20m — 138,493 × 26,744, 20M nnz,
  rank 32: the MovieLens-20M shape from BASELINE.md's target table.

Epochs are timed as a fused on-device run (``EPOCHS_PER_DISPATCH``
chained in one dispatch, as real training runs them), so the number
reflects device throughput, not host↔device round-trips.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

WORKLOADS = {
    # name: (n_users, n_items, nnz, rank)
    "default": (49_152, 8_192, 2_000_000, 32),
    "ml20m": (138_493, 26_744, 20_000_000, 32),
    # Criteo-magnitude interaction count (BASELINE.md targets table:
    # "MovieLens-20M/Criteo scale"); 5x the nnz and ~9x the entity
    # rows of ml20m — a single-chip headroom probe, not a driver
    # default (PIO_BENCH_SCALE=criteo100m to run)
    "criteo100m": (1_000_000, 500_000, 100_000_000, 32),
}
BLOCK_LEN = 64
EPOCHS_PER_DISPATCH = 8
TIMED_ROUNDS = 3


def _per_chip_hour(epoch_seconds: float, n_devices) -> float | None:
    """Fused ALS epochs one chip-hour buys: 3600 / (epoch_s × chips).
    The $/throughput figure every scale-out decision should cite —
    speedup that costs proportionally more chips leaves it flat."""
    if not epoch_seconds or not n_devices:
        return None
    return round(3600.0 / (epoch_seconds * int(n_devices)), 2)


def _scale() -> str:
    if "--large" in sys.argv:
        return "ml20m"
    return os.environ.get("PIO_BENCH_SCALE", "default")


def serving_bench_summary() -> dict | None:
    """The latest recorded serving-bench run (scripts/serving_bench.py
    appends every run — including the overload-mode goodput numbers —
    to SERVING_BENCH.json). Attached to the per-round record so the
    driver's trajectory carries the SERVING numbers alongside the
    training number (ROADMAP item 5), instead of them living only in a
    repo file nobody diffs."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "SERVING_BENCH.json"
    )
    try:
        with open(path) as f:
            doc = json.load(f)
        runs = doc.get("runs") or []
        last = runs[-1]
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    extra = last.get("extra") or {}
    summary = {
        "recordedAtUtc": last.get("recordedAtUtc"),
        "runs_recorded": len(runs),
    }
    open_loop = extra.get("open_loop")
    if isinstance(open_loop, dict):
        summary["open_loop"] = {
            k: open_loop.get(k)
            for k in ("offered_qps", "achieved_qps", "p99_ms")
        }
    overload = extra.get("overload")
    if isinstance(overload, dict):
        summary["overload"] = {
            k: overload.get(k)
            for k in (
                "capacity_qps", "offered_qps", "goodput_ratio",
                "critical_p99_ms", "sheddable_shed_ratio",
            )
        }
    return summary


def multichip_summary() -> dict | None:
    """The latest recorded multichip scaling run
    (scripts/multichip_bench.py appends every sweep — strong/weak
    curves, sharded-serving latency, factor bytes-per-device, the
    sharded-vs-replicated equality check — to MULTICHIP.json).
    Attached to the per-round record so scale-out decisions cite the
    measured curves, not the dryrun's mere existence."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "MULTICHIP.json"
    )
    try:
        with open(path) as f:
            doc = json.load(f)
        runs = doc.get("runs") or []
        last = runs[-1]
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    extra = last.get("extra") or {}
    summary = {
        "recordedAtUtc": last.get("recordedAtUtc"),
        "strong_speedup": extra.get("strong_speedup"),
        "strong_efficiency": extra.get("strong_efficiency"),
        "weak_efficiency": extra.get("weak_efficiency"),
        "equality_ok": (extra.get("equality") or {}).get("ok"),
        "runs_recorded": len(runs),
    }
    devices = extra.get("devices") or []
    if devices:
        top = devices[-1]
        summary["max_devices"] = top.get("n_devices")
        serving = top.get("serving") or {}
        summary["serving_p99_ms"] = serving.get("p99_ms")
        summary["factor_bytes_per_device"] = serving.get(
            "factor_bytes_per_device"
        )
    return summary


def make_data(scale: str):
    n_users, n_items, nnz, _rank = WORKLOADS[scale]
    rng = np.random.default_rng(42)
    # power-law item popularity, uniform users
    pop = rng.zipf(1.3, nnz) % n_items
    rows = rng.integers(0, n_users, nnz).astype(np.int32)
    cols = pop.astype(np.int32)
    vals = rng.integers(1, 6, nnz).astype(np.float32)
    return rows, cols, vals


def _phase(msg: str) -> None:
    """Per-phase progress on stderr so a hang is diagnosable from the
    driver's captured output (which phase died, not just 'timed out')."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_epoch_bench(scale: str) -> dict:
    """Median per-epoch wall-clock of the fused alternating solve."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.als import (
        _device_slabs,
        build_bucketed,
        make_train_step,
    )
    from predictionio_tpu.parallel.mesh import ComputeContext

    n_users, n_items, nnz, rank = WORKLOADS[scale]
    ctx = ComputeContext.create(batch="bench")
    first = ctx.mesh.devices.flat[0]
    if first.platform == "cpu":
        raise SystemExit(
            "bench.py: no accelerator — JAX found only the host CPU "
            "(JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); an epoch time "
            "is a device metric and is not measured on the CPU"
        )
    n_data = ctx.data_parallelism
    _phase(f"backend up ({ctx.mesh.devices.size} device(s)); generating "
           f"{scale} data")
    rows, cols, vals = make_data(scale)

    t_pack = time.perf_counter()
    user_packed = build_bucketed(
        rows, cols, vals, n_users, block_len=BLOCK_LEN,
        row_multiple=n_data,
    )
    item_packed = build_bucketed(
        cols, rows, vals, n_items, block_len=BLOCK_LEN,
        row_multiple=n_data,
    )
    pack_seconds = time.perf_counter() - t_pack
    _phase(f"pack done in {pack_seconds:.1f}s")
    run = make_train_step(ctx, user_packed, item_packed, True, 1.0)
    u_slabs, u_heavy = _device_slabs(ctx, user_packed)
    i_slabs, i_heavy = _device_slabs(ctx, item_packed)

    rng = np.random.default_rng(7)
    y = jax.device_put(
        (rng.normal(size=(item_packed.n_rows_padded, rank))
         / np.sqrt(rank)).astype(np.float32),
        ctx.replicated,
    )
    x = jax.device_put(
        np.zeros((user_packed.n_rows_padded, rank), np.float32),
        ctx.replicated,
    )
    lam = jnp.float32(0.01)

    sync = jax.block_until_ready

    args = (u_slabs, u_heavy, i_slabs, i_heavy, lam)

    # warmup (compile)
    t_compile = time.perf_counter()
    x, y = run(x, y, *args, n_iters=EPOCHS_PER_DISPATCH)
    sync(y)
    _phase(f"compile+warmup done in {time.perf_counter() - t_compile:.1f}s")

    times = []
    for r in range(TIMED_ROUNDS):
        t0 = time.perf_counter()
        x, y = run(x, y, *args, n_iters=EPOCHS_PER_DISPATCH)
        sync(y)
        times.append(
            (time.perf_counter() - t0) / EPOCHS_PER_DISPATCH
        )
        _phase(f"round {r + 1}/{TIMED_ROUNDS}: "
               f"{times[-1]:.4f}s/epoch")
    peak = (first.memory_stats() or {}).get("peak_bytes_in_use")
    return {
        "seconds": float(np.median(times)),
        "pack_seconds": round(pack_seconds, 3),
        "device": {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": int(ctx.n_devices),
        },
        "workload": f"{n_users}x{n_items}x{nnz}@r{rank}",
        "peak_hbm_gib": round(peak / 2**30, 2) if peak else None,
    }


def main() -> None:
    scale = _scale()
    result = run_epoch_bench(scale)
    secs = float(result["seconds"])
    print(
        json.dumps(
            {
                "metric": "als_epoch_time" + (
                    f"_{scale}" if scale != "default" else ""
                ),
                "value": round(secs, 4),
                "unit": "s",
                "device": result["device"],
                "extra": {
                    "workload": result["workload"],
                    "pack_seconds": result["pack_seconds"],
                    "peak_hbm_gib": result["peak_hbm_gib"],
                    # cost-performance axis: fused epochs one chip-hour
                    # buys at the measured rate — scale-out decisions
                    # compare THIS across device counts, not raw epoch
                    # time (8 chips at 2x speedup is 4x the $/epoch)
                    "throughput_per_chip_hour": _per_chip_hour(
                        secs, result["device"]["count"]
                    ),
                    # the serving + multichip records ride along
                    "serving_bench": serving_bench_summary(),
                    "multichip": multichip_summary(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
