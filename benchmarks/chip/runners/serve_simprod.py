"""Runner of the similar-product cell: an in-process multi-tenant
`EngineServer` of the `similarproduct` template on `memory` storage, each
tenant the engine of ``examples/similarproduct/engine.json`` (two ALS
algorithms, ``view`` and ``like``, one shared item vocabulary: two models,
two batchers a tenant), served over real HTTP on localhost and driven by
`loadgen_simprod` processes.

One run: publish the seeded tenants, let the server's own loader stage and
warm them, settle, measure the window, read the device's peak memory, close
the server, and only then hold a sample of the window's own answers against
`reference_simprod`. What `serve_http`, `serve_ecomm` and `loadgen` have is
used from there.
"""

from __future__ import annotations

import datetime as _dt
import gc
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import time

import layer_metrics
import loadgen
import loadgen_ecomm
import loadgen_simprod
import modelstore
import reference_simprod
import roofline
import trace_reduce
from runners.serve_ecomm import _category_names, sample_answers, stage_means
from runners.serve_http import (
    cell_metrics, collect, evictions, split_cores, watch_window, window_numbers,
)

ENGINE_ID = "chipbench"
#: what this runner reads of a configuration's file, beside the general keys
CONFIG_KEYS = (
    "n_items", "n_categories", "rank", "tenants", "num", "zipf_exponent",
    "quantize", "jit_names", "algorithms", "like_coverage",
)
_LOADGEN = os.path.join(
    os.path.dirname(os.path.abspath(loadgen_simprod.__file__)), "loadgen_simprod.py"
)


def device_table(seed, tenant, algorithm: int, config: dict):
    """The item table of a tenant's ``algorithm``-th algorithm, on the
    device: `modelstore`'s draw for (seed, tenant, algorithm), and for
    ``like`` the rows of the items that have no such event set to 0."""
    import jax.numpy as jnp

    n_items = config["n_items"]
    _users, items = modelstore.device_factors(
        seed, len(config["algorithms"]) * tenant + algorithm, 1, n_items,
        config["rank"],
    )
    if config["algorithms"][algorithm] == "like":
        liked = loadgen_simprod.like_rows(
            seed, tenant, n_items, config["like_coverage"]
        )
        items = jnp.where(jnp.asarray(liked)[:, None], items, 0.0)
    return items


def build_model(seed, tenant, algorithm, config_text: str):
    """What unpickling one entry of a tenant's blob returns: the
    `SimilarModel` a train would have published for that algorithm, its
    factors already on the device, its categories encoded."""
    from predictionio_tpu.models.similarproduct import SimilarModel

    config = json.loads(config_text)
    n_items = config["n_items"]
    return SimilarModel(
        item_factors=device_table(seed, tenant, algorithm, config),
        item_map=modelstore.id_maps(1, n_items)[1], item_categories={},
        category_names=_category_names(config["n_categories"]),
        category_rows=loadgen_ecomm.item_categories(
            seed, tenant, n_items, config["n_categories"]
        )[None, :],
    )


class _LazyModel:
    """Pickles to a call of :func:`build_model`."""

    def __init__(self, *args):
        self.args = args

    def __reduce__(self):
        return build_model, self.args


def publish_tenants(config: dict, seed: int):
    """A `memory` storage holding one COMPLETED instance a tenant and one
    blob of two lazy entries, an algorithm each; ``(storage, {tenant:
    variant})``."""
    from predictionio_tpu.core.persistence import _FORMAT_VERSION
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    instances = storage.get_meta_data_engine_instances()
    models = storage.get_model_data_models()
    now = _dt.datetime.now(_dt.timezone.utc)
    # what `build_model` reads of the configuration, as one hashable string
    text = json.dumps({
        k: config[k] for k in
        ("n_items", "n_categories", "rank", "algorithms", "like_coverage")
    })
    tenants = {}
    for t in range(config["tenants"]):
        name = modelstore.tenant_name(t)
        iid = instances.insert(EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=ENGINE_ID, engine_version="1", engine_variant=name,
            engine_factory="similarproduct",
        ))
        blob = pickle.dumps({
            "version": _FORMAT_VERSION,
            "entries": [
                ("auto", _LazyModel(seed, t, a, text))
                for a in range(len(config["algorithms"]))
            ],
        })
        models.insert(Model(id=iid, models=blob))
        tenants[name] = name
    return storage, tenants


def build_server(config: dict, storage, tenants, devices):
    """The server as `pio-tpu deploy` builds it, over the seeded tenants."""
    from predictionio_tpu.core.engine import EngineParams
    from predictionio_tpu.models.similarproduct import (
        SimilarALSParams, SimilarDataSourceParams, similarproduct_engine,
    )
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer

    registry = MetricRegistry()
    server = EngineServer(
        similarproduct_engine(),
        EngineParams(
            data_source=("view", SimilarDataSourceParams(
                app_name=ENGINE_ID, event_names=tuple(config["algorithms"])
            )),
            algorithms=[
                ("als", SimilarALSParams(event_name=name, rank=config["rank"]))
                for name in config["algorithms"]
            ],
        ),
        engine_id=ENGINE_ID,
        storage=storage,
        ctx=ComputeContext.create(batch="chipbench", devices=devices),
        tenants=tenants,
        quantize=config["quantize"] or "",
        registry=registry,
        **config["server"],
    )
    http_server = server.serve(host="127.0.0.1", port=0)
    http_server.start()
    return server, http_server, registry


def send_plans(procs, traffic, config, port, seed, seconds, cores) -> dict:
    t_start = time.monotonic() + 0.3
    times = {
        "t_start": t_start,
        "t_window": t_start + traffic["settle_s"],
        "t_end": t_start + traffic["settle_s"] + seconds,
    }
    for i, proc in enumerate(procs):
        plan = {
            **times, "host": "127.0.0.1", "port": port, "seed": seed,
            "proc": i, "clients": traffic["clients"] // len(procs),
            "tenants": [
                modelstore.tenant_name(t) for t in range(config["tenants"])
            ],
            "zipf_exponent": config["zipf_exponent"],
            "n_items": config["n_items"],
            "n_categories": config["n_categories"], "num": config["num"],
            "batch": traffic["batch"],
            "unknown_item_share": traffic["unknown_item_share"],
            "unknown_query_share": traffic["unknown_query_share"],
            "keep": traffic["keep"], "cores": cores,
        }
        proc.stdin.write(json.dumps(plan) + "\n")
        proc.stdin.close()
        proc.stdin = None  # so that communicate() leaves it alone
    return times


def kept_answers(results, config):
    """``[(tenant, query, answer or None)]`` of every kept reply."""
    out = []
    for tenant, queries, text in (k for r in results for k in r["kept"]):
        try:
            body = json.loads(text)
        except ValueError:
            body = None
        slots = body if isinstance(body, list) else []
        slots = slots + [None] * (len(queries) - len(slots))
        for query, slot in zip(queries, slots):
            out.append((tenant, query, reference_simprod.parse_answer(
                (slot or {}).get("prediction"), config["num"], config["n_items"]
            )))
    return out


def shop_of(config: dict, seed: int, tenant: int) -> reference_simprod.Shop:
    """The tenant as seeded, for the reference: the same tables and
    category numbers."""
    import jax

    tables = jax.device_get([
        device_table(seed, tenant, a, config)
        for a in range(len(config["algorithms"]))
    ])
    return reference_simprod.Shop(
        tables=tables,
        category=loadgen_ecomm.item_categories(
            seed, tenant, config["n_items"], config["n_categories"]
        ),
    )


def compare(sample, config, seed, control=None) -> dict[str, float]:
    """The reference over the sample, tenant by tenant and 16 queries at a
    time (two [16, I] float64 score matrices); with ``control`` the
    control's answers stand in for the served."""
    comparison = reference_simprod.Comparison(config["num"])
    for tenant, pairs in sorted(sample.items()):
        shop = shop_of(config, seed, tenant)
        for at in range(0, len(pairs), 16):
            queries = [p[0] for p in pairs[at:at + 16]]
            answers = [p[1] for p in pairs[at:at + 16]]
            if control:
                answers = reference_simprod.control_answers(
                    shop, queries, config["num"], control
                )
            comparison.add(shop, queries, answers)
    return comparison.numbers()


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of the cell; returns the result line as a dictionary."""
    from predictionio_tpu.core.controller import Algorithm
    from predictionio_tpu.models.similarproduct import SimilarALSAlgorithm

    if SimilarALSAlgorithm.batch_predict_launch is Algorithm.batch_predict_launch:
        raise SystemExit(
            "this program's similarproduct template has no launch of its own "
            "(one predict a query, the rules after the top-k): the cell "
            "cannot run on it"
        )
    named = [k for k in loadgen.OPTIONAL_PARAMETERS if k in traffic]
    if named:
        raise SystemExit(
            f"traffic names {named}: `loadgen_simprod.py` draws its own items "
            "on a closed loop and follows neither"
        )
    import jax

    from predictionio_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    server_cores, generator_cores = split_cores(traffic["generator_cores"])
    if server_cores:
        os.sched_setaffinity(0, server_cores)
    procs = [
        subprocess.Popen(
            [sys.executable, _LOADGEN],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        for _ in range(traffic["procs"])
    ]
    server = http_server = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    phases = {"start_s": time.monotonic() - t_process_start}
    try:
        storage, tenants = publish_tenants(config, args.seed)
        server, http_server, registry = build_server(
            config, storage, tenants, jax.devices()[:cell["chips"]]
        )
        # nothing allocated in set-up is looked at by a collection again
        gc.collect()
        gc.freeze()
        phases["server_s"] = time.monotonic() - t_process_start
        times = send_plans(
            procs, traffic, config, http_server.port, args.seed, args.seconds,
            generator_cores,
        )
        seen = watch_window(times, registry, traffic, trace_dir)
        results = collect(procs)
        events = trace_reduce.load_events(trace_dir) if trace_dir else []
        stats = jax.devices()[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        evicted = evictions(registry)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if http_server is not None:
            http_server.shutdown()
        if server is not None:
            server.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    del server, http_server, storage
    gc.unfreeze()
    gc.collect()

    load = window_numbers(traffic, times, results, args.seconds)
    load["setup_s"] = times["t_window"] - t_process_start
    sample = sample_answers(kept_answers(results, config), traffic, args.seed)
    t_reference = time.monotonic()
    numbers = compare(sample, config, args.seed)
    reference_s = time.monotonic() - t_reference
    numbers["unanswered"] = float(load["unanswered"])
    numbers["evictions"] = evicted
    correct, compared = reference_simprod.judge(numbers, config["limits"])
    device = {**device, "memory_peak_bytes": memory_peak}
    result = {
        "correct": correct,
        "attempted": load["attempted"], "failed": load["failed"],
    }
    if args.trace:
        trace = trace_reduce.reduce(
            events, ["jit_" + n for n in config["jit_names"]]
        )
        gathered = {
            "before": seen["before"], "after": seen["after"], "trace": trace,
            "traced_queries": seen["traced_queries"], "load": load,
            "config": config, "traffic": traffic,
            "memory_peak_bytes": memory_peak,
            "peak": roofline.peaks(device["kind"]) if trace else None,
        }
        wanted = cell_metrics(bench, "per_layer", cell["name"])
        values = {m["name"]: layer_metrics.read(m["name"], gathered) for m in wanted}
        if trace:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {
                "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
                "stage_ms": stage_means(gathered),
            }
    else:
        wanted = cell_metrics(bench, "end_to_end", cell["name"])
        values = {m["name"]: load.get(m["name"]) for m in wanted}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values[m["name"]] is not None
    }
    result["device"] = device
    result["sampled"] = {
        "tenants": len(sample), "queries": sum(len(v) for v in sample.values()),
        "reference_s": reference_s,
    }
    result["phases"] = phases
    if args.control:
        result["control"] = compare(sample, config, args.seed, args.control)
    result["compared"] = compared
    return result
