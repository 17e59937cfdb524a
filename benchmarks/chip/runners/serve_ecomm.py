"""Runner of the e-commerce cell: an in-process multi-tenant `EngineServer`
of the `ecommerce` template on `memory` storage, each tenant an app of its
own whose events (the active users' seen lists, the unknown users' views,
one ``$set`` of unavailable items) are written through the storage's own
`insert_batch` before the window, served over real HTTP on localhost and
driven by `loadgen_ecomm` processes.

One run: publish the seeded tenants and write their events, let the
server's own loader stage and warm them, settle, measure the window, then
the probe (a write through the storage the server reads, and the same
query again: an item the write rules out and that is still served is a
stale answer), read the device's peak memory, close the server, and only
then hold a sample of the window's own answers against `reference_ecomm`.
What `serve_http` and `loadgen` have is used from there.
"""

from __future__ import annotations

import datetime as _dt
import functools
import gc
import http.client
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

import layer_metrics
import loadgen
import loadgen_ecomm
import modelstore
import reference_ecomm
import roofline
import trace_reduce
from runners.serve_http import (
    cell_metrics, collect, evictions, split_cores, watch_window, window_numbers,
)

ENGINE_ID = "chipbench"
#: what this runner reads of a configuration's file, beside the general keys
CONFIG_KEYS = (
    "n_users", "n_items", "n_categories", "rank", "tenants", "num",
    "zipf_exponent", "quantize", "jit_names", "active_users",
)
_LOADGEN = os.path.join(
    os.path.dirname(os.path.abspath(loadgen_ecomm.__file__)), "loadgen_ecomm.py"
)
#: the first day of the data set's window (2017-11-25)
_T0 = _dt.datetime(2017, 11, 25, tzinfo=_dt.timezone.utc)
_PROBES = 64


def _norm_ranks(items):
    """popularity: the rank of each item's norm (0 = shortest), f32."""
    import jax.numpy as jnp

    order = jnp.argsort(jnp.linalg.norm(items, axis=1))
    return jnp.zeros(len(order), jnp.float32).at[order].set(
        jnp.arange(len(order), dtype=jnp.float32)
    )


@functools.lru_cache(maxsize=1)
def _category_names(n_categories: int):
    return tuple(f"c{c}" for c in range(n_categories))


def build_model(seed, tenant, n_users, n_items, n_categories, rank):
    """What unpickling a tenant's blob returns: the `ECommModel` a train
    would have published, its factors and popularity already on the
    device, its categories encoded, its app the tenant's own."""
    import jax

    from predictionio_tpu.models.ecommerce import ECommModel

    users, items = modelstore.device_factors(seed, tenant, n_users, n_items, rank)
    user_map, item_map = modelstore.id_maps(n_users, n_items)
    return ECommModel(
        user_factors=users, item_factors=items,
        user_map=user_map, item_map=item_map, item_categories={},
        popularity=jax.jit(_norm_ranks)(items),
        app_name=modelstore.tenant_name(tenant),
        category_names=_category_names(n_categories),
        category_rows=loadgen_ecomm.item_categories(
            seed, tenant, n_items, n_categories
        )[None, :],
    )


class _LazyModel:
    """Pickles to a call of :func:`build_model`."""

    def __init__(self, *args):
        self.args = args

    def __reduce__(self):
        return build_model, self.args


def publish_tenants(config: dict, seed: int):
    """A `memory` storage, the process's default (the template reads the
    rules through it), holding one app, one COMPLETED instance and one
    lazy blob per tenant; ``(storage, {tenant: variant}, {tenant: app id})``."""
    from predictionio_tpu.core.persistence import _FORMAT_VERSION
    from predictionio_tpu.data.storage import Storage, set_storage
    from predictionio_tpu.data.storage.base import App, EngineInstance, Model

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    set_storage(storage)
    instances = storage.get_meta_data_engine_instances()
    models = storage.get_model_data_models()
    now = _dt.datetime.now(_dt.timezone.utc)
    tenants, apps = {}, {}
    for t in range(config["tenants"]):
        name = modelstore.tenant_name(t)
        apps[name] = storage.get_meta_data_apps().insert(App(0, name, None))
        storage.get_events().init(apps[name])
        iid = instances.insert(EngineInstance(
            id="", status="COMPLETED", start_time=now, end_time=now,
            engine_id=ENGINE_ID, engine_version="1", engine_variant=name,
            engine_factory="ecommerce",
        ))
        blob = pickle.dumps({
            "version": _FORMAT_VERSION,
            "entries": [("auto", _LazyModel(
                seed, t, config["n_users"], config["n_items"],
                config["n_categories"], config["rank"],
            ))],
        })
        models.insert(Model(id=iid, models=blob))
        tenants[name] = name
    return storage, tenants, apps


def seen_events(config: dict, seed: int):
    """The active users' seen items as events (``view`` or ``buy``). One
    population for every tenant: the events are immutable, so the tenants'
    stores hold the same objects, each under its own app, and set-up
    builds them once."""
    from predictionio_tpu.data.event import Event

    active, lists = _population(
        seed, config["n_users"], config["n_items"], config["active_users"]
    )
    buys = np.random.default_rng([seed, 6]).random(
        sum(len(x) for x in lists)
    ) < loadgen_ecomm.BUY_SHARE
    events, n = [], 0
    for user, rows in zip(active.tolist(), lists):
        uid = f"u{user}"
        for row in rows.tolist():
            events.append(Event(
                event="buy" if buys[n] else "view", entity_type="user",
                entity_id=uid, target_entity_type="item",
                target_entity_id=f"i{row}", event_time=_T0, creation_time=_T0,
                event_id=f"s{n}",
            ))
            n += 1
    return events


def tenant_events(config: dict, seed: int, tenant: int):
    """The tenant's own events: its unknown users' views (a second apart,
    the newest last) and the ``$set`` of its unavailable items."""
    from predictionio_tpu.data.event import Event

    n_items = config["n_items"]
    events = []
    for uid, rows in loadgen_ecomm.unknown_views(seed, tenant, n_items).items():
        # the dictionary holds them newest first
        for age, row in enumerate(rows.tolist()):
            at = _T0 + _dt.timedelta(seconds=len(rows) - age)
            events.append(Event(
                event="view", entity_type="user", entity_id=uid,
                target_entity_type="item", target_entity_id=f"i{row}",
                event_time=at, creation_time=at, event_id=f"v{uid}-{age}",
            ))
    events.append(unavailable_event(
        loadgen_ecomm.unavailable_items(seed, tenant, n_items), _T0, "a0"
    ))
    return events


def unavailable_event(rows, at, event_id):
    from predictionio_tpu.data.event import Event

    return Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties={"items": [f"i{int(r)}" for r in rows]},
        event_time=at, creation_time=at, event_id=event_id,
    )


def load_events(storage, apps: dict, config: dict, seed: int, phases: dict) -> None:
    """Writes every tenant's events through the backend's `insert_batch`;
    leaves how many and how long in ``phases``."""
    t0 = time.monotonic()
    backend, shared = storage.get_events(), seen_events(config, seed)
    phases["events_built_s"] = time.monotonic() - t0
    total = 0
    for t in range(config["tenants"]):
        events = shared + tenant_events(config, seed, t)
        app_id = apps[modelstore.tenant_name(t)]
        for at in range(0, len(events), 65536):
            backend.insert_batch(events[at:at + 65536], app_id)
        total += len(events)
    phases["events"] = total
    phases["events_s"] = time.monotonic() - t0


def build_server(config: dict, storage, tenants, devices):
    """The server as `pio-tpu deploy` builds it, over the seeded tenants."""
    from predictionio_tpu.core.engine import EngineParams
    from predictionio_tpu.models.ecommerce import (
        ECommAlgorithmParams, ECommDataSourceParams, ecommerce_engine,
    )
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer

    registry = MetricRegistry()
    server = EngineServer(
        ecommerce_engine(),
        EngineParams(
            data_source=("", ECommDataSourceParams(app_name=ENGINE_ID)),
            algorithms=[("ecomm", ECommAlgorithmParams(
                app_name=ENGINE_ID, rank=config["rank"]
            ))],
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
            "n_users": config["n_users"], "n_items": config["n_items"],
            "n_categories": config["n_categories"],
            "active_users": config["active_users"], "num": config["num"],
            "batch": traffic["batch"], "unknown_share": traffic["unknown_share"],
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
            out.append((tenant, query, reference_ecomm.parse_answer(
                (slot or {}).get("prediction"), config["num"], config["n_items"]
            )))
    return out


def sample_answers(answers, traffic, seed) -> dict:
    """``{tenant: [(query, answer)]}``: a sample, drawn from the seed, over
    at most ``check_tenants`` tenants and ``check_queries`` queries."""
    rng = np.random.default_rng([seed, 0xC4EC])
    sample: dict[int, list] = {}
    n = 0
    for i in rng.permutation(len(answers)):
        tenant, query, answer = answers[i]
        if tenant not in sample and len(sample) >= traffic["check_tenants"]:
            continue
        sample.setdefault(tenant, []).append((query, answer))
        n += 1
        if n >= traffic["check_queries"]:
            break
    return sample


def _ask(conn, tenant: int, query: dict):
    status, data = loadgen._post(
        conn, f"/queries.json?accessKey={modelstore.tenant_name(tenant)}",
        json.dumps(query).encode(),
    )
    rows = json.loads(data)["itemScores"] if status == 200 else None
    return None if rows is None else [r["item"] for r in rows]


def probe(storage, apps, port, answers, config, seed) -> dict:
    """After the window: for `_PROBES` answered queries a ``view`` of the
    item served first, for as many others a new ``$set`` of their tenant's
    unavailable items with theirs added, each through the storage the
    server reads; then the same queries again over HTTP. An item still
    served is a stale answer."""
    from predictionio_tpu.data.event import Event

    rng = np.random.default_rng([seed, 0x57A1E])
    served = [
        (t, q, f"i{int(a[0][0])}") for t, q, a in answers
        if a is not None and len(a[0]) and not q.get("whiteList")
    ]
    picked = [served[i] for i in rng.permutation(len(served))[: 2 * _PROBES]]
    views, gone = picked[: len(picked) // 2], picked[len(picked) // 2:]
    backend = storage.get_events()
    now = _dt.datetime.now(_dt.timezone.utc)
    for n, (tenant, query, item) in enumerate(views):
        backend.insert(Event(
            event="view", entity_type="user", entity_id=query["user"],
            target_entity_type="item", target_entity_id=item,
            event_time=now, creation_time=now, event_id=f"p{n}",
        ), apps[modelstore.tenant_name(tenant)])
    for tenant in sorted({t for t, _q, _i in gone}):
        rows = set(loadgen_ecomm.unavailable_items(seed, tenant, config["n_items"]).tolist())
        rows |= {int(i[1:]) for t, _q, i in gone if t == tenant}
        backend.insert(
            unavailable_event(sorted(rows), now, "a1"),
            apps[modelstore.tenant_name(tenant)],
        )
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=loadgen.REQUEST_TIMEOUT_S)
    stale = unasked = 0
    try:
        for tenant, query, item in views + gone:
            again = _ask(conn, tenant, query)
            unasked += again is None
            stale += again is not None and item in again
    finally:
        conn.close()
    return {"probes": len(picked), "stale_answers": stale, "unasked": unasked}


@functools.lru_cache(maxsize=1)
def _population(seed: int, n_users: int, n_items: int, n_active: int):
    """(active users, their seen lists): the same for every tenant."""
    active = loadgen_ecomm.active_users(seed, n_users, n_active)
    return active, loadgen_ecomm.seen_lists(seed, n_items, len(active))


def shop_of(config: dict, seed: int, tenant: int, users_asked) -> reference_ecomm.Shop:
    """The tenant as seeded, for the reference: the same tables, category
    numbers, popularity, unavailable items, and the seen lists of the
    users the sample asks about."""
    import jax

    n_users, n_items = config["n_users"], config["n_items"]
    users, items = modelstore.device_factors(
        seed, tenant, n_users, n_items, config["rank"]
    )
    popularity = jax.jit(_norm_ranks)(items)
    users, items, popularity = jax.device_get((users, items, popularity))
    active, lists = _population(seed, n_users, n_items, config["active_users"])
    views = {
        uid: rows.astype(np.int64)
        for uid, rows in loadgen_ecomm.unknown_views(seed, tenant, n_items).items()
    }
    seen = {
        f"u{int(active[k])}": lists[k]
        for k in np.flatnonzero(np.isin(active, list(users_asked)))
    }
    seen.update(views)  # a view is a seen event
    unavailable = np.zeros(n_items, bool)
    unavailable[loadgen_ecomm.unavailable_items(seed, tenant, n_items)] = True
    return reference_ecomm.Shop(
        users=users, items=items,
        category=loadgen_ecomm.item_categories(
            seed, tenant, n_items, config["n_categories"]
        ),
        popularity=popularity.astype(np.float64), unavailable=unavailable,
        seen=seen, views=views,
    )


def compare(sample, config, seed, control=None) -> dict[str, float]:
    """The reference over the sample, tenant by tenant and 32 queries at a
    time; with ``control`` the control's answers stand in for the served."""
    comparison = reference_ecomm.Comparison(config["num"])
    for tenant, pairs in sorted(sample.items()):
        asked = {
            reference_ecomm.user_index(q["user"], config["n_users"])
            for q, _a in pairs
        }
        shop = shop_of(config, seed, tenant, asked)
        for at in range(0, len(pairs), 32):
            queries = [p[0] for p in pairs[at:at + 32]]
            answers = [p[1] for p in pairs[at:at + 32]]
            if control:
                answers = reference_ecomm.control_answers(
                    shop, queries, config["num"], control
                )
            comparison.add(shop, queries, answers)
    return comparison.numbers()


def stage_means(gathered: dict) -> dict[str, float]:
    """Mean milliseconds of every stage observed in the window
    (`pio_stage_seconds`), for PERF.md's stage table."""
    stages = sorted({
        s["labels"]["stage"]
        for s in layer_metrics.samples(gathered["after"], "pio_stage_seconds", {})
    })
    means = {
        stage: layer_metrics.histogram_mean(gathered, {
            "families": ["pio_stage_seconds"], "labels": {"stage": stage},
            "scale": 1000.0,
        })
        for stage in stages
    }
    return {k: v for k, v in means.items() if v is not None}


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of the cell; returns the result line as a dictionary."""
    from predictionio_tpu.models import ecommerce

    if not hasattr(ecommerce, "StagedRules"):
        raise SystemExit(
            "this program's ecommerce template has no rules before the "
            "top-k (no two-phase predict): the cell cannot run on it"
        )
    named = [k for k in loadgen.OPTIONAL_PARAMETERS if k in traffic]
    if named:
        raise SystemExit(
            f"traffic names {named}: `loadgen_ecomm.py` draws its own users "
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
        storage, tenants, apps = publish_tenants(config, args.seed)
        gc.disable()  # millions of events, none of them garbage
        # the events load while the server stages and warms the tenants
        loader = threading.Thread(
            target=load_events,
            args=(storage, apps, config, args.seed, phases), daemon=True,
        )
        loader.start()
        server, http_server, registry = build_server(
            config, storage, tenants, jax.devices()[:cell["chips"]]
        )
        phases["built_s"] = time.monotonic() - t_process_start
        loader.join()
        if "events" not in phases:
            raise SystemExit("the events did not load")
        gc.enable()
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
        answers = kept_answers(results, config)
        probed = probe(
            storage, apps, http_server.port, answers, config, args.seed
        )
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
    from predictionio_tpu.data.storage import set_storage

    set_storage(None)
    del server, http_server, storage
    gc.unfreeze()
    gc.collect()

    load = window_numbers(traffic, times, results, args.seconds)
    load["setup_s"] = times["t_window"] - t_process_start
    sample = sample_answers(answers, traffic, args.seed)
    t_reference = time.monotonic()
    numbers = compare(sample, config, args.seed)
    reference_s = time.monotonic() - t_reference
    numbers["stale_answers"] = float(probed["stale_answers"] + probed["unasked"])
    numbers["unanswered"] = float(load["unanswered"])
    numbers["evictions"] = evicted
    correct, compared = reference_ecomm.judge(numbers, config["limits"])
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
        "reference_s": reference_s, "probes": probed["probes"],
    }
    phases["events_per_s"] = phases["events"] / max(phases["events_s"], 1e-9)
    result["phases"] = phases
    if args.control:
        result["control"] = compare(sample, config, args.seed, args.control)
    result["compared"] = compared
    return result
