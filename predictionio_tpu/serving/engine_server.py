"""Engine Server — the predict REST service.

Capability parity with the reference's ServerActor/MasterActor
(core/.../workflow/CreateServer.scala:266-718), default port 8000:

* ``GET  /``             → status: JSON by default, the HTML status page
  (twirl index.scala.html) when the client prefers ``text/html``
* ``POST /queries.json`` → the predict hot path (:495-647): parse query →
  ``serving.supplement`` → per-algorithm predict → ``serving.serve`` →
  JSON; optional feedback loop storing a ``predict`` event with a
  ``prId`` (entity type ``pio_pr``, :539-600); latency bookkeeping
* ``POST /batch/queries.json`` → many queries in one HTTP round trip
  with per-query statuses (shape mirrors the event API's
  ``/batch/events.json``). TPU-first extension with no reference
  counterpart: the Python HTTP tier costs far more per request than
  the batched device path does per prediction — batching amortizes the
  HTTP tier away and the submitted queries coalesce in the
  micro-batcher into full device dispatches
* ``POST /reload``       → hot-swap to the latest COMPLETED instance
  (MasterActor :337-363)
* ``POST /stop``         → undeploy (Console.undeploy posts here, :905-932)
* ``GET /metrics`` / ``GET /metrics.json`` → telemetry scrape
  (Prometheus text / JSON with derived percentiles; docs/observability.md)

TPU-first difference: queries flow through a
:class:`~predictionio_tpu.serving.batching.MicroBatcher` per algorithm
onto pre-compiled batch predict programs instead of per-request model
code.
"""

from __future__ import annotations

import base64
import contextlib
import dataclasses
import datetime as _dt
import html as _html
import json
import logging
import os
import queue
import secrets
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any

from predictionio_tpu.core.engine import Engine, EngineParams
from predictionio_tpu.core.workflow import load_deployment
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.obs import MetricRegistry, get_registry
from predictionio_tpu.obs import timeline as timeline_mod
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.device import (
    CompileTracker,
    CompileWatch,
    DeviceSampler,
)
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving import admission as admission_mod
from predictionio_tpu.serving import canary as canary_mod
from predictionio_tpu.serving import modelpool as modelpool_mod
from predictionio_tpu.serving import querycache as querycache_mod
from predictionio_tpu.serving import resilience
from predictionio_tpu.serving.batching import (
    DEPTH_ZERO_GONE,
    BatcherOverloaded,
    MicroBatcher,
    TwoPhaseBatchFn,
)
from predictionio_tpu.serving.plugins import (
    OUTPUT_SNIFFER,
    PluginContext,
    install_plugin_routes,
)
from predictionio_tpu.serving.http import (
    HTTPError,
    HTTPServer,
    Request,
    Response,
    Router,
    install_metrics_routes,
)
from predictionio_tpu.utils import profiling

logger = logging.getLogger(__name__)


def _model_signature(model, _depth: int = 3):
    """What of a staged model decides which programs its predict
    compiles: shape and dtype of every array it holds, its scalars, and
    kind and size of whatever else (an id map), field by field. Two
    models of one signature, under one algorithm's params on one
    compute context, run the same compiled programs."""
    if isinstance(model, (type(None), bool, int, float, str)):
        return model
    shape, dtype = getattr(model, "shape", None), getattr(model, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    if _depth > 0:
        if dataclasses.is_dataclass(model) and not isinstance(model, type):
            return (type(model).__qualname__,) + tuple(
                (f.name, _model_signature(getattr(model, f.name, None), _depth - 1))
                for f in dataclasses.fields(model)
            )
        if isinstance(model, (list, tuple)):
            return tuple(_model_signature(v, _depth - 1) for v in model)
    size = len(model) if hasattr(model, "__len__") else None
    return (type(model).__qualname__, size)


@dataclasses.dataclass
class _StagedGeneration:
    """One loaded generation: the instance record, its serving layer,
    and its (warmed) batchers — buildable beside the serving one, so
    canary promotion and rollback are pointer swaps, not reloads."""

    instance: Any
    serving: Any
    batchers: list
    warmed: bool
    #: device bytes the generation's models hold (model pool budget
    #: accounting; 0 when the models expose no measurable arrays)
    nbytes: int = 0


class EngineServer:
    def __init__(
        self,
        engine: Engine,
        params: EngineParams,
        engine_id: str,
        engine_version: str = "1",
        engine_variant: str = "default",
        storage: Storage | None = None,
        ctx: ComputeContext | None = None,
        feedback: bool = False,
        feedback_app_id: int | None = None,
        max_batch: int = 64,
        max_wait_ms: float = 2.0,
        max_queue: int | None = None,
        pipeline_depth: int = 2,
        adaptive_wait: bool = True,
        predict_timeout_s: float = 30.0,
        plugins: PluginContext | None = None,
        server_config=None,
        warmup: bool = True,
        log_url: str | None = None,
        log_prefix: str = "",
        registry: MetricRegistry | None = None,
        tracer: tracing.Tracer | None = None,
        admission: bool | admission_mod.AdmissionController = True,
        canary: bool | canary_mod.CanaryConfig = False,
        tenants: dict[str, str] | None = None,
        pool: modelpool_mod.ModelPool | None = None,
        quantize: str | None = None,
        cache: bool | querycache_mod.QueryCache | None = None,
    ):
        self._engine = engine
        self._params = params
        self._engine_id = engine_id
        self._engine_version = engine_version
        self._engine_variant = engine_variant
        self._storage = storage or get_storage()
        self._ctx = ctx or ComputeContext.create(
            batch=f"serving:{engine_id}"
        )
        self._feedback = feedback
        self._feedback_app_id = feedback_app_id
        self._max_batch = max_batch
        self._max_wait_ms = max_wait_ms
        self._max_queue = max_queue
        if pipeline_depth < 1:
            # here and not at the first batcher: a pool builds its
            # batchers when a tenant loads
            raise ValueError(DEPTH_ZERO_GONE.format(pipeline_depth))
        self._pipeline_depth = pipeline_depth
        self._adaptive_wait = adaptive_wait
        self._predict_timeout_s = predict_timeout_s
        self._plugins = plugins or PluginContext()
        self._warmup = warmup
        if log_url:
            parsed = urllib.parse.urlsplit(log_url)
            if parsed.scheme not in ("http", "https") or not parsed.netloc:
                # fail at deploy, not per failing query
                raise ValueError(
                    f"--log-url {log_url!r} is not an http(s) URL"
                )
        self._log_url = log_url
        self._log_prefix = log_prefix
        # bounded handoff to ONE sender thread: a slow/dead collector
        # under overload must never grow threads or block serving.
        # close() stops it with a None sentinel. The thread starts at
        # the END of __init__ (not per failure — check-then-act race;
        # not here — a later init failure would leak it unjoinably).
        self._log_queue: queue.Queue | None = (
            queue.Queue(maxsize=64) if log_url else None
        )
        if server_config is None:
            from predictionio_tpu.serving.config import ServerConfig

            server_config = ServerConfig.from_env()
        self._server_config = server_config

        self._lock = threading.Lock()
        self._request_count = 0
        # wall clock of the last request — single and batch routes agree
        self._last_serving_sec = 0.0
        # per-query mean of the last BATCH request (ADVICE r5: the old
        # code stored this into lastServingSec, silently mixing units)
        self._last_batch_per_query_sec = 0.0
        self._avg_serving_sec = 0.0
        self._start_time = _dt.datetime.now(_dt.timezone.utc)
        self._registry = registry if registry is not None else get_registry()
        self._tracer = tracer if tracer is not None else tracing.get_tracer()
        # incident timeline (docs/observability.md "Incident
        # timeline"): one bounded ring per process, served at
        # /debug/timeline.json. Installed as the process-global ring
        # too, so emitters with no constructor seam (breaker
        # transitions, noisy-neighbor flags) land beside the pool and
        # canary events.
        self._timeline = timeline_mod.Timeline(registry=self._registry)
        timeline_mod.set_timeline(self._timeline)
        # every ring opens with a start marker: restarts are visible in
        # the merged fleet narrative, and a scraped ring is never empty
        self._timeline.record(
            "server_start", f"engine server {engine_id!r} starting",
        )
        self._shed_wasted = self._registry.counter(
            "pio_shed_wasted_dispatch_total",
            "Per-algorithm dispatches abandoned by partially-shed batch "
            "slots that could not be cancelled before device dispatch",
        )
        # guarded promotion (docs/training.md "Canary promotion"):
        # /reload with canary stages the new generation beside the old,
        # shadow-scores sampled live traffic, promotes on a clean gate,
        # and auto-rolls-back on post-promotion regression
        if canary is True:
            self._canary_config = canary_mod.CanaryConfig.from_env()
        elif isinstance(canary, canary_mod.CanaryConfig):
            self._canary_config = canary
        else:
            self._canary_config = None
        self._canary: canary_mod.ShadowCanary | None = None
        self._last_canary: dict | None = None
        # multi-tenant mode (docs/serving.md "Multi-tenant serving"):
        # one process serves N engine variants through a byte-budgeted
        # device model pool keyed by accessKey/X-PIO-Tenant. Tables
        # quantize per ``quantize`` (int8|bf16; None or "" = f32) so
        # many catalogs fit one chip's HBM.
        self._tenants = dict(tenants) if tenants else None
        self._quantize = quantize or ""
        if self._quantize and self._quantize not in ("int8", "bf16"):
            raise ValueError(
                f"unknown quantize mode {self._quantize!r} "
                "(expected int8, bf16, or empty)"
            )
        if self._tenants is not None and self._canary_config is not None:
            # per-tenant reload is immediate; shadow-canary promotion
            # assumes ONE serving generation per process
            raise ValueError(
                "canary and multi-tenant mode are mutually exclusive"
            )
        self._pool: modelpool_mod.ModelPool | None = None
        self._owns_pool = False
        #: tenant → monotonic reload count / latest instance (guarded
        #: by self._lock; the labeled generation/age gauges read these)
        self._tenant_generations: dict[str, int] = {}
        self._tenant_instances: dict[str, Any] = {}
        # serializes /reload handling (staging can take seconds of
        # warmup; two concurrent reloads must not both stage, and a
        # manual reload must deterministically supersede a live canary)
        self._reload_mutex = threading.Lock()
        self._generation = 0
        # per-tenant labeled series: a pooled server swaps models for
        # MANY tenants, and unlabeled gauges would silently overwrite
        # each other across tenants. Single-tenant mode publishes the
        # same series under the empty tenant label, so scrapers sum/
        # first-sample identically in both modes.
        self._generation_gauge = self._registry.gauge(
            "pio_model_generation",
            "Monotonic count of model hot-swaps this process served "
            "(promotions AND rollbacks each advance it — every serving-"
            "model transition is scrape-visible; labeled per tenant in "
            "multi-tenant mode, empty label otherwise)",
            ("tenant",),
        )
        self._warmed_gauge = self._registry.gauge(
            "pio_warmup_complete",
            "1 once the newest generation's warmup compiled every "
            "attempted bucket; 0 while cold (warmup running, disabled, "
            "or every compile failed)",
        )
        self._age_gauge = self._registry.gauge(
            "pio_model_age_seconds",
            "Seconds since the serving generation finished training "
            "(freshness of the model users are hitting; labeled per "
            "tenant in multi-tenant mode, empty label otherwise)",
            ("tenant",),
        )
        if self._tenants is None:
            self._age_gauge.labels("").set_function(
                self._model_age_seconds
            )
        # device runtime telemetry (docs/observability.md "Device
        # telemetry"): HBM/live-array sampler started by serve(), and
        # compile counters the warmup path records into. CPU backends
        # without memory stats degrade to a clean no-op.
        self._device_sampler = DeviceSampler(self._registry)
        self._compile_tracker = CompileTracker(self._registry)
        #: (algorithm, params, model signature, bucket) of every warm-up
        #: bucket this server has run without a failure (under
        #: ``self._lock``: a reload and the pool's loader both stage)
        self._warmed: set = set()
        # what XLA itself compiled, from here (the loads and warm-ups
        # below included) until close()
        self._compile_watch = CompileWatch(self._registry)
        #: one profile capture at a time (jax.profiler is process-
        #: global) — guarded by self._lock, never held across the
        #: capture window itself
        self._profile_active = False
        # generation-keyed serving cache + single-flight coalescing
        # (docs/serving.md "Serving query cache"): opt-in (PIO_CACHE /
        # explicit arg). Keyed by (tenant, generation token, canonical
        # query bytes) — every swap path bumps a sub-generation epoch
        # so stale entries die by key; hits never consume a batcher
        # slot, so cost attribution charges them ~zero device-seconds.
        if cache is None:
            cache = querycache_mod.cache_enabled_from_env()
        if cache and self._feedback:
            # feedback mode injects a fresh random prId per response
            # and must record a predict event per request — responses
            # are intentionally non-identical and non-replayable
            logger.warning(
                "serving cache disabled: incompatible with feedback mode"
            )
            cache = False
        if cache is True:
            self._cache: querycache_mod.QueryCache | None = (
                querycache_mod.QueryCache(
                    registry=self._registry, timeline=self._timeline
                )
            )
        elif isinstance(cache, querycache_mod.QueryCache):
            self._cache = cache
        else:
            self._cache = None
        #: per-tenant sub-generation epoch ("" in single-tenant mode),
        #: guarded by self._lock: part of the cache key so a fold-in —
        #: a child generation of the SAME lineage — still changes every
        #: key and events→serving freshness never regresses past one
        #: fold-in interval
        self._cache_epochs: dict[str, int] = {}
        self._batchers: list[MicroBatcher] = []
        if self._tenants is None:
            self._load()
        else:
            self._instance = None
            self._serving = None
            if pool is not None:
                self._pool = pool
            else:
                self._pool = modelpool_mod.ModelPool(
                    registry=self._registry,
                    timeline=self._timeline,
                )
                self._owns_pool = True
            self._preload_tenants()

        self.router = Router()
        self.router.route("GET", "/", self._status)
        self.router.route("POST", "/queries.json", self._queries)
        self.router.route(
            "POST", "/batch/queries.json", self._batch_queries
        )
        self.router.route("POST", "/reload", self._reload)
        self.router.route("GET", "/canary", self._canary_status)
        self.router.route("POST", "/stop", self._stop)
        self.router.route("POST", "/debug/profile", self._debug_profile)
        install_metrics_routes(
            self.router, self._registry, self._tracer,
            server_config=self._server_config,
            timeline=self._timeline,
        )
        install_plugin_routes(self.router, self._plugins, OUTPUT_SNIFFER)
        # adaptive overload control (docs/robustness.md "Overload &
        # backpressure"): the limit follows observed latency instead of
        # the static batcher queue bound. Attached BEFORE serve() so
        # HTTPServer picks it up; admission=False (or PIO_ADMISSION=0)
        # restores the pre-admission behavior.
        if admission is True:
            self.router.admission = admission_mod.AdmissionController.from_env(
                "engine", registry=self._registry,
                # the limit must never starve the device: one full
                # pipeline of batches stays admissible
                min_limit=float(
                    self._max_batch * (self._pipeline_depth + 1)
                ),
            )
        elif isinstance(admission, admission_mod.AdmissionController):
            self.router.admission = admission
        self._http: HTTPServer | None = None
        if self._log_queue is not None:
            threading.Thread(
                target=self._drain_log_queue,
                name="remote-error-log",
                daemon=True,
            ).start()

    # -- model loading / hot swap ----------------------------------------
    def _model_age_seconds(self) -> float:
        instance = getattr(self, "_instance", None)
        if instance is None:
            return 0.0
        age = (
            _dt.datetime.now(_dt.timezone.utc) - instance.end_time
        ).total_seconds()
        return max(0.0, age)

    def _load(self) -> None:
        """Load the latest generation and swap it in immediately (the
        unguarded path: initial load, and /reload without canary)."""
        self._activate(self._stage())

    # -- serving query cache ----------------------------------------------
    def _bump_cache_generation(
        self, reason: str, tenant: str = "", generation=None
    ) -> None:
        """Invalidate the serving cache for one tenant ("" = the
        single-tenant namespace): bump the sub-generation epoch so new
        lookups miss by KEY immediately, then eagerly flush resident
        entries (one ``cache_flush{reason}`` timeline event). Every
        swap path routes here: /reload, canary promote, rollback, and
        trainer fold-in."""
        if self._cache is None:
            return
        with self._lock:
            self._cache_epochs[tenant] = (
                self._cache_epochs.get(tenant, 0) + 1
            )
        self._cache.flush(
            tenant if tenant else None,
            reason=reason,
            generation=(
                str(generation) if generation is not None else None
            ),
        )

    def _cache_token(self, tenant: str) -> str | None:
        """Generation token for cache keys: the serving instance id
        plus the flush epoch. None (skip the cache, compute instead)
        when the tenant has no resolved instance yet — a hit must
        never force a pool load or take a pin."""
        with self._lock:
            if self._tenants is None:
                instance = self._instance
            else:
                instance = self._tenant_instances.get(tenant)
            epoch = self._cache_epochs.get(tenant, 0)
        if instance is None:
            return None
        return f"{instance.id}:{epoch}"

    def _cache_bypass(self, request: Request) -> bool:
        """``Cache-Control: no-cache`` (or ``no-store``) bypasses the
        cache — the read-your-writes escape hatch; the fleet canary
        gate shadow-scores with it so a cached answer is never judged
        against a fresh one."""
        directives = (
            request.headers.get(querycache_mod.CACHE_CONTROL_HEADER)
            or ""
        ).lower()
        return "no-cache" in directives or "no-store" in directives

    # -- multi-tenant pool plumbing ---------------------------------------
    def _tenant_age_seconds(self, tenant: str) -> float:
        with self._lock:
            instance = self._tenant_instances.get(tenant)
        if instance is None:
            return 0.0
        age = (
            _dt.datetime.now(_dt.timezone.utc) - instance.end_time
        ).total_seconds()
        return max(0.0, age)

    def _tenant_loader(self, tenant: str):
        """Pool loader for one tenant: stage the tenant's engine
        variant (host load + device promotion + warmup, all on the
        pool's loader thread — never a request thread), advance its
        labeled generation/age series, and hand the pool the staged
        generation with its measured device bytes."""

        def load():
            staged = self._stage(
                engine_variant=self._tenants[tenant], tenant=tenant
            )
            first = False
            with self._lock:
                generation = self._tenant_generations.get(tenant, 0) + 1
                first = tenant not in self._tenant_generations
                self._tenant_generations[tenant] = generation
                self._tenant_instances[tenant] = staged.instance
            self._generation_gauge.labels(tenant).set(generation)
            if first:
                self._age_gauge.labels(tenant).set_function(
                    lambda t=tenant: self._tenant_age_seconds(t)
                )
            logger.info(
                "tenant %r serving instance %s (variant %r, "
                "generation %d, %d bytes)",
                tenant, staged.instance.id, self._tenants[tenant],
                generation, staged.nbytes,
            )

            def close():
                for b in staged.batchers:
                    b.close()

            return staged, staged.nbytes, close

        return load

    def _preload_tenants(self) -> None:
        """Eager initial load through the pool, in the order the
        tenants were given, until the pool is full: the first tenant
        that would need a victim (judged by the size of the one staged
        before it) and all after it stay cold and load on first hit —
        staging them now would only evict what was just staged. The
        replica advertises warm once every tenant it staged warmed —
        matching the single-tenant contract the router's admission
        gate reads."""
        warmed_all = True
        staged_count = 0
        nbytes = 0
        for tenant in self._tenants:
            if staged_count and not self._pool.fits(nbytes):
                break
            with self._pool.pin(
                tenant, self._tenant_loader(tenant)
            ) as staged:
                warmed_all = warmed_all and staged.warmed
                nbytes = staged.nbytes
            staged_count += 1
        self._warmed_gauge.set(1 if warmed_all else 0)
        logger.info(
            "multi-tenant server preloaded %d of %d tenant(s), %d left "
            "cold (they load on first hit)",
            staged_count, len(self._tenants),
            len(self._tenants) - staged_count,
        )

    def _resolve_tenant(self, request: Request) -> str:
        """Tenant key for a request: ``accessKey`` query param, then
        the ``X-PIO-Tenant`` header — the same resolution order the
        admission controller's fair-share accounting uses."""
        tenant = (
            request.query.get("accessKey")
            or request.headers.get(admission_mod.TENANT_HEADER)
            or ""
        )
        if not tenant:
            raise HTTPError(
                400,
                "multi-tenant server requires an accessKey query "
                f"param or {admission_mod.TENANT_HEADER} header",
            )
        if tenant not in self._tenants:
            raise HTTPError(404, f"unknown tenant {tenant!r}")
        return tenant

    @contextlib.contextmanager
    def _serving_snapshot(self, request: Request):
        """Yield ``(serving, batchers)`` for one request. Single-tenant:
        the locked serving pointers. Multi-tenant: the tenant's pool
        entry, PINNED for the scope — submit through collect — so an
        eviction racing this in-flight query can never close the
        generation under it."""
        if self._tenants is None:
            with self._lock:
                serving = self._serving
                batchers = self._batchers
            yield serving, batchers
            return
        tenant = self._resolve_tenant(request)
        try:
            with self._pool.pin(
                tenant,
                self._tenant_loader(tenant),
                timeout=self._predict_timeout_s,
            ) as staged:
                yield staged.serving, staged.batchers
        except modelpool_mod.PoolLoadTimeout:
            raise HTTPError(
                503,
                f"tenant {tenant!r} is still loading; retry",
                headers={
                    "Retry-After": admission_mod.format_retry_after(1.0)
                },
            ) from None
        except modelpool_mod.PoolLoadError as exc:
            raise HTTPError(
                500, f"tenant {tenant!r} failed to load: {exc}"
            ) from exc

    def _activate(self, staged: _StagedGeneration) -> None:
        with self._lock:
            old = self._batchers
            self._instance = staged.instance
            self._serving = staged.serving
            self._batchers = staged.batchers
            self._generation += 1
            generation = self._generation
        self._generation_gauge.labels("").set(generation)
        self._warmed_gauge.set(1 if staged.warmed else 0)
        if generation > 1:
            # not the initial load: the serving answers just changed.
            # A fold-in publishes a CHILD generation of the same
            # lineage (trainer marks it batch="fold-in") — flushed
            # under its own reason so freshness regressions are
            # attributable on the timeline.
            self._bump_cache_generation(
                "foldin"
                if getattr(staged.instance, "batch", "") == "fold-in"
                else "reload",
                generation=staged.instance.id,
            )
        for b in old:
            b.close()
        logger.info(
            "engine server serving instance %s (%d algorithm(s), "
            "generation %d)",
            staged.instance.id, len(staged.batchers), generation,
        )

    def _stage(
        self,
        for_canary: bool = False,
        engine_variant: str | None = None,
        tenant: str | None = None,
    ) -> _StagedGeneration:
        """Load + warm the latest generation WITHOUT touching the
        serving pointers — the canary path evaluates the result beside
        live traffic before :meth:`_activate` ever runs.

        ``tenant`` stages one pooled tenant's variant: batcher/compile
        sites are named per tenant (the fair-share plumbing keys
        batches on those names) and the global warm gauge is left
        alone — a cold tenant loading mid-traffic must not flap the
        replica's router admission."""
        if not for_canary and tenant is None:
            # the gauge describes the NEWEST generation: an immediate
            # reload makes the incoming (cold) generation newest, so it
            # reads 0 through the compile window. Canary staging keeps
            # it untouched — the WARM old generation is still serving
            # (and the gate separately requires the candidate warm).
            self._warmed_gauge.set(0)
        instance, algorithms, models, serving = load_deployment(
            self._engine,
            self._params,
            engine_id=self._engine_id,
            engine_version=self._engine_version,
            engine_variant=(
                engine_variant
                if engine_variant is not None
                else self._engine_variant
            ),
            ctx=self._ctx,
            storage=self._storage,
        )
        nbytes = 0
        if self._quantize or self._tenants is not None:
            # quantized tables (int8/bf16) + byte accounting: the pool
            # charges each tenant the measured device residency. Lazy
            # import: quantize pulls in jax kernels the single-tenant
            # f32 path never needs.
            from predictionio_tpu.ops import quantize as quantize_mod

            if self._quantize:
                with tracing.stage(tracing.POOL_PROMOTE):
                    models = [
                        quantize_mod.quantize_model_factors(
                            m, self._quantize
                        )
                        for m in models
                    ]
            nbytes = sum(
                quantize_mod.model_resident_bytes(m) for m in models
            )
        name_prefix = (
            f"{self._engine_id}/{tenant}/"
            if tenant is not None
            else f"{self._engine_id}/"
        )
        with tracing.stage(tracing.POOL_WARMUP):
            warmed = bool(
                self._warmup
                and self._precompile(algorithms, models, name_prefix)
            )

        def batch_fn(a, m):
            # the collector enqueues batch N+1's device work while the
            # completer is still inside batch N's barrier + per-query
            # JSON materialization
            launch, collect = a.serving_hooks()
            return TwoPhaseBatchFn(
                lambda qs: (launch(m, qs), qs),
                lambda state: collect(m, *state),
            )

        with tracing.stage(tracing.POOL_BATCHERS):
            batchers = [
                MicroBatcher(
                    batch_fn(algo, model),
                    max_batch=self._max_batch,
                    max_wait_ms=self._max_wait_ms,
                    max_queue=self._max_queue,
                    pipeline_depth=self._pipeline_depth,
                    adaptive_wait=self._adaptive_wait,
                    registry=self._registry,
                    name=f"{name_prefix}algo{i}",
                )
                for i, (algo, model) in enumerate(
                    zip(algorithms, models)
                )
            ]
        return _StagedGeneration(
            instance=instance,
            serving=serving,
            batchers=batchers,
            warmed=warmed,
            nbytes=nbytes,
        )

    def _precompile(
        self, algorithms, models, name_prefix: str | None = None
    ) -> bool:
        """Compile every power-of-two batch bucket before traffic hits.

        XLA compiles per static shape; without this, each new bucket
        size compiles lazily mid-traffic (seconds-long p99 spikes on
        first occurrence). Algorithms expose a neutral ``warmup_query``
        (default ``{}``).

        Failure policy: an algorithm whose ``warmup_query`` is None is
        served cold by design (INFO). A bucket that raises — a failed
        compile as much as an unsupported query — is a WARNING naming
        the exception type, and leaves ``pio_warmup_complete`` at 0.
        One failing bucket does not skip the rest — larger buckets may
        compile fine — but repeated failures cap out rather than burn
        the whole reload window.

        A bucket this server has already warmed for the same
        algorithm class and params over a model of the same signature
        (:func:`_model_signature`: the second tenant of a pool, the
        next generation of a reload) is not run again: its programs
        are in this process's jit cache, and a warm run is only a round
        trip to the device a bucket. The first model of a signature
        still warms them all.

        Returns True when every bucket is compiled — run now without a
        failure, or by an earlier model of the same key, whose programs
        this one relies on (cold-by-design algorithms don't count
        against it) — the condition for ``pio_warmup_complete`` to read
        1; an all-failures warmup must not advertise a warm server to
        traffic gates.
        """
        t0 = time.perf_counter()
        # per-bucket wall time lands in the registry so a scrape
        # (`pio-tpu status --metrics-url`) shows exactly which compile
        # buckets a freshly deployed server has paid for already
        bucket_gauge = self._registry.gauge(
            "pio_warmup_seconds",
            "Wall time spent warming one power-of-two compile bucket "
            "(set whether the compile succeeded or failed)",
            ("batcher", "bucket"),
        )
        total_failures = 0
        if name_prefix is None:
            name_prefix = f"{self._engine_id}/"
        for i, (algo, model) in enumerate(zip(algorithms, models)):
            name = type(algo).__name__
            batcher_name = f"{name_prefix}algo{i}"
            query = getattr(algo, "warmup_query", lambda: {})()
            if query is None:
                # the algorithm declares no neutral query exists (e.g.
                # data-dependent feature width) — serve cold by design,
                # without burning three failed warmup attempts
                logger.info("%s: no warmup query — serving cold", name)
                continue
            failures, compiled = 0, 0
            # what decides the programs a bucket compiles: the
            # algorithm, its params, and the model's shapes and dtypes
            programs = (
                type(algo).__qualname__, i,
                repr(getattr(algo, "params", None)),
                _model_signature(model),
            )
            # powers of two through the next one a non-power-of-two
            # max_batch rounds up into at predict time
            buckets = [1]
            while buckets[-1] < self._max_batch:
                buckets.append(2 * buckets[-1])
            with self._lock:
                todo = [
                    b for b in buckets if (programs, b) not in self._warmed
                ]
            warm = len(buckets) - len(todo)
            for bucket in todo:
                b0 = time.perf_counter()
                try:
                    algo.batch_predict(model, [query] * bucket)
                    compiled += 1
                    bucket_gauge.labels(batcher_name, str(bucket)).set(
                        time.perf_counter() - b0
                    )
                    self._compile_tracker.record(
                        batcher_name, str(bucket)
                    )
                    with self._lock:
                        self._warmed.add((programs, bucket))
                except Exception as e:  # noqa: BLE001 - warmup best-effort
                    bucket_gauge.labels(batcher_name, str(bucket)).set(
                        time.perf_counter() - b0
                    )
                    # a failed compile still burned a trace attempt —
                    # shape-churn accounting counts it
                    self._compile_tracker.record(
                        batcher_name, str(bucket)
                    )
                    failures += 1
                    if compiled == 0:
                        logger.warning(
                            "%s: warmup FAILED at batch %d (%s: %s) — "
                            "serving cold, pio_warmup_complete stays 0",
                            name, bucket, type(e).__name__, e,
                        )
                    else:
                        logger.warning(
                            "%s: warmup FAILED at batch %d after smaller "
                            "buckets compiled — predict may be broken at "
                            "this shape: %s: %s",
                            name, bucket, type(e).__name__, e,
                        )
                    if failures >= 3:
                        break
            total_failures += failures
            logger.info(
                "%s: warmup compiled %d bucket(s)%s%s",
                name, compiled,
                f", {warm} warm already" if warm else "",
                f", {failures} failed" if failures else "",
            )
        logger.info(
            "warmup finished in %.1fs", time.perf_counter() - t0
        )
        return total_failures == 0

    # -- routes -----------------------------------------------------------
    def _status_data(self) -> dict:
        with self._lock:
            data = {
                "status": "alive",
                # which SO_REUSEPORT worker answered (ops parity with
                # the event server's status route)
                "pid": os.getpid(),
                "engineId": self._engine_id,
                "engineVersion": self._engine_version,
                "engineVariant": self._engine_variant,
                # serving mesh topology: a model axis > 1 means the
                # factor catalog is row-sharded across devices — one
                # instance serving a catalog bigger than one chip's
                # HBM (docs/parallelism.md "Sharded ALS")
                "mesh": {
                    str(name): int(size)
                    for name, size in self._ctx.mesh.shape.items()
                },
                "modelSharded": self._ctx.model_parallelism > 1,
                "canaryState": (
                    self._canary.state
                    if self._canary is not None
                    else (self._last_canary or {}).get(
                        "state", canary_mod.IDLE
                    )
                ),
                "startTime": self._start_time.isoformat(),
                "requestCount": self._request_count,
                "avgServingSec": round(self._avg_serving_sec, 6),
                "lastServingSec": round(self._last_serving_sec, 6),
                "lastBatchPerQuerySec": round(
                    self._last_batch_per_query_sec, 6
                ),
            }
            if self._tenants is None:
                data["engineInstanceId"] = self._instance.id
                data["generation"] = self._generation
                data["trainingStartTime"] = (
                    self._instance.start_time.isoformat()
                )
                data["trainingEndTime"] = (
                    self._instance.end_time.isoformat()
                )
            else:
                data["multiTenant"] = True
                data["tenants"] = sorted(self._tenants)
                data["tenantGenerations"] = dict(
                    self._tenant_generations
                )
        if self._tenants is not None:
            # pool.stats() takes the pool's own lock — never nest it
            # inside ours
            data["pool"] = self._pool.stats()
        if self._cache is not None:
            # cache.stats() takes the cache's shard locks — outside ours
            data["cache"] = self._cache.stats()
        return data

    def _status(self, request: Request) -> Response:
        data = self._status_data()
        accept = request.headers.get("Accept") or ""
        if "text/html" in accept:
            # content-negotiated status page (reference twirl template,
            # core/.../workflow/index.scala.html rendered by ServerActor
            # on GET /)
            return Response(
                200, self._status_html(data), content_type="text/html"
            )
        return Response(200, data)

    def _status_html(self, data: dict) -> str:
        e = _html.escape

        def table(rows: list[tuple[str, str]]) -> str:
            return "<table>" + "".join(
                f"<tr><th>{e(k)}</th><td>{e(v)}</td></tr>"
                for k, v in rows
            ) + "</table>"

        def params_rows(named) -> list[tuple[str, str]]:
            name, params = named
            return [("Class", name or type(params).__name__),
                    ("Parameters", repr(params))]

        p = self._params
        algo_rows: list[tuple[str, str]] = []
        for i, (name, params) in enumerate(p.algorithms):
            algo_rows.append((f"Algorithm {i}", name))
            algo_rows.append((f"Algorithm {i} Parameters", repr(params)))
        title = (
            f"{e(self._engine_id)} ({e(self._engine_variant)}) - "
            "Engine Server"
        )
        return f"""<!DOCTYPE html>
<html lang="en">
  <head>
    <title>{title}</title>
    <style>
      body {{ font-family: sans-serif; margin: 2em; }}
      table {{ border-collapse: collapse; margin-bottom: 1.5em; }}
      th, td {{ border: 1px solid #ccc; padding: 4px 10px;
               font-family: monospace; text-align: left; }}
      th {{ background: #f3f3f3; }}
    </style>
  </head>
  <body>
    <h1>Engine Server</h1>
    <p>{e(self._engine_id)} {e(self._engine_version)}
       ({e(self._engine_variant)})</p>
    <h2>Engine Information</h2>
    {table([
        ("Training Start Time", data.get("trainingStartTime", "-")),
        ("Training End Time", data.get("trainingEndTime", "-")),
        ("Variant ID", data["engineVariant"]),
        ("Instance ID", data.get("engineInstanceId", "-")),
        ("Tenants", ", ".join(data.get("tenants", [])) or "-"),
    ])}
    <h2>Server Information</h2>
    {table([
        ("Start Time", data["startTime"]),
        ("Request Count", str(data["requestCount"])),
        ("Average Serving Time", f'{data["avgServingSec"]} seconds'),
        ("Last Serving Time", f'{data["lastServingSec"]} seconds'),
        ("Last Batch Per-Query Time",
         f'{data["lastBatchPerQuerySec"]} seconds'),
    ])}
    <h2>Data Source</h2>
    {table(params_rows(p.data_source))}
    <h2>Data Preparator</h2>
    {table(params_rows(p.preparator))}
    <h2>Algorithms</h2>
    {table(algo_rows)}
    <h2>Serving</h2>
    {table(params_rows(p.serving))}
  </body>
</html>"""

    def _shed_headers(self) -> dict[str, str]:
        """The cooperative-backpressure hint for a batcher shed: a
        ``Retry-After`` computed from live queue state (deepest backlog
        across the algorithm batchers), not a hardcoded constant. The
        shed marker is safe here: a shed query produced no prediction
        and recorded no feedback — nothing externally visible ran."""
        with self._lock:
            batchers = self._batchers or ()
        hint = max(
            (b.retry_after_s() for b in batchers), default=0.05
        )
        return {
            "Retry-After": admission_mod.format_retry_after(hint),
            admission_mod.SHED_HEADER: "batcher",
        }

    def _queries(self, request: Request) -> Response:
        return self._with_remote_log(self._queries_inner, request)

    def _batch_queries(self, request: Request) -> Response:
        return self._with_remote_log(self._batch_queries_inner, request)

    def _with_remote_log(self, handler, request: Request) -> Response:
        try:
            return handler(request)
        except Exception as exc:
            # remote error log (reference CreateServer.scala:446-457,
            # --log-url/--log-prefix): ship serving failures to a
            # collector, asynchronously, before the HTTP error goes out.
            # Overload sheds (503) are excluded — logging each shed
            # would amplify the very condition shedding protects against
            shed = isinstance(exc, HTTPError) and exc.status == 503
            if self._log_queue is not None and not shed:
                self._post_remote_log(exc, request)
            raise

    #: reports carry at most this much of the failing query body —
    #: the 64-slot queue must bound bytes, not just entries
    _LOG_QUERY_LIMIT = 4096

    def _post_remote_log(self, exc: Exception, request: Request) -> None:
        """Enqueue an error report; the single sender thread POSTs it.
        Nothing here may raise — the original serving error must reach
        the client untouched."""
        try:
            body = request.body[: self._LOG_QUERY_LIMIT]
            payload = json.dumps(
                {
                    "message":
                        f"{self._log_prefix}{type(exc).__name__}: {exc}",
                    "engineInstance": {
                        "engineId": self._engine_id,
                        "engineVersion": self._engine_version,
                        "engineVariant": self._engine_variant,
                    },
                    "query": body.decode("utf-8", "replace"),
                    "queryTruncated":
                        len(request.body) > self._LOG_QUERY_LIMIT,
                }
            ).encode("utf-8")
            self._log_queue.put_nowait(payload)
        except queue.Full:
            logger.debug("remote error log queue full; report dropped")
        except Exception as enc_exc:  # noqa: BLE001 - must not mask exc
            logger.debug("remote error log encode failed: %s", enc_exc)

    def _drain_log_queue(self) -> None:
        while True:
            payload = self._log_queue.get()
            if payload is None:  # close() sentinel
                return
            try:
                req = urllib.request.Request(
                    self._log_url,
                    data=payload,
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                urllib.request.urlopen(req, timeout=5).read()
            except Exception as send_exc:  # noqa: BLE001 - best effort
                logger.debug("remote error log failed: %s", send_exc)

    def _queries_inner(self, request: Request) -> Response:
        t0 = time.perf_counter()
        with tracing.stage(tracing.ENGINE_DECODE):
            query = request.json()
        if not isinstance(query, dict):
            raise HTTPError(400, "query must be a JSON object")
        claim = None
        if self._cache is not None and not self._cache_bypass(request):
            tenant = (
                "" if self._tenants is None
                else self._resolve_tenant(request)
            )
            token = self._cache_token(tenant)
            if token is not None:
                # lookup AFTER admission (the wrapper admitted us) but
                # BEFORE the batcher: a hit consumes no batcher slot
                # and (multi-tenant) takes no pool pin
                claim = self._cache.claim(
                    tenant, token,
                    querycache_mod.canonical_query_bytes(query),
                )
                if claim.hit:
                    return self._cached_response(claim.value, "hit", t0)
                if not claim.leader:
                    return self._join_coalesced(claim, t0)
        try:
            return self._compute_query(request, query, t0, claim)
        except BaseException as exc:
            if claim is not None:
                # leader failed: wake every waiter with the REAL error
                # and clear the slot — the next claimant leads afresh
                # (no cache poisoning)
                self._cache.abort(claim, exc)
            raise

    def _cached_response(
        self, value: bytes, state: str, t0: float
    ) -> Response:
        """A response served from the cache (hit) or another request's
        computation (coalesced): same latency bookkeeping as the
        compute path, plus the X-PIO-Cache provenance header. Canary
        observation is skipped — near-zero cache latencies must not
        skew the regression-watch baseline (the gate shadow-scores
        through the no-cache bypass instead)."""
        elapsed = time.perf_counter() - t0
        with self._lock:
            self._request_count += 1
            self._last_serving_sec = elapsed
            self._avg_serving_sec += (
                elapsed - self._avg_serving_sec
            ) / self._request_count
        return Response(
            200, value,
            headers={querycache_mod.CACHE_HEADER: state},
        )

    def _join_coalesced(
        self, claim: querycache_mod.Claim, t0: float
    ) -> Response:
        """Waiter side of single-flight: block on the leader's result
        under THIS request's own budget. Expiry detaches the waiter
        without cancelling the leader; a leader failure surfaces the
        leader's real error."""
        timeout = self._predict_timeout_s
        request_deadline = resilience.get_deadline()
        if request_deadline is not None:
            timeout = min(
                timeout,
                max(0.001,
                    request_deadline.expires_mono - time.monotonic()),
            )
        try:
            value = self._cache.join(claim, timeout)
        except querycache_mod.WaiterTimeout:
            raise HTTPError(
                504,
                "deadline expired while coalesced on an identical "
                "in-flight query",
            ) from None
        except querycache_mod.LeaderFailed as exc:
            cause = exc.__cause__
            if isinstance(cause, HTTPError):
                raise HTTPError(
                    cause.status, cause.message,
                    headers=dict(cause.headers) or None,
                ) from None
            raise HTTPError(
                500, f"coalesced computation failed: {cause}"
            ) from exc
        return self._cached_response(value, "coalesced", t0)

    def _compute_query(
        self,
        request: Request,
        query: dict,
        t0: float,
        claim: querycache_mod.Claim | None,
    ) -> Response:
        for _attempt in range(2):
            # the snapshot holds the tenant's pool pin (multi-tenant)
            # for the WHOLE submit→collect span, so eviction can't
            # close the generation under an in-flight query
            with contextlib.ExitStack() as pinned:
                # the snapshot comes before the stage: in a pool it is
                # the tenant's pin, and where the tenant has to load
                # first the pool times that wait as `pool.wait`
                serving, batchers = pinned.enter_context(
                    self._serving_snapshot(request)
                )
                with tracing.stage(tracing.ENGINE_SUBMIT):
                    supplemented = serving.supplement(query)
                    futures = []
                    # single-flight leaders submit at the HIGHEST class
                    # coalesced so far: a CRITICAL waiter must not sit
                    # behind a SHEDDABLE leader's batcher slot
                    escalate = (
                        admission_mod.criticality(claim.criticality())
                        if claim is not None
                        else contextlib.nullcontext()
                    )
                    try:
                        with escalate:
                            for b in batchers:
                                futures.append(b.submit(supplemented))
                    except BatcherOverloaded:
                        # queue-depth bound hit: shed immediately
                        # instead of queueing into a predict-timeout
                        # hang. Earlier algorithms' accepted submits
                        # must not run for nothing.
                        self._abandon(futures)
                        raise HTTPError(
                            503, "server overloaded; retry later",
                            headers=self._shed_headers(),
                        )
                    except resilience.DeadlineExceeded:
                        self._abandon(futures)
                        raise HTTPError(
                            504, "deadline expired before dispatch"
                        )
                    except RuntimeError:
                        # /reload swapped+closed the batchers between
                        # our snapshot and submit — retry once against
                        # the fresh set (a re-pin in multi-tenant mode)
                        self._abandon(futures)
                        continue
                try:
                    prediction = self._serve_one(
                        serving, query, supplemented, futures
                    )
                except resilience.DeadlineExceeded:
                    # the batcher dropped the slot pre-dispatch: the
                    # client's budget ran out while the query was queued
                    raise HTTPError(
                        504, "deadline expired before device dispatch"
                    )
                except BatcherOverloaded:
                    # a queued slot was evicted by a higher-criticality
                    # submission while we waited — a shed, not a fault.
                    # The sibling algorithms' still-live slots are
                    # abandoned (the evicted future is already done;
                    # only pending peers are cancelled, so the
                    # wasted-dispatch counter stays honest)
                    self._abandon([f for f in futures if not f.done()])
                    raise HTTPError(
                        503, "shed under overload; retry later",
                        headers=self._shed_headers(),
                    )
                except Exception:
                    # a genuine serving error feeds the post-promotion
                    # watch (sheds/expiries above don't: they indict
                    # load, not the model) before surfacing to the
                    # client untouched
                    self._canary_observe(
                        supplemented, None,
                        time.perf_counter() - t0, ok=False,
                    )
                    raise

                elapsed = time.perf_counter() - t0
                with self._lock:
                    self._request_count += 1
                    self._last_serving_sec = elapsed
                    self._avg_serving_sec += (
                        elapsed - self._avg_serving_sec
                    ) / self._request_count
                self._canary_observe(
                    supplemented, prediction, elapsed, ok=True
                )
                if claim is not None:
                    # serialize ONCE with the exact call the dict
                    # response path uses, so hits/coalesced answers
                    # stay byte-identical to uncached ones; fill wakes
                    # every coalesced waiter with these bytes
                    body = json.dumps(prediction).encode("utf-8")
                    self._cache.fill(claim, body)
                    return Response(
                        200, body,
                        headers={querycache_mod.CACHE_HEADER: "miss"},
                    )
                return Response(200, prediction)
        raise HTTPError(503, "server is reloading; retry")

    def _serve_one(self, serving, query, supplemented, futures):
        """One query of the single route: wait for its per-algorithm
        futures, then the shared tail of the predict pipeline."""
        with tracing.stage(tracing.ENGINE_AWAIT):
            predictions = self._await_predictions(
                futures, time.monotonic() + self._predict_timeout_s
            )
        with tracing.stage(tracing.ENGINE_SERVE):
            return self._serve_tail(
                serving, query, supplemented, predictions
            )

    def _await_predictions(self, futures, deadline: float) -> list:
        """Block on one query's per-algorithm futures. ``deadline`` (a
        ``time.monotonic()`` value) bounds the TOTAL wait across all of
        them, further capped by the request's propagated
        X-PIO-Deadline when one rode in."""
        deadline, request_deadline = self._wait_until(deadline)
        try:
            return [
                f.result(timeout=max(0.001, deadline - time.monotonic()))
                for f in futures
            ]
        except FuturesTimeout:
            raise self._timeout_error(request_deadline) from None

    @staticmethod
    def _wait_until(deadline: float):
        """``(deadline, request deadline)``: ``deadline`` capped by the
        request's propagated X-PIO-Deadline when one rode in."""
        request_deadline = resilience.get_deadline()
        if request_deadline is not None:
            deadline = min(deadline, request_deadline.expires_mono)
        return deadline, request_deadline

    @staticmethod
    def _timeout_error(request_deadline) -> Exception:
        """What a wait for the batcher that ran out raises. Where the
        CLIENT's budget ran out while the query sat in the batch queue
        that is a 504, not a server fault (the batcher will drop the
        still-queued slot pre-dispatch); else the time-out stands."""
        if request_deadline is not None and request_deadline.expired:
            return resilience.DeadlineExceeded(
                "deadline expired while queued for dispatch"
            )
        return FuturesTimeout()

    def _serve_tail(self, serving, query, supplemented, predictions):
        """serve → feedback → plugin block/sniff
        (CreateServer.scala:603-606). Used by the single and the batch
        routes so their semantics cannot diverge."""
        prediction = serving.serve(supplemented, predictions)
        if self._feedback:
            prediction = self._record_feedback(query, prediction)
        engine_info = {
            "engineId": self._engine_id,
            "engineVersion": self._engine_version,
            "engineVariant": self._engine_variant,
        }
        prediction = self._plugins.block_output(
            engine_info, query, prediction
        )
        self._plugins.sniff_output(engine_info, query, prediction)
        return prediction

    #: queries per /batch/queries.json call — generous relative to the
    #: event API's 50 (a query is one dict; responses dominate the
    #: payload), still bounding a single request's memory
    MAX_QUERY_BATCH = 100

    def _batch_queries_inner(self, request: Request) -> Response:
        """Many queries, one HTTP round trip, per-query statuses.

        All queries are SUBMITTED to the micro-batchers before any
        result is collected, so a batch fills device dispatches instead
        of serializing one query per dispatch."""
        t0 = time.perf_counter()
        with tracing.stage(tracing.ENGINE_DECODE):
            payload = request.json()
        if not isinstance(payload, list):
            raise HTTPError(400, "batch must be a JSON array of queries")
        if len(payload) > self.MAX_QUERY_BATCH:
            raise HTTPError(
                400,
                f"batch too large: {len(payload)} queries "
                f"(max {self.MAX_QUERY_BATCH})",
            )
        if not payload:
            return Response(200, [])
        for _attempt in range(2):
            # pin (multi-tenant) spans submit AND collection, same as
            # the single-query route
            with contextlib.ExitStack() as pinned:
                serving, batchers = pinned.enter_context(
                    self._serving_snapshot(request)
                )
                with tracing.stage(tracing.ENGINE_SUBMIT):
                    entries, groups, any_submitted = self._submit_batch(
                        serving, batchers, payload
                    )
                if _attempt == 0 and not any_submitted and any(
                    e[0] == "reloading" for e in entries
                ):
                    # a /reload raced us before ANY submit was accepted
                    # (not even a partial multi-algorithm one): nothing
                    # was dispatched, so retrying against the fresh
                    # batchers is safe (mirrors the single-query retry)
                    continue
                results = self._collect_batch(
                    serving, entries, groups, payload, request
                )
                break

        elapsed = time.perf_counter() - t0
        n = len(payload)
        with self._lock:
            self._request_count += n
            # wall clock here, per-query mean in its OWN field — the
            # old code stored elapsed/n into lastServingSec while the
            # single route stored wall clock (ADVICE r5 semantics mix)
            self._last_serving_sec = elapsed
            self._last_batch_per_query_sec = elapsed / n
            self._avg_serving_sec += (
                elapsed / n - self._avg_serving_sec
            ) * n / self._request_count
        return Response(200, results)

    def _collect_batch(
        self, serving, entries, groups, payload, request
    ) -> list[dict]:
        """Collect a submitted post's groups into per-query statuses
        (runs inside the serving snapshot so multi-tenant pins cover
        the waits)."""
        # one deadline for the WHOLE post: a hung dispatch must not
        # hold the connection for N sequential predict timeouts
        deadline, request_deadline = self._wait_until(
            time.monotonic() + self._predict_timeout_s
        )

        # every wait first, then every tail: the response leaves when
        # the last query is done either way, and each stage is one
        # interval of the post, observed once. One wait an algorithm:
        # a group wakes this thread once, when its last slot is in
        with tracing.stage(tracing.ENGINE_AWAIT):
            for group in groups:
                group.wait(max(0.001, deadline - time.monotonic()))

        results = []
        logged = False  # one remote report per batch, not per slot
        with tracing.stage(tracing.ENGINE_SERVE):
            for (state, data, slot), q in zip(entries, payload):
                if state == "bad":
                    results.append(
                        {"status": 400,
                         "message": "query must be a JSON object"}
                    )
                    continue
                if state == "shed":
                    results.append(
                        {"status": 503,
                         "message": "server overloaded; retry later"}
                    )
                    continue
                if state == "reloading":
                    results.append(
                        {"status": 503,
                         "message": "server is reloading; retry"}
                    )
                    continue
                if state == "expired":
                    results.append(
                        {"status": 504,
                         "message": "deadline expired before dispatch"}
                    )
                    continue
                if state == "error":
                    if self._log_queue is not None and not logged:
                        self._post_remote_log(data, request)
                        logged = True
                    results.append({"status": 500, "message": str(data)})
                    continue
                try:
                    try:
                        predictions = [g.result(slot) for g in groups]
                    except FuturesTimeout:
                        raise self._timeout_error(
                            request_deadline
                        ) from None
                    prediction = self._serve_tail(
                        serving, q, data, predictions
                    )
                    results.append(
                        {"status": 200, "prediction": prediction}
                    )
                except resilience.DeadlineExceeded:
                    results.append(
                        {"status": 504,
                         "message": "deadline expired before device "
                         "dispatch"}
                    )
                except BatcherOverloaded:
                    # a queued slot was evicted by a higher-criticality
                    # submission while the post waited: the sibling
                    # algorithms' slots of this query that have no
                    # answer yet are abandoned
                    self._abandon_slots(groups, (slot,))
                    results.append(
                        {"status": 503,
                         "message": "shed under overload; retry later"}
                    )
                except Exception as exc:  # noqa: BLE001 - per-slot status
                    if self._log_queue is not None and not logged:
                        self._post_remote_log(exc, request)
                        logged = True
                    results.append({"status": 500, "message": str(exc)})
        return results

    def _abandon(self, futures) -> None:
        """A query's accepted per-algorithm submits are being discarded
        (partial overload or mid-submit reload): cancel them so the
        batcher drops the slots before dispatch. A future past the
        point of cancellation is genuinely wasted device work — counted
        in ``pio_shed_wasted_dispatch_total`` instead of silently
        thrown away (ADVICE r5)."""
        for f in futures:
            if not f.cancel():
                self._shed_wasted.inc()

    def _abandon_slots(self, groups, slots) -> None:
        """:meth:`_abandon` for a post: the queries at ``slots`` are
        discarded in every algorithm's group. The slots still queued
        are dropped before dispatch; those the device is already
        working on are the wasted ones."""
        for group in groups:
            if wasted := group.cancel(slots):
                self._shed_wasted.inc(wasted)

    def _submit_batch(
        self, serving, batchers, payload
    ) -> tuple[list[tuple], list, bool]:
        """Submit a post's queries, one group an algorithm; returns
        ``(entries, groups, any_submitted)``.

        Entries, one a query: ``("ok", supplemented, slot)`` (its place
        in every group) | ``("bad"|"shed"|"reloading"|"expired", None,
        None)`` | ``("error", exc, None)``. A query is ``ok`` when every
        algorithm's batcher admitted it; one that any batcher shed at
        its bound is ``shed``, and its slots the other batchers took
        are cancelled (:meth:`_abandon_slots`). ``any_submitted`` is
        True once ANY slot was admitted — including those of a group
        abandoned because a later batcher refused the post — which is
        exactly the condition under which a whole-post retry would
        double-dispatch (close() is graceful: accepted items still
        run): a cancelled slot can already have been dispatched by the
        time cancel() runs."""
        entries: list[tuple[str, Any, int | None]] = []
        items = []
        for q in payload:
            if not isinstance(q, dict):
                entries.append(("bad", None, None))
                continue
            try:
                supplemented = serving.supplement(q)
            except Exception as exc:  # noqa: BLE001 - per-slot status
                entries.append(("error", exc, None))
                continue
            entries.append(("ok", supplemented, len(items)))
            items.append(supplemented)
        if not items:
            return entries, [], False
        groups: list = []
        refused = "shed"
        try:
            for b in batchers:
                groups.append(b.submit_group(items))
            # each batcher admits the post as far as its queue has room
            admitted = min((g.admitted for g in groups), default=len(items))
        except resilience.DeadlineExceeded:
            refused, admitted = "expired", 0
        except RuntimeError:
            # /reload closed the snapshot's batchers mid-submit
            refused, admitted = "reloading", 0
        any_submitted = any(g.admitted for g in groups)
        if admitted < len(items):
            # what some batcher did not take must not run for nothing
            # in the others
            self._abandon_slots(groups, range(admitted, len(items)))
            entries = [
                (refused, None, None)
                if entry[2] is not None and entry[2] >= admitted
                else entry
                for entry in entries
            ]
            if not admitted:
                groups = []  # nothing left to wait for
        return entries, groups, any_submitted

    def _record_feedback(self, query: dict, prediction):
        """Store a ``predict`` event (entity ``pio_pr``) carrying query +
        prediction, and inject the prId into the response
        (reference CreateServer.scala:539-600)."""
        pr_id = None
        if isinstance(prediction, dict):
            pr_id = prediction.get("prId")
        pr_id = pr_id or secrets.token_hex(16)
        try:
            with self._lock:
                instance = self._instance
            event = Event(
                event="predict",
                entity_type="pio_pr",
                entity_id=pr_id,
                properties=DataMap(
                    {
                        "engineInstanceId": (
                            instance.id if instance is not None else ""
                        ),
                        "query": query,
                        "prediction": prediction,
                    }
                ),
            )
            app_id = self._feedback_app_id
            if app_id is not None:
                with tracing.span("store/insert_event", kind="feedback"):
                    self._storage.get_events().insert(event, app_id)
        except Exception:  # noqa: BLE001 - feedback must not break serving
            logger.exception("feedback event failed")
        if isinstance(prediction, dict):
            prediction = {**prediction, "prId": pr_id}
        return prediction

    def _reload(self, request: Request) -> Response:
        # admin routes require the server key when auth is enforced
        # (reference ServerActor mixes in KeyAuthentication for /stop;
        # queries.json stays open)
        self._server_config.check_key(request)
        body: Any = {}
        if request.body:
            try:
                body = request.json()
            except Exception:  # noqa: BLE001 - bad body is a 400
                raise HTTPError(400, "reload body must be JSON") from None
        if not isinstance(body, dict):
            raise HTTPError(400, "reload body must be a JSON object")
        if self._tenants is not None:
            return self._reload_tenant(request, body)
        want_canary = body.get("canary")
        if want_canary is None:
            want_canary = self._canary_config is not None
        with self._reload_mutex:
            if not want_canary:
                # an explicit immediate reload supersedes whatever the
                # canary was evaluating — resolved deterministically
                # BEFORE the swap so a late watch verdict cannot roll a
                # freshly-loaded generation back to an ancient one. The
                # ≤0.15 s settle-retry inside deliberately holds the
                # reload mutex: serializing reloads behind a racing
                # verdict applier is the point of the mutex.
                # pio-lint: disable-next=lock-blocking -- bounded 0.15s settle; reload serialization is intentional
                self._cancel_active_canary("superseded by manual reload")
                self._load()
                return Response(
                    200,
                    {
                        "message": "reloaded",
                        "engineInstanceId": self._instance.id,
                    },
                )
            return self._start_canary()

    def _reload_tenant(self, request: Request, body: dict) -> Response:
        """Per-tenant /reload in multi-tenant mode: restage ONE
        tenant's variant through the pool. In-flight queries keep the
        old generation pinned until they drain; everything else is
        untouched."""
        tenant = (
            body.get("tenant")
            or request.query.get("accessKey")
            or request.headers.get(admission_mod.TENANT_HEADER)
            or ""
        )
        if not tenant:
            raise HTTPError(
                400,
                'multi-tenant reload requires a tenant (body '
                '{"tenant": ...}, accessKey param, or '
                f"{admission_mod.TENANT_HEADER} header)",
            )
        if tenant not in self._tenants:
            raise HTTPError(404, f"unknown tenant {tenant!r}")
        with self._reload_mutex:
            try:
                self._pool.replace(tenant, self._tenant_loader(tenant))
            except Exception as exc:  # noqa: BLE001 - surfaced as 500
                self._timeline.record(
                    "tenant_reload",
                    f"tenant {tenant!r} reload failed: {exc}",
                    severity=timeline_mod.ERROR, tenant=tenant,
                )
                raise HTTPError(
                    500, f"tenant {tenant!r} reload failed: {exc}"
                ) from exc
            with self._lock:
                generation = self._tenant_generations.get(tenant, 0)
                instance = self._tenant_instances.get(tenant)
            self._bump_cache_generation(
                "foldin"
                if getattr(instance, "batch", "") == "fold-in"
                else "reload",
                tenant=tenant,
                generation=getattr(instance, "id", generation),
            )
            self._timeline.record(
                "tenant_reload",
                f"tenant {tenant!r} reloaded to generation {generation}",
                tenant=tenant, generation=generation,
            )
        return Response(
            200,
            {
                "message": "reloaded",
                "tenant": tenant,
                "generation": generation,
            },
        )

    def _cancel_active_canary(self, reason: str) -> None:
        """Resolve a live canary in favor of the CURRENT serving state:
        shadowing → discard the staged candidate; watching → keep the
        promoted generation and release the retained one. Claims the
        verdict slot first so no request thread can apply a competing
        verdict; if one was already claimed, a brief settle-retry lets
        its applier finish (promotion resets the slot, so the second
        attempt claims it)."""
        for _attempt in range(3):
            canary = self._canary
            if canary is None:
                return
            if canary.cancel(reason):
                if canary.state == canary_mod.WATCHING:
                    canary.finished(canary_mod.STABLE)
                    retained = canary.retained
                    if (
                        retained is not None
                        and retained.batchers is not self._batchers
                    ):
                        self._close_batchers_async(retained.batchers)
                else:
                    canary.finished(canary_mod.REJECTED)
                    if canary.staged.batchers is not self._batchers:
                        self._close_batchers_async(canary.staged.batchers)
                self._finish_canary(canary)
                return
            time.sleep(0.05)
        logger.warning(
            "could not cancel the active canary (verdict applier racing)"
        )

    def _start_canary(self) -> Response:
        active = self._canary
        if active is not None and active.state in (
            canary_mod.SHADOWING, canary_mod.WATCHING
        ):
            raise HTTPError(
                409,
                f"a canary is already {active.state}; wait for its "
                "verdict (GET /canary)",
            )
        staged = self._stage(for_canary=True)
        with self._lock:
            serving_id = self._instance.id
        if staged.instance.id == serving_id:
            self._close_batchers_async(staged.batchers)
            return Response(
                200,
                {
                    "message": "already serving the latest generation",
                    "engineInstanceId": serving_id,
                },
            )
        if self._warmup and not staged.warmed:
            # the canary gate REQUIRES a warm candidate (a cold one
            # would promote into compile-spike latency and instantly
            # roll back); a never-warm generation fails the swap with
            # the old generation untouched — router swap semantics
            self._close_batchers_async(staged.batchers)
            raise HTTPError(
                409,
                f"canary rejected: generation {staged.instance.id} "
                "did not complete warmup",
            )
        fresh = canary_mod.ShadowCanary(
            staged,
            config=self._canary_config or canary_mod.CanaryConfig(),
            registry=self._registry,
            shadow_fn=self._shadow_score,
        )
        with self._lock:
            # same guard _finish_canary's CAS takes: installs and
            # clears of the canary slot agree on one lock
            self._canary = fresh
        logger.info(
            "canary shadowing generation %s beside %s",
            staged.instance.id, serving_id,
        )
        return Response(
            202,
            {
                "message": "canary shadowing; promotion is gated on "
                           "live-traffic shadow scores (GET /canary)",
                "engineInstanceId": staged.instance.id,
                "state": canary_mod.SHADOWING,
            },
        )

    def _canary_status(self, request: Request) -> Response:
        canary = self._canary
        if canary is not None:
            data = canary.to_dict()
        else:
            data = self._last_canary or {"state": canary_mod.IDLE}
        with self._lock:
            data = {
                **data,
                "servingInstanceId": self._instance.id,
                "generation": self._generation,
            }
        return Response(200, data)

    # -- canary plumbing --------------------------------------------------
    def _shadow_score(self, supplemented):
        """Score one sampled query on the staged generation (shadow
        worker thread only). Infrastructure drops (shed, expired,
        mid-close) raise ShadowDropped — never a gate veto; a model
        exception propagates and vetoes the canary."""
        canary = self._canary
        if canary is None:
            raise canary_mod.ShadowDropped()
        staged = canary.staged
        timeout = (
            self._canary_config or canary_mod.CanaryConfig()
        ).shadow_timeout_s
        futures = []
        try:
            for b in staged.batchers:
                futures.append(b.submit(supplemented))
            predictions = [f.result(timeout=timeout) for f in futures]
        except (
            BatcherOverloaded,
            resilience.DeadlineExceeded,
            FuturesTimeout,
            RuntimeError,
        ) as e:
            self._abandon([f for f in futures if not f.done()])
            raise canary_mod.ShadowDropped() from e
        prediction = staged.serving.serve(supplemented, predictions)
        if self._feedback and isinstance(prediction, dict):
            # mirror the prId strip on the old side (_canary_observe):
            # only model-comparable content enters the divergence score
            prediction = {
                k: v for k, v in prediction.items() if k != "prId"
            }
        return prediction

    def _canary_observe(
        self, supplemented, prediction, elapsed_s: float, ok: bool
    ) -> None:
        """Request-path canary hook: feed the baseline/watch stats,
        maybe enqueue a shadow score, and apply any pending verdict."""
        canary = self._canary
        if canary is None:
            return
        if self._feedback and isinstance(prediction, dict):
            # _record_feedback injected a random prId AFTER serving;
            # the shadow path never runs feedback, so leaving it in
            # would score a guaranteed key-mismatch on every shadow
            # sample and veto every canary
            prediction = {
                k: v for k, v in prediction.items() if k != "prId"
            }
        canary.observe(supplemented, prediction, elapsed_s, ok=ok)
        decision = canary.take_decision()
        if decision is not None:
            self._apply_canary_decision(canary, decision)

    def _apply_canary_decision(
        self, canary: canary_mod.ShadowCanary, decision: str
    ) -> None:
        """Apply a single-fire canary verdict. Runs on a request
        thread; generation swaps happen under the server lock, batcher
        teardown is deferred to a closer thread (close() joins batcher
        threads — never from a path a batcher callback might own)."""
        if decision == "promote":
            staged = canary.staged
            with self._lock:
                retained = _StagedGeneration(
                    instance=self._instance,
                    serving=self._serving,
                    batchers=self._batchers,
                    warmed=True,
                )
                self._instance = staged.instance
                self._serving = staged.serving
                self._batchers = staged.batchers
                self._generation += 1
                generation = self._generation
            self._generation_gauge.labels("").set(generation)
            self._warmed_gauge.set(1 if staged.warmed else 0)
            self._bump_cache_generation(
                "promote", generation=staged.instance.id
            )
            canary.promoted(retained)
            self._timeline.record(
                "canary_verdict",
                f"canary PROMOTED instance {staged.instance.id} "
                f"(now generation {generation})",
                generation=generation, decision="promote",
            )
            logger.info(
                "canary PROMOTED generation %s (now generation %d); "
                "watching for regression, previous %s retained",
                staged.instance.id, generation, retained.instance.id,
            )
        elif decision == "reject":
            canary.finished(canary_mod.REJECTED)
            self._close_batchers_async(canary.staged.batchers)
            self._finish_canary(canary)
            self._timeline.record(
                "canary_verdict",
                f"canary REJECTED instance {canary.staged.instance.id}: "
                f"{canary.reason}",
                severity=timeline_mod.WARN, decision="reject",
            )
            logger.warning(
                "canary REJECTED generation %s: %s (still serving %s)",
                canary.staged.instance.id, canary.reason,
                self._instance.id,
            )
        elif decision == "rollback":
            retained = canary.retained
            rolled_back = canary.staged
            with self._lock:
                self._instance = retained.instance
                self._serving = retained.serving
                self._batchers = retained.batchers
                self._generation += 1
                generation = self._generation
            self._generation_gauge.labels("").set(generation)
            self._warmed_gauge.set(1 if retained.warmed else 0)
            # the rolled-back generation's answers must vanish: the
            # epoch bump reknames every key (entries from the bad
            # generation are unreachable) and the flush drops them
            self._bump_cache_generation(
                "rollback", generation=retained.instance.id
            )
            canary.finished(canary_mod.ROLLED_BACK)
            self._close_batchers_async(rolled_back.batchers)
            self._finish_canary(canary)
            self._timeline.record(
                "canary_verdict",
                f"canary ROLLED BACK to instance {retained.instance.id}: "
                f"{canary.reason}",
                severity=timeline_mod.ERROR, generation=generation,
                decision="rollback",
            )
            logger.warning(
                "canary ROLLED BACK to generation %s: %s",
                retained.instance.id, canary.reason,
            )
        elif decision == "stable":
            canary.finished(canary_mod.STABLE)
            self._close_batchers_async(canary.retained.batchers)
            self._finish_canary(canary)
            self._timeline.record(
                "canary_verdict",
                f"canary STABLE on instance {canary.staged.instance.id} "
                f"({canary.reason})",
                decision="stable",
            )
            logger.info(
                "canary STABLE on generation %s (%s)",
                canary.staged.instance.id, canary.reason,
            )

    def _finish_canary(self, canary: canary_mod.ShadowCanary) -> None:
        self._last_canary = canary.to_dict()
        # CAS under the lock, not a blind (or bare-checked) clear: a
        # verdict applier finishing late must not clobber a newer
        # canary a reload installed between its check and its write
        with self._lock:
            if self._canary is canary:
                self._canary = None

    def _close_batchers_async(self, batchers) -> None:
        # close() drains in-flight dispatches and joins the batcher's
        # threads — bounded but slow; a request thread must not pay it
        threading.Thread(
            target=lambda: [b.close() for b in batchers],
            name="generation-close",
            daemon=True,
        ).start()

    def _stop(self, request: Request) -> Response:
        self._server_config.check_key(request)
        if self._http is not None:
            threading.Thread(
                target=self._http.shutdown, daemon=True
            ).start()
        return Response(200, {"message": "stopping"})

    def _debug_profile(self, request: Request) -> Response:
        """Key-gated on-demand profile capture (docs/observability.md
        "Profile capture"): run a duration-bounded jax.profiler trace
        plus a flight-recorder/device snapshot of the same window and
        return the whole artifact as a base64 tar.gz — one at a time
        (jax.profiler is process-global), 409 on overlap."""
        self._server_config.check_key(request)
        body = request.json() if request.body else {}
        if body is None:
            body = {}
        if not isinstance(body, dict):
            raise HTTPError(400, "body must be a JSON object")
        max_ms = max(
            50.0, resilience._env_float("PIO_PROFILE_MAX_MS", 30000.0)
        )
        try:
            duration_ms = float(body.get("durationMs", 1000.0))
        except (TypeError, ValueError):
            raise HTTPError(400, "durationMs must be a number")
        duration_ms = min(max_ms, max(50.0, duration_ms))
        with self._lock:
            # flag, not a held lock: the capture window sleeps for
            # durationMs and must not block status/metrics readers
            if self._profile_active:
                raise HTTPError(
                    409, "a profile capture is already running"
                )
            self._profile_active = True
        try:
            manifest = profiling.capture(
                duration_ms / 1000.0,
                tracer=self._tracer,
                device_sample_fn=self._device_sampler.sample_once,
            )
            bundle = profiling.bundle(manifest["artifactDir"])
        finally:
            with self._lock:
                self._profile_active = False
        return Response(
            200,
            {
                "profile": manifest,
                "bundle": base64.b64encode(bundle).decode("ascii"),
            },
        )

    # -- lifecycle --------------------------------------------------------
    def serve(
        self,
        host: str = "0.0.0.0",
        port: int = 8000,
        bind_retries: int = 3,
        undeploy_first: bool = True,
        reuse_port: bool = False,
    ) -> HTTPServer:
        """Bind the REST service: undeploy-before-deploy handshake, then
        bind with retries (reference MasterActor StartServer →
        undeploy() → BindServer with retry 3,
        CreateServer.scala:280-378)."""
        if undeploy_first and port:
            undeploy_existing(host, port, self._server_config)
        last_exc: OSError | None = None
        for attempt in range(max(1, bind_retries)):
            try:
                # enforce_key=False: TLS still applies, but key auth is
                # per-route (/stop, /reload) — queries.json stays open
                self._http = HTTPServer(
                    self.router,
                    host=host,
                    port=port,
                    server_config=self._server_config,
                    enforce_key=False,
                    reuse_port=reuse_port,
                    service="engine",
                    registry=self._registry,
                    tracer=self._tracer,
                )
                # graceful drain: after in-flight requests finish,
                # close() the batchers so the current device batch
                # completes before the process exits
                self._http.add_drain_hook(self.close)
                self._device_sampler.start()
                return self._http
            except OSError as exc:
                last_exc = exc
                remaining = bind_retries - attempt - 1
                if remaining <= 0:
                    break
                logger.error(
                    "Bind to %s:%d failed (%s). Retrying... "
                    "(%d more trial(s))",
                    host, port, exc, remaining,
                )
                time.sleep(1.0)
        raise last_exc  # type: ignore[misc]

    def close(self) -> None:
        # take the canary and the serving batcher list in one locked
        # step: a request thread applying a late verdict (or a reload)
        # may be swapping these exact fields while the drain hook runs.
        # The batcher list is REPLACED on swap, never mutated in place,
        # so holding the reference keeps the identity comparison below
        with self._lock:
            canary = self._canary
            self._canary = None
            batchers = self._batchers
        # an in-flight canary's staged/retained generations hold their
        # own batchers; close them too (skipping whichever set IS the
        # serving one — closed below)
        if canary is not None:
            canary.close()
            for gen in (canary.staged, canary.retained):
                if gen is None or gen.batchers is batchers:
                    continue
                for b in gen.batchers:
                    b.close()
        for b in batchers or ():
            b.close()
        if self._pool is not None and self._owns_pool:
            # pool close drains the loader thread and closes every
            # resident generation's batchers
            self._pool.close()
        if self._cache is not None:
            # fails any still-coalesced waiters instead of stranding
            # their threads on a dead leader
            self._cache.close()
        self._device_sampler.stop()
        self._compile_watch.close()
        self._plugins.close()
        if self._log_queue is not None:
            # stop the sender so a retired server (and its staged
            # model, reachable through the bound method) can be GC'd.
            # A full queue is being actively drained (≤5 s per send),
            # so a bounded blocking put suffices; on timeout the
            # daemon thread is abandoned to process exit.
            try:
                self._log_queue.put(None, timeout=10)
            except queue.Full:
                logger.debug("remote error log sender did not stop")


def undeploy_existing(host: str, port: int, server_config=None) -> bool:
    """POST /stop to whatever occupies ``host:port`` before binding
    there (reference MasterActor.undeploy, CreateServer.scala:280-305).
    Returns True if an old server acknowledged the stop."""
    probe_host = "127.0.0.1" if host in ("0.0.0.0", "") else host
    ssl_enabled = bool(getattr(server_config, "ssl_enabled", False))
    scheme = "https" if ssl_enabled else "http"
    url = f"{scheme}://{probe_host}:{port}/stop"
    key = getattr(server_config, "access_key", "") or ""
    if key:
        url += "?" + urllib.parse.urlencode({"accessKey": key})
    tls_ctx = None
    if ssl_enabled:
        # the old server typically runs a self-signed cert; this is a
        # localhost control handshake, not a trust decision
        import ssl as _ssl

        tls_ctx = _ssl.create_default_context()
        tls_ctx.check_hostname = False
        tls_ctx.verify_mode = _ssl.CERT_NONE
    logger.info(
        "Undeploying any existing engine instance at %s:%d",
        probe_host, port,
    )
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, method="POST"),
            timeout=5,
            context=tls_ctx,
        ) as resp:
            if resp.status == 200:
                # give the old server a moment to release the socket
                time.sleep(1.0)
                return True
            logger.error(
                "Existing server at %s:%d answered HTTP %d to /stop; "
                "unable to undeploy",
                probe_host, port, resp.status,
            )
    except urllib.error.HTTPError as exc:
        logger.error(
            "Another process is using %s:%d (HTTP %d on /stop). "
            "Unable to undeploy.",
            probe_host, port, exc.code,
        )
    except OSError:
        logger.debug("Nothing at %s:%d", probe_host, port)
    return False


def create_engine_server(
    engine: Engine,
    params: EngineParams,
    engine_id: str,
    host: str = "0.0.0.0",
    port: int = 8000,
    **kwargs,
) -> tuple[EngineServer, HTTPServer]:
    server = EngineServer(engine, params, engine_id, **kwargs)
    return server, server.serve(host=host, port=port)
