"""Workflow runtime — train/deploy orchestration + instance bookkeeping.

Capability parity with the reference's ``workflow`` package:
``CoreWorkflow.runTrain`` (workflow/CoreWorkflow.scala:42-98) and the
deploy-side model recovery in ``CreateServer.createServerActorWithEngine``
(workflow/CreateServer.scala:204-263). The spark-submit process boundary
disappears: the CLI calls these functions in-process (multi-host runs
start one such process per TPU host via
:mod:`predictionio_tpu.parallel.distributed`).
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import os
import pickle
from typing import Any, Sequence

from predictionio_tpu.core.controller import PersistenceMode
from predictionio_tpu.core.engine import (
    Engine,
    EngineParams,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    WorkflowParams,
)
from predictionio_tpu.core.persistence import (
    ModelIntegrityError,
    deserialize_models,
    load_generation,
    publish_generation,
    quarantine_generation,
    serialize_models,
)
from predictionio_tpu.data.storage import (
    EngineInstance,
    Storage,
    get_storage,
)
from predictionio_tpu.obs import tracing
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.profiling import StepTimer, trace

logger = logging.getLogger(__name__)


def _now() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def _write_train_trace(
    tracer, trace_id: str | None, instance_id: str
) -> None:
    """Persist the run's span timeline as Chrome trace-event JSON in
    ``PIO_TRACE_DIR`` (the directory ``utils/profiling.trace`` already
    uses for device-level traces) — ``pio train`` produces the same
    Perfetto-loadable artifact the servers serve at ``/debug/traces``.
    Best-effort: a full disk must not fail a COMPLETED run."""
    trace_dir = os.environ.get("PIO_TRACE_DIR")
    if not trace_dir or trace_id is None:
        return
    try:
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(
            trace_dir, f"pio_train_{instance_id}.trace.json"
        )
        timeline = tracer.chrome_trace(trace_id=trace_id)
        with open(path, "w") as f:
            # default=str: span attributes are caller-supplied (numpy
            # scalars, shapes, ...) and must not fail a COMPLETED run
            json.dump(timeline, f, default=str)
        if timeline["traceEvents"]:
            logger.info("wrote training span timeline to %s", path)
        else:
            # the recorder can abandon a very long run's open trace at
            # its cap (a trainer that also serves heavy traffic) — an
            # empty timeline must not masquerade as a success
            logger.warning(
                "training trace %s has no spans (recorder abandoned "
                "it?); wrote empty timeline to %s", trace_id, path,
            )
    except (OSError, TypeError, ValueError) as e:
        # truly best-effort: a serialization surprise in the finally
        # must neither fail a COMPLETED run nor mask a training error
        logger.warning("could not write training trace: %s", e)


def apply_checkpoint_params(
    algorithms: Sequence[Any],
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    resume: bool = False,
) -> int:
    """Thread CLI/trainer checkpoint settings into every algorithm whose
    params dataclass declares the ``checkpoint_dir``/``checkpoint_every``
    /``resume`` fields (the :mod:`~predictionio_tpu.ops.als` contract).
    Returns how many algorithms were rewired — 0 means the engine has no
    checkpointable algorithm and the flags are inert (logged, not an
    error: mixed-engine variants are legal)."""
    if not checkpoint_dir:
        return 0
    rewired = 0
    for algo in algorithms:
        p = algo.params
        if not dataclasses.is_dataclass(p):
            continue
        names = {f.name for f in dataclasses.fields(p)}
        if not {"checkpoint_dir", "checkpoint_every", "resume"} <= names:
            continue
        algo.params = dataclasses.replace(
            p,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            resume=resume,
        )
        rewired += 1
    if rewired == 0:
        logger.warning(
            "checkpoint_dir=%s requested but no algorithm supports "
            "checkpointing; training runs without restore points",
            checkpoint_dir,
        )
    return rewired


def latest_completed_id(
    storage: Storage,
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
) -> str | None:
    """Id of the current latest COMPLETED instance (the parent of the
    next published generation), or None for a first train."""
    latest = storage.get_meta_data_engine_instances().get_latest_completed(
        engine_id, engine_version, engine_variant
    )
    return latest.id if latest is not None else None


def run_train(
    engine: Engine,
    params: EngineParams,
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
    engine_factory: str = "",
    workflow: WorkflowParams | None = None,
    ctx: ComputeContext | None = None,
    storage: Storage | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    watermark: dict | None = None,
) -> str:
    """Train + persist; returns the EngineInstance id.

    Lifecycle mirrors the reference (INIT on entry; COMPLETED only after
    models are persisted, so deploy's ``getLatestCompleted`` never picks
    a half-written run; FAILED on error).

    ``checkpoint_dir``/``checkpoint_every``/``resume`` thread the CLI's
    mid-training checkpoint flags down to checkpoint-capable algorithms
    (:func:`apply_checkpoint_params` → ``ops/als.py``), so a trainer
    killed mid-epoch resumes from its latest restore point. ``watermark``
    (event count / latest event time the training data was read at) is
    recorded in the generation manifest — the freshness provenance the
    continuous trainer keys its triggers off."""
    workflow = workflow or WorkflowParams()
    storage = storage or get_storage()
    instances = storage.get_meta_data_engine_instances()
    instance = EngineInstance(
        id="",
        status="INIT",
        start_time=_now(),
        end_time=_now(),
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=workflow.batch,
    )
    instance_id = instances.insert(instance)
    instance = instances.get(instance_id)
    ctx = ctx or ComputeContext.create(batch=workflow.batch or engine_id)
    tracer = tracing.get_tracer()
    # the whole run is one trace (trace ID = instance ID): the
    # StepTimer steps inside engine.train become child spans, and the
    # same timeline format every server exposes at /debug/traces is
    # written to PIO_TRACE_DIR after the run — in the finally, because
    # the timeline of a FAILED run is the one most worth keeping
    root_trace_id = None
    try:
        with tracer.trace(
            "pio_train",
            trace_id=instance_id,
            attributes={
                "engineId": engine_id,
                "engineVersion": engine_version,
                "engineVariant": engine_variant,
            },
        ) as root_span:
            if root_span is not None:
                root_trace_id = root_span.trace_id
            # record the compute topology on the run record (the
            # reference stores sparkConf on EngineInstance,
            # EngineInstances.scala:43-69); inside the try so a storage
            # failure still marks the run FAILED
            mesh = ctx.mesh
            instance = dataclasses.replace(
                instance,
                mesh_conf={
                    "shape": ",".join(str(s) for s in mesh.devices.shape),
                    "axes": ",".join(mesh.axis_names),
                    "devices": str(mesh.devices.size),
                    "platform": mesh.devices.flat[0].platform,
                },
            )
            instances.update(instance)
            # build algorithm instances once: the SAME objects train and
            # (for MANUAL persistence) save, so trained state is what
            # gets saved
            algorithms = engine.make_algorithms(params)
            apply_checkpoint_params(
                algorithms,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                resume=resume,
            )
            # the parent generation is whatever deploy would pick RIGHT
            # NOW — recorded in the manifest so a corrupt publish has a
            # named last-good to fall back to
            parent_generation = latest_completed_id(
                storage, engine_id, engine_version, engine_variant
            )
            timer = StepTimer()
            for algo in algorithms:
                algo.timer = timer
            # train-time telemetry joins the process registry: a trainer
            # that also serves (or exposes /metrics) scrapes both as one
            from predictionio_tpu.obs import get_registry
            from predictionio_tpu.obs.device import CompileWatch

            with timer.step("train/total"), trace(), CompileWatch(
                get_registry()
            ):
                models = engine.train(
                    ctx, params, workflow, algorithms=algorithms
                )
            timer.log_summary(prefix=f"[{engine_id}] ")
            timer.publish(get_registry())
            instance = dataclasses.replace(
                instance, env={"timing": timer.to_json()}
            )
            if workflow.save_model:
                with tracing.span("train/persist_model"):
                    blob = serialize_models(
                        instance_id, algorithms, models
                    )
                    # transactional publish: blob first, checksum
                    # manifest LAST (the commit point) — a crash
                    # between the two can never become the serving
                    # model (docs/training.md "Model generations")
                    publish_generation(
                        storage.get_model_data_models(),
                        instance_id,
                        blob,
                        watermark=watermark,
                        parent=parent_generation,
                    )
                logger.info(
                    "persisted %d model(s) for instance %s (%d bytes)",
                    len(models),
                    instance_id,
                    len(blob),
                )
            instances.update(
                dataclasses.replace(
                    instance, status="COMPLETED", end_time=_now()
                )
            )
        return instance_id
    except (StopAfterReadInterruption, StopAfterPrepareInterruption):
        instances.update(
            dataclasses.replace(
                instance, status="INTERRUPTED", end_time=_now()
            )
        )
        raise
    except Exception:
        instances.update(
            dataclasses.replace(
                instance, status="FAILED", end_time=_now()
            )
        )
        raise
    finally:
        # the root span finalized when the with-block unwound, so the
        # trace is in the ring even when train raised
        _write_train_trace(tracer, root_trace_id, instance_id)


def run_evaluation(
    evaluation,
    batch: str = "",
    workflow: WorkflowParams | None = None,
    ctx: ComputeContext | None = None,
    storage: Storage | None = None,
):
    """Run an Evaluation; returns (instance_id, MetricEvaluatorResult).

    Lifecycle mirrors the reference (CoreWorkflow.runEvaluation,
    workflow/CoreWorkflow.scala:100-157): EvaluationInstance INIT →
    EVALCOMPLETED with one-liner / HTML / JSON results persisted."""
    from predictionio_tpu.core.evaluation import MetricEvaluator
    from predictionio_tpu.core.fasteval import FastEvalEngine
    from predictionio_tpu.data.storage import EvaluationInstance

    workflow = workflow or WorkflowParams()
    storage = storage or get_storage()
    instances = storage.get_meta_data_evaluation_instances()
    instance_id = instances.insert(
        EvaluationInstance(
            id="",
            status="INIT",
            start_time=_now(),
            end_time=_now(),
            evaluation_class=type(evaluation).__name__,
            batch=batch,
        )
    )
    instance = instances.get(instance_id)
    ctx = ctx or ComputeContext.create(batch=batch or "evaluation")
    try:
        # memoize pipeline prefixes by default so a grid sweep reads /
        # prepares / trains each distinct prefix once (reference wires
        # FastEvalEngine the same way for tuning); only wrap plain
        # Engines — a subclass may override eval() with custom logic
        engine = evaluation.engine
        if (
            getattr(evaluation, "fast_eval", True)
            and type(engine) is Engine
        ):
            engine = FastEvalEngine.from_engine(engine)
        evaluator = MetricEvaluator(
            metric=evaluation.metric,
            other_metrics=evaluation.other_metrics,
            output_path=evaluation.output_path,
            parallelism=getattr(evaluation, "parallelism", None),
        )
        result = evaluator.evaluate(
            ctx, engine, evaluation.engine_params_list, workflow
        )
    except Exception:
        instances.update(
            dataclasses.replace(
                instance, status="FAILED", end_time=_now()
            )
        )
        raise
    instances.update(
        dataclasses.replace(
            instance,
            status="EVALCOMPLETED",
            end_time=_now(),
            evaluator_results=result.to_one_liner(),
            evaluator_results_html=result.to_html(),
            evaluator_results_json=result.to_json(),
        )
    )
    return instance_id, result


def load_deployment(
    engine: Engine,
    params: EngineParams,
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
    instance_id: str | None = None,
    ctx: ComputeContext | None = None,
    storage: Storage | None = None,
):
    """Recover (algorithms, models, serving) for serving.

    ``instance_id=None`` picks the latest COMPLETED instance (the
    reference deploy path, Console.scala:844-879 →
    CreateServer.scala:204-263) whose model blob passes checksum
    verification: a corrupt generation (torn publish, flipped bit) is
    quarantined — moved aside and counted in
    ``pio_model_quarantined_total`` — and the NEXT newest COMPLETED
    generation serves instead (last-good fallback). An explicit
    ``instance_id`` never falls back silently: corruption raises
    :class:`~predictionio_tpu.core.persistence.ModelIntegrityError`
    after quarantining."""
    storage = storage or get_storage()
    instances = storage.get_meta_data_engine_instances()
    explicit = instance_id is not None
    if explicit:
        instance = instances.get(instance_id)
        if instance is None:
            raise RuntimeError(f"engine instance {instance_id} not found")
        candidates = [instance]
    else:
        candidates = instances.get_completed(
            engine_id, engine_version, engine_variant
        )
        if not candidates:
            raise RuntimeError(
                f"No COMPLETED engine instance for {engine_id} "
                f"{engine_version} {engine_variant}; run train first."
            )
    ctx = ctx or ComputeContext.create(batch=f"serving:{engine_id}")

    algorithms = engine.make_algorithms(params)
    needs_blob = any(
        a.persistence_mode == PersistenceMode.AUTO for a in algorithms
    )
    stored: Sequence[Any]
    instance = candidates[0]
    if needs_blob:
        models_backend = storage.get_model_data_models()
        stored = None
        last_error: Exception | None = None
        for candidate in candidates:
            try:
                # the three steps of a load are stages (a pool's cold
                # load nests them in its `pool.load`; a single-tenant
                # deploy or /reload observes them bare)
                with tracing.stage(tracing.POOL_READ):
                    blob = load_generation(models_backend, candidate.id)
                # a blob that passed (or predates) checksums can still
                # be an unreadable pickle — for fallback purposes both
                # are the same failure: this generation cannot serve
                with tracing.stage(tracing.POOL_DESERIALIZE):
                    entries = deserialize_models(blob)
                del blob  # tens of MB a tenant: gone before the promote
            except (
                ModelIntegrityError,
                pickle.UnpicklingError,
                ValueError,
                EOFError,
                KeyError,
            ) as e:
                last_error = e
                logger.error(
                    "model generation %s is unloadable (%s); "
                    "quarantining%s",
                    candidate.id, e,
                    "" if explicit else " and falling back to last-good",
                )
                quarantine_generation(models_backend, candidate.id)
                from predictionio_tpu.obs import get_registry

                get_registry().counter(
                    "pio_model_quarantined_total",
                    "Published model generations that failed integrity "
                    "verification at load and were moved aside",
                ).inc()
                if explicit:
                    raise
                continue
            instance = candidate
            stored = [payload for _tag, payload in entries]
            break
        if stored is None:
            raise RuntimeError(
                f"no loadable model generation for {engine_id} "
                f"{engine_version} {engine_variant} "
                f"({len(candidates)} candidate(s) quarantined; last "
                f"error: {last_error})"
            )
    else:
        stored = [None] * len(algorithms)
    with tracing.stage(tracing.POOL_PROMOTE):
        algorithms, models, serving = engine.prepare_deploy(
            ctx, params, instance.id, stored
        )
    return instance, algorithms, models, serving
