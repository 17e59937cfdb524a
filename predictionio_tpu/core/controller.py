"""DASE controller API — DataSource, Preparator, Algorithm, Serving.

Capability parity with the reference controller layer:

* ``DataSource``  ≈ PDataSource/LDataSource (controller/PDataSource.scala:34-57)
* ``Preparator``  ≈ PPreparator/LPreparator/IdentityPreparator
* ``Algorithm``   ≈ PAlgorithm/P2LAlgorithm/LAlgorithm
  (controller/PAlgorithm.scala:44-126 etc.) — collapsed into one base, see
  package docstring; the persistence trichotomy (auto / manual / retrain,
  core/BaseAlgorithm.scala:107-112) survives as :class:`PersistenceMode`.
* ``Serving``     ≈ LServing (+ LFirstServing / LAverageServing built-ins)
* ``Params``      ≈ controller/Params.scala with JSON extraction by
  dataclass fields instead of constructor reflection
  (workflow/WorkflowUtils.extractParams:131-160).

Queries and predictions travel as JSON-like dicts (or any pytree the
template chooses); typed wrappers are the template's business. The
ComputeContext parameter sits exactly where the reference passes
``sc: SparkContext``.
"""

from __future__ import annotations

import abc
import dataclasses
import enum
import functools
import logging
from typing import Any, Generic, Sequence, TypeVar

from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils import profiling

logger = logging.getLogger(__name__)

TD = TypeVar("TD")  # training data
PD = TypeVar("PD")  # prepared data
M = TypeVar("M")    # model
Q = TypeVar("Q")    # query
P = TypeVar("P")    # prediction
A = TypeVar("A")    # actual
EI = TypeVar("EI")  # evaluation info


class Params:
    """Marker base for controller params (reference controller/Params.scala:31).

    Subclasses are plain ``@dataclasses.dataclass`` types; JSON round-trip
    comes from the field schema via :func:`params_from_json`.
    """


@dataclasses.dataclass(frozen=True)
class EmptyParams(Params):
    pass


class ParamsError(ValueError):
    pass


def params_from_json(cls: type, data: dict[str, Any] | None) -> Params:
    """JSON dict → params dataclass (reference extractParams).

    Unknown keys are rejected (they are almost always typos in
    engine.json); missing keys fall back to field defaults; missing
    non-default keys raise.
    """
    data = dict(data or {})
    if not dataclasses.is_dataclass(cls):
        if data:
            raise ParamsError(
                f"{cls.__name__} takes no params but got {sorted(data)}"
            )
        return cls()
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - names
    if unknown:
        raise ParamsError(
            f"unknown params for {cls.__name__}: {sorted(unknown)} "
            f"(accepted: {sorted(names)})"
        )
    try:
        return cls(**data)
    except TypeError as e:
        raise ParamsError(f"bad params for {cls.__name__}: {e}") from e


def params_to_json(params: Params) -> dict[str, Any]:
    if dataclasses.is_dataclass(params):
        return dataclasses.asdict(params)
    return {}


class SanityCheck(abc.ABC):
    """Data objects may self-validate after each pipeline stage
    (reference controller/SanityCheck.scala:30, enforced by
    Engine.train unless skip_sanity_check)."""

    @abc.abstractmethod
    def sanity_check(self) -> None: ...


class _Controller:
    """Shared constructor protocol: ``cls(params)`` (the Doer-equivalent;
    reference core/AbstractDoer.scala:26-66 instantiates controllers
    reflectively — here it is a plain call)."""

    params_class: type = EmptyParams

    def __init__(self, params: Params | None = None):
        if params is None or (
            type(params) is EmptyParams
            and self.params_class is not EmptyParams
        ):
            # default-construct the declared params type (an EmptyParams
            # placeholder from a default EngineParams means "use defaults")
            params = self.params_class()
        self.params = params


class DataSource(_Controller, Generic[TD, EI, Q, A], abc.ABC):
    """Reads training / evaluation data from the event store."""

    @abc.abstractmethod
    def read_training(self, ctx: ComputeContext) -> TD: ...

    def read_eval(
        self, ctx: ComputeContext
    ) -> list[tuple[TD, EI, list[tuple[Q, A]]]]:
        """k evaluation folds: (trainingData, evalInfo, [(query, actual)])
        (reference readEvalBase, core/BaseDataSource.scala:45-52)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement read_eval; "
            "evaluation is unsupported for this data source."
        )


class Preparator(_Controller, Generic[TD, PD], abc.ABC):
    @abc.abstractmethod
    def prepare(self, ctx: ComputeContext, training_data: TD) -> PD: ...


class IdentityPreparator(Preparator[TD, TD]):
    """Pass-through (reference controller/IdentityPreparator.scala:31-92)."""

    def prepare(self, ctx: ComputeContext, training_data: TD) -> TD:
        return training_data


class PersistenceMode(enum.Enum):
    """Model persistence trichotomy (core/BaseAlgorithm.scala:107-112):

    * AUTO    — framework serializes the (host-staged) model pytree into
      the model store (reference: Kryo blob, CoreWorkflow.scala:73-78;
      here: pickled numpy pytree).
    * MANUAL  — algorithm saves/loads itself (reference PersistentModel;
      here typically an orbax sharded checkpoint); the store keeps only a
      manifest marker.
    * RETRAIN — model is not persisted; deploy re-trains
      (reference Unit models, Engine.prepareDeploy Engine.scala:208-230).
    """

    AUTO = "auto"
    MANUAL = "manual"
    RETRAIN = "retrain"


class Algorithm(_Controller, Generic[PD, M, Q, P], abc.ABC):
    """Train on prepared data; answer queries.

    TPU-first contract: ``train`` stages data onto ``ctx.mesh`` and runs
    jitted programs; ``predict``/``batch_predict`` should dispatch onto
    pre-compiled fixed-shape executables (the serving anti-pattern to
    avoid is the reference's per-query Spark job, SURVEY.md §3.2 note).
    """

    persistence_mode: PersistenceMode = PersistenceMode.AUTO
    #: optional StepTimer injected by the workflow runtime; algorithms
    #: may record per-step timings into it during train
    timer = None

    @abc.abstractmethod
    def train(self, ctx: ComputeContext, prepared_data: PD) -> M: ...

    @abc.abstractmethod
    def predict(self, model: M, query: Q) -> P: ...

    def batch_predict(self, model: M, queries: Sequence[Q]) -> list[P]:
        """Bulk predict for evaluation (reference batchPredictBase).
        Default loops; algorithms override with a vmapped/jitted path."""
        return [self.predict(model, q) for q in queries]

    # -- serving hooks (pipelined micro-batching) -------------------------
    def batch_predict_launch(self, model: M, queries: Sequence[Q]) -> Any:
        """Enqueue the device work for ``queries`` and return an opaque
        handle WITHOUT blocking on the device (JAX async dispatch: run
        the jitted program, return the un-fetched device arrays plus
        whatever host metadata the decode needs). Pairs with
        :meth:`batch_predict_collect`; the serving micro-batcher calls
        the pair on its two threads to overlap batch N+1's enqueue with
        batch N's barrier (docs/serving.md "Pipelined dispatch").
        Default: nothing is launched, the queries are the handle, and
        :meth:`batch_predict_collect` does all the work.

        Sharded-model contract: implementations must accept model
        state whose arrays are mesh-sharded ``jax.Array``s (e.g. ALS
        factor matrices split over the ``model`` axis,
        docs/parallelism.md "Sharded ALS") WITHOUT gathering them to
        the host — dispatch the jitted program against the sharded
        arrays and let GSPMD insert the collectives. A host gather
        here would both serialize serving and cap the catalog at one
        chip's HBM."""
        return queries

    def batch_predict_collect(
        self, model: M, handle: Any, queries: Sequence[Q]
    ) -> list[P]:
        """Pay the device barrier for a :meth:`batch_predict_launch`
        handle and materialize one result per query, in order.
        Default: :meth:`batch_predict`, then a barrier on whatever
        device arrays its predictions still hold — the batcher stops
        its sync clock when this returns, and async dispatch would
        otherwise make ``pio_device_sync_seconds`` measure the enqueue,
        not the work."""
        out = self.batch_predict(model, queries)
        profiling.sync(out)
        return out

    def serving_hooks(self):
        """``(launch, collect)`` as a server wires them: the
        algorithm's own pair. One that overrides exactly one of the two
        would meet the other's default with a handle it does not know,
        so it is served through the default pair, and told so."""
        cls = type(self)
        if (cls.batch_predict_launch is Algorithm.batch_predict_launch) == (
            cls.batch_predict_collect is Algorithm.batch_predict_collect
        ):
            return self.batch_predict_launch, self.batch_predict_collect
        logger.warning(
            "%s overrides only one of batch_predict_launch/"
            "batch_predict_collect — serving single-phase through "
            "batch_predict",
            cls.__name__,
        )
        return (
            functools.partial(Algorithm.batch_predict_launch, self),
            functools.partial(Algorithm.batch_predict_collect, self),
        )

    def stage_model(self, ctx: ComputeContext, model: M) -> M:
        """Deploy-time hook: place model state onto the device(s) ONCE so
        serving never re-uploads it per request (the reference keeps the
        deployed model resident in the server JVM,
        workflow/CreateServer.scala:495-647; the TPU analogue is
        device-committed ``jax.Array`` factors). Called by
        ``Engine.prepare_deploy`` for every load and ``/reload``.
        Default: identity (host-resident models).

        When ``ctx.model_parallelism > 1`` implementations should
        commit large row-addressed state SHARDED over the model mesh
        axis (``predictionio_tpu.parallel.partition`` has the rule
        tables and ``stage_factor_matrix`` helper) so per-device HBM
        divides by the axis size; already-sharded device arrays must
        pass through untouched — that is the unbroken
        train→serve path."""
        return model

    # -- persistence hooks (MANUAL mode) ---------------------------------
    def save_model(self, instance_id: str, model: M) -> None:
        raise NotImplementedError(
            f"{type(self).__name__}.save_model required for MANUAL persistence"
        )

    def load_model(self, instance_id: str, ctx: ComputeContext) -> M:
        raise NotImplementedError(
            f"{type(self).__name__}.load_model required for MANUAL persistence"
        )

    def prepare_model_for_host(self, model: M) -> Any:
        """AUTO-mode hook: return the host-serializable form of the model
        (reference makeSerializableModels / LAlgorithm RDD unwrap,
        Engine.scala:283-301). Default: identity — the persistence layer
        device_get()s jax arrays itself."""
        return model


class Serving(_Controller, Generic[Q, P], abc.ABC):
    """Combine per-algorithm predictions (reference LServing.scala:27-52)."""

    def supplement(self, query: Q) -> Q:
        """Enrich the query before prediction (supplementBase)."""
        return query

    @abc.abstractmethod
    def serve(self, query: Q, predictions: Sequence[P]) -> P: ...


class FirstServing(Serving[Q, P]):
    """Reference LFirstServing: first algorithm wins."""

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        return predictions[0]


class AverageServing(Serving[Q, Any]):
    """Reference LAverageServing: numeric mean of predictions."""

    def serve(self, query: Q, predictions: Sequence[Any]) -> Any:
        return sum(predictions) / len(predictions)
