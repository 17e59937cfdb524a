"""Recommendation template — implicit/explicit ALS.

Capability parity with the reference
``examples/scala-parallel-recommendation`` (custom-query variant:
MLlib ``ALS.trainImplicit`` over "rate" events,
custom-query/src/main/scala/ALSAlgorithm.scala:24-105,
DataSource.scala:23-66): events (user → item with a rating property)
train factor matrices; queries ``{"user": id, "num": N}`` answer
``{"itemScores": [{"item": id, "score": s}, ...]}``.

TPU path: mesh ALS (:func:`predictionio_tpu.ops.als.train_als`) for
training; serving scores with one pre-compiled matmul + top-k instead of
the reference's per-query Spark job.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
    register_engine,
)
from predictionio_tpu.core.controller import SanityCheck
from predictionio_tpu.data.eventframe import Interactions
from predictionio_tpu.data.store import EventStore
from predictionio_tpu.obs import tracing
from predictionio_tpu.ops import similarity
from predictionio_tpu.ops.als import train_als
from predictionio_tpu.parallel import partition
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.bimap import BiMap

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class RecDataSourceParams(Params):
    app_name: str = "MyApp"
    event_names: tuple[str, ...] = ("rate",)
    rating_key: str | None = "rating"  # None → implicit count of 1 per event
    eval_k: int = 0


@dataclasses.dataclass
class RecTrainingData(SanityCheck):
    interactions: Interactions

    def sanity_check(self) -> None:
        if self.interactions.nnz == 0:
            raise ValueError("no interaction events found")


class RecDataSource(DataSource[RecTrainingData, dict, dict, list]):
    params_class = RecDataSourceParams

    def _interactions(self) -> Interactions:
        p = self.params
        # uses the backend's native columnar scan when available
        return EventStore().interactions(
            p.app_name,
            event_names=list(p.event_names),
            value_key=p.rating_key,
        )

    def read_training(self, ctx: ComputeContext) -> RecTrainingData:
        return RecTrainingData(interactions=self._interactions())

    def read_eval(self, ctx: ComputeContext):
        """k-fold over interactions (shared
        :func:`~predictionio_tpu.core.evaluation.kfold_indices`):
        held-out items per user become the actuals (ranking
        evaluation)."""
        from predictionio_tpu.core.evaluation import kfold_indices

        inter = self._interactions()
        folds = []
        for fold, train_idx, test_idx in kfold_indices(
            inter.nnz, self.params.eval_k
        ):
            train = Interactions(
                entity_map=inter.entity_map,
                target_map=inter.target_map,
                rows=inter.rows[train_idx],
                cols=inter.cols[train_idx],
                values=inter.values[train_idx],
                times=inter.times[train_idx],
            )
            # group held-out items by user
            by_user: dict[int, list[str]] = {}
            for r, c in zip(inter.rows[test_idx], inter.cols[test_idx]):
                by_user.setdefault(int(r), []).append(
                    inter.target_map.inverse(int(c))
                )
            qa = [
                (
                    {
                        "user": inter.entity_map.inverse(u),
                        "num": max(10, len(items)),
                    },
                    items,
                )
                for u, items in by_user.items()
            ]
            folds.append(
                (RecTrainingData(interactions=train), {"fold": fold}, qa)
            )
        return folds


@dataclasses.dataclass(frozen=True)
class RecPreparatorParams(Params):
    dedupe: str = "sum"  # "sum" (implicit counts) | "latest" (ratings)


class RecPreparator(Preparator[RecTrainingData, RecTrainingData]):
    """Dedupe repeated (user, item) events — MLlib-convention sum for
    implicit counts, keep-latest for rating data (reference DataSource
    takes the latest "rate" event per pair)."""

    params_class = RecPreparatorParams

    def prepare(
        self, ctx: ComputeContext, td: RecTrainingData
    ) -> RecTrainingData:
        inter = td.interactions
        deduped = (
            inter.dedupe_latest()
            if self.params.dedupe == "latest"
            else inter.dedupe_sum()
        )
        return RecTrainingData(interactions=deduped)


@dataclasses.dataclass(frozen=True)
class ALSParams(Params):
    """Reference ALSAlgorithmParams (rank, numIterations, lambda, seed,
    custom-query/src/main/scala/ALSAlgorithm.scala:19-22) + implicit
    controls."""

    rank: int = 32
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    implicit: bool = True
    seed: int = 13
    block_len: int = 64
    row_chunk: int = 256
    #: "" = auto (bf16 on TPU, f32 elsewhere — see
    #: ops.als._resolve_compute); "float32" opts out, "bfloat16"
    #: forces bf16
    compute_dtype: str = ""
    # mid-training checkpoint/resume (ops/als.py); dir empty = disabled
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    resume: bool = False
    #: factor-matrix layout: "auto" shards over the model mesh axis
    #: whenever the serving/training mesh has one (docs/parallelism.md
    #: "Sharded ALS"); "replicated"/"sharded" force a mode
    factor_sharding: str = "auto"


@dataclasses.dataclass
class ALSRecModel:
    # np.ndarray after train (host, picklable); device-committed
    # jax.Array after Algorithm.stage_model at deploy
    user_factors: np.ndarray | jax.Array
    item_factors: np.ndarray | jax.Array
    user_map: BiMap
    item_map: BiMap
    #: [rows(item_factors)] bool device array, True on phantom padding
    #: rows of a model-sharded catalog (None when factors are
    #: unpadded); serving passes it as the top-k score mask so a
    #: padded row never surfaces as a recommendation. Optional so
    #: pre-sharding pickled models load unchanged.
    item_phantom_mask: "jax.Array | None" = None


class ALSAlgorithm(Algorithm[RecTrainingData, ALSRecModel, dict, dict]):
    params_class = ALSParams

    def train(self, ctx: ComputeContext, pd: RecTrainingData) -> ALSRecModel:
        p = self.params
        inter = pd.interactions
        factors = train_als(
            ctx,
            inter.rows,
            inter.cols,
            inter.values,
            n_users=inter.n_rows,
            n_items=inter.n_cols,
            rank=p.rank,
            iterations=p.num_iterations,
            reg=p.lambda_,
            alpha=p.alpha,
            implicit=p.implicit,
            seed=p.seed,
            block_len=p.block_len,
            row_chunk=p.row_chunk,
            compute_dtype=p.compute_dtype or None,
            timer=self.timer,
            checkpoint_dir=p.checkpoint_dir or None,
            checkpoint_every=p.checkpoint_every,
            resume=p.resume,
            factor_sharding=p.factor_sharding,
        )
        return ALSRecModel(
            user_factors=factors.user_factors,
            item_factors=factors.item_factors,
            user_map=inter.entity_map,
            item_map=inter.target_map,
        )

    # -- serving ----------------------------------------------------------
    def stage_model(
        self, ctx: ComputeContext, model: ALSRecModel
    ) -> ALSRecModel:
        """Commit both factor matrices once at deploy; the per-request
        upload is then just the int32 user indices.

        On a mesh with a model axis the matrices are committed
        ROW-SHARDED over it (the same partition rule that trained
        them), so the catalog's HBM footprint divides by
        model_parallelism — a factor table too big for one chip serves
        from one engine instance; on a model-axis-1 mesh the same spec
        is physically replicated. Already-sharded device arrays (the
        ``train_als(return_layout="device")`` path) pass straight
        through without a host gather. The phantom mask is keyed on
        the factors actually carrying padded rows (device-layout
        training pads on EVERY mesh, data-parallel ones included) —
        never on the mesh shape."""
        user_f, _ = partition.stage_factor_matrix(
            ctx, model.user_factors, n_real=len(model.user_map)
        )
        item_f, item_mask = partition.stage_factor_matrix(
            ctx, model.item_factors, n_real=len(model.item_map)
        )
        return dataclasses.replace(
            model,
            user_factors=user_f,
            item_factors=item_f,
            item_phantom_mask=item_mask,
        )

    def predict(self, model: ALSRecModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: ALSRecModel, queries) -> list[dict]:
        if not queries:
            return []
        return self.batch_predict_collect(
            model, self.batch_predict_launch(model, queries), queries
        )

    def batch_predict_launch(self, model: ALSRecModel, queries):
        """Host prep + device enqueue, no barrier: the returned handle
        holds un-fetched device arrays, so the serving pipeline can
        enqueue the next batch while this one computes. Works unchanged
        on model-sharded factor matrices (the jitted program runs GSPMD
        over their mesh; nothing here gathers factors to the host) —
        phantom padding rows are masked out of the ranking and the
        top-k size clamps to the REAL catalog, never the padded one."""
        if not queries:
            return None
        with tracing.stage(tracing.PREDICT_PREP):
            n_items = len(model.item_map)
            num = max(int(q.get("num", 10)) for q in queries)
            num = min(num, n_items)
            # bucket the jit-static shapes (top-k size and batch rows)
            # to powers of two so arbitrary client input cannot force
            # unbounded recompiles at serving time
            num_bucket = min(1 << max(0, (num - 1)).bit_length(), n_items)
            # a restored map answers by binary search, building no dict
            user_idx = model.user_map.rows([q.get("user", "") for q in queries])
            idx = np.clip(user_idx, 0, None)
            batch_bucket = 1 << max(0, (len(idx) - 1)).bit_length()
            if batch_bucket > len(idx):
                idx = np.pad(idx, (0, batch_bucket - len(idx)))
        # fused gather + score + top-k on device, one call into the
        # runtime: the jitted program takes the numpy `idx` and uploads
        # it itself (factors are staged jax.Arrays after stage_model;
        # the evaluation path passes host arrays and pays the upload
        # there)
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            scores, items = similarity.gather_top_k_dot(
                model.user_factors, idx, model.item_factors, num_bucket,
                mask=getattr(model, "item_phantom_mask", None),
            )
        return scores, items, user_idx, num

    def batch_predict_collect(
        self, model: ALSRecModel, handle, queries
    ) -> list[dict]:
        """Device barrier + per-query JSON materialization for a
        :meth:`batch_predict_launch` handle."""
        if handle is None:
            return []
        scores, items, user_idx, num = handle
        # one device_get for both arrays: one barrier, one transfer
        with tracing.stage(tracing.PREDICT_DEVICE_GET):
            scores, items = jax.device_get((scores, items))
        with tracing.stage(tracing.PREDICT_MATERIALIZE):
            out = []
            for i, q in enumerate(queries):
                if user_idx[i] < 0:
                    out.append({"itemScores": []})  # unknown user
                    continue
                n = min(int(q.get("num", 10)), num)
                out.append(
                    {
                        "itemScores": [
                            {
                                "item": model.item_map.inverse(
                                    int(items[i, j])
                                ),
                                "score": float(scores[i, j]),
                            }
                            for j in range(n)
                        ]
                    }
                )
        return out


def recommendation_engine() -> Engine:
    return Engine(
        RecDataSource,
        RecPreparator,
        {"als": ALSAlgorithm},
        FirstServing,
    )


register_engine("recommendation", recommendation_engine)
