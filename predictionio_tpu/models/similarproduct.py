"""Similar-product template — item-to-item similarity from ALS factors.

Capability parity with the reference
``examples/scala-parallel-similarproduct`` (``multi`` variant:
ALSAlgorithm over "view" events + LikeAlgorithm over "like" events,
item-to-item cosine on ``productFeatures``, a Serving that standardizes
each algorithm's scores and sums them per item; item ``$set`` properties
feed the category filter): queries
``{"items": [...], "num": N, "categories": [...], "whiteList": [...],
"blackList": [...]}`` answer ``{"itemScores": [...]}``.

What one algorithm serves, for its item table V:

1. Q = the query's items that the model knows, each once (none left:
   an empty answer).
2. ``score(j) = sum over i in Q of cos(V[i], V[j])``, over every item of
   Q whatever the basket's length.
3. j is a candidate iff it is not one of the query's items, not on the
   blackList, on the whiteList if one is given, in one of the
   ``categories`` if any is given, and ``score(j) > 0``.
4. The ``num`` best candidates, fewer if fewer exist; never padded.

The rules act BEFORE the top-k, on the device: the step is the e-commerce
template's (:func:`predictionio_tpu.ops.similarity.rules_top_k`, every row
on its ``SIMILAR`` branch, no user table), so a category that holds one
item in ten thousand still answers in full. Serving is two-phase
(`batch_predict_launch` / `batch_predict_collect`): the host resolves a
batch to compact operands (``predict.prep``), one jitted step scores,
masks and takes the top-k (``predict.enqueue``), and the collect phase
maps ids back. :class:`SimilarProductServing` combines the algorithms'
lists (step 5 of the reference's ``Serving.scala``): unless ``num`` is 1,
each list's scores become z-scores by that list's own mean and sample
standard deviation (0 where the deviation is 0, one-item lists
included), the z of one item are summed over the lists that hold it, and
the ``num`` largest sums are the answer.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    IdentityPreparator,
    Params,
    Serving,
    register_engine,
)
from predictionio_tpu.core.controller import SanityCheck
from predictionio_tpu.data.eventframe import Interactions
from predictionio_tpu.data.store import EventStore
from predictionio_tpu.models import staged_rules
from predictionio_tpu.models.staged_rules import StagedRules
from predictionio_tpu.obs import tracing
from predictionio_tpu.ops import similarity
from predictionio_tpu.ops.als import train_als
from predictionio_tpu.parallel import partition
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.bimap import BiMap

#: item slots of a batch's operands: a basket up to this long takes the
#: shapes the warm-up compiled; a longer one the next power of two, which
#: compiles on first use (four times `similarity.RECENT_SLOTS`: the gather
#: of 64 x 64 rows is 0.2 ms of a 5.6 ms step at 4.16 M items, and one
#: slot count is a third of the shapes that three would be)
BASKET_SLOTS = 64


@dataclasses.dataclass(frozen=True)
class SimilarDataSourceParams(Params):
    app_name: str = "MyApp"
    event_names: tuple[str, ...] = ("view", "like")
    item_entity_type: str = "item"


@dataclasses.dataclass
class SimilarTrainingData(SanityCheck):
    #: per-event-name interactions sharing one item vocabulary (the multi
    #: variant trains one ALS per behavioral signal)
    interactions: dict[str, Interactions]
    item_categories: dict[str, list[str]]

    def sanity_check(self) -> None:
        if all(i.nnz == 0 for i in self.interactions.values()):
            raise ValueError("no view/like events found")


class SimilarDataSource(DataSource):
    params_class = SimilarDataSourceParams

    def read_training(self, ctx: ComputeContext) -> SimilarTrainingData:
        p = self.params
        store = EventStore()
        frame = store.frame(p.app_name, event_names=list(p.event_names))
        # one shared item vocabulary across signals so factor spaces align
        # with the serving-side item ids
        full = frame.to_interactions()
        interactions = {}
        for name in p.event_names:
            sub = frame.filter_events([name]).to_interactions(
                entity_map=full.entity_map, target_map=full.target_map
            )
            interactions[name] = sub.dedupe_sum()
        props = store.aggregate_properties(
            p.app_name, entity_type=p.item_entity_type
        )
        categories = {
            eid: [str(c) for c in pm.get("categories") or []]
            for eid, pm in props.items()
        }
        return SimilarTrainingData(
            interactions=interactions,
            item_categories=categories,
        )


@dataclasses.dataclass(frozen=True)
class SimilarALSParams(Params):
    event_name: str = "view"  # "like" → the reference's LikeAlgorithm
    rank: int = 16
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    block_len: int = 64
    row_chunk: int = 256


@dataclasses.dataclass
class SimilarModel:
    # [I, k]; host np.ndarray after train, device jax.Array after staging
    item_factors: np.ndarray | jax.Array
    item_map: BiMap
    item_categories: dict[str, list[str]]
    #: True on phantom padding rows of the staged item table (None when
    #: unpadded). Optional so pre-sharding pickled models load unchanged.
    item_phantom_mask: "jax.Array | None" = None
    #: categories already encoded, in place of ``item_categories``: names,
    #: and a [C, I] int32 array of indices into them (-1 = none)
    category_names: "tuple[str, ...] | None" = None
    category_rows: "np.ndarray | jax.Array | None" = None
    #: set by `stage_model` (or on first use of an unstaged model)
    rules: "StagedRules | None" = None


class _Counters(staged_rules.RegistryCounters):
    """The template's counters in one registry."""

    def __init__(self, registry):
        self.queries = registry.counter(
            "pio_similar_queries_total",
            "Similar-product queries of one algorithm by result: "
            "answered with num items, short (fewer), empty",
            ("algorithm", "result"),
        )
        self.query_items = registry.counter(
            "pio_similar_query_items_total",
            "Items that similar-product queries named, by whether the "
            "algorithm's model knows them: yes, no, zero_row (known, "
            "with no vector of this algorithm's kind)",
            ("algorithm", "known"),
        )
        self.excluded = registry.counter(
            "pio_similar_excluded_items_total",
            "Item entries of the packed own-items/black/white lists sent "
            "to the device",
            ("algorithm",),
        )
        self.filtered = registry.counter(
            "pio_similar_filtered_queries_total",
            "Similar-product queries that carried the rule, counted by "
            "each algorithm that served them",
            ("rule",),
        )
        self.combined = registry.counter(
            "pio_serving_combined_items_total",
            "Items answered by a multi-algorithm Serving, by how many of "
            "the algorithms' lists held them",
            ("lists",),
        )
        self.rule = {
            r: self.filtered.labels(r)
            for r in ("category", "blackList", "whiteList")
        }


class SimilarALSAlgorithm(Algorithm):
    """ALS on (user, item) events → item factors; predict = the rules
    step over the summed cosine to the query's items."""

    params_class = SimilarALSParams

    def train(self, ctx: ComputeContext, pd: SimilarTrainingData):
        p = self.params
        inter = pd.interactions.get(p.event_name)
        if inter is None or inter.nnz == 0:
            raise ValueError(f"no {p.event_name!r} events to train on")
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
            implicit=True,
            seed=p.seed,
            block_len=p.block_len,
            row_chunk=p.row_chunk,
        )
        return SimilarModel(
            item_factors=factors.item_factors,
            item_map=inter.target_map,
            item_categories=pd.item_categories,
        )

    def stage_model(
        self, ctx: ComputeContext, model: SimilarModel
    ) -> SimilarModel:
        """Item factors commit through the sharded-catalog machinery the
        other ALS templates use, the table padded to a whole number of
        ``similarity.CATALOG_ROW_MULTIPLE`` rows (whole blocks for the
        fused kernel). Beside it, one entry per item row: category ids,
        1/norm and the phantom rows marked unavailable."""
        n_items = len(model.item_map)
        item_f, item_mask = partition.stage_factor_matrix(
            ctx,
            similarity.pad_rows(
                model.item_factors, staged_rules.padded_rows(ctx, n_items)
            ),
            n_real=n_items,
        )
        return dataclasses.replace(
            model,
            item_factors=item_f,
            item_phantom_mask=item_mask,
            rules=self._stage_rules(
                model, item_f, NamedSharding(ctx.mesh, PartitionSpec())
            ),
        )

    def _stage_rules(self, model, item_f, sharding=None) -> StagedRules:
        """The shared staging (no popularity: no query is POPULAR), and on
        the host which rows are zero, for the launch's counter."""
        rules = staged_rules.stage(model, item_f, sharding)
        rules.zero_rows = np.asarray(rules.inv_norm == 0).tobytes()
        return rules

    def predict(self, model: SimilarModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: SimilarModel, queries) -> list[dict]:
        if not queries:
            return []
        return self.batch_predict_collect(
            model, self.batch_predict_launch(model, queries), queries
        )

    def batch_predict_launch(self, model: SimilarModel, queries):
        """Host prep + device enqueue, no barrier. Every query becomes
        one row of compact operands: the rows of its known items, its
        category ids, and one packed list of item rows that is either what
        to leave out (its own items + blackList) or, with a whiteList,
        what alone may come back. Shapes are bucketed (batch rows, top-k
        size and category slots to powers of two, item slots by
        `BASKET_SLOTS`, the packed lists by `similarity.list_capacity`).
        No numpy call here runs over more than `similarity._HELD` elements
        but the packed lists' one sort."""
        if not queries:
            return None
        with tracing.stage(tracing.PREDICT_PREP):
            if model.rules is None:  # an unstaged model (evaluation)
                model.rules = self._stage_rules(
                    model, jnp.asarray(model.item_factors)
                )
            rules = model.rules
            counters = _Counters.of(tracing.bound_registry())
            n, n_items = len(queries), len(model.item_map)
            named, nums, wanted, black, white = zip(*[
                (
                    q.get("items") or (), int(q.get("num", 10)),
                    q.get("categories") or (), q.get("blackList") or (),
                    q.get("whiteList") or (),
                )
                for q in queries
            ])
            num = min(max(1, max(nums)), n_items)
            num_bucket = min(similarity.bucket(num), n_items)
            get = model.item_map.getter()
            # step 1: the rows of the items the model knows, each once
            found = [[get(str(x)) for x in items] for items in named]
            baskets = [
                list(dict.fromkeys(r for r in rows if r is not None))
                for rows in found
            ]
            operands = similarity.QueryRules.blank(
                similarity.bucket(n),
                similarity.bucket(max(1, max(map(len, wanted)))),
                item_slots=similarity.recent_slots(
                    max(BASKET_SLOTS, max(map(len, baskets)))
                ),
                mode=similarity.SIMILAR,
            )
            slots, q_cats = operands.recent, operands.categories
            lists = [staged_rules.NO_ROWS] * n
            for i, rows in enumerate(baskets):
                if not rows:
                    continue  # no row named: the step answers nothing
                own = np.fromiter(rows, np.int32, len(rows))
                slots[i, : len(rows)] = own
                lists[i] = (
                    staged_rules.listed_rows(get, black[i], white[i], own)
                    if black[i] or white[i] else own
                )
                operands.allow[i] = bool(white[i])
            filtered = [i for i in range(n) if wanted[i]]
            category_id = rules.category_ids.get
            for i in filtered:
                # a category the model does not know matches nothing
                q_cats[i, : len(wanted[i])] = [
                    category_id(str(c), similarity.NO_CATEGORY - 1)
                    for c in wanted[i]
                ]
            operands = dataclasses.replace(
                operands, lists=similarity.pack_lists(lists)
            )
            # the batch's counts, each counter once
            algorithm, zero = self.params.event_name, rules.zero_rows
            known = sum(map(len, baskets))
            zeros = sum(zero[r] for rows in baskets for r in rows)
            items_of = counters.query_items
            items_of.labels(algorithm, "yes").inc(known - zeros)
            items_of.labels(algorithm, "zero_row").inc(zeros)
            items_of.labels(algorithm, "no").inc(
                sum(rows.count(None) for rows in found)
            )
            counters.rule["blackList"].inc(sum(map(bool, black)))
            counters.rule["whiteList"].inc(sum(map(bool, white)))
            counters.rule["category"].inc(len(filtered))
            counters.excluded.labels(algorithm).inc(sum(map(len, lists)))
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            scores, items = similarity.rules_top_k(
                None, model.item_factors, num_bucket, rules.catalog,
                operands,
            )
        return scores, items, nums, counters

    def batch_predict_collect(
        self, model: SimilarModel, handle, queries
    ) -> list[dict]:
        """Device barrier + per-query JSON: the slots that hold a
        candidate (score above -inf), at most ``num`` of them."""
        if handle is None:
            return []
        scores, items, nums, counters = handle
        with tracing.stage(tracing.PREDICT_DEVICE_GET):
            scores, items = jax.device_get((scores, items))
        with tracing.stage(tracing.PREDICT_MATERIALIZE):
            out = staged_rules.served_lists(
                scores, items, nums, model.item_map.inverse
            )
            lengths = [len(a["itemScores"]) for a in out]
            empty = lengths.count(0)
            short = sum(0 < n < num for n, num in zip(lengths, nums))
            result = counters.queries
            algorithm = self.params.event_name
            result.labels(algorithm, "answered").inc(len(nums) - short - empty)
            result.labels(algorithm, "short").inc(short)
            result.labels(algorithm, "empty").inc(empty)
        return out


def _standardized(scores: list[float]) -> list[float]:
    """z-scores by the list's own mean and sample standard deviation; 0
    for every item where the deviation is 0 (a one-item list included)."""
    n = len(scores)
    if n < 2:
        return [0.0] * n
    mean = sum(scores) / n
    deviation = math.sqrt(sum((s - mean) ** 2 for s in scores) / (n - 1))
    if deviation == 0:
        return [0.0] * n
    return [(s - mean) / deviation for s in scores]


class SimilarProductServing(Serving):
    """Multi-algorithm combine (reference ``multi`` variant's
    Serving.scala): each algorithm's list standardized by its own mean
    and sample standard deviation unless ``num`` is 1, the z of one item
    summed over the lists that hold it, the ``num`` largest sums served;
    ties in the order the lists gave. Plain Python over at most
    algorithms x ``num`` entries: it runs once a query on a handler's
    thread."""

    def serve(self, query, predictions):
        num = int(query.get("num", 10))
        combined: dict[str, float] = {}
        held: dict[str, int] = {}
        for p in predictions:
            listed = p.get("itemScores", [])
            scores = [s["score"] for s in listed]
            if num != 1:
                scores = _standardized(scores)
            for s, z in zip(listed, scores):
                item = s["item"]
                combined[item] = combined.get(item, 0.0) + z
                held[item] = held.get(item, 0) + 1
        # `sorted` is stable and a dict keeps insertion order: ties stay
        # in the order the lists gave
        ranked = sorted(
            combined.items(), key=lambda kv: kv[1], reverse=True
        )[:num]
        if len(predictions) > 1 and ranked:
            counter = _Counters.of(tracing.bound_registry()).combined
            lists = [held[item] for item, _z in ranked]
            for count in set(lists):
                counter.labels(str(count)).inc(lists.count(count))
        return {
            "itemScores": [
                {"item": item, "score": score} for item, score in ranked
            ]
        }


def similarproduct_engine() -> Engine:
    return Engine(
        {"view": SimilarDataSource},
        IdentityPreparator,
        {"als": SimilarALSAlgorithm},
        SimilarProductServing,
    )


register_engine("similarproduct", similarproduct_engine)
