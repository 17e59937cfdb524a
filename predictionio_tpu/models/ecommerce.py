"""E-commerce recommendation template — ALS + serve-time business rules.

Capability parity with the reference
``examples/scala-parallel-ecommercerecommendation`` (train-with-rate-event
variant, ECommAlgorithm.scala): implicit ALS over view/buy events, and a
predict path that applies live business rules, read from the event store
*at predict time* (the LEventStore pattern): the user's seen items, the
globally unavailable items (latest ``$set`` of the ``constraint`` entity
``unavailableItems``), and the query's ``categories`` / ``whiteList`` /
``blackList``.

The rules act BEFORE the top-k, on the device. A query's candidates are
the items that are not seen, not unavailable, not blacklisted, on the
whiteList if it has one and in one of its categories if it names any;
the answer is the top ``num`` of the candidates (the reference's
``isCandidateItem`` + ``getTopN``), never a filter over a global top-k,
which at a real catalog answers a one-category query short or empty.
Three branches, mixed freely in one batch:

* known user: ``U[u] . V[i]``, candidates with a score above 0;
* unknown user with recent views (the latest 10 ``similar_events`` whose
  items the model knows): the summed cosine to those items, above 0;
* unknown user with none: the item's popularity (interaction count).

Fewer candidates than ``num`` give a shorter answer. Serving is
two-phase (`batch_predict_launch` / `batch_predict_collect`): the host
resolves each query to compact operands (``predict.prep``, the store
lookups inside it as ``predict.rules``), one jitted step per batch forms
the mask, scores and takes the top-k
(:func:`predictionio_tpu.ops.similarity.rules_top_k`), and the collect
phase maps ids back. The event store answers the lookups from its entity
index, and what was derived from a user's events is used again only
while the store reports the entity unchanged
(`EventsBackend.entity_version`), so a write that has returned is in the
next answer.
"""

from __future__ import annotations

import dataclasses
import logging
from itertools import repeat

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    Params,
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

logger = logging.getLogger(__name__)

#: the unknown user's branch looks at this many of their latest views
#: (reference ECommAlgorithm.predictSimilar: ``limit = Some(10)``)
RECENT_VIEWS = 10
#: users whose resolved seen items a tenant keeps beside the store's
#: version of them; past it the oldest half goes
_SEEN_CACHE_USERS = 1 << 18


@dataclasses.dataclass(frozen=True)
class ECommDataSourceParams(Params):
    app_name: str = "MyApp"
    event_names: tuple[str, ...] = ("view", "buy")
    item_entity_type: str = "item"


@dataclasses.dataclass
class ECommTrainingData(SanityCheck):
    interactions: Interactions
    item_categories: dict[str, list[str]]

    def sanity_check(self) -> None:
        if self.interactions.nnz == 0:
            raise ValueError("no view/buy events found")


class ECommDataSource(DataSource):
    params_class = ECommDataSourceParams

    def read_training(self, ctx: ComputeContext) -> ECommTrainingData:
        p = self.params
        store = EventStore()
        frame = store.frame(p.app_name, event_names=list(p.event_names))
        props = store.aggregate_properties(
            p.app_name, entity_type=p.item_entity_type
        )
        return ECommTrainingData(
            interactions=frame.to_interactions().dedupe_sum(),
            item_categories={
                eid: [str(c) for c in pm.get("categories") or []]
                for eid, pm in props.items()
            },
        )


@dataclasses.dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    app_name: str = "MyApp"          # for serve-time event reads
    seen_events: tuple[str, ...] = ("view", "buy")
    similar_events: tuple[str, ...] = ("view",)
    unseen_only: bool = True
    rank: int = 16
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 5
    block_len: int = 64
    row_chunk: int = 256


@dataclasses.dataclass
class ECommModel:
    # host np.ndarray after train, device jax.Array after staging
    user_factors: np.ndarray | jax.Array
    item_factors: np.ndarray | jax.Array
    user_map: BiMap
    item_map: BiMap
    item_categories: dict[str, list[str]]
    popularity: np.ndarray  # [I] interaction counts (cold-user fallback)
    #: True on phantom padding rows of the staged item table (None when
    #: unpadded). Optional so pre-sharding pickled models load unchanged.
    item_phantom_mask: "jax.Array | None" = None
    #: the app whose events the rules read at serve time; None = the
    #: algorithm's ``app_name``. A pool's tenants share one set of engine
    #: params, so each tenant's model names its own app.
    app_name: "str | None" = None
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
            "pio_ecomm_queries_total",
            "E-commerce queries by branch: known user, similar to the "
            "unknown user's recent views, popular",
            ("branch",),
        )
        self.filtered = registry.counter(
            "pio_ecomm_filtered_queries_total",
            "E-commerce queries that carried the rule",
            ("rule",),
        )
        self.excluded = registry.counter(
            "pio_ecomm_excluded_items_total",
            "Item entries of the packed seen/black/white lists sent to "
            "the device",
        )
        self.lookups = registry.counter(
            "pio_ecomm_rule_lookups_total",
            "Entities the predict path asked the event store about",
        )
        self.seen_lookups = registry.counter(
            "pio_ecomm_seen_lookups_total",
            "Queries whose user's seen item rows were kept from an "
            "earlier read at the same entity version (hit), or read "
            "from the store's entity index (miss)",
            ("result",),
        )
        self.short = registry.counter(
            "pio_ecomm_short_answers_total",
            "E-commerce answers with fewer items than the query's num",
        )
        self.branch = {
            similarity.KNOWN: self.queries.labels("known"),
            similarity.SIMILAR: self.queries.labels("similar"),
            similarity.POPULAR: self.queries.labels("popular"),
        }
        self.rule = {
            r: self.filtered.labels(r)
            for r in ("categories", "whiteList", "blackList")
        }
        self.seen = {
            r: self.seen_lookups.labels(r) for r in ("hit", "miss")
        }


class ECommAlgorithm(Algorithm):
    params_class = ECommAlgorithmParams

    def train(self, ctx: ComputeContext, pd: ECommTrainingData) -> ECommModel:
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
            implicit=True,
            seed=p.seed,
            block_len=p.block_len,
            row_chunk=p.row_chunk,
        )
        popularity = np.bincount(
            inter.cols, weights=inter.values, minlength=inter.n_cols
        ).astype(np.float32)
        return ECommModel(
            user_factors=factors.user_factors,
            item_factors=factors.item_factors,
            user_map=inter.entity_map,
            item_map=inter.target_map,
            item_categories=pd.item_categories,
            popularity=popularity,
            app_name=p.app_name,
        )

    def stage_model(self, ctx, model: ECommModel) -> ECommModel:
        """Factors commit through the sharded-catalog machinery the
        other ALS templates use (row-sharded over a model mesh axis,
        phantom padding rows masked — the ``Algorithm.stage_model``
        sharded-model contract), the item table padded to a whole number
        of ``similarity.CATALOG_ROW_MULTIPLE`` rows. Beside them, one
        entry per item row: category ids, 1/norm, popularity and the
        unavailable bitmap (phantom rows now; the ``constraint`` entity's
        items from the first predict on). ``model.popularity`` itself
        stays as trained."""
        n_items = len(model.item_map)
        user_f, _ = partition.stage_factor_matrix(
            ctx, model.user_factors, n_real=len(model.user_map)
        )
        item_f, item_mask = partition.stage_factor_matrix(
            ctx,
            similarity.pad_rows(
                model.item_factors, staged_rules.padded_rows(ctx, n_items)
            ),
            n_real=n_items,
        )
        return dataclasses.replace(
            model,
            user_factors=user_f,
            item_factors=item_f,
            item_phantom_mask=item_mask,
            rules=staged_rules.stage(
                model, item_f, NamedSharding(ctx.mesh, PartitionSpec()),
                popularity=model.popularity,
            ),
        )

    # -- serve-time business rules (reference ECommAlgorithm.predict) -----
    def _reader(self, model: ECommModel):
        """The store's reader of this model's app, or None where the
        store cannot be reached: the rules that need it are then left
        out, as the reference serves on when its event read times out."""
        try:
            return EventStore().entity_reader(
                model.app_name or self.params.app_name
            )
        except Exception as e:  # noqa: BLE001 - serve without the rules
            logger.debug("event store unavailable to the rules: %s", e)
            return None

    def _refresh_unavailable(self, model, rules: StagedRules, reader):
        """`rules.unavailable` from the latest ``$set`` of
        ``constraint/unavailableItems``: rebuilt only when the store
        reports the entity changed, and then only if the latest event is
        another one."""
        version = reader.version("constraint", "unavailableItems")
        if version is not None and version == rules.constraint[0]:
            return
        events = reader.find(
            "constraint", "unavailableItems", event_names=["$set"], limit=1
        )
        event_id = events[0].event_id if events else ""
        if event_id != rules.constraint[1]:
            rows = rules.unavailable.shape[0]
            bitmap = np.arange(rows) >= len(model.item_map)
            if events:
                found = np.fromiter(
                    (
                        model.item_map.get(str(i), -1)
                        for i in events[0].properties.get("items") or ()
                    ),
                    np.int64,
                )
                bitmap[found[found >= 0]] = True
            rules.unavailable = jax.device_put(
                bitmap, rules.unavailable.sharding
            )
        rules.constraint = (version, event_id)

    def _seen_rows(
        self, model, rules: StagedRules, reader, users, seen, counters
    ) -> None:
        """``seen[i]``: item rows of the ``seen_events`` of ``users[i]``,
        as the store has them now. Rows kept from an earlier read are
        used again only while the store's version of the user reads the
        same; otherwise the targets come straight from the store's
        entity index."""
        kept_rows, names = rules.seen, frozenset(self.params.seen_events)
        get = model.item_map.getter()
        misses = 0
        for i, user in enumerate(users):
            version = reader.version("user", user)
            kept = kept_rows.get(user)
            if kept is not None and version is not None and kept[0] == version:
                seen[i] = kept[1]
                continue
            misses += 1
            # the rows of the ids the model knows, with no numpy call over
            # a long array (`similarity._HELD`)
            known = [
                r for r in map(get, reader.targets("user", user, names))
                if r is not None
            ]
            seen[i] = found = np.fromiter(known, np.int32, len(known))
            if version is not None:
                if len(kept_rows) >= _SEEN_CACHE_USERS:
                    for old in list(kept_rows)[: _SEEN_CACHE_USERS // 2]:
                        del kept_rows[old]
                kept_rows[user] = (version, found)
        counters.seen["miss"].inc(misses)
        counters.seen["hit"].inc(len(users) - misses)

    def _recent_rows(self, model, reader, user: str) -> list[int]:
        """Rows of the items of the user's latest views that the model
        knows, newest first."""
        events = reader.find(
            "user", user, event_names=self.params.similar_events,
            limit=RECENT_VIEWS,
        )
        rows = (
            model.item_map.get(e.target_entity_id, -1)
            for e in events if e.target_entity_id
        )
        return [r for r in rows if r >= 0]

    def _read_rules(
        self, model, rules, reader, users, mode, recent, seen, counters
    ) -> None:
        """The batch's store lookups: the constraint, an unknown user's
        recent views (which make the query SIMILAR), and every user's
        seen items."""
        self._refresh_unavailable(model, rules, reader)
        for i in np.flatnonzero(mode != similarity.KNOWN).tolist():
            views = self._recent_rows(model, reader, users[i])
            if views:
                mode[i] = similarity.SIMILAR
                recent[i, : len(views)] = views
        if self.params.unseen_only:
            self._seen_rows(model, rules, reader, users, seen, counters)

    def predict(self, model: ECommModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: ECommModel, queries) -> list[dict]:
        if not queries:
            return []
        return self.batch_predict_collect(
            model, self.batch_predict_launch(model, queries), queries
        )

    def batch_predict_launch(self, model: ECommModel, queries):
        """Host prep + device enqueue, no barrier. Every query of the
        batch becomes one row of compact operands: its branch, its user
        row or recent views, its category ids, and one packed list of
        item rows that is either what to leave out (seen + blackList) or,
        with a whiteList, what alone may come back. Shapes are bucketed
        (batch rows, top-k size and category slots to powers of two, the
        packed lists by `similarity.list_capacity`)."""
        if not queries:
            return None
        with tracing.stage(tracing.PREDICT_PREP):
            if model.rules is None:  # an unstaged model (evaluation)
                model.rules = staged_rules.stage(
                    model, jnp.asarray(model.item_factors),
                    popularity=model.popularity,
                )
            rules = model.rules
            counters = _Counters.of(tracing.bound_registry())
            n, n_items = len(queries), len(model.item_map)
            # one pass over the queries, then the batch by arrays
            users, nums, wanted, black, white = zip(*[
                (
                    str(q.get("user", "")), int(q.get("num", 10)),
                    q.get("categories") or (), q.get("blackList") or (),
                    q.get("whiteList") or (),
                )
                for q in queries
            ])
            num = min(max(1, max(nums)), n_items)
            num_bucket = min(similarity.bucket(num), n_items)
            # the batch's operands, filled in place through these views
            operands = similarity.QueryRules.blank(
                similarity.bucket(n),
                similarity.bucket(max(1, max(map(len, wanted)))),
            )
            per_query, q_cats = operands.per_query, operands.categories
            user_rows = np.fromiter(
                map(model.user_map.getter(), users, repeat(-1)), np.int32, n
            )
            mode = np.where(
                user_rows >= 0, similarity.KNOWN, similarity.POPULAR
            )
            # each user's seen rows, then the rest
            lists = [staged_rules.NO_ROWS] * n
            with tracing.stage(tracing.PREDICT_RULES):
                reader = self._reader(model)
                try:
                    if reader is not None:
                        self._read_rules(
                            model, rules, reader, users, mode,
                            operands.recent, lists, counters,
                        )
                        counters.lookups.inc(1 + n)
                except Exception as e:  # noqa: BLE001 - serve on
                    # as the reference serves on when its read times out
                    logger.warning(
                        "event store failed during the rules' lookups "
                        "(%s: %s): the batch goes without what was not "
                        "read", type(e).__name__, e,
                    )
            per_query[:n, 0] = np.maximum(user_rows, 0)
            per_query[:n, 1] = mode
            get = model.item_map.getter()
            for i in range(n):
                if black[i] or white[i]:
                    lists[i] = staged_rules.listed_rows(
                        get, black[i], white[i], lists[i]
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
            counters.rule["blackList"].inc(sum(map(bool, black)))
            counters.rule["whiteList"].inc(sum(map(bool, white)))
            counters.rule["categories"].inc(len(filtered))
            for branch, child in counters.branch.items():
                child.inc(int((mode == branch).sum()))
            counters.excluded.inc(sum(map(len, lists)))
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            scores, items = similarity.rules_top_k(
                model.user_factors, model.item_factors, num_bucket,
                rules.catalog, operands,
            )
        return scores, items, nums, counters

    def batch_predict_collect(
        self, model: ECommModel, handle, queries
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
            short = sum(
                len(a["itemScores"]) < num for a, num in zip(out, nums)
            )
            if short:
                counters.short.inc(short)
        return out


def ecommerce_engine() -> Engine:
    return Engine(
        ECommDataSource,
        IdentityPreparator,
        {"ecomm": ECommAlgorithm},
        FirstServing,
    )


register_engine("ecommerce", ecommerce_engine)
