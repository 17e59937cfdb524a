"""Complementary-purchase template — "frequently bought together".

Gallery parity: PredictionIO's official template gallery shipped a
Complementary Purchase engine (basket analysis over ``buy`` events —
the reference repo links the gallery rather than bundling it; the
nearest in-tree pattern is ``examples/scala-parallel-similarproduct``,
whose DASE layout this follows). The gallery engine mined association
rules with FP-Growth on Spark; queries named a basket and got back the
items most often bought together with it.

TPU-first redesign: instead of lattice-walking FP-Growth (pointer-heavy,
hostile to XLA), each chunk of baskets becomes multi-hot rows ``B`` on
the device and the whole pair-count table is one MXU product per chunk,
``C += Bᵀ B``, accumulated in the table's own buffer
(:mod:`predictionio_tpu.ops.cooccurrence`). The model is that table,
held on the device: supports, lift and confidence fall out of it with
elementwise math at serve time, so ``metric`` and ``min_support`` are
serving parameters. A batch of carts is scored exactly in one program:
the table rows of the carts' items are gathered, turned into rule
scores, summed per cart and ranked on the device. Nothing is truncated
before the sum (a per-item top-k merged on the host misranks the
complements that several cart items share), and the catalog is bounded
by one chip's memory: 4·n² bytes, about 60,000 items in 16 GiB.

DASE:

* DataSource reads ``buy`` interactions (COO + event times) and groups
  each user's purchases into baskets split at ``basket_window_secs``
  gaps (the gallery's "basket = events close in time" rule).
* Preparator is identity (basketing is part of the read; re-windowing
  belongs to the data source contract).
* Algorithm counts pairs; ``lift`` (default) or ``confidence`` scoring,
  ``min_support`` basket-count floor.
* Queries ``{"items": ["i1", ...], "num": N}`` answer
  ``{"itemScores": [{"item": ..., "score": ...}, ...]}`` — the summed
  rule scores of the queried items, the queried items excluded, only
  items with a positive score, at most ``N``, best first.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

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
from predictionio_tpu.data.store import EventStore
from predictionio_tpu.models.staged_rules import RegistryCounters
from predictionio_tpu.obs import tracing
from predictionio_tpu.ops import cooccurrence as co
from predictionio_tpu.ops.similarity import host_operand
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.bimap import BiMap

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class CPDataSourceParams(Params):
    app_name: str = "MyApp"
    event_names: tuple[str, ...] = ("buy",)
    #: a gap longer than this starts a new basket for the user
    basket_window_secs: float = 3600.0


@dataclasses.dataclass
class CPTrainingData(SanityCheck):
    """Baskets as sorted unique dense item ids, ``items[indptr[b]:
    indptr[b + 1]]`` the items of basket b."""

    item_map: BiMap
    indptr: np.ndarray  # int64 [n_baskets + 1]
    items: np.ndarray   # int32 [indptr[-1]]

    @property
    def n_baskets(self) -> int:
        return len(self.indptr) - 1

    def sanity_check(self) -> None:
        if self.n_baskets == 0:
            raise ValueError("no buy events found — seed data first")
        if np.all(np.diff(self.indptr) < 2):
            raise ValueError(
                "no basket contains two items; co-occurrence needs "
                "multi-item baskets (check basket_window_secs)"
            )


def baskets_of(users, items, times, window_secs: float, n_items: int):
    """``(indptr, items)`` of the baskets: each user's events in time
    order, split where the gap passes ``window_secs``, each basket's
    distinct items (dense ids below ``n_items``) sorted. One pass of
    sorts, no loop over baskets."""
    if len(users) == 0:
        return np.zeros(1, np.int64), np.zeros(0, np.int32)
    order = np.lexsort((times, users))
    users, items, times = users[order], items[order], times[order]
    new = np.empty(len(users), bool)
    new[0] = True
    new[1:] = (users[1:] != users[:-1]) | (
        (times[1:] - times[:-1]) > window_secs
    )
    basket = np.cumsum(new) - 1
    keys = np.unique(basket * n_items + items.astype(np.int64))
    indptr = np.zeros(int(basket[-1]) + 2, np.int64)
    np.cumsum(np.bincount(keys // n_items, minlength=len(indptr) - 1),
              out=indptr[1:])
    return indptr, (keys % n_items).astype(np.int32)


class CPDataSource(DataSource[CPTrainingData, dict, dict, list]):
    params_class = CPDataSourceParams

    def read_training(self, ctx: ComputeContext) -> CPTrainingData:
        p = self.params
        inter = EventStore().interactions(
            p.app_name, event_names=list(p.event_names)
        )
        indptr, items = baskets_of(
            inter.rows, inter.cols, inter.times, p.basket_window_secs,
            len(inter.target_map),
        )
        return CPTrainingData(
            item_map=inter.target_map, indptr=indptr, items=items
        )


@dataclasses.dataclass(frozen=True)
class CPAlgoParams(Params):
    """``metric``: "lift" (P(i,j)·N / (P(i)P(j)), default — the
    gallery's interestingness measure) or "confidence" (P(j|i)).
    ``min_support``: minimum baskets an item pair must co-occur in.
    Both apply at serve time, over the trained pair counts."""

    metric: str = "lift"
    min_support: int = 2
    #: baskets per device chunk for the BᵀB accumulation
    chunk: int = 1024


@dataclasses.dataclass
class CPModel:
    """The pair-count table (:mod:`predictionio_tpu.ops.cooccurrence`:
    f32 [n_panels, n_items, width], counts as integers, the diagonal each
    item's basket count), the number of baskets counted, and the item
    map. ``stats`` is derived on the device from the table and is not
    pickled."""

    table: np.ndarray | jax.Array
    n_baskets: int
    item_map: BiMap
    stats: jax.Array | None = None

    def __getstate__(self) -> dict:
        return {
            "table": np.asarray(self.table),
            "n_baskets": self.n_baskets,
            "item_map": self.item_map,
        }

    def __setstate__(self, state: dict) -> None:
        if "table" not in state:
            raise ValueError(
                "this complementary-purchase model was trained before the "
                "model held its pair counts (it holds each item's top "
                "complements only, from which no exact answer can be made): "
                "retrain it with `pio-tpu train`"
            )
        self.__dict__.update(state, stats=None)


#: each cart's share of a batch's pair slots: a batch of B carts sends a
#: power of two of at least ``ITEMS_PER_QUERY[0] * B`` slots, and the
#: first launch of a batch bucket compiles the pair buckets up to
#: ``ITEMS_PER_QUERY[-1] * B`` beside it
ITEMS_PER_QUERY = (16, 32, 64)
#: the least top-k a batch asks the device for: every ``num`` up to it
#: shares one program
MIN_TOP_K = 16


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _padding(slots: int, n_rows: int) -> np.ndarray:
    """int32 [2, slots] of padding pairs: (cart ``n_rows``, item 0), which
    the step gathers and drops."""
    pairs = np.zeros((2, slots), np.int32)
    pairs[0] = n_rows
    return pairs


class _Counters(RegistryCounters):
    """The template's counters in one registry."""

    def __init__(self, registry):
        self.items = registry.counter(
            "pio_cpurchase_items_total",
            "Distinct known (cart, item) pairs sent to the device: the "
            "table rows a batch gathers",
        )
        self.slots = registry.counter(
            "pio_cpurchase_item_slots_total",
            "Pair slots sent to the device, padding to the batch's "
            "power-of-two pair bucket included",
        )
        self.unknown = registry.counter(
            "pio_cpurchase_unknown_items_total",
            "Cart items the model's item map lacks (they score nothing)",
        )


class CPAlgorithm(Algorithm[CPTrainingData, CPModel, dict, dict]):
    params_class = CPAlgoParams

    def __init__(self, params=None):
        super().__init__(params)
        #: (table shape, batch bucket) whose pair buckets are compiled
        self._warm: set = set()

    def _check_metric(self) -> None:
        if self.params.metric not in co.METRICS:
            raise ValueError(
                f"metric must be 'lift' or 'confidence', "
                f"got {self.params.metric!r}"
            )

    def train(self, ctx: ComputeContext, data: CPTrainingData) -> CPModel:
        self._check_metric()
        chunk = self.params.chunk
        n_items = len(data.item_map)
        n_panels, width = co.layout(n_items)
        table = jnp.zeros((n_panels, n_items, width), jnp.float32)
        sizes = np.diff(data.indptr)
        bounds = list(range(0, data.n_baskets, chunk)) + [data.n_baskets]
        # one pair bucket for every chunk: one program
        most = max((int(data.indptr[hi] - data.indptr[lo])
                    for lo, hi in zip(bounds[:-1], bounds[1:])), default=0)
        slots = _pow2(max(most, 1))
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            at, end = data.indptr[lo], data.indptr[hi]
            pairs = np.zeros((2, slots), np.int32)
            pairs[0] = chunk
            pairs[0, :end - at] = np.repeat(
                np.arange(hi - lo, dtype=np.int32), sizes[lo:hi]
            )
            pairs[1, :end - at] = data.items[at:end]
            table = co.accumulate_pairs(table, pairs, n_rows=chunk)
        logger.info(
            "complementary-purchase model: %d items, %d baskets, "
            "%d panel(s) of %d lanes", n_items, data.n_baskets, n_panels,
            width,
        )
        return CPModel(
            table=table, n_baskets=data.n_baskets, item_map=data.item_map,
            stats=co.item_stats(table, data.n_baskets),
        )

    def stage_model(self, ctx: ComputeContext, model: CPModel) -> CPModel:
        """Commit the table once at deploy and derive the item counts
        beside it; a train's device table passes through."""
        self._check_metric()
        table = model.table
        if not isinstance(table, jax.Array):
            table = ctx.replicate(np.asarray(table, np.float32))
        if model.stats is not None and table is model.table:
            return model
        return dataclasses.replace(
            model, table=table, stats=co.item_stats(table, model.n_baskets)
        )

    def predict(self, model: CPModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: CPModel, queries) -> list[dict]:
        if not queries:
            return []
        return self.batch_predict_collect(
            model, self.batch_predict_launch(model, queries), queries
        )

    def _top_k(self, model: CPModel, queries) -> int:
        """The batch's top-k on the device: a power of two of at least
        ``MIN_TOP_K`` over the largest ``num``, at most the catalog."""
        most = max(int(q.get("num", 10)) for q in queries)
        return min(len(model.item_map), max(MIN_TOP_K, _pow2(most)))

    def _scores(self, model: CPModel, pairs, n_rows: int, k: int):
        """The step over a trained or staged model (its table and item
        counts on the device)."""
        return co.basket_rule_scores(
            model.table, model.stats, host_operand(pairs), n_rows, k,
            metric=self.params.metric, min_support=self.params.min_support,
        )

    def _warm_pair_buckets(self, model: CPModel, n_rows: int) -> None:
        """A batch bucket's first launch (a server's warm-up) also
        compiles the step at each of its pair buckets for carts with a
        ``num`` up to ``MIN_TOP_K``, so that such traffic compiles
        nothing after warm-up while its carts hold up to
        ``ITEMS_PER_QUERY[-1]`` items."""
        key = (np.shape(model.table), n_rows)
        if key in self._warm:
            return
        self._warm.add(key)
        k = min(MIN_TOP_K, len(model.item_map))
        jax.block_until_ready([
            self._scores(model, _padding(per_query * n_rows, n_rows), n_rows, k)
            for per_query in ITEMS_PER_QUERY
        ])

    def batch_predict_launch(self, model: CPModel, queries):
        """Host prep + device enqueue, no barrier: the batch's cart items
        to table rows, one (cart, item) pair each distinct known item,
        padded to a power-of-two bucket; enqueue the scoring step."""
        if not queries:
            return None
        n_rows = _pow2(len(queries))
        self._warm_pair_buckets(model, n_rows)
        with tracing.stage(tracing.PREDICT_PREP):
            carts = [q.get("items") or [] for q in queries]
            sizes = np.fromiter(map(len, carts), np.int64, len(carts))
            rows = model.item_map.rows(
                [item for cart in carts for item in cart]
            ).astype(np.int64)
            known = rows >= 0
            keys = np.unique(
                np.repeat(np.arange(len(carts), dtype=np.int64), sizes)[known]
                * len(model.item_map) + rows[known]
            )
            n_pairs = len(keys)
            pairs = _padding(
                max(ITEMS_PER_QUERY[0] * n_rows, _pow2(n_pairs)), n_rows
            )
            pairs[0, :n_pairs] = keys // len(model.item_map)
            pairs[1, :n_pairs] = keys % len(model.item_map)
            k = self._top_k(model, queries)
            counters = _Counters.of(tracing.bound_registry())
            counters.items.inc(n_pairs)
            counters.slots.inc(pairs.shape[1])
            counters.unknown.inc(int(len(rows) - known.sum()))
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            tracing.launch_call(2)  # the pairs' upload, the step
            out = self._scores(model, pairs, n_rows, k)
        return out

    def batch_predict_collect(
        self, model: CPModel, handle, queries
    ) -> list[dict]:
        """Device barrier + per-query answers for a launch's handle."""
        if handle is None:
            return []
        with tracing.stage(tracing.PREDICT_DEVICE_GET):
            scores, items = jax.device_get(handle)
        with tracing.stage(tracing.PREDICT_MATERIALIZE):
            name = model.item_map.inverse
            answers = []
            for i, q in enumerate(queries):
                n = max(0, int(q.get("num", 10)))
                positive = int(np.count_nonzero(scores[i, :n] > 0))
                answers.append({"itemScores": [
                    {"item": name(int(j)), "score": float(s)}
                    for j, s in zip(items[i, :positive], scores[i, :positive])
                ]})
            return answers

    def warmup_query(self) -> dict:
        return {"items": [], "num": 1}


def complementarypurchase_engine() -> Engine:
    return Engine(
        CPDataSource,
        IdentityPreparator,
        {"cooccurrence": CPAlgorithm},
        FirstServing,
    )


register_engine("complementarypurchase", complementarypurchase_engine)
