"""Pair counts over baskets, and the rule scores of a cart.

The complementary-purchase template's model is one table of pair
counts: ``C[i, j]``, the number of baskets holding both item i and item
j, integers in f32 (exact up to 2^24 baskets). The diagonal is each
item's basket count. The table lies on the device in column panels,
f32 [n_panels, n_items, width] (:func:`layout`): ``C[i, j]`` is
``table[j // width, i, j % width]``, and the items past the last are
padding that counts 0. A gather reads whole rows of at most
``PANEL_LANES`` lanes: the TPU compiler splits a gather of wider rows by
slicing its operand, which copies half the table at every call.

Training adds ``B[:, :n]ᵀ B`` of each chunk of baskets into the donated
table (:func:`accumulate_pairs`), ``B`` the chunk's multi-hot rows built
on the device from (basket, item) index pairs: no [chunk, n] array
leaves the host, and the table is the only buffer of its size (the
compiler adds the product into it in place).

Serving scores a batch of carts exactly (:func:`basket_rule_scores`):
the rows of each cart's items are gathered, turned into lift
``C[i,j]·N/(c_i·c_j)`` or confidence ``C[i,j]/c_i`` with the pairs under
the support floor zeroed, summed per cart in f32, and ranked with the
cart's own items excluded: the form of
:func:`predictionio_tpu.ops.naive_bayes.sparse_log_scores`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: lanes of a TPU vector register: a panel's width is a multiple of them,
#: so a row of it is whole tiles' rows and a gather copies nothing else
LANES = 128
#: the widest panel: the widest row the TPU compiler gathers without
#: slicing the table first (a [49,688, 49,792] f32 table was sliced into
#: two panels of this width and the other's, a 5 GB copy a call; a
#: [49,688, 24,960] one is not)
PANEL_LANES = 195 * LANES
#: the named scope of the serving step (device-trace attribution)
SCORE_SCOPE = "cpurchase_score"
METRICS = ("lift", "confidence")


def layout(n_items: int) -> tuple[int, int]:
    """``(n_panels, width)`` of the table of ``n_items`` items: the fewest
    panels of at most ``PANEL_LANES`` lanes, of one width."""
    lanes = max(1, -(-n_items // LANES))
    n_panels = -(-lanes // (PANEL_LANES // LANES))
    return n_panels, LANES * -(-lanes // n_panels)


@partial(jax.jit, static_argnames=("n_rows",), donate_argnums=0)
def accumulate_pairs(table: jax.Array, pairs: jax.Array, n_rows: int) -> jax.Array:
    """``table`` plus the pair counts of one chunk of ``n_rows`` baskets,
    in the table's own buffer.

    pairs: int32 [2, P] of (basket in the chunk, item), each pair once;
    padding pairs name basket ``n_rows`` and are dropped. The chunk's
    multi-hot rows are 0/1 in bfloat16 and their product accumulates in
    f32, so the counts are exact on any backend."""
    n_panels, n_items, width = table.shape
    hot = jnp.zeros((n_rows, n_panels * width), jnp.bfloat16).at[
        pairs[0], pairs[1]
    ].set(1, mode="drop")
    return table + jnp.einsum(
        "bi,bpw->piw", hot[:, :n_items], hot.reshape(n_rows, n_panels, width),
        preferred_element_type=jnp.float32,
    )


@jax.jit
def item_stats(table: jax.Array, n_baskets: jax.Array) -> jax.Array:
    """f32 [2, n_panels * width]: each item's basket count (the diagonal)
    and its share of the baskets. Padding items read 1 for both, so no
    division by them makes a NaN."""
    n_panels, n_items, width = table.shape
    items = jnp.arange(n_items)
    diag = table[items // width, items, items % width]
    counts = jnp.ones(n_panels * width, jnp.float32).at[:n_items].set(
        jnp.maximum(diag, 1.0)
    )
    share = jnp.ones(n_panels * width, jnp.float32).at[:n_items].set(
        counts[:n_items] / n_baskets.astype(jnp.float32)
    )
    return jnp.stack([counts, share])


@partial(jax.jit, static_argnames=("n_rows", "k", "metric", "min_support"))
def basket_rule_scores(
    table: jax.Array,
    stats: jax.Array,
    pairs: jax.Array,
    n_rows: int,
    k: int,
    metric: str = "lift",
    min_support: int = 2,
):
    """The ``k`` best complements of each of ``n_rows`` carts: ``(scores,
    items)`` [n_rows, k], best first.

    pairs: int32 [2, P] of (cart, item), sorted by cart, each pair once;
    padding pairs name cart ``n_rows`` and are dropped. stats:
    :func:`item_stats`. A cart's score of item j is the sum over its
    items i of the rule ``i -> j`` (``metric``), 0 where the pair is in
    fewer than ``min_support`` baskets or j is i: f32 elementwise work
    and f32 adds, no matmul, so no operand is rounded to bfloat16. The
    cart's own items and the padding items score -inf."""
    n_panels, n_items, width = table.shape
    with jax.named_scope(SCORE_SCOPE):
        cart, item = pairs[0], pairs[1]
        per_item = stats[0, item][:, None]
        panels = []
        for p in range(n_panels):
            lanes = p * width + jnp.arange(width)
            rows = table[jnp.full_like(item, p), item]            # [P, width]
            rule = rows / per_item                                # confidence
            if metric == "lift":
                rule = rule / stats[1, p * width:(p + 1) * width][None, :]
            keep = (rows >= min_support) & (lanes[None, :] != item[:, None])
            panels.append(jax.ops.segment_sum(
                jnp.where(keep, rule, 0.0), cart, num_segments=n_rows,
                indices_are_sorted=True,
            ))
        scores = jnp.concatenate(panels, axis=1)
        scores = scores.at[cart, item].set(-jnp.inf, mode="drop")
        scores = jnp.where(
            jnp.arange(n_panels * width)[None, :] < n_items, scores, -jnp.inf
        )
    return jax.lax.top_k(scores, k)
