"""Regex-rule partition engine — PartitionSpecs matched to pytree paths.

The reference blocks its factor RDDs across the cluster with a
partitioner chosen per-RDD (MLlib ALS ``setBlocks``); the TPU-native
equivalent is a **rule table**: an ordered sequence of
``(regex, PartitionSpec)`` pairs matched against each leaf's "/"-joined
pytree path (the DrJAX / fmengine ``match_partition_rules`` idiom —
SNIPPETS.md [1]). One table describes the layout of a whole model or
staged-geometry pytree; the same table derives the ``NamedSharding``
in/out specs of the jitted programs that consume it, so the array
placement and the program contract cannot drift apart.

Rules are matched first-wins with ``re.search``; scalar leaves are never
partitioned (they get ``P()`` without consulting the table); a leaf no
rule matches is a hard error — silent replication of a tensor someone
meant to shard is exactly the bug this engine exists to prevent.

``validate_rules`` checks every axis a table names against a concrete
mesh at staging time; the static ``sharding-spec`` lint rule
(docs/static_analysis.md) performs the same check at review time over
the axis names the project's meshes actually construct.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_unflatten

from predictionio_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    ComputeContext,
    pad_to_multiple,
    record_padded_rows,
)

logger = logging.getLogger(__name__)

#: one partition-rule table: ordered (regex, PartitionSpec) pairs
Rules = Sequence[tuple[str, P]]


# --------------------------------------------------------------------------
# Leaf naming
# --------------------------------------------------------------------------


def _key_name(entry: Any) -> str:
    """One path entry → its name fragment (dict key, attr name, index)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def leaf_path_name(path: tuple) -> str:
    """"/"-joined name of a pytree leaf path (``slabs/0/idx``)."""
    return "/".join(_key_name(p) for p in path)


def tree_leaf_names(tree: Any) -> list[str]:
    """Every leaf's "/"-joined path name, in flatten order — the names
    :func:`match_partition_rules` matches rules against."""
    paths, _ = tree_flatten_with_path(tree)
    return [leaf_path_name(p) for p, _leaf in paths]


# --------------------------------------------------------------------------
# Rule matching
# --------------------------------------------------------------------------


def match_partition_rule(rules: Rules, name: str) -> P:
    """The PartitionSpec the first matching rule assigns to ``name``.

    Raises ``ValueError`` when no rule matches — a table is a complete
    layout description, not a set of hints.
    """
    for pattern, spec in rules:
        if re.search(pattern, name) is not None:
            return spec
    raise ValueError(
        f"no partition rule matches leaf {name!r}; add a rule (or an "
        f"explicit catch-all) to the table"
    )


def match_partition_rules(rules: Rules, tree: Any) -> Any:
    """Pytree of PartitionSpecs matching ``tree``'s structure.

    Each leaf's "/"-joined path is matched against the table
    (first-wins, ``re.search``). Scalar leaves — 0-d or single-element
    arrays, plain Python numbers — are never partitioned and get
    ``P()`` without consulting the table (the fmengine convention).
    """
    paths, treedef = tree_flatten_with_path(tree)
    specs = []
    for path, leaf in paths:
        shape = np.shape(leaf)
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            specs.append(P())
            continue
        specs.append(match_partition_rule(rules, leaf_path_name(path)))
    return tree_unflatten(treedef, specs)


def _spec_axes(spec: P):
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        for axis in names:
            if axis is not None:
                yield axis


def validate_rules(rules: Rules, mesh) -> None:
    """Every axis a rule's spec names must exist on ``mesh``.

    GSPMD surfaces a bad axis name deep inside lowering (or silently
    replicates); this fails at staging with the offending rule named.
    """
    axes = set(mesh.axis_names)
    for pattern, spec in rules:
        for axis in _spec_axes(spec):
            if axis not in axes:
                raise ValueError(
                    f"partition rule {pattern!r} names mesh axis "
                    f"{axis!r}, not on mesh axes {sorted(axes)}"
                )


# --------------------------------------------------------------------------
# Placement
# --------------------------------------------------------------------------


def named_shardings(mesh, spec_tree: Any) -> Any:
    """PartitionSpec tree → NamedSharding tree over ``mesh``."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_pytree(
    ctx_or_mesh, rules: Rules, tree: Any, *, validate: bool = True
) -> Any:
    """Commit every leaf of ``tree`` to the mesh per the rule table.

    The one-call staging path: match rules → validate axes → one
    ``jax.device_put`` per leaf with the matched ``NamedSharding``.
    Accepts a :class:`ComputeContext` or a bare ``Mesh``.
    """
    mesh = getattr(ctx_or_mesh, "mesh", ctx_or_mesh)
    if validate:
        validate_rules(rules, mesh)
    specs = match_partition_rules(rules, tree)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        tree,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------------------------
# shard_map
# --------------------------------------------------------------------------


def shard_map(body, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by
    default: the ALS epoch ``fori_loop`` carries factors that enter
    unvarying over ``data`` and come back varying (after the
    ``all_gather`` + ``take`` reassembly), which ``check_vma=True``
    rejects as a carry type mismatch."""
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check,
    )


# --------------------------------------------------------------------------
# Mesh-from-topology helpers
# --------------------------------------------------------------------------


def topology_mesh_shape(
    n_devices: int, model_parallelism: int = 0
) -> tuple[int, int]:
    """(data, model) mesh shape for ``n_devices``.

    ``model_parallelism=0`` picks the default topology: model axis of 2
    whenever the device count is even (the multichip-dryrun convention
    — factor matrices genuinely split while the data axis keeps the
    slab rows wide), else 1. An explicit value must divide the device
    count.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    model = model_parallelism or (
        2 if n_devices % 2 == 0 and n_devices > 1 else 1
    )
    if model < 1 or n_devices % model:
        raise ValueError(
            f"model_parallelism {model} does not divide {n_devices} "
            "devices"
        )
    return (n_devices // model, model)


def mesh_from_topology(
    n_devices: int | None = None,
    model_parallelism: int = 0,
    batch: str = "",
    devices: Sequence[jax.Device] | None = None,
) -> ComputeContext:
    """ComputeContext over a (data, model) topology.

    ``n_devices=None`` uses every available device; otherwise the first
    ``n_devices`` (the multichip bench sweeps 1→2→4→8 this way on one
    simulated host platform).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return ComputeContext.create(
        batch=batch,
        mesh_shape=topology_mesh_shape(n, model_parallelism),
        devices=devs[:n],
    )


# --------------------------------------------------------------------------
# ALS rule tables (the flagship layout)
# --------------------------------------------------------------------------

#: Model-sharded ALS geometry (docs/parallelism.md "Sharded ALS"):
#: factor matrices row-sliced over ``model`` (each device persistently
#: holds 1/model_parallelism of the rows), slab interaction arrays
#: row-split over the combined (data, model) axes so every chip solves
#: normal equations, the heavy-sub-row owner map split with its slab,
#: and the device-major reassembly permutation split over ``model``.
ALS_SHARDED_RULES: Rules = (
    (r"(^|/)(user|item)_factors$", P(MODEL_AXIS, None)),
    (r"(^|/)owner$", P((DATA_AXIS, MODEL_AXIS))),
    (r"(^|/)(idx|weights|valid)$", P((DATA_AXIS, MODEL_AXIS), None)),
    (r"(^|/)inv_perm$", P(MODEL_AXIS)),
)

#: Replicated-factor ALS geometry (1-D data meshes): factor matrices
#: replicated per device, slab rows split over ``data`` only.
ALS_REPLICATED_RULES: Rules = (
    (r"(^|/)(user|item)_factors$", P()),
    (r"(^|/)(idx|weights|valid|owner)$", P(DATA_AXIS)),
    (r".*", P()),
)


def als_partition_rules(sharded: bool) -> Rules:
    """The ALS rule table for a factor layout (docs/parallelism.md)."""
    return ALS_SHARDED_RULES if sharded else ALS_REPLICATED_RULES


# --------------------------------------------------------------------------
# Serving-side factor staging
# --------------------------------------------------------------------------


def stage_factor_matrix(
    ctx: ComputeContext,
    arr,
    n_real: int | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Commit one factor matrix model-sharded; returns
    ``(factors, phantom_mask)``.

    Rows are padded to the model-axis multiple (phantom rows zero) so
    each device holds an equal slice — the serving-side counterpart of
    the trainer's ``row_multiple`` padding. ``phantom_mask`` is a
    device-resident ``[rows] bool`` array, ``True`` on phantom rows
    (``None`` when nothing was padded); serving top-k paths pass it as
    the score mask so a padded row can never surface as a result, even
    if a corrupt artifact gives it nonzero factors. An already
    device-resident array with the right sharding passes through
    without a host round-trip — the unbroken train→serve path.
    """
    spec = match_partition_rule(ALS_SHARDED_RULES, "item_factors")
    sharding = NamedSharding(ctx.mesh, spec)
    n_rows = int(arr.shape[0])
    n_real = n_rows if n_real is None else int(n_real)
    multiple = max(ctx.model_parallelism, 1)
    if isinstance(arr, jax.Array) and not arr.is_deleted():
        if n_rows % multiple:
            raise ValueError(
                f"device-resident factor matrix has {n_rows} rows, not "
                f"a multiple of model_parallelism {multiple}; pad at "
                "training time (train_als row_multiple does)"
            )
        staged = (
            arr
            if arr.sharding == sharding
            else jax.device_put(arr, sharding)
        )
    else:
        padded = pad_to_multiple(np.asarray(arr), multiple, axis=0)
        if padded.shape[0] != n_rows:
            record_padded_rows(
                padded.shape[0] - n_rows, n_rows, multiple
            )
        staged = jax.device_put(padded, sharding)
    if staged.shape[0] <= n_real:
        return staged, None
    mask = np.arange(staged.shape[0]) >= n_real
    mask_sharding = NamedSharding(
        ctx.mesh, match_partition_rule(ALS_SHARDED_RULES, "inv_perm")
    )
    return staged, jax.device_put(mask, mask_sharding)
