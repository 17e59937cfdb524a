"""ComputeContext — the SparkContext replacement.

The reference threads a ``SparkContext`` through every controller
signature and creates it per workflow run (``WorkflowContext.scala:25-44``,
app name "PredictionIO <Mode>: <batch>"). Here the equivalent carrier is a
:class:`ComputeContext`: a ``jax.sharding.Mesh`` over the available
devices plus sharding helpers and host-staging utilities. Controllers
receive it as their first argument exactly where the reference passes
``sc``.

Mesh convention (scaling-book style):

* axis ``"data"`` — batch / example / entity-row parallelism (the RDD
  partition analogue; SURVEY.md §2.9 strategy 1);
* axis ``"model"`` — feature / factor / vocabulary sharding (the
  embedding-table tensor-parallel analogue; SURVEY.md §2.9 strategy 2).

Single-chip runs get a 1×1 mesh and every sharding degenerates to
replicated — the same jitted programs run unchanged from 1 chip to a
multi-host slice, which is the whole point of GSPMD.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"


def describe_devices(devices: Sequence[jax.Device]) -> str:
    """One line naming the backend this process got:
    ``platform=tpu device_kind="TPU v5 lite" devices=1 jax=0.9.0``.
    ``status``, ``train`` and ``deploy`` print it so a run that landed
    on the host CPU cannot pass for one that ran on the chip."""
    devs = list(devices)
    return (
        f"platform={devs[0].platform} "
        f'device_kind="{devs[0].device_kind}" '
        f"devices={len(devs)} jax={jax.__version__}"
    )


def pad_to_multiple(
    arr: np.ndarray, multiple: int, axis: int = 0, fill: Any = 0
) -> np.ndarray:
    """Pad ``axis`` up to the next multiple — the fixed-shape boundary
    (SURVEY.md §7 hard-part (a): bucketing/padding at the Preparator)."""
    size = arr.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, target - size)
    return np.pad(arr, widths, constant_values=fill)


def record_padded_rows(added: int, n_rows: int, parallelism: int) -> None:
    """Telemetry for mesh-padding sites (`shard_rows`, factor staging):
    counts phantom rows added so a workload quietly dominated by
    padding — e.g. an array smaller than the device count — is
    scrape-visible instead of silent."""
    from predictionio_tpu.obs import get_registry

    get_registry().counter(
        "pio_mesh_pad_rows_total",
        "Phantom rows added when padding arrays to a mesh-axis "
        "multiple (shard_rows / sharded factor staging)",
    ).inc(added)
    if n_rows < parallelism:
        logger.warning(
            "padding %d-row array to %d rows to shard over %d "
            "devices — padding exceeds the real data",
            n_rows, n_rows + added, parallelism,
        )


def assert_phantom_rows_zero(
    arr: np.ndarray, n_real: int, what: str = "factors"
) -> None:
    """The phantom-row invariant, asserted once centrally: rows past
    ``n_real`` exist only for mesh-shape padding and must be EXACT
    zeros (the padded normal equations have ``b = 0``, so the solver
    produces 0 — any nonzero phantom means corrupt packing/solve state
    and would score into serving top-k as a ghost entity)."""
    tail = np.asarray(arr)[n_real:]
    if tail.size and np.any(tail != 0):
        bad = int(np.count_nonzero(np.any(tail != 0, axis=-1)))
        raise AssertionError(
            f"phantom-row invariant violated: {bad} padded row(s) of "
            f"{what} past row {n_real} are nonzero"
        )


@dataclasses.dataclass
class ComputeContext:
    """Mesh + sharding helpers threaded through DASE controllers."""

    mesh: Mesh
    batch: str = ""  # run label (reference WorkflowContext app name)

    # -- construction -----------------------------------------------------
    @staticmethod
    def create(
        batch: str = "",
        mesh_shape: Sequence[int] | None = None,
        axis_names: Sequence[str] = (DATA_AXIS, MODEL_AXIS),
        devices: Sequence[jax.Device] | None = None,
    ) -> "ComputeContext":
        """Build a context over the available devices.

        Default mesh: all devices on the ``data`` axis, ``model`` axis of
        size 1 — the right default for the framework's workloads, whose
        first scaling dimension is #entities (SURVEY.md §5). Callers
        (engine variants) may request e.g. ``mesh_shape=(4, 2)`` for
        factor-sharded ALS.

        A chip belongs to one process. Measured on the v5e host (JAX
        0.9.0): while another process holds it, ``jax.devices()``
        raises within seconds ("Unable to initialize backend 'tpu'"),
        with ``JAX_PLATFORMS`` unset as well as set; it neither hangs
        nor hands back a CPU client, so no bound is put around it.
        """
        devs = list(devices if devices is not None else jax.devices())
        if mesh_shape is None:
            mesh_shape = (len(devs),) + (1,) * (len(axis_names) - 1)
        if int(np.prod(mesh_shape)) != len(devs):
            raise ValueError(
                f"mesh_shape {tuple(mesh_shape)} does not cover "
                f"{len(devs)} devices"
            )
        device_grid = np.asarray(devs).reshape(tuple(mesh_shape))
        mesh = Mesh(device_grid, tuple(axis_names))
        logger.info(
            "ComputeContext %r: mesh %s over %d %s device(s)",
            batch,
            dict(zip(axis_names, mesh_shape)),
            len(devs),
            devs[0].platform,
        )
        return ComputeContext(mesh=mesh, batch=batch)

    # -- mesh facts -------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return self.mesh.size

    @property
    def data_parallelism(self) -> int:
        return self.mesh.shape.get(DATA_AXIS, 1)

    @property
    def model_parallelism(self) -> int:
        return self.mesh.shape.get(MODEL_AXIS, 1)

    # -- sharding helpers -------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def data_sharded(self) -> NamedSharding:
        """Rows split over the data axis (the RDD-partition analogue)."""
        return NamedSharding(self.mesh, P(DATA_AXIS))

    @property
    def model_sharded(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(MODEL_AXIS))

    def shard_rows(self, arr: np.ndarray, fill: Any = 0) -> jax.Array:
        """Pad rows to the data-axis multiple and place data-sharded.

        An array smaller than the device count pads up to one row per
        device and still shards (never a silent replicated fallback);
        the added phantom rows are counted in
        ``pio_mesh_pad_rows_total`` and warned about, since a workload
        dominated by padding usually means the mesh is too wide for
        the data."""
        multiple = max(self.data_parallelism, 1)
        padded = pad_to_multiple(arr, multiple, axis=0, fill=fill)
        if padded.shape[0] != arr.shape[0]:
            record_padded_rows(
                padded.shape[0] - arr.shape[0], arr.shape[0], multiple
            )
        return jax.device_put(padded, self.data_sharded)

    def replicate(self, arr: Any) -> jax.Array:
        return jax.device_put(arr, self.replicated)

    def stop(self) -> None:
        """Release compiled-program/array references (reference
        ``sc.stop()``; jax owns the runtime so this is advisory)."""
        jax.clear_caches()
