"""The co-occurrence template's two device programs compiled for one v5e
chip at the Instacart catalog's width (49,688 items), without the chip:
the TPU compiler refuses what would not fit and reports what each program
keeps beside the 9.24 GiB table. Nothing runs, so nothing here is a time.

The table is held in column panels (`ops/cooccurrence.layout`) because
the compiler splits a gather of wider rows by slicing the table first;
these tests hold that neither program copies the table."""

from __future__ import annotations

import os

import pytest

N_ITEMS = 49688


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table(sharding):
    import jax.numpy as jnp

    from predictionio_tpu.ops import cooccurrence as co

    n_panels, width = co.layout(N_ITEMS)
    assert (n_panels, width) == (2, 24960)
    return _shape(sharding, (n_panels, N_ITEMS, width), jnp.float32), n_panels * width


def test_the_cart_step_gathers_without_copying_the_table(one_chip):
    """A batch of 64 carts at 1,024 pair slots keeps the gathered rows
    beside the table (1,024 x 49,920 f32, 204 MB), not a copy of it."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import cooccurrence as co

    table, n_pad = _table(one_chip)
    compiled = co.basket_rule_scores.lower(
        table, _shape(one_chip, (2, n_pad), jnp.float32),
        _shape(one_chip, (2, 1024), jnp.int32), n_rows=64, k=16,
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1024 * n_pad * 4 * 1.1
    assert "mini-gather" not in compiled.as_text()


def test_the_train_step_adds_into_the_donated_table(one_chip):
    """A chunk of 1,024 baskets: the output is the table's own buffer, and
    beside it only the chunk's multi-hot rows (bfloat16, 102 MB)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import cooccurrence as co

    table, n_pad = _table(one_chip)
    memory = co.accumulate_pairs.lower(
        table, _shape(one_chip, (2, 16384), jnp.int32), n_rows=1024
    ).compile().memory_analysis()
    assert memory.alias_size_in_bytes == N_ITEMS * n_pad * 4 == 9921699840
    assert memory.temp_size_in_bytes < 1024 * n_pad * 2 * 2.5
