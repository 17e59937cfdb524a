"""ComputeContext: mesh construction and the line that names the
backend a verb got."""

from __future__ import annotations

import jax
import pytest

from predictionio_tpu.parallel.mesh import ComputeContext, describe_devices


class TestDescribeDevices:
    def test_names_platform_kind_count_and_jax(self):
        line = describe_devices(jax.devices())
        first = jax.devices()[0]
        assert f"platform={first.platform}" in line
        assert f'device_kind="{first.device_kind}"' in line
        assert f"devices={len(jax.devices())}" in line
        assert f"jax={jax.__version__}" in line

    def test_counts_the_devices_given(self):
        assert "devices=2" in describe_devices(jax.devices()[:2])


class TestMeshShapes:
    def test_default_mesh_all_data(self):
        ctx = ComputeContext.create(batch="t")
        assert ctx.data_parallelism == len(jax.devices())
        assert ctx.model_parallelism == 1

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError, match="does not cover"):
            ComputeContext.create(mesh_shape=(3, 5))
