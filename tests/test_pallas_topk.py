"""Fused Pallas top-k kernel vs the XLA reference path.

Runs the kernel in interpreter mode (tests force the CPU backend,
tests/conftest.py) — the driver's real-chip bench exercises the compiled
Mosaic path."""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from predictionio_tpu.ops import similarity
from predictionio_tpu.ops.pallas_topk import fused_top_k_dot
from predictionio_tpu.ops.similarity import _top_k_dot_xla, top_k_dot


def _random(b, i, k, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, k)), dtype=jnp.float32)
    items = jnp.asarray(rng.standard_normal((i, k)), dtype=jnp.float32)
    return q, items


def _check_against_xla(q, items, num, mask=None):
    ps, pi = fused_top_k_dot(q, items, num, mask=mask, interpret=True)
    xs, xi = _top_k_dot_xla(q, items, num, mask=mask)
    np.testing.assert_allclose(
        np.asarray(ps), np.asarray(xs), rtol=1e-5, atol=1e-5
    )
    # indices must agree wherever scores are distinct; verify the picked
    # items really score what the kernel claims (robust to near-ties)
    full = np.asarray(q) @ np.asarray(items).T
    if mask is not None:
        full = np.where(np.asarray(mask), -np.inf, full)
    gathered = np.take_along_axis(full, np.asarray(pi), axis=1)
    np.testing.assert_allclose(
        gathered, np.asarray(ps), rtol=1e-5, atol=1e-5
    )
    # descending order per row
    assert (np.diff(np.asarray(ps), axis=1) <= 1e-6).all()
    # no duplicate picks per row
    for row in np.asarray(pi):
        assert len(set(row.tolist())) == len(row)


class TestFusedTopK:
    def test_matches_xla_single_block(self):
        q, items = _random(8, 100, 16)
        _check_against_xla(q, items, 10)

    def test_matches_xla_multi_block(self):
        q, items = _random(4, 1000, 8)
        ps, pi = fused_top_k_dot(
            q, items, 7, block=256, interpret=True
        )
        xs, xi = _top_k_dot_xla(q, items, 7)
        np.testing.assert_allclose(
            np.asarray(ps), np.asarray(xs), rtol=1e-5, atol=1e-5
        )
        assert (np.asarray(pi) == np.asarray(xi)).mean() > 0.95

    def test_padding_never_selected(self):
        # 130 items force padding to 256; padded rows must not appear
        q, items = _random(3, 130, 4, seed=1)
        ps, pi = fused_top_k_dot(q, items, 130, block=256, interpret=True)
        assert int(np.asarray(pi).max()) < 130
        assert int(np.asarray(pi).min()) >= 0

    def test_mask_excludes(self):
        q, items = _random(5, 300, 8, seed=2)
        mask = np.zeros((5, 300), dtype=bool)
        mask[:, :250] = True  # only items 250..299 allowed
        ps, pi = fused_top_k_dot(
            q, items, 5, mask=jnp.asarray(mask), block=128, interpret=True
        )
        assert (np.asarray(pi) >= 250).all()
        _check_against_xla(q, items, 5, mask=jnp.asarray(mask))

    def test_num_larger_than_items(self):
        q, items = _random(2, 6, 4, seed=3)
        ps, pi = fused_top_k_dot(q, items, 10, interpret=True)
        # clamped to n_items
        assert ps.shape == (2, 6) and pi.shape == (2, 6)
        assert len(set(np.asarray(pi)[0].tolist())) == 6

    def test_single_query_row(self):
        q, items = _random(1, 400, 8, seed=4)
        _check_against_xla(q, items, 3)

    def test_ragged_tail_merges_without_pad(self):
        # 1000 items, block 256 → 3 full blocks + 232-item tail epilogue
        q, items = _random(4, 1000, 8, seed=5)
        ps, pi = fused_top_k_dot(q, items, 9, block=256, interpret=True)
        xs, xi = _top_k_dot_xla(q, items, 9)
        np.testing.assert_allclose(
            np.asarray(ps), np.asarray(xs), rtol=1e-5, atol=1e-5
        )
        assert (np.asarray(pi) == np.asarray(xi)).mean() > 0.95

    def test_nan_scores_excluded_not_hung(self):
        # a NaN factor row must not hang the merge loop; NaN items are
        # treated as unrankable (excluded)
        q, items = _random(3, 600, 8, seed=6)
        items = np.array(items)  # writable copy
        items[100] = np.nan
        items[500] = np.nan
        ps, pi = fused_top_k_dot(
            q, jnp.asarray(items), 5, block=256, interpret=True
        )
        pi = np.asarray(pi)
        assert not np.isin(pi, [100, 500]).any()
        assert np.isfinite(np.asarray(ps)).all()


class TestDispatch:
    def test_xla_side_of_the_choice(self, monkeypatch):
        monkeypatch.setattr(similarity, "_use_pallas", lambda *a, **k: False)
        q, items = _random(2, 50, 4)
        s, i = top_k_dot(q, items, 3)
        xs, xi = _top_k_dot_xla(q, items, 3)
        assert (np.asarray(i) == np.asarray(xi)).all()

    def test_kernel_side_runs_the_interpreter_off_tpu(self, monkeypatch):
        # on the CPU backend the kernel's side must route through the
        # Pallas interpreter, not try to compile Mosaic
        monkeypatch.setattr(similarity, "_use_pallas", lambda *a, **k: True)
        q, items = _random(2, 300, 4, seed=7)
        s, i = top_k_dot(q, items, 3)
        xs, xi = _top_k_dot_xla(q, items, 3)
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(xs), rtol=1e-5, atol=1e-5
        )
        assert (np.asarray(i) == np.asarray(xi)).all()

    def test_overmasked_row_contract(self):
        # fewer rankable items than num: score -inf, index still valid
        q, items = _random(2, 40, 4, seed=8)
        mask = np.ones((2, 40), dtype=bool)
        mask[:, :3] = False  # only 3 rankable
        ps, pi = fused_top_k_dot(
            q, items, 5, mask=jnp.asarray(mask), block=128, interpret=True
        )
        ps, pi = np.asarray(ps), np.asarray(pi)
        assert np.isneginf(ps[:, 3:]).all()
        assert (pi >= 0).all() and (pi < 40).all()
        assert np.isfinite(ps[:, :3]).all()
        assert (pi[:, :3] < 3).all()

    def test_cpu_backend_defaults_to_xla(self):
        # conftest forces CPU; the dispatcher must not pick pallas
        from predictionio_tpu.ops.similarity import _use_pallas

        assert jax.default_backend() == "cpu"
        assert not _use_pallas(1024, 1_000_000)


@pytest.mark.tpu
@pytest.mark.skipif(
    not os.environ.get("PIO_TPU_TESTS"),
    reason="real-TPU test: set PIO_TPU_TESTS=1 to run",
)
class TestCompiledMosaicOnTPU:
    """Compiled (non-interpreter) Mosaic kernel vs the XLA path on real
    hardware, in the variants and at the shapes serving hands it: f32
    and int8+scale items, with and without an [I] phantom mask, B=256
    and B=8 at rank 32, I=524,288 (the dispatcher's threshold) and a
    ragged tail; then f32 at layouts that are neither powers of two nor
    multiples of 128 (odd batch and num, rank 16, block 512). The
    shapes and checks live in ``chip_smoke.py``'s kernel child, which this runs on the TPU (the test process itself
    pins CPU). A chip that cannot be had is a failure, not a skip."""

    def test_compiled_matches_xla(self):
        import json
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            k: v for k, v in os.environ.items() if k != "XLA_FLAGS"
        }
        env["JAX_PLATFORMS"] = "tpu"
        env["PYTHONPATH"] = repo
        out = subprocess.run(
            [
                sys.executable, os.path.join(repo, "chip_smoke.py"),
                "--child", "kernels",
            ],
            env=env, capture_output=True, text=True, timeout=900,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        result = json.loads(out.stdout.strip().splitlines()[-1])
        assert result["device"]["platform"] == "tpu"
        assert result["cases"] == 15 and result["mosaic"] is True
        assert result["dispatcher_takes_kernel"] is True
