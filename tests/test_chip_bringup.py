"""What the chip bring-up added, checked on the CPU: where the compile
cache goes, one process per chip at deploy, the line that names the
device, no silent fallback in ``bench.py`` / ``chip_smoke.py``, and the
failures that used to be logged below WARNING."""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

from predictionio_tpu.cli import main as cli_main  # noqa: E402
from predictionio_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture()
def cache_config():
    """Restore JAX's global cache settings after a test changes them."""
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_max_size",
    )
    saved = [getattr(jax.config, name) for name in names]
    yield
    for name, value in zip(names, saved):
        jax.config.update(name, value)


class TestCompileCachePlacement:
    def test_env_dir_is_respected_and_none_set_in_code(
        self, monkeypatch, tmp_path, cache_config
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        # JAX read the variable itself at import; the function must not
        # point the cache anywhere in code
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
        # nor bound a directory the environment owns
        assert jax.config.jax_compilation_cache_max_size == -1

    def test_unset_uses_the_fixed_path_inside_the_checkout(
        self, monkeypatch, cache_config
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = compile_cache.configure_compile_cache()
        assert got == os.path.join(_REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # every trivial jit is persisted (no floor), so the directory
        # the program chose is the one it bounds
        assert (
            jax.config.jax_compilation_cache_max_size
            == compile_cache.DEFAULT_CACHE_MAX_BYTES
        )

    def test_fixed_path_has_no_tempdir_pid_or_time_in_it(self):
        # the same string in another process, at another time
        out = subprocess.run(
            [
                sys.executable, "-c",
                "from predictionio_tpu.utils.compile_cache import "
                "DEFAULT_CACHE_DIR; print(DEFAULT_CACHE_DIR)",
            ],
            capture_output=True, text=True, timeout=60, cwd=_REPO,
            env={**os.environ, "PYTHONPATH": _REPO},
        )
        assert out.stdout.strip() == compile_cache.DEFAULT_CACHE_DIR
        assert str(os.getpid()) not in compile_cache.DEFAULT_CACHE_DIR
        import tempfile

        assert not compile_cache.DEFAULT_CACHE_DIR.startswith(
            tempfile.gettempdir()
        )

    def test_warmup_sized_compiles_are_cached(self, cache_config):
        compile_cache.configure_compile_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_entry_count(self, tmp_path):
        assert compile_cache.cache_entry_count(str(tmp_path / "no")) == 0
        (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
        (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
        assert compile_cache.cache_entry_count(str(tmp_path)) == 1

    def test_gitignore_lists_the_cache_dir(self):
        with open(os.path.join(_REPO, ".gitignore")) as f:
            ignored = f.read().split()
        assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


class _Refused(Exception):
    pass


def _deploy_args(workers: int):
    parser = cli_main.build_parser()
    return parser.parse_args(
        ["deploy", "--engine", "classification", "--workers", str(workers)]
    )


def _fake_ctx(platform: str):
    device = types.SimpleNamespace(
        platform=platform, device_kind=f"fake {platform}"
    )
    devices = np.empty((1, 1), dtype=object)
    devices[0, 0] = device
    return types.SimpleNamespace(mesh=types.SimpleNamespace(devices=devices))


class TestOneProcessPerChip:
    @pytest.fixture()
    def no_server(self, monkeypatch):
        """An EngineServer that refuses to be built: the verb must
        decide before staging a model."""
        from predictionio_tpu.serving import engine_server

        def build(*a, **kw):
            raise _Refused("EngineServer was built")

        monkeypatch.setattr(engine_server, "EngineServer", build)

    def test_workers_2_refused_off_cpu(
        self, monkeypatch, no_server, capsys
    ):
        monkeypatch.setattr(
            cli_main, "_mesh_ctx", lambda *a, **kw: _fake_ctx("tpu")
        )
        rc = cli_main.cmd_deploy(_deploy_args(2))
        captured = capsys.readouterr()
        assert rc == 1
        assert "--workers 2 needs the cpu backend" in captured.err
        assert "one process owns an accelerator" in captured.err
        assert 'Compute: platform=tpu device_kind="fake tpu"' in captured.out

    def test_workers_2_accepted_on_cpu(self, monkeypatch, no_server):
        monkeypatch.setattr(
            cli_main, "_mesh_ctx", lambda *a, **kw: _fake_ctx("cpu")
        )
        # gets as far as building the server
        with pytest.raises(_Refused):
            cli_main.cmd_deploy(_deploy_args(2))

    def test_single_worker_never_refused(self, monkeypatch, no_server):
        monkeypatch.setattr(
            cli_main, "_mesh_ctx", lambda *a, **kw: _fake_ctx("tpu")
        )
        with pytest.raises(_Refused):
            cli_main.cmd_deploy(_deploy_args(1))

    def test_trainer_supervisor_modules_stay_off_jax(self):
        """The supervising `pio-tpu trainer` parent imports these and
        nothing that touches a backend; its child takes the chip."""
        out = subprocess.run(
            [
                sys.executable, "-c",
                "import sys\n"
                "import predictionio_tpu.cli.main\n"
                "import predictionio_tpu.serving.workers\n"
                "import predictionio_tpu.data.storage\n"
                "print('jax' in sys.modules)",
            ],
            capture_output=True, text=True, timeout=60, cwd=_REPO,
            env={**os.environ, "PYTHONPATH": _REPO},
        )
        assert out.stdout.strip() == "False", out.stderr


class TestComputeLine:
    def test_train_prints_the_device_it_got(
        self, memory_storage, capsys, tmp_path
    ):
        from tests.test_cli import TestBuildTrainExportImport

        def cli(*argv):
            code = cli_main.main(list(argv))
            out = capsys.readouterr()
            return code, out.out, out.err

        TestBuildTrainExportImport()._seed(cli, memory_storage)
        variant = tmp_path / "engine.json"
        variant.write_text(json.dumps({
            "id": "clf-line", "engineFactory": "classification",
            "datasource": {"params": {"app_name": "clfapp"}},
        }))
        code, out, _ = cli("train", "--variant", str(variant))
        assert code == 0
        assert (
            f'Compute: platform=cpu device_kind="cpu" devices=8 '
            f"jax={jax.__version__} mesh=8x1"
        ) in out

    def test_line_carries_the_mesh(self, capsys):
        from predictionio_tpu.parallel.mesh import ComputeContext

        cli_main._print_compute_line(
            ComputeContext.create(mesh_shape=(4, 2))
        )
        assert "devices=8" in capsys.readouterr().out.split("mesh=4x2")[0]

    def test_status_names_the_device_kind(self, memory_storage, capsys):
        assert cli_main.main(["status"]) == 0
        out = capsys.readouterr().out
        assert 'Compute: platform=cpu device_kind="cpu" devices=8' in out

    def test_status_fails_cleanly_when_the_chip_is_taken(
        self, memory_storage, capsys, monkeypatch
    ):
        def taken():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", taken)
        assert cli_main.main(["status"]) == 1
        out = capsys.readouterr().out
        assert "Unable to initialize backend 'tpu'" in out
        assert "Compute status: FAILED" in out


class TestNoSilentFallback:
    def test_bench_exits_nonzero_on_the_cpu(self):
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py")],
            capture_output=True, text=True, timeout=120, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode != 0
        assert "no accelerator" in out.stderr
        assert out.stdout.strip() == ""  # no number under any name

    def test_smoke_exits_nonzero_without_a_tpu(self):
        t0 = time.monotonic()
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=60, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert time.monotonic() - t0 < 30
        assert out.returncode != 0
        assert "no TPU" in out.stderr
        assert out.stdout.strip() == ""

    def test_smoke_alone_in_a_directory_fails(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("PYTHONPATH", "JAX_PLATFORMS")
        }
        out = subprocess.run(
            [sys.executable, "chip_smoke.py"],
            capture_output=True, text=True, timeout=60, cwd=tmp_path,
            env=env,
        )
        assert out.returncode != 0
        assert "the repo is not here" in out.stderr
        assert out.stdout.strip() == ""

    def test_smoke_refuses_a_child_on_the_wrong_platform(self):
        import argparse

        args = argparse.Namespace(
            rehearse_cpu=False, log_dir="", mesh_shape=[],
            save_factors="", compare_factors="",
        )
        smoke = chip_smoke.Smoke(args)
        try:
            with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
                smoke.note_device(
                    "train",
                    'Compute: platform=cpu device_kind="cpu" devices=1 '
                    "jax=0.9.0 mesh=1x1\n",
                )
            with pytest.raises(chip_smoke.SmokeFailure, match="Compute:"):
                smoke.note_device("train", "Training completed.\n")
        finally:
            smoke.close()

    def test_smoke_events_cover_every_user_and_item(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        n = chip_smoke.write_events(str(path), users=50, items=40, events=60)
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        assert len(rows) == n >= 60
        assert {r["entityId"] for r in rows} == {f"u{i}" for i in range(50)}
        assert {r["targetEntityId"] for r in rows} == {
            f"i{i}" for i in range(40)
        }
        assert all(1 <= r["properties"]["rating"] <= 5 for r in rows)

    def test_smoke_factor_check_sees_the_user_matrix(self):
        """Item factors are hundreds of times larger than user factors;
        the comparison is per matrix, so a lost user matrix fails."""
        import numpy as np

        rng = np.random.default_rng(0)
        ref_u = rng.normal(size=(200, 8)).astype(np.float32) * 0.05
        ref_i = rng.normal(size=(50, 8)).astype(np.float32) * 20.0
        near = chip_smoke.factor_difference(
            ref_u * 1.001, ref_i * 0.999, ref_u, ref_i
        )
        assert near["user"]["p99"] < chip_smoke.FACTOR_TOL
        assert near["item"]["p99"] < chip_smoke.FACTOR_TOL
        lost = chip_smoke.factor_difference(
            np.zeros_like(ref_u), ref_i, ref_u, ref_i
        )
        assert lost["user"]["p99"] > chip_smoke.FACTOR_TOL
        assert lost["item"]["worst"] == 0.0

    def test_smoke_collects_failed_checks_for_the_end(self, capsys):
        import argparse

        smoke = chip_smoke.Smoke(argparse.Namespace(
            rehearse_cpu=True, log_dir="", mesh_shape=[],
            save_factors="", compare_factors="",
        ))
        try:
            def bad():
                raise chip_smoke.SmokeFailure("first check")

            smoke.collect(bad)
            smoke.collect(lambda: None)
            smoke.devices["status"] = {
                "platform": "cpu", "kind": "cpu", "count": 1,
                "jax": "x", "mesh": None,
            }
            with pytest.raises(chip_smoke.SmokeFailure, match="first check"):
                smoke.summary()
        finally:
            smoke.close()

    def test_smoke_rehearsal_runs_the_whole_sequence(self, tmp_path):
        """app new → import → train → model checks + fused program →
        deploy twice → queries vs the numpy reference → kernels
        (interpreted), tiny, on the CPU; the output says so."""
        cache = tmp_path / "cache"
        in_checkout = compile_cache.cache_entry_count(
            compile_cache.DEFAULT_CACHE_DIR
        )
        out = subprocess.run(
            [
                sys.executable, os.path.join(_REPO, "chip_smoke.py"),
                "--rehearse-cpu", "--log-dir", str(tmp_path / "logs"),
            ],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache)},
        )
        assert out.returncode == 0, out.stderr + out.stdout[-2000:]
        assert "platform: cpu" in out.stdout
        result = json.loads(out.stdout.strip().splitlines()[-1])
        # the driver's contract: exactly these keys, nothing beside them
        assert result == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        }
        assert "rehearsal: True" in out.stdout
        for phase in ("packer: native", "fused program", "SIGTERM -> exit 0",
                      "kernel int8+mask"):
            assert phase in out.stdout, phase
        # the cache went where the environment said, and nowhere else
        assert compile_cache.cache_entry_count(str(cache)) > 0
        assert compile_cache.cache_entry_count(
            compile_cache.DEFAULT_CACHE_DIR
        ) == in_checkout


class TestFailuresAreLoud:
    def test_failed_native_packer_build_is_a_warning(
        self, monkeypatch, caplog
    ):
        from predictionio_tpu.ops import als
        from predictionio_tpu.utils import native

        def broken(name):
            raise RuntimeError(
                "building libalspack.so failed:\ng++: fatal error: boom"
            )

        monkeypatch.setattr(native, "load_native_lib", broken)
        monkeypatch.setattr(als, "_ALSPACK_TRIED", False)
        monkeypatch.setattr(als, "_ALSPACK_LIB", None)
        monkeypatch.delenv("PIO_NO_NATIVE", raising=False)
        with caplog.at_level(logging.WARNING, logger=als.__name__):
            assert als._load_alspack() is None
        (record,) = [
            r for r in caplog.records if "ALS packer" in r.getMessage()
        ]
        assert record.levelno == logging.WARNING
        assert "numpy fallback" in record.getMessage()
        assert "g++: fatal error: boom" in record.getMessage()

    def test_failed_warmup_bucket_is_a_warning_with_the_type(
        self, memory_storage, caplog
    ):
        from predictionio_tpu.core import Engine
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.obs import MetricRegistry
        from predictionio_tpu.parallel.mesh import ComputeContext
        from predictionio_tpu.serving.engine_server import EngineServer
        from tests.test_engine_server import (
            DictQueryAlgorithm,
            DictServing,
            FakeDataSource,
            FakePreparator,
            _params,
        )

        class BrokenCompile(DictQueryAlgorithm):
            def batch_predict(self, model, queries):
                raise FloatingPointError("mosaic said no")

        ctx = ComputeContext.create(batch="t")
        engine = Engine(
            FakeDataSource, FakePreparator, BrokenCompile, DictServing
        )
        run_train(engine, _params(), engine_id="srv-warn", ctx=ctx,
                  storage=memory_storage)
        registry = MetricRegistry()
        with caplog.at_level(logging.INFO):
            es = EngineServer(
                engine, _params(), engine_id="srv-warn",
                storage=memory_storage, ctx=ctx, warmup=True, max_batch=2,
                registry=registry,
            )
        try:
            failed = [
                r for r in caplog.records
                if "warmup FAILED" in r.getMessage()
            ]
            assert failed and all(
                r.levelno == logging.WARNING for r in failed
            )
            assert "FloatingPointError" in failed[0].getMessage()
            data = registry.to_dict()
            assert data["pio_warmup_complete"]["samples"][0]["value"] == 0
        finally:
            es.close()

    def test_sync_waits_on_pytrees_and_passes_non_arrays(self):
        import jax.numpy as jnp

        from predictionio_tpu.utils import profiling

        profiling.sync([{"a": jnp.ones(3) * 2}, "text", 3, None])
        profiling.sync((jnp.zeros((0,)), jnp.ones(())))
