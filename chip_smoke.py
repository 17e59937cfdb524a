#!/usr/bin/env python3
"""Train → deploy → query on the chip, through the CLI.

The quickstart's sequence (``app new`` → ``import`` → ``train`` →
``deploy`` → queries → SIGTERM) for the recommendation template as
``examples/recommendation/engine.json`` ships it (implicit ALS, rank 32,
10 iterations) at 49,152 users × 8,192 items, on interactions from the
seeded generator ``bench.py`` uses (Zipf 1.3 item popularity). Then the
model is checked (finite, no zeroed rows), the fused ALS program is
compiled and run once, the served answers are compared with a numpy
float32 reference over the persisted factors, and the four
``fused_top_k_dot`` variants serving uses are compiled with Mosaic and
compared with XLA.

The parent never imports JAX: a chip belongs to one process at a time,
so every verb runs as a child with ``JAX_PLATFORMS=tpu`` (JAX raises
instead of dropping to the host) and exits before the next one starts.
It exits non-zero, with the reason and no result line, when there is no
TPU or any phase fails. The last stdout line of a passing run is one
JSON object with exactly these keys: ``{"ok": true, "device":
{"platform": ..., "kind": ..., "count": ...}}``, the device as the
children reported it (JAX version, meshes and seconds are on the line
before it).

``--rehearse-cpu`` runs the same sequence at a tiny size on the CPU
backend with the kernels interpreted (its output says ``platform:
cpu``); it is for debugging the script, never a device result.
``--mesh-shape D,M`` is passed through to ``train`` and ``deploy``
(four-chip host: ``4,1`` replicated factors, ``2,2`` model-sharded). It
may be repeated: the events are imported once and train → model checks
→ deploy → queries run for each shape; what need not be repeated for
each mesh — the fused-program build, the second deploy that proves the
compile cache, the ``--workers 2`` refusal and the kernels — runs once,
with the first shape. Checks that fail after ``train`` are collected and
reported together at the end, so one expensive run says everything.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
VARIANT = os.path.join(REPO, "examples", "recommendation", "engine.json")
APP_NAME = "MyRecApp"  # the app engine.json's datasource names

#: full width of the one workload with a chip record (bench.py
#: default). Its 2M interactions took 536 s to import on the chip's host
#: (3.7k events/s into sqlite), most of the 1200 s the smoke may take;
#: 500k keeps users × items × rank, and still fills every item degree
#: bucket (1..16 blocks) and the heavy-row group of build_bucketed.
FULL = {"users": 49_152, "items": 8_192, "events": 500_000}
TINY = {"users": 96, "items": 64, "events": 3_000}
DATA_SEED = 42
QUERY_SEED = 7

#: served top-10 vs numpy float32 reference: how far a served item's
#: reference score may fall short of the reference's 10th-best, relative
#: to it (an f32 matmul at default precision runs as bf16 passes on a
#: TPU, so near-ties may swap)
RANK_TOL = 0.02
#: and how far a served score may sit from the reference score of the
#: same item, relative to Σ|u_k·v_k| of that dot product: rounding both
#: operands to bf16 moves each product by at most 2^-8 of itself. (The
#: head items' factors reach |v| ≈ 20 and cancel, so relative to the
#: score itself a correct bf16 answer is off by several percent.)
SERVED_SCORE_TOL = 2.0 ** -7
#: factors of another mesh (or the other ALS program) vs the reference,
#: for the user and the item matrix each on its own: the 99th percentile
#: over rows of |row - ref row| / |ref row|. (Item factors reach 21 and
#: user factors 0.08, so one bound over both would not see the users.)
#: tests/test_als.py holds sharded vs replicated to rtol 1e-4 / atol
#: 1e-5 in f32. On this data in f32 a 4x1 mesh differs from one device
#: by 0.002 % (reduction order alone); with the chip's bf16 gathers by
#: 0.49 % (users) and 1.00 % (items), because every half-step re-rounds
#: the factors to bf16, which turns a last-bit difference into a 2^-9
#: one (CPU study, PR 21). On the v5e, 2x2 and 4x1 vs one chip: 0.73-
#: 0.89 % (users), 0.97-1.00 % (items). The bound is twice what the
#: study explains.
FACTOR_TOL = 0.02
#: kernel vs XLA (the on-chip test's tolerance; both multiply alike)
KERNEL_TOL = 1e-4
#: (B, I, num) at rank 16 and block 512: odd batch, odd num, a catalog
#: that is no multiple of the block — layouts the serving shapes miss
ODD_KERNEL_SHAPES = [(5, 4000, 7), (3, 1000, 50), (8, 2048, 100)]

TOTAL_BUDGET_S = 1150.0  # the contract allows 1200


class SmokeFailure(Exception):
    """A phase failed; the message is the reason printed at exit."""


# --------------------------------------------------------------------------
# parent: process plumbing (stdlib only — no jax, no numpy import needed
# until the reference check)
# --------------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearsal = args.rehearse_cpu
        self.size = TINY if self.rehearsal else FULL
        # imported here, not at the top: alone in a directory the
        # script must fail with its own message (jax-free module)
        from predictionio_tpu.utils import compile_cache

        self.cache_dir = compile_cache.cache_dir()
        self.cache_entries = lambda: compile_cache.cache_entry_count(
            self.cache_dir
        )
        self.t_start = time.monotonic()
        self.procs: list[subprocess.Popen] = []
        self.phases: dict[str, float] = {}
        self.devices: dict[str, dict] = {}
        self.failures: list[str] = []
        self.meshes: list[str] = args.mesh_shape or [""]
        self.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        self.log_dir = args.log_dir or os.path.join(self.workdir, "logs")
        os.makedirs(self.log_dir, exist_ok=True)
        self.env = self._child_env()

    # -- environment ------------------------------------------------------
    def _child_env(self) -> dict:
        env = {
            k: v for k, v in os.environ.items()
            if not k.startswith("PIO_STORAGE_")
        }
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        env["PIO_FS_BASEDIR"] = os.path.join(self.workdir, "store")
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
            n = math.prod(
                int(x) for x in (self.meshes[0] or "1").split(",")
            )
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", ""),
            )
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}"
            ).strip()
        else:
            # JAX raises when the chip cannot be had, instead of
            # running the verb on the host
            env["JAX_PLATFORMS"] = "tpu"
        return env

    def remaining(self) -> float:
        left = TOTAL_BUDGET_S - (time.monotonic() - self.t_start)
        if left <= 0:
            raise SmokeFailure(
                f"out of time: {TOTAL_BUDGET_S:.0f}s budget spent"
            )
        return left

    # -- children ---------------------------------------------------------
    def _log_paths(self, name: str) -> tuple[str, str]:
        return (
            os.path.join(self.log_dir, f"{name}.out"),
            os.path.join(self.log_dir, f"{name}.err"),
        )

    def run(self, name: str, argv: list[str], expect_rc: int | None = 0):
        """Run one child to completion; returns (rc, stdout, stderr)."""
        out_path, err_path = self._log_paths(name)
        t0 = time.monotonic()
        with open(out_path, "w") as out, open(err_path, "w") as err:
            proc = subprocess.Popen(
                argv, env=self.env, stdout=out, stderr=err, cwd=REPO
            )
            self.procs.append(proc)
            try:
                rc = proc.wait(timeout=self.remaining())
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                raise SmokeFailure(
                    f"{name}: killed at the smoke's time limit"
                    + self._tail(err_path)
                ) from None
        self.phases[name] = round(time.monotonic() - t0, 1)
        stdout, stderr = _read(out_path), _read(err_path)
        if expect_rc is not None and rc != expect_rc:
            raise SmokeFailure(
                f"{name}: exit code {rc}, expected {expect_rc}"
                + self._tail(err_path)
            )
        return rc, stdout, stderr

    def pio(self, name: str, *verb_args: str, **kw):
        return self.run(
            name,
            [sys.executable, "-m", "predictionio_tpu.cli.main", *verb_args],
            **kw,
        )

    def child(self, kind: str, mesh: str, *extra: str):
        """A chip-holding child of this script; returns its last stdout
        line parsed as JSON."""
        name = kind + _tag(mesh)
        argv = [sys.executable, os.path.abspath(__file__), "--child", kind]
        if self.rehearsal:
            argv.append("--rehearse-cpu")
        if mesh:
            argv += ["--mesh-shape", mesh]
        _rc, stdout, _err = self.run(name, argv + list(extra))
        for line in stdout.splitlines():
            if not line.startswith("{"):
                print(f"  [{name}] {line}")
        try:
            return json.loads(stdout.strip().splitlines()[-1])
        except (IndexError, ValueError):
            raise SmokeFailure(
                f"{name}: no JSON result on its last stdout line"
            ) from None

    @staticmethod
    def _tail(path: str, n: int = 25) -> str:
        lines = _read(path).strip().splitlines()[-n:]
        return "\n    " + "\n    ".join(lines) if lines else ""

    def note_device(self, who: str, stdout: str) -> None:
        """Parse a verb's ``Compute:`` line; every child must be on the
        platform this run is for."""
        m = re.search(
            r'^Compute: platform=(\S+) device_kind="([^"]*)" '
            r"devices=(\d+) jax=(\S+)(?: mesh=(\S+))?",
            stdout, re.M,
        )
        if not m:
            raise SmokeFailure(f"{who}: no 'Compute:' line on stdout")
        dev = {
            "platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3)), "jax": m.group(4),
            "mesh": m.group(5),
        }
        want = "cpu" if self.rehearsal else "tpu"
        if dev["platform"] != want:
            raise SmokeFailure(
                f"{who} ran on platform {dev['platform']!r}, not {want!r}"
            )
        self.devices[who] = dev
        print(f"  {who}: platform: {dev['platform']}, device_kind: "
              f"{dev['kind']}, devices: {dev['count']}, jax {dev['jax']}"
              + (f", mesh {dev['mesh']}" if dev["mesh"] else ""))

    # -- the server -------------------------------------------------------
    def start_server(
        self, name: str, mesh: str
    ) -> tuple[subprocess.Popen, int]:
        port = _free_port()
        argv = [
            sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
            "--variant", VARIANT, "--ip", "127.0.0.1", "--port", str(port),
        ]
        if mesh:
            argv += ["--mesh-shape", mesh]
        out_path, err_path = self._log_paths(name)
        with open(out_path, "w") as out, open(err_path, "w") as err:
            proc = subprocess.Popen(
                argv, env=self.env, stdout=out, stderr=err, cwd=REPO
            )
        self.procs.append(proc)
        deadline = time.monotonic() + self.remaining()
        while "listening on" not in _read(out_path):
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"{name}: deploy exited with code {proc.returncode} "
                    "before listening" + self._tail(err_path)
                )
            if time.monotonic() > deadline:
                raise SmokeFailure(f"{name}: never started listening")
            time.sleep(0.2)
        # warm-up must be complete BEFORE the first query
        while True:
            metrics = _http_json(f"http://127.0.0.1:{port}/metrics.json")
            if _gauge(metrics, "pio_warmup_complete") == 1:
                break
            if time.monotonic() > deadline or proc.poll() is not None:
                raise SmokeFailure(
                    f"{name}: pio_warmup_complete never reached 1"
                    + self._tail(err_path)
                )
            time.sleep(0.5)
        return proc, port

    def stop_server(self, name: str, proc: subprocess.Popen) -> None:
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=min(60.0, self.remaining()))
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise SmokeFailure(
                f"{name}: no exit within 60s of SIGTERM"
            ) from None
        if rc != 0:
            raise SmokeFailure(
                f"{name}: exit code {rc} after SIGTERM, expected a clean 0"
                + self._tail(self._log_paths(name)[1])
            )

    def close(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        shutil.rmtree(self.workdir, ignore_errors=True)

    # -- phases -----------------------------------------------------------
    def main(self) -> dict:
        label = "REHEARSAL on the CPU" if self.rehearsal else "on the chip"
        shapes = ", ".join(
            m or "default (all devices on data)" for m in self.meshes
        )
        print(f"chip_smoke ({label}): {self.size['users']} users x "
              f"{self.size['items']} items x rank 32, "
              f"{self.size['events']} events, mesh {shapes}")
        self.build_native()
        self.preflight()
        self.import_events()
        for n, mesh in enumerate(self.meshes):
            self.collect(self.one_mesh, mesh, first=n == 0)
        self.collect(self.refuse_second_worker)
        self.collect(self.kernels)
        if sys.modules.get("jax") is not None:
            self.failures.append("the smoke's parent imported jax")
        return self.summary()

    def collect(self, phase, *a, **kw) -> None:
        """Run a phase; a failed check is kept for the end instead of
        ending the run, so the later phases still report."""
        try:
            phase(*a, **kw)
        except SmokeFailure as e:
            print(f"  FAILED: {e}")
            self.failures.append(str(e))

    def one_mesh(self, mesh: str, first: bool) -> None:
        instance_id = self.train(mesh)
        model = self.check_model(instance_id, mesh, fused=first)
        self.serve_and_query(model, mesh, twice=first)

    def kernels(self) -> None:
        kernels = self.child("kernels", self.meshes[0])
        print(f"  kernels: {kernels['cases']} cases, "
              f"mosaic={kernels['mosaic']}, dispatcher takes the kernel="
              f"{kernels['dispatcher_takes_kernel']}, largest score "
              f"deviation from XLA {kernels['max_abs_dev']:.3g}")

    def build_native(self) -> None:
        """The tool's copy may carry another machine's ``native/*.so``;
        what git would commit has none. Remove them and build through
        the repo's own loader, so a missing ``g++`` fails here."""
        for name in ("alspack", "eventlog"):
            path = os.path.join(REPO, "native", f"libpio_{name}.so")
            if os.path.exists(path):
                os.unlink(path)
        self.run(
            "native",
            [
                sys.executable, "-c",
                "from predictionio_tpu.utils.native import load_native_lib\n"
                "for n in ('alspack', 'eventlog'):\n"
                "    load_native_lib(n)\n",
            ],
        )
        print(f"  native: built libpio_alspack.so, libpio_eventlog.so "
              f"from source ({self.phases['native']}s)")

    def preflight(self) -> None:
        """``pio-tpu status`` first, as the quickstart does: a missing
        chip fails here in seconds, before any data is written."""
        rc, stdout, stderr = self.pio("status", "status", expect_rc=None)
        if rc != 0 or "Compute status: FAILED" in stdout:
            reason = [
                ln for ln in (stdout + stderr).splitlines()
                if "Unable to initialize backend" in ln or "[ERROR]" in ln
            ]
            raise SmokeFailure(
                "no TPU: `pio-tpu status` could not take a tpu device "
                f"(JAX_PLATFORMS=tpu): {reason[-1] if reason else stderr[-300:]}"
            )
        self.note_device("status", stdout)

    def import_events(self) -> None:
        t0 = time.monotonic()
        self.pio("app_new", "app", "new", APP_NAME)
        path = os.path.join(self.workdir, "events.jsonl")
        n = write_events(path, **self.size)
        self.phases["generate"] = round(time.monotonic() - t0, 1)
        _rc, stdout, _err = self.pio(
            "import", "import", "--appname", APP_NAME, "--input", path
        )
        if f"Imported {n} events." not in stdout:
            raise SmokeFailure(f"import: expected {n} events: {stdout!r}")
        os.unlink(path)
        print(f"  import: {n} events in {self.phases['import']}s "
              f"(generated in {self.phases['generate']}s)")

    def train(self, mesh: str) -> str:
        before = self.cache_entries()
        name = "train" + _tag(mesh)
        argv = ["train", "--variant", VARIANT]
        if mesh:
            argv += ["--mesh-shape", mesh]
        _rc, stdout, stderr = self.pio(name, *argv)
        self.note_device(name, stdout)
        m = re.search(r"Training completed\. Engine instance: (\S+)", stdout)
        if not m:
            raise SmokeFailure("train: no 'Training completed' line")
        packer = re.findall(r"ALS packer: (\w+)", stderr)
        if not packer or set(packer) != {"native"}:
            raise SmokeFailure(
                f"train: ALS packer was {packer or 'not reported'}, "
                "expected the native one built from native/alspack.cc"
            )
        print(f"  {name}: {self.phases[name]}s wall, packer: native, "
              f"cache entries {before} -> {self.cache_entries()}")
        for line in stderr.splitlines():
            step = re.search(r"\] ((?:als|train)/\w+: \d+ step.*)$", line)
            if step:
                print(f"    StepTimer {step.group(1)}")
        return m.group(1)

    def check_model(self, instance_id: str, mesh: str, fused: bool) -> dict:
        """Model checks, the sharding checks and (once) the fused
        program, in one child that holds the chip after ``train`` has
        exited."""
        factors_path = os.path.join(
            self.workdir, f"factors{_tag(mesh)}.npz"
        )
        extra = ["--instance-id", instance_id, "--factors-out", factors_path]
        if fused:
            extra.append("--fused")
        if self.args.compare_factors:
            extra += ["--compare-factors", self.args.compare_factors]
        result = self.child("model", mesh, *extra)
        if self.args.save_factors:
            os.makedirs(self.args.save_factors, exist_ok=True)
            shutil.copy(factors_path, self.args.save_factors)
        result["factors_path"] = factors_path
        line = (f"  model{_tag(mesh)}: {result['n_users']} x "
                f"{result['n_items']} x rank {result['rank']}, nnz "
                f"{result['nnz']}")
        lines, problems = [], []
        if (result["n_users"], result["n_items"]) != (
            self.size["users"], self.size["items"]
        ):
            problems.append(
                f"model is {result['n_users']} x {result['n_items']}, "
                f"not {self.size['users']} x {self.size['items']}"
            )
        for key, what in (
            ("fused_vs_persisted", "fused program vs the per-half-step "
                                   "program `pio-tpu train` ran"),
            ("vs_one_chip", "factors vs the one-chip run"),
        ):
            if key not in result:
                continue
            for side, d in result[key].items():
                lines.append(
                    f"    {what}, {side} rows: relative difference median "
                    f"{d['median']:.3%}, p99 {d['p99']:.3%} (tolerance "
                    f"{FACTOR_TOL:.0%}), worst {d['worst']:.3%}"
                )
                if not d["p99"] <= FACTOR_TOL:
                    problems.append(
                        f"{what}: p99 relative difference of the {side} "
                        f"rows {d['p99']:.3%}, tolerance {FACTOR_TOL:.0%}"
                    )
        if "fused_seconds" in result:
            line += f"; fused program {result['fused_seconds']}s"
        print("\n".join([line] + lines))
        self.failures += [f"model{_tag(mesh)}: {p}" for p in problems]
        return result

    def serve_and_query(self, model: dict, mesh: str, twice: bool) -> None:
        tag = _tag(mesh)
        before = self.cache_entries()
        t0 = time.monotonic()
        name = "deploy" + tag
        proc, port = self.start_server(name, mesh)
        self.phases[name + "_to_warm"] = round(time.monotonic() - t0, 1)
        self.note_device(name, _read(self._log_paths(name)[0]))
        after_first = self.cache_entries()
        print(f"  {name}: to warm {self.phases[name + '_to_warm']}s; cache "
              f"{self.cache_dir}: {before} -> {after_first} entries")
        if after_first == 0:
            raise SmokeFailure(f"no compile-cache entry in {self.cache_dir}")
        if twice:
            # a second deploy of the same model must find every warm-up
            # bucket in the cache: SIGTERM, clean exit, start again
            self.stop_server(name, proc)
            t0 = time.monotonic()
            name = "deploy_again" + tag
            proc, port = self.start_server(name, mesh)
            self.phases[name + "_to_warm"] = round(time.monotonic() - t0, 1)
            after_second = self.cache_entries()
            print(f"  {name}: to warm {self.phases[name + '_to_warm']}s; "
                  f"cache: {after_first} -> {after_second} entries")
            if after_second != after_first:
                self.failures.append(
                    f"{name}: the second deploy added "
                    f"{after_second - after_first} cache entries for the "
                    "warm-up buckets; it should have found them all"
                )
        metrics = _http_json(f"http://127.0.0.1:{port}/metrics.json")
        if not self.rehearsal:
            limits = [
                s.get("value") for s in (
                    metrics.get("pio_device_hbm_limit_bytes") or {}
                ).get("samples", [])
            ]
            if not limits or not all(limits):
                self.failures.append(
                    f"{name}: pio_device_hbm_limit_bytes absent or zero "
                    f"in /metrics.json: {limits}"
                )
            print(f"  {name}: pio_device_hbm_limit_bytes "
                  f"{[int(v or 0) for v in limits]}")
        t0 = time.monotonic()
        try:
            report = run_queries(port, model["factors_path"])
        finally:
            self.phases["queries" + tag] = round(time.monotonic() - t0, 1)
            self.stop_server(name, proc)
        print(f"  queries{tag}: {report['single']} single + 1 batch of "
              f"{report['batch']} in {self.phases['queries' + tag]}s, all "
              f"200; top-10 vs numpy f32 for {report['sampled']} users: "
              f"largest shortfall below the reference's 10th-best "
              f"{report['max_rank_dev']:.3%} (tolerance {RANK_TOL:.0%}), "
              f"largest served-score deviation "
              f"{report['max_score_dev']:.3%} of the dot's terms "
              f"(tolerance {SERVED_SCORE_TOL:.3%})")
        print(f"  {name}: SIGTERM -> exit 0")

    def refuse_second_worker(self) -> None:
        """One process per chip: ``deploy --workers 2`` off the CPU
        backend must be refused before any model is staged."""
        if self.rehearsal:
            return  # on cpu it is accepted (tests/test_workers.py)
        rc, stdout, stderr = self.pio(
            "deploy_workers2", "deploy", "--variant", VARIANT,
            "--ip", "127.0.0.1", "--port", str(_free_port()),
            "--workers", "2", expect_rc=None,
        )
        if rc == 0 or "--workers 2 needs the cpu backend" not in stderr:
            raise SmokeFailure(
                f"deploy --workers 2 was not refused (exit {rc})"
            )
        if "listening on" in stdout or "warmup" in stderr:
            raise SmokeFailure("deploy --workers 2 staged a model first")
        print(f"  deploy --workers 2: refused, exit {rc}")

    def summary(self) -> dict:
        total = round(time.monotonic() - self.t_start, 1)
        print("  seconds per phase: " + ", ".join(
            f"{k} {v}" for k, v in self.phases.items()
        ) + f"; total {total}")
        seen = [
            d for who, d in self.devices.items()
            if who.startswith(("train", "deploy"))
        ]
        dev = seen[0] if seen else self.devices["status"]
        for other in seen[1:]:
            if any(other[k] != dev[k] for k in ("platform", "kind", "count")):
                self.failures.append(
                    f"children disagree on the device: {dev} vs {other}"
                )
        if self.failures:
            raise SmokeFailure(
                f"{len(self.failures)} failed check(s):\n  - "
                + "\n  - ".join(self.failures)
            )
        meshes = [
            d["mesh"] for who, d in self.devices.items()
            if who.startswith("train")
        ]
        print(f"  passed: jax {dev['jax']}, mesh "
              f"{', '.join(str(m) for m in meshes)}, "
              f"rehearsal: {self.rehearsal}, {total}s")
        # the contract's result line: these keys and no other
        return {
            "ok": True,
            "device": {
                "platform": dev["platform"],
                "kind": dev["kind"],
                "count": dev["count"],
            },
        }


def _tag(mesh: str) -> str:
    """``"2,2"`` → ``"_2x2"`` (log, phase and file names)."""
    return "_" + mesh.replace(",", "x") if mesh else ""


def _read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_json(url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            if resp.status != 200:
                raise SmokeFailure(f"{url}: HTTP {resp.status}")
            return json.load(resp)
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"{url}: HTTP {e.code}: {e.read()[:300]!r}"
        ) from None
    except (OSError, ValueError) as e:
        raise SmokeFailure(f"{url}: {e}") from None


def _gauge(metrics: dict, name: str):
    samples = (metrics.get(name) or {}).get("samples") or []
    return samples[0].get("value") if samples else None


# --------------------------------------------------------------------------
# data and queries
# --------------------------------------------------------------------------


def write_events(path: str, users: int, items: int, events: int) -> int:
    """``bench.make_data``'s seeded generator (power-law item
    popularity, uniform users, ratings 1..5) written as ``rate`` events.
    Any user or item the draws missed gets one event appended, so the
    model's width is users × items whatever ``events`` is (48 items at
    500k, none at 2M)."""
    import numpy as np

    rng = np.random.default_rng(DATA_SEED)
    cols = rng.zipf(1.3, events) % items
    rows = rng.integers(0, users, events)
    vals = rng.integers(1, 6, events)
    lost_u = np.setdiff1d(np.arange(users), rows)
    lost_i = np.setdiff1d(np.arange(items), cols)
    rows = np.concatenate(
        [rows, lost_u, rng.integers(0, users, len(lost_i))]
    )
    cols = np.concatenate(
        [cols, rng.integers(0, items, len(lost_u)), lost_i]
    )
    vals = np.concatenate([vals, rng.integers(1, 6, len(rows) - len(vals))])
    line = (
        '{"event":"rate","entityType":"user","entityId":"u%d",'
        '"targetEntityType":"item","targetEntityId":"i%d",'
        '"properties":{"rating":%d},'
        '"eventTime":"2026-01-01T00:00:00+00:00"}\n'
    )
    with open(path, "w") as f:
        for r, c, v in zip(rows.tolist(), cols.tolist(), vals.tolist()):
            f.write(line % (r, c, v))
    return len(rows)


def run_queries(port: int, factors_path: str) -> dict:
    """A few dozen ``POST /queries.json`` (known and unknown users, two
    ``num``) and one ``POST /batch/queries.json``; every answer is
    checked, and a seeded sample of 32 users is compared by SCORE with
    a numpy float32 dot product over the persisted factors."""
    import numpy as np

    with np.load(factors_path) as z:
        user_f = z["user_factors"].astype(np.float32)
        item_f = z["item_factors"].astype(np.float32)
        user_ids = [str(u) for u in z["user_ids"]]
        item_index = {str(i): n for n, i in enumerate(z["item_ids"])}
    rng = np.random.default_rng(QUERY_SEED)
    sample = rng.choice(len(user_ids), size=min(32, len(user_ids)),
                        replace=False)
    base = f"http://127.0.0.1:{port}"
    max_rank_dev = max_score_dev = 0.0

    def check_known(user_idx: int, num: int, answer: dict, against_ref: bool):
        nonlocal max_rank_dev, max_score_dev
        scores = answer.get("itemScores")
        who = f"user {user_ids[user_idx]} num {num}"
        want = min(num, len(item_index))
        if not isinstance(scores, list) or len(scores) != want:
            raise SmokeFailure(f"{who}: expected {want} items: {answer}")
        values = [s["score"] for s in scores]
        if any(s["item"] not in item_index for s in scores):
            raise SmokeFailure(f"{who}: item not in the catalog: {scores}")
        if not all(np.isfinite(values)):
            raise SmokeFailure(f"{who}: non-finite score: {scores}")
        if any(b > a + 1e-6 for a, b in zip(values, values[1:])):
            raise SmokeFailure(f"{who}: scores not non-increasing")
        if not against_ref:
            return
        ref = item_f @ user_f[user_idx]
        kth = float(np.sort(ref)[-want])
        for s in scores:
            i = item_index[s["item"]]
            max_rank_dev = max(
                max_rank_dev, (kth - float(ref[i])) / max(abs(kth), 1e-12)
            )
            terms = float(np.abs(item_f[i]) @ np.abs(user_f[user_idx]))
            max_score_dev = max(
                max_score_dev,
                abs(s["score"] - float(ref[i])) / max(terms, 1e-12),
            )
        if max_rank_dev > RANK_TOL or max_score_dev > SERVED_SCORE_TOL:
            raise SmokeFailure(
                f"{who}: served top-{want} disagrees with the numpy f32 "
                f"reference: rank shortfall {max_rank_dev:.3%} (tolerance "
                f"{RANK_TOL:.0%}), score deviation {max_score_dev:.3%} of "
                f"the dot's terms (tolerance {SERVED_SCORE_TOL:.3%})"
            )

    single = 0
    for u in sample:
        answer = _http_json(f"{base}/queries.json",
                            {"user": user_ids[u], "num": 10})
        check_known(int(u), 10, answer, against_ref=True)
        single += 1
    for u in sample[:8]:
        answer = _http_json(f"{base}/queries.json",
                            {"user": user_ids[u], "num": 4})
        check_known(int(u), 4, answer, against_ref=False)
        single += 1
    for n in range(4):
        answer = _http_json(f"{base}/queries.json",
                            {"user": f"nobody-{n}", "num": 10})
        if answer != {"itemScores": []}:
            raise SmokeFailure(f"unknown user got {answer}")
        single += 1
    batch = [{"user": user_ids[u], "num": 10} for u in sample[:12]]
    batch += [{"user": "nobody-b", "num": 10}]
    batch += [{"user": user_ids[u], "num": 4} for u in sample[12:15]]
    slots = _http_json(f"{base}/batch/queries.json", batch)
    if len(slots) != len(batch):
        raise SmokeFailure(f"batch: {len(slots)} slots for {len(batch)}")
    for q, slot in zip(batch, slots):
        if slot.get("status") != 200:
            raise SmokeFailure(f"batch slot {q}: {slot}")
        if q["user"].startswith("nobody"):
            if slot["prediction"] != {"itemScores": []}:
                raise SmokeFailure(f"batch unknown user got {slot}")
        else:
            check_known(user_ids.index(q["user"]), q["num"],
                        slot["prediction"], against_ref=q["num"] == 10)
    return {
        "single": single, "batch": len(batch), "sampled": len(sample),
        "max_rank_dev": max_rank_dev, "max_score_dev": max_score_dev,
    }


# --------------------------------------------------------------------------
# children that hold the chip (these import jax)
# --------------------------------------------------------------------------


def _child_ctx(args):
    import jax

    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    shape = (
        tuple(int(x) for x in args.mesh_shape[0].split(","))
        if args.mesh_shape else None
    )
    ctx = ComputeContext.create(batch="chip_smoke", mesh_shape=shape)
    first = jax.devices()[0]
    want = "cpu" if args.rehearse_cpu else "tpu"
    if first.platform != want:
        raise SystemExit(f"child is on {first.platform!r}, not {want!r}")
    device = {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
    }
    return ctx, device


def _shard_report(what: str, arr, mesh_devices, expect_rows: int) -> str:
    """Every device of the mesh must hold a shard of ``arr`` with
    ``expect_rows`` rows."""
    held = {s.device: s.data.shape[0] for s in arr.addressable_shards}
    missing = [d for d in mesh_devices if d not in held]
    if missing:
        raise SystemExit(f"{what}: no shard on {missing}")
    if set(held.values()) != {expect_rows}:
        raise SystemExit(
            f"{what}: shard rows {sorted(set(held.values()))}, "
            f"expected {expect_rows} on each of {len(held)} devices"
        )
    return f"{what}: {len(held)} devices x {expect_rows} rows"


def factor_difference(got_u, got_i, ref_u, ref_i) -> dict:
    """Per matrix, |row - ref row| / |ref row| over the rows (see
    ``FACTOR_TOL``)."""
    import numpy as np

    out = {}
    for what, got, ref in (("user", got_u, ref_u), ("item", got_i, ref_i)):
        ref = np.asarray(ref, np.float32)
        rel = np.linalg.norm(got - ref, axis=1) / np.maximum(
            np.linalg.norm(ref, axis=1), 1e-30
        )
        out[what] = {
            "median": float(np.median(rel)),
            "p99": float(np.percentile(rel, 99)),
            "worst": float(rel.max()),
        }
    return out


def child_model(args) -> int:
    import numpy as np

    from predictionio_tpu.core.persistence import (
        deserialize_models,
        load_generation,
    )
    from predictionio_tpu.core.registry import resolve_engine_factory
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.ops import als

    ctx, device = _child_ctx(args)
    with open(VARIANT) as f:
        variant = json.load(f)
    engine = resolve_engine_factory(variant["engineFactory"])()
    params = engine.params_from_variant(variant)

    # the persisted model, exactly what deploy will load
    blob = load_generation(
        get_storage().get_model_data_models(), args.instance_id
    )
    ((_tag, model),) = deserialize_models(blob)
    user_f = np.asarray(model.user_factors)
    item_f = np.asarray(model.item_factors)
    # first of all, what the parent's reference check needs
    np.savez(
        args.factors_out, user_factors=user_f, item_factors=item_f,
        user_ids=model.user_map.keys(), item_ids=model.item_map.keys(),
    )

    # the prepared training data, through the engine's own components
    pd = engine.make_preparator(params).prepare(
        ctx, engine.make_data_source(params).read_training(ctx)
    )
    inter = pd.interactions
    n_users, n_items = inter.n_rows, inter.n_cols
    if user_f.shape[0] != n_users or item_f.shape[0] != n_items:
        raise SystemExit("persisted factors do not match the event data")
    result = {
        "device": device, "n_users": n_users, "n_items": n_items,
        "rank": int(user_f.shape[1]), "nnz": int(inter.nnz),
    }

    # no non-finite factor, and no zeroed row for an entity that has
    # interactions (what _solve's isfinite → 0 would leave behind)
    for what, f, deg in (
        ("user", user_f, np.bincount(inter.rows, minlength=n_users)),
        ("item", item_f, np.bincount(inter.cols, minlength=n_items)),
    ):
        if not np.isfinite(f).all():
            raise SystemExit(f"non-finite {what} factors")
        zeroed = int(((np.abs(f).sum(axis=1) == 0) & (deg > 0)).sum())
        if zeroed:
            raise SystemExit(
                f"{zeroed} all-zero {what} factor rows with interactions"
            )
    print(f"model: finite, no zeroed row among {n_users} users / "
          f"{n_items} items with interactions")

    mesh_devices = list(ctx.mesh.devices.flat)
    sharded = ctx.model_parallelism > 1
    m_par = max(ctx.model_parallelism, 1)
    (algo,) = engine.make_algorithms(params)
    p = algo.params
    if args.fused:
        # the fused multi-epoch program (the path `pio-tpu eval` takes:
        # train_als with no timer), same shapes and parameters
        t0 = time.monotonic()
        fused = als.train_als(
            ctx, inter.rows, inter.cols, inter.values,
            n_users=n_users, n_items=n_items, rank=p.rank,
            iterations=p.num_iterations, reg=p.lambda_, alpha=p.alpha,
            implicit=p.implicit, seed=p.seed, block_len=p.block_len,
            compute_dtype=p.compute_dtype or None, timer=None,
            factor_sharding=p.factor_sharding, return_layout="device",
        )
        fused_u = np.asarray(fused.user_factors)[:n_users]
        fused_i = np.asarray(fused.item_factors)[:n_items]
        result["fused_seconds"] = round(time.monotonic() - t0, 1)
        result["fused_vs_persisted"] = factor_difference(
            fused_u, fused_i, user_f, item_f
        )
        for what, arr in (("trained user factors", fused.user_factors),
                          ("trained item factors", fused.item_factors)):
            print(_shard_report(
                what, arr, mesh_devices, arr.shape[0] // m_par
            ))

    # where the arrays live: every device holds its share
    n_split = ctx.n_devices if sharded else ctx.data_parallelism
    for side, rows, cols, n_rows in (
        ("user", inter.rows, inter.cols, n_users),
        ("item", inter.cols, inter.rows, n_items),
    ):
        packed = als.build_bucketed(
            rows, cols, inter.values, n_rows,
            block_len=p.block_len, row_multiple=n_split,
        )
        if sharded:
            staged_side = als.stage_sharded(
                ctx, packed, als.plan_shards(packed, ctx.n_devices)
            )
            slabs = list(staged_side.slabs)
            heavy = [staged_side.heavy[:3]] if staged_side.heavy else []
        else:
            slabs, heavy = (list(x) for x in als._device_slabs(ctx, packed))
        for idx, _w, _v in slabs + heavy:
            _shard_report(
                f"{side} slab {tuple(idx.shape)}", idx, mesh_devices,
                idx.shape[0] // n_split,
            )
        print(f"{side} slabs {[tuple(s[0].shape) for s in slabs]} and "
              f"heavy groups {[tuple(h[0].shape) for h in heavy]}: rows "
              f"split evenly over all {len(mesh_devices)} devices")
    staged = algo.stage_model(ctx, model)
    for what, arr in (("staged user factors", staged.user_factors),
                      ("staged item factors", staged.item_factors)):
        print(_shard_report(
            what, arr, mesh_devices, arr.shape[0] // m_par
        ))
    print("staged item phantom rows masked: "
          f"{0 if staged.item_phantom_mask is None else int(np.asarray(staged.item_phantom_mask).sum())}")
    if sharded:
        # the small probe the tests use, on the real mesh
        rng = np.random.default_rng(0)
        als.check_factor_sharding(
            ctx, rng.integers(0, 64, 512).astype(np.int32),
            rng.integers(0, 48, 512).astype(np.int32),
            rng.integers(1, 5, 512).astype(np.float32), 64, 48,
        )
        print("check_factor_sharding: factors split over the model axis")

    if args.compare_factors:
        # the one-chip run of the same seeded data; ids line up because
        # both vocabularies are the sorted unique entity ids
        with np.load(args.compare_factors) as z:
            ref_u, ref_i = z["user_factors"], z["item_factors"]
            same_ids = np.array_equal(
                z["user_ids"], model.user_map.keys()
            ) and np.array_equal(z["item_ids"], model.item_map.keys())
        if not same_ids:
            raise SystemExit("--compare-factors: different entity ids")
        result["vs_one_chip"] = factor_difference(
            user_f, item_f, ref_u, ref_i
        )
    print(json.dumps(result))
    return 0


def _kernel_case(b, n_items, rank, num, block, variants, interpret):
    """One shape of ``fused_top_k_dot`` in each of ``variants`` against
    the XLA path; returns the largest score deviation."""
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops import quantize, similarity
    from predictionio_tpu.ops.pallas_topk import fused_top_k_dot

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, rank)).astype(np.float32))
    items = jnp.asarray(rng.normal(size=(n_items, rank)).astype(np.float32))
    qf = quantize.quantize_factors(items, "int8")
    phantom = np.zeros(n_items, bool)
    phantom[-37:] = True  # a sharded catalog's padded tail
    phantom[5] = True
    phantom = jnp.asarray(phantom)
    max_dev = 0.0
    for variant in variants:
        what = f"{variant} B={b} I={n_items} rank={rank} num={num}"
        mask = phantom if "mask" in variant else None
        kmask = similarity._pallas_mask(mask, b)
        if variant.startswith("f32"):
            kargs, kkw = (q, items, num, kmask), {}
            xs, xi = similarity._top_k_dot_xla(q, items, num, mask)
        else:
            kargs, kkw = (q, qf.data, num, kmask), {"scale": qf.scale}
            xs, xi = quantize._top_k_dot_quant_xla(
                q, qf.data, qf.scale, num, mask
            )
        kkw.update(block=block, interpret=interpret)
        if not interpret:
            text = fused_top_k_dot.lower(*kargs, **kkw).as_text()
            if "tpu_custom_call" not in text:
                raise SystemExit(
                    f"{what}: no Mosaic call in the lowered program"
                )
        ps, pi = fused_top_k_dot(*kargs, **kkw)
        ps, pi, xs, xi = (np.asarray(a) for a in (ps, pi, xs, xi))
        if not np.allclose(ps, xs, rtol=KERNEL_TOL, atol=KERNEL_TOL):
            raise SystemExit(
                f"{what}: kernel scores differ from XLA by "
                f"{np.abs(ps - xs).max():.3g}"
            )
        # indices must agree wherever XLA's neighbouring scores are
        # further apart than the tolerance (a near-tie may swap)
        gap = np.abs(np.diff(xs, axis=1))
        tie = np.zeros(xs.shape, bool)
        tie[:, 1:] |= gap <= KERNEL_TOL
        tie[:, :-1] |= gap <= KERNEL_TOL
        if ((pi != xi) & ~tie).any():
            raise SystemExit(
                f"{what}: kernel indices differ from XLA away from any tie"
            )
        max_dev = max(max_dev, float(np.abs(ps - xs).max()))
        print(f"kernel {variant:9s} B={b:<3d} I={n_items} rank={rank} "
              f"num={num}: {'interpreted' if interpret else 'Mosaic'}, "
              f"matches XLA (max |diff| {np.abs(ps - xs).max():.3g}, "
              f"indices equal {float((pi == xi).mean()):.4f})")
    return max_dev


def child_kernels(args) -> int:
    """``fused_top_k_dot`` compiled (Mosaic) in the variants serving
    uses, at the first shape the dispatcher hands it on the chip, each
    against the XLA path; then f32 at layouts that are neither powers
    of two nor multiples of 128."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import quantize, similarity

    _ctx, device = _child_ctx(args)
    interpret = args.rehearse_cpu
    rank, num = 32, 16
    if interpret:
        shapes = [(8, 2048), (4, 2048 + 100)]
    else:
        # B·I·4 = 512 MiB exactly at B = 256 (serving_qps's max_batch)
        shapes = [(256, 524_288), (8, 524_288), (256, 524_288 + 300)]
    serving = ("f32", "f32+mask", "int8", "int8+mask")
    cases = [(b, i, rank, num, 1024, serving) for b, i in shapes]
    cases += [(b, i, 16, n, 512, ("f32",)) for b, i, n in ODD_KERNEL_SHAPES]
    max_dev = max(_kernel_case(*case, interpret) for case in cases)
    n_cases = sum(len(case[-1]) for case in cases)
    takes = None
    if not interpret:
        # the dispatcher itself, no override, at the threshold shape
        b, n_items = shapes[0]
        q = jnp.zeros((b, rank), jnp.float32)
        items = jnp.zeros((n_items, rank), jnp.float32)
        qf = quantize.quantize_factors(items, "int8")
        takes = all(
            "tpu_custom_call"
            in jax.jit(lambda a, t: similarity.top_k_dot(a, t, num))
            .lower(q, table).as_text()
            for table in (items, qf)
        )
        if not takes:
            raise SystemExit(
                f"top_k_dot did not take the kernel at B={b} I={n_items}"
            )
        below = jax.jit(
            lambda a, t: similarity.top_k_dot(a, t, num)
        ).lower(q[: b // 2], items).as_text()
        if "tpu_custom_call" in below:
            raise SystemExit("top_k_dot took the kernel below 512 MiB")
    print(json.dumps({
        "device": device, "cases": n_cases, "mosaic": not interpret,
        "dispatcher_takes_kernel": takes, "max_abs_dev": max_dev,
    }))
    return 0


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh-shape", action="append", default=[],
                    help="data,model device counts, passed to train/deploy; "
                         "repeat to run several meshes over one import")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU backend, kernels interpreted")
    ap.add_argument("--save-factors", default="",
                    help="directory to copy each mesh's persisted factors "
                         "(npz) into")
    ap.add_argument("--compare-factors", default="",
                    help="npz from a one-chip run of the same data")
    ap.add_argument("--log-dir", default="",
                    help="keep the children's output here")
    ap.add_argument("--child", choices=("model", "kernels"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--instance-id", help=argparse.SUPPRESS)
    ap.add_argument("--factors-out", help=argparse.SUPPRESS)
    ap.add_argument("--fused", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "model":
        return child_model(args)
    if args.child == "kernels":
        return child_kernels(args)
    if not os.path.isdir(os.path.join(REPO, "predictionio_tpu")):
        print("chip_smoke: FAILED: the repo is not here — no "
              f"predictionio_tpu/ beside {os.path.abspath(__file__)}",
              file=sys.stderr)
        return 1
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if not args.rehearse_cpu and first == "cpu":
        print("chip_smoke: FAILED: no TPU: JAX_PLATFORMS="
              f"{os.environ['JAX_PLATFORMS']} holds JAX to the host CPU "
              "(--rehearse-cpu runs the sequence there, tiny)",
              file=sys.stderr)
        return 1
    smoke = Smoke(args)
    try:
        result = smoke.main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        smoke.close()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
