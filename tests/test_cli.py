"""CLI console tests (reference console/Console.scala command tree),
plus dashboard and admin server REST."""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.cli.main import main
from predictionio_tpu.data import DataMap, Event


@pytest.fixture()
def cli(memory_storage, capsys):
    """Run the CLI against the process-default (memory) storage."""

    def run(*argv):
        code = main(list(argv))
        out = capsys.readouterr()
        return code, out.out, out.err

    return run


class TestAppCommands:
    def test_app_lifecycle(self, cli, memory_storage):
        code, out, _ = cli("app", "new", "myapp", "--description", "d")
        assert code == 0 and "Access Key:" in out
        code, out, _ = cli("app", "list")
        assert "myapp" in out
        code, out, _ = cli("app", "show", "myapp")
        info = json.loads(out)
        assert info["name"] == "myapp" and len(info["accessKeys"]) == 1
        # duplicate rejected
        code, _, err = cli("app", "new", "myapp")
        assert code == 1 and "already exists" in err
        code, out, _ = cli("app", "delete", "myapp")
        assert code == 0
        code, out, _ = cli("app", "list")
        assert "myapp" not in out

    def test_channels(self, cli, memory_storage):
        cli("app", "new", "chapp")
        code, out, _ = cli("app", "channel-new", "chapp", "ch1")
        assert code == 0
        code, out, _ = cli("app", "show", "chapp")
        assert json.loads(out)["channels"][0]["name"] == "ch1"
        code, _, err = cli("app", "channel-new", "chapp", "bad name!")
        assert code == 1
        code, out, _ = cli("app", "channel-delete", "chapp", "ch1")
        assert code == 0

    def test_accesskey(self, cli, memory_storage):
        cli("app", "new", "akapp")
        code, out, _ = cli(
            "accesskey", "new", "akapp", "--events", "view,buy"
        )
        assert code == 0
        key = out.strip().split(": ")[1]
        code, out, _ = cli("accesskey", "list", "akapp")
        assert key in out and "view,buy" in out
        code, out, _ = cli("accesskey", "delete", key)
        assert code == 0

    def test_data_delete(self, cli, memory_storage):
        cli("app", "new", "ddapp")
        app = memory_storage.get_meta_data_apps().get_by_name("ddapp")
        memory_storage.get_events().insert(
            Event(event="view", entity_type="u", entity_id="1"), app.id
        )
        code, _, _ = cli("app", "data-delete", "ddapp")
        assert code == 0
        assert list(memory_storage.get_events().find(app.id)) == []


class TestStatusVersionTemplates:
    def test_version(self, cli):
        code, out, _ = cli("version")
        assert code == 0 and out.strip()

    def test_status_ok(self, cli, memory_storage):
        code, out, _ = cli("status")
        assert code == 0
        assert "all ready to go" in out

    def test_template_list(self, cli):
        code, out, _ = cli("template", "list")
        assert code == 0
        for name in (
            "classification",
            "recommendation",
            "similarproduct",
            "ecommerce",
        ):
            assert name in out


class TestBuildTrainExportImport:
    def _seed(self, cli, storage):
        cli("app", "new", "clfapp")
        app = storage.get_meta_data_apps().get_by_name("clfapp")
        events = storage.get_events()
        rng = np.random.default_rng(0)
        for i in range(30):
            label = i % 2
            base = [8.0, 1.0, 1.0] if label == 0 else [1.0, 1.0, 8.0]
            f = np.clip(np.asarray(base) + rng.poisson(1.0, 3), 0, None)
            events.insert(
                Event(
                    event="$set",
                    entity_type="user",
                    entity_id=f"u{i}",
                    properties=DataMap(
                        {
                            "attr0": float(f[0]),
                            "attr1": float(f[1]),
                            "attr2": float(f[2]),
                            "plan": str(label),
                        }
                    ),
                ),
                app.id,
            )

    def test_build_validates_variant(self, cli, tmp_path):
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "id": "clf-test",
                    "engineFactory": "classification",
                    "datasource": {"params": {"app_name": "clfapp"}},
                    "algorithms": [
                        {"name": "naive", "params": {"lambda_": 0.5}}
                    ],
                }
            )
        )
        code, out, _ = cli("build", "--variant", str(variant))
        assert code == 0 and "OK" in out

    def test_build_registers_manifest_and_unregister(
        self, cli, memory_storage, tmp_path
    ):
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "id": "clf-test",
                    "engineFactory": "classification",
                    "algorithms": [
                        {"name": "naive", "params": {"lambda_": 0.5}}
                    ],
                }
            )
        )
        code, out, _ = cli("build", "--variant", str(variant))
        assert code == 0 and "Registered engine clf-test" in out
        manifests = memory_storage.get_meta_data_engine_manifests()
        all_m = manifests.get_all()
        assert len(all_m) == 1
        m = all_m[0]
        assert m.id == "clf-test"
        assert m.engine_factory == "classification"
        code, out, _ = cli(
            "unregister", "--engine-id", "clf-test",
            "--engine-version", m.version,
        )
        assert code == 0 and manifests.get_all() == []
        code, _, err = cli(
            "unregister", "--engine-id", "clf-test",
            "--engine-version", m.version,
        )
        assert code == 1 and "not registered" in err

    def test_upgrade_migrates_events_between_sources(
        self, cli, tmp_path, monkeypatch
    ):
        from predictionio_tpu.data.storage import Storage, set_storage

        storage = Storage(
            env={
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
                "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "m.sqlite"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            }
        )
        set_storage(storage)
        try:
            code, out, _ = cli("app", "new", "migapp")
            assert code == 0
            app = storage.get_meta_data_apps().get_by_name("migapp")
            events = storage.get_events()
            for i in range(7):
                events.insert(
                    Event(
                        event="view",
                        entity_type="user",
                        entity_id=f"u{i}",
                        target_entity_type="item",
                        target_entity_id="i1",
                    ),
                    app.id,
                )
            code, out, _ = cli(
                "upgrade", "--from", "MEM", "--to", "SQL",
                "--app", "migapp",
            )
            assert code == 0 and "Migrated 7 events" in out
            migrated = list(storage.backend_for_source("SQL").find(app.id))
            assert len(migrated) == 7
            assert {e.entity_id for e in migrated} == {
                f"u{i}" for i in range(7)
            }
        finally:
            set_storage(None)

    def test_build_rejects_bad_params(self, cli, tmp_path):
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "engineFactory": "classification",
                    "algorithms": [
                        {"name": "naive", "params": {"lambdaaa": 0.5}}
                    ],
                }
            )
        )
        with pytest.raises(Exception, match="unknown params"):
            cli("build", "--variant", str(variant))

    def test_train_via_cli_and_variant(self, cli, memory_storage, tmp_path):
        self._seed(cli, memory_storage)
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "id": "clf-cli",
                    "engineFactory": "classification",
                    "datasource": {"params": {"app_name": "clfapp"}},
                }
            )
        )
        code, out, _ = cli("train", "--variant", str(variant))
        assert code == 0 and "Training completed" in out
        insts = memory_storage.get_meta_data_engine_instances().get_all()
        assert insts[0].engine_id == "clf-cli"
        assert insts[0].status == "COMPLETED"

    def test_export_import_roundtrip(self, cli, memory_storage, tmp_path):
        self._seed(cli, memory_storage)
        out_file = tmp_path / "events.jsonl"
        code, out, _ = cli(
            "export", "--appname", "clfapp", "--output", str(out_file)
        )
        assert code == 0 and "Exported 30" in out
        cli("app", "new", "copyapp")
        code, out, _ = cli(
            "import",
            "--appname",
            "copyapp",
            "--input",
            str(out_file),
        )
        assert code == 0 and "Imported 30" in out
        app = memory_storage.get_meta_data_apps().get_by_name("copyapp")
        assert len(list(memory_storage.get_events().find(app.id))) == 30

    def test_export_import_npz_roundtrip(
        self, cli, memory_storage, tmp_path
    ):
        """Columnar format (the reference's parquet analogue,
        EventsToFile.scala:40-104): full-fidelity export → import."""
        self._seed(cli, memory_storage)
        out_file = tmp_path / "events.npz"
        code, out, _ = cli(
            "export", "--appname", "clfapp", "--output", str(out_file)
        )
        assert code == 0 and "Exported 30" in out
        cli("app", "new", "npzapp")
        code, out, _ = cli(
            "import", "--appname", "npzapp", "--input", str(out_file)
        )
        assert code == 0 and "Imported 30" in out
        src = memory_storage.get_meta_data_apps().get_by_name("clfapp")
        dst = memory_storage.get_meta_data_apps().get_by_name("npzapp")
        events = memory_storage.get_events()
        orig = list(events.find(src.id))
        copy = list(events.find(dst.id))
        # exact fidelity: every field except the backend-assigned id
        strip = lambda e: (  # noqa: E731
            e.event, e.entity_type, e.entity_id, e.target_entity_type,
            e.target_entity_id, e.properties.to_dict(), e.event_time,
            e.tags, e.pr_id, e.creation_time,
        )
        assert sorted(map(strip, copy)) == sorted(map(strip, orig))

    def test_eventfile_rejects_foreign_npz(self, tmp_path):
        import numpy as _np

        from predictionio_tpu.data.eventfile import read_events_npz

        bad = tmp_path / "other.npz"
        _np.savez(bad, x=_np.arange(3))
        with pytest.raises(ValueError, match="not an event export"):
            list(read_events_npz(str(bad)))


def _call(url, method="GET", body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            ct = resp.headers.get("Content-Type", "")
            raw = resp.read()
            return resp.status, (
                json.loads(raw) if "json" in ct else raw.decode()
            )
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


class TestAdminServer:
    def test_app_rest(self, memory_storage):
        from predictionio_tpu.serving.admin import create_admin_server

        http = create_admin_server(
            host="127.0.0.1", port=0, storage=memory_storage
        )
        http.start()
        base = f"http://127.0.0.1:{http.port}"
        try:
            assert _call(f"{base}/")[1] == {"status": "alive"}
            status, body = _call(
                f"{base}/cmd/app", "POST", {"name": "adminapp"}
            )
            assert status == 201 and body["accessKey"]
            status, body = _call(f"{base}/cmd/app")
            assert [a["name"] for a in body] == ["adminapp"]
            # duplicate → 409
            status, _ = _call(
                f"{base}/cmd/app", "POST", {"name": "adminapp"}
            )
            assert status == 409
            status, _ = _call(f"{base}/cmd/app/adminapp/data", "DELETE")
            assert status == 200
            status, _ = _call(f"{base}/cmd/app/adminapp", "DELETE")
            assert status == 200
            status, _ = _call(f"{base}/cmd/app/nope", "DELETE")
            assert status == 404
        finally:
            http.shutdown()


class TestDashboard:
    def test_lists_completed_evaluations(self, memory_storage):
        import datetime as dt

        from predictionio_tpu.data.storage import EvaluationInstance
        from predictionio_tpu.serving.dashboard import create_dashboard

        memory_storage.get_meta_data_evaluation_instances().insert(
            EvaluationInstance(
                id="eval1",
                status="EVALCOMPLETED",
                start_time=dt.datetime.now(dt.timezone.utc),
                end_time=dt.datetime.now(dt.timezone.utc),
                evaluation_class="MyEval",
                evaluator_results="[Metric] best: 0.9",
                evaluator_results_html="<table><tr><td>0.9</td></tr></table>",
                evaluator_results_json='{"bestScore": 0.9}',
            )
        )
        http = create_dashboard(
            host="127.0.0.1", port=0, storage=memory_storage
        )
        http.start()
        base = f"http://127.0.0.1:{http.port}"
        try:
            status, body = _call(f"{base}/")
            assert status == 200
            assert "MyEval" in body and "eval1"[:8] in body
            status, body = _call(f"{base}/engine_instances/eval1")
            assert status == 200 and "0.9" in body
            status, _ = _call(f"{base}/engine_instances/nope")
            assert status == 404
        finally:
            http.shutdown()


class TestTemplateAndRun:
    def test_template_list(self, cli):
        code, out, _ = cli("template", "list")
        assert code == 0
        assert "classification" in out and "recommendation" in out

    def test_help_verb(self, cli):
        """Reference Console has an explicit `help` verb besides -h."""
        code, out, _ = cli("help")
        assert code == 0
        for verb in ("train", "deploy", "eventserver", "template"):
            assert verb in out

    def test_shell_verb_runs_piped_commands(self):
        """`pio-tpu shell` preloads storage/ctx/event_store; EOF on
        stdin exits cleanly (the bin/pio-shell analogue)."""
        import subprocess
        import sys

        env = dict(os.environ)
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        })
        out = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.cli.main", "shell"],
            input="print('CTX-AXES', sorted(ctx.mesh.axis_names))\n",
            env=env, capture_output=True, text=True, timeout=180,
        )
        assert out.returncode == 0, out.stderr
        assert "CTX-AXES ['data', 'model']" in out.stdout
        assert "preloaded: storage" in out.stderr + out.stdout

    def test_template_get(self, cli, tmp_path):
        dst = str(tmp_path / "myengine")
        code, out, _ = cli(
            "template", "get", "classification", dst,
            "--engine-id", "my-classifier",
        )
        assert code == 0
        variant = json.loads((tmp_path / "myengine" / "engine.json").read_text())
        assert variant["id"] == "my-classifier"

    def test_template_get_missing(self, cli, tmp_path):
        code, _, err = cli(
            "template", "get", "no-such-template", str(tmp_path / "x")
        )
        assert code == 1 and "not found" in err

    def test_template_get_nonempty_dest(self, cli, tmp_path):
        (tmp_path / "occupied").mkdir()
        (tmp_path / "occupied" / "f").write_text("x")
        code, _, err = cli(
            "template", "get", "classification", str(tmp_path / "occupied")
        )
        assert code == 1 and "empty directory" in err

    @staticmethod
    def _make_git_repo(tmp_path, tag: str = "") -> str:
        """A local git repo playing the remote gallery (the reference
        fetches GitHub tag tarballs, Template.scala:226-369; offline
        here via file://)."""
        import subprocess

        repo = tmp_path / "gallery-repo"
        (repo / "engines" / "myrec").mkdir(parents=True)
        (repo / "engines" / "myrec" / "engine.json").write_text(
            json.dumps({"id": "default", "engineFactory": "x:y"})
        )
        (repo / "engines" / "myrec" / "engine.py").write_text("# engine\n")
        (repo / "README.md").write_text("gallery\n")

        def git(*argv):
            subprocess.run(
                ["git", "-C", str(repo), *argv],
                check=True, capture_output=True,
                env={
                    **os.environ,
                    "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                    "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
                },
            )

        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "gallery")
        if tag:
            git("tag", tag)
        return f"file://{repo}"

    def test_template_get_from_git_url(self, cli, tmp_path):
        url = self._make_git_repo(tmp_path)
        dst = str(tmp_path / "fetched")
        code, out, _ = cli(
            "template", "get", url, dst,
            "--subdir", "engines/myrec", "--engine-id", "mine",
        )
        assert code == 0
        variant = json.loads(
            (tmp_path / "fetched" / "engine.json").read_text()
        )
        assert variant["id"] == "mine"
        assert (tmp_path / "fetched" / "engine.py").exists()
        # the clone's metadata must not leak into the project
        assert not (tmp_path / "fetched" / ".git").exists()

    def test_template_get_git_whole_repo_and_ref(self, cli, tmp_path):
        url = self._make_git_repo(tmp_path, tag="v1.0")
        dst = str(tmp_path / "whole")
        code, _out, _ = cli("template", "get", url, dst, "--ref", "v1.0")
        assert code == 0
        assert (tmp_path / "whole" / "README.md").exists()

    def test_template_get_git_bad_ref(self, cli, tmp_path):
        url = self._make_git_repo(tmp_path)
        code, _, err = cli(
            "template", "get", url, str(tmp_path / "x"),
            "--ref", "no-such-tag",
        )
        assert code == 1 and "cannot fetch" in err

    def test_template_get_git_bad_subdir(self, cli, tmp_path):
        url = self._make_git_repo(tmp_path)
        code, _, err = cli(
            "template", "get", url, str(tmp_path / "x"),
            "--subdir", "engines/nope",
        )
        assert code == 1 and "--subdir" in err

    @pytest.mark.parametrize("subdir", ["../..", "/etc", "engines/../.."])
    def test_template_get_subdir_confined_to_clone(
        self, cli, tmp_path, subdir
    ):
        """An absolute or ../-traversing --subdir must not scaffold
        from the host filesystem."""
        url = self._make_git_repo(tmp_path)
        code, _, err = cli(
            "template", "get", url, str(tmp_path / "x"),
            "--subdir", subdir,
        )
        assert code == 1 and "--subdir" in err
        assert not (tmp_path / "x").exists()

    def test_template_get_ref_rejected_for_local_source(
        self, cli, tmp_path
    ):
        code, _, err = cli(
            "template", "get", "classification", str(tmp_path / "x"),
            "--subdir", "sub",
        )
        assert code == 1 and "git sources" in err

    def test_template_get_symlinks_not_dereferenced(self, cli, tmp_path):
        """A hostile template repo must not exfiltrate host files via
        symlinks: links are preserved as links, never followed."""
        secret = tmp_path / "secret.txt"
        secret.write_text("host-private")
        url = self._make_git_repo(tmp_path)
        repo = tmp_path / "gallery-repo"
        os.symlink(str(secret), repo / "engines" / "myrec" / "leak")
        import subprocess

        subprocess.run(
            ["git", "-C", str(repo), "add", "-A"],
            check=True, capture_output=True,
        )
        subprocess.run(
            ["git", "-C", str(repo), "-c", "user.name=t",
             "-c", "user.email=t@t", "commit", "-qm", "link"],
            check=True, capture_output=True,
        )
        dst = tmp_path / "linked"
        code, _, _ = cli(
            "template", "get", url, str(dst), "--subdir", "engines/myrec",
        )
        assert code == 0
        # the scaffold carries the LINK itself, not a dereferenced copy
        # of whatever it pointed at on the fetching host
        assert os.path.islink(dst / "leak")

    def test_template_get_unreachable_url(self, cli, tmp_path):
        code, _, err = cli(
            "template", "get",
            f"file://{tmp_path}/definitely-missing.git",
            str(tmp_path / "x"),
        )
        assert code == 1 and "cannot fetch" in err

    def test_run(self, cli, tmp_path, monkeypatch):
        (tmp_path / "fakejob.py").write_text(
            "def job(ctx):\n"
            "    return {'devices': ctx.mesh.devices.size}\n"
        )
        monkeypatch.chdir(tmp_path)
        code, out, _ = cli("run", "fakejob:job")
        assert code == 0
        assert json.loads(out)["devices"] >= 1

    def test_run_bad_target(self, cli):
        code, _, err = cli("run", "nocolon")
        assert code == 1 and "module:function" in err


class TestDeployFlags:
    def test_max_batch_zero_rejected(self, cli):
        code, _out, err = cli(
            "deploy", "--variant", "nope.json", "--max-batch", "0"
        )
        assert code != 0 and "max-batch" in err

    def test_pipeline_depth_zero_rejected_with_the_reason(self, cli):
        code, _out, err = cli(
            "deploy", "--variant", "nope.json", "--pipeline-depth", "0"
        )
        assert code != 0 and "--pipeline-depth" in err
        assert "serial batcher" in err and "is gone" in err

    def test_variant_mesh_conf_used_and_recorded(
        self, cli, memory_storage, tmp_path
    ):
        """engine.json meshConf (the reference's embedded sparkConf,
        WorkflowUtils.extractSparkConf:308-327) selects the mesh when
        no --mesh-shape flag is given; the topology lands on the
        EngineInstance record."""
        TestBuildTrainExportImport()._seed(cli, memory_storage)
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "id": "clf-mesh",
                    "engineFactory": "classification",
                    "datasource": {"params": {"app_name": "clfapp"}},
                    "meshConf": {"shape": "4,2", "batch": "from-variant"},
                }
            )
        )
        code, out, _ = cli("train", "--variant", str(variant))
        assert code == 0 and "Training completed" in out
        inst = memory_storage.get_meta_data_engine_instances().get_all()[-1]
        assert inst.mesh_conf["shape"] == "4,2"
        assert inst.mesh_conf["axes"] == "data,model"
        assert inst.mesh_conf["devices"] == "8"
        assert inst.batch == "from-variant"  # meshConf.batch recorded

    def test_bad_mesh_shape_is_clean_cli_error(self, cli, tmp_path):
        variant = tmp_path / "engine.json"
        variant.write_text(
            json.dumps(
                {
                    "id": "clf-bad",
                    "engineFactory": "classification",
                    "meshConf": {"shape": "data,model"},
                }
            )
        )
        with pytest.raises(SystemExit, match="mesh shape"):
            cli("train", "--variant", str(variant))

    def test_negative_max_wait_rejected(self, cli):
        code, _out, err = cli(
            "deploy", "--variant", "nope.json", "--max-wait-ms", "-5"
        )
        assert code != 0 and "max-wait-ms" in err

    def test_tenant_parser_flags(self):
        from predictionio_tpu.cli.main import build_parser

        args = build_parser().parse_args([
            "deploy", "--tenant", "alice=va", "--tenant", "bob=vb",
            "--pool-budget-bytes", "1048576", "--quantize", "int8",
        ])
        assert args.tenant == ["alice=va", "bob=vb"]
        assert args.pool_budget_bytes == 1048576
        assert args.quantize == "int8"

    def test_tenant_bad_spec_rejected(self, cli):
        code, _out, err = cli(
            "deploy", "--variant", "nope.json", "--tenant", "noequals"
        )
        assert code != 0 and "NAME=VARIANT" in err

    def test_tenant_canary_mutually_exclusive(self, cli):
        code, _out, err = cli(
            "deploy", "--variant", "nope.json",
            "--tenant", "alice=va", "--canary",
        )
        assert code != 0 and "mutually exclusive" in err


class TestFleetCLI:
    def test_router_parser_fleet_flags(self):
        from predictionio_tpu.cli.main import build_parser

        args = build_parser().parse_args([
            "router", "--state-file", "/tmp/fleet.json", "--fleet-gate",
            "--spawn-replica",
            "python tests/fleet_replica_child.py --port {port} "
            "--generation {generation}",
            "--min-replicas", "2", "--max-replicas", "5",
            "--state-max-age", "120",
        ])
        assert args.state_file == "/tmp/fleet.json"
        assert args.fleet_gate
        assert "{port}" in args.spawn_replica
        assert args.min_replicas == 2 and args.max_replicas == 5
        assert args.state_max_age == 120.0

    def test_trainer_parser_router_flags(self):
        from predictionio_tpu.cli.main import build_parser

        args = build_parser().parse_args([
            "trainer", "--app", "a",
            "--router-url", "http://router:8100",
            "--router-key", "k", "--promote-timeout", "42",
        ])
        assert args.router_url == "http://router:8100"
        assert args.router_key == "k"
        assert args.promote_timeout == 42.0

    def test_status_router_url_prints_fleet_summary(self, cli):
        from predictionio_tpu.obs import MetricRegistry
        from predictionio_tpu.serving.router import ServingRouter

        router = ServingRouter(
            probe_interval_s=999.0, registry=MetricRegistry()
        )
        router.add_replica(
            "http://127.0.0.1:9001", replica_id="a", generation="g1"
        )
        http = router.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            code, out, _ = cli(
                "status", "--router-url",
                f"http://127.0.0.1:{http.port}",
            )
            assert code == 0
            assert "fleet: replicas=1" in out
            assert "generation=g1" in out
            assert "swap=none" in out
            # the metrics scrape rides along (router gauges visible)
            assert "pio_router_replica_healthy" in out
        finally:
            router.close()
            http.shutdown()

    def test_status_router_url_rejects_non_router(self, cli):
        code, _out, err = cli(
            "status", "--router-url", "http://127.0.0.1:1"
        )
        assert code == 1 and "ERROR" in err


class TestObservabilityCLI:
    """ISSUE 16: the fleet-health status line and the profile verb."""

    def test_fleet_health_line_formats(self):
        from predictionio_tpu.cli.main import _fleet_health_line

        line = _fleet_health_line(
            {
                "goodputQps": 12.5,
                "burnRate": 0.8,
                "replicas": {
                    "b": {"stale": True, "residentBytes": 3 * 2**20},
                    "a": {
                        "stale": False,
                        "hbmUsedBytes": 600.0,
                        "hbmLimitBytes": 1000.0,
                        "hbmHeadroomBytes": 400.0,
                    },
                },
            }
        )
        assert line.startswith("health: goodput=12.5qps burn=0.8")
        assert "a[hbmFree=400B]" in line
        assert "b[rss=3.00MiB stale]" in line
        assert _fleet_health_line(None) is None

    def test_status_router_url_prints_health_and_federation(self, cli):
        from predictionio_tpu.obs import MetricRegistry
        from predictionio_tpu.serving.router import ServingRouter

        router = ServingRouter(
            probe_interval_s=999.0, registry=MetricRegistry()
        )
        http = router.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            code, out, _ = cli(
                "status", "--router-url",
                f"http://127.0.0.1:{http.port}",
            )
            assert code == 0
            assert "health: goodput=" in out
            assert "burn=" in out
            # the metrics scrape prints the federated shape
            assert "federation: replicas=none" in out
            assert "pio_slo_burn_rate" in out
        finally:
            router.close()
            http.shutdown()

    def test_profile_parser_flags(self):
        from predictionio_tpu.cli.main import build_parser

        args = build_parser().parse_args([
            "profile", "--url", "http://h:8000", "--out", "prof",
            "--duration-ms", "2500", "--access-key", "k",
        ])
        assert args.url == "http://h:8000"
        assert args.out == "prof"
        assert args.duration_ms == 2500.0
        assert args.access_key == "k"
        assert args.func.__name__ == "cmd_profile"

    @pytest.fixture()
    def profile_server(self):
        """A /debug/profile-shaped endpoint answering a tiny bundle."""
        import base64
        import io
        import tarfile

        from predictionio_tpu.serving.http import (
            HTTPServer,
            Response,
            Router,
        )

        manifest = {
            "id": "abc123",
            "durationS": 0.25,
            "files": ["manifest.json", "spans.json"],
        }
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            for name, payload in (
                ("manifest.json", json.dumps(manifest)),
                ("spans.json", '{"traceEvents": []}'),
            ):
                data = payload.encode()
                info = tarfile.TarInfo(f"profile-abc123/{name}")
                info.size = len(data)
                tar.addfile(info, io.BytesIO(data))
        bundle = base64.b64encode(buf.getvalue()).decode()
        seen = {}

        def handler(request):
            seen["body"] = json.loads(request.body)
            seen["key"] = request.headers.get("X-PIO-Server-Key")
            return Response(
                200, {"profile": manifest, "bundle": bundle}
            )

        router = Router()
        router.route("POST", "/debug/profile", handler)
        http = HTTPServer(router, host="127.0.0.1", port=0)
        http.start()
        yield f"http://127.0.0.1:{http.port}", seen
        http.shutdown()

    def test_profile_pulls_and_extracts_bundle(
        self, cli, profile_server, tmp_path
    ):
        base, seen = profile_server
        out = tmp_path / "prof"
        code, stdout, _ = cli(
            "profile", "--url", base, "--out", str(out),
            "--duration-ms", "250", "--access-key", "sekrit",
        )
        assert code == 0
        assert seen["body"] == {"durationMs": 250.0}
        assert seen["key"] == "sekrit"
        assert "Wrote profile artifact abc123" in stdout
        extracted = out / "profile-abc123"
        assert json.loads((extracted / "manifest.json").read_text())[
            "id"
        ] == "abc123"
        assert (extracted / "spans.json").exists()

    def test_profile_rejects_non_bundle_payload(self, cli):
        from predictionio_tpu.serving.http import (
            HTTPServer,
            Response,
            Router,
        )

        router = Router()
        router.route(
            "POST", "/debug/profile", lambda r: Response(200, {})
        )
        http = HTTPServer(router, host="127.0.0.1", port=0)
        http.start()
        try:
            code, _, err = cli(
                "profile", "--url",
                f"http://127.0.0.1:{http.port}", "--out", "prof",
            )
            assert code == 1
            assert "did not answer a profile bundle" in err
        finally:
            http.shutdown()

    def test_safe_extract_rejects_traversal(self, tmp_path):
        import io
        import tarfile

        from predictionio_tpu.cli.main import _safe_extract

        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            data = b"evil"
            info = tarfile.TarInfo("../escaped.txt")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
        buf.seek(0)
        dest = tmp_path / "out"
        dest.mkdir()
        with tarfile.open(fileobj=buf, mode="r:gz") as tar:
            with pytest.raises((ValueError, tarfile.TarError)):
                _safe_extract(tar, str(dest))
        assert not (tmp_path / "escaped.txt").exists()


class TestPoolCLI:
    """ISSUE 17: the multi-tenant model-pool status line."""

    def test_pool_summary_line_formats(self):
        from predictionio_tpu.cli.main import _pool_summary_line

        line = _pool_summary_line(
            {
                "pio_pool_budget_bytes": {
                    "samples": [{"labels": {}, "value": 20000}]
                },
                "pio_pool_tenants_resident": {
                    "samples": [{"labels": {}, "value": 1}]
                },
                "pio_pool_resident_bytes": {
                    "samples": [
                        {"labels": {"tenant": "alice"}, "value": 16384}
                    ]
                },
                "pio_pool_hits_total": {
                    "samples": [
                        {"labels": {"tenant": "alice"}, "value": 7},
                        {"labels": {"tenant": "bob"}, "value": 0},
                    ]
                },
                "pio_pool_misses_total": {
                    "samples": [
                        {"labels": {"tenant": "alice"}, "value": 1}
                    ]
                },
                "pio_pool_evictions_total": {
                    "samples": [
                        {"labels": {"tenant": "bob"}, "value": 19}
                    ]
                },
            }
        )
        assert line == (
            "pool: tenantsResident=1 bytes=16384/20000 "
            "hitRate=0.88 evictions=19"
        )
        # no pool series scraped → no line (single-tenant server)
        assert _pool_summary_line({}) is None
        # a pool with no lookups yet omits the hit rate
        cold = _pool_summary_line(
            {
                "pio_pool_budget_bytes": {
                    "samples": [{"labels": {}, "value": 100}]
                }
            }
        )
        assert cold == "pool: tenantsResident=0 bytes=0/100 evictions=0"

    def test_status_metrics_url_prints_pool_line(self, cli):
        import sys as _sys

        _sys.path.insert(
            0, str(__import__("pathlib").Path(__file__).parent)
        )
        from pool_replica_child import build_replica

        from predictionio_tpu.obs import MetricRegistry

        server = build_replica(
            "gcli", budget_bytes=200_000, warmup=False,
            registry=MetricRegistry(),
        )
        http = server.serve(host="127.0.0.1", port=0)
        http.start()
        try:
            code, out, _ = cli(
                "status", "--metrics-url",
                f"http://127.0.0.1:{http.port}",
            )
            assert code == 0
            assert "pool: tenantsResident=" in out
            assert "pio_pool_budget_bytes" in out
        finally:
            server.close()
            # build_replica hands the server an externally-owned pool;
            # close it here or its loader + batcher threads outlive us
            server._pool.close()
            http.shutdown()
