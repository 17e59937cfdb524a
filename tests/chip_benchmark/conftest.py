"""The ``manifest`` fixture: every test of the manifest's shape (order,
counts, which cell reports what, which reference judges which
configuration) takes ``(bench, root)`` from it and so runs twice, over the
repository and over a copy grown by `made_up.py`'s addition. A test that
holds the end of a list to its own PR's entries passes on the first and
fails on the second: in the PR that writes it, not in the next one."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import made_up  # noqa: E402
import manifest_rules as rules  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="session")
def grown_root(tmp_path_factory) -> str:
    """The grown copy, written once a session; tests only read it."""
    return made_up.grown_copy(ROOT, str(tmp_path_factory.mktemp("grown")))[1]


@pytest.fixture
def servers_built(monkeypatch) -> list:
    """``(pipeline_depth, adaptive_wait)`` of every `EngineServer` built
    while the test runs: what a cell's server got for the two settings its
    configuration leaves to `EngineServer`'s own defaults."""
    from predictionio_tpu.serving.engine_server import EngineServer

    built = []
    init = EngineServer.__init__

    def recording(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append((self._pipeline_depth, self._adaptive_wait))

    monkeypatch.setattr(EngineServer, "__init__", recording)
    return built


@pytest.fixture(params=["tree", "grown"])
def manifest(request):
    """``(bench, root)`` of the repository, then of the grown copy."""
    root = ROOT if request.param == "tree" else request.getfixturevalue("grown_root")
    return rules.load_bench(root), root
