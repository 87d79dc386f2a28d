"""Fixtures of the benchmark's tests: a temporary copy of the benchmark at the
`tiny` preset, and a harness pointed at it and at the CPU."""
import json

import pytest

from benchmark.lib import harness
from tests.benchmark import tinyroot


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """One copy a test module: its store and its work directory are shared by
    the module's tests."""
    return tinyroot.make(tmp_path_factory.mktemp("bench"))


@pytest.fixture()
def tiny(tiny_root, monkeypatch):
    """The harness steered as tests/test_chip_smoke.py steers chip_smoke: the
    sizes (through ROOT) and the platform check shrunk from here, no option of
    run.py. JAX's cache stays where tests/conftest.py put it."""
    monkeypatch.setattr(harness, "ROOT", tiny_root)
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    monkeypatch.setattr(harness, "setup_compile_cache", lambda root=None: "tests")
    return tiny_root


def last_line(capsys) -> tuple[dict, list[dict]]:
    """(the result, the lines before it) of a run's standard output."""
    lines = [line for line in capsys.readouterr().out.splitlines() if line]
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]
                                   if x.startswith('{"bench"')]
