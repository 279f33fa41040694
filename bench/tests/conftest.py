"""Fixtures of the harness tests: a tiny checkout, and the compile cache
left off (the command turns it on; a CPU test has no use for it)."""

import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")
