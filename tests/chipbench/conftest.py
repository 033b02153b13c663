"""Shared by the benchmark's CPU tests."""
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def env(monkeypatch):
    """A run sets cache paths in the environment: restore them after."""
    for k in ("REPRO_CACHE", "JAX_COMPILATION_CACHE_DIR"):
        old = os.environ.get(k)
        monkeypatch.setenv(k, old or "unset")
        if old is None:
            monkeypatch.delenv(k)
    monkeypatch.syspath_prepend(os.path.join(ROOT, "src"))
