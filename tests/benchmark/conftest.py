"""Fixtures for the benchmark's tests (helpers in ``bench_tiny.py``)."""

import pytest

from bench_tiny import build_tiny_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return build_tiny_root(tmp_path_factory.mktemp("tiny_checkout"))


@pytest.fixture(autouse=True)
def short_warm_up(monkeypatch):
    """A run warms up for 3 s on the chip; a test need not."""
    from benchmark import train_cell

    monkeypatch.setattr(train_cell, "WARMUP_SECONDS", 0.1)
