"""Fixtures of the benchmark's CPU tests."""
import pytest

from bench_tiny import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
