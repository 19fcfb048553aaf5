"""Fixtures of the benchmark's CPU tests."""

import pytest

from bench_tiny import make_tiny_tree


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_tree(tmp_path_factory.mktemp("bench_tiny"))
