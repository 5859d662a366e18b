"""Fixtures of the benchmark's CPU tests: a temporary copy of the benchmark
(``BENCHMARK.json`` + ``benchmark/``) that a test extends with NEW files
only, and a driver that runs one cell of it in this process the way the
command does, minus the look for a chip (``require_tpu=False`` is reachable
from Python alone: no flag, no environment variable)."""

import os
import shutil

import pytest

from bench_helpers import REPO, TINY_RANK, BenchCopy


@pytest.fixture
def bench_copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return BenchCopy(root)


@pytest.fixture(params=["committed", "fourth_cell"])
def manifest(request):
    """The ``Manifest`` a manifest-level test of the cells reads: the
    committed ``BENCHMARK.json``, then a copy with a tiny fourth cell
    appended to ``configs``, ``workloads`` and every metric's list, as the
    next cell will be.  A ranking cell, so that it may stand in every list:
    a test that finds a cell by its place in a list, or holds a list to
    the cells it has today, fails on the copy."""
    from benchmark.manifest import Manifest

    if request.param == "committed":
        return Manifest(REPO)
    copy = request.getfixturevalue("bench_copy")
    copy.add_cell("tiny-fourth", TINY_RANK, "train-window-rank", like="every")
    return Manifest(str(copy.root))
