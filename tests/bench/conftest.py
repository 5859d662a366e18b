"""Fixtures of the benchmark's CPU tests: a temporary copy of the benchmark
(``BENCHMARK.json`` + ``benchmark/``) that a test extends with NEW files
only, and a driver that runs one cell of it in this process the way the
command does, minus the look for a chip (``require_tpu=False`` is reachable
from Python alone: no flag, no environment variable)."""

import os
import shutil

import pytest

from bench_helpers import REPO, BenchCopy


@pytest.fixture
def bench_copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return BenchCopy(root)
