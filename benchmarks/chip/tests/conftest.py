"""Fixtures of the benchmark's own tests.

Run from the repository root with ``python -m pytest benchmarks/chip/tests -q``
(the CPU backend; Pallas kernels in interpret mode).  The benchmark's
files are imported by path, as ``run.py`` imports them.
"""
import importlib.util
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parent.parent


def _load(stem: str):
    name = f"chipbench_{stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name,
                                                      CHIP / f"{stem}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="session")
def run():
    return _load("run")


@pytest.fixture(scope="session")
def bench(run):
    return run.load_module


@pytest.fixture(scope="session")
def reference(run):
    return run.bench_module("reference")


@pytest.fixture(scope="session")
def work(run):
    return run.bench_module("work")


@pytest.fixture(scope="session")
def tracemod(run):
    return run.bench_module("trace")
