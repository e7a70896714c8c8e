"""Tests of the benchmark's own arithmetic; run with
``python -m pytest bench/tests`` from the root of the repository.  They run
on the CPU."""
import json
import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
DATA = BENCH / "tests" / "data"


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="session")
def tiny_config() -> dict:
    return json.loads((DATA / "tiny.json").read_text())


@pytest.fixture(scope="session")
def tiny_coll(tiny_config):
    from lib import collection
    coll = collection.make(tiny_config, 123456789012)
    coll.index()
    return coll
