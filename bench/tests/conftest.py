"""Harness tests on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

``tiny_bench`` copies the benchmark into a temporary directory and cuts
its configurations to a few thousand keys there; every lookup still goes
through ``PlexService`` and the real harness, only the device check is
skipped (the CPU is no chip).
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_KEYS = 60_000
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def write_bench(root: pathlib.Path, n_keys: int = TINY_KEYS,
                keys_per_request: int = 8192,
                data: dict | None = None) -> pathlib.Path:
    """A copy of the benchmark under ``root`` with ``n_keys`` per
    configuration, ``data`` changed in its data parameters, and
    ``keys_per_request`` per request (8192: two whole blocks); returns its
    bench directory."""
    bench = root / "bench"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", ".cache", ".run", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg["data"].update(data or {}, recordcount=n_keys)
        path.write_text(json.dumps(cfg))
    for path in (bench / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix["keys_per_request"] = keys_per_request
        path.write_text(json.dumps(mix))
    return bench


@pytest.fixture
def tiny_bench(tmp_path):
    from harness.spec import Bench
    bench_dir = write_bench(tmp_path)
    return Bench(tmp_path, bench_dir)
