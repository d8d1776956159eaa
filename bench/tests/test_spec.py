"""Configurations, mixes and metrics are found by name from files, and a
new one is added with new files and entries only."""
import json
import time

import jax
import pytest

from harness import cell_run
from harness.spec import Bench

NEW_METRIC = '''"""Test metric: the window's requests."""


def read(rec):
    return rec["requests"]
'''

HALF_ABSENT = '''"""Half of the keys present, half one past a present key."""
import numpy as np


def make(mix, keys, rng):
    def draw(n):
        q = keys[rng.integers(0, keys.size, n)].copy()
        q[: n // 2] += np.uint64(1)
        return q
    return draw
'''

INSERT_LOOP = '''"""Each step inserts a few new keys, then looks up a batch."""
import time

import numpy as np

from harness.traffic import Op, Window


def warm(svc, mix, draw):
    svc.lookup(draw(int(mix["keys_per_request"])))


def run(svc, mix, draw, seconds, span):
    n, ops = int(mix["keys_per_request"]), []
    t0 = time.perf_counter()
    while time.perf_counter() < t0 + seconds:
        new = draw(4) + np.uint64(3)
        ops.append(Op("insert", new, np.array([svc.insert(new)])))
        op = Op("lookup", draw(n))
        op.answer = np.asarray(svc.lookup(op.args))
        ops.append(op)
    return Window(ops, [], time.perf_counter() - t0)
'''

REFERENCE_RW = '''"""Lower bound over the logical key set that inserts grow."""
import numpy as np


def expected(keys, ops):
    logical, out = keys, []
    for op in ops:
        if op.kind == "insert":
            logical = np.sort(np.concatenate([logical, op.args]))
            out.append(np.array([op.args.size]))
        else:
            out.append(np.searchsorted(logical, op.args).astype(np.int64))
    return out


def control(keys):
    raise NotImplementedError
'''


def test_every_named_piece_has_its_file():
    bench = Bench()
    spec = bench.spec
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        bench.module("datasets", cell.config["generator"])
        bench.module("loops", cell.traffic["loop"])
        bench.module("draws", cell.traffic["distribution"])
        ref = bench.reference(cell.config)
        assert callable(ref.expected) and callable(ref.control)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_metrics_follow_the_cells_lists():
    bench = Bench()
    cell = bench.cell("ycsb200M-sosd-lookup")
    names = {m["name"] for m in cell.metrics(trace=False)}
    assert names == {"lookups_per_s", "setup_s"}
    traced = {m["name"] for m in cell.metrics(trace=True)}
    assert "pipeline_ns_per_lookup.batch" in traced
    assert "lookups_per_s" not in traced


def test_unknown_names_fail(tiny_bench):
    with pytest.raises(KeyError):
        tiny_bench.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        tiny_bench.reader("no_such_metric")


def add(bench, files: dict, config: dict, mix: dict, entries: dict) -> Bench:
    """Files and entries added to a copy, as a later PR adds them."""
    root, bd = bench.root, bench.bench_dir
    for rel, text in files.items():
        (bd / rel).write_text(text)
    base = json.loads((bd / "configs" / "ycsb-hashed-200M.json").read_text())
    data = dict(base["data"], **config.pop("data", {}))
    base.update(config, data=data)
    (bd / "configs" / f"{base['name']}.json").write_text(json.dumps(base))
    (bd / "traffic" / f"{mix.pop('name')}.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": base["name"], "source": "test",
                            "file": f"bench/configs/{base['name']}.json",
                            "reduced": [], "why": "test"})
    for section, items in entries.items():
        spec[section] += items
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Bench(root, bd)


def run_line(bench, name):
    cell = bench.cell(name)
    rec, checked = cell_run.measure(bench, cell, 5, 0.3, False,
                                    time.perf_counter(), jax.devices())
    return rec, checked, cell_run.result(bench, cell, rec, checked, False)


def test_added_config_draw_and_metric_run(tiny_bench):
    """A configuration, a mix with a draw of its own and a metric, added
    as files, run through the unchanged harness."""
    bench = add(
        tiny_bench,
        {"draws/half-absent.py": HALF_ABSENT,
         "metrics/requests_seen.py": NEW_METRIC},
        {"name": "ycsb-ordered-tiny", "data": {"insertorder": "ordered",
                                               "insertstart": 10**12}},
        {"name": "half-absent", "loop": "closed", "keys_per_request": 1024,
         "distribution": "half-absent"},
        {"workloads": [{"name": "ordered-half-absent",
                        "config": "ycsb-ordered-tiny",
                        "traffic": "half-absent", "chips": 1,
                        "why": "test"}],
         "end_to_end": [{"name": "requests_seen", "unit": "requests",
                         "better": "higher", "bound": 0.05,
                         "source": "host_clock",
                         "workloads": ["ordered-half-absent"]}]})
    rec, checked, line = run_line(bench, "ordered-half-absent")
    assert line["correct"], line
    assert line["metrics"]["requests_seen"]["value"] == rec["requests"] > 0


def test_added_write_mix_runs(tiny_bench):
    """A mix with a kind of operation no existing file knows (inserts),
    its loop and its reference, added as files: the reference replays the
    window's inserts, and a lookup is checked against the key set as it
    stood then."""
    bench = add(
        tiny_bench,
        {"loops/insert-lookup.py": INSERT_LOOP,
         "references/lower_bound_rw.py": REFERENCE_RW},
        {"name": "ycsb-hashed-rw-tiny", "reference": "lower_bound_rw"},
        {"name": "insert-lookup", "loop": "insert-lookup",
         "keys_per_request": 512, "distribution": "uniform"},
        {"workloads": [{"name": "rw", "config": "ycsb-hashed-rw-tiny",
                        "traffic": "insert-lookup", "chips": 1,
                        "why": "test"}]})
    rec, checked, line = run_line(bench, "rw")
    assert line["correct"], line
    assert rec["requests"] >= 2 and checked["checked"] > 0
