"""``correct`` comes out false for the control and for each fault a cell
can have, and true for the program: the harness is driven whole at a tiny
size on the CPU, with the timed path broken underneath."""
import time

import jax
import numpy as np
import pytest

from conftest import CELLS
from harness import cell_run
from harness.traffic import Op, Window
from repro.kernels import jnp_lookup


def line_of(bench, name, seconds=0.3, **kw):
    cell = bench.cell(name)
    rec, checked = cell_run.measure(bench, cell, 2**32 + 17, seconds, False,
                                    time.perf_counter(), jax.devices(), **kw)
    return cell_run.result(bench, cell, rec, checked, False)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(tiny_bench, name):
    line = line_of(tiny_bench, name)
    assert line["correct"], line["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tmp_path, name):
    """YCSB's hashed keys lie on a lattice, and share their high word only
    in some stretches of record numbers: 8.3% of the 200M keys do, none
    of the first 4M, 1108 of the 1M from record 100M on. The test serves
    that slice, where the control answers about one key in a thousand
    wrongly."""
    from conftest import write_bench
    from harness.spec import Bench
    bench = Bench(tmp_path, write_bench(
        tmp_path, n_keys=1_000_000, keys_per_request=65536,
        data={"insertstart": 100_000_000}))
    ref = bench.reference(bench.cell(name).config)
    line = line_of(bench, name, seconds=1.0, make_service=lambda cfg, keys:
                   cell_run.Control(ref.control(keys)))
    assert not line["correct"]
    assert line["compared"]["inexact_answers"]["value"] > 0


def altered(orig):
    """An answer altered where it is produced: lane 0 of every block."""
    def lookup_planes(self, *a, **kw):
        res = orig(self, *a, **kw)
        return res._replace(out=res.out.at[0].add(1))
    return lookup_planes


def half_lanes(orig):
    """Half of every block left out: its upper lanes never computed."""
    def lookup_planes(self, *a, **kw):
        res = orig(self, *a, **kw)
        half = res.out.shape[0] // 2
        return res._replace(out=res.out.at[half:].set(0))
    return lookup_planes


@pytest.mark.parametrize("name,fault", [(c, f) for c in CELLS
                                        for f in (altered, half_lanes)])
def test_device_fault_is_not_correct(tiny_bench, monkeypatch, name, fault):
    orig = jnp_lookup.StackedJnpPlex.lookup_planes
    monkeypatch.setattr(jnp_lookup.StackedJnpPlex, "lookup_planes",
                        fault(orig))
    line = line_of(tiny_bench, name)
    assert not line["correct"], line["compared"]


def test_compare_counts_wrong_and_missing():
    from harness.spec import Bench
    keys = np.arange(0, 1000, 10, dtype=np.uint64)
    win = Window([Op("lookup", np.array([5, 10], np.uint64),
                     np.array([1, 2])),
                  Op("lookup", np.array([999], np.uint64)),
                  Op("lookup", np.array([20, 25], np.uint64),
                     np.array([2]))], [], 1.0)
    ref = Bench().module("references", "lower_bound")
    got = cell_run.compare(ref, keys, win)
    assert got == {"wrong": 1, "missing": 3, "checked": 2}


def test_read_only_reference_refuses_writes():
    from harness.spec import Bench
    ref = Bench().module("references", "lower_bound")
    with pytest.raises(ValueError, match="insert"):
        ref.expected(np.arange(4, dtype=np.uint64),
                     [Op("insert", np.array([7], np.uint64))])
