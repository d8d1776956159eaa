"""Parallel sharded build: bit-identity, streaming, transports, faults.

The tentpole contract (ISSUE 8): ``Snapshot.build(..., workers=N)`` and the
streamed durable ``build_generation`` produce results **bit-identical** to
the serial build — same shard planes, same tuning decisions, same persisted
snapshot bytes (modulo the wall-clock ``build_s`` header field), same
lookup results — with the key array shared into the workers by memmap or
fork, or shipped one shard per task under spawn, never whole."""
from __future__ import annotations

import os
import pathlib

import numpy as np
import pytest

from repro.core.index import Snapshot, shard_offsets
from repro.core.parallel_build import (build_generation, build_shard_plexes,
                                       iter_built_shards, spans_of)
from repro.core.plex import BuildStats
from repro.persist.format import load_snapshot, save_snapshot
from repro.resilience.faults import (FAULTS, POINT_BUILD_SHARD, fail_once,
                                     injected)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def _keys(n: int = 200_000, seed: int = 3) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.integers(0, 2**62, n, dtype=np.uint64))


def _layer_arr(px):
    return px.layer.table if hasattr(px.layer, "table") else px.layer.cells


def _snap_lookup(snap: Snapshot, q: np.ndarray) -> np.ndarray:
    """Routed host lookup over a snapshot (global indices)."""
    sid = snap.route(q)
    out = np.empty(q.size, dtype=np.int64)
    for s in np.unique(sid):
        m = sid == s
        out[m] = snap.shards[s].lookup(q[m], backend="numpy") \
            + int(snap.offsets[s])
    return out


def assert_snapshots_identical(a: Snapshot, b: Snapshot) -> None:
    assert np.array_equal(a.keys, b.keys)
    assert np.array_equal(a.offsets, b.offsets)
    assert a.n_shards == b.n_shards
    for x, y in zip(a.shards, b.shards):
        px, py = x.plex, y.plex
        assert (px.tuning.kind, px.tuning.r, px.tuning.delta) == \
            (py.tuning.kind, py.tuning.r, py.tuning.delta)
        assert np.array_equal(px.spline.keys, py.spline.keys)
        assert np.array_equal(px.spline.positions, py.spline.positions)
        assert np.array_equal(_layer_arr(px), _layer_arr(py))


@pytest.mark.parametrize("pool", ["process", "thread"])
def test_parallel_build_bit_identical(pool):
    keys = _keys()
    serial = Snapshot.build(keys.copy(), 64, n_shards=5)
    par = Snapshot.build(keys.copy(), 64, n_shards=5, workers=3, pool=pool)
    assert_snapshots_identical(serial, par)
    q = keys[::311]
    assert np.array_equal(_snap_lookup(par, q),
                          np.searchsorted(keys, q, "left"))


def test_parallel_build_persisted_bytes_identical(tmp_path):
    keys = _keys(120_000)
    serial = Snapshot.build(keys.copy(), 32, n_shards=4)
    par = Snapshot.build(keys.copy(), 32, n_shards=4, workers=2)
    # build_s is wall-clock metadata embedded in the snapshot header —
    # never index content — so it is equalised before the byte comparison
    par.build_s = serial.build_s
    save_snapshot(tmp_path / "a", serial, fsync=False)
    save_snapshot(tmp_path / "b", par, fsync=False)
    assert (tmp_path / "a/snapshot.plex").read_bytes() == \
        (tmp_path / "b/snapshot.plex").read_bytes(), "persisted bytes differ"


def test_build_stats_aggregate_on_snapshot():
    keys = _keys(80_000)
    snap = Snapshot.build(keys.copy(), 64, n_shards=3, workers=2)
    st = snap.build_stats
    assert isinstance(st, BuildStats)
    per_shard = [s.plex.stats for s in snap.shards]
    assert st.total_s == pytest.approx(sum(p.total_s for p in per_shard))
    assert st.spline_s == pytest.approx(sum(p.spline_s for p in per_shard))
    assert st.tune_s == pytest.approx(sum(p.tune_s for p in per_shard))
    assert st.layer_s == pytest.approx(sum(p.layer_s for p in per_shard))
    # phases partition (approximately) the per-shard total
    assert st.spline_s + st.tune_s + st.layer_s == pytest.approx(
        st.total_s, rel=0.05)


def test_iter_built_shards_yields_in_order():
    keys = _keys(100_000)
    offsets = shard_offsets(keys, 4)
    got = list(iter_built_shards(keys, offsets, 64, workers=3))
    assert [s for s, _ in got] == [0, 1, 2, 3]
    spans = spans_of(offsets, keys.size)
    for (s, px), (lo, hi) in zip(got, spans):
        # the parent re-attaches its own keys view, same as the serial path
        assert px.keys.size == hi - lo
        assert np.shares_memory(px.keys, keys)


def test_memmap_keys_transport(tmp_path):
    keys = _keys(90_000)
    raw = tmp_path / "keys.bin"
    keys.tofile(raw)
    km = np.memmap(raw, dtype=np.uint64, mode="r")
    offsets = shard_offsets(np.asarray(km), 3)
    par = build_shard_plexes(np.asarray(km), offsets, 64, workers=2)
    ser = build_shard_plexes(keys, offsets, 64, workers=1)
    for a, b in zip(par, ser):
        assert np.array_equal(a.spline.keys, b.spline.keys)
        assert np.array_equal(a.spline.positions, b.spline.positions)


def test_build_generation_round_trip(tmp_path):
    keys = _keys(150_000)
    gen_dir = build_generation(tmp_path, keys.copy(), 64, n_shards=4,
                               workers=2, fsync=False)
    snap = load_snapshot(gen_dir, verify=True)
    ref = Snapshot.build(keys.copy(), 64, n_shards=4)
    assert np.array_equal(np.asarray(snap.keys), ref.keys)
    assert np.array_equal(np.asarray(snap.offsets), ref.offsets)
    for x, y in zip(snap.shards, ref.shards):
        assert np.array_equal(np.asarray(x.plex.spline.keys),
                              y.plex.spline.keys)
        assert np.array_equal(np.asarray(x.plex.spline.positions),
                              y.plex.spline.positions)
    q = keys[::173]
    assert np.array_equal(_snap_lookup(snap, q),
                          np.searchsorted(keys, q, "left"))


def test_build_generation_servable_by_open(tmp_path):
    from repro.serving.plex_service import PlexService
    keys = _keys(100_000)
    build_generation(tmp_path, keys.copy(), 64, n_shards=3, workers=2,
                     fsync=False)
    with PlexService.open(tmp_path, backend="numpy",
                          durable=False) as svc:
        q = keys[::97]
        assert np.array_equal(svc.lookup(q),
                              np.searchsorted(keys, q, "left"))


def test_build_generation_increments_generation(tmp_path):
    keys = _keys(40_000)
    g0 = build_generation(tmp_path, keys.copy(), 64, n_shards=2,
                          fsync=False)
    g1 = build_generation(tmp_path, keys.copy(), 64, n_shards=2,
                          fsync=False)
    assert g0.name == "gen-000000" and g1.name == "gen-000001"


def test_build_shard_fault_aborts_cleanly(tmp_path):
    keys = _keys(60_000)
    with injected(POINT_BUILD_SHARD, fail_once(shard=1)):
        with pytest.raises(Exception):
            build_generation(tmp_path, keys.copy(), 64, n_shards=3,
                             fsync=False)
    assert FAULTS.trips(POINT_BUILD_SHARD) == 1
    # the aborted build swept its temp file and committed nothing
    assert not list(pathlib.Path(tmp_path).glob("gen-*"))
    assert not list(pathlib.Path(tmp_path).glob("**/*.tmp"))


def test_single_shard_build_honours_devices():
    """Regression for the devices round-robin quirk: a 1-shard build must
    pin its shard to the first explicitly-passed device, not ignore the
    argument."""
    import jax
    dev = jax.devices()[0]
    keys = _keys(20_000)
    snap = Snapshot.build(keys.copy(), 64, n_shards=1, devices=[dev])
    assert snap.shards[0].device == dev
    multi = Snapshot.build(keys.copy(), 64, n_shards=2, devices=[dev])
    assert all(s.device == dev for s in multi.shards)


def test_workers_exceeding_shards_clamped():
    keys = _keys(30_000)
    par = Snapshot.build(keys.copy(), 64, n_shards=2, workers=16)
    ser = Snapshot.build(keys.copy(), 64, n_shards=2)
    assert_snapshots_identical(ser, par)


def test_invalid_pool_rejected():
    keys = _keys(10_000)
    offsets = shard_offsets(keys, 2)
    with pytest.raises(ValueError, match="pool"):
        build_shard_plexes(keys, offsets, 64, workers=2, pool="fiber")


def test_build_worker_imports_stay_off_jax():
    """A process that holds the chip builds through spawned workers, which
    import only what unpickling their task needs. None of it may import
    jax: a worker that initialised a backend would reach for the parent's
    chip and fail or hang."""
    import subprocess
    import sys

    import repro.core
    src = pathlib.Path(repro.core.__file__).resolve().parents[2]
    code = ("import sys, repro.core.parallel_build; "
            "sys.exit(int('jax' in sys.modules))")
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": str(src)},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_spawn_transport_ships_shard_slices(tmp_path, monkeypatch):
    """Spawned workers share no key array: each task carries its own
    shard's keys, nothing is written outside the process, and the build
    is bit-identical to the serial one."""
    import tempfile

    from repro.core import parallel_build
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", None)   # re-read $TMPDIR
    keys = _keys(50_000)
    desc, _ = parallel_build._keys_descriptor(keys, "spawn")
    assert desc is None
    par = Snapshot.build(keys.copy(), 64, n_shards=3, workers=2,
                         mp_context="spawn")
    assert not list(tmp_path.iterdir())
    assert_snapshots_identical(Snapshot.build(keys.copy(), 64, n_shards=3),
                               par)
