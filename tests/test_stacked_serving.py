"""Stacked single-dispatch serving path: layout unification, shard-boundary
correctness, async submit/drain queue, hot-key cache, probe modes, and the
perf-trajectory diff tool."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BACKENDS, LearnedIndex
from repro.core.cht import build_cht
from repro.core.plex import build_plex
from repro.data import generate
from repro.kernels.jnp_lookup import (JnpPlex, StackedJnpPlex,
                                      default_probe_mode)
from repro.kernels.pairs import join_u64, pair_shr, pair_shr_dyn, split_u64
from repro.kernels.planes import UNSCOPED, build_stacked_planes, stage_of_hlo
from repro.serving import PlexService

from conftest import sorted_u64


def _force_cht(px, r, delta):
    return dataclasses.replace(px, layer=build_cht(px.spline.keys, r, delta))


def _shard_plexes(keys, offs, eps=32, **kw):
    ends = list(offs[1:]) + [keys.size]
    return [build_plex(keys[o:e], eps, **kw) for o, e in zip(offs, ends)]


# ----------------------------------------------------------- pairs.py ----

def test_pair_shr_dyn_matches_static(rng):
    x = rng.integers(0, 1 << 64, 256, dtype=np.uint64)
    h, l = map(jnp.asarray, split_u64(x))
    for s in (0, 1, 17, 31, 32, 33, 57, 63):
        want = join_u64(*map(np.asarray, pair_shr(h, l, s))) & np.uint64(
            0xFFFFFFFF)
        got = np.asarray(pair_shr_dyn(h, l, jnp.full(x.size, s, jnp.int32)))
        assert np.array_equal(got, want.astype(np.uint32)), s
    # mixed per-element shifts
    s = rng.integers(0, 64, x.size)
    want = (x >> s.astype(np.uint64)) & np.uint64(0xFFFFFFFF)
    got = np.asarray(pair_shr_dyn(h, l, jnp.asarray(s, jnp.int32)))
    assert np.array_equal(got, want.astype(np.uint32))


# ------------------------------------------- stacked layout + kernels ----

@pytest.mark.parametrize("probe", ["count", "bisect"])
def test_stacked_radix_multi_shard(probe, rng):
    keys = sorted_u64(rng, 40_000, dups=True)
    offs = np.asarray([0, 10_000, 20_000, 30_000])
    st = StackedJnpPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                    block=512, probe=probe)
    assert st is not None and st.planes.kind == "radix"
    q = keys[rng.integers(0, keys.size, 2_048)]
    assert np.array_equal(st.lookup(q), np.searchsorted(keys, q, "left"))


@pytest.mark.parametrize("probe", ["count", "bisect"])
def test_stacked_cht_unequal_depth_and_delta(probe, rng):
    """Shards share r (the unification gate) but differ in delta and tree
    depth; shallower shards' extra descent rounds must be no-ops."""
    keys = generate("amzn", 40_000)
    offs = np.asarray([0, 10_000, 20_000, 30_000])
    plexes = [_force_cht(px, r=3, delta=8 + 8 * i)
              for i, px in enumerate(_shard_plexes(keys, offs, eps=48))]
    depths = {px.layer.max_depth for px in plexes}
    st = StackedJnpPlex.from_plexes(plexes, offs, block=512, probe=probe)
    assert st is not None and st.planes.kind == "cht"
    assert st.planes.static["levels"] == max(d + 1 for d in depths)
    q = keys[rng.integers(0, keys.size, 2_048)]
    assert np.array_equal(st.lookup(q), np.searchsorted(keys, q, "left"))
    qa = rng.integers(keys[0], keys[-1], 2_048, dtype=np.uint64)
    assert (st.lookup(qa) == np.searchsorted(keys, qa, "left")).mean() > 0.99


def test_stacked_unification_gates(rng):
    keys = sorted_u64(rng, 20_000)
    offs = np.asarray([0, 10_000])
    plexes = _shard_plexes(keys, offs)
    # mixed layer kinds cannot be unified
    mixed = [plexes[0], _force_cht(plexes[1], r=4, delta=16)]
    assert build_stacked_planes(mixed, offs) is None
    # CHT shards with different radix widths cannot be unified
    chts = [_force_cht(plexes[0], r=4, delta=16),
            _force_cht(plexes[1], r=5, delta=16)]
    assert build_stacked_planes(chts, offs) is None
    # same-kind shards unify
    assert build_stacked_planes(plexes, offs) is not None


def test_snapshot_build_unifies_shard_layers(rng, monkeypatch):
    """Shards tuned apart (one CHT among radix tables, as on the 200M-key
    amzn stand-in) are re-tuned over radix tables, so the snapshot serves
    through the one stacked pipeline, exactly."""
    from repro.core import Snapshot, parallel_build
    keys = sorted_u64(rng, 30_000)
    built = []

    def tuned_apart(k, eps, **kw):
        px = build_plex(k, eps, **kw)
        built.append(px)
        return _force_cht(px, r=4, delta=16) if len(built) == 2 else px
    monkeypatch.setattr(parallel_build, "build_plex", tuned_apart)
    snap = Snapshot.build(keys.copy(), 32, n_shards=3)
    assert [type(s.plex.layer).__name__ for s in snap.shards] == \
        ["RadixTable"] * 3
    assert [s.plex.tuning.kind for s in snap.shards] == ["radix"] * 3
    assert np.array_equal(snap.shards[1].plex.spline.keys,
                          built[1].spline.keys)          # spline kept
    st = snap.stacked_impl("jnp", block=512)
    assert st is not None and st.planes.kind == "radix"
    q = np.concatenate([keys[rng.integers(0, keys.size, 2_000)],
                        rng.integers(keys[0], keys[-1], 500,
                                     dtype=np.uint64)])
    assert np.array_equal(st.lookup(q), np.searchsorted(keys, q, "left"))


def test_service_falls_back_when_not_unifiable(rng, monkeypatch):
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=16, n_shards=3, block=512)
    monkeypatch.setattr(svc, "stacked_impl", lambda *a, **k: None)
    q = keys[rng.integers(0, keys.size, 2_000)]
    got = svc.lookup(q, backend="jnp")
    assert np.array_equal(got, np.searchsorted(keys, q, side="left"))


# ---------------------------------------- multi-shard serving contract ----

def test_single_jit_dispatch_per_microbatch(rng):
    """Acceptance: a 4-shard jnp lookup issues exactly one jit dispatch per
    micro-batch — no per-shard Python dispatch."""
    keys = sorted_u64(rng, 40_000)
    svc = PlexService(keys, eps=32, n_shards=4, block=512)
    assert svc.n_shards == 4
    st = svc.stacked_impl()
    assert st is not None
    calls = []
    orig = st._fn
    st._fn = lambda *a: (calls.append(1), orig(*a))[1]
    q = keys[rng.integers(0, keys.size, 3 * 512 + 100)]  # 4 micro-batches
    got = svc.lookup(q, backend="jnp")
    assert np.array_equal(got, np.searchsorted(keys, q, side="left"))
    assert len(calls) == 4
    assert svc.stats.batches == 4
    assert svc.stats.drained_batches == 4
    assert svc.stats.inflight_batches == 0


@pytest.mark.parametrize("kind", ["radix", "cht"])
def test_device_stages_named_in_the_program(kind, rng):
    """Every stage of the stacked pipeline reaches the compiled program
    under its ``plex.*`` scope, and the scoped program answers exactly
    (present and absent keys against ``np.searchsorted``)."""
    keys = sorted_u64(rng, 40_000)
    offs = np.asarray([0, 13_000, 26_000])
    plexes = _shard_plexes(keys, offs)
    if kind == "cht":
        plexes = [_force_cht(px, 6, 3) for px in plexes]
    st = StackedJnpPlex.from_plexes(plexes, offs, block=512)
    assert st is not None and st.planes.kind == kind
    q = np.concatenate([keys[rng.integers(0, keys.size, 1_500)],
                        sorted_u64(rng, 1_500)])
    assert np.array_equal(st.lookup(q), np.searchsorted(keys, q, "left"))
    qh, ql = (jnp.asarray(a) for a in split_u64(q[:512]))
    stages = st.stage_of_ops(qh, ql)
    for stage in ("plex.route", "plex.segment", "plex.probe", "plex.fold"):
        assert stage in stages.values(), stage
    assert set(stages.values()) <= {"plex.route", "plex.segment",
                                    "plex.probe", "plex.fold", UNSCOPED}


def test_stage_of_hlo_rule():
    """The innermost ``plex.*`` scope of an instruction's ``op_name``
    names its stage; without one, or without metadata, it is unscoped."""
    text = "\n".join([
        "ENTRY %main (p: u32[8]) -> s32[8] {",
        '  %p = u32[8]{0} parameter(0), metadata={op_name="args[0]"}',
        '  %fusion.3 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(traced)/plex.probe/jit(_take)/gather" '
        'stack_frame_id=4}',
        '  %copy-start = (u32[8]{0}) copy-start(%p)',
        '  ROOT %add.1 = s32[8]{0} add(%fusion.3, %fusion.3), '
        'metadata={op_name="jit(traced)/plex.fold/add"}',
        "}"])
    assert stage_of_hlo(text) == {
        "p": UNSCOPED, "fusion.3": "plex.probe", "copy-start": UNSCOPED,
        "add.1": "plex.fold"}


def test_shard_boundary_absent_keys_exact(rng):
    """Absent keys at/next to shard boundaries resolve to the exact global
    lower bound: one key below a boundary routes to the predecessor shard
    and clamps to its key count; below the global min clamps to 0."""
    keys = np.unique(sorted_u64(rng, 40_000))
    svc = PlexService(keys, eps=16, n_shards=4, block=512)
    q = np.concatenate([svc.shard_min, svc.shard_min - 1,
                        np.asarray([0], np.uint64), keys[-1:] + 1])
    want = np.searchsorted(keys, q, side="left")
    for backend in BACKENDS:
        assert np.array_equal(svc.lookup(q, backend=backend), want), backend


def test_duplicate_run_snapped_boundary_stacked(rng):
    """A duplicate run wider than a naive shard boundary through the
    stacked path still resolves to the global first occurrence."""
    run = np.full(6_000, 1 << 40, np.uint64)
    keys = np.sort(np.concatenate([sorted_u64(rng, 10_000), run]))
    svc = PlexService(keys, eps=16, n_shards=8, block=256)
    assert svc.stacked_impl() is not None
    # present keys are exact everywhere; the absent key just past the run is
    # the documented inconclusive-window case (identical across backends,
    # not asserted equal to searchsorted)
    q = np.asarray([1 << 40, (1 << 40) - 1], dtype=np.uint64)
    want = np.searchsorted(keys, q, side="left")
    for backend in BACKENDS:
        assert np.array_equal(svc.lookup(q, backend=backend), want), backend


def test_three_way_parity_forced_four_shards(rng):
    keys = sorted_u64(rng, 8_192, dups=True)
    q = keys[rng.integers(0, keys.size, 2_000)]
    want = np.searchsorted(keys, q, side="left")
    svc = PlexService(keys, eps=24, n_shards=4, block=256)
    assert svc.n_shards == 4
    for backend in BACKENDS:
        assert np.array_equal(svc.lookup(q, backend=backend), want), backend


def test_stacked_radix_prefix_wraparound(rng):
    """Absent queries astronomically above a dense shard's key range wrap
    the int32 radix prefix negative; the stacked path must clip it into the
    routed shard's own table (it would otherwise gather a neighbour's)."""
    keys = np.unique(np.concatenate([
        (1 << 20) + np.sort(rng.integers(0, 1 << 14, 20_000,
                                         dtype=np.uint64)),
        (1 << 40) + np.sort(rng.integers(0, 1 << 14, 20_000,
                                         dtype=np.uint64))]))
    svc = PlexService(keys, eps=16, n_shards=2, block=512)
    assert svc.stacked_impl() is not None
    q = np.concatenate([
        rng.integers(1 << 41, np.iinfo(np.uint64).max, 2_000,
                     dtype=np.uint64),
        np.asarray([np.iinfo(np.uint64).max], np.uint64)])
    got = svc.lookup(q, backend="jnp")
    assert np.array_equal(got, np.full(q.size, keys.size))


# ------------------------------------------------- async submit/drain ----

def test_submit_drain_tickets_and_stats(rng):
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=16, n_shards=3, block=512, max_delay_s=60.0)
    svc.warmup()
    qs = [keys[:300], keys[5_000:5_900], keys[-100:]]
    tickets = [svc.submit(q) for q in qs]
    # 1300 queued queries -> two full blocks dispatched, 276 still queued
    assert svc.stats.inflight_batches == 2
    assert not tickets[-1].ready
    svc.drain()
    assert svc.stats.inflight_batches == 0
    assert svc.stats.drained_batches == 3
    assert svc.stats.padded_lanes == 3 * 512 - 1_300
    for t, q in zip(tickets, qs):
        assert t.ready
        assert np.array_equal(t.result(), np.searchsorted(keys, q, "left"))


def test_submit_deadline_flush(rng):
    keys = sorted_u64(rng, 10_000)
    svc = PlexService(keys, eps=16, block=512, max_delay_s=0.0)
    svc.warmup()
    svc.submit(keys[:100])
    svc.submit(keys[100:200])    # deadline 0: queued remainder flushes
    assert svc.stats.inflight_batches >= 1
    svc.drain()
    assert svc.stats.inflight_batches == 0


def test_ticket_result_triggers_drain(rng):
    keys = sorted_u64(rng, 10_000)
    svc = PlexService(keys, eps=16, block=512, max_delay_s=60.0)
    t = svc.submit(keys[:100])
    assert not t.ready
    assert np.array_equal(t.result(), np.searchsorted(keys, keys[:100],
                                                      "left"))
    assert svc.submit(np.zeros(0, np.uint64)).result().size == 0


# ----------------------------------------------------- hot-key cache ----

def test_hot_key_cache_hits_and_parity(rng):
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=16, n_shards=2, block=512,
                      cache_slots=1 << 13)
    hot = keys[rng.integers(0, 64, 10_000)]
    want = np.searchsorted(keys, hot, side="left")
    assert np.array_equal(svc.lookup(hot), want)     # cold pass fills
    assert np.array_equal(svc.lookup(hot), want)     # warm pass hits
    assert svc.stats.cache_queries > 0
    assert svc.stats.cache_hit_rate > 0.4
    # cache off: same results
    svc2 = PlexService(keys, eps=16, n_shards=2, block=512)
    assert np.array_equal(svc2.lookup(hot), want)
    assert svc2.stats.cache_queries == 0


def test_serving_knobs_validated_at_construction(rng):
    keys = sorted_u64(rng, 2_000)
    with pytest.raises(ValueError):
        PlexService(keys, eps=16, block=512, cache_slots=1000)
    with pytest.raises(ValueError):
        PlexService(keys, eps=16, block=512, probe="nope")


# -------------------------------------------------------- probe modes ----

def test_probe_modes_identical(rng):
    keys = sorted_u64(rng, 30_000, dups=True)
    idx = LearnedIndex.build(keys, eps=32)
    q = np.concatenate([keys[rng.integers(0, keys.size, 3_000)],
                        rng.integers(0, 1 << 62, 3_000, dtype=np.uint64)])
    got = {p: JnpPlex.from_plex(idx.plex, block=512, probe=p).lookup(q)
           for p in ("count", "bisect")}
    assert np.array_equal(got["count"], got["bisect"])
    with pytest.raises(ValueError):
        JnpPlex.from_plex(idx.plex, probe="nope")


@pytest.mark.parametrize("platform", ["tpu", "gpu", "cpu"])
def test_default_probe_is_bisect_on_every_backend(platform, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert default_probe_mode() == "bisect"


@pytest.mark.parametrize("platform", ["tpu", "gpu", "cpu"])
def test_unset_probe_resolves_to_bisect(platform, monkeypatch, rng):
    """``probe=None`` (what ``PlexService`` passes by default) builds the
    bisect probe whatever the platform, on both jnp lookup surfaces."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    keys = sorted_u64(rng, 20_000)
    offs = np.asarray([0, 10_000])
    st = StackedJnpPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                    block=512, probe=None)
    assert st is not None and st.probe == "bisect"
    jp = JnpPlex.from_plex(build_plex(keys, 32), block=512, probe=None)
    assert jp.probe == "bisect"
    assert JnpPlex(planes=jp.planes, block=512).probe == "bisect"


@pytest.mark.parametrize("kind", ["radix", "cht"])
@pytest.mark.parametrize("probe", ["count", "bisect"])
def test_probe_at_clamped_window_edge(probe, kind, rng):
    """Both probes stay exact where the window base is clamped to
    ``n_data_max - window``: the last keys of the shards that fill a
    stacked data row, queries at and past each shard's last key (past the
    end of the index too), and present keys plus one, as the half-absent
    traffic sends them."""
    sizes = [12_800, 9_001, 12_800]       # two shards fill a whole row
    keys = np.unique(sorted_u64(rng, sum(sizes) + 64))[:sum(sizes)]
    assert keys.size == sum(sizes)
    offs = np.cumsum([0] + sizes[:-1])
    plexes = _shard_plexes(keys, offs)
    if kind == "cht":
        plexes = [_force_cht(px, 6, 3) for px in plexes]
    st = StackedJnpPlex.from_plexes(plexes, offs, block=512, probe=probe)
    assert st is not None
    sp = st.planes
    assert sp.kind == kind and sp.n_data_max == 12_800
    # a last key's base (its prediction, within eps_eff, less eps_eff) lies
    # past n_data_max - window: the clamp decides it
    assert sp.n_data_max - 1 - 2 * sp.eps_eff > sp.n_data_max - sp.window
    ends = np.cumsum(sizes)
    tail = np.concatenate([keys[e - sp.window:e] for e in ends])
    u64_max = np.iinfo(np.uint64).max
    q = np.concatenate([
        tail, tail + np.uint64(1), keys[ends - 1] - np.uint64(1),
        keys[offs], keys[offs] - np.uint64(1),
        np.asarray([u64_max - 1, u64_max], np.uint64)])
    want = np.searchsorted(keys, q, side="left")
    assert np.array_equal(st.lookup(q), want)
    assert not np.isin(tail + np.uint64(1), keys).any()   # all absent


# ------------------------------------------------ bench_diff + zipf ----

def _rec(dataset="a", eps=16, backend="jnp", ns=100.0, workload="uniform"):
    return {"dataset": dataset, "n": 10, "eps": eps, "backend": backend,
            "workload": workload, "ns_per_lookup": ns, "build_s": 0.1,
            "size_bytes": 10}


def test_bench_diff_regression_gate(tmp_path):
    from benchmarks.bench_diff import main
    old = [_rec(ns=100.0), _rec(backend="numpy", ns=50.0)]
    new_ok = [_rec(ns=110.0), _rec(backend="numpy", ns=40.0),
              _rec(workload="zipf", ns=999.0)]       # new records never fail
    new_bad = [_rec(ns=120.0), _rec(backend="numpy", ns=50.0)]
    (tmp_path / "old.json").write_text(json.dumps(old))
    (tmp_path / "ok.json").write_text(json.dumps(new_ok))
    (tmp_path / "bad.json").write_text(json.dumps(new_bad))
    assert main([str(tmp_path / "old.json"), str(tmp_path / "ok.json")]) == 0
    assert main([str(tmp_path / "old.json"), str(tmp_path / "bad.json")]) == 1
    assert main([str(tmp_path / "old.json"), str(tmp_path / "bad.json"),
                 "--threshold", "0.5"]) == 0


def test_bench_diff_write_frac_keys_never_collide(tmp_path):
    """update_mix records with different write fractions are distinct keys
    (schema-additive: legacy records without write_frac still match)."""
    from benchmarks.bench_diff import diff, load
    old = [_rec(workload="update_mix", ns=100.0) | {"write_frac": 0.1},
           _rec(ns=100.0)]
    new = [_rec(workload="update_mix", ns=500.0) | {"write_frac": 0.5},
           _rec(ns=100.0)]
    (tmp_path / "old.json").write_text(json.dumps(old))
    (tmp_path / "new.json").write_text(json.dumps(new))
    lines, regressions = diff(load(tmp_path / "old.json"),
                              load(tmp_path / "new.json"), 0.15)
    assert not regressions        # different mixes never compared
    assert any("new record" in ln for ln in lines)
    assert any("dropped" in ln for ln in lines)


def test_update_mix_stream_deterministic_and_mixed(rng):
    from benchmarks.serve_bench import update_mix_stream
    keys = np.unique(sorted_u64(rng, 20_000))
    ops, model = update_mix_stream(keys, 10_000, write_frac=0.2, rounds=4,
                                   seed=5)
    assert len(ops) == 4
    n_reads = sum(r.size for _, _, r in ops)
    n_writes = sum(i.size + d.size for i, d, _ in ops)
    assert n_reads == 10_000
    assert 0.05 < n_writes / (n_reads + n_writes) < 0.35
    # the final model reflects tombstone-over-everything semantics
    check = keys.copy()
    for ins, dels, _ in ops:
        check = np.sort(np.concatenate([check, ins]))
        check = check[~np.isin(check, dels)]
    assert np.array_equal(model, check)
    ops2, model2 = update_mix_stream(keys, 10_000, write_frac=0.2, rounds=4,
                                     seed=5)
    assert np.array_equal(model, model2)
    assert all(np.array_equal(a, b) for x, y in zip(ops, ops2)
               for a, b in zip(x, y))


def test_zipf_queries_skew_and_absent(rng):
    from benchmarks.serve_bench import zipf_queries
    keys = np.unique(sorted_u64(rng, 20_000))
    q = zipf_queries(keys, 50_000, theta=1.2, absent_frac=0.2, seed=3)
    assert q.size == 50_000
    present = np.isin(q, keys)
    assert 0.1 < (~present).mean() < 0.3       # ~20% absent
    # skew: the hottest key dominates a uniform draw's expectation
    _, counts = np.unique(q[present], return_counts=True)
    assert counts.max() > 50 * q.size / keys.size
    # deterministic
    assert np.array_equal(q, zipf_queries(keys, 50_000, theta=1.2,
                                          absent_frac=0.2, seed=3))
