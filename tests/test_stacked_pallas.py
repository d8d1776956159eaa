"""Fused Pallas stacked kernel + backend registry: bit-parity with the jnp
stacked pipeline across the serving matrix (single/multi-shard, CHT-forced,
duplicate-heavy, live delta, routed mesh spans, persisted warm starts), the
one-``pallas_call``-per-micro-batch dispatch guarantee, registry-exclusive
backend resolution, and the deprecation shims."""
import dataclasses

import numpy as np
import pytest

import jax

from repro.core import LearnedIndex, Snapshot
from repro.core.cht import build_cht
from repro.core.plex import build_plex
from repro.kernels.backends import (backend_names, get_backend,
                                    register_backend, unregister_backend)
from repro.kernels.jnp_lookup import StackedJnpPlex
from repro.kernels.stacked_pallas import StackedPallasPlex
from repro.serving import PlexService

from conftest import sorted_u64

BLOCK = 512


def _shard_plexes(keys, offs, eps=32, **kw):
    ends = list(offs[1:]) + [keys.size]
    return [build_plex(keys[o:e], eps, **kw) for o, e in zip(offs, ends)]


def _force_cht(px, r, delta):
    return dataclasses.replace(px, layer=build_cht(px.spline.keys, r, delta))


def _count_primitive(jaxpr, name: str) -> int:
    """Occurrences of a primitive anywhere in a (possibly nested) jaxpr."""
    n = 0
    for eq in jaxpr.eqns:
        if eq.primitive.name == name:
            n += 1
        for v in eq.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    inner = sub.jaxpr if hasattr(sub.jaxpr, "eqns") else sub
                    n += _count_primitive(inner, name)
    return n


# ---------------------------------------------------------- bit parity ----

@pytest.mark.parametrize("probe", ["count", "bisect"])
def test_pallas_parity_radix_present_absent(probe, rng):
    keys = sorted_u64(rng, 40_000, dups=True)      # duplicate-heavy
    offs = np.asarray([0, 10_000, 20_000, 30_000])
    jn = StackedJnpPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                    block=BLOCK, probe=probe)
    pl = StackedPallasPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                       block=BLOCK, probe=probe)
    assert pl.planes.kind == "radix"
    q = np.concatenate([keys[rng.integers(0, keys.size, 2_048)],
                        rng.integers(0, 1 << 62, 2_048, dtype=np.uint64)])
    got_j, got_p = jn.lookup(q), pl.lookup(q)
    assert np.array_equal(got_j, got_p)            # bit parity, incl. absent
    present = np.isin(q, keys)
    want = np.searchsorted(keys, q, side="left")
    assert np.array_equal(got_p[present], want[present])


def test_pallas_parity_cht(rng):
    keys = sorted_u64(rng, 40_000)
    offs = np.asarray([0, 10_000, 20_000, 30_000])
    plexes = [_force_cht(px, r=3, delta=8 + 8 * i)
              for i, px in enumerate(_shard_plexes(keys, offs, eps=48))]
    jn = StackedJnpPlex.from_plexes(plexes, offs, block=BLOCK)
    pl = StackedPallasPlex.from_plexes(plexes, offs, block=BLOCK)
    assert pl.planes.kind == "cht"
    q = np.concatenate([keys[rng.integers(0, keys.size, 2_048)],
                        rng.integers(0, 1 << 62, 2_048, dtype=np.uint64)])
    assert np.array_equal(jn.lookup(q), pl.lookup(q))


def test_pallas_parity_merged_live_delta(rng):
    """Service-level merged lookups (live delta buffer) agree exactly
    between the jnp and pallas backends and match searchsorted over the
    logical key array."""
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=32, n_shards=3, block=BLOCK,
                      merge_threshold=1 << 30)     # keep the delta live
    svc.insert(rng.integers(keys[0], keys[-1], 700, dtype=np.uint64))
    svc.delete(keys[rng.integers(0, keys.size, 300)])
    assert svc.n_pending > 0
    logical = svc.logical_keys()
    q = np.concatenate([logical[rng.integers(0, logical.size, 2_000)],
                        rng.integers(0, 1 << 62, 500, dtype=np.uint64)])
    got_j = svc.lookup(q, backend="jnp")
    got_p = svc.lookup(q, backend="pallas")
    assert np.array_equal(got_j, got_p)
    present = np.isin(q, logical)
    want = np.searchsorted(logical, q, side="left")
    assert np.array_equal(got_p[present], want[present])


def test_pallas_hot_key_cache_parity(rng):
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=16, n_shards=2, block=BLOCK,
                      backend="pallas", cache_slots=1 << 13)
    hot = keys[rng.integers(0, 64, 8_192)]
    want = np.searchsorted(keys, hot, side="left")
    assert np.array_equal(svc.lookup(hot), want)   # cold pass fills
    assert np.array_equal(svc.lookup(hot), want)   # warm pass hits
    assert svc.stats.cache_hit_rate > 0.4


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_pallas_routed_mesh_parity(n_dev, rng):
    """Routed mesh serving with pallas device impls (virtual devices on a
    1-device host; the multi-device CI leg provides real ones)."""
    keys = sorted_u64(rng, 40_000)
    svc = PlexService(keys, eps=32, n_shards=8, block=256,
                      backend="pallas", plan=min(n_dev, len(jax.devices())))
    assert svc.plan is not None
    q = np.concatenate([keys[rng.integers(0, keys.size, 2_000)],
                        rng.integers(0, 1 << 62, 500, dtype=np.uint64)])
    ref = PlexService(keys, eps=32, n_shards=8, block=256)
    assert np.array_equal(svc.lookup(q), ref.lookup(q, backend="jnp"))


def test_pallas_persisted_warm_start_parity(rng, tmp_path):
    keys = sorted_u64(rng, 30_000)
    svc = PlexService(keys, eps=32, n_shards=3, block=BLOCK,
                      backend="pallas")
    q = np.concatenate([keys[rng.integers(0, keys.size, 2_000)],
                        rng.integers(0, 1 << 62, 500, dtype=np.uint64)])
    fresh = svc.lookup(q)
    svc.save(tmp_path / "svc", fsync=False)
    svc.close()
    warm = PlexService.open(tmp_path / "svc", backend="pallas",
                            durable=False)
    assert warm.default_backend == "pallas"
    assert np.array_equal(warm.lookup(q), fresh)
    warm.close()


# ------------------------------------------------- dispatch guarantees ----

def test_single_pallas_call_per_dispatch(rng):
    """The whole pipeline — routing, window base, probe, clamp, offset
    fold, and the merged delta fold — is ONE pallas_call, delta-free and
    merged alike."""
    keys = sorted_u64(rng, 20_000)
    offs = np.asarray([0, 10_000])
    pl = StackedPallasPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                       block=BLOCK)
    qh = np.zeros(BLOCK, np.uint32)
    jx = jax.make_jaxpr(pl._fn)(qh, qh)
    assert _count_primitive(jx.jaxpr, "pallas_call") == 1
    cap = 64
    d = (np.zeros(cap, np.uint32), np.zeros(cap, np.uint32),
         np.zeros(cap + 1, np.int32))
    jx_m = jax.make_jaxpr(pl._merged_fn(cap))(qh, qh, *d)
    assert _count_primitive(jx_m.jaxpr, "pallas_call") == 1


def test_one_dispatch_per_microbatch_through_service(rng):
    keys = sorted_u64(rng, 40_000)
    svc = PlexService(keys, eps=32, n_shards=4, block=BLOCK,
                      backend="pallas")
    st = svc.stacked_impl()
    assert isinstance(st, StackedPallasPlex)
    calls = []
    orig = st._fn
    st._fn = lambda *a: (calls.append(1), orig(*a))[1]
    q = keys[rng.integers(0, keys.size, 3 * BLOCK + 100)]  # 4 micro-batches
    got = svc.lookup(q)
    assert np.array_equal(got, np.searchsorted(keys, q, side="left"))
    assert len(calls) == 4


# -------------------------------------------------------- registry API ----

def test_unknown_backend_lists_registered():
    with pytest.raises(ValueError, match="registered backends"):
        get_backend("cuda")
    keys = np.arange(1, 2_000, dtype=np.uint64)
    with pytest.raises(ValueError, match="registered backends"):
        LearnedIndex.build(keys, eps=16, backend="nope")
    with pytest.raises(ValueError, match="registered backends"):
        PlexService(keys, eps=16, backend="nope")
    idx = LearnedIndex.build(keys, eps=16)
    with pytest.raises(ValueError, match="registered backends"):
        idx.lookup(keys[:10], backend="nope")


def test_custom_backend_plugs_into_every_surface(rng):
    """A third-party registration is reachable from LearnedIndex dispatch,
    Snapshot stacked builds, and PlexService serving with zero string
    branches anywhere outside the registry."""
    calls = {"stacked": 0, "index": 0}

    def stacked_factory(plexes, row_off, **kw):
        calls["stacked"] += 1
        return StackedPallasPlex.from_plexes(plexes, row_off,
                                             block=kw["block"],
                                             probe=kw.get("probe"))

    def index_factory(px, *, block, device):
        calls["index"] += 1
        return px

    register_backend("custom-test", stacked_factory,
                     index_factory=index_factory)
    try:
        assert "custom-test" in backend_names()
        keys = sorted_u64(rng, 10_000)
        q = keys[rng.integers(0, keys.size, 1_000)]
        want = np.searchsorted(keys, q, side="left")
        idx = LearnedIndex.build(keys, eps=16, backend="custom-test")
        assert np.array_equal(idx.lookup(q), want)
        assert calls["index"] == 1
        svc = PlexService(keys, eps=16, n_shards=2, block=BLOCK,
                          backend="custom-test")
        assert np.array_equal(svc.lookup(q), want)
        assert calls["stacked"] >= 1
        snap = Snapshot.build(keys, eps=16, n_shards=2)
        st = snap.stacked_impl("custom-test", block=BLOCK)
        assert isinstance(st, StackedPallasPlex)
    finally:
        unregister_backend("custom-test")
    with pytest.raises(ValueError):
        get_backend("custom-test")


def test_duplicate_registration_guard():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("jnp", None)


def test_host_backend_has_no_stacked_path(rng):
    keys = sorted_u64(rng, 5_000)
    idx = LearnedIndex.build(keys, eps=16)
    with pytest.raises(ValueError, match="no stacked device path"):
        idx.stacked_impl("numpy")
    snap = Snapshot.build(keys, eps=16)
    with pytest.raises(ValueError, match="no stacked device path"):
        snap.stacked_impl("numpy")


# --------------------------------------------------- deprecation shims ----

def test_learned_index_lookup_planes_deprecated(rng):
    from repro.kernels.pairs import split_u64
    keys = sorted_u64(rng, 10_000)
    idx = LearnedIndex.build(keys, eps=16)
    q = keys[rng.integers(0, keys.size, BLOCK)]
    qh, ql = split_u64(np.ascontiguousarray(q))
    want = np.searchsorted(keys, q, side="left")
    for backend in ("jnp", "pallas"):
        with pytest.warns(DeprecationWarning):
            out = idx.lookup_planes(qh, ql, backend=backend)
        assert np.array_equal(np.asarray(out), want), backend
    with pytest.raises(ValueError):
        idx.lookup_planes(qh, ql, backend="numpy")


def test_device_plex_lookup_planes_deprecated(rng):
    from repro.kernels.ops import DevicePlex
    from repro.kernels.pairs import split_u64
    keys = sorted_u64(rng, 10_000)
    px = build_plex(keys, eps=16)
    dp = DevicePlex.from_plex(px, block=BLOCK)
    q = keys[rng.integers(0, keys.size, BLOCK)]
    qh, ql = split_u64(np.ascontiguousarray(q))
    with pytest.warns(DeprecationWarning):
        out = dp.lookup_planes(qh, ql)
    assert np.array_equal(np.asarray(out),
                          np.searchsorted(keys, q, side="left"))


# ------------------------------------------------------------ roofline ----

def test_roofline_bytes_model_sane(rng):
    """The analytic traffic model tracks the layout statics: bisect probes
    move fewer bytes than count sweeps, and every term is positive."""
    from benchmarks.roofline import bytes_per_lookup
    keys = sorted_u64(rng, 20_000)
    offs = np.asarray([0, 10_000])
    by = {}
    for probe in ("count", "bisect"):
        st = StackedJnpPlex.from_plexes(_shard_plexes(keys, offs), offs,
                                        block=BLOCK, probe=probe)
        by[probe] = bytes_per_lookup(st.planes, st.probe)
        assert by[probe] > 0
    assert by["bisect"] < by["count"]


def test_roofline_peak_keyed_by_device_kind():
    """The peak is the published figure of the chip the run is on; a
    device the table does not know, the CPU included, is an error."""
    from benchmarks.roofline import peak_gbps
    assert peak_gbps("TPU v5 lite") == 819.0
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(ValueError, match="no published peak"):
            peak_gbps(kind)
