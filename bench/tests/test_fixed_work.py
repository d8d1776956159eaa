"""The roofline's fixed work follows the index, not the kernel."""
import numpy as np
import pytest

from harness import fixed_work


@pytest.fixture(scope="module")
def keys():
    from harness.spec import Bench
    gen = Bench().module("datasets", "ycsb")
    return gen.generate({"recordcount": 300_000, "insertstart": 10**8})


def statics_of(keys, probe):
    from repro.serving import PlexService
    svc = PlexService(keys, eps=64, backend="jnp", fallback=None,
                      merge_threshold=0, n_shards=3, probe=probe)
    st = svc.stacked_impl()
    assert st.probe == probe
    sp = st.planes
    return svc, (sp.n_shards, sp.kind, dict(sp.static))


def test_same_count_for_count_and_bisect_probes(keys):
    svc_c, s_count = statics_of(keys, "count")
    svc_b, s_bisect = statics_of(keys, "bisect")
    assert s_count == s_bisect
    n = fixed_work.bytes_per_lookup(64, *s_count)
    assert n == fixed_work.bytes_per_lookup(64, *s_bisect)
    q = keys[::997]
    assert np.array_equal(svc_c.lookup(q), svc_b.lookup(q))


def test_stage_by_stage():
    # 24 shards, radix, widest window 39, eps 64: query 8, routing 5 trips,
    # radix parameters 20 + two table entries 8, segment 6 trips,
    # interpolation 24, eps window 8 trips (130 keys), fold 8, result 4
    assert fixed_work.bytes_per_lookup(64, 24, "radix", {"max_win": 39}) == (
        8 + 5 * 8 + 28 + 6 * 8 + 24 + 8 * 8 + 8 + 4)
    # a 32412-point window takes 15 trips
    assert fixed_work.trips(32412) == 15
    assert fixed_work.bytes_per_lookup(
        64, 24, "radix", {"max_win": 32412}) - fixed_work.bytes_per_lookup(
        64, 24, "radix", {"max_win": 39}) == (15 - 6) * 8
    assert fixed_work.bytes_per_lookup(
        64, 1, "cht", {"levels": 3, "delta_max": 15}) == (
        8 + 0 + 8 + 12 + 4 * 8 + 24 + 8 * 8 + 8 + 4)
    assert fixed_work.trips(1) == 0 and fixed_work.trips(2) == 1
