"""End-to-end observability (ISSUES 9 + 10): metrics registry, pipeline
tracing, the live shard-hotness export, and the production layer — the
always-on flight recorder, SLO watchdog, and automatic incident bundles.

The contract under test:

* both singletons are **disabled by default** and a disabled hook site
  costs one attribute read / one shared null context — results NEVER
  change when observability is armed (counted dispatch is bit-identical
  on every backend);
* armed, the device counter planes make ``live_hotness`` an exact
  running ``np.bincount(snap.route(stream))`` over everything served
  this epoch, probe-trip totals match the counted query count, and
  spans cover every pipeline stage;
* ``health()`` grows a schema-additive ``metrics`` section that stays
  JSON-serialisable through chaos, and the per-epoch stats counters
  survive the background merge worker's epoch rollover race-free;
* the tracer survives the always-on posture: mismatched or
  exception-crossed span exits never corrupt the per-thread depth,
  cross-thread record/event interleaving is safe at the deque, and a
  long soak holds the bounded-memory contract;
* exporters iterate a locked registry snapshot (scrape-during-register
  never raises) and emit *valid* Prometheus exposition (one TYPE per
  family, cumulative ``le`` buckets ending at ``+Inf``);
* the recorder/SLO/incident layer: bounded series rings, multi-window
  burn-rate breaches with events, and debounced retention-capped
  bundles written from every wired failure class.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.obs import (METRICS, TRACE, disable_observability,
                       enable_observability, observability_enabled)
from repro.obs import incident as incident_mod
from repro.obs.export import prometheus_text, write_jsonl
from repro.obs.metrics import RING_SIZE, Histogram, MetricsRegistry
from repro.obs.recorder import RECORDER, FlightRecorder
from repro.obs.slo import SLOSpec, SLOWatchdog, default_slos
from repro.obs import trace as TRACE_MODULE
from repro.obs.trace import Tracer, _NULL
from repro.serving.plex_service import PlexService, ServiceStats


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends disarmed with empty instruments — the
    singletons are process-global, exactly like resilience.FAULTS."""
    disable_observability()
    METRICS.reset()
    TRACE.clear()
    TRACE.sample_n = 1
    incident_mod.uninstall()
    yield
    if RECORDER.armed:
        RECORDER.disarm()
    RECORDER.clear()
    incident_mod.uninstall()
    disable_observability()
    METRICS.reset()
    TRACE.clear()
    TRACE.sample_n = 1


def _keys(n: int = 50_000, seed: int = 5) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.unique(rng.integers(0, 2**62, n, dtype=np.uint64))


# -- registry primitives -----------------------------------------------------

def test_disabled_by_default_and_null_span_shared():
    assert not observability_enabled()
    assert TRACE.span("x") is _NULL
    assert TRACE.span("y", a=1) is _NULL     # attrs never allocate a span
    TRACE.record("x", 1.0)
    TRACE.event("x")
    assert TRACE.events() == []


def test_registry_counters_gauges_vectors():
    r = MetricsRegistry()
    c = r.counter("a.b")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    assert r.counter("a.b") is c             # get-or-create returns shared
    r.gauge("g").set(2.5)
    v = r.vector("shards", 4)
    v.add(np.asarray([1, 2, 3, 4]))
    v.add_at(0, 10)
    assert v.snapshot() == [11, 2, 3, 4]
    with pytest.raises(ValueError, match="shape"):
        v.add(np.zeros(3))
    # a length change replaces (epoch-scoped per-shard planes)
    v2 = r.vector("shards", 6)
    assert v2 is not v and v2.snapshot() == [0] * 6
    snap = r.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["g"] == 2.5
    json.dumps(snap)                          # JSON-serialisable contract


def test_histogram_percentiles_and_ring_wrap():
    h = Histogram("lat")
    for v in range(1, 1001):
        h.observe(float(v))
    assert h.count == 1000 and h.max == 1000.0
    assert h.percentile(0.50) == 500.0
    assert h.percentile(0.99) == 990.0
    assert h.percentile(0.0) == 1.0
    # wrap the ring: the recent window forgets the first samples
    for v in range(RING_SIZE):
        h.observe(10_000.0)
    assert h.percentile(0.50) == 10_000.0
    assert h.count == 1000 + RING_SIZE        # totals stay cumulative
    buckets = h.bucket_counts()
    assert buckets[-1][0] == float("inf")
    assert buckets[-1][1] == h.count          # cumulative ends at total
    snap = h.snapshot()
    assert set(snap) == {"count", "sum", "max", "p50", "p90", "p99"}


def test_tracer_nesting_record_event_jsonl():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", n=2):
        with tr.span("inner"):
            pass
    tr.record("posthoc", 0.5, shard=1)
    tr.event("marker", state="open")
    evs = tr.events()
    by = {e["name"]: e for e in evs}
    assert by["inner"]["depth"] == 1 and by["outer"]["depth"] == 0
    # inner exits (and emits) before outer
    assert evs.index(by["inner"]) < evs.index(by["outer"])
    assert by["posthoc"]["dur_us"] == pytest.approx(5e5)
    assert by["marker"]["dur_us"] == 0.0
    assert by["outer"]["attrs"]["n"] == 2
    for line in tr.to_jsonl().splitlines():
        json.loads(line)


def test_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("wal.append_records").inc(3)
    r.histogram("serve.lookup_us").observe(5.0)
    r.vector("serve.shard.routed", 2).add(np.asarray([7, 9]))
    text = prometheus_text(r, prefix="plex")
    assert "plex_wal_append_records_total 3" in text
    assert 'plex_serve_shard_routed_total{shard="0"} 7' in text
    assert "# TYPE plex_serve_lookup_us histogram" in text
    assert 'plex_serve_lookup_us_bucket{le="+Inf"} 1' in text
    assert "plex_serve_lookup_us_count 1" in text
    # recent-window quantiles live in their own gauge family: a bare
    # {quantile=...} sample under the histogram name is invalid exposition
    assert "# TYPE plex_serve_lookup_us_recent gauge" in text
    assert 'plex_serve_lookup_us_recent{quantile="0.5"} 5' in text
    assert 'plex_serve_lookup_us{quantile' not in text


def _parse_prometheus(text: str) -> dict[str, dict]:
    """Minimal exposition parser: family -> {type, samples: [(name,
    labels, value)]}. Raises on malformed lines or samples that belong
    to no declared family."""
    fams: dict[str, dict] = {}
    hist_suffixes = ("_bucket", "_sum", "_count")
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, fam, typ = line.split(" ")
            assert fam not in fams, f"duplicate TYPE for {fam}"
            fams[fam] = {"type": typ, "samples": []}
            continue
        assert not line.startswith("#"), f"unexpected comment: {line}"
        metric, value = line.rsplit(" ", 1)
        labels = ""
        if "{" in metric:
            metric, _, rest = metric.partition("{")
            labels = rest.rstrip("}")
        owner = None
        if metric in fams:
            owner = metric
            if fams[metric]["type"] == "histogram":
                # a bare sample under a histogram family is invalid
                raise AssertionError(f"bare sample under histogram "
                                     f"family: {line}")
        else:
            for suf in hist_suffixes:
                base = metric[:-len(suf)] if metric.endswith(suf) else None
                if base in fams and fams[base]["type"] == "histogram":
                    owner = base
                    break
        assert owner is not None, f"sample outside any TYPE family: {line}"
        fams[owner]["samples"].append((metric, labels, float(value)))
    return fams


def test_prometheus_format_validity():
    """Whole-page validity: unique TYPE per family, every sample owned by
    a declared family, histogram buckets cumulative and ending at +Inf
    == _count."""
    r = MetricsRegistry()
    r.counter("serve.dispatch.jnp").inc(4)
    r.gauge("queue.depth").set(7)
    for v in (3.0, 30.0, 300.0, 3e6):
        r.histogram("serve.lookup_us").observe(v)
    r.vector("serve.shard.routed", 3).add(np.asarray([1, 2, 3]))
    fams = _parse_prometheus(prometheus_text(r))
    h = fams["plex_serve_lookup_us"]
    assert h["type"] == "histogram"
    buckets = [(lab, v) for m, lab, v in h["samples"]
               if m.endswith("_bucket")]
    counts = [v for _, v in buckets]
    assert counts == sorted(counts), "le buckets must be cumulative"
    assert buckets[-1][0] == 'le="+Inf"'
    count = [v for m, _, v in h["samples"] if m.endswith("_count")][0]
    assert buckets[-1][1] == count == 4
    assert fams["plex_serve_lookup_us_recent"]["type"] == "gauge"
    assert fams["plex_serve_dispatch_jnp_total"]["type"] == "counter"
    assert len(fams["plex_serve_shard_routed_total"]["samples"]) == 3


def test_scrape_during_registration_race():
    """Satellite: exporters and ``snapshot()`` iterate via the locked
    ``collect()`` — hammer concurrent instrument *registration* against
    scrapes and snapshots; no RuntimeError, every page parses."""
    r = MetricsRegistry()
    stop = threading.Event()
    errors: list[BaseException] = []

    def registrar(tid: int):
        i = 0
        while not stop.is_set():
            r.counter(f"c.{tid}.{i}").inc()
            r.gauge(f"g.{tid}.{i}").set(i)
            r.histogram(f"h.{tid}.{i}").observe(float(i + 1))
            r.vector(f"v.{tid}.{i}", 2).add_at(0)
            i += 1

    def scraper():
        while not stop.is_set():
            try:
                _parse_prometheus(prometheus_text(r))
                json.dumps(r.snapshot())
            except BaseException as e:   # pragma: no cover - the bug
                errors.append(e)
                return

    threads = [threading.Thread(target=registrar, args=(t,))
               for t in range(2)] + \
        [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors


def test_write_jsonl_spans_then_metrics(tmp_path):
    enable_observability()
    with TRACE.span("serve.lookup", n=1):
        METRICS.counter("c").inc()
    disable_observability()
    path = write_jsonl(tmp_path / "events.jsonl")
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[0]["type"] == "span" and lines[0]["name"] == "serve.lookup"
    assert lines[-1]["type"] == "metrics" and lines[-1]["counters"]["c"] == 1


# -- counted dispatch: parity + exact hotness --------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_counted_dispatch_bit_identical(backend):
    """Arming METRICS must never change a result on any stacked backend
    (the counted pipeline is the same math over the same planes)."""
    keys = _keys(30_000)
    svc = PlexService(keys, 32, n_shards=4, backend=backend)
    try:
        q = np.random.default_rng(0).choice(keys, 4000)
        off = svc.lookup(q)
        enable_observability()
        on = svc.lookup(q)
        assert np.array_equal(off, on)
        assert np.array_equal(on, np.searchsorted(keys, q, "left"))
    finally:
        svc.close()


def test_live_hotness_is_exact_bincount():
    keys = _keys()
    svc = PlexService(keys, 32, n_shards=4)
    try:
        assert svc.live_hotness().tolist() == [0, 0, 0, 0]
        rng = np.random.default_rng(1)
        enable_observability()
        q1 = rng.choice(keys, 6000)
        q2 = rng.choice(keys, 3000)
        svc.lookup(q1)
        svc.lookup(q2)
        want = (np.bincount(svc.route(q1), minlength=4)
                + np.bincount(svc.route(q2), minlength=4))
        assert np.array_equal(svc.live_hotness(), want)
        # probe trips: every counted query lands in exactly one bucket
        assert svc.probe_trip_hist().sum() == 9000
        # the registry mirror agrees
        assert METRICS.vector("serve.shard.routed", 4).snapshot() == \
            want.tolist()
        assert METRICS.counter("serve.routed_queries").snapshot() == 9000
    finally:
        svc.close()


def test_hotness_counts_merged_delta_and_queue_paths():
    keys = _keys(40_000)
    svc = PlexService(keys, 32, n_shards=4, merge_threshold=0)
    try:
        fresh = np.unique(np.random.default_rng(2).integers(
            0, 2**62, 500, dtype=np.uint64))
        svc.insert(fresh)                    # pending delta: merged path
        model = svc.logical_keys()
        rng = np.random.default_rng(3)
        q = np.asarray(model)[rng.integers(0, model.size, 5000)]
        enable_observability()
        got = svc.lookup(q)                  # merged counted dispatch
        assert np.array_equal(got, np.searchsorted(model, q, "left"))
        t = svc.submit(q[:2000])             # queue path counts too
        svc.drain()
        np.testing.assert_array_equal(
            t.result(), np.searchsorted(model, q[:2000], "left"))
        want = (np.bincount(svc.route(q), minlength=4)
                + np.bincount(svc.route(q[:2000]), minlength=4))
        assert np.array_equal(svc.live_hotness(), want)
    finally:
        svc.close()


def test_hotness_resets_at_merge_epoch():
    keys = _keys(40_000)
    svc = PlexService(keys, 32, n_shards=4, merge_threshold=256)
    try:
        enable_observability()
        rng = np.random.default_rng(4)
        svc.lookup(rng.choice(keys, 3000))
        assert svc.live_hotness().sum() == 3000
        fresh = np.unique(rng.integers(0, 2**62, 600, dtype=np.uint64))
        svc.insert(fresh)                    # crosses threshold: sync merge
        assert svc.stats.merges == 1
        assert svc.live_hotness().sum() == 0  # per-epoch estimate restarts
        model = svc.logical_keys()
        q = np.asarray(model)[rng.integers(0, model.size, 2000)]
        svc.lookup(q)
        assert np.array_equal(svc.live_hotness(),
                              np.bincount(svc.route(q), minlength=4))
    finally:
        svc.close()


def test_host_backend_hotness_fold():
    keys = _keys(30_000)
    svc = PlexService(keys, 32, n_shards=4, backend="numpy")
    try:
        enable_observability()
        q = np.random.default_rng(5).choice(keys, 4000)
        svc.lookup(q)
        assert np.array_equal(svc.live_hotness(),
                              np.bincount(svc.route(q), minlength=4))
        # the host path routes without probing: no probe trips
        assert svc.probe_trip_hist().sum() == 0
    finally:
        svc.close()


def test_routed_mesh_hotness(tmp_path):
    """plan=1 routed path on the single host device: per-part counter
    planes fold at their global shard offsets."""
    keys = _keys(40_000)
    svc = PlexService(keys, 32, n_shards=4, plan=1)
    try:
        if svc.plan is None:
            pytest.skip("routed path unavailable (shards did not unify)")
        enable_observability()
        q = np.random.default_rng(6).choice(keys, 5000)
        got = svc.lookup(q)
        assert np.array_equal(got, np.searchsorted(keys, q, "left"))
        assert np.array_equal(svc.live_hotness(),
                              np.bincount(svc.route(q), minlength=4))
    finally:
        svc.close()


# -- spans through the pipeline ----------------------------------------------

def test_serve_spans_cover_pipeline_stages():
    keys = _keys()
    svc = PlexService(keys, 32, n_shards=2)
    try:
        enable_observability()
        q = np.random.default_rng(7).choice(keys, 6000)
        svc.lookup(q)
        t = svc.submit(q[:1000])
        svc.drain()
        t.result()
        names = TRACE.span_names()
        for need in ("serve.lookup", "serve.staging", "serve.dispatch",
                     "serve.sync", "serve.submit", "serve.queue_wait",
                     "serve.drain"):
            assert need in names, f"missing span {need}: {sorted(names)}"
        assert len(names) >= 6
        # lookup latency histograms observed per call
        assert METRICS.histogram("serve.lookup_us").count >= 1
        assert METRICS.histogram("serve.lookup_ns_per_key") \
            .percentile(0.99) > 0
    finally:
        svc.close()


def test_merge_wal_build_spans(tmp_path):
    keys = _keys(30_000)
    svc = PlexService(keys, 32, n_shards=2)
    root = tmp_path / "svc"
    svc.save(root)
    svc.close()
    enable_observability()
    svc = PlexService.open(root, backend="jnp", merge_threshold=128)
    try:
        fresh = np.unique(np.random.default_rng(8).integers(
            0, 2**62, 300, dtype=np.uint64))
        svc.insert(fresh)                    # WAL append + sync merge
        names = TRACE.span_names()
        for need in ("persist.open", "wal.append", "merge.capture",
                     "merge.build", "merge.publish", "build.shard",
                     "build.spline", "build.tune", "build.layer"):
            assert need in names, f"missing span {need}: {sorted(names)}"
        assert METRICS.counter("merge.cycles").snapshot() == 1
        assert METRICS.counter("wal.append_records").snapshot() >= 1
        assert METRICS.counter("wal.append_bytes").snapshot() > 0
    finally:
        svc.close()


def test_breaker_transition_events():
    from repro.resilience.breakers import CircuitBreaker
    enable_observability()
    br = CircuitBreaker("b", failure_threshold=2, cooldown_s=0.0)
    br.record_failure(RuntimeError("x"))
    assert [e for e in TRACE.events()
            if e["name"] == "breaker.transition"] == []
    br.record_failure(RuntimeError("x"))     # threshold: closed -> open
    assert br.allow()                        # cooldown 0: half-open probe
    br.record_success()                      # probe ok: -> closed
    evs = [e for e in TRACE.events() if e["name"] == "breaker.transition"]
    assert [(e["attrs"]["frm"], e["attrs"]["to"]) for e in evs] == \
        [("closed", "open"), ("half_open", "closed")]
    assert METRICS.counter("breaker.b.to_open").snapshot() == 1


# -- health schema + stats thread-safety -------------------------------------

def test_health_schema_pinned_and_json():
    keys = _keys(20_000)
    svc = PlexService(keys, 32, n_shards=2)
    try:
        h = svc.health()
        assert set(h) == {
            "generation", "epoch", "n_keys", "n_pending", "routed_devices",
            "fallback_chain", "breakers", "degraded", "queue_depth",
            "queue_limit", "inflight_batches", "shed_queries",
            "backend_failures", "fallback_lookups", "merge_failures",
            "merge_retry_in_s", "merge_backlog_s", "merge_mode",
            "merge_worker_alive", "journal_ops", "wal_bytes",
            "last_errors", "armed_faults", "closed", "metrics",
        }
        assert set(h["metrics"]) == {
            "enabled", "shard_hotness", "probe_trips", "cache_hits",
            "cache_queries", "full_hit_batches", "registry",
        }
        assert h["metrics"]["enabled"] is False
        json.dumps(h)
        enable_observability()
        svc.lookup(keys[:100])
        json.dumps(svc.health())             # armed snapshot serialises too
    finally:
        svc.close()


def test_health_json_after_chaos_fallback():
    from repro.resilience.faults import FAULTS, POINT_BACKEND_DISPATCH, always
    keys = _keys(20_000)
    svc = PlexService(keys, 32, n_shards=2, backend="jnp",
                      breaker_threshold=1)
    try:
        enable_observability()
        with FAULTS.injected(POINT_BACKEND_DISPATCH,
                             always(backend="jnp")):
            q = keys[:500]
            got = svc.lookup(q)              # degrades to numpy, stays exact
            assert np.array_equal(got, np.searchsorted(keys, q, "left"))
        h = svc.health()
        assert h["degraded"] and h["fallback_lookups"] >= 1
        json.dumps(h)
    finally:
        svc.close()


def test_stats_epoch_rollover_race_free():
    """note_cache_synced vs new_epoch: a stale-epoch fold must be dropped
    atomically, and concurrent folds must never be lost. Hammer the pair
    from threads and check exact conservation."""
    stats = ServiceStats()
    stats.new_epoch(0)
    applied = [0]
    stop = threading.Event()

    def roller():
        e = 0
        while not stop.is_set():
            e += 1
            stats.new_epoch(e)
            time.sleep(0)

    def writer():
        n = 0
        while not stop.is_set():
            if stats.note_cache_synced(1, 2, False, stats.epoch):
                n += 1
        applied[0] += n

    threads = [threading.Thread(target=roller)] + \
        [threading.Thread(target=writer) for _ in range(4)]
    for t in threads[1:]:
        t.start()
    threads[0].start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    final_epoch = stats.epoch
    stats.new_epoch(final_epoch)             # roll once more: counters zero
    assert stats.cache_queries == 0 and stats.cache_hits == 0
    assert applied[0] > 0                    # some folds landed


def test_background_merge_with_obs_stress():
    """Writer inserts past the threshold while readers serve with obs
    armed: final lookups stay exact, health stays JSON-serialisable, and
    the per-epoch live hotness matches the current shard count. A scraper
    thread exports Prometheus text throughout — the merge worker
    registers instruments (``merge.cycles``) concurrently, the exact
    race the locked ``collect()`` snapshot closes."""
    keys = _keys(40_000)
    svc = PlexService(keys.copy(), 32, n_shards=2, backend="numpy",
                      merge_mode="background", merge_threshold=256)
    errors: list[BaseException] = []
    stop = threading.Event()
    try:
        enable_observability()
        rng = np.random.default_rng(9)

        def reader():
            r = np.random.default_rng(10)
            while not stop.is_set():
                model = svc.logical_keys()
                q = np.asarray(model)[r.integers(0, model.size, 500)]
                try:
                    got = svc.lookup(q)
                    want = np.searchsorted(model, q, "left")
                    # a concurrent merge may publish between the capture
                    # and the lookup; exactness is re-checked at the end
                    if got.shape != want.shape:
                        raise AssertionError("shape drift")
                except Exception as e:       # pragma: no cover
                    errors.append(e)
                    return

        def scraper():
            while not stop.is_set():
                try:
                    prometheus_text()
                    json.dumps(METRICS.snapshot())
                except Exception as e:       # pragma: no cover
                    errors.append(e)
                    return

        readers = [threading.Thread(target=reader) for _ in range(2)] + \
            [threading.Thread(target=scraper)]
        for t in readers:
            t.start()
        for _ in range(4):
            svc.insert(np.unique(rng.integers(0, 2**62, 300,
                                              dtype=np.uint64)))
            time.sleep(0.02)
        deadline = time.monotonic() + 30.0
        while svc.n_pending and time.monotonic() < deadline:
            time.sleep(0.01)
        stop.set()
        for t in readers:
            t.join()
        assert not errors, errors
        model = svc.logical_keys()
        q = np.asarray(model)[::29]
        assert np.array_equal(svc.lookup(q),
                              np.searchsorted(model, q, "left"))
        assert svc.live_hotness().size == svc.n_shards
        json.dumps(svc.health())
    finally:
        stop.set()
        svc.close()


# -- bench integration -------------------------------------------------------

def test_serve_bench_latency_percentiles_helper():
    from benchmarks.serve_bench import _latency_percentiles
    keys = _keys(30_000)
    svc = PlexService(keys, 32, n_shards=2)
    try:
        q = np.random.default_rng(11).choice(keys, svc.block * 4)
        p50, p99 = _latency_percentiles(svc, q, "jnp", max_calls=4)
        assert np.isfinite(p50) and np.isfinite(p99)
        assert 0 < p50 <= p99
        assert not METRICS.enabled           # switch restored
        assert METRICS.snapshot()["histograms"] == {}   # state restored
    finally:
        svc.close()


def test_bench_diff_ignores_unknown_fields():
    from benchmarks.bench_diff import _key
    base = {"dataset": "osm", "n": 10, "eps": 16, "backend": "jnp",
            "workload": "uniform", "ns_per_lookup": 100.0}
    extended = dict(base, p50_ns=90.0, p99_ns=500.0, some_future_field=1)
    assert _key(base) == _key(extended)
    # a record missing even identity fields keys without raising
    _key({"ns_per_lookup": 1.0})


# -- tracer robustness under the always-on mode ------------------------------

def test_span_mismatched_exit_restores_depth():
    """Out-of-order exits (outer before inner) must truncate the stale
    frames, not leak them into every later span's depth."""
    tr = Tracer()
    tr.enable()
    a = tr.span("a")
    b = tr.span("b")
    a.__enter__()
    b.__enter__()
    a.__exit__(None, None, None)     # exits while b is still on the stack
    b.__exit__(None, None, None)     # stale frame: must not corrupt depth
    with tr.span("after") as s:
        assert s._depth == 0
    assert tr._stack() == []


def test_span_exception_crossed_exit_restores_depth():
    """A generator-held span abandoned by an exception must not inflate
    depth once the enclosing span exits."""
    tr = Tracer()
    tr.enable()

    def gen():
        with tr.span("leaky"):
            yield 1
            yield 2                  # never reached: span never exits

    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            g = gen()
            next(g)
            del g                    # leaky's frame is now stale
            raise RuntimeError("boom")
    # outer's truncating exit swept the abandoned inner frame with it
    with tr.span("after") as s:
        assert s._depth == 0
    assert tr._stack() == []


def test_trace_cross_thread_interleave_and_soak():
    """record()/event() from sampler/worker-style threads interleave
    safely at the deque, and a long soak holds the bounded-memory
    contract (newest maxlen events kept)."""
    tr = Tracer(maxlen=1024)
    tr.enable()
    errors: list[BaseException] = []

    def hammer(tid: int):
        try:
            for i in range(5000):
                tr.record(f"t{tid}.r", 1e-6, i=i)
                tr.event(f"t{tid}.e", i=i)
                with tr.span(f"t{tid}.s"):
                    pass
        except BaseException as e:   # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    evs = tr.events()
    assert len(evs) == 1024          # soak: bounded, newest kept
    for line in tr.to_jsonl().splitlines():
        json.loads(line)
    # per-thread depths never bled across threads
    assert all(e["depth"] == 0 for e in evs)


def test_span_sampling_keeps_one_in_n():
    tr = Tracer()
    tr.enable()
    tr.sample_n = 4
    for _ in range(100):
        with tr.span("s"):
            pass
    for _ in range(100):
        tr.record("r", 1e-6)
    for _ in range(10):
        tr.event("e")                # events are never sampled
    names = [e["name"] for e in tr.events()]
    assert names.count("s") == 25
    assert names.count("r") == 25
    assert names.count("e") == 10
    tr.sample_n = 1
    tr.clear()
    with tr.span("t"):
        pass
    assert len(tr.events()) == 1     # back to full fidelity


# -- span ids, requests, the profiler's clock, set-up timings -----------------

def test_span_ids_parents_and_requests():
    """Every event carries its id and its parent span's id; a request
    span opens a request id that everything nested under it inherits,
    and post-hoc records and events take their parent from the open
    stack."""
    tr = Tracer()
    tr.enable()
    with tr.span("setup"):
        pass
    with tr.request("serve.lookup", n=3):
        with tr.span("serve.dispatch"):
            tr.record("inner.record", 0.0)
        tr.event("marker")
    with tr.span("after"):
        pass
    by = {e["name"]: e for e in tr.events()}
    ids = [e["id"] for e in tr.events()]
    assert len(set(ids)) == len(ids)
    lookup = by["serve.lookup"]
    assert lookup["parent"] is None and lookup["request"] == lookup["id"]
    assert by["serve.dispatch"]["parent"] == lookup["id"]
    assert by["inner.record"]["parent"] == by["serve.dispatch"]["id"]
    assert by["inner.record"]["depth"] == 2
    assert by["marker"]["parent"] == lookup["id"]
    for name in ("serve.dispatch", "inner.record", "marker"):
        assert by[name]["request"] == lookup["id"]
    for name in ("setup", "after"):
        assert by[name]["parent"] is None and "request" not in by[name]
    with tr.request("serve.submit"):
        pass
    assert tr.events()[-1]["request"] != lookup["request"]


def test_record_parent_began_before_it():
    """A record's parent is the innermost open span that began before the
    recorded interval did, so no child starts before its parent."""
    tr = Tracer()
    tr.enable()
    with tr.span("outer"):
        time.sleep(0.02)
        with tr.span("inner"):
            tr.record("long", 0.01)      # began before inner did
            tr.record("short", 0.0)
        tr.record("older", 10.0)         # began before outer too
    by = {e["name"]: e for e in tr.events()}
    assert by["long"]["parent"] == by["outer"]["id"]
    assert by["long"]["depth"] == 1
    assert by["short"]["parent"] == by["inner"]["id"]
    assert by["older"]["parent"] is None and by["older"]["depth"] == 0


def test_disabled_request_and_timed():
    """Disabled, ``request`` is the shared null context like ``span``, and
    ``timed`` still adds its seconds to the dict without emitting."""
    tr = Tracer()
    assert tr.request("serve.lookup", n=1) is _NULL
    assert tr.span("serve.dispatch") is _NULL
    into: dict = {}
    with tr.timed("phase", into):
        time.sleep(0.01)
    with tr.timed("phase", into):
        pass
    assert into["phase"] >= 0.01 and tr.events() == []
    tr.enable()
    with tr.timed("phase", into, k=1):
        pass
    assert [e["name"] for e in tr.events()] == ["phase"]


def test_obs_imports_without_jax():
    """``obs`` imports, and an enabled span records, where jax cannot be
    imported at all (the annotation is bound only when jax is there)."""
    code = ("import sys\n"
            "sys.modules['jax'] = None\n"
            "import repro.obs as o\n"
            "o.TRACE.enable()\n"
            "with o.TRACE.span('x'):\n"
            "    pass\n"
            "assert o.TRACE.events()[0]['name'] == 'x'\n")
    src = pathlib.Path(TRACE_MODULE.__file__).resolve().parents[2]
    out = subprocess.run([sys.executable, "-c", code],
                         env=dict(os.environ, PYTHONPATH=str(src)),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_serve_spans_on_the_profiler_clock(tmp_path):
    """With TRACE on, a profiler trace around a lookup holds the program's
    serve.* spans as host events under their own names: serve.lookup
    around its staging, dispatch and sync, with no anchor applied."""
    import jax
    from jax.profiler import ProfileData
    keys = _keys(20_000)
    svc = PlexService(keys, 32, n_shards=2, block=1024)
    try:
        svc.warmup()
        q = np.random.default_rng(3).choice(keys, 3000)
        TRACE.enable()
        jax.profiler.start_trace(str(tmp_path))
        try:
            got = svc.lookup(q)
        finally:
            jax.profiler.stop_trace()
        assert np.array_equal(got, np.searchsorted(keys, q))
    finally:
        svc.close()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    by = {}
    for s, e, name in host:
        by.setdefault(name, []).append((s, e))
    assert len(by.get("serve.lookup", ())) == 1
    lo, hi = by["serve.lookup"][0]
    for child in ("serve.staging", "serve.dispatch", "serve.sync"):
        assert len(by.get(child, ())) == 1, sorted(by)
        s, e = by[child][0]
        assert lo <= s <= e <= hi


def test_build_and_warmup_timings_reconcile():
    """``setup_stats`` is always on: the build's phases sum to ``build_s``
    and warm-up names its planes and each program it loads. Traced, the
    build's per-shard and per-phase CPU-seconds are zero-duration events,
    the phases are spans, and no span or event begins before its
    parent."""
    keys = _keys(60_000)
    TRACE.enable()
    svc = PlexService(keys, 32, n_shards=3, build_workers=2, pool="thread",
                      merge_threshold=64)
    try:
        svc.warmup()
        st = svc.setup_stats
        build = [st[k] for k in ("build.pool", "build.shards",
                                 "build.assemble")]
        assert sum(build) == pytest.approx(svc.build_s, rel=0.05)
        for k in ("warmup.planes", "warmup.compile.plain",
                  "warmup.compile.merged"):
            assert st[k] > 0, k
    finally:
        svc.close()
    evs = TRACE.events()
    by_id = {e["id"]: e for e in evs}
    for name in ("build.shard", "build.spline", "build.tune",
                 "build.layer"):
        mine = [e for e in evs if e["name"] == name]
        assert mine and all(e["dur_us"] == 0.0 and e["attrs"]["cpu_s"] >= 0
                            for e in mine), name
    shards = next(e for e in evs if e["name"] == "build.shards")
    assert all(e["parent"] == shards["id"] for e in evs
               if e["name"] == "build.shard")
    names = {e["name"] for e in evs}
    assert {"build.pool", "build.assemble", "warmup.planes",
            "warmup.compile"} <= names
    assert {e["attrs"]["program"] for e in evs
            if e["name"] == "warmup.compile"} == {"plain", "merged"}
    for e in evs:
        if e["parent"] is not None:
            assert e["ts"] >= by_id[e["parent"]]["ts"], e


# -- flight recorder ---------------------------------------------------------

def test_recorder_arm_disarm_and_series():
    rec = FlightRecorder(interval_s=3600.0)   # thread effectively idle
    rec.arm(span_sample=8)
    try:
        assert METRICS.enabled and TRACE.enabled and TRACE.sample_n == 8
        # sampled posture: no counted-dispatch kernels while armed
        assert not METRICS.counted_dispatch
        METRICS.counter("serve.lookups").inc(5)
        METRICS.gauge("queue.depth").set(3.0)
        h = METRICS.histogram("serve.lookup_ns_per_key")
        for v in (100.0, 200.0, 900.0):
            h.observe(v)
        rec.tick(now=1.0)
        METRICS.counter("serve.lookups").inc(2)
        rec.tick(now=2.0)
        assert rec.series("counter.serve.lookups") == [(1.0, 5.0),
                                                       (2.0, 7.0)]
        assert rec.series("gauge.queue.depth")[-1] == (2.0, 3.0)
        assert rec.series("hist.serve.lookup_ns_per_key.count")[-1][1] == 3
        snap = rec.snapshot()
        json.loads(json.dumps(snap))            # bundle payload round-trips
        assert snap["ticks"] == 2 and snap["span_sample"] == 8
    finally:
        rec.disarm()
    assert not METRICS.enabled and not TRACE.enabled
    assert TRACE.sample_n == 1 and METRICS.counted_dispatch
    assert not rec.armed


def test_recorder_sampler_thread_runs_and_stops():
    rec = FlightRecorder(interval_s=0.01)
    rec.arm()
    try:
        deadline = time.monotonic() + 5.0
        while rec.ticks == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rec.ticks > 0
        assert rec.armed
    finally:
        rec.disarm()
    assert not rec.armed
    n = rec.ticks
    time.sleep(0.05)
    assert rec.ticks == n            # really stopped


def test_recorder_bounded_memory_and_probe_containment():
    rec = FlightRecorder(interval_s=3600.0, series_maxlen=8, max_series=4)
    calls = [0]
    rec.add_probe(lambda: calls.__setitem__(0, calls[0] + 1))

    def bad_probe():
        raise RuntimeError("probe boom")

    rec.add_probe(bad_probe)
    rec.arm()
    try:
        for i in range(20):
            METRICS.counter("a").inc()
            METRICS.counter("b").inc()
            METRICS.gauge("c").set(i)
            METRICS.gauge("d").set(i)
            METRICS.gauge(f"overflow.{i}").set(i)   # past max_series
            rec.tick(now=float(i))
        assert len(rec.series("counter.a")) == 8    # ring bounded
        assert len(rec.series_names()) == 4         # series cap held
        assert rec.snapshot()["dropped_series"] > 0
        assert calls[0] == 20                       # good probe ran each tick
        rec.remove_probe(bad_probe)                 # and never killed a tick
    finally:
        rec.disarm()


# -- SLO watchdog ------------------------------------------------------------

def _clocked_watchdog(specs):
    t = [0.0]

    def clock():
        return t[0]

    return SLOWatchdog(specs, clock=clock), t


def test_slo_level_breach_event_and_recovery():
    spec = SLOSpec("p99", ("metrics", "p99"), bound=100.0,
                   windows=(10.0, 40.0), budget=0.5)
    wd, t = _clocked_watchdog([spec])
    enable_observability()
    # healthy samples fill both windows
    for i in range(4):
        t[0] = float(i)
        st = wd.observe({"metrics": {"p99": 50.0}})
    assert st["p99"]["state"] == "ok"
    # sustained violation: the short window saturates fast, the long
    # window's burn crosses 1.0 (budget 0.5) once half its samples are bad
    for i in range(4, 10):
        t[0] = float(i)
        st = wd.observe({"metrics": {"p99": 500.0}})
    assert st["p99"]["state"] == "breach"
    assert st["p99"]["burn"]["10s"] >= 1.0
    assert wd.breaches["p99"] == 1
    breach_evs = [e for e in TRACE.events() if e["name"] == "slo.breach"]
    assert len(breach_evs) == 1 and breach_evs[0]["attrs"]["slo"] == "p99"
    # recovery: good samples age the bad ones out of the short window
    for i in range(10, 22):
        t[0] = float(i)
        st = wd.observe({"metrics": {"p99": 50.0}})
    assert st["p99"]["state"] == "ok"
    assert wd.breaches["p99"] == 1   # no double-count on recovery
    json.dumps(st)


def test_slo_rate_kind_counter_delta():
    spec = SLOSpec("shed", ("shed_queries",), bound=10.0, kind="rate",
                   windows=(5.0, 5.0), budget=0.5)
    wd, t = _clocked_watchdog([spec])
    total = 0
    for i in range(6):
        t[0] = float(i)
        total += 2                   # 2 sheds/s: under the 10/s bound
        st = wd.observe({"shed_queries": total})
    assert st["shed"]["state"] == "ok"
    assert st["shed"]["value"] == pytest.approx(2.0)
    for i in range(6, 12):
        t[0] = float(i)
        total += 100                 # 100/s: way over
        st = wd.observe({"shed_queries": total})
    assert st["shed"]["state"] == "breach"
    # a counter reset (service restart) clamps to 0, never negative
    t[0] = 12.0
    st = wd.observe({"shed_queries": 0})
    assert st["shed"]["value"] == 0.0


def test_slo_missing_field_and_breach_incident(tmp_path):
    wd, t = _clocked_watchdog([
        SLOSpec("x", ("absent", "path"), bound=1.0, windows=(1.0, 1.0))])
    st = wd.observe({"something": 1})     # absent path: no sample, no crash
    assert "value" not in st["x"] and st["x"]["state"] == "ok"
    # a breach writes an slo.<name> incident bundle when one is installed
    incident_mod.install(tmp_path / "inc")
    spec = SLOSpec("err", ("errs",), bound=1.0, windows=(5.0, 5.0),
                   budget=0.9)
    wd, t = _clocked_watchdog([spec])
    for i in range(5):
        t[0] = float(i)
        wd.observe({"errs": 100.0})
    bundles = incident_mod.manager().bundles()
    assert len(bundles) == 1 and bundles[0].name.endswith("slo-err")


def test_default_slos_cover_issue_objectives():
    names = {s.name for s in default_slos()}
    assert names == {"lookup_p99_ns", "fallback_rate", "error_rate",
                     "shed_rate", "merge_backlog_s", "wal_bytes"}
    with pytest.raises(ValueError, match="mode"):
        SLOSpec("bad", ("x",), 1.0, mode="avg")
    with pytest.raises(ValueError, match="budget"):
        SLOSpec("bad", ("x",), 1.0, budget=0.0)


def test_attach_slo_health_section_and_observe():
    keys = _keys(20_000)
    svc = PlexService(keys, 32, n_shards=2)
    try:
        enable_observability()
        # generous latency bound: the first lookup pays JIT compilation,
        # and a one-sample breach would make this test machine-dependent
        wd = svc.attach_slo(SLOWatchdog(default_slos(lookup_p99_ns=1e12)))
        svc.lookup(keys[:svc.block].copy())
        st = wd.observe(svc.health())
        h = svc.health()
        assert set(h["slo"]) == set(st)
        assert all(v["state"] == "ok" for v in h["slo"].values())
        json.dumps(h)
        svc.attach_slo(None)
        assert "slo" not in svc.health()     # schema-additive: detachable
    finally:
        svc.close()


def test_merge_backlog_age_tracks_unmerged_threshold():
    from repro.resilience.faults import FAULTS, POINT_MERGE_BUILD, fail_once
    keys = _keys(20_000)
    svc = PlexService(keys.copy(), 32, n_shards=2, merge_threshold=64,
                      merge_backoff_s=0.0)
    try:
        assert svc.health()["merge_backlog_s"] == 0.0
        with FAULTS.injected(POINT_MERGE_BUILD, fail_once()):
            # crosses the threshold; the auto-merge trips and is contained,
            # so the delta stays over-threshold and the backlog clock runs
            svc.insert(np.unique(np.arange(2**40, 2**40 + 128,
                                           dtype=np.uint64)))
        time.sleep(0.01)
        assert svc.health()["merge_backlog_s"] > 0.0
        assert svc.merge()           # fault cleared: explicit merge lands
        assert svc.health()["merge_backlog_s"] == 0.0
    finally:
        svc.close()


# -- incident bundles --------------------------------------------------------

def _read_bundle(bundle):
    out = {"incident": json.loads((bundle / "incident.json").read_text()),
           "health": json.loads((bundle / "health.json").read_text()),
           "metrics": json.loads((bundle / "metrics.json").read_text())}
    for line in (bundle / "spans.jsonl").read_text().splitlines():
        if line:
            json.loads(line)
    assert (bundle / "metrics.prom").exists()
    return out


def test_incident_bundle_contents_debounce_retention(tmp_path):
    t = [0.0]
    mgr = incident_mod.IncidentManager(
        tmp_path / "inc", debounce_s=10.0, retention=3,
        health_source=lambda: {"generation": 7, "degraded": True},
        clock=lambda: t[0])
    enable_observability()
    METRICS.counter("serve.lookups").inc(9)
    with TRACE.span("serve.lookup", n=4):
        pass
    b = mgr.trigger("breaker.open", "jnp breaker opened",
                    context={"breaker": "jnp"})
    assert b is not None and b.name == "0001-breaker-open"
    got = _read_bundle(b)
    assert got["incident"]["kind"] == "breaker.open"
    assert got["incident"]["context"]["breaker"] == "jnp"
    assert got["incident"]["generation"] == 7    # headline from health
    assert got["health"]["degraded"] is True
    assert got["metrics"]["registry"]["counters"]["serve.lookups"] == 9
    assert "armed_faults" in got["incident"]
    # debounce: same kind within the window is suppressed and counted
    t[0] = 5.0
    assert mgr.trigger("breaker.open", "again") is None
    assert mgr.debounced["breaker.open"] == 1
    # a different kind is fresh
    assert mgr.trigger("queue.shed", "overflow") is not None
    # past the window the kind fires again; retention keeps newest 3
    for i in range(3):
        t[0] = 20.0 + 20.0 * i
        assert mgr.trigger("breaker.open", f"flap {i}") is not None
    names = [p.name for p in mgr.bundles()]
    assert len(names) == 3
    assert names[-1].endswith("breaker-open")
    assert mgr.written == 5


def test_incident_seq_continues_across_install(tmp_path):
    root = tmp_path / "inc"
    incident_mod.install(root).trigger("queue.shed", "x")
    incident_mod.uninstall()
    mgr = incident_mod.install(root)      # fresh manager, same directory
    b = mgr.trigger("queue.shed", "y")
    assert b.name.startswith("0002-")     # sequence resumed, not reset


def test_report_noop_when_uninstalled_and_never_raises(tmp_path):
    incident_mod.report("breaker.open", "nobody listening")  # no-op
    mgr = incident_mod.install(tmp_path / "inc")

    def exploding_health():
        raise RuntimeError("health mid-failure")

    mgr.bind_health(exploding_health)
    incident_mod.report("merge.failure", "health source broken")
    got = _read_bundle(mgr.bundles()[0])
    assert "error" in got["health"]       # captured, not propagated


def test_breaker_open_writes_bundle(tmp_path):
    from repro.resilience.breakers import CircuitBreaker
    incident_mod.install(tmp_path / "inc")
    br = CircuitBreaker("jnp", failure_threshold=2, cooldown_s=0.0)
    br.record_failure(RuntimeError("d1"))
    assert incident_mod.manager().bundles() == []   # below threshold
    br.record_failure(RuntimeError("d2"))           # -> open
    bundles = incident_mod.manager().bundles()
    assert len(bundles) == 1
    got = _read_bundle(bundles[0])
    assert got["incident"]["kind"] == "breaker.open"
    assert got["incident"]["context"]["breaker"] == "jnp"


def test_chain_exhaustion_and_shed_bundles(tmp_path):
    from repro.resilience import BackendUnavailableError, QueueFullError
    from repro.resilience.faults import (FAULTS, POINT_BACKEND_DISPATCH,
                                         always)
    keys = _keys(20_000)
    svc = PlexService(keys.copy(), 32, n_shards=2, backend="jnp",
                      fallback=None, breaker_threshold=100,
                      max_queue=64, overflow="shed", max_delay_s=60.0)
    incident_mod.install(tmp_path / "inc", health_source=svc.health)
    try:
        with FAULTS.injected(POINT_BACKEND_DISPATCH, always(backend="jnp")):
            with pytest.raises(BackendUnavailableError):
                svc.lookup(keys[:100].copy())
        t1 = svc.submit(keys[:60].copy())     # parked sub-block (60 queued)
        t2 = svc.submit(keys[:10].copy())     # 70 > 64: shed
        kinds = [json.loads((b / "incident.json").read_text())["kind"]
                 for b in incident_mod.manager().bundles()]
        assert kinds == ["backend.unavailable", "queue.shed"]
        for b in incident_mod.manager().bundles():
            got = _read_bundle(b)
            # health captured through the service source at trigger time
            assert "generation" in got["health"]
        svc.drain()
        np.testing.assert_array_equal(t1.result(),
                                      np.searchsorted(keys, keys[:60]))
        with pytest.raises(QueueFullError):
            t2.result()
    finally:
        svc.close()


def test_quarantine_and_manifest_bundles(tmp_path):
    from repro.persist.manifest import (CorruptManifestError, Manifest,
                                        read_manifest, write_manifest)
    incident_mod.install(tmp_path / "inc", debounce_s=0.0)
    # corrupt manifest read
    root = tmp_path / "dur"
    root.mkdir()
    write_manifest(root, Manifest.for_generation(0))
    (root / "MANIFEST.json").write_text("{ torn")
    with pytest.raises(CorruptManifestError):
        read_manifest(root)
    kinds = [json.loads((b / "incident.json").read_text())["kind"]
             for b in incident_mod.manager().bundles()]
    assert kinds == ["manifest.corrupt"]
    # LKG quarantine during open(): destroy the newest generation's
    # snapshot so recovery falls back to gen 0 and quarantines gen 1
    from repro.persist.manifest import gen_name
    droot = tmp_path / "svc"
    droot.mkdir()
    keys = _keys(20_000)
    svc = PlexService(keys.copy(), 32, n_shards=2,
                      keep_generations=2, merge_threshold=0)
    try:
        svc.save(droot, fsync=False)
        svc.insert(np.unique(np.arange(2**40, 2**40 + 64,
                                       dtype=np.uint64)))
        assert svc.merge() and svc.generation == 1
    finally:
        svc.close()
    (droot / gen_name(1) / "snapshot.plex").write_bytes(b"garbage")
    svc2 = PlexService.open(droot, fsync=False)
    try:
        assert svc2.generation == 0
        kinds = [json.loads((b / "incident.json").read_text())["kind"]
                 for b in incident_mod.manager().bundles()]
        assert "generation.quarantine" in kinds
    finally:
        svc2.close()
