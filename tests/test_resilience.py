"""Fault-tolerant serving: the chaos matrix.

Every injection point x scenario must end in one of exactly two outcomes —
**parity** (the answer still equals ``np.searchsorted`` over the logical
key array, possibly served degraded through the fallback chain) or a
**typed fast failure** (``resilience.errors`` / the injected exception /
``TimeoutError``). Never a wrong answer, never a hang, and the
last-known-good generation always opens.

Covers: the deterministic fault registry itself, circuit breaker
lifecycle (injectable clock, no sleeps), backend fallback parity on the
sync and queued paths, admission control, ticket/drain timeouts,
merge-failure isolation + backoff recovery, durable commit abort
(manifest/WAL faults), last-known-good ``open()`` with quarantine, and
partition-load device loss (legacy fallback on 1 device; re-plan onto
survivors on the forced-8-device CI leg)."""
import json
import threading
import time

import numpy as np
import pytest

import jax

from repro.persist import gen_name, read_manifest, wal_name
from repro.resilience import (CLOSED, HALF_OPEN, OPEN, BackendUnavailableError,
                              CircuitBreaker, FAULTS, FaultRegistry,
                              InjectedFault, MergeFailedError,
                              NoServableGenerationError, PartitionLoadError,
                              QueueFullError, always, fail_n, fail_once,
                              intermittent)
from repro.resilience.faults import (POINT_BACKEND_DISPATCH,
                                     POINT_BACKEND_FACTORY,
                                     POINT_MANIFEST_COMMIT,
                                     POINT_MERGE_BUILD, POINT_PARTITION_LOAD,
                                     POINT_SNAPSHOT_MAP, POINT_WAL_APPEND,
                                     POINT_WAL_FSYNC, is_injected)
from repro.serving import PlexService
from repro.serving.plex_service import QUARANTINE_DIR

from conftest import sorted_u64

BLOCK = 512

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(autouse=True)
def _clean_faults():
    """No armed scenario may ever leak between tests."""
    FAULTS.reset()
    yield
    FAULTS.reset()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _service(rng, n=20_000, **kw):
    keys = sorted_u64(rng, n)
    kw.setdefault("eps", 32)
    kw.setdefault("n_shards", 2)
    kw.setdefault("block", BLOCK)
    return PlexService(keys.copy(), **kw), keys


def _queries(rng, keys, n_present=2_000, n_absent=200):
    q = np.concatenate([keys[rng.integers(0, keys.size, n_present)],
                        rng.integers(0, 1 << 62, n_absent, dtype=np.uint64)])
    return q, np.searchsorted(keys, q, side="left")


# ---------------------------------------------------------- fault registry ----

def test_registry_scenarios_deterministic():
    reg = FaultRegistry()
    reg.inject("p", fail_n(2))
    for i in range(4):
        if i < 2:
            with pytest.raises(InjectedFault):
                reg.fire("p")
        else:
            reg.fire("p")
    assert reg.trips("p") == 2
    # exhausted scenarios are pruned: the point is disarmed again
    assert reg.active() == {}


def test_registry_context_match_and_cleanup():
    reg = FaultRegistry()
    with reg.injected("p", fail_once(backend="jnp")):
        reg.fire("p", backend="numpy")          # no match, passes
        with pytest.raises(InjectedFault):
            reg.fire("p", backend="jnp")
    reg.fire("p", backend="jnp")                 # disarmed on exit
    assert reg.trips("p") == 1


def test_registry_intermittent_is_seeded():
    def trips(seed):
        reg = FaultRegistry()
        reg.inject("p", intermittent(0.5, seed))
        pattern = []
        for _ in range(64):
            try:
                reg.fire("p")
                pattern.append(0)
            except InjectedFault:
                pattern.append(1)
        return pattern

    a, b = trips(7), trips(7)
    assert a == b                                # same seed, same trips
    assert 0 < sum(a) < 64                       # actually intermittent
    assert trips(8) != a                         # seed matters


def test_registry_custom_exception_type():
    reg = FaultRegistry()
    reg.inject("p", fail_once(exc=OSError))
    with pytest.raises(OSError) as ei:
        reg.fire("p")
    assert is_injected(ei.value)                 # whatever the exc type
    assert not is_injected(OSError("a real one"))


# --------------------------------------------------------- circuit breaker ----

def test_breaker_lifecycle_with_injectable_clock():
    clk = FakeClock()
    br = CircuitBreaker("b", failure_threshold=2, cooldown_s=10.0, clock=clk)
    assert br.state == CLOSED and br.allow()
    br.record_failure(RuntimeError("x"))
    assert br.state == CLOSED                    # below threshold
    br.record_failure(RuntimeError("y"))
    assert br.state == OPEN
    assert not br.allow()                        # open: refused outright
    clk.advance(9.0)
    assert not br.allow()                        # cooldown not elapsed
    clk.advance(2.0)
    assert br.state == HALF_OPEN
    assert br.allow()                            # exactly one probe
    assert not br.allow()                        # concurrent probe refused
    br.record_failure(RuntimeError("z"))         # probe failed
    assert br.state == OPEN and not br.allow()
    clk.advance(11.0)
    assert br.allow()
    br.record_success()                          # probe succeeded
    assert br.state == CLOSED and br.allow()
    snap = br.snapshot()
    assert snap["state"] == CLOSED and snap["opens"] == 2
    json.dumps(snap)                             # health() payload contract


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker("b", failure_threshold=3)
    br.record_failure(RuntimeError())
    br.record_failure(RuntimeError())
    br.record_success()
    br.record_failure(RuntimeError())
    br.record_failure(RuntimeError())
    assert br.state == CLOSED                    # blips never accumulate


# ------------------------------------------------- fallback chain (lookup) ----

@pytest.mark.parametrize("scenario", [
    lambda: fail_once(backend="jnp"),
    lambda: fail_n(3, backend="jnp"),
    lambda: always(backend="jnp"),
    lambda: intermittent(0.5, 11, backend="jnp"),
])
def test_dispatch_fault_matrix_parity(rng, scenario):
    """Every dispatch scenario on the default backend: exact searchsorted
    parity, served through the chain (degraded or primary), never wrong."""
    svc, keys = _service(rng)
    q, exp = _queries(rng, keys)
    with FAULTS.injected(POINT_BACKEND_DISPATCH, scenario()):
        for _ in range(3):
            assert np.array_equal(svc.lookup(q), exp)
    assert np.array_equal(svc.lookup(q), exp)    # clean again once disarmed


def test_fallback_counts_and_breaker_opens_then_recovers(rng):
    clk = FakeClock()
    svc, keys = _service(rng, breaker_threshold=2, breaker_cooldown_s=30.0,
                         breaker_clock=clk)
    q, exp = _queries(rng, keys)
    scen = FAULTS.inject(POINT_BACKEND_DISPATCH, always(backend="jnp"))
    assert np.array_equal(svc.lookup(q), exp)
    assert np.array_equal(svc.lookup(q), exp)
    assert svc.stats.fallback_lookups == 2
    assert svc.stats.breakers["jnp"] == OPEN     # 2 consecutive failures
    trips_when_open = FAULTS.trips(POINT_BACKEND_DISPATCH)
    assert np.array_equal(svc.lookup(q), exp)    # open: jnp skipped outright
    assert FAULTS.trips(POINT_BACKEND_DISPATCH) == trips_when_open
    assert svc.health()["degraded"]
    # cooldown elapses while the backend is healthy again: half-open probe
    # succeeds and the breaker closes
    FAULTS.clear(POINT_BACKEND_DISPATCH)
    clk.advance(31.0)
    assert np.array_equal(svc.lookup(q), exp)
    assert svc.stats.breakers["jnp"] == CLOSED
    assert not svc.health()["degraded"]
    assert scen.kind == "always"                 # handle stays inspectable


def test_chain_exhausted_raises_typed_never_wrong(rng):
    svc, keys = _service(rng, fallback=None)
    q, exp = _queries(rng, keys)
    with FAULTS.injected(POINT_BACKEND_DISPATCH, always(backend="jnp")):
        with pytest.raises(BackendUnavailableError) as ei:
            svc.lookup(q)
        assert ei.value.chain == ("jnp",)
        assert isinstance(ei.value.last_error, InjectedFault)
    assert np.array_equal(svc.lookup(q), exp)


def test_host_backend_dispatch_point_fires(rng):
    svc, keys = _service(rng)
    q, exp = _queries(rng, keys, 200, 20)
    with FAULTS.injected(POINT_BACKEND_DISPATCH, fail_once(backend="numpy")):
        # explicit numpy request: the host path trips, then the chain has
        # nothing after numpy -> typed failure; jnp serving is untouched
        with pytest.raises(BackendUnavailableError):
            svc.lookup(q, backend="numpy")
    assert np.array_equal(svc.lookup(q, backend="numpy"), exp)


def test_factory_fault_falls_back_then_retries(rng):
    svc, keys = _service(rng)
    q, exp = _queries(rng, keys, 500, 50)
    with FAULTS.injected(POINT_BACKEND_FACTORY, fail_once(backend="jnp")):
        assert np.array_equal(svc.lookup(q), exp)    # served via fallback
    assert svc.stats.fallback_lookups == 1
    assert np.array_equal(svc.lookup(q), exp)        # factory retried, jnp
    assert svc.stats.fallback_lookups == 1


def test_warmup_raises_default_backend_compile_error(rng, monkeypatch):
    """A backend the platform's compiler refuses is a configuration error:
    warmup raises the compiler's own error instead of leaving the backend
    cold for the fallback chain to serve around in silence. The test
    steers the platform choice to "tpu", so the Pallas wrappers compile
    instead of interpreting — which the CPU backend refuses."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    keys = sorted_u64(rng, 20_000)
    svc = PlexService(keys.copy(), eps=32, n_shards=2, block=BLOCK,
                      backend="pallas")
    assert svc.stacked_impl().interpret is False
    with pytest.raises(ValueError, match="interpret") as ei:
        svc.warmup()
    assert not is_injected(ei.value)
    assert svc.stats.fallback_lookups == 0


def test_warmup_injected_fault_stays_best_effort(rng):
    """A drill is not a configuration error: an injected dispatch fault
    leaves warmup best-effort (noted, not raised), and lookups still fall
    back along the chain to exact answers."""
    svc, keys = _service(rng, backend="pallas")
    q, exp = _queries(rng, keys)
    with FAULTS.injected(POINT_BACKEND_DISPATCH, always(backend="pallas")):
        svc.warmup()
        assert any("injected fault" in e
                   for e in svc.health()["last_errors"])
        assert np.array_equal(svc.lookup(q), exp)
    assert svc.stats.fallback_lookups == 1


def test_unknown_backend_still_raises_value_error(rng):
    svc, _ = _service(rng, n=5_000, n_shards=1)
    with pytest.raises(ValueError, match="unknown backend"):
        svc.lookup(np.zeros(1, np.uint64), backend="nope")


# ----------------------------------------------------------- queued path ----

def test_queue_dispatch_fault_fills_tickets_via_fallback(rng):
    svc, keys = _service(rng)
    q, exp = _queries(rng, keys, BLOCK * 2, 0)   # two full blocks
    with FAULTS.injected(POINT_BACKEND_DISPATCH, fail_n(1, backend="jnp")):
        t = svc.submit(q)
        out = t.result()
    assert np.array_equal(out, exp)
    assert svc.stats.backend_failures >= 1


def test_queue_total_failure_parks_typed_error_on_ticket(rng):
    svc, keys = _service(rng)
    q, _ = _queries(rng, keys, BLOCK, 0)
    with FAULTS.injected(POINT_BACKEND_DISPATCH, always()):   # every backend
        t = svc.submit(q)
        svc.drain()
        assert t.ready                            # never hangs
        with pytest.raises(BackendUnavailableError):
            t.result()
    # the service recovers for fresh work once disarmed
    q2, exp2 = _queries(rng, keys, 300, 30)
    assert np.array_equal(svc.submit(q2).result(), exp2)


def test_deadline_timer_flush_survives_dispatch_fault(rng):
    svc, keys = _service(rng, max_delay_s=0.01)
    q, exp = _queries(rng, keys, 100, 0)         # sub-block: timer flushes
    with FAULTS.injected(POINT_BACKEND_DISPATCH, fail_n(1, backend="jnp")):
        t = svc.submit(q)
        deadline = time.monotonic() + 5.0
        while not t.ready and time.monotonic() < deadline:
            time.sleep(0.005)
        assert t.ready, "deadline flush must fill the ticket despite faults"
    assert np.array_equal(t.result(), exp)


def test_admission_control_reject_and_shed(rng):
    # max_delay_s keeps the sub-block queue parked so admission is what
    # the second submit actually hits (not a raced deadline flush)
    svc, keys = _service(rng, max_queue=256, max_delay_s=60.0)
    q1 = keys[:200].copy()
    t1 = svc.submit(q1)                          # 200 queued (< block)
    with pytest.raises(QueueFullError):
        svc.submit(keys[:100].copy())            # 300 > 256: rejected
    assert svc.stats.shed_queries == 100
    assert np.array_equal(t1.result(), np.searchsorted(keys, q1))

    svc2, keys2 = _service(rng, max_queue=256, overflow="shed",
                           max_delay_s=60.0)
    t2 = svc2.submit(keys2[:200].copy())
    shed = svc2.submit(keys2[:100].copy())       # shed: error on the ticket
    assert shed.ready
    with pytest.raises(QueueFullError):
        shed.result()
    assert np.array_equal(t2.result(), np.searchsorted(keys2, keys2[:200]))


def test_drain_timeout_on_wedged_lock(rng):
    svc, keys = _service(rng, max_delay_s=60.0)  # timer must not pre-fill
    t = svc.submit(keys[:64].copy())
    holding = threading.Event()
    release = threading.Event()

    def hold():
        with svc._lock:
            holding.set()
            release.wait(5.0)

    thr = threading.Thread(target=hold, daemon=True)
    thr.start()
    assert holding.wait(5.0)
    with pytest.raises(TimeoutError):
        svc.drain(timeout=0.05)
    with pytest.raises(TimeoutError):
        t.result(timeout=0.05)                   # wedged queue raises...
    release.set()
    thr.join()
    assert np.array_equal(t.result(timeout=5.0),  # ...and stays servable
                          np.searchsorted(keys, keys[:64]))


def test_close_is_idempotent_and_context_managed(rng, tmp_path):
    svc, keys = _service(rng, n=10_000)
    svc.save(tmp_path, fsync=False)
    svc.close()
    svc.close()                                  # idempotent
    assert not svc.durable
    with PlexService.open(tmp_path, fsync=False) as back:
        assert back.durable
        assert np.array_equal(back.lookup(keys[:100]),
                              np.searchsorted(keys, keys[:100]))
    assert not back.durable                      # __exit__ closed the WAL
    assert back.health()["closed"]


# ---------------------------------------------------------- merge isolation ----

def test_merge_failure_isolated_old_state_bit_identical(rng):
    """The satellite contract: a mid-merge build failure leaves serving
    bit-identical to the pre-merge state, and the next successful merge
    recovers fully."""
    svc, keys = _service(rng, merge_threshold=64, merge_backoff_s=0.0)
    state_before = svc._state
    ins = rng.integers(0, 1 << 62, 100, dtype=np.uint64)
    with FAULTS.injected(POINT_MERGE_BUILD, fail_once()):
        svc.insert(ins)                          # crosses threshold: auto-
        # merge fires, trips, and is contained — the update itself lands
    assert FAULTS.trips(POINT_MERGE_BUILD) == 1
    assert svc.stats.merge_failures == 1 and svc.stats.merges == 0
    assert svc._state.snapshot is state_before.snapshot   # no swap
    model = np.sort(np.concatenate([keys, ins]))
    q, exp = _queries(rng, model)
    assert np.array_equal(svc.lookup(q), exp)    # delta keeps serving
    # next update retries the merge (backoff 0) and succeeds
    more = rng.integers(0, 1 << 62, 8, dtype=np.uint64)
    svc.insert(more)
    assert svc.stats.merges == 1 and svc.n_pending == 0
    model = np.sort(np.concatenate([model, more]))
    q, exp = _queries(rng, model)
    assert np.array_equal(svc.lookup(q), exp)


def test_explicit_merge_raises_typed_and_backs_off(rng):
    svc, keys = _service(rng, merge_threshold=0, merge_backoff_s=10.0)
    svc.insert(rng.integers(0, 1 << 62, 50, dtype=np.uint64))
    with FAULTS.injected(POINT_MERGE_BUILD, fail_once()):
        with pytest.raises(MergeFailedError):
            svc.merge()
    h = svc.health()
    assert h["merge_failures"] == 1 and h["degraded"]
    assert h["merge_retry_in_s"] > 0
    assert svc.merge()                           # explicit merge ignores
    assert not svc.health()["degraded"]          # backoff, and recovers


def test_durable_commit_fault_leaves_disk_and_memory_untouched(rng,
                                                               tmp_path):
    svc, keys = _service(rng, n=10_000, merge_threshold=0)
    svc.save(tmp_path, fsync=False)
    listing_before = sorted(p.name for p in tmp_path.iterdir())
    svc.insert(rng.integers(0, 1 << 62, 40, dtype=np.uint64))
    for exc in (None, OSError):                  # injected + a real IO type
        scen = fail_once() if exc is None else fail_once(exc=exc)
        with FAULTS.injected(POINT_MANIFEST_COMMIT, scen):
            with pytest.raises(MergeFailedError):
                svc.merge()
        # abort swept the partial generation: disk == committed state
        assert sorted(p.name for p in tmp_path.iterdir()) == listing_before
        assert svc.generation == 0 and svc.n_pending == 40
    assert svc.merge()                           # clean retry commits gen 1
    assert svc.generation == 1
    model = svc.logical_keys()
    back = PlexService.open(tmp_path, fsync=False)
    q, exp = _queries(rng, np.asarray(model))
    assert np.array_equal(back.lookup(q), exp)
    back.close()
    svc.close()


def test_save_seed_fault_aborts_commit_cleanly(rng, tmp_path):
    """``save`` seeds the fresh WAL with the pending delta; a failed seed
    append aborts the whole commit (no partial generation, service stays
    in-memory) and a clean retry publishes everything."""
    svc, keys = _service(rng, n=10_000)
    ins = rng.integers(0, 1 << 62, 30, dtype=np.uint64)
    svc.insert(ins)                              # in-memory: no WAL yet
    with FAULTS.injected(POINT_WAL_APPEND, fail_once()):
        with pytest.raises(InjectedFault):
            svc.save(tmp_path, fsync=False)
    assert not svc.durable
    assert sorted(p.name for p in tmp_path.iterdir()) == []
    svc.save(tmp_path, fsync=False)              # clean retry
    assert svc.durable and svc.generation == 0
    svc.close()
    back = PlexService.open(tmp_path, fsync=False)
    model = np.sort(np.concatenate([keys, ins]))
    assert np.array_equal(np.asarray(back.logical_keys()), model)
    back.close()


def test_wal_append_fault_keeps_served_state_consistent(rng, tmp_path):
    """WAL-before-mutation: a failed append surfaces to the caller with
    the in-memory delta untouched, so durable >= served always holds.
    An *append* fault (before the record write) loses the update on both
    sides; an *fsync* fault happens after the record was written and
    flushed, so the update is durable-but-not-served — recovery replays
    it, which is exactly the ">=" half of the invariant."""
    svc, keys = _service(rng, n=10_000)
    svc.save(tmp_path, fsync=True)
    pending_before = svc.n_pending
    with FAULTS.injected(POINT_WAL_APPEND, fail_once()):
        with pytest.raises(InjectedFault):
            svc.insert(np.asarray([1], dtype=np.uint64))
    assert svc.n_pending == pending_before       # nothing half-applied
    with FAULTS.injected(POINT_WAL_FSYNC, fail_once()):
        with pytest.raises(InjectedFault):
            svc.insert(np.asarray([2], dtype=np.uint64))
    assert svc.n_pending == pending_before       # served state untouched
    svc.insert(np.asarray([3], dtype=np.uint64))  # healthy again
    svc.close()
    back = PlexService.open(tmp_path, fsync=False)
    # key 1 never reached the log; key 2's record did (write+flush ran,
    # only the fsync faulted) so recovery replays it; key 3 is normal
    model = np.sort(np.concatenate(
        [keys, np.asarray([2, 3], dtype=np.uint64)]))
    assert np.array_equal(back.logical_keys(), model)
    back.close()


# ----------------------------------------------------- last-known-good open ----

def _two_generations(rng, tmp_path, n=10_000):
    """A durable store retaining generations 0 and 1 (keep_generations=2)."""
    svc, keys = _service(rng, n=n, merge_threshold=0, keep_generations=2)
    svc.save(tmp_path, fsync=False)
    ins = rng.integers(0, 1 << 62, 200, dtype=np.uint64)
    svc.insert(ins)
    assert svc.merge() and svc.generation == 1
    model = np.asarray(svc.logical_keys())
    svc.close()
    names = sorted(p.name for p in tmp_path.iterdir())
    assert gen_name(0) in names and gen_name(1) in names
    assert wal_name(0) in names and wal_name(1) in names
    return model


def test_keep_generations_retains_fallback_candidates(rng, tmp_path):
    _two_generations(rng, tmp_path)


def test_open_falls_back_to_last_known_good_on_map_fault(rng, tmp_path):
    model = _two_generations(rng, tmp_path)
    with FAULTS.injected(POINT_SNAPSHOT_MAP,
                         fail_once(gen_dir=gen_name(1))):
        back = PlexService.open(tmp_path, fsync=False)
    assert back.generation == 0
    # gen 0 + its retained WAL replay == the exact pre-quarantine logical
    # state: the merge that built gen 1 folded the same WAL'd updates
    assert np.array_equal(np.asarray(back.logical_keys()), model)
    q, exp = _queries(rng, model)
    assert np.array_equal(back.lookup(q), exp)
    # the bad generation is quarantined, the manifest re-committed at 0
    qdir = tmp_path / QUARANTINE_DIR
    assert (qdir / gen_name(1)).is_dir()
    assert read_manifest(tmp_path).generation == 0
    # a durable update after recovery appends + merges normally
    back.insert(np.asarray([7], dtype=np.uint64))
    assert back.merge() and back.generation == 1
    back.close()
    again = PlexService.open(tmp_path, fsync=False)
    assert again.generation == 1
    again.close()


def test_open_recovers_from_real_corruption(rng, tmp_path):
    model = _two_generations(rng, tmp_path)
    snap_file = tmp_path / gen_name(1) / "snapshot.plex"
    snap_file.write_bytes(b"garbage")            # destroyed header
    back = PlexService.open(tmp_path, fsync=False)
    assert back.generation == 0
    assert np.array_equal(np.asarray(back.logical_keys()), model)
    back.close()


def test_open_no_servable_generation_raises_typed(rng, tmp_path):
    svc, _ = _service(rng, n=5_000, n_shards=1)
    svc.save(tmp_path, fsync=False)
    svc.close()
    (tmp_path / gen_name(0) / "snapshot.plex").write_bytes(b"garbage")
    # strict mode surfaces the original validation error
    with pytest.raises(Exception) as ei:
        PlexService.open(tmp_path, fsync=False, recover=False)
    assert not isinstance(ei.value, NoServableGenerationError)
    # recovering mode exhausts (and quarantines) every candidate
    with pytest.raises(NoServableGenerationError):
        PlexService.open(tmp_path, fsync=False)
    assert (tmp_path / QUARANTINE_DIR / gen_name(0)).is_dir()


def test_open_missing_manifest_still_file_not_found(tmp_path):
    with pytest.raises(FileNotFoundError):
        PlexService.open(tmp_path)


# ------------------------------------------------------------ device loss ----

def test_partition_fault_every_device_falls_back_to_legacy(rng):
    # one trip per replan attempt: after every mesh device has been
    # dropped, the router gives up and the legacy path serves instead
    keys = sorted_u64(rng, 20_000)
    n_dev = len(jax.devices())
    with FAULTS.injected(POINT_PARTITION_LOAD, fail_n(n_dev)):
        svc = PlexService(keys.copy(), eps=32, n_shards=2, block=BLOCK,
                          plan=1)
    assert svc.plan is None                      # legacy path, not a crash
    q, exp = _queries(rng, keys)
    assert np.array_equal(svc.lookup(q), exp)
    assert svc.health()["routed_devices"] == 0


def test_partition_load_error_names_the_device(rng):
    from repro.core import Snapshot
    from repro.distrib import partition_stacked, plan_placement
    keys = sorted_u64(rng, 20_000)
    snap = Snapshot.build(keys, eps=32, n_shards=2)
    plan = plan_placement(snap, 2)
    devs = [jax.devices()[0]] * 2
    with FAULTS.injected(POINT_PARTITION_LOAD, fail_once(device=1)):
        with pytest.raises(PartitionLoadError) as ei:
            partition_stacked(snap, plan, devs, block=BLOCK)
    assert ei.value.device_index == 1


@multi_device
def test_device_loss_replans_onto_survivors(rng):
    # a full-mesh plan has no spare device to substitute: dropping the
    # failed one forces a re-plan at reduced capacity (8 -> 7)
    keys = sorted_u64(rng, 40_000)
    with FAULTS.injected(POINT_PARTITION_LOAD, fail_once(device=2)):
        svc = PlexService(keys.copy(), eps=32, n_shards=8, block=BLOCK,
                          plan=8)
    assert svc.plan is not None and svc.plan.n_devices == 7
    q, exp = _queries(rng, keys)
    assert np.array_equal(svc.lookup(q), exp)
    assert svc.health()["routed_devices"] == 7


@multi_device
def test_open_routed_replan_on_device_failure(rng, tmp_path):
    from repro.distrib import open_routed, plan_from_dir
    keys = sorted_u64(rng, 40_000)
    svc = PlexService(keys.copy(), eps=32, n_shards=8, block=BLOCK)
    svc.save(tmp_path, fsync=False)
    svc.close()
    gen_dir = tmp_path / gen_name(0)
    plan = plan_from_dir(gen_dir, 4)
    devs = jax.devices()[:4]
    with FAULTS.injected(POINT_PARTITION_LOAD, fail_once(device=1)):
        with pytest.raises(PartitionLoadError):
            open_routed(gen_dir, plan, devs, block=BLOCK)   # default: raise
    with FAULTS.injected(POINT_PARTITION_LOAD, fail_once(device=1)):
        router, snaps, _ = open_routed(gen_dir, plan, devs, block=BLOCK,
                                       on_device_failure="replan")
    assert router.plan.n_devices == 3
    q, exp = _queries(rng, keys)
    batch = router.dispatch(q, None)
    assert np.array_equal(batch.assemble(q.size), exp)


# ----------------------------------------------------------------- health ----

def test_health_is_json_and_tracks_wal(rng, tmp_path):
    svc, _ = _service(rng, n=10_000)
    h0 = svc.health()
    json.dumps(h0)
    assert h0["generation"] == -1 and h0["wal_bytes"] == 0
    svc.save(tmp_path, fsync=False)
    svc.insert(np.asarray([5], dtype=np.uint64))
    h1 = svc.health()
    json.dumps(h1)
    assert h1["generation"] == 0 and h1["wal_bytes"] > 0
    assert h1["n_pending"] == 1
    assert set(h1["breakers"]) == set(h1["fallback_chain"])
    svc.close()
