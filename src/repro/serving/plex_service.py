"""PlexService — sharded, micro-batched, async, *updatable* PLEX serving.

One serving front-end over the snapshot + delta ownership model:

* **Immutable snapshots.** All read-only state lives in a
  ``core.index.Snapshot``: the sorted key array, per-shard frozen ``PLEX``
  indexes (boundaries snapped to first occurrences; each shard's float32
  rank plane stays < 2^24 positions, the device-path requirement for
  200M-key scale), the shard-minima routing plane, and the lazily-fused
  shard-major stacked device layout. Snapshot arrays are frozen
  (``writeable = False``); nothing on the read path ever mutates them.
  The constructor *adopts* the caller's key array and freezes it in place
  (no defensive copy at 200M-key scale — pass ``keys.copy()`` to keep a
  mutable array).
* **Device-resident delta buffer.** ``insert()``/``delete()`` land in a
  ``DeltaBuffer`` (sorted inserts + tombstones with snapshot
  multiplicities). Every lookup is a **merged** lookup: the jit'd stacked
  pipeline folds the delta's signed-weight rank adjustment into the same
  single dispatch per micro-batch (``kernels.jnp_lookup.delta_rank_adjust``)
  so results equal ``np.searchsorted`` over the logical merged key array;
  host backends (numpy / per-shard fallback / pallas) apply the identical
  adjustment on the host. Read-only epochs keep the delta-free pipeline —
  updatability costs nothing until the first update.
* **Threshold-triggered merge + atomic swap.** When the buffer exceeds
  ``merge_threshold`` entries (or on an explicit ``merge()``), the logical
  key array is materialised and a complete new ``Snapshot`` is rebuilt via
  ``build_plex`` *off the hot path*; the service then publishes the new
  (snapshot, fresh delta) pair with a single reference assignment — the
  atomic-swap contract: a reader that captured the old state keeps a fully
  consistent index, and no reader ever observes a half-built one. Swaps
  start a new stats epoch and a fresh hot-key cache. The rebuild fans the
  per-shard work over a process pool when ``build_workers`` is set
  (``core.parallel_build``), bit-identical to the serial build.
* **Background merge (opt-in).** ``merge_mode="background"`` moves the
  whole merge cycle onto a dedicated worker thread and turns the update
  path lock-free MPSC with respect to merging: ``insert``/``delete``
  append to the WAL/delta and return without ever waiting on an in-flight
  rebuild. The worker captures the delta state (plus a journal sequence
  number) under the lock, then materialises, rebuilds, pre-warms, and
  writes the new generation's snapshot with **no service lock held**;
  mutations accepted meanwhile land in an op journal. Publish re-acquires
  the lock only for the fast tail — seed a fresh WAL with the residual
  journal, one manifest rename, replay the residual into the fresh delta,
  and the same single ``_state`` assignment. Merge failures *and worker
  death* (chaos point ``serving.merge.worker``) are contained exactly like
  sync-mode failures: backoff armed, live state untouched, a fresh worker
  starts on the next update.
* **Single-dispatch stacked routing (jnp backend).** Shard routing, the
  radix->spline->probe pipeline, the per-shard clamp, the global-offset
  fold, and the delta fold all run inside **one** jit'd function per
  micro-batch — no per-shard Python dispatch. Shards whose layers cannot
  be unified fall back to host routing + per-shard dispatch (still with
  the host-side delta adjustment).
* **Async micro-batch pipeline.** ``lookup`` chops query streams into
  fixed ``block``-sized micro-batches, dispatches them all eagerly, and
  syncs once. ``submit()`` queues queries into deadline-driven micro-batch
  formation across callers; full blocks dispatch immediately, and a
  **background timer thread** flushes (and drains) a sub-block remainder
  when the oldest queued query's ``max_delay_s`` deadline expires — tail
  latency is bounded even when no further submit/drain call arrives.
* **Hot-key result cache.** ``cache_slots > 0`` threads a device-side
  direct-mapped cache of *snapshot ranks* through the stacked pipeline —
  the delta folds in after cache resolution, so entries survive updates
  untouched and retire with their snapshot at a swap (no invalidation, no
  reset race with lock-free readers). A micro-batch whose valid lanes
  *all* hit takes a ``lax.cond`` fast path that skips the snapshot
  pipeline entirely — full-hit batches are actually cheaper, still one
  dispatch. Hit accounting masks padded lanes, and counters reset per
  epoch, so ``stats.cache_hit_rate`` is the current snapshot's number.
  Results are bit-identical with the cache on or off.

* **Mesh distribution (opt-in).** ``plan=`` takes the service multi-device:
  an int spans that many mesh ``data``-axis devices (a ``PlacementPlan``
  pins the assignment explicitly). The snapshot's shards are bin-packed
  onto devices from build statics (``distrib.placement``), each device
  holds only its shard-contiguous plane slab (``distrib.partition``), and
  lookups run the **collective-free routed path**
  (``distrib.routed_lookup``): host-side device binning, the existing
  per-device stacked merged pipeline (global row offsets, so devices emit
  final indices), host-side re-permutation — zero cross-device
  communication inside any compiled dispatch, still one dispatch per
  micro-batch per device. A merge re-plans the *new* snapshot and swaps
  plan + partitions + delta replicas together with it. A 1-device plan is
  bit-identical to the legacy path; a plan that fails per-device
  unification falls back to it.

* **Durability (opt-in).** ``save(dir)`` persists the current snapshot as
  a numbered generation (``persist.format``), seeds a fresh WAL segment
  with the live delta, and atomically publishes the generation manifest —
  from then on the service is *durable*: every ``insert()``/``delete()``
  appends a checksummed WAL record **before** mutating the delta buffer,
  and ``merge()`` writes the new generation + rotates the WAL (commit =
  one manifest rename) *before* the in-memory swap, then garbage-collects
  the old generation. ``PlexService.open(dir)`` restarts from the last
  committed generation in **load** time, not build time: the snapshot
  planes are memmapped (no spline scan, no auto-tune, no plane
  re-derivation) and the WAL's valid prefix is replayed into a fresh
  delta; torn WAL tails and uncommitted generations from a crash are
  logged and discarded.

* **Fault tolerance.** Every lookup runs through a **fallback chain**
  guarded by per-backend **circuit breakers**: a failed dispatch (or an
  open breaker) retries the identical merged lookup on the next backend —
  pallas -> jnp -> numpy by default — so degraded serving is slower,
  never wrong; only a fully exhausted chain raises (typed,
  ``BackendUnavailableError``). Merge failures are **isolated**: a thrown
  rebuild/re-plan/durable-commit leaves the live (snapshot, delta,
  router) triple untouched and retries with capped exponential backoff.
  ``open()`` recovers **last-known-good**: an unservable newest
  generation is quarantined and the next older retained one
  (``keep_generations``) serves. A failed device partition drops exactly
  that device and re-plans onto the survivors. ``max_queue`` bounds the
  submit queue (reject or shed, both typed), ``drain(timeout=)`` /
  ``result(timeout=)`` turn a wedged queue into ``TimeoutError``, and
  ``health()`` reports generation, queue depth, WAL bytes, breaker
  states, and recent errors. The chaos story lives in
  ``repro.resilience`` (deterministic fault injection at named points).

Consistency contract: updates (and merges) first drain the submit queue,
so every queued lookup observes the state at its dispatch; lookups then see
delta changes immediately. Mutations are single-writer (serialised under
the service lock); ``lookup`` itself is lock-free and captures one
consistent (snapshot, delta) state per call.

Global contract: for present keys ``lookup`` returns the first-occurrence
index in the *logical* (merged) key array, identical across backends. For
absent keys each backend returns its eps-window lower bound plus the delta
adjustment — exact whenever the snapshot's window is conclusive (always,
for snapshots without duplicate runs wider than eps).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import pathlib
import shutil
import threading
import time
from typing import Iterable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..core.index import BACKENDS, SHARD_MAX_KEYS, LearnedIndex, Snapshot
from ..kernels.backends import get_backend
from ..distrib.partition import partition_stacked
from ..distrib.placement import (PlacementPlan, live_hotness, plan_matches,
                                 plan_placement)
from ..distrib.routed_lookup import RoutedStackedLookup
from ..kernels.jnp_lookup import N_PROBE_BUCKETS, PROBE_MODES
from ..obs.incident import report as _report_incident
from ..obs.metrics import METRICS
from ..obs.trace import TRACE
from ..kernels.pairs import split_u64
from ..kernels.planes import finalize_indices
from ..parallel.sharding import logical_sharding
from ..persist.format import load_snapshot, save_snapshot
from ..persist.manifest import (CorruptManifestError, Manifest, gen_name,
                                read_manifest, wal_name, write_manifest)
from ..persist.wal import OP_DELETE, OP_INSERT, WriteAheadLog
from ..resilience.breakers import (CLOSED, DEFAULT_COOLDOWN_S,
                                   DEFAULT_FAILURE_THRESHOLD, CircuitBreaker)
from ..resilience.errors import (BackendUnavailableError, MergeFailedError,
                                 NoServableGenerationError,
                                 PartitionLoadError, QueueFullError)
from ..resilience.faults import (FAULTS, POINT_BACKEND_DISPATCH,
                                 POINT_MERGE_BUILD, POINT_MERGE_WORKER, fire,
                                 is_injected)
from .delta import DELTA_CAP_MIN, DeltaBuffer, next_pow2

__all__ = ["DEFAULT_BLOCK", "DEFAULT_MERGE_THRESHOLD",
           "DEFAULT_WAL_ROTATE_BYTES", "LookupTicket", "PlexService",
           "ServiceStats", "SHARD_MAX_KEYS", "service_mesh"]

log = logging.getLogger("repro.persist")

_WAL_OPS = {"insert": OP_INSERT, "delete": OP_DELETE}

# one logical rule: query batches shard over the mesh's data axis
_SERVICE_RULES = {"act_batch": ("data",)}

# default micro-batch: large enough to amortise dispatch overhead on every
# backend, small enough that deadline-driven formation stays sub-ms-ish
DEFAULT_BLOCK = 4096

# delta entries that trigger a snapshot rebuild + swap. Sized so the merged
# pipeline's extra bisect depth stays ~log2(4096) = 12 gather rounds.
DEFAULT_MERGE_THRESHOLD = 4096

# WAL bytes that trigger an in-place compaction (checkpoint + pending ops):
# recovery replay stays bounded by the *delta* size regardless of how much
# insert/delete churn an epoch appends. 0 disables rotation.
DEFAULT_WAL_ROTATE_BYTES = 4 << 20

# the canonical degradation order for fallback="auto": every backend
# computes the identical answer, so each step right is slower, never wrong
_CHAIN_ORDER = ("pallas", "jnp", "numpy")

# where open()'s last-known-good recovery moves unservable generations —
# outside every gen-*/wal-* glob, so GC and recovery scans never see them
QUARANTINE_DIR = "quarantine"


@dataclasses.dataclass
class ServiceStats:
    queries: int = 0
    batches: int = 0
    padded_lanes: int = 0
    inflight_batches: int = 0     # dispatched to device, not yet synced
    drained_batches: int = 0      # synced back to the host
    # per-epoch counters (reset by new_epoch on every snapshot swap)
    epoch: int = 0
    cache_queries: int = 0        # valid (unpadded) lanes through the cache
    cache_hits: int = 0           # valid-lane hits
    full_hit_batches: int = 0     # micro-batches served by the fast path
    # update-path counters
    inserts: int = 0
    deletes: int = 0              # logical occurrences removed
    merges: int = 0
    merge_s: float = 0.0          # snapshot rebuild time (build, not serve)
    wal_rotations: int = 0        # durable-WAL compactions (bounded replay)
    # resilience counters
    fallback_lookups: int = 0     # lookups answered by a non-first backend
    backend_failures: int = 0     # dispatch/sync failures (incl. injected)
    merge_failures: int = 0       # contained merge/commit failures
    shed_queries: int = 0         # admission-control rejected/shed lanes
    # per-backend breaker states (mirrors CircuitBreaker.state; the full
    # snapshots live in PlexService.health())
    breakers: dict = dataclasses.field(default_factory=dict)
    # guards the per-epoch cache counters against the background merge
    # worker's ``new_epoch`` rollover racing a serving thread's sync-point
    # adds (check-epoch-then-add must be atomic or a counter from the old
    # epoch can land *after* the reset and pollute the new epoch's rate)
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def note(self, n_queries: int, n_batches: int, n_padded: int) -> None:
        self.queries += n_queries
        self.batches += n_batches
        self.padded_lanes += n_padded

    def note_drained(self, n_batches: int) -> None:
        self.inflight_batches -= n_batches
        self.drained_batches += n_batches

    def note_cache_synced(self, hits: int, queries: int,
                          full_hit: bool, epoch: int) -> bool:
        """Fold one synced micro-batch's cache telemetry into the current
        epoch — atomically dropped when ``epoch`` is stale (the batch was
        dispatched against a snapshot that has since been swapped out).
        Returns whether the fold was applied."""
        with self._lock:
            if epoch != self.epoch:
                return False
            self.cache_queries += queries
            self.cache_hits += hits
            if full_hit:
                self.full_hit_batches += 1
            return True

    def new_epoch(self, epoch: int) -> None:
        """Start a fresh stats epoch at a snapshot swap: cache counters
        restart so ``cache_hit_rate`` describes the *current* snapshot
        instead of mixing epochs (the old epoch's totals stay in the
        cumulative query/batch counters)."""
        with self._lock:
            self.epoch = epoch
            self.cache_queries = 0
            self.cache_hits = 0
            self.full_hit_batches = 0

    @property
    def cache_hit_rate(self) -> float:
        """Valid-lane hit rate for the current epoch (padded lanes are
        excluded from both numerator and denominator)."""
        return self.cache_hits / self.cache_queries if self.cache_queries \
            else 0.0


class LookupTicket:
    """Handle for a ``PlexService.submit`` batch.

    Filled in-place as its micro-batches drain; ``result()`` forces a
    service-wide ``drain()`` when lanes are still outstanding. A ticket
    whose work failed terminally (fallback chain exhausted, queue shed)
    carries the error and re-raises it from ``result()`` — a ticket never
    hangs and never returns partial garbage."""

    def __init__(self, svc: "PlexService", n: int):
        self._svc = svc
        self.n = n
        self._out = np.empty(n, dtype=np.int64)
        self._filled = 0
        self._error: BaseException | None = None

    @property
    def ready(self) -> bool:
        return self._filled >= self.n

    def result(self, timeout: float | None = None) -> np.ndarray:
        """The batch's indices; drains the service when lanes are still
        outstanding. ``timeout`` bounds that drain — on expiry a
        ``TimeoutError`` propagates and the ticket stays valid for a
        later call (a wedged queue raises instead of blocking forever)."""
        if not self.ready:
            self._svc.drain(timeout=timeout)
        if self._error is not None:
            raise self._error
        assert self.ready
        return self._out


@dataclasses.dataclass(frozen=True)
class _ServiceState:
    """The atomically-swapped (snapshot, delta, router) triple. One
    reference assignment publishes all of it together, so a reader can
    never pair a new snapshot with the previous epoch's delta — or a
    routed mesh partition with a snapshot it wasn't cut from. ``router``
    is ``None`` unless the service was built with a placement plan."""
    snapshot: Snapshot
    delta: DeltaBuffer
    router: RoutedStackedLookup | None = None


@dataclasses.dataclass
class _DurableState:
    """Durable-mode attachment: the directory this service persists to,
    the committed generation number, and the open WAL append handle for
    that generation. Swapped as a unit when ``merge()`` rotates
    generations (mutations hold the service lock, so the pair (in-memory
    state, durable state) can never mix epochs)."""
    root: pathlib.Path
    generation: int
    wal: WriteAheadLog
    fsync: bool = True


def service_mesh(devices: Sequence | None = None) -> Mesh:
    """1-D ``data`` mesh over the available jax devices."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, ("data",))


def _coalesce_ops(records: Sequence[tuple[int, np.ndarray]]
                  ) -> Iterable[tuple[int, np.ndarray]]:
    """Merge runs of consecutive same-opcode WAL records into one batched
    op each. Semantics-preserving: inserts within a run commute, deletes
    within a run commute (tombstones are idempotent per key value), and
    the run boundaries keep every insert/delete interleaving intact."""
    run_op: int | None = None
    run: list[np.ndarray] = []
    for op, keys in records:
        if op != run_op and run:
            yield run_op, np.concatenate(run)
            run = []
        run_op = op
        run.append(keys)
    if run:
        yield run_op, np.concatenate(run)


def _gen_num(p: pathlib.Path) -> int | None:
    """Generation number encoded in a ``gen-*`` / ``wal-*`` name, or
    ``None`` for a name that doesn't parse (never delete what we cannot
    identify)."""
    try:
        return int(p.stem.split("-", 1)[1])
    except (IndexError, ValueError):
        return None


def _gc_generations(root: pathlib.Path, keep: int, retain: int = 1) -> None:
    """Remove generation dirs and WAL segments superseded by ``keep``,
    retaining the newest ``retain`` generations (``keep`` itself plus up
    to ``retain - 1`` predecessors — the last-known-good fallback
    candidates for ``PlexService.open``). Called only after the manifest
    has committed ``keep``, so the removals can never touch recoverable
    state. Best-effort: a leftover from a failed removal is re-collected
    on the next rotation."""
    retain = max(int(retain), 1)
    gens = sorted((g for p in root.glob("gen-*")
                   if p.is_dir() and (g := _gen_num(p)) is not None
                   and g <= keep), reverse=True)
    live = set(gens[:retain]) | {keep}
    for p in root.glob("gen-*"):
        if p.is_dir() and _gen_num(p) not in live:
            log.info("gc(%s): removing generation %s", root, p.name)
            shutil.rmtree(p, ignore_errors=True)
    for p in root.glob("wal-*.log"):
        if _gen_num(p) not in live:
            log.info("gc(%s): removing WAL segment %s", root, p.name)
            try:
                p.unlink()
            except OSError:  # pragma: no cover
                pass


def _quarantine(root: pathlib.Path, *paths: pathlib.Path) -> None:
    """Move unservable on-disk state into ``root/quarantine/`` instead of
    deleting it — a bad generation is forensic evidence, and the
    quarantine dir sits outside every ``gen-*``/``wal-*`` glob so GC and
    recovery scans never reconsider it. Best-effort: a path that cannot
    be moved is left in place for the operator."""
    qdir = root / QUARANTINE_DIR
    for p in paths:
        if not p.exists():
            continue
        try:
            qdir.mkdir(exist_ok=True)
            target = qdir / p.name
            if target.is_dir():
                shutil.rmtree(target, ignore_errors=True)
            elif target.exists():
                target.unlink()
            p.rename(target)
            log.warning("quarantine(%s): moved %s aside", root, p.name)
        except OSError as e:  # pragma: no cover - fs-specific
            log.warning("quarantine(%s): could not move %s (%s)", root,
                        p.name, e)


class PlexService:
    """Serve (and update) PLEX lookups across shards and backends."""

    def __init__(self, keys: np.ndarray | None, eps: int = 64, *,
                 n_shards: int | None = None, backend: str = "jnp",
                 block: int = DEFAULT_BLOCK, mesh: Mesh | None = None,
                 probe: str | None = None, cache_slots: int = 0,
                 max_delay_s: float = 0.002,
                 merge_threshold: int = DEFAULT_MERGE_THRESHOLD,
                 plan: PlacementPlan | int | None = None,
                 wal_rotate_bytes: int = DEFAULT_WAL_ROTATE_BYTES,
                 fallback: object = "auto",
                 breaker_threshold: int = DEFAULT_FAILURE_THRESHOLD,
                 breaker_cooldown_s: float = DEFAULT_COOLDOWN_S,
                 breaker_clock=time.monotonic,
                 max_queue: int = 0, overflow: str = "reject",
                 merge_backoff_s: float = 0.05,
                 merge_backoff_cap_s: float = 5.0,
                 keep_generations: int = 1,
                 merge_mode: str = "sync",
                 build_workers: int | None = None,
                 _snapshot: Snapshot | None = None,
                 **build_kw):
        get_backend(backend)          # fail unknown names at construction
        if block % 128 != 0:
            raise ValueError("block must be a multiple of 128 lanes")
        # fail at construction, not at the first serving-path lookup
        if probe is not None and probe not in PROBE_MODES:
            raise ValueError(f"unknown probe mode {probe!r}")
        if cache_slots and cache_slots & (cache_slots - 1):
            raise ValueError("cache_slots must be a power of two")
        if _snapshot is not None:
            # warm start (PlexService.open): adopt a prebuilt snapshot,
            # skip the host-side build entirely
            self.eps = _snapshot.eps
        else:
            keys = np.ascontiguousarray(keys, dtype=np.uint64)
            if keys.size == 0:
                raise ValueError("cannot serve an empty key set")
            if np.any(keys[1:] < keys[:-1]):
                raise ValueError("keys must be sorted")
            self.eps = int(eps)
        self.default_backend = backend
        self.block = int(block)
        self.mesh = mesh if mesh is not None else service_mesh()
        self.probe = probe
        self.cache_slots = int(cache_slots)
        self.max_delay_s = float(max_delay_s)
        self.merge_threshold = int(merge_threshold)
        self.stats = ServiceStats()
        self._n_shards_req = n_shards
        self._build_kw = build_kw
        self._devices = list(self.mesh.devices.flat)
        self.wal_rotate_bytes = int(wal_rotate_bytes)
        # mesh placement request: int = span that many data-axis devices,
        # PlacementPlan = pin the initial assignment (re-planned at merges)
        if isinstance(plan, int):
            if not 1 <= plan <= len(self._devices):
                raise ValueError(f"plan={plan} devices requested but the "
                                 f"mesh has {len(self._devices)}")
        elif plan is not None and not isinstance(plan, PlacementPlan):
            raise ValueError("plan must be an int device count or a "
                             "PlacementPlan")
        self._plan_req = plan

        # resilience: fallback chain + per-backend circuit breakers +
        # admission control + merge backoff. fallback is "auto" (degrade
        # along pallas -> jnp -> numpy from the default backend's
        # position), None (no fallback), or an explicit name sequence.
        if overflow not in ("reject", "shed"):
            raise ValueError("overflow must be 'reject' or 'shed'")
        if max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        if keep_generations < 1:
            raise ValueError("keep_generations must be >= 1")
        if merge_mode not in ("sync", "background"):
            raise ValueError("merge_mode must be 'sync' or 'background'")
        if build_workers is not None and int(build_workers) < 1:
            raise ValueError("build_workers must be >= 1 (None = serial)")
        if isinstance(fallback, str) and fallback != "auto":
            raise ValueError("fallback must be 'auto', None, or a sequence "
                             "of backend names")
        if fallback is not None and fallback != "auto":
            fallback = tuple(fallback)
            for b in fallback:
                get_backend(b)        # fail unknown chain names up front
        self._fallback_req = fallback
        self.max_queue = int(max_queue)
        self.overflow = overflow
        self.keep_generations = int(keep_generations)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self._breaker_clock = breaker_clock
        self.merge_backoff_s = float(merge_backoff_s)
        self.merge_backoff_cap_s = float(merge_backoff_cap_s)
        self._chains: dict[str, tuple[str, ...]] = {}
        self._breakers: dict[str, CircuitBreaker] = {}
        self._chain = self._chain_for(backend)
        for b in self._chain:
            self.stats.breakers[b] = self._breaker(b).state
        self._last_errors: collections.deque = collections.deque(maxlen=16)
        self._consec_merge_failures = 0
        self._merge_retry_at = 0.0
        self._closed = False
        # monotonic timestamp of the moment the delta first crossed the
        # merge threshold without merging (None = no backlog): the age of
        # the oldest over-threshold unmerged write, health's
        # "merge_backlog_s" and the SLO watchdog's backlog objective
        self._backlog_since: float | None = None
        # optional obs.slo.SLOWatchdog; attach_slo() wires it and health()
        # grows a schema-additive "slo" section while attached
        self._slo = None

        # background-merge machinery. _merge_mutex serialises merges with
        # each other (NOT with mutations: the lock order is _merge_mutex ->
        # _lock, and a background merge never holds _lock across the
        # rebuild). The op journal records every accepted mutation since
        # the last merge capture point (background mode only), so the
        # publish phase can replay the residual — ops accepted while the
        # rebuild ran — into the fresh delta without ever blocking writers.
        self.merge_mode = merge_mode
        self.build_workers = None if build_workers is None \
            else int(build_workers)
        self._merge_mutex = threading.Lock()
        self._merge_wakeup = threading.Event()
        self._merge_worker: threading.Thread | None = None
        self._op_seq = 0
        self._op_journal: collections.deque = collections.deque()

        # fixed delta capacity: the merge threshold bounds the buffer, so
        # sizing the device view to it up front means the merged pipeline
        # compiles once per snapshot, never mid-stream on capacity growth
        # (manual-merge services, threshold 0, grow geometrically instead)
        self._delta_capacity = max(
            next_pow2(max(self.merge_threshold, 1)), DELTA_CAP_MIN)
        snap = _snapshot if _snapshot is not None else Snapshot.build(
            keys, eps, n_shards=n_shards, backend=backend,
            block=self.block, devices=self._devices,
            workers=self.build_workers, **build_kw)
        self._state = _ServiceState(
            snap, DeltaBuffer(snap.keys, capacity=self._delta_capacity),
            self._make_router(snap))
        # live per-shard routed-query counts + probe-trip histogram for the
        # *current* epoch, folded from the device counter planes at sync
        # points while METRICS is armed. Per-epoch by design (reset at each
        # snapshot publish): shard identities change across a merge, so a
        # cumulative fold would blend incompatible shard maps. Host numpy,
        # written only under best-effort telemetry contract.
        self._hotness = np.zeros(snap.n_shards, np.int64)
        self._probe_hist = np.zeros(N_PROBE_BUCKETS, np.int64)
        # durable-mode attachment (None = in-memory only); load_s is the
        # wall time PlexService.open spent mapping + replaying
        self._dur: _DurableState | None = None
        self.load_s = 0.0

        # fixed per-service: micro-batch query planes shard over "data"
        self._batch_sharding = logical_sharding(
            ("act_batch",), (self.block,), self.mesh, _SERVICE_RULES)
        # preallocated staging buffers: final-micro-batch padding reuses
        # these instead of concatenating a fresh array per call. They are
        # *thread-local* because lookup() is lock-free — concurrent readers
        # must not stage tails into one shared buffer — and safe to reuse
        # per call within a thread: every lookup path syncs before
        # returning, so a staged batch can never still be in flight at the
        # same thread's next staging.
        self._staging = threading.local()
        # submit()/drain() queue: chunks are [ticket, queries, consumed,
        # arrival]; outstanding holds dispatched-but-unsynced batches.
        # All queue/update mutation is serialised under the RLock (submit,
        # drain, the deadline timer thread, insert/delete/merge).
        self._q_chunks: collections.deque = collections.deque()
        self._q_len = 0
        self._outstanding: list[tuple] = []
        self._lock = threading.RLock()
        self._timer: threading.Timer | None = None

    # -- metadata -----------------------------------------------------------
    @property
    def keys(self) -> np.ndarray:
        """The *snapshot* key array (immutable). See ``logical_keys()`` for
        the merged view including pending updates."""
        return self._state.snapshot.keys

    @property
    def offsets(self) -> np.ndarray:
        return self._state.snapshot.offsets

    @property
    def shard_min(self) -> np.ndarray:
        return self._state.snapshot.shard_min

    @property
    def shards(self) -> Sequence[LearnedIndex]:
        return self._state.snapshot.shards

    @property
    def n_shards(self) -> int:
        return self._state.snapshot.n_shards

    @property
    def build_s(self) -> float:
        return self._state.snapshot.build_s

    @property
    def size_bytes(self) -> int:
        return self._state.snapshot.size_bytes

    @property
    def epoch(self) -> int:
        return self._state.snapshot.epoch

    @property
    def n_keys(self) -> int:
        """Logical key count (snapshot plus pending delta)."""
        state = self._state
        return state.snapshot.n_keys + state.delta.net_keys

    @property
    def n_pending(self) -> int:
        """Delta entries buffered since the last merge."""
        return self._state.delta.n_entries

    @property
    def name(self) -> str:
        return "PlexService"

    def logical_keys(self) -> np.ndarray:
        """Materialise the logical merged key array (for verification and
        merge; the serve path never needs it)."""
        state = self._state
        if state.delta.empty:
            return state.snapshot.keys
        return state.delta.logical_keys()

    # -- routed mesh path (distrib) -----------------------------------------
    @property
    def plan(self) -> PlacementPlan | None:
        """The active placement plan (``None`` when serving single-device
        or the plan path fell back to the legacy pipeline)."""
        router = self._state.router
        return router.plan if router is not None else None

    def _make_router(self, snap: Snapshot) -> RoutedStackedLookup | None:
        """Build the routed mesh path for ``snap`` from the placement
        request, or ``None`` when no plan was requested / a device's shard
        subset failed unification (legacy fallback). A user-pinned
        ``PlacementPlan`` is honoured only while it matches ``snap``'s
        exact shard table (``plan_matches`` — count AND offsets AND
        boundary keys: a merge shifts offsets/minima even at an unchanged
        shard count, and routing with stale boundaries would silently
        misbin queries); otherwise the plan is re-derived for the same
        device span (placement is snapshot-scoped state, exactly like the
        stacked planes)."""
        req = self._plan_req
        if req is None or get_backend(self.default_backend).stacked_factory \
                is None:
            return None
        devices = list(self._devices)
        if isinstance(req, PlacementPlan) and plan_matches(
                req, snap.offsets, snap.keys.size, snap.shard_min):
            plan = req
        else:
            n_dev = req.n_devices if isinstance(req, PlacementPlan) else req
            # skew-aware re-plan: the live routed-query fold (when armed
            # and fresh for this shard count) scales the static weights, so
            # a merge-triggered re-plan packs fewer hot shards per device.
            # getattr: the __init__-time call runs before the fold exists.
            hot = live_hotness(getattr(self, "_hotness", None), snap.n_shards)
            plan = plan_placement(snap, min(int(n_dev), len(devices)),
                                  hotness=hot)
        while True:
            try:
                parts = partition_stacked(snap, plan, devices,
                                          block=self.block, probe=self.probe,
                                          cache_slots=self.cache_slots,
                                          backend=self.default_backend)
            except PartitionLoadError as e:
                # device loss: drop exactly the failed device and re-plan
                # the same snapshot onto the survivors (degraded capacity,
                # identical routing math); with no survivors left, serve
                # the legacy single-device path instead
                self._note_error(e)
                _report_incident("device.loss", str(e),
                                 device_index=int(e.device_index),
                                 survivors=len(devices) - 1)
                if len(devices) <= 1:
                    log.warning("router: %s; no surviving device to "
                                "re-plan onto, falling back to the legacy "
                                "path", e)
                    return None
                dropped = devices.pop(e.device_index)
                log.warning("router: %s; re-planning onto %d surviving "
                            "device(s) (dropped %r)", e, len(devices),
                            dropped)
                plan = plan_placement(
                    snap, min(plan.n_devices, len(devices)),
                    hotness=live_hotness(getattr(self, "_hotness", None),
                                         snap.n_shards))
                continue
            break
        if parts is None:
            return None
        return RoutedStackedLookup(plan, parts, self.block)

    def _routed_lookup(self, state: _ServiceState, q: np.ndarray
                       ) -> np.ndarray:
        """Whole-batch routed mesh (merged) lookup: host device binning,
        eager per-device micro-batch dispatch, one sync, host
        re-permutation. Stats accounting mirrors the stacked path."""
        epoch = self.stats.epoch
        with TRACE.span("serve.dispatch", path="routed", n=q.size):
            batch = state.router.dispatch(q, self._delta_view(state))
        self.stats.inflight_batches += batch.n_batches
        self.stats.note(q.size, batch.n_batches, batch.padded_lanes)
        with TRACE.span("serve.sync", path="routed", n=q.size):
            out = batch.assemble(q.size)   # the one sync point
        for _, count, lanes in batch.spans:
            for i, res in enumerate(lanes):
                nv = min(self.block, max(count - i * self.block, 1))
                self._note_synced(res, epoch, nv)
        self.stats.note_drained(batch.n_batches)
        if METRICS.enabled:
            router = state.router
            n_shards = state.snapshot.n_shards
            for d in router.plan.active:
                part = router.parts[d]
                if part.impl is not None:
                    self._fold_impl_counters(part.impl, n_shards,
                                             base=part.shard_lo)
        return out

    # -- stacked single-dispatch path ---------------------------------------
    def stacked_impl(self, state: _ServiceState | None = None,
                     backend: str | None = None):
        """The fused shard-major stacked path of ``state``'s snapshot (the
        current one by default) on ``backend`` (the service default when
        omitted), or ``None`` when the shards' static parameters could not
        be unified (per-shard fallback). Callers that already captured a
        state MUST pass it, so a concurrent swap can never pair one
        snapshot's planes with another epoch's delta."""
        state = state if state is not None else self._state
        return state.snapshot.stacked_impl(
            backend or self.default_backend,
            block=self.block, probe=self.probe, cache_slots=self.cache_slots)

    @staticmethod
    def _delta_view(state: _ServiceState):
        """Device delta planes for merged dispatch (``None`` when the epoch
        is read-only, keeping the delta-free pipeline)."""
        return None if state.delta.empty else state.delta.device_view()

    def _dispatch_planes(self, st, qhi: np.ndarray, qlo: np.ndarray,
                         n_valid: int, delta):
        """One micro-batch of query planes -> async device result. The one
        host->device round trip of the stacked path: two plane puts in, one
        fused jit dispatch (merged with the delta when one is live),
        nothing synced."""
        qhi = jax.device_put(qhi, self._batch_sharding)
        qlo = jax.device_put(qlo, self._batch_sharding)
        res = st.lookup_planes(qhi, qlo, n_valid=n_valid, delta=delta)
        self.stats.inflight_batches += 1
        return res

    def _note_synced(self, res, epoch: int, n_valid: int = 0) -> None:
        """Fold one synced ``LaneResult``'s cache telemetry into the stats
        (called only after the host has materialised the batch). ``epoch``
        is the stats epoch the batch was dispatched under: a batch that
        straddled a snapshot swap is dropped from the fresh epoch's
        counters atomically (``ServiceStats.note_cache_synced`` holds the
        stats lock across the epoch check and the adds), so a swap can
        never leave ``cache_hits`` without its matching ``cache_queries``
        and a stale batch can never pollute the fresh epoch's rate."""
        if res.hits is not None:
            self.stats.note_cache_synced(
                int(res.hits), int(n_valid),
                bool(np.asarray(res.full_hit)), epoch)

    # -- live hotness / observability folds ----------------------------------
    def _fold_hotness(self, shard_counts, probe_hist,
                      n_shards: int, base: int = 0) -> None:
        """Fold one device counter plane (per-shard routed counts at global
        shard offset ``base`` + probe-trip histogram) into the service's
        per-epoch live estimate and mirror it into ``METRICS``. Guarded:
        a fold whose shard count no longer matches the live array straddled
        a snapshot swap and is dropped (per-epoch semantics)."""
        h = self._hotness
        if h.size != n_shards or shard_counts is None:
            return
        counts = np.asarray(shard_counts, np.int64)
        h[base:base + counts.size] += counts
        self._probe_hist += np.asarray(probe_hist, np.int64)
        METRICS.counter("serve.routed_queries").inc(int(counts.sum()))
        vec = METRICS.vector("serve.shard.routed", n_shards)
        full = np.zeros(n_shards, np.int64)
        full[base:base + counts.size] = counts
        vec.add(full)
        METRICS.vector("serve.probe.trips", N_PROBE_BUCKETS).add(
            np.asarray(probe_hist, np.int64))

    def _fold_impl_counters(self, impl, n_shards: int,
                            base: int = 0) -> None:
        """Drain ``impl``'s device counter plane (if it has one) into the
        live fold. Never raises: telemetry must not fail serving."""
        try:
            taken = impl.take_counters()
            if taken is not None:
                self._fold_hotness(taken[0], taken[1], n_shards, base)
        except Exception as e:          # pragma: no cover - defensive
            self._note_error(e)

    def _tail_planes(self, qh_all: np.ndarray, ql_all: np.ndarray,
                     start: int) -> tuple[np.ndarray, np.ndarray]:
        """Stage the final partial micro-batch into this thread's
        preallocated tail buffers, padded by repeating the last plane
        values (reuse contract on ``_staging``)."""
        st = self._staging
        if not hasattr(st, "tail_hi"):
            st.tail_hi = np.empty(self.block, dtype=np.uint32)
            st.tail_lo = np.empty(self.block, dtype=np.uint32)
        th, tl = st.tail_hi, st.tail_lo
        rem = qh_all.size - start
        th[:rem] = qh_all[start:]
        th[rem:] = qh_all[-1]
        tl[:rem] = ql_all[start:]
        tl[rem:] = ql_all[-1]
        return th, tl

    def _block_planes(self, qh_all: np.ndarray, ql_all: np.ndarray
                      ) -> Iterable[tuple[np.ndarray, np.ndarray]]:
        """Block-shaped (hi, lo) plane micro-batches: full-block views of
        the split planes, then the staged padded tail."""
        b = self.block
        n_full, rem = divmod(qh_all.size, b)
        for i in range(n_full):
            sl = slice(i * b, (i + 1) * b)
            yield qh_all[sl], ql_all[sl]
        if rem:
            yield self._tail_planes(qh_all, ql_all, n_full * b)

    def _stacked_lookup(self, st, q: np.ndarray,
                        state: _ServiceState) -> np.ndarray:
        """Whole-batch stacked (merged) lookup: split once, dispatch every
        micro-batch eagerly, sync once at the end."""
        b = self.block
        epoch = self.stats.epoch
        delta = self._delta_view(state)
        with TRACE.span("serve.staging", n=q.size):
            qh_all, ql_all = split_u64(q)
        with TRACE.span("serve.dispatch", path="stacked", n=q.size):
            outs = [self._dispatch_planes(st, qh, ql,
                                          min(b, q.size - i * b), delta)
                    for i, (qh, ql) in enumerate(
                        self._block_planes(qh_all, ql_all))]
        n_batches = len(outs)
        self.stats.note(q.size, n_batches, n_batches * b - q.size)
        # one sync point: host materialisation of the eagerly-queued results
        with TRACE.span("serve.sync", path="stacked", n=q.size):
            res = np.concatenate([np.asarray(o.out) for o in outs])[:q.size]
        for i, o in enumerate(outs):
            self._note_synced(o, epoch, min(b, q.size - i * b))
        self.stats.note_drained(n_batches)
        if METRICS.enabled:
            self._fold_impl_counters(st, state.snapshot.n_shards)
        return res.astype(np.int64)

    # -- resilience ---------------------------------------------------------
    def _chain_for(self, backend: str) -> tuple[str, ...]:
        """The fallback chain starting at ``backend``: the requested
        backend first, then each configured fallback that is actually
        registered. ``"auto"`` degrades along pallas -> jnp -> numpy from
        the requested backend's position (a custom backend falls back to
        jnp then numpy); an explicit sequence is honoured in order;
        ``None`` disables fallback entirely."""
        chain = self._chains.get(backend)
        if chain is not None:
            return chain
        req = self._fallback_req
        if req is None:
            tail: tuple[str, ...] = ()
        elif req == "auto":
            start = _CHAIN_ORDER.index(backend) + 1 \
                if backend in _CHAIN_ORDER else 1
            tail = _CHAIN_ORDER[start:]
        else:
            tail = req
        out = [backend]
        for b in tail:
            if b in out:
                continue
            try:
                get_backend(b)
            except ValueError:
                continue
            out.append(b)
        chain = tuple(out)
        self._chains[backend] = chain
        return chain

    def _breaker(self, backend: str) -> CircuitBreaker:
        br = self._breakers.get(backend)
        if br is None:
            # setdefault keeps exactly one breaker under lock-free races
            br = self._breakers.setdefault(backend, CircuitBreaker(
                backend, failure_threshold=self.breaker_threshold,
                cooldown_s=self.breaker_cooldown_s,
                clock=self._breaker_clock))
        return br

    def _record_breaker(self, br: CircuitBreaker, ok: bool,
                        error: BaseException | None = None) -> None:
        if ok:
            br.record_success()
        else:
            br.record_failure(error)
        self.stats.breakers[br.name] = br.state

    def _note_error(self, e: BaseException) -> None:
        """Bounded error journal surfaced by ``health()`` (deque appends
        are atomic, so lock-free readers may note errors too)."""
        self._last_errors.append(f"{type(e).__name__}: {e}")

    def health(self) -> dict:
        """One JSON-friendly operational snapshot: what an operator (or
        the chaos CI job) needs to tell *degraded* from *broken* —
        generation, queue depth, WAL size, breaker states, recent errors.
        Lock-free; safe to poll from a monitoring thread while serving."""
        state = self._state
        dur = self._dur
        wal_bytes = 0
        if dur is not None and not dur.wal.closed:
            wal_bytes = dur.wal.size_bytes
        breakers = {n: b.snapshot()
                    for n, b in sorted(self._breakers.items())}
        retry_in = max(0.0, self._merge_retry_at - time.monotonic()) \
            if self._consec_merge_failures else 0.0
        backlog = 0.0 if self._backlog_since is None \
            else time.monotonic() - self._backlog_since
        out = {
            "generation": self.generation,
            "epoch": int(state.snapshot.epoch),
            "n_keys": int(state.snapshot.n_keys + state.delta.net_keys),
            "n_pending": int(state.delta.n_entries),
            "routed_devices": state.router.plan.n_devices
            if state.router is not None else 0,
            "fallback_chain": list(self._chain),
            "breakers": breakers,
            "degraded": any(b["state"] != CLOSED for b in breakers.values())
            or self._consec_merge_failures > 0,
            "queue_depth": int(self._q_len),
            "queue_limit": int(self.max_queue),
            "inflight_batches": int(self.stats.inflight_batches),
            "shed_queries": int(self.stats.shed_queries),
            "backend_failures": int(self.stats.backend_failures),
            "fallback_lookups": int(self.stats.fallback_lookups),
            "merge_failures": int(self.stats.merge_failures),
            "merge_retry_in_s": round(retry_in, 3),
            # age of the oldest over-threshold unmerged delta (0 = none):
            # the write path's SLO input — a growing value means merges
            # are not keeping up with (or failing behind) the update rate
            "merge_backlog_s": round(backlog, 3),
            "merge_mode": self.merge_mode,
            "merge_worker_alive": self._merge_worker is not None
            and self._merge_worker.is_alive(),
            "journal_ops": len(self._op_journal),
            "wal_bytes": int(wal_bytes),
            "last_errors": list(self._last_errors),
            "armed_faults": FAULTS.active(),
            "closed": self._closed,
            # schema-additive observability section (PR 9): the live
            # per-epoch hotness/probe estimates plus the full registry
            "metrics": {
                "enabled": bool(METRICS.enabled),
                "shard_hotness": [int(x) for x in self._hotness],
                "probe_trips": [int(x) for x in self._probe_hist],
                "cache_hits": int(self.stats.cache_hits),
                "cache_queries": int(self.stats.cache_queries),
                "full_hit_batches": int(self.stats.full_hit_batches),
                "registry": METRICS.snapshot(),
            },
        }
        slo = self._slo
        if slo is not None:
            # schema-additive, present only while a watchdog is attached
            out["slo"] = slo.status()
        return out

    def attach_slo(self, watchdog):
        """Attach an ``obs.slo.SLOWatchdog`` (or ``None`` to detach):
        while attached, ``health()`` carries a schema-additive ``"slo"``
        section with each objective's state and burn rates. The service
        never drives the watchdog itself — a flight-recorder probe (see
        ``obs.slo.watch_service``) or any caller loop feeds it
        ``observe(health())`` samples. Returns the watchdog."""
        self._slo = watchdog
        return watchdog

    def live_hotness(self) -> np.ndarray:
        """Per-shard routed-query counts for the current epoch, accumulated
        by the device counter planes while ``obs.METRICS`` is armed (zeros
        when observability was never enabled this epoch). This is the live
        equivalent of ``distrib.placement.shard_hotness`` over the served
        stream, and feeds the placement re-plan at the next merge."""
        return self._hotness.copy()

    def probe_trip_hist(self) -> np.ndarray:
        """Per-epoch log2-bucketed probe-travel histogram (bucket 0 =
        prediction exactly right; bucket ``b`` = travel in
        ``[2**(b-1), 2**b)`` rows) from the device counter planes."""
        return self._probe_hist.copy()

    # -- serving ------------------------------------------------------------
    def route(self, q: np.ndarray) -> np.ndarray:
        """Shard id per query (largest shard whose min key is <= q)."""
        return self._state.snapshot.route(q)

    def _microbatches(self, q: np.ndarray) -> Iterable[np.ndarray]:
        """Fixed ``block``-sized micro-batches; the final one is padded by
        repeating the last query into this thread's preallocated staging
        buffer (no per-call concatenate churn; reuse contract on
        ``_staging``)."""
        b = self.block
        n_full, rem = divmod(q.size, b)
        for i in range(n_full):
            yield q[i * b:(i + 1) * b]
        if rem:
            st = self._staging
            if not hasattr(st, "mb_buf"):
                st.mb_buf = np.empty(b, dtype=np.uint64)
            buf = st.mb_buf
            buf[:rem] = q[n_full * b:]
            buf[rem:] = q[-1]
            yield buf

    def _lookup_shard(self, shard: LearnedIndex, q: np.ndarray,
                      backend: str, offset: int) -> np.ndarray:
        """Per-shard fallback: micro-batched *snapshot* lookup of ``q`` (all
        routed to ``shard``), global ``offset`` folded in on the host; the
        caller adds the delta adjustment. Accelerated backends dispatch
        every micro-batch eagerly and sync once."""
        n = q.size
        b = self.block
        n_batches = -(-n // b)
        if get_backend(backend).host:
            out = np.empty(n, dtype=np.int64)
            for i, mb in enumerate(self._microbatches(q)):
                take = min(b, n - i * b)
                out[i * b:i * b + take] = shard.lookup(mb,
                                                      backend=backend)[:take]
        else:
            # single-shard stacked impl (a lone shard always unifies); its
            # out is already clamped with the shard-local offset (0) folded
            st = shard.stacked_impl(backend, probe=self.probe)
            # co-locate micro-batches with a mesh-pinned shard's planes
            put = (functools.partial(jax.device_put, device=shard.device)
                   if shard.device is not None else lambda a: a)
            qh_all, ql_all = split_u64(np.ascontiguousarray(q))
            devs = [st.lookup_planes(put(qh), put(ql)).out
                    for qh, ql in self._block_planes(qh_all, ql_all)]
            self.stats.inflight_batches += n_batches
            out = finalize_indices(
                np.concatenate([np.asarray(d) for d in devs]), n,
                shard.keys.size)
            self.stats.note_drained(n_batches)
        self.stats.note(n, n_batches, n_batches * b - n)
        return out + offset

    def lookup(self, q: np.ndarray, backend: str | None = None) -> np.ndarray:
        """Global first-occurrence index per query key in the *logical*
        (snapshot plus delta) key array.

        Served through the fallback chain: the requested backend first,
        then — on a dispatch failure or an open circuit breaker — each
        configured fallback, all computing the identical answer (degraded
        is slower, never wrong). A lookup fails only when the whole chain
        is exhausted, as ``BackendUnavailableError``."""
        backend = backend or self.default_backend
        get_backend(backend)  # unknown names raise here, not as chain noise
        q = np.ascontiguousarray(q, dtype=np.uint64)
        if q.size == 0:
            return np.zeros(0, dtype=np.int64)
        if not (METRICS.enabled or TRACE.enabled):
            return self._lookup_chain(q, backend)
        t0 = time.perf_counter()
        with TRACE.span("serve.lookup", backend=backend, n=q.size):
            out = self._lookup_chain(q, backend)
        if METRICS.enabled:
            dur = time.perf_counter() - t0
            METRICS.histogram("serve.lookup_us").observe(dur * 1e6)
            METRICS.histogram("serve.lookup_ns_per_key").observe(
                dur * 1e9 / q.size)
        return out

    def _lookup_chain(self, q: np.ndarray, backend: str) -> np.ndarray:
        """The fallback-chain walk behind ``lookup`` (observability hooks
        live in the wrapper so the unobserved path stays untouched)."""
        state = self._state       # one consistent (snapshot, delta) capture
        chain = self._chain_for(backend)
        last_err: BaseException | None = None
        for b in chain:
            br = self._breaker(b)
            if not br.allow():
                continue          # open breaker: skip the known-bad backend
            try:
                out = self._lookup_backend(state, q, b)
            except Exception as e:
                self.stats.backend_failures += 1
                self._note_error(e)
                self._record_breaker(br, False, e)
                last_err = e
                log.warning("lookup: backend %r failed (%s)%s", b, e,
                            "; falling back" if b != chain[-1] else "")
                continue
            self._record_breaker(br, True)
            if b != backend:
                self.stats.fallback_lookups += 1
            return out
        err = BackendUnavailableError(chain, last_err)
        _report_incident("backend.unavailable", str(err),
                         health=self.health, chain=list(chain),
                         last_error=repr(last_err)
                         if last_err is not None else None)
        raise err from last_err

    def _lookup_backend(self, state: _ServiceState, q: np.ndarray,
                        backend: str) -> np.ndarray:
        """One backend's merged lookup over a captured state: routed mesh,
        fused stacked, or host/per-shard fallback — identical results on
        every path (the chain in ``lookup`` relies on that)."""
        spec = get_backend(backend)
        if spec.stacked_factory is not None:
            # the router is built for (and its parts placed by) the default
            # backend; other stacked backends take the single-device path
            if state.router is not None and backend == self.default_backend:
                return self._routed_lookup(state, q)
            st = self.stacked_impl(state, backend)
            if st is not None:
                return self._stacked_lookup(st, q, state)
        if spec.host:
            # host backends have no built impl to instrument, so the
            # dispatch injection point fires here instead
            fire(POINT_BACKEND_DISPATCH, backend=backend)
        snap = state.snapshot
        if snap.n_shards == 1:
            out = self._lookup_shard(snap.shards[0], q, backend, 0)
            if METRICS.enabled and METRICS.counted_dispatch:
                self._fold_hotness(np.asarray([q.size], np.int64),
                                   np.zeros(N_PROBE_BUCKETS, np.int64), 1)
        else:
            sid = snap.route(q)
            if METRICS.enabled and METRICS.counted_dispatch:
                # host path: routed counts from the binning we already did
                # (no device probe histogram on this path)
                self._fold_hotness(
                    np.bincount(sid, minlength=snap.n_shards),
                    np.zeros(N_PROBE_BUCKETS, np.int64), snap.n_shards)
            out = np.empty(q.size, dtype=np.int64)
            for s in np.unique(sid):
                mask = sid == s
                out[mask] = self._lookup_shard(snap.shards[s], q[mask],
                                               backend,
                                               int(snap.offsets[s]))
        if not state.delta.empty:
            out = out + state.delta.adjust(q)
        return out

    # -- updates ------------------------------------------------------------
    def insert(self, keys: np.ndarray) -> int:
        """Buffer inserted keys (duplicates add logical occurrences).

        Drains the submit queue first (queued lookups observe the
        pre-update state) and triggers a merge once the delta exceeds
        ``merge_threshold``. The hot-key cache needs no invalidation —
        it stores delta-independent snapshot ranks. Returns the number of
        keys buffered."""
        keys = np.asarray(keys, dtype=np.uint64).ravel()
        if keys.size == 0:
            return 0
        with self._lock:
            self.drain()
            state = self._state
            if self._dur is not None:
                # WAL-before-mutation: if the append raises, the in-memory
                # state is untouched and durable >= served still holds
                self._dur.wal.append(OP_INSERT, keys)
            n = state.delta.insert(keys)
            self._journal_op("insert", keys)
            self.stats.inserts += n
            self._maybe_rotate_wal(state)
            self._after_update(state)
            return n

    def delete(self, keys: np.ndarray) -> int:
        """Tombstone key values: every logical occurrence of each key
        (snapshot and pending inserts) is removed. Returns the number of
        occurrences removed."""
        keys = np.asarray(keys, dtype=np.uint64).ravel()
        if keys.size == 0:
            return 0
        with self._lock:
            self.drain()
            state = self._state
            if self._dur is not None:
                self._dur.wal.append(OP_DELETE, keys)
            n = state.delta.delete(keys)
            self._journal_op("delete", keys)
            self.stats.deletes += n
            self._maybe_rotate_wal(state)
            self._after_update(state)
            return n

    def _maybe_rotate_wal(self, state: _ServiceState) -> None:
        """Compact the durable WAL once it exceeds ``wal_rotate_bytes``
        (lock held; called after the delta mutation, so the seed ops
        include the record just logged). Recovery replay is thereafter
        bounded by the live delta, not by the epoch's append history.

        Rotation is skipped when the compacted seed would not shrink the
        segment to at most half its size — a delta whose own encoding is
        near the threshold (huge manual-merge buffers) would otherwise be
        rewritten in full on *every* mutation, turning O(record) appends
        into O(delta) rewrites."""
        dur = self._dur
        if dur is None or not 0 < self.wal_rotate_bytes <= dur.wal.size_bytes:
            return
        delta = state.delta
        seed_est = 9 * 3 + 8 * (delta.n_inserts + delta.n_tombstones)
        if seed_est * 2 > dur.wal.size_bytes:
            return
        ops = [(_WAL_OPS[name], op_keys)
               for name, op_keys in delta.pending_ops()]
        dur.wal = dur.wal.rotate(ops)
        self.stats.wal_rotations += 1

    def _journal_op(self, opname: str, keys: np.ndarray) -> None:
        """Record one accepted mutation in the op journal (lock held;
        background mode only). The journal is the background merge's
        residual source: ops still journaled at publish time arrived after
        the merge's capture point and are replayed into the fresh delta."""
        if self.merge_mode != "background":
            return
        self._op_seq += 1
        self._op_journal.append((self._op_seq, opname, keys.copy()))

    def _after_update(self, state: _ServiceState) -> None:
        # no cache invalidation needed: cached entries hold delta-
        # independent snapshot ranks (the delta folds in after resolution)
        if not 0 < self.merge_threshold <= state.delta.n_entries:
            return
        if self._backlog_since is None:
            # the delta just crossed the threshold: the backlog clock runs
            # until a successful publish clears it (health/SLO input)
            self._backlog_since = time.monotonic()
        if self._consec_merge_failures and \
                time.monotonic() < self._merge_retry_at:
            return    # backing off: the delta keeps serving merged reads
        if self.merge_mode == "background":
            # lock-free MPSC update path: hand the rebuild to the worker
            # thread and return immediately — this mutation never waits on
            # a merge, in-flight or otherwise
            self._notify_merge_worker()
            return
        try:
            self.merge()
        except MergeFailedError:
            # contained — counted and backoff armed inside merge(); the
            # failed attempt left the live state untouched, so updates
            # and lookups just keep going against the buffered delta
            pass

    def merge(self) -> bool:
        """Fold the delta into a brand-new snapshot and swap it in.

        The rebuild (spline + auto-tune + radix layer via ``build_plex``,
        per shard — parallelised over ``build_workers``) happens entirely
        off the hot path on the materialised logical key array; only when
        the new snapshot is complete does the single ``_state`` assignment
        publish it together with a fresh delta — readers never see a
        half-built index. Starts a new stats epoch. Returns ``True`` if a
        swap happened (``False`` for an empty delta or an empty logical
        key set, which stays buffered).

        In ``merge_mode="sync"`` (the default) the whole merge runs under
        the service lock: when this call returns, the swap is published
        and no mutation interleaved. In ``merge_mode="background"`` this
        explicit call still merges *in the calling thread* (serialised
        with the worker via the merge mutex) but follows the background
        protocol — the service lock is held only for the capture and
        publish instants, so concurrent ``insert``/``delete``/``lookup``
        proceed during the rebuild and land in the residual journal."""
        if self.merge_mode == "background":
            # lock order _merge_mutex -> _lock; never hold _lock across
            # the rebuild (that is the whole point of background mode)
            with self._merge_mutex:
                return self._merge_once()
        with self._lock:
            self.drain()
            return self._merge_once()

    def _merge_once(self) -> bool:
        """One capture -> rebuild -> publish merge cycle.

        Caller must serialise merges: sync mode holds the service lock for
        the whole call (so the capture/publish lock acquisitions below are
        re-entrant and the cycle is atomic, the classic behaviour);
        background mode holds ``_merge_mutex`` and nothing else, so the
        expensive middle — logical-key materialisation, ``Snapshot.build``,
        pre-warm, the phase-1 snapshot write — runs with no service lock
        held and mutations flow freely into the journal."""
        with TRACE.span("merge.capture"), self._lock:
            state = self._state
            if state.delta.empty:
                return False
            # the capture point: an immutable delta state plus the journal
            # sequence folded into it. Everything journaled after seq0 is
            # residual — replayed into the fresh delta at publish.
            dstate = state.delta.capture()
            seq0 = self._op_seq
            while self._op_journal and self._op_journal[0][0] <= seq0:
                self._op_journal.popleft()
        t0 = time.perf_counter()
        new_keys = state.delta.logical_keys(dstate)
        if new_keys.size == 0:
            # a snapshot cannot be empty; keep buffering until an
            # insert arrives (lookups stay correct via the delta fold)
            return False
        dur = self._dur
        new_gen = dur.generation + 1 if dur is not None else -1
        try:
            fire(POINT_MERGE_BUILD)
            snap = Snapshot.build(
                new_keys, self.eps, n_shards=self._n_shards_req,
                backend=self.default_backend, block=self.block,
                devices=self._devices, epoch=state.snapshot.epoch + 1,
                workers=self.build_workers, **self._build_kw)
            # pre-warm the new snapshot's device pipelines while the
            # old one still serves (only when the jnp path is actually
            # in use), so the first post-swap lookup never pays a cold
            # compile — warm time is merge/build work, not serving
            # work. The routed mesh path re-plans + re-partitions the
            # NEW snapshot here (placement is snapshot-scoped),
            # warming every device slab.
            new_router = self._make_router(snap)
            if new_router is not None:
                new_router.warmup(np.uint64(snap.keys[0]),
                                  self._delta_capacity)
                if METRICS.enabled:
                    # warm dispatches are not served traffic
                    for d in new_router.plan.active:
                        impl = new_router.parts[d].impl
                        if impl is not None:
                            impl.take_counters()
            elif state.snapshot.built_stacked() is not None:
                self._warm_stacked(snap, self._delta_capacity)
            # durable phase 1: the heavyweight snapshot write, still off
            # the service lock. Nothing is live until the manifest rename
            # in phase 2, so a crash here leaves a dead gen dir at worst.
            if dur is not None:
                try:
                    save_snapshot(dur.root / gen_name(new_gen), snap,
                                  fsync=dur.fsync)
                except Exception:
                    shutil.rmtree(dur.root / gen_name(new_gen),
                                  ignore_errors=True)
                    raise
        except Exception as e:
            # merge-failure isolation: nothing above touched the live
            # (snapshot, delta, router) triple or the committed
            # on-disk generation, so serving continues bit-identically
            # against the buffered delta; auto-merges retry after a
            # capped exponential backoff
            raise self._arm_merge_backoff(e) from e
        if TRACE.enabled:
            # build + warm + phase-1 write, measured from the capture point
            TRACE.record("merge.build", time.perf_counter() - t0,
                         n_keys=new_keys.size, epoch=snap.epoch)
        # the publish phase: drain the queue (queued lookups observe the
        # state they were dispatched against), durable phase 2 (fresh WAL
        # seeded with the residual + one manifest rename), then the atomic
        # swap — one reference assignment publishes the new (snapshot,
        # delta, router) triple with the residual ops replayed in order.
        with TRACE.span("merge.publish", epoch=snap.epoch), self._lock:
            self.drain()
            residual = list(self._op_journal)
            new_dur = None
            if dur is not None:
                try:
                    new_dur = self._commit_generation(
                        dur.root, new_gen, snap,
                        [(name, op_keys) for _, name, op_keys in residual],
                        dur.fsync, snapshot_saved=True)
                except Exception as e:
                    raise self._arm_merge_backoff(e) from e
            delta = DeltaBuffer(snap.keys, capacity=self._delta_capacity)
            for _, name, op_keys in residual:
                getattr(delta, name)(op_keys)
            self._op_journal.clear()
            self._state = _ServiceState(snap, delta, new_router)
            if new_dur is not None:
                self._swap_durable(new_dur)
            self._consec_merge_failures = 0
            self._merge_retry_at = 0.0
            self._backlog_since = None   # the over-threshold delta merged
            self.stats.merges += 1
            self.stats.merge_s += time.perf_counter() - t0
            self.stats.new_epoch(snap.epoch)
            # per-epoch live hotness restarts with the new shard map (the
            # counter-plane folds guard on the array length, so any
            # straggler fold from the old epoch is dropped, not misbinned)
            self._hotness = np.zeros(snap.n_shards, np.int64)
            self._probe_hist = np.zeros(N_PROBE_BUCKETS, np.int64)
        if METRICS.enabled:
            METRICS.counter("merge.cycles").inc()
        return True

    def _arm_merge_backoff(self, e: BaseException, *,
                           kind: str = "merge.failure") -> MergeFailedError:
        """Account one contained merge failure and arm the capped
        exponential retry backoff; returns the ``MergeFailedError`` to
        raise (callers ``raise self._arm_merge_backoff(e) from e``).
        ``kind`` names the incident class (worker death reports its own
        so a dead worker and a failed cycle bundle separately)."""
        self.stats.merge_failures += 1
        self._consec_merge_failures += 1
        backoff = min(self.merge_backoff_cap_s,
                      self.merge_backoff_s *
                      2.0 ** (self._consec_merge_failures - 1))
        self._merge_retry_at = time.monotonic() + backoff
        self._note_error(e)
        log.warning("merge failed (attempt %d, retry in %.3fs): "
                    "%r; live state untouched",
                    self._consec_merge_failures, backoff, e)
        _report_incident(kind, repr(e), health=self.health,
                         consecutive=self._consec_merge_failures,
                         retry_in_s=round(backoff, 3))
        return MergeFailedError(
            f"merge failed ({self._consec_merge_failures} "
            f"consecutive attempt(s)): {e!r}; the live state is "
            "untouched and the delta keeps serving")

    # -- background merge worker --------------------------------------------
    def _notify_merge_worker(self) -> None:
        """Wake (lazily starting or restarting) the merge worker thread
        (lock held). A worker that died — chaos-injected or real — is
        replaced here on the next update, after its armed backoff expires
        inside the worker loop, so worker death degrades exactly like a
        contained merge failure instead of silently stopping merges."""
        if self._closed:
            return
        w = self._merge_worker
        if w is None or not w.is_alive():
            w = threading.Thread(target=self._merge_worker_main,
                                 name="plex-merge-worker", daemon=True)
            self._merge_worker = w
            w.start()
        self._merge_wakeup.set()

    def _merge_worker_main(self) -> None:
        """The background merge loop: wait for a wakeup, re-check that a
        merge is actually due (threshold still exceeded, backoff expired),
        then run one full capture/rebuild/publish cycle under the merge
        mutex. ``MergeFailedError`` is contained (backoff armed inside);
        anything else — including a chaos fault injected at
        ``POINT_MERGE_WORKER`` — kills this worker, arms the same backoff,
        and leaves the live state untouched: the delta keeps serving and
        the next update starts a fresh worker."""
        while True:
            self._merge_wakeup.wait()
            self._merge_wakeup.clear()
            if self._closed:
                return
            try:
                fire(POINT_MERGE_WORKER)
                if self._consec_merge_failures and \
                        time.monotonic() < self._merge_retry_at:
                    continue
                if not 0 < self.merge_threshold \
                        <= self._state.delta.n_entries:
                    continue
                with self._merge_mutex:
                    try:
                        self._merge_once()
                    except MergeFailedError:
                        pass      # contained; backoff armed, retry later
            except BaseException as e:  # noqa: BLE001 - worker death path
                self._arm_merge_backoff(e, kind="merge.worker_death")
                log.warning("merge worker died: %r; live state untouched, "
                            "a fresh worker starts on the next update", e)
                return

    # -- durability ----------------------------------------------------------
    @staticmethod
    def _commit_generation(root: pathlib.Path, gen: int, snap: Snapshot,
                           seed_ops, fsync: bool, *,
                           snapshot_saved: bool = False) -> _DurableState:
        """THE durable commit protocol, in one place: write generation
        ``gen``'s snapshot, create its fresh WAL seeded with ``seed_ops``
        (``DeltaBuffer.pending_ops`` order), then publish with one atomic
        manifest rename. Nothing is live until the rename, so a crash
        anywhere in here leaves the previous generation (and its WAL)
        authoritative — and a *caught* failure additionally sweeps the
        partial generation away, so disk state always equals committed
        state plus at most one in-progress commit.

        ``snapshot_saved=True`` is the background merge's split commit:
        the (slow) snapshot write already happened off the service lock
        (phase 1), so this call only runs the fast tail — WAL seed +
        manifest rename — under the lock, keeping writers unblocked for
        the duration of the rebuild."""
        wal = None
        try:
            if not snapshot_saved:
                save_snapshot(root / gen_name(gen), snap, fsync=fsync)
            wal = WriteAheadLog.create(root / wal_name(gen), fsync=fsync)
            for opname, op_keys in seed_ops:
                wal.append(_WAL_OPS[opname], op_keys)
            write_manifest(root, Manifest.for_generation(gen), fsync=fsync)
        except Exception:
            if wal is not None:
                wal.close()
            shutil.rmtree(root / gen_name(gen), ignore_errors=True)
            try:
                (root / wal_name(gen)).unlink()
            except OSError:
                pass
            raise
        return _DurableState(root=root, generation=gen, wal=wal,
                             fsync=fsync)

    def _swap_durable(self, new_dur: _DurableState) -> None:
        """Adopt a freshly committed generation (lock held): close the
        previous WAL handle and collect superseded on-disk state."""
        old = self._dur
        self._dur = new_dur
        if old is not None:
            old.wal.close()
        _gc_generations(new_dur.root, new_dur.generation,
                        self.keep_generations)

    def save(self, root, *, fsync: bool = True) -> pathlib.Path:
        """Persist the current (snapshot, delta) state under ``root`` and
        attach this service to it (durable mode).

        Writes the snapshot as a new numbered generation, seeds that
        generation's WAL with the live delta (deletes before inserts — the
        replay-equivalent order), and atomically publishes the manifest.
        From here on every ``insert``/``delete`` is WAL-logged before it is
        applied and every ``merge`` rotates the generation; older
        generations are garbage-collected. Safe to call repeatedly (each
        call commits a fresh generation)."""
        root = pathlib.Path(root)
        root.mkdir(parents=True, exist_ok=True)
        # serialise with merges (lock order _merge_mutex -> _lock): a
        # background merge mid-rebuild targets generation N+1 too, and two
        # writers racing for the same gen dir must never interleave
        with self._merge_mutex:
            with self._lock:
                self.drain()
                state = self._state
                man = read_manifest(root)
                gen = man.generation + 1 if man is not None else 0
                self._swap_durable(self._commit_generation(
                    root, gen, state.snapshot, state.delta.pending_ops(),
                    fsync))
        return root

    @classmethod
    def open(cls, root, *, backend: str = "jnp", durable: bool = True,
             fsync: bool = True, verify: bool = False, recover: bool = True,
             **kw) -> "PlexService":
        """Warm-start a service from a persisted directory in load time.

        Follows the manifest to the last committed generation, memmaps its
        snapshot (no rebuild of any kind), and replays the WAL's valid
        prefix into a fresh delta buffer — crash leftovers (uncommitted
        generation dirs, stray WAL segments, torn WAL tails) are logged
        and discarded, and a torn tail is truncated away before the
        segment is reused. ``durable=True`` (default) keeps the service
        attached: subsequent updates append to the recovered WAL and
        merges rotate generations. ``load_s`` records the total open wall
        time (map + replay).

        Last-known-good recovery (``recover=True``, the default): when
        the manifest is corrupt or the committed generation fails
        validation (header, CRC, plane mapping), the bad generation is
        moved to ``root/quarantine/`` and the open falls back generation
        by generation to the newest older one that validates (retained
        on disk by serving with ``keep_generations > 1``); a durable open
        then re-commits the manifest at the recovered generation.
        ``NoServableGenerationError`` means every candidate failed;
        ``FileNotFoundError`` still means the directory was never
        published to. ``recover=False`` restores strict fail-fast
        behaviour."""
        t0 = time.perf_counter()
        root = pathlib.Path(root)
        last_err: BaseException | None = None
        try:
            man = read_manifest(root)
        except CorruptManifestError as e:
            if not recover:
                raise
            log.warning("open(%s): manifest corrupt (%s); falling back to "
                        "the newest on-disk generation", root, e)
            last_err = e
            man = None
        if man is None and last_err is None:
            raise FileNotFoundError(f"no committed manifest under {root}")
        gens = sorted((g for p in root.glob("gen-*")
                       if p.is_dir() and (g := _gen_num(p)) is not None),
                      reverse=True)
        if man is not None:
            for g in gens:
                if g > man.generation:
                    log.warning("open(%s): discarding uncommitted "
                                "generation %s", root, gen_name(g))
            candidates = [man.generation] + [g for g in gens
                                             if g < man.generation]
        else:
            candidates = gens
        for p in sorted(root.glob("wal-*.log")):
            g = _gen_num(p)
            if man is not None and (g is None or g > man.generation):
                log.warning("open(%s): discarding stray WAL segment %s",
                            root, p.name)
        for p in sorted(root.glob("wal-*.log.rot")):
            # a crash between rotate()'s temp write and its rename leaves
            # this; the live segment is authoritative, the temp is garbage
            log.warning("open(%s): removing leftover rotation temp %s",
                        root, p.name)
            try:
                p.unlink()
            except OSError:  # pragma: no cover
                pass
        snap = None
        chosen = -1
        for g in candidates:
            gdir = root / gen_name(g)
            try:
                snap = load_snapshot(gdir, verify=verify)
                chosen = g
                break
            except Exception as e:
                if not recover:
                    raise
                last_err = e
                log.warning("open(%s): generation %s failed validation "
                            "(%r); quarantining and falling back", root,
                            gen_name(g), e)
                _quarantine(root, gdir, root / wal_name(g))
                _report_incident("generation.quarantine",
                                 f"{gen_name(g)}: {e!r}", root=str(root),
                                 generation=int(g))
        if snap is None:
            raise NoServableGenerationError(root, last_err)
        svc = cls(None, backend=backend, _snapshot=snap, **kw)
        wal_path = root / wal_name(chosen)
        records, valid, discarded = WriteAheadLog.replay(wal_path)
        if discarded:
            log.warning("open(%s): WAL %s: discarded %d trailing byte(s) "
                        "past the last valid record", root, wal_path.name,
                        discarded)
        # replay, coalescing consecutive same-op records first: only the
        # insert/delete *interleaving* is order-sensitive, and each delta
        # mutation rebuilds the whole published state, so applying one
        # batched op per run keeps recovery linear in WAL size instead of
        # quadratic in record count
        delta = svc._state.delta
        for op, op_keys in _coalesce_ops(records):
            if op == OP_INSERT:
                delta.insert(op_keys)
            else:
                delta.delete(op_keys)
        if durable:
            if man is None or chosen != man.generation:
                # recovery demoted the store to an older generation:
                # re-commit the manifest there so appends and rotations
                # bind to the generation actually being served
                write_manifest(root, Manifest.for_generation(chosen),
                               fsync=fsync)
            if wal_path.exists() and valid > 0:
                # valid > 0 implies the segment's magic verified; truncate
                # the torn tail (if any) and append after the good prefix
                wal = WriteAheadLog.open(wal_path, fsync=fsync,
                                         truncate_at=valid)
            else:
                # missing segment or corrupt magic: appending after a bad
                # header would make every new record unrecoverable, so
                # start a fresh segment instead
                log.warning("open(%s): WAL %s %s; starting a fresh segment",
                            root, wal_path.name,
                            "has an invalid header" if wal_path.exists()
                            else "is missing")
                wal = WriteAheadLog.create(wal_path, fsync=fsync)
            svc._dur = _DurableState(root=root, generation=chosen,
                                     wal=wal, fsync=fsync)
        svc.load_s = time.perf_counter() - t0
        if TRACE.enabled:
            TRACE.record("persist.open", svc.load_s,
                         generation=svc.generation)
        return svc

    @property
    def durable(self) -> bool:
        """True when attached to a persisted directory (updates WAL-logged,
        merges rotate generations)."""
        return self._dur is not None

    @property
    def generation(self) -> int:
        """Committed durable generation (-1 for in-memory services)."""
        return self._dur.generation if self._dur is not None else -1

    def close(self) -> None:
        """Drain outstanding work and release the WAL handle (the durable
        directory stays openable; an in-memory service just drains).
        Idempotent, and the service is a context manager — ``with
        PlexService(...) as svc:`` closes on exit even when the body
        raises."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._cancel_timer()
            self.drain()
            worker = self._merge_worker
        # join the merge worker OUTSIDE the lock: its publish phase needs
        # the lock, so joining under it would deadlock mid-merge. The
        # worker observes _closed at its next wakeup and exits; an
        # in-flight merge is allowed to finish (its durable commit needs
        # the WAL we are about to close).
        if worker is not None and worker.is_alive():
            self._merge_wakeup.set()
            worker.join()
        with self._lock:
            if self._dur is not None:
                self._dur.wal.close()
                self._dur = None

    def __enter__(self) -> "PlexService":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- continuous-stream queue --------------------------------------------
    def submit(self, q: np.ndarray) -> LookupTicket:
        """Queue queries for deadline-driven micro-batch formation.

        Queries from successive submits are packed into shared ``block``-
        sized micro-batches; full blocks dispatch immediately (async), and
        a sub-block remainder dispatches once the oldest queued query has
        waited ``max_delay_s`` — enforced by a background timer thread, so
        the deadline holds even when no further submit/drain call arrives.
        Uses the stacked jnp device path; when that path (or the jnp
        backend) is unavailable the ticket is filled synchronously.

        Admission control (``max_queue > 0``): a submit that would push
        the queue past the bound is refused — ``overflow="reject"``
        raises ``QueueFullError`` here, ``overflow="shed"`` returns a
        ticket carrying that error instead (raised by ``result()``) —
        so a wedged consumer degrades into fast typed failures, never
        unbounded memory growth."""
        q = np.ascontiguousarray(q, dtype=np.uint64)
        ticket = LookupTicket(self, q.size)
        if q.size == 0:
            return ticket
        with TRACE.span("serve.submit", n=q.size), self._lock:
            if self.max_queue and self._q_len + q.size > self.max_queue:
                err = QueueFullError(
                    f"submit: queue holds {self._q_len} of "
                    f"{self.max_queue} lanes; {q.size} more would exceed "
                    "the bound")
                self.stats.shed_queries += q.size
                self._note_error(err)
                _report_incident("queue.shed", str(err), health=self.health,
                                 shed=int(q.size), overflow=self.overflow)
                if self.overflow == "reject":
                    raise err
                ticket._error = err        # shed: the ticket carries it
                ticket._filled = q.size
                return ticket
            # capture the stacked path under the lock: mutations hold the
            # same lock, so the queued dispatch can never pair this
            # snapshot's planes with a different epoch's delta. The routed
            # mesh path fills tickets synchronously (its host binning is
            # per-batch; queue formation stays a single-device feature)
            try:
                st = (self.stacked_impl()
                      if get_backend(self.default_backend).stacked_factory
                      is not None and self._state.router is None else None)
            except Exception as e:      # factory fault: degrade to sync
                self._note_error(e)
                st = None
            if st is None:
                ticket._out[:] = self.lookup(q)
                ticket._filled = q.size
                return ticket
            now = time.monotonic()
            self._q_chunks.append([ticket, q, 0, now])
            self._q_len += q.size
            self.stats.queries += q.size
            self._flush_full(st)
            if self._q_len:
                if now - self._q_chunks[0][3] >= self.max_delay_s:
                    self._flush_partial(st)
                else:
                    self._arm_timer(self.max_delay_s
                                    - (now - self._q_chunks[0][3]))
        return ticket

    def _arm_timer(self, delay_s: float) -> None:
        """Schedule the background deadline flush (one live timer at most;
        must be called with the lock held)."""
        if self._timer is not None:
            return
        t = threading.Timer(max(delay_s, 0.0), self._deadline_flush)
        t.daemon = True
        self._timer = t
        t.start()

    def _cancel_timer(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _deadline_flush(self) -> None:
        """Timer-thread entry: flush (and drain) the queued remainder once
        its deadline has expired, filling the pending tickets without any
        further caller action; re-arm when woken early. Failures degrade
        through the fallback chain and park on tickets — an exception may
        never escape and kill the timer thread silently."""
        with self._lock:
            self._timer = None
            if not self._q_len:
                return
            age = time.monotonic() - self._q_chunks[0][3]
            if age < self.max_delay_s:
                self._arm_timer(self.max_delay_s - age)
                return
            try:
                st = self.stacked_impl()
            except Exception as e:
                self._note_error(e)
                st = None
            if st is None:
                self._fill_queue_sync()
            else:
                self._flush_partial(st)
            self._drain_outstanding()

    def _take_block(self, want: int) -> tuple[np.ndarray, list, int]:
        """Pop up to ``want`` queued queries into a fresh block buffer
        (fresh because queued dispatches can stay in flight across calls);
        returns (buffer, ticket pieces, lanes filled)."""
        buf = np.empty(self.block, dtype=np.uint64)
        pieces = []
        filled = 0
        obs = METRICS.enabled or TRACE.enabled
        now = time.monotonic() if obs else 0.0
        while filled < want and self._q_chunks:
            entry = self._q_chunks[0]
            ticket, arr, consumed, _ = entry
            take = min(want - filled, arr.size - consumed)
            buf[filled:filled + take] = arr[consumed:consumed + take]
            pieces.append((ticket, filled, consumed, take))
            entry[2] += take
            filled += take
            if obs:
                wait_s = max(now - entry[3], 0.0)
                TRACE.record("serve.queue_wait", wait_s, lanes=take)
                if METRICS.enabled:
                    METRICS.histogram("serve.queue_wait_us").observe(
                        wait_s * 1e6)
            if entry[2] == arr.size:
                self._q_chunks.popleft()
        self._q_len -= filled
        return buf, pieces, filled

    def _dispatch_queue_block(self, st, buf: np.ndarray, pieces: list,
                              filled: int) -> None:
        if filled < self.block:
            buf[filled:] = buf[filled - 1]
        try:
            qh, ql = split_u64(buf)
            res = self._dispatch_planes(st, qh, ql, filled,
                                        self._delta_view(self._state))
        except Exception as e:
            # failed async dispatch: answer this block synchronously
            # through the fallback chain (same result, degraded latency)
            self.stats.backend_failures += 1
            self._note_error(e)
            self._record_breaker(self._breaker(self.default_backend),
                                 False, e)
            self._fill_pieces_fallback(buf, pieces, filled)
            return
        self._outstanding.append((res, buf, filled, pieces,
                                  self.stats.epoch))
        self.stats.batches += 1
        self.stats.padded_lanes += self.block - filled

    def _fill_pieces_fallback(self, buf: np.ndarray, pieces: list,
                              filled: int) -> None:
        """Answer one failed queue block synchronously via ``lookup``'s
        fallback chain and fill its ticket pieces; when even the chain is
        exhausted the error parks on each ticket (raised by ``result()``,
        never a hang, never partial garbage)."""
        try:
            out = self.lookup(buf[:filled])
        except Exception as e:
            for ticket, src, dst, cnt in pieces:
                ticket._error = e
                ticket._filled += cnt
            return
        for ticket, src, dst, cnt in pieces:
            ticket._out[dst:dst + cnt] = out[src:src + cnt]
            ticket._filled += cnt

    def _fill_queue_sync(self) -> None:
        """Last-resort queue path (lock held): the stacked pipeline could
        not be built at all, so pop every queued chunk and answer it
        synchronously through the fallback chain; chain-exhausted
        failures park on the tickets."""
        while self._q_chunks:
            ticket, arr, consumed, _ = self._q_chunks.popleft()
            rest = arr[consumed:]
            self._q_len -= rest.size
            try:
                ticket._out[consumed:] = self.lookup(rest)
            except Exception as e:
                ticket._error = e
            ticket._filled += rest.size

    def _flush_full(self, st) -> None:
        while self._q_len >= self.block:
            buf, pieces, filled = self._take_block(self.block)
            self._dispatch_queue_block(st, buf, pieces, filled)

    def _flush_partial(self, st) -> None:
        self._flush_full(st)
        if self._q_len:
            buf, pieces, filled = self._take_block(self._q_len)
            self._dispatch_queue_block(st, buf, pieces, filled)

    def _drain_outstanding(self, deadline: float | None = None) -> None:
        """Sync every in-flight queued batch and fill its tickets (lock
        held by the caller). A batch whose sync fails is recomputed
        through the fallback chain; ``deadline`` bounds the blocking
        syncs — on expiry ``TimeoutError`` propagates with the remaining
        batches left outstanding for a later drain."""
        while self._outstanding:
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain: deadline expired with "
                    f"{len(self._outstanding)} batch(es) still in flight")
            res, buf, filled, pieces, epoch = self._outstanding.pop(0)
            try:
                arr = np.asarray(res.out)       # sync
            except Exception as e:
                self.stats.backend_failures += 1
                self._note_error(e)
                self._record_breaker(self._breaker(self.default_backend),
                                     False, e)
                self._fill_pieces_fallback(buf, pieces, filled)
                self.stats.note_drained(1)
                continue
            for ticket, src, dst, cnt in pieces:
                ticket._out[dst:dst + cnt] = arr[src:src + cnt]
                ticket._filled += cnt
            self._note_synced(res, epoch, filled)
            self.stats.note_drained(1)
        if METRICS.enabled:
            # drain the queue path's device counter plane (best-effort;
            # counters from dispatches that straddled a swap are dropped
            # by the fold's shard-count guard)
            state = self._state
            try:
                st = self.stacked_impl(state)
            except Exception:
                st = None
            if st is not None:
                self._fold_impl_counters(st, state.snapshot.n_shards)

    def drain(self, timeout: float | None = None) -> None:
        """Flush the queued sub-block remainder and sync every in-flight
        batch, filling all pending tickets. The service's single blocking
        point: everything before it is async dispatch. ``timeout`` bounds
        the whole call — both the lock acquisition (a wedged writer) and
        the per-batch syncs check the deadline and raise ``TimeoutError``
        instead of blocking forever; un-synced batches stay outstanding
        for the next drain."""
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        if timeout is None:
            self._lock.acquire()
        elif not self._lock.acquire(timeout=float(timeout)):
            raise TimeoutError(
                f"drain: service lock not acquired within {timeout}s")
        try:
            with TRACE.span("serve.drain", queued=self._q_len,
                            outstanding=len(self._outstanding)):
                self._cancel_timer()
                if self._q_len:
                    try:
                        st = self.stacked_impl()
                    except Exception as e:
                        self._note_error(e)
                        st = None
                    if st is None:
                        self._fill_queue_sync()
                    else:
                        self._flush_partial(st)
                self._drain_outstanding(deadline)
        finally:
            self._lock.release()

    def _warm_stacked(self, snap: Snapshot, delta_cap: int | None,
                      backend: str | None = None) -> bool:
        """Compile the exact serving dispatch for ``snap`` — same batch
        sharding layout and cache state as the micro-batch pipeline — plus,
        when ``delta_cap`` is given, the merged variant at that capacity
        (warmed with a zero-weight dummy entry, which leaves every result
        untouched). Does not touch the stats; returns False when the shards
        did not unify."""
        st = snap.stacked_impl(backend or self.default_backend,
                               block=self.block, probe=self.probe,
                               cache_slots=self.cache_slots)
        if st is None:
            return False
        qh, ql = split_u64(np.repeat(snap.keys[:1], self.block))
        qhi = jax.device_put(qh, self._batch_sharding)
        qlo = jax.device_put(ql, self._batch_sharding)
        jax.block_until_ready(st.lookup_planes(qhi, qlo, n_valid=1).out)
        if delta_cap:
            from ..kernels.planes import build_delta_planes
            dummy = build_delta_planes(snap.keys[:1],
                                       np.zeros(1, np.int64), delta_cap)
            jax.block_until_ready(
                st.lookup_planes(qhi, qlo, n_valid=1, delta=dummy).out)
        if METRICS.enabled:
            st.take_counters()    # warm dispatches are not served traffic
        return True

    def warmup(self, backend: str | None = None) -> None:
        """Force jit compilation of every dispatch the serving path can
        take in this epoch: the delta-free pipeline and the merged pipeline
        at the standing delta capacity — so neither the first update nor a
        queue flush on the deadline timer thread ever hits a cold
        compile.

        A real failure here — the compiler refusing the backend's program,
        a lowering error, a device error — is a configuration error and
        propagates with its own message: a backend that cannot compile on
        this platform must not be served through the fallback chain in
        silence. Only an injected fault (``resilience.faults`` drills) is
        best-effort: the backend is left cold, the fault noted in
        ``health()``'s error journal, and the chain covers it at lookup
        time."""
        backend = backend or self.default_backend
        try:
            if get_backend(backend).stacked_factory is not None:
                state = self._state
                dv = self._delta_view(state)
                cap = dv.cap if dv is not None else self._delta_capacity
                if state.router is not None and \
                        backend == self.default_backend:
                    state.router.warmup(np.uint64(state.snapshot.keys[0]),
                                        cap)
                    return
                if self._warm_stacked(state.snapshot, cap, backend):
                    return
            for shard in self.shards:
                shard.warmup(backend)
        except Exception as e:
            if not is_injected(e):
                raise
            self._note_error(e)
            log.warning("warmup: backend %r failed (%s); left cold — the "
                        "fallback chain covers it at lookup time", backend, e)

    # -- measurement ---------------------------------------------------------
    def throughput(self, q: np.ndarray, backends: Sequence[str] = BACKENDS,
                   repeats: int = 3) -> dict[str, float]:
        """Best-of-repeats ns per lookup for each backend.

        The timed region ends only after the device work is finished:
        ``lookup`` materialises its result on the host (the async
        pipeline's one sync point) and ``drain()`` syncs anything queued
        via ``submit`` — async dispatch cannot undercount device time."""
        report: dict[str, float] = {}
        for backend in backends:
            self.warmup(backend)
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                out = self.lookup(q, backend=backend)
                self.drain()
                jax.block_until_ready(out)
                best = min(best, time.perf_counter() - t0)
            report[backend] = best / q.size * 1e9
        return report
