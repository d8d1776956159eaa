"""LearnedIndex + Snapshot — lookup dispatch and the immutable ownership unit.

The repo grows three lookup paths (the numpy reference in ``plex.py``, the
portable jit'd jnp pipeline, and the Pallas TPU pipeline). ``LearnedIndex``
is the single dispatch point the serving layer (and every later scaling PR)
builds on:

    idx = LearnedIndex.build(keys, eps=64)
    idx.lookup(q)                      # default backend
    idx.lookup(q, backend="jnp")       # explicit dispatch

Backends resolve through the registry in ``kernels.backends`` (the single
place backend names mean anything); the built-ins:

* ``"numpy"``  — vectorised float64 host reference (``PLEX.lookup``).
* ``"jnp"``    — jit-compiled pure-jnp pipeline, portable to CPU/GPU/TPU
  (``kernels.jnp_lookup.JnpPlex`` / ``StackedJnpPlex``).
* ``"pallas"`` — the fused Pallas kernel pipeline
  (``kernels.stacked_pallas.StackedPallasPlex``); runs under interpret
  mode on CPU, compiled on TPU.

All backends return the index of the first occurrence for present keys
(identical across backends); for absent keys each returns the lower bound
within its eps window, which may differ by the documented float32 slack at
the extreme array edge. Accelerated backends are constructed lazily and
cached, so a host-only user never imports jax kernels (the registry itself
is jax-free).

Snapshot (the updatable-index ownership model)
----------------------------------------------
``Snapshot`` is the immutable unit everything read-only hangs off: the
sorted key array, the per-shard frozen ``PLEX`` indexes (shard boundaries
snapped to first occurrences), the shard-minima routing plane, and —
lazily — the fused shard-major ``StackedPlanes`` device layout. Once built,
a snapshot never changes: every host array is frozen
(``plex.freeze_arrays``), so device planes and in-flight async batches can
alias it safely, and an updatable service can swap in a *new* snapshot
atomically while readers of the old one finish undisturbed. Updates between
swaps live in a separate delta buffer (``serving.delta.DeltaBuffer``); the
serving layer folds delta ranks into snapshot ranks at lookup time.
"""
from __future__ import annotations

import dataclasses
import logging
import time
import warnings
from typing import Any, Sequence

import numpy as np

from ..kernels.backends import BACKENDS, get_backend
from .plex import PLEX, as_radix, build_plex, freeze_arrays, layers_unify

# keep each shard's float32 rank plane well inside the 2^24 limit
SHARD_MAX_KEYS = 1 << 23

log = logging.getLogger(__name__)


@dataclasses.dataclass
class LearnedIndex:
    plex: PLEX
    default_backend: str = "numpy"
    block: int = 512
    device: Any = None            # jax device for the device planes (optional)
    _impls: dict = dataclasses.field(default_factory=dict, repr=False)
    _stacked_impls: dict = dataclasses.field(default_factory=dict, repr=False)

    def __post_init__(self) -> None:
        get_backend(self.default_backend)     # fail unknown names early

    @classmethod
    def build(cls, keys: np.ndarray, eps: int, *, backend: str = "numpy",
              block: int = 512, device: Any = None, **build_kw
              ) -> "LearnedIndex":
        """Build the underlying PLEX (host-side, the paper's single-pass
        build) and wrap it for multi-backend dispatch."""
        return cls(plex=build_plex(keys, eps, **build_kw),
                   default_backend=backend, block=block, device=device)

    # -- passthrough metadata ------------------------------------------------
    @property
    def keys(self) -> np.ndarray:
        return self.plex.keys

    @property
    def eps(self) -> int:
        return self.plex.eps

    @property
    def size_bytes(self) -> int:
        return self.plex.size_bytes

    @property
    def stats(self):
        return self.plex.stats

    @property
    def name(self) -> str:
        return "LearnedIndex"

    # -- dispatch ------------------------------------------------------------
    def backend_impl(self, backend: str | None = None) -> Any:
        """The (lazily constructed, cached) implementation for ``backend``,
        resolved through the ``kernels.backends`` registry. Host backends
        serve straight from the underlying ``PLEX``."""
        backend = backend or self.default_backend
        spec = get_backend(backend)
        impl = self._impls.get(backend)
        if impl is None:
            impl = (self.plex if spec.host else
                    spec.index_factory(self.plex, block=self.block,
                                       device=self.device))
            self._impls[backend] = impl
        return impl

    def stacked_impl(self, backend: str | None = None, *,
                     probe: str | None = None, cache_slots: int = 0) -> Any:
        """The single-shard *stacked* impl for ``backend`` — the
        ``lookup_planes(qhi, qlo, n_valid=None, delta=None) -> LaneResult``
        contract the serving layer drives. Cached per configuration. A
        single shard always unifies with itself, so this never returns
        ``None``; host backends have no device path and raise."""
        backend = backend or self.default_backend
        spec = get_backend(backend)
        if spec.stacked_factory is None:
            raise ValueError(
                f"backend {backend!r} has no stacked device path")
        cfg = (backend, probe, cache_slots)
        impl = self._stacked_impls.get(cfg)
        if impl is None:
            impl = spec.stacked_factory(
                [self.plex], np.zeros(1, dtype=np.int64), block=self.block,
                probe=probe, cache_slots=cache_slots,
                sharding=self.device)
            self._stacked_impls[cfg] = impl
        return impl

    def warmup(self, backend: str | None = None) -> None:
        """Force construction + jit compilation (one block-sized lookup)."""
        impl = self.backend_impl(backend)
        if impl is not self.plex:
            impl.lookup(self.plex.keys[:1])

    def lookup(self, q: np.ndarray, backend: str | None = None) -> np.ndarray:
        """First-occurrence index per query key (PLEX.lookup contract)."""
        return self.backend_impl(backend).lookup(q)

    def lookup_planes(self, qhi, qlo, backend: str | None = None):
        """Deprecated: use ``stacked_impl(backend).lookup_planes(...)``.

        The per-backend plane-level entry points collapsed into the fused
        stacked contract (``LaneResult``-returning, delta/cache aware); this
        thin shim forwards one chunk of (hi, lo) uint32 query planes to the
        single-shard stacked impl and returns its global clamped int32
        indices. Host backends have no device planes and raise."""
        warnings.warn(
            "LearnedIndex.lookup_planes is deprecated; drive "
            "LearnedIndex.stacked_impl(backend).lookup_planes(...) instead",
            DeprecationWarning, stacklevel=2)
        backend = backend or self.default_backend
        if get_backend(backend).host:
            raise ValueError(
                f"{backend} backend has no async plane-level path")
        return self.stacked_impl(backend).lookup_planes(qhi, qlo).out


def shard_offsets(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Contiguous shard start offsets, snapped to first occurrences so a
    duplicate run never straddles a boundary (global first-occurrence
    semantics stay exact)."""
    raw = (np.arange(n_shards, dtype=np.int64) * keys.size) // n_shards
    snapped = np.searchsorted(keys, keys[raw], side="left")
    snapped[0] = 0
    return np.unique(snapped)


class Snapshot:
    """Immutable sharded index state: keys + frozen per-shard PLEX + planes.

    Everything a lookup reads lives here and never changes after
    ``Snapshot.build``: the sorted key array, the shard offset table, the
    shard-minima routing plane, and the per-shard ``LearnedIndex`` wrappers
    (their host arrays frozen via ``PLEX.freeze``). The fused shard-major
    stacked device layout is built lazily at the first jnp lookup and cached
    per (block, probe, cache_slots) configuration — the device-side hot-key
    cache therefore lives *with* the snapshot and dies with it, which is
    what makes a snapshot swap automatically invalidate stale cached
    results.

    A ``Snapshot`` is the unit of atomic replacement for updatable serving:
    builders construct a complete new snapshot off the hot path and publish
    it with a single reference assignment; readers that captured the old
    reference keep a fully consistent index.

    A snapshot may also be a *partial view* of a persisted generation
    (``persist.format.load_snapshot(shard_range=...)`` — the mesh-serving
    partial-load path): ``keys``/``offsets`` are then rebased to the local
    slice and ``shard_base``/``key_base`` record the view's global
    position, so global row offsets are ``offsets + key_base``. Full
    snapshots keep the zero defaults, so every existing consumer is
    unaffected.
    """

    # partial-view metadata (instance attrs set by persist.format loads)
    shard_base: int = 0           # global index of this view's first shard
    key_base: int = 0             # global key row of this view's first key
    mapped_bytes: int = 0         # bytes memmapped by the loader (0 = built)

    def __init__(self, keys: np.ndarray, eps: int, offsets: np.ndarray,
                 shards: Sequence[LearnedIndex], *, build_s: float = 0.0,
                 epoch: int = 0, host_planes_fn: Any = None):
        self.keys = keys
        self.eps = int(eps)
        self.offsets = offsets
        self.shards = tuple(shards)
        self.shard_min = keys[offsets].copy()
        self.build_s = float(build_s)
        # wall seconds of the build's phases (build.pool, build.shards,
        # build.assemble); empty for a snapshot that was not built here
        self.build_phases: dict[str, float] = {}
        self.epoch = int(epoch)
        freeze_arrays(self.keys, self.offsets, self.shard_min)
        for s in self.shards:
            s.plex.freeze()
        self._stacked = {}            # (backend, block, probe, slots) -> impl
        self._stacked_last = None
        self._stacked_built = False
        # durable warm-start hook (persist.format): a thunk yielding the
        # per-shard host planes straight from a memmapped snapshot file, so
        # the stacked build skips every host-side re-derivation. Invoked
        # per stacked build and NOT cached: the planes are device-uploaded
        # copies, and pinning host copies too would double resident memory
        self._host_planes_fn = host_planes_fn

    @classmethod
    def build(cls, keys: np.ndarray, eps: int, *, n_shards: int | None = None,
              backend: str = "numpy", block: int = 512,
              devices: Sequence | None = None, epoch: int = 0,
              workers: int | None = None, pool: str = "process",
              mp_context: Any = None, **build_kw) -> "Snapshot":
        """Host-side sharded build (the paper's single-pass build per shard).

        ``devices``, when given, places shard planes round-robin (a
        single-shard build pins to ``devices[0]``). This runs off any
        serving hot path: an updatable service keeps answering from the
        previous snapshot until the new one is complete.

        ``workers > 1`` fans the independent per-shard ``build_plex``
        calls over a process pool (``core.parallel_build``): the key array
        crosses into the workers by memmap / copy-on-write fork / shared
        memory — never pickled — and the result is bit-identical to the
        serial build (same planes, same persisted bytes), only the
        schedule changes. ``pool="thread"`` swaps in a thread pool (useful
        when process start-up would dominate). Per-shard phase timings are
        aggregated on ``Snapshot.build_stats`` either way.

        Shards are tuned one by one; when their layers come out apart
        (``plex.layers_unify``), the CHT shards are re-tuned over radix
        tables so that the snapshot always serves through one stacked
        pipeline.

        Ownership: the key array is adopted and **frozen in place**
        (``writeable = False``) rather than copied — at the 200M-key scale
        this repo targets, a defensive copy would double resident memory.
        Pass ``keys.copy()`` if you need to keep mutating your array after
        the build; a frozen array can also be re-thawed by its owner via
        ``arr.flags.writeable = True`` once the snapshot is discarded.
        """
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        if keys.size == 0:
            raise ValueError("cannot snapshot an empty key set")
        if np.any(keys[1:] < keys[:-1]):
            raise ValueError("keys must be sorted")
        if n_shards is None:
            n_shards = -(-keys.size // SHARD_MAX_KEYS)
        offsets = shard_offsets(keys, max(int(n_shards), 1))
        from ..obs.trace import TRACE
        from .parallel_build import build_shard_plexes
        phases: dict[str, float] = {}
        t0 = time.perf_counter()
        plexes = build_shard_plexes(
            keys, offsets, eps, workers=int(workers or 1), pool=pool,
            mp_context=mp_context, timings=phases, **build_kw)
        with TRACE.timed("build.assemble", phases):
            if not layers_unify(plexes):
                # shards tuned apart (mixed radix/CHT, or CHT radix widths
                # that differ) would leave serving without its one stacked
                # pipeline: re-tune the CHT shards over radix tables, which
                # always unify
                plexes = [as_radix(px, **build_kw) for px in plexes]
            shards = []
            for s, px in enumerate(plexes):
                dev = devices[s % len(devices)] if devices else None
                shards.append(LearnedIndex(plex=px, default_backend=backend,
                                           block=block, device=dev))
        build_s = time.perf_counter() - t0
        snap = cls(keys, eps, offsets, shards, build_s=build_s, epoch=epoch)
        snap.build_phases = phases
        if TRACE.enabled:
            # CPU-seconds summed over the shards, which ran in parallel:
            # facts about the build, not intervals on any clock
            bs = snap.build_stats
            for name, cpu_s in (("build.spline", bs.spline_s),
                                ("build.tune", bs.tune_s),
                                ("build.layer", bs.layer_s)):
                TRACE.event(name, cpu_s=cpu_s, shards=len(shards))
        return snap

    # -- metadata -----------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_keys(self) -> int:
        return int(self.keys.size)

    @property
    def size_bytes(self) -> int:
        return sum(s.size_bytes for s in self.shards)

    @property
    def build_stats(self):
        """Per-phase build timings aggregated over the shards
        (``BuildStats``: spline fit / auto-tune / layer build CPU-seconds).
        Unlike ``build_s`` (the shard loop's wall time) these sum *index
        work*, so ``build_stats.total_s / build_s`` is the realised build
        parallelism. Loaded snapshots report zeros (nothing was built)."""
        from .plex import BuildStats
        return BuildStats.aggregate([s.plex.stats for s in self.shards])

    @property
    def name(self) -> str:
        return "Snapshot"

    # -- routing ------------------------------------------------------------
    def route(self, q: np.ndarray) -> np.ndarray:
        """Shard id per query (largest shard whose min key is <= q)."""
        q = np.asarray(q, dtype=np.uint64)
        return np.clip(np.searchsorted(self.shard_min, q, side="right") - 1,
                       0, self.n_shards - 1)

    # -- stacked single-dispatch path ---------------------------------------
    def stacked_impl(self, backend: str = "jnp", *, block: int = 512,
                     probe: str | None = None, cache_slots: int = 0):
        """The fused shard-major stacked path for this snapshot on
        ``backend`` (resolved through the registry), or ``None`` when the
        shards' static parameters could not be unified. Cached per
        configuration — including ``None`` results — so a benchmark sweep
        that alternates backends per call never rebuilds device planes."""
        spec = get_backend(backend)
        if spec.stacked_factory is None:
            raise ValueError(
                f"backend {backend!r} has no stacked device path")
        cfg = (backend, block, probe, cache_slots)
        if cfg not in self._stacked:
            hps = (self._host_planes_fn()
                   if self._host_planes_fn is not None else None)
            self._stacked[cfg] = spec.stacked_factory(
                [s.plex for s in self.shards], self.offsets, block=block,
                probe=probe, cache_slots=cache_slots, host_planes=hps,
                sharding=None)
            if self._stacked[cfg] is None:
                # the serving layer drops to per-shard host routing: say so
                log.warning(
                    "snapshot epoch %d: the layers of its %d shards do not "
                    "unify into one stacked %s pipeline; lookups take the "
                    "per-shard host-routed path", self.epoch, self.n_shards,
                    backend)
            self._stacked_built = True
        self._stacked_last = self._stacked[cfg]
        return self._stacked_last

    def built_stacked(self):
        """The most recently served stacked impl if one has already been
        built, else ``None`` — a side-effect-free peek (no device plane
        construction) for callers that only need to poke an existing
        instance (cache reset)."""
        return self._stacked_last if self._stacked_built else None

    # -- durability (persist subsystem) --------------------------------------
    def save(self, gen_dir, *, fsync: bool = True):
        """Serialise this snapshot into ``gen_dir`` (one generation of the
        on-disk format; see ``persist.format``). Standalone use only — a
        durable ``PlexService`` manages generations + manifest itself."""
        from ..persist.format import save_snapshot
        return save_snapshot(gen_dir, self, fsync=fsync)

    @classmethod
    def load(cls, gen_dir, *, verify: bool = False) -> "Snapshot":
        """Memmap one persisted generation back into an immutable snapshot
        (no index rebuild; see ``persist.format.load_snapshot``)."""
        from ..persist.format import load_snapshot
        return load_snapshot(gen_dir, verify=verify)
