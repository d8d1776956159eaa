"""Parallel sharded PLEX build — fan the per-shard build over a pool.

``Snapshot.build`` is a loop of independent per-shard ``build_plex`` calls
(spline fit + auto-tune + radix/CHT layer, no shared state), so the build
is embarrassingly parallel at shard granularity. This module is the fan-out
engine behind ``Snapshot.build(..., workers=N)`` and the streamed durable
build (``build_generation``):

* **Key passing.** The whole key array is never pickled into a worker.
  Three transports, picked automatically per (array, pool):

  - file-backed ``np.memmap`` keys (the SOSD datasets): each worker re-maps
    the same file range read-only;
  - fork start method: the parent parks the array in a module-level table
    before the pool forks, so children inherit the pages copy-on-write;
  - spawn start method (the parent has already imported jax, e.g. it
    holds the chip): each task carries its own shard's keys, pickled once
    — one memcpy per key, and a worker holds one shard, never the whole
    array. (Mapping one shared scratch file instead charged each worker
    on a TPU host about 1.7 GB resident for the whole 1.6 GB array.)
    Workers import only the jax-free ``repro.core``, so no worker ever
    initialises a jax backend or reaches for the parent's chip.

* **Shard-order streaming.** ``iter_built_shards`` yields ``(s, PLEX)`` in
  shard order as soon as each shard (and all its predecessors) completes,
  buffering only out-of-order completions — the streamed snapshot writer
  appends shard planes to disk and drops each PLEX immediately, so a
  200M-key build never holds every shard's index in memory at once.

* **Bit-identity.** Workers run the exact same ``build_plex`` on the exact
  same key bytes; only the schedule changes. The parallel result is
  asserted bit-identical to the serial one by ``tests/test_parallel_build``
  and the ``build_scale`` bench (same planes, same persisted bytes).

Workers strip ``PLEX.keys`` before returning (the parent re-attaches its
own ``keys[lo:hi]`` view), so result pickling moves only the index planes —
~2 x spline size — never the data.

The module's top-level imports stay jax-free and cheap: a spawned worker
pays one small ``repro.core`` import, not a jax initialisation.
"""
from __future__ import annotations

import concurrent.futures
import itertools
import multiprocessing
import pathlib
import sys
import threading
import time
from typing import Iterator

import numpy as np

from ..obs.trace import TRACE
from ..resilience.faults import POINT_BUILD_SHARD, fire
from .plex import PLEX, build_plex

__all__ = ["build_generation", "build_shard_plexes", "iter_built_shards",
           "spans_of"]

# -- worker-side shared key array --------------------------------------------
# one slot per worker process: set by the pool initializer, read by every
# task. Thread pools bypass this entirely (they share the parent's array).
_WORKER_KEYS: np.ndarray | None = None

# parent-side table for the fork transport: arrays parked here before the
# pool forks are inherited copy-on-write by the children. Keyed by a unique
# token so concurrent builds in one process never collide.
_INHERITED: dict[int, np.ndarray] = {}
_token_counter = itertools.count(1)
_token_lock = threading.Lock()


def _keys_descriptor(keys: np.ndarray, start_method: str):
    """-> (picklable transport descriptor, cleanup callable). The
    descriptor tells ``_pool_init`` how to materialise the key array in a
    worker; ``None`` means no shared array: each task carries its own
    shard's keys."""
    if isinstance(keys, np.memmap) and getattr(keys, "filename", None):
        return (("mmap", str(keys.filename), int(keys.offset),
                 str(keys.dtype.str), int(keys.size)), lambda: None)
    if start_method == "fork":
        with _token_lock:
            token = next(_token_counter)
        _INHERITED[token] = keys
        return ("inherit", token), lambda: _INHERITED.pop(token, None)
    return None, lambda: None


def _pool_init(desc) -> None:
    """Worker initializer: materialise the shared key array once per
    worker process (module global), whatever the transport."""
    global _WORKER_KEYS
    if desc is None:
        return
    kind = desc[0]
    if kind == "mmap":
        _, path, offset, dtype, n = desc
        _WORKER_KEYS = np.memmap(path, dtype=np.dtype(dtype), mode="r",
                                 offset=offset, shape=(n,))
    else:
        _WORKER_KEYS = _INHERITED[desc[1]]


def _build_shard_task(s: int, lo: int, hi: int, eps: int, build_kw: dict,
                      shard_keys: np.ndarray | None = None
                      ) -> tuple[int, PLEX]:
    """One worker task: build shard ``s`` over ``shard_keys`` when the task
    carries them, else over the process-shared key array. ``keys`` is
    stripped before pickling the result back — the parent re-attaches its
    own view, so only index planes cross the pipe."""
    if shard_keys is None:
        shard_keys = _WORKER_KEYS[lo:hi]
    px = build_plex(shard_keys, eps, **build_kw)
    px.keys = None
    return s, px


def spans_of(offsets: np.ndarray, n_keys: int) -> list[tuple[int, int]]:
    """Per-shard [lo, hi) key spans from the shard offset table."""
    return [(int(offsets[s]),
             int(offsets[s + 1]) if s + 1 < len(offsets) else int(n_keys))
            for s in range(len(offsets))]


def _mp_context(mp_context=None) -> multiprocessing.context.BaseContext:
    """Pick the process start method: an explicit context wins; otherwise
    fork (cheapest, copy-on-write key inheritance) unless jax is already
    initialised in this process — forking a process with live XLA runtime
    threads is not safe, so those fall back to spawn (workers re-import
    the jax-free ``repro.core`` only)."""
    if mp_context is not None:
        if isinstance(mp_context, str):
            return multiprocessing.get_context(mp_context)
        return mp_context
    if "fork" in multiprocessing.get_all_start_methods() \
            and "jax" not in sys.modules:
        return multiprocessing.get_context("fork")
    return multiprocessing.get_context("spawn")


def iter_built_shards(keys: np.ndarray, offsets: np.ndarray, eps: int, *,
                      workers: int = 1, pool: str = "process",
                      mp_context=None, timings: dict | None = None,
                      **build_kw) -> Iterator[tuple[int, PLEX]]:
    """Yield ``(shard_index, PLEX)`` in shard order, building up to
    ``workers`` shards concurrently.

    Each yielded PLEX has its ``keys`` re-attached as a view of the
    parent's ``keys`` array (same aliasing as the serial build). Results
    are yielded as soon as each shard *and all its predecessors* are done,
    so a streaming consumer can write shard ``s`` to disk while shards
    ``> s`` are still building. ``workers <= 1`` (or a single shard)
    degrades to the serial in-process loop — no pool, no transport.

    ``timings`` gets the wall seconds of ``build.pool`` (the pool started
    and every task queued with its keys) and ``build.shards`` (the first
    result awaited to the pool shut down, the consumer's time between
    yields included); each is also a span while ``TRACE`` is on. Each
    shard is a ``build.shard`` event carrying its worker's CPU-seconds
    (``cpu_s``): workers overlap, so that is no interval of the parent's
    clock."""
    timings = {} if timings is None else timings
    spans = spans_of(offsets, keys.size)
    if workers <= 1 or len(spans) <= 1 or pool == "serial":
        with TRACE.timed("build.shards", timings):
            for s, (lo, hi) in enumerate(spans):
                fire(POINT_BUILD_SHARD, shard=s)
                px = build_plex(keys[lo:hi], eps, **build_kw)
                TRACE.event("build.shard", cpu_s=px.stats.total_s,
                            shard=s, n_keys=hi - lo)
                yield s, px
        return
    if pool not in ("process", "thread"):
        raise ValueError(f"pool must be 'process', 'thread', or 'serial', "
                         f"got {pool!r}")

    workers = min(int(workers), len(spans))
    cleanup = lambda: None  # noqa: E731 - trivial no-op
    ex = None
    try:
        with TRACE.timed("build.pool", timings):
            if pool == "thread":
                ex = concurrent.futures.ThreadPoolExecutor(
                    max_workers=workers)

                def submit(s: int, lo: int, hi: int):
                    return ex.submit(
                        lambda: (s, build_plex(keys[lo:hi], eps,
                                               **build_kw)))
            else:
                ctx = _mp_context(mp_context)
                desc, cleanup = _keys_descriptor(keys,
                                                 ctx.get_start_method())
                ex = concurrent.futures.ProcessPoolExecutor(
                    max_workers=workers, mp_context=ctx,
                    initializer=_pool_init, initargs=(desc,))

                def submit(s: int, lo: int, hi: int):
                    return ex.submit(_build_shard_task, s, lo, hi, eps,
                                     build_kw, None if desc else keys[lo:hi])
            futs = {submit(s, lo, hi): s for s, (lo, hi) in enumerate(spans)}
        with TRACE.timed("build.shards", timings):
            ready: dict[int, PLEX] = {}
            next_s = 0
            for fut in concurrent.futures.as_completed(futs):
                s, px = fut.result()      # a worker failure propagates here
                if px.keys is None:       # process transport stripped it
                    lo, hi = spans[s]
                    px.keys = keys[lo:hi]
                ready[s] = px
                while next_s in ready:
                    fire(POINT_BUILD_SHARD, shard=next_s)
                    nxt = ready.pop(next_s)
                    TRACE.event("build.shard", cpu_s=nxt.stats.total_s,
                                shard=next_s, pool=pool)
                    yield next_s, nxt
                    next_s += 1
            ex.shutdown(wait=True)
    finally:
        # after an error or an abandoned iteration; a no-op after the
        # shutdown above
        if ex is not None:
            ex.shutdown(wait=True, cancel_futures=True)
        cleanup()


def build_shard_plexes(keys: np.ndarray, offsets: np.ndarray, eps: int, *,
                       workers: int = 1, pool: str = "process",
                       mp_context=None, timings: dict | None = None,
                       **build_kw) -> list[PLEX]:
    """All shard PLEXes in shard order (the ``Snapshot.build`` fan-out)."""
    return [px for _, px in iter_built_shards(
        keys, offsets, eps, workers=workers, pool=pool,
        mp_context=mp_context, timings=timings, **build_kw)]


def build_generation(root, keys: np.ndarray, eps: int, *,
                     n_shards: int | None = None, workers: int = 1,
                     pool: str = "process", mp_context=None,
                     epoch: int = 0, fsync: bool = True,
                     manifest: bool = True, **build_kw) -> pathlib.Path:
    """Parallel build streamed straight into one durable generation.

    The SOSD-scale path: shard planes are appended to the PR-4 snapshot
    format *as each shard completes* (``persist.format.SnapshotWriter``)
    and the built PLEX is dropped immediately, so peak memory is the key
    array plus O(workers) in-flight shard indexes — never the whole
    assembled snapshot. The generation is assembled by the manifest: when
    ``manifest=True`` the next generation number is taken from (and
    committed to) ``root/MANIFEST.json`` with a fresh empty WAL segment,
    making the directory directly servable by ``PlexService.open``.

    ``keys`` may be a read-only ``np.memmap`` of a raw uint64 file — the
    workers then re-map the file instead of copying anything, and the key
    plane is streamed to the output in bounded chunks.

    Returns the generation directory; raises before any manifest change on
    failure (a partial ``snapshot.plex.tmp`` is swept by the writer)."""
    from ..persist.format import SnapshotWriter
    from ..persist.manifest import (Manifest, gen_name, read_manifest,
                                    wal_name, write_manifest)
    from ..persist.wal import WriteAheadLog
    from .index import SHARD_MAX_KEYS, shard_offsets

    root = pathlib.Path(root)
    root.mkdir(parents=True, exist_ok=True)
    keys = np.asarray(keys)
    if keys.dtype != np.uint64:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
    if keys.size == 0:
        raise ValueError("cannot build a generation from an empty key set")
    if n_shards is None:
        n_shards = -(-keys.size // SHARD_MAX_KEYS)
    offsets = shard_offsets(keys, max(int(n_shards), 1))

    gen = 0
    if manifest:
        man = read_manifest(root)
        gen = man.generation + 1 if man is not None else 0
    gen_dir = root / gen_name(gen)

    t0 = time.perf_counter()
    writer = SnapshotWriter(gen_dir, n_shards_hint=len(offsets), fsync=fsync)
    try:
        writer.add_plane("keys", keys)
        writer.add_plane("offsets", np.ascontiguousarray(offsets, np.int64))
        for s, px in iter_built_shards(keys, offsets, eps, workers=workers,
                                       pool=pool, mp_context=mp_context,
                                       **build_kw):
            writer.add_shard(s, px)
            # px goes out of scope here: the streamed build never holds
            # every shard's index at once
        writer.finalize(eps=int(eps), epoch=int(epoch), n_keys=keys.size,
                        build_s=time.perf_counter() - t0)
    except BaseException:
        writer.abort()
        raise
    if manifest:
        wal = WriteAheadLog.create(root / wal_name(gen), fsync=fsync)
        wal.close()
        write_manifest(root, Manifest.for_generation(gen), fsync=fsync)
    return gen_dir
