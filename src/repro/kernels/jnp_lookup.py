"""Portable jit-compiled pure-jnp PLEX lookup (CPU/GPU/TPU, no Pallas).

Same pipeline as ``ops.DevicePlex`` — segment lookup (radix | CHT) ->
window gather -> eps-window probe — but expressed as plain ``jnp`` on the
shared ``PlexPlanes``, so it runs anywhere XLA does. The segment math is
literally the Pallas kernel bodies (``plex_segment_lookup``), which keeps
the two accelerated backends numerically identical; every search has a
fixed trip count, the TPU-friendly form inherited from
``core.plex.bounded_lower_bound``.

The final data probe has two numerically identical modes
(``plex_segment_lookup.probe_lower_bound``): a fixed-trip bisect, the
default on every backend, and the branchless count sweep, kept as an
explicit option. The sweep gathers the whole padded window per lane, the
bisect ``window.bit_length()`` single keys, and the gather's per-element
cost decides: bisect is 2-4x faster on CPU, and on a TPU v5e serving 200M
keys at eps 64 (window 256) the probe takes 239.5 ns a lookup against the
sweep's 13548, the whole pipeline 364 against 13673.

``StackedJnpPlex`` is the serving hot path: the shard-major fused layout
(``planes.StackedPlanes``) runs shard routing (predecessor count over the
shard-minima planes), the full radix->spline->probe pipeline, the per-shard
result clamp, and the global-offset fold inside **one** jit'd function —
one dispatch per micro-batch regardless of shard count, with an optional
device-side hot-key result cache threaded through as explicit state.
Passing a ``planes.DeltaPlanes`` buffer turns the same dispatch into a
*merged* lookup (``delta_rank_adjust``): snapshot ranks plus the delta
buffer's signed-weight prefix, matching searchsorted over the logical
updated key array at no extra dispatches. A micro-batch whose valid lanes
all hit the cache takes a ``lax.cond`` fast path that skips the snapshot
pipeline (the delta fold still applies — cached entries are
delta-independent snapshot ranks).

Batches are processed in fixed ``block``-shaped chunks so XLA compiles the
pipeline exactly once per index regardless of batch size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from ..core.plex import PLEX
from ..obs.metrics import METRICS
from .pairs import extract_bits, pair_le, split_u64
from .planes import (PLANE_ARRAYS, STACKED_ARRAYS, DeltaPlanes, PlexPlanes,
                     StackedPlanes, bind_planes, build_planes,
                     build_stacked_planes, finalize_indices, pad_queries)
from .plex_segment_lookup import (DEFAULT_BLOCK, cht_window_base,
                                  probe_lower_bound, radix_window_base,
                                  stacked_cht_window_base,
                                  stacked_radix_window_base)

PROBE_MODES = ("count", "bisect")


def default_probe_mode() -> str:
    """The fixed-trip bisect, on every backend: each of its rounds gathers
    one key per lane where the count sweep gathers the whole window, and
    gathered elements are what the probe pays for on CPU and TPU alike."""
    return "bisect"


def _jnp_pipeline(pp: PlexPlanes, probe: str, qhi, qlo):
    s = pp.static
    n_spline = pp.skhi.shape[0]
    if pp.kind == "radix":
        base = radix_window_base(
            qhi, qlo, pp.layer_arrays["table"], pp.skhi, pp.sklo, pp.spos,
            shift=s["shift"], r=s["r"], min_hi=s["min_hi"],
            min_lo=s["min_lo"], max_win=s["max_win"], n_spline=n_spline,
            eps_eff=pp.eps_eff, n_data=pp.n_data, window=pp.window,
            mode=s["mode"])
    else:
        bins = jnp.stack([extract_bits(qhi, qlo, lvl * s["r"], s["r"])
                          for lvl in range(s["levels"])])
        base = cht_window_base(
            qhi, qlo, bins, pp.layer_arrays["cells"], pp.skhi, pp.sklo,
            pp.spos, r=s["r"], levels=s["levels"], delta=s["delta"],
            n_spline=n_spline, eps_eff=pp.eps_eff, n_data=pp.n_data,
            window=pp.window, mode=s["mode"])
    return probe_lower_bound(qhi, qlo, pp.dhi, pp.dlo, base,
                             window=pp.window, mode=probe)


@dataclasses.dataclass
class JnpPlex:
    """jit'd pure-jnp lookup over ``PlexPlanes`` (same contract as
    ``DevicePlex.lookup``; backend-portable)."""

    planes: PlexPlanes
    block: int
    probe: str = "bisect"
    _fn: Any = None

    @classmethod
    def from_plex(cls, px: PLEX, *, block: int = DEFAULT_BLOCK,
                  device=None, probe: str | None = None) -> "JnpPlex":
        probe = probe or default_probe_mode()
        if probe not in PROBE_MODES:
            raise ValueError(f"unknown probe mode {probe!r}")
        pp = build_planes(px)
        if device is not None:
            put = functools.partial(jax.device_put, device=device)
            pp = dataclasses.replace(
                pp, skhi=put(pp.skhi), sklo=put(pp.sklo), spos=put(pp.spos),
                dhi=put(pp.dhi), dlo=put(pp.dlo),
                layer_arrays={k: put(v) for k, v in pp.layer_arrays.items()})
        jp = cls(planes=pp, block=block, probe=probe)
        jp._fn = bind_planes(
            lambda p, qhi, qlo: _jnp_pipeline(p, probe, qhi, qlo), pp,
            PLANE_ARRAYS)
        return jp

    def lookup_planes(self, qhi, qlo):
        """One [block]-shaped chunk of query planes -> raw int32 indices
        (may exceed ``n_real`` for past-the-end absent keys; callers clamp).
        Dispatches asynchronously: the result is a device array."""
        return self._fn(qhi, qlo)

    def lookup(self, q: np.ndarray) -> np.ndarray:
        """Batched lookup; same contract as PLEX.lookup for present keys."""
        qp, b = pad_queries(q, self.block)
        qh, ql = split_u64(qp)
        # dispatch every chunk eagerly, sync once at np.concatenate
        outs = [self._fn(jnp.asarray(qh[i:i + self.block]),
                         jnp.asarray(ql[i:i + self.block]))
                for i in range(0, qp.size, self.block)]
        return finalize_indices(np.concatenate([np.asarray(o) for o in outs]),
                                b, self.planes.n_real)


def _route(sp: StackedPlanes, qhi, qlo):
    """Shard id per query: predecessor count over the shard-minima planes
    (== host-side ``searchsorted(shard_min, q, 'right') - 1``, clipped)."""
    if sp.n_shards == 1:
        return jnp.zeros(qhi.shape, jnp.int32)
    le = pair_le(sp.min_hi[None, :], sp.min_lo[None, :],
                 qhi[:, None], qlo[:, None])
    cnt = jnp.sum(le.astype(jnp.int32), axis=1)
    return jnp.clip(cnt - 1, 0, sp.n_shards - 1)


@contextlib.contextmanager
def _stage(name: str):
    """One stage of the stacked pipeline: a ``jax.named_scope`` (the HLO
    ``op_name`` that ``stage_of_ops`` reads) and the same name as an XLA
    frontend attribute. JAX's persistent compile cache keys a program by
    its IR without debug info, ``op_name`` included; the attribute is IR,
    so the cache never serves this program from an entry compiled with
    other stage boundaries, or none, whose ``op_name`` would be stale."""
    with jax.named_scope(name), set_xla_metadata(plex_stage=name):
        yield


def _stacked_pipeline_aux(sp: StackedPlanes, probe: str, qhi, qlo):
    """The stacked pipeline plus its observability by-products.

    Returns ``(res, sid, dist)``: the global clamped indices (exactly
    ``_stacked_pipeline``'s result — it is this function's first output),
    the routed shard id per lane, and the probe travel ``got - (base +
    row)`` — how far past the spline's eps-window base the final probe
    landed, the measured per-query error the piecewise-linear-
    approximation analysis needs. Both extras are values the pipeline
    already computes; exposing them costs nothing when untraced (XLA
    dead-code-eliminates unused outputs in the plain wrapper below).

    Each stage runs under a named scope (``_stage``): ``plex.route``,
    ``plex.segment`` (radix or CHT descent, spline segment search and
    interpolation up to the window base), ``plex.probe`` (the eps-window
    probe over ``dhi``/``dlo``) and ``plex.fold`` (clamp and global
    offset; ``delta_rank_adjust`` too). The scopes are metadata in the
    HLO and cost nothing at run time; ``StackedJnpPlex.stage_of_ops``
    reads them back.
    """
    with _stage("plex.route"):
        sid = _route(sp, qhi, qlo)
    s = sp.static
    la = sp.layer_arrays
    with _stage("plex.segment"):
        if sp.kind == "radix":
            base = stacked_radix_window_base(
                qhi, qlo, sid, la["table"], la["table_off"], la["shift"],
                la["p_max"], la["lmin_hi"], la["lmin_lo"], sp.skhi, sp.sklo,
                sp.spos, sp.n_spline, n_spline_max=sp.n_spline_max,
                max_win=s["max_win"], eps_eff=sp.eps_eff,
                n_data_max=sp.n_data_max, window=sp.window, mode=s["mode"])
        else:
            bins = jnp.stack([extract_bits(qhi, qlo, lvl * s["r"], s["r"])
                              for lvl in range(s["levels"])])
            base = stacked_cht_window_base(
                qhi, qlo, sid, bins, la["cells"], la["cells_off"],
                la["delta"], sp.skhi, sp.sklo, sp.spos, sp.n_spline,
                r=s["r"], levels=s["levels"], delta_max=s["delta_max"],
                n_spline_max=sp.n_spline_max, eps_eff=sp.eps_eff,
                n_data_max=sp.n_data_max, window=sp.window, mode=s["mode"])
    with _stage("plex.probe"):
        row = sid * jnp.int32(sp.n_data_max)
        got = probe_lower_bound(qhi, qlo, sp.dhi, sp.dlo, row + base,
                                window=sp.window, mode=probe)
        dist = got - (row + base)
    with _stage("plex.fold"):
        local = jnp.minimum(got - row, jnp.take(sp.n_real, sid))
        return local + jnp.take(sp.row_off, sid), sid, dist


def _stacked_pipeline(sp: StackedPlanes, probe: str, qhi, qlo):
    """Route + segment + probe + clamp + global-offset fold, one dispatch.

    Returns global int32 first-occurrence indices (already clamped to each
    shard's real key count and shifted by its global offset) — the host
    only strips padding lanes.
    """
    return _stacked_pipeline_aux(sp, probe, qhi, qlo)[0]


# probe-travel histogram resolution of the device counter plane: bucket 0
# is an exact window-base landing (0 probe steps), bucket k covers travel
# in [2^(k-1), 2^k), the last bucket overflows — 16 buckets span any eps
N_PROBE_BUCKETS = 16


def _probe_bucket(dist):
    """log2 bucket of a probe travel distance (int32 lanes -> int32)."""
    d = jnp.maximum(dist, 1).astype(jnp.float32)
    b = jnp.where(dist <= 0, 0, jnp.floor(jnp.log2(d)).astype(jnp.int32) + 1)
    return jnp.clip(b, 0, N_PROBE_BUCKETS - 1)


def _stacked_counted(aux, n_shards: int, cap: int, sp, qhi, qlo, n_valid,
                     counters, dkhi=None, dklo=None, dcum=None):
    """The counted dispatch: stacked (optionally merged) pipeline plus the
    device-resident telemetry counter plane.

    ``counters`` is explicit state threaded exactly like the hot-key
    cache: one uint32 array of ``n_shards + N_PROBE_BUCKETS`` slots
    (uint32, not int64 — jax serves with x64 disabled), laid out as
    ``[per-shard routed-query counts | probe-travel histogram]``. Each
    valid lane scatter-adds 1 into its routed shard's slot and its probe
    bucket's slot — two ``at[].add`` scatters fused into the same jit
    dispatch, so live shard hotness costs no extra dispatches and no
    sample pass. Padded lanes (masked by ``n_valid``) count nowhere, so
    the folded host counts equal ``np.bincount(snap.route(q))`` exactly
    on any single-threaded stream. ``cap`` appends the merged delta fold
    (``cap == 0`` is the read-only epoch), which adjusts results only —
    routing and probing are snapshot-side work either way.
    """
    res, sid, dist = aux(sp, qhi, qlo)
    inc = (jax.lax.iota(jnp.int32, qhi.shape[0])
           < n_valid).astype(jnp.uint32)
    counters = counters.at[sid].add(inc)
    counters = counters.at[jnp.int32(n_shards) + _probe_bucket(dist)].add(inc)
    if cap:
        res = res + delta_rank_adjust(qhi, qlo, dkhi, dklo, dcum, cap=cap)
    return res, counters


def delta_rank_adjust(qhi, qlo, dkhi, dklo, dcum, *, cap: int):
    """Merged-lookup rank adjustment against a device-resident delta buffer.

    ``planes.DeltaPlanes`` layout: sorted delta key planes padded to the
    static capacity ``cap`` with the max u64 key, plus the exclusive signed
    weight prefix ``dcum`` (+1 per live insert, -multiplicity per tombstone).
    The adjustment for query ``q`` is ``dcum[# delta keys < q]``: one
    fixed-trip bisect (``ceil(log2(cap + 1))`` gather rounds) plus one
    gather — cheap enough to fold into the snapshot pipeline's single jit
    dispatch, which is what keeps merged lookups at one dispatch per
    micro-batch.
    """
    with _stage("plex.fold"):
        zero = jnp.zeros(qhi.shape, jnp.int32)
        cnt = probe_lower_bound(qhi, qlo, dkhi, dklo, zero, window=cap,
                                mode="bisect")
        return jnp.take(dcum, cnt)


def _stacked_merged(pipeline, cap: int, sp, qhi, qlo, dkhi, dklo, dcum):
    """Snapshot pipeline + delta fold: global *merged* first-occurrence
    indices equal to searchsorted over the logical (snapshot - tombstones +
    inserts) key array, in one dispatch. ``pipeline`` is the backend's
    snapshot-rank function ``(planes, qhi, qlo) -> int32 [B]`` (the jnp stacked
    pipeline here; the Pallas backend fuses the fold into its kernel and
    does not use this composition)."""
    out = pipeline(sp, qhi, qlo)
    return out + delta_rank_adjust(qhi, qlo, dkhi, dklo, dcum, cap=cap)


def _cache_slot(qhi, qlo, n_slots: int):
    """Direct-mapped slot per query: a 32-bit multiplicative mix of both key
    words, masked to the power-of-two capacity."""
    h = (qlo * jnp.uint32(0x9E3779B1)) ^ (qhi * jnp.uint32(0x85EBCA77))
    h ^= h >> 16
    return (h & jnp.uint32(n_slots - 1)).astype(jnp.int32)


_CACHE_EMPTY = 0xFFFFFFFF   # sentinel value row; real indices are < 2^31


def _stacked_cached(pipeline, cap: int, sp, qhi, qlo,
                    n_valid, cache, dkhi=None, dklo=None, dcum=None):
    """Stacked (optionally merged) pipeline + device hot-key result cache.

    ``pipeline`` is the backend's snapshot-rank function ``(planes, qhi,
    qlo) -> int32 [B]`` — the jnp stacked pipeline or the fused Pallas
    kernel; the cache resolution, write-through, and delta fold below are
    backend-independent management that wraps whichever pipeline misses
    run through.

    The cache is explicit state threaded through every micro-batch: one
    uint32 [3, n_slots] array (rows: key hi, key lo, cached *snapshot*
    rank; value ``_CACHE_EMPTY`` marks an empty slot). Hits select the
    cached rank; every lane write-through inserts its (key, snapshot rank)
    as a single whole-column scatter, so a colliding batch can never tear
    a slot's (key, value) pair even where duplicate-scatter order is
    unspecified.

    Caching snapshot ranks — not merged results — is what makes entries
    *delta-independent*: the delta fold is applied after cache resolution
    on every lane, so the cache stays valid across insert/delete
    mutations with no invalidation (and no writer/reader race on a reset),
    and dies naturally with its snapshot at a swap.

    Fast path: when *every* valid lane hits (``n_valid`` masks the padded
    tail, whose lanes replicate the last valid query), a ``lax.cond``
    skips the snapshot pipeline entirely — full-hit micro-batches cost a
    hash, three gathers, a compare, and (in updated epochs) the delta
    bisect instead of the whole lookup, still within the same single
    dispatch. Results are bit-identical with the cache on or off.

    ``cap == 0`` means no delta buffer (a read-only epoch); ``cap > 0``
    appends the ``DeltaPlanes`` arrays. Returns (results, new cache,
    valid-lane hit count, full-hit flag).
    """
    slot = _cache_slot(qhi, qlo, cache.shape[1])
    ckhi, cklo, cval = (jnp.take(cache[0], slot), jnp.take(cache[1], slot),
                        jnp.take(cache[2], slot))
    hit = (cval != jnp.uint32(_CACHE_EMPTY)) & (ckhi == qhi) & (cklo == qlo)
    valid = jax.lax.iota(jnp.int32, qhi.shape[0]) < n_valid
    full_hit = jnp.all(hit | ~valid)

    def fast(_):
        return cval.astype(jnp.int32)

    def slow(_):
        return pipeline(sp, qhi, qlo)

    snap = jax.lax.cond(full_hit, fast, slow, None)
    snap = jnp.where(hit, cval.astype(jnp.int32), snap)
    new = cache.at[:, slot].set(
        jnp.stack([qhi, qlo, snap.astype(jnp.uint32)]))
    res = snap
    if cap:
        res = res + delta_rank_adjust(qhi, qlo, dkhi, dklo, dcum, cap=cap)
    return res, new, jnp.sum((hit & valid).astype(jnp.int32)), full_hit


class LaneResult(NamedTuple):
    """One micro-batch dispatch: async device results + cache telemetry
    (``hits``/``full_hit`` are device scalars, ``None`` with the cache
    off — readable without an extra dispatch at the caller's sync point)."""
    out: Any
    hits: Any = None
    full_hit: Any = None


@dataclasses.dataclass
class StackedJnpPlex:
    """Single-dispatch multi-shard lookup over ``StackedPlanes``.

    With a ``DeltaPlanes`` buffer passed to ``lookup_planes`` the dispatch
    becomes a *merged* lookup — snapshot ranks plus delta rank adjustment,
    still one jit call per micro-batch. The merged variants are compiled
    lazily per delta capacity (``_merged_fns``/``_cached_fns``); the
    delta-free fns stay separate so read-only epochs pay nothing for
    updatability.

    This class is also the base of every stacked device backend: the
    micro-batch management (lazy per-capacity compilation, hot-key cache
    state, ``lookup_planes``/``lookup``) is backend-independent, and a
    subclass swaps the compute by overriding the builder hooks —
    ``_snapshot_fn`` (snapshot ranks; feeds the cached wrapper) and
    ``_build_fn`` (the full, possibly delta-merged dispatch). The Pallas
    backend (``stacked_pallas.StackedPallasPlex``) overrides exactly
    those two.
    """

    planes: StackedPlanes
    block: int
    probe: str
    cache_slots: int = 0
    sharding: Any = None      # device placement of the planes (distrib)
    _fn: Any = None           # delta-free pipeline (read-only epochs)
    _cached_fn: Any = None    # delta-free pipeline + hot-key cache
    _cache: Any = None        # uint32 [3, n_slots] device array or None
    _merged_fns: dict = dataclasses.field(default_factory=dict)
    _cached_merged_fns: dict = dataclasses.field(default_factory=dict)
    # observability: counted dispatches (per-cap, like _merged_fns) and the
    # device-resident uint32 counter plane they thread (None until armed)
    _counted_fns: dict = dataclasses.field(default_factory=dict)
    _counters: Any = None

    @classmethod
    def from_plexes(cls, plexes: Sequence[PLEX], row_off: np.ndarray, *,
                    block: int = DEFAULT_BLOCK, probe: str | None = None,
                    cache_slots: int = 0, host_planes=None,
                    sharding=None, **impl_kw) -> "StackedJnpPlex | None":
        """Build the fused stacked path, or ``None`` when the shards' static
        parameters cannot be unified (the caller falls back to per-shard
        dispatch). ``host_planes`` feeds a persisted snapshot's precomputed
        per-shard planes straight through (warm start, no re-derivation).
        ``sharding`` places the planes (and the hot-key cache state) on one
        mesh device — the distrib partitioner's per-device slab placement;
        queries fed to ``lookup_planes`` must then be committed to the same
        device so the dispatch stays device-local. Extra keywords pass
        through to the subclass constructor (backend-specific fields)."""
        probe = probe or default_probe_mode()
        if probe not in PROBE_MODES:
            raise ValueError(f"unknown probe mode {probe!r}")
        if cache_slots and cache_slots & (cache_slots - 1):
            raise ValueError("cache_slots must be a power of two")
        sp = build_stacked_planes(plexes, row_off, host_planes=host_planes,
                                  sharding=sharding)
        if sp is None:
            return None
        st = cls(planes=sp, block=block, probe=probe,
                 cache_slots=int(cache_slots), sharding=sharding, **impl_kw)
        st._fn = st._build_fn(0)
        if cache_slots:
            st._cached_fn = st._build_cached_fn(0)
            cache = np.full((3, cache_slots), _CACHE_EMPTY, np.uint32)
            st._cache = (jnp.asarray(cache) if sharding is None
                         else jax.device_put(cache, sharding))
        return st

    # -- backend builder hooks ----------------------------------------------
    def _bind(self, body):
        """jit ``body(planes, *args)`` over this impl's planes, passed as
        program arguments (``planes.bind_planes``)."""
        return bind_planes(body, self.planes, STACKED_ARRAYS)

    def _snapshot_fn(self):
        """The snapshot-rank pipeline ``(planes, qhi, qlo) -> int32 [B]``
        (untraced; the merged and cached wrappers compose around it).
        Subclasses override."""
        probe = self.probe
        return lambda sp, qhi, qlo: _stacked_pipeline(sp, probe, qhi, qlo)

    def _build_fn(self, cap: int):
        """jit'd full dispatch at delta capacity ``cap`` (0 = delta-free:
        ``(qhi, qlo)``; else merged: ``(qhi, qlo, dkhi, dklo, dcum)``).
        Subclasses override to swap the compute."""
        if cap == 0:
            return self._bind(self._snapshot_fn())
        return self._bind(functools.partial(_stacked_merged,
                                            self._snapshot_fn(), cap))

    def _build_cached_fn(self, cap: int):
        """jit'd hot-key-cached dispatch at delta capacity ``cap``. The
        cache wrapper itself is backend-independent (``_stacked_cached``);
        it wraps whatever ``_snapshot_fn`` the backend supplies, so cached
        misses still run the backend's own pipeline."""
        return self._bind(functools.partial(_stacked_cached,
                                            self._snapshot_fn(), cap))

    def _aux_fn(self):
        """The instrumented pipeline ``(planes, qhi, qlo) -> (res, sid,
        dist)`` feeding the counted dispatch. The default is the jnp
        expression of the stacked pipeline — every stacked backend shares
        the same planes and the same pipeline math (the Pallas kernel body
        *is* this pipeline), so the counted results are bit-identical to
        the backend's own by construction."""
        probe = self.probe
        return lambda sp, qhi, qlo: _stacked_pipeline_aux(sp, probe, qhi, qlo)

    def _build_counted_fn(self, cap: int):
        """jit'd counted dispatch at delta capacity ``cap`` (observability
        armed): full pipeline + the telemetry counter-plane scatter."""
        return self._bind(functools.partial(
            _stacked_counted, self._aux_fn(), self.planes.n_shards, cap))

    def stage_of_ops(self, qhi, qlo) -> dict[str, str]:
        """``{instruction name: stage}`` of the delta-free serving program
        for query planes like ``qhi``/``qlo`` (arrays or
        ``jax.ShapeDtypeStruct``; ``planes.stage_of_hlo`` gives the rule).
        Compiles the program again: call it on demand, after a measured
        window, never on the serving path."""
        return self._fn.stage_of_ops(qhi, qlo)

    def _counted_fn(self, cap: int):
        fn = self._counted_fns.get(cap)
        if fn is None:
            fn = self._build_counted_fn(cap)
            self._counted_fns[cap] = fn
        return fn

    def _fresh_counters(self):
        z = np.zeros(self.planes.n_shards + N_PROBE_BUCKETS, np.uint32)
        return jnp.asarray(z) if self.sharding is None \
            else jax.device_put(z, self.sharding)

    def take_counters(self) -> tuple[np.ndarray, np.ndarray] | None:
        """Fold the device telemetry counter plane back to host and reset
        it (the serving layer's sync-point hook). Returns ``(shard_counts,
        probe_hist)`` as host int64 arrays, or ``None`` when no counted
        dispatch has run. Best-effort under concurrent dispatches — a
        dispatch racing the reset may drop its counts (same contract as
        the cache state); single-threaded streams fold exactly."""
        c = self._counters
        if c is None:
            return None
        self._counters = self._fresh_counters()
        host = np.asarray(c).astype(np.int64)
        n = self.planes.n_shards
        return host[:n], host[n:]

    @property
    def n_real_total(self) -> int:
        return self.planes.n_real_total

    def reset_cache(self) -> None:
        """Empty the hot-key cache. Not needed on updates — entries hold
        delta-independent snapshot ranks — but kept for manual telemetry
        resets; a snapshot swap retires the whole impl (cache included)."""
        if self._cache is not None:
            cache = np.full((3, self.cache_slots), _CACHE_EMPTY, np.uint32)
            self._cache = (jnp.asarray(cache) if self.sharding is None
                           else jax.device_put(cache, self.sharding))

    def _merged_fn(self, cap: int):
        fn = self._merged_fns.get(cap)
        if fn is None:
            fn = self._build_fn(cap)
            self._merged_fns[cap] = fn
        return fn

    def _cached_merged_fn(self, cap: int):
        fn = self._cached_merged_fns.get(cap)
        if fn is None:
            fn = self._build_cached_fn(cap)
            self._cached_merged_fns[cap] = fn
        return fn

    def lookup_planes(self, qhi, qlo, n_valid: int | None = None,
                      delta: DeltaPlanes | None = None) -> LaneResult:
        """One [block]-shaped chunk of query planes -> ``LaneResult`` of
        global int32 indices (+ cache telemetry). Dispatches asynchronously
        and advances the cache state. ``delta`` folds the device-resident
        delta buffer into the same dispatch (merged lookup); ``n_valid``
        marks the real (unpadded) lane count for cache accounting."""
        dp = delta if delta is not None and delta.n_entries else None
        if METRICS.enabled and METRICS.counted_dispatch:
            # counted dispatch: same pipeline + the telemetry counter
            # plane, bypassing the hot-key cache on purpose — the live
            # hotness estimate must see every query through the full
            # pipeline (a cache absorbs exactly the hottest keys, which
            # would bias the estimate precisely where it matters), and
            # probe-travel is only meaningful on actually-probed lanes.
            # Results are bit-identical either way (the cache contract).
            # The armed flight recorder clears ``counted_dispatch`` so the
            # always-on posture serves through the plain kernels below.
            nv = np.int32(self.block if n_valid is None else n_valid)
            if self._counters is None:
                self._counters = self._fresh_counters()
            if dp is None:
                out, self._counters = self._counted_fn(0)(
                    qhi, qlo, nv, self._counters)
            else:
                out, self._counters = self._counted_fn(dp.cap)(
                    qhi, qlo, nv, self._counters, dp.khi, dp.klo, dp.cum0)
            return LaneResult(out)
        if self._cache is not None:
            nv = np.int32(self.block if n_valid is None else n_valid)
            if dp is None:
                out, self._cache, hits, fh = self._cached_fn(
                    qhi, qlo, nv, self._cache)
            else:
                out, self._cache, hits, fh = self._cached_merged_fn(dp.cap)(
                    qhi, qlo, nv, self._cache, dp.khi, dp.klo, dp.cum0)
            return LaneResult(out, hits, fh)
        if dp is None:
            return LaneResult(self._fn(qhi, qlo))
        return LaneResult(self._merged_fn(dp.cap)(qhi, qlo, dp.khi, dp.klo,
                                                  dp.cum0))

    def lookup(self, q: np.ndarray, delta: DeltaPlanes | None = None
               ) -> np.ndarray:
        """Batched global lookup (convenience; the serving layer drives
        ``lookup_planes`` directly for the async pipeline)."""
        qp, b = pad_queries(q, self.block)
        qh, ql = split_u64(qp)
        outs = []
        for i in range(0, qp.size, self.block):
            nv = min(self.block, max(b - i, 1))
            outs.append(self.lookup_planes(
                jnp.asarray(qh[i:i + self.block]),
                jnp.asarray(ql[i:i + self.block]), n_valid=nv,
                delta=delta).out)
        return np.concatenate([np.asarray(o) for o in outs])[:b].astype(
            np.int64)
