"""Device-plane preparation shared by every accelerated PLEX backend.

A host-built ``repro.core.PLEX`` is converted once into uint32 key planes
(TPUs have no u64, and the same representation is portable to any jax
backend), a float32 rank plane, max-key-padded data planes, and the static
search parameters (eps slack, window geometry, layer mode). Both the Pallas
pipeline (``ops.DevicePlex``) and the portable pure-jnp pipeline
(``jnp_lookup.JnpPlex``) consume the same ``PlexPlanes``, so their numeric
contracts agree by construction.

Stacked layout (multi-shard serving)
------------------------------------
``build_stacked_planes`` fuses the planes of *several* shard-local PLEX
indexes into one shard-major device layout so a whole routed batch runs
through a single jit'd pipeline (one dispatch per micro-batch, no per-shard
Python loop). Layout decision, recorded here because every stacked kernel
depends on it:

* Per-shard planes are padded to the max shard size and stored **flattened
  row-major** (``[n_shards * n_spline_max]`` / ``[n_shards * n_data_max]``),
  so a query routed to shard ``s`` gathers with one flat index
  ``s * row_len + local_idx`` — the same ``jnp.take`` the single-shard
  kernel bodies already use, which is what lets
  ``plex_segment_lookup.radix_window_base`` / ``cht_window_base`` serve both
  layouts.
* Spline/data pads are the max u64 key (never counted by the ``< q`` probe;
  count-mode gathers are clamped to the per-shard real length anyway), and
  the rank-plane pad repeats the last rank (never read: segments are clamped
  to ``n_spline_s - 2`` before interpolation).
* Static kernel parameters are **unified** across shards: window geometry
  takes the max (a wider-than-needed window is still correct — the probe
  counts, it does not bisect to an edge), while genuinely per-shard scalars
  (radix ``shift``/``min_key``/table extent, CHT ``delta``) become [S]
  parameter planes gathered per query. Shards whose layers cannot be
  unified (mixed radix/CHT kinds, or CHT shards with different radix
  widths; ``core.plex.layers_unify``) are rejected —
  ``build_stacked_planes`` returns ``None`` and the serving layer falls
  back to per-shard dispatch. ``Snapshot.build`` never produces such a
  shard set: it re-tunes the CHT shards of one over radix tables.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cht import CHT
from ..core.plex import PLEX, layers_unify
from ..core.radix_table import RadixTable
from .pairs import split_u64

COUNT_MODE_MAX = 512    # windows at most this wide use compare-and-count

_U64_MAX = np.iinfo(np.uint64).max


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class BoundPlanes:
    """A jit'd ``body(planes, *args)`` bound to one set of device planes.

    The plane arrays are *arguments* of the compiled program, never
    closed-over constants: a closure would bake every plane into the
    program (1.6 GB of constants for a 200M-key index) and upload it again
    with the executable. As arguments they stay where they were placed,
    and ``lower`` compiles from shapes alone when the bound arrays are
    ``jax.ShapeDtypeStruct`` (the ahead-of-time chip compile check)."""
    jitted: Any
    arrays: dict

    def __call__(self, *args):
        return self.jitted(self.arrays, *args)

    def lower(self, *args):
        return self.jitted.lower(self.arrays, *args)


def bind_planes(body, planes, fields: Sequence[str]) -> BoundPlanes:
    """jit ``body(planes, *args)`` with ``planes``' array ``fields`` passed
    as arguments (see ``BoundPlanes``); every other field of the planes
    dataclass stays a trace-time static."""
    def traced(arrays, *args):
        return body(dataclasses.replace(planes, **arrays), *args)
    return BoundPlanes(jax.jit(traced),
                       {f: getattr(planes, f) for f in fields})


def pad_queries(q: np.ndarray, block: int) -> tuple[np.ndarray, int]:
    """Pad a query batch to a block multiple by repeating the last query.

    Returns (padded queries, original batch size). Shared batch-entry
    contract of every accelerated backend.
    """
    q = np.asarray(q, dtype=np.uint64)
    b = q.size
    bp = round_up(max(b, block), block)
    if bp > b:
        q = np.concatenate([q, np.repeat(q[-1:], bp - b)])
    return q, b


def finalize_indices(out, n_queries: int, n_real: int) -> np.ndarray:
    """Strip padding lanes and clamp past-the-end absent-key results to
    ``n_real``. Shared batch-exit contract of every accelerated backend."""
    return np.minimum(np.asarray(out)[:n_queries].astype(np.int64), n_real)


@dataclasses.dataclass
class PlexPlanes:
    # spline planes
    skhi: Any
    sklo: Any
    spos: Any                 # float32 ranks
    # data planes (padded to >= window with the max key)
    dhi: Any
    dlo: Any
    n_data: int               # padded length
    n_real: int
    # layer
    kind: str                 # "radix" | "cht"
    layer_arrays: dict[str, Any]
    static: dict[str, Any]
    eps_eff: int
    window: int


# the device-array fields of PlexPlanes / StackedPlanes (``bind_planes``)
PLANE_ARRAYS = ("skhi", "sklo", "spos", "dhi", "dlo", "layer_arrays")


@dataclasses.dataclass
class _HostPlanes:
    """Host-side (numpy) planes + static params for one PLEX; the shared
    intermediate of the single-index and stacked builders."""
    skh: np.ndarray
    skl: np.ndarray
    spos: np.ndarray
    dh: np.ndarray
    dl: np.ndarray
    n_data: int
    n_real: int
    kind: str
    layer_np: dict[str, np.ndarray]
    static: dict[str, Any]
    eps_eff: int
    window: int


@dataclasses.dataclass
class _HostStatics:
    """The scalar half of ``_HostPlanes``: everything derivable without
    touching the bulk key array. The snapshot serialiser
    (``persist.format``) persists exactly this, so ``save`` never does
    O(n_keys) throwaway work and ``open`` never re-derives it."""
    kind: str
    layer_np: dict[str, np.ndarray]
    static: dict[str, Any]
    eps_eff: int
    window: int
    n_data: int
    n_real: int


def _host_statics(px: PLEX) -> _HostStatics:
    """Static search parameters of one PLEX (no plane construction).

    Float32 interpolation cannot reproduce the host's float64 predictions
    bit-for-bit, so the eps window is widened by a statically-computed
    ``slack`` (2 + max segment position span * 2^-22, covering worst-case
    f32 rounding of ``y0 + t*(y1-y0)``); correctness remains *by
    construction*, not by accident.
    """
    if px.spline.positions.size and px.spline.positions[-1] >= (1 << 24):
        raise ValueError("float32 rank plane supports < 2^24 positions; "
                         "shard the index first (serving does)")
    spans = np.diff(px.spline.positions)
    max_span = int(spans.max()) if spans.size else 1
    slack = int(np.ceil(max_span * 2.0 ** -22)) + 2
    eps_eff = px.eps + slack
    window = round_up(2 * eps_eff + 2, 128)
    n_real = px.keys.size
    n_pad = max(round_up(n_real, 128), window)

    if isinstance(px.layer, RadixTable):
        kind = "radix"
        mk = int(px.layer.min_key)
        layer_np = {"table": np.asarray(px.layer.table)}
        max_win = px.layer.max_window
        static = dict(shift=int(px.layer.shift), r=int(px.layer.r),
                      min_hi=(mk >> 32) & 0xFFFFFFFF,
                      min_lo=mk & 0xFFFFFFFF,
                      max_win=int(max_win),
                      mode="count" if max_win <= COUNT_MODE_MAX
                      else "bisect")
    else:
        assert isinstance(px.layer, CHT)
        kind = "cht"
        layer_np = {"cells": np.asarray(px.layer.cells)}
        static = dict(r=int(px.layer.r),
                      levels=int(px.layer.max_depth) + 1,
                      delta=int(px.layer.delta),
                      mode="count" if px.layer.delta + 1 <= COUNT_MODE_MAX
                      else "bisect")
    return _HostStatics(kind=kind, layer_np=layer_np, static=static,
                        eps_eff=eps_eff, window=window, n_data=n_pad,
                        n_real=n_real)


def _host_planes(px: PLEX) -> _HostPlanes:
    """Host PLEX -> host plane arrays + static search parameters (see
    ``_host_statics`` for the window/slack derivation)."""
    hs = _host_statics(px)            # includes the f32 rank-plane guard
    skh, skl = split_u64(px.spline.keys)
    spos = px.spline.positions.astype(np.float32)
    pad = np.full(hs.n_data - hs.n_real, _U64_MAX, dtype=np.uint64)
    dh, dl = split_u64(np.concatenate([px.keys, pad]))
    return _HostPlanes(skh=skh, skl=skl, spos=spos, dh=dh, dl=dl,
                       n_data=hs.n_data, n_real=hs.n_real, kind=hs.kind,
                       layer_np=hs.layer_np, static=hs.static,
                       eps_eff=hs.eps_eff, window=hs.window)


def build_planes(px: PLEX) -> PlexPlanes:
    """Host PLEX -> device planes + static search parameters."""
    hp = _host_planes(px)
    return PlexPlanes(skhi=jnp.asarray(hp.skh), sklo=jnp.asarray(hp.skl),
                      spos=jnp.asarray(hp.spos), dhi=jnp.asarray(hp.dh),
                      dlo=jnp.asarray(hp.dl), n_data=hp.n_data,
                      n_real=hp.n_real, kind=hp.kind,
                      layer_arrays={k: jnp.asarray(v)
                                    for k, v in hp.layer_np.items()},
                      static=hp.static, eps_eff=hp.eps_eff, window=hp.window)


@dataclasses.dataclass
class DeltaPlanes:
    """Device-resident sorted delta buffer planes (updatable serving).

    The logical content is a sorted multiset of (key, signed weight) entries:
    ``+1`` per live inserted key, ``-multiplicity`` per tombstoned snapshot
    key. ``cum0`` is the exclusive prefix sum of the weights (length
    ``cap + 1``, leading 0), so the merged-lookup rank adjustment for a
    query ``q`` is ``cum0[count of delta keys < q]`` — one fixed-trip
    bisect over the key planes plus one gather, cheap enough to fold into
    the stacked pipeline's single jit dispatch.

    ``cap`` is the padded static capacity (the jit'd merged pipeline is
    compiled per ``cap``; the serving layer grows it geometrically so a
    busy updatable service compiles the merged path a handful of times,
    not per update). Pad keys are the max u64 — never strictly below any
    query — with weight 0, so padding never perturbs the adjustment.
    """
    khi: Any                  # uint32 [cap]
    klo: Any                  # uint32 [cap]
    cum0: Any                 # int32 [cap + 1], exclusive weight prefix
    cap: int
    n_entries: int            # real (unpadded) entries


def move_delta_planes(dp: DeltaPlanes, sharding: Any) -> DeltaPlanes:
    """Re-place a delta buffer's device view onto ``sharding`` (device-to-
    device copy, async). The routed mesh path replicates the (small) delta
    onto every serving device so the merged fold stays device-local."""
    return DeltaPlanes(khi=jax.device_put(dp.khi, sharding),
                       klo=jax.device_put(dp.klo, sharding),
                       cum0=jax.device_put(dp.cum0, sharding),
                       cap=dp.cap, n_entries=dp.n_entries)


def build_delta_planes(keys: np.ndarray, weights: np.ndarray,
                       cap: int) -> DeltaPlanes:
    """Sorted delta entries -> padded device planes (see ``DeltaPlanes``)."""
    keys = np.asarray(keys, dtype=np.uint64)
    weights = np.asarray(weights, dtype=np.int64)
    if keys.size > cap:
        raise ValueError(f"delta size {keys.size} exceeds capacity {cap}")
    if np.any(keys[1:] < keys[:-1]):
        raise ValueError("delta keys must be sorted")
    kh, kl = split_u64(np.concatenate(
        [keys, np.full(cap - keys.size, _U64_MAX, dtype=np.uint64)]))
    cum0 = np.zeros(cap + 1, dtype=np.int64)
    np.cumsum(weights, out=cum0[1:keys.size + 1])
    cum0[keys.size + 1:] = cum0[keys.size]
    if np.abs(cum0).max(initial=0) >= (1 << 31):
        raise ValueError("delta weight prefix exceeds int32 range")
    return DeltaPlanes(khi=jnp.asarray(kh), klo=jnp.asarray(kl),
                       cum0=jnp.asarray(cum0.astype(np.int32)), cap=int(cap),
                       n_entries=int(keys.size))


@dataclasses.dataclass
class StackedPlanes:
    """Shard-major fused planes of several shard-local PLEX indexes.

    Row-flattened per-shard planes plus [S] parameter planes; consumed by
    the single-dispatch stacked pipeline in ``jnp_lookup.StackedJnpPlex``
    (see the module docstring for the layout decision).
    """
    # spline planes, [S * n_spline_max] row-major flat
    skhi: Any
    sklo: Any
    spos: Any
    # data planes, [S * n_data_max] row-major flat
    dhi: Any
    dlo: Any
    # per-shard geometry planes, [S]
    n_spline: Any             # int32 real spline points per shard
    n_real: Any               # int32 real keys per shard
    row_off: Any              # int32 global key offset per shard
    min_hi: Any               # uint32 routing plane: first key per shard
    min_lo: Any
    # shapes / unified statics
    n_shards: int
    n_spline_max: int
    n_data_max: int
    n_real_total: int
    kind: str                 # "radix" | "cht"
    layer_arrays: dict[str, Any]
    static: dict[str, Any]
    eps_eff: int              # max over shards
    window: int               # max over shards


STACKED_ARRAYS = PLANE_ARRAYS + ("n_spline", "n_real", "row_off",
                                 "min_hi", "min_lo")


def build_stacked_planes(plexes: Sequence[PLEX], row_off: np.ndarray,
                         host_planes: Sequence[_HostPlanes] | None = None,
                         sharding: Any = None) -> StackedPlanes | None:
    """Fuse shard-local PLEX indexes into one ``StackedPlanes``.

    ``row_off[s]`` is shard ``s``'s global key offset (the serving layer's
    shard table) — pass *global* offsets for a contiguous shard subset and
    the stacked pipeline's results stay global with no extra fold, which
    is what the mesh partitioner (``distrib.partition``) relies on.
    Returns ``None`` when the shards' layers cannot be unified under one
    jit'd pipeline: mixed layer kinds, CHT shards with different radix
    widths, or a global key count past int32 range (the on-device global
    index plane is int32).

    ``host_planes`` short-circuits the per-shard host derivation: a
    memmapped snapshot (``persist.format``) supplies ``_HostPlanes`` built
    from the mapped arrays + persisted statics, so a warm start never
    recomputes slack/window/layer parameters.

    ``sharding`` places every device plane (a ``jax.sharding.Sharding`` or
    device; default = the uncommitted default device). The mesh
    partitioner passes a single-device ``NamedSharding`` so each device
    holds only its own shard-contiguous slab instead of a replica of all
    of them.
    """
    put = (jnp.asarray if sharding is None
           else functools.partial(jax.device_put, device=sharding))
    if not layers_unify(plexes):
        return None
    hps = (list(host_planes) if host_planes is not None
           else [_host_planes(px) for px in plexes])
    kind = hps[0].kind
    n_real_total = int(row_off[-1]) + hps[-1].n_real
    if n_real_total >= (1 << 31):
        return None

    s_count = len(hps)
    eps_eff = max(hp.eps_eff for hp in hps)
    window = max(hp.window for hp in hps)
    n_spline_max = max(hp.skh.size for hp in hps)
    n_data_max = max(max(hp.n_data for hp in hps), window)

    def pad_to(a: np.ndarray, n: int, fill) -> np.ndarray:
        out = np.full(n, fill, dtype=a.dtype)
        out[:a.size] = a
        return out

    skh = np.stack([pad_to(hp.skh, n_spline_max, 0xFFFFFFFF) for hp in hps])
    skl = np.stack([pad_to(hp.skl, n_spline_max, 0xFFFFFFFF) for hp in hps])
    spos = np.stack([pad_to(hp.spos, n_spline_max, hp.spos[-1])
                     for hp in hps])
    dh = np.stack([pad_to(hp.dh, n_data_max, 0xFFFFFFFF) for hp in hps])
    dl = np.stack([pad_to(hp.dl, n_data_max, 0xFFFFFFFF) for hp in hps])

    mins = np.asarray([px.keys[0] for px in plexes], dtype=np.uint64)
    min_hi, min_lo = split_u64(mins)

    if kind == "radix":
        tables = [hp.layer_np["table"] for hp in hps]
        sizes = np.asarray([t.size for t in tables], dtype=np.int64)
        table_off = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        max_win = max(hp.static["max_win"] for hp in hps)
        layer_arrays = {
            "table": put(np.concatenate(tables)),
            "table_off": put(table_off.astype(np.int32)),
            "shift": put(
                np.asarray([hp.static["shift"] for hp in hps], np.int32)),
            "p_max": put(
                np.asarray([(1 << hp.static["r"]) - 1 for hp in hps],
                           np.int32)),
            "lmin_hi": put(
                np.asarray([hp.static["min_hi"] for hp in hps], np.uint32)),
            "lmin_lo": put(
                np.asarray([hp.static["min_lo"] for hp in hps], np.uint32)),
        }
        static = dict(max_win=int(max_win),
                      mode="count" if max_win <= COUNT_MODE_MAX
                      else "bisect")
    else:
        cells = [hp.layer_np["cells"] for hp in hps]
        sizes = np.asarray([c.size for c in cells], dtype=np.int64)
        cells_off = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        delta_max = max(hp.static["delta"] for hp in hps)
        layer_arrays = {
            "cells": put(np.concatenate(cells)),
            "cells_off": put(cells_off.astype(np.int32)),
            "delta": put(
                np.asarray([hp.static["delta"] for hp in hps], np.int32)),
        }
        static = dict(r=int(hps[0].static["r"]),
                      levels=max(hp.static["levels"] for hp in hps),
                      delta_max=int(delta_max),
                      mode="count" if delta_max + 1 <= COUNT_MODE_MAX
                      else "bisect")

    return StackedPlanes(
        skhi=put(skh.reshape(-1)), sklo=put(skl.reshape(-1)),
        spos=put(spos.reshape(-1)), dhi=put(dh.reshape(-1)),
        dlo=put(dl.reshape(-1)),
        n_spline=put(
            np.asarray([hp.skh.size for hp in hps], np.int32)),
        n_real=put(np.asarray([hp.n_real for hp in hps], np.int32)),
        row_off=put(np.asarray(row_off, np.int32)),
        min_hi=put(min_hi), min_lo=put(min_lo),
        n_shards=s_count, n_spline_max=n_spline_max, n_data_max=n_data_max,
        n_real_total=n_real_total, kind=kind, layer_arrays=layer_arrays,
        static=static, eps_eff=eps_eff, window=window)
