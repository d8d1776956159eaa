"""Pallas kernel: PLEX segment lookup + spline interpolation (TPU target).

One fused kernel maps a block of queries to the *base* of their final
eps-bounded data window:

    radix layer (table gather | CHT descent)  ->  bounded spline-segment
    search  ->  float32 interpolation  ->  window base = floor(pred) - eps_eff

Layout: queries are blocked along the batch axis (grid dim 0); the spline
planes / radix arrays are small by construction (the auto-tuner caps the radix
layer at the spline size, and a tuned spline is O(N/eps) points) and are
VMEM-resident as whole-array blocks. Keys travel as (hi, lo) uint32 planes
(``pairs.py``) — TPUs have no u64.

Two search modes for the spline window, selected statically by ops.py:
  * "count": branchless masked compare-and-popcount over the window. One
    vectorised sweep; optimal for the small windows tuned indexes produce
    (this is the TPU-idiomatic replacement for binary search, DESIGN.md §3).
  * "bisect": fixed-trip-count bounded binary search (log2(max_window) gather
    rounds); used when a degenerate layer leaves a huge max window.

The kernel is validated in interpret mode against ``ref.py`` and the numpy
core; block shapes keep the lane dimension a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairs import pair_le, pair_lt, pair_shr, pair_shr_dyn, pair_sub, \
    pair_to_f32

DEFAULT_BLOCK = 512


def default_interpret() -> bool:
    """Pallas interpret mode by platform: compiled on a TPU, where nothing
    runs in the interpreter; interpreted elsewhere (the CPU parity mode)."""
    return jax.default_backend() != "tpu"


def _predecessor_count(qhi, qlo, skhi, sklo, lo, hi):
    """Masked popcount predecessor search: largest i in [lo, hi] with
    sk[i] <= q (assumes sk[lo] <= q), window width static = max over batch."""
    width = skhi.shape[1]
    offs = jax.lax.broadcasted_iota(jnp.int32, (qhi.shape[0], width), 1)
    valid = offs <= (hi - lo)[:, None]
    le = pair_le(skhi, sklo, qhi[:, None], qlo[:, None])
    cnt = jnp.sum((le & valid).astype(jnp.int32), axis=1)
    return lo + jnp.maximum(cnt - 1, 0)


def _interp(qhi, qlo, skhi, sklo, spos, seg, n_spline):
    """Float32 spline interpolation at the found segment."""
    seg = jnp.clip(seg, 0, n_spline - 2)
    x0h = jnp.take(skhi, seg)
    x0l = jnp.take(sklo, seg)
    x1h = jnp.take(skhi, seg + 1)
    x1l = jnp.take(sklo, seg + 1)
    y0 = jnp.take(spos, seg)
    y1 = jnp.take(spos, seg + 1)
    dxh, dxl = pair_sub(x1h, x1l, x0h, x0l)
    # clamp q to segment start: q >= x0 by construction of the search for
    # present keys, but an absent key below the first spline key would wrap
    # the unsigned subtraction (the host reference extrapolates in signed
    # float64; snapping t to 0 keeps the prediction at the segment start)
    dqh, dql = pair_sub(qhi, qlo, x0h, x0l)
    dx = jnp.maximum(pair_to_f32(dxh, dxl), jnp.float32(1.0))
    dq = jnp.where((qhi < x0h) | ((qhi == x0h) & (qlo < x0l)),
                   jnp.float32(0.0), pair_to_f32(dqh, dql))
    t = jnp.clip(dq / dx, 0.0, 1.0)
    return y0 + t * (y1 - y0)


def radix_window_base(qhi, qlo, table, skhi, sklo, spos, *, shift, r, min_hi,
                      min_lo, max_win, n_spline, eps_eff, n_data, window,
                      mode):
    """Pure-jnp radix-layer pipeline: queries -> eps-window bases.

    This is the Pallas kernel body's math on plain arrays; the kernel wraps
    it behind refs and the portable jnp backend (``jnp_lookup.py``) calls it
    directly, so both paths share one implementation by construction.
    """
    mh = jnp.uint32(min_hi)
    ml = jnp.uint32(min_lo)
    below = (qhi < mh) | ((qhi == mh) & (qlo < ml))
    dh, dl = pair_sub(qhi, qlo, mh, ml)
    dh = jnp.where(below, jnp.uint32(0), dh)
    dl = jnp.where(below, jnp.uint32(0), dl)
    _, pfx = pair_shr(dh, dl, shift)
    p = jnp.clip(pfx.astype(jnp.int32), 0, (1 << r) - 1)
    lo = jnp.maximum(jnp.take(table, p).astype(jnp.int32) - 1, 0)
    hi = jnp.maximum(jnp.take(table, p + 1).astype(jnp.int32) - 1, 0)

    if mode == "count":
        offs = jax.lax.broadcasted_iota(jnp.int32, (qhi.shape[0], max_win), 1)
        idx = jnp.minimum(lo[:, None] + offs, n_spline - 1)
        wh = jnp.take(skhi, idx)
        wl = jnp.take(sklo, idx)
        seg = _predecessor_count(qhi, qlo, wh, wl, lo, hi)
    else:  # bisect: fixed-trip bounded binary search
        trips = max(int(max_win - 1).bit_length(), 0)
        for _ in range(trips):
            mid = (lo + hi + 1) >> 1
            mh_, ml_ = (jnp.take(skhi, jnp.minimum(mid, n_spline - 1)),
                        jnp.take(sklo, jnp.minimum(mid, n_spline - 1)))
            go = pair_le(mh_, ml_, qhi, qlo)
            lo = jnp.where(go, mid, lo)
            hi = jnp.where(go, hi, mid - 1)
        seg = lo

    pred = _interp(qhi, qlo, skhi, sklo, spos, seg, n_spline)
    base = jnp.floor(pred).astype(jnp.int32) - eps_eff
    return jnp.clip(base, 0, n_data - window)


def stacked_radix_window_base(qhi, qlo, sid, table, table_off, shift, p_max,
                              lmin_hi, lmin_lo, skhi, sklo, spos, n_spline,
                              *, n_spline_max, max_win, eps_eff, n_data_max,
                              window, mode):
    """Shard-stacked radix pipeline: routed queries -> *local* window bases.

    Same math as ``radix_window_base`` (shared ``_predecessor_count`` /
    ``_interp`` bodies), but every per-shard static scalar becomes an [S]
    parameter plane gathered by ``sid``, and spline gathers address the
    row-flattened stacked planes at ``sid * n_spline_max + local``
    (layout decision in ``planes.py``). ``shift`` is traced data here, hence
    ``pair_shr_dyn``.
    """
    mh = jnp.take(lmin_hi, sid)
    ml = jnp.take(lmin_lo, sid)
    below = (qhi < mh) | ((qhi == mh) & (qlo < ml))
    dh, dl = pair_sub(qhi, qlo, mh, ml)
    dh = jnp.where(below, jnp.uint32(0), dh)
    dl = jnp.where(below, jnp.uint32(0), dl)
    pfx = pair_shr_dyn(dh, dl, jnp.take(shift, sid))
    # clip below 0 too: a huge absent query on a small-shift shard can wrap
    # the int32 cast negative, which would gather from another shard's table
    p = jnp.clip(pfx.astype(jnp.int32), 0, jnp.take(p_max, sid))
    toff = jnp.take(table_off, sid)
    lo = jnp.maximum(jnp.take(table, toff + p).astype(jnp.int32) - 1, 0)
    hi = jnp.maximum(jnp.take(table, toff + p + 1).astype(jnp.int32) - 1, 0)

    ns = jnp.take(n_spline, sid)
    row = sid * jnp.int32(n_spline_max)
    if mode == "count":
        offs = jax.lax.broadcasted_iota(jnp.int32, (qhi.shape[0], max_win), 1)
        idx = row[:, None] + jnp.minimum(lo[:, None] + offs, (ns - 1)[:, None])
        wh = jnp.take(skhi, idx)
        wl = jnp.take(sklo, idx)
        seg = _predecessor_count(qhi, qlo, wh, wl, lo, hi)
    else:  # bisect: fixed-trip bounded binary search
        trips = max(int(max_win - 1).bit_length(), 0)
        for _ in range(trips):
            mid = (lo + hi + 1) >> 1
            g = row + jnp.minimum(mid, ns - 1)
            go = pair_le(jnp.take(skhi, g), jnp.take(sklo, g), qhi, qlo)
            lo = jnp.where(go, mid, lo)
            hi = jnp.where(go, hi, mid - 1)
        seg = lo

    seg = row + jnp.clip(seg, 0, ns - 2)
    pred = _interp(qhi, qlo, skhi, sklo, spos, seg, skhi.shape[0])
    base = jnp.floor(pred).astype(jnp.int32) - eps_eff
    return jnp.clip(base, 0, n_data_max - window)


def stacked_cht_window_base(qhi, qlo, sid, bins, cells, cells_off, delta,
                            skhi, sklo, spos, n_spline, *, r, levels,
                            delta_max, n_spline_max, eps_eff, n_data_max,
                            window, mode):
    """Shard-stacked CHT pipeline (see ``stacked_radix_window_base``).

    Shards must share the radix width ``r`` (the unification gate in
    ``planes.build_stacked_planes``); ``levels`` is the deepest shard's
    level count — shallower shards finish their descent early and the extra
    unrolled rounds are masked no-ops, gathering a last valid in-shard cell.
    """
    fanout = jnp.int32(1 << r)
    coff = jnp.take(cells_off, sid)
    node = jnp.zeros(qhi.shape, jnp.int32)
    out = jnp.zeros(qhi.shape, jnp.int32)
    done = jnp.zeros(qhi.shape, jnp.bool_)
    for level in range(levels):            # static unroll: levels <= ~12
        cell = jnp.take(cells, coff + node * fanout + bins[level])
        is_child = (cell >> 31) != 0
        val = (cell & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        newly = jnp.logical_and(~done, ~is_child)
        out = jnp.where(newly, val, out)
        node = jnp.where(jnp.logical_and(~done, is_child), val, node)
        done = jnp.logical_or(done, ~is_child)

    ns = jnp.take(n_spline, sid)
    row = sid * jnp.int32(n_spline_max)
    lo = out
    hi = jnp.minimum(out + jnp.take(delta, sid), ns - 1)
    if mode == "count":
        width = delta_max + 1
        offs = jax.lax.broadcasted_iota(jnp.int32, (qhi.shape[0], width), 1)
        idx = row[:, None] + jnp.minimum(lo[:, None] + offs, (ns - 1)[:, None])
        wh = jnp.take(skhi, idx)
        wl = jnp.take(sklo, idx)
        seg = _predecessor_count(qhi, qlo, wh, wl, lo, hi)
    else:
        trips = max(int(delta_max).bit_length(), 0)
        for _ in range(trips):
            mid = (lo + hi + 1) >> 1
            g = row + jnp.minimum(mid, ns - 1)
            go = pair_le(jnp.take(skhi, g), jnp.take(sklo, g), qhi, qlo)
            lo = jnp.where(go, mid, lo)
            hi = jnp.where(go, hi, mid - 1)
        seg = lo

    seg = row + jnp.clip(seg, 0, ns - 2)
    pred = _interp(qhi, qlo, skhi, sklo, spos, seg, skhi.shape[0])
    base = jnp.floor(pred).astype(jnp.int32) - eps_eff
    return jnp.clip(base, 0, n_data_max - window)


def probe_lower_bound(qhi, qlo, dhi, dlo, base, *, window, mode):
    """Final eps-window data probe: first index in ``[base, base + window]``
    whose key is >= q (``base + window`` when every window key is < q).

    Two numerically identical forms, selected statically:
      * "bisect": fixed-trip bounded binary search, ceil(log2(window + 1))
        single-element gather rounds — the default on every backend. At
        window 256 that is 9 gathers per lane and plane against 256.
      * "count": branchless masked compare-and-popcount over the whole
        window — one vectorised sweep, but a window-wide gather per lane,
        which a large key plane pays per element: 2-4x slower on CPU,
        57x on a TPU v5e at 200M keys (13548 against 239.5 ns a lookup).
    """
    if mode == "count":
        offs = jnp.arange(window, dtype=jnp.int32)
        idx = base[:, None] + offs[None, :]
        whi = jnp.take(dhi, idx)
        wlo = jnp.take(dlo, idx)
        lt = pair_lt(whi, wlo, qhi[:, None], qlo[:, None])
        return base + jnp.sum(lt.astype(jnp.int32), axis=1)
    lo = base
    hi = base + window - 1
    trips = int(window).bit_length()       # ceil(log2(window + 1)) candidates
    for _ in range(trips):
        mid = (lo + hi) >> 1
        ge = ~pair_lt(jnp.take(dhi, mid), jnp.take(dlo, mid), qhi, qlo)
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    return lo


def _radix_body(qhi_ref, qlo_ref, table_ref, skhi_ref, sklo_ref, spos_ref,
                base_ref, **static):
    base_ref[...] = radix_window_base(
        qhi_ref[...], qlo_ref[...], table_ref[...], skhi_ref[...],
        sklo_ref[...], spos_ref[...], **static)


def cht_window_base(qhi, qlo, bins, cells, skhi, sklo, spos, *, r, levels,
                    delta, n_spline, eps_eff, n_data, window, mode):
    """Pure-jnp CHT-layer pipeline (see ``radix_window_base``). ``bins`` is
    int32 [levels, B] of per-level radix digits."""
    fanout = jnp.int32(1 << r)
    node = jnp.zeros(qhi.shape, jnp.int32)
    out = jnp.zeros(qhi.shape, jnp.int32)
    done = jnp.zeros(qhi.shape, jnp.bool_)
    for level in range(levels):            # static unroll: levels <= ~12
        cell = jnp.take(cells, node * fanout + bins[level])
        is_child = (cell >> 31) != 0
        val = (cell & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        newly = jnp.logical_and(~done, ~is_child)
        out = jnp.where(newly, val, out)
        node = jnp.where(jnp.logical_and(~done, is_child), val, node)
        done = jnp.logical_or(done, ~is_child)

    lo = out
    hi = jnp.minimum(out + delta, n_spline - 1)
    if mode == "count":
        width = delta + 1
        offs = jax.lax.broadcasted_iota(jnp.int32, (qhi.shape[0], width), 1)
        idx = jnp.minimum(lo[:, None] + offs, n_spline - 1)
        wh = jnp.take(skhi, idx)
        wl = jnp.take(sklo, idx)
        seg = _predecessor_count(qhi, qlo, wh, wl, lo, hi)
    else:
        trips = max(int(delta).bit_length(), 0)
        for _ in range(trips):
            mid = (lo + hi + 1) >> 1
            mh_, ml_ = (jnp.take(skhi, jnp.minimum(mid, n_spline - 1)),
                        jnp.take(sklo, jnp.minimum(mid, n_spline - 1)))
            go = pair_le(mh_, ml_, qhi, qlo)
            lo = jnp.where(go, mid, lo)
            hi = jnp.where(go, hi, mid - 1)
        seg = lo

    pred = _interp(qhi, qlo, skhi, sklo, spos, seg, n_spline)
    base = jnp.floor(pred).astype(jnp.int32) - eps_eff
    return jnp.clip(base, 0, n_data - window)


def _cht_body(qhi_ref, qlo_ref, bins_ref, cells_ref, skhi_ref, sklo_ref,
              spos_ref, base_ref, **static):
    base_ref[...] = cht_window_base(
        qhi_ref[...], qlo_ref[...], bins_ref[...], cells_ref[...],
        skhi_ref[...], sklo_ref[...], spos_ref[...], **static)


def radix_segment_lookup(qhi, qlo, table, skhi, sklo, spos, *, shift, r,
                         min_hi, min_lo, max_win, eps_eff, n_data, window,
                         mode="count", block=DEFAULT_BLOCK, interpret=None):
    """Window bases [B] for a batch of queries through a radix-table layer."""
    if interpret is None:
        interpret = default_interpret()
    b = qhi.shape[0]
    assert b % block == 0, "ops.py pads the batch"
    n_spline = skhi.shape[0]
    body = functools.partial(
        _radix_body, shift=shift, r=r, min_hi=min_hi, min_lo=min_lo,
        max_win=max_win, n_spline=n_spline, eps_eff=eps_eff, n_data=n_data,
        window=window, mode=mode)
    grid = (b // block,)
    qspec = pl.BlockSpec((block,), lambda i: (i,))
    full = lambda n: pl.BlockSpec((n,), lambda i: (0,))
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[qspec, qspec, full(table.shape[0]), full(n_spline),
                  full(n_spline), full(n_spline)],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        interpret=interpret,
    )(qhi, qlo, table, skhi, sklo, spos)


def cht_segment_lookup(qhi, qlo, bins, cells, skhi, sklo, spos, *, r, levels,
                       delta, eps_eff, n_data, window, mode="count",
                       block=DEFAULT_BLOCK, interpret=None):
    """Window bases [B] through a CHT layer. ``bins`` is int32 [levels, B]
    (per-level radix digits, precomputed vectorised outside the kernel)."""
    if interpret is None:
        interpret = default_interpret()
    b = qhi.shape[0]
    assert b % block == 0
    n_spline = skhi.shape[0]
    body = functools.partial(
        _cht_body, r=r, levels=levels, delta=delta, n_spline=n_spline,
        eps_eff=eps_eff, n_data=n_data, window=window, mode=mode)
    grid = (b // block,)
    qspec = pl.BlockSpec((block,), lambda i: (i,))
    bspec = pl.BlockSpec((levels, block), lambda i: (0, i))
    full = lambda n: pl.BlockSpec((n,), lambda i: (0,))
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[qspec, qspec, bspec, full(cells.shape[0]), full(n_spline),
                  full(n_spline), full(n_spline)],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        interpret=interpret,
    )(qhi, qlo, bins, cells, skhi, sklo, spos)
