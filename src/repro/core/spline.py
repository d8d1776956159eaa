"""Error-bounded linear spline over a sorted key array (PLEX's bottom layer).

Faithful to the paper: the spline is a subset of CDF points (key, rank) chosen
greedily in one pass (Neumann & Michel's corridor algorithm, the same one
RadixSpline uses) such that linear interpolation between consecutive spline
points predicts the rank of every key within ``eps`` positions.

Implementation notes (recorded per DESIGN.md §9):

* Keys are uint64 (SOSD convention).  Corridor slopes are evaluated in
  ``np.longdouble`` (80-bit x87 on x86-64, 64-bit mantissa) which represents
  every uint64 exactly; products are avoided in favour of slope comparisons.
* Because lookup-time interpolation runs in float64, a final *verification and
  repair* pass checks the paper's invariant |p~ - p*| <= eps under float64
  arithmetic and inserts extra spline points at any violation.  This makes the
  eps bound hold *by construction* under the exact arithmetic the lookup uses
  (the greedy pass alone can be off by one ULP-induced position on adversarial
  64-bit keys).  The repair pass is vectorised and converges in <= 2 rounds on
  all tested distributions; it typically inserts zero points.
* The greedy scan is vectorised in chunks: from the current corridor base we
  evaluate candidate corridor slopes for a whole chunk with
  ``np.minimum.accumulate`` and find the first violation, which touches every
  CDF point at most twice (once per segment it terminates).  Chunks grow
  geometrically between emissions so spline-dense regions do not pay O(chunk)
  per point.
* Duplicate keys: the spline is built on unique keys with the rank of their
  *first* occurrence, exactly as in the paper (lookups return the first
  occurrence; this is also why PLEX handles the ``wiki`` dataset while plain
  CHT does not).
"""
from __future__ import annotations

import dataclasses

import numpy as np

_LD = np.longdouble


def _unique_first(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Unique keys + rank of first occurrence. ``keys`` must be sorted."""
    keys = np.asarray(keys, dtype=np.uint64)
    if keys.size == 0:
        return keys, np.zeros(0, dtype=np.int64)
    mask = np.empty(keys.size, dtype=bool)
    mask[0] = True
    np.not_equal(keys[1:], keys[:-1], out=mask[1:])
    pos = np.nonzero(mask)[0].astype(np.int64, copy=False)
    return keys[mask], pos


def _greedy_indices(ukeys: np.ndarray, upos: np.ndarray, eps: float) -> np.ndarray:
    """Indices (into the unique-key arrays) of greedy corridor spline points."""
    n = ukeys.size
    if n <= 2:
        return np.arange(n, dtype=np.int64)
    eps_ld = _LD(eps)

    out = [0]
    b = 0                      # corridor base (index of last spline point)
    hi = _LD(np.inf)           # current corridor slope bounds from base
    lo = _LD(-np.inf)
    i0 = b + 1                 # next unexamined point
    chunk = 64
    while i0 < n:
        j1 = min(i0 + chunk, n)
        # long double per chunk: a whole-shard copy would cost 32 B per key
        dx = ukeys[i0:j1].astype(_LD) - _LD(ukeys[b])
        dy = upos[i0:j1].astype(_LD) - _LD(upos[b])
        s = dy / dx
        s_hi = (dy + eps_ld) / dx
        s_lo = (dy - eps_ld) / dx
        # Corridor bounds *before* each point narrows it.
        hi_run = np.minimum.accumulate(s_hi)
        lo_run = np.maximum.accumulate(s_lo)
        hi_before = np.empty_like(hi_run)
        lo_before = np.empty_like(lo_run)
        hi_before[0] = hi
        lo_before[0] = lo
        np.minimum(hi_run[:-1], hi, out=hi_before[1:])
        np.maximum(lo_run[:-1], lo, out=lo_before[1:])
        viol = (s > hi_before) | (s < lo_before)
        idx = np.nonzero(viol)[0]
        if idx.size:
            v = int(idx[0])
            # Emit the point *before* the violator as a new spline point and
            # restart the corridor from it; the violator is re-examined.
            b = i0 + v - 1
            out.append(b)
            hi = _LD(np.inf)
            lo = _LD(-np.inf)
            i0 = b + 1
            chunk = 64
        else:
            hi = min(hi, _LD(hi_run[-1]))
            lo = max(lo, _LD(lo_run[-1]))
            i0 = j1
            chunk = min(chunk * 2, 16384)
    if out[-1] != n - 1:
        out.append(n - 1)
    return np.asarray(out, dtype=np.int64)


def _interp_f64(sk: np.ndarray, sp: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Float64 spline interpolation; the exact arithmetic lookups use."""
    seg = np.clip(np.searchsorted(sk, q, side="right") - 1, 0, sk.size - 2)
    x0 = sk[seg].astype(np.float64)
    x1 = sk[seg + 1].astype(np.float64)
    y0 = sp[seg].astype(np.float64)
    y1 = sp[seg + 1].astype(np.float64)
    qf = q.astype(np.float64)
    t = np.where(x1 > x0, (qf - x0) / np.maximum(x1 - x0, 1.0), 0.0)
    return y0 + t * (y1 - y0)


def _violations(sk: np.ndarray, sp: np.ndarray, ukeys: np.ndarray,
                upos: np.ndarray, eps: int, chunk: int = 1 << 20
                ) -> np.ndarray:
    """Indices of the unique keys whose float64 prediction misses their
    first-occurrence rank by more than ``eps``. Chunked, so the float64
    temporaries stay O(chunk) instead of ~80 B per key of the shard."""
    out = [np.zeros(0, dtype=np.int64)]
    for i in range(0, ukeys.size, chunk):
        pred = _interp_f64(sk, sp, ukeys[i:i + chunk])
        bad = np.abs(pred - upos[i:i + chunk].astype(np.float64)) > eps
        out.append(np.nonzero(bad)[0] + i)
    return np.concatenate(out)


@dataclasses.dataclass
class Spline:
    """An eps-bounded linear spline: ``|predict(k) - rank(k)| <= eps``."""

    keys: np.ndarray      # uint64 [S] spline-point keys (subset of data keys)
    positions: np.ndarray # int64  [S] spline-point ranks
    eps: int
    n_keys: int           # number of indexed (non-unique) data keys

    @property
    def size_bytes(self) -> int:
        # 16 B per spline point (u64 key + 8 B position), paper convention.
        return 16 * self.keys.size

    def segment_of(self, q: np.ndarray) -> np.ndarray:
        q = np.asarray(q, dtype=np.uint64)
        return np.clip(np.searchsorted(self.keys, q, side="right") - 1,
                       0, self.keys.size - 2)

    def predict(self, q: np.ndarray) -> np.ndarray:
        """Approximate rank, |predict - true rank of first occurrence| <= eps."""
        q = np.asarray(q, dtype=np.uint64)
        return _interp_f64(self.keys, self.positions, q)

    def predict_in_segment(self, q: np.ndarray, seg: np.ndarray) -> np.ndarray:
        x0 = self.keys[seg].astype(np.float64)
        x1 = self.keys[seg + 1].astype(np.float64)
        y0 = self.positions[seg].astype(np.float64)
        y1 = self.positions[seg + 1].astype(np.float64)
        qf = np.asarray(q, dtype=np.uint64).astype(np.float64)
        t = np.where(x1 > x0, (qf - x0) / np.maximum(x1 - x0, 1.0), 0.0)
        return y0 + t * (y1 - y0)


def build_spline(keys: np.ndarray, eps: int) -> Spline:
    """Greedy corridor build + float64 verification/repair (see module doc)."""
    if eps < 1:
        raise ValueError("eps must be >= 1")
    keys = np.asarray(keys, dtype=np.uint64)
    if keys.size == 0:
        raise ValueError("cannot index an empty key set")
    if np.any(keys[1:] < keys[:-1]):
        raise ValueError("keys must be sorted")
    ukeys, upos = _unique_first(keys)
    sel = _greedy_indices(ukeys, upos, float(eps))
    sk, sp = ukeys[sel], upos[sel]

    # Verification/repair: enforce the paper's bound under float64 arithmetic.
    for _ in range(8):
        extra = _violations(sk, sp, ukeys, upos, eps)
        if not extra.size:
            break
        take = np.union1d(np.searchsorted(ukeys, sk), extra)
        sk, sp = ukeys[take], upos[take]
    else:  # pragma: no cover - repair always converges (every point selected)
        sk, sp = ukeys, upos
    return Spline(keys=sk, positions=sp, eps=int(eps), n_keys=int(keys.size))


def reference_spline_indices(ukeys: np.ndarray, upos: np.ndarray,
                             eps: float) -> np.ndarray:
    """Pure-Python scalar corridor (test oracle for the vectorised build)."""
    n = ukeys.size
    if n <= 2:
        return np.arange(n, dtype=np.int64)
    out = [0]
    b = 0
    hi, lo = _LD(np.inf), _LD(-np.inf)
    eps_ld = _LD(eps)
    i = 1
    while i < n:
        dx = _LD(ukeys[i]) - _LD(ukeys[b])
        dy = _LD(int(upos[i]) - int(upos[b]))
        s = dy / dx
        if s > hi or s < lo:
            b = i - 1
            out.append(b)
            hi, lo = _LD(np.inf), _LD(-np.inf)
            i = b + 1
        else:
            hi = min(hi, (dy + eps_ld) / dx)
            lo = max(lo, (dy - eps_ld) / dx)
            i += 1
    if out[-1] != n - 1:
        out.append(n - 1)
    return np.asarray(out, dtype=np.int64)
