"""YCSB's record keys, as its load phase inserts them.

YCSB's core workload (``site.ycsb.workloads.CoreWorkload``) loads the
records numbered ``insertstart`` to ``insertstart + recordcount - 1``.
With ``insertorder=hashed``, its default, record ``k`` gets the key
``"user" + Utils.fnvhash64(k)``; with ``ordered``, ``"user" + k``. The
index here holds the number after ``"user"`` as an unsigned 64-bit key
and orders keys by value.

``Utils.fnvhash64`` is FNV-1a over the eight bytes of the long, low byte
first, followed by ``Math.abs``: values lie in [0, 2^63], and the rare
collisions stay in the key set as duplicates.

Nothing here is random: the parameters fix the keys.
"""
from __future__ import annotations

import numpy as np

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)
CHUNK = 1 << 24


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of non-negative values, as uint64."""
    x = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET_64, np.uint64)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= FNV_PRIME_64
        x >>= np.uint64(8)
    # Math.abs on the signed value; Long.MIN_VALUE stays 2^63 unsigned
    return np.where(h >> np.uint64(63), ~h + np.uint64(1), h)


def generate(params: dict) -> np.ndarray:
    """Sorted uint64 keys of the records ``params`` loads
    (``recordcount``, ``insertstart``, ``insertorder``)."""
    n = int(params["recordcount"])
    start = int(params.get("insertstart", 0))
    order = params.get("insertorder", "hashed")
    if order == "ordered":
        return np.arange(start, start + n, dtype=np.uint64)
    if order != "hashed":
        raise ValueError(f"unknown insertorder {order!r}")
    out = np.empty(n, np.uint64)
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        out[lo:hi] = fnvhash64(np.arange(start + lo, start + hi))
    out.sort()
    return out
