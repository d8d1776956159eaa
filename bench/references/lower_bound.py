"""Plain reference of an exact lower-bound index over sorted u64 keys.

``expected(keys, ops)`` replays a window's operations and returns what
the configuration guarantees for each: for a ``lookup``, the first
position of every query whose key is at least the query, for present and
absent keys alike. The key set is read-only, so every other kind of
operation is refused. It imports nothing of the program.

``control(keys)`` returns the same search done in the nearest precision
below the configuration's 64-bit keys: on the high 32-bit word of keys
and queries only, the step a device without 64-bit integers tempts an
implementation to take. A comparison that the control passes cannot tell
an exact index from a truncated one.
"""
from __future__ import annotations

import numpy as np

KINDS = ("lookup",)


def _lower_bound(keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    order = np.argsort(q, kind="stable")      # sorted needles search faster
    out = np.empty(q.size, np.int64)
    out[order] = np.searchsorted(keys, q[order], side="left")
    return out


def _split(ops: list, flat: np.ndarray) -> list:
    bounds = np.cumsum([op.args.size for op in ops])[:-1]
    return np.split(flat, bounds)


def expected(keys: np.ndarray, ops: list) -> list:
    """The guaranteed answer of each operation, in order."""
    for op in ops:
        if op.kind not in KINDS:
            raise ValueError(f"a read-only index has no {op.kind!r}")
    if not ops:
        return []
    q = np.concatenate([np.asarray(op.args, np.uint64) for op in ops])
    return _split(ops, _lower_bound(keys, q))


def control(keys: np.ndarray) -> dict:
    """``lookup`` on 32-bit keys, the high word of each: the operations
    that take the place of the program's."""
    hi = (keys >> np.uint64(32)).astype(np.uint32)

    def lookup(q: np.ndarray) -> np.ndarray:
        qhi = (np.asarray(q, np.uint64) >> np.uint64(32)).astype(np.uint32)
        return _lower_bound(hi, qhi)
    return {"lookup": lookup}
