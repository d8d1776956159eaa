"""Bytes an exact PLEX lookup must read: the roofline's fixed work.

The count belongs to the lookup, not to a kernel. Every search is taken
as a bisection, whatever the serving program does, so a change of probe,
block or kernel leaves it where it is. It follows the configuration's
eps and the built snapshot's statics (shard count, layer kind, widest
segment window), stage by stage:

* the query, a 64-bit key;
* routing: a bisection over the shards' first keys;
* the layer: the shard's radix parameters (shift, first key, table
  offset, mask) and the two table entries that bound the spline window,
  or for a CHT the table offset, the window width and one cell per level;
* the spline segment: a bisection over the widest window of spline keys;
* interpolation: the two spline points (key and position) that bracket
  the query;
* the eps window: a bisection over the ``2 * eps + 2`` keys around the
  prediction;
* the fold: the shard's key count (the clamp) and global offset;
* the result, a 32-bit position.

Keys count 8 bytes; positions, offsets and table entries 4.
"""
from __future__ import annotations

KEY = 8
WORD = 4


def trips(n: int) -> int:
    """Comparisons a bisection needs to pick one of ``n`` candidates."""
    return max(int(n) - 1, 0).bit_length()


def bytes_per_lookup(eps: int, n_shards: int, kind: str,
                     static: dict) -> int:
    """Fixed-work bytes of one lookup (see the module docstring).
    ``static`` is the stacked snapshot's unified statics: ``max_win`` for a
    radix layer; ``levels`` and ``delta_max`` for a CHT."""
    query = KEY
    route = trips(n_shards) * KEY
    if kind == "radix":
        layer = (WORD + KEY + WORD + WORD) + 2 * WORD
        segment = trips(static["max_win"]) * KEY
    elif kind == "cht":
        layer = 2 * WORD + static["levels"] * WORD
        segment = trips(static["delta_max"] + 1) * KEY
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    interpolation = 2 * (KEY + WORD)
    window = trips(2 * int(eps) + 2) * KEY
    fold = 2 * WORD
    result = WORD
    return (query + route + layer + segment + interpolation + window + fold
            + result)
