"""Keys drawn uniformly over the key array's positions.

``absent_share`` (0 unless the mix sets it) of each request's keys,
at positions drawn from the seed, are replaced by keys the set does not
hold: a key plus one, where that lies strictly before the next key, so
every absent key falls inside the key range.
"""
import numpy as np


def make(mix: dict, keys: np.ndarray, rng: np.random.Generator):
    """``draw(n)``: ``n`` keys, ``absent_share`` of them absent."""
    share = float(mix.get("absent_share", 0.0))
    if not 0.0 <= share <= 1.0:
        raise ValueError("absent_share must lie in [0, 1]")

    def absent(n: int) -> np.ndarray:
        out, got = np.empty(n, np.uint64), 0
        while got < n:
            i = rng.integers(0, keys.size - 1, 2 * (n - got) + 16)
            cand = keys[i] + np.uint64(1)
            cand = cand[keys[i + 1] > cand]
            take = min(cand.size, n - got)
            out[got:got + take] = cand[:take]
            got += take
        return out

    def draw(n: int) -> np.ndarray:
        q = keys[rng.integers(0, keys.size, n)]
        k = int(round(share * n))
        if k:
            q[rng.permutation(n)[:k]] = absent(k)
        return q
    return draw
