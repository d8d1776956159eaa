"""YCSB's record keys, and the key cache."""
import numpy as np

from harness import keys as key_cache


def fnv1a_abs(v: int) -> int:
    """``Utils.fnvhash64`` worked by hand: FNV-1a 64 over the eight
    low-first bytes, then ``Math.abs`` of the signed result."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) % (1 << 64)
        v >>= 8
    return abs(h - (1 << 64) if h >= 1 << 63 else h)


def test_fnvhash64_matches_ycsb(tiny_bench):
    ycsb = tiny_bench.module("datasets", "ycsb")
    vals = [0, 1, 255, 256, 123456789, 199_999_999, 9_999_999_999]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [
        fnv1a_abs(v) for v in vals]
    # the hand-worked hash against FNV-1a 64's published vector for "a"
    h = ((0xCBF29CE484222325 ^ ord("a")) * 1099511628211) % (1 << 64)
    assert h == 0xAF63DC4C8601EC8C


def test_records_are_loaded_as_ycsb_numbers_them(tiny_bench):
    ycsb = tiny_bench.module("datasets", "ycsb")
    k = ycsb.generate({"recordcount": 5000, "insertstart": 70,
                       "insertorder": "hashed"})
    assert k.dtype == np.uint64 and np.all(k[1:] >= k[:-1])
    assert k.tolist() == sorted(fnv1a_abs(v) for v in range(70, 5070))
    assert k.max() <= 1 << 63
    dense = ycsb.generate({"recordcount": 10, "insertorder": "ordered"})
    assert dense.tolist() == list(range(10))


def test_keys_are_made_once_and_read_back(tiny_bench):
    cfg = tiny_bench.cell("ycsb200M-sosd-lookup").config
    a, how_a = key_cache.load(tiny_bench, cfg)
    b, how_b = key_cache.load(tiny_bench, cfg)
    assert (how_a, how_b) == ("made", "read")
    assert np.array_equal(a, b) and a.size == cfg["data"]["recordcount"]
    # changed data parameters make a new file and drop the old one
    cfg2 = dict(cfg, data=dict(cfg["data"], insertstart=1000))
    c, how_c = key_cache.load(tiny_bench, cfg2)
    assert how_c == "made" and not np.array_equal(a, c)
    files = list((tiny_bench.bench_dir / ".cache" / "keys").iterdir())
    assert len(files) == 1
    assert key_cache.load(tiny_bench, cfg2)[1] == "read"
