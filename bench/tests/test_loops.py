"""Loops and draws: requests come from the seed, and the closed loop
stops at the window's end or at the first failure."""
import numpy as np

from harness.cell_run import draw_for, seed_rng
from harness.spec import Bench
from harness.traffic import annotation

KEYS = np.sort(np.random.default_rng(0).integers(
    0, 1 << 62, 100_000, dtype=np.uint64))
MIX = {"loop": "closed", "keys_per_request": 16, "distribution": "uniform"}


def test_same_seed_same_requests():
    bench = Bench()
    a = draw_for(bench, MIX, KEYS, seed_rng(2**31 + 7, 1))(1000)
    b = draw_for(bench, MIX, KEYS, seed_rng(2**31 + 7, 1))(1000)
    c = draw_for(bench, MIX, KEYS, seed_rng(2**31 + 8, 1))(1000)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.isin(a, KEYS))


def test_large_seeds_make_independent_streams():
    a = seed_rng(2**40 + 3, 1).integers(0, 1 << 30, 8)
    b = seed_rng(2**40 + 3, 2).integers(0, 1 << 30, 8)
    assert not np.array_equal(a, b)


class Echo:
    def __init__(self, fail=False):
        self.fail = fail

    def lookup(self, q):
        if self.fail:
            raise RuntimeError("device lost")
        return np.zeros(q.size, np.int64)


def test_closed_loop_stops_after_the_window_and_on_failure():
    loop = Bench().module("loops", "closed")
    draw = draw_for(Bench(), MIX, KEYS, seed_rng(1, 1))
    win = loop.run(Echo(), MIX, draw, 0.2, annotation(False))
    assert 0.2 <= win.window_s < 0.5 and win.attempted > 0 and not win.errors
    assert all(op.kind == "lookup" and op.answer.shape == op.args.shape
               for op in win.ops)
    win = loop.run(Echo(fail=True), MIX, draw, 0.2, annotation(False))
    assert len(win.ops) == 1 and win.ops[0].answer is None and win.errors


def test_absent_share_draws_keys_inside_the_range_the_set_lacks():
    mix = dict(MIX, absent_share=0.5)
    q = draw_for(Bench(), mix, KEYS, seed_rng(9, 1))(4000)
    pos = np.searchsorted(KEYS, q)
    assert np.all(pos < KEYS.size)                 # none past the end
    present = KEYS[pos] == q
    assert np.count_nonzero(~present) == 2000
    assert np.all(q[~present] == KEYS[pos[~present] - 1] + np.uint64(1))
