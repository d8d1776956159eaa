"""Closed loop, one client: a lookup of ``keys_per_request`` keys, sent
when the previous one has returned, until the window's seconds have
passed; the window ends when the lookup in flight then returns. A failed
lookup ends the window."""
import time

import numpy as np

from harness.traffic import Op, Window


def warm(svc, mix: dict, draw) -> None:
    """One untimed lookup of the window's size."""
    svc.lookup(draw(int(mix["keys_per_request"])))


def run(svc, mix: dict, draw, seconds: float, span) -> Window:
    n = int(mix["keys_per_request"])
    ops, errors = [], []
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        with span("bench.draw"):
            op = Op("lookup", draw(n))
        ops.append(op)
        try:
            with span("bench.lookup"):
                t = time.perf_counter()
                op.answer = np.asarray(svc.lookup(op.args))
                op.seconds = time.perf_counter() - t
        except Exception as e:                # a failed operation
            errors.append((len(ops) - 1, repr(e)))
            break
        if time.perf_counter() >= end:
            break
    return Window(ops, errors, time.perf_counter() - t0)
