"""What a mix's loop hands back: the operations of the window.

A mix is a file of parameters, ``traffic/<name>.json``. Its ``loop``
names the module ``loops/<loop>.py`` that drives the service, and its
``distribution`` the module ``draws/<distribution>.py`` that draws the
keys of each request from the seed. A loop returns a ``Window``: every
operation it sent, as the reference names it, with what the program
answered. The reference replays the same operations, so a mix with new
kinds of operation comes as new files: a loop, a draw, a reference.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np


@dataclasses.dataclass
class Op:
    """One operation sent to the service."""
    kind: str                        # as the reference names it: "lookup"
    args: np.ndarray                 # the keys it carried
    answer: np.ndarray | None = None  # what came back; None if nothing did
    seconds: float | None = None      # sent to answered, host clock


@dataclasses.dataclass
class Window:
    """What one measured window did."""
    ops: list                        # every operation, in the order sent
    errors: list                     # (operation index, repr of the error)
    window_s: float                  # first operation sent to last answer

    @property
    def attempted(self) -> int:
        return sum(op.args.size for op in self.ops)


def annotation(trace: bool):
    """Host spans on the profiler's clock for the traced run."""
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation
