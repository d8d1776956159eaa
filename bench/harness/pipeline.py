"""The serving pipeline's device events, by the name the trace gives them.

``PlexService`` dispatches each block of lookups as one compiled program,
the stacked pipeline (``kernels/jnp_lookup._stacked_pipeline``, bound by
``kernels/planes.bind_planes``). JAX names its module after the jitted
function, ``traced``, and the trace's ``XLA Modules`` line carries it as
``jit_traced``. No other program runs in the measured window.
"""
from __future__ import annotations

PIPELINE_MODULE = "jit_traced"


def pipeline_device(red: dict) -> tuple[float, int]:
    """(device seconds, events) of the pipeline in a trace reduction."""
    seconds, count = 0.0, 0
    for name, (s, n) in red["modules"].items():
        if name.split("(")[0] == PIPELINE_MODULE:
            seconds += s
            count += n
    return seconds, count
