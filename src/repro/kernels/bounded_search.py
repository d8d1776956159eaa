"""Pallas kernel: the final eps-bounded probe into the data array.

Inputs are per-query windows of the (HBM-resident) data key planes, gathered
by XLA outside the kernel — on a real TPU the 200M-key array cannot live in
VMEM, so the HBM gather stays at the XLA level and the kernel consumes the
[B, W] VMEM tiles (DESIGN.md §3). Inside: one branchless masked
compare-and-count per query — ``ans = base + |{j : window[j] < q}|`` — which
is exact because the window provably contains the lower bound (the spline's
eps guarantee) and the data is sorted, so every window element below the
answer is < q and every one at/after it is >= q regardless of window padding.

W is static and padded to a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairs import pair_lt
from .plex_segment_lookup import default_interpret

DEFAULT_BLOCK = 512


def _body(qhi_ref, qlo_ref, whi_ref, wlo_ref, base_ref, out_ref):
    qhi = qhi_ref[...]
    qlo = qlo_ref[...]
    whi = whi_ref[...]
    wlo = wlo_ref[...]
    lt = pair_lt(whi, wlo, qhi[:, None], qlo[:, None])
    out_ref[...] = base_ref[...] + jnp.sum(lt.astype(jnp.int32), axis=1)


def bounded_search(qhi, qlo, win_hi, win_lo, base, *, block=DEFAULT_BLOCK,
                   interpret=None):
    """Lower-bound index per query given its [W]-wide sorted data window."""
    if interpret is None:
        interpret = default_interpret()
    b, w = win_hi.shape
    assert b % block == 0 and w % 128 == 0
    grid = (b // block,)
    qspec = pl.BlockSpec((block,), lambda i: (i,))
    wspec = pl.BlockSpec((block, w), lambda i: (i, 0))
    return pl.pallas_call(
        _body,
        grid=grid,
        in_specs=[qspec, qspec, wspec, wspec, qspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        interpret=interpret,
    )(qhi, qlo, win_hi, win_lo, base)
