"""jit'd device lookup path assembling the Pallas kernels (TPU target).

``DevicePlex.from_plex`` converts a host-built ``repro.core.PLEX`` into device
planes + static search parameters (shared with the portable jnp backend via
``planes.build_planes``); ``DevicePlex.lookup`` runs the batched pipeline:

    segment kernel (radix | CHT)  ->  XLA HBM window gather  ->
    bounded_search kernel

The eps-window slack covering float32 interpolation rounding is computed in
``planes.py``; the data planes are padded with the maximum key so window
reads never wrap.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax.numpy as jnp
import numpy as np

from ..core.plex import PLEX
from .bounded_search import bounded_search
from .pairs import extract_bits, split_u64
from .planes import (COUNT_MODE_MAX, PLANE_ARRAYS, bind_planes, build_planes,
                     finalize_indices, pad_queries)
from .plex_segment_lookup import (DEFAULT_BLOCK, cht_segment_lookup,
                                  default_interpret, radix_segment_lookup)

__all__ = ["COUNT_MODE_MAX", "DevicePlex"]


@dataclasses.dataclass
class DevicePlex:
    # spline planes
    skhi: Any
    sklo: Any
    spos: Any                 # float32 ranks
    # data planes (padded to >= window with the max key)
    dhi: Any
    dlo: Any
    n_data: int               # padded length
    n_real: int
    # layer
    kind: str                 # "radix" | "cht"
    layer_arrays: dict[str, Any]
    static: dict[str, Any]
    eps_eff: int
    window: int
    block: int
    interpret: bool
    _fn: Any = None
    _px: Any = None            # source PLEX, for the deprecated shim below
    _stacked: Any = None

    @classmethod
    def from_plex(cls, px: PLEX, *, block: int = DEFAULT_BLOCK,
                  interpret: bool | None = None) -> "DevicePlex":
        """``interpret`` defaults by platform (``default_interpret``)."""
        if interpret is None:
            interpret = default_interpret()
        pp = build_planes(px)
        dp = cls(skhi=pp.skhi, sklo=pp.sklo, spos=pp.spos, dhi=pp.dhi,
                 dlo=pp.dlo, n_data=pp.n_data, n_real=pp.n_real, kind=pp.kind,
                 layer_arrays=pp.layer_arrays, static=pp.static,
                 eps_eff=pp.eps_eff, window=pp.window, block=block,
                 interpret=interpret, _px=px)
        dp._fn = bind_planes(_lookup_pipeline, dp, PLANE_ARRAYS)
        return dp

    def lookup_planes(self, qhi, qlo):
        """Deprecated: the serving layer drives the fused stacked kernel
        (``stacked_pallas.StackedPallasPlex``) instead — one ``pallas_call``
        for the whole pipeline where this multi-kernel path dispatched
        three. This shim forwards one block-multiple chunk of query planes
        to a lazily built single-shard stacked impl and returns its global
        clamped int32 indices (clamping is idempotent under the historical
        caller contract)."""
        warnings.warn(
            "DevicePlex.lookup_planes is deprecated; drive "
            "StackedPallasPlex.lookup_planes (the fused stacked kernel) "
            "instead", DeprecationWarning, stacklevel=2)
        if self._stacked is None:
            from .stacked_pallas import StackedPallasPlex
            self._stacked = StackedPallasPlex.from_plexes(
                [self._px], np.zeros(1, dtype=np.int64), block=self.block,
                interpret=self.interpret)
        return self._stacked.lookup_planes(
            jnp.asarray(qhi), jnp.asarray(qlo)).out

    def lookup(self, q: np.ndarray) -> np.ndarray:
        """Batched device lookup; same contract as PLEX.lookup."""
        qp, b = pad_queries(q, self.block)
        qh, ql = split_u64(qp)
        out = self._fn(jnp.asarray(qh), jnp.asarray(ql))
        return finalize_indices(out, b, self.n_real)


def _lookup_pipeline(dp: DevicePlex, qhi, qlo):
    s = dp.static
    if dp.kind == "radix":
        base = radix_segment_lookup(
            qhi, qlo, dp.layer_arrays["table"], dp.skhi, dp.sklo, dp.spos,
            shift=s["shift"], r=s["r"], min_hi=s["min_hi"],
            min_lo=s["min_lo"], max_win=s["max_win"], eps_eff=dp.eps_eff,
            n_data=dp.n_data, window=dp.window, mode=s["mode"],
            block=dp.block, interpret=dp.interpret)
    else:
        bins = jnp.stack([extract_bits(qhi, qlo, lvl * s["r"], s["r"])
                          for lvl in range(s["levels"])])
        base = cht_segment_lookup(
            qhi, qlo, bins, dp.layer_arrays["cells"], dp.skhi, dp.sklo,
            dp.spos, r=s["r"], levels=s["levels"], delta=s["delta"],
            eps_eff=dp.eps_eff, n_data=dp.n_data, window=dp.window,
            mode=s["mode"], block=dp.block, interpret=dp.interpret)
    offs = jnp.arange(dp.window, dtype=jnp.int32)
    idx = base[:, None] + offs[None, :]
    whi = jnp.take(dp.dhi, idx)
    wlo = jnp.take(dp.dlo, idx)
    return bounded_search(qhi, qlo, whi, wlo, base, block=dp.block,
                          interpret=dp.interpret)
