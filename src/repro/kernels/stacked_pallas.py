"""Pallas-native stacked merged PLEX lookup — one kernel per micro-batch.

``StackedPallasPlex`` is the Pallas twin of ``jnp_lookup.StackedJnpPlex``:
it consumes the exact same shard-major ``planes.StackedPlanes`` arrays
(same layout, same global row offsets, same ``lookup_planes`` contract)
but runs the whole serving pipeline — shard routing, radix/CHT window-base
lookup, the eps-window data probe, the per-shard result clamp, the
global-offset fold, and (for merged epochs) the ``delta_rank_adjust``
fold — inside **one** ``pl.pallas_call`` dispatch per micro-batch.

The kernel body is not a re-implementation: it rebuilds a
``StackedPlanes``-shaped view over the kernel refs (``dataclasses.replace``
swapping each device array for its in-kernel value) and calls the very
``_stacked_pipeline`` / ``delta_rank_adjust`` the jnp backend jits — every
search is fixed-trip and every gather is a plain ``jnp.take``, so the math
is Pallas-legal as written and the two backends are bit-identical by
construction, not by test luck.

Layout inside the kernel: queries are blocked along the batch axis (grid
dim 0, ``[block]`` lanes per program); every plane — spline/data key
planes, the [S] parameter planes, the concatenated layer arrays, and the
delta buffer — is a whole-array block, following the precedent of the
per-shard segment kernels (``plex_segment_lookup``). On a real TPU that
makes the *data* planes the VMEM budget: the stacked slab must fit VMEM
(~16 MiB/core), which holds for the per-device slabs the mesh partitioner
cuts; an HBM-resident variant would hoist the probe gather exactly like
``bounded_search`` and is left to the roofline numbers to motivate.
Interpret mode (the CPU default) has no such limit and is the parity
harness CI runs.

``from_plexes`` matches the jnp factory (the ``build_device_impl``
contract), so the backend registry can hand either to the serving layer,
the routed mesh partitioner, the delta buffer, the hot-key cache, and
persisted warm starts without any of them knowing which backend they got.
The hot-key-cached variant reuses the backend-independent cache wrapper
(``jnp_lookup._stacked_cached``) around this kernel: cache resolution is
cheap jnp glue, misses run the fused kernel, still one ``pallas_call`` per
micro-batch.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .jnp_lookup import StackedJnpPlex, _stacked_pipeline, delta_rank_adjust
from .planes import StackedPlanes
from .plex_segment_lookup import DEFAULT_BLOCK, default_interpret

# canonical positional order of the StackedPlanes array fields as kernel
# inputs (the layer_arrays dict follows, in sorted-key order)
_PLANE_FIELDS = ("skhi", "sklo", "spos", "dhi", "dlo",
                 "n_spline", "n_real", "row_off", "min_hi", "min_lo")


def _plane_inputs(sp: StackedPlanes):
    """Flatten the StackedPlanes arrays into the kernel's positional input
    list + the layer-array key order used to rebuild the dict inside."""
    layer_keys = tuple(sorted(sp.layer_arrays))
    arrays = [getattr(sp, f) for f in _PLANE_FIELDS]
    arrays += [sp.layer_arrays[k] for k in layer_keys]
    return arrays, layer_keys


def _kernel_body(sp: StackedPlanes, layer_keys, probe: str, cap: int, *refs):
    """The fused kernel: rebuild a StackedPlanes view over the refs and run
    the shared stacked pipeline (+ the delta fold when ``cap > 0``)."""
    qhi = refs[0][...]
    qlo = refs[1][...]
    n_planes = len(_PLANE_FIELDS)
    vals = [r[...] for r in refs[2:2 + n_planes + len(layer_keys)]]
    view = dataclasses.replace(
        sp, **dict(zip(_PLANE_FIELDS, vals[:n_planes])),
        layer_arrays=dict(zip(layer_keys, vals[n_planes:])))
    out = _stacked_pipeline(view, probe, qhi, qlo)
    if cap:
        dkhi, dklo, dcum = (r[...] for r in refs[-4:-1])
        out = out + delta_rank_adjust(qhi, qlo, dkhi, dklo, dcum, cap=cap)
    refs[-1][...] = out


def stacked_pallas_lookup(sp: StackedPlanes, probe: str, cap: int,
                          qhi, qlo, dkhi=None, dklo=None, dcum=None, *,
                          block: int = DEFAULT_BLOCK,
                          interpret: bool | None = None):
    """Global (optionally merged) int32 indices for a block-multiple query
    batch, one ``pallas_call`` dispatch covering the entire pipeline.
    ``cap > 0`` appends the ``DeltaPlanes`` arrays and folds the delta
    rank adjustment inside the same kernel. ``interpret`` defaults by
    platform (``default_interpret``)."""
    if interpret is None:
        interpret = default_interpret()
    b = qhi.shape[0]
    assert b % block == 0, "callers pad the batch to a block multiple"
    plane_arrays, layer_keys = _plane_inputs(sp)
    inputs = [qhi, qlo, *plane_arrays]
    if cap:
        inputs += [dkhi, dklo, dcum]
    qspec = pl.BlockSpec((block,), lambda i: (i,))
    full = lambda n: pl.BlockSpec((n,), lambda i: (0,))
    body = functools.partial(_kernel_body, sp, layer_keys, probe, cap)
    return pl.pallas_call(
        body,
        grid=(b // block,),
        in_specs=[qspec, qspec] + [full(a.shape[0]) for a in inputs[2:]],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        interpret=interpret,
    )(*inputs)


@dataclasses.dataclass
class StackedPallasPlex(StackedJnpPlex):
    """Single-dispatch stacked (merged) lookup through the fused Pallas
    kernel. Same planes, contract, cache, and management as
    ``StackedJnpPlex`` — only the builder hooks differ, swapping the jit'd
    jnp pipeline for ``stacked_pallas_lookup``.

    Observability: the counted dispatch (``obs.METRICS`` armed) is
    inherited unchanged — it runs the jnp expression of the same pipeline
    over the same shared planes, so routed-shard and probe-trip counts are
    exact for this backend too and results stay bit-identical. The fused
    kernel remains the obs-off serving path; only an *observed* run pays
    the jnp dispatch."""

    interpret: bool = dataclasses.field(default_factory=default_interpret)

    @classmethod
    def from_plexes(cls, plexes, row_off, *, block: int = DEFAULT_BLOCK,
                    probe: str | None = None, cache_slots: int = 0,
                    host_planes=None, sharding=None,
                    interpret: bool | None = None
                    ) -> "StackedPallasPlex | None":
        """Same contract as ``StackedJnpPlex.from_plexes``. ``interpret``
        defaults by platform: compiled on TPU, interpreter elsewhere (the
        CPU parity/CI mode)."""
        if interpret is None:
            interpret = default_interpret()
        return super().from_plexes(
            plexes, row_off, block=block, probe=probe,
            cache_slots=cache_slots, host_planes=host_planes,
            sharding=sharding, interpret=interpret)

    def _kernel(self, cap: int):
        """The fused kernel ``(planes, qhi, qlo[, dkhi, dklo, dcum])`` at
        delta capacity ``cap`` (untraced)."""
        probe, block, interpret = self.probe, self.block, self.interpret
        return lambda sp, *args: stacked_pallas_lookup(
            sp, probe, cap, *args, block=block, interpret=interpret)

    def _snapshot_fn(self):
        return self._kernel(0)

    def _build_fn(self, cap: int):
        """Delta-free and merged dispatches are both the one fused kernel —
        ``cap`` bakes the delta fold (and its fixed-trip bisect) into the
        kernel body, so merged epochs stay at one ``pallas_call`` too."""
        return self._bind(self._kernel(cap))
