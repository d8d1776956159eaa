"""Ahead-of-time compiles of the serving path for a TPU v5e, no chip needed.

The TPU compiler is installed with jax; it compiles for a v5e that is
described, not attached. These tests lower the stacked lookup programs at
the plane shapes ``chip_smoke.py`` serves (the 200M-key ``amzn`` stand-in
at eps 64: 24 shards of about 8.3M keys) from shapes alone, so a program
the chip's compiler refuses fails here, at no chip time. Nothing runs:
they say nothing about results or speed.

Every jnp dispatch the service can take is covered: radix and CHT layers,
``count`` and ``bisect`` probes, delta-free, merged (delta fold) and
hot-key-cached. The fused Pallas kernel is refused by the TPU compiler
today (1-D gathers inside the kernel body); its test pins that refusal as
a strict xfail, so the change that makes the kernel compile must flip it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file. Keep these tests in this one file.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cht import build_cht
from repro.core.plex import build_plex
from repro.kernels.jnp_lookup import StackedJnpPlex
from repro.kernels.planes import build_stacked_planes
from repro.kernels.stacked_pallas import StackedPallasPlex

from conftest import sorted_u64

# the smoke's planes (chip_smoke.py prints them): 200M keys over 24 shards
N_SHARDS = 24
N_SPLINE_MAX = 1852          # largest shard spline, points
N_DATA_MAX = 8_333_440       # largest shard's keys, padded to 128
N_REAL_TOTAL = 200_000_000
EPS_EFF = 67                 # eps 64 + f32 interpolation slack
WINDOW = 256                 # probe window, round_up(2 * eps_eff + 2, 128)
RADIX_R = 12                 # every shard's radix table: 2^12 + 1 entries
# widest radix window over the shards, in spline points: the shard the
# tuner first gave a CHT, re-tuned over a radix table (chip_smoke.py
# prints it)
RADIX_STATIC = dict(max_win=39, mode="count")
CHT_R, CHT_CELLS = 6, 3968   # the CHT the tuner picks for the last shard
CHT_STATIC = dict(r=CHT_R, levels=4, delta_max=3, mode="count")
BLOCK = 4096                 # PlexService's default micro-batch
DELTA_CAP = 4096             # 2,000 inserts + 2,000 deletes, next pow2
CACHE_SLOTS = 1 << 15


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # no skip: the TPU compiler ships with the installed jax, so a failure
    # to describe the chip is a failure of this check
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _template(kind: str):
    """Small real stacked planes of ``kind``: the field structure the
    smoke-sized shapes below replace."""
    keys = sorted_u64(np.random.default_rng(0), 20_000)
    offs = np.asarray([0, 10_000])
    plexes = [build_plex(keys[:10_000], 64), build_plex(keys[10_000:], 64)]
    if kind == "cht":
        plexes = [dataclasses.replace(
            px, layer=build_cht(px.spline.keys, CHT_R, 3)) for px in plexes]
    sp = build_stacked_planes(plexes, offs)
    assert sp is not None and sp.kind == kind
    return sp


def _smoke_planes(kind: str, sharding):
    """``StackedPlanes`` of ``kind`` at the smoke's shapes, every array a
    ``ShapeDtypeStruct`` on the described chip."""
    sp = _template(kind)

    def shape(a, n):
        return jax.ShapeDtypeStruct((n,), a.dtype, sharding=sharding)

    per_shard = (N_SHARDS * ((1 << RADIX_R) + 1) if kind == "radix"
                 else N_SHARDS * CHT_CELLS)
    layer = {k: shape(v, per_shard if k in ("table", "cells") else N_SHARDS)
             for k, v in sp.layer_arrays.items()}
    flat = {f: shape(getattr(sp, f), N_SHARDS * n)
            for f, n in (("skhi", N_SPLINE_MAX), ("sklo", N_SPLINE_MAX),
                         ("spos", N_SPLINE_MAX), ("dhi", N_DATA_MAX),
                         ("dlo", N_DATA_MAX))}
    per = {f: shape(getattr(sp, f), N_SHARDS)
           for f in ("n_spline", "n_real", "row_off", "min_hi", "min_lo")}
    return dataclasses.replace(
        sp, **flat, **per, layer_arrays=layer, n_shards=N_SHARDS,
        n_spline_max=N_SPLINE_MAX, n_data_max=N_DATA_MAX,
        n_real_total=N_REAL_TOTAL, eps_eff=EPS_EFF, window=WINDOW,
        static=RADIX_STATIC if kind == "radix" else CHT_STATIC)


def _args(variant: str, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    q = (s((BLOCK,), jnp.uint32),) * 2
    delta = (s((DELTA_CAP,), jnp.uint32), s((DELTA_CAP,), jnp.uint32),
             s((DELTA_CAP + 1,), jnp.int32))
    if variant == "delta_free":
        return q
    if variant == "merged":
        return q + delta
    return q + (s((), jnp.int32), s((3, CACHE_SLOTS), jnp.uint32))


@pytest.mark.parametrize("variant", ["delta_free", "merged", "cached"])
@pytest.mark.parametrize("probe", ["count", "bisect"])
@pytest.mark.parametrize("kind", ["radix", "cht"])
def test_jnp_stacked_pipeline_compiles_for_v5e(one_chip, kind, probe,
                                               variant):
    impl = StackedJnpPlex(planes=_smoke_planes(kind, one_chip), block=BLOCK,
                          probe=probe, cache_slots=CACHE_SLOTS)
    fn = {"delta_free": lambda: impl._build_fn(0),
          "merged": lambda: impl._build_fn(DELTA_CAP),
          "cached": lambda: impl._build_cached_fn(0)}[variant]()
    compiled = fn.lower(*_args(variant, one_chip)).compile()
    mem = compiled.memory_analysis()
    # the planes are arguments of the program, never baked-in constants
    assert mem.argument_size_in_bytes > 2 * N_SHARDS * N_DATA_MAX * 4
    assert mem.generated_code_size_in_bytes < 1 << 24


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="the TPU compiler refuses the fused kernel's 1-D "
                          "in-kernel gathers (Only 2D gather is supported)")
def test_fused_pallas_kernel_compiles_for_v5e(one_chip):
    impl = StackedPallasPlex(planes=_smoke_planes("radix", one_chip),
                             block=BLOCK, probe="count", interpret=False)
    impl._build_fn(0).lower(*_args("delta_free", one_chip)).compile()
