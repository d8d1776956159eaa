"""Pallas flash-attention forward kernel (TPU target).

This is the fix for the dominant §Roofline memory term: the jnp flash path
materialises its [*, block_k] score chain through HBM on every elementwise
op (CPU-XLA doesn't fuse), while this kernel keeps q/k/v tiles and the
entire online-softmax state in VMEM — HBM traffic collapses to q+k+v+o.

Structure: grid (batch*heads, q_blocks, k_blocks), k innermost; the output
block and the running (m, l) rows are revisited across the k dimension
(classic flash revisit pattern). GQA is handled in the BlockSpec index maps
(q head -> its kv head), so repeated K/V are never materialised. Causal
masking is applied per-tile; fully-masked tiles still traverse the grid
(documented; a future scalar-prefetch skip is the perf TODO).

Validated in interpret mode against the pure-jnp online-softmax oracle and
a naive softmax reference (tests/test_kernels.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .plex_segment_lookup import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, scale, causal,
            block_q, block_k, n_k):
    pq = pl.program_id(1)
    pk = pl.program_id(2)

    @pl.when(pk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [bq, d]
    k = k_ref[0]                                   # [bk, d]
    v = v_ref[0]                                   # [bk, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = pq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = pk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    o_ref[0] = (o_ref[0] * corr[:, None]
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, scale: float | None = None,
                        block_q: int = 256, block_k: int = 256,
                        interpret: bool | None = None) -> jnp.ndarray:
    """q [B,Sq,H,D], k/v [B,Skv,KVH,D] -> [B,Sq,H,D] (forward only).
    ``interpret`` defaults by platform (``default_interpret``)."""
    if interpret is None:
        interpret = default_interpret()
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    nq, nk = sq // block_q, skv // block_k

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)

    def kv_index(bh, pq, pk):
        # program bh = b*H + h  ->  kv row = b*KVH + h // group
        return ((bh // h) * kvh + (bh % h) // g, pk, 0)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=nk)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, pq, pk: (bh, pq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, pq, pk: (bh, pq, 0)),
            pl.BlockSpec((block_q,), lambda bh, pq, pk: (bh * nq + pq,)),
            pl.BlockSpec((block_q,), lambda bh, pq, pk: (bh * nq + pq,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h * sq,), jnp.float32),
            jax.ShapeDtypeStruct((b * h * sq,), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    l = l.reshape(b * h, sq)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3).astype(q.dtype)
