"""Pallas TPU decode attention (one new token against a padded KV cache).

TPU-native adaptation of flash-decoding: the KV sequence is tiled into
VMEM-resident blocks and reduced with an online softmax.  Each grid step
processes one (batch, kv-head) pair and one KV block; the whole GQA query
group (H/KV heads) rides along in a single (group, D) VMEM block so the
MXU sees a (group, bk) logits tile instead of H separate vector products.

Per-request valid lengths arrive by scalar prefetch: a (B,) int32 array in
SMEM, read by the kernel body and by the K/V index maps.  The index maps
clamp the block index to the last block holding valid keys, so blocks past
a request's length repeat the previous block index and are not fetched
again.  Masking covers both the cache padding and an optional sliding
window (kpos >= length - window).  Softmax statistics are (group, 1)
columns, never 1-D vectors.

Layout: q (B, KV, G, D)   k/v cache (B, KV, Smax, D)   lengths (B,)
        -> out (B, KV, G, Dv)

Validated in interpret mode against ``ref.decode_attention``
(tests/test_kernels.py), and natively by chip_smoke.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *,
            window: int, bk: int, nk: int):
    ib, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[ib]
    k_start = ik * bk
    lo = length - window if window > 0 else 0
    relevant = (k_start < length) & (k_start + bk > lo)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, dv)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))  # (g,bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window > 0:
            mask &= kpos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     block_k: int = 0, interpret: bool = False):
    """q: (B,KV,G,D)  k/v: (B,KV,Smax,D[v])  lengths: (B,) -> (B,KV,G,Dv)."""
    b, kv, g, d = q.shape
    smax, dv = k_cache.shape[2], v_cache.shape[3]
    bk = min(block_k or 512, smax)
    nk = pl.cdiv(smax, bk)

    def kv_map(ib, ih, ik, lens):
        last = jnp.maximum(lens[ib] - 1, 0) // bk
        return (ib, ih, jnp.minimum(ik, last), 0)

    kernel = functools.partial(_kernel, window=window, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, dv), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, dv),
                                   lambda ib, ih, ik, lens: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, dv), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
