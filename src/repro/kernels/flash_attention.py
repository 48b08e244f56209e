"""Pallas TPU flash attention (forward + backward).

TPU-native adaptation of FlashAttention: online-softmax tiling over KV blocks
with VMEM accumulators, MXU-aligned (128) block shapes, GQA via index-mapped
KV blocks (each KV head's block is streamed once per query-head group).

Layout: q (B,H,Sq,D), k/v (B,KV,Sk,D) — head-major so BlockSpecs tile the
sequence dim contiguously in VMEM.

Supports: causal masking, sliding window, q_offset (chunked prefill).
The forward also emits the LSE needed by the backward kernels.

Per-row softmax statistics (running max, normalizer, LSE, delta) are
(bq, 1) columns, never 1-D vectors: a (B, H, Sq, 1) array tiles as
(bq, 1) blocks, which meets Mosaic's rule that a block's last two dims be
multiples of (8, 128) or the array's full dims.

Validated in interpret mode against ``ref.attention`` / jax.grad of the
reference (tests/test_kernels.py), and natively by chip_smoke.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_sizes(sq: int, sk: int, d: int):
    bq = min(128, sq)
    bk = min(128, sk)
    return bq, bk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                causal: bool, window: int, q_offset: int,
                sk: int, bq: int, bk: int, nk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq + q_offset
    k_start = ik * bk
    # block-level relevance test (skips fully-masked blocks)
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window > 0:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, dv)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))  # (bq,bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, q_offset=0,
                        interpret=False):
    """q: (B,H,Sq,D)  k,v: (B,KV,Sk,D)  ->  out (B,H,Sq,Dv), lse (B,H,Sq,1)."""
    b, h, sq, d = q.shape
    kv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // kv
    bq, bk = _block_sizes(sq, sk, d)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, q_offset=q_offset,
        sk=sk, bq=bq, bk=bk, nk=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks, stream kv) and
#           dkv kernel (grid over kv blocks, stream q).
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, causal, window, q_offset, sk, bq, bk, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq + q_offset
    k_start = ik * bk
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window > 0:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, window, q_offset, sk, bq, bk, nq):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = iq * bq + q_offset
    k_start = ik * bk
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window > 0:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())))
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)                     # (bq,bk)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale                                  # (bq,bk)
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=0,
                        q_offset=0, interpret=False):
    """``lse`` is the forward's (B,H,Sq,1).  Returns (dq, dk, dv) with
    dk/dv per *query* head (B,H,Sk,D); the GQA group-sum happens in
    ops.py."""
    b, h, sq, d = q.shape
    kv, sk, dv_dim = k.shape[1], k.shape[2], v.shape[3]
    group = h // kv
    bq, bk = _block_sizes(sq, sk, d)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                          # (B,H,Sq,1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          q_offset=q_offset, sk=sk, bq=bq, bk=bk, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv_dim), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bq, dv_dim), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          q_offset=q_offset, sk=sk, bq=bq, bk=bk, nq=nq),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, iq: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv_dim), lambda ib, ih, ik, iq: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bq, dv_dim), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv_dim), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sk, dv_dim), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
