"""jit'd wrappers around the Pallas kernels with custom VJPs.

Model-facing layout is (B, S, H, D); kernels use head-major (B, H, S, D).
On the CPU backend the kernels run in interpret mode (Python execution of
the kernel body) so the same code path is validated there; on any other
backend they compile natively.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as fa
from repro.kernels import decode_attention as da
from repro.kernels import mamba_scan as ms


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# flash attention (differentiable)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0):
    """q (B,Sq,H,D)  k,v (B,Sk,KV,D) -> (B,Sq,H,Dv)."""
    out, _ = _fwd(q, k, v, causal, window, q_offset)
    return out


def _fwd(q, k, v, causal, window, q_offset):
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out, lse = fa.flash_attention_fwd(qh, kh, vh, causal=causal, window=window,
                                      q_offset=q_offset, interpret=_interpret())
    return out.transpose(0, 2, 1, 3), lse


def _fwd_vjp(q, k, v, causal, window, q_offset):
    out, lse = _fwd(q, k, v, causal, window, q_offset)
    return out, (q, k, v, out, lse)


def _bwd_vjp(causal, window, q_offset, res, do):
    q, k, v, out, lse = res
    kv = k.shape[2]
    group = q.shape[2] // kv
    dq, dk, dv = fa.flash_attention_bwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), out.transpose(0, 2, 1, 3), lse,
        do.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_offset=q_offset, interpret=_interpret())
    dq = dq.transpose(0, 2, 1, 3)
    # dk/dv arrive per *query* head: sum each GQA group back to its kv head
    b, h, sk, d = dk.shape
    dk = dk.reshape(b, kv, group, sk, d).sum(2).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, kv, group, sk, -1).sum(2).transpose(0, 2, 1, 3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd_vjp, _bwd_vjp)


# ---------------------------------------------------------------------------
# decode attention (inference only)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0):
    """q (B,1,H,D)  caches (B,S,KV,D[v])  lengths (B,) -> (B,1,H,Dv)."""
    b, _, h, d = q.shape
    kv = k_cache.shape[2]
    qh = q[:, 0].reshape(b, kv, h // kv, d)
    out = da.decode_attention(qh, k_cache.transpose(0, 2, 1, 3),
                              v_cache.transpose(0, 2, 1, 3), lengths,
                              window=window, interpret=_interpret())
    return out.reshape(b, 1, h, -1)


# ---------------------------------------------------------------------------
# mamba selective scan (differentiable via chunked recompute in ms)
# ---------------------------------------------------------------------------

def selective_scan(x, dt, A, Bc, Cc, D, h0=None):
    """Pallas chunked scan (interpret mode on the CPU backend)."""
    return ms.mamba_scan(x, dt, A, Bc, Cc, D, h0=h0, interpret=_interpret())
