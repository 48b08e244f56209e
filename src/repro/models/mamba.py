"""Mamba-1 selective-SSM block (falcon-mamba, Jamba, hymba's SSM half).

Off the TPU, prefill/train uses a chunked selective scan (lax.scan over
sequence chunks, associative scan within a chunk) so live memory is
O(B * chunk * d_inner * N) instead of O(B * S * d_inner * N); on a TPU the
Pallas kernel (kernels/mamba_scan.py) runs in its place (:func:`scan`).
Decode carries two pieces of state per layer: the causal-conv tail (conv-1
inputs) and the SSM hidden state.

A serving chunk is padded to a size bucket.  Its real length ``n_valid``
makes the padding inert: dt = 0 at the padded positions, so exp(dt * A) = 1
and dt * B * x = 0 carry the state through unchanged, and the conv tail is
taken at the real end.  A chunk continues from the state it is handed; the
serving engine zeroes a slot's state when a new request takes the slot.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.models.layers import ParamSpec, linear, rmsnorm, rmsnorm_spec
from repro.parallel.sharding import constrain

Tree = Any

SCAN_CHUNK = 512


def mamba_spec(cfg: ModelConfig) -> Tree:
    d, di, st = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state
    dtr = cfg.resolved_dt_rank
    spec = {
        "in_proj": {"w": ParamSpec((d, 2 * di), ("embed_fsdp", "ff"))},
        "conv_w": ParamSpec((cfg.ssm_conv, di), (None, "ff"), scale=0.5),
        "conv_b": ParamSpec((di,), ("ff",), init="zeros"),
        "x_proj": {"w": ParamSpec((di, dtr + 2 * st), ("ff", None))},
        "dt_w": ParamSpec((dtr, di), (None, "ff")),
        "dt_b": ParamSpec((di,), ("ff",), init="zeros", dtype="float32"),
        "A_log": ParamSpec((di, st), ("ff", None), init="zeros", dtype="float32"),
        "D": ParamSpec((di,), ("ff",), init="ones", dtype="float32"),
        "out_proj": {"w": ParamSpec((di, d), ("ff", "embed_fsdp"))},
    }
    if cfg.ssm_dt_norms:
        spec.update(dt_norm=rmsnorm_spec(dtr), b_norm=rmsnorm_spec(st),
                    c_norm=rmsnorm_spec(st))
    return spec


def _ssm_params(p: Tree, u: jax.Array, cfg: ModelConfig):
    """u: (..., Di) -> dt (..., Di), Bc (..., N), Cc (..., N)."""
    dtr, st = cfg.resolved_dt_rank, cfg.ssm_state
    xdbc = linear(p["x_proj"], u, "x_proj")
    dt_in, Bc, Cc = jnp.split(xdbc, [dtr, dtr + st], axis=-1)
    if cfg.ssm_dt_norms:
        dt_in = rmsnorm(p["dt_norm"], dt_in, cfg.norm_eps)
        Bc = rmsnorm(p["b_norm"], Bc, cfg.norm_eps)
        Cc = rmsnorm(p["c_norm"], Cc, cfg.norm_eps)
    dt = jax.nn.softplus(dt_in.astype(jnp.float32) @ p["dt_w"].astype(jnp.float32)
                         + p["dt_b"])
    return dt, Bc, Cc


def selective_scan_chunked(x, dt, A, Bc, Cc, D, h0=None, chunk: int = SCAN_CHUNK):
    """ref.selective_scan applied chunk-by-chunk carrying the state."""
    b, s, di = x.shape
    if s <= chunk:
        return ref.selective_scan(x, dt, A, Bc, Cc, D, h0)
    n = -(-s // chunk)
    pad = n * chunk - s
    def pads(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    xs = tuple(pads(a).reshape(b, n, chunk, -1).swapaxes(0, 1)
               for a in (x, dt, Bc, Cc))
    h0 = h0 if h0 is not None else jnp.zeros((b, di, A.shape[1]), jnp.float32)

    def body(h, inp):
        xc, dtc, bc, cc = inp
        y, h = ref.selective_scan(xc, dtc, A, bc, cc, D, h)
        return h, y

    h, ys = jax.lax.scan(body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(b, n * chunk, di)[:, :s]
    return y, h


@jax.custom_vjp
def _pallas_scan(x, dt, A, Bc, Cc, D, h0):
    return kops.selective_scan(x, dt, A, Bc, Cc, D, h0)


def _pallas_scan_fwd(*args):
    return _pallas_scan(*args), args


def _pallas_scan_bwd(args, g):
    """The kernel is forward-only: differentiate the chunked scan."""
    return jax.vjp(selective_scan_chunked, *args)[1](g)


_pallas_scan.defvjp(_pallas_scan_fwd, _pallas_scan_bwd)


def scan(x, dt, A, Bc, Cc, D, h0=None):
    """The selective scan, (y, final state).  On a TPU the Pallas kernel
    (the device trace names its ops ``mamba_scan``): on a TPU v5e, one
    bfloat16 row at Jamba's d_inner 5120, it took 10.9 against 12.7 us at
    8 positions and 176 against 5915 us at 512 (``tools/scan_ab.py``,
    PERF.md), less at every bucket between.  Elsewhere Pallas runs in its
    slow interpreter, so other platforms run the chunked XLA scan."""
    if jax.default_backend() == "tpu":
        if h0 is None:
            h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), jnp.float32)
        return _pallas_scan(x, dt, A, Bc, Cc, D, h0)
    return selective_scan_chunked(x, dt, A, Bc, Cc, D, h0)


def mamba_mixer(p: Tree, x: jax.Array, cfg: ModelConfig,
                h0: Optional[jax.Array] = None,
                conv_tail: Optional[jax.Array] = None,
                return_state: bool = False,
                n_valid: Optional[jax.Array] = None):
    """Full-sequence mixer.  x: (B,S,D) -> (B,S,D) [, (conv_tail, h)].

    h0 / conv_tail continue a previous chunk (chunked prefill): conv_tail is
    the last kw-1 raw conv inputs of the previous chunk.  n_valid (B,): the
    real tokens of each row, the rest padding that leaves the state as the
    real tokens left it."""
    b, s, _ = x.shape
    di, kw = cfg.ssm_d_inner, cfg.ssm_conv
    with jax.named_scope("mamba"):
        xz = linear(p["in_proj"], x, "in_proj")
        u_raw, z = jnp.split(xz, 2, axis=-1)                   # (B,S,Di) each
        u_raw = constrain(u_raw, "batch", None, "ff")
        # causal depthwise conv over seq (pre-activation inputs kept for state)
        if conv_tail is not None:
            u_pad = jnp.concatenate([conv_tail.astype(u_raw.dtype), u_raw],
                                    axis=1)
        else:
            u_pad = jnp.pad(u_raw, ((0, 0), (kw - 1, 0), (0, 0)))
        conv = sum(u_pad[:, i:i + s] * p["conv_w"][i] for i in range(kw))
        u = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
        dt, Bc, Cc = _ssm_params(p, u, cfg)
        if n_valid is not None:
            dt = jnp.where((jnp.arange(s) < n_valid[:, None])[..., None],
                           dt, 0.0)
        A = -jnp.exp(p["A_log"])
        y, h = scan(u, dt, A, Bc, Cc, p["D"], h0)
        y = y * jax.nn.silu(z)
        out = linear(p["out_proj"], y, "out_proj")
        if return_state:
            # last kw-1 raw conv inputs before the real end
            if n_valid is None:
                tail = u_pad[:, s:s + kw - 1]
            else:
                idx = n_valid[:, None] + jnp.arange(kw - 1)[None, :]
                tail = jnp.take_along_axis(u_pad, idx[..., None], axis=1)
            return out, (tail, h)
        return out


def init_mamba_state(cfg: ModelConfig, batch: int, dtype):
    di, st, kw = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_conv
    return {
        "conv": jax.ShapeDtypeStruct((batch, kw - 1, di), dtype),
        "h": jax.ShapeDtypeStruct((batch, di, st), jnp.float32),
    }


def mamba_step(p: Tree, x: jax.Array, state: Tree, cfg: ModelConfig
               ) -> Tuple[jax.Array, Tree]:
    """One-token decode.  x: (B,1,D)."""
    with jax.named_scope("mamba"):
        xz = linear(p["in_proj"], x[:, 0], "in_proj")          # (B,2Di)
        u_raw, z = jnp.split(xz, 2, axis=-1)
        window = jnp.concatenate([state["conv"],
                                  u_raw[:, None].astype(state["conv"].dtype)], axis=1)
        conv = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))
        u = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
        dt, Bc, Cc = _ssm_params(p, u, cfg)
        A = -jnp.exp(p["A_log"])
        y, h = ref.selective_scan_step(u, dt, A, Bc, Cc, p["D"], state["h"])
        y = y * jax.nn.silu(z)
        out = linear(p["out_proj"], y, "out_proj")[:, None]
        return out, {"conv": window[:, 1:], "h": h}
