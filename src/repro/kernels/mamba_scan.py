"""Pallas TPU chunked selective-scan (Mamba-1 recurrence).

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t
    y_t = C_t . h_t + D * x_t

TPU-native layout: grid (batch, d_inner blocks, seq chunks) with the chunk
axis sequential ("arbitrary") so the hidden state lives in a VMEM scratch
accumulator across chunks — the HBM traffic is exactly one read of
(x, dt, B, C) and one write of y, with no O(S * Di * N) intermediate like the
pure-jnp associative scan materializes.

The state is kept transposed, (N, bd): d_inner runs along the 128-wide
lanes and the small state dim along sublanes, so each step is a handful of
dense (N, bd) VPU ops and ``y_t`` is a sublane reduction.  The chunk is
walked in groups of ``rows`` steps (one sublane tile: 8 rows of float32,
16 of bfloat16): each group loads its x/dt/B/C rows with one aligned
dynamic slice, runs its steps unrolled on values, and stores its y rows
with one aligned slice — Mosaic refuses single-row dynamic loads and
stores it cannot prove tile-aligned.

Forward-only (serving / profiling); training uses the chunked associative
scan in models/mamba.py.  Validated in interpret mode against
``ref.selective_scan`` (tests/test_kernels.py), and natively by
chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
            y_ref, hout_ref, h_ref, *, t: int, nc: int, rows: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)              # (n, bd)
    d = d_ref[...].astype(jnp.float32)              # (1, bd)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def group(j, h):
        base = pl.multiple_of(j * rows, rows)
        xs = x_ref[0, pl.ds(base, rows), :].astype(jnp.float32)    # (r, bd)
        dts = dt_ref[0, pl.ds(base, rows), :].astype(jnp.float32)  # (r, bd)
        bs = b_ref[0, pl.ds(base, rows), :].astype(jnp.float32).T  # (n, r)
        cs = c_ref[0, pl.ds(base, rows), :].astype(jnp.float32).T  # (n, r)
        ys = jnp.zeros(xs.shape, jnp.float32)
        for r in range(rows):
            dt_i, x_i = dts[r:r + 1], xs[r:r + 1]                  # (1, bd)
            dA = jnp.exp(dt_i * a)                                 # (n, bd)
            h = dA * h + (dt_i * x_i) * bs[:, r:r + 1]
            y = jnp.sum(h * cs[:, r:r + 1], axis=0,
                        keepdims=True) + d * x_i                   # (1, bd)
            ys = jnp.where(row_id == r, y, ys)
        y_ref[0, pl.ds(base, rows), :] = ys.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, t // rows, group, h_ref[...])

    @pl.when(ic == nc - 1)
    def _finalize():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def mamba_scan(x, dt, A, Bc, Cc, D, h0=None, *, block_d: int = 0,
               chunk: int = 128, interpret: bool = False):
    """x, dt: (B,S,Di)  A: (Di,N)  Bc,Cc: (B,S,N)  D: (Di,)  h0: (B,Di,N).

    Returns (y (B,S,Di), h_final (B,Di,N) float32).
    """
    b, s, di = x.shape
    n = A.shape[1]
    rows = 8 * max(1, 4 // jnp.dtype(x.dtype).itemsize)   # one sublane tile
    t = pl.cdiv(min(chunk, s), rows) * rows
    nc = pl.cdiv(s, t)
    # zero padding is inert: dt = 0 makes exp(dt * A) = 1 and dt * x = 0,
    # so padded steps carry h through unchanged and emit y = 0
    pad = nc * t - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    bd = min(block_d or 512, di)
    assert di % bd == 0, (di, bd)
    nd = di // bd
    if h0 is None:
        h0 = jnp.zeros((b, di, n), jnp.float32)

    kernel = functools.partial(_kernel, t=t, nc=nc, rows=rows)
    y, h = pl.pallas_call(
        kernel,
        grid=(b, nd, nc),
        in_specs=[
            pl.BlockSpec((1, t, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, t, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, t, n), lambda ib, id_, ic: (ib, ic, 0)),
            pl.BlockSpec((1, t, n), lambda ib, id_, ic: (ib, ic, 0)),
            pl.BlockSpec((n, bd), lambda ib, id_, ic: (0, id_)),
            pl.BlockSpec((1, bd), lambda ib, id_, ic: (0, id_)),
            pl.BlockSpec((1, n, bd), lambda ib, id_, ic: (ib, 0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, n, bd), lambda ib, id_, ic: (ib, 0, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc * t, di), x.dtype),
            jax.ShapeDtypeStruct((b, n, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(x, dt, Bc, Cc, A.T, D.reshape(1, di), h0.transpose(0, 2, 1))
    return y[:, :s], h.transpose(0, 2, 1)
