"""AI21-Jamba2-3B as the harness computes it: the reference's blocks, the
operations of a token, the profiler's modules and the bytes of the
selective scan, from ``jamba2-3b.json``.

Layer i attends where i % attn_layer_period == attn_layer_offset: MQA with
no positional encoding.  Every other layer is a Mamba-1 mixer (Jamba's:
dt, B and C pass an RMSNorm before dt_proj), its recurrence computed by a
sequential scan over positions.  Every layer, of either kind, ends in a
SwiGLU MLP.  The float32 mixer follows transformers' ``modeling_jamba.py``
(``JambaMambaMixer.slow_forward``).
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from typing import Any, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R

#: positions of the reference's scan per step of its loop
SCAN_UNROLL = 8


@dataclass(frozen=True)
class Jamba(R.Arch):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    attn_period: int
    attn_offset: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    rope_theta: float = 0.0         # no positional encoding

    def attends(self, i: int) -> bool:
        return i % self.attn_period == self.attn_offset

    def layer(self, lp, x, i, quant=None):
        fn = _attn_layer if self.attends(i) else _mamba_layer
        return fn(lp, x, a=self, quant=quant)

    def layer_weights(self, i):
        d, di = self.d_model, self.d_inner
        ffn = 3 * d * self.d_ff
        if self.attends(i):
            hd = self.head_dim
            return (2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                    + ffn)
        return (2 * d * di + di * (self.dt_rank + 2 * self.d_state)
                + self.dt_rank * di + di * d + ffn)

    def scan_ops(self) -> int:
        """Operations of one token's recurrence in a Mamba layer: the
        causal conv (a multiply-add per tap), then per state element
        dt * A, dA * h + dt * B * x (two multiplies, one add, the dt * x
        shared) and C * h summed."""
        return (2 * self.d_conv * self.d_inner
                + 6 * self.d_inner * self.d_state)

    def layer_attention(self, i, ctx):
        ctx = np.asarray(ctx, np.int64)
        if self.attends(i):
            return int(2 * 2 * self.n_heads * self.head_dim * np.sum(ctx))
        return int(self.scan_ops() * ctx.size)    # one scan step a token

    # -- the profiler's modules --------------------------------------------

    def attn_params(self):
        d, q, kv = (self.d_model, self.n_heads * self.head_dim,
                    self.n_kv_heads * self.head_dim)
        return tuple(sorted({"q/w": (d, q), "k/w": (d, kv), "v/w": (d, kv),
                             "o/w": (q, d)}.items()))

    def mamba_params(self):
        d, di, n, dtr = self.d_model, self.d_inner, self.d_state, self.dt_rank
        return tuple(sorted({
            "in_proj/w": (d, 2 * di), "conv_w": (self.d_conv, di),
            "conv_b": (di,), "x_proj/w": (di, dtr + 2 * n),
            "dt_norm/scale": (dtr,), "b_norm/scale": (n,),
            "c_norm/scale": (n,), "dt_w": (dtr, di), "dt_b": (di,),
            "A_log": (di, n), "D": (di,), "out_proj/w": (di, d),
        }.items()))

    def module_params(self):
        return self.attn_params() + self.mamba_params()

    def module_points(self, sweep) -> Set[Tuple]:
        """Attention: a chunk's cache holds the larger of its context and
        its tokens, a decode's its context.  Mamba: the shapes carry no
        context (the state is the same size at every position)."""
        attn = ({("prefill", t, r, max(c, 1, t)) for t in sweep.toks
                 for r in sweep.reqs for c in (0,) + tuple(sweep.ctx)}
                | {("decode", r, c) for r in sweep.reqs for c in sweep.ctx})
        mamba = ({("mamba", "prefill", t, r) for t in sweep.toks
                  for r in sweep.reqs}
                 | {("mamba", "decode", r) for r in sweep.reqs})
        return attn | mamba

    def module_point(self, sweep, params, ins, window):
        if window != 0:
            return False, None
        if params == self.mamba_params():
            return self._mamba_point(sweep, ins)
        if params != self.attn_params() or len(ins) != 4:
            return False, None
        kv = (self.n_kv_heads, self.head_dim)
        (r, t, dx), k, v, n = ins
        s = k[1] if len(k) == 4 else None
        if not (dx == self.d_model and k == v == (r, s) + kv and n == (r,)
                and r in sweep.reqs):
            return False, None
        if t == 1 and s in sweep.ctx:
            return True, ("decode", r, s)
        ok = t in sweep.toks and s in {max(c, 1, t)
                                      for c in (0,) + tuple(sweep.ctx)}
        return ok, ("prefill", t, r, s) if ok else None

    def _mamba_point(self, sweep, ins):
        d, di = self.d_model, self.d_inner
        if len(ins) == 1:
            (r, t, dx), = ins
            ok = dx == d and t in sweep.toks and r in sweep.reqs
            return ok, ("mamba", "prefill", t, r) if ok else None
        if len(ins) == 3:
            (r, one, dx), conv, h = ins
            ok = (one == 1 and dx == d and conv == (r, self.d_conv - 1, di)
                  and h == (r, di, self.d_state) and r in sweep.reqs)
            return ok, ("mamba", "decode", r) if ok else None
        return False, None

    def op_widths(self):
        return {self.d_model, self.d_ff, self.vocab}

    def module_reference(self, args, window):
        """Row by row.  Attention: ``(x, k_cache, v_cache, lengths)``, a
        chunk written into the cache at ``lengths`` and attending it by
        position, one token written into slot ``lengths % slots`` and
        attending what the cache holds.  Mamba: a whole sequence from a zero
        state ``(x,)``, or one token from a given conv tail and state
        ``(x, conv, h)``."""
        p, x = args[0], args[1]
        rows = []
        for b in range(x.shape[0]):
            xb = x[b].astype(jnp.float32)
            if "in_proj" in p:
                state = (args[2][b], args[3][b]) if len(args) == 4 else None
                rows.append(mixer(self, p, xb, None, state))
                continue
            if set(p) != {"q", "k", "v", "o"}:
                return None
            k_cache, v_cache, n = args[2][b], args[3][b], args[4][b]
            s = k_cache.shape[0]
            qpos = n + jnp.arange(xb.shape[0])
            q, k, v = R.gqa_qkv(self, p, xb, qpos, None)
            slots = qpos % s if xb.shape[0] == 1 else qpos
            kc = k_cache.astype(jnp.float32).at[slots].set(k)
            vc = v_cache.astype(jnp.float32).at[slots].set(v)
            j = jnp.arange(s)
            kpos = n - (n - j) % s if xb.shape[0] == 1 else j
            o = R.attend(q, kc, vc, qpos, kpos, kpos >= 0, 0)
            rows.append(R.mm(o.reshape(xb.shape[0], -1), p["o"]["w"]))
        return jnp.stack(rows)


def arch(c: Dict[str, Any]) -> Jamba:
    return Jamba(**R.arch_sizes(c), n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                 d_ff=c["intermediate_size"],
                 attn_period=c["attn_layer_period"],
                 attn_offset=c["attn_layer_offset"],
                 d_inner=c["mamba_expand"] * c["hidden_size"],
                 d_state=c["mamba_d_state"], d_conv=c["mamba_d_conv"],
                 dt_rank=c["mamba_dt_rank"])


# ---------------------------------------------------------------------------
# the float32 blocks
# ---------------------------------------------------------------------------

def attention(a: Jamba, p, h, quant):
    """Causal MQA of one sequence over itself, no positional encoding,
    ``R.Q_BLOCK`` queries at a time: one block program mapped over the
    blocks (``R.attend`` unrolls them, which at 32768 positions takes
    the compiler 90 s a shape)."""
    s = h.shape[0]
    q, k, v = R.gqa_qkv(a, p, h, jnp.arange(s), quant)
    group = a.n_heads // a.n_kv_heads
    k = jnp.repeat(k, group, 1).astype(jnp.float32)
    v = jnp.repeat(v, group, 1).astype(jnp.float32)
    blk = R.Q_BLOCK if s % R.Q_BLOCK == 0 else s
    kpos = jnp.arange(s)

    def block(args):
        qb, start = args
        logits = jnp.einsum("qhd,khd->hqk", qb.astype(jnp.float32), k,
                            precision=R.HI) / np.sqrt(a.head_dim)
        causal = kpos[None, :] <= start + jnp.arange(blk)[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=R.HI)

    o = jax.lax.map(block, (q.reshape(s // blk, blk, *q.shape[1:]),
                            jnp.arange(0, s, blk)))
    return R.mm(o.reshape(s, -1), p["o"]["w"], quant)


def mixer(a: Jamba, p, h, quant, state=None):
    """The Mamba-1 mixer over one sequence ``h`` (S, d_model), from a zero
    state or from ``state`` = (conv tail (d_conv - 1, d_inner), SSM state
    (d_inner, d_state)): in_proj, causal depthwise conv and SiLU, x_proj,
    RMSNorms on dt, B and C, dt_proj and softplus, the recurrence
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t . h_t + D x_t
    position by position, the SiLU gate, out_proj."""
    f32 = jnp.float32
    s, di, n, kw = h.shape[0], a.d_inner, a.d_state, a.d_conv
    u_raw, z = jnp.split(R.mm(h, p["in_proj"]["w"], quant), 2, -1)
    tail = (jnp.zeros((kw - 1, di), f32) if state is None
            else state[0].astype(f32))
    u_pad = jnp.concatenate([tail, u_raw], 0)
    conv = sum(u_pad[i:i + s] * p["conv_w"][i].astype(f32)
               for i in range(kw))
    u = jax.nn.silu(conv + p["conv_b"].astype(f32))
    dt_in, bs, cs = jnp.split(R.mm(u, p["x_proj"]["w"], quant),
                              [a.dt_rank, a.dt_rank + n], -1)
    dt_in = R.rmsnorm(dt_in, p["dt_norm"]["scale"], a.eps)
    bs = R.rmsnorm(bs, p["b_norm"]["scale"], a.eps)
    cs = R.rmsnorm(cs, p["c_norm"]["scale"], a.eps)
    dt = jax.nn.softplus(R.mm(dt_in, p["dt_w"], quant)
                         + p["dt_b"].astype(f32))
    neg_a = -jnp.exp(p["A_log"].astype(f32))                   # (di, n)
    d_skip = p["D"].astype(f32)

    def step(hs, inp):
        u_t, dt_t, b_t, c_t = inp
        hs = jnp.exp(dt_t[:, None] * neg_a) * hs \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return hs, jnp.sum(hs * c_t[None, :], -1) + d_skip * u_t

    h0 = jnp.zeros((di, n), f32) if state is None else state[1].astype(f32)
    _, y = jax.lax.scan(step, h0, (u, dt, bs, cs), unroll=SCAN_UNROLL)
    return R.mm(y * jax.nn.silu(z), p["out_proj"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _attn_layer(lp, x, *, a: Jamba, quant: Optional[str]):
    return R.prenorm_block(lp, x, lambda h: attention(a, lp["attn"], h, quant),
                           a.eps, quant)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _mamba_layer(lp, x, *, a: Jamba, quant: Optional[str]):
    return R.prenorm_block(lp, x, lambda h: mixer(a, lp["mamba"], h, quant),
                           a.eps, quant)


# ---------------------------------------------------------------------------
# the selective scan's bytes (``metrics/ssm.scan_roofline.long-doc.py``)
# ---------------------------------------------------------------------------

#: an op of the device trace that runs the scan: the Pallas kernel's custom
#: call (``mamba_scan``), or the fusion XLA wraps around it and names after
#: it; the op's results, before its operands, include y (rows, positions,
#: d_inner) in the activations' bfloat16, beside the float32 state
SCAN_OP = re.compile(r"%mamba_scan(?:\.[\w\-]+)* = (.*?) (?:fusion|custom-call)\(")
Y_SHAPE = re.compile(r"\bbf16\[(\d+),(\d+),(\d+)\]")


def scan_call(a: Jamba, name: str) -> Optional[Tuple[int, int]]:
    """(rows, positions) of a device op that runs the scan; None for any
    other op."""
    m = SCAN_OP.match(name)
    if m is None:
        return None
    for rows, tokens, width in Y_SHAPE.findall(m.group(1)):
        if int(width) == a.d_inner:
            return int(rows), int(tokens)
    return None


ITEMSIZE = {"activation": 2, "dt": 4, "state": 4}


def scan_bytes(a: Jamba, rows: int, tokens: int) -> int:
    """HBM bytes one call of the scan must move for ``rows`` rows of
    ``tokens`` positions: x, dt, B and C read once, y written once, the
    state read and written once (one call carries a chunk).  x, B, C and y
    in bfloat16, the configuration's dtype; dt and the state in float32,
    as the program keeps them."""
    di, n = a.d_inner, a.d_state
    act, dt, st = ITEMSIZE["activation"], ITEMSIZE["dt"], ITEMSIZE["state"]
    per_row = (tokens * (di * (2 * act + dt) + 2 * n * act)
               + 2 * di * n * st)
    return rows * per_row
