"""MiniCPM3-4B as the harness computes it: the reference's block, the
operations of a token, and the profiler's module, from ``minicpm3-4b.json``.

Multi-head latent attention (low-rank query, normed latent, one shared
rotary key), in its plain form: the latent expanded to per-head keys and
values.  Every layer alike, no window.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R


@dataclass(frozen=True)
class MLA(R.Arch):
    n_heads: int
    d_ff: int
    rope_theta: float
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int

    def layer(self, lp, x, i, quant=None):
        return _layer(lp, x, a=self, quant=quant)

    def layer_weights(self, i):
        d, qk = self.d_model, self.qk_nope + self.qk_rope
        attn = (d * self.q_lora_rank + self.q_lora_rank * self.n_heads * qk
                + d * (self.kv_lora_rank + self.qk_rope)
                + self.kv_lora_rank * self.n_heads
                * (self.qk_nope + self.v_head)
                + self.n_heads * self.v_head * d)
        return attn + 3 * d * self.d_ff

    def layer_attention(self, i, ctx):
        per = self.n_heads * (self.qk_nope + self.qk_rope + self.v_head)
        return int(2 * per * np.sum(np.asarray(ctx, np.int64)))

    def module_params(self):
        h, qk = self.n_heads, self.qk_nope + self.qk_rope
        return tuple(sorted({
            "wdq/w": (self.d_model, self.q_lora_rank),
            "q_norm/scale": (self.q_lora_rank,),
            "wuq/w": (self.q_lora_rank, h * qk),
            "wdkv/w": (self.d_model, self.kv_lora_rank + self.qk_rope),
            "kv_norm/scale": (self.kv_lora_rank,),
            "wuk/w": (self.kv_lora_rank, h * self.qk_nope),
            "wuv/w": (self.kv_lora_rank, h * self.v_head),
            "o/w": (h * self.v_head, self.d_model),
        }.items()))

    def module_points(self, sweep) -> Set[Tuple]:
        """A prefill's shapes carry no context."""
        return ({("prefill", t, r) for t in sweep.toks for r in sweep.reqs}
                | {("decode", r, c) for r in sweep.reqs for c in sweep.ctx})

    def module_point(self, sweep, params, ins, window):
        if params != self.module_params() or window != 0:
            return False, None
        d = self.d_model
        if len(ins) == 2:
            (r, t, dx), pos = ins
            ok = (dx == d and pos == (r, t) and t in sweep.toks
                  and r in sweep.reqs)
            return ok, ("prefill", t, r) if ok else None
        if len(ins) == 4:
            (r, one, dx), c, kr, n = ins
            ctx = c[1] if len(c) == 3 else None
            ok = (one == 1 and dx == d and c == (r, ctx, self.kv_lora_rank)
                  and kr == (r, ctx, self.qk_rope) and n == (r,)
                  and r in sweep.reqs and ctx in sweep.ctx)
            return ok, ("decode", r, ctx) if ok else None
        return False, None

    def op_widths(self):
        return {self.d_model, self.d_ff, self.vocab}

    def module_reference(self, args, window):
        """Row by row: a full sequence (x, positions), or one token
        decoded over a latent cache (x, latents, rotary keys, lengths)."""
        p, x = args[0], args[1]
        if "wdq" not in p:
            return None
        rows = []
        for b in range(x.shape[0]):
            xb = x[b].astype(jnp.float32)
            if len(args) == 3:                         # full sequence
                rows.append(attention(self, p, xb, args[2][b], None))
            else:                                      # one-token decode
                c, kr, n = args[2][b], args[3][b], args[4][b]
                pos = n[None]
                q = R.mla_q(self, p, xb, pos, None)
                c_new, kr_new = R.mla_latent(self, p, xb, pos, None)
                c_all = c.astype(jnp.float32).at[n].set(c_new[0])
                kr_all = kr.astype(jnp.float32).at[n].set(kr_new[0])
                k, v = R.mla_kv(self, p, c_all, kr_all, None)
                kpos = jnp.arange(c.shape[0])
                o = R.attend(q, k, v, pos, kpos, kpos <= n, 0)
                rows.append(R.mm(o.reshape(1, -1), p["o"]["w"]))
        return jnp.stack(rows)


def arch(c: Dict[str, Any]) -> MLA:
    return MLA(**R.arch_sizes(c), n_heads=c["num_attention_heads"],
               d_ff=c["intermediate_size"], rope_theta=c["rope_theta"],
               q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
               qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
               v_head=c["v_head_dim"])


def attention(a: MLA, p, h, pos, quant):
    """Causal self-attention of one sequence over itself."""
    s = h.shape[0]
    valid = jnp.ones((s,), bool)
    q = R.mla_q(a, p, h, pos, quant)
    c, kr = R.mla_latent(a, p, h, pos, quant)
    k, v = R.mla_kv(a, p, c, kr, quant)
    o = R.attend(q, k, v, jnp.arange(s), jnp.arange(s), valid, 0)
    return R.mm(o.reshape(s, -1), p["o"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _layer(lp, x, *, a: MLA, quant: Optional[str]):
    pos = jnp.arange(x.shape[0])
    return R.prenorm_block(
        lp, x, lambda h: attention(a, lp["attn"], h, pos, quant), a.eps,
        quant)
