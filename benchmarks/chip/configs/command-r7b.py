"""Command R7B as the harness computes it: the reference's block, the
operations of a token, and the profiler's module, from ``command-r7b.json``.

Grouped-query attention with a sliding window on three of every four
layers.  The window is the published mask: a query at position q attends
the keys at positions k with q - W < k <= q, over the whole sequence, over
a chunk's cache, and over the ring of slots the profiler's decode context
holds.
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
class GQA(R.Arch):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    window: int               # sliding window of the windowed layers
    window_pattern: int       # every k-th layer global (0: none windowed)

    def layer_window(self, i: int) -> int:
        """Window of layer ``i``: every ``window_pattern``-th layer is
        global, the rest windowed."""
        if not self.window or not self.window_pattern:
            return 0
        return 0 if i % self.window_pattern == self.window_pattern - 1 \
            else self.window

    def windows(self) -> Set[int]:
        return {self.layer_window(i) for i in range(self.n_layers)}

    def layer(self, lp, x, i, quant=None):
        return _layer(lp, x, a=self, window=self.layer_window(i),
                      quant=quant)

    def layer_weights(self, i):
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    def layer_attention(self, i, ctx):
        ctx = np.asarray(ctx, np.int64)
        w = self.layer_window(i)
        span = np.minimum(ctx, w) if w else ctx
        return int(2 * 2 * self.n_heads * self.head_dim * np.sum(span))

    def module_params(self):
        d, hd = self.d_model, self.head_dim
        return tuple(sorted({
            "q/w": (d, self.n_heads * hd),
            "k/w": (d, self.n_kv_heads * hd),
            "v/w": (d, self.n_kv_heads * hd),
            "o/w": (self.n_heads * hd, d),
        }.items()))

    def _cache_slots(self, window: int, ctx: int) -> int:
        """Slots of a decode context's cache: a ring of the window's
        length on a windowed layer, the context on a global one."""
        return min(window, ctx) if window else ctx

    def module_points(self, sweep) -> Set[Tuple]:
        """A chunk's cache holds the larger of its context and its
        tokens; a decode's, its ring's or its context's slots."""
        pre = {("prefill", t, r, max(c, 1, t)) for t in sweep.toks
               for r in sweep.reqs for c in (0,) + tuple(sweep.ctx)}
        dec = {("decode", r, self._cache_slots(w, c)) for r in sweep.reqs
               for c in sweep.ctx for w in self.windows()}
        return pre | dec

    def module_point(self, sweep, params, ins, window):
        if params != self.module_params() or window not in self.windows() \
                or len(ins) != 4:
            return False, None
        kv = (self.n_kv_heads, self.head_dim)
        (r, t, dx), k, v, n = ins
        s = k[1] if len(k) == 4 else None
        if not (dx == self.d_model and k == v == (r, s) + kv and n == (r,)
                and r in sweep.reqs):
            return False, None
        if t == 1 and s in {self._cache_slots(window, c) for c in sweep.ctx}:
            return True, ("decode", r, s)
        ok = t in sweep.toks and s in {max(c, 1, t)
                                      for c in (0,) + tuple(sweep.ctx)}
        return ok, ("prefill", t, r, s) if ok else None

    def op_widths(self):
        return {self.d_model, self.d_ff, self.vocab}

    def module_reference(self, args, window):
        """Row by row, ``(x, k_cache, v_cache, lengths)`` with ``lengths``
        tokens already cached: a chunk of several tokens is written into
        the cache at ``lengths`` and attends it by position; one token is
        written into ring slot ``lengths % slots`` and attends what the
        ring holds."""
        p, x, k_cache, v_cache, lengths = args
        if set(p) != {"q", "k", "v", "o"}:
            return None
        rows = []
        for b in range(x.shape[0]):
            xb = x[b].astype(jnp.float32)
            n, s = lengths[b], k_cache.shape[1]
            qpos = n + jnp.arange(xb.shape[0])
            q, k, v = R.gqa_qkv(self, p, xb, qpos, None)
            if xb.shape[0] == 1:
                slot = n % s
                kc = k_cache[b].astype(jnp.float32).at[slot].set(k[0])
                vc = v_cache[b].astype(jnp.float32).at[slot].set(v[0])
                j = jnp.arange(s)
                kpos = n - (n - j) % s      # the position slot j holds
            else:
                kc = k_cache[b].astype(jnp.float32).at[qpos].set(k)
                vc = v_cache[b].astype(jnp.float32).at[qpos].set(v)
                kpos = jnp.arange(s)
            o = R.attend(q, kc, vc, qpos, kpos, kpos >= 0, window)
            rows.append(R.mm(o.reshape(xb.shape[0], -1), p["o"]["w"]))
        return jnp.stack(rows)


def arch(c: Dict[str, Any]) -> GQA:
    return GQA(**R.arch_sizes(c), n_heads=c["num_attention_heads"],
               n_kv_heads=c["num_key_value_heads"],
               head_dim=c.get("head_dim")
               or c["hidden_size"] // c["num_attention_heads"],
               d_ff=c["intermediate_size"], rope_theta=c["rope_theta"],
               window=c.get("sliding_window") or 0,
               window_pattern=c.get("sliding_window_pattern") or 0)


def attention(a: GQA, p, h, pos, window, quant):
    """Causal self-attention of one sequence over itself."""
    s = h.shape[0]
    valid = jnp.ones((s,), bool)
    q, k, v = R.gqa_qkv(a, p, h, pos, quant)
    o = R.attend(q, k, v, jnp.arange(s), jnp.arange(s), valid, window)
    return R.mm(o.reshape(s, -1), p["o"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("a", "window", "quant"))
def _layer(lp, x, *, a: GQA, window: int, quant: Optional[str]):
    pos = jnp.arange(x.shape[0])
    return R.prenorm_block(
        lp, x, lambda h: attention(a, lp["attn"], h, pos, window, quant),
        a.eps, quant)
