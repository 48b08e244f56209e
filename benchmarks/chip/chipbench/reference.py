"""Plain float32 reference of the configurations the benchmark serves:
the parts they share and the loops over layers.

Written from the configuration file's sizes alone: it imports nothing of
the program and computes every matrix product at ``Precision.HIGHEST``
(a TPU otherwise multiplies float32 in bfloat16 passes).  The weights are
the benchmark's own (``weights.make_params``), addressed by the names of
the program's parameter layout.

Each configuration's ``configs/<name>.py`` subclasses :class:`Arch` and
composes the parts below (norms, rotary embedding over split halves,
masked attention, SwiGLU, GQA and MLA projections) into the block the
configuration file states and into the references of the module calls
the profiler times.  Where that departs from the published model, the
file lists it under ``assumed``.

``quant`` computes every matrix product on operands rounded to a lower
precision (``bf16``, or ``fp8`` e4m3 with per-row or per-column scales):
the control that ``correct`` must reject.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block (bounds the scores)
SEQ_BUCKET = 512       # sequences are padded to a multiple of this


@dataclass(frozen=True)
class Arch:
    """What the shared loops and drivers ask of a configuration.

    Each ``configs/<name>.py`` builds a subclass of it from the
    configuration file (its function ``arch(config)``): the sizes below,
    the sizes of its own layers, and the methods that answer every
    architecture-specific question of the harness."""
    d_model: int
    n_layers: int
    vocab: int
    eps: float
    tie_embeddings: bool

    # the float32 forward (``forward_hidden``, ``served_gaps``)
    def layer(self, lp, x: jax.Array, i: int,
              quant: Optional[str] = None) -> jax.Array:
        """Layer ``i`` of one sequence ``x`` (S, d_model), float32 at
        HIGHEST, with every product on ``quant`` operands."""
        raise NotImplementedError

    # operations (``chipbench.flops``)
    def layer_weights(self, i: int) -> int:
        """Weights one token multiplies through in layer ``i``."""
        raise NotImplementedError

    def layer_attention(self, i: int, ctx) -> int:
        """Operations of layer ``i``'s attention for one query over
        ``ctx`` positions (itself included), summed over ``ctx`` when it
        is an array of them."""
        raise NotImplementedError

    # the profile driver (``chipbench.profile``)
    def module_params(self) -> Tuple:
        """The parameter shapes of the modules the profiler may time,
        sorted ((path, shape), ...), as ``profile.call_shapes`` gives
        them."""
        raise NotImplementedError

    def module_points(self, sweep) -> Set[Tuple]:
        """The sweep points the module calls of one pass show."""
        raise NotImplementedError

    def module_point(self, sweep, params: Tuple, ins: Tuple, window: int
                     ) -> Tuple[bool, Optional[Tuple]]:
        """(on the plan, point shown) of one timed module call from its
        parameter and input shapes, and the window of its task."""
        raise NotImplementedError

    def op_widths(self) -> Set[int]:
        """The widths an operator's operands may take besides 1 and the
        point's tokens and requests."""
        raise NotImplementedError

    def module_reference(self, args, window: int) -> Optional[jax.Array]:
        """A timed module call recomputed in float32 at HIGHEST; None for
        a module it does not cover."""
        raise NotImplementedError


def arch_sizes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The base :class:`Arch` fields of a configuration file."""
    return dict(d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                vocab=c["vocab_size"], eps=c["rms_norm_eps"],
                tie_embeddings=c["tie_word_embeddings"])


# ---------------------------------------------------------------------------
# lower-precision operands (the control)
# ---------------------------------------------------------------------------

def quantize(x: jax.Array, mode: Optional[str], axis: int) -> jax.Array:
    """``x`` (float32) rounded as ``mode`` stores it, scaled along ``axis``
    (the contracted one), returned in float32."""
    if mode is None:
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if mode == "fp8":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {mode!r}")


def mm(x: jax.Array, w: jax.Array, quant: Optional[str] = None) -> jax.Array:
    """x (..., k) @ w (k, n) in float32 at HIGHEST precision."""
    x = quantize(x.astype(F32), quant, -1)
    w = quantize(w.astype(F32), quant, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, pos, theta):
    """x (..., S, H, D), pos (..., S): rotary over split halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v, qpos, kpos, kvalid, window: int):
    """q (Sq,H,D) k (Sk,KV,D) v (Sk,KV,Dv): softmax attention with a causal
    mask by position, an optional window and a validity mask on keys."""
    h, kv = q.shape[1], k.shape[1]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    outs = []
    for s in range(0, q.shape[0], Q_BLOCK):
        qb = q[s:s + Q_BLOCK].astype(F32)
        logits = jnp.einsum("qhd,khd->hqk", qb, k.astype(F32),
                            precision=HI) * scale
        qp = qpos[s:s + Q_BLOCK, None]
        mask = (kpos[None, :] <= qp) & kvalid[None, :]
        if window > 0:
            mask &= kpos[None, :] > qp - window
        logits = jnp.where(mask[None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, -1)
        p = jnp.where(jnp.any(mask, -1)[None, :, None], p, 0.0)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v.astype(F32),
                               precision=HI))
    return jnp.concatenate(outs, 0)


def swiglu(p, x, quant):
    g = mm(x, p["gate"]["w"], quant)
    u = mm(x, p["up"]["w"], quant)
    return mm(jax.nn.silu(g) * u, p["down"]["w"], quant)


def prenorm_block(lp, x, attention: Callable, eps: float, quant):
    """Pre-norm residual block: RMSNorm, ``attention(h)``, residual,
    RMSNorm, SwiGLU, residual."""
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    x = x + attention(h)
    h2 = rmsnorm(x, lp["ln2"]["scale"], eps)
    return x + swiglu(lp["ffn"], h2, quant)


# ---------------------------------------------------------------------------
# attention parts (the configurations compose them into layers and into the
# references of the profiled module calls)
# ---------------------------------------------------------------------------

def gqa_qkv(a: Arch, p, h, pos, quant):
    s = h.shape[0]
    q = mm(h, p["q"]["w"], quant).reshape(s, a.n_heads, a.head_dim)
    k = mm(h, p["k"]["w"], quant).reshape(s, a.n_kv_heads, a.head_dim)
    v = mm(h, p["v"]["w"], quant).reshape(s, a.n_kv_heads, a.head_dim)
    if a.rope_theta > 0:
        q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    return q, k, v


def mla_q(a: Arch, p, h, pos, quant):
    s = h.shape[0]
    cq = rmsnorm(mm(h, p["wdq"]["w"], quant), p["q_norm"]["scale"], a.eps)
    q = mm(cq, p["wuq"]["w"], quant).reshape(s, a.n_heads,
                                              a.qk_nope + a.qk_rope)
    q_rope = rope(q[..., a.qk_nope:], pos, a.rope_theta)
    return jnp.concatenate([q[..., :a.qk_nope], q_rope], -1)


def mla_latent(a: Arch, p, h, pos, quant):
    ckv = mm(h, p["wdkv"]["w"], quant)
    c = rmsnorm(ckv[..., :a.kv_lora_rank], p["kv_norm"]["scale"], a.eps)
    kr = rope(ckv[..., None, a.kv_lora_rank:], pos, a.rope_theta)[..., 0, :]
    return c, kr


def mla_kv(a: Arch, p, c, kr, quant):
    s = c.shape[0]
    k_nope = mm(c, p["wuk"]["w"], quant).reshape(s, a.n_heads, a.qk_nope)
    v = mm(c, p["wuv"]["w"], quant).reshape(s, a.n_heads, a.v_head)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, None, :].astype(F32),
                                  (s, a.n_heads, a.qk_rope))], -1)
    return k, v


# ---------------------------------------------------------------------------
# whole model, layer by layer
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("a", "quant"))
def head(params, x, *, a: Arch, quant: Optional[str]):
    x = rmsnorm(x, params["final_norm"]["scale"], a.eps)
    w = (params["embed"]["table"].T if a.tie_embeddings
         else params["lm_head"]["w"])
    return mm(x, w, quant)


@jax.jit
def _gap(ref_logits, tokens):
    """Per position: how far the given token's reference logit lies below
    the reference's best."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return best - got


_argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(jnp.int32))


def layer_params(params, i: int):
    """Layer ``i``'s slice of the stacked parameter tree."""
    blocks = params["blocks"]
    j, period = i % len(blocks), i // len(blocks)
    return jax.tree.map(lambda t: t[period], blocks[j])


def forward_hidden(a: Arch, params, seqs: Sequence[np.ndarray],
                   quant: Optional[str] = None) -> List[jax.Array]:
    """Final hidden states of each sequence (padded to SEQ_BUCKET),
    running the stack one layer at a time over all sequences."""
    table = params["embed"]["table"]
    xs = []
    for toks in seqs:
        n = -(-len(toks) // SEQ_BUCKET) * SEQ_BUCKET
        padded = np.zeros((n,), np.int32)
        padded[:len(toks)] = toks
        xs.append(jnp.take(table, jnp.asarray(padded), axis=0).astype(F32))
    for i in range(a.n_layers):
        lp = layer_params(params, i)
        xs = [a.layer(lp, x, i, quant) for x in xs]
    return xs


def served_gaps(a: Arch, params, seqs: Sequence[np.ndarray],
                starts: Sequence[int], served: Sequence[np.ndarray],
                control: Optional[str] = None, block: int = 256
                ) -> Dict[str, np.ndarray]:
    """For each sequence, the reference's logits at positions
    ``start .. start + len(served) - 1``, and the gap by which each served
    token's logit lies below the reference's best.  With ``control``, also
    the gap of the token that the lower-precision reference puts first."""
    ref_h = forward_hidden(a, params, seqs)
    ctl_h = forward_hidden(a, params, seqs, control) if control else None
    gaps, ctl_gaps = [], []
    for si, (start, toks) in enumerate(zip(starts, served)):
        for b in range(0, len(toks), block):
            rows = slice(start + b, start + min(b + block, len(toks)))
            lg = head(params, ref_h[si][rows], a=a, quant=None)
            t = jnp.asarray(np.asarray(toks[b:b + block], np.int32))
            gaps.append(np.asarray(_gap(lg, t)))
            if ctl_h is not None:
                lc = head(params, ctl_h[si][rows], a=a, quant=control)
                ctl_gaps.append(np.asarray(_gap(lg, _argmax(lc))))
    out = {"gap": np.concatenate(gaps) if gaps else np.zeros(0)}
    if control:
        out["control_gap"] = (np.concatenate(ctl_gaps) if ctl_gaps
                              else np.zeros(0))
    return out
