"""Blocks + stacked (scan-over-period) / unrolled forwards, prefill & decode.

Layer heterogeneity (MoE interleave, SWA interleave, hybrid) is handled by
finding the smallest repeating *period* of (kind, window) block descriptors
and scanning over periods; the scan body executes one full period in layer
order, so interleaved architectures are numerically faithful while the HLO
stays one-period-sized.

The *unrolled* forward (one named_scope per layer: ``layers.0``, ``layers.1``,
…) is what the Dooly Tainted Runner traces — it reproduces the module
hierarchy a PyTorch profiler would record, and the Hierarchy Constructor
collapses the structurally identical subtrees (paper §5.1).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import (mlp, mlp_spec, rmsnorm, rmsnorm_spec)

Tree = Any

ZERO_AUX = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}


# ---------------------------------------------------------------------------
# period pattern
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockDesc:
    kind: str          # dense | moe | mamba | hybrid
    window: int        # 0 = global attention
    cross: bool = False


def layer_descs(cfg: ModelConfig) -> List[BlockDesc]:
    kinds = cfg.layer_kinds()
    out = []
    for i, kind in enumerate(kinds):
        win = 0
        if kind != "mamba" and not cfg.layer_is_global_attn(i):
            win = cfg.sliding_window
        out.append(BlockDesc(kind, win, cross=cfg.is_encdec))
    return out


def period_pattern(cfg: ModelConfig) -> Tuple[List[BlockDesc], int]:
    """Smallest repeating pattern; returns (pattern, n_periods)."""
    descs = layer_descs(cfg)
    n = len(descs)
    for p in range(1, n + 1):
        if n % p == 0 and descs == descs[:p] * (n // p):
            return descs[:p], n // p
    return descs, 1


# ---------------------------------------------------------------------------
# block: specs
# ---------------------------------------------------------------------------

def block_spec(cfg: ModelConfig, desc: BlockDesc) -> Tree:
    d = cfg.d_model
    spec: Dict[str, Tree] = {"ln1": rmsnorm_spec(d)}
    if desc.kind == "mamba":
        spec["mamba"] = mamba_mod.mamba_spec(cfg)
        if cfg.ssm_ffn:
            spec["ln2"] = rmsnorm_spec(d)
            spec["ffn"] = mlp_spec(d, cfg.d_ff, cfg.act)
        return spec
    if cfg.attn_type == "mla":
        spec["attn"] = mla_mod.mla_spec(cfg)
    else:
        spec["attn"] = attn_mod.attn_spec(cfg)
    if desc.kind == "hybrid":
        spec["mamba"] = mamba_mod.mamba_spec(cfg)
    if desc.cross:
        spec["ln_x"] = rmsnorm_spec(d)
        spec["xattn"] = attn_mod.attn_spec(cfg)
    spec["ln2"] = rmsnorm_spec(d)
    if desc.kind == "moe":
        spec["ffn"] = moe_mod.moe_spec(cfg)
    else:
        spec["ffn"] = mlp_spec(d, cfg.d_ff, cfg.act)
    return spec


# ---------------------------------------------------------------------------
# block: full-sequence apply (train / prefill)
# ---------------------------------------------------------------------------

def block_apply(p: Tree, x: jax.Array, cfg: ModelConfig, desc: BlockDesc, *,
                positions: jax.Array, impl: str, causal: bool = True,
                enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                collect_cache: bool = False, max_seq: int = 0,
                ) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[Tree]]:
    """Returns (x_out, aux_losses, cache_entry_or_None)."""
    aux = dict(ZERO_AUX)
    cache: Dict[str, jax.Array] = {}
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)

    if desc.kind == "mamba":
        if collect_cache:
            y, (tail, hstate) = mamba_mod.mamba_mixer(p["mamba"], h, cfg,
                                                      return_state=True)
            cache = {"conv": tail, "h": hstate}
        else:
            y = mamba_mod.mamba_mixer(p["mamba"], h, cfg)
        return _mamba_ffn(p, x + y, cfg), aux, (cache or None)

    # attention (+ parallel mamba for hybrid)
    if cfg.attn_type == "mla":
        y = mla_mod.mla_attention(p["attn"], h, cfg, positions=positions,
                                  impl=impl)
        if collect_cache:
            c, k_rope = mla_mod._project_latent(p["attn"], h, cfg, positions)
            cache.update(_fill_linear(c, max_seq, prefix="c"),
                         **_fill_linear(k_rope, max_seq, prefix="k_rope"))
    else:
        y = attn_mod.attention(p["attn"], h, cfg, positions=positions,
                               causal=causal, window=desc.window, impl=impl)
        if collect_cache:
            k, v = attn_mod.compute_kv(p["attn"], h, cfg, positions)
            slots = min(desc.window, max_seq) if desc.window > 0 else max_seq
            cache["k"] = _fill_ring(k, slots)
            cache["v"] = _fill_ring(v, slots)
    if desc.kind == "hybrid":
        if collect_cache:
            ym, (tail, hstate) = mamba_mod.mamba_mixer(p["mamba"], h, cfg,
                                                       return_state=True)
            cache.update({"conv": tail, "h": hstate})
        else:
            ym = mamba_mod.mamba_mixer(p["mamba"], h, cfg)
        y = y + ym
    x = x + y

    if desc.cross:
        assert enc_kv is not None
        hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
        x = x + attn_mod.attention(p["xattn"], hx, cfg, positions=positions,
                                   impl=impl, kv_override=enc_kv)

    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if desc.kind == "moe":
        y2, aux = moe_mod.moe_ffn(p["ffn"], h2, cfg)
        aux = dict(aux)
    else:
        y2 = mlp(p["ffn"], h2, cfg.act)
    x = x + y2
    return x, aux, (cache or None)


def _mamba_ffn(p: Tree, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A Mamba block's FFN after its mixer (Jamba), where it has one."""
    if not cfg.ssm_ffn:
        return x
    return x + mlp(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg.act)


def _keep_inactive(new: Tree, old: Tree, active: Optional[jax.Array]
                   ) -> Tree:
    """Recurrent state of the rows in ``active`` (B,) from ``new``, of the
    rest from ``old``."""
    if active is None:
        return new
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new, old)


def _fill_ring(kv: jax.Array, slots: int) -> jax.Array:
    """(B,S,KV,D) -> ring cache (B,slots,KV,D): last min(S,slots) rows at
    slot = pos % slots."""
    b, s = kv.shape[:2]
    if s <= slots:
        pad = [(0, 0), (0, slots - s)] + [(0, 0)] * (kv.ndim - 2)
        return jnp.pad(kv, pad)
    pos = jnp.arange(s - slots, s)
    ring = jnp.zeros((b, slots) + kv.shape[2:], kv.dtype)
    return ring.at[:, pos % slots].set(kv[:, s - slots:])


def _fill_linear(x: jax.Array, max_seq: int, prefix: str) -> Dict[str, jax.Array]:
    """(B,S,R) -> {prefix: (B,max_seq,R)} zero-padded."""
    b, s = x.shape[:2]
    out = jnp.pad(x, [(0, 0), (0, max_seq - s)] + [(0, 0)] * (x.ndim - 2))
    return {prefix: out}


# ---------------------------------------------------------------------------
# block: chunked prefill (serving engine: attend a C-token chunk against the
# cache prefix, then append the chunk's K/V — Sarathi-style chunked prefill)
# ---------------------------------------------------------------------------

def _write_chunk(cache: jax.Array, new: jax.Array, lengths: jax.Array
                 ) -> jax.Array:
    """cache (B,Smax,...) <- new (B,C,...) at rows [lengths, lengths+C)."""
    b, c = new.shape[:2]
    rows = jnp.arange(b)[:, None]
    cols = lengths[:, None] + jnp.arange(c)[None, :]
    return cache.at[rows, cols].set(new.astype(cache.dtype))


def block_prefill_chunk(p: Tree, x: jax.Array, cache: Tree, cfg: ModelConfig,
                        desc: BlockDesc, *, lengths: jax.Array, impl: str,
                        enc_kv=None, n_valid: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, Tree]:
    """x: (B,C,D) chunk; lengths (B,): tokens already cached per row;
    n_valid (B,): the chunk's real tokens per row, the rest bucket padding.
    Engine caches are absolute-position (use_ring=False)."""
    from repro.kernels import ref as kref
    b, c, _ = x.shape
    new_cache: Dict[str, jax.Array] = {}
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    positions = lengths[:, None] + jnp.arange(c)[None, :]

    if desc.kind == "mamba":
        y, (tail, hs) = mamba_mod.mamba_mixer(
            p["mamba"], h, cfg, h0=cache["h"],
            conv_tail=cache["conv"], return_state=True, n_valid=n_valid)
        return _mamba_ffn(p, x + y, cfg), {"conv": tail, "h": hs}

    if cfg.attn_type == "mla":
        m = cfg.mla
        q_nope, q_rope = mla_mod._project_q(p["attn"], h, cfg, positions)
        c_new, kr_new = mla_mod._project_latent(p["attn"], h, cfg, positions)
        c_cache = _write_chunk(cache["c"], c_new, lengths)
        kr_cache = _write_chunk(cache["k_rope"], kr_new, lengths)
        # naive expansion for the chunk query (absorbed path is decode-only)
        nh = cfg.n_heads
        k_nope = (c_cache.astype(jnp.float32)
                  @ p["attn"]["wuk"]["w"].astype(jnp.float32)
                  ).reshape(b, -1, nh, m.qk_nope_head_dim)
        v_exp = (c_cache.astype(jnp.float32)
                 @ p["attn"]["wuv"]["w"].astype(jnp.float32)
                 ).reshape(b, -1, nh, m.v_head_dim)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_cache[:, :, None, :].astype(
                jnp.float32), k_nope.shape[:3] + (m.qk_rope_head_dim,))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        y = kref.chunk_cache_attention(q, k_full.astype(x.dtype),
                                       v_exp.astype(x.dtype), lengths)
        y = y.reshape(b, c, nh * m.v_head_dim)
        y = attn_mod.linear(p["attn"]["o"], y, "o_proj")
        new_cache.update({"c": c_cache, "k_rope": kr_cache})
    else:
        hd = cfg.resolved_head_dim
        q = attn_mod.linear(p["attn"]["q"], h, "q_proj").reshape(
            b, c, cfg.n_heads, hd)
        k, v = attn_mod.compute_kv(p["attn"], h, cfg, positions)
        if cfg.rope_theta > 0:
            q = attn_mod.apply_rope(q, positions, cfg.rope_theta)
        k_cache = _write_chunk(cache["k"], k, lengths)
        v_cache = _write_chunk(cache["v"], v, lengths)
        y = kref.chunk_cache_attention_impl(impl)(
            q, k_cache, v_cache, lengths, window=desc.window)
        y = y.reshape(b, c, cfg.n_heads * hd)
        y = attn_mod.linear(p["attn"]["o"], y, "o_proj")
        new_cache.update({"k": k_cache, "v": v_cache})

    if desc.kind == "hybrid":
        ym, (tail, hs) = mamba_mod.mamba_mixer(
            p["mamba"], h, cfg, h0=cache["h"],
            conv_tail=cache["conv"], return_state=True, n_valid=n_valid)
        y = y + ym
        new_cache.update({"conv": tail, "h": hs})
    x = x + y

    if desc.cross:
        assert enc_kv is not None
        hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
        x = x + attn_mod.attention(p["xattn"], hx, cfg, positions=positions,
                                   impl=impl, kv_override=enc_kv)

    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if desc.kind == "moe":
        y2, _ = moe_mod.moe_ffn(p["ffn"], h2, cfg)
    else:
        y2 = mlp(p["ffn"], h2, cfg.act)
    return x + y2, new_cache


# ---------------------------------------------------------------------------
# block: one-token decode
# ---------------------------------------------------------------------------

def block_decode(p: Tree, x: jax.Array, cache: Tree, cfg: ModelConfig,
                 desc: BlockDesc, *, lengths: jax.Array, impl: str,
                 enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                 kv_seq_shards: int = 1,
                 active: Optional[jax.Array] = None) -> Tuple[jax.Array, Tree]:
    """active (B,): the rows whose recurrent state the step advances (all
    when None); the others keep theirs, as a row between prefill chunks
    must."""
    new_cache: Dict[str, jax.Array] = {}
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)

    if desc.kind == "mamba":
        y, st = mamba_mod.mamba_step(p["mamba"], h, cache, cfg)
        return _mamba_ffn(p, x + y, cfg), _keep_inactive(st, cache, active)

    if cfg.attn_type == "mla":
        y, nc = mla_mod.mla_decode(p["attn"], h, cache, cfg, lengths=lengths)
        new_cache.update(nc)
    else:
        y, nc = attn_mod.decode_attention(
            p["attn"], h, {"k": cache["k"], "v": cache["v"]}, cfg,
            lengths=lengths, window=desc.window, impl=impl,
            kv_seq_shards=kv_seq_shards)
        new_cache.update(nc)
    if desc.kind == "hybrid":
        old = {"conv": cache["conv"], "h": cache["h"]}
        ym, st = mamba_mod.mamba_step(p["mamba"], h, old, cfg)
        y = y + ym
        new_cache.update(_keep_inactive(st, old, active))
    x = x + y

    if desc.cross:
        assert enc_kv is not None
        hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
        x = x + attn_mod.attention(p["xattn"], hx, cfg, positions=lengths[:, None],
                                   impl=impl, kv_override=enc_kv)

    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if desc.kind == "moe":
        y2, _ = moe_mod.moe_ffn(p["ffn"], h2, cfg)
    else:
        y2 = mlp(p["ffn"], h2, cfg.act)
    return x + y2, new_cache


def block_cache_spec(cfg: ModelConfig, desc: BlockDesc, batch: int,
                     max_seq: int, dtype, use_ring: bool = True) -> Tree:
    """ShapeDtypeStruct tree + matching logical axes for one block's cache.
    use_ring=False (serving engine): absolute-position caches even for SWA
    layers, so chunked prefill can address slots directly."""
    spec: Dict[str, jax.ShapeDtypeStruct] = {}
    if desc.kind != "mamba":
        if cfg.attn_type == "mla":
            spec.update(mla_mod.init_mla_cache(cfg, batch, max_seq, dtype))
        else:
            window = desc.window if use_ring else 0
            spec.update(attn_mod.init_kv_cache(cfg, batch, max_seq,
                                               window, dtype))
    if desc.kind in ("mamba", "hybrid"):
        spec.update(mamba_mod.init_mamba_state(cfg, batch, dtype))
    return spec


CACHE_AXES = {
    "k": ("batch", "cache_seq", "kv_heads", None),
    "v": ("batch", "cache_seq", "kv_heads", None),
    "c": ("batch", "cache_seq", None),
    "k_rope": ("batch", "cache_seq", None),
    "conv": ("batch", None, "ff"),
    "h": ("batch", "ff", None),
    "enc_out": ("batch", None, None),
    "enc_k": ("batch", None, None, None),
    "enc_v": ("batch", None, None, None),
}


def cache_axes(cache_spec: Tree) -> Tree:
    return jax.tree_util.tree_map_with_path(_axes_for, cache_spec)


def _axes_for(path, leaf):
    key = None
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str) and k in CACHE_AXES:
            key = k
            break
    axes = CACHE_AXES.get(key, ())
    nd = len(leaf.shape)
    if len(axes) < nd:                      # stacked leading dims (periods)
        axes = (None,) * (nd - len(axes)) + tuple(axes)
    return tuple(axes[-nd:]) if nd else ()
