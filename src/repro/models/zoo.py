"""Public model API: ``Model(cfg)`` — init/abstract params, forward (stacked
scan or unrolled-for-tracing), loss, prefill, decode_step, input_specs.

One class serves all 10 assigned architectures; family differences live in
the period pattern (transformer.py) and block kinds.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import transformer as tfm
from repro.models.layers import (ParamSpec, abstract_params, axes_tree,
                                 embedding, embedding_spec, init_params,
                                 rmsnorm, rmsnorm_spec, stack_specs)
from repro.parallel.sharding import constrain

Tree = Any


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern, self.n_periods = tfm.period_pattern(cfg)
        self.enc_desc = tfm.BlockDesc("dense", 0, cross=False)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def param_specs(self) -> Tree:
        cfg = self.cfg
        specs: Dict[str, Tree] = {
            "embed": embedding_spec(cfg.vocab_size, cfg.d_model),
            "blocks": [stack_specs(tfm.block_spec(cfg, d), self.n_periods)
                       for d in self.pattern],
            "final_norm": rmsnorm_spec(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = {"w": ParamSpec(
                (cfg.d_model, cfg.vocab_size), ("embed_fsdp", "vocab"))}
        if cfg.is_encdec:
            specs["enc_blocks"] = [stack_specs(
                tfm.block_spec(cfg, self.enc_desc), cfg.n_enc_layers)]
            specs["enc_norm"] = rmsnorm_spec(cfg.d_model)
        return specs

    def init(self, key: jax.Array) -> Tree:
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract_params(self) -> Tree:
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def param_axes(self) -> Tree:
        return axes_tree(self.param_specs())

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------

    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, int]:
        """Token embeddings (+ optional frontend frames prepended)."""
        x = embedding(params["embed"], batch["tokens"])
        n_front = 0
        if self.cfg.frontend != "none" and not self.cfg.is_encdec \
                and "frames" in batch:
            frames = batch["frames"].astype(x.dtype)
            with jax.named_scope("frontend"):
                x = jnp.concatenate([frames, x], axis=1)
            n_front = frames.shape[1]
        return constrain(x, "batch", None, None), n_front

    def _head(self, params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        with jax.named_scope("lm_head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = x @ params["embed"]["table"].T
            else:
                logits = x @ params["lm_head"]["w"]
            return constrain(logits.astype(jnp.float32), "batch", None, "vocab")

    # ------------------------------------------------------------------
    # encoder (enc-dec only)
    # ------------------------------------------------------------------

    def encode(self, params, frames: jax.Array, *, impl: str = "auto",
               unrolled: bool = False, remat: Optional[bool] = None) -> jax.Array:
        cfg = self.cfg
        remat = cfg.remat if remat is None else remat
        x = constrain(frames.astype(jnp.dtype(cfg.dtype)), "batch", None, None)
        positions = jnp.arange(x.shape[1])[None, :]
        apply = functools.partial(tfm.block_apply, cfg=cfg, desc=self.enc_desc,
                                  positions=positions, impl=impl, causal=False)
        if unrolled:
            for i in range(cfg.n_enc_layers):
                lp = jax.tree.map(lambda a: a[i], params["enc_blocks"][0])
                with jax.named_scope(f"enc_layers.{i}"):
                    x, _, _ = apply(lp, x)
        else:
            def body(x, lp):
                x, _, _ = apply(lp, x)
                return x, None
            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            x, _ = jax.lax.scan(body, x, params["enc_blocks"][0])
        return rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------------
    # forward (train / full-sequence)
    # ------------------------------------------------------------------

    def forward(self, params, batch, *, impl: str = "auto",
                unrolled: bool = False, remat: Optional[bool] = None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        remat = cfg.remat if remat is None else remat
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"], impl=impl,
                                  unrolled=unrolled, remat=remat)
        x, n_front = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        pattern = self.pattern

        def run_period(x, aux, slices, scope_fmt="block{j}"):
            for j, desc in enumerate(pattern):
                with jax.named_scope(scope_fmt.format(j=j)):
                    enc_kv = None
                    if desc.cross:
                        enc_kv = attn_mod.compute_kv(slices[j]["xattn"],
                                                     enc_out, cfg)
                    x, a, _ = tfm.block_apply(slices[j], x, cfg, desc,
                                              positions=positions, impl=impl,
                                              enc_kv=enc_kv)
                    aux = {k: aux[k] + a[k] for k in aux}
            return x, aux

        aux = dict(tfm.ZERO_AUX)
        if unrolled:
            p = len(pattern)
            for i in range(cfg.n_layers):
                j = i % p
                lp = jax.tree.map(lambda a: a[i // p], params["blocks"][j])
                with jax.named_scope(f"layers.{i}"):
                    enc_kv = None
                    if pattern[j].cross:
                        enc_kv = attn_mod.compute_kv(lp["xattn"], enc_out, cfg)
                    x, a, _ = tfm.block_apply(lp, x, cfg, pattern[j],
                                              positions=positions, impl=impl,
                                              enc_kv=enc_kv)
                    aux = {k: aux[k] + a[k] for k in aux}
        else:
            def body(carry, slices):
                x, aux = carry
                x, aux = run_period(x, aux, slices)
                return (x, aux), None
            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux),
                                       tuple(params["blocks"]))
        logits = self._head(params, x)
        if n_front:
            logits = logits[:, n_front:]
        return logits, aux

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------

    def loss(self, params, batch, *, impl: str = "auto",
             remat: Optional[bool] = None):
        logits, aux = self.forward(params, batch, impl=impl, remat=remat)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ce = jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)
        total = (ce + 0.01 * aux["load_balance"] + 1e-3 * aux["router_z"])
        metrics = {"ce": ce, "load_balance": aux["load_balance"],
                   "router_z": aux["router_z"], "tokens": mask.sum()}
        return total, metrics

    # ------------------------------------------------------------------
    # prefill -> cache
    # ------------------------------------------------------------------

    def prefill(self, params, batch, *, max_seq: int, impl: str = "auto"
                ) -> Tuple[jax.Array, Tree]:
        """Full-sequence pass that fills the decode cache.

        Returns (logits at the last position (B, vocab), cache).
        """
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"], impl=impl)
        x, _ = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        pattern = self.pattern

        def body(x, slices):
            caches = []
            for j, desc in enumerate(pattern):
                with jax.named_scope(f"block{j}"):
                    enc_kv = None
                    if desc.cross:
                        enc_kv = attn_mod.compute_kv(slices[j]["xattn"],
                                                     enc_out, cfg)
                    x, _, c = tfm.block_apply(slices[j], x, cfg, desc,
                                              positions=positions, impl=impl,
                                              enc_kv=enc_kv,
                                              collect_cache=True,
                                              max_seq=max_seq)
                    if desc.cross:
                        c = dict(c or {})
                        c["enc_k"], c["enc_v"] = enc_kv
                    caches.append(c)
            return x, tuple(caches)

        x, caches = jax.lax.scan(body, x, tuple(params["blocks"]))
        logits = self._head(params, x[:, -1:])[:, 0]
        return logits, {"blocks": list(caches)}

    # ------------------------------------------------------------------
    # one-token decode
    # ------------------------------------------------------------------

    def decode_step(self, params, cache, tokens: jax.Array,
                    lengths: jax.Array, *, impl: str = "auto",
                    kv_seq_shards: int = 1,
                    active: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Tree]:
        """tokens (B,) or (B,1); lengths (B,) = context size so far;
        active (B,) bool: the rows that decode, whose recurrent state the
        step advances (all rows when None)."""
        cfg = self.cfg
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        x = embedding(params["embed"], tokens)
        x = constrain(x, "batch", None, None)
        pattern = self.pattern

        def body(x, inp):
            slices, caches = inp
            new_caches = []
            for j, desc in enumerate(pattern):
                with jax.named_scope(f"block{j}"):
                    enc_kv = None
                    if desc.cross:
                        enc_kv = (caches[j]["enc_k"], caches[j]["enc_v"])
                    x, nc = tfm.block_decode(slices[j], x, caches[j], cfg,
                                             desc, lengths=lengths, impl=impl,
                                             enc_kv=enc_kv,
                                             kv_seq_shards=kv_seq_shards,
                                             active=active)
                    if desc.cross:
                        nc = dict(nc)
                        nc["enc_k"], nc["enc_v"] = enc_kv
                    new_caches.append(nc)
            return x, tuple(new_caches)

        x, new_caches = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(cache["blocks"])))
        logits = self._head(params, x)[:, 0]
        return logits, {"blocks": list(new_caches)}

    # ------------------------------------------------------------------
    # cache specs (abstract, for dry-run & engine init)
    # ------------------------------------------------------------------

    def cache_spec(self, batch: int, max_seq: int, enc_len: int = 0,
                   use_ring: bool = True) -> Tree:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        blocks = []
        for desc in self.pattern:
            spec = tfm.block_cache_spec(cfg, desc, batch, max_seq, dtype,
                                        use_ring=use_ring)
            if desc.cross:
                hd = cfg.resolved_head_dim
                spec["enc_k"] = jax.ShapeDtypeStruct(
                    (batch, enc_len, cfg.n_kv_heads, hd), dtype)
                spec["enc_v"] = jax.ShapeDtypeStruct(
                    (batch, enc_len, cfg.n_kv_heads, hd), dtype)
            # stack leading period dim
            spec = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (self.n_periods,) + s.shape, s.dtype), spec)
            blocks.append(spec)
        return {"blocks": blocks}

    def zero_cache(self, batch: int, max_seq: int, enc_len: int = 0,
                   use_ring: bool = True) -> Tree:
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_spec(batch, max_seq, enc_len, use_ring=use_ring))

    def reset_recurrent(self, cache: Tree) -> Tree:
        """``cache`` with its recurrent state (each Mamba layer's conv tail
        and SSM state) zeroed and its KV cache as it was: what a row holds
        before a new request's first chunk."""
        state = mamba_mod.init_mamba_state(self.cfg, 1, jnp.float32)
        return {"blocks": [{k: jnp.zeros_like(v) if k in state else v
                            for k, v in block.items()}
                           for block in cache["blocks"]]}

    # ------------------------------------------------------------------
    # chunked prefill (serving engine path; caches are absolute-position)
    # ------------------------------------------------------------------

    def prefill_chunk(self, params, cache, tokens: jax.Array,
                      lengths: jax.Array, *, impl: str = "auto",
                      last_pos: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Tree]:
        """tokens (B,C): next C prompt tokens per row; lengths (B,): tokens
        already cached.  Returns (logits at ``last_pos`` (default: the
        chunk's last position) (B,V), updated cache).  last_pos (B,) indexes
        within the chunk — used when the engine pads chunks to size buckets:
        the positions after it are padding, which leaves recurrent state as
        the real tokens left it."""
        cfg = self.cfg
        n_valid = None if last_pos is None else last_pos + 1
        x = embedding(params["embed"], tokens)
        x = constrain(x, "batch", None, None)
        pattern = self.pattern

        def body(x, inp):
            slices, caches = inp
            new_caches = []
            for j, desc in enumerate(pattern):
                with jax.named_scope(f"block{j}"):
                    enc_kv = None
                    if desc.cross:
                        enc_kv = (caches[j]["enc_k"], caches[j]["enc_v"])
                    x, nc = tfm.block_prefill_chunk(
                        slices[j], x, caches[j], cfg, desc, lengths=lengths,
                        impl=impl, enc_kv=enc_kv, n_valid=n_valid)
                    if desc.cross:
                        nc = dict(nc)
                        nc["enc_k"], nc["enc_v"] = enc_kv
                    new_caches.append(nc)
            return x, tuple(new_caches)

        x, new_caches = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(cache["blocks"])))
        if last_pos is None:
            xl = x[:, -1:]
        else:
            xl = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)
        logits = self._head(params, xl)[:, 0]
        return logits, {"blocks": list(new_caches)}

    # ------------------------------------------------------------------
    # input specs per assigned shape (ShapeDtypeStruct stand-ins; §dry-run)
    # ------------------------------------------------------------------

    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        dt = jnp.dtype(cfg.dtype)

        def text_batch(with_labels: bool):
            out = {}
            s_text = s
            if cfg.is_encdec:
                enc_len = min(s, cfg.n_frontend_tokens or s)
                out["frames"] = sds((b, enc_len, cfg.d_model), dt)
            elif cfg.frontend != "none":
                s_text = max(s - cfg.n_frontend_tokens, 1)
                out["frames"] = sds((b, cfg.n_frontend_tokens, cfg.d_model), dt)
            out["tokens"] = sds((b, s_text), i32)
            if with_labels:
                out["labels"] = sds((b, s_text), i32)
            return out

        if shape.kind == "train":
            return {"batch": text_batch(True)}
        if shape.kind == "prefill":
            return {"batch": text_batch(False)}
        # decode: one token against a cache of size s
        enc_len = min(s, cfg.n_frontend_tokens or s) if cfg.is_encdec else 0
        return {
            "cache": self.cache_spec(b, s, enc_len),
            "tokens": sds((b,), i32),
            "lengths": sds((b,), i32),
        }


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
