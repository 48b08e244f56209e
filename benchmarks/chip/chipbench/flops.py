"""Operations of one processed token, from a configuration's sizes.

Counted as the model states it (the reference's mathematics, not the
program's): a multiply-add is 2 operations; attention is charged for the
positions a token attends, and MLA in its plain form (latent expanded per
head) for the token itself, with the cache holding expanded keys and values.
Only useful tokens count: bucket padding, idle cache rows and the program's
recomputation do not.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

from chipbench.reference import Arch


def matmul_params(a: Arch) -> int:
    """Weights one token multiplies through in one layer."""
    d = a.d_model
    if a.attention == "mla":
        qk = a.qk_nope + a.qk_rope
        attn = (d * a.q_lora_rank + a.q_lora_rank * a.n_heads * qk
                + d * (a.kv_lora_rank + a.qk_rope)
                + a.kv_lora_rank * a.n_heads * (a.qk_nope + a.v_head)
                + a.n_heads * a.v_head * d)
    else:
        hd = a.head_dim
        attn = 2 * d * a.n_heads * hd + 2 * d * a.n_kv_heads * hd
    return attn + 3 * d * a.d_ff


def _layer_windows(a: Arch):
    return [a.layer_window(i) for i in range(a.n_layers)]


def attn_flops(a: Arch, ctx) -> int:
    """Scores and weighted values of one query over ``ctx`` positions
    (an int, or an array of them), over all layers."""
    ctx = np.asarray(ctx, np.int64)
    if a.attention == "mla":
        per = a.n_heads * (a.qk_nope + a.qk_rope + a.v_head)
    else:
        per = 2 * a.n_heads * a.head_dim
    spans = sum(np.minimum(ctx, w) if w else ctx for w in _layer_windows(a))
    return int(2 * per * np.sum(spans))


def token_flops(a: Arch, ctx: int) -> int:
    """All layers for one token that sees ``ctx`` positions (itself
    included); the output head is apart (:func:`head_flops`)."""
    return 2 * matmul_params(a) * a.n_layers + attn_flops(a, ctx)


def head_flops(a: Arch) -> int:
    return 2 * a.d_model * a.vocab


def chunk_flops(a: Arch, start: int, length: int, emits: bool) -> int:
    """A prefill chunk of ``length`` tokens after ``start`` cached ones;
    ``emits``: its last position yields a served token."""
    ctx = np.arange(start + 1, start + length + 1)
    body = 2 * matmul_params(a) * a.n_layers * length + attn_flops(a, ctx)
    return body + (head_flops(a) if emits else 0)


def iteration_flops(a: Arch, chunks: Iterable[Tuple[int, int, bool]],
                    decode_ctx: Iterable[int]) -> int:
    """One engine iteration: ``chunks`` as (start, length, emits) and one
    decode per context in ``decode_ctx`` (positions seen, itself
    included)."""
    total = sum(chunk_flops(a, s, n, e) for s, n, e in chunks)
    return total + sum(token_flops(a, c) + head_flops(a) for c in decode_ctx)

