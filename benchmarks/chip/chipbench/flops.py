"""Operations of one processed token, from a configuration's sizes.

Counted as the model states it (the reference's mathematics, not the
program's): a multiply-add is 2 operations; attention is charged for the
positions a token attends.  Each configuration's ``Arch`` states, layer by
layer, the weights a token multiplies through and its attention's
operations over a context; this module sums them.  Only useful tokens
count: bucket padding, idle cache rows and the program's recomputation do
not.
"""
from __future__ import annotations

import functools
from typing import Iterable, Tuple

import numpy as np

from chipbench.reference import Arch


@functools.lru_cache(maxsize=None)
def matmul_params(a: Arch) -> int:
    """Weights one token multiplies through, over all layers."""
    return sum(a.layer_weights(i) for i in range(a.n_layers))


def attn_flops(a: Arch, ctx) -> int:
    """Scores and weighted values of one query over ``ctx`` positions
    (an int, or an array of them), over all layers."""
    ctx = np.asarray(ctx, np.int64)
    return sum(a.layer_attention(i, ctx) for i in range(a.n_layers))


def token_flops(a: Arch, ctx: int) -> int:
    """All layers for one token that sees ``ctx`` positions (itself
    included); the output head is apart (:func:`head_flops`)."""
    return 2 * matmul_params(a) + attn_flops(a, ctx)


def head_flops(a: Arch) -> int:
    return 2 * a.d_model * a.vocab


def chunk_flops(a: Arch, start: int, length: int, emits: bool) -> int:
    """A prefill chunk of ``length`` tokens after ``start`` cached ones;
    ``emits``: its last position yields a served token."""
    ctx = np.arange(start + 1, start + length + 1)
    body = 2 * matmul_params(a) * length + attn_flops(a, ctx)
    return body + (head_flops(a) if emits else 0)


def iteration_flops(a: Arch, chunks: Iterable[Tuple[int, int, bool]],
                    decode_ctx: Iterable[int]) -> int:
    """One engine iteration: ``chunks`` as (start, length, emits) and one
    decode per context in ``decode_ctx`` (positions seen, itself
    included)."""
    total = sum(chunk_flops(a, s, n, e) for s, n, e in chunks)
    return total + sum(token_flops(a, c) + head_flops(a) for c in decode_ctx)
