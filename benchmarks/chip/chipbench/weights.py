"""Seeded weights, made on the device in one jitted call.

The tree has the program's parameter layout (names, shapes and dtypes, as
``Model.abstract_params`` states them); the values are the benchmark's own:
norm scales 1, the embedding table N(0, d^-1/2), every other matrix
N(0, fan_in^-1/2) with fan_in the second-to-last dimension.  The reference
regenerates the same tree with the same call, so it shares no array with
the program.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key for any whole-number seed (the driver's exceed 32
    signed bits)."""
    return int(np.random.SeedSequence([int(seed), stream]).generate_state(1)[0])


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return ""


def _scale(name: str, shape) -> float:
    if name == "table":
        return shape[-1] ** -0.5
    return 1.0 / math.sqrt(shape[-2])


@functools.lru_cache(maxsize=None)
def _generator(abstract_flat, treedef):
    def gen(key):
        out = []
        for i, (name, shape, dtype) in enumerate(abstract_flat):
            if name == "scale":
                out.append(jnp.ones(shape, dtype))
            elif len(shape) < 2:
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * _scale(name, shape)).astype(dtype))
        return jax.tree.unflatten(treedef, out)
    return jax.jit(gen)


def make_params(cfg, seed: int) -> Tree:
    """The full parameter tree for ``cfg`` on the default device."""
    from repro.models import build_model
    abstract = build_model(cfg).abstract_params()
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    spec = tuple((_leaf_name(path), tuple(s.shape), jnp.dtype(s.dtype).name)
                 for path, s in flat)
    return _generator(spec, treedef)(jax.random.key(seed32(seed)))
