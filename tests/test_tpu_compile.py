"""Compiles for a TPU v5e that is described, not attached.

Every test here compiles at a real width for one chip of a described
``v5e:2x2`` topology: the three Pallas kernels (which must lower to a
Mosaic ``tpu_custom_call``, not interpret mode), and minicpm3-4b's
full-width serving programs (the engine's decode step over 8 rows x 4096
slots, and its 256-token prefill chunk), whose argument, output and
temporary bytes must fit one chip's 16 GiB.  Nothing runs, so this says
nothing about results or times.

The topology is described inside a module-scoped fixture — never at
import — and all such tests live in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import mamba_scan as ms
from repro.models import build_model

CHIP_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_case(name, chip):
    cr, hy, fm = (get_config(n) for n in ("command-r7b", "hymba-1.5b",
                                          "falcon-mamba-7b"))
    s = functools.partial(_sds, chip)
    if name == "flash_causal":
        h, kv, d = cr.n_heads, cr.n_kv_heads, cr.resolved_head_dim
        return (functools.partial(fa.flash_attention_fwd, causal=True),
                s((1, h, 2048, d)), s((1, kv, 2048, d)), s((1, kv, 2048, d)))
    if name == "flash_window":
        h, kv, d = hy.n_heads, hy.n_kv_heads, hy.resolved_head_dim
        return (functools.partial(fa.flash_attention_fwd, causal=True,
                                  window=hy.sliding_window),
                s((1, h, 2048, d)), s((1, kv, 2048, d)), s((1, kv, 2048, d)))
    if name == "flash_backward":
        h, kv, d = cr.n_heads, cr.n_kv_heads, cr.resolved_head_dim
        return (functools.partial(fa.flash_attention_bwd, causal=True),
                s((1, h, 1024, d)), s((1, kv, 1024, d)), s((1, kv, 1024, d)),
                s((1, h, 1024, d)), s((1, h, 1024, 1), jnp.float32),
                s((1, h, 1024, d)))
    if name == "decode":
        h, kv, d = cr.n_heads, cr.n_kv_heads, cr.resolved_head_dim
        return (da.decode_attention, s((8, kv, h // kv, d)),
                s((8, kv, 4096, d)), s((8, kv, 4096, d)),
                s((8,), jnp.int32))
    di, n = fm.ssm_d_inner, fm.ssm_state
    return (ms.mamba_scan, s((1, 512, di)), s((1, 512, di)),
            s((di, n), jnp.float32), s((1, 512, n)), s((1, 512, n)),
            s((di,), jnp.float32), s((1, di, n), jnp.float32))


@pytest.mark.parametrize("name", ["flash_causal", "flash_window",
                                  "flash_backward", "decode", "mamba_scan"])
def test_kernel_compiles_to_mosaic(one_chip, name):
    fn, *args = _kernel_case(name, one_chip)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def _serving_program(chip, which):
    cfg = get_config("minicpm3-4b")
    model = build_model(cfg)

    def place(tree):
        return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype), tree)

    params = place(model.abstract_params())
    if which == "decode":
        fn = functools.partial(model.decode_step, impl="xla")
        return fn, (params, place(model.cache_spec(8, 4096, use_ring=False)),
                    _sds(chip, (8,), jnp.int32), _sds(chip, (8,), jnp.int32))
    def fn(p, cache, toks, lens, last):         # the engine's chunk program
        return model.prefill_chunk(p, cache, toks, lens, impl="xla",
                                   last_pos=last)
    return fn, (params, place(model.cache_spec(1, 4096, use_ring=False)),
                _sds(chip, (1, 256), jnp.int32), _sds(chip, (1,), jnp.int32),
                _sds(chip, (1,), jnp.int32))


@pytest.mark.parametrize("which", ["decode", "prefill_chunk"])
def test_minicpm3_serving_step_fits_one_chip(one_chip, which):
    fn, args = _serving_program(one_chip, which)
    mem = _compile(fn, *args).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 7 * 2**30      # the bf16 weights
    assert total < CHIP_BYTES, total / 2**30
