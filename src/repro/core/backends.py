"""Measurement oracles.

Two oracles stand in for the paper's CUDA events:

* ``cpu_wallclock`` — host clock around the jit-compiled entry, ending in
  ``block_until_ready`` on whatever backend JAX runs (the CPU in tests,
  the chip on a TPU host).  Rows it writes under a TPU hardware label must
  come from a TPU: :func:`require_measuring_device` refuses otherwise.
* ``tpu_analytical`` — the v5e roofline model over the compiled artifact
  (trip-aware hlo_cost): latency = max(flops/peak, bytes/bw).  Works at any
  model size with zero allocation; used for the full-size dedup accounting.

The profiling *structure* (taint, signatures, dedup, sweeps) is identical
under either oracle — which is exactly the paper's point.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.parallel.roofline import HBM_BW, PEAK_FLOPS
from repro.runtime import span


_DISPATCH_FLOOR: list = []


def _dispatch_floor() -> float:
    """Per-call harness overhead (jit dispatch + sync), measured once and
    subtracted from op measurements — the CPU analogue of CUDA events
    excluding launch overhead."""
    if not _DISPATCH_FLOOR:
        f = jax.jit(lambda x: x)
        x = jnp.zeros((1,), jnp.float32)
        jax.block_until_ready(f(x))
        ts = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        _DISPATCH_FLOOR.append(ts[len(ts) // 2])
    return _DISPATCH_FLOOR[0]


def cpu_wallclock(fn: Callable, args: Sequence[Any], *, repeats: int = 5,
                  warmup: int = 2) -> float:
    """Median wall-clock seconds of one jitted call (concrete args),
    harness dispatch floor subtracted.

    Spans: ``oracle.first_call`` (tracing, lowering, compiling or loading,
    and running the program), ``oracle.warmup`` (the other warm-up
    calls), ``oracle.timed`` (around the timed repeats, never inside
    one)."""
    jitted = jax.jit(fn)
    for i in range(warmup):
        with span("oracle.first_call" if i == 0 else "oracle.warmup"):
            out = jitted(*args)
            jax.block_until_ready(out)
    times = []
    with span("oracle.timed"):
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = jitted(*args)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
    times.sort()
    med = times[len(times) // 2]
    return max(med - _dispatch_floor(), med * 0.05, 1e-8)


def tpu_analytical(fn: Callable, args: Sequence[Any]) -> float:
    """Roofline seconds on one v5e chip from the compiled (CPU-backend)
    module, FLOPs/bytes trip-aware."""
    from repro.parallel import hlo_cost
    compiled = jax.jit(fn).lower(*args).compile()
    cost = hlo_cost.analyze_text(compiled.as_text())
    return max(cost.flops / PEAK_FLOPS, cost.bytes / HBM_BW, 1e-7)


ORACLES = {"cpu_wallclock": cpu_wallclock, "tpu_analytical": tpu_analytical}


def require_measuring_device(oracle: str, hardware: str) -> None:
    """Refuse wall-clock rows labelled with a TPU the process does not
    run on: a run that means to measure the chip and finds none fails
    instead of writing CPU timings under the chip's name."""
    if (oracle == "cpu_wallclock" and "tpu" in hardware.lower()
            and jax.default_backend() != "tpu"):
        raise RuntimeError(
            f"oracle cpu_wallclock would time the {jax.default_backend()} "
            f"backend but label the rows {hardware!r}; run on the TPU, or "
            "label the rows with the measuring device's device_kind")


def measure(oracle: str, fn: Callable, args: Sequence[Any]) -> float:
    impl = ORACLES.get(oracle)
    if impl is None:
        raise ValueError(f"unknown oracle {oracle!r}; expected one of "
                         f"{', '.join(sorted(ORACLES))}")
    return impl(fn, args)
