"""Quantities the per-layer metric files read from a run.

Each returns None when the run holds nothing to read, so the harness
leaves the metric out rather than print a 0.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def idle_share(run) -> Optional[float]:
    """Per cent of the traced window with no operation on the device."""
    t = getattr(run, "trace", None)
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def decode_iter_ms(run) -> Optional[float]:
    """Median engine seconds of the iterations that only decode."""
    ts = [it.model_s for it in getattr(run, "iterations", ())
          if it.decodes and not it.chunks]
    return 1e3 * float(np.median(ts)) if ts else None


def prefill_tokens_per_s(run) -> Optional[float]:
    """Prompt tokens over the engine seconds of the iterations that
    carry prefill chunks."""
    its = [it for it in getattr(run, "iterations", ()) if it.chunks]
    secs = sum(it.model_s for it in its)
    if not its or secs <= 0:
        return None
    return sum(c[2] for it in its for c in it.chunks) / secs


def step_mfu(run) -> Optional[float]:
    """Per cent of the chip's bf16 peak: the useful operations of every
    iteration (``flops.py``) over the engine seconds of all of them."""
    from chipbench.serve import iteration_flops
    its = getattr(run, "iterations", ())
    secs = sum(it.model_s for it in its)
    if not its or secs <= 0:
        return None
    ops = sum(iteration_flops(run, it) for it in its)
    return 100.0 * ops / secs / run.peak_flops


def compile_share(run) -> Optional[float]:
    """Per cent of the window spent in JAX's compile pipeline: tracing,
    lowering, compiling, and loading programs from the persistent cache."""
    events = getattr(run, "compile_events", None)
    if events is None or run.window_s <= 0:
        return None
    w0, w1 = run.window
    secs = sum(d for _, d, t in events if w0 <= t <= w1)
    return 100.0 * secs / run.window_s


def of_trace(reader):
    """A metric reader that applies ``reader`` (a ``chipbench.spans``
    reader) to a traced run's events; None for an untraced run."""
    def read(run) -> Optional[float]:
        events = getattr(run, "trace_events", None)
        return reader(events) if events else None
    return read
