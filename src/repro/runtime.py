"""Process-level policy: one process per chip, and where compiled code is
cached.

A TPU belongs to one process at a time.  A parent that has touched JAX
holds the chip, and a spawned child that needs it then fails or hangs.
So on a TPU backend the paths that measure in child processes refuse
(:func:`refuse_child_processes`), and the paths whose children only
price from fitted latency models start them on the CPU backend
(:func:`cpu_only_children`).

:func:`use_compile_cache` is called by the command-line entry points
(``repro.profile``, ``repro.sweep``, ``repro.optimize``) and by
``chip_smoke.py``, never at import: a ``JAX_COMPILATION_CACHE_DIR`` set in
the environment is left to JAX, and otherwise the persistent cache goes to
one fixed, git-ignored path inside the checkout.

:func:`span` names a stretch of host work in a ``jax.profiler`` trace.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Iterator, Optional

import jax

#: the checkout's own compile-cache directory (fixed: the path is part of
#: the cache key, so a directory that moves never hits)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def refuse_child_processes(what: str) -> None:
    """Raise when ``what`` would start child processes that need the chip
    this process already holds."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} starts child processes that would need the TPU this "
            "process holds; run it in one process (workers=1, no "
            "task_timeout)")


@contextlib.contextmanager
def cpu_only_children() -> Iterator[None]:
    """Processes spawned inside this block see ``JAX_PLATFORMS=cpu``.  The
    current process keeps its backend: JAX reads the variable once, when
    it is imported."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = old


def use_compile_cache() -> Optional[Path]:
    """Turn on JAX's persistent compilation cache at the checkout's fixed
    path, unless ``JAX_COMPILATION_CACHE_DIR`` already names one.  Returns
    the directory this call set, or None when it left the choice to JAX."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return COMPILE_CACHE_DIR


#: prefix of every program span in a ``jax.profiler`` trace
SPAN_PREFIX = "dooly."


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A host span ``dooly.<name>`` in any ``jax.profiler`` trace that is
    recording, with ``attrs`` as its arguments; with no trace recording
    it costs what a ``contextlib.nullcontext`` costs.

    The spans sit on the same clock as the device's ops, so an operator
    who wraps a run in ``jax.profiler.trace(logdir)`` sees in TensorBoard
    or Perfetto which host work each idle stretch of the device waited
    on.  A span never wraps a timed interval: it would add to the time."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **attrs)
