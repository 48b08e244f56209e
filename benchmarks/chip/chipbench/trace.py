"""From a profiler trace to device busy time, top device ops and idle gaps.

``Recorder`` runs ``jax.profiler`` over part of a window into a temporary
directory and returns the trace as plain events (``load_events``): the
device's op and whole-program events, the benchmark's ``chipbench.*``
host spans and the program's own ``dooly.*`` spans.  ``reduce_events`` is
the reduction the per-layer metrics and the breakdown read:

- the traced window is the host span ``chipbench.window`` that the driver
  opens around the traced part of its measured window;
- busy time is the union of the intervals of the device planes' op events
  (line ``XLA Ops``) inside that window, averaged over the chips used;
- idle gaps are the rest of the window, each named by the innermost host
  span around its midpoint, the benchmark's or the program's (what the
  host was doing).
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Any, Dict, List, Tuple

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "dooly."
DEVICE_PREFIX = "/device:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: an op's name is its HLO instruction text; the breakdown keeps its head
NAME_CHARS = 120


def span(on: bool, name: str):
    """A ``chipbench.<name>`` host span in the trace, or nothing."""
    import contextlib
    import jax
    return (jax.profiler.TraceAnnotation(SPAN_PREFIX + name) if on
            else contextlib.nullcontext())


class Recorder:
    """The profiler around part of a window: ``start`` opens the trace and
    the ``chipbench.window`` span, ``stop`` closes both, ``events`` reads
    the trace and removes its files."""

    def __init__(self):
        self.logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._span = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.logdir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def events(self) -> Dict[str, List]:
        try:
            paths = glob.glob(os.path.join(self.logdir, "**",
                                           "*.xplane.pb"), recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return load_events(paths[0])
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


def base_name(name: str) -> str:
    """A span's name without the ``#`` that opens its arguments."""
    return name.split("#", 1)[0]


def load_events(path: str) -> Dict[str, List]:
    """The events of an xplane file:

    - ``"device"``: ``[[plane, name, start_ns, dur_ns], ...]``, the device
      planes' op events (line ``XLA Ops``);
    - ``"modules"``: the same, of their whole-program events (line ``XLA
      Modules``, named ``jit_<function>``);
    - ``"host"``: ``[[name, start_ns, dur_ns], ...]``, the ``chipbench.*``
      host spans;
    - ``"program"``: the same, of the program's ``dooly.*`` spans, each
      name cut at the ``#`` that opens its arguments."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, modules, host, program = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                into = (device if line.name in OP_LINES else
                        modules if line.name in MODULE_LINES else None)
                if into is not None:
                    into.extend([plane.name, e.name, int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = [e.name, int(e.start_ns), int(e.duration_ns)]
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(span)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        span[0] = base_name(e.name)
                        program.append(span)
    return {"device": device, "modules": modules, "host": host,
            "program": program}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _label(spans, t: int) -> str:
    """Innermost (shortest) host span that covers instant ``t``, the
    benchmark's or the program's, without its prefix and arguments."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and name != WINDOW_SPAN \
                and (best is None or d < best[1]):
            best = (name, d)
    if best is None:
        return "none"
    name = base_name(best[0])
    for prefix in (SPAN_PREFIX, PROGRAM_PREFIX):
        if name.startswith(prefix):
            return name[len(prefix):]
    return name


def host_spans(events) -> List:
    """The benchmark's host spans but its window, and the program's."""
    return ([h for h in events["host"] if h[0] != WINDOW_SPAN]
            + list(events.get("program", ())))


def reduce_events(events: Dict[str, List], chips: int = 1,
                  top: int = 10) -> Dict[str, Any]:
    windows = [(s, s + d) for name, s, d in events["host"]
               if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = windows[0]
    per_plane: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    op_time: Dict[str, int] = defaultdict(int)
    for plane, name, s, d in events["device"]:
        s2, e2 = max(s, w0), min(s + d, w1)
        if e2 > s2:
            per_plane[plane].append((s2, e2))
            op_time[name] += e2 - s2
    busy_ns = 0
    gaps: List[Tuple[int, int]] = []
    for plane in sorted(per_plane):
        merged = _union(per_plane[plane])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = max(chips, 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = host_spans(events)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[name[:NAME_CHARS], t / n / 1e9] for name, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(spans, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }
