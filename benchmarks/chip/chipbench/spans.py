"""The program's own spans and device programs in a profiler trace.

The program marks its host work with ``dooly.*`` spans
(``repro.runtime.span``): the engine's iteration and its parts, the
profiler's tasks and the oracle's calls.  ``trace.load_events`` reads them
under ``"program"`` (each name cut at the ``#`` that opens its arguments),
and the device's whole-program events (line ``XLA Modules``, named
``jit_<function>``) under ``"modules"``.

The readers below take such events and return None where the trace holds
no program span (or, for a program's device time, no module event), so
that a program without spans reports nothing rather than a 0.  All of them
read the traced window (the host span ``chipbench.window``) only.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import trace


def execute_traced(cell, seed: int, seconds: float, **kw):
    """``harness.execute(cell, seed, seconds, True, **kw)``, keeping the
    trace's events (``serve.run_cell`` and ``profile.run_cell`` look
    ``trace.Recorder`` up when they run).  Returns (result, events); the
    events also hold ``"compiles"``: ``[[event, seconds, end_ns], ...]``,
    JAX's compile-pipeline events that end inside the traced window, on the
    trace's clock."""
    import time
    import jax
    from chipbench import harness
    kept, compiles = [], []

    class Keeping(trace.Recorder):
        def start(self):
            super().start()
            self.t_start = time.perf_counter()

        def events(self):
            ev = super().events()
            w0, w1 = _window(ev)
            ev["compiles"] = [
                [name.rsplit("/", 1)[-1], secs, ns]
                for name, secs, t in compiles
                for ns in [w0 + int((t - self.t_start) * 1e9)]
                if w0 <= ns <= w1]
            kept.append(ev)
            return ev

    def on_duration(event, duration, **_):
        if event in harness.COMPILE_EVENTS:
            compiles.append((event, float(duration), time.perf_counter()))

    orig, trace.Recorder = trace.Recorder, Keeping
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        result = harness.execute(cell, seed, seconds, True, **kw)
    finally:
        trace.Recorder = orig
        jax.monitoring.unregister_event_duration_listener(on_duration)
    return result, kept[0]


def _window(events) -> Tuple[int, int]:
    for name, s, d in events["host"]:
        if name == trace.WINDOW_SPAN:
            return s, s + d
    raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")


def _program(events, name: Optional[str] = None) -> List[List]:
    """Program spans named ``dooly.<name>`` (all, without a name) that lie
    inside the traced window."""
    w0, w1 = _window(events)
    full = None if name is None else trace.PROGRAM_PREFIX + name
    return [e for e in events.get("program", ())
            if (full is None or e[0] == full)
            and e[1] >= w0 and e[1] + e[2] <= w1]


def idle_gaps(events, top: int = 10) -> Optional[List]:
    """The window's longest idle gaps, each named by the innermost span
    around its midpoint, the program's ``dooly.*`` spans among the
    benchmark's ``chipbench.*`` ones (each without its prefix).  None
    without program spans."""
    if not events.get("program"):
        return None
    return trace.reduce_events(events, top=top)["idle_gaps"]


def compile_by_span(events) -> Optional[Dict[str, float]]:
    """Seconds of JAX's compile pipeline (``"compiles"``) by the innermost
    span around the instant each event ends, program spans included.
    None without program spans or compile events."""
    if not events.get("program") or not events.get("compiles"):
        return None
    spans = trace.host_spans(events)
    out: Dict[str, float] = {}
    for _, secs, t in events["compiles"]:
        label = trace._label(spans, t)
        out[label] = out.get(label, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def host_ms_per_iter(events) -> Optional[float]:
    """Median over the window's ``engine.execute`` spans of the span's
    milliseconds less those of the ``engine.sync`` spans inside it: the
    host's own time per engine iteration."""
    its = _program(events, "engine.execute")
    if not its:
        return None
    syncs = _program(events, "engine.sync")
    host = []
    for _, s, d in its:
        waited = sum(d2 for _, s2, d2 in syncs if s <= s2 and s2 + d2 <= s + d)
        host.append(d - waited)
    return float(np.median(host)) / 1e6


def spans_per_iter(events) -> Optional[float]:
    """Program spans per ``engine.execute`` span in the window."""
    its = _program(events, "engine.execute")
    if not its:
        return None
    return len(_program(events)) / len(its)


def program_device_ms(events, function: str) -> Optional[float]:
    """Median device milliseconds of the program ``jit_<function>``: its
    module events that start inside the window."""
    w0, w1 = _window(events)
    full = "jit_" + function
    ds = [d for _, name, s, d in events.get("modules", ())
          if trace.base_name(name).split("(", 1)[0] == full
          and w0 <= s < w1]
    return float(np.median(ds)) / 1e6 if ds else None


def span_share(events, name: str) -> Optional[float]:
    """Per cent of the traced window inside ``dooly.<name>`` spans (their
    union).  None without program spans."""
    if not _program(events):
        return None
    w0, w1 = _window(events)
    merged = trace._union([(s, s + d) for _, s, d in _program(events, name)])
    return 100.0 * sum(e - s for s, e in merged) / (w1 - w0)


#: the quantities a traced serve or profile run reports from program
#: spans and module events, by name
READERS: Dict[str, Dict[str, Any]] = {
    "serve": {
        "engine.host_ms_per_iter": host_ms_per_iter,
        "engine.decode_device_ms":
            lambda ev: program_device_ms(ev, "decode_step"),
        "engine.chunk_device_ms":
            lambda ev: program_device_ms(ev, "prefill_chunk"),
        "engine.spans_per_iter": spans_per_iter,
    },
    "profile": {
        "profile.timed_share": lambda ev: span_share(ev, "oracle.timed"),
        "profile.operand_share":
            lambda ev: span_share(ev, "profile.operands"),
        "profile.first_call_share":
            lambda ev: span_share(ev, "oracle.first_call"),
    },
}


def report(events, kind: str) -> Dict[str, Any]:
    """Each quantity of ``READERS[kind]`` that the trace holds, the idle
    gaps named by the program's spans, and the compile seconds by span."""
    out = {name: fn(events) for name, fn in READERS[kind].items()}
    out = {k: v for k, v in out.items() if v is not None}
    for name, fn in (("idle_gaps", idle_gaps),
                     ("compile_s_by_span", compile_by_span)):
        value = fn(events)
        if value is not None:
            out[name] = value
    return out
