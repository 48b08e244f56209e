"""One run of one cell: checks the device, runs the cell's driver, prints
the result.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), and last of all ``checks``: each number that decided
``correct``, beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time
from typing import Any, Dict, List, Optional

from chipbench import cells

#: JAX's compile-pipeline events (seconds each): tracing, lowering, and
#: the backend step, which compiles or loads from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: the checkout's persistent compile cache (a fixed path: it is part of
#: the cache key)
CACHE_DIR = cells.CHECKOUT / ".bench_cache" / "jax"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


class Report:
    """What a driver tells the harness while it runs."""

    def __init__(self, t_process: float, peak_flops: float):
        self.t_process = t_process
        self.peak_flops = peak_flops
        self.memory_peak_bytes: Optional[int] = None
        self.compile_events: List = []
        self.gc_pauses: List = []       # (seconds, clock at the end)
        self._gc_start: Optional[float] = None

    def setup_done(self) -> float:
        return time.perf_counter() - self.t_process

    def memory_peak(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.gc_pauses.append((now - self._gc_start, now))
            self._gc_start = None

    def __enter__(self):
        """Record JAX's compile-pipeline events and Python's garbage
        collections with the clock they end at, until ``__exit__``."""
        import jax
        gc.callbacks.append(self._on_gc)

        def on_duration(event, duration, **_):
            if event in COMPILE_EVENTS:
                self.compile_events.append((event, float(duration),
                                            time.perf_counter()))

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.compile_events.append((event, 0.0, time.perf_counter()))
        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax
        gc.callbacks.remove(self._on_gc)
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
        return False


def use_compile_cache():
    """Keep every compiled program in the checkout's own cache, whatever
    the environment names."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int, platform: Optional[str] = "tpu"
                ) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if platform is not None and devs[0].platform != platform:
        raise NoDevice(f"JAX found {devs[0].platform} devices, no "
                       f"{platform}: this benchmark measures the chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_flops(kind: str, table: Optional[Dict] = None) -> float:
    table = table or cells.load_json(cells.BENCH_DIR / "peaks.json")
    if kind not in table["devices"]:
        raise NoDevice(f"no peak for device kind {kind!r} in peaks.json")
    return float(table["devices"][kind]["bf16_flops"])


def passed(check: Dict[str, Any]) -> bool:
    v, lim = check["value"], check["limit"]
    return v >= lim if check.get("at_least") else v <= lim


def execute(cell: cells.Cell, seed: int, seconds: float, traced: bool, *,
            t_process: float, platform: Optional[str] = "tpu",
            peaks: Optional[Dict] = None) -> Dict[str, Any]:
    """Run ``cell`` once and return its result object."""
    device = device_info(cell.chips, platform)
    if cell.driver == "serve":
        from chipbench import serve as driver
    elif cell.driver == "profile":
        from chipbench import profile as driver
    else:
        raise ValueError(f"unknown driver {cell.driver!r}")
    with Report(t_process, peak_flops(device["kind"], peaks)) as report:
        run = driver.run_cell(cell, seed, seconds, traced, report)
    run.compile_events = report.compile_events
    run.peak_flops = report.peak_flops
    device["memory_peak_bytes"] = report.memory_peak_bytes
    out: Dict[str, Any] = {}
    if traced:
        from chipbench import trace
        run.trace = trace.reduce_events(run.trace_events, chips=cell.chips)
        metrics = cells.read_per_layer(list(cell.per_layer), run)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    else:
        values = driver.end_to_end(run)
        values["setup_s"] = run.setup_s
        metrics = {}
        for m in cell.end_to_end:
            if m.name not in values:
                raise RuntimeError(f"the run measured no {m.name}")
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    inside = [e[0] for e in report.compile_events
              if run.window[0] <= e[2] <= run.window[1]]
    compiles = inside.count(BACKEND_COMPILE) - inside.count(CACHE_HIT)
    gc_s = sum(d for d, end in report.gc_pauses
               if run.window[0] <= end <= run.window[1])
    print(f"[chipbench] {cell.name} seed={seed} setup_s={run.setup_s:.3f} "
          f"window_s={run.window_s:.3f} compiles_in_window={compiles} "
          f"cache_loads_in_window={inside.count(CACHE_HIT)} "
          f"gc_s_in_window={gc_s:.3f} check_s={run.check_s:.3f}",
          file=sys.stderr)
    result = {"correct": all(passed(c) for c in run.checks.values()),
              "attempted": int(run.attempted), "failed": int(run.failed),
              "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = run.checks
    return result


#: stands in for a check value with no finite reading (nothing compared,
#: or a non-finite output): strict JSON has no infinity
NO_READING = 1e30


def emit(result: Dict[str, Any]):
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = NO_READING
    for name, c in result["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name} = {c['value']!r} (limit {rel} {c['limit']!r}) "
              f"{'ok' if passed(c) else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
