"""The reduction from trace events to busy time, top ops and idle gaps."""
import json

import pytest

from _chipbench_smoke import BENCH
from chipbench import trace

SAMPLE = BENCH / "tests" / "data" / "trace_v5e.json"


def test_reduction_by_hand():
    w = 1_000_000
    events = {
        "host": [["chipbench.window", 0, w],
                 ["chipbench.execute", 0, 600_000],
                 ["chipbench.complete", 600_000, 400_000]],
        "device": [["/device:TPU:0", "fusion.1", 100_000, 200_000],
                   ["/device:TPU:0", "fusion.2", 250_000, 150_000],
                   ["/device:TPU:0", "fusion.1", 900_000, 200_000],
                   ["/device:TPU:0", "copy", -50_000, 80_000]],
    }
    r = trace.reduce_events(events)
    # busy: [0, 30k) + [100k, 400k) + [900k, 1000k) inside the window
    assert r["busy_s"] == pytest.approx(430_000 / 1e9)
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300_000 / 1e9)]
    # gaps: [400k, 900k) during complete (mid 650k), [30k, 100k) in execute
    assert r["idle_gaps"] == [["complete", pytest.approx(5e-4)],
                              ["execute", pytest.approx(7e-5)]]


def test_reduction_of_a_trace_recorded_on_the_chip():
    if not SAMPLE.is_file():
        pytest.fail(f"missing {SAMPLE}")
    events = json.loads(SAMPLE.read_text())
    r = trace.reduce_events(events)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert {g[0] for g in r["idle_gaps"]} <= {"execute", "wait_for_arrival",
                                               "none"}


def test_cpu_trace_reads_host_spans():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    rec = trace.Recorder()
    rec.start()
    with jax.profiler.TraceAnnotation("chipbench.execute"):
        f(x).block_until_ready()
    rec.stop()
    events = rec.events()
    names = [h[0] for h in events["host"]]
    assert "chipbench.window" in names and "chipbench.execute" in names
    r = trace.reduce_events(events)
    assert r["window_s"] > 0
