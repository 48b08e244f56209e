"""Record a small trace on the chip and write its events for the tests.

    python3 benchmarks/chip/tools/trace_probe.py <out.json>

Runs a few jitted steps with host spans and idle sleeps between them
under ``trace.Recorder``, prints every plane and line of the raw trace
with its event count and first timestamps, and writes the extracted
events (``trace.load_events``' form) to ``<out.json>``.
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(out):
    import glob
    import os
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from chipbench import trace

    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    rec = trace.Recorder()
    rec.start()
    for i in range(3):
        with jax.profiler.TraceAnnotation("chipbench.execute"):
            for _ in range(4):
                x = step(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.wait_for_arrival"):
            time.sleep(0.01)
    rec.stop()
    path = glob.glob(os.path.join(rec.logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            ev = list(line.events)
            print(plane.name, "|", line.name, "|", len(ev),
                  [(e.name, e.start_ns, e.duration_ns) for e in ev[:2]])
    events = rec.events()
    with open(out, "w") as fh:
        json.dump(events, fh)
    print(json.dumps(trace.reduce_events(events)))


if __name__ == "__main__":
    main(sys.argv[1])
