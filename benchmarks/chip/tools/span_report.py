"""Run one cell traced and report what the program's spans show.

    python3 benchmarks/chip/tools/span_report.py --workload <cell> \\
        --seed <n> --seconds <s> [--out <file.json>]

The run is ``run.py --trace 1``'s, through ``spans.execute_traced``: its
trace keeps the program's ``dooly.*`` spans and the device's module
events.  Prints the harness's result line, then one line with
``spans.report`` for the cell's kind (serve or profile) and the median
milliseconds of the benchmark's own ``chipbench.execute`` spans in the
traced window (a serve cell's iteration as the benchmark times it, there
on any program).  ``--out`` also keeps the events.  Run from the root of
a checkout, as ``run.py``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parents[1]


def execute_ms(events):
    """Median ``chipbench.execute`` milliseconds inside the window."""
    import numpy as np
    from chipbench import trace
    w = [(s, s + d) for n, s, d in events["host"] if n == trace.WINDOW_SPAN]
    ds = [d for n, s, d in events["host"]
          if n == trace.SPAN_PREFIX + "execute" and w
          and w[0][0] <= s and s + d <= w[0][1]]
    return float(np.median(ds)) / 1e6 if ds else None


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None)
    a = p.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    from chipbench import cells, harness, spans

    cell = cells.find_cell(cells.load_benchmark(), a.workload)
    harness.use_compile_cache()
    result, events = spans.execute_traced(cell, a.seed, a.seconds,
                                          t_process=T_PROCESS)
    harness.emit(result)
    line = {"workload": a.workload, "seed": a.seed,
            "program": spans.report(events, cell.driver),
            "chipbench_execute_ms": execute_ms(events),
            "program_spans": len(events.get("program", ())),
            "module_events": len(events.get("modules", ()))}
    print(json.dumps(line), flush=True)
    if a.out:
        with open(a.out, "w") as fh:
            json.dump(events, fh)
    return 0


if __name__ == "__main__":
    os.chdir(HERE.parents[1])
    sys.exit(main())
