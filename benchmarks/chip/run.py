"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; ``--trace 0`` prints its end-to-end metrics, ``--trace
1`` its per-layer metrics from a run with the profiler on.  The last line
of standard output is the result object; the run exits 1 when the cell
cannot run (no TPU, too few chips, a missing file) and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    try:
        import repro  # noqa: F401
        from chipbench import cells, harness
    except ImportError as e:
        print(f"run.py: cannot import the program or the harness ({e}); "
              "run from the root of a checkout", file=sys.stderr)
        return 1
    try:
        cell = cells.find_cell(cells.load_benchmark(), args.workload)
        harness.use_compile_cache()
        result = harness.execute(cell, args.seed, args.seconds,
                                 bool(args.trace), t_process=T_PROCESS)
    except harness.NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    except Exception:                                   # noqa: BLE001
        traceback.print_exc()
        return 1
    harness.emit(result)
    return 0


if __name__ == "__main__":
    os.chdir(CHECKOUT)
    sys.exit(main())
