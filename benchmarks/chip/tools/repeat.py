"""Run one cell several times, one process per run, and summarise.

    python3 benchmarks/chip/tools/repeat.py --workload <cell> \\
        --seeds 11 12 13 --seconds 45 [--trace 1] [--out <file.jsonl>]

Each run is ``benchmarks/chip/run.py`` in a child process (this process
never touches JAX, so each child can hold the chip).  Every result line,
with the run's exit code and the end of its standard error, goes to
``--out``; the summary gives each metric's median and the spread between
its quartiles (``statistics.quantiles(n=4)``) as a share of the median.
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out", default=None)
    p.add_argument("--timeout", type=float, default=1500)
    a = p.parse_args(argv)
    results = []
    for seed in a.seeds:
        t0 = time.time()
        cmd = [sys.executable, str(RUN), "--workload", a.workload,
               "--seed", str(seed), "--seconds", str(a.seconds),
               "--trace", str(a.trace)]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=a.timeout)
            rc, out, err = proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out if isinstance(out, str) else out.decode()
            err = err if isinstance(err, str) else err.decode()
        lines = [ln for ln in out.splitlines() if ln.strip()]
        try:
            res = json.loads(lines[-1]) if rc == 0 else None
        except (IndexError, ValueError):
            res = None
        rec = {"seed": seed, "rc": rc, "wall_s": time.time() - t0,
               "result": res, "stderr_tail": err[-3000:]}
        results.append(rec)
        if a.out:
            with open(a.out, "a") as fh:
                fh.write(json.dumps(rec) + "\n")
        brief = (json.dumps({k: v["value"] for k, v in
                             res["metrics"].items()}) if res else
                 err[-1500:])
        print(f"seed {seed} rc {rc} wall {rec['wall_s']:.1f}s correct "
              f"{res and res['correct']} {brief}", flush=True)
        if res:
            print("   checks " + json.dumps(
                {k: v["value"] for k, v in res["checks"].items()}),
                flush=True)
    ok = [r["result"] for r in results if r["result"]]
    names = sorted({k for r in ok for k in r["metrics"]})
    for n in names:
        vals = [r["metrics"][n]["value"] for r in ok if n in r["metrics"]]
        print(f"{n}: median {statistics.median(vals):.6g} spread "
              f"{spread(vals):.4f} n={len(vals)} values "
              + " ".join(f"{v:.6g}" for v in vals))
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
