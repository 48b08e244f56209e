"""Find the rate a serve cell's engine sustains: one engine, one window per
offered rate, each from an empty scheduler.

    python3 benchmarks/chip/tools/knee.py --workload minicpm3-4b.chat \\
        --rates 0.3 0.45 0.6 0.75 0.9 --seconds 90 --seed 5

Prints per rate: requests offered and finished, requests still waiting at
the close, TTFT p50 and ITL p95.  Past the knee the waiting queue grows
with the window.  Needs the chip.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=90)
    p.add_argument("--seed", type=int, default=5)
    a = p.parse_args(argv)
    import numpy as np
    from chipbench import cells, harness, serve, traffic
    from repro.serving.scheduler import Scheduler
    harness.use_compile_cache()
    cell = cells.find_cell(cells.load_benchmark(), a.workload)
    harness.device_info(cell.chips)
    eng, cap, _ = serve.setup(cell, a.seed)
    config = eng.sched.config
    for rate in a.rates:
        params = dict(cell.traffic["params"], rate=rate,
                      n=int(rate * a.seconds * 1.5) + 2)
        reqs = [serve._to_request(i) for i in
                traffic.requests(params, a.seed, eng.cfg.vocab_size)]
        eng.sched = Scheduler(config)
        iters, window = serve.serve_loop(eng, cap, reqs, a.seconds)
        cap.log.clear()
        arrived = [r for r in reqs if r.arrival <= window]
        ttft = [r.first_token_t - r.arrival for r in arrived
                if r.first_token_t is not None]
        gaps = np.concatenate([np.diff(r.token_times) for r in arrived
                               if len(r.token_times) > 1] or [np.zeros(1)])
        print(json.dumps({
            "rate": rate, "offered": len(arrived),
            "finished": sum(r.done for r in arrived),
            "waiting_at_close": len(eng.sched.waiting),
            "ttft_p50_s": float(np.median(ttft)) if ttft else None,
            "ttft_max_s": float(max(ttft)) if ttft else None,
            "itl_p95_s": float(np.percentile(gaps, 95)),
            "iterations": len(iters),
            "decode_iter_ms": 1e3 * float(np.median(
                [it.model_s for it in iters if not it.chunks] or [0]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
