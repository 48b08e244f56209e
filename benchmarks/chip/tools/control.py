"""The control of ``correct``: the reference itself in the program's place,
computed in the next precision below the configuration's.

    python3 benchmarks/chip/tools/control.py --workload <cell> \\
        --seeds 1 2 3 [--precision fp8] [--candidates 12]

Serve cells: for each seed, the requests a run would judge (the longest
and ``check_requests`` more, drawn from the seed among the ``--candidates``
first to arrive, served to their full length) are run through the
float32 reference and through the reference on lowered operands; the
number printed is the widest gap by which the token the lowered reference
puts first lies below the float32 reference's best.

Profile cells: for each seed, one pass of the plan with the run's seeded
sample of timed calls; each call is recomputed by the reference on
lowered operands (bfloat16 calls in ``--precision``, float32 calls in
bfloat16), and the number printed is the widest relative error per
precision class.  Needs the chip; prints one JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def serve_control(cell, seeds, precision, candidates):
    import numpy as np
    from chipbench import reference, traffic, weights
    from chipbench.cells import model_config
    from chipbench.serve import _to_request, decode_inputs, sample
    cfg = model_config(cell.config)
    a = cell.arch
    for seed in seeds:
        t0 = time.time()
        items = traffic.requests(cell.traffic["params"], seed,
                                 cfg.vocab_size)
        reqs = sorted((_to_request(i) for i in items),
                      key=lambda r: r.arrival)[:candidates]
        for r in reqs:
            r.generated = r.max_new_tokens
        chosen = sample(reqs, int(cell.traffic["check_requests"]), seed)
        seqs = [np.concatenate([np.asarray(r.prompt, np.int32),
                                np.asarray(decode_inputs(r.generated),
                                           np.int32)]) for r in chosen]
        starts = [r.prompt_len - 1 for r in chosen]
        toks = [np.zeros(r.generated, np.int32) for r in chosen]
        params = weights.make_params(cfg, seed)
        out = reference.served_gaps(a, params, seqs, starts, toks,
                                    control=precision)
        del params
        print(json.dumps({"seed": seed, "precision": precision,
                          "control_logit_gap": float(out["control_gap"].max()),
                          "tokens": int(len(out["control_gap"])),
                          "seconds": time.time() - t0}), flush=True)


def profile_control(cell, seeds, precision):
    import jax
    from chipbench import profile as P
    from chipbench.cells import model_config
    from repro.api import ProfileStore
    cfg = model_config(cell.config)
    sweep = P.sweep_config(cell.traffic)
    hw = jax.devices()[0].device_kind
    with ProfileStore(":memory:", hardware=hw, oracle=P.ORACLE,
                      sweep=sweep) as store:
        plan = store.plan([cfg], backends=("xla",))
    a = cell.arch
    low = {"bf16": precision, "f32": "bf16"}
    for seed in seeds:
        sample = P.CallSample(seed, dict(cell.traffic["check_calls"]))
        P.reset_caches()
        with sample:
            P.one_pass(plan, hw, sweep)
        errs = {"bf16": 0.0, "f32": 0.0}
        prog = {"bf16": 0.0, "f32": 0.0}
        for fn, args, window in sample.calls():
            c = P.call_error(a, fn, args, window, control=low)
            g = P.call_error(a, fn, args, window)
            if c is not None:
                errs[c[0]] = max(errs[c[0]], c[1])
                prog[g[0]] = max(prog[g[0]], g[1])
        print(json.dumps({"seed": seed, "precision": low,
                          "control_op_err": errs, "program_op_err": prog}),
              flush=True)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--precision", default="fp8")
    p.add_argument("--candidates", type=int, default=12)
    a = p.parse_args(argv)
    from chipbench import cells, harness
    harness.use_compile_cache()
    cell = cells.find_cell(cells.load_benchmark(), a.workload)
    harness.device_info(cell.chips)
    if cell.driver == "serve":
        serve_control(cell, a.seeds, a.precision, a.candidates)
    else:
        profile_control(cell, a.seeds, a.precision)
    return 0


if __name__ == "__main__":
    sys.exit(main())
