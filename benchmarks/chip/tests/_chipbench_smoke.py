"""Smoke-width cells for the harness's CPU tests: the committed cells with
their configurations cut to a few narrow layers (each configuration
file's ``smoke``: the zoo overrides and the reference's sizes) and their
traffic to a few short requests.  Layouts, drivers and the comparison stay
the cells' own.
"""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import cells, harness  # noqa: E402

CPU_PEAKS = {"devices": {"cpu": {"bf16_flops": 1e12}}}


def smoke_cell(name: str, bench=None, bench_dir=BENCH) -> cells.Cell:
    cell = cells.find_cell(bench or cells.load_benchmark(), name, bench_dir)
    cfg = copy.deepcopy(cell.config)
    cfg["overrides"] = dict(cfg["smoke"]["overrides"])
    cfg.update(cfg["smoke"]["config"])
    cfg["engine"] = dict(cfg["engine"], max_seq=256, chunk_size=32,
                         max_batch_tokens=64, max_num_seqs=4)
    cfg["limits"]["serve"]["min_tokens_compared"] = 5
    tr = copy.deepcopy(cell.traffic)
    if tr["driver"] == "serve":
        tr["params"].update(max_total=256, max_output=24, prompt_median=40,
                            prompt_mean=60, output_median=6, output_mean=9,
                            n=min(tr["params"]["n"], 40))
        if tr["params"]["rate"] != "backlog":
            tr["params"]["rate"] = 4.0
        tr["check_requests"] = 3
    else:
        tr["sweep"] = {"toks": [8], "reqs": [1, 2], "ctx": [64],
                       "op_points": [[8, 1]]}
    return cells.Cell(cell.name, cell.chips, cfg, tr, cell.end_to_end,
                      cell.per_layer, cell.model)


def run(cell, seconds=2.0, traced=False, seed=2**33 + 5):
    """One run with the harness's look for a chip skipped."""
    import time
    return harness.execute(cell, seed, seconds, traced,
                           t_process=time.perf_counter(), platform=None,
                           peaks=CPU_PEAKS)
