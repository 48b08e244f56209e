"""Smoke-width cells for the harness's CPU tests: the committed cells with
their configurations cut to a few narrow layers and their traffic to a few
short requests.  Layouts, drivers and the comparison stay the cells' own.
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

SMOKE = {
    "minicpm3-4b": (
        {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 32, "d_ff": 256, "vocab_size": 384,
         "mla": {"q_lora_rank": 48, "kv_lora_rank": 32,
                 "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                 "v_head_dim": 16}},
        {"hidden_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
         "intermediate_size": 256, "vocab_size": 384, "q_lora_rank": 48,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16}),
    # the window equals the cache length, as at full size (4096 and 4096)
    "command-r7b": (
        {"n_layers": 4, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 384, "vocab_size": 512,
         "sliding_window": 256},
        {"hidden_size": 128, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 384, "vocab_size": 512,
         "sliding_window": 256}),
}


def smoke_cell(name: str) -> cells.Cell:
    cell = cells.find_cell(cells.load_benchmark(), name)
    cfg = copy.deepcopy(cell.config)
    overrides, sizes = SMOKE[cfg["name"]]
    cfg["overrides"] = dict(overrides)
    cfg.update(sizes)
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
                      cell.per_layer)


def run(cell, seconds=2.0, traced=False, seed=2**33 + 5):
    """One run with the harness's look for a chip skipped."""
    import time
    return harness.execute(cell, seed, seconds, traced,
                           t_process=time.perf_counter(), platform=None,
                           peaks=CPU_PEAKS)
