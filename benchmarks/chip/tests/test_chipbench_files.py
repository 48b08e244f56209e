"""Every file a cell is built from loads, the configuration files match the
program's zoo, and a later cell can be added as files alone."""
import json
import shutil

import pytest

from _chipbench_smoke import BENCH, cells

BENCHMARK = cells.load_benchmark()


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=lambda e: e["name"])
def test_config_file_matches_the_zoo(entry):
    conf = cells.load_json(cells.CHECKOUT / entry["file"])
    cfg = cells.model_config(conf)
    assert conf["name"] == entry["name"] and conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    assert cfg.d_model == conf["hidden_size"]
    assert cfg.n_layers == conf["num_hidden_layers"]
    assert cfg.n_heads == conf["num_attention_heads"]
    assert cfg.n_kv_heads == conf["num_key_value_heads"]
    assert cfg.resolved_head_dim == conf["head_dim"]
    assert cfg.d_ff == conf["intermediate_size"]
    assert cfg.vocab_size == conf["vocab_size"]
    assert cfg.norm_eps == conf["rms_norm_eps"]
    assert cfg.rope_theta == conf["rope_theta"]
    assert cfg.tie_embeddings == conf["tie_word_embeddings"]
    assert cfg.sliding_window == conf["sliding_window"]
    assert cfg.swa_interleave == conf["sliding_window_pattern"]
    assert cfg.dtype == conf["torch_dtype"]
    assert (cfg.attn_type == "mla") == (conf["attention"] == "mla")
    if cfg.mla is not None:
        m = cfg.mla
        assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
                m.qk_rope_head_dim, m.v_head_dim) == (
            conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"])
    for key in ("max_num_seqs", "max_seq", "chunk_size", "max_batch_tokens",
                "prefix_caching"):
        assert key in conf["engine"]
    assert {"serve", "profile"} <= set(conf["limits"])


@pytest.mark.parametrize("w", BENCHMARK["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_with_its_metrics(w):
    cell = cells.find_cell(BENCHMARK, w["name"])
    assert cell.driver in ("serve", "profile")
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m.moves in names
        assert callable(cells.metric_reader(m.name))


def test_every_metric_has_a_reader_and_peaks_name_the_chip():
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    peaks = cells.load_json(BENCH / "peaks.json")
    assert peaks["devices"]["TPU v5 lite"]["bf16_flops"] == 197e12
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_a_cell_added_as_files_alone_loads(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus BENCHMARK.json entries, with no existing file edited."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    conf = json.loads((bench_dir / "configs" / "minicpm3-4b.json")
                      .read_text())
    conf["name"] = "minicpm3-4b-half"
    conf["overrides"] = {"n_layers": 31}
    conf["num_hidden_layers"] = 31
    (bench_dir / "configs" / "minicpm3-4b-half.json").write_text(
        json.dumps(conf))
    tr = json.loads((bench_dir / "traffic" / "chat.json").read_text())
    tr["params"]["rate"] = 0.25
    (bench_dir / "traffic" / "chat-slow.json").write_text(json.dumps(tr))
    (bench_dir / "metrics" / "engine.iterations.py").write_text(
        "def read(run):\n    return len(run.iterations) or None\n")
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "minicpm3-4b-half.chat-slow",
                               "config": "minicpm3-4b-half",
                               "traffic": "chat-slow", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p50_s", "itl_p99_s"):
            m["workloads"].append("minicpm3-4b-half.chat-slow")
    bench["per_layer"].append({
        "name": "engine.iterations", "unit": "1", "better": "higher",
        "source": "program_span", "layer": "engine", "moves": "itl_p99_s",
        "workloads": ["minicpm3-4b-half.chat-slow"]})
    cell = cells.find_cell(bench, "minicpm3-4b-half.chat-slow", bench_dir)
    assert cells.model_config(cell.config).n_layers == 31
    assert cell.traffic["params"]["rate"] == 0.25
    assert [m.name for m in cell.per_layer][-1] == "engine.iterations"

    class Run:
        iterations = [1, 2, 3]
    assert cells.read_per_layer([cell.per_layer[-1]], Run(), bench_dir) == {
        "engine.iterations": {"value": 3, "unit": "1"}}
    assert all(p.read_bytes() == b for p, b in before.items())
