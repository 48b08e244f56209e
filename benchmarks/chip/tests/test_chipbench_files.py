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
    a = cells.config_module(entry["name"]).arch(conf)
    assert (a.d_model, a.n_layers, a.vocab) == (
        conf["hidden_size"], conf["num_hidden_layers"], conf["vocab_size"])
    assert {"overrides", "config"} <= set(conf["smoke"])


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
    shutil.copy(bench_dir / "configs" / "minicpm3-4b.py",
                bench_dir / "configs" / "minicpm3-4b-half.py")
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
    assert cell.arch.n_layers == 31
    assert cell.traffic["params"]["rate"] == 0.25
    assert [m.name for m in cell.per_layer][-1] == "engine.iterations"

    class Run:
        iterations = [1, 2, 3]
    assert cells.read_per_layer([cell.per_layer[-1]], Run(), bench_dir) == {
        "engine.iterations": {"value": 3, "unit": "1"}}
    assert all(p.read_bytes() == b for p, b in before.items())


#: a configuration's ``.py`` that exists only in the test: GQA with one
#: fused query-key-value product (neither committed file's layer), every
#: answer counted
PROBE_PY = '''
import collections
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R

CALLS = collections.Counter()


def counted(fn):
    @functools.wraps(fn)
    def wrapped(*a, **k):
        CALLS[fn.__name__] += 1
        return fn(*a, **k)
    return wrapped


@dataclass(frozen=True)
class Fused(R.Arch):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float

    @counted
    def layer(self, lp, x, i, quant=None):
        return _layer(lp, x, a=self, quant=quant)

    @counted
    def layer_weights(self, i):
        d, hd = self.d_model, self.head_dim
        return (2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                + 3 * d * self.d_ff)

    @counted
    def layer_attention(self, i, ctx):
        return int(4 * self.n_heads * self.head_dim
                   * np.sum(np.asarray(ctx, np.int64)))

    @counted
    def module_params(self):
        d, q, kv = (self.d_model, self.n_heads * self.head_dim,
                    self.n_kv_heads * self.head_dim)
        return tuple(sorted({"q/w": (d, q), "k/w": (d, kv), "v/w": (d, kv),
                             "o/w": (q, d)}.items()))

    @counted
    def module_points(self, sweep):
        return ({("prefill", t, r, max(c, 1, t)) for t in sweep.toks
                 for r in sweep.reqs for c in (0,) + tuple(sweep.ctx)}
                | {("decode", r, c) for r in sweep.reqs for c in sweep.ctx})

    @counted
    def module_point(self, sweep, params, ins, window):
        if params != self.module_params() or window or len(ins) != 4:
            return False, None
        (r, t, _), k, _, _ = ins
        if t == 1:
            return k[1] in sweep.ctx, ("decode", r, k[1])
        return True, ("prefill", t, r, k[1])

    @counted
    def op_widths(self):
        return {self.d_model, self.d_ff, self.vocab}

    @counted
    def module_reference(self, args, window):
        p, x, k_cache, v_cache, lengths = args
        rows = []
        for b in range(x.shape[0]):
            n, s = lengths[b], k_cache.shape[1]
            qpos = n + jnp.arange(x.shape[1])
            q, k, v = qkv(self, p, x[b].astype(jnp.float32), qpos, None)
            slot = qpos % s
            kc = k_cache[b].astype(jnp.float32).at[slot].set(k)
            vc = v_cache[b].astype(jnp.float32).at[slot].set(v)
            kpos = jnp.arange(s)
            o = R.attend(q, kc, vc, qpos, kpos, kpos >= 0, 0)
            rows.append(R.mm(o.reshape(x.shape[1], -1), p["o"]["w"]))
        return jnp.stack(rows)


def arch(c):
    return Fused(**R.arch_sizes(c), n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                 d_ff=c["intermediate_size"], rope_theta=c["rope_theta"])


def qkv(a, p, h, pos, quant):
    s, hd = h.shape[0], a.head_dim
    w = jnp.concatenate([p["q"]["w"], p["k"]["w"], p["v"]["w"]], 1)
    q, k, v = jnp.split(R.mm(h, w, quant), [a.n_heads * hd,
                                            (a.n_heads + a.n_kv_heads) * hd], 1)
    q = R.rope(q.reshape(s, a.n_heads, hd), pos, a.rope_theta)
    k = R.rope(k.reshape(s, a.n_kv_heads, hd), pos, a.rope_theta)
    return q, k, v.reshape(s, a.n_kv_heads, hd)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _layer(lp, x, *, a, quant):
    s = x.shape[0]
    pos = jnp.arange(s)

    def attention(h):
        q, k, v = qkv(a, lp["attn"], h, pos, quant)
        o = R.attend(q, k, v, pos, pos, jnp.ones((s,), bool), 0)
        return R.mm(o.reshape(s, -1), lp["attn"]["o"]["w"], quant)
    return R.prenorm_block(lp, x, attention, a.eps, quant)
'''


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
def test_a_configuration_added_as_files_alone_runs_both_drivers(tmp_path):
    """A configuration that exists only as new files (its JSON and its
    ``.py``) and ``BENCHMARK.json`` entries runs the serve and the profile
    driver at smoke width with ``correct`` true, and every reference, FLOP
    and shape question of both runs is answered by its ``.py``."""
    from _chipbench_smoke import run, smoke_cell
    from repro.configs import get_config
    bench_dir = tmp_path / "chip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    z = get_config("yi-9b")
    sizes = {"hidden_size": 128, "num_hidden_layers": 3,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 32, "intermediate_size": 384, "vocab_size": 384}
    conf = json.loads((bench_dir / "configs" / "command-r7b.json")
                      .read_text())
    conf.update(name="probe", zoo="yi-9b", overrides={}, reduced=[],
                hidden_size=z.d_model, num_hidden_layers=z.n_layers,
                num_attention_heads=z.n_heads, num_key_value_heads=z.n_kv_heads,
                head_dim=z.resolved_head_dim, intermediate_size=z.d_ff,
                vocab_size=z.vocab_size, rms_norm_eps=z.norm_eps,
                rope_theta=z.rope_theta, tie_word_embeddings=False,
                smoke={"overrides": {"n_layers": 3, "d_model": 128,
                                     "n_heads": 4, "n_kv_heads": 2,
                                     "head_dim": 32, "d_ff": 384,
                                     "vocab_size": 384},
                       "config": sizes})
    for key in ("attention", "sliding_window", "sliding_window_pattern",
                "assumed", "deployment"):
        conf.pop(key)
    (bench_dir / "configs" / "probe.json").write_text(json.dumps(conf))
    (bench_dir / "configs" / "probe.py").write_text(PROBE_PY)
    bench = json.loads(json.dumps(BENCHMARK))
    for traffic in ("chat", "profile"):
        bench["workloads"].append({"name": f"probe.{traffic}",
                                   "config": "probe", "traffic": traffic,
                                   "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        wl = m.get("workloads", [])
        for traffic in ("chat", "profile"):
            if f"minicpm3-4b.{traffic}" in wl:
                wl.append(f"probe.{traffic}")

    serve_cell = smoke_cell("probe.chat", bench, bench_dir)
    probe = serve_cell.model
    assert probe.__file__ == str(bench_dir / "configs" / "probe.py")
    assert type(serve_cell.arch).__name__ == "Fused"
    res = run(serve_cell, seconds=3.0, traced=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["step_mfu.chat"]["value"] > 0
    assert probe.CALLS["layer"] >= 3
    assert probe.CALLS["layer_weights"] == 3     # once, then cached
    assert probe.CALLS["layer_attention"] > 0

    res = run(smoke_cell("probe.profile", bench, bench_dir))
    assert res["correct"], res["checks"]
    assert res["checks"]["calls_off_plan"]["value"] == 0
    assert res["checks"]["calls_compared"]["value"] >= 4
    for name in ("module_params", "module_points", "module_point",
                 "op_widths", "module_reference"):
        assert probe.CALLS[name] > 0, name
