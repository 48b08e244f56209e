"""The cell ``jamba2-3b.long-doc`` at smoke width on the CPU: ``correct``
on the program, not ``correct`` with each fault injected underneath;
Jamba's operations and scan bytes counted by hand; its profile pass; the
long-doc traffic and the cell's per-layer readers."""
import json
import math

import numpy as np
import pytest

from _chipbench_smoke import BENCH, cells, run, smoke_cell

BENCHMARK = cells.load_benchmark()
#: a window in which every request of the smoke backlog finishes (in about
#: 2 s on an idle machine), so that the judged requests are the same on a
#: loaded one: which requests a shorter window finishes moves the widest
#: bfloat16 gap of the smoke model between 0.13 and 0.49
WHOLE_BACKLOG_S = 12.0


def test_long_doc_cell_is_correct():
    res = run(smoke_cell("jamba2-3b.long-doc"), seconds=WHOLE_BACKLOG_S)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def _unreset(monkeypatch):
    from repro.models.zoo import Model
    monkeypatch.setattr(Model, "reset_recurrent", lambda self, cache: cache)


def _pads_unmasked(monkeypatch):
    from repro.models import mamba
    orig = mamba.mamba_mixer
    monkeypatch.setattr(mamba, "mamba_mixer",
                        lambda *a, n_valid=None, **k: orig(*a, **k))


def _fp8_reference(monkeypatch):
    """Each judged request's tokens replaced by those the reference puts
    first with every matrix product on fp8 operands."""
    from chipbench import reference, serve
    orig = serve.check

    def control(run, served, chosen, params):
        a = run.arch
        served = dict(served)
        seqs = [np.concatenate([np.asarray(r.prompt, np.int32),
                                np.asarray(serve.decode_inputs(r.generated),
                                           np.int32)]) for r in chosen]
        hidden = reference.forward_hidden(a, params, seqs, "fp8")
        for r, h in zip(chosen, hidden):
            rows = h[r.prompt_len - 1:r.prompt_len - 1 + r.generated]
            toks = reference._argmax(reference.head(params, rows, a=a,
                                                    quant="fp8"))
            served[r.rid] = dict(enumerate(np.asarray(toks).tolist()))
        return orig(run, served, chosen, params)
    monkeypatch.setattr(serve, "check", control)


@pytest.mark.parametrize("fault", [_unreset, _pads_unmasked, _fp8_reference],
                         ids=["state_unreset_on_slot_reuse", "pads_unmasked",
                              "fp8_reference"])
def test_long_doc_with_a_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run(smoke_cell("jamba2-3b.long-doc"), seconds=WHOLE_BACKLOG_S)
    assert not res["correct"]
    assert res["checks"]["requests_missing_tokens"]["value"] == 0
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_jamba_operations_by_hand():
    """One Mamba layer, one attention layer and the head, at the published
    widths."""
    from chipbench import flops
    a = smoke_cell("jamba2-3b.long-doc").model.arch(
        cells.load_json(BENCH / "configs" / "jamba2-3b.json"))
    d, di, n, dtr, ff = 2560, 5120, 16, 160, 8192
    mamba = (d * 2 * di + di * (dtr + 2 * n) + dtr * di + di * d
             + 3 * d * ff)
    attn = d * 20 * 128 + 2 * d * 128 + 20 * 128 * d + 3 * d * ff
    assert a.layer_weights(0) == mamba and a.layer_weights(7) == attn
    assert flops.matmul_params(a) == 26 * mamba + 2 * attn
    scan = 2 * 4 * di + 6 * di * n          # conv taps, then the recurrence
    ctx = np.arange(1, 513)
    assert a.layer_attention(0, ctx) == 512 * scan     # no context term
    assert a.layer_attention(0, 5) == a.layer_attention(0, 30000) == scan
    assert a.layer_attention(21, ctx) == 2 * 2 * 20 * 128 * int(ctx.sum())
    assert flops.head_flops(a) == 2 * d * 65536
    assert flops.token_flops(a, 100) == (2 * (26 * mamba + 2 * attn)
                                         + 26 * scan + 2 * 4 * 20 * 128 * 100)


def test_scan_bytes_by_hand():
    model = cells.config_module("jamba2-3b")
    a = model.arch(cells.load_json(BENCH / "configs" / "jamba2-3b.json"))
    di, n = 5120, 16
    x = dt = y = 512 * di
    assert model.scan_bytes(a, 1, 512) == (2 * x + 4 * dt + 2 * y
                                           + 2 * 2 * 512 * n
                                           + 2 * 4 * di * n)
    assert model.scan_bytes(a, 8, 512) == 8 * model.scan_bytes(a, 1, 512)


def test_profile_pass_of_jamba_times_its_mamba_module_without_context():
    """The profile driver on a workload entry of this test's own: the plan
    of the smoke configuration runs with nothing quarantined, every call
    on the plan (a Mamba call's shapes name no context) and every module
    call matching its reference."""
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "jamba2-3b.profile",
                               "config": "jamba2-3b", "traffic": "profile",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "minicpm3-4b.profile" in m.get("workloads", []):
            m["workloads"].append("jamba2-3b.profile")
    cell = smoke_cell("jamba2-3b.profile", bench)
    res = run(cell)
    assert res["correct"], res["checks"]
    checks = {k: v["value"] for k, v in res["checks"].items()}
    assert checks["quarantined"] == checks["rows_missing"] == 0
    assert checks["calls_off_plan"] == checks["points_not_timed"] == 0
    from repro.core.profiler import SweepConfig
    sweep = SweepConfig(toks=(8,), reqs=(1, 2), ctx=(64,), op_points=((8, 1),))
    points = cell.arch.module_points(sweep)
    assert {p for p in points if p[0] == "mamba"} == {
        ("mamba", "prefill", 8, 1), ("mamba", "prefill", 8, 2),
        ("mamba", "decode", 1), ("mamba", "decode", 2)}


def test_long_doc_traffic_draws_its_stated_medians():
    from chipbench import traffic
    tr = cells.load_json(BENCH / "traffic" / "long-doc.json")
    p = tr["params"]
    prompts, outs, gaps = traffic.shape(p)
    assert len(prompts) == 256 and not gaps.any()
    assert abs(np.median(prompts) / p["prompt_median"] - 1) < 0.05
    assert abs(np.median(outs) / p["output_median"] - 1) < 0.05
    assert (prompts + outs).max() <= p["max_total"]
    assert outs.max() <= p["max_output"]
    sigma = traffic.lognormal_sigma(p["prompt_median"], p["prompt_mean"])
    assert abs(sigma - 0.87) < 0.01


class _Run:
    """What a reader sees of a run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_scan_roofline_reads_the_kernels_ops_in_the_window(monkeypatch):
    """Bytes from each scan op's rows and positions, time from its device
    duration; the op as XLA names it (the fusion around the kernel, its
    stacked state first) or as the kernel's own custom call; an op inside
    another counted once, ops outside the traced window left out."""
    import jax
    model = cells.config_module("jamba2-3b")
    a = model.arch(cells.load_json(BENCH / "configs" / "jamba2-3b.json"))

    class Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    fused = ("%mamba_scan.39 = (f32[2,1,5120,16]{2,3,1,0:T(8,128)}, "
             "bf16[1,512,5120]{2,1,0:T(8,128)(2,1)S(1)}) "
             "fusion(f32[2,1,5120,16]{2,3,1,0} %get-tuple-element.50)")
    kernel = ("%mamba_scan.3 = (bf16[1,512,5120]{2,1,0:T(8,128)(2,1)}, "
              "f32[1,16,5120]{2,1,0:T(8,128)}) custom-call(%a, %b)")
    assert model.scan_call(a, fused) == model.scan_call(a, kernel) == (1, 512)
    assert model.scan_call(a, "%fusion.1 = bf16[1,512,5120] fusion("
                           "bf16[1,512,5120] %mamba_scan.3)") is None
    ms = 1_000_000
    events = {"host": [["chipbench.window", 0, 100 * ms]],
              "device": [["/device:TPU:0", fused, 10 * ms, ms],
                         ["/device:TPU:0", kernel, 10 * ms, ms // 2],
                         ["/device:TPU:0", fused, 20 * ms, ms],
                         ["/device:TPU:0", "%fusion.1 = bf16[8]", 30 * ms, ms],
                         ["/device:TPU:0", fused, 150 * ms, ms]]}
    run_ = _Run(trace_events=events, cell=smoke_cell("jamba2-3b.long-doc"),
                arch=a)
    read = cells.metric_reader("ssm.scan_roofline.long-doc")
    want = 100 * 2 * model.scan_bytes(a, 1, 512) / (2e-3 * 819e9)
    assert read(run_) == pytest.approx(want)
    assert 0 < want < 100
    run_.trace_events = dict(events, device=events["device"][3:4])
    assert read(run_) is None           # no scan op: nothing to read


def test_pad_share_reads_the_engines_buckets():
    from chipbench.serve import Iteration
    cell = smoke_cell("jamba2-3b.long-doc")     # chunk_size 32
    its = [Iteration(0, 1, 0.1, [(0, 0, 32, False), (1, 0, 5, True)], [], 0),
           Iteration(1, 2, 0.1, [(0, 32, 17, True)], [(1, 6, 0)], 2)]
    read = cells.metric_reader("engine.pad_share.long-doc")
    # buckets 32, 8 and 32 for 32, 5 and 17 tokens
    assert read(_Run(cell=cell, iterations=its)) == pytest.approx(
        100 * (0 + 3 + 15) / (32 + 8 + 32))
    assert read(_Run(cell=cell, iterations=[])) is None


def test_pad_share_agrees_with_the_chunk_spans_arguments(tmp_path):
    """The ``bucket`` and ``pad`` arguments of the engine's
    ``engine.prefill_chunk`` spans in a real profiler trace are the
    buckets the reader computes (the harness's trace reader keeps span
    names, not their arguments)."""
    import jax
    from chipbench import serve
    from jax.profiler import ProfileData
    cell = smoke_cell("jamba2-3b.long-doc")
    eng, cap, reqs = serve.setup(cell, 7)
    jax.profiler.start_trace(str(tmp_path))
    iters, _ = serve.serve_loop(eng, cap, reqs[:6], 1.0)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    args = [dict(e.stats) for pl in ProfileData.from_file(str(path)).planes
            for ln in pl.lines for e in ln.events
            if e.name == "dooly.engine.prefill_chunk"]
    chunks = [c for it in iters for c in it.chunks]
    assert len(args) == len(chunks) > 0
    size = cell.config["engine"]["chunk_size"]
    from repro.serving.engine import bucket_chunk
    for a, (_, _, n, _) in zip(args, chunks):
        assert int(a["bucket"]) == bucket_chunk(n, size)
        assert int(a["pad"]) == int(a["bucket"]) - n
    read = cells.metric_reader("engine.pad_share.long-doc")
    pads = sum(int(a["pad"]) for a in args)
    buckets = sum(int(a["bucket"]) for a in args)
    assert read(_Run(cell=cell, iterations=iters)) == pytest.approx(
        100 * pads / buckets)


def test_compiles_in_window_counts_backend_compiles_less_cache_loads():
    from chipbench import harness
    ev = [(harness.BACKEND_COMPILE, 1.0, 5.0),
          (harness.BACKEND_COMPILE, 1.0, 15.0),
          (harness.CACHE_HIT, 0.0, 15.0),
          (harness.BACKEND_COMPILE, 1.0, 16.0),
          (harness.COMPILE_EVENTS[0], 0.5, 17.0)]
    read = cells.metric_reader("engine.compiles_in_window.long-doc")
    assert read(_Run(compile_events=ev, window=(10.0, 20.0))) == 1.0
    assert read(_Run(compile_events=ev[:1], window=(10.0, 20.0))) == 0.0
    assert read(_Run(compile_events=None, window=(10.0, 20.0))) is None


def test_traced_long_doc_run_reads_its_new_metrics():
    """On the CPU: the pad share and the window's compiles (0: every
    bucket warmed), not the device's numbers (no device plane)."""
    cell = smoke_cell("jamba2-3b.long-doc")
    res = run(cell, seconds=3.0, traced=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["engine.compiles_in_window.long-doc"] == 0
    assert 0 < m["engine.pad_share.long-doc"] < 100
    assert "ssm.scan_roofline.long-doc" not in m
    assert m["engine.host_ms_per_iter.batch"] > 0
    assert math.isfinite(m["step_mfu.batch"])
