"""Chip smoke run: profile -> serve -> simulate on one TPU at full width.

    python chip_smoke.py

Five phases run in one process (a TPU belongs to one process at a time),
each building what it needs from a seed — random weights, traffic from
the ``repro.workload`` generators:

1. device   — JAX must report a TPU; anything else exits 1.
2. kernels  — the three Pallas kernels, compiled natively at zoo-model
              widths in bf16 (``tpu_custom_call`` in the compiled text),
              agree with ``kernels/ref.py``.
3. profile  — ``ProfileStore.plan`` + ``execute`` of minicpm3-4b into an
              empty latency DB with the wall-clock oracle, rows labelled
              with the device's ``device_kind``; nothing may quarantine,
              and a re-plan on the same DB must find 0 tasks to measure.
4. serve    — ``serving.Engine`` at full width serves a calibration trace,
              then (second engine, same weights) a seeded ShareGPT-like
              trace; every request completes, and ``prefill_chunk``'s
              first-token logits agree with ``Model.forward``.
5. simulate — ``DoolySim`` over the phase-3 fits (not degraded),
              calibrated on phase 4's calibration records, predicts the
              same trace; its TTFT/TPOT and MAPE against the engine are
              printed, not gated.

The last stdout line is ``{"ok": true, "device": {...}}``.  Any failed
phase exits 1 and prints no such line.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
sys.path.insert(0, SRC)

try:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ProfileStore
    from repro.configs import get_config
    from repro.core.profiler import SweepConfig
    from repro.kernels import ops, ref
    from repro.models import build_model
    from repro.runtime import use_compile_cache
    from repro.serving.engine import Engine, bucket_chunk
    from repro.serving.scheduler import SchedulerConfig
    from repro.sim import metrics as M
    from repro.workload import sharegpt_like, synthetic
except ImportError as e:
    sys.exit(f"chip_smoke.py: cannot import the repro package from {SRC} "
             f"({e}); run it from a checkout of the repository")

MODEL = "minicpm3-4b"
SEED = 0
MAX_SEQ = 4096
SCHED = SchedulerConfig(max_num_seqs=8, max_batch_tokens=512, chunk_size=256)
#: covers what phase 4 runs: chunk buckets 8..256 on one row, decode of 8
#: rows, contexts up to MAX_SEQ (the simulator prices calls at MAX_SEQ)
PROFILE_SWEEP = SweepConfig(toks=(8, 64, 256), reqs=(1, 8),
                            ctx=(1024, MAX_SEQ),
                            op_points=((1, 8), (8, 1), (64, 1), (256, 1)))
N_REQUESTS = 16
TRACE_SCALE = 0.25          # ShareGPT lengths x 0.25: prompts ~240 tokens
#: bf16 agreement bound on max|got - want| / max|want|
BF16_TOL = 2e-2
#: depth of the prefill-vs-forward logits check (periods of the stack)
LOGITS_PERIODS = 4


class SmokeFailure(RuntimeError):
    """A phase ran but its result is wrong."""


def _rel_err(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


def _p(x, q) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}")
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX found only {dev['platform']} "
                           "devices; this run measures the chip")
    return dev


# ---------------------------------------------------------------------------
# 2. kernels
# ---------------------------------------------------------------------------

def kernel_cases(get=get_config, *, seq=2048, smax=4096, scan_len=256):
    """(name, kernel, reference, args) at the widths of three zoo models:
    command-r7b's 32/8/128 GQA (causal flash, decode of 8 rows against
    ``smax`` slots), hymba-1.5b's 25/5/64 sliding window, and
    falcon-mamba-7b's selective scan (d_inner 8192, state 16)."""
    cr, hy, fm = (get(n) for n in ("command-r7b", "hymba-1.5b",
                                   "falcon-mamba-7b"))
    keys = iter(jax.random.split(jax.random.key(SEED), 32))
    bf = jnp.bfloat16

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def flash(cfg, window):
        hd = cfg.resolved_head_dim
        args = (normal((1, seq, cfg.n_heads, hd)),
                normal((1, seq, cfg.n_kv_heads, hd)),
                normal((1, seq, cfg.n_kv_heads, hd)))
        return (functools.partial(ops.flash_attention, causal=True,
                                  window=window),
                functools.partial(ref.attention, causal=True, window=window),
                args)

    hd = cr.resolved_head_dim
    decode_args = (normal((8, 1, cr.n_heads, hd)),
                   normal((8, smax, cr.n_kv_heads, hd)),
                   normal((8, smax, cr.n_kv_heads, hd)),
                   jax.random.randint(next(keys), (8,), 1, smax + 1))
    di, n = fm.ssm_d_inner, fm.ssm_state
    scan_args = (normal((1, scan_len, di)),
                 jax.nn.softplus(normal((1, scan_len, di), jnp.float32)
                                 ).astype(bf),
                 -jnp.exp(normal((di, n), jnp.float32) * 0.3),
                 normal((1, scan_len, n)), normal((1, scan_len, n)),
                 normal((di,), jnp.float32),
                 normal((1, di, n), jnp.float32))
    return [
        (f"flash causal {cr.n_heads}/{cr.n_kv_heads}/{hd} S={seq}",
         *flash(cr, 0)),
        (f"flash window={hy.sliding_window} {hy.n_heads}/{hy.n_kv_heads}/"
         f"{hy.resolved_head_dim} S={seq}", *flash(hy, hy.sliding_window)),
        (f"decode B=8 {cr.n_heads}/{cr.n_kv_heads}/{hd} slots={smax}",
         ops.decode_attention, ref.decode_attention, decode_args),
        (f"mamba scan d_inner={di} state={n} S={scan_len}",
         ops.selective_scan, ref.selective_scan, scan_args),
    ]


def phase_kernels(cases) -> dict:
    """Each kernel against its reference.  Off the CPU backend the
    compiled program must also hold the Mosaic kernel: interpret mode is
    for the CPU only."""
    native = jax.default_backend() != "cpu"
    errs = {}
    for name, kernel, reference, args in cases:
        compiled = jax.jit(kernel).lower(*args).compile()
        custom = "tpu_custom_call" in compiled.as_text()
        got = jax.tree.leaves(compiled(*args))
        want = jax.tree.leaves(jax.jit(reference)(*args))
        err = max(_rel_err(g, w) for g, w in zip(got, want))
        errs[name] = err
        print(f"[kernels] {name}: rel err {err:.3e} "
              f"tpu_custom_call={custom}")
        if native and not custom:
            raise SmokeFailure(f"{name}: no tpu_custom_call in the compiled "
                               "program (interpret mode taken)")
        if not err <= BF16_TOL:
            raise SmokeFailure(f"{name}: rel err {err:.3e} > {BF16_TOL}")
    return errs


# ---------------------------------------------------------------------------
# 3. profile
# ---------------------------------------------------------------------------

def phase_profile(store: ProfileStore, cfg) -> dict:
    t0 = time.perf_counter()
    plan = store.plan([cfg], backends=("xla",))
    plan_s = time.perf_counter() - t0
    cov = plan.coverage()
    print(f"[profile] {cfg.name} on {store.hardware!r}: plan "
          f"{cov.plan_tasks} tasks / {cov.plan_points} points "
          f"({plan_s:.1f}s to trace and plan)")
    rep = store.execute(plan, workers=1, fail_fast=True)
    print(f"[profile] measured {rep.measured} tasks / {rep.rows_written} "
          f"points in {rep.elapsed_s:.1f}s; quarantined {rep.quarantined} "
          f"(+{rep.skipped_quarantined} from a journal)")
    if rep.quarantined or rep.skipped_quarantined:
        raise SmokeFailure(f"quarantined tasks: {rep.quarantine}")
    replan = store.plan([cfg], backends=("xla",))
    print(f"[profile] re-plan on the same DB: {len(replan.todo)} tasks to "
          f"measure, {replan.coverage().satisfied_tasks} satisfied")
    if replan.todo:
        raise SmokeFailure(f"re-plan still has {len(replan.todo)} tasks")
    return {"tasks": rep.measured, "points": rep.rows_written,
            "measure_s": rep.elapsed_s, "plan_s": plan_s,
            "replan_tasks": len(replan.todo)}


# ---------------------------------------------------------------------------
# 4. serve
# ---------------------------------------------------------------------------

def calibration_trace(cfg, sched):
    """Prompts that exercise every chunk bucket, with decode between."""
    return synthetic(4, rate=1.0, prompt_len=sched.chunk_size + 40,
                     out_len=24, seed=SEED + 1, vocab=cfg.vocab_size)


def serving_trace(cfg, max_seq, *, n=N_REQUESTS, scale=TRACE_SCALE):
    reqs = sharegpt_like(n, rate=2.0, seed=SEED + 2, scale=scale,
                         vocab=cfg.vocab_size)
    longest = max(r.prompt_len + r.max_new_tokens for r in reqs)
    if longest > max_seq:
        raise SmokeFailure(f"trace needs {longest} cache slots > {max_seq}")
    return reqs


def check_prefill_logits(model, params, sched, max_seq) -> float:
    """First-token logits of one prompt: the engine's chunked prefill path
    (one bucketed, padded chunk into an empty cache row) against the
    full-sequence ``Model.forward``, both on the engine's own weights cut
    to the first LOGITS_PERIODS periods of the stack.  The cut keeps the
    check a bf16 one: the two paths round at different points, and with
    random weights that difference grows with depth (relative error 0.6%
    at 4 layers of minicpm3-4b's width, 2-4.5% at 62 layers of widths
    128-512; 6e-6 in float32 at 62 layers)."""
    periods = min(LOGITS_PERIODS, model.n_periods)
    cut = build_model(model.cfg.with_overrides(
        n_layers=periods * len(model.pattern)))
    cut_params = dict(params, blocks=jax.tree.map(lambda a: a[:periods],
                                                  params["blocks"]))
    prompt_len = min(100, sched.chunk_size)
    toks = jax.random.randint(jax.random.key(SEED + 3), (1, prompt_len), 0,
                              cut.cfg.vocab_size)
    b = bucket_chunk(prompt_len, sched.chunk_size)
    padded = jnp.pad(toks, ((0, 0), (0, b - prompt_len)))
    chunk_logits, _ = jax.jit(functools.partial(
        cut.prefill_chunk, impl="xla"))(
        cut_params, cut.zero_cache(1, max_seq, use_ring=False), padded,
        jnp.zeros((1,), jnp.int32),
        last_pos=jnp.full((1,), prompt_len - 1, jnp.int32))
    full_logits, _ = jax.jit(functools.partial(cut.forward, impl="xla"))(
        cut_params, {"tokens": toks})
    return _rel_err(chunk_logits[0], full_logits[0, -1])


def _engine_metrics(requests) -> dict:
    bad = [r.rid for r in requests
           if not r.done or r.generated != r.max_new_tokens]
    if bad:
        raise SmokeFailure(f"requests {bad} did not finish their outputs")
    return M.request_metrics(requests)


def phase_serve(cfg, sched=SCHED, max_seq=MAX_SEQ, **trace_kw) -> dict:
    t0 = time.perf_counter()
    eng = Engine(cfg, sched_config=sched, max_seq=max_seq, impl="xla",
                 seed=SEED)
    print(f"[serve] engine up in {time.perf_counter() - t0:.1f}s "
          f"({cfg.param_count() / 1e9:.2f} B params, max_num_seqs="
          f"{sched.max_num_seqs}, chunk={sched.chunk_size}, "
          f"max_seq={max_seq})")
    err = check_prefill_logits(eng.model, eng.params, sched, max_seq)
    print(f"[serve] prefill_chunk vs Model.forward first-token logits, "
          f"stack cut to {LOGITS_PERIODS} periods: rel err {err:.3e}")
    if not err <= BF16_TOL:
        raise SmokeFailure(f"prefill logits rel err {err:.3e} > {BF16_TOL}")
    calib = calibration_trace(cfg, sched)
    eng.run(calib)
    _engine_metrics(calib)
    calib_records, params = eng.records, eng.params
    # two copies of the weights do not fit: the second engine shares
    # them, and the first one's cache goes before the second allocates
    # (its jitted closures hold it in a cycle, hence the collect)
    del eng
    gc.collect()
    eng = Engine(cfg, sched_config=sched, max_seq=max_seq, impl="xla",
                 params=params)
    reqs = serving_trace(cfg, max_seq, **trace_kw)
    t0 = time.perf_counter()
    out = eng.run(reqs)
    wall_s = time.perf_counter() - t0
    real = _engine_metrics(reqs)
    n_tok = sum(r.max_new_tokens for r in reqs)
    print(f"[serve] {len(reqs)} requests, {n_tok} output tokens, "
          f"{len(out['iterations'])} iterations, engine clock "
          f"{out['makespan']:.2f}s, wall {wall_s:.2f}s")
    print(f"[serve] engine TTFT p50 {_p(real['ttft'], 50):.4f}s p90 "
          f"{_p(real['ttft'], 90):.4f}s | TPOT p50 {_p(real['tpot'], 50):.4f}s"
          f" p90 {_p(real['tpot'], 90):.4f}s")
    return {"calib_records": calib_records, "metrics": real,
            "logits_err": err}


# ---------------------------------------------------------------------------
# 5. simulate
# ---------------------------------------------------------------------------

def phase_simulate(store: ProfileStore, cfg, served: dict, sched=SCHED,
                   max_seq=MAX_SEQ, **trace_kw) -> dict:
    sim = store.simulator(cfg, sched_config=sched, max_seq=max_seq)
    be = sim.latency
    unmeasured = be.unprofiled_sigs()
    if not be.rows or unmeasured:
        raise SmokeFailure(
            f"simulator degraded: {len(be.rows)} call-graph rows, "
            f"{len(unmeasured)} signatures without measurements")
    cal = sim.calibrate(served["calib_records"])
    print("[simulate] calibration: " + ", ".join(
        f"{k}={v:.3e}" for k, v in cal.items()))
    res = sim.run(serving_trace(cfg, max_seq, **trace_kw))
    pred = M.request_metrics(res["requests"])
    err = M.compare(pred, served["metrics"])
    print(f"[simulate] {len(be.rows)} call-graph rows, engine="
          f"{res['engine']}; sim TTFT p50 {_p(pred['ttft'], 50):.4f}s "
          f"p90 {_p(pred['ttft'], 90):.4f}s | TPOT p50 "
          f"{_p(pred['tpot'], 50):.4f}s p90 {_p(pred['tpot'], 90):.4f}s")
    print("[simulate] MAPE vs engine (printed, not gated): " + ", ".join(
        f"{k}={err[k]:.1f}%" for k in ("ttft_p50_mape", "ttft_p90_mape",
                                       "tpot_p50_mape", "tpot_p90_mape")))
    return err


# ---------------------------------------------------------------------------

def main() -> int:
    timings = {}

    def run(name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        timings[name] = time.perf_counter() - t0
        print(f"[{name}] done in {timings[name]:.1f}s", flush=True)
        return out

    try:
        dev = run("device", phase_device)
        cache = use_compile_cache()
        print(f"[device] compile cache: "
              f"{cache or os.environ['JAX_COMPILATION_CACHE_DIR']}")
        run("kernels", phase_kernels, kernel_cases())
        cfg = get_config(MODEL)
        with ProfileStore(":memory:", hardware=dev["kind"],
                          oracle="cpu_wallclock",
                          sweep=PROFILE_SWEEP) as store:
            run("profile", phase_profile, store, cfg)
            served = run("serve", phase_serve, cfg)
            run("simulate", phase_simulate, store, cfg, served)
    except Exception:                                   # noqa: BLE001
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print("[timings] " + ", ".join(f"{k}={v:.1f}s"
                                   for k, v in timings.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
