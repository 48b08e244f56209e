"""Serve driver: an open-loop, wall-clock client of ``serving.Engine``.

Set-up builds the engine at the configuration's engine settings with the
benchmark's seeded weights, and warms every program the window uses by
serving a short burst through the same loop.  The window then calls
``Scheduler.schedule`` -> ``Engine.execute`` ->
``Scheduler.complete_iteration`` in the order ``Engine.run`` does, admitting
each request when the wall clock passes its arrival and stamping tokens
with wall seconds since the window opened (``Engine.run`` has no deadline
and stamps a virtual clock).

The engine's step programs are wrapped so that each returned logits array
also yields its argmax on the device: the token the engine serves.  After
the window those tokens are judged against the float32 reference.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference, traffic, weights
from chipbench.cells import Cell, model_config
from chipbench.trace import span

#: seconds of the window a traced run records, centred in it
TRACE_SECONDS = 4.0


@dataclass
class Iteration:
    t_start: float
    t_end: float
    model_s: float
    chunks: List[Tuple[int, int, int, bool]]   # (rid, start, length, emits)
    decodes: List[Tuple[int, int, int]]        # (rid, ctx, slot)
    first_call: int                            # index into Capture.log


@dataclass
class ServeRun:
    cell: Cell
    arch: reference.Arch
    peak_flops: float
    window_s: float = 0.0
    setup_s: float = 0.0
    check_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    iterations: List[Iteration] = field(default_factory=list)
    requests: List[Any] = field(default_factory=list)
    trace_events: Optional[Dict[str, Any]] = None
    checks: Dict[str, Dict[str, float]] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0


class Capture:
    """Records, per call of the engine's step programs, the argmax of the
    logits they return (one tiny device program, no host sync).

    It wraps ``Engine._decode_fn`` and ``Engine._chunk_fns``: the engine
    returns none of the tokens it serves.  ``serve_loop`` relies on one call
    per prefill chunk, in plan order, then one for the decodes, and stops
    the run where an iteration makes another number of calls."""

    def __init__(self, eng):
        self._argmax = jax.jit(lambda lg: jnp.argmax(lg, -1)
                               .astype(jnp.int32))
        self.log: List[jax.Array] = []
        eng._decode_fn = self._wrap(eng._decode_fn)
        eng._chunk_fns = {b: self._wrap(f)
                          for b, f in eng._chunk_fns.items()}

    def _wrap(self, fn):
        def wrapped(*args):
            out = fn(*args)
            self.log.append(self._argmax(out[0]))
            return out
        return wrapped


def _to_request(item):
    from repro.serving.scheduler import Request
    return Request(rid=item.rid, arrival=item.arrival,
                   prompt=item.prompt.tolist(),
                   max_new_tokens=item.max_new_tokens)


def warmup_items(engine_cfg: Dict[str, Any], vocab: int):
    """A burst that fills every cache row and ends prompts in every chunk
    bucket, so each program and each eager op of the loop runs once."""
    rows, chunk = engine_cfg["max_num_seqs"], engine_cfg["chunk_size"]
    buckets = []
    b = 8
    while b <= chunk:
        buckets.append(b)
        b *= 2
    lens = [chunk + buckets[i % len(buckets)] - 3 for i in range(rows)]
    lens += [buckets[i % len(buckets)] for i in range(rows)]
    rng = np.random.default_rng(0)
    return [traffic.Item(i, 0.0, rng.integers(0, vocab, n, dtype=np.int32), 3)
            for i, n in enumerate(lens)]


def serve_loop(eng, cap: Capture, reqs, seconds: float, recorder=None,
               trace_from: float = 0.0, trace_s: float = 0.0):
    """Drive the engine for ``seconds`` of wall time (to the end of the
    iteration that crosses it).  With a ``recorder``, the profiler runs
    from ``trace_from`` for ``trace_s`` seconds of the window.  Returns
    (iterations, window seconds)."""
    sched = eng.sched
    pending = deque(sorted(reqs, key=lambda r: r.arrival))
    iters: List[Iteration] = []
    state = "before" if recorder is not None else "off"
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if state == "before" and now >= trace_from:
            recorder.start()
            state = "on"
        elif state == "on" and now >= trace_from + trace_s:
            recorder.stop()
            state = "off"
        if now >= seconds:
            break
        on = state == "on"
        with span(on, "admit"):
            while pending and pending[0].arrival <= now:
                sched.add_request(pending.popleft())
            plan = sched.schedule()
        if plan.empty:
            wake = min(pending[0].arrival if pending else seconds, seconds)
            if state == "before":
                wake = min(wake, trace_from)
            elif on:
                wake = min(wake, trace_from + trace_s)
            with span(on, "wait_for_arrival"):
                time.sleep(max(0.0, wake - now))
            continue
        chunks = [(c.req.rid, c.start, c.length,
                   c.start + c.length >= c.req.prompt_len)
                  for c in plan.prefills]
        decodes = [(r.rid, r.prompt_len + r.generated, r.slot)
                   for r in plan.decodes]
        first = len(cap.log)
        with span(on, "execute"):
            model_s = eng.execute(plan)
        if len(cap.log) - first != len(chunks) + bool(decodes):
            raise RuntimeError(
                f"Engine.execute made {len(cap.log) - first} step-program "
                f"calls for {len(chunks)} chunks and {len(decodes)} "
                "decodes; the served tokens cannot be read")
        end = time.perf_counter() - t0
        with span(on, "complete"):
            sched.complete_iteration(plan, end)
        iters.append(Iteration(now, end, model_s, chunks, decodes, first))
    window_s = time.perf_counter() - t0
    if state == "on":
        recorder.stop()
    return iters, window_s


def setup(cell: Cell, seed: int):
    """(engine, capture, requests) ready for the window."""
    from repro.serving.engine import Engine
    from repro.serving.scheduler import SchedulerConfig
    cfg = model_config(cell.config)
    e = cell.config["engine"]
    sched = SchedulerConfig(max_num_seqs=e["max_num_seqs"],
                            max_batch_tokens=e["max_batch_tokens"],
                            chunk_size=e["chunk_size"],
                            prefix_caching=e["prefix_caching"])
    params = weights.make_params(cfg, seed)
    eng = Engine(cfg, sched_config=sched, max_seq=e["max_seq"],
                 params=params)
    cap = Capture(eng)
    warm = [_to_request(i) for i in warmup_items(e, cfg.vocab_size)]
    _drain(eng, warm)
    jax.block_until_ready(cap.log)
    cap.log.clear()
    items = traffic.requests(cell.traffic["params"], seed, cfg.vocab_size)
    reqs = [_to_request(i) for i in items]
    return eng, cap, reqs


def _drain(eng, reqs):
    """Serve ``reqs`` to completion with the window's calls."""
    for r in reqs:
        eng.sched.add_request(r)
    while eng.sched.has_work():
        plan = eng.sched.schedule()
        eng.execute(plan)
        eng.sched.complete_iteration(plan, 0.0)


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def end_to_end(run: ServeRun) -> Dict[str, float]:
    end = run.window_s
    out: Dict[str, float] = {}
    ttft = [r.first_token_t - r.arrival for r in run.requests
            if r.first_token_t is not None and r.first_token_t <= end]
    gaps = np.concatenate([np.diff(r.token_times) for r in run.requests
                           if len(r.token_times) > 1] or [np.zeros(0)])
    if ttft:
        out["ttft_p50_s"] = float(np.percentile(ttft, 50))
    if len(gaps):
        out["itl_p99_s"] = float(np.percentile(gaps, 99))
    tokens = sum(sum(c[2] for c in it.chunks)
                 + sum(c[3] for c in it.chunks) + len(it.decodes)
                 for it in run.iterations)
    out["tokens_per_s"] = tokens / end
    return out


def iteration_flops(run: ServeRun, it: Iteration) -> int:
    return flops.iteration_flops(
        run.arch, [(s, n, e) for _, s, n, e in it.chunks],
        [ctx for _, ctx, _ in it.decodes])


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def served_tokens(iters: List[Iteration], log: List[np.ndarray],
                  prompt_len: Dict[int, int]) -> Dict[int, Dict[int, int]]:
    """{rid: {output index: token}} from the captured argmaxes."""
    served: Dict[int, Dict[int, int]] = {}
    for it in iters:
        for k, (rid, start, length, emits) in enumerate(it.chunks):
            if emits:
                served.setdefault(rid, {})[0] = int(log[it.first_call + k][0])
        if it.decodes:
            row = log[it.first_call + len(it.chunks)]
            for rid, ctx, slot in it.decodes:
                served.setdefault(rid, {})[ctx - prompt_len[rid]] = \
                    int(row[slot])
    return served


def decode_inputs(n_out: int) -> List[int]:
    """Token ids the engine feeds its decode steps after the first output
    (``Engine.execute`` replays fixed ids, so the sequence it processes
    does not depend on what it served)."""
    return [1 + (g % 7) for g in range(1, n_out)]


def sample(done, k: int, seed: int):
    """The longest finished request and ``k`` more drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.generated, r.prompt_len, -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), size=min(k, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def check(run: ServeRun, served, chosen, params) -> Dict[str, Dict]:
    limits = run.cell.config["limits"]["serve"]
    missing = 0
    seqs, starts, toks = [], [], []
    for r in chosen:
        got = served.get(r.rid, {})
        if sorted(got) != list(range(r.max_new_tokens)):
            missing += 1
            continue
        seqs.append(np.concatenate([np.asarray(r.prompt, np.int32),
                                    np.asarray(decode_inputs(r.generated),
                                               np.int32)]))
        starts.append(r.prompt_len - 1)
        toks.append(np.asarray([got[g] for g in range(r.generated)]))
    gap = reference.served_gaps(run.arch, params, seqs, starts, toks)["gap"]
    return {
        "logit_gap": {"value": float(gap.max()) if len(gap) else float("inf"),
                      "limit": limits["logit_gap"]},
        "requests_missing_tokens": {"value": missing, "limit": 0},
        "tokens_compared": {"value": int(len(gap)),
                            "limit": limits["min_tokens_compared"],
                            "at_least": True},
    }


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             report) -> ServeRun:
    """Set up, serve for ``seconds``, then judge the served tokens.
    ``report.setup_done()`` is called when the window opens and
    ``report.memory_peak()`` before the program's state is freed."""
    eng, cap, reqs = setup(cell, seed)
    run = ServeRun(cell, cell.arch, report.peak_flops)
    run.setup_s = report.setup_done()
    recorder = None
    if traced:
        from chipbench.trace import Recorder
        recorder = Recorder()
    trace_s = min(TRACE_SECONDS, seconds)
    t_open = time.perf_counter()
    iters, window_s = serve_loop(eng, cap, reqs, seconds, recorder,
                                 trace_from=(seconds - trace_s) / 2,
                                 trace_s=trace_s)
    run.window = (t_open, t_open + window_s)
    run.trace_events = recorder.events() if recorder is not None else None
    run.iterations, run.window_s, run.requests = iters, window_s, reqs
    run.attempted = sum(r.arrival <= window_s for r in reqs)
    run.failed = 0
    report.memory_peak()
    t_check = time.perf_counter()
    log = [np.asarray(a) for a in jax.device_get(cap.log)]
    served = served_tokens(iters, log, {r.rid: r.prompt_len for r in reqs})
    chosen = sample([r for r in reqs if r.done],
                    int(cell.traffic["check_requests"]), seed)
    cfg = eng.cfg
    del eng, cap, log
    gc.collect()
    run.checks = check(run, served, chosen, weights.make_params(cfg, seed))
    run.check_s = time.perf_counter() - t_check
    return run
