"""Serving engine: real execution of the scheduler's iteration plans.

TPU-style static-shape engine: one padded cache of ``max_num_seqs`` rows is
allocated up front (absolute-position slots, no ring); decode runs the full
row batch every iteration (inactive rows masked by lengths, their recurrent
state held), prefill chunks run per-row through ``Model.prefill_chunk``,
padded to a power-of-two bucket.  Fixed shapes mean one decode program and
one program per chunk bucket, which is the bucketing discipline real TPU
serving stacks (JetStream-style) use.  A row's recurrent state (Mamba
layers) is zeroed when a new request takes the row.

The engine clock advances by *measured model time* per iteration, so a
trace replay is reproducible and directly comparable with DoolySim (which
advances the same clock by *predicted* time, driving the same Scheduler).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.runtime import span
from repro.serving.scheduler import (IterationPlan, Request, Scheduler,
                                     SchedulerConfig)

Tree = Any


def bucket_chunk(c: int, chunk_size: int) -> int:
    """Round a prefill chunk up to a power-of-two bucket <= chunk_size, so
    the engine compiles a handful of fixed shapes (TPU bucketing) and the
    sim predicts the same bucketed compute."""
    b = 8
    while b < c:
        b *= 2
    return min(b, chunk_size) if c <= chunk_size else c


@dataclass
class IterationRecord:
    t_start: float
    t_end: float
    n_prefill_tokens: int
    n_decodes: int
    model_s: float
    n_chunks: int = 0
    chunks: Tuple[Tuple[int, int], ...] = ()    # (length, start) per chunk


class Engine:
    def __init__(self, cfg: ModelConfig, *, sched_config: SchedulerConfig,
                 max_seq: int, params: Optional[Tree] = None,
                 impl: str = "auto", seed: int = 0):
        if cfg.is_encdec:
            raise NotImplementedError(
                "the CPU smoke engine serves decoder-only archs; enc-dec is "
                "covered by prefill/decode dry-runs and profiling")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.sched = Scheduler(sched_config)
        self.max_seq = max_seq
        self.impl = impl
        self.params = params if params is not None else self.model.init(
            jax.random.key(seed))
        r = sched_config.max_num_seqs
        self.cache = self.model.zero_cache(r, max_seq, use_ring=False)
        self.lengths = jnp.zeros((r,), jnp.int32)
        self.clock = 0.0
        self.records: List[IterationRecord] = []

        def decode_step(p, c, t, l, active=None):
            return self.model.decode_step(p, c, t, l, impl=impl,
                                          active=active)

        # named functions: the device trace shows ``jit_decode_step``
        self._decode_fn = jax.jit(decode_step)
        self._chunk_fns: Dict[int, Any] = {}
        self._reset_fn = (jax.jit(self.model.reset_recurrent)
                          if cfg.ssm_state > 0 else None)
        self.warmup()

    # ------------------------------------------------------------------

    def _chunk_fn(self, c: int):
        if c not in self._chunk_fns:
            def prefill_chunk(p, cache, toks, lens, last):
                return self.model.prefill_chunk(p, cache, toks, lens,
                                                impl=self.impl, last_pos=last)
            self._chunk_fns[c] = jax.jit(prefill_chunk)
        return self._chunk_fns[c]

    def warmup(self):
        """Compile the decode program and every chunk bucket up front, so no
        compilation lands inside timed iterations."""
        r = self.sched.config.max_num_seqs
        toks = jnp.zeros((r,), jnp.int32)
        jax.block_until_ready(
            self._decode_fn(self.params, self.cache, toks, self.lengths,
                            jnp.zeros((r,), bool))[0])
        b = 8
        while b <= self.sched.config.chunk_size:
            fn = self._chunk_fn(b)
            row = self._row_cache(0)
            if self._reset_fn is not None:
                row = self._reset_fn(row)
            out = fn(self.params, row, jnp.zeros((1, b), jnp.int32),
                     jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
            jax.block_until_ready(out[0])
            b *= 2

    def _row_cache(self, slot: int) -> Tree:
        return jax.tree.map(lambda a: a[:, slot:slot + 1], self.cache)

    def _write_row(self, slot: int, row: Tree):
        self.cache = jax.tree.map(
            lambda a, r: jax.lax.dynamic_update_slice_in_dim(a, r, slot,
                                                             axis=1),
            self.cache, row)

    # ------------------------------------------------------------------

    def execute(self, plan: IterationPlan) -> float:
        """Run one iteration plan; returns measured model seconds.

        Host spans (:func:`repro.runtime.span`) split the iteration in a
        ``jax.profiler`` trace: per chunk ``engine.prefill_chunk`` (with
        its ``bucket`` and the ``pad`` tokens that fill it), then
        ``engine.decode``, each made of ``engine.inputs``,
        ``engine.dispatch`` (the step program's call), ``engine.sync``
        (waiting on the device), and ``engine.write_row`` /
        ``engine.readback`` (host work on the outputs).  A request's first
        chunk on a model with recurrent state first zeroes the row's state
        (``engine.state_reset``)."""
        with span("engine.execute"):
            t0 = time.perf_counter()
            new_tokens: Dict[int, int] = {}
            for chunk in plan.prefills:
                r = chunk.req
                b = bucket_chunk(chunk.length, self.sched.config.chunk_size)
                with span("engine.prefill_chunk", rid=r.rid, bucket=b,
                          pad=b - chunk.length):
                    with span("engine.inputs"):
                        ids = r.prompt[chunk.start:chunk.start
                                       + chunk.length]
                        ids = ids + [0] * (b - chunk.length)    # pad
                        toks = jnp.asarray(ids, jnp.int32)[None]
                        lens = jnp.asarray([chunk.start], jnp.int32)
                        last = jnp.asarray([chunk.length - 1], jnp.int32)
                        row = self._row_cache(r.slot)
                    if (self._reset_fn is not None
                            and chunk.start == r.cache_hit_tokens):
                        with span("engine.state_reset", slot=r.slot):
                            row = self._reset_fn(row)
                    with span("engine.dispatch"):
                        logits, row = self._chunk_fn(b)(
                            self.params, row, toks, lens, last)
                    with span("engine.sync"):
                        jax.block_until_ready(logits)
                    with span("engine.write_row"):
                        self._write_row(r.slot, row)
                        self.lengths = self.lengths.at[r.slot].set(
                            chunk.start + chunk.length)
                    if chunk.start + chunk.length >= r.prompt_len:
                        with span("engine.readback"):
                            new_tokens[r.rid] = int(jnp.argmax(logits[0]))
            if plan.decodes:
                with span("engine.decode"):
                    # replay mode: deterministic dummy token ids
                    # (latency-identical)
                    with span("engine.inputs"):
                        n = self.sched.config.max_num_seqs
                        toks, active = np.zeros(n, np.int32), np.zeros(n, bool)
                        for r in plan.decodes:
                            toks[r.slot] = 1 + (r.generated % 7)
                            active[r.slot] = True
                        toks, active = jnp.asarray(toks), jnp.asarray(active)
                    with span("engine.dispatch"):
                        logits, self.cache = self._decode_fn(
                            self.params, self.cache, toks, self.lengths,
                            active)
                    with span("engine.sync"):
                        jax.block_until_ready(logits)
                    with span("engine.readback"):
                        for r in plan.decodes:
                            new_tokens[r.rid] = int(
                                jnp.argmax(logits[r.slot]))
                            self.lengths = self.lengths.at[r.slot].add(1)
            return time.perf_counter() - t0

    # ------------------------------------------------------------------

    def run(self, requests: List[Request]) -> Dict[str, Any]:
        """Replay a workload trace; the clock advances by measured model
        time (plus arrival gaps when idle)."""
        pending = sorted(requests, key=lambda r: r.arrival)
        i = 0
        self.clock = 0.0
        while i < len(pending) or self.sched.has_work():
            while i < len(pending) and pending[i].arrival <= self.clock:
                self.sched.add_request(pending[i])
                i += 1
            plan = self.sched.schedule()
            if plan.empty:
                if i < len(pending):
                    self.clock = pending[i].arrival
                    continue
                break
            model_s = self.execute(plan)
            t_start = self.clock
            self.clock += model_s
            self.sched.complete_iteration(plan, self.clock)
            self.records.append(IterationRecord(
                t_start, self.clock,
                sum(c.length for c in plan.prefills), len(plan.decodes),
                model_s, n_chunks=len(plan.prefills),
                chunks=tuple((c.length, c.start) for c in plan.prefills)))
        return {"requests": requests, "iterations": self.records,
                "makespan": self.clock}
