"""Profile driver: ``ProfileStore.plan`` + ``ProfileStore.execute`` on the chip.

Set-up plans the configuration into an empty in-memory latency DB (the
plan's host seconds are ``plan.plan_s``) and executes that plan once into a
scratch DB, so that every program the window times is in the persistent
compile cache.  The window then executes the plan again and again, each
pass into a fresh empty DB after dropping JAX's in-memory caches and the
profiler's context cache: each pass pays what profiling a model new to
the DB pays in a fresh process on this machine (tracing, lowering, loading
every program, measuring), and compiles nothing.  The window closes at the
first pass boundary after ``--seconds``.

``correct`` holds every pass to the plan's accounting (each planned point
written once, finite, positive, labelled with the device's kind, nothing
quarantined), holds the operand shapes of every call the oracle timed to
the configuration's widths and the sweep's points (no call off the plan,
every planned point timed in every pass), and compares the outputs of a
seeded sample of those calls, re-run at their timed shapes, with the plain
reference.
"""
from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.cells import Cell, model_config
from chipbench.trace import span

ORACLE = "cpu_wallclock"
#: device bytes the sampled calls may hold between them: a tied 256k
#: vocabulary table is 2 GB, and more of them held through the window
#: would crowd the profiler's own operands
SAMPLE_BYTES = 4 << 30
#: elements of a reference's output (and of an operand it lowers) taken
#: on the host at once
BLOCK = 1 << 25


@dataclass
class Pass:
    t_start: float
    t_end: float
    report: Any
    rows: List[Tuple]


@dataclass
class ProfileRun:
    cell: Cell
    arch: reference.Arch
    plan_s: float = 0.0
    setup_s: float = 0.0
    window_s: float = 0.0
    check_s: float = 0.0
    passes: List[Pass] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)
    trace_events: Optional[Dict[str, Any]] = None
    checks: Dict[str, Dict] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0


def sweep_config(traffic: Dict[str, Any]):
    from repro.core.profiler import SweepConfig
    sw = traffic["sweep"]
    return SweepConfig(toks=tuple(sw["toks"]), reqs=tuple(sw["reqs"]),
                       ctx=tuple(sw["ctx"]),
                       op_points=tuple(tuple(p) for p in sw["op_points"]))


def reset_caches():
    """What a fresh process has not got: JAX's in-memory caches and the
    profiler's memo of execution contexts."""
    from repro.serving import context
    jax.clear_caches()
    context._CONTEXT_CACHE.clear()


def one_pass(plan, hardware: str, sweep) -> Pass:
    from repro.api import ProfileStore
    t0 = time.perf_counter()
    with ProfileStore(":memory:", hardware=hardware, oracle=ORACLE,
                      sweep=sweep) as store:
        rep = store.execute(plan, workers=1)
        rows = store.db.conn.execute(
            "SELECT sig_hash, hardware, phase, num_toks, num_reqs, ctx_len,"
            " oracle, latency_us FROM measurements").fetchall()
    return Pass(t0, time.perf_counter(), rep, rows)


class CallSample:
    """A seeded reservoir sample of the oracle's timed calls, per kind
    (module contexts, single operators), held for the check; and the
    operand shapes of every timed call, per pass.  Each call goes with the
    window of the plan's task that made it (0 for an operator): the
    module's layer window, which its reference applies.  A call whose
    operands would take the sample past ``SAMPLE_BYTES`` is passed over
    (the seed's draws stay the same)."""

    def __init__(self, seed: int, sizes: Dict[str, int]):
        self.rng = np.random.default_rng([int(seed), 3])
        self.sizes = sizes
        self.kept: Dict[str, List] = {k: [] for k in sizes}
        self.seen: Dict[str, int] = {k: 0 for k in sizes}
        self.nbytes = 0
        self.shapes: List[List[Tuple]] = []     # per pass: (shapes, window)
        self.traced = False         # mark each timed call in the trace
        self.window = 0             # of the task being measured
        self._orig = None

    def new_pass(self):
        self.shapes.append([])

    def offer(self, fn, args):
        kind = "module" if isinstance(args[0], dict) else "op"
        if self.shapes:
            self.shapes[-1].append((call_shapes(args), self.window))
        self.seen[kind] += 1
        keep, n = self.kept[kind], self.seen[kind]
        nbytes = sum(math.prod(np.shape(x)) * np.dtype(x.dtype).itemsize
                     for x in jax.tree_util.tree_leaves(args))
        call = (fn, args, self.window, nbytes)
        if len(keep) < self.sizes[kind]:
            if self.nbytes + nbytes <= SAMPLE_BYTES:
                keep.append(call)
                self.nbytes += nbytes
        else:
            j = int(self.rng.integers(0, n))
            if j < len(keep) \
                    and self.nbytes - keep[j][3] + nbytes <= SAMPLE_BYTES:
                self.nbytes += nbytes - keep[j][3]
                keep[j] = call

    def __enter__(self):
        from repro.core import backends
        from repro.core.profiler import DoolyProf
        self._orig = orig = backends.ORACLES[ORACLE]
        self._orig_rows = orig_rows = DoolyProf.measure_payload_rows

        def timed(fn, args, **kw):
            self.offer(fn, args)
            with span(self.traced, "oracle"):
                return orig(fn, args, **kw)

        def rows(prof, payload, *a, **kw):
            self.window = payload[2] if payload[0] == "module" else 0
            return orig_rows(prof, payload, *a, **kw)
        backends.ORACLES[ORACLE] = timed
        DoolyProf.measure_payload_rows = rows
        return self

    def __exit__(self, *exc):
        from repro.core import backends
        from repro.core.profiler import DoolyProf
        backends.ORACLES[ORACLE] = self._orig
        DoolyProf.measure_payload_rows = self._orig_rows
        return False

    def calls(self):
        """(fn, args, window) of each kept call."""
        return [c[:3] for k in sorted(self.kept) for c in self.kept[k]]


# ---------------------------------------------------------------------------
# shapes of a timed call against the plan
# ---------------------------------------------------------------------------

def call_shapes(args) -> Tuple:
    """The operand shapes of one timed call: (kind, ((parameter path,
    shape), ...), input shapes)."""
    if isinstance(args[0], dict):
        leaves = jax.tree_util.tree_leaves_with_path(args[0])
        params = tuple(sorted(("/".join(str(k.key) for k in path),
                               tuple(x.shape)) for path, x in leaves))
        return ("module", params, tuple(tuple(x.shape) for x in args[1:]))
    return ("op", (), tuple(tuple(np.shape(x)) for x in args))


def planned_points(a: reference.Arch, plan, sweep) -> Set[Tuple]:
    """The sweep points a pass has to time, as far as operand shapes show
    them: a module's as its configuration states them, an operator's
    by its tokens and requests."""
    kinds = {t.payload[0] for t in plan.todo}
    points: Set[Tuple] = set()
    if "module" in kinds:
        points |= a.module_points(sweep)
    if kinds - {"module"}:
        points |= {("op", t, r) for t, r in sweep.op_points}
    return points


def call_point(a: reference.Arch, sweep, shapes: Tuple, window: int = 0
               ) -> Tuple[bool, Optional[Tuple]]:
    """(on the plan, point shown): whether a timed call's operands have
    the configuration's widths and the sizes of one of the sweep's points,
    and which point, where the shapes show one.  ``window``: that of the
    call's task, one of the configuration's layer windows for a module."""
    kind, params, ins = shapes
    if kind == "module":
        return a.module_point(sweep, params, ins, window)
    widths = {1} | a.op_widths()
    if all(x in widths for s in ins for x in s):
        return True, None               # weights alone: no point shown
    main = max(ins, key=len)
    if len(main) < 2:
        return False, None
    r, t = main[:2]
    widths = {1, r, t} | a.op_widths()
    ok = ((t, r) in {tuple(p) for p in sweep.op_points}
          and all(x in widths for s in ins for x in s))
    return ok, ("op", t, r) if ok else None


def shape_checks(a: reference.Arch, plan, sweep,
                 passes: List[List[Tuple]]) -> Dict[str, int]:
    """Calls whose operands fit no planned point at the configuration's
    widths, and planned points that a pass did not time."""
    planned = planned_points(a, plan, sweep)
    off = missing = 0
    for calls in passes:
        seen = set()
        for shapes, window in calls:
            ok, point = call_point(a, sweep, shapes, window)
            off += not ok
            if point is not None:
                seen.add(point)
        missing += len(planned - seen)
    return {"calls_off_plan": off, "points_not_timed": missing}


# ---------------------------------------------------------------------------
# reference of a timed call
# ---------------------------------------------------------------------------

def _np(x) -> np.ndarray:
    """``x`` in float64 on the host (converted there, with no copy on the
    device)."""
    return np.asarray(x).astype(np.float64)


def _blocks(x: np.ndarray) -> List:
    """Index of ``x`` block by block of its first axis, each of at most
    ``BLOCK`` elements (one block for a smaller array)."""
    if x.ndim == 0 or x.size <= BLOCK:
        return [Ellipsis]
    rows = max(1, BLOCK // (x.size // x.shape[0]))
    return [slice(s, s + rows) for s in range(0, x.shape[0], rows)]


def _lowered(x: np.ndarray, low: str) -> np.ndarray:
    """``x`` (float64) rounded as ``low`` stores it, scaled along its last
    axis, in float64."""
    out = np.empty(np.shape(x), np.float64)
    for b in _blocks(out):
        out[b] = np.asarray(reference.quantize(
            jnp.asarray(x[b], jnp.float32), low, -1), np.float64)
    return out


def rel_error(got, want: np.ndarray) -> float:
    """Relative L2 error of ``got`` against ``want`` (float64) over the
    entries where ``want`` is finite (e.g. not rsqrt of a negative input),
    block by block, so that an output the size of a vocabulary table is
    never copied whole; infinite where ``got`` is not finite there."""
    got = np.asarray(got)
    if not want.flags.c_contiguous and want.T.flags.c_contiguous:
        got, want = got.T, want.T       # a transpose's: walk it in order
    want_norms, diff_norms = [], []
    for b in _blocks(want):
        w, g = want[b], _np(got[b])
        norm = float(np.linalg.norm(w))
        if not math.isfinite(norm):
            ok = np.isfinite(w)
            w, g = w[ok], g[ok]
            norm = float(np.linalg.norm(w))
        diff = float(np.linalg.norm(g - w))
        if not math.isfinite(diff):
            return float("inf")
        want_norms.append(norm)
        diff_norms.append(diff)
    return math.hypot(*diff_norms) / max(math.hypot(*want_norms), 1e-30)


def _dot_general(x, y, dimension_numbers):
    (lc, rc), (lb, rb) = dimension_numbers
    letters = iter("abcdefghijklmnopqrstuvwxyz")
    xs = [None] * x.ndim
    ys = [None] * y.ndim
    for i, j in zip(lb, rb):
        xs[i] = ys[j] = next(letters)
    for i, j in zip(lc, rc):
        xs[i] = ys[j] = next(letters)
    for i in range(x.ndim):
        xs[i] = xs[i] or next(letters)
    for j in range(y.ndim):
        ys[j] = ys[j] or next(letters)
    out = ([xs[i] for i in lb] + [xs[i] for i in range(x.ndim)
                                  if i not in lb and i not in lc]
           + [ys[j] for j in range(y.ndim) if j not in rb and j not in rc])
    return np.einsum(f"{''.join(xs)},{''.join(ys)}->{''.join(out)}", x, y,
                     optimize=True)


def _broadcast_in_dim(x, shape, broadcast_dimensions):
    dims = [1] * len(shape)
    for i, d in enumerate(broadcast_dimensions):
        dims[d] = x.shape[i]
    return np.broadcast_to(np.reshape(x, dims), shape)


_ELEMENTWISE = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "div": np.divide, "max": np.maximum, "min": np.minimum,
    "neg": np.negative, "exp": np.exp, "log": np.log, "tanh": np.tanh,
    "sqrt": np.sqrt, "rsqrt": lambda x: 1.0 / np.sqrt(x),
    "logistic": lambda x: 1.0 / (1.0 + np.exp(-x)),
    "square": np.square,
}


def op_reference(eqn, inputs: List[np.ndarray]) -> Optional[np.ndarray]:
    """One operator in float64 on the host, from its name and parameters;
    None for an operator this reference does not cover."""
    name, p = eqn.primitive.name, eqn.params
    if name in _ELEMENTWISE:
        return _ELEMENTWISE[name](*inputs)
    x = inputs[0]
    if name == "integer_pow":
        return x ** p["y"]
    if name in ("convert_element_type", "copy"):
        return x
    if name == "reduce_sum":
        return np.sum(x, axis=tuple(p["axes"]))
    if name == "reduce_max":
        return np.max(x, axis=tuple(p["axes"]))
    if name == "broadcast_in_dim":
        return _broadcast_in_dim(x, tuple(p["shape"]),
                                 tuple(p["broadcast_dimensions"]))
    if name == "transpose":
        return np.transpose(x, p["permutation"])
    if name == "reshape":
        return np.reshape(x, p["new_sizes"])
    if name == "dot_general":
        return _dot_general(x, inputs[1], p["dimension_numbers"])
    return None


def call_error(a: reference.Arch, fn, args, window: int = 0,
               control: Optional[Dict[str, str]] = None
               ) -> Optional[Tuple[str, float]]:
    """(precision class, relative L2 error) of one timed call against the
    reference (a module's by its configuration, at the ``window`` of its
    task): the program's output, or with ``control`` (precision class ->
    lower precision), the reference computed on lowered operands."""
    got = jax.jit(fn)(*args)
    cls = "bf16" if got.dtype == jnp.bfloat16 else "f32"
    low = control.get(cls) if control else None
    if isinstance(args[0], dict):
        want = a.module_reference(args, window)
        if want is None:
            return None
        want = _np(want)
        if low:
            lowered = jax.tree.map(
                lambda t: reference.quantize(t.astype(jnp.float32), low, -1)
                if jnp.issubdtype(t.dtype, jnp.floating) else t, args)
            got = a.module_reference(lowered, window)
    else:
        eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
        if len(eqns) != 1:
            return None
        inputs = [_np(x) for x in args]
        want = op_reference(eqns[0], inputs)
        if want is None:
            return None
        if low:
            got = np.asarray(op_reference(
                eqns[0], [_lowered(x, low) for x in inputs])
            ).astype(np.float32).astype(got.dtype)
    return cls, rel_error(got, want)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def end_to_end(run: ProfileRun) -> Dict[str, float]:
    """Sweep points written to the DB per second of the window."""
    points = sum(len(p.rows) for p in run.passes)
    return {"profile_points_per_s": points / run.window_s}


def accounting(plan, passes: List[Pass], hardware: str) -> Dict:
    """Each pass against the plan: planned points not written exactly
    once, rows that are not finite, positive and labelled with this
    device and oracle, and quarantined tasks."""
    from repro.core.profiler import DoolyProf
    from repro.core.database import LatencyDB
    with LatencyDB() as db:
        prof = DoolyProf(db, oracle=ORACLE, hardware=hardware,
                         sweep=plan.sweep)
        planned = {(t.sig_hash,) + tuple(k) for t in plan.todo
                   for k in prof.task_point_keys(t.payload, t.cfg)}
    missing = invalid = quarantined = 0
    for p in passes:
        written = [(r[0],) + tuple(r[2:6]) for r in p.rows]
        missing += len(planned.symmetric_difference(written))
        missing += len(written) - len(set(written))
        missing += abs(p.report.rows_written - len(planned))
        invalid += sum(1 for r in p.rows
                       if r[1] != hardware or r[6] != ORACLE
                       or not (math.isfinite(r[7]) and r[7] > 0))
        quarantined += p.report.quarantined + p.report.skipped_quarantined
    return {"rows_missing": missing, "rows_invalid": invalid,
            "quarantined": quarantined}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             report) -> ProfileRun:
    from repro.api import ProfileStore
    cfg = model_config(cell.config)
    sweep = sweep_config(cell.traffic)
    hardware = jax.devices()[0].device_kind
    run = ProfileRun(cell, cell.arch)
    t0 = time.perf_counter()
    with ProfileStore(":memory:", hardware=hardware, oracle=ORACLE,
                      sweep=sweep) as store:
        plan = store.plan([cfg], backends=("xla",))
    run.plan_s = time.perf_counter() - t0
    one_pass(plan, hardware, sweep)
    run.setup_s = report.setup_done()
    recorder = None
    if traced:
        from chipbench.trace import Recorder
        recorder = Recorder()
    sample = CallSample(seed, dict(cell.traffic["check_calls"]))
    t0 = time.perf_counter()
    with sample:
        while True:
            reset_caches()
            sample.new_pass()
            sample.traced = recorder is not None and not run.passes
            if sample.traced:
                recorder.start()
            with span(sample.traced, "pass"):
                run.passes.append(one_pass(plan, hardware, sweep))
            if sample.traced:
                recorder.stop()
                sample.traced = False
            if time.perf_counter() - t0 >= seconds:
                break
    run.window_s = time.perf_counter() - t0
    run.window = (t0, t0 + run.window_s)
    print("[chipbench] passes_s=" + ",".join(
        f"{p.t_end - p.t_start:.3f}" for p in run.passes), file=sys.stderr)
    run.trace_events = recorder.events() if recorder is not None else None
    run.attempted = sum(p.report.measured + p.report.quarantined
                        for p in run.passes)
    run.failed = sum(p.report.quarantined for p in run.passes)
    report.memory_peak()
    t_check = time.perf_counter()
    limits = cell.config["limits"]["profile"]
    acc = accounting(plan, run.passes, hardware)
    acc.update(shape_checks(run.arch, plan, sweep, sample.shapes))
    errs: Dict[str, float] = {"bf16": 0.0, "f32": 0.0}
    compared = 0
    secs = []
    for fn, args, window in sample.calls():
        t_call = time.perf_counter()
        out = call_error(run.arch, fn, args, window)
        secs.append(f"{call_shapes(args)[0]}:{time.perf_counter() - t_call:.1f}")
        if out is not None:
            errs[out[0]] = max(errs[out[0]], out[1])
            compared += 1
    print("[chipbench] check_call_s=" + ",".join(secs), file=sys.stderr)
    run.checks = {k: {"value": v, "limit": 0} for k, v in acc.items()}
    run.checks.update({
        f"op_err_{k}": {"value": v, "limit": limits[f"op_err_{k}"]}
        for k, v in errs.items()})
    run.checks["calls_compared"] = {"value": compared,
                                    "limit": limits["min_calls_compared"],
                                    "at_least": True}
    run.check_s = time.perf_counter() - t_check
    return run
