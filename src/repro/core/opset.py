"""Operation Set Finder (paper §5): bottom-up resolution of the tainted
trace into the minimal runnable set.

* Leaf operations are tested for standalone execution by re-binding their
  primitive with taint-generated inputs ("import and run", §5.2).
* Stateful modules (attention, Mamba, MoE — identified by the serving
  engine's stateful-module registry, the vLLM AttentionGroup analogue) are
  resolved at module granularity with *execution context emulation*: the
  profiler rebuilds them through the serving engine's own module builders,
  which also supply the decode-phase context (KV cache, lengths) that the
  prefill trace alone cannot provide (App. D).
* Leaves that fail standalone execution are absorbed into their enclosing
  module (sub-jaxpr extraction), exactly the paper's fallback.

Taint-driven input generation (§5.2): MODEL_CONFIG dims stay fixed,
NUM_TOKS / NUM_REQS dims are substituted per sweep point, MIX dims are
recalculated from H with the workload component replaced, untainted dims
are kept.

Operands are drawn without a program per shape (``generate_array``): a
profiling pass draws hundreds of them in some sixty shapes, and an eager
RNG program for each shape would be traced, lowered and loaded anew in
every fresh process.  Floating values come from a seeded host pool and
reach the device by ``jax.device_put``; only operands too large for a
host copy and transfer to beat one program are drawn on the device.
"""
from __future__ import annotations

import functools
import math
import operator
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import core as jcore

from repro.core.callgraph import Node, build_hierarchy, collapse
from repro.core.taint import NUM_REQS, NUM_TOKS, Taint
from repro.core.tracer import TaintedTrace, TraceOp

Tree = Any

# the serving engine's stateful-module registry (serving/context.py builds
# execution contexts for exactly these kinds)
STATEFUL_MODULES = ("self_attn", "cross_attn", "mla_attn", "mamba", "moe")

# operator params whose values encode output sizes (rewritten on resize)
_SHAPE_PARAM_PRIMS = {
    "reshape": "new_sizes",
    "broadcast_in_dim": "shape",
    "iota": "shape",
}

_NO_SWEEP_PRIMS = {"slice", "pad", "dynamic_slice", "dynamic_update_slice",
                   "gather", "scatter", "scatter-add", "concatenate",
                   "conv_general_dilated", "rev", "split"}


# ---------------------------------------------------------------------------
# taint-driven size substitution
# ---------------------------------------------------------------------------

def resize_dim(size: int, taint: Taint, *, toks: Optional[int],
               reqs: Optional[int]) -> int:
    if taint.is_bot:
        return size
    if taint.is_mix:
        out = 1
        for v, label in taint.h:
            if label == NUM_TOKS:
                out *= toks if toks is not None else v
            elif label == NUM_REQS:
                out *= reqs if reqs is not None else v
            else:
                out *= v
        return out
    if taint.kind == NUM_TOKS:
        return toks if toks is not None else size
    if taint.kind == NUM_REQS:
        return reqs if reqs is not None else size
    return size                                   # MODEL_CONFIG fixed


def resize_shape(shape: Sequence[int], taints: Sequence[Taint], *,
                 toks: Optional[int], reqs: Optional[int]) -> Tuple[int, ...]:
    return tuple(resize_dim(s, t, toks=toks, reqs=reqs)
                 for s, t in zip(shape, taints))


# ---------------------------------------------------------------------------
# operand drawing
# ---------------------------------------------------------------------------

#: floating operands are N(0, 1) x OPERAND_SCALE, cast to their dtype
OPERAND_SCALE = 0.02
#: values in the host pool; keys below it start distinct windows
_POOL_LEN = 1 << 19
#: odd, so ``key * _POOL_STRIDE`` is a bijection on keys mod _POOL_LEN
_POOL_STRIDE = 0x9E3779B1 % _POOL_LEN
#: floating operands larger than this are drawn on the device by one fused
#: program per shape: above it, tiling a window of the pool to the
#: operand's size and transferring it costs more than the program's first
#: call (on a TPU v5e both took ~85 ms at 64 MiB; PERF.md)
DEVICE_DRAW_BYTES = 64 << 20

# dtype -> the pool in that dtype, twice over, read-only: every window of
# up to _POOL_LEN values is one contiguous slice.  It holds values, never
# an operand, and drawing it costs under 1% of a profiling pass.
_POOLS: Dict[np.dtype, np.ndarray] = {}
# running totals of generate_array's draws per path (``operand_counts``)
_COUNTS = {"host_arrays": 0, "host_bytes": 0, "device_arrays": 0}


def _pool(dt: np.dtype) -> np.ndarray:
    pool = _POOLS.get(dt)
    if pool is None:
        f32 = np.dtype(np.float32)
        if dt == f32:
            z = np.random.default_rng(0).standard_normal(_POOL_LEN, f32)
            pool = np.concatenate([z, z]) * f32.type(OPERAND_SCALE)
        else:
            pool = _pool(f32).astype(dt)
        pool.flags.writeable = False
        _POOLS[dt] = pool
    return pool


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_on_device(key, shape, dtype):
    z = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    return (z * OPERAND_SCALE).astype(dtype)


def generate_array(shape, dtype, key=None) -> jax.Array:
    """One operand on the default device.  Integers are zeros (valid
    indices everywhere), booleans ones, floating values N(0, 1) x
    OPERAND_SCALE in ``dtype``.  ``key`` (an int, default 0) picks the
    values: the same shape, dtype and key give the same array.

    Below DEVICE_DRAW_BYTES no program runs: the values are a window of a
    host pool, tiled to the operand's size, placed by ``jax.device_put``.
    Above it, one fused program per shape draws them on the device."""
    dt = jnp.dtype(dtype)
    shape = tuple(int(s) for s in shape)
    key = 0 if key is None else operator.index(key)
    n = math.prod(shape)
    if dt.kind in "iu":
        host = np.zeros(shape, dt)
    elif dt.kind == "b":
        host = np.ones(shape, dt)
    elif n * dt.itemsize > DEVICE_DRAW_BYTES:
        _COUNTS["device_arrays"] += 1
        return _draw_on_device(key, shape, dt)
    else:
        start = key * _POOL_STRIDE % _POOL_LEN
        window = _pool(dt)[start:start + min(n, _POOL_LEN)]
        host = (window if n <= _POOL_LEN else np.resize(window, n)
                ).reshape(shape)
    _COUNTS["host_arrays"] += 1
    _COUNTS["host_bytes"] += host.nbytes
    return jax.device_put(host)


def operand_counts() -> Dict[str, int]:
    """Running totals of ``generate_array``'s draws in this process:
    ``host_arrays`` and ``host_bytes`` placed from the host,
    ``device_arrays`` drawn by a program on the device."""
    return dict(_COUNTS)


def generate_inputs(op: TraceOp, *, toks: Optional[int] = None,
                    reqs: Optional[int] = None) -> List[jax.Array]:
    out = []
    for i, (shape, dtype, taints) in enumerate(
            zip(op.in_shapes, op.in_dtypes, op.in_taints)):
        rs = resize_shape(shape, taints, toks=toks, reqs=reqs)
        out.append(generate_array(rs, dtype, i + 1))
    return out


# ---------------------------------------------------------------------------
# runnable-set entries
# ---------------------------------------------------------------------------

def entry_task_id(sig_hash: str, hardware: str) -> str:
    """Canonical identity of one measurement task: a signature swept on one
    hardware.  This is the unit of corpus-wide dedup (two models needing
    the same id share one measurement), of DB satisfaction checks, and of
    ProfilePlan journaling/resume — one string, so a checkpoint file and a
    plan built in another process agree byte-for-byte."""
    return f"{hardware}:{sig_hash}"


_PRIM_REGISTRY: dict = {}       # primitive name -> Primitive singleton
_PRIM_HOMES: dict = {}          # primitive name -> defining module name


def _scan_primitives():
    import sys
    for mod in list(sys.modules.values()):
        mod_name = getattr(mod, "__name__", "")
        if not mod_name.startswith("jax"):
            continue
        for attr in dir(mod):
            if attr.endswith("_p"):
                v = getattr(mod, attr, None)
                if isinstance(v, jcore.Primitive):
                    _PRIM_REGISTRY.setdefault(v.name, v)
                    _PRIM_HOMES.setdefault(v.name, mod_name)


def primitive_home(prim: jcore.Primitive) -> Optional[str]:
    """Name of a loaded jax module exposing a ``<name>_p`` attribute for
    this primitive, or None.  Recorded at detach time so a worker process
    that never traced the model can import the defining module before
    resolving.  Backed by the same one-shot scan as ``resolve_primitive``."""
    if prim.name not in _PRIM_HOMES:
        _scan_primitives()
    return _PRIM_HOMES.get(prim.name)


def resolve_primitive(name: str, home: Optional[str] = None
                      ) -> jcore.Primitive:
    """Look a primitive singleton up by name in the loaded jax modules
    (they are all registered as ``<name>_p`` attributes).  Lets a detached
    OpEntry — shipped to a sweep worker without its live jaxpr equation —
    re-bind the exact computation for measurement.  Misses first import
    ``home`` (the defining module recorded at detach time, covering
    primitives from lazily-imported jax modules) and rescan
    ``sys.modules``."""
    prim = _PRIM_REGISTRY.get(name)
    if prim is None:
        if home is not None:
            import importlib
            try:
                importlib.import_module(home)
            except ImportError:
                pass
        _scan_primitives()
        prim = _PRIM_REGISTRY.get(name)
    if prim is None:
        raise KeyError(f"primitive {name!r} not found in loaded jax modules")
    return prim


@dataclass
class OpEntry:
    """Operator-level entry (standalone-runnable primitive).

    Normally bound through the live ``op.eqn``; a *detached* entry (see
    ``detach_op_entry``) instead carries the full bind params in ``bind``
    and resolves its primitive by name — the picklable form a parallel
    profiling sweep ships to worker processes so they measure without
    re-tracing the model."""
    kind: str                       # primitive name
    op: TraceOp
    count: int                      # occurrences across collapsed layers
    module: str                     # canonical module path
    sweepable: bool = True
    # detached form: (prim name, full eqn params, defining module or None)
    bind: Optional[Tuple[str, dict, Optional[str]]] = None

    def _bind_spec(self):
        eqn = self.op.eqn
        if eqn is not None:
            return eqn.primitive, dict(eqn.params)
        if self.bind is None:
            raise ValueError(f"OpEntry {self.kind!r} has neither a live "
                             "eqn nor detached bind params")
        name, params, home = self.bind
        return resolve_primitive(name, home), dict(params)

    def _bind_params(self, *, toks, reqs):
        prim, params = self._bind_spec()
        key = _SHAPE_PARAM_PRIMS.get(self.kind)
        if key is not None and (toks is not None or reqs is not None):
            params[key] = resize_shape(self.op.out_shapes[0],
                                       self.op.out_taints[0],
                                       toks=toks, reqs=reqs)
        return prim, params

    def run(self, *, toks=None, reqs=None):
        args = generate_inputs(self.op, toks=toks, reqs=reqs)
        prim, params = self._bind_params(toks=toks, reqs=reqs)
        return prim.bind(*args, **params)

    def jit_callable(self, *, toks=None, reqs=None):
        args = generate_inputs(self.op, toks=toks, reqs=reqs)
        prim, params = self._bind_params(toks=toks, reqs=reqs)

        def fn(*a):
            return prim.bind(*a, **params)
        return fn, args


def detach_op_entry(entry: OpEntry) -> OpEntry:
    """Picklable copy of an OpEntry: the live jaxpr equation (which holds
    unpicklable tracer state) is dropped and replaced by its (primitive
    name, full params) so a spawn-started worker can rebuild the identical
    bind.  ``run``/``jit_callable`` on the detached copy produce the same
    lowered computation as the original."""
    import dataclasses
    prim, params = entry._bind_spec()
    return dataclasses.replace(
        entry, op=dataclasses.replace(entry.op, eqn=None),
        bind=(prim.name, params, primitive_home(prim)))


@dataclass
class ModuleEntry:
    """Module-level entry (stateful, or absorbed failed leaves).

    ``context_kind`` selects the serving-engine builder that reconstructs the
    execution context (phase-dependent for attention-like modules)."""
    kind: str                       # module name ("self_attn", "mlp", ...)
    node: Node
    count: int
    module: str
    context_kind: Optional[str] = None   # one of STATEFUL_MODULES or None
    ops: List[TraceOp] = field(default_factory=list)

    def sub_jaxpr(self):
        return extract_subjaxpr(self.ops or self.node.all_ops())

    def run(self):
        jaxpr, invars = self.sub_jaxpr()
        args = []
        for i, v in enumerate(invars):
            # taints for free vars: find the producing/consuming TraceOp
            shape = tuple(getattr(v.aval, "shape", ()))
            dtype = getattr(v.aval, "dtype", jnp.float32)
            args.append(generate_array(shape, dtype, i + 1))
        return jcore.eval_jaxpr(jaxpr, [], *args)


Entry = Any  # OpEntry | ModuleEntry


# ---------------------------------------------------------------------------
# sub-jaxpr extraction (module fallback)
# ---------------------------------------------------------------------------

def extract_subjaxpr(ops: List[TraceOp]):
    """Closed jaxpr over the eqns of a module: invars = free vars,
    outvars = vars not consumed inside (the module's results)."""
    eqns = [op.eqn for op in sorted(ops, key=lambda o: o.eqn_id)
            if op.eqn is not None]
    defined = set()
    consumed = set()
    invars = []
    for eqn in eqns:
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                continue
            consumed.add(v)
            if v not in defined and v not in invars:
                invars.append(v)
        for v in eqn.outvars:
            defined.add(v)
    outvars = [v for eqn in eqns for v in eqn.outvars
               if v in defined and v not in consumed
               and not isinstance(v, jcore.DropVar)]
    if not outvars:
        outvars = [v for v in eqns[-1].outvars
                   if not isinstance(v, jcore.DropVar)]
    dbg = None
    try:
        jaxpr = jcore.Jaxpr(constvars=(), invars=tuple(invars),
                            outvars=tuple(outvars), eqns=tuple(eqns))
    except TypeError:
        from jax._src import api_util as _au
        dbg = _au.debug_info("dooly_subjaxpr", None, (), {})
        jaxpr = jcore.Jaxpr(constvars=(), invars=tuple(invars),
                            outvars=tuple(outvars), eqns=tuple(eqns),
                            debug_info=dbg)
    return jaxpr, invars


# ---------------------------------------------------------------------------
# bottom-up resolution (§5.2)
# ---------------------------------------------------------------------------

def find_runnable_set(trace: TaintedTrace) -> List[Entry]:
    root = build_hierarchy(trace)
    canon = collapse(root)
    entries: List[Entry] = []
    for cm in canon:
        entries.extend(_resolve_module(cm.node, cm.count))
    return entries


def _stateful_kind(path: Tuple[str, ...]) -> Optional[str]:
    for comp in path:
        base = comp.split(".")[0]
        if base in STATEFUL_MODULES:
            return base
    return None


def _resolve_module(node: Node, count: int) -> List[Entry]:
    sk = _stateful_kind(node.path)
    if sk is not None:
        # stateful: stop here, absorb the whole subtree (context emulation)
        return [ModuleEntry(kind=sk, node=node, count=count,
                            module="/".join(node.path), context_kind=sk,
                            ops=node.all_ops())]
    out: List[Entry] = []
    failed: List[TraceOp] = []
    for op in node.ops:
        if op.eqn is None:
            failed.append(op)
            continue
        # skip untainted dispatch-mechanics leaves (§5.2 bottom-up rule)
        if all(t.is_bot for ts in op.in_taints for t in ts) and op.in_shapes:
            if all(len(s) == 0 for s in op.in_shapes):
                continue
        entry = OpEntry(kind=op.prim, op=op, count=count,
                        module="/".join(node.path),
                        sweepable=op.prim not in _NO_SWEEP_PRIMS)
        try:
            entry.run()
            out.append(entry)
        except Exception:
            failed.append(op)
    for name in node.children:
        child = node.children[name]
        sk_child = _stateful_kind(child.path)
        if sk_child is not None:
            out.append(ModuleEntry(kind=sk_child, node=child, count=count,
                                   module="/".join(child.path),
                                   context_kind=sk_child,
                                   ops=child.all_ops()))
        else:
            out.extend(_resolve_module(child, count))
    if failed:
        # absorb failed leaves into a module-level entry at this node
        me = ModuleEntry(kind=node.name or "root", node=node, count=count,
                         module="/".join(node.path), ops=failed)
        try:
            me.run()
            out.append(me)
        except Exception:
            # final fallback: absorb the ENTIRE node (children included)
            me_all = ModuleEntry(kind=node.name or "root", node=node,
                                 count=count, module="/".join(node.path),
                                 ops=node.all_ops())
            try:
                me_all.run()
                # replace child-level entries we already emitted
                out = [me_all]
            except Exception:
                pass
    return out
