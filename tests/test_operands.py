"""Operand drawing for the profiler (``opset.generate_array`` and
``ModuleContext.materialize``).

Below ``opset.DEVICE_DRAW_BYTES`` an operand is a window of a seeded host
pool placed by ``jax.device_put``: no XLA program is traced, lowered or
compiled, whatever its shape.  Above it, one fused program per shape
draws it on the device.  Either way the result is a ``jax.Array`` on the
default device with the operand's shape and dtype, N(0, 1) x 0.02 for
floating dtypes, zeros for integers and ones for booleans, the same for
the same key and different for different keys.  Every argument the oracle
times is such an array, never a host array.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import backends, opset
from repro.serving.context import build_context

COMPILE_EVENT_PREFIX = "/jax/core/compile/"


def _by_generate_array(shape, dtype, key):
    return opset.generate_array(shape, dtype, key)


def _by_materialize(shape, dtype, key):
    mc = build_context(get_smoke_config("minicpm3-4b"), "self_attn",
                       phase="decode")
    tree = {"leaf": jax.ShapeDtypeStruct(shape, dtype)}
    return mc.materialize(tree, key)["leaf"]


SITES = {"generate_array": _by_generate_array,
         "materialize": _by_materialize}

FLOATING = [
    ((48, 80), jnp.bfloat16),
    ((3, 40, 64), jnp.float32),
    ((1200, 1000), jnp.float32),        # longer than the pool: tiled
]
CASES = ([(shape, dtype, "host") for shape, dtype in FLOATING]
         + [((4, 7), jnp.int32, "host"), ((9,), jnp.bool_, "host")]
         + [(shape, dtype, "device") for shape, dtype in FLOATING[:2]])


def _draw(site, shape, dtype, key):
    """(array, compile-pipeline events, operand_counts delta) of one draw."""
    events = []

    def on_duration(event, duration, **_):
        if event.startswith(COMPILE_EVENT_PREFIX):
            events.append(event)
    before = opset.operand_counts()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        x = SITES[site](shape, dtype, key)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    after = opset.operand_counts()
    return x, events, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("shape,dtype,path", CASES)
@pytest.mark.parametrize("site", sorted(SITES))
def test_drawn_operand(site, shape, dtype, path, monkeypatch):
    dt = jnp.dtype(dtype)
    floating = dt.kind not in "iub"
    if path == "device":        # the fused program, at a smoke size
        monkeypatch.setattr(opset, "DEVICE_DRAW_BYTES", 1024)
    x, compiles, counts = _draw(site, shape, dtype, 5)
    if path == "host":
        assert compiles == []
        assert counts == {"host_arrays": 1, "device_arrays": 0,
                          "host_bytes": int(np.prod(shape)) * dt.itemsize}
    else:
        assert counts == {"host_arrays": 0, "device_arrays": 1,
                          "host_bytes": 0}
    assert isinstance(x, jax.Array)
    assert x.devices() == {jax.devices()[0]}
    assert x.shape == tuple(shape) and x.dtype == dt
    v = np.asarray(x)
    assert np.array_equal(v, np.asarray(SITES[site](shape, dtype, 5)))
    if not floating:
        want = np.ones if dt.kind == "b" else np.zeros
        assert np.array_equal(v, want(shape, dt))
        return
    assert not np.array_equal(v, np.asarray(SITES[site](shape, dtype, 6)))
    v = v.astype(np.float64)
    assert abs(v.mean()) < 5 * opset.OPERAND_SCALE / np.sqrt(v.size)
    assert abs(v.std() / opset.OPERAND_SCALE - 1) < 0.05


def test_every_timed_argument_is_a_device_array(monkeypatch):
    """One smoke pass of an MLA model under a stand-in oracle: operators
    and modules alike are timed on ``jax.Array``s, so no timed repeat
    includes a transfer from the host."""
    from repro.api import ProfileStore
    from repro.core.profiler import QUICK_SWEEP
    seen = {"module": [], "op": []}

    def oracle(fn, args, **kw):
        kind = "module" if isinstance(args[0], dict) else "op"
        seen[kind].extend(jax.tree.leaves(args))
        return 1e-3
    monkeypatch.setitem(backends.ORACLES, "cpu_wallclock", oracle)
    with ProfileStore(":memory:", hardware="cpu", oracle="cpu_wallclock",
                      sweep=QUICK_SWEEP) as store:
        plan = store.plan([get_smoke_config("minicpm3-4b")],
                          backends=("xla",))
        report = store.execute(plan, workers=1)
    assert report.rows_written > 0
    assert seen["module"] and seen["op"]
    for leaves in seen.values():
        assert all(isinstance(x, jax.Array) and not isinstance(x, np.ndarray)
                   for x in leaves)
