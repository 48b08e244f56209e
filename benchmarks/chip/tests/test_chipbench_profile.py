"""The profile driver rehearsed at smoke width on the CPU, and ``correct``
coming out false with the timed path broken underneath."""
import dataclasses

import pytest

from _chipbench_smoke import cells, run, smoke_cell

CELLS = ["minicpm3-4b.profile", "command-r7b.profile"]


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("name", CELLS)
def test_profile_run_is_correct_and_reports_its_metrics(name):
    cell = smoke_cell(name)
    res = run(cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m.name for m in cell.end_to_end}
    assert res["metrics"]["profile_points_per_s"]["value"] > 0
    assert res["checks"]["rows_missing"]["value"] == 0
    assert res["checks"]["calls_off_plan"]["value"] == 0
    assert res["checks"]["points_not_timed"]["value"] == 0
    assert res["checks"]["calls_compared"]["value"] >= 4


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
def test_traced_profile_run_reports_per_layer_metrics():
    cell = smoke_cell("minicpm3-4b.profile")
    res = run(cell, traced=True)
    assert res["correct"], res["checks"]
    # the CPU has no device plane: the idle share is left out, not 0
    assert set(res["metrics"]) == {"plan.plan_s", "profile.compile_share",
                                   "profile.timed_share",
                                   "profile.operand_share"}
    for name in ("profile.compile_share", "profile.timed_share",
                 "profile.operand_share"):
        assert 0 < res["metrics"][name]["value"] < 100


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("name", CELLS)
def test_timed_module_answering_zeros_is_not_correct(monkeypatch, name):
    from repro.serving import context
    orig = context.build_context

    def broken(*a, **k):
        mc = orig(*a, **k)
        fn = mc.fn
        return dataclasses.replace(mc, fn=lambda *x: fn(*x) * 0)
    monkeypatch.setattr(context, "build_context", broken)
    context._CONTEXT_CACHE.clear()
    cell = smoke_cell(name)
    cell.traffic["check_calls"] = {"module": 4, "op": 0}
    res = run(cell)
    context._CONTEXT_CACHE.clear()
    assert not res["correct"]
    assert res["checks"]["op_err_bf16"]["value"] > \
        res["checks"]["op_err_bf16"]["limit"]


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
def test_dropped_row_is_not_correct(monkeypatch):
    from repro.core.profiler import DoolyProf
    orig = DoolyProf.measure_payload_rows

    def broken(self, *a, **k):
        return orig(self, *a, **k)[:-1]
    monkeypatch.setattr(DoolyProf, "measure_payload_rows", broken)
    res = run(smoke_cell("minicpm3-4b.profile"))
    assert not res["correct"]
    assert res["checks"]["rows_missing"]["value"] > 0


def test_reference_matches_each_operator():
    """The float64 operator reference against JAX on exact inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import profile
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 4, 5)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((5, 6)), jnp.float32)
    cases = [
        (lambda a, b: jax.lax.dot_general(a, b, (((2,), (0,)), ((), ()))),
         (x, y)),
        (lambda a: jnp.sum(a, axis=(0, 2)), (x,)),
        (lambda a: jnp.transpose(a, (2, 0, 1)), (x,)),
        (lambda a: jax.lax.broadcast_in_dim(a, (2, 3, 4, 5), (1, 2, 3)),
         (x,)),
        (jax.lax.rsqrt, (jnp.abs(x) + 1,)),
        (jnp.multiply, (x, x)),
    ]
    for fn, args in cases:
        (eqn,) = jax.make_jaxpr(fn)(*args).jaxpr.eqns
        got = profile.op_reference(eqn, [np.asarray(v, np.float64)
                                         for v in args])
        np.testing.assert_allclose(got, np.asarray(fn(*args)), rtol=1e-5,
                                   atol=1e-5)


def _wrong_context(monkeypatch):
    """Decode module contexts timed at twice the planned context."""
    from repro.serving import context
    orig = context.build_context

    def broken(*a, **k):
        mc = orig(*a, **k)
        if mc.phase != "decode":
            return mc
        spec = mc.input_spec
        return dataclasses.replace(
            mc, input_spec=lambda t, r, c: spec(t, r, 2 * c))
    monkeypatch.setattr(context, "build_context", broken)
    context._CONTEXT_CACHE.clear()


def _wrong_tokens(monkeypatch):
    """Operators timed at twice the planned tokens."""
    from repro.core.opset import OpEntry
    orig = OpEntry.jit_callable

    def broken(self, *, toks=None, reqs=None):
        return orig(self, toks=2 * toks if toks else toks, reqs=reqs)
    monkeypatch.setattr(OpEntry, "jit_callable", broken)


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_wrong_context, _wrong_tokens],
                         ids=["module_at_wrong_context",
                              "op_at_wrong_tokens"])
def test_call_timed_off_the_plan_is_not_correct(monkeypatch, fault, name):
    from repro.serving import context
    fault(monkeypatch)
    res = run(smoke_cell(name))
    context._CONTEXT_CACHE.clear()
    assert not res["correct"]
    assert res["checks"]["calls_off_plan"]["value"] > 0
    assert res["checks"]["points_not_timed"]["value"] > 0


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_reference_in_the_programs_place_is_not_correct(
        monkeypatch, name):
    """The control: each sampled call answered by the reference on operands
    rounded to fp8 (bfloat16 calls) or bfloat16 (float32 calls)."""
    from chipbench import profile
    orig = profile.call_error
    low = {"bf16": "fp8", "f32": "bf16"}
    monkeypatch.setattr(profile, "call_error",
                        lambda a, fn, args, window: orig(a, fn, args, window,
                                                         control=low))
    res = run(smoke_cell(name))
    assert not res["correct"]
    assert res["checks"]["calls_compared"]["value"] >= 4
    assert any(res["checks"][k]["value"] > res["checks"][k]["limit"]
               for k in ("op_err_bf16", "op_err_f32"))


@pytest.mark.parametrize("name,calls,points", [
    ("minicpm3-4b.profile", 90, 6 + 4 + 4),
    # 18 operators at 4 points; two attention modules (windowed, global)
    # at 3 x 2 x 3 prefill and 2 x 2 decode points; the points shapes show:
    # 18 prefill, 4 decode (the ring holds the context), 4 operator
    ("command-r7b.profile", 18 * 4 + 2 * (18 + 4), 18 + 4 + 4)])
def test_every_call_of_a_full_width_pass_is_on_the_plan(monkeypatch, name,
                                                        calls, points):
    """The shape check on the committed configuration at its own widths:
    one pass of its plan, with operands left abstract and a stand-in
    oracle, times every planned point and nothing else."""
    import jax
    from chipbench import profile
    from repro.api import ProfileStore
    from repro.core import backends, opset
    from repro.serving import context
    cell = cells.find_cell(cells.load_benchmark(), name)
    cfg = cells.model_config(cell.config)
    sweep = profile.sweep_config(cell.traffic)
    with ProfileStore(":memory:", hardware="cpu", oracle=profile.ORACLE,
                      sweep=sweep) as store:
        plan = store.plan([cfg], backends=("xla",))
    monkeypatch.setattr(opset, "generate_array",
                        lambda shape, dtype, key=None:
                        jax.ShapeDtypeStruct(shape, dtype))
    monkeypatch.setattr(context.ModuleContext, "materialize",
                        lambda self, tree, key=None: tree)
    monkeypatch.setitem(backends.ORACLES, profile.ORACLE,
                        lambda fn, args, **kw: 1e-3)
    sample = profile.CallSample(1, {"module": 0, "op": 0})
    profile.reset_caches()
    with sample:
        sample.new_pass()
        p = profile.one_pass(plan, "cpu", sweep)
    a = cell.arch
    assert len(sample.shapes[0]) == len(p.rows) == calls
    assert profile.shape_checks(a, plan, sweep, sample.shapes) == \
        {"calls_off_plan": 0, "points_not_timed": 0}
    assert len(profile.planned_points(a, plan, sweep)) == points


def test_relative_error_block_by_block_is_the_whole_arrays(monkeypatch):
    """``rel_error`` in blocks of the first axis equals the error of the
    whole arrays, non-finite reference entries left out; a non-finite
    output where the reference is finite reads infinite."""
    import numpy as np
    from chipbench import profile
    rng = np.random.default_rng(1)
    want = rng.standard_normal((37, 5, 3))
    want[3, 1, 2] = np.nan
    got = (want + 1e-3 * rng.standard_normal(want.shape)).astype(np.float32)
    ok = np.isfinite(want)
    whole = (np.linalg.norm(got[ok] - want[ok]) / np.linalg.norm(want[ok]))
    assert profile.rel_error(got, want) == whole
    monkeypatch.setattr(profile, "BLOCK", 40)
    assert len(profile._blocks(want)) == 19
    assert profile.rel_error(got, want) == pytest.approx(whole, rel=1e-12)
    got[5, 0, 0] = np.inf
    assert profile.rel_error(got, want) == float("inf")
