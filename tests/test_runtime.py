"""One process per chip, no hidden oracle fallback, the compile cache, and
where the program's spans may not go.

The TPU cases steer ``jax.default_backend`` itself: no option of the
program selects them."""
import multiprocessing as mp
import os
import re

import jax
import pytest

from repro import runtime
from repro.api import ProfileStore
from repro.configs import get_smoke_config
from repro.core import backends as oracles
from repro.core.database import LatencyDB
from repro.core.plan import build_plan, execute_plan
from repro.core.profiler import DoolyProf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_measure_rejects_an_unknown_oracle():
    with pytest.raises(ValueError, match="unknown oracle 'gpu_events'"):
        oracles.measure("gpu_events", lambda x: x, ())


def test_wallclock_rows_under_a_tpu_label_need_a_tpu():
    with LatencyDB() as db:
        with pytest.raises(RuntimeError, match="label the rows"):
            DoolyProf(db, oracle="cpu_wallclock", hardware="TPU v5 lite")
        DoolyProf(db, oracle="cpu_wallclock", hardware="cpu")
        DoolyProf(db, oracle="tpu_analytical", hardware="tpu-v5e")


@pytest.mark.parametrize("kw", [{"workers": 2}, {"task_timeout": 30.0}])
def test_plan_execution_pool_refuses_on_tpu(tpu_backend, kw):
    with LatencyDB() as db:
        plan = build_plan(db, [])
        with pytest.raises(RuntimeError, match="child processes"):
            execute_plan(db, plan, **kw)
        with ProfileStore.wrap(db) as store, \
                pytest.raises(RuntimeError, match="child processes"):
            store.execute(plan, **kw)
        assert execute_plan(db, plan).measured == 0     # serial is fine


def test_parallel_profile_model_refuses_on_tpu(tpu_backend):
    with LatencyDB() as db:
        prof = DoolyProf(db, oracle="tpu_analytical")
        with pytest.raises(RuntimeError, match="child processes"):
            prof.profile_model(get_smoke_config("llama3-8b"), workers=2)


def _report_platforms(conn):
    conn.send(os.environ.get("JAX_PLATFORMS"))


def test_cpu_only_children_pins_spawned_children_to_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    parent, child = mp.Pipe()
    with runtime.cpu_only_children():
        proc = mp.get_context("spawn").Process(target=_report_platforms,
                                               args=(child,))
        proc.start()
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    assert parent.poll(60) and parent.recv() == "cpu"
    proc.join(30)
    assert not proc.is_alive()


def test_compile_cache_defers_to_the_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert runtime.use_compile_cache() is None and calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = runtime.use_compile_cache()
    assert calls == [("jax_compilation_cache_dir", str(path))]
    assert path == runtime.COMPILE_CACHE_DIR
    assert path.parent == type(path)(REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert f"{path.name}/" in f.read().split()


#: a call of ``span`` (not ``makespan``)
SPAN_CALL = re.compile(r"(?<!\w)span\(")


def test_span_names_program_work_under_one_prefix():
    s = runtime.span("engine.prefill_chunk", rid=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    assert runtime.SPAN_PREFIX == "dooly."
    with s, runtime.span("engine.sync"):      # no trace recording: inert
        pass


@pytest.mark.parametrize("module", [
    "repro.serving.scheduler", "repro.sim.events", "repro.sim.metrics",
    "repro.sim.replay", "repro.sim.simulator", "repro.sim.workload"])
def test_scheduler_and_simulator_hold_no_span(module):
    """The simulator drives the scheduler millions of times in a sweep."""
    import importlib
    import inspect
    src = inspect.getsource(importlib.import_module(module))
    assert not SPAN_CALL.search(src) and "TraceAnnotation" not in src


def test_no_span_inside_a_timed_repeat():
    """A span between a repeat's start and end clocks would add to the
    latency written to the database."""
    import inspect
    lines = inspect.getsource(oracles.cpu_wallclock).splitlines()
    start = next(i for i, ln in enumerate(lines) if "t0 = time." in ln)
    end = next(i for i, ln in enumerate(lines) if "times.append(" in ln)
    assert start < end
    assert not any(SPAN_CALL.search(ln) for ln in lines[start:end + 1])
    assert any('span("oracle.timed")' in ln for ln in lines[:start])
