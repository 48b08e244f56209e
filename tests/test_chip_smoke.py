"""chip_smoke.py's phases at smoke width on the CPU backend.

The script itself refuses to run without a TPU; these tests call its
phase functions directly, with smoke configs and interpret-mode kernels,
so the path the chip runs is exercised on every test run."""
import importlib.util
import os

import jax
import pytest

from repro.api import ProfileStore
from repro.configs import get_smoke_config
from repro.core.plan import PlanExecutionError
from repro.core.profiler import SweepConfig
from repro.serving.scheduler import SchedulerConfig

_PATH = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

SCHED = SchedulerConfig(max_num_seqs=4, max_batch_tokens=128, chunk_size=64)
MAX_SEQ = 256
SWEEP = SweepConfig(toks=(8, 64), reqs=(1, 4), ctx=(MAX_SEQ,),
                    op_points=((1, 4), (8, 1), (32, 1), (64, 1)))
TRACE = dict(n=6, scale=0.05)


def _store():
    return ProfileStore(hardware=jax.devices()[0].device_kind,
                        oracle="cpu_wallclock", sweep=SWEEP)


def test_device_phase_refuses_a_machine_without_tpu():
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device()


def test_kernel_phase_matches_references_in_interpret_mode():
    cases = smoke.kernel_cases(get_smoke_config, seq=128, smax=256,
                               scan_len=32)
    errs = smoke.phase_kernels(cases)
    assert len(errs) == 4 and max(errs.values()) <= smoke.BF16_TOL


def test_profile_phase_replans_to_zero_tasks():
    cfg = get_smoke_config(smoke.MODEL)
    with _store() as store:
        out = smoke.phase_profile(store, cfg)
    assert out["tasks"] > 0 and out["points"] > 0
    assert out["replan_tasks"] == 0


def test_profile_phase_fails_on_a_quarantined_task(monkeypatch):
    monkeypatch.setenv("REPRO_MEASURE_SHIM", "_faults:shim")
    monkeypatch.setenv("REPRO_FAULT_MODE", "error")
    cfg = get_smoke_config(smoke.MODEL)
    with _store() as store, pytest.raises(PlanExecutionError):
        smoke.phase_profile(store, cfg)


def test_serve_and_simulate_phases_at_smoke_width():
    cfg = get_smoke_config(smoke.MODEL)
    with _store() as store:
        smoke.phase_profile(store, cfg)
        served = smoke.phase_serve(cfg, SCHED, MAX_SEQ, **TRACE)
        assert served["logits_err"] <= smoke.BF16_TOL
        assert len(served["metrics"]["ttft"]) == TRACE["n"]
        err = smoke.phase_simulate(store, cfg, served, SCHED, MAX_SEQ,
                                   **TRACE)
    assert all(v == v for v in err.values())            # no NaN
