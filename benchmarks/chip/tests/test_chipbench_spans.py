"""The program's spans as a trace records them, and the readers of
``chipbench.spans``: by hand, on the CPU, and on a trace from the chip."""
import json

import jax
import pytest

from _chipbench_smoke import BENCH, CPU_PEAKS, smoke_cell
from chipbench import spans, trace

SAMPLE = BENCH / "tests" / "data" / "trace_v5e_spans.json"
MS = 1_000_000


def _inside(outer, inner):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def _named(events, name):
    return [e for e in events["program"] if e[0] == "dooly." + name]


def _covered(parent, children):
    merged = trace._union([(s, s + d) for _, s, d in children
                           if _inside(parent, (None, s, d))])
    return sum(e - s for s, e in merged)


# ---------------------------------------------------------------------------
# the program's spans, recorded on the CPU
# ---------------------------------------------------------------------------

def _serve(eng, prompts, calls):
    from repro.serving.scheduler import Request
    for i, n in enumerate(prompts):
        eng.sched.add_request(Request(rid=100 * len(calls) + i, arrival=0.0,
                                      prompt=[1 + j % 50 for j in range(n)],
                                      max_new_tokens=4))
    while eng.sched.has_work():
        plan = eng.sched.schedule()
        before = len(calls)
        eng.execute(plan)
        assert len(calls) - before == len(plan.prefills) + bool(plan.decodes)
        eng.sched.complete_iteration(plan, 0.0)


def test_engine_execute_spans_cover_the_iteration():
    from repro.configs import get_smoke_config
    from repro.serving.engine import Engine
    from repro.serving.scheduler import SchedulerConfig
    eng = Engine(get_smoke_config("minicpm3-4b"), max_seq=128,
                 sched_config=SchedulerConfig(max_num_seqs=4,
                                              max_batch_tokens=64,
                                              chunk_size=32))
    calls = []

    def counted(fn):
        def wrapped(*a):
            calls.append(fn)
            return fn(*a)
        return wrapped
    eng._decode_fn = counted(eng._decode_fn)
    eng._chunk_fns = {b: counted(f) for b, f in eng._chunk_fns.items()}
    _serve(eng, [40, 20, 9], calls)                 # every eager op once
    rec = trace.Recorder()
    rec.start()
    _serve(eng, [45, 30, 12, 5], calls)
    rec.stop()
    ev = rec.events()
    its = _named(ev, "engine.execute")
    assert its
    steps = _named(ev, "engine.prefill_chunk") + _named(ev, "engine.decode")
    assert len(_named(ev, "engine.sync")) == len(steps)
    assert len(_named(ev, "engine.dispatch")) == len(steps)
    for e in ev["program"]:
        assert any(_inside(it, e) for it in its), e
    for step in steps:
        inner = [e for e in ev["program"] if e is not step
                 and _inside(step, e)]
        assert sum(e[0] == "dooly.engine.sync" for e in inner) == 1
    leaves = [e for e in ev["program"] if e[0] in {
        "dooly.engine." + n for n in ("inputs", "dispatch", "sync",
                                      "write_row", "readback")}]
    total = sum(d for _, _, d in its)
    assert sum(_covered(it, steps) for it in its) >= 0.9 * total
    assert sum(_covered(it, leaves) for it in its) >= 0.9 * total


def test_engine_step_programs_have_stable_names():
    from repro.configs import get_smoke_config
    from repro.serving.engine import Engine
    from repro.serving.scheduler import SchedulerConfig
    eng = Engine(get_smoke_config("minicpm3-4b"), max_seq=64,
                 sched_config=SchedulerConfig(max_num_seqs=2,
                                              max_batch_tokens=16,
                                              chunk_size=8))
    r = eng.sched.config.max_num_seqs
    toks = jax.numpy.zeros((r,), jax.numpy.int32)
    text = eng._decode_fn.lower(eng.params, eng.cache, toks,
                                eng.lengths).as_text()
    assert "@jit_decode_step" in text
    z = jax.numpy.zeros((1,), jax.numpy.int32)
    text = eng._chunk_fn(8).lower(eng.params, eng._row_cache(0),
                                  jax.numpy.zeros((1, 8), jax.numpy.int32),
                                  z, z).as_text()
    assert "@jit_prefill_chunk" in text


def test_profile_execute_spans_each_oracle_call(monkeypatch):
    from repro.api import ProfileStore
    from repro.configs import get_smoke_config
    from repro.core import backends
    from repro.core.profiler import SweepConfig
    sweep = SweepConfig(toks=(8,), reqs=(1,), ctx=(32,), op_points=((8, 1),))
    store = ProfileStore(hardware="cpu", oracle="cpu_wallclock", sweep=sweep)
    with store:
        plan = store.plan([get_smoke_config("llama3-8b")], backends=("xla",))
        orig = backends.ORACLES["cpu_wallclock"]
        n_calls = []

        def counted(fn, args, **kw):
            n_calls.append(1)
            return orig(fn, args, **kw)
        monkeypatch.setitem(backends.ORACLES, "cpu_wallclock", counted)
        rec = trace.Recorder()
        rec.start()
        rep = store.execute(plan)
        rec.stop()
    ev = rec.events()
    timed = _named(ev, "oracle.timed")
    assert len(n_calls) > 0 and rep.measured > 0
    assert len(timed) == len(_named(ev, "oracle.first_call")) == len(n_calls)
    assert len(_named(ev, "profile.task")) == rep.measured
    assert len(_named(ev, "profile.commit")) == rep.measured
    # no span inside the timed repeats: it would add to the latency
    for t in timed:
        assert not [e for e in ev["program"] if e is not t and _inside(t, e)]
        assert any(_inside(task, t) for task in _named(ev, "profile.task"))
    assert _named(ev, "profile.operands") and _named(ev, "profile.context")


def test_traced_cell_run_keeps_program_spans():
    import time
    cell = smoke_cell("minicpm3-4b.chat")
    recorder = trace.Recorder
    res, ev = spans.execute_traced(cell, 2**33 + 7, 3.0,
                                   t_process=time.perf_counter(),
                                   platform=None, peaks=CPU_PEAKS)
    assert res["correct"], res["checks"]
    assert trace.Recorder is recorder
    out = spans.report(ev, "serve")
    assert out["engine.host_ms_per_iter"] > 0
    assert out["engine.spans_per_iter"] >= 6


# ---------------------------------------------------------------------------
# the readers, on hand-built events
# ---------------------------------------------------------------------------

def _hand_events():
    """A 100 ms window: two iterations, the second with a chunk; the device
    runs the decode program twice and the chunk program once."""
    return {
        "host": [["chipbench.window", 0, 100 * MS],
                 ["chipbench.execute", 0, 45 * MS],
                 ["chipbench.execute", 50 * MS, 50 * MS]],
        "program": [
            ["dooly.engine.execute", 1 * MS, 38 * MS],
            ["dooly.engine.decode", 1 * MS, 38 * MS],
            ["dooly.engine.inputs", 1 * MS, 4 * MS],
            ["dooly.engine.dispatch", 5 * MS, 1 * MS],
            ["dooly.engine.sync", 6 * MS, 20 * MS],
            ["dooly.engine.readback", 26 * MS, 13 * MS],
            ["dooly.engine.execute", 51 * MS, 48 * MS],
            ["dooly.engine.prefill_chunk", 51 * MS, 20 * MS],
            ["dooly.engine.sync", 55 * MS, 10 * MS],
            ["dooly.engine.decode", 71 * MS, 28 * MS],
            ["dooly.engine.sync", 75 * MS, 14 * MS],
            ["dooly.engine.readback", 89 * MS, 10 * MS],
            # outside the window: not read
            ["dooly.engine.execute", 200 * MS, 10 * MS],
        ],
        "device": [["/device:TPU:0", "fusion.1", 6 * MS, 19 * MS],
                   ["/device:TPU:0", "fusion.2", 55 * MS, 9 * MS],
                   ["/device:TPU:0", "fusion.1", 75 * MS, 13 * MS]],
        "modules": [["/device:TPU:0", "jit_decode_step(12)", 6 * MS, 19 * MS],
                    ["/device:TPU:0", "jit_prefill_chunk(3)", 55 * MS,
                     9 * MS],
                    ["/device:TPU:0", "jit_decode_step(12)", 75 * MS,
                     13 * MS],
                    ["/device:TPU:0", "jit_decode_step(12)", 150 * MS, MS]],
    }


def test_host_time_per_iteration_by_hand():
    # 38 - 20 = 18 ms and 48 - 10 - 14 = 24 ms: median 21
    assert spans.host_ms_per_iter(_hand_events()) == pytest.approx(21.0)
    assert spans.spans_per_iter(_hand_events()) == pytest.approx(12 / 2)


def test_program_device_time_by_hand():
    ev = _hand_events()
    assert spans.program_device_ms(ev, "decode_step") == pytest.approx(16.0)
    assert spans.program_device_ms(ev, "prefill_chunk") == pytest.approx(9.0)
    assert spans.program_device_ms(ev, "other") is None


def test_span_share_by_hand():
    ev = _hand_events()
    # syncs: 20 + 10 + 14 = 44 ms of 100
    assert spans.span_share(ev, "engine.sync") == pytest.approx(44.0)
    assert spans.span_share(ev, "engine.decode") == pytest.approx(66.0)
    assert spans.span_share(ev, "oracle.timed") == pytest.approx(0.0)


def test_gaps_are_named_by_the_innermost_program_span():
    ev = _hand_events()
    gaps = spans.idle_gaps(ev, top=4)
    # [25, 55) ms: mid 40, after the first iteration's spans end (39 ms),
    # so the benchmark's own span names it
    assert gaps[0] == ["execute", pytest.approx(0.030)]
    # [88, 100) ms: mid 94, in the second readback
    assert gaps[1] == ["engine.readback", pytest.approx(0.012)]
    # [64, 75) ms: mid 69.5, in the chunk, outside its sync
    assert gaps[2] == ["engine.prefill_chunk", pytest.approx(0.011)]
    # [0, 6) ms: mid 3, in the first iteration's inputs
    assert gaps[3] == ["engine.inputs", pytest.approx(0.006)]
    # the harness's own reduction names them alike
    assert trace.reduce_events(ev, top=4)["idle_gaps"] == gaps
    # without the program's spans, by the benchmark's alone
    ev["program"] = []
    assert [g[0] for g in trace.reduce_events(ev, top=4)["idle_gaps"]] == \
        ["execute"] * 4


def test_compile_seconds_by_span():
    ev = dict(_hand_events(), compiles=[
        ["backend_compile_duration", 0.5, 3 * MS],
        ["jaxpr_trace_duration", 0.25, 27 * MS],
        ["jaxpr_trace_duration", 0.125, 42 * MS]])
    assert spans.compile_by_span(ev) == {"engine.inputs": 0.5,
                                         "engine.readback": 0.25,
                                         "execute": 0.125}


@pytest.mark.parametrize("reader", [
    spans.host_ms_per_iter, spans.spans_per_iter, spans.idle_gaps,
    spans.compile_by_span,
    lambda ev: spans.program_device_ms(ev, "decode_step"),
    lambda ev: spans.span_share(ev, "oracle.timed")],
    ids=["host_ms_per_iter", "spans_per_iter", "idle_gaps",
         "compile_by_span", "program_device_ms", "span_share"])
def test_readers_give_none_without_program_spans(reader):
    ev = _hand_events()
    ev["program"], ev["modules"] = [], []
    assert reader(ev) is None
    # events without either key (a trace recorded before the program had
    # spans)
    del ev["program"], ev["modules"]
    assert reader(ev) is None
    assert spans.report(ev, "serve") == {}


# ---------------------------------------------------------------------------
# a trace recorded on the chip
# ---------------------------------------------------------------------------

def test_trace_recorded_on_the_chip_names_the_programs():
    if not SAMPLE.is_file():
        pytest.fail(f"missing {SAMPLE}")
    ev = json.loads(SAMPLE.read_text())
    r = trace.reduce_events(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < spans.program_device_ms(ev, "decode_step") < 1e3
    assert 0 < spans.program_device_ms(ev, "prefill_chunk") < 1e3
    assert spans.host_ms_per_iter(ev) > 0
    gaps = spans.idle_gaps(ev)
    assert gaps and all(g[0].startswith("engine.") for g in gaps)


def test_idle_share_of_the_chip_trace_is_the_same_with_program_spans():
    """The harness reads the program's spans since they name idle gaps;
    busy time, the window and so every ``device_idle_share.*`` reading
    are what the benchmark's spans alone gave."""
    from chipbench import readers
    if not SAMPLE.is_file():
        pytest.fail(f"missing {SAMPLE}")
    ev = json.loads(SAMPLE.read_text())
    before = dict(ev, program=[], modules=[])
    r0, r1 = trace.reduce_events(before), trace.reduce_events(ev)
    assert (r0["busy_s"], r0["window_s"]) == (r1["busy_s"], r1["window_s"])
    assert r0["device_ops"] == r1["device_ops"]

    class Run:
        pass
    shares = []
    for r in (r0, r1):
        run = Run()
        run.trace = r
        shares.append(readers.idle_share(run))
    assert shares[0] == shares[1] and 0 < shares[0] < 100
    assert {g[0] for g in r0["idle_gaps"]} <= {"execute", "none"}
    assert all(g[0].startswith("engine.") for g in r1["idle_gaps"])


@pytest.mark.parametrize("name,kind,field", [
    ("profile.timed_share", "profile", "profile.timed_share"),
    ("profile.operand_share", "profile", "profile.operand_share"),
    ("engine.host_ms_per_iter.chat", "serve", "engine.host_ms_per_iter"),
    ("engine.host_ms_per_iter.batch", "serve", "engine.host_ms_per_iter"),
    ("engine.decode_device_ms.chat", "serve", "engine.decode_device_ms"),
    ("engine.decode_device_ms.batch", "serve", "engine.decode_device_ms")])
def test_span_metric_files_read_the_spans_readers(name, kind, field):
    """Each span metric of ``BENCHMARK.json`` is its ``chipbench.spans``
    reader on a traced run's events, and nothing on an untraced run."""
    from chipbench import cells

    class Run:
        trace_events = _hand_events()
    read = cells.metric_reader(name)
    assert read(Run()) == spans.READERS[kind][field](Run.trace_events)
    Run.trace_events = None
    assert read(Run()) is None
