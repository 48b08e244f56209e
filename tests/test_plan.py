"""ProfilePlan semantics gates.

The plan-first surface must be *provably* a pure reorganization of the
imperative profiler: plan build is a deterministic dry run (same corpus
-> same task ids, zero measurements), executing a corpus plan lands rows
bit-identical to sequential per-model ``profile_model`` calls, a crashed
execute resumes from its checkpoint journal without re-measuring, and
the dry-run point accounting predicts the realized DB writes exactly.
The overlapping corpus (two models x two attention backends sharing op
and attention signatures) must dedup >= 30% of measurement tasks — the
paper's headline redundancy, visible before anything is measured.
"""
import json

import pytest

from repro.configs import get_smoke_config
from repro.core.database import LatencyDB
from repro.core.plan import build_plan, execute_plan, read_journal
from repro.core.profiler import QUICK_SWEEP, DoolyProf
from repro.core.runner import trace_model

MODELS = ("yi-9b", "command-r7b")
BACKENDS = ("xla", "chunked")
HW = "tpu-v5e"
ORACLE = "tpu_analytical"

MEAS_Q = ("SELECT * FROM measurements ORDER BY sig_hash, hardware, phase, "
          "num_toks, num_reqs, ctx_len, oracle")
SIGS_Q = "SELECT * FROM signatures ORDER BY hash"
OPS_Q = ("SELECT * FROM model_operations ORDER BY config_id, sig_hash, "
         "module")


def _tables(db: LatencyDB):
    return {q: db.conn.execute(q).fetchall()
            for q in (MEAS_Q, SIGS_Q, OPS_Q)}


@pytest.fixture(scope="module")
def corpus():
    return [get_smoke_config(m) for m in MODELS]


@pytest.fixture(scope="module")
def traces(corpus):
    return {cfg.name: trace_model(cfg) for cfg in corpus}


def _plan(db, corpus, traces, backends=BACKENDS):
    return build_plan(db, corpus, backends=backends, hardware=HW,
                      oracle=ORACLE, sweep=QUICK_SWEEP, traces=traces)


@pytest.fixture(scope="module")
def sequential_state(corpus, traces):
    """Tables after the legacy sequential corpus profile (model outer,
    backend inner — the order the old ensure_profiled loop used)."""
    with LatencyDB() as db:
        prof = DoolyProf(db, oracle=ORACLE, hardware=HW, sweep=QUICK_SWEEP)
        for cfg in corpus:
            for b in BACKENDS:
                prof.profile_model(cfg, backend=b, trace=traces[cfg.name])
        return _tables(db)


@pytest.fixture(scope="module")
def executed_state(corpus, traces, tmp_path_factory):
    """(plan, coverage, tables, checkpoint) after a clean corpus
    plan+execute on a fresh DB."""
    ckpt = str(tmp_path_factory.mktemp("plan") / "journal")
    with LatencyDB() as db:
        plan = _plan(db, corpus, traces)
        cov = plan.coverage()
        rep = execute_plan(db, plan, checkpoint=ckpt)
        return plan, cov, rep, _tables(db), ckpt


def test_plan_build_is_pure_and_deterministic(corpus, traces):
    with LatencyDB() as db:
        p1 = _plan(db, corpus, traces)
        assert db.stats()["measurements"] == 0          # dry run
        assert db.stats()["signatures"] == 0
        p2 = _plan(db, corpus, traces)
    assert p1.plan_id == p2.plan_id
    assert [t.task_id for t in p1.tasks] == [t.task_id for t in p2.tasks]
    assert [t.n_points for t in p1.tasks] == [t.n_points for t in p2.tasks]
    assert p1.models == p2.models


# The plan of this corpus before operands were drawn from a host pool:
# values, not shapes, changed, so signatures (HLO op sets) and the plan's
# id must not, or latency DBs profiled earlier would stop deduplicating.
RECORDED_PLAN_ID = "0249151af8cb9865"
RECORDED_TASK_HASHES = (
    "8d3968fc0cddc8bb", "000d241d8f1a7a5b", "f18f81b19ac8ba89",
    "dac197ed0edfd432", "e7eb36a749e4ff13", "8fecc7dd3358c3c7",
    "80cdfb37f84f98c1", "12cc35243b0b3068", "6e68a3ee6fe33d9f",
    "e6966edba444ec38", "e77c503a05c73de0", "1dde03b375c02776",
    "bea80a9bd5874733", "319f50424d77e58f", "5c30fcaa889450be",
    "2e99a9605e9f9fc6", "426a62270e33d51d", "72b3bb24486dfd2b",
    "52aefe612e579e36")


def test_plan_id_and_task_signatures_match_recorded(corpus, traces):
    with LatencyDB() as db:
        plan = _plan(db, corpus, traces)
    assert tuple(t.sig_hash[:16] for t in plan.tasks) == \
        RECORDED_TASK_HASHES
    assert plan.plan_id == RECORDED_PLAN_ID


def test_overlapping_corpus_dedups_at_least_30pct(executed_state):
    _, cov, _, _, _ = executed_state
    assert cov.naive_tasks > cov.plan_tasks
    assert cov.dedup_frac >= 0.30, (
        f"corpus dedup {100 * cov.dedup_frac:.1f}% < 30%")
    assert cov.shared_tasks > 0
    # per-model rows add up to the corpus totals
    assert sum(m.n_tasks for m in cov.models) == cov.naive_tasks
    assert sum(m.points for m in cov.models) == cov.naive_points


def test_execute_rows_bit_identical_to_sequential(sequential_state,
                                                  executed_state):
    _, _, _, plan_tables, _ = executed_state
    for q in (MEAS_Q, SIGS_Q, OPS_Q):
        assert plan_tables[q] == sequential_state[q]
    assert len(plan_tables[MEAS_Q]) > 0


def test_dry_run_points_match_realized_writes(executed_state, corpus,
                                              traces):
    plan, cov, rep, tables, _ = executed_state
    # the corpus plan's predicted write count is exactly what landed
    assert cov.plan_points == rep.rows_written == len(tables[MEAS_Q])
    # and the naive estimate is exactly what one model profiled alone
    # writes: check the first (model, backend) pair on a fresh DB
    with LatencyDB() as db:
        prof = DoolyProf(db, oracle=ORACLE, hardware=HW, sweep=QUICK_SWEEP)
        prof.profile_model(corpus[0], backend=BACKENDS[0],
                           trace=traces[corpus[0].name])
        alone = db.stats()["measurements"]
    assert cov.models[0].points == alone


def test_execute_resumes_after_crash(corpus, traces, tmp_path,
                                     executed_state):
    _, _, _, clean_tables, _ = executed_state
    ckpt = str(tmp_path / "journal")
    crash_after = 5

    class Boom(RuntimeError):
        pass

    def crashing_progress(task, i, n):
        if i >= crash_after:
            raise Boom

    with LatencyDB() as db:
        plan = _plan(db, corpus, traces)
        n_todo = len(plan.todo)
        assert n_todo > crash_after
        with pytest.raises(Boom):
            execute_plan(db, plan, checkpoint=ckpt,
                         progress=crashing_progress)
        # crashed run journaled exactly the tasks whose rows committed
        assert len(read_journal(ckpt, plan)) == crash_after
        assert db.stats()["measurements"] > 0

        # a rebuilt plan (the CLI resume path) keeps its identity even
        # though the DB now satisfies the crashed-run's completed tasks
        replan = _plan(db, corpus, traces)
        assert replan.plan_id == plan.plan_id
        assert len(replan.todo) == n_todo - crash_after

        # resuming the ORIGINAL plan object (whose satisfied flags predate
        # the crash) exercises the journal skip: completed tasks are
        # skipped by id, only the remainder is measured
        rep = execute_plan(db, plan, checkpoint=ckpt)
        assert rep.skipped_journal == crash_after
        assert rep.measured == n_todo - crash_after
        # resumed DB is indistinguishable from a never-crashed run
        assert _tables(db) == clean_tables


def test_checkpoint_refuses_foreign_plan(corpus, traces, tmp_path):
    ckpt = str(tmp_path / "journal")
    with LatencyDB() as db:
        plan_a = _plan(db, [corpus[0]], traces, backends=("xla",))
        execute_plan(db, plan_a, checkpoint=ckpt)
        plan_b = _plan(db, corpus, traces)
        with pytest.raises(RuntimeError, match="different plan"):
            read_journal(ckpt, plan_b)
        with pytest.raises(RuntimeError, match="different plan"):
            execute_plan(db, plan_b, checkpoint=ckpt)


def test_ensure_profiled_shim_matches_legacy(corpus, traces):
    from repro.api import ProfileStore
    cfg = corpus[0]
    with LatencyDB() as db:
        legacy = DoolyProf(db, oracle=ORACLE, hardware=HW,
                           sweep=QUICK_SWEEP).profile_model(
            cfg, backend="xla", trace=traces[cfg.name])
    with ProfileStore(hardware=HW, oracle=ORACLE,
                      sweep=QUICK_SWEEP) as store:
        rep = store.ensure_profiled(cfg)
        assert rep is not None
        assert store.ensure_profiled(cfg) is None       # now satisfied
        got = [(e.sig, e.name, e.group, e.variant, e.count, e.reused,
                e.cost_s) for e in rep.entries]
        want = [(e.sig, e.name, e.group, e.variant, e.count, e.reused,
                 e.cost_s) for e in legacy.entries]
        assert got == want                              # costs bitwise too
        forced = store.ensure_profiled(cfg, force=True)
        assert forced is not None
        assert all(e.reused for e in forced.entries)


def test_profile_cli_plan_json(capsys, corpus):
    from repro.profile.__main__ import main
    assert main(["plan", "--models", MODELS[0], "--json", "-"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["plan_tasks"] == payload["naive_tasks"] > 0
    assert payload["satisfied_tasks"] == 0
    assert payload["models"][0]["model"] == corpus[0].name


def test_store_plan_coverage_reflects_db(corpus, traces):
    """A second plan over a half-profiled store reports the satisfied
    tasks instead of re-measuring them."""
    from repro.api import ProfileStore
    with ProfileStore(hardware=HW, oracle=ORACLE,
                      sweep=QUICK_SWEEP) as store:
        first = store.plan([corpus[0]], traces=traces)
        store.execute(first)
        both = store.plan(corpus, traces=traces)
        cov = both.coverage()
        assert cov.satisfied_tasks == len(first.tasks)
        assert cov.plan_tasks < cov.naive_tasks
        rep = store.execute(both)
        assert rep.measured == cov.plan_tasks