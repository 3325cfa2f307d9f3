"""Dijkstra's EWD998 as tlaplus/Examples publishes it, bounded by its
cfg's `CONSTRAINT StateConstraint` (ISSUE 39): the unmodified
specs/EWD998.toolbox/Model_1 files through `api.run_check -frontend
struct`, the served path and `cli check`, against the host interpreter
(struct/eval.py + struct/oracle.py) and the plain reference
(benchmark/reference/ewd998.py), at N = 2 on the CPU (N = 3, the
benchmark cell's rung, is marked slow)."""

import io
import json
import os
import sys

import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.struct.loader import load
from jaxtlc.struct.oracle import bfs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = os.path.join(REPO, "specs", "EWD998.toolbox", "Model_1")
CFG = os.path.join(MODEL, "MC.cfg")
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))

N2 = dict(generated=31184, distinct=6236, depth=29, discarded=2032,
          action_generated={"InitiateProbe": 10562, "PassToken": 486,
                            "SendMsg": 5950, "RecvMsg": 8220,
                            "Deactivate": 5950})


def reference(n):
    import ewd998

    with open(os.path.join(REPO, "benchmark", "configs",
                           "ewd998-mc.json")) as f:
        return ewd998.pins_for(json.load(f), n=n)


def engine(n, **kw):
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=CFG, frontend="struct", workers="cpu", noTool=True,
        out=out, err=out, **{**dict(constants={"N": n}, chunk=256,
                                    qcap=8192, fpcap=32768), **kw}))
    assert o.verdict == "ok", out.getvalue()[-600:]
    return o.result


def five(r):
    return dict(generated=r.generated, distinct=r.distinct, depth=r.depth,
                discarded=r.constraint_discarded,
                action_generated=dict(r.action_generated))


def test_the_shipped_files_are_the_sources_model():
    m = load(CFG)
    assert list(m.constraints) == ["StateConstraint"]
    assert list(m.invariants) == ["TypeOK", "Inv", "TerminationDetection"]
    assert m.constants["N"] == 3 and m.root_name == "EWD998"
    assert m.system.variables == ("active", "color", "counter",
                                  "pending", "token")
    assert len(m.system.initial_states()) == 64
    with open(CFG) as f:
        assert "CONSTRAINT" in f.read()


@pytest.fixture(scope="module")
def n2_runs():
    """The module's two engine runs at N = 2, each made once: the
    immediate and the deferred invariant mode ({deferred: result})."""
    return {False: engine(2), True: engine(2, deferredinv=True)}


def test_engine_interpreter_and_reference_agree_at_n2(n2_runs):
    """All five numbers, three ways: generated, distinct, depth, the
    per-action totals and the discards."""
    want = reference(2)
    assert {k: want[k] for k in N2} == N2
    m = load(CFG, const_overrides={"N": 2})
    host = bfs(m.system, m.invariants, check_deadlock=False,
               constraints=m.constraints)
    assert host.violations == []
    assert dict(generated=host.generated, distinct=host.distinct,
                depth=host.depth, discarded=host.discarded,
                action_generated=host.action_generated) == N2
    r = n2_runs[False]
    assert five(r) == N2
    assert r.constraint_rows == N2["generated"] - 16
    assert (r.step_lanes, r.step_slots, r.struct_traps) == (10, 10, 0)
    assert r.state_words <= 2  # a fingerprint of at most 64 message bits
    assert r.constraint_names == ("StateConstraint",)


def test_the_deferred_invariant_mode_agrees_at_n2(n2_runs):
    assert five(n2_runs[True]) == N2


@pytest.mark.parametrize("deferred", [False, True],
                         ids=["immediate", "deferred"])
def test_the_commits_own_counts_on_a_constrained_struct_model(
        n2_runs, deferred):
    """ISSUE 50's block on the struct path, where a CONSTRAINT masks
    the insert: the valid lanes are what was generated less the
    initial states less the discards; the claims and the walk's new
    rows are the kept states less the kept initial ones; both ladder
    sorts ran once a body; and the deferred checker's trips are counted
    only where the mode runs one - the same counts otherwise."""
    r = n2_runs[deferred]
    n_init = 16
    assert r.commit_valid == (
        r.generated - n_init - r.constraint_discarded)
    new = r.distinct - n_init  # every initial state is kept at N = 2
    assert r.commit_new == new
    assert r.commit_claimed <= new <= (
        r.commit_claimed + r.commit_stragglers) <= r.commit_reps
    assert sum(r.commit_compact_rung) == r.commit_bodies > 0
    assert sum(r.commit_enqueue_rung) == r.commit_bodies
    assert (r.commit_width, r.commit_probe_width) == (256 * r.step_slots,
                                                      512)
    if deferred:
        # a trip a probe segment: the checker walks the
        # representatives, not the new rows (PERF.md 7-20a)
        assert r.commit_checker_trips == r.commit_probe_segments > 0
    else:
        assert r.commit_checker_trips == 0
    other = n2_runs[not deferred]
    assert {f: getattr(r, f) for f in r._fields
            if f.startswith("commit_") and "checker" not in f} == {
        f: getattr(other, f) for f in r._fields
        if f.startswith("commit_") and "checker" not in f}


def test_preflight_names_the_constraint_of_the_unmodified_cfg():
    """The preflight report of `cli check MC.cfg -frontend struct` names
    the cfg's CONSTRAINT leaf by leaf, and refuses nothing here."""
    from jaxtlc.analysis.preflight import preflight_struct

    rep = preflight_struct(load(CFG), fp_capacity=1 << 22, chunk=4096,
                           queue_capacity=1 << 18)
    text = "\n".join(rep.constraint_lines)
    assert "CONSTRAINT StateConstraint" in text and not rep.errors
    for leaf in ("counter[0]: <= 3 by StateConstraint",
                 "pending[2]: <= 3 by StateConstraint",
                 "token.q: <= 9 by StateConstraint"):
        assert leaf in text
    assert "token.pos: -1..2 by inference alone" in text


def test_a_served_job_runs_the_constrained_model(tmp_path):
    """A job posted to the scheduler loads the same files; the pool
    route hands a constrained model on to api.run_check, whose journal
    names the constraint and counts what it discards."""
    from jaxtlc.obs import journal as jrn
    from jaxtlc.serve.scheduler import Scheduler

    with open(os.path.join(MODEL, "EWD998.tla")) as f:
        spec = f.read()
    with open(CFG) as f:
        cfg = f.read()
    sched = Scheduler(str(tmp_path))
    try:
        job = sched.submit(spec, cfg, name="ewd998-n2",
                           constants={"N": 2},
                           options=dict(chunk=256, qcap=8192, fpcap=32768))
        assert sched.drain(timeout=600)
    finally:
        sched.shutdown()
    assert job.state == "done", job.error
    assert job.result["engine"] != "pool"
    assert (job.result["generated"], job.result["distinct"],
            job.result["depth"]) == (31184, 6236, 29)
    events = jrn.read(os.path.join(str(tmp_path),
                                   f"{job.id}.journal.jsonl"))
    final = next(e for e in events if e["event"] == "final")
    assert final["constraint_discarded"] == 2032
    start = next(e for e in events if e["event"] == "run_start")
    assert start["params"]["constraints"] == ["StateConstraint"]


@pytest.mark.slow
def test_the_benchmark_cells_rung_matches_its_pins():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ewd998-mc.json")) as f:
        config = json.load(f)
    r = engine(3, constants=None, chunk=4096, qcap=1 << 18,
               fpcap=1 << 22)
    pins = config["pins"]
    assert (r.generated, r.distinct, r.depth) == (
        pins["generated"], pins["distinct"], pins["depth"])
    assert dict(r.action_generated) == pins["action_generated"]
    assert r.constraint_discarded == 1436680
    assert (r.step_lanes, r.struct_traps) == (17, 0)
