"""benchmark/placement.py: where a traced slice lies, as a pure function
of what the last finished job did.  The cases are ISSUE 34's table: the
five batch cells as the ledger's PR 33 lines read them (`build_ms`,
`level_ms`, a check at the caller), and the same five with the per-call
rebuild gone (h = 0: a kept engine, ROADMAP A1)."""

from __future__ import annotations

import json
import os
import sys

import pytest
from conftest import BENCH

sys.path.insert(0, BENCH)
import placement  # noqa: E402

SHARE = 0.3
# cell: (traffic mix, h, L, a check at the caller) in seconds
CELLS = {
    "kubeapi-model1.recheck": ("recheck", 2.4, 0.12, 2.5),
    "paxos-mc-sym.struct-exhaustive": ("struct-sym-exhaustive",
                                       5.7, 1.97, 7.8),
    "kubeapi-2x1ff.sharded4": ("sharded4", 8.3, 9.4, 17.8),
    "kubeapi-1x2ff.exhaustive": ("exhaustive", 5.3, 9.2, 14.7),
    "paxos-mc.struct-exhaustive": ("struct-exhaustive", 5.5, 20.4, 26.2),
}
# what the rule has to say: (mode, whole jobs in the slice)
WANT = {
    "kubeapi-model1.recheck": ("whole", 2),
    "paxos-mc-sym.struct-exhaustive": ("whole", 1),
    "kubeapi-2x1ff.sharded4": ("loop", 1),
    "kubeapi-1x2ff.exhaustive": ("loop", 1),
    "paxos-mc.struct-exhaustive": ("loop", 1),
}


def budget_of(mix: str) -> float:
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        trace = json.load(f)["trace"]
    assert trace["loop_share"] == SHARE
    return trace["busy_budget_s"]


@pytest.mark.parametrize("kept_engine", [False, True],
                         ids=["as-it-stands", "h=0"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_on_each_batch_cell(cell, kept_engine):
    mix, h, loop_s, _ = CELLS[cell]
    h = 0.0 if kept_engine else h
    budget = budget_of(mix)
    plan = placement.place(h, loop_s, budget, SHARE)
    mode, jobs = WANT[cell]
    assert (plan.mode, plan.jobs) == (mode, jobs)
    if mode == "whole":
        # from the job's start to the jobs' end: the duty cycle, and the
        # busy seconds are the loops', within the budget
        assert (plan.start_s, plan.length_s) == (0.0, None)
        assert plan.busy_s == pytest.approx(jobs * loop_s)
        assert plan.busy_s <= budget and plan.sure
    else:
        # budget long, from h + share x L: the whole slice inside the
        # loop, whose device is busy throughout
        assert plan.length_s == plan.busy_s == budget
        assert plan.start_s == pytest.approx(h + SHARE * loop_s)
        assert placement.inside(plan.start_s, plan.length_s, h, loop_s)
        assert h <= plan.start_s
        assert plan.start_s + plan.length_s < h + loop_s


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_plan_from_the_warm_job_under_a_kept_engine(cell):
    """The first timed job is placed from the warm one, which built its
    engine; with a kept engine the timed one does not (h = 0).  `sure`
    says whether the slice still lies inside the loop then: it has to
    where a window holds ONE check (Paxos), and where it does not hold
    the harness waits for a timed job's own numbers (is_last is false
    for the first of several jobs)."""
    mix, h, loop_s, job_s = CELLS[cell]
    plan = placement.place(h, loop_s, budget_of(mix), SHARE)
    if plan.mode == "whole":
        assert plan.sure
        return
    assert plan.sure == placement.inside(plan.start_s, plan.length_s, 0.0,
                                          loop_s)
    if cell == "paxos-mc.struct-exhaustive":
        assert plan.sure
        assert placement.is_last(0.0, job_s, 1, 51.0)
    else:  # five checks a window with the engine kept, two or three now
        assert not plan.sure
        assert not placement.is_last(0.0, job_s, 1, 51.0)


def test_a_loop_just_over_the_budget_is_one_whole_job():
    # no 2.0 s slice fits inside 0.3 x L .. L of a 2.5 s loop
    plan = placement.place(6.0, 2.5, 2.0, SHARE)
    assert (plan.mode, plan.jobs, plan.length_s) == ("whole", 1, None)
    assert plan.busy_s == 2.5 <= 2.0 / (1 - SHARE)
    # at 2.0 / (1 - 0.3) = 2.857 s the slice begins to fit inside
    assert placement.place(6.0, 2.85, 2.0, SHARE).mode == "whole"
    assert placement.place(6.0, 2.87, 2.0, SHARE).mode == "loop"


def test_no_loop_no_plan_and_many_tiny_jobs_are_capped():
    assert placement.place(1.0, 0.0, 2.0, SHARE) is None
    assert placement.place(1.0, None, 2.0, SHARE) is None
    assert placement.place(1.0, 0.001, 2.0, SHARE).jobs == (
        placement.MAX_WHOLE_JOBS)
    assert placement.place(-0.2, 9.0, 2.0, SHARE).start_s == pytest.approx(
        2.7)  # a negative h (clock skew) is no host part


@pytest.mark.parametrize("starts, job_s, jobs, want", [
    # the wide cell as it stands: three checks of 14.7 s, the third last
    ([0.0, 14.7, 29.4], 14.7, 1, [False, False, True]),
    # with the engine kept: five of 9.2 s
    ([0.0, 9.2, 18.4, 27.6, 36.8], 9.2, 1, [False] * 4 + [True]),
    # four chips: two of 17.8 s
    ([0.0, 17.8], 17.8, 1, [False, True]),
    # one Paxos check a window
    ([0.0], 26.2, 1, [True]),
    # reduced Paxos: six of 7.75 s
    ([7.75 * i for i in range(6)], 7.75, 1, [False] * 5 + [True]),
    # recheck: the last two of twenty
    ([2.5 * i for i in range(20)], 2.5, 2, [False] * 17 + [True] * 3),
])
def test_is_last_is_the_window_rule_asked_ahead(starts, job_s, jobs, want):
    got = [placement.is_last(t, job_s, jobs, 51.0) for t in starts]
    assert got == want
    # and it never says "not last" of a job after which the window rule
    # (loadgen.drive_closed) would start fewer than `jobs` more
    for t, last in zip(starts, got):
        more = 0
        now = t + job_s
        while 51.0 - now >= job_s and now < 51.0:
            more += 1
            now += job_s
        if more < jobs:
            assert last
