"""Where a traced run's slice lies, as arithmetic on what the last
finished job did: no clock time of the window is written down anywhere.

A closed-loop job is host work, then the engine's loop, then a little
host work: `h` seconds from the job's start to the start of its `loop`
span, and a loop of `L` seconds in which the device is busy throughout.
The traffic mix gives policy, not times: `busy_budget_s`, the
device-busy seconds one traced run may hold (what the profiler's stop
and the reduction cost follows: benchmark/README.md), and `loop_share`,
how far into a loop a partial slice starts.

    L <= busy_budget_s                      whole jobs: from a job's start
                                            to the end of as many jobs as
                                            the budget holds; the slice
                                            reads the check's duty cycle
    loop_share * L + busy_budget_s < L      inside the loop: busy_budget_s
                                            long from h + loop_share * L
                                            after the job's start; the
                                            slice reads the loop's steady
                                            state
    else (L just over the budget)           one whole job: no slice of the
                                            budget's length fits inside,
                                            and the loop is at most
                                            busy_budget_s / (1 - loop_share)

The slice lies in the LAST job (or jobs) the window rule will start, so
that the profiler's stop and the reduction fall behind the last verdict
and no job of the window runs beside them: `is_last` is the window rule
of loadgen.drive_closed asked ahead of time, with room for a job slower
than the last one.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

MAX_WHOLE_JOBS = 8   # of one slice, however short a job's loop is
SLOWER_JOB = 1.15    # a job may take this much longer than the last one


class Plan(NamedTuple):
    mode: str        # "whole": whole jobs; "loop": inside one job's loop
    start_s: float   # from the job's start to the profiler's start
    length_s: Optional[float]  # the slice's length; None: to the jobs' end
    jobs: int        # whole jobs in the slice ("loop": 1, a part of it)
    busy_s: float    # device-busy seconds the slice should hold
    sure: bool       # holds for a host part anywhere from 0 to h


def place(h: float, loop_s: float, busy_budget_s: float,
          loop_share: float) -> Optional[Plan]:
    """The slice for a job whose last finished one spent `h` seconds on
    the host before a loop of `loop_s` seconds; None without a loop to
    go by."""
    if loop_s is None or loop_s <= 0 or busy_budget_s <= 0:
        return None
    h = max(0.0, h or 0.0)
    if loop_s <= busy_budget_s:
        n = max(1, min(int(busy_budget_s / loop_s), MAX_WHOLE_JOBS))
        return Plan("whole", 0.0, None, n, n * loop_s, True)
    into = loop_share * loop_s
    if into + busy_budget_s < loop_s:
        # with the host part gone (a kept engine) the loop starts h
        # earlier than this plan assumes: still inside it?
        sure = h + into + busy_budget_s < loop_s
        return Plan("loop", h + into, busy_budget_s, 1, busy_budget_s, sure)
    return Plan("whole", 0.0, None, 1, loop_s, True)


def is_last(now_s: float, job_s: float, jobs: int, seconds: float) -> bool:
    """Will the window rule start no more than `jobs` jobs from `now_s`
    (seconds into the window), if each takes up to SLOWER_JOB times the
    last one's `job_s`?  The rule (loadgen.drive_closed): a job starts
    while what is left is at least the last job's duration."""
    return now_s + (jobs + 1) * job_s * SLOWER_JOB > seconds


def inside(start_s: float, length_s: float, h: float, loop_s: float) -> bool:
    """Does a slice of `length_s` seconds from `start_s` after a job's
    start lie inside the loop of a job that spent `h` seconds before a
    loop of `loop_s`?  Asked of a plan ahead of time, and of the slice as
    it was taken once its job has ended."""
    return start_s >= h and start_s + length_s <= h + loop_s
