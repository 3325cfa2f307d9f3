"""How a reader under benchmark/layers/ gets the program's host spans.

The program records its own spans (jaxtlc/obs/spans.py: `check`,
`build`, `build.trace`, `loop.wait`, `sched.run`, ... on time.time(),
the clock loadgen stamps `start_t` / `done_t` with) in one bounded
process-wide recorder.  A reader calls `job_spans(run)` and gets, for
each correct job of the window, that job's closed spans as dicts
(name, t0, t1, parent, id, job, attrs): by `job_id` where the record has
one (the served cell: the scheduler gives every span of a dispatch the
job's id), else by containment in the record's [start_t, done_t] (the
batch cells: one caller, one check at a time).

It returns None - and the metric is then left out of the line - where
the program has no recorder (a commit before PR 24), where the recorder
dropped rows that the window may have needed, or where no job has a
span.  `median_of` is the arithmetic every span reader shares: the
median over those jobs, as the other readers take theirs.

A recorded run_view (benchmark/tests) carries the rows under
`run["spans"]` and the dropped count under `run["spans_dropped"]`; a
live run has neither key and the recorder is asked.

The harness itself reads the recorder through three more doors here:
`job_shape`, a finished job's host seconds before its `loop` span and
that loop's length (what benchmark/placement.py places a traced slice
by), `loop_started`, the instant a running job's loop began (what a
slice inside a loop waits for), and `rows_between`, the closed spans that overlap a traced slice
(what trace_reduce.py names the idle gaps by).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from stats import median

FIELDS = ("id", "name", "t0", "t1", "parent", "job", "thread", "attrs")


def _recorded(run) -> Optional[tuple]:
    """(rows as dicts, oldest first; dropped count), or None without a
    recorder."""
    if "spans" in run:
        rows, dropped = run["spans"], run.get("spans_dropped", 0)
    else:
        try:
            from jaxtlc.obs import spans
        except ImportError:
            return None
        rows, dropped = spans.snapshot(), spans.dropped
    return [dict(zip(FIELDS, r)) for r in rows], dropped


def rows_between(t0: float, t1: float) -> Optional[List[Dict]]:
    """The recorder's closed spans that overlap [t0, t1] on the host
    clock, oldest first; None without a recorder."""
    try:
        from jaxtlc.obs import spans
    except ImportError:
        return None
    return [dict(zip(FIELDS, r)) for r in spans.snapshot(since=t0)
            if r[2] <= t1]


def loop_started(since: float) -> Optional[float]:
    """When the loop of the job that began at `since` started: the start
    of its first closed `loop.dispatch` span (the loop's first segment
    dispatches as the loop opens; the `loop` span itself closes only at
    the loop's end).  None until one has closed, or without a recorder."""
    rows = rows_between(since, float("inf")) or []
    starts = [r["t0"] for r in rows if r["name"] == "loop.dispatch"
              and r["t0"] >= since]
    return min(starts) if starts else None


def job_shape(start_t: float, done_t: float, rows=None) -> Optional[tuple]:
    """(h, loop_s) of the job the caller ran from start_t to done_t:
    the seconds from its start to the start of its first `loop` span,
    and the summed length of its `loop` spans.  None without a recorder
    or where the job closed no `loop` span.  `rows`: spans already read
    (one snapshot for many jobs), else the recorder is asked."""
    if rows is None:
        rows = rows_between(start_t, done_t)
    loops = [r for r in rows or [] if r["name"] == "loop"
             and r["t0"] >= start_t and r["t1"] <= done_t]
    if not loops:
        return None
    return (min(r["t0"] for r in loops) - start_t,
            sum(r["t1"] - r["t0"] for r in loops))


def job_spans(run) -> Optional[List[List[Dict]]]:
    """The spans of each correct job of run["jobs"], or None."""
    got = _recorded(run)
    if got is None:
        return None
    rows, dropped = got
    jobs = [r for r in run["jobs"] if r.get("ok") and not r.get("findings")
            and r.get("start_t") is not None and r.get("done_t") is not None]
    if not rows or not jobs:
        return None
    if dropped and min(r["t1"] for r in rows) > min(
            j["start_t"] for j in jobs):
        return None  # the window's first rows may be among the dropped
    by_job: Dict[object, List[Dict]] = {}
    for r in rows:
        by_job.setdefault(r["job"], []).append(r)
    out = []
    for j in jobs:
        if j.get("job_id") is not None:
            mine = by_job.get(j["job_id"], [])
        else:
            mine = [r for r in rows if r["t0"] >= j["start_t"]
                    and r["t1"] <= j["done_t"]]
        if mine:
            out.append(mine)
    return out or None


def seconds(rows: List[Dict], *names: str) -> Optional[float]:
    """The summed duration of the job's spans of these names; None
    where it has none of them."""
    xs = [r["t1"] - r["t0"] for r in rows if r["name"] in names]
    return sum(xs) if xs else None


def attr(rows: List[Dict], names, key: str) -> Optional[float]:
    """Attribute `key` summed over the job's spans of these names."""
    xs = [r["attrs"][key] for r in rows
          if r["name"] in names and key in (r.get("attrs") or {})]
    return sum(xs) if xs else None


def median_of(run, per_job: Callable[[List[Dict]], Optional[float]],
              scale: float = 1e3) -> Optional[float]:
    """The median over the window's correct jobs of per_job(spans),
    times `scale` (seconds to ms by default); None where no job gives a
    number."""
    jobs = job_spans(run)
    if jobs is None:
        return None
    xs = [x for x in map(per_job, jobs) if x is not None]
    m = median(xs)
    return None if m is None else scale * m
