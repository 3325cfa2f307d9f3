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
