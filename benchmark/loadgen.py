"""The one traffic generator: reads a traffic mix (a data file under
benchmark/traffic/) and drives jobs through an entry.

A mix is parameters only.  `loop: "closed"` is one caller sending the
next job when the last verdict is in hand; the window rule is: the first
job always starts, and a later one starts only while time is left in the
window and what is left is at least the last job's duration - no job is
started that the window cannot hold.  `loop: "open"` sends jobs on a
schedule whatever the system does: `rate_per_s` Poisson arrivals over
the window, each timed from the instant it was due, through
`client_threads` threads.

The seed changes the order and never the work: the number of arrivals,
the sequence of gaps between them (drawn once from the mix's own
`base_seed`) and the count of jobs of each class and tenant are the same
for every seed.  `--seed` ROTATES the gap sequence (where in the one
cyclic pattern the window starts) and shuffles classes and tenants.  It
does not shuffle the gaps: in front of a queue the order of the gaps is
work - a run of short gaps is a backlog - and a shuffled order moved the
tail by a factor of two from seed to seed on identical multisets.
"""

from __future__ import annotations

import concurrent.futures
import random
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional


class Draw(NamedTuple):
    """One job as the generator drew it."""

    index: int
    due_s: Optional[float]  # offset from window start; None = closed loop
    klass: str
    tenant: str
    options: dict


def _apportion(weighted: List[dict], n: int) -> List[dict]:
    """n items split over `weighted` by largest remainder: the counts are
    a function of n and the weights alone."""
    total = float(sum(w["weight"] for w in weighted))
    exact = [n * w["weight"] / total for w in weighted]
    counts = [int(x) for x in exact]
    by_rem = sorted(range(len(weighted)),
                    key=lambda i: (exact[i] - counts[i], -i), reverse=True)
    for i in by_rem[: n - sum(counts)]:
        counts[i] += 1
    out = []
    for w, c in zip(weighted, counts):
        out += [w] * c
    return out


def schedule(traffic: dict, seed: int, seconds: float) -> List[Draw]:
    """Every job of a run, from the mix, the seed and the window."""
    rng = random.Random(int(seed))
    classes = traffic.get("classes") or [
        dict(name="job", weight=1, options={})]
    tenants = traffic.get("tenants") or [dict(name="default", weight=1)]
    if traffic["loop"] == "closed":
        n = 4096  # more than any window holds: the window rule ends it
        due: List[Optional[float]] = [None] * n
    else:
        arr = traffic["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        n = max(1, int(round(float(arr["rate_per_s"]) * seconds)))
        base = random.Random(int(traffic.get("base_seed", 0)))
        gaps = [base.expovariate(1.0) for _ in range(n)]
        k = rng.randrange(n)
        gaps = gaps[k:] + gaps[:k]
        # the last arrival falls inside the window: the gaps fill
        # n/(n+1) of it
        scale = seconds * n / (n + 1) / sum(gaps)
        t, due = 0.0, []
        for g in gaps:
            t += g * scale
            due.append(t)
    ks = _apportion(classes, n)
    ts = _apportion(tenants, n)
    rng.shuffle(ks)
    rng.shuffle(ts)
    return [Draw(i, due[i], ks[i]["name"], ts[i]["name"],
                 dict(ks[i].get("options") or {})) for i in range(n)]


def drive_closed(run_job: Callable[[Draw], dict], draws: List[Draw],
                 seconds: float, clock=time.time,
                 hooks=None) -> List[dict]:
    """One caller, back to back, under the window rule.  `hooks`, a
    traced run's (before(index), after(index, start, done)), are called
    on the caller's thread outside the job's own time; what they take
    comes off the window."""
    t0 = clock()
    records: List[dict] = []
    last = 0.0
    before, after = hooks or (None, None)
    for d in draws:
        now = clock() - t0
        if records and (now >= seconds or seconds - now < last):
            break
        if before:
            before(d.index)
        start = clock()
        rec = dict(run_job(d))
        done = clock()
        if after:
            after(d.index, start, done)
        rec.update(index=d.index, klass=d.klass, tenant=d.tenant,
                   due_t=start, start_t=start, done_t=done)
        last = done - start
        records.append(rec)
    return records


def drive_open(run_job: Callable[[Draw], dict], draws: List[Draw],
               seconds: float, threads: int, drain_s: float,
               clock=time.time, sleep=time.sleep) -> List[dict]:
    """Jobs at their due times, whatever the system does.  A job that
    has no verdict `drain_s` after the window closed is failed."""
    t0 = clock()
    lock = threading.Lock()
    records: Dict[int, dict] = {}

    def one(d: Draw):
        start = clock()
        try:
            rec = dict(run_job(d))
        except Exception as e:  # a refused or broken job is a failed job
            rec = dict(ok=False, why=f"{type(e).__name__}: {e}")
        done = clock()
        rec.update(index=d.index, klass=d.klass, tenant=d.tenant,
                   due_t=t0 + d.due_s, start_t=start, done_t=done)
        with lock:
            records[d.index] = rec

    pool = concurrent.futures.ThreadPoolExecutor(max_workers=threads)
    futures = []
    try:
        for d in draws:
            wait = t0 + d.due_s - clock()
            if wait > 0:
                sleep(wait)
            futures.append(pool.submit(one, d))
        concurrent.futures.wait(
            futures, timeout=max(0.0, t0 + seconds + drain_s - clock()))
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
    with lock:
        out = dict(records)
    for d in draws:
        if d.index not in out:
            out[d.index] = dict(
                ok=False, why="no verdict by the end of the drain",
                index=d.index, klass=d.klass, tenant=d.tenant,
                due_t=t0 + d.due_s, start_t=None, done_t=None)
    return [out[d.index] for d in draws]


def drive(run_job: Callable[[Draw], dict], traffic: dict, seed: int,
          seconds: float, hooks=None) -> List[dict]:
    draws = schedule(traffic, seed, seconds)
    if traffic["loop"] == "closed":
        return drive_closed(run_job, draws, seconds, hooks=hooks)
    if traffic["loop"] == "open":
        return drive_open(run_job, draws, seconds,
                          threads=int(traffic.get("client_threads", 8)),
                          drain_s=float(traffic.get("drain_s", 30.0)))
    raise ValueError(f"unknown loop {traffic['loop']!r}")
