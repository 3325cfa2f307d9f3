"""Host spans inside a check: the one recorder.

`span(name, **attrs)` times a block on the host clock the run journal
uses (`time.time()`) and keeps one row per closed span in a bounded
process-wide deque: (id, name, t0, t1, parent, job, thread, attrs).
Parent and job identifier come from context variables, so the spans of
one request share an identifier - the served job's id where the
scheduler set one (`job(id)`), else the ordinal of the check in the
process (`check()`).  For its duration a span also holds
`jax.profiler.TraceAnnotation("jaxtlc:" + name)`, so any profiler trace
that is running (the benchmark's slice, an operator's `-xprof`) carries
the same span on the device trace's clock.

Always on: no flag, no environment variable, no sampling.  What keeps
that honest is the budget - at most 16 spans per pooled job on the
scheduler thread, at most 13 plus 6 per segment per supervised check
(`loop.dispatch`, `loop.overlap`, `loop.wait`, and `loop.readback` over
its two halves: `loop.readback.get`, the device reads and their
decoding, and `loop.readback.emit`, the journal lines and fsyncs; 5 in
`check_with_checkpoints`, which emits in `loop.overlap`), nothing per
level, step or state.  PERF.md section 3 lists every name
with the metric it is for.

Once per check the rows closed so far go into the run journal as ONE
`spans` event (`journal_event`), which `obs.views.phase_totals` folds
into `/metrics` and tlcstat; the recorder stays the complete source.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import threading
import time
from collections import deque, namedtuple

MAX_ROWS = 65536
MAX_JOB_ROWS = 4096  # of one job's rows kept for its `spans` event

Row = namedtuple("Row", "id name t0 t1 parent job thread attrs")

_rows: deque = deque(maxlen=MAX_ROWS)
_lock = threading.Lock()
_ids = itertools.count(1)
_checks = itertools.count(1)
_current = contextvars.ContextVar("jaxtlc_span", default=None)
_job = contextvars.ContextVar("jaxtlc_span_job", default=None)
_annotation = None  # jax.profiler.TraceAnnotation, imported at first use
dropped = 0  # rows pushed out of the deque since start


class span:
    """Context manager recording one host span.  `attrs` may be added
    to until it closes (`build.compile`'s CompileMeter deltas, the
    journal's cost on the span around its close); `seconds` stays
    readable afterwards, so a caller that needs the duration it just
    measured does not time the block a second time."""

    __slots__ = ("id", "name", "attrs", "job", "parent", "t0", "t1",
                 "_ctx", "_token", "_trace")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.t1 = None

    def __enter__(self) -> "span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        outer = _current.get()
        self.id = next(_ids)
        self.parent = outer.id if outer is not None else 0
        self._ctx = _job.get()
        self.job = self._ctx.id if self._ctx is not None else None
        self._token = _current.set(self)
        self._trace = _annotation("jaxtlc:" + self.name)
        self._trace.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, *exc) -> bool:
        global dropped
        self.t1 = time.time()
        self._trace.__exit__(*exc)
        _current.reset(self._token)
        row = Row(self.id, self.name, self.t0, self.t1, self.parent,
                  self.job, threading.get_ident(), self.attrs)
        if self._ctx is not None and len(self._ctx.rows) < MAX_JOB_ROWS:
            self._ctx.rows.append(row)
        with _lock:
            dropped += len(_rows) == MAX_ROWS
            _rows.append(row)
        return False

    @property
    def seconds(self) -> float:
        return (self.t1 if self.t1 is not None else time.time()) - self.t0


def annotate(**attrs) -> None:
    """Add `attrs` to the innermost span open in this context; with none
    open, nothing.  For what only a callee knows about its caller's
    span: `struct.loader.load` tells the span around it (`sched.load`,
    `check.resolve`) whether the model was kept, `memo` = hit | miss."""
    outer = _current.get()
    if outer is not None:
        outer.attrs.update(attrs)


class job:
    """Every span opened in this context belongs to job `job_id`; the
    context keeps that job's closed rows, so writing them into the
    journal costs their number, not a scan of the deque."""

    in_check = False  # a `check` span is open in this job (check())

    def __init__(self, job_id):
        self.id = job_id
        self.rows = []

    def __enter__(self) -> "job":
        self._token = _job.set(self)
        return self

    def __exit__(self, *exc) -> bool:
        _job.reset(self._token)
        return False


class check:
    """The `check` span of one check, whatever the entry.  An entry
    called inside another's (`api.run_check` -> `supervise`) shares the
    outer one; the outermost opens it and, where no served job id is
    set, names the job by the ordinal of the check in the process."""

    def __enter__(self):
        ctx = _job.get()
        self._job = self._span = None
        if ctx is not None and ctx.in_check:
            return None
        if ctx is None:
            ctx = self._job = job(f"check-{next(_checks)}")
            ctx.__enter__()
        ctx.in_check = True
        self._ctx = ctx
        self._span = span("check")
        return self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        if self._span is not None:
            self._span.__exit__(*exc)
            self._ctx.in_check = False
        if self._job is not None:
            self._job.__exit__(*exc)
        return False


def in_check(fn):
    """Decorator: the whole call runs inside `check()`."""
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with check():
            return fn(*args, **kw)
    return wrapped


def snapshot(since: float = None) -> list:
    """The closed rows, oldest first; `since` keeps those that ended at
    or after that time."""
    with _lock:
        rows = list(_rows)
    return rows if since is None else [r for r in rows if r.t1 >= since]


def journal_event() -> dict:
    """The `spans` journal event's fields for the job of this context:
    `rows` = [[name, t0, dur_s, parent_index], ...] over its spans
    closed by now (parent_index is -1 where the parent is still open -
    `check`, `sched.run` - or is no row of this job), and `attrs` =
    {str(row index): that span's attributes} for the rows that carry
    any (`build`'s `engine_cache`, `build.compile`'s meter deltas, a
    journal close's cost)."""
    ctx = _job.get()
    rows = list(ctx.rows) if ctx is not None else []
    index = {r.id: i for i, r in enumerate(rows)}
    return dict(
        rows=[[r.name, r.t0, round(r.t1 - r.t0, 6),
               index.get(r.parent, -1)] for r in rows],
        attrs={str(i): dict(r.attrs) for i, r in enumerate(rows)
               if r.attrs})
