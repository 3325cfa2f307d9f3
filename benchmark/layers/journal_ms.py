"""journal_ms: the seconds a job's RunJournal spent inside its own
event() and sync() - validation, write, flush, fsync - as the
journal counted them and put on its closing span
(`check.journal_close`, `sched.journal`), median over the window's
jobs.  A counter, not a span: it lies inside loop.readback and the
entry's own time, it is a share of them and not a further term."""
from span_read import attr, median_of

CLOSING = ("check.journal_close", "sched.journal")


def read(run):
    return median_of(run, lambda rows: attr(rows, CLOSING, "seconds"))
