"""live_capture_ms: the liveness route's edge capture in a check - the
program's `live.capture` span: every enumerated state re-expanded on the
device, each successor's id resolved, the state-changing rows written to
the edge store in source order (one dispatch, blocked on) - median over
the window's checks.  Read through span_read.py; None where the program
records no such span (a commit before PR 41, a cfg without a
PROPERTY)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "live.capture"))
