"""live_fixpoint_ms: the fair-cycle analysis in a check - every
`live.fixpoint` span (one a property: Emerson and Lei's nested fixpoint
as sweeps over the edge store, one dispatch, blocked on, and its stats
vector read back) summed - median over the window's checks.  Read
through span_read.py; None where the program records no such span (a
commit before PR 41, a cfg without a PROPERTY)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "live.fixpoint"))
