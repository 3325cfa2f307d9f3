"""live_share_pct: the share of a check that is its temporal half - the
program's `live` span (the device liveness route: enumerate, capture,
masks, fixpoint, verdict; opened after `loop`, inside `check`) over its
`check` span - median over the window's checks.  The claim "the
mechanism does most of the work" as a number: over 50 in the cell that
exists for it, and gone the day a check stops reaching the route.  Read
through span_read.py; None where the program records no such span (a
commit before PR 41, a cfg without a PROPERTY)."""
from span_read import median_of, seconds


def read(run):
    def share(rows):
        live, check = seconds(rows, "live"), seconds(rows, "check")
        return live / check if live is not None and check else None

    return median_of(run, share, scale=100.0)
