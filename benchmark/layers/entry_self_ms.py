"""entry_self_ms: the `check` span less its `build` and `loop` spans -
resolution, preflight, journal open and close, verdict rendering and
whatever else the entry does outside build and loop - median over the
window's checks."""
from span_read import median_of, seconds


def _self(rows):
    whole = seconds(rows, "check")
    if whole is None:
        return None
    return whole - (seconds(rows, "build") or 0.0) - (
        seconds(rows, "loop") or 0.0)


def read(run):
    return median_of(run, _self)
