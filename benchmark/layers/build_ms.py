"""build_ms: the program's `build` span of a check - the engine factory,
init, trace, lower and `.compile()` (cache fetch and executable load on
a warm process) that every call pays before its first segment - median
over the window's checks.  Read through span_read.py."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "build"))
