"""build_load_ms: `build.compile` of a check - on a warm process the
persistent cache's fetch and the load of the executable onto the chip
(its attributes split it: requests, cache_hits, backend_s,
retrieval_s) - median over the window's checks."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "build.compile"))
