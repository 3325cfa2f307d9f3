"""constraint_build_ms: what a check pays on the host for its cfg's
CONSTRAINT before any engine is built - every `build.struct.constraint`
span of the check summed (the loader's resolution of the cfg's names to
state predicates, inside `build.struct.load`; and, where the backend
memo misses, the compile of their conjunction to a predicate on raw
successor fields, inside `build.struct`) - median over the window's
checks.  Read through span_read.py; None where the program records no
such span (a commit before PR 39, a model without a CONSTRAINT)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(
        rows, "build.struct.constraint"))
