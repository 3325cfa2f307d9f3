"""symmetry_build_ms: what a check pays on the host for its symmetry
reduction before any engine is built - every `build.struct.symmetry`
span of the check summed (the loader's evaluation of the cfg's SYMMETRY
definition to constant sets, inside `build.struct.load`; and, where the
backend memo misses, the sets' static verification and the permutation
plan's build, inside `build.struct`) - median over the window's checks.
Read through span_read.py; None where the program records no such span
(a commit before PR 33, a check that is not reduced)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(
        rows, "build.struct.symmetry"))
