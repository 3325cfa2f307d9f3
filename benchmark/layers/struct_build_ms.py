"""struct_build_ms: the struct frontend's own host work in a check - the
program's `build.struct.load` span (parse of cfg and modules, constants)
and every `build.struct` span (the backend memo's look-up; on a miss the
shape inference and the lane walk, `build.struct.shapes` and
`build.struct.lanes`, inside it) - summed per check, median over the
window's checks.  Read through span_read.py; None where the program
records no such span (a commit before PR 31)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(
        rows, "build.struct.load", "build.struct"))
