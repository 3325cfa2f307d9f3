"""action_prop_build_ms: what a check pays on the host for its cfg's
refinement PROPERTY (a specification as a property, `I /\\ [][A]_v`)
before any engine is built - every `build.struct.instance` span of the
check (the instanced module found, hashed, and its definitions
substituted under `N!`, inside `build.struct.load`: the file is read and
hashed on every check, the substitution where this process has not made
it) and every `build.struct.actionprop` span (where the backend memo
misses, the compile of `[A]_v` to a predicate on a source row's columns
and a successor row, inside `build.struct`) summed - median over the
window's checks.  A warm check reads microseconds to a millisecond, as
its siblings `constraint_build_ms` and `seq_cap_build_ms` do.  Read
through span_read.py; None where the program records no such span (a
commit before PR 48, a model without such a PROPERTY)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(
        rows, "build.struct.instance", "build.struct.actionprop"))
