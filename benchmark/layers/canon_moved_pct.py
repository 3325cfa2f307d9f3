"""canon_moved_pct: the share of candidate rows the symmetry tournament
rewrote - `canon_moved` (valid candidate rows whose orbit representative
differs from the candidate) over `canon_rows` (valid candidate rows
canonicalized) of the `final` event - median over the window's checks.
With the cell's counts pinned it is a constant of the model and of the
order the program compares images in: it moves only when that order does,
and it reads 0 the day the reduction silently stops engaging.  A plain
counter ratio: no time, no peak.  None where the program writes no such
counters (a commit before PR 33, an unreduced run, the mesh engine)."""
from mesh_read import median_of


def read(run):
    def share(final):
        rows = final["canon_rows"]
        return 100.0 * final["canon_moved"] / rows if rows else None

    return median_of(run, share, "canon_moved", "canon_rows")
