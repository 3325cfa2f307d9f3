"""constraint_discard_pct: the share of generated successors the cfg's
CONSTRAINT rejected - `constraint_discarded` (valid successors that
failed the constraint: counted as generated, never fingerprinted,
enqueued or checked) over `constraint_rows` (valid successors judged) of
the `final` event - median over the window's checks.  With the cell's
counts pinned it is a constant of the model (14.15 % of EWD998's
successors at N = 3), and it reads 0 the day the constraint silently
stops engaging.  A plain counter ratio: no time, no peak.  None where
the program writes no such counters (a commit before PR 39, a model
without a CONSTRAINT)."""
from mesh_read import median_of


def read(run):
    def share(final):
        rows = final["constraint_rows"]
        return 100.0 * final["constraint_discarded"] / rows if rows else None

    return median_of(run, share, "constraint_discarded", "constraint_rows")
