"""action_prop_moved_pct: of the edges an action property `[][A]_v` was
judged on, the share on which `v' # v`, where A itself and not the
stuttering disjunct decides - `action_prop_moved` over
`action_prop_edges` of the `final` event (every successor the search
generated, to new and to seen states alike: generated less the initial
states) - median over the window's checks.  With the cell's counts
pinned it is a constant of the model (4.84 % of PaxosCommit's edges
change rmState), it reads 0 the day the predicate stops seeing the
subscript move, and `action_prop_edges` falling to the distinct states'
count is the day it rides the invariants' seam.  A plain counter ratio:
no time, no peak.  None where the program writes no such counters (a
commit before PR 48, a model without an action property)."""
from mesh_read import median_of


def read(run):
    def share(final):
        edges = final["action_prop_edges"]
        return 100.0 * final["action_prop_moved"] / edges if edges else None

    return median_of(run, share, "action_prop_moved", "action_prop_edges")
