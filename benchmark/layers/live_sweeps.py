"""live_sweeps: the sweeps over the edge store the fair fixpoint took
in a check - `live_sweeps` of the journal's `final` event (every inner
backward-reachability sweep of every outer pass, summed over the cfg's
properties) - median over the window's checks.  With the cell's counts
pinned it is a constant of the model and of the formulation: it moves
only when the fixpoint is written another way.  A plain counter: no
time.  None where the program writes no such counter (a commit before
PR 41, a cfg without a PROPERTY)."""
from mesh_read import median_of


def read(run):
    return median_of(run, lambda final: final["live_sweeps"],
                     "live_sweeps")
