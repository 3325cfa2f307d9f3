"""lane_live_pct: how much of the compiled step's static lane fan a state
uses - `lane_fires` (lanes that fired: the per-action generated totals
summed) over `states_expanded` x `step_lanes` (the static fan, before the
step compacts it) of the `final` event - median over the window's checks.
The universe-lane form trades this share for exactness: a lane per element
of a set's universe, most of them dead in any one state.  With the cell's
counts pinned it is a constant of the compile: it moves only when the
static fan does (a sharper static prune, another lane form).  None where
the program writes no such counters (a commit before PR 31, a hand
kernel)."""
from mesh_read import median_of


def read(run):
    def share(final):
        lanes = final["states_expanded"] * final["step_lanes"]
        return 100.0 * final["lane_fires"] / lanes if lanes else None

    return median_of(run, share, "lane_fires", "states_expanded",
                     "step_lanes")
