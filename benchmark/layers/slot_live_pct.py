"""slot_live_pct: how full the compiled step leaves the candidate slots it
hands the engine - `lane_fires` (lanes that fired: the per-action generated
totals summed) over `states_expanded` x `step_slots` (the slots a state
keeps after the step's compaction; the static fan where the step is not
compacted) of the `final` event - median over the window's checks.  The
engine's commit half costs a candidate slot whether it is live or not, so
this is the share of that cost spent on real successors; it rises when the
compaction narrows, and a state that fills every slot is one lane from the
widen rung.  A plain counter ratio: no time, no peak.  None where the
program writes no such counters (a commit before PR 31, a hand kernel)."""
from mesh_read import median_of


def read(run):
    def share(final):
        slots = final["states_expanded"] * final["step_slots"]
        return 100.0 * final["lane_fires"] / slots if slots else None

    return median_of(run, share, "lane_fires", "states_expanded",
                     "step_slots")
