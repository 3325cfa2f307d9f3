"""straggler_rounds_per_body: rounds of the straggler walk a loop body
(`commit_walk_rounds` / `commit_bodies`: a compaction of the claimants
round 0 left pending, or one bucket step of their slice - each a sort or
a blocked scatter of the slice's width for a handful of rows), median
over the window's checks.  0 where every claim fits its home bucket.
None where the program writes no such counts."""
from commit_read import over, ratio


def read(run):
    return ratio(run, lambda b: over(b["walk_rounds"], b["bodies"]),
                 scale=1.0)
