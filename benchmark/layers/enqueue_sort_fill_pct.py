"""enqueue_sort_fill_pct: the live share of the enqueue's ordering sort -
the distinct representatives (`commit_reps`: every new row is one of
them) over the rows the sort ran at (`commit_enqueue_rung` x
`commit_enqueue_ladder`, the probe width first) - median over the
window's checks.  None where the program writes no such counts, and on
the mesh, whose enqueue sorts what it received in one sort with no
ladder."""
from commit_read import over, ratio, sorted_rows


def read(run):
    return ratio(run, lambda b: over(b["reps"], sorted_rows(b, "enqueue")))
