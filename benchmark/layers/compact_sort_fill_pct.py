"""compact_sort_fill_pct: the live share of the dedup's compaction sort
- the candidate lanes the insert mask let through (`commit_valid`) over
the rows the sort ran at (each rung of `commit_compact_ladder` times the
bodies that took it, `commit_compact_rung`) - median over the window's
checks.  What a narrower rung, or one more, would buy: the sort is priced
by its width, live or not.  None where the program writes no such counts
(a commit before PR 50)."""
from commit_read import over, ratio, sorted_rows


def read(run):
    return ratio(run, lambda b: over(b["valid"], sorted_rows(b, "compact")))
