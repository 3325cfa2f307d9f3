"""probe_fill_pct: the live share of the probe's row gathers - the
distinct representatives (`commit_reps`) over the rows the probe / claim
ran at (`commit_probe_segments` x `commit_probe_width`: a segment
gathers, ranks and sorts its width whatever it holds) - median over the
window's checks.  None where the program writes no such counts."""
from commit_read import over, ratio


def read(run):
    return ratio(run, lambda b: over(
        b["reps"], b["probe_segments"] * b["probe_width"]))
