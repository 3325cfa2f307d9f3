"""checker_fresh_pct: the share of the deferred invariant checker's rows
that are fresh states - the rows found new (`commit_new`: distinct less
the initial states) over the rows it gathers and judges
(`commit_checker_trips` x `commit_probe_width`: it walks the insert's
representatives a probe width at a time, new or not) - median over the
window's checks.  None where the checker is immediate (a chunk under
2,048: no trips) or the program writes no such counts."""
from commit_read import over, ratio


def read(run):
    return ratio(run, lambda b: over(
        b["new"], b["checker_trips"] * b["probe_width"]))
