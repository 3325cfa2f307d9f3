"""claim_fill_pct: the live share of the round-0 claim's row scatter-add
- the claims written (`commit_claimed`) over the rows scattered
(`commit_claim_blocks` x `commit_claim_block`: the write goes block by
block as far as its claimers, and a scattered row costs the same live or
not) - median over the window's checks.  None where the program writes
no such counts."""
from commit_read import over, ratio


def read(run):
    return ratio(run, lambda b: over(
        b["claimed"], b["claim_blocks"] * b["claim_block"]))
