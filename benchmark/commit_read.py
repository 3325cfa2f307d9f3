"""How a reader under benchmark/layers/ gets the commit's own counts.

Every engine commits through one seam (jaxtlc/engine/fpset.py,
`fpset_insert_sorted`) and sums what each commit did into one leaf of
its carry: the lanes the insert mask let through (`commit_valid`), their
distinct representatives (`commit_reps`), how often the compaction's
sort and the enqueue's ran at each rung of its ladder
(`commit_compact_rung` beside `commit_compact_ladder`, the static widths;
`commit_enqueue_rung` / `commit_enqueue_ladder`, which the mesh's
enqueue does not have), the probe's segments of `commit_probe_width`
rows, the round-0 claims (`commit_claimed`) and the blocks of
`commit_claim_block` rows their write scattered (`commit_claim_blocks`),
the straggler walk's rounds, the rows found new (`commit_new`), the
deferred checker's trips of a probe width each, and the loop's bodies.
The program writes the block as the attributes of the `check.result`
span - the span around its one read of the finished carry, which every
entry point closes, the one without a journal too.  Counts and static
widths only: the ratio is the reader's.

`ratio(run, fn)` is the median over the window's correct jobs of
`fn(block)`, the block being that job's attributes; None - and the
metric is then left out of the line - where no job has them (a commit
before the counters, the served cell) or where `fn` gives None for
every job (no ladder, an immediate checker).  The counts are those of
the loop's full-width bodies: where it also steps a small body (a chunk
of 2^14) that body counts nothing.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from span_read import median_of

SPAN = "check.result"


def block(rows: List[Dict]) -> Optional[Dict]:
    """The `commit_*` attributes of the job's last `check.result` span,
    less the prefix; None where it has none, or the counts without the
    widths they are read against."""
    for r in reversed(rows):
        attrs = r.get("attrs") or {}
        if r["name"] == SPAN and "commit_width" in attrs:
            return {k[len("commit_"):]: v for k, v in attrs.items()
                    if k.startswith("commit_")}
    return None


def ratio(run, fn: Callable[[Dict], Optional[float]],
          scale: float = 100.0) -> Optional[float]:
    def per_job(rows):
        b = block(rows)
        return None if b is None else fn(b)

    return median_of(run, per_job, scale=scale)


def over(num, den) -> Optional[float]:
    return num / den if den else None


def sorted_rows(b: Dict, sort: str) -> int:
    """Rows the check's `sort` ("compact" | "enqueue") sorts ran over:
    each rung's count times its static width; 0 where the engine has
    no such ladder."""
    return sum(n * w for n, w in zip(b.get(sort + "_rung", ()),
                                     b.get(sort + "_ladder", ())))
