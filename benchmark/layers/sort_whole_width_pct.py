"""sort_whole_width_pct: of the ladder sorts of the commit (the
compaction's and the enqueue's), the share that ran at the LAST rung -
the whole candidate array, the width every sort had before there was a
ladder - median over the window's checks.  A ladder of one rung is left
out (its one rung is the whole array by construction: a compaction of
32,768 lanes or fewer, the mesh's segments), and so is a sort the engine
does not have; None where that leaves none.  0 says the narrower rungs
hold every body; a share says how often the worst case is paid."""
from commit_read import over, ratio


def read(run):
    def whole(b):
        hists = [b[s + "_rung"] for s in ("compact", "enqueue")
                 if len(b.get(s + "_ladder", ())) > 1]
        return over(sum(h[-1] for h in hists), sum(map(sum, hists)))

    return ratio(run, whole)
