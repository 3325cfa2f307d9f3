"""state_row_fill_pct: how full the packed state row is - `state_bits`
(the bits the codec lays a state out in) over 32 x `state_words` (the
uint32 words the dedup sorts, the fingerprint reads, the table and the
queue hold) of the `final` event - median over the window's checks.  A
model whose state is FIFO channels of records is dense past 64 bits: the
row is what it is because of the bits, not because of padding.  With the
cell's counts pinned it is a constant of the compile: it moves when the
layout does (a sequence's capacity, a slot's width, a field's range).
None where the program writes no such counter (a commit before PR 45, a
hand kernel)."""
from mesh_read import median_of


def read(run):
    def share(final):
        words = final["state_words"]
        return 100.0 * final["state_bits"] / (32 * words) if words else None

    return median_of(run, share, "state_bits", "state_words")
