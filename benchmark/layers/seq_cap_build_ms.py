"""seq_cap_build_ms: what a check pays on the host to settle the
capacities of its sequences before any engine is built - every
`build.struct.seqcap` span of the check summed (the loader's walk of the
invariants and the cfg's CONSTRAINT for the bounds they declare,
`Len(network[p][q]) <= 3`, inside `build.struct.load`: paid on every
check, warm ones too, like the constraint's resolution) - median over
the window's checks.  Read through span_read.py; None where the program
records no such span (a commit before PR 45)."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(
        rows, "build.struct.seqcap"))
