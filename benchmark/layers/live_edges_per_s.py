"""live_edges_per_s: successor rows of the behaviour graph a second of
the liveness route - `live_edges` of the journal's `final` event (every
row the capture walked: generated less the initial states) over the
check's `live` span - each the median over the window's checks.  What a
`perf_opt` on jaxtlc/live/ moves.  None where the program writes no
such counter or span (a commit before PR 41, a cfg without a
PROPERTY)."""
import mesh_read
import span_read


def read(run):
    edges = mesh_read.median_of(run, lambda final: final["live_edges"],
                                "live_edges")
    secs = span_read.median_of(
        run, lambda rows: span_read.seconds(rows, "live"), scale=1.0)
    if edges is None or not secs:
        return None
    return edges / secs
