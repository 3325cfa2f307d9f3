"""live_fixpoint_hbm_pct: the fair fixpoint's share of the chip's HBM
roofline - the bytes its sweeps have to move (`sweep_bytes`, below) over
the seconds of the check's `live.fixpoint` spans, over peaks.json's
hbm_bytes_per_s of the device - median over the window's checks.

The bytes are the formulation's own reads and writes (jaxtlc/live/
fixpoint.py, `make_fair_fixpoint`), counted from the `final` event's
counters.  A PASS over the edge store reads, a state-changing row, its
destination id (4 B) and the gathered word of the set at that id (4 B),
and writes and reads back the rows' prefix counts (4 + 4 B): 16 B a row;
and, a state, reads its row bound (4 B), the gathered count there (4 B)
and the two sets it joins (1 + 1 B), and writes the new set (1 B): 11 B
a state.  A sweep is one pass; an outer pass costs one more (which rows
stay in Z).  So

    bytes = (live_sweeps + live_outer)
            * (16 * live_changed_edges + 11 * live_states)

The span's seconds are the host's around a blocked call, so this is a
FLOOR of the device's share until device seconds by scope are a metric
(ROADMAP A9a); an element gather moves a 4-byte word where the memory
system moves 32 or more, so a sweep bound by its gathers reads a few per
cent and cannot read over 100.  None where the program writes no such
counters or spans (a commit before PR 41, a cfg without a PROPERTY)."""
import json
import os

import mesh_read
import span_read

ROW_BYTES = 16
STATE_BYTES = 11


def sweep_bytes(changed_edges: int, states: int, sweeps: int,
                outer: int) -> int:
    """The bytes the formulation moves: (sweeps + outer) passes of
    ROW_BYTES a state-changing row and STATE_BYTES a state."""
    return (sweeps + outer) * (ROW_BYTES * changed_edges
                               + STATE_BYTES * states)


def read(run):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        peak = json.load(f)["devices"].get(
            run["device"].get("kind"), {}).get("hbm_bytes_per_s")
    if not peak:
        return None
    moved = mesh_read.median_of(
        run, lambda final: sweep_bytes(
            final["live_changed_edges"], final["live_states"],
            final["live_sweeps"], final["live_outer"]),
        "live_changed_edges", "live_states", "live_sweeps", "live_outer")
    secs = span_read.median_of(
        run, lambda rows: span_read.seconds(rows, "live.fixpoint"),
        scale=1.0)
    if moved is None or not secs:
        return None
    return 100.0 * moved / secs / peak
