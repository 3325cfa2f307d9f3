"""Fence-mode phase attribution: the walls a segment fence already measures.

The supervisor (and the pod driver, jaxtlc.dist, per host with a `host`
field) pays a host sync at every segment fence; `segment_phases` turns
the dispatch->fence wall and the readback wall measured there into
schema-validated `phase` journal events (scope="segment").  No device
work, no extra sync: pure host arithmetic.  Where a STEP's time goes is
read from the device scopes (`jaxtlc.expand`, `jaxtlc.dedup`,
`jaxtlc.fpset`, `jaxtlc.enqueue`, `jaxtlc.level`, the mesh scopes)
joined to a profiler slice (PERF.md section 5), not from here.
"""

from __future__ import annotations

from typing import List

# the `phase` field vocabulary of fence mode (extra names are allowed
# by the schema - views ignore what they don't know)
PHASE_DEVICE = "device"
PHASE_READBACK = "readback"

def segment_phases(index: int, wall_s: float,
                   readback_s: float = None) -> List[dict]:
    """Fence-mode `phase` event rows for one supervised segment: the
    device dispatch->fence wall plus the host readback wall the
    supervisor measures around the progress/ring device_get it already
    pays.  Pure host arithmetic over timestamps that already exist."""
    rows = [{"scope": "segment", "index": index, "phase": PHASE_DEVICE,
             "wall_s": round(wall_s, 6)}]
    if readback_s is not None:
        rows.append({"scope": "segment", "index": index,
                     "phase": PHASE_READBACK,
                     "wall_s": round(readback_s, 6)})
    return rows
