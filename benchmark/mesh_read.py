"""How a reader under benchmark/layers/ gets the mesh engine's counters.

The mesh engine (jaxtlc/engine/sharded.py) puts its owner-routing
counters on the run journal's `final` event: `shard_distinct` and
`shard_generated` (one number a device), `route_max_fill` beside
`route_bucket` (the fullest per-destination bucket any device packed in
any step, and the bucket's width), `route_bytes` (what each device
handed to the two all_to_alls over the check, from the static shapes and
the step count).  `finals(run, *keys)` gives the `final` events of the
window's correct jobs that hold all of `keys`; a reader takes its median
over them with `median_of`.  Where no job has them - a one-chip engine,
a commit before the counters - it is None and the metric is left out of
the line.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from stats import median


def finals(run, *keys: str) -> List[dict]:
    out = []
    for r in run["jobs"]:
        if not r.get("ok") or r.get("findings"):
            continue
        final = next((e for e in r.get("events") or []
                      if e.get("event") == "final"), None)
        if final is not None and all(final.get(k) is not None
                                     for k in keys):
            out.append(final)
    return out


def median_of(run, fn: Callable[[dict], Optional[float]],
              *keys: str) -> Optional[float]:
    xs = [fn(f) for f in finals(run, *keys)]
    return median([x for x in xs if x is not None])
