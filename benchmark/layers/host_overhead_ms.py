"""host_overhead_ms: a job's verdict time on the caller's side less the
engine wall its run journal's `final` event states - entry, preflight,
supervisor, scheduler, HTTP and the client's poll - median."""
from stats import median


def read(run):
    xs = []
    for r in run["jobs"]:
        final = next((e for e in r.get("events") or []
                      if e.get("event") == "final"), None)
        if r.get("ok") and final and final.get("wall_s") is not None:
            xs.append(1e3 * (r["done_t"] - r["due_t"] - final["wall_s"]))
    return median(xs)
