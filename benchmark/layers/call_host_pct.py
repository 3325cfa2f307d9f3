"""call_host_pct: the share of a check's wall on the caller's side that
lies outside the engine's own wall (CheckResult.wall_s, or the run
journal's final.wall_s) - resolution, preflight, re-trace, re-lower and
executable load per call, with the device idle - median over the
window's checks.  It stands beside device_idle_pct.batch, whose traced
slice may lie wholly inside the engine's loop."""
from stats import median


def read(run):
    xs = [100.0 * (1.0 - r["engine_wall_s"] / (r["done_t"] - r["start_t"]))
          for r in run["jobs"] if r.get("ok") and r.get("engine_wall_s")
          and r.get("done_t") and r.get("start_t")
          and r["done_t"] > r["start_t"]]
    return median(xs)
