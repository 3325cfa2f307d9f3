"""level_ms: the engine's wall for a check over its BFS depth - the
fixed cost a level pays - median over the window's checks."""
from stats import median


def read(run):
    xs = [1e3 * r["engine_wall_s"] / r["result"]["depth"]
          for r in run["jobs"] if r.get("ok") and r.get("engine_wall_s")
          and (r.get("result") or {}).get("depth")]
    return median(xs)
