"""step_ms: the engine's wall for a check over the chunk steps it ran,
median over the window's checks.  Readable only where the entry knows
the step count (check_with_checkpoints: segments x ckpt_every, which
counts the no-op steps that pad the last segment)."""
from stats import median


def read(run):
    xs = [1e3 * r["engine_wall_s"] / r["steps"] for r in run["jobs"]
          if r.get("ok") and r.get("steps") and r.get("engine_wall_s")]
    return median(xs)
