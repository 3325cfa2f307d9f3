"""queue_wait_ms: scheduler journal, admit to dispatch of a job,
median."""
from stats import median


def read(run):
    xs = [1e3 * (r["sched"]["dispatch"] - r["sched"]["admit"])
          for r in run["jobs"]
          if "admit" in (r.get("sched") or {}) and "dispatch" in r["sched"]]
    return median(xs)
