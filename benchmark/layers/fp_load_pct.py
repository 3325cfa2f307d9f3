"""fp_load_pct: fingerprint-table load at the end of a check
(CheckResult.fp_occupancy), median over the window's checks."""
from stats import median


def read(run):
    xs = [100.0 * r["fp_load"] for r in run["jobs"]
          if r.get("ok") and r.get("fp_load") is not None]
    return median(xs)
