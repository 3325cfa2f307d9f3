"""route_fill_pct: the fullest per-destination bucket any device packed in
any step, as a share of the bucket's width - 100 * route_max_fill /
route_bucket, from the `final` event - median over the window's checks.
At 100 a candidate would not fit and the run halts with
VIOL_ROUTE_OVERFLOW; what is under it is padding that every exchange
carries."""
from mesh_read import median_of


def _fill(final):
    return 100.0 * final["route_max_fill"] / final["route_bucket"]


def read(run):
    return median_of(run, _fill, "route_max_fill", "route_bucket")
