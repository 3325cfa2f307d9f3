"""shard_skew_pct: how far the fullest shard of the fingerprint space is
over an even share - 100 * (max(shard_distinct) / mean - 1), from the
`final` event's per-device distinct counts - median over the window's
checks.  The fullest owner sets the pace of every level: the others wait
for it at the fence."""
from mesh_read import median_of


def _skew(final):
    shards = final["shard_distinct"]
    mean = sum(shards) / len(shards)
    return 100.0 * (max(shards) / mean - 1.0) if mean else None


def read(run):
    return median_of(run, _skew, "shard_distinct")
