"""verdict_ms.p95: submit-to-verdict from the due instant, 95th
percentile over the window's correct jobs (every run also prints it, with
p90, p99 and the maximum, on a `bench: verdict_ms:` line).  A per-layer
metric and not a bounded one: the shipped client polls every 50 ms, so
latencies come in steps of 50 ms and a percentile is a plateau with a
cliff; at the served cell's rate 93-96 % of jobs are answered within two
polls, so the p95 reads ~120 or ~165 ms from run to run on identical
work.  The bounded statistic that feels the tail is `verdict_ms.mean`."""
from stats import percentile, samples_beyond


def read(run):
    lat = [1e3 * (r["done_t"] - r["due_t"]) for r in run["jobs"]
           if r.get("ok") and not r.get("findings")]
    if samples_beyond(lat, 0.95) < 10:
        return None  # not a tail: the highest few samples
    return percentile(lat, 0.95)
