"""pool_device_ms: `pool.carry` + `pool.run` + `pool.readback` of a job -
the fresh carry, dispatch to block_until_ready, and the result's
device_get: the part of a pooled job that talks to the chip - median
over the window's jobs."""
from span_read import median_of, seconds


def read(run):
    return median_of(
        run,
        lambda rows: seconds(rows, "pool.carry", "pool.run",
                             "pool.readback"))
