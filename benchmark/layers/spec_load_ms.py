"""spec_load_ms: `sched.jobdir` + `sched.load` of a job - writing the
job's spec and cfg to disk and parsing them (struct.loader.load) on the
scheduler thread, before the pool is asked - median over the window's
jobs."""
from span_read import median_of, seconds


def read(run):
    return median_of(
        run, lambda rows: seconds(rows, "sched.jobdir", "sched.load"))
