"""service_ms: the scheduler thread's `sched.run` span of a job - job
files, spec load, cache lookup, pool lookup, carry, run, readback,
journal, finish - median over the window's jobs."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "sched.run"))
