"""readback_ms: the host seconds a check spends in `loop.readback` - the
stretch behind each segment's fence in which the device waits for the
host: the device reads of the progress counters, the counter ring and
the coverage plane, and the `progress` / `level` / `coverage` / `segment`
journal events written from them - summed over the check's segments,
median over the window's checks."""
from span_read import median_of, seconds


def read(run):
    return median_of(run, lambda rows: seconds(rows, "loop.readback"))
