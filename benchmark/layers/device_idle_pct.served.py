"""device_idle_pct: 1 - (union of device operation intervals / traced
slice), from the profiler trace of a slice of the window."""


def read(run):
    return (run.get("trace") or {}).get("idle_pct")
