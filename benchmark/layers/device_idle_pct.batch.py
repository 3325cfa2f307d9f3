"""device_idle_pct: 1 - (union of device operation intervals / traced
slice), from the profiler trace of a slice of the window.  It is the
idle share INSIDE the slice the traffic mix places (`trace`): in
`exhaustive` the slice lies in the engine's loop (steady state; the
per-call host phase before it is call_host_pct), in `recheck` it spans
more than one whole call."""


def read(run):
    return (run.get("trace") or {}).get("idle_pct")
