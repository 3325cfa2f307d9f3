"""build_trace_ms: `build.trace` + `build.lower` of a check - Python
tracing to a jaxpr and lowering to MLIR, the part of the per-call build
no compile cache can answer - median over the window's checks."""
from span_read import median_of, seconds


def read(run):
    return median_of(
        run, lambda rows: seconds(rows, "build.trace", "build.lower"))
