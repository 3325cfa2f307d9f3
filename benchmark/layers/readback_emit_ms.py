"""readback_emit_ms: the journal's share of readback_ms - the seconds of
`loop.readback.emit` (the journal lines and fsyncs written behind a
fence), summed over a check's segments, median over the window's checks.
The rest of readback_ms is `loop.readback.get`, the device reads and
their decoding.  A check whose loop closes `loop.readback.get` and no
`.emit` writes nothing there (check_with_checkpoints emits in
`loop.overlap`) and reads 0; a program without the split (before PR 37)
reads nothing."""
from span_read import median_of, seconds


def _emit(rows):
    if seconds(rows, "loop.readback.get") is None:
        return None
    return seconds(rows, "loop.readback.emit") or 0.0


def read(run):
    return median_of(run, _emit)
