"""loop_wait_pct: the share of a check's `loop` span that the host spends
in `loop.wait` (jax.block_until_ready) - higher is better: the host
waits for the chip, not the chip for the host - median over the
window's checks."""
from span_read import median_of, seconds


def _share(rows):
    loop, wait = seconds(rows, "loop"), seconds(rows, "loop.wait")
    if not loop or wait is None:
        return None
    return wait / loop


def read(run):
    return median_of(run, _share, scale=100.0)
