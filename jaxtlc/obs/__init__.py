"""Unified observability plane: device counter ring, structured run
journal, and pipeline timeline tracing.

Three tiers, one source of truth:

* **Device tier** (obs.counters): a fixed-shape per-level counter ring
  carried inside every engine carry, written with one contiguous row
  store per level flip and read back only at the segment fences the
  drivers already pay for.
* **Host tier** (obs.journal + obs.schema): a crash-safe append-only
  JSONL run journal - manifest, segments, levels, checkpoints, regrows,
  retries, faults, violations, final verdict - validated against a
  versioned schema at write AND read time.  TLC progress lines and
  the tlcstat dashboard are derived views (obs.views).
* **Timeline tier** (obs.trace): Chrome-trace/Perfetto export of the
  journal (`-trace-out`), plus the `-xprof DIR` jax.profiler hook in
  the CLI for ground-truth device timelines, which ends in the device's
  time by `jaxtlc.*` scope (obs.scopes).

The live ops plane rides on top (ISSUE 8): the **segment walls** at
every fence (the `segment` event's `wall_s` / `readback_s`), the check's
**host spans** (obs.spans) and the **run-monitoring server** (obs.serve
- /metrics Prometheus text, /events SSE journal tail, /runs registry;
`-serve PORT` or `python -m jaxtlc.obs.serve`).
"""

from .counters import (  # noqa: F401
    DEFAULT_OBS_SLOTS,
    ring_cols,
    ring_new,
    rows_from_ring,
    shard_rows_from_ring,
)
from .journal import RunJournal, read as read_journal  # noqa: F401
from .schema import (  # noqa: F401
    SCHEMA_VERSION,
    JournalSchemaError,
    validate_event,
)
from .trace import export_chrome_trace  # noqa: F401
from .views import render_tlc_event  # noqa: F401
