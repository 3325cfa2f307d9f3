"""On-device counter ring: the device telemetry tier.

A fixed-shape uint32 ring buffer rides inside every engine carry
(EngineCarry / ShardCarry / EnumCarry optional leaves, None when obs is
off so pre-obs checkpoint layouts are untouched).  The engines write
ONE row per BFS level flip (the enumerator: one per body) with a single
contiguous dynamic-update-slice - no host sync, no scatter - and
non-flip bodies write into a dump row, so the write is unconditional
and XLA-friendly.  The host reads the ring back only at the segment
fences it already pays for (the supervisor's batched async device_get),
decodes the new rows here, and journals them as `level` events: that is
where TLC-style per-level rate attribution (BLEST, arXiv:2512.21967)
comes from (every benchmark cell runs with the ring on; results are
bit-for-bit an obs-off run's: tests/test_obs.py::
test_obs_bit_identical_and_ring).

Row layout (all cumulative uint32 counters; cumulative so a lost row -
ring wrap between fences - degrades per-level resolution, never total
accuracy):

    col 0  level      BFS level just completed
    col 1  generated  states generated so far
    col 2  distinct   distinct states found so far
    col 3  queue      width of the NEXT level (states left on queue)
    col 4  bodies     engine loop bodies executed so far
    col 5  expanded   states popped/expanded so far
    col 6  overflow   STICKY saturation flag: 1 once any cumulative
                      uint32 column wrapped (new < old between bodies);
                      decoded as a `counter_overflow` warning so
                      saturated counters are detected, never silently
                      wrong (the jaxtlc.analysis counter-width audit
                      flags the risky configs before the run)
    col 7  spill      cumulative host-spill-tier hits: candidates the
                      host fingerprint store vetoed (engine.spill);
                      always 0 on engines without the spill tier, so
                      pre-spill ring layouts are unchanged
    col 8  cert       STICKY certificate flag: 1 once any generated
                      state violated a bound the certified abstract
                      interpretation (jaxtlc.analysis.absint) claimed -
                      decoded as `cert_violation` and escalated to an
                      error verdict, so an unsound narrowing can never
                      silently drop real states; always 0 on engines
                      without a certificate check
    col 9  sym        STICKY orbit-certificate flag (ISSUE 18): 1 once
                      the runtime orbit check caught the symmetry
                      canonicalization NOT constant on a reachable
                      orbit - decoded as `sym_violation` and escalated
                      to an error verdict, so an unsound symmetry
                      reduction can never silently merge real states;
                      always 0 on engines without symmetry reduction
    col 10..10+A-1      per-action generated (cumulative)
    col 10+A..10+2A-1   per-action distinct  (cumulative)

The ring array is [slots + 1, cols]: row `slots` is the dump row.
`head` counts rows ever written (the slot of row k is k % slots), so
wrap-around is detectable host-side.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

DEFAULT_OBS_SLOTS = 256

N_FIXED_COLS = 10
(COL_LEVEL, COL_GENERATED, COL_DISTINCT, COL_QUEUE, COL_BODIES,
 COL_EXPANDED, COL_OVERFLOW, COL_SPILL, COL_CERT,
 COL_SYM) = range(N_FIXED_COLS)
COL_RES0 = COL_OVERFLOW  # pre-overflow name of col 6
COL_RES1 = COL_SPILL  # pre-spill name of col 7


def ring_cols(n_labels: int) -> int:
    """Row width for an engine with `n_labels` actions."""
    return N_FIXED_COLS + 2 * n_labels


def ring_new(slots: int, n_labels: int):
    """Fresh device ring ([slots + 1, cols]; last row = dump) + head."""
    import jax.numpy as jnp

    return (
        jnp.zeros((slots + 1, ring_cols(n_labels)), jnp.uint32),
        jnp.int32(0),
    )


def ring_update(ring, head, row, flip):
    """Write `row` at the ring head when `flip` is true, else into the
    dump row - one unconditional contiguous row write either way (the
    queue-enqueue discipline applied to telemetry)."""
    import jax.numpy as jnp
    from jax import lax

    slots = ring.shape[0] - 1
    idx = jnp.where(flip, head % slots, jnp.int32(slots))
    ring = lax.dynamic_update_slice(
        ring, row[None, :], (idx, jnp.int32(0))
    )
    return ring, head + flip.astype(head.dtype)


def pack_row(level, generated, distinct, queue, bodies, expanded,
             act_gen, act_dist, overflow=None, spill=None, cert=None,
             sym=None):
    """Assemble one ring row from carry scalars (device-side).
    `overflow` is the sticky uint32 saturation flag (COL_OVERFLOW);
    `spill` the cumulative host-spill-hit counter (COL_SPILL); `cert`
    the sticky certificate-violation flag (COL_CERT); `sym` the sticky
    orbit-certificate flag (COL_SYM); None writes 0 (engines that
    predate the flag / carry no such tier)."""
    import jax.numpy as jnp

    u = jnp.uint32
    fixed = jnp.stack([
        level.astype(u), generated.astype(u), distinct.astype(u),
        queue.astype(u), bodies.astype(u), expanded.astype(u),
        u(0) if overflow is None else overflow.astype(u),
        u(0) if spill is None else spill.astype(u),
        u(0) if cert is None else cert.astype(u),
        u(0) if sym is None else sym.astype(u),
    ])
    return jnp.concatenate(
        [fixed, act_gen.astype(u), act_dist.astype(u)]
    )


def sticky_overflow(ring, wrapped):
    """The sticky saturation flag for the row about to be written:
    1 once ANY past row recorded an overflow (the flag never unsets,
    so the max over the whole ring - dump row included - is exactly
    "ever wrapped") OR a cumulative counter wrapped this body.
    `wrapped` is a device bool; returns uint32."""
    import jax.numpy as jnp

    prev = ring[:, COL_OVERFLOW].max()
    return jnp.maximum(prev, wrapped.astype(jnp.uint32))


def wrapped_any(pairs):
    """Device bool: any (new, old) cumulative uint32 pair wrapped this
    body (new < old is impossible for a monotone counter except via
    2^32 wrap-around)."""
    import jax.numpy as jnp

    out = jnp.bool_(False)
    for new, old in pairs:
        out = out | (new < old).any()
    return out


def rows_from_ring(
    ring: np.ndarray,
    head: int,
    labels: Optional[Sequence[str]] = None,
    since: int = 0,
    fp_capacity: int = 0,
) -> List[Dict]:
    """Decode the ring rows written in [since, head) that are still
    resident (ring wrap drops the oldest; cumulative counters mean the
    NEXT retained row still carries exact totals).  Returns journal-
    `level`-event-shaped dicts, oldest first."""
    ring = np.asarray(ring)
    head = int(head)
    slots = ring.shape[0] - 1
    first = max(int(since), head - slots, 0)
    out = []
    for k in range(first, head):
        r = ring[k % slots].astype(np.int64)
        row = {
            "level": int(r[COL_LEVEL]),
            "generated": int(r[COL_GENERATED]),
            "distinct": int(r[COL_DISTINCT]),
            "queue": int(r[COL_QUEUE]),
            "bodies": int(r[COL_BODIES]),
            "expanded": int(r[COL_EXPANDED]),
        }
        if fp_capacity:
            row["fp_load"] = round(int(r[COL_DISTINCT]) / fp_capacity, 6)
        if r[COL_OVERFLOW]:
            # sticky device-side saturation flag: totals in this row
            # (and every later one) may have wrapped uint32
            row["counter_overflow"] = True
        if r[COL_SPILL]:
            # host spill tier active: cumulative host-store vetoes
            row["spill_hits"] = int(r[COL_SPILL])
        if r[COL_CERT]:
            # sticky certificate flag: a generated state violated a
            # bound the certified abstract interpretation claimed
            row["cert_violation"] = True
        if r[COL_SYM]:
            # sticky orbit-certificate flag: the symmetry
            # canonicalization was caught non-constant on an orbit
            row["sym_violation"] = True
        if labels is not None:
            a = len(labels)
            gen = r[N_FIXED_COLS:N_FIXED_COLS + a]
            dist = r[N_FIXED_COLS + a:N_FIXED_COLS + 2 * a]
            row["action_generated"] = {
                labels[i]: int(v) for i, v in enumerate(gen) if v
            }
            row["action_distinct"] = {
                labels[i]: int(v) for i, v in enumerate(dist) if v
            }
        out.append(row)
    return out


def shard_rows_from_ring(
    ring: np.ndarray,
    head: np.ndarray,
    labels: Optional[Sequence[str]] = None,
    since: int = 0,
    fp_capacity_total: int = 0,
) -> List[Dict]:
    """Sharded decode: every device flips levels in lock-step (level
    fencing is a global psum), so row k of each device's ring describes
    the SAME level with per-device partial counters - sum them.  level
    and queue-of-next-level semantics: level is replicated (max), the
    others add."""
    ring = np.asarray(ring)  # [D, slots + 1, cols]
    heads = np.asarray(head)
    h = int(heads.min())
    summed = ring.astype(np.int64).sum(axis=0)
    summed[:, COL_LEVEL] = ring[:, :, COL_LEVEL].max(axis=0)
    return rows_from_ring(
        summed, h, labels=labels, since=since,
        fp_capacity=fp_capacity_total,
    )
