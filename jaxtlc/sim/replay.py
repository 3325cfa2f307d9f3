"""Seed-exact host replay of one walker lane (the violation story).

A sim lane's trajectory is a pure function of ``(run_seed, lane_id)``
(sim.engine): transition ``d`` consumes exactly
``bits(fold_in(fold_in(PRNGKey(seed), lane), d))`` and picks the
idx-th ENABLED successor lane in kernel-lane order.  This module
re-derives the identical draw host-side and re-steps the lane through
the SAME backend kernel, eagerly, one state at a time - so the replay
reproduces the device trajectory bit-for-bit (tests pin this) with no
on-device trace storage, and the walk prefix IS the counterexample
trace: decoded through the struct codec and rendered as TLA conjuncts,
it is the PlusCal-level exit-12 trace a BFS run would print for the
same forced path.

Eager execution is deliberate: a replay is <= depth single-state
kernel steps - milliseconds of work that must never cost an XLA
compile (tier-1's zero-extra-compile discipline).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.backend import SpecBackend
from ..engine.bfs import (
    OK,
    VIOL_ASSERT,
    VIOL_DEADLOCK,
    VIOL_SLOT_OVERFLOW,
)


class ReplayedWalk(NamedTuple):
    """One lane's re-walked trajectory, host-side."""

    seed: int
    lane: int
    # the visited states as raw [F] int32 field vectors, init first
    fields: List[np.ndarray]
    # action label per entry (None for the initial state)
    labels: List[Optional[str]]
    violation: int  # OK when the walk just ran out of steps
    violation_step: int  # index into `fields` of the violating state
    halted: bool  # lane stopped at a successor-less state (no-deadlock)


def _draw(key, lane: int, step: int) -> int:
    """The counter-based choice bits of (lane, step) - scalar twin of
    the engine's vmapped lane_bits (threefry is shape-independent, so
    the two agree bit-for-bit; tests pin it)."""
    k = jax.random.fold_in(jax.random.fold_in(key, lane), step)
    return int(jax.random.bits(k, dtype=jnp.uint32))


def replay_lane(
    backend: SpecBackend,
    seed: int,
    lane: int,
    steps: int,
    inits: Optional[np.ndarray] = None,
    check_deadlock: bool = None,
) -> ReplayedWalk:
    """Re-walk lane `lane` of run `seed` for up to `steps` transitions.

    Stops early at the first violation on the walked path (invariant >
    assert > deadlock > slot overflow - the engine's own priority, so
    the replay lands on the same state the device reported)."""
    from ..engine.backend import require_unconstrained

    require_unconstrained(backend, "random-walk simulation (-simulate)")

    if check_deadlock is None:
        check_deadlock = backend.check_deadlock
    key = jax.random.PRNGKey(seed)
    if inits is None:
        inits = backend.initial_vectors()
    inits = np.asarray(inits)
    n0 = inits.shape[0]
    labels = backend.labels
    inv_codes = backend.inv_codes

    state = inits[_draw(key, lane, 0) % n0]
    fields = [np.asarray(state, np.int32)]
    lbls: List[Optional[str]] = [None]

    def inv_viol(vec) -> int:
        bits = int(backend.inv_check(jnp.asarray(vec)))
        for k, code in enumerate(inv_codes):
            if not (bits >> k) & 1:
                return code
        return OK

    code = inv_viol(state)
    if code != OK:
        return ReplayedWalk(seed, lane, fields, lbls, code, 0, False)

    for d in range(1, steps + 1):
        succs, valid, action, afail, ovf = backend.step(
            jnp.asarray(state)
        )
        valid = np.asarray(valid)
        n = int(valid.sum())
        if n == 0:
            if check_deadlock:
                return ReplayedWalk(seed, lane, fields, lbls,
                                    VIOL_DEADLOCK, len(fields) - 1,
                                    False)
            return ReplayedWalk(seed, lane, fields, lbls, OK,
                                len(fields) - 1, True)
        idx = _draw(key, lane, d) % n
        chosen = int(np.flatnonzero(valid)[idx])
        state = np.asarray(succs)[chosen].astype(np.int32)
        act_id = int(np.asarray(action).reshape(-1)[chosen])
        fields.append(state)
        lbls.append(labels[act_id] if 0 <= act_id < len(labels)
                    else None)
        if bool(np.asarray(ovf).reshape(-1)[chosen]):
            return ReplayedWalk(seed, lane, fields, lbls,
                                VIOL_SLOT_OVERFLOW, len(fields) - 1,
                                False)
        if bool(np.asarray(afail).reshape(-1)[chosen]):
            return ReplayedWalk(seed, lane, fields, lbls, VIOL_ASSERT,
                                len(fields) - 1, False)
        code = inv_viol(state)
        if code != OK:
            return ReplayedWalk(seed, lane, fields, lbls, code,
                                len(fields) - 1, False)
    return ReplayedWalk(seed, lane, fields, lbls, OK, len(fields) - 1,
                        False)


def walk_trace(walk: ReplayedWalk, cdc) -> List[Tuple[tuple, object]]:
    """The walk as [(decoded state tuple, action label | None), ...] -
    the exact shape struct.oracle.violation_trace returns, so the
    api's trace renderer prints a replayed walk and a BFS-found trace
    through one code path (byte-for-byte transcripts)."""
    return [
        (cdc.decode(vec), lbl)
        for vec, lbl in zip(walk.fields, walk.labels)
    ]
