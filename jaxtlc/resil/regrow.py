"""Carry migration between engine geometries - the auto-regrow core.

A capacity halt (VIOL_FPSET_FULL / VIOL_QUEUE_FULL / VIOL_ROUTE_OVERFLOW)
reaches the supervisor as a poisoned carry: the saturating step already
popped a chunk whose successors were discarded, so the post-violation
carry cannot simply continue.  The supervisor therefore always regrows
from the LAST GOOD carry (the segment boundary before the halt): the
functions here rebuild that carry inside the doubled geometry -
re-inserting every stored fingerprint into the larger bucketized table,
re-seating the frontier buffers, preserving every counter bit-for-bit -
and the supervisor replays the segment.  Because a segment is a pure
function of the carry and dedup verdicts are independent of table
geometry (fpset sort-compaction orders candidates by fingerprint, not by
slot), the regrown run's final statistics equal an uninterrupted
correctly-sized run's exactly (tests/test_resil.py pins this).

What is NOT regrowable: VIOL_SLOT_OVERFLOW means the codec's per-field
bit widths are too narrow - a recompile of the codec/kernel, not a carry
migration; the supervisor degrades that to checkpoint + actionable error.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.bfs import (
    VIOL_FPSET_FULL,
    VIOL_QUEUE_FULL,
    VIOL_ROUTE_OVERFLOW,
    EngineCarry,
)
from ..engine.fpset import (
    BUCKET,
    FPSet,
    fpset_insert_sorted,
    fpset_new,
    unmix_host,
)
from ..engine.sharded import ShardCarry

# violation code -> the engine parameter whose doubling clears it
# (route_factor is sharded-only: a pure engine-geometry knob, the carry
# passes through migration unchanged)
GROWABLE = {
    VIOL_FPSET_FULL: "fp_capacity",
    VIOL_QUEUE_FULL: "queue_capacity",
    VIOL_ROUTE_OVERFLOW: "route_factor",
}


def grown(params: Dict, resource: str) -> Dict:
    """The parameter dict with `resource` doubled (capacities stay powers
    of two; route_factor is a float multiplier)."""
    out = dict(params)
    out[resource] = (
        out[resource] * 2.0 if resource == "route_factor"
        else int(out[resource]) * 2
    )
    return out


def migrate_table(old_table: np.ndarray, new_capacity: int,
                  batch: int = 8192) -> FPSet:
    """Re-insert every stored fingerprint into a fresh table of
    `new_capacity` slots.

    Stored words are avalanche-MIXED; they are unmixed host-side
    (fpset.unmix_host) and fed back through the production insert path
    (fpset_insert_sorted), so the new table is exactly what a from-scratch
    run with the larger capacity would have built for the same fingerprint
    set.  Asserts that no entry was lost or duplicated."""
    old_table = np.asarray(old_table)
    lo = old_table[:, 0::2].reshape(-1)
    hi = old_table[:, 1::2].reshape(-1)
    occ = (lo != 0) | (hi != 0)
    lo, hi = lo[occ], hi[occ]
    n = int(lo.shape[0])
    assert n <= new_capacity, "new capacity below current occupancy"
    raw_lo, raw_hi = unmix_host(lo, hi)
    fps = fpset_new(new_capacity)
    inserted = 0
    for off in range(0, n, batch):
        b_lo = raw_lo[off : off + batch]
        b_hi = raw_hi[off : off + batch]
        nb = len(b_lo)
        if nb < batch:
            b_lo = np.pad(b_lo, (0, batch - nb))
            b_hi = np.pad(b_hi, (0, batch - nb))
        mask = np.arange(batch) < nb
        fps, is_new, _, _, _ = fpset_insert_sorted(
            fps, jnp.asarray(b_lo), jnp.asarray(b_hi), jnp.asarray(mask)
        )
        inserted += int(np.asarray(is_new).sum())
    assert inserted == n, (
        f"fpset migration lost entries: {inserted} != {n}"
    )
    return fps


def migrate_engine_carry(
    carry, old_params: Dict, new_params: Dict, new_chunk: int = None
) -> EngineCarry:
    """Rebuild a single-device EngineCarry inside the new geometry.

    `carry` is a last-good (pre-violation) carry, host- or device-side.
    Counters, level fencing, and the pop cursor are preserved verbatim;
    only the containers are re-seated: the fingerprint table is
    re-bucketized into the larger capacity and the ping-pong level buffers
    are copied into the wider queue (normalized to parity 0).

    `new_chunk` re-seats the queue's chunk padding for a different pop
    width (the degradation ladder's chunk-shrink rung): level contents
    and every counter are unchanged, but the pop BATCHING changes, so
    in-batch duplicate attribution (outdegree min/max, per-action
    distinct splits of same-fingerprint candidates) may differ from a
    clean run at the original chunk - total counts and the verdict do
    not.  Unpipelined carries only (the staged block is chunk-shaped)."""
    chunk = (int(np.asarray(carry.queue).shape[1])
             - int(old_params["queue_capacity"])) // 2
    if new_chunk is not None:
        assert carry.st_n is None, \
            "chunk re-seat supports unpipelined carries only"
        chunk = int(new_chunk)
    W = int(np.asarray(carry.queue).shape[2])
    qcap2 = int(new_params["queue_capacity"])
    old_queue = np.asarray(carry.queue)
    par = int(carry.parity)
    lvl = int(carry.level_n)
    nxt = int(carry.next_n)
    assert lvl <= qcap2 and nxt <= qcap2, "regrown queue still too small"

    queue2 = np.zeros((2, qcap2 + 2 * chunk, W), np.uint32)
    queue2[0, :lvl] = old_queue[par, :lvl]
    queue2[1, :nxt] = old_queue[1 - par, :nxt]

    fp_cap2 = int(new_params["fp_capacity"])
    if fp_cap2 != int(old_params["fp_capacity"]):
        fps2 = migrate_table(np.asarray(carry.fps.table), fp_cap2)
    else:
        fps2 = FPSet(jnp.asarray(np.asarray(carry.fps.table)))
        assert fps2.table.shape[0] * BUCKET == fp_cap2

    # pipelined staged block (expand-stage output awaiting commit):
    # geometry-independent - packed candidate rows + raw fingerprint
    # words travel verbatim; the replayed segment commits them against
    # the regrown table/queue through the normal insert path
    staged = {}
    if carry.st_n is not None:
        staged = {
            f: jnp.asarray(np.asarray(getattr(carry, f)))
            for f in ("st_packed", "st_lo", "st_hi", "st_valid",
                      "st_action", "st_gen", "st_n", "st_viol",
                      "st_viol_state", "st_viol_action")
        }
    # observability ring: telemetry only, its shape depends on neither
    # capacity - travels verbatim so per-level history survives regrow
    if carry.obs_ring is not None:
        staged.update({
            f: jnp.asarray(np.asarray(getattr(carry, f)))
            for f in ("obs_ring", "obs_head", "obs_bodies",
                      "obs_expanded")
        })
    # spill-mode hit counter: scalar telemetry, travels verbatim (the
    # host store itself rolls back through SpillStore.snapshot/restore)
    if getattr(carry, "spill_hits", None) is not None:
        staged["spill_hits"] = jnp.asarray(
            np.asarray(carry.spill_hits), jnp.uint32
        )
    # runtime-certificate leaves: sticky flag + staged block bit travel
    # verbatim (telemetry; a violation already seen must survive regrow)
    if getattr(carry, "cert_viol", None) is not None:
        staged["cert_viol"] = jnp.asarray(
            np.asarray(carry.cert_viol), bool
        )
    if getattr(carry, "st_cert", None) is not None:
        staged["st_cert"] = jnp.asarray(
            np.asarray(carry.st_cert), bool
        )
    # deferred-evaluation staged raw fields (ISSUE 15): chunk-shaped
    # like the rest of the staged block, geometry-independent - travel
    # verbatim (the chunk re-seat path asserts st_n is None above)
    if getattr(carry, "st_flat", None) is not None:
        staged["st_flat"] = jnp.asarray(
            np.asarray(carry.st_flat), jnp.int32
        )
    # device coverage counters: telemetry, shape depends on neither
    # capacity - travel verbatim so per-site history survives regrow
    for f in ("cov_counts", "st_cov"):
        if getattr(carry, f, None) is not None:
            staged[f] = jnp.asarray(
                np.asarray(getattr(carry, f)), jnp.uint32
            )
    # state-space reduction leaves (ISSUE 18, 33): the sticky orbit
    # flag, the canon counters and the POR count travel verbatim in
    # their own dtypes - a reduced run regrows like any other
    for f in ("sym_viol", "st_sym", "sym_stat", "st_sym_stat",
              "por_pruned", "st_pruned", "con_stat", "st_con_stat",
              "ap_stat", "st_ap_stat", "ap_src", "st_ap_src",
              "commit_stat"):
        if getattr(carry, f, None) is not None:
            staged[f] = jnp.asarray(np.asarray(getattr(carry, f)))

    return EngineCarry(
        fps=fps2,
        queue=jnp.asarray(queue2),
        parity=jnp.int32(0),
        qhead=jnp.int32(int(carry.qhead)),
        level_n=jnp.int32(lvl),
        next_n=jnp.int32(nxt),
        level=jnp.int32(int(carry.level)),
        depth=jnp.int32(int(carry.depth)),
        generated=jnp.uint32(int(carry.generated)),
        distinct=jnp.uint32(int(carry.distinct)),
        act_gen=jnp.asarray(np.asarray(carry.act_gen), jnp.uint32),
        act_dist=jnp.asarray(np.asarray(carry.act_dist), jnp.uint32),
        outdeg_hist=jnp.asarray(np.asarray(carry.outdeg_hist), jnp.uint32),
        viol=jnp.int32(int(carry.viol)),
        viol_state=jnp.asarray(np.asarray(carry.viol_state), jnp.int32),
        viol_action=jnp.int32(int(carry.viol_action)),
        **staged,
    )


def migrate_shard_carry(
    carry, old_params: Dict, new_params: Dict
) -> ShardCarry:
    """Rebuild a ShardCarry inside the new geometry (every capacity is
    PER DEVICE; fingerprint ownership - hi & (D-1) - is capacity-
    independent, so entries never move between devices).

    The circular per-device frontier is renumbered to qhead=0 when the
    queue grows (positions are pop-order-preserving: entry i of the
    in-flight window lands at slot i).  route_factor growth changes only
    the engine's all_to_all bucket width - the carry passes through,
    except a PIPELINED carry's pending-verdict buffers, which are sized
    by that width: their statistics are drained host-side first and the
    buffers re-seated empty at the new width."""
    D = int(np.asarray(carry.qhead).shape[0])
    if carry.pv_n is not None:
        old_B = int(np.asarray(carry.pv_send).shape[2])
        ncand = int(np.asarray(carry.pv_sown).shape[1])
        L = int(np.asarray(carry.outdeg_hist).shape[1]) - 2
        from ..engine.sharded import drain_pending_host, route_bucket_width

        new_B = route_bucket_width(
            ncand // L, L, D, float(new_params.get("route_factor", 2.0))
        )
        if new_B != old_B:
            carry = drain_pending_host(carry)
            carry = carry._replace(
                pv_send=jnp.zeros((D, D, new_B), jnp.uint8)
            )
    qcap = int(old_params["queue_capacity"])
    qcap2 = int(new_params["queue_capacity"])
    fp_cap = int(old_params["fp_capacity"])
    fp_cap2 = int(new_params["fp_capacity"])

    table = np.asarray(carry.table)
    if fp_cap2 != fp_cap:
        table2 = np.stack(
            [np.asarray(migrate_table(table[d], fp_cap2).table)
             for d in range(D)]
        )
    else:
        table2 = table

    if qcap2 != qcap:
        queue = np.asarray(carry.queue)
        F = queue.shape[2]
        qhead = np.asarray(carry.qhead)
        qtail = np.asarray(carry.qtail)
        level_end = np.asarray(carry.level_end)
        queue2 = np.zeros((D, qcap2 + 1, F), queue.dtype)
        qhead2 = np.zeros(D, np.int32)
        qtail2 = np.zeros(D, np.int32)
        level_end2 = np.zeros(D, np.int32)
        for d in range(D):
            cnt = int(qtail[d] - qhead[d])
            assert cnt <= qcap2, "regrown queue still too small"
            idxs = (int(qhead[d]) + np.arange(cnt)) % qcap
            queue2[d, :cnt] = queue[d][idxs]
            qtail2[d] = cnt
            level_end2[d] = int(level_end[d]) - int(qhead[d])
    else:
        queue2 = np.asarray(carry.queue)
        qhead2 = np.asarray(carry.qhead)
        qtail2 = np.asarray(carry.qtail)
        level_end2 = np.asarray(carry.level_end)

    pv = {}
    if carry.pv_n is not None:
        pv = {
            f: jnp.asarray(np.asarray(getattr(carry, f)))
            for f in ("pv_send", "pv_sown", "pv_pos", "pv_svalid",
                      "pv_order", "pv_faction", "pv_n")
        }
    if carry.obs_ring is not None:
        pv.update({
            f: jnp.asarray(np.asarray(getattr(carry, f)))
            for f in ("obs_ring", "obs_head", "obs_bodies",
                      "obs_expanded")
        })
    if getattr(carry, "obs_pl_flag", None) is not None:
        # pipeline x obs: the deferred level-flip row (level + staged
        # flag) migrates verbatim - geometry-independent scalars
        pv.update({
            f: jnp.asarray(np.asarray(getattr(carry, f)))
            for f in ("obs_pl_level", "obs_pl_flag")
        })
    if getattr(carry, "cov_counts", None) is not None:
        # device coverage partials: telemetry, geometry-independent
        pv["cov_counts"] = jnp.asarray(
            np.asarray(carry.cov_counts), jnp.uint32
        )
    if getattr(carry, "spill_hits", None) is not None:
        # sharded spill-mode hit partials: telemetry, travels verbatim
        # (the host store rolls back via SpillStore.snapshot/restore)
        pv["spill_hits"] = jnp.asarray(
            np.asarray(carry.spill_hits), jnp.uint32
        )
    if getattr(carry, "route_stat", None) is not None:
        # owner-routing telemetry: the fullest bucket is counted in
        # candidates, not slots, and the insert's segments and the
        # enqueue's blocks in chunks, so all survive a route_factor
        # change
        pv["route_stat"] = jnp.asarray(
            np.asarray(carry.route_stat), jnp.int32
        )
    if getattr(carry, "commit_stat", None) is not None:
        # the commit's counts: the insert's segment is a chunk wide
        # whatever the capacities
        pv["commit_stat"] = jnp.asarray(
            np.asarray(carry.commit_stat), jnp.uint32
        )
    return ShardCarry(
        table=jnp.asarray(table2),
        queue=jnp.asarray(queue2),
        qhead=jnp.asarray(qhead2, jnp.int32),
        qtail=jnp.asarray(qtail2, jnp.int32),
        level_end=jnp.asarray(level_end2, jnp.int32),
        level=jnp.asarray(np.asarray(carry.level), jnp.int32),
        depth=jnp.asarray(np.asarray(carry.depth), jnp.int32),
        generated=jnp.asarray(np.asarray(carry.generated), jnp.uint32),
        distinct=jnp.asarray(np.asarray(carry.distinct), jnp.uint32),
        act_gen=jnp.asarray(np.asarray(carry.act_gen), jnp.uint32),
        act_dist=jnp.asarray(np.asarray(carry.act_dist), jnp.uint32),
        outdeg_hist=jnp.asarray(np.asarray(carry.outdeg_hist), jnp.uint32),
        viol=jnp.asarray(np.asarray(carry.viol), jnp.int32),
        viol_state=jnp.asarray(np.asarray(carry.viol_state), jnp.int32),
        viol_local=jnp.asarray(np.asarray(carry.viol_local), bool),
        cont=jnp.asarray(np.asarray(carry.cont), bool),
        **pv,
    )
