"""Multi-device sharded BFS engine - the distributed-TLC replacement.

The reference ships distributed TLC (Java RMI workers + separately sharded
fingerprint servers), present but disabled in the committed run
(/root/reference/KubeAPI.toolbox/KubeAPI___Model_1.launch:4-7:
distributedTLC="off", distributedFPSetCount=0, distributedNodesCount=1).
This module is the TPU-native equivalent (SURVEY.md §2.3 E12, §2.4):

* the **frontier is sharded** across a `jax.sharding.Mesh` axis ("fp"):
  each device owns the states whose fingerprint lands in its partition;
* the **fingerprint space is partitioned by fp low bits**: owner(fp) =
  hi & (D-1) - replacing TLC's distributed fingerprint servers;
* candidate successors are **routed to their owner via `all_to_all` over
  ICI** (replacing RMI RPC); dedup happens only at the owner, so exactness
  is preserved: one fingerprint, one owner, one verdict;
* counters/termination/level fencing are `psum`s - level-synchronous BFS
  with exact depth, lock-step across the mesh inside one `lax.while_loop`
  under `shard_map`.

Topology (ISSUE 19): the SAME compiled body runs single-process (one
process owns every mesh device - the tested default, and the 8-device
virtual-mesh dryrun `__graft_entry__.dryrun_multichip`) and
multi-process (`jax.distributed` pods, jaxtlc.dist: one process per
host, the global mesh spanning all of them, the candidate-routing
`all_to_all` crossing DCN at exactly the level-fence seam the deferred
collective already batches).  Process membership is NOT elastic inside
a dispatch: a host that must leave checkpoints its shard slice and the
pod relaunches at the new width through the reshard-on-recover path
(jaxtlc.dist.pod.reshard_carry), which re-partitions table fingerprints
and frontier states by the new owner mapping hi & (D'-1).

Capacity ladder note: the sharded engine now HAS a host spill tier
(SPILL_CAPABLE below, ISSUE 19 closing ROADMAP #1's pinned gap): the
fused body is split at the owner seam into `expand_half` (pop, expand,
route, owner-side `fpset_member` filter) and `commit_half` (owner-side
insert, deferred invariants, the new rows compacted and written
onto the queue's ring as contiguous blocks, verdict return, level
fences), and
`ShardedSpillRuntime` drives the two jitted halves from the host with a
per-host SpillStore probe in between - each host's local device tables
flush into that host's store at the fp_highwater load, exactly the
engine.spill lifeboat, shard by shard.  The fused engine composes the
same two halves back into one `lax.while_loop` body, so there is one
implementation and no drift; the owner-side insert and the
PR 15 owner-side distinct-first deferred invariant evaluation both live
in `commit_half` and therefore run identically on the fused, spill and
pod paths.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

# the supervisor's degradation ladder consults this before offering the
# host spill tier: ShardedSpillRuntime (below) drives the expand/commit
# halves with a per-host SpillStore between them (ISSUE 19; unpipelined
# sharded carries only - the adapter gates the pipeline case)
SPILL_CAPABLE = True

# spill-mode owner filter walk cap (engine.spill's MEMBER_ROUNDS): near
# the highwater load ABSENT keys walk long full-bucket runs; unresolved
# lanes safely degrade to a host probe, so a small cap bounds the device
# filter at the price of a few extra host lookups
SPILL_MEMBER_ROUNDS = 4

from ..config import ModelConfig
from ..spec.labels import LABELS
from .bfs import (
    CheckResult,
    OK,
    run_steps,
    VIOL_ASSERT,
    VIOL_DEADLOCK,
    VIOL_FPSET_FULL,
    VIOL_QUEUE_FULL,
    VIOL_ROUTE_OVERFLOW,
    VIOL_SLOT_OVERFLOW,
    VIOLATION_NAMES,
    ENGINE_COUNTS,
    commit_result_fields,
    outdegree_from_hist,
)
from . import backend as _backend
from .fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from .fpset import (
    COMMIT_COUNTS,
    COMMIT_STAT_COLS,
    FPSet,
    commit_widths,
    count_block,
    fpset_insert_sorted,
    fpset_member,
    host_insert,
)


# the frontend -> engine seam now lives in engine.backend (shared with
# the single-device fused engine); re-exported here for compatibility
from .backend import SpecBackend, gen_backend, kubeapi_backend  # noqa: F401,E402


# columns of ShardCarry.route_stat (below); a snapshot whose leaf has
# another count was cut by another version and is refused by the
# leaf's name (checkpoint.load_checkpoint, dist.pod)
ROUTE_STAT_COLS = 2
# what a device's loop adds to fpset's block a body (ShardCarry.
# commit_stat): the one-chip engine's counts, and the blocks its
# enqueue wrote
MESH_COUNTS = ENGINE_COUNTS + ("enqueue_trips",)
MESH_STAT_COLS = COMMIT_STAT_COLS + len(MESH_COUNTS)


class ShardCarry(NamedTuple):
    """Per-device state; every leaf's leading axis is the mesh axis."""

    table: jnp.ndarray  # [D, cap/8, 16] uint32 interleaved bucket rows
    queue: jnp.ndarray  # [D, qcap + 1, F]
    qhead: jnp.ndarray  # [D]
    qtail: jnp.ndarray  # [D]
    level_end: jnp.ndarray  # [D]
    level: jnp.ndarray  # [D] (replicated value)
    depth: jnp.ndarray  # [D]
    generated: jnp.ndarray  # [D] uint32 (partial; psum at read-out)
    distinct: jnp.ndarray  # [D] uint32 (partial)
    act_gen: jnp.ndarray  # [D, n_labels + 1] uint32 (partial)
    act_dist: jnp.ndarray  # [D, n_labels + 1]
    outdeg_hist: jnp.ndarray  # [D, L + 2] uint32 (partial; TLC outdegree)
    viol: jnp.ndarray  # [D] int32 (global max, replicated)
    viol_state: jnp.ndarray  # [D, F] (valid on devices that saw it)
    viol_local: jnp.ndarray  # [D] bool: this device captured viol_state
    cont: jnp.ndarray  # [D] bool (replicated)
    # --- pipelined seam overlap (None on unpipelined engines) ---------
    # The verdict-return all_to_all of chunk k-1 is deferred into chunk
    # k's body so it can be in flight WHILE chunk k's candidate-routing
    # all_to_all and kernel expansion run (BLEST-style frontier/dedup
    # wave overlap).  Verdicts feed only source-side statistics
    # (outdegree, per-action distinct) - never control flow - so the
    # deferral is exact: the same uint32 adds land one body later.
    pv_send: jnp.ndarray = None  # [D, D, B] uint8 owner-side is_new buckets
    pv_sown: jnp.ndarray = None  # [D, ncand] int32 owner per sorted cand
    pv_pos: jnp.ndarray = None  # [D, ncand] int32 position in bucket
    pv_svalid: jnp.ndarray = None  # [D, ncand] bool sorted-candidate valid
    pv_order: jnp.ndarray = None  # [D, ncand] int32 owner-sort permutation
    pv_faction: jnp.ndarray = None  # [D, ncand] int32 candidate action ids
    pv_n: jnp.ndarray = None  # [D] int32 popped rows of the pending chunk
    # --- observability counter ring (None when obs is off) ------------
    # Per-device partial-counter rows, one per GLOBAL level flip (level
    # fencing is a psum, so every device writes row k for the same
    # level; obs.counters.shard_rows_from_ring sums the partials).
    obs_ring: jnp.ndarray = None  # [D, obs_slots + 1, cols] uint32
    obs_head: jnp.ndarray = None  # [D] int32 rows ever written
    obs_bodies: jnp.ndarray = None  # [D] uint32 loop bodies
    obs_expanded: jnp.ndarray = None  # [D] uint32 states popped
    # --- deferred obs row (pipeline x obs only) ------------------------
    # In pipeline mode the flip body's act_dist is still missing its
    # last chunk's verdicts (they are pending in pv_*), so the level-
    # flip ring row is written one body LATE - right after the deferred
    # verdict fold completes the counters.  Every other row column is a
    # cumulative counter whose next-body ENTRY value equals the flip
    # body's exit value, so only the flip's level (and a staged flag)
    # ride the carry.  Fixes the PR 5 documented per-level act_dist lag.
    obs_pl_level: jnp.ndarray = None  # [D] int32 staged flip's level
    obs_pl_flag: jnp.ndarray = None  # [D] bool a flip row is staged
    # --- device coverage plane (None without a backend plane) ----------
    # Per-device partial per-site visit counters (obs.coverage); summed
    # across the mesh axis at readback (engine.bfs.cov_totals), exactly
    # like the partial generated/distinct counters above.
    cov_counts: jnp.ndarray = None  # [D, n_sites] uint32
    # --- host spill tier (None until ShardedSpillRuntime adopts) -------
    # Per-device count of candidates the host store vetoed (they dedup
    # exactly like a device-table hit); partials, psum'd at read-out
    # like generated/distinct.
    spill_hits: jnp.ndarray = None  # [D] uint32
    # --- owner-routing telemetry (no control flow reads it) ------------
    # column 0: the fullest per-destination bucket this device packed in
    # any body (its width is route_bucket_width: at that width a
    # candidate would not fit and the run halts with
    # VIOL_ROUTE_OVERFLOW); column 1: bodies run, each of which hands
    # the two all_to_alls their static shapes (route_geometry)
    route_stat: jnp.ndarray = None  # [D, 2] int32
    # --- the commit's own counts (ISSUE 50; telemetry) -----------------
    # per device, cumulative: fpset_insert_sorted's block of every
    # segment of the owner-side insert (a segment is one call of the
    # seam, commit_width rows: `probe_segments` is the result's
    # `commit_segments`), then the MESH_COUNTS a body - 1, the rows it
    # enqueued, the deferred checker's trips, and the blocks of
    # commit_width rows its enqueue wrote (enqueue_new_rows: they
    # follow a body's new rows; the result's `enqueue_segments`)
    commit_stat: jnp.ndarray = None  # [D, MESH_STAT_COLS] uint32


class ShardEx(NamedTuple):
    """The expand-half -> commit-half seam of the sharded body (device-
    level leaves, no mesh axis).  `expand_half` pops a chunk, expands,
    canonicalizes, fingerprints and routes candidates to their owners
    (the candidate-routing all_to_all is INSIDE expand); `commit_half`
    performs the owner-side insert + deferred invariants +
    enqueue + verdict return + level fencing.  The fused engine
    composes the two back into one while_loop body (bit-identical op
    graph); ShardedSpillRuntime runs them as separate jits with a
    host SpillStore probe in between, exactly the engine.spill
    expand/commit protocol lifted onto the mesh."""

    outdeg0: jnp.ndarray  # [L+2] outdeg hist after the pipeline fold
    act_dist0: jnp.ndarray  # [n_labels+1] act_dist after the fold
    n: jnp.ndarray  # [] rows popped this chunk
    mask: jnp.ndarray  # [chunk] popped-row mask
    batch: jnp.ndarray  # [chunk, F] popped states
    valid: jnp.ndarray  # [chunk, L] post-POR successor validity
    flat: jnp.ndarray  # [ncand, F] canonicalized candidates
    fvalid: jnp.ndarray  # [ncand]
    faction: jnp.ndarray  # [ncand] candidate action ids
    inv_bad: jnp.ndarray  # [n_inv, ncand] immediate-mode sweep (0 rows
    #                       in deferred mode - the owner checks instead)
    afail: jnp.ndarray  # [chunk, L] action assertion failures
    ovf: jnp.ndarray  # [chunk, L] slot overflows
    dead: jnp.ndarray  # [chunk] deadlocked popped states
    order: jnp.ndarray  # [ncand] owner-sort permutation
    # the next three from the D per-owner counts, no gather
    # (sorted_route)
    s_own: jnp.ndarray  # [ncand] owner per sorted candidate (D: none)
    s_pos: jnp.ndarray  # [ncand] position within owner bucket
    s_valid: jnp.ndarray  # [ncand] sorted-candidate validity
    route_ovf: jnp.ndarray  # [] a count above the bucket's B slots
    route_fill: jnp.ndarray  # [] int32 fullest destination bucket
    r_flat: jnp.ndarray  # [D*B, F] received (owner-side) candidates
    r_lo: jnp.ndarray  # [D*B] uint32 received fp low words
    r_hi: jnp.ndarray  # [D*B] uint32 received fp high words
    r_valid: jnp.ndarray  # [D*B] received-slot validity
    member: jnp.ndarray  # [D*B] bounded owner-table membership filter
    #                      (all-False when the spill filter is off)


def route_bucket_width(chunk: int, n_lanes: int, D: int,
                       route_factor: float) -> int:
    """Per-destination all_to_all bucket slots (shared with the regrow
    migration so a route_factor change can resize the pipelined pending-
    verdict buffers to the new engine's geometry)."""
    ncand = chunk * n_lanes
    return ncand if D == 1 else min(
        ncand, int(route_factor * ncand / D) + 8
    )


def commit_width(chunk: int, D: int, bucket: int) -> int:
    """Rows of one segment of the owner-side insert: the received
    candidates are inserted as a compacted stream, this many at a time
    (insert_compacted), and of one block of the enqueue
    (enqueue_new_rows).  One per-device chunk: on the chip a gathered,
    scattered or sorted row costs the same live or dead, so what a
    body's insert costs follows its rows, trips x width, and a narrow
    segment wastes the least of its last trip; the trips themselves
    cost nothing that shows down to half a chunk (PERF.md section 6,
    PR 28: a 2x1FF body that received 18k candidates takes 19.8 ms at
    4 x chunk, 9.2 at one chunk, 8.7 at half - numbers of the program
    whose enqueue scattered every claimant row into the queue, most of
    what moved there; since PR 40 the enqueue walks a body's new rows
    and not the claimants, one block in the common case)."""
    return min(chunk, D * bucket)


def compact_lanes(cnt, j, bucket: int):
    """Received lanes of the compacted positions `j` (int32, any
    shape): the send pack turned round.  After the candidate
    all_to_all, bucket d of the received [D, bucket] batch holds its
    cnt[d] live rows as a prefix (the send pack fills slot (d, p) while
    p < counts[d]), so the compacted stream is those prefixes end to
    end, in lane order.  Position j lies past every bucket whose
    prefix ends at or before it, and each of those adds its unused
    tail, bucket - cnt[d], to the lane; a position at or past the total
    gives the out-of-range lane D * bucket.  Index arithmetic only."""
    ends = jnp.cumsum(cnt)
    tails = bucket - cnt
    skip = jnp.where(j[..., None] >= ends[:-1], tails[:-1], 0).sum(-1)
    return jnp.where(j < ends[-1], j + skip, cnt.shape[0] * bucket)


def compact_rows(padded, cnt, start, width: int):
    """Elements [start, start + width) of the compacted stream of a
    received [D * bucket] array (compact_lanes' order; zero past the
    total), without a gather: bucket d's live prefix is contiguous, so
    the part of the segment that lies in it is one dynamic slice, and
    a select per bucket puts the D of them together.  The slice for
    bucket d starts at lane d * bucket - first[d] + start: never
    negative (no earlier bucket holds more than `bucket` rows), and
    below D * bucket wherever the segment reads it, so with `width`
    zeros behind the array (`padded`) no slice that is read is
    clamped."""
    (D,) = cnt.shape
    bucket = (padded.shape[0] - width) // D
    ends = jnp.cumsum(cnt)
    first = ends - cnt
    j = start + jnp.arange(width, dtype=jnp.int32)
    out = jnp.zeros(width, padded.dtype)
    for d in range(D):
        piece = lax.dynamic_slice(
            padded, (d * bucket - first[d] + start,), (width,))
        out = jnp.where((j >= first[d]) & (j < ends[d]), piece, out)
    return out


def owner_counts(key, D: int):
    """Candidates per owner, [D] int32, from an owner key of any order
    (values 0 .. D; D marks a lane that routes nowhere and is not
    counted): one [D, n] compare-reduce."""
    owners = jnp.arange(D, dtype=jnp.int32)
    return (key[None, :] == owners[:, None]).sum(axis=1, dtype=jnp.int32)


def sorted_route(counts, ncand: int):
    """What a stable sort by owner key implies for sorted lane i, from
    the D per-owner counts alone - no gather through the permutation
    and no search of the sorted key: bucket d is lanes
    [starts[d], ends[d]), so the lane's owner is the number of buckets
    that end at or before it (D on the invalid tail), it is valid
    below ends[D - 1], and its position in its bucket is i less the
    counts of those same buckets (the invalid tail counts on from
    ends[D - 1]).  Returns (s_own, s_pos, s_valid), [ncand] each."""
    (D,) = counts.shape
    ends = jnp.cumsum(counts)
    i = jnp.arange(ncand, dtype=jnp.int32)
    s_own = jnp.zeros(ncand, jnp.int32)
    first = jnp.zeros(ncand, jnp.int32)
    for d in range(D):
        past = i >= ends[d]
        s_own = s_own + past
        first = first + jnp.where(past, counts[d], 0)
    return s_own, i - first, i < ends[D - 1]


def sorted_verdicts(verd, s_own):
    """verd[s_own[i], i - starts[s_own[i]]] for every sorted lane i
    ([ncand] of verd's dtype; zero on the invalid tail), without a
    gather: the send pack turned round.  Bucket d's lanes are
    contiguous from starts[d] (the sorted owners give the counts back
    by one compare-reduce) and its verdicts are row d of the returned
    [D, B] batch, so lane i of bucket d reads flat element
    d * B + i - starts[d]: one dynamic slice of ncand elements an
    owner and a select.  On a skewed body starts[d] passes d * B (and
    a lane past its bucket's B slots reads on into the next row: the
    caller gates those), so the flat verdicts carry ncand zeros in
    front and behind and no slice is ever clamped."""
    D, B = verd.shape
    (ncand,) = s_own.shape
    counts = owner_counts(s_own, D)
    starts = jnp.cumsum(counts) - counts
    pad = jnp.zeros(ncand, verd.dtype)
    padded = jnp.concatenate([pad, verd.reshape(-1), pad])
    out = jnp.zeros(ncand, verd.dtype)
    for d in range(D):
        piece = lax.dynamic_slice(
            padded, (ncand + d * B - starts[d],), (ncand,))
        out = jnp.where(s_own == d, piece, out)
    return out


def masked_hist(ids, mask, n_bins: int):
    """Lanes per id in 0 .. n_bins - 1 among those `mask` keeps, [n_bins]
    uint32: an [n_bins, n] compare-reduce - dense work in place of an
    n-element scatter-add (engine.bfs's enqueue has counted its
    per-action distinct states this way since round 4)."""
    bins = jnp.arange(n_bins, dtype=jnp.int32)
    return ((ids[None, :] == bins[:, None]) & mask[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def insert_compacted(table, r_lo, r_hi, ins_mask, cnt, width: int):
    """The owner-side insert of one body: the received candidates as a
    compacted stream (compact_lanes over the per-bucket live counts
    `cnt` [D]), `width` rows a segment behind a trip count - no
    conditional holds the table - so every gather, scatter and sort of
    the insert is `width` lanes wide, not D * B, and a body that
    received nothing inserts nothing.  r_lo / r_hi / ins_mask are
    [D * B]; the state rows are not touched.

    The HIGHEST segment goes first: a fingerprint that lies in two
    segments is new in the higher one and found in the table by the
    lower.  is_new therefore marks each new fingerprint's highest
    received lane, as one insert over all D * B lanes does (the dedup's
    pinned representative rule), and everything read from it - queue
    rows and their order, act_dist, outdeg_hist, the deferred checker's
    violating lane - is that insert's bit for bit.  The table holds the
    same fingerprints; where two segments claim slots of one bucket,
    their order inside it is the segments' and not the fingerprints'.
    A body of one segment is the one insert exactly, table included:
    compaction keeps lane order.

    Returns (table, is_new [D * B], c_lane, c_new, c_rows, trips,
    stat): the claimants of every segment end to end, each segment's
    up to its last new row - received lane (D * B on the rows between)
    and verdict, `c_rows` rows in use of a whole number of segments -
    for the deferred checker (the enqueue reads is_new alone), the
    segments run, and the segments' blocks of counts
    (fpset_insert_sorted's fifth value, MESH_STAT_COLS wide) summed."""
    (D,) = cnt.shape
    DB = r_lo.shape[0]
    bucket = DB // D
    trips = (cnt.sum() + (width - 1)) // width
    row = jnp.arange(width, dtype=jnp.int32)
    p_lo, p_hi, p_mask = (
        jnp.concatenate([a, jnp.zeros(width, a.dtype)])
        for a in (r_lo, r_hi, ins_mask))

    def insert_segment(st):
        k, table, is_new, c_lane, c_new, used, stat = st
        # device scope of the compaction: the segment's words, and the
        # claimants mapped back to received lanes
        with jax.named_scope("jaxtlc.compact"):
            lo_k, hi_k, mask_k = (
                compact_rows(a, cnt, k * width, width)
                for a in (p_lo, p_hi, p_mask))
        with jax.named_scope("jaxtlc.dedup"):
            fset, new_k, idx_k, _, stat_k = fpset_insert_sorted(
                FPSet(table), lo_k, hi_k, mask_k, stat_cols=MESH_STAT_COLS)
        with jax.named_scope("jaxtlc.compact"):
            # idx_k names each representative's row once; past them a
            # segment wider than 32,768 rows may hold the out-of-range
            # row `width` (fpset._sorted_order's pad), which the compare
            # and the drop below leave out (ROADMAP C10)
            lane_k = jnp.where(
                idx_k < width,
                compact_lanes(cnt, k * width + idx_k, bucket), DB)
            return (
                k - 1, fset.table,
                is_new.at[lane_k].set(new_k, mode="drop"),
                lax.dynamic_update_slice(c_lane, lane_k, (used,)),
                lax.dynamic_update_slice(c_new, new_k, (used,)),
                used + jnp.max(jnp.where(new_k, row + 1, 0)),
                stat + stat_k,
            )

    # the claimant buffers hold every received lane and one segment's
    # overrun, in whole segments
    cap = (-(-DB // width) + 1) * width
    _, table, is_new, c_lane, c_new, c_rows, stat = lax.while_loop(
        lambda st: st[0] >= 0, insert_segment,
        (trips - 1, table, jnp.zeros(DB, bool),
         jnp.full(cap, DB, jnp.int32), jnp.zeros(cap, bool),
         jnp.int32(0), jnp.zeros(MESH_STAT_COLS, jnp.uint32)))
    return table, is_new, c_lane, c_new, c_rows, trips, stat


def enqueue_new_rows(queue, r_flat, is_new, qtail, n_go, width: int):
    """The new rows of one body onto the ring: `queue` is [qcap + 1, F]
    (the last row is the old scatter's dump row and is never written),
    `r_flat` [D * B, F] the received rows, `is_new` [D * B], `qtail`
    the tail before the body and `n_go` the rows to write: is_new's
    count, or 0 where the body halts on a full queue, which then
    leaves every row as it was.  Row `(qtail + k) % qcap` takes the
    k-th new lane in ascending lane order, what
    `pos = qtail + cumsum(is_new) - 1` gave the scatter this replaces.

    A compaction and contiguous writes, as engine.bfs's enqueue: one
    single-operand sort at D * B lanes brings the new lanes to the
    front in order; then `width` rows a trip, as many trips as the new
    rows need and not as the claimants, one row gather each and two
    slice writes, in place in the loop whose carry is the queue - no
    conditional holds it and nothing indexes it by row.  A block lies
    anywhere on the ring: the trip's lanes are rotated BEFORE the
    gather so that block row i already holds the row of ring position
    `start + i` (or, where the block passes row qcap - 1, of position
    i: what wrapped), and the two writes - at `start`, moved back to
    keep the block inside [0, qcap), and at row 0 - select with
    complementary masks against the slices read back from the same
    places, so a lane past the new rows, or on the other side of the
    seam, changes nothing.

    Returns (queue, trips)."""
    qcap = queue.shape[0] - 1
    F = queue.shape[1]
    (DB,) = is_new.shape
    A = min(width, qcap)
    lane = jnp.arange(DB, dtype=jnp.uint32)
    # the keys differ, so the sort need not be stable (a stable one
    # carries a second operand on the chip to break its ties)
    e_lane = lax.sort(jnp.where(is_new, lane, lane + jnp.uint32(DB)),
                      is_stable=False)
    e_lane = jnp.concatenate([e_lane, jnp.zeros(A, jnp.uint32)])
    i = jnp.arange(A, dtype=jnp.int32)

    def write_block(st):
        s, q = st
        offs = s * A
        p = (qtail + offs) % qcap
        # rows of the block in front of the ring's end; the rest wraps
        t = jnp.minimum(qcap - p, A)
        idx = lax.dynamic_slice(e_lane, (offs,), (A,))
        idx = lax.dynamic_slice(jnp.concatenate([idx, idx]), (t,), (A,))
        rows = r_flat[jnp.minimum(idx, DB - 1).astype(jnp.int32)]
        # block row i holds new row (i + t) % A of this trip
        wrapped = i < A - t
        live = offs + (i + t) % A < n_go
        for start, mask in ((p - (A - t), live & ~wrapped),
                            (jnp.int32(0), live & wrapped)):
            old = lax.dynamic_slice(q, (start, 0), (A, F))
            q = lax.dynamic_update_slice(
                q, jnp.where(mask[:, None], rows, old), (start, 0))
        return s + 1, q

    trips, queue = lax.while_loop(
        lambda st: st[0] * A < n_go, write_block, (jnp.int32(0), queue))
    return queue, trips


def route_geometry(backend: SpecBackend, chunk: int, D: int,
                   route_factor: float) -> dict:
    """What one body hands the two all_to_alls, from the static shapes:
    `bucket` slots per destination, and `step_bytes` a device - the
    candidate exchange's [D, B, F + 3] int32 (state words, fingerprint
    lo and hi, valid) plus the verdict return's [D, B] uint8.  The
    device's own bucket is in the count: it is packed and handed over
    like the others and never crosses a link.  `commit_rows` is the
    width of one segment of the owner-side insert (commit_width)."""
    B = route_bucket_width(chunk, backend.n_lanes, D, route_factor)
    return dict(bucket=B,
                step_bytes=D * B * (backend.cdc.n_fields + 3) * 4 + D * B,
                commit_rows=commit_width(chunk, D, B))


def make_sharded_engine(
    cfg: ModelConfig,
    mesh: Mesh,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    route_factor: float = 2.0,
    segment: int = 0,
    backend: SpecBackend = None,
    fp_highwater: float = None,
    pipeline: bool = False,
    obs_slots: int = 0,
    deferred: bool = None,
    _parts: dict = None,
):
    """Build (init_fn, run_fn) over `mesh` (single axis named "fp").

    chunk/queue_capacity/fp_capacity are PER DEVICE.  Exactness contract:
    identical generated/distinct/depth as the single-device engine for any
    device count (test_sharded.py verifies against the oracle counts).

    route_factor sizes the per-destination all_to_all buckets at
    route_factor * ncand / D (fingerprints spread candidates ~uniformly
    over owners, so 2x the mean keeps overflow probability negligible
    while the send buffer stays O(ncand) regardless of device count);
    a bucket overflow halts with VIOL_ROUTE_OVERFLOW rather than dropping
    a candidate.

    segment > 0 makes run_fn execute up to `segment` chunk steps (one
    `while`, bfs.run_steps; it ends with the check) instead of running
    to exhaustion - the checkpointing driver's unit of work.

    pipeline=True defers chunk k-1's verdict-return all_to_all into
    chunk k's body: the candidate-routing collective of chunk k is
    issued while the verdict return of chunk k-1 is still in flight,
    and the verdicts feed only source-side statistics (outdegree /
    per-action distinct - never control flow), so final counts are
    bit-for-bit those of the unpipelined engine; the loop runs one
    extra drain iteration at the end to apply the last chunk's stats.

    obs_slots > 0 carries the per-device observability counter ring
    (obs.counters): one partial-counter row per global level flip,
    summed host-side.  Pure telemetry - no control flow reads it - so
    results with obs on are bit-for-bit those of an obs-off run.  In
    pipeline mode the flip row is written one body LATE, after the
    deferred verdict exchange folds the flip chunk's stats, so
    per-level act_dist attributes to the correct level (the PR 5
    documented lag, since fixed; the deferred-row leaves on ShardCarry
    carry the staged flip across the body boundary).

    The owner-side insert (insert_compacted) takes what a body
    received, not the D*B bucket slots it arrived in: bucket d of the
    received batch holds its live rows as a prefix, so the candidates
    are inserted as a compacted stream, commit_width (= one per-device
    chunk) rows a segment behind a trip count; every gather, scatter
    and sort of the insert, and the deferred checker that walks its
    claimants, are that wide.  The enqueue walks neither the slots nor
    the claimants but what is new (enqueue_new_rows): one sort of the
    D*B verdicts brings the new lanes to the front in lane order, and
    their rows go onto the ring as blocks of the same width, a row
    gather and two contiguous slice writes each, as many blocks as the
    new rows need (`enqueue_segments`, the carry's commit_stat: one a
    body in the common case).  The highest segment goes first,
    which keeps the dedup's highest-lane representative across
    segments: counts, queue rows, per-action and outdegree statistics
    are bit-for-bit those of one insert over all D*B lanes, and the
    table holds the same fingerprints (slot order inside a bucket may
    differ where two segments claim in it).  `commit_segments` (per
    device, the carry's commit_stat) and `commit_rows` on the
    result and in the journal's `final` event say how often it ran.

    deferred (tri-state, resolved against the PER-DEVICE chunk by
    bfs.resolve_deferred) moves invariant evaluation OWNER-SIDE and
    POST-ROUTING (ISSUE 15): instead of every source device sweeping
    all chunk*L generated candidates pre-routing, the owner checks
    only the fresh-insert claimants of its received batch, compacted
    by the same insert it already pays (backend.make_deferred_checker,
    a commit_width segment of them at a time).  Counts, depth and the
    table's fingerprints are bit-for-bit; the violating STATE is then
    captured on the owner device under the pinned highest-lane rule
    instead of on the generating source (the viol_local machinery is
    device-agnostic either way).  The mesh engine has no certificate
    column, so the checker runs invariants only - exactly like the
    immediate mesh body, which never called cert_check either.
    """
    from .backend import require_unconstrained

    if backend is not None:
        # the mesh body has its own expand half (expand_half), which
        # does not part kept from counted: a constrained model is
        # refused by name, never run unconstrained
        require_unconstrained(backend, "the mesh-sharded engine (-sharded)")

    from ..obs.counters import (
        pack_row,
        ring_cols,
        ring_update,
        sticky_overflow,
        wrapped_any,
    )
    (axis,) = mesh.axis_names
    D = mesh.devices.size
    assert D & (D - 1) == 0, "device count must be a power of two"
    if fp_highwater is None:
        from .bfs import DEFAULT_FP_HIGHWATER

        fp_highwater = DEFAULT_FP_HIGHWATER
    assert 0.0 < fp_highwater <= 1.0, "fp_highwater must be in (0, 1]"
    if backend is None:
        backend = kubeapi_backend(cfg)
    cdc = backend.cdc
    F = cdc.n_fields
    step = backend.step
    L = backend.n_lanes
    inv_check = backend.inv_check
    n_labels = len(backend.labels)
    nbits = cdc.nbits
    qcap = queue_capacity
    ncand = chunk * L
    # per-destination bucket size: O(ncand/D) so send-buffer bytes stay
    # constant as the mesh grows (VERDICT round 2, weak #5)
    B = route_bucket_width(chunk, L, D, route_factor)
    from .bfs import resolve_deferred

    deferred = resolve_deferred(deferred, chunk)
    # state-space reduction (ISSUE 18) rides on the backend: orbit
    # canonicalization runs BEFORE fingerprinting so representatives
    # route to consistent owners on every device; the mesh engine has
    # no sticky ring columns, so (like the certificate column) the
    # orbit check is a single-device feature - sharded runs still get
    # the reduction itself
    red = backend.reduce
    sym_plan = red.plan if red is not None else None
    por_on = bool(
        red is not None and red.por and red.safe_ids
        and backend.lane_action is not None
    )
    if por_on:
        from .reduce import por_keep

        safe_vec = jnp.asarray(np.array(
            [a in red.safe_ids for a in range(n_labels)], bool
        ))
    # the owner inserts what it received as a compacted stream, W rows
    # at a time (commit_half); W is also the width the deferred checker
    # walks the insert's claimants at, and of a block of the enqueue
    DB = D * B
    W = commit_width(chunk, D, B)
    # owner-side deferred invariant checker (ISSUE 15)
    checker = None
    if deferred and backend.inv_codes:
        from .backend import make_deferred_checker

        checker = make_deferred_checker(
            backend, DB, probe_width=W, with_cert=False,
        )

    def owner_of(hi):
        return (hi & jnp.uint32(D - 1)).astype(jnp.int32)

    # ---------------- init ------------------------------------------------

    def init_fn() -> ShardCarry:
        inits = backend.initial_vectors()  # [n0, F] numpy
        if sym_plan is not None:
            # orbit-canonical seeds (host twin of the device canon):
            # Init is permutation-closed under the verified sets, so
            # canonicalizing loses no initial orbit
            inits = sym_plan.canon_host(inits)
        packed = cdc.pack(jnp.asarray(inits))
        lo, hi = _backend.fp64_words_mxu(packed, nbits, fp_index, seed)
        own = np.asarray(owner_of(hi))
        queue = np.zeros((D, qcap + 1, F), np.int32)
        qtail = np.zeros(D, np.int32)
        # interleaved bucket rows (fpset.FPSet layout); host_insert views
        # the same memory as flat [cap, 2] slot rows
        table = np.zeros((D, fp_capacity // 8, 16), np.uint32)
        lo_np, hi_np = np.asarray(lo), np.asarray(hi)
        distinct = np.zeros(D, np.uint32)
        for i in range(inits.shape[0]):
            d = int(own[i])
            # host-side insert (tiny): same probe sequence as the device set
            if host_insert(table[d], int(lo_np[i]), int(hi_np[i])):
                queue[d, qtail[d]] = inits[i]
                qtail[d] += 1
                distinct[d] += 1
        n0 = inits.shape[0]
        gen = np.zeros(D, np.uint32)
        gen[0] = n0  # count initial generation once (device 0's partial)
        pv = {}
        if backend.coverage is not None:
            # Init-site visits charged to device 0's partial (like the
            # initial-generation credit above)
            seed_row = backend.coverage.seed(inits)
            cov0 = np.zeros((D, len(seed_row)), np.uint32)
            cov0[0] = seed_row
            pv["cov_counts"] = jnp.asarray(cov0)
        if pipeline:
            pv.update(
                pv_send=jnp.zeros((D, D, B), jnp.uint8),
                pv_sown=jnp.zeros((D, ncand), jnp.int32),
                pv_pos=jnp.zeros((D, ncand), jnp.int32),
                pv_svalid=jnp.zeros((D, ncand), bool),
                pv_order=jnp.zeros((D, ncand), jnp.int32),
                pv_faction=jnp.zeros((D, ncand), jnp.int32),
                pv_n=jnp.zeros(D, jnp.int32),
            )
        obs = {}
        if obs_slots:
            obs = dict(
                obs_ring=jnp.zeros(
                    (D, obs_slots + 1, ring_cols(n_labels)), jnp.uint32
                ),
                obs_head=jnp.zeros(D, jnp.int32),
                obs_bodies=jnp.zeros(D, jnp.uint32),
                obs_expanded=jnp.zeros(D, jnp.uint32),
            )
            if pipeline:
                obs.update(
                    obs_pl_level=jnp.zeros(D, jnp.int32),
                    obs_pl_flag=jnp.zeros(D, bool),
                )
        return ShardCarry(
            table=jnp.asarray(table),
            queue=jnp.asarray(queue),
            qhead=jnp.zeros(D, jnp.int32),
            qtail=jnp.asarray(qtail),
            level_end=jnp.asarray(qtail),
            level=jnp.ones(D, jnp.int32),
            depth=jnp.ones(D, jnp.int32),
            generated=jnp.asarray(gen),
            distinct=jnp.asarray(distinct),
            act_gen=jnp.zeros((D, n_labels + 1), jnp.uint32),
            act_dist=jnp.zeros((D, n_labels + 1), jnp.uint32),
            outdeg_hist=jnp.zeros((D, L + 2), jnp.uint32),
            viol=jnp.zeros(D, jnp.int32),
            viol_state=jnp.zeros((D, F), jnp.int32),
            viol_local=jnp.zeros(D, bool),
            cont=jnp.ones(D, bool),
            route_stat=jnp.zeros((D, ROUTE_STAT_COLS), jnp.int32),
            commit_stat=jnp.zeros((D, MESH_STAT_COLS), jnp.uint32),
            **pv,
            **obs,
        )

    # ---------------- per-device loop body --------------------------------
    # Split at the owner seam (ISSUE 19): expand_half pops + expands +
    # routes, commit_half owns insert/invariants/enqueue/fences.  The
    # fused body below composes them back into the single while_loop
    # body this engine always ran; ShardedSpillRuntime runs the halves
    # as separate jits with a host SpillStore probe between them.

    def expand_half(c, with_member: bool = False) -> ShardEx:
        # c leaves have their [D] axis stripped to size 1 by shard_map; we
        # index [0] for scalars and keep arrays as-is.
        (qhead,) = c.qhead
        (qtail,) = c.qtail
        (level_end,) = c.level_end
        (viol,) = c.viol
        queue = c.queue[0]
        table = c.table[0]

        # ---- deferred verdict return of chunk k-1 (pipeline mode) ----
        # issued FIRST so this collective can be in flight while chunk
        # k's expansion + candidate-routing all_to_all below run; it
        # feeds only source-side statistics, never control flow.  With
        # nothing pending (pv_svalid all false, pv_n 0) nothing is new
        # and no popped row is counted, so fill/drain iterations are
        # exact no-ops.
        if pipeline:
            with jax.named_scope("jaxtlc.verdict_return"):
                verd_prev = lax.all_to_all(
                    c.pv_send[0], axis, split_axis=0, concat_axis=0,
                    tiled=False,
                )
                p_got = (
                    sorted_verdicts(verd_prev, c.pv_sown[0]) == 1
                ) & c.pv_svalid[0] & (c.pv_pos[0] < B)
                is_new_prev = (
                    jnp.zeros(ncand, bool).at[c.pv_order[0]].set(p_got)
                )
                newdeg_prev = is_new_prev.reshape(chunk, L).sum(axis=1)
                p_mask = jnp.arange(chunk, dtype=jnp.int32) < c.pv_n[0]
                outdeg_hist0 = c.outdeg_hist[0].at[:L + 1].add(
                    masked_hist(newdeg_prev, p_mask, L + 1))
                act_dist0 = c.act_dist[0].at[:n_labels].add(
                    masked_hist(c.pv_faction[0], is_new_prev, n_labels))
        else:
            outdeg_hist0 = c.outdeg_hist[0]
            act_dist0 = c.act_dist[0]

        # device scopes as bfs.make_stage_pair names them (expand >
        # pack_fp), plus the mesh's own: route, verdict_return, fence
        with jax.named_scope("jaxtlc.expand"):
            avail = jnp.minimum(level_end, qtail) - qhead
            # gate on viol so a halted or finished engine's body pops
            # nothing
            n = jnp.where(viol == OK, jnp.minimum(chunk, avail), 0)
            rows = jnp.arange(chunk, dtype=jnp.int32)
            mask = rows < n
            idx = (qhead + rows) % qcap
            batch = queue[idx]

            succs, valid, action, afail, ovf = jax.vmap(step)(batch)
            valid = valid & mask[:, None]
            afail = afail & valid
            ovf = ovf & valid
            dead = (
                mask & ~valid.any(axis=1) if backend.check_deadlock
                else jnp.zeros(chunk, bool)
            )
            if por_on:
                # singleton-ample pruning AFTER afail/ovf/dead are taken
                # from the full valid set: a pruned trapping transition
                # still halts, and POR never fabricates a deadlock
                valid = por_keep(valid, backend.lane_action, safe_vec,
                                 n_labels)

            flat = succs.reshape(ncand, F)
            fvalid = valid.reshape(-1)
            faction = action.reshape(-1)
            if sym_plan is not None:
                # canonicalize before invariants/pack/fingerprint: the
                # invariant sweep sees the orbit representative (sound -
                # symfind verified the invariants cannot distinguish
                # orbit members) and owners dedup representatives
                flat = sym_plan.canon(flat)

            # deferred mode skips the pre-routing chunk*L invariant
            # sweep: the owner checks its fresh-insert claimants below
            inv_bad = []
            if not deferred:
                inv = jax.vmap(inv_check)(flat)
                inv_bad = [
                    fvalid & ((inv & (1 << k)) == 0)
                    for k in range(len(backend.inv_codes))
                ]

            with jax.named_scope("jaxtlc.pack_fp"):
                packed = cdc.pack(flat)
                # the backend module's MXU fingerprint, as the one-chip
                # engines take it (same words as fingerprint.fp64_words)
                lo, hi = _backend.fp64_words_mxu(
                    packed, nbits, fp_index, seed)

        # ---- route candidates to owners over ICI ----
        # sort by owner -> bucket boundaries -> one row gather -> pack:
        # the stable sort leaves D contiguous buckets (and the invalid
        # tail), which the pack cuts to B slots each (B = route_factor *
        # ncand / D: send bytes stay O(ncand) as the mesh grows;
        # overflow halts rather than dropping a candidate).  Nothing
        # here indexes per element at candidate width: the boundaries
        # are D compare-and-sums over the unsorted key, a sorted lane's
        # owner, validity and position follow from them by arithmetic
        # (sorted_route), and the fingerprint words and the valid flag
        # ride the one row gather the sort needs anyway.  On the chip an
        # element gather costs 7-9 ns a lane live or dead, a row of 37
        # words under 3 (PERF.md section 6, PR 30)
        with jax.named_scope("jaxtlc.route"):
            key = jnp.where(fvalid, owner_of(hi), D)
            order = jnp.argsort(key, stable=True)
            counts = owner_counts(key, D)
            starts = jnp.cumsum(counts) - counts
            s_own, pos_in_bucket, s_valid = sorted_route(counts, ncand)
            route_ovf = (counts > B).any()
            # telemetry: the fullest destination bucket of this body
            route_fill = counts.max()
            payload = jnp.concatenate(
                [
                    flat,
                    lo.astype(jnp.int32)[:, None],
                    hi.astype(jnp.int32)[:, None],
                    fvalid.astype(jnp.int32)[:, None],
                ],
                axis=1,
            )[order]
            # the owner sort left bucket d's candidates contiguous from
            # starts[d]: slot (d, p) takes sorted row starts[d] + p
            # while p is under the bucket's count, and is zero past it.
            # One dynamic slice of B rows an owner (B zero rows behind
            # the payload, so none is clamped); as a row gather this
            # pack took 0.81 ms of a 2x1FF body on the chip, as slices
            # 0.11 (PERF.md, Step 0 of PR 30), and as a scatter of the
            # ncand rows to (owner, position) a tenth of the step
            # (section 5, Step 0 of PR 27)
            padded = jnp.concatenate(
                [payload, jnp.zeros((B, F + 3), jnp.int32)])
            rows = jnp.stack([
                lax.dynamic_slice(padded, (starts[d], 0), (B, F + 3))
                for d in range(D)])
            live = jnp.arange(B, dtype=jnp.int32)[None, :] < counts[:, None]
            send = jnp.where(live[:, :, None], rows, 0)
            recv = lax.all_to_all(send, axis, split_axis=0,
                                  concat_axis=0, tiled=False)
            r = recv.reshape(DB, F + 3)
            r_flat = r[:, :F]
            r_lo = r[:, F].astype(jnp.uint32)
            r_hi = r[:, F + 1].astype(jnp.uint32)
            r_valid = r[:, F + 2] == 1

        if with_member:
            # spill-mode owner filter: bounded membership walk over the
            # device table keeps definitely-old candidates off the host
            # round trip (engine.spill's MEMBER_ROUNDS rationale:
            # unresolved lanes safely degrade to a host probe)
            member = fpset_member(FPSet(table), r_lo, r_hi, r_valid,
                                  max_rounds=SPILL_MEMBER_ROUNDS)
        else:
            member = jnp.zeros(DB, bool)

        return ShardEx(
            outdeg0=outdeg_hist0,
            act_dist0=act_dist0,
            n=n,
            mask=mask,
            batch=batch,
            valid=valid,
            flat=flat,
            fvalid=fvalid,
            faction=faction,
            inv_bad=(jnp.stack(inv_bad) if inv_bad
                     else jnp.zeros((0, ncand), bool)),
            afail=afail,
            ovf=ovf,
            dead=dead,
            order=order,
            s_own=s_own,
            s_pos=pos_in_bucket,
            s_valid=s_valid,
            route_ovf=route_ovf,
            route_fill=route_fill,
            r_flat=r_flat,
            r_lo=r_lo,
            r_hi=r_hi,
            r_valid=r_valid,
            member=member,
        )

    def commit_half(c, ex: ShardEx, veto=None):
        (qhead,) = c.qhead
        (qtail,) = c.qtail
        (level_end,) = c.level_end
        (level,) = c.level
        (depth,) = c.depth
        (viol,) = c.viol
        (viol_local,) = c.viol_local
        queue = c.queue[0]
        table = c.table[0]
        viol_state = c.viol_state[0]
        spill = veto is not None
        (n, mask, batch, flat, fvalid, faction) = (
            ex.n, ex.mask, ex.batch, ex.flat, ex.fvalid, ex.faction
        )
        (order, s_own, pos_in_bucket, s_valid, route_ovf) = (
            ex.order, ex.s_own, ex.s_pos, ex.s_valid, ex.route_ovf
        )
        r_flat, r_lo, r_hi, r_valid = ex.r_flat, ex.r_lo, ex.r_hi, ex.r_valid
        outdeg_hist0, act_dist0 = ex.outdeg0, ex.act_dist0
        afail, ovf, dead, valid = ex.afail, ex.ovf, ex.dead, ex.valid
        inv_bad = [ex.inv_bad[k] for k in range(ex.inv_bad.shape[0])]

        # ---- dedup + insert at owner ----
        my_distinct = c.distinct[0]
        if spill:
            # the runtime's pre-step flush guarantees table room, and a
            # host-vetoed candidate dedups exactly like a table hit
            fp_full = jnp.bool_(False)
            ins_mask = r_valid & ~veto
        else:
            fp_full = (my_distinct.astype(jnp.int32) + DB) > int(
                fp_capacity * fp_highwater
            )
            ins_mask = r_valid & ~fp_full
        cnt = r_valid.reshape(D, B).sum(axis=1).astype(jnp.int32)
        table, is_new, c_lane, c_new, c_rows, _, cstat = (
            insert_compacted(table, r_lo, r_hi, ins_mask, cnt, W))

        with jax.named_scope("jaxtlc.enqueue"):
            n_new = is_new.sum().astype(jnp.int32)
            q_full = (qtail - qhead) + n_new > qcap
            # the new rows in lane order from the tail on, as a
            # compaction and contiguous writes, W rows a trip: the
            # trips follow what is new, not the insert's claimants (a
            # row scattered into the queue cost the chip ~140 ns live
            # or aimed at the dump row: 6.3 ms of a 2x1FF body for
            # ~11.5k new rows, PERF.md section 5, PR 37 and PR 40)
            queue, enq_trips = enqueue_new_rows(
                queue, r_flat, is_new, qtail,
                jnp.where(q_full, 0, n_new), W)

        # ---- route verdicts back to the source (second all_to_all) ----
        # back[d, p] = is_new of the candidate this device placed in bucket
        # d at position p - the outdegree (TLC's distinct-new-successors
        # per expanded state, MC.out:1104) needs source-side attribution.
        # Pipeline mode STASHES the exchange instead: the next body
        # issues it while its own routing collective is in flight.
        if pipeline:
            outdeg_hist = outdeg_hist0
            act_dist = act_dist0
        else:
            with jax.named_scope("jaxtlc.verdict_return"):
                verd = lax.all_to_all(
                    is_new.reshape(D, B).astype(jnp.uint8),
                    axis, split_axis=0, concat_axis=0, tiled=False,
                )
                got_new = (
                    sorted_verdicts(verd, s_own) == 1
                ) & s_valid & (pos_in_bucket < B)
                # undoing the permutation takes a gather or a scatter:
                # the one per-element index left on the source side
                is_new_local = jnp.zeros(ncand, bool).at[order].set(
                    got_new)
            with jax.named_scope("jaxtlc.level"):
                newdeg = is_new_local.reshape(chunk, L).sum(axis=1)
                outdeg_hist = outdeg_hist0.at[:L + 1].add(
                    masked_hist(newdeg, mask, L + 1))
                act_dist = act_dist0.at[:n_labels].add(
                    masked_hist(faction, is_new_local, n_labels))

        with jax.named_scope("jaxtlc.level"):
            generated = c.generated[0] + valid.sum().astype(jnp.uint32)
            distinct = my_distinct + n_new.astype(jnp.uint32)
            # the histograms by compare-reduce: their last bins (the
            # scatter-adds' dump bins, which nothing reads) stay in the
            # carry's shapes and are no longer written
            act_gen = c.act_gen[0].at[:n_labels].add(
                masked_hist(faction, fvalid, n_labels))

        cov_acc = {}
        if backend.coverage is not None:
            # device coverage plane: per-device partial visit counters,
            # summed across the mesh at readback (pure telemetry)
            cov = backend.coverage.count(batch, mask, valid).astype(
                jnp.uint32
            )
            cov_acc = dict(cov_counts=(c.cov_counts[0] + cov)[None])

        # ---- violations (local detect, global max) ----
        new_viol = jnp.int32(OK)
        new_vstate = viol_state
        chk_trips = jnp.int32(0)
        if checker is not None:
            # owner-side deferred invariants over the fresh-insert
            # claimants of the received batch (the r_* payload carries
            # no action ids - violation_action stays -1, as the
            # sharded result always reports)
            d_viol, d_state, _d_act, _d_cert, chk_trips = checker(
                r_flat, None, c_new[:DB], c_lane[:DB], c_rows
            )
            hit = d_viol != OK
            new_viol = jnp.where(hit, d_viol, new_viol)
            new_vstate = jnp.where(hit, d_state, new_vstate)
        # `per` candidates share a reported row: a lane's assert or
        # overflow reports its SOURCE state, `batch[at // L]` (as
        # engine.backend.make_expand_stage reads it)
        for code, vmask, states, per in (
            *((c, b, flat, 1) for c, b in zip(backend.inv_codes, inv_bad)),
            (VIOL_ASSERT, afail.reshape(-1), batch, L),
            (VIOL_DEADLOCK, dead, batch, 1),
            (VIOL_SLOT_OVERFLOW, ovf.reshape(-1), batch, L),
        ):
            hit = vmask.any() & (new_viol == OK)
            new_viol = jnp.where(hit, code, new_viol)
            new_vstate = jnp.where(
                hit, states[jnp.argmax(vmask) // per], new_vstate)
        new_viol = jnp.where(
            (new_viol == OK) & fp_full & r_valid.any(), VIOL_FPSET_FULL, new_viol
        )
        new_viol = jnp.where((new_viol == OK) & q_full, VIOL_QUEUE_FULL, new_viol)
        new_viol = jnp.where(
            (new_viol == OK) & route_ovf, VIOL_ROUTE_OVERFLOW, new_viol
        )
        with jax.named_scope("jaxtlc.fence"):
            global_viol = lax.pmax(
                jnp.where(viol == OK, new_viol, viol), axis)
        became = (viol == OK) & (new_viol != OK)
        viol_local2 = viol_local | became
        viol_state2 = jnp.where(became, new_vstate, viol_state)

        # ---- advance + level fencing (global) ----
        # `adv` gates the level bookkeeping so a halted engine's no-op
        # bodies (the spill runtime's, the drain body) cannot inflate
        # level/depth
        adv = viol == OK
        qhead = qhead + n
        qtail = jnp.where(q_full, qtail, qtail + n_new)
        rem_in_level = jnp.minimum(level_end, qtail) - qhead
        with jax.named_scope("jaxtlc.fence"):
            total_rem = lax.psum(rem_in_level, axis)
            total_left = lax.psum(qtail - qhead, axis)
        level_done = total_rem == 0
        more = total_left > 0
        level2 = jnp.where(adv & level_done & more, level + 1, level)
        depth2 = jnp.where(
            adv, jnp.maximum(depth, jnp.where(more, level2, level)), depth
        )
        level_end2 = jnp.where(adv & level_done, qtail, level_end)
        cont = more & (global_viol == OK)
        obs2 = {}
        if obs_slots:
            # one partial-counter row per GLOBAL level flip (level_done
            # is a psum verdict, so every device's ring stays in
            # lock-step); non-flip bodies write the dump row
            obs_bodies = c.obs_bodies[0] + jnp.uint32(1)
            obs_expanded = c.obs_expanded[0] + n.astype(jnp.uint32)
            wrapped = wrapped_any([
                (generated, c.generated[0]),
                (distinct, c.distinct[0]),
                (act_gen, c.act_gen[0]),
                (obs_bodies, c.obs_bodies[0]),
                (obs_expanded, c.obs_expanded[0]),
            ])
            if pipeline:
                # deferred-row scheme (ShardCarry docstring): write the
                # PREVIOUS body's staged flip row now - its lagging
                # act_dist just completed via the verdict fold at the
                # top of this body (act_dist0) - and stage this body's
                # flip.  Every other column is a cumulative counter
                # whose entry value here equals the flip body's exit
                # value, so the row is exact per-level attribution.
                row = pack_row(
                    c.obs_pl_level[0], c.generated[0], c.distinct[0],
                    c.qtail[0] - c.qhead[0], c.obs_bodies[0],
                    c.obs_expanded[0], c.act_gen[0][:n_labels],
                    act_dist0[:n_labels],
                    overflow=sticky_overflow(c.obs_ring[0], wrapped),
                )
                ring, rhead = ring_update(
                    c.obs_ring[0], c.obs_head[0], row, c.obs_pl_flag[0]
                )
                # only a body that globally popped can NEWLY flip: the
                # gate keeps no-op iterations (segment mode, the drain
                # body) from re-staging an already-written flip
                stage = (adv & level_done
                         & (lax.psum(n, axis) > 0))
                obs2 = dict(
                    obs_ring=ring[None], obs_head=rhead[None],
                    obs_bodies=obs_bodies[None],
                    obs_expanded=obs_expanded[None],
                    obs_pl_level=jnp.where(
                        stage, level, c.obs_pl_level[0]
                    )[None],
                    obs_pl_flag=stage[None],
                )
            else:
                row = pack_row(
                    level, generated, distinct, qtail - qhead,
                    obs_bodies, obs_expanded, act_gen[:n_labels],
                    act_dist[:n_labels],
                    overflow=sticky_overflow(c.obs_ring[0], wrapped),
                )
                ring, rhead = ring_update(
                    c.obs_ring[0], c.obs_head[0], row, adv & level_done
                )
                obs2 = dict(
                    obs_ring=ring[None], obs_head=rhead[None],
                    obs_bodies=obs_bodies[None],
                    obs_expanded=obs_expanded[None],
                )
        pv2 = {}
        if pipeline:
            # a popped chunk leaves its verdicts pending: keep the loop
            # alive one extra (drain) iteration so the last chunk's
            # statistics land; pmax keeps the flag replicated (devices
            # may finish their partitions at different times)
            pending_any = lax.pmax((n > 0).astype(jnp.int32), axis) > 0
            cont = cont | pending_any
            pv2 = dict(
                pv_send=is_new.reshape(D, B).astype(jnp.uint8)[None],
                pv_sown=s_own.astype(jnp.int32)[None],
                pv_pos=pos_in_bucket.astype(jnp.int32)[None],
                pv_svalid=s_valid[None],
                pv_order=order.astype(jnp.int32)[None],
                pv_faction=faction.astype(jnp.int32)[None],
                pv_n=n[None],
            )
        sp = {}
        if c.spill_hits is not None:
            hits = c.spill_hits[0]
            if spill:
                # host-vetoed candidates dedup like table hits; the
                # count is pure telemetry (SupervisedResult.spill_hits)
                hits = hits + (veto & r_valid).sum().astype(jnp.uint32)
            sp = dict(spill_hits=hits[None])
        route_stat = jnp.stack([
            jnp.maximum(c.route_stat[0, 0], ex.route_fill),
            c.route_stat[0, 1] + 1,
        ])
        commit_stat = c.commit_stat[0] + cstat + count_block(
            MESH_COUNTS, lead=COMMIT_STAT_COLS, bodies=1, new=n_new,
            checker_trips=chk_trips, enqueue_trips=enq_trips)

        return ShardCarry(
            table=table[None],
            queue=queue[None],
            qhead=qhead[None],
            qtail=qtail[None],
            level_end=level_end2[None],
            level=level2[None],
            depth=depth2[None],
            generated=generated[None],
            distinct=distinct[None],
            act_gen=act_gen[None],
            act_dist=act_dist[None],
            outdeg_hist=outdeg_hist[None],
            viol=global_viol[None],
            viol_state=viol_state2[None],
            viol_local=viol_local2[None],
            cont=cont[None],
            route_stat=route_stat[None],
            commit_stat=commit_stat[None],
            **pv2,
            **obs2,
            **cov_acc,
            **sp,
        )

    def body(c):
        # the fused composition: bit-identical to the historical single
        # fused body (the seam only names intermediates; no collective,
        # insert or fence moved across it)
        return commit_half(c, expand_half(c))

    def device_loop(c: ShardCarry) -> ShardCarry:
        return lax.while_loop(lambda cc: cc.cont[0], body, c)

    def device_segment(c: ShardCarry) -> ShardCarry:
        # up to `segment` bodies, as the one-chip engines' segments
        # (bfs.run_steps): a finished or halted check leaves the loop
        # instead of running its last segment out on empty pops
        return run_steps(lambda cc: cc.cont[0], body, c, segment)

    pv_specs = {}
    if pipeline:
        pv_specs = {
            f: P(axis)
            for f in ("pv_send", "pv_sown", "pv_pos", "pv_svalid",
                      "pv_order", "pv_faction", "pv_n")
        }
    if obs_slots:
        pv_specs.update({
            f: P(axis)
            for f in ("obs_ring", "obs_head", "obs_bodies",
                      "obs_expanded")
        })
        if pipeline:
            pv_specs.update(
                obs_pl_level=P(axis), obs_pl_flag=P(axis)
            )
    if backend.coverage is not None:
        pv_specs["cov_counts"] = P(axis)
    specs = ShardCarry(
        table=P(axis),
        queue=P(axis),
        qhead=P(axis),
        qtail=P(axis),
        level_end=P(axis),
        level=P(axis),
        depth=P(axis),
        generated=P(axis),
        distinct=P(axis),
        act_gen=P(axis),
        act_dist=P(axis),
        outdeg_hist=P(axis),
        viol=P(axis),
        viol_state=P(axis),
        viol_local=P(axis),
        cont=P(axis),
        route_stat=P(axis),
        commit_stat=P(axis),
        **pv_specs,
    )
    run_fn = jax.jit(
        shard_map(
            device_segment if segment > 0 else device_loop,
            mesh=mesh,
            in_specs=(specs,),
            out_specs=specs,
            check_vma=False,
        )
    )
    if _parts is not None:
        # the ShardedSpillRuntime seam: the two body halves plus the
        # geometry it needs to jit them as separate shard_map dispatches
        _parts.update(
            expand_half=expand_half, commit_half=commit_half,
            specs=specs, axis=axis, D=D, B=B, ncand=ncand, F=F,
            n_inv=(0 if deferred else len(backend.inv_codes)),
            chunk_l=(chunk, L), pipeline=pipeline,
        )
    return init_fn, run_fn


# ---------------- multi-process shard access helpers ---------------------
# The spill runtime and the jax.distributed pod driver (jaxtlc.dist) both
# need host access to [D, ...]-sharded carry leaves.  In a single process
# every row is addressable and np.asarray works; in a pod each process
# sees only its own rows, and functional updates must go through
# jax.make_array_from_callback (a collective-style constructor every
# process calls with its addressable rows).


def shard_host_rows(arr) -> dict:
    """Host copies of the ADDRESSABLE rows of a [D, ...]-sharded array,
    keyed by global row index (single-process: every row)."""
    if jax.process_count() == 1:
        a = np.asarray(arr)
        return {i: a[i] for i in range(a.shape[0])}
    out = {}
    for sh in arr.addressable_shards:
        start = sh.index[0].start or 0
        data = np.asarray(sh.data)
        for k in range(data.shape[0]):
            out[start + k] = data[k]
    return out


def shard_replace_rows(arr, rows: dict):
    """Functionally replace rows of a [D, ...]-sharded array from a
    {global_row: np value} dict; unlisted rows keep their value.  In a
    pod every process must call this collectively, each passing its OWN
    addressable rows (make_array_from_callback contract)."""
    if jax.process_count() == 1:
        a = np.asarray(arr).copy()
        for r, v in rows.items():
            a[r] = v
        return jnp.asarray(a)
    local = shard_host_rows(arr)
    local.update({r: v for r, v in rows.items() if r in local})

    def cb(idx):
        s = idx[0]
        stop = s.stop if s.stop is not None else arr.shape[0]
        return np.stack([local[r] for r in range(s.start or 0, stop)])

    return jax.make_array_from_callback(arr.shape, arr.sharding, cb)


def shard_global(mesh: Mesh, arr):
    """A ["fp"]-sharded global device array from a host-replicated numpy
    value (every pod process passes the SAME full array and contributes
    its addressable rows); single-process: a plain device put."""
    a = np.asarray(arr)
    if jax.process_count() == 1:
        return jnp.asarray(a)
    from jax.sharding import NamedSharding

    (axis,) = mesh.axis_names
    return jax.make_array_from_callback(
        a.shape, NamedSharding(mesh, P(axis)), lambda idx: a[idx]
    )


def carry_to_global(mesh: Mesh, carry: ShardCarry) -> ShardCarry:
    """Lift a host-built ShardCarry (init_fn output, identical on every
    process) into globally-sharded arrays over `mesh`."""
    return jax.tree.map(lambda x: shard_global(mesh, x), carry)


class ShardedSpillRuntime:
    """Spill-mode execution of the MESH engine (ISSUE 19, the sharded
    twin of engine.spill.SpillRuntime): the supervisor swaps its segment
    function for `segment_fn` when the ladder activates the spill tier
    on a sharded run, keeping checkpoints/retry/regrow unchanged.

    The runtime drives the engine's own expand/commit halves as two
    shard_map dispatches with a host probe between them:

        expand + owner fpset_member filter (device, all_to_all inside)
        -> probable-new readback of THIS HOST's rows ->
        SpillStore probe (host) -> commit with the host veto (device)

    One SpillStore per process: fingerprint spaces are disjoint across
    devices (owner = hi & (D-1)), so a single host store is exact for
    every local device, and in a jax.distributed pod each process's
    store is precisely the per-host lifeboat - a fingerprint lives in
    its owner device's table or its owner HOST's store, never both.

    The flush decision is a device-side collective (pmax over per-table
    occupancy), so every pod process takes the flush on the same chunk
    step - required, because resetting the global table is a collective
    array construction.  But the SWEEP is selective (ROADMAP #1 residue
    (c) closed): each host migrates only its local tables that actually
    crossed the highwater threshold, judged from the same occupancy
    readback that fed the pmax - an under-water table keeps its hot
    fingerprints resident instead of being eagerly dumped to the cold
    tier.  Still deterministic and exact: the needy set is a pure
    function of the collective step's occupancies, identical on every
    process, and a fingerprint lives in its owner's table or its owner
    host's store, never both.

    Exactness: a host-vetoed candidate dedups exactly like an owner-
    table hit, so counters/verdict are bit-for-bit a correctly-sized
    clean sharded run's (tests/test_multihost.py::
    test_pod_over_capacity_needs_spill pins the counts)."""

    def __init__(self, cfg, mesh: Mesh, chunk: int, queue_capacity: int,
                 fp_capacity: int, fp_index: int = DEFAULT_FP_INDEX,
                 seed: int = DEFAULT_SEED, route_factor: float = 2.0,
                 backend: SpecBackend = None, fp_highwater: float = None,
                 obs_slots: int = 0, deferred: bool = None, store=None,
                 on_event=None, spill_write_hook=None):
        from .spill import SpillStore

        if backend is None:
            backend = kubeapi_backend(cfg)
        if fp_highwater is None:
            from .bfs import DEFAULT_FP_HIGHWATER

            fp_highwater = DEFAULT_FP_HIGHWATER
        parts = {}
        init_fn, _ = make_sharded_engine(
            cfg, mesh, chunk, queue_capacity, fp_capacity,
            fp_index=fp_index, seed=seed, route_factor=route_factor,
            backend=backend, fp_highwater=fp_highwater, pipeline=False,
            obs_slots=obs_slots, deferred=deferred,
            _parts=parts,
        )
        self.backend = backend
        self.mesh = mesh
        self.chunk = chunk
        self.fp_capacity = fp_capacity
        self.fp_highwater = fp_highwater
        self.store = store if store is not None else SpillStore()
        self.on_event = on_event
        # fault seam: called before every host flush (resil.faults
        # spill_fail@N raises OSError here)
        self.spill_write_hook = spill_write_hook
        self.flushes = 0
        self.probes = 0  # candidates that paid the host round trip
        self._base_init = init_fn
        self._D = D = parts["D"]
        self._DB = DB = D * parts["B"]
        axis = parts["axis"]
        self._axis = axis
        expand_half = parts["expand_half"]
        commit_half = parts["commit_half"]
        specs = parts["specs"]._replace(spill_hits=P(axis))
        self._specs = specs
        ex_specs = ShardEx(*(P(axis) for _ in ShardEx._fields))

        def _expand_dev(c):
            ex = expand_half(c, with_member=True)
            return jax.tree.map(lambda x: x[None], ex)

        def _commit_dev(c, ex, veto):
            return commit_half(c, jax.tree.map(lambda x: x[0], ex),
                               veto[0])

        def _res_dev(table):
            # per-device table occupancy + the collective flush verdict
            # (measured, not derived from the distinct counter, so a
            # rolled-back carry whose failed attempt already flushed
            # entries stays exact - engine.spill's rationale)
            t = table[0]
            lo = t[:, 0::2].reshape(-1)
            hi = t[:, 1::2].reshape(-1)
            occ = ((lo != 0) | (hi != 0)).sum().astype(jnp.int32)
            need = occ + DB > int(fp_capacity * fp_highwater)
            any_need = lax.pmax(need.astype(jnp.int32), axis)
            return occ[None], any_need[None]

        self._expand_fn = jax.jit(shard_map(
            _expand_dev, mesh=mesh, in_specs=(specs,),
            out_specs=ex_specs, check_vma=False,
        ))
        self._commit_fn = jax.jit(shard_map(
            _commit_dev, mesh=mesh, in_specs=(specs, ex_specs, P(axis)),
            out_specs=specs, check_vma=False,
        ))
        self._res_fn = jax.jit(shard_map(
            _res_dev, mesh=mesh, in_specs=(P(axis),),
            out_specs=(P(axis), P(axis)), check_vma=False,
        ))
        # the preflight self-check's composition: one full device step
        # with an all-false veto (the host probe happens between the two
        # jits in production, outside any device body)

        def audit_step(c):
            ex = self._expand_fn(c)
            return self._commit_fn(
                c, ex, jnp.zeros((D, DB), bool)
            )

        audit_step.donate_requested = False
        audit_step.donates_carry = False
        self.audit_step_fn = audit_step

    # -- carries ---------------------------------------------------------

    def init_fn(self):
        """Fresh spill-mode carry (also the checkpoint template)."""
        c = self._base_init()
        if jax.process_count() > 1:
            c = carry_to_global(self.mesh, c)
        return self.adopt(c)

    def adopt(self, carry: ShardCarry) -> ShardCarry:
        """Enter spill mode: add the spill_hits leaf (idempotent).  The
        saturated device tables stay put - the first chunk's residency
        collective flushes them to the host store."""
        assert carry.pv_n is None, \
            "spill mode runs unpipelined sharded carries only"
        if carry.spill_hits is None:
            carry = carry._replace(
                spill_hits=shard_global(
                    self.mesh, np.zeros(self._D, np.uint32)
                )
            )
        return carry

    def _emit(self, kind: str, **info) -> None:
        if self.on_event is not None:
            self.on_event(kind, info)

    # -- host readbacks (replicated scalars: any addressable row works) --

    def _cont(self, carry) -> bool:
        return bool(np.any([v for v in
                            shard_host_rows(carry.cont).values()]))

    def _viol(self, carry) -> int:
        return int(max(int(v) for v in
                       shard_host_rows(carry.viol).values()))

    def _hits(self, carry) -> int:
        return int(sum(int(v) for v in
                       shard_host_rows(carry.spill_hits).values()))

    # -- the host-driven step loop --------------------------------------

    def _flush(self, carry: ShardCarry, needy=None) -> ShardCarry:
        """Migrate this host's OVER-HIGHWATER device tables into the
        store and reset their global rows (all processes flush on the
        same chunk step - the residency verdict is a pmax; the
        shard_replace_rows construction is collective either way).
        `needy` is the set of local row ids to sweep (None = all, the
        pre-highwater whole-table semantics adopt/recover paths use).
        Raises OSError through spill_write_hook under fault
        injection."""
        try:
            if self.spill_write_hook is not None:
                self.spill_write_hook()
        except OSError as e:
            from .spill import SpillWriteError

            raise SpillWriteError(str(e)) from e
        from .fpset import unmix_host

        t_flush = time.time()
        rows = shard_host_rows(carry.table)
        zeroed = {}
        resident = 0
        for d, t in rows.items():
            lo = t[:, 0::2].reshape(-1)
            hi = t[:, 1::2].reshape(-1)
            occ = (lo != 0) | (hi != 0)
            if needy is not None and d not in needy:
                # under-water table: its fingerprints stay resident
                resident += int(occ.sum())
                continue
            raw_lo, raw_hi = unmix_host(lo[occ], hi[occ])
            self.store.insert_batch(raw_lo, raw_hi)
            zeroed[d] = np.zeros_like(t)
        self.flushes += 1
        carry = carry._replace(
            table=shard_replace_rows(carry.table, zeroed)
        )
        self._emit(
            "spill", phase="flush", resident=resident,
            spilled=self.store.count, capacity=self.store.capacity,
            hits=self._hits(carry), probes=self.probes,
            flushed_tables=len(zeroed),
            wall_s=round(time.time() - t_flush, 6),
        )
        return carry

    def _veto_array(self, rows: dict):
        if jax.process_count() == 1:
            a = np.zeros((self._D, self._DB), bool)
            for r, v in rows.items():
                a[r] = v
            return jnp.asarray(a)
        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, P(self._axis))

        def cb(idx):
            s = idx[0]
            stop = s.stop if s.stop is not None else self._D
            return np.stack([rows[r] for r in range(s.start or 0, stop)])

        return jax.make_array_from_callback(
            (self._D, self._DB), sharding, cb
        )

    def segment_fn(self, ckpt_every: int):
        """seg_fn(carry) -> carry after up to `ckpt_every` chunk steps
        (synchronous - the host sits in the loop; the supervisor's
        block_until_ready at the fence is then a no-op).  Chunk steps
        and their pop sequence match the fused sharded body's, so
        bit-for-bit parity with a clean run holds."""

        highwater_slots = int(self.fp_capacity * self.fp_highwater)

        def seg(carry):
            for _ in range(ckpt_every):
                if not self._cont(carry):
                    break
                occ, need = self._res_fn(carry.table)
                if max(int(v) for v in
                       shard_host_rows(need).values()):
                    # collective verdict (pmax) says SOME device crossed
                    # highwater: every process enters the flush on this
                    # step, but each sweeps only its local tables that
                    # are actually over the threshold (same predicate
                    # the device residency check evaluates)
                    needy = {
                        d for d, v in shard_host_rows(occ).items()
                        if int(v) + self._DB > highwater_slots
                    }
                    carry = self._flush(carry, needy=needy)
                ex = self._expand_fn(carry)
                lo_rows = shard_host_rows(ex.r_lo)
                hi_rows = shard_host_rows(ex.r_hi)
                va_rows = shard_host_rows(ex.r_valid)
                mb_rows = shard_host_rows(ex.member)
                veto_rows = {}
                for d in lo_rows:
                    probable = va_rows[d] & ~mb_rows[d]
                    veto = np.zeros(self._DB, bool)
                    npn = int(probable.sum())
                    if npn:
                        self.probes += npn
                        veto[probable] = self.store.probe(
                            lo_rows[d][probable], hi_rows[d][probable]
                        )
                    veto_rows[d] = veto
                carry = self._commit_fn(
                    carry, ex, self._veto_array(veto_rows)
                )
                if self._viol(carry) != OK:
                    break
            return carry

        return seg


def result_from_shard_carry(
    out: ShardCarry, wall: float, iterations: int = -1,
    labels: tuple = LABELS, viol_names: dict = None,
    fp_capacity_total: int = 0, sites: tuple = None,
    route: dict = None,
) -> CheckResult:
    """Globally-reduced statistics from a (finished or paused) carry.

    fp_capacity_total (= per-device fp_capacity * device count) enables
    the fp_occupancy fraction on the result; `route` (route_geometry of
    the engine that ran the carry) the owner-routing counters."""
    routing = {}
    if route is not None and getattr(out, "route_stat", None) is not None:
        stat = np.asarray(out.route_stat)
        routing = dict(
            route_max_fill=int(stat[:, 0].max()),
            route_bucket=int(route["bucket"]),
            # every device runs every body: column 1 is the same on all
            route_bytes=int(stat[:, 1].max()) * int(route["step_bytes"]),
            commit_rows=int(route["commit_rows"]),
        )
    commit = {}
    if getattr(out, "commit_stat", None) is not None:
        # summed over the devices, but for the two counts that had a
        # name a device before they had a block
        stat = np.asarray(out.commit_stat).astype(np.int64)
        commit = commit_result_fields(
            stat.sum(axis=0),
            route and commit_widths(int(route["commit_rows"])),
            MESH_COUNTS, None)
        commit.pop("commit_enqueue_trips")
        commit.update(
            commit_segments=tuple(
                stat[:, COMMIT_COUNTS.index("probe_segments")].tolist()),
            enqueue_segments=tuple(stat[:, -1].tolist()))
    act_gen = np.asarray(out.act_gen).sum(axis=0)[: len(labels)]
    act_dist = np.asarray(out.act_dist).sum(axis=0)[: len(labels)]
    hist = np.asarray(out.outdeg_hist).sum(axis=0)[:-1].astype(np.int64)
    viol = int(np.asarray(out.viol).max())
    vstate = np.zeros(out.viol_state.shape[-1], np.int32)
    vl = np.asarray(out.viol_local)
    if vl.any():
        vstate = np.asarray(out.viol_state)[np.argmax(vl)]
    vname = (viol_names or {}).get(viol) or VIOLATION_NAMES.get(
        viol, f"violation {viol}"
    )
    site_coverage = None
    if sites is not None and getattr(out, "cov_counts", None) is not None:
        from ..obs.coverage import site_totals_dict
        from .bfs import cov_totals

        site_coverage = site_totals_dict(sites, cov_totals(out))
    return CheckResult(
        generated=int(np.asarray(out.generated).sum()),
        distinct=int(np.asarray(out.distinct).sum()),
        depth=int(np.asarray(out.depth).max()),
        queue_left=int((np.asarray(out.qtail) - np.asarray(out.qhead)).sum()),
        violation=viol,
        violation_name=vname,
        violation_state=vstate,
        violation_action=-1,
        action_generated={
            labels[i]: int(v) for i, v in enumerate(act_gen) if v
        },
        action_distinct={
            labels[i]: int(v) for i, v in enumerate(act_dist) if v
        },
        wall_s=wall,
        iterations=iterations,
        outdegree=outdegree_from_hist(hist),
        fp_occupancy=(
            int(np.asarray(out.distinct).sum()) / fp_capacity_total
            if fp_capacity_total else None
        ),
        shard_distinct=tuple(
            int(v) for v in np.asarray(out.distinct).reshape(-1)
        ),
        shard_generated=tuple(
            int(v) for v in np.asarray(out.generated).reshape(-1)
        ),
        site_coverage=site_coverage,
        **routing,
        **commit,
    )


def obs_rows_sharded(carry: ShardCarry, labels: tuple = None,
                     since: int = 0, fp_capacity_total: int = 0):
    """Decode a ShardCarry's observability rings (per-device partials
    summed per level) into journal-`level`-event dicts + the new head
    cursor; ([], since) when obs is off."""
    from ..obs.counters import shard_rows_from_ring

    if getattr(carry, "obs_ring", None) is None:
        return [], int(since)
    heads = np.asarray(carry.obs_head)
    return (
        shard_rows_from_ring(
            np.asarray(carry.obs_ring), heads, labels=labels,
            since=since, fp_capacity_total=fp_capacity_total,
        ),
        int(heads.min()),
    )


def obs_rows_sharded_local(carry: ShardCarry, labels: tuple = None,
                           since: int = 0, fp_capacity_total: int = 0):
    """Pod twin of obs_rows_sharded: decode only THIS process's
    ADDRESSABLE ring rows into per-host PARTIAL `level` events (every
    device flips levels in lock-step - the level fence is a global psum
    - so summing the local subset per row yields this host's partial
    cumulative counters for the same level sequence).  The obs.views
    fold (fold_pod_levels) sums the per-host partials back into
    pod-global rows.  `fp_capacity_total` should be the GLOBAL pod
    capacity so each host's fp_load is its partial contribution and the
    fold can SUM loads.  Returns (rows, new local-min head cursor);
    ([], since) when obs is off."""
    from ..obs.counters import shard_rows_from_ring

    if getattr(carry, "obs_ring", None) is None:
        return [], int(since)
    rings = shard_host_rows(carry.obs_ring)
    heads = shard_host_rows(carry.obs_head)
    ids = sorted(rings)
    local_ring = np.stack([np.asarray(rings[i]) for i in ids])
    local_heads = np.asarray([int(heads[i]) for i in ids])
    return (
        shard_rows_from_ring(
            local_ring, local_heads, labels=labels, since=since,
            fp_capacity_total=fp_capacity_total,
        ),
        int(local_heads.min()),
    )


def cov_totals_local(carry: ShardCarry):
    """This process's PARTIAL site-coverage totals: the int64 sum of
    its addressable cov_counts rows (a site accrues counts on every
    device that processes its candidates, so summing each host's
    partial deltas across the pod reproduces the global totals).  None
    when the carry has no coverage plane."""
    if getattr(carry, "cov_counts", None) is None:
        return None
    rows = shard_host_rows(carry.cov_counts)
    return np.sum(
        [np.asarray(v, np.int64) for v in rows.values()], axis=0
    )


def drain_pending_host(carry: ShardCarry) -> ShardCarry:
    """Apply a pipelined carry's pending verdict statistics host-side.

    The deferred verdict exchange is a pure permutation - the verdict
    for the candidate that source device s placed in owner o's bucket is
    pv_send[o, s] - so it can be replayed exactly on the host.  The
    regrow migration calls this before a route_factor change resizes the
    bucket axis; because the adds commute, a drained carry replays to
    the same final statistics as an undrained one.  Unpipelined carries
    pass through untouched."""
    if carry.pv_n is None:
        return carry
    send = np.asarray(carry.pv_send)  # [D owner, D source, B]
    D, _, B = send.shape
    sown = np.asarray(carry.pv_sown)
    pos = np.asarray(carry.pv_pos)
    svalid = np.asarray(carry.pv_svalid)
    order = np.asarray(carry.pv_order)
    faction = np.asarray(carry.pv_faction)
    pv_n = np.asarray(carry.pv_n)
    ncand = sown.shape[1]
    outdeg = np.asarray(carry.outdeg_hist).astype(np.int64)
    act_dist = np.asarray(carry.act_dist).astype(np.int64)
    L = outdeg.shape[1] - 2
    chunk = ncand // L
    for s in range(D):
        verd = send[:, s, :]
        got = (
            (verd[np.clip(sown[s], 0, D - 1),
                  np.clip(pos[s], 0, B - 1)] == 1)
            & svalid[s] & (pos[s] < B)
        )
        is_new_local = np.zeros(ncand, bool)
        is_new_local[order[s]] = got
        newdeg = is_new_local.reshape(chunk, L).sum(axis=1)
        mask = np.arange(chunk) < pv_n[s]
        # bit-for-bit what the deferred device application would have
        # added (the device counts by compare-reduce: no dump bin)
        np.add.at(outdeg[s], newdeg[mask], 1)
        np.add.at(act_dist[s], faction[s][is_new_local], 1)
    return carry._replace(
        outdeg_hist=jnp.asarray(outdeg.astype(np.uint32)),
        act_dist=jnp.asarray(act_dist.astype(np.uint32)),
        pv_send=jnp.zeros_like(jnp.asarray(send)),
        pv_svalid=jnp.zeros((D, ncand), bool),
        pv_n=jnp.zeros(D, jnp.int32),
    )


def sharded_survive_fixpoint(
    mesh: Mesh,
    n_states: int,
    src: np.ndarray,
    dst: np.ndarray,
    in_h: np.ndarray,
    terminal: np.ndarray,
):
    """Mesh-parallel greatest-fixpoint survive sweep for the device
    liveness subsystem (jaxtlc.live.fixpoint): the EDGE relation is
    sharded over the mesh axis (each device owns an E/D slice of the
    captured (src, dst) tensors), the per-state survive bit-plane is
    replicated, and every sweep reduces the per-device successor-support
    partials with a psum - the liveness analog of the BFS engine's
    fingerprint-space partitioning, over the same mesh.

    survive(s) iff s in H and (terminal(s) or some captured state-changing
    successor of s survives); the whole converging `lax.while_loop` runs
    inside one shard_map dispatch.  Returns (alive bool [V], sweeps).

    Caller contract: (src, dst) are already restricted to state-changing
    edges internal to H (jaxtlc.live.fixpoint filters them).
    """
    (axis,) = mesh.axis_names
    D = mesh.devices.size
    V = int(n_states)
    E = len(src)
    Ep = max(-(-max(E, 1) // D) * D, D)
    # pad with src = V: out of range, dropped by the scatter
    src_p = np.full(Ep, V, np.int32)
    dst_p = np.zeros(Ep, np.int32)
    src_p[:E] = src
    dst_p[:E] = dst

    def run(src_s, dst_s, in_h_r, term_r):
        def body(st):
            alive, _, sweeps = st
            part = jnp.zeros(V, jnp.int32).at[src_s].max(
                alive[dst_s].astype(jnp.int32), mode="drop"
            )
            support = lax.psum(part, axis) > 0
            alive2 = alive & (term_r | support)
            return alive2, (alive2 != alive).any(), sweeps + 1

        return lax.while_loop(
            lambda st: st[1],
            body,
            (in_h_r, jnp.bool_(True), jnp.int32(0)),
        )

    fn = jax.jit(
        shard_map(
            run,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )
    alive, _, sweeps = jax.block_until_ready(
        fn(
            jnp.asarray(src_p),
            jnp.asarray(dst_p),
            jnp.asarray(in_h, bool),
            jnp.asarray(terminal, bool),
        )
    )
    return np.asarray(alive), int(sweeps)


def check_sharded(
    cfg: ModelConfig,
    mesh: Mesh,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    route_factor: float = 2.0,
    backend: SpecBackend = None,
    pipeline: bool = False,
    obs_slots: int = 0,
    deferred: bool = None,
) -> CheckResult:
    """Exhaustive sharded check; returns globally-reduced statistics.

    The fused loop is AOT-compiled before the timer starts, matching the
    single-device engine's timing discipline (bfs.check)."""
    if backend is None:
        backend = kubeapi_backend(cfg)
    init_fn, run_fn = make_sharded_engine(
        cfg, mesh, chunk, queue_capacity, fp_capacity,
        route_factor=route_factor, backend=backend, pipeline=pipeline,
        obs_slots=obs_slots, deferred=deferred,
    )
    carry = init_fn()
    compiled = run_fn.lower(carry).compile()
    t0 = time.time()
    out = jax.block_until_ready(compiled(carry))
    wall = time.time() - t0
    return result_from_shard_carry(
        out, wall, labels=backend.labels, viol_names=backend.viol_names,
        fp_capacity_total=fp_capacity * mesh.devices.size,
        sites=backend.coverage.sites if backend.coverage else None,
        route=route_geometry(backend, chunk, int(mesh.devices.size),
                             route_factor),
    )


def check_sharded_with_checkpoints(
    cfg: ModelConfig,
    mesh: Mesh,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    route_factor: float = 2.0,
    ckpt_path: str = None,
    ckpt_every: int = 256,
    resume: bool = False,
    max_segments: int = None,
    backend: SpecBackend = None,
    meta_config: dict = None,
    pipeline: bool = False,
    obs_slots: int = 0,
    deferred: bool = None,
) -> CheckResult:
    """Sharded check with periodic whole-carry checkpoints (TLC checkpoint
    analog under distribution: one snapshot covers every shard's partition
    of the fingerprint space + frontier).  Same contract as
    checkpoint.check_with_checkpoints, over the mesh engine."""
    from .bfs import resolve_deferred
    from .checkpoint import (
        _meta,
        load_checkpoint,
        require_checkpoint,
        save_checkpoint,
    )

    program = cfg if backend is None else backend
    if backend is None:
        backend = kubeapi_backend(cfg)
    if resume:
        require_checkpoint(ckpt_path)
    deferred = resolve_deferred(deferred, chunk)
    from ..runtime import aot_build, engine_key

    # the reduction flags ride on the backend; a reduced run explores a
    # DIFFERENT (smaller) frontier, so resuming a reduced checkpoint
    # without the flags (or vice versa) must mismatch loudly
    red = getattr(backend, "reduce", None)
    meta = _meta(
        cfg,
        meta_config=meta_config,
        queue_capacity=queue_capacity,
        fp_capacity=fp_capacity,
        devices=int(mesh.devices.size),
        pipeline=pipeline,
        obs_slots=obs_slots,
        deferred=deferred,
        symmetry=bool(red is not None and red.plan is not None),
        por=bool(red is not None and red.por and red.safe_ids),
    )
    # the one AOT build (its `build*` spans), as the supervisor's; kept
    # under the meta and what the meta leaves out (a backend built
    # afresh a call, as the gen frontend's, is a new key a call)
    template, compiled = aot_build(lambda: make_sharded_engine(
        cfg, mesh, chunk, queue_capacity, fp_capacity,
        route_factor=route_factor, segment=ckpt_every, backend=backend,
        pipeline=pipeline, obs_slots=obs_slots, deferred=deferred,
    ), key=engine_key("sharded-ckpt", program, meta, mesh, chunk,
                      route_factor, ckpt_every))
    t0 = time.time()
    if resume:
        saved_meta, carry = load_checkpoint(ckpt_path, template)
        for key in ("format", "config", "queue_capacity", "fp_capacity",
                    "devices", "pipeline", "obs_slots", "deferred",
                    "symmetry", "por"):
            # pre-pipeline/pre-obs/pre-deferred/pre-reduction
            # snapshots carry no key: treat as off - they were cut
            # from engines without those features
            saved = saved_meta.get(
                key, False if key in ("pipeline", "deferred",
                                      "symmetry", "por")
                else 0 if key == "obs_slots" else None
            )
            if saved != meta[key]:
                raise ValueError(
                    f"checkpoint {key} mismatch: "
                    f"{saved!r} != {meta[key]!r}"
                )
    else:
        carry = template

    segments = 0
    while bool(np.asarray(carry.cont).any()):
        if max_segments is not None and segments >= max_segments:
            break
        carry = jax.block_until_ready(compiled(carry))
        segments += 1
        if ckpt_path is not None:
            save_checkpoint(ckpt_path, carry, meta)
    return result_from_shard_carry(
        carry, time.time() - t0, iterations=segments,
        labels=backend.labels, viol_names=backend.viol_names,
        fp_capacity_total=fp_capacity * mesh.devices.size,
        route=route_geometry(backend, chunk, int(mesh.devices.size),
                             route_factor),
    )
