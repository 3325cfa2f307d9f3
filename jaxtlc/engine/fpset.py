"""Device-resident fingerprint set - the OffHeapDiskFPSet replacement.

TLC stores every seen state's 64-bit fingerprint in an open-addressing
off-heap table (`OffHeapDiskFPSet`, /root/reference/KubeAPI.toolbox/Model_1/
MC.out:5); 72% of generated states are rejected here (MC.out:1098), making
dedup the hot path.  The cost model is *row operations* on the table, so
the structure minimizes them.  On a TPU v5e with the table at
[2^21, 16] (PERF.md PR 26, Step 0): a gather of 32,768 bucket rows takes
0.73 ms and a scatter-add of as many rows 3.3 ms, both in place on the
table's own layout; an element scatter into the rank-2 table is
flattened by XLA first, two whole-table relayouts (6.1 ms) a call.

* **Bucketized table**: ``[cap/8, 16] uint32`` - one 64-byte row per
  8-slot bucket, slots interleaved ``lo0,hi0,...,lo7,hi7``; (0, 0) = empty
  slot.  The rank-2 interleaved layout is the measured fast point: a probe
  is ONE row gather (0.73 ms for 32,768 probes on the chip, against
  9.9 ms as element gathers from a flat [2 * cap] table and 37.8 ms as
  a windowed gather from it: PERF.md PR 26).  A bucket's occupied slots are always a prefix (inserts
  fill in order, nothing is ever deleted), and the home bucket of a
  (mixed) fingerprint is the top bits of ``hi`` - monotonic in
  fingerprint sort order.
* **Sort-compact, then probe only unique candidates**: one stable sort
  groups duplicate fingerprints; invalid lanes encode as the RESERVED
  (0,0) word pair (safe because ``_remap`` maps any real (0,0)
  fingerprint to (1,0) first), so validity costs no extra sort key -
  3 arrays / 2 keys per comparator pass.  A second stable 1-key sort
  compacts the group representatives to the front, so the probe phase
  touches O(unique) rows, not O(batch).  The grouping leaves the valid
  lanes as the LAST rows of its order, so the compaction sorts that
  tail alone, at the narrowest of a short ladder of static power-of-two
  widths that holds it (`sort_ladder`, `sort_live`: a sort is priced by
  the power of two above its width, and a fifth to a half of the lanes
  are valid).  Rows past the `nreps` representatives are therefore not
  a permutation's rest: inside the rung they hold the non-representative
  lanes, past it (0, 0) words and the out-of-range lane n.  Nothing
  reads them: the probe masks by `nreps`, a scatter by c_idx drops lane
  n, the enqueue's key is 1 there.
* **Conflict-free claims**: because compacted candidates arrive sorted,
  same-bucket claimants are adjacent runs; each claimant takes slot
  ``occupancy + rank-in-run``, so round-0 insertions cannot collide - no
  claim-verify round trip for the common case.
* **Straggler path**: candidates whose home bucket is (or becomes) full
  walk buckets linearly; each walk round rank-claims against the
  CURRENT bucket so straggler writes are conflict-free too.  The round's
  rank arbitration is the dense [S, S] bucket-coincidence reduction of
  the BLEST tensor-core BFS papers (ISSUE 15: no comparator network in
  the walk), on every platform; tests/test_deferred.py::
  test_dense_walk_matches_host_replay pins it.  No claim-verify
  exists anywhere: a slot write is one scatter-add of whole bucket rows
  (`_slot_write`), and with every claim targeting a distinct EMPTY slot
  the sum is the write whatever order the rows land in (a verify-based
  loop would live-lock on a backend that resolved two element scatters
  in different orders).  tests/test_fpset.py's high-load test drives
  the straggler walk hard (0.68 load, 5.5 expected per 8-slot bucket).

Lookup/insert invariant: a fingerprint lives in bucket ``b + j`` only if
buckets ``b .. b+j-1`` are full; so a probe that sees its home bucket
non-full and no match knows the fingerprint is absent.

Exactness: duplicate fingerprints within a batch yield exactly one
``is_new=True`` (the highest lane index - the dedup sort is stable), and
the distinct count is exact; only fingerprint *collisions* (two states, one
fp) merge classes, the same risk TLC reports (MC.out:39-42).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BUCKET = 8  # slots per bucket; 64-byte bucket rows gather in one access


class CapacityError(RuntimeError):
    """A fingerprint table (or another bounded resource) ran out of room.

    Carries the saturated resource's occupancy/capacity so callers - the
    run supervisor above all (jaxtlc.resil.supervisor) - can react
    programmatically (regrow, checkpoint, report) instead of string-
    matching an exception message."""

    def __init__(self, occupancy: int, capacity: int,
                 resource: str = "fpset"):
        self.occupancy = int(occupancy)
        self.capacity = int(capacity)
        self.resource = resource
        super().__init__(
            f"{resource} full: {self.occupancy}/{self.capacity} slots "
            f"occupied (raise the {resource} capacity or enable auto-grow)"
        )


class FPSet(NamedTuple):
    # [cap / BUCKET, 2 * BUCKET] uint32: bucket rows, slots interleaved
    # lo0,hi0,...  A flat [cap, 2] view in slot order is table.reshape(-1, 2).
    table: jnp.ndarray


def fpset_new(cap: int) -> FPSet:
    assert cap & (cap - 1) == 0, "capacity must be a power of two"
    assert cap >= BUCKET, f"capacity must be at least {BUCKET}"
    return FPSet(
        table=jnp.zeros((cap // BUCKET, 2 * BUCKET), dtype=jnp.uint32)
    )


def fpset_count(s: FPSet) -> jnp.ndarray:
    """Occupied-slot count (uint32)."""
    lo = s.table[:, 0::2]
    hi = s.table[:, 1::2]
    return ((lo != 0) | (hi != 0)).sum().astype(jnp.uint32)


WRITE_BLOCKS = 8  # a wide write goes block by block, as far as lanes are live


def _blocked(n: int) -> bool:
    """Whether an n-lane write is worth cutting into WRITE_BLOCKS blocks
    behind a trip count: a scattered row costs ~100 ns on the chip
    whether its lane is live or not (PERF.md PR 26), so a wide write
    pays for its width unless it stops where the live lanes do."""
    return n >= 512 and n % WRITE_BLOCKS == 0


def _slot_write(table, slot, lo, hi, active, n_live=None):
    """Write (lo, hi) into global slot ids where active.

    A scatter-add of whole [2B] bucket rows, each zero but for its
    lane's word pair at its slot's columns.  Every claim targets an
    EMPTY (0, 0) slot and no two claims share a slot (the rank claims),
    so adding is writing, rows aimed at one bucket combine, and an
    inactive lane adds a zero row: no lane needs an out-of-range index.
    The row window is the form the TPU scatters in place on the table's
    own layout ([2B, nb] tiled); an element scatter on the rank-2 table
    is flattened first, at two whole-table relayouts a call (PERF.md
    PR 26, Step 0).

    `n_live` (traced) promises that only the first n_live lanes can be
    active: a `_blocked` write then scatters block after block only as
    far as that.  Returns (table, the blocks scattered: 1 for a write
    that is not cut)."""

    def add_rows(table, slot, lo, hi, active):
        b = jnp.where(active, slot // BUCKET, 0)
        col = (2 * (slot % BUCKET))[:, None]
        j = jnp.arange(2 * BUCKET, dtype=col.dtype)[None, :]
        zero = jnp.uint32(0)
        row = jnp.where(j == col, lo[:, None],
                        jnp.where(j == col + 1, hi[:, None], zero))
        row = jnp.where(active[:, None], row, zero)
        return table.at[b].add(row, mode="promise_in_bounds")

    n = slot.shape[0]
    if n_live is None or not _blocked(n):
        return add_rows(table, slot, lo, hi, active), jnp.int32(1)
    block = n // WRITE_BLOCKS

    def write(st):
        table, k = st
        part = [lax.dynamic_slice(x, (k * block,), (block,))
                for x in (slot, lo, hi, active)]
        return add_rows(table, *part), k + 1

    return lax.while_loop(
        lambda st: st[1] * block < n_live, write, (table, jnp.int32(0))
    )


def _remap(lo, hi):
    """Reserve (0,0) as the empty marker: real fingerprint (0,0) becomes
    (1,0).  Merges two fp classes with probability 2^-64 - the same risk
    class as TLC's own fingerprint collisions (MC.out:39-42)."""
    z = (lo == 0) & (hi == 0)
    return jnp.where(z, jnp.uint32(1), lo), hi


def _fmix32(h):
    """murmur3 finalizer: full-avalanche bijection on uint32."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _mix(lo, hi):
    """Bijective avalanche of the 64-bit fingerprint (3-round Feistel over
    the two uint32 halves).  The Rabin fingerprint is GF(2)-LINEAR in the
    state bits, so its raw top bits are badly non-uniform on structured
    state populations (measured 20x-overloaded buckets on Model_1); the
    table stores and buckets the MIXED value instead.  Bijectivity means
    no fingerprint classes merge - collision risk is exactly the raw fp's."""
    for c in (0x9E3779B9, 0x517CC1B7, 0x27220A95):
        lo, hi = hi, lo ^ _fmix32(hi + jnp.uint32(c))
    return lo, hi


def _unmix(lo, hi):
    """Inverse of _mix (the Feistel rounds reversed): recovers the raw
    fingerprint from a stored table entry."""
    for c in (0x27220A95, 0x517CC1B7, 0x9E3779B9):
        lo, hi = hi ^ _fmix32(lo + jnp.uint32(c)), lo
    return lo, hi


@jax.jit
def fpset_actual_collision(s: FPSet) -> jnp.ndarray:
    """TLC's "based on the actual fingerprints" collision estimate
    (MC.out:42): 1 / min adjacent gap of the sorted stored fingerprints
    (OffHeapDiskFPSet.checkFPs's statistic).

    Computed over the avalanche-MIXED table values, not the raw affine
    fingerprints: the mix is a bijection, so the collision probability the
    statistic proxies is identical, while the integer-gap estimator
    regains the uniformity it assumes (raw GF(2)-affine fingerprints of
    structured states cluster in integer space - measured min gaps ~1e2
    instead of the ~1e9 a uniform draw of this size gives - without that
    implying any XOR-collision risk)."""
    # read the interleaved columns directly: a [cap, 2] reshape would get a
    # padded TPU tile layout (minor dim 2 -> 128, a 64x allocation)
    lo = s.table[:, 0::2].reshape(-1)
    hi = s.table[:, 1::2].reshape(-1)
    occupied = (lo != 0) | (hi != 0)
    inval = (~occupied).astype(jnp.uint32)
    s_inv, s_hi, s_lo = lax.sort((inval, hi, lo), num_keys=3)
    both = (s_inv[1:] == 0) & (s_inv[:-1] == 0)
    # 64-bit gap via subtract-with-borrow in uint32 (floats would round
    # the raw words); the float conversion of the small RESULT is exact
    # enough for the printed %.1E estimate
    dl = s_lo[1:] - s_lo[:-1]
    borrow = (s_lo[1:] < s_lo[:-1]).astype(jnp.uint32)
    dh = s_hi[1:] - s_hi[:-1] - borrow
    gap = dh.astype(jnp.float32) * 4294967296.0 + dl.astype(jnp.float32)
    min_gap = jnp.min(jnp.where(both, gap, jnp.inf))
    return jnp.where(jnp.isfinite(min_gap) & (min_gap > 0), 1.0 / min_gap, 0.0)


def _fmix32_host(h: int) -> int:
    m = 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & m
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & m
    h ^= h >> 16
    return h


def mix_host(lo: int, hi: int) -> Tuple[int, int]:
    """Host replica of _mix (must match bit-for-bit: sharded-engine tables
    are seeded host-side and probed on device)."""
    for c in (0x9E3779B9, 0x517CC1B7, 0x27220A95):
        lo, hi = hi, lo ^ _fmix32_host((hi + c) & 0xFFFFFFFF)
    return lo, hi


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h ^= h >> np.uint32(13)
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h ^= h >> np.uint32(16)
    return h


def unmix_host(lo: np.ndarray, hi: np.ndarray):
    """Vectorized host inverse of _mix over uint32 arrays: recovers raw
    fingerprints from stored table words.  The regrow migration
    (jaxtlc.resil.regrow) unmixes a saturated table's entries and feeds
    them back through fpset_insert_sorted into the larger geometry, so
    the new table's stored words are reproduced exactly; the spill
    flush (engine.spill) does the same device-to-host direction."""
    lo = np.asarray(lo, np.uint32).copy()
    hi = np.asarray(hi, np.uint32).copy()
    with np.errstate(over="ignore"):
        for c in (0x27220A95, 0x517CC1B7, 0x9E3779B9):
            lo, hi = (
                hi ^ _fmix32_np((lo + np.uint32(c)).astype(np.uint32)),
                lo,
            )
    return lo, hi


def mix_host_np(lo: np.ndarray, hi: np.ndarray):
    """Vectorized host replica of _mix over uint32 arrays (the batch
    form of mix_host, inverse of unmix_host).  The host spill tier
    (engine.spill.SpillStore) keys its store on MIXED words so its
    equality semantics - including the (0,0)->(1,0) remap class merge -
    are bit-identical to the device table's."""
    lo = np.asarray(lo, np.uint32).copy()
    hi = np.asarray(hi, np.uint32).copy()
    with np.errstate(over="ignore"):
        for c in (0x9E3779B9, 0x517CC1B7, 0x27220A95):
            lo, hi = (
                hi.copy(),
                lo ^ _fmix32_np((hi + np.uint32(c)).astype(np.uint32)),
            )
    return lo, hi


def _bucket_of(hi, nbuckets: int):
    """Home bucket = top log2(nbuckets) bits of hi (monotonic in (hi, lo)
    sort order - the property the conflict-free rank claims rely on)."""
    lognb = nbuckets.bit_length() - 1
    if lognb == 0:
        return jnp.zeros_like(hi, jnp.int32)
    return (hi >> jnp.uint32(32 - lognb)).astype(jnp.int32)


def bucket_of_host(hi: int, nbuckets: int) -> int:
    lognb = nbuckets.bit_length() - 1
    return (hi >> (32 - lognb)) if lognb else 0


def host_insert(table: np.ndarray, lo: int, hi: int) -> bool:
    """Insert-or-find one fingerprint in a host-side numpy table (any
    shape whose memory order is slot-major (lo, hi) pairs - both the
    device's interleaved [cap/B, 2B] rows and a flat [cap, 2] qualify),
    walking the exact slot sequence the device uses (linear from the home
    bucket's first slot).  Returns is_new."""
    table = table.reshape(-1, 2)  # view: writes propagate to the caller
    cap = table.shape[0]
    lo, hi = mix_host(lo, hi)
    if lo == 0 and hi == 0:
        lo = 1
    base = bucket_of_host(hi, cap // BUCKET) * BUCKET
    for k in range(cap):
        slot = (base + k) % cap
        r0, r1 = int(table[slot, 0]), int(table[slot, 1])
        if r0 == lo and r1 == hi:
            return False
        if r0 == 0 and r1 == 0:
            table[slot, 0] = lo
            table[slot, 1] = hi
            return True
    raise CapacityError(cap, cap)


def fpset_member(s: FPSet, lo, hi, mask,
                 max_rounds: int = 0) -> jnp.ndarray:
    """Membership-only probe (no insert, no mutation): True where the
    masked fingerprint is already stored.  Walks the exact bucket
    sequence of the insert path - a non-full bucket with no match ends
    the walk (the lookup invariant in the module docstring), so the loop
    terminates whenever the table is below full occupancy (the engines'
    fp_highwater guarantees that).

    This is the device-side filter of the host spill tier
    (engine.spill): candidates found here are definitely-old and never
    pay the PCIe/host round trip; only the probable-new remainder is
    checked against the host store.

    max_rounds > 0 BOUNDS the walk: lanes still unresolved after that
    many bucket rounds report False.  That is safe for the filter use -
    the result must never claim an absent fingerprint present (it
    cannot: True still requires an exact word match), but a stored
    fingerprint reported False merely pays the host round trip and
    dedups correctly there/at insert.  Near the highwater load, absent
    keys otherwise walk long full-bucket runs (the open-addressing
    tail), and the while_loop runs to the WORST lane of the batch - the
    cap keeps the filter O(max_rounds) per chunk (PERF.md round 10)."""
    table = s.table
    nb = table.shape[0]
    lo, hi = _mix(lo, hi)
    lo, hi = _remap(lo, hi)
    bid = _bucket_of(hi, nb)

    def cond(st):
        _, pend, _, k = st
        more = (k < max_rounds) if max_rounds else True
        return pend.any() & more

    def body(st):
        cur, pend, found, k = st
        row = table[jnp.where(pend, cur, 0)]  # [N, 2B] row gather
        rlo, rhi = row[:, 0::2], row[:, 1::2]
        hit = pend & ((rlo == lo[:, None]) & (rhi == hi[:, None])).any(1)
        full = ((rlo != 0) | (rhi != 0)).all(axis=1)
        found = found | hit
        pend = pend & ~hit & full
        cur = jnp.where(pend, (cur + 1) % nb, cur)
        return cur, pend, found, k + 1

    _, _, found, _ = lax.while_loop(
        cond, body, (bid, mask, jnp.zeros_like(mask), jnp.int32(0))
    )
    return found


def _probe_block(table, lo, hi, active, claim_width: int):
    """`_probe_claim` under the device scope `jaxtlc.fpset` (the probe
    / claim of the engine's commit; bfs.make_stage_pair has the list)."""
    with jax.named_scope("jaxtlc.fpset"):
        return _probe_claim(table, lo, hi, active, claim_width)


def _probe_claim(table, lo, hi, active, claim_width: int):
    """Insert-or-find `active` entries of a fingerprint block that is
    sorted ascending by (hi, lo) and duplicate-free.  Returns
    (table, is_new, counts).  table: [nb, 2B]; lo/hi/active: [R];
    counts: what the block did, int32 scalars it has anyway and the
    trip counts of its loops - (claimed, claim_blocks, stragglers,
    walk_rounds) of COMMIT_COUNTS."""
    nb = table.shape[0]
    cap = nb * BUCKET
    R = lo.shape[0]
    C = min(claim_width, R)
    bid = _bucket_of(hi, nb)

    bk = table[bid]  # [R, 2B]: one 64-byte row gather per candidate
    blo, bhi = bk[:, 0::2], bk[:, 1::2]
    hit = (blo == lo[:, None]) & (bhi == hi[:, None])
    found = active & hit.any(axis=1)
    occ_mask = (blo != 0) | (bhi != 0)
    noccup = occ_mask.sum(axis=1).astype(jnp.int32)

    # conflict-free slot assignment: same-bucket claimants are adjacent
    # (bid is monotonic), so rank-in-run places them in distinct slots
    want = active & ~found
    start = jnp.concatenate([jnp.ones(1, bool), bid[1:] != bid[:-1]])
    wc = jnp.cumsum(want.astype(jnp.int32))
    base = lax.cummax(jnp.where(start, wc - want.astype(jnp.int32), 0))
    rank = wc - want.astype(jnp.int32) - base
    slot = noccup + rank
    fits = want & (slot < BUCKET)

    # the first C fitting claimers write now; the rest (and claimers
    # whose bucket is full) settle in the straggler loop.  The claimers
    # are compacted to the front first (stable, so they stay slot-
    # ascending), and a wide write stops where they do.
    claim_pos = jnp.cumsum(fits.astype(jnp.int32)) - 1
    claimed = fits & (claim_pos < C)
    nclaim = claimed.sum()
    _, t_tgt, t_lo, t_hi = lax.sort(
        ((~claimed).astype(jnp.uint32), bid * BUCKET + slot, lo, hi),
        num_keys=1, is_stable=True,
    )
    table, claim_blocks = _slot_write(table, t_tgt, t_lo, t_hi,
                                      jnp.arange(R) < nclaim, nclaim)

    is_new = claimed
    pending = active & ~found & ~claimed

    # straggler loop: candidates whose home bucket is full (or whose claim
    # fell beyond C) walk buckets linearly.  Each outer round compacts the
    # pending set to an S-slice; each walk round rank-claims against
    # the slice's CURRENT buckets - conflict-free again, so no
    # claim-verify (whose torn-write hazard under the interleaved layout
    # could live-lock) and every write is to a distinct slot.
    S = min(R, 2048)

    def outer_cond(st):
        table, is_new, pending, rounds = st
        return pending.any()

    def outer_body(st):
        table, is_new, pending, rounds = st
        npend = (~pending).astype(jnp.uint32)
        pos = jnp.arange(R, dtype=jnp.uint32)
        _, p_bid, p_lo, p_hi, p_pos = lax.sort(
            (npend, bid.astype(jnp.uint32), lo, hi, pos),
            num_keys=1, is_stable=True,
        )
        s_bid = p_bid[:S].astype(jnp.int32)
        s_lo, s_hi = p_lo[:S], p_hi[:S]
        s_pos = p_pos[:S].astype(jnp.int32)
        # the slice's live lanes are its first n_act
        n_act = jnp.minimum(pending.sum(), S)
        s_act = jnp.arange(S) < n_act

        def walk_cond(wst):
            _, _, pend, _, _ = wst
            return pend.any()

        def walk_body(wst):
            # dense rank-claim round (ISSUE 15, BLEST formulation): the
            # slice stays in ITS OWN order - no per-round sort.  Each
            # pending lane gathers its current bucket row (the
            # membership test needs the stored words), and the in-
            # bucket claim rank comes from one [S, S] bucket-
            # coincidence x fingerprint-order mask reduced row-wise: a
            # dense segmented reduction (VPU/MXU-shaped), no comparator
            # network.  The slice is duplicate-free, so ascending
            # (lo, hi) is a strict order: same-bucket claimants get
            # distinct ranks, hence distinct slots.
            table, cur_b, pend, new, k = wst
            row = table[jnp.where(pend, cur_b, 0)]  # [S, 2B]
            rlo, rhi = row[:, 0::2], row[:, 1::2]
            f = pend & (
                (rlo == s_lo[:, None]) & (rhi == s_hi[:, None])
            ).any(1)
            occ = ((rlo != 0) | (rhi != 0)).sum(axis=1).astype(jnp.int32)
            wnt = pend & ~f
            same = (
                wnt[:, None] & wnt[None, :]
                & (cur_b[:, None] == cur_b[None, :])
            )
            less = (s_lo[None, :] < s_lo[:, None]) | (
                (s_lo[None, :] == s_lo[:, None])
                & (s_hi[None, :] < s_hi[:, None])
            )
            rnk = (same & less).sum(axis=1).astype(jnp.int32)
            sl = occ + rnk
            ok = wnt & (sl < BUCKET)
            table, _ = _slot_write(table, cur_b * BUCKET + sl, s_lo, s_hi,
                                   ok, n_act)
            new = new | ok
            pend2 = pend & ~(f | ok)
            # unsettled claimants advance to the next bucket
            cur_b = jnp.where(wnt & ~ok & pend2, (cur_b + 1) % nb,
                              cur_b)
            return table, cur_b, pend2, new, k + 1

        table, _, _, s_new, walked = lax.while_loop(
            walk_cond, walk_body,
            (table, s_bid, s_act, jnp.zeros(S, bool), jnp.int32(0)),
        )
        upd_pos = jnp.where(s_act, s_pos, R)
        is_new = is_new.at[upd_pos].set(s_new, mode="drop")
        pending = pending.at[upd_pos].set(False, mode="drop")
        return table, is_new, pending, rounds + 1 + walked

    table, is_new, _, rounds = lax.while_loop(
        outer_cond, outer_body, (table, is_new, pending, jnp.int32(0))
    )
    # no reduction at block width for the counts: the claim's running
    # sum ends on the entries the home bucket did not hold, of which
    # round 0 wrote nclaim and left the rest pending
    counts = (nclaim.astype(jnp.int32), claim_blocks,
              wc[-1] - nclaim.astype(jnp.int32), rounds)
    return table, is_new, counts


def _probe_segments(table, c_lo, c_hi, active, n_rows, R: int, C: int):
    """Probe / claim the ordered candidates c_lo/c_hi [n] (fp-ascending,
    `active` marking the dup-free representatives: the first `n_rows`
    rows) in R-wide blocks.  Returns (table, is_new [n], the blocks
    run, `_probe_claim`'s counts summed over them).  The one place the
    commit's table is written, and never under a conditional."""
    n = c_lo.shape[0]
    if R == n:
        table, is_new, counts = _probe_block(table, c_lo, c_hi, active, C)
        return table, is_new, jnp.int32(1), counts

    # block loop: one trip unless a chunk is nearly all-distinct; each
    # block stays fp-sorted.
    # Pad to a whole number of blocks: dynamic_slice CLAMPS out-of-
    # bounds start offsets, so an unpadded final partial block would
    # re-probe earlier entries and never probe the tail.
    nseg = (n + R - 1) // R
    pad = nseg * R - n
    p_lo = jnp.pad(c_lo, (0, pad))
    p_hi = jnp.pad(c_hi, (0, pad))
    p_act = jnp.pad(active, (0, pad))

    def seg_cond(st):
        table, is_new_p, seg, counts = st
        return (seg * R < n_rows) & (seg < nseg)

    def seg_body(st):
        table, is_new_p, seg, counts = st
        off = seg * R
        b_lo = lax.dynamic_slice(p_lo, (off,), (R,))
        b_hi = lax.dynamic_slice(p_hi, (off,), (R,))
        b_act = lax.dynamic_slice(p_act, (off,), (R,))
        table, b_new, b_counts = _probe_block(table, b_lo, b_hi, b_act, C)
        is_new_p = lax.dynamic_update_slice(is_new_p, b_new, (off,))
        return (table, is_new_p, seg + 1,
                tuple(a + b for a, b in zip(counts, b_counts)))

    table, is_new_p, seg, counts = lax.while_loop(
        seg_cond, seg_body,
        (table, jnp.zeros(nseg * R, bool), jnp.int32(0),
         (jnp.int32(0),) * 4)
    )
    return table, is_new_p[:n], seg, counts


LADDER_FLOOR = 16384  # no power-of-two rung is narrower
LADDER_RUNGS = 4  # a commit sort is instantiated at most this often


def sort_ladder(n: int, first: int = 0) -> Tuple[int, ...]:
    """The static widths an n-lane commit sort may run at, ascending;
    the last is n, the whole array, so the worst case is the sort as it
    stood before there was a ladder.  A sort is priced by the power of
    two above its width (PERF.md section 5, PR 49), so the rungs in
    front are powers of two from LADDER_FLOOR below n: with no `first`
    (the dedup's compaction, whose live part is a share of n) the
    widest LADDER_RUNGS - 1 of them; with `first` (the enqueue's order,
    whose live part is a few chunks: `first` = the probe width) that
    width and the doublings above it.  An array of 2 * LADDER_FLOOR
    lanes or fewer gets no power of two: a whole sort there is tens of
    microseconds, a conditional buys nothing, and under a vmap
    (serve/sweep.py) a switch on a batched count runs every rung."""
    pows = []
    if n > 2 * LADDER_FLOOR:
        w = LADDER_FLOOR
        while w < n:
            if w > first:
                pows.append(w)
            w *= 2
    if 0 < first < n:
        return (first,) + tuple(pows[: LADDER_RUNGS - 2]) + (n,)
    return tuple(pows[-(LADDER_RUNGS - 1):]) + (n,)


# What one commit did, as counts (ISSUE 50): `fpset_insert_sorted` hands
# back one uint32 vector a call - the COMMIT_COUNTS, then a
# LADDER_RUNGS-bin histogram of the rung of `sort_ladder(n)` the
# compaction sorted at.  Scalars the program has anyway and the final
# values of its loops' counters - histograms and trip counts, never a
# sum of widths: a width is static (`commit_widths`), the host
# multiplies.  An engine appends its own loops' counts and sums the
# whole a body into a leaf of its carry (engine.bfs, engine.sharded).
COMMIT_COUNTS = (
    "valid", "reps", "probe_segments",  # the seam, `fpset_insert_sorted`
    "claimed", "claim_blocks", "stragglers",
    "walk_rounds")  # `_probe_claim`, summed over the segments
COMMIT_STAT_COLS = len(COMMIT_COUNTS) + LADDER_RUNGS


def count_block(names: tuple, bins: int = 0, at=None, lead: int = 0,
                tail: int = 0, **counts):
    """A [lead + len(names) + bins + tail] uint32 vector: `lead` zeros,
    the named counts (0 where not given), a one-hot of the bin index
    `at` where given, `tail` zeros.  Selects against one iota and
    nothing else, and every block of a body at the leaf's one width
    (the seam's with a `tail` for its caller's columns, the caller's
    behind a `lead`), so that the blocks and their sum into the leaf
    fuse into one small elementwise operation: on the chip every
    operation of a body costs microseconds whatever its size - a stack
    of scalars, a one-hot and a concatenate were three, and blocks of
    two widths joined by a concatenate were three again (PERF.md
    section 6, PR 50)."""
    cols = lead + len(names) + bins + tail
    col = jnp.arange(cols, dtype=jnp.int32)
    out = jnp.zeros(cols, jnp.uint32)
    for name, value in counts.items():
        out = out + jnp.where(col == lead + names.index(name),
                              jnp.asarray(value).astype(jnp.uint32), 0)
    if at is not None:  # an index past the bins counts nowhere
        out = out + ((col == lead + len(names) + at) & (at < bins)
                     ).astype(jnp.uint32)
    return out


def commit_stat_fields(stat, names: tuple = COMMIT_COUNTS,
                       hist: str = "compact_rung") -> dict:
    """A host `count_block` by name: the counts, and what follows them
    as the tuple `hist` (None: nothing follows)."""
    stat = [int(v) for v in np.asarray(stat)]
    out = dict(zip(names, stat))
    if hist is not None:
        out[hist] = tuple(stat[len(names):])
    return out


def commit_widths(width: int, probe_width: int = 0) -> dict:
    """The static widths a commit's counts are read against, for an
    insert over `width` candidate lanes probed `probe_width` rows a
    segment (0: all of them): the rows of one block of the round-0
    claim's write, and the two sorts' ladders."""
    R = min(probe_width or width, width)
    return dict(width=width, probe_width=R,
                claim_block=R // WRITE_BLOCKS if _blocked(R) else R,
                compact_ladder=sort_ladder(width),
                enqueue_ladder=sort_ladder(width, R))


def sort_live(operands, num_keys: int, n_live, widths, fill, tail=False):
    """Stable `lax.sort` of `operands` ([n] each) whose live part is
    known to be the first - `tail`: the last - `n_live` rows, every
    other row sorting behind the rows a reader looks at: run on the
    static slice of the narrowest of `widths` (a `sort_ladder`) that
    holds n_live, by `lax.switch` on the traced count.  Hands back, at
    the old shape [n], the sorted operands whose `fill` is not None,
    the rows past the rung holding that operand's `fill`, and after
    them the index of the rung taken.  One rung: the plain sort, no
    conditional."""
    n = operands[0].shape[0]
    kept = [i for i, f in enumerate(fill) if f is not None]

    def rung(w):
        def run(ops):
            cut = [o[n - w:] if tail else o[:w] for o in ops]
            out = lax.sort(tuple(cut), num_keys=num_keys, is_stable=True)
            return tuple(
                out[i] if w == n else jnp.concatenate(
                    [out[i], jnp.full(n - w, fill[i], out[i].dtype)])
                for i in kept
            )
        return run

    if len(widths) == 1:
        return rung(n)(operands) + (jnp.int32(0),)
    at = sum((n_live > w).astype(jnp.int32) for w in widths[:-1])
    return lax.switch(at, [rung(w) for w in widths], operands) + (at,)


def _sorted_order(lo, hi):
    """The in-batch dedup's ordering: two stable sorts of already
    MIXED, remapped, mask-zeroed fingerprint words give (c_lo, c_hi,
    c_idx int32, nreps, the valid lanes, the compaction's rung), the
    distinct representatives compacted fp-ascending into the first
    nreps rows.  The grouping sort sees all
    n candidate lanes; the compaction runs at the rung of
    `sort_ladder(n)` that holds the valid lanes, so rows past nreps are
    non-representatives and then, past the rung, (0, 0) words with the
    out-of-range lane n - nobody reads them.
    On the chip a sort at candidate width is cheap and an element
    gather or scatter there is not (PERF.md section 5, PR 38, one
    TPU v5e: at 196,608 lanes the two sorts cost 0.20 and 0.24 ms a
    step, ONE element gather 0.92 ms): index nothing at this width."""
    n = lo.shape[0]
    # sort 1: group duplicates.  Invalid lanes are encoded as the RESERVED
    # (0,0) word pair - _remap guarantees no real fingerprint is (0,0) -
    # so validity needs no separate sort key: 3 arrays / 2 keys instead of
    # 4 / 3 (each key array is a full comparator-network pass on TPU).
    # Invalids therefore sort FIRST; reps are the last element of each
    # nonzero group.
    idx = jnp.arange(n, dtype=jnp.uint32)
    s_hi, s_lo, s_idx = lax.sort((hi, lo, idx), num_keys=2, is_stable=True)
    last = jnp.concatenate(
        [
            (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1]),
            jnp.ones(1, bool),
        ]
    )
    valid = (s_hi != 0) | (s_lo != 0)
    rep = valid & last

    # sort 2: compact representatives to the front (stable single-key sort
    # keeps them fingerprint-sorted - required by _probe_block's rank math).
    # The valid lanes are the LAST valid.sum() rows of the grouped order,
    # and a few tens of percent of it (the cells' lane_live_pct): the sort
    # runs over that tail alone
    nonrep = (~rep).astype(jnp.uint32)
    nvalid = valid.sum()
    c_lo, c_hi, c_idx, at = sort_live(
        (nonrep, s_lo, s_hi, s_idx), 1, nvalid, sort_ladder(n),
        fill=(None, 0, 0, n), tail=True,
    )
    nreps = rep.sum().astype(jnp.int32)
    return c_lo, c_hi, c_idx.astype(jnp.int32), nreps, nvalid, at


def enqueue_order(is_new_c, c_idx, nreps, first: int):
    """The new rows' lanes in lane order, [n] uint32 (the engines' append
    order: `fpset_insert_sorted`'s verdicts sorted by (not new, lane));
    entries past the new rows are not to be read.  Every new row lies in
    the first nreps compacted rows, so the sort runs at the rung of
    `sort_ladder(n, first)` that holds nreps, `first` being the caller's
    probe width.  Returns (e_idx, the index of the rung taken)."""
    return sort_live(
        ((~is_new_c).astype(jnp.uint32), c_idx.astype(jnp.uint32)),
        2, nreps, sort_ladder(c_idx.shape[0], first), fill=(None, 0),
    )


def fpset_insert_sorted(
    s: FPSet, lo, hi, mask, probe_width: int = 0, claim_width: int = 0,
    stat_cols: int = COMMIT_STAT_COLS,
) -> Tuple[FPSet, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Insert-or-find a batch; results in *compacted* order.

    lo/hi: [N] uint32; mask: [N] bool.  Returns (set, is_new_c [N] bool,
    c_idx [N] int32, nreps int32, stat): entry j < nreps of the compacted order
    is the representative of a distinct masked fingerprint, originally at
    lane c_idx[j]; is_new_c[j] says whether it was new to the table.
    Representatives are fingerprint-sorted (ascending (hi, lo)).  Past
    nreps is_new_c is False and c_idx is NOT the rest of a permutation:
    a non-representative lane or, past the compaction's rung
    (`_sorted_order`), the out-of-range lane N - index with a clamp or a
    drop there, as every caller does.

    In-batch duplicates resolve to the highest lane index (stable dedup
    sort), keeping attribution deterministic across engines/backends.
    probe_width bounds the per-segment probe row count (0 = whole batch);
    claim_width bounds the round-0 claim scatter (0 = probe_width).
    `stat` is the call's [stat_cols] uint32 block of counts: the lanes
    the mask let through, their representatives, the probe's segments
    and what they claimed and walked, the rung the compaction sorted
    at - COMMIT_STAT_COLS columns, and zeros after them where the
    caller asks for room for its own.  A caller that keeps no block
    drops it, and the adds with it.
    """
    n = lo.shape[0]
    R = min(probe_width or n, n)
    C = min(claim_width or R, R)
    lo, hi = _mix(lo, hi)
    lo, hi = _remap(lo, hi)
    lo = jnp.where(mask, lo, 0)
    hi = jnp.where(mask, hi, 0)
    c_lo, c_hi, c_idx, nreps, nvalid, at = _sorted_order(lo, hi)
    table, is_new_c, segments, counts = _probe_segments(
        s.table, c_lo, c_hi, jnp.arange(n) < nreps, nreps, R, C
    )
    stat = count_block(
        COMMIT_COUNTS, LADDER_RUNGS, at, tail=stat_cols - COMMIT_STAT_COLS,
        valid=nvalid, reps=nreps, probe_segments=segments,
        **dict(zip(COMMIT_COUNTS[3:], counts)))
    return FPSet(table), is_new_c, c_idx, nreps, stat


def fpset_insert(s: FPSet, lo, hi, mask) -> Tuple[FPSet, jnp.ndarray]:
    """Insert-or-find a batch of fingerprints.

    lo/hi: [N] uint32 lanes; mask: [N] bool (candidates to consider).
    Returns (updated set, is_new [N] bool) in the original lane order.
    Duplicate fingerprints within the batch yield exactly one is_new=True
    (the highest lane index), keeping the committed outdegree statistics
    (max 4 on Model_1, as TLC reports, MC.out:1104) stable across fpset
    generations.  The caller must keep occupancy + N below capacity (the
    engine checks before calling)."""
    n = lo.shape[0]
    with jax.named_scope("jaxtlc.dedup"):
        s2, is_new_c, c_idx, _, _ = fpset_insert_sorted(s, lo, hi, mask)
    # c_idx holds each representative's lane once; past nreps it may hold
    # the out-of-range lane n (_sorted_order), which the drop leaves out
    is_new = jnp.zeros(n, bool).at[c_idx].set(is_new_c, mode="drop")
    return s2, is_new
