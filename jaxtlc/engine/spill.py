"""Host-RAM fingerprint spill tier - the capacity lifeboat.

TLC survives state spaces far beyond RAM because its fingerprint set
spills to disk (OffHeapDiskFPSet); the device engines died at HBM
capacity instead: auto-regrow doubles the table until the allocation
itself fails, and VIOL_FPSET_FULL then killed the run exactly when it
mattered most (ROADMAP #3).  This module makes that halt survivable:

* **SpillStore** - a host-side open-addressing fingerprint table with
  the exact slot-walk and MIXED-word equality semantics of the device
  table (fpset.host_insert's layout; fpset.mix_host_np keys the store,
  so even the (0,0)->(1,0) remap class merge is shared bit-for-bit).
  It auto-grows in host RAM, snapshots/restores in O(table) for the
  supervisor's rollback points, and serializes through the checkpoint
  machinery (CRC manifest + fsync-rename), so `-recover` restores the
  host tier bit-for-bit alongside the device carry.
* **SpillRuntime** - the spill-mode execution of the single-device
  engine: the SAME pop/commit stages as the fused body
  (bfs.make_stage_pair - one implementation, no drift), driven from
  the host one chunk at a time so a host dedup pass can sit between
  expand and commit:

      expand (device) -> fpset_member filter (device) ->
      probable-new readback (the PR 4 async-readback pattern) ->
      SpillStore probe (host) -> commit with the host veto (device)

  The device table acts as the RECENT tier: when it reaches the
  fp_highwater load, its entries are unmixed host-side
  (fpset.unmix_host - the PR 2 regrow migration direction) and bulk-
  inserted into the store, then the device table resets empty - cold
  fingerprints live in host RAM, hot ones on device, and the
  `fpset_member` filter keeps definitely-old candidates off the host
  round trip.

Exactness: a host-vetoed candidate dedups exactly like a device-table
hit (not new, not enqueued, no stat credit), every seen fingerprint is
in exactly one tier between flushes, and the pop sequence matches the
unpipelined fused engine's chunk-for-chunk - so a spill-mode run's
final counters/verdict are bit-for-bit a correctly-sized clean run's
(tests/test_spill.py pins this through the chaos matrix; the contract
holds below the 2^14 two-tier chunk threshold, like the pipeline
contract).  The price is a host synchronization per chunk - the
lifeboat trades throughput for completion, never correctness (PERF.md
round 10 quantifies it).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .bfs import OK, carry_done, make_stage_pair
from .fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from .fpset import (
    BUCKET,
    CapacityError,
    bucket_of_host,
    fpset_count,
    fpset_member,
    fpset_new,
    mix_host_np,
    unmix_host,
)

SPILL_FORMAT = 1
DEFAULT_SPILL_CAPACITY = 1 << 15


class SpillWriteError(RuntimeError):
    """A device-table flush into the host store failed (OSError from
    the write seam).  The device table is still full and the host tier
    cannot absorb it, so the run cannot proceed: the supervisor's
    ladder degrades this to checkpoint + exit 75 (the store itself is
    untouched - the hook fires before any insertion)."""


class SpillStoreSnapshot(NamedTuple):
    """Immutable rollback point of a SpillStore (the supervisor pairs
    one with every last-good carry, so retry/regrow replays roll the
    host tier back in lock-step with the device tier)."""

    table: np.ndarray
    count: int


class SpillStore:
    """Host-RAM open-addressing fingerprint store.

    The table is flat ``[capacity, 2]`` uint32 slot-major (lo, hi)
    MIXED word pairs - the same memory order fpset.host_insert walks,
    with the same home-bucket linear probe - plus an O(1) membership
    mirror (a python set of packed 64-bit mixed words) rebuilt from the
    table on load/restore.  The table is the durable representation;
    the mirror is derived state.

    Growth doubles the table at the same 0.85 highwater the device
    table uses, re-placing every entry (host RAM is the only bound -
    the ladder's rung 4 handles the day THAT runs out)."""

    def __init__(self, capacity: int = DEFAULT_SPILL_CAPACITY,
                 highwater: float = 0.85):
        assert capacity & (capacity - 1) == 0, "capacity must be 2^k"
        assert capacity >= BUCKET
        self.table = np.zeros((capacity, 2), np.uint32)
        self.count = 0
        self.highwater = highwater
        self._mirror = set()

    @property
    def capacity(self) -> int:
        return self.table.shape[0]

    @staticmethod
    def _keys(raw_lo: np.ndarray, raw_hi: np.ndarray):
        """Packed 64-bit MIXED words of raw fingerprint arrays (the
        device table's equality classes, remap included)."""
        mlo, mhi = mix_host_np(raw_lo, raw_hi)
        z = (mlo == 0) & (mhi == 0)
        mlo[z] = 1  # the device _remap: (0,0) is the empty marker
        return mlo, mhi

    def probe(self, raw_lo: np.ndarray, raw_hi: np.ndarray) -> np.ndarray:
        """Membership of each raw fingerprint (bool array) - the host
        half of the spill dedup; read-only."""
        mlo, mhi = self._keys(raw_lo, raw_hi)
        mirror = self._mirror
        return np.fromiter(
            (((int(h) << 32) | int(l)) in mirror
             for l, h in zip(mlo, mhi)),
            dtype=bool, count=len(mlo),
        )

    def _place(self, lo: int, hi: int) -> None:
        """Insert one MIXED pair known absent: the host_insert slot walk
        (home bucket from the hi top bits, linear to the first empty
        slot) - deterministic, so save/load and replay reproduce the
        table bytes exactly."""
        table, cap = self.table, self.capacity
        base = bucket_of_host(hi, cap // BUCKET) * BUCKET
        for k in range(cap):
            slot = (base + k) % cap
            if table[slot, 0] == 0 and table[slot, 1] == 0:
                table[slot, 0] = lo
                table[slot, 1] = hi
                return
        raise CapacityError(cap, cap, "spill")

    def _grow(self) -> None:
        old = self.table
        occ = (old[:, 0] != 0) | (old[:, 1] != 0)
        self.table = np.zeros((self.capacity * 2, 2), np.uint32)
        # re-place in slot-scan order: deterministic layout again
        for lo, hi in old[occ]:
            self._place(int(lo), int(hi))

    def reserve(self, n: int) -> None:
        """Grow until `n` more entries fit under the highwater.  Bulk
        inserts MUST presize: flush batches arrive in table-scan order
        (sorted by home bucket), and feeding sorted keys into a table
        that is grown incrementally mid-batch degenerates linear
        probing into one giant displacement run (measured 166 s for a
        101k-entry flush vs 0.3 s presized - PERF.md round 10)."""
        while self.count + n > self.highwater * self.capacity:
            self._grow()

    def insert_batch(self, raw_lo: np.ndarray,
                     raw_hi: np.ndarray) -> int:
        """Insert raw fingerprints (already-present ones are no-ops -
        the replay-overlap case); returns how many were new."""
        self.reserve(len(raw_lo))
        mlo, mhi = self._keys(raw_lo, raw_hi)
        added = 0
        for l, h in zip(mlo.tolist(), mhi.tolist()):
            key = (h << 32) | l
            if key in self._mirror:
                continue
            if self.count + 1 > self.highwater * self.capacity:
                self._grow()
            self._place(l, h)
            self._mirror.add(key)
            self.count += 1
            added += 1
        return added

    # -- rollback points (supervisor retry/regrow replays) ---------------

    def snapshot(self) -> SpillStoreSnapshot:
        return SpillStoreSnapshot(self.table.copy(), self.count)

    def restore(self, snap: SpillStoreSnapshot) -> None:
        self.table = snap.table.copy()
        self.count = int(snap.count)
        self._rebuild_mirror()

    def _rebuild_mirror(self) -> None:
        t = self.table
        occ = (t[:, 0] != 0) | (t[:, 1] != 0)
        self._mirror = {
            (int(h) << 32) | int(l) for l, h in t[occ]
        }

    # -- durability (rides the checkpoint CRC/fsync machinery) -----------

    def save(self, path: str) -> None:
        from .checkpoint import save_checkpoint

        save_checkpoint(
            path, {"table": self.table},
            {"spill_format": SPILL_FORMAT, "count": self.count,
             "capacity": self.capacity},
        )

    @classmethod
    def load(cls, path: str) -> "SpillStore":
        """Load + CRC-verify a saved store; raises
        checkpoint.CheckpointCorruptError on a torn/rotten file (the
        generation fallback treats that like a torn carry snapshot)."""
        from .checkpoint import load_checkpoint, read_checkpoint_meta

        meta = read_checkpoint_meta(path)
        cap = int(meta["capacity"])
        template = {"table": np.zeros((cap, 2), np.uint32)}
        meta, loaded = load_checkpoint(path, template)
        store = cls(cap)
        store.table = np.asarray(loaded["table"], np.uint32).copy()
        store.count = int(meta["count"])
        store._rebuild_mirror()
        return store


def spill_sibling(ckpt_path: str) -> str:
    """The host-tier file that travels beside a checkpoint file."""
    return ckpt_path + ".spill"


def save_snapshot(path: str, snap: SpillStoreSnapshot) -> None:
    """Persist a store SNAPSHOT (the supervisor pairs each checkpoint
    generation with the host-tier state of the SAME boundary, never the
    live store, which may already have run ahead)."""
    from .checkpoint import save_checkpoint

    save_checkpoint(
        path, {"table": snap.table},
        {"spill_format": SPILL_FORMAT, "count": int(snap.count),
         "capacity": int(snap.table.shape[0])},
    )


class SpillRuntime:
    """Spill-mode execution of the single-device engine: the supervisor
    swaps its segment function for `segment_fn` when the ladder
    activates the spill tier, keeping every other supervision mechanism
    (checkpoints, SIGTERM drain, retry, queue regrow) unchanged.

    The runtime owns the jitted device halves (expand+filter, commit)
    and the host store; `on_event(kind, info)` receives `spill` journal
    events at activation/flush.  Unpipelined single-device carries
    only: the pipelined staged block and the mesh-sharded carry have no
    spill composition yet (the ladder degrades those runs to the next
    rung instead - supervisor docstring)."""

    def __init__(self, backend, chunk: int, queue_capacity: int,
                 fp_capacity: int, fp_index: int = DEFAULT_FP_INDEX,
                 seed: int = DEFAULT_SEED,
                 fp_highwater: float = 0.85,
                 check_deadlock: bool = None, obs_slots: int = 0,
                 deferred: bool = None,
                 store: Optional[SpillStore] = None,
                 on_event: Optional[Callable] = None,
                 spill_write_hook: Optional[Callable] = None):
        from .bfs import make_backend_engine, resolve_deferred

        deferred = resolve_deferred(deferred, chunk)

        self.backend = backend
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
        self.ncand = chunk * backend.n_lanes

        # init template through the production factory (no compile -
        # jits are lazy), then adopt into spill mode
        init_fn, _, _ = make_backend_engine(
            backend, chunk, queue_capacity, fp_capacity, fp_index,
            seed, fp_highwater=fp_highwater,
            check_deadlock=check_deadlock, donate=False,
            obs_slots=obs_slots, deferred=deferred,
        )
        self._base_init = init_fn
        pop_expand, commit = make_stage_pair(
            backend, chunk, queue_capacity=queue_capacity,
            fp_capacity=fp_capacity, fp_highwater=fp_highwater,
            check_deadlock=check_deadlock, fp_index=fp_index,
            seed=seed, obs_slots=obs_slots, spill=True,
            deferred=deferred,
        )

        # filter walk cap: near the highwater load, ABSENT keys walk
        # long full-bucket runs and the while_loop runs to the worst
        # lane of the whole chunk; unresolved lanes safely degrade to
        # a host probe (fpset_member docstring), so a small cap trades
        # a few extra host lookups for a bounded device filter
        MEMBER_ROUNDS = 4

        @jax.jit
        def expand_fn(c):
            ex, n = pop_expand(c)
            member = fpset_member(c.fps, ex.lo, ex.hi, ex.valid,
                                  max_rounds=MEMBER_ROUNDS)
            return ex, n, member

        @jax.jit
        def commit_fn(c, ex, n, veto):
            return commit(c, ex, n, c.qhead + n, c.qhead + n, veto=veto)

        self._expand_fn = expand_fn
        self._commit_fn = commit_fn
        # the preflight self-check's traceable composition: one full
        # device step with an all-false veto (the host probe happens
        # between the two jits in production, outside any device body)
        def audit_step(c):
            ex, n, _member = expand_fn(c)
            return commit_fn(c, ex, n,
                             jnp.zeros(self.ncand, bool))

        audit_step.donate_requested = False
        audit_step.donates_carry = False
        self.audit_step_fn = audit_step

    # -- carries ---------------------------------------------------------

    def init_fn(self):
        """Fresh spill-mode carry (also the checkpoint template)."""
        return self.adopt(self._base_init())

    def adopt(self, carry):
        """Enter spill mode: add the spill_hits leaf (idempotent).  The
        saturated device table stays put - the first chunk's residency
        check flushes it to the host store."""
        assert carry.st_n is None, \
            "spill mode runs unpipelined carries only"
        if carry.spill_hits is None:
            carry = carry._replace(spill_hits=jnp.uint32(0))
        return carry

    def _emit(self, kind: str, **info) -> None:
        if self.on_event is not None:
            self.on_event(kind, info)

    # -- the host-driven step loop --------------------------------------

    def _flush(self, carry):
        """Migrate the device table to the host store and reset it: the
        cold tier absorbs everything, the hot tier starts empty.
        Raises OSError through spill_write_hook under fault injection
        (the ladder's spill-write-failure rung)."""
        try:
            if self.spill_write_hook is not None:
                self.spill_write_hook()
        except OSError as e:
            raise SpillWriteError(str(e)) from e
        import time

        t_flush = time.time()
        table = np.asarray(carry.fps.table)
        lo = table[:, 0::2].reshape(-1)
        hi = table[:, 1::2].reshape(-1)
        occ = (lo != 0) | (hi != 0)
        raw_lo, raw_hi = unmix_host(lo[occ], hi[occ])
        self.store.insert_batch(raw_lo, raw_hi)
        self.flushes += 1
        carry = carry._replace(fps=fpset_new(self.fp_capacity))
        self._emit(
            "spill", phase="flush", resident=0,
            spilled=self.store.count, capacity=self.store.capacity,
            hits=int(carry.spill_hits), probes=self.probes,
            wall_s=round(time.time() - t_flush, 6),
        )
        return carry

    def segment_fn(self, ckpt_every: int):
        """seg_fn(carry) -> carry after up to `ckpt_every` chunk steps
        (synchronous - the host sits in the loop; the supervisor's
        block_until_ready at the fence is then a no-op).  Chunk steps
        and their pop sequence match the unpipelined fused body's, so
        bit-for-bit parity with a clean run holds."""
        highwater_slots = int(self.fp_capacity * self.fp_highwater)

        def seg(carry):
            # resident = device-table occupancy; measured (not derived
            # from the distinct counter) so a rolled-back carry whose
            # failed attempt already flushed entries stays exact
            resident = int(fpset_count(carry.fps))
            for _ in range(ckpt_every):
                if carry_done(carry):
                    break
                if resident + self.ncand > highwater_slots:
                    carry = self._flush(carry)
                    resident = 0
                ex, n, member = self._expand_fn(carry)
                lo, hi, valid, memb = jax.device_get(
                    (ex.lo, ex.hi, ex.valid, member)
                )
                probable_new = valid & ~memb
                veto = np.zeros(self.ncand, bool)
                npn = int(probable_new.sum())
                if npn:
                    self.probes += npn
                    veto[probable_new] = self.store.probe(
                        lo[probable_new], hi[probable_new]
                    )
                before = int(carry.distinct)
                carry = self._commit_fn(
                    carry, ex, n, jnp.asarray(veto)
                )
                resident += int(carry.distinct) - before
                if int(carry.viol) != OK:
                    break
            return carry

        return seg
