"""The run supervisor: self-healing exhaustive runs.

TLC's production value rests on surviving long runs (periodic disk
checkpoints + `-recover`); the TPU-native engines add three failure modes
TLC does not have - fixed-capacity device containers (fpset/queue/route
buckets sized at compile time), preemptible accelerator jobs (SIGTERM is
how TPU pods die), and transient XLA/device errors.  This module wraps
the segmented drivers (engine.checkpoint / engine.sharded) in a
supervision loop that converts all three from run-killers into events:

* **The capacity degradation ladder**: a capacity halt (VIOL_FPSET_FULL
  / VIOL_QUEUE_FULL / VIOL_ROUTE_OVERFLOW) walks rungs until one holds,
  instead of the old binary regrow-or-die:

  1. **regrow** - double the saturated resource, but only after a PROBE
     ALLOCATION confirms the doubled buffer is allocatable (a
     deterministic RESOURCE_EXHAUSTED used to crash mid-migration);
     migrate the last-good carry (resil.regrow) and replay the segment -
     final statistics provably equal an uninterrupted correctly-sized
     run's.  Bounded by max_regrow.
  2. **host spill tier** (fpset saturation on unpipelined single-device
     runs) - activate engine.spill: cold fingerprints migrate to a
     host-RAM SpillStore, the device table becomes the hot tier with an
     fpset_member filter in front of the host round trip, and the run
     COMPLETES inside the device memory it has - bit-for-bit the clean
     run's counters/verdict.
  3. **chunk shrink** - halve the pop width (freeing candidate-buffer
     memory) and retry the regrow probe; repeats to a floor of 64.
     Counts/verdict are preserved; in-batch duplicate attribution may
     shift (documented in resil.regrow).
  4. **checkpoint + exit 75** - write a final generation (host tier
     included), journal an `exhausted` event with the resume command,
     and return exhausted=True (the CLI exits EXIT_INTERRUPTED).

  VIOL_SLOT_OVERFLOW (codec bit-widths too narrow) is NOT on the ladder
  - it needs a recompile - and degrades to checkpoint + actionable
  error as before (a compacted struct step's overflow is answered one
  level up, by api._run_check_struct's widen rung).
* **Preemption safety**: SIGTERM/SIGINT finish the current segment,
  write a final checkpoint generation, and return `interrupted=True`
  (the CLI exits with EXIT_INTERRUPTED and prints the resume command).
* **Retry with backoff**: TRANSIENT errors around segment execution are
  retried from the last good carry with exponential backoff + jitter
  (deterministic, seeded) up to `retries` attempts.  Runtime errors are
  CLASSIFIED first: a RESOURCE_EXHAUSTED/OOM is deterministic - it goes
  to the ladder immediately instead of burning the whole retry budget.
* **Crash-consistent storage**: checkpoints are CRC-manifested,
  fsync'd, generation-numbered files; resume loads the newest generation
  that passes verification, falling back past a torn newest file, and
  rebuilds the engine with the geometry THE CHECKPOINT RECORDS - so a
  resume command never needs to repeat auto-grown capacities.  A
  spilling run pairs every generation with a CRC'd host-tier file
  (PATH.gNNNNNN.npz.spill); `-recover` restores BOTH tiers bit-for-bit
  or falls back to the previous intact pair.

Every recovery path is proven by fault injection (resil.faults,
tools/chaos.py --matrix, tests/test_resil.py, tests/test_spill.py).
"""

from __future__ import annotations

import dataclasses
import os
import random
import signal
import time
from typing import Callable, NamedTuple, Optional

import jax
import numpy as np
from jax.errors import JaxRuntimeError

from ..engine import checkpoint as ckpt
from ..engine.bfs import (
    DEFAULT_FP_HIGHWATER,
    OK,
    VIOL_SLOT_OVERFLOW,
    VIOLATION_NAMES,
    CheckResult,
    carry_done,
    commit_counters,
    commit_geometry,
    make_engine,
    mesh_counters,
    with_step_counters,
    result_from_carry,
)
from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from ..engine.spill import SpillWriteError
from ..obs import spans
from ..obs.spans import span
from ..runtime import aot_build, drop_kept_engines, engine_key
from .faults import FaultInjector, FaultPlan, TransientFault
from .regrow import (
    GROWABLE,
    grown,
    migrate_engine_carry,
    migrate_shard_carry,
)

# exception types the segment-retry loop CATCHES: the injected stand-in
# plus jax's runtime error.  Caught is not retried: every caught error
# is classified first (is_resource_exhausted) - a deterministic
# RESOURCE_EXHAUSTED routes to the degradation ladder, only genuinely
# transient errors get the backoff budget.
_TRANSIENT: tuple = (TransientFault, JaxRuntimeError)

# python-level allocation failures (and the injected AllocDeniedFault,
# a MemoryError) are caught alongside the runtime errors - they are
# always classified as resource exhaustion, never retried
_CAUGHT: tuple = _TRANSIENT + (MemoryError,)

# XLA status markers of a deterministic allocation failure.  Retrying
# these with backoff burned the full retry budget before dying (the
# PR 2 overreach); the ladder absorbs them instead.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "Out of memory", "Allocation failure")


def is_resource_exhausted(e: BaseException) -> bool:
    """Classify a caught runtime error: True for deterministic
    device/host allocation failures (route to the degradation ladder),
    False for the transient class (retry with backoff).  XLA surfaces
    its status code in the message, so classification is by
    status-string; MemoryError (python hosts + the injected
    AllocDeniedFault) is always exhaustion."""
    if isinstance(e, MemoryError):
        return True
    msg = str(e)
    return any(m in msg for m in _OOM_MARKERS)


# CLI exit code for an interrupted-but-checkpointed run (EX_TEMPFAIL:
# "try again later" - distinct from 0/12/13 so schedulers can requeue).
# Capacity exhaustion that survives to a checkpoint (ladder rung 4)
# exits with the same code: both mean "resume me".
EXIT_INTERRUPTED = 75

# chunk-shrink floor of the ladder's rung 3 (below this the fixed
# per-step overheads dominate and halving frees almost nothing)
MIN_CHUNK = 64


class SlotOverflowError(RuntimeError):
    """Codec slot overflow: a state field exceeded its compiled bit
    width.  Not survivable by regrow - the codec/kernel must be rebuilt
    with wider ModelConfig bounds - so the supervisor checkpoints the
    last good carry and raises this with the resume story attached."""

    def __init__(self, ckpt_path: Optional[str], state=None):
        self.ckpt_path = ckpt_path
        # the [F] field vector of the state whose expansion trapped
        # (the struct path reads the trap's cause off it)
        self.state = state
        hint = (
            f"; last good carry checkpointed at {ckpt_path!r} - after "
            "raising the bounds, restart (a recompiled codec changes the "
            "state encoding, so the checkpoint is diagnostic only)"
            if ckpt_path else "; re-run with -checkpoint to keep a snapshot"
        )
        super().__init__(
            "codec slot overflow: raise the ModelConfig bounds and "
            "recompile - auto-grow cannot widen compiled bit fields" + hint
        )


@dataclasses.dataclass
class SupervisorOptions:
    """Knobs of one supervised run (CLI: -auto-grow/-no-auto-grow,
    -max-regrow, -retry, -checkpoint, -checkpointevery, -recover)."""

    auto_grow: bool = True
    max_regrow: int = 8
    retries: int = 2
    backoff_base_s: float = 0.5
    backoff_cap_s: float = 30.0
    ckpt_path: Optional[str] = None
    ckpt_every: int = 256
    keep_generations: int = 2
    resume: bool = False
    faults: Optional[FaultPlan] = None
    # host spill tier policy (CLI -spill/-no-spill): "auto" activates it
    # when an fpset regrow is denied by the allocation probe (or
    # max_regrow is exhausted); "on" prefers it over regrowing at the
    # FIRST fpset saturation; "off" removes the rung from the ladder
    spill: str = "auto"
    # initial host-store capacity (auto-grows in host RAM)
    spill_capacity: int = 1 << 15
    # rung-3 floor: chunk never shrinks below this
    min_chunk: int = MIN_CHUNK
    # coverage-saturation signal: once the run has gone this many BFS
    # levels without visiting a NEW coverage site, one `coverage`
    # journal event with saturated=true is emitted (the live "the spec
    # stopped exploring new behavior" cue; only with a coverage plane)
    coverage_sat_levels: int = 8
    # artifact cache (struct.artifacts): read the final fingerprint
    # table back to host on a CLEAN verdict so the reachable-set tier
    # can be derived from it.  Single-device non-spilled runs only -
    # the spill tier's table is partial and the sharded carry is
    # per-device (CAPTURES_FPS on the adapter gates it)
    capture_fps: bool = False
    # programmatic drain request (ISSUE 17): a threading.Event twin of
    # _SignalCatcher for in-process preemption - the serve scheduler
    # sets it to preempt ONE supervised job (deadline / priority /
    # cancel) without signaling the whole server.  Checked at the same
    # segment boundaries as sig.hit, so a drained run rides the
    # identical checkpoint + exit-75 machinery and its -recover resume
    # is bit-for-bit the uninterrupted run
    drain: Optional[object] = None
    # on_event(kind, info_dict): checkpoint / ckpt_write_failed / recovery
    # / regrow / retry / interrupted / progress / spill / degrade /
    # exhausted - the tlc_log banner seam
    on_event: Optional[Callable[[str, dict], None]] = None
    # finish(result) -> (result, verdict | None): what a clean verdict
    # still owes before it is final - the temporal properties of a
    # struct check (api._run_check_struct: the liveness route's `live`
    # span and counters) - run after the loop, inside `check`, BEFORE
    # the `spans` and `final` events, so both carry it.  Called only
    # where the verdict so far is "ok"; a verdict it returns replaces it
    finish: Optional[Callable] = None


class SupervisedResult(NamedTuple):
    result: CheckResult
    params: dict  # final engine geometry (auto-grown values included)
    regrows: int
    retries: int
    interrupted: bool
    segments: int
    ckpt_writes: int
    ckpt_write_s: float  # total seconds spent writing checkpoints
    regrow_s: float  # total seconds spent in regrow migration + rebuild
    # --- degradation-ladder telemetry (defaults keep old callers) -----
    exhausted: bool = False  # rung 4: capacity unrecoverable, resume me
    spilled: int = 0  # fingerprints resident in the host spill store
    spill_flushes: int = 0  # device-table -> host-store migrations
    spill_hits: int = 0  # candidates the host tier vetoed
    shrinks: int = 0  # rung-3 chunk halvings


class _SignalCatcher:
    """Installs SIGTERM/SIGINT handlers that record the signal instead of
    killing the process, so the supervision loop can drain the current
    segment and checkpoint.  Restores previous handlers on exit; degrades
    to a no-op off the main thread (signal.signal raises there)."""

    SIGNUMS = (signal.SIGTERM, signal.SIGINT)

    def __enter__(self):
        self.hit = None
        self._saved = {}
        for s in self.SIGNUMS:
            try:
                self._saved[s] = signal.signal(
                    s, lambda signum, frame: self._record(signum)
                )
            except ValueError:  # not the main thread
                pass
        return self

    def _record(self, signum):
        self.hit = signum

    def __exit__(self, *exc):
        for s, h in self._saved.items():
            signal.signal(s, h)
        return False


class SingleDeviceAdapter:
    """Supervision seam over the single-device segmented engine
    (engine.checkpoint's driver, reshaped so the supervisor owns the
    loop).  Growable params: queue_capacity, fp_capacity.

    `backend` (a SpecBackend) swaps the hand-tuned KubeAPI kernel for
    any frontend's compiled step - struct-compiled specs ride the SAME
    supervision loop, checkpoint format and regrow migration with zero
    frontend-specific recovery code; `meta_config` then replaces the
    ModelConfig stanza in the checkpoint meta."""

    kind = "single"
    # the artifact cache may read this adapter's final fpset table back
    # (one table, whole reachable set; the sharded adapter's carry is
    # per-device and stays uncaptured)
    CAPTURES_FPS = True
    GEOM_KEYS = ("queue_capacity", "fp_capacity")
    FIXED_KEYS = ("format", "config", "chunk", "fp_index", "seed",
                  "fp_highwater", "pipeline", "obs_slots", "coverage",
                  "deferred", "symmetry", "por")

    def __init__(self, cfg, chunk: int = 1024,
                 fp_index: int = DEFAULT_FP_INDEX, seed: int = DEFAULT_SEED,
                 fp_highwater: float = DEFAULT_FP_HIGHWATER,
                 backend=None, meta_config: dict = None,
                 check_deadlock: bool = True, pipeline: bool = False,
                 obs_slots: int = 0, coverage: bool = False,
                 deferred: bool = None):
        from ..engine.bfs import resolve_deferred

        self.cfg = cfg
        self.chunk = chunk
        # resolved once, against the INITIAL chunk: a later ladder
        # chunk-shrink keeps the mode (meta stays consistent across
        # the resume)
        self.deferred = resolve_deferred(deferred, chunk)
        self.fp_index = fp_index
        self.seed = seed
        self.fp_highwater = fp_highwater
        # what a kept engine is named by (runtime.engine_key): the frozen
        # config the hand kernel is built from, else the backend object
        self.program = cfg if backend is None else backend
        if backend is None and coverage:
            # the KubeAPI path with the device coverage plane: build
            # the covered backend once so sites/meta/engine agree
            from ..engine.backend import kubeapi_backend

            backend = kubeapi_backend(cfg, coverage=True)
            check_deadlock = True  # the kubeapi backend's own default
        self.backend = backend
        self.meta_config = meta_config
        self.check_deadlock = check_deadlock
        self.pipeline = pipeline
        self.obs_slots = obs_slots
        # the flag that shapes the carry layout (checkpoint meta key):
        # True iff the engine actually carries the coverage leaves
        self.coverage = (backend is not None
                         and backend.coverage is not None)
        # reduction flags ride the backend the same way: a reduced run
        # explores a different (smaller) frontier, so resuming across
        # a flag change must mismatch loudly (checkpoint meta keys)
        red = getattr(backend, "reduce", None)
        self.symmetry = bool(red is not None and red.plan is not None)
        self.por = bool(red is not None and red.por and red.safe_ids)

    def build(self, params: dict, ckpt_every: int):
        # donate=False: the supervisor feeds the SAME last-good carry
        # back into the segment on retry/regrow and checkpoints it while
        # the next segment is in flight - donation would invalidate it
        def make():
            if self.backend is not None:
                from ..engine.bfs import make_backend_engine

                init_fn, _, step_fn = make_backend_engine(
                    self.backend, self.chunk, params["queue_capacity"],
                    params["fp_capacity"], self.fp_index, self.seed,
                    fp_highwater=self.fp_highwater,
                    check_deadlock=self.check_deadlock,
                    pipeline=self.pipeline, donate=False,
                    obs_slots=self.obs_slots, deferred=self.deferred,
                )
            else:
                init_fn, _, step_fn = make_engine(
                    self.cfg, self.chunk, params["queue_capacity"],
                    params["fp_capacity"], self.fp_index, self.seed,
                    fp_highwater=self.fp_highwater,
                    pipeline=self.pipeline, donate=False,
                    obs_slots=self.obs_slots, deferred=self.deferred,
                )

            return init_fn, step_fn.segment(ckpt_every)

        # async contract: seg_fn DISPATCHES and returns in-flight arrays;
        # the supervision loop overlaps host work (checkpoint write,
        # stats readback of the previous carry) with the running segment
        # and fences with jax.block_until_ready.  The build is kept under
        # everything that shapes it: a regrow, a chunk shrink or a resume
        # into another geometry is another key
        return aot_build(make, key=engine_key(
            self.kind, self.program, self.meta(params),
            self.check_deadlock, ckpt_every))

    def meta(self, params: dict) -> dict:
        return ckpt._meta(
            self.cfg, meta_config=self.meta_config, chunk=self.chunk,
            fp_index=self.fp_index, seed=self.seed,
            fp_highwater=self.fp_highwater, pipeline=self.pipeline,
            obs_slots=self.obs_slots, coverage=self.coverage,
            deferred=self.deferred,
            symmetry=self.symmetry, por=self.por,
            **params,
        )

    def viol(self, carry) -> int:
        return int(carry.viol)

    def done(self, carry) -> bool:
        return carry_done(carry)

    def cov_sites(self):
        """The coverage plane's site table (None when coverage is off);
        the supervisor keys its `coverage` journal deltas on it."""
        if self.backend is not None and self.backend.coverage is not None:
            return self.backend.coverage.sites
        return None

    def cov_totals(self, carry):
        from ..engine.bfs import cov_totals

        return cov_totals(carry)

    def obs_rows(self, carry, since: int, params: dict):
        """New observability-ring rows since cursor `since` (journal
        `level` events); ([], since) when obs is off."""
        from ..engine.bfs import obs_rows

        return obs_rows(carry, since=since,
                        fp_capacity=params["fp_capacity"])

    def progress(self, carry):
        # one batched device_get instead of four blocking scalar pulls;
        # a pipelined carry's staged block counts as queued work
        st = carry.st_n if carry.st_n is not None else 0
        d, g, di, ln, qh, nn, sn = jax.device_get(
            (carry.depth, carry.generated, carry.distinct,
             carry.level_n, carry.qhead, carry.next_n, st)
        )
        return (
            int(d), int(g), int(di),
            int(ln) - int(qh) + int(nn) + int(sn),
        )

    def migrate(self, carry, old_params: dict, new_params: dict):
        return migrate_engine_carry(carry, old_params, new_params)

    # ---- degradation-ladder seams (engine.spill / chunk shrink) -------

    def supports_spill(self) -> bool:
        # the spill driver runs the unpipelined fused stages; a
        # pipelined carry's staged block has no spill composition (the
        # ladder degrades those runs to the next rung instead)
        return not self.pipeline

    def build_spill(self, params: dict, store, on_event=None,
                    spill_write_hook=None):
        """A SpillRuntime over this adapter's backend + geometry (the
        supervisor swaps its segment function for the runtime's when
        the ladder activates the host tier)."""
        from ..engine.spill import SpillRuntime

        backend = self.backend
        check_deadlock = self.check_deadlock
        if backend is None:
            from ..engine.backend import kubeapi_backend

            backend = kubeapi_backend(self.cfg)
            check_deadlock = None  # the kubeapi backend's own default
        return SpillRuntime(
            backend, self.chunk, params["queue_capacity"],
            params["fp_capacity"], fp_index=self.fp_index,
            seed=self.seed, fp_highwater=self.fp_highwater,
            check_deadlock=check_deadlock, obs_slots=self.obs_slots,
            deferred=self.deferred,
            store=store, on_event=on_event,
            spill_write_hook=spill_write_hook,
        )

    def can_shrink(self, floor: int = MIN_CHUNK) -> bool:
        return not self.pipeline and self.chunk // 2 >= floor

    def reseat_chunk(self, carry, params: dict):
        """Halve the pop width: re-seat the carry's queue padding for
        chunk/2 and record the new width (rung 3 - counts/verdict
        preserved, in-batch attribution caveat in resil.regrow)."""
        new_chunk = self.chunk // 2
        migrated = migrate_engine_carry(
            carry, params, params, new_chunk=new_chunk
        )
        self.chunk = new_chunk
        return migrated

    def result(self, carry, wall: float, segments: int,
               params: dict) -> CheckResult:
        from ..engine.fpset import fpset_actual_collision

        afc = float(fpset_actual_collision(carry.fps))
        if self.backend is not None:
            n_lanes = self.backend.n_lanes
            kw = dict(labels=self.backend.labels,
                      viol_names=self.backend.viol_names,
                      sites=self.cov_sites())
        else:
            from ..spec.kernel import lane_layout

            n_lanes, kw = lane_layout(self.cfg)[1], {}
        return with_step_counters(result_from_carry(
            carry, wall, iterations=segments,
            fp_capacity=params["fp_capacity"],
            commit=commit_geometry(n_lanes, self.chunk), **kw,
        )._replace(actual_fp_collision=afc), self.backend)


class ShardedAdapter:
    """Supervision seam over the mesh-sharded engine.  All capacities are
    PER DEVICE; route_factor regrows without carry migration."""

    kind = "sharded"
    GEOM_KEYS = ("queue_capacity", "fp_capacity", "route_factor")
    FIXED_KEYS = ("format", "config", "devices", "fp_highwater",
                  "pipeline", "obs_slots", "coverage", "deferred",
                  "symmetry", "por")

    def __init__(self, cfg, mesh, chunk: int = 512, backend=None,
                 meta_config: dict = None,
                 fp_highwater: float = DEFAULT_FP_HIGHWATER,
                 pipeline: bool = False, obs_slots: int = 0,
                 coverage: bool = False, deferred: bool = None):
        from ..engine.bfs import resolve_deferred
        from ..engine.sharded import kubeapi_backend

        self.cfg = cfg
        self.mesh = mesh
        self.chunk = chunk
        self.deferred = resolve_deferred(deferred, chunk)
        self.program = cfg if backend is None else backend
        self.backend = (backend if backend is not None
                        else kubeapi_backend(cfg, coverage=coverage))
        self.meta_config = meta_config
        self.fp_highwater = fp_highwater
        self.pipeline = pipeline
        self.obs_slots = obs_slots
        self.coverage = self.backend.coverage is not None
        red = getattr(self.backend, "reduce", None)
        self.symmetry = bool(red is not None and red.plan is not None)
        self.por = bool(red is not None and red.por and red.safe_ids)

    def build(self, params: dict, ckpt_every: int):
        from ..engine.sharded import make_sharded_engine

        # async contract: dispatch only; the supervision loop fences
        return aot_build(lambda: make_sharded_engine(
            self.cfg, self.mesh, self.chunk,
            params["queue_capacity"], params["fp_capacity"],
            route_factor=params["route_factor"], segment=ckpt_every,
            backend=self.backend, fp_highwater=self.fp_highwater,
            pipeline=self.pipeline, obs_slots=self.obs_slots,
            deferred=self.deferred,
        ), key=engine_key(self.kind, self.program, self.meta(params),
                          self.mesh, ckpt_every))

    def meta(self, params: dict) -> dict:
        return ckpt._meta(
            self.cfg, meta_config=self.meta_config, chunk=self.chunk,
            devices=int(self.mesh.devices.size),
            fp_highwater=self.fp_highwater, pipeline=self.pipeline,
            obs_slots=self.obs_slots, coverage=self.coverage,
            deferred=self.deferred,
            symmetry=self.symmetry, por=self.por,
            **params,
        )

    def cov_sites(self):
        if self.backend.coverage is not None:
            return self.backend.coverage.sites
        return None

    def cov_totals(self, carry):
        from ..engine.bfs import cov_totals

        return cov_totals(carry)

    def viol(self, carry) -> int:
        return int(np.asarray(carry.viol).max())

    def done(self, carry) -> bool:
        return not bool(np.asarray(carry.cont).any())

    def progress(self, carry):
        # one batched device_get instead of five blocking pulls
        d, g, di, qt, qh = jax.device_get(
            (carry.depth, carry.generated, carry.distinct,
             carry.qtail, carry.qhead)
        )
        return (
            int(np.asarray(d).max()),
            int(np.asarray(g).sum()),
            int(np.asarray(di).sum()),
            int((np.asarray(qt) - np.asarray(qh)).sum()),
        )

    def obs_rows(self, carry, since: int, params: dict):
        from ..engine.sharded import obs_rows_sharded

        return obs_rows_sharded(
            carry, since=since,
            fp_capacity_total=(params["fp_capacity"]
                               * int(self.mesh.devices.size)),
        )

    def supports_spill(self) -> bool:
        from ..engine.sharded import SPILL_CAPABLE

        # like the single-device adapter: the spill driver runs the
        # unpipelined halves; a pipelined carry's pending-verdict block
        # has no spill composition (ladder degrades to the next rung)
        return SPILL_CAPABLE and not self.pipeline

    def build_spill(self, params: dict, store, on_event=None,
                    spill_write_hook=None):
        """A ShardedSpillRuntime over this adapter's backend + geometry
        (the supervisor swaps its segment function for the runtime's
        when the ladder activates the host tier on a sharded run)."""
        from ..engine.sharded import ShardedSpillRuntime

        return ShardedSpillRuntime(
            self.cfg, self.mesh, self.chunk,
            params["queue_capacity"], params["fp_capacity"],
            route_factor=params["route_factor"], backend=self.backend,
            fp_highwater=self.fp_highwater, obs_slots=self.obs_slots,
            deferred=self.deferred,
            store=store, on_event=on_event,
            spill_write_hook=spill_write_hook,
        )

    def migrate(self, carry, old_params: dict, new_params: dict):
        return migrate_shard_carry(carry, old_params, new_params)

    def result(self, carry, wall: float, segments: int,
               params: dict) -> CheckResult:
        from ..engine.sharded import (
            result_from_shard_carry,
            route_geometry,
        )

        D = int(self.mesh.devices.size)
        return with_step_counters(result_from_shard_carry(
            carry, wall, iterations=segments,
            labels=self.backend.labels,
            viol_names=self.backend.viol_names,
            fp_capacity_total=params["fp_capacity"] * D,
            sites=self.cov_sites(),
            route=route_geometry(self.backend, self.chunk, D,
                                 params["route_factor"]),
        ), self.backend)


def _params_from_meta(adapter, meta: dict, params: dict) -> dict:
    """Resume geometry resolution: fixed keys (config, codec-shaping
    parameters) must match what this process would write; growable
    geometry keys are TAKEN FROM THE CHECKPOINT (auto-grown capacities
    travel with the snapshot, so the resume command needs none of them)."""
    want = adapter.meta(params)
    for key in adapter.FIXED_KEYS:
        # pre-pipeline/pre-obs/pre-coverage/pre-deferred/pre-reduction
        # snapshots carry no key: they were cut from engines without
        # those features, so missing means off.  A `sort_free` key (every
        # snapshot before ISSUE 44 has one, true or false) is not
        # compared: the hash slab it names was a per-commit temporary and
        # the carry was the sorted ordering's bit for bit
        have = meta.get(key, False if key in ("pipeline", "coverage",
                                              "deferred", "symmetry",
                                              "por")
                        else 0 if key == "obs_slots" else None)
        if have != want.get(key):
            raise ValueError(
                f"checkpoint {key} mismatch: "
                f"{have!r} != {want.get(key)!r}"
            )
    out = dict(params)
    for key in adapter.GEOM_KEYS:
        if key in meta:
            out[key] = meta[key]
    return out


def _emit(opts: SupervisorOptions, kind: str, **info) -> None:
    if opts.on_event is not None:
        opts.on_event(kind, info)


def _resume(adapter, params: dict, opts: SupervisorOptions,
            make_spill_runtime, build=None):
    """Load the newest verifiable checkpoint of the family `ckpt_path`
    (generations first, then the plain file for pre-supervisor
    snapshots), rebuilding the engine with the recorded geometry.  A
    checkpoint whose meta records an active spill tier restores the
    paired host-store file too (engine.spill.spill_sibling) - a torn
    or missing sibling fails the WHOLE generation, falling back to the
    previous intact pair, so the two tiers can never resume skewed.
    Returns (params, template, seg_fn, carry, path, spill_rt)."""
    from ..engine.spill import SpillStore, spill_sibling

    base = opts.ckpt_path
    cands = [p for _, p in reversed(ckpt.list_generations(base))]
    if os.path.exists(base):
        cands.append(base)
    if not cands:
        raise FileNotFoundError(f"no checkpoint at {base!r}")
    last_err = None
    for path in cands:
        try:
            meta = ckpt.read_checkpoint_meta(path)
        except ckpt.CheckpointCorruptError as e:
            last_err = e
            _emit(opts, "ckpt_fallback", path=path, error=str(e))
            continue
        new_params = _params_from_meta(adapter, meta, params)
        spill_rt = None
        if (meta.get("spill") or {}).get("active"):
            try:
                store = SpillStore.load(spill_sibling(path))
            except (ckpt.CheckpointCorruptError, OSError,
                    FileNotFoundError, KeyError) as e:
                last_err = e
                _emit(opts, "ckpt_fallback", path=path,
                      error=f"spill sibling: {e}")
                continue
            spill_rt = make_spill_runtime(new_params, store)
            template = spill_rt.init_fn()
            seg_fn = spill_rt.segment_fn(opts.ckpt_every)
        else:
            template, seg_fn = (
                build(new_params) if build is not None
                else adapter.build(new_params, opts.ckpt_every)
            )
        try:
            _, carry = ckpt.load_checkpoint(path, template)
        except ckpt.CheckpointCorruptError as e:
            last_err = e
            _emit(opts, "ckpt_fallback", path=path, error=str(e))
            continue
        return new_params, template, seg_fn, carry, path, spill_rt
    raise FileNotFoundError(
        f"no intact checkpoint under {base!r} (newest failure: {last_err})"
    )


def _probe_grow(resource: str, new_value, faults) -> Optional[str]:
    """The regrow allocation probe: confirm the DOUBLED resource is
    allocatable before tearing into a carry migration (a denied
    allocation used to crash mid-regrow - the exact moment the run
    mattered most).  Returns None when allocatable, else the denial
    reason.  Sized per resource (bytes of the new container, the
    dominant term; route_factor buckets are too small to probe)."""
    import jax
    import jax.numpy as jnp

    nbytes = {
        "fp_capacity": 8,  # 2 uint32 words per slot
        "queue_capacity": 64,  # 2 buffers x packed words, upper bound
        "route_factor": 0,
    }.get(resource, 8) * int(new_value if resource != "route_factor"
                             else 0)
    try:
        faults.alloc_probe()
        if nbytes > 0:
            buf = jnp.zeros(nbytes, jnp.uint8)
            jax.block_until_ready(buf)
            del buf
        return None
    except Exception as e:  # noqa: BLE001 - classified right below
        if is_resource_exhausted(e):
            return str(e)
        raise


def _supports_spill(adapter) -> bool:
    f = getattr(adapter, "supports_spill", None)
    return bool(f()) if callable(f) else False


def _can_shrink(adapter, floor: int) -> bool:
    f = getattr(adapter, "can_shrink", None)
    return bool(f(floor)) if callable(f) else False


@spans.in_check
def supervise(adapter, params: dict,
              opts: SupervisorOptions = None) -> SupervisedResult:
    """Run an exhaustive check under supervision.  `params` holds the
    adapter's growable geometry (queue_capacity, fp_capacity, and
    route_factor for the sharded adapter); everything else is fixed in
    the adapter.  Returns the final CheckResult plus recovery telemetry.

    Capacity exhaustion walks the degradation ladder (module
    docstring): probed regrow -> host spill tier -> chunk shrink ->
    checkpoint + exhausted=True.  When the spill tier is active the
    supervisor keeps a host-store SNAPSHOT paired with every last-good
    carry, so retry/regrow replays roll both tiers back in lock-step
    (a store that ran ahead of a rolled-back carry would veto states
    the carry has not counted yet - a silent undercount)."""
    opts = opts or SupervisorOptions()
    faults = FaultInjector(opts.faults)
    rng = random.Random(0xC0FFEE)  # deterministic backoff jitter
    params = dict(params)
    regrows = retries_used = segments = ckpt_writes = shrinks = 0
    ckpt_write_s = regrow_s = 0.0
    interrupted = exhausted = False
    exhaust_resource = ""
    spill_rt = None  # engine.spill.SpillRuntime once the tier is active
    good_store = None  # SpillStoreSnapshot paired with `good`

    def emit_info(kind, info):
        _emit(opts, kind, **info)

    def make_spill_runtime(p, store):
        return adapter.build_spill(
            p, store, on_event=emit_info,
            spill_write_hook=faults.spill_write,
        )

    def build_engine(p):
        return adapter.build(p, opts.ckpt_every)

    def rebuild(p):
        """(template, seg_fn) for geometry `p` in the CURRENT mode: the
        spill runtime is rebuilt around the same host store when the
        tier is active (queue regrow / chunk shrink under spill)."""
        nonlocal spill_rt
        if spill_rt is not None:
            old = spill_rt
            spill_rt = make_spill_runtime(p, old.store)
            spill_rt.flushes = old.flushes
            spill_rt.probes = old.probes
            return spill_rt.init_fn(), spill_rt.segment_fn(opts.ckpt_every)
        return build_engine(p)

    if opts.resume:
        if not opts.ckpt_path:
            raise ValueError("resume requires a checkpoint path")
        params, template, seg_fn, carry, path, spill_rt = _resume(
            adapter, params, opts, make_spill_runtime,
            build=build_engine,
        )
        prog = adapter.progress(carry)
        _emit(opts, "recovery", path=path, depth=prog[0],
              generated=prog[1], distinct=prog[2], queue=prog[3])
    else:
        template, seg_fn = build_engine(params)
        carry = template
    # timer starts after the (AOT) build, matching bfs.check's discipline
    # (regrow rebuilds DO count: recompilation is part of regrow's price)
    t0 = time.time()

    def save(carry_to_save, label: str, store_snap=None):
        nonlocal ckpt_writes, ckpt_write_s
        if not opts.ckpt_path:
            return None
        faults.before_write()
        t = time.time()
        meta = adapter.meta(params)
        if spill_rt is not None and store_snap is not None:
            # the host tier travels as a CRC'd sibling file; meta
            # records it so -recover knows to restore BOTH tiers
            meta["spill"] = {
                "active": True, "count": int(store_snap.count),
                "capacity": int(store_snap.table.shape[0]),
            }
        path = ckpt.save_generation(
            opts.ckpt_path, carry_to_save, meta,
            keep=opts.keep_generations,
        )
        if spill_rt is not None and store_snap is not None:
            from ..engine.spill import save_snapshot, spill_sibling

            save_snapshot(spill_sibling(path), store_snap)
        # refresh the plain family head too (hardlink, no data copy):
        # non-supervised tooling and the TLC `-recover` muscle memory
        # expect the checkpoint to exist under the path the user gave
        heads = [(path, opts.ckpt_path)]
        if spill_rt is not None and store_snap is not None:
            heads.append((path + ".spill", opts.ckpt_path + ".spill"))
        for src_path, head in heads:
            tmp = head + ".head.tmp"
            try:
                os.link(src_path, tmp)
                os.replace(tmp, head)
            except OSError:
                try:
                    import shutil

                    shutil.copyfile(src_path, tmp)
                    os.replace(tmp, head)
                except OSError:
                    pass
        ckpt_write_s += time.time() - t
        ckpt_writes += 1
        faults.after_write(path)
        _emit(opts, "checkpoint", path=path,
              seconds=round(time.time() - t, 3), label=label)
        return path

    good = carry
    if spill_rt is not None:
        good_store = spill_rt.store.snapshot()
    # observability cursor: ring rows below this head are already
    # journaled.  A resumed carry starts past its restored history (the
    # original journal already holds those levels); regrow/retry replays
    # re-derive rows below the cursor bit-for-bit, so nothing duplicates.
    obs_read = getattr(adapter, "obs_rows", None)
    obs_seen = 0
    if obs_read is not None:
        _, obs_seen = obs_read(carry, 0, params)
    # coverage cursor: per-site totals already journaled.  A resumed
    # carry's restored totals are in the original journal, so they seed
    # the cursor; a fresh run's first event carries the Init visits.
    cov_sites = None
    if callable(getattr(adapter, "cov_sites", None)):
        cov_sites = adapter.cov_sites()
    cov_seen = None
    cov_visited = 0
    cov_level = 0
    cov_last_new_level = 0
    cov_saturated = False
    if cov_sites is not None and opts.resume:
        cov_seen = adapter.cov_totals(carry)
        cov_visited = int((cov_seen > 0).sum())
    # deferred periodic checkpoint: written while the NEXT segment is in
    # flight, so snapshot serialization/fsync overlaps device execution
    # instead of stalling the step loop (the carry is safe to read
    # concurrently because the engines are built donate=False here).
    # In spill mode the pair (carry, host-store snapshot) is deferred
    # TOGETHER so the two tiers can never publish skewed.
    pending_save = None

    def flush_save():
        nonlocal pending_save
        if pending_save is None:
            return
        c, snap = pending_save
        pending_save = None
        try:
            save(c, "periodic", store_snap=snap)
        except OSError as e:
            # a failed snapshot write must not kill a healthy run; the
            # next segment boundary retries
            _emit(opts, "ckpt_write_failed", error=str(e))

    def rollback_store():
        """Roll the host tier back to the last-good boundary: a failed
        or violated segment may have flushed device entries into the
        store, and a store ahead of the carry silently undercounts."""
        if spill_rt is not None and good_store is not None:
            spill_rt.store.restore(good_store)

    drained = (lambda: opts.drain is not None and opts.drain.is_set())
    with span("loop"), _SignalCatcher() as sig:
        while not adapter.done(carry):
            if sig.hit is not None or drained():
                interrupted = True
                break

            # ---- one segment: classify, then retry only transients ----
            attempt = 0
            oom = None
            spill_broken = None
            while True:
                try:
                    faults.segment_start(segments)
                    t_dispatch = time.time()
                    with span("loop.dispatch"):
                        in_flight = seg_fn(good)
                    # host work overlapping the running segment: the
                    # previous segment's checkpoint write + progress line
                    with span("loop.overlap"):
                        flush_save()
                    with span("loop.wait"):
                        carry2 = jax.block_until_ready(in_flight)
                    t_fence = time.time()
                    break
                except SpillWriteError as e:
                    # the host tier cannot absorb the full device table:
                    # retrying cannot help (the table stays full) - the
                    # ladder's final rung takes it
                    spill_broken = e
                    break
                except _CAUGHT as e:
                    if is_resource_exhausted(e):
                        # deterministic RESOURCE_EXHAUSTED: retrying it
                        # burned the whole backoff budget before dying
                        # (the PR 2 overreach) - the ladder absorbs it
                        oom = e
                        break
                    if attempt >= opts.retries:
                        raise
                    delay = min(
                        opts.backoff_cap_s,
                        opts.backoff_base_s * (2 ** attempt),
                    ) * (0.5 + rng.random())
                    _emit(opts, "retry", attempt=attempt + 1,
                          delay_s=round(delay, 3), error=str(e))
                    time.sleep(delay)
                    attempt += 1
                    retries_used += 1
                    if spill_rt is not None:
                        # both tiers roll back together; the on-disk
                        # path below cannot guarantee a tier-consistent
                        # pair mid-retry, so spill retries stay in-memory
                        rollback_store()
                    elif opts.ckpt_path and ckpt.list_generations(
                        opts.ckpt_path
                    ):
                        # restore from the last good on-disk snapshot
                        # when one exists (device state may be gone
                        # after a real device error); otherwise retry
                        # from the in-memory good carry
                        try:
                            _, _, good = ckpt.load_latest_generation(
                                opts.ckpt_path, template
                            )
                        except FileNotFoundError:
                            pass

            if spill_broken is not None:
                # ladder rung 4 via the spill-write-failure edge:
                # checkpoint what we have (the last-good pair is still
                # consistent - the failed flush never touched the
                # store) and hand back a resumable exit
                rollback_store()
                _emit(opts, "degrade", rung="halt", resource="spill",
                      action="checkpoint+exit", reason=str(spill_broken))
                exhausted = interrupted = True
                exhaust_resource = "spill"
                carry = good
                break

            if oom is not None:
                rollback_store()
                if drop_kept_engines(keep=seg_fn):
                    # other checks' kept engines held device memory:
                    # they go first and the segment runs again, before
                    # any rung is taken (nothing left to drop next time)
                    _emit(opts, "degrade", rung="oom", resource="segment",
                          action="drop-kept-engines", reason=str(oom))
                    continue
                can = _can_shrink(adapter, opts.min_chunk)
                _emit(opts, "degrade", rung="oom", resource="segment",
                      action="shrink" if can else "halt",
                      reason=str(oom))
                if can:
                    old_chunk = adapter.chunk
                    good = adapter.reseat_chunk(good, params)
                    shrinks += 1
                    template, seg_fn = rebuild(params)
                    carry = good
                    _emit(opts, "degrade", rung="shrink",
                          resource="chunk",
                          action=f"{old_chunk}->{adapter.chunk}",
                          reason=str(oom))
                    continue
                exhausted = interrupted = True
                exhaust_resource = "segment"
                carry = good
                break

            v = adapter.viol(carry2)
            if v in GROWABLE:
                resource = GROWABLE[v]
                if not opts.auto_grow:
                    carry = carry2  # explicit opt-out: report the halt
                    break
                rollback_store()
                denial = None
                spill_first = (
                    resource == "fp_capacity" and opts.spill == "on"
                    and spill_rt is None and _supports_spill(adapter)
                )
                # ---- rung 1: probed regrow ---------------------------
                if not spill_first:
                    if regrows >= opts.max_regrow:
                        denial = f"max-regrow ({opts.max_regrow}) reached"
                    else:
                        new_params = grown(params, resource)
                        denial = _probe_grow(
                            resource, new_params[resource], faults
                        )
                        if denial is not None and drop_kept_engines(
                                keep=seg_fn):
                            denial = _probe_grow(
                                resource, new_params[resource], faults
                            )
                    if denial is None:
                        t = time.time()
                        # route_factor is an engine-geometry-only knob
                        # for the carry's containers, but a PIPELINED
                        # sharded carry sizes its pending-verdict
                        # buffers by the route bucket width - migrate()
                        # drains + re-seats them (pass-through otherwise)
                        migrated = adapter.migrate(good, params,
                                                   new_params)
                        template, seg_fn = rebuild(new_params)
                        regrow_s += time.time() - t
                        regrows += 1
                        _emit(opts, "regrow", resource=resource,
                              old=params[resource],
                              new=new_params[resource],
                              violation=VIOLATION_NAMES.get(v, str(v)),
                              regrows=regrows,
                              seconds=round(time.time() - t, 3))
                        params = new_params
                        good = migrated
                        carry = migrated
                        continue  # replay inside the new geometry
                    _emit(opts, "degrade", rung="regrow",
                          resource=resource, action="denied",
                          reason=denial)
                # ---- rung 2: host spill tier (fpset only) ------------
                if (resource == "fp_capacity" and opts.spill != "off"
                        and spill_rt is None
                        and _supports_spill(adapter)):
                    from ..engine.spill import SpillStore

                    spill_rt = make_spill_runtime(
                        params, SpillStore(opts.spill_capacity)
                    )
                    template = spill_rt.init_fn()
                    seg_fn = spill_rt.segment_fn(opts.ckpt_every)
                    good = spill_rt.adopt(good)
                    carry = good
                    good_store = spill_rt.store.snapshot()
                    reason = denial or "spill-first policy (-spill)"
                    _emit(opts, "degrade", rung="spill",
                          resource=resource, action="activate",
                          reason=reason)
                    prog = adapter.progress(good)
                    _emit(opts, "spill", phase="activate",
                          resident=prog[2], spilled=0,
                          capacity=spill_rt.store.capacity,
                          hits=0, probes=0)
                    continue  # replay through the two-tier dedup
                # ---- rung 3: chunk shrink, re-probe on recurrence ----
                if _can_shrink(adapter, opts.min_chunk):
                    old_chunk = adapter.chunk
                    good = adapter.reseat_chunk(good, params)
                    shrinks += 1
                    template, seg_fn = rebuild(params)
                    carry = good
                    _emit(opts, "degrade", rung="shrink",
                          resource="chunk",
                          action=f"{old_chunk}->{adapter.chunk}",
                          reason=denial or "capacity ladder")
                    continue  # replay; the regrow probe retries next halt
                # ---- rung 4: checkpoint + exit 75 --------------------
                _emit(opts, "degrade", rung="halt", resource=resource,
                      action="checkpoint+exit",
                      reason=denial or "no ladder rung applicable")
                exhausted = interrupted = True
                exhaust_resource = resource
                carry = good
                break

            if v == VIOL_SLOT_OVERFLOW:
                path = None
                try:
                    path = save(good, "slot-overflow",
                                store_snap=good_store)
                except OSError:
                    pass
                # (a carry of another engine's shape names no state)
                state = getattr(carry2, "viol_state", None)
                raise SlotOverflowError(
                    path, state=None if state is None else np.asarray(state))

            carry = carry2
            good = carry2
            if spill_rt is not None:
                good_store = spill_rt.store.snapshot()
            segments += 1
            if opts.ckpt_path:
                pending_save = (good, good_store)
            with span("loop.readback") as readback:
                # the device reads the fence already paid for, and their
                # decoding: progress counters, the counter ring's new
                # per-level rows, the coverage plane's totals
                with span("loop.readback.get"):
                    progress = payload = None
                    rows = ()
                    if (adapter.viol(carry) == OK
                            and not adapter.done(carry)):
                        progress = adapter.progress(carry)
                    if obs_read is not None:
                        rows, obs_seen = obs_read(carry, obs_seen, params)
                    if cov_sites is not None:
                        from ..obs.coverage import coverage_delta_event

                        totals = adapter.cov_totals(carry)
                        payload = coverage_delta_event(cov_sites, totals,
                                                       cov_seen)
                # what they say, written: journal lines and fsyncs
                with span("loop.readback.emit"):
                    if progress is not None:
                        d, g, di, q = progress
                        _emit(opts, "progress", depth=d, generated=g,
                              distinct=di, queue=q)
                    for row in rows:
                        _emit(opts, "level", **row)
                    if rows:
                        cov_level = max(cov_level, rows[-1]["level"])
                    if cov_sites is not None:
                        # per-site DELTAS journal as one `coverage`
                        # event, and a run that stops visiting NEW sites
                        # for N levels journals the saturation signal
                        # once
                        if payload is not None:
                            _emit(opts, "coverage", **payload)
                            cov_seen = totals
                            if payload["visited"] > cov_visited:
                                cov_visited = payload["visited"]
                                cov_last_new_level = cov_level
                        if (not cov_saturated and cov_visited
                                and cov_level - cov_last_new_level
                                >= opts.coverage_sat_levels):
                            cov_saturated = True
                            _emit(opts, "coverage", visited=cov_visited,
                                  sites=len(cov_sites), delta={},
                                  saturated=True, level=cov_level)
                    # timeline telemetry, last of the fence's events: the
                    # host-observed dispatch -> fence interval of the
                    # segment just completed and the readback wall behind
                    # it (the trace exporter's slices, /metrics' and
                    # tlcstat's phase walls come from these)
                    _emit(opts, "segment", index=segments - 1,
                          t_dispatch=t_dispatch, t_fence=t_fence,
                          wall_s=round(t_fence - t_dispatch, 6),
                          readback_s=round(readback.seconds, 6))

        # the final segment's snapshot has no next segment to hide
        # behind: write it at the fence
        if interrupted:
            pending_save = None  # superseded by the final generation
            path = None
            try:
                path = save(good,
                            "capacity-exhausted" if exhausted
                            else "final",
                            store_snap=good_store)
            except OSError as e:
                _emit(opts, "ckpt_write_failed", error=str(e))
            # the structured record carries the counters and wall time
            # even when NO checkpoint path is configured (path None =
            # progress lost): the journal still ends with an
            # accountable event, never a silent death
            d, g, di, q = adapter.progress(good)
            if exhausted:
                _emit(opts, "exhausted", resource=exhaust_resource,
                      path=path, generated=g, distinct=di, queue=q,
                      wall_s=round(time.time() - t0, 6))
            else:
                _emit(opts, "interrupted",
                      signum=int(sig.hit) if sig.hit else None,
                      path=path, generated=g, distinct=di, queue=q,
                      wall_s=round(time.time() - t0, 6),
                      drained=drained())
        else:
            flush_save()

    wall = time.time() - t0
    with span("check.result") as read:
        result = adapter.result(carry, wall, segments, params)
        # the commit's counts, on the record every entry point writes
        # (check_with_checkpoints, which has no journal, too)
        read.attrs.update(commit_counters(result))
        # every supervised run ends with exactly one structured final
        # event: verdict + counters + wall, whatever the exit path
        verdict = ("exhausted" if exhausted
                   else "interrupted" if interrupted
                   else "violation" if result.violation != OK else "ok")
        if (opts.capture_fps and verdict == "ok" and spill_rt is None
                and getattr(adapter, "CAPTURES_FPS", False)
                and getattr(carry, "fps", None) is not None):
            # the artifact cache's reachable-set source: one host copy
            # of the final table, only on a clean non-spilled
            # single-device verdict (a spilled run's table is partial)
            result = result._replace(
                fp_table=np.asarray(jax.device_get(carry.fps.table))
            )
    if opts.finish is not None and verdict == "ok":
        result, later = opts.finish(result)
        verdict = later or verdict
    # the host spans of this check that have closed by now (build, loop
    # and their children; `check` itself is still open), once
    _emit(opts, "spans", **spans.journal_event())
    _emit(opts, "final", verdict=verdict, generated=result.generated,
          distinct=result.distinct, depth=result.depth,
          queue=result.queue_left, wall_s=round(wall, 6),
          interrupted=interrupted, **mesh_counters(result))
    spill_hits = 0
    if spill_rt is not None and getattr(carry, "spill_hits",
                                        None) is not None:
        # scalar on the single-device carry, [D] partials on the
        # sharded carry - sum covers both
        spill_hits = int(np.asarray(carry.spill_hits).sum())
    return SupervisedResult(
        result=result,
        params=params,
        regrows=regrows,
        retries=retries_used,
        interrupted=interrupted,
        segments=segments,
        ckpt_writes=ckpt_writes,
        ckpt_write_s=round(ckpt_write_s, 6),
        regrow_s=round(regrow_s, 6),
        exhausted=exhausted,
        spilled=spill_rt.store.count if spill_rt is not None else 0,
        spill_flushes=spill_rt.flushes if spill_rt is not None else 0,
        spill_hits=spill_hits,
        shrinks=shrinks,
    )


def check_supervised(
    cfg,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    backend=None,
    meta_config: dict = None,
    check_deadlock: bool = True,
    pipeline: bool = False,
    obs_slots: int = 0,
    coverage: bool = False,
    deferred: bool = None,
    opts: SupervisorOptions = None,
) -> SupervisedResult:
    """Supervised single-device exhaustive check (the check_with_
    checkpoints signature, plus self-healing).  `backend`/`meta_config`
    run any SpecBackend (struct-compiled specs included) through the
    same supervision loop; cfg is then ignored.  `coverage` (KubeAPI
    path) compiles the device coverage plane into the engine; a
    backend that already carries a plane turns it on regardless."""
    adapter = SingleDeviceAdapter(
        cfg, chunk=chunk, fp_index=fp_index, seed=seed,
        fp_highwater=fp_highwater, backend=backend,
        meta_config=meta_config, check_deadlock=check_deadlock,
        pipeline=pipeline, obs_slots=obs_slots, coverage=coverage,
        deferred=deferred,
    )
    return supervise(
        adapter,
        {"queue_capacity": queue_capacity, "fp_capacity": fp_capacity},
        opts,
    )


def check_sharded_supervised(
    cfg,
    mesh,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    route_factor: float = 2.0,
    backend=None,
    meta_config: dict = None,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    pipeline: bool = False,
    obs_slots: int = 0,
    coverage: bool = False,
    deferred: bool = None,
    opts: SupervisorOptions = None,
) -> SupervisedResult:
    """Supervised mesh-sharded exhaustive check (capacities PER DEVICE)."""
    adapter = ShardedAdapter(
        cfg, mesh, chunk=chunk, backend=backend, meta_config=meta_config,
        fp_highwater=fp_highwater, pipeline=pipeline,
        obs_slots=obs_slots, coverage=coverage, deferred=deferred,
    )
    return supervise(
        adapter,
        {
            "queue_capacity": queue_capacity,
            "fp_capacity": fp_capacity,
            "route_factor": route_factor,
        },
        opts,
    )
