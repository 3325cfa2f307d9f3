"""Fully device-resident BFS model-checking engine.

The TLC BFS core replacement (tlc2.tool.Worker + DiskStateQueue +
OffHeapDiskFPSet, /root/reference/KubeAPI.toolbox/Model_1/MC.out:5): one
``lax.while_loop`` whose body pops a fixed-size chunk from the frontier,
expands it through the vmapped next-state kernel, evaluates invariants,
fingerprints + dedups against the device hash table, and appends the new
states - no host round-trips until the state space is exhausted or a
violation is found.

Data layout and step as the chip runs them (PERF.md PR 26, Step 0: a
row gather or a row scatter-add costs its rows, in place; a conditional
that takes the carry copies it):

* The frontier is a ping-pong pair of level buffers of *packed* state
  words ([2, qcap + 2*chunk, W] uint32): a pop is a contiguous dynamic
  slice, an append one row gather of the new states and a contiguous
  dynamic-update-slice.  States are unpacked to field vectors only at
  the kernel boundary (codec.unpack); fingerprints ride the MXU.
* The commit dedups the chunk*L candidates (fpset.fpset_insert_sorted:
  two stable sorts, the second over the valid lanes alone), probes only
  the unique ones and writes the table by one scatter-add of whole
  bucket rows; enqueue and per-new-state statistics run over compacted
  probe-width segments, the enqueue's order sorted at the width of the
  representatives (fpset.enqueue_order: a `lax.switch` that holds sorts
  only).
* Every loop is a `while` (`run_steps`; the two pop widths at chunk >=
  2^14 are two inner loops): no conditional holds the carry.  The stages
  are device scopes (`jaxtlc.expand`, `.dedup`, `.fpset`, `.enqueue`, `.level`).
* Per-action generated counters are factorized through the dispatch
  structure (all lanes of a client share that client's pc label; server
  lanes are always APIStart) instead of scatter-adds over all candidates.

Level-synchronous by construction: a chunk never crosses a BFS level
boundary, so reported depth is the exact BFS level count, matching TLC's
"depth of the complete state graph search" (MC.out:1101), and in-batch
fingerprint arbitration never has to choose between states of different
levels.

Violation handling: the fused loop carries a violation code + the
offending encoded state; on violation the CLI re-runs in the host driver
(engine.hostdriver) which keeps parent pointers and reconstructs the
counterexample trace (TLC trace-explorer analog, SURVEY.md §2.3 E11).
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..spec.labels import LABELS
from .fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED, fp64_words_mxu
from .fpset import (
    COMMIT_STAT_COLS,
    LADDER_RUNGS,
    commit_stat_fields,
    commit_widths,
    count_block,
    enqueue_order,
    fpset_insert_sorted,
    fpset_new,
)

# violation codes
OK = 0
VIOL_TYPEOK = 1
VIOL_ONLYONEVERSION = 2
VIOL_ASSERT = 3
VIOL_DEADLOCK = 4
VIOL_SLOT_OVERFLOW = 5
VIOL_FPSET_FULL = 6
VIOL_QUEUE_FULL = 7
VIOL_ROUTE_OVERFLOW = 8

VIOLATION_NAMES = {
    OK: "none",
    VIOL_TYPEOK: "Invariant TypeOK is violated",
    VIOL_ONLYONEVERSION: "Invariant OnlyOneVersion is violated",
    VIOL_ASSERT: "Assertion failure (PlusCal assert)",
    VIOL_DEADLOCK: "Deadlock reached",
    VIOL_SLOT_OVERFLOW: "Codec slot overflow (raise ModelConfig bounds)",
    VIOL_FPSET_FULL: ("Fingerprint table full (auto-grow doubles it; "
                      "when device memory is exhausted the host spill "
                      "tier takes over - raise fp_capacity only to "
                      "avoid the regrow recompiles)"),
    VIOL_QUEUE_FULL: "Frontier queue full (raise queue_capacity)",
    VIOL_ROUTE_OVERFLOW: "Routing bucket overflow (raise route_factor)",
}


class EngineCarry(NamedTuple):
    fps: "FPSet"  # noqa: F821 - fpset.FPSet
    queue: jnp.ndarray  # [2, qcap + 2*chunk, W] uint32 packed level buffers
    parity: jnp.ndarray  # int32: which buffer holds the CURRENT level
    qhead: jnp.ndarray  # int32: pop position within the current level
    level_n: jnp.ndarray  # int32: states in the current level
    next_n: jnp.ndarray  # int32: states appended to the next level so far
    level: jnp.ndarray  # int32: BFS level of states being popped (init = 1)
    depth: jnp.ndarray  # int32: deepest nonempty level
    generated: jnp.ndarray  # uint32
    distinct: jnp.ndarray  # uint32
    act_gen: jnp.ndarray  # [n_labels + 1] uint32
    act_dist: jnp.ndarray  # [n_labels + 1] uint32
    outdeg_hist: jnp.ndarray  # [L + 2] uint32: #popped states with d new
    # children (TLC's outdegree, MC.out:1104); last row = scatter dump
    viol: jnp.ndarray  # int32 code
    viol_state: jnp.ndarray  # [F] int32
    viol_action: jnp.ndarray  # int32
    # --- pipelined-engine staged block (None on unpipelined engines) ---
    # The expand-stage output (backend.ExpandOut) of the in-flight pop:
    # popped and expanded but not yet committed - the next loop body
    # commits it while expanding the following block, so XLA can overlap
    # block k's kernel/fingerprint work with block k-1's sort/probe/
    # enqueue row ops (PERF.md round 7).  None leaves vanish from the
    # pytree, so unpipelined carries keep their exact pre-pipeline
    # checkpoint layout.
    st_packed: jnp.ndarray = None  # [chunk*L, W] uint32
    st_lo: jnp.ndarray = None  # [chunk*L] uint32
    st_hi: jnp.ndarray = None  # [chunk*L] uint32
    st_valid: jnp.ndarray = None  # [chunk*L] bool
    st_action: jnp.ndarray = None  # [chunk*L] int32
    st_gen: jnp.ndarray = None  # [n_labels] uint32
    st_n: jnp.ndarray = None  # int32: popped rows staged (0 = empty)
    st_viol: jnp.ndarray = None  # int32 expand-stage violation code
    st_viol_state: jnp.ndarray = None  # [F] int32
    st_viol_action: jnp.ndarray = None  # int32
    # --- observability counter ring (None when obs is off) ------------
    # One row per completed BFS level (obs.counters layout), written
    # with a single contiguous row store per body (non-flip bodies hit
    # the dump row), read back at segment fences.  None leaves vanish
    # from the pytree, so obs-off carries keep the pre-obs checkpoint
    # layout bit-for-bit.
    obs_ring: jnp.ndarray = None  # [obs_slots + 1, cols] uint32
    obs_head: jnp.ndarray = None  # int32 level rows ever written
    obs_bodies: jnp.ndarray = None  # uint32 loop bodies executed
    obs_expanded: jnp.ndarray = None  # uint32 states popped so far
    # --- host spill tier (None when spill mode is off) ----------------
    # Cumulative count of candidates the HOST fingerprint store vetoed
    # (already-seen fingerprints whose device-table entry was flushed to
    # host RAM - engine.spill).  Present only on spill-mode carries, so
    # every other engine keeps its exact checkpoint layout.
    spill_hits: jnp.ndarray = None  # uint32
    # --- runtime certificate (None without a backend cert_check) -------
    # Sticky bool: some generated state violated a bound the certified
    # abstract interpretation claimed (analysis.absint).  Latched every
    # body, mirrored into the obs ring's COL_CERT, escalated to an
    # error verdict by the check drivers - never silent.
    cert_viol: jnp.ndarray = None  # bool
    st_cert: jnp.ndarray = None  # staged block's cert bit (pipelined)
    # staged block's raw pre-pack fields ([chunk*L, F] int32): present
    # only on deferred-evaluation pipelined carries (ISSUE 15), where
    # the commit gathers the fresh-insert claimants from it.  None
    # leaves vanish, so immediate-mode carries keep their layout.
    st_flat: jnp.ndarray = None
    # --- device coverage plane (None without a backend coverage plane)
    # Cumulative [n_sites] uint32 per-site visit counters (obs.coverage,
    # ISSUE 11): incremented by every commit from the expand stage's
    # block increments, read back at segment fences, migrated verbatim
    # on regrow, checkpointed/resumed, psum-merged across shards.  Pure
    # telemetry, exactly like the obs ring above.
    cov_counts: jnp.ndarray = None  # [n_sites] uint32
    st_cov: jnp.ndarray = None  # staged block's increments (pipelined)
    # --- state-space reduction (None without backend.reduce, ISSUE 18)
    # Sticky bool: the orbit-certification sample of some block failed
    # to re-canonicalize (engine.reduce.ReducePlan.orbit_check) - the
    # symmetry plan is not acting as a permutation group, so the orbit
    # dedup cannot be trusted.  Latched every body, mirrored into the
    # obs ring's COL_SYM, escalated to an error verdict by the check
    # drivers - the COL_CERT pattern exactly.
    sym_viol: jnp.ndarray = None  # bool
    st_sym: jnp.ndarray = None  # staged block's orbit-check bit
    # Cumulative [4] uint32 beside it (ExpandOut.sym_stat summed): rows
    # canonicalized, rows moved, orbit checks made, orbit checks tripped
    sym_stat: jnp.ndarray = None
    st_sym_stat: jnp.ndarray = None  # staged block's four
    # Cumulative uint32: candidate transitions the POR ample-set mask
    # pruned at expand time (journalled as the `reduce` event's counter
    # delta; state counts legitimately shrink under POR)
    por_pruned: jnp.ndarray = None  # uint32
    st_pruned: jnp.ndarray = None  # staged block's pruned count
    # --- state constraint (None without backend.constraint, ISSUE 39)
    # Cumulative [2] uint32 (ExpandOut.con_stat summed): valid
    # successors the cfg's CONSTRAINT judged, and those it rejected -
    # counted as generated, never kept
    con_stat: jnp.ndarray = None
    st_con_stat: jnp.ndarray = None  # staged block's two
    # --- action properties (None without backend.action_prop, ISSUE 48)
    # Cumulative [2] uint32 (ExpandOut.ap_stat summed): edges judged,
    # edges on which a property's subscript changed; and the SOURCE row
    # [F] int32 of the edge that failed one (`viol_state` holds its
    # successor), latched with the violation
    ap_stat: jnp.ndarray = None
    st_ap_stat: jnp.ndarray = None  # staged block's two
    ap_src: jnp.ndarray = None
    st_ap_src: jnp.ndarray = None  # staged block's failing source
    # --- the commit's own counts (ISSUE 50; every engine of
    # make_backend_engine) -------------------------------------------
    # Cumulative [COMMIT_LEAF_COLS] uint32: fpset_insert_sorted's block
    # of every chunk-wide body, then the ENGINE_COUNTS and the
    # histogram of the rung the enqueue's sort ran at.  Counts only:
    # the widths they are read against are static (commit_geometry).
    # Where the loop also steps a small body, that body adds nothing:
    # its widths are another's.  Telemetry: no control flow reads it.
    # The commit makes the block itself, so the pipelined pair stages
    # nothing
    commit_stat: jnp.ndarray = None


class CheckResult(NamedTuple):
    generated: int
    distinct: int
    depth: int
    queue_left: int
    violation: int
    violation_name: str
    violation_state: np.ndarray
    violation_action: int
    action_generated: dict
    action_distinct: dict
    wall_s: float
    iterations: int
    # (avg, min, max, p95) of TLC's outdegree = distinct new states per
    # expanded state (matches MC.out:1104); None when not tracked (sharded)
    outdegree: tuple = None
    # TLC's "based on the actual fingerprints" collision estimate
    # (MC.out:42); None when the engine variant doesn't compute it
    actual_fp_collision: float = None
    # final fingerprint-table load: distinct / fp_capacity (summed over
    # shards for the mesh engine); None when the driver didn't compute it.
    # Reported on the 2193 stats line so users can size fp_capacity (and
    # see how close a run came to the fp_highwater regrow trigger)
    fp_occupancy: float = None
    # mesh engine only: distinct states held by each device's shard of
    # the fingerprint table (sums to `distinct`; a zero means a device
    # that owned no part of the space); None on single-device engines
    shard_distinct: tuple = None
    # device per-site coverage totals ({site key: visits}, obs.coverage);
    # None when the engine carried no coverage plane
    site_coverage: dict = None
    # runtime-certificate verdict of a narrowed (certified-bound) run:
    # None = no certificate check carried; False = every generated
    # state satisfied the certified bounds; True = a claimed bound was
    # VIOLATED - the check drivers escalate this to an error verdict
    cert_violated: bool = None
    # final fingerprint-table words ([n_buckets, 2*BUCKET] uint32 on
    # host), captured ONLY when the artifact cache wants to derive the
    # reachable-set tier from a clean single-device run
    # (struct.artifacts.states_from_table); None everywhere else so
    # results stay light
    fp_table: object = None
    # orbit-certification verdict of a symmetry-reduced run: None = no
    # orbit check carried; False = every sampled canonical row
    # re-canonicalized consistently; True = the symmetry plan LIED -
    # the check drivers escalate this to an error verdict (exit 1)
    sym_violated: bool = None
    # candidate transitions pruned by POR ample sets (None when POR is
    # off) - the journalled counter delta of the `reduce` event
    por_pruned: int = None
    # mesh engine only (telemetry; None elsewhere): successors each
    # device generated (sums to `generated`; device 0's holds the
    # initial states); the fullest per-destination bucket any device
    # packed in any step beside the bucket's width - at the width the
    # run halts with VIOL_ROUTE_OVERFLOW; and the bytes each device
    # handed to the two all_to_alls over the check, from the static
    # shapes and the step count (engine.sharded.route_geometry); and
    # the segments of `commit_rows` compacted candidates each device's
    # owner-side insert ran over the check (a body runs as many as
    # what it received needs: none for nothing, one in the common case);
    # and the blocks of `commit_rows` rows each device's enqueue wrote
    # onto its queue (as many a body as its NEW rows need)
    shard_generated: tuple = None
    route_max_fill: int = None
    route_bucket: int = None
    route_bytes: int = None
    commit_segments: tuple = None
    commit_rows: int = None
    enqueue_segments: tuple = None
    # struct-compiled step only (telemetry; None elsewhere): the static
    # lane fan of the compiled step (`step_lanes`) and the slots a
    # state keeps of it when it leaves the step (`step_slots`, the
    # engine's candidate lanes a state; equal where the step is not
    # compacted); packed 32-bit words a state; states popped and
    # expanded (distinct less what was left on the queue: every state
    # of an exhaustive run, once); lanes that fired (the per-action
    # generated totals summed: generated less the initial states); and
    # whether a trap of the compiled step (range, declared-universe or
    # compaction overflow: VIOL_SLOT_OVERFLOW) halted the run; and the
    # forms in which the compiled functions read a field of an
    # enum-coded value (struct.compile.table_form; distinct (table,
    # value) pairs of the step, the invariants, the constraint and the
    # liveness predicates): a literal, arithmetic on the code, or a
    # gather from a host table - 0 of the last while every table a
    # model looks up is a digit of its code or a constant
    step_lanes: int = None
    step_slots: int = None
    state_words: int = None
    # the bits of a packed state (`state_bits / (32 state_words)`: how
    # full the row is that the dedup sorts, fingerprints and enqueues);
    # the static slots of all its sequences, where their capacities
    # came from ("declared": an invariant or the CONSTRAINT bounds
    # every one; "guess": some capacity is the first guess or a
    # rung's; None: no growing sequence) and the capacity rungs taken
    # (struct.cache.widen_seq_caps: 0 unless an Append met a full
    # sequence and the check started again)
    state_bits: int = None
    seq_slots: int = None
    seq_cap_from: str = None
    seq_widen: int = None
    states_expanded: int = None
    lane_fires: int = None
    struct_traps: int = None
    lookup_const: int = None
    lookup_arith: int = None
    lookup_gather: int = None
    # symmetry-reduced single-device runs only (telemetry; None
    # elsewhere): the order of the group the tournament minimises over
    # (identity included) and the constant sets it permutes; valid
    # candidate rows canonicalized and those whose representative
    # differs from the candidate (`canon_moved / canon_rows`: the share
    # the tournament rewrote, a constant of the model, and 0 the day the
    # reduction stops engaging); bodies whose orbit certificate had a
    # row to sample, and those where it tripped (`sym_violated`)
    sym_perms: int = None
    sym_sets: int = None
    canon_rows: int = None
    canon_moved: int = None
    sym_cert_checks: int = None
    sym_cert_trips: int = None
    # constrained runs only (a cfg's CONSTRAINT; None elsewhere): valid
    # successors the constraint judged (generated less the initial
    # states) and those it rejected, which count as generated and are
    # never fingerprinted, enqueued or checked (`constraint_discarded /
    # constraint_rows`: a constant of the model, and 0 the day the
    # constraint stops engaging); and the cfg's names for it
    constraint_rows: int = None
    constraint_discarded: int = None
    constraint_names: tuple = None
    # a model with an action property only (a cfg PROPERTY `I /\\
    # [][A]_v`, ISSUE 48; None elsewhere): the cfg's names for them;
    # the initial states I was judged on; the edges `[A]_v` was judged
    # on (every kept successor the search generated: generated less the
    # initial states on an unconstrained model) and those on which v
    # changed, where A itself decides (`action_prop_moved /
    # action_prop_edges`: a constant of the model); the source columns
    # the predicate reads, broadcast to candidate width; and, where a
    # property failed on an edge, that edge's SOURCE row
    # (`violation_state` is its successor)
    action_prop_names: tuple = None
    action_prop_init_states: int = None
    action_prop_edges: int = None
    action_prop_moved: int = None
    action_prop_src_cols: int = None
    action_prop_source: object = None
    # the cfg's PROPERTY lines the run did NOT judge, by name (a shape
    # the struct route does not check: `[]<>P`, `[]P ~> Q`, a
    # specification with a fairness conjunct); None where every line
    # was judged.  A verdict `ok` with a name here says nothing of it
    properties_skipped: tuple = None
    # a struct check with a PROPERTY only (live.check, ISSUE 41; None
    # elsewhere): the behaviour graph the liveness route analysed on
    # the device - its states and successor rows (what the safety run
    # counted: distinct; generated less the initial states), the rows
    # that change the state, the bytes of the edge store - and, summed
    # over the cfg's properties, the rows of the fairness constraints'
    # actions, the states of H = ~Q and of P, the P-states the fair
    # fixpoint kept (0 iff every property holds), its outer passes and
    # sweeps, the rows at which it read a set at a row's destination
    # (ISSUE 42: (outer + sweeps) x the store's rows where every sweep
    # reads every row), and the bytes of states or rows read to the
    # host (0 unless a property is violated: the lasso)
    live_states: int = None
    live_edges: int = None
    live_changed_edges: int = None
    live_fair_edges: int = None
    live_h_states: int = None
    live_p_states: int = None
    live_survivors: int = None
    live_outer: int = None
    live_sweeps: int = None
    live_swept_rows: int = None
    live_edge_bytes: int = None
    live_host_bytes: int = None
    # the commit's own counts over the check (ISSUE 50; telemetry; every
    # engine of make_backend_engine and the mesh; None elsewhere), read
    # from the carry's `commit_stat`: loop bodies (the chunk-wide ones;
    # a small body counts nothing); the candidate lanes the insert mask
    # let through and their distinct representatives; how often the
    # compaction's sort and the enqueue's ran at each rung of its
    # ladder; the probe's segments, the representatives whose round-0
    # claim wrote a slot, the blocks that write scattered, the
    # claimants it left to the straggler walk (with `commit_claimed`
    # what sizes the walk's block, ROADMAP A12) and the walk's rounds
    # (a compaction of the pending set, or one bucket step of its
    # slice); the rows found new and enqueued; the trips of the
    # deferred checker, `commit_probe_width` rows each (0: the checker
    # is immediate).  Beside them, where the caller names the geometry
    # (commit_geometry; on the mesh `route`), the static widths a ratio
    # needs: the candidate lanes, the rows of a probe segment and of
    # one block of the claim's write, the two ladders (the last rung
    # the whole array).  On the mesh every count is summed over the
    # devices (`commit_bodies` is devices x the loop's bodies), a
    # segment of the owner-side insert is one call of the seam
    # (`commit_probe_segments` sums `commit_segments`) and the enqueue
    # has no ladder
    commit_bodies: int = None
    commit_valid: int = None
    commit_reps: int = None
    commit_compact_rung: tuple = None
    commit_enqueue_rung: tuple = None
    commit_probe_segments: int = None
    commit_claimed: int = None
    commit_claim_blocks: int = None
    commit_stragglers: int = None
    commit_walk_rounds: int = None
    commit_new: int = None
    commit_checker_trips: int = None
    commit_width: int = None
    commit_compact_ladder: tuple = None
    commit_enqueue_ladder: tuple = None
    commit_probe_width: int = None
    commit_claim_block: int = None


MESH_COUNTERS = ("shard_distinct", "shard_generated", "route_max_fill",
                 "route_bucket", "route_bytes", "commit_segments",
                 "commit_rows", "enqueue_segments")
STEP_COUNTERS = ("step_lanes", "step_slots", "state_words", "state_bits",
                 "seq_slots", "seq_cap_from", "seq_widen",
                 "states_expanded", "lane_fires", "struct_traps",
                 "lookup_const", "lookup_arith", "lookup_gather",
                 "sym_perms", "sym_sets", "canon_rows", "canon_moved",
                 "sym_cert_checks", "sym_cert_trips", "constraint_rows",
                 "constraint_discarded", "constraint_names",
                 "action_prop_names", "action_prop_init_states",
                 "action_prop_edges", "action_prop_moved",
                 "action_prop_src_cols", "properties_skipped",
                 "live_states", "live_edges", "live_changed_edges",
                 "live_fair_edges", "live_h_states", "live_p_states",
                 "live_survivors", "live_outer", "live_sweeps",
                 "live_swept_rows", "live_edge_bytes", "live_host_bytes")
COMMIT_COUNTERS = tuple(
    f for f in CheckResult._fields if f.startswith("commit_")
    and f not in MESH_COUNTERS)


def _counters(result: CheckResult, names: tuple) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v
            for k in names
            for v in (getattr(result, k),) if v is not None}


def mesh_counters(result: CheckResult) -> dict:
    """The mesh engine's, the struct-compiled step's and the commit's
    counters of a result, as the extra fields of the journal's `final`
    event."""
    return _counters(
        result, MESH_COUNTERS + STEP_COUNTERS + COMMIT_COUNTERS)


def commit_counters(result: CheckResult) -> dict:
    """The commit's counters of a result alone: the attributes of the
    `check.result` span, the one record every entry point writes."""
    return _counters(result, COMMIT_COUNTERS)


# what an engine's loop adds to fpset's block a body: 1, the rows it
# enqueued, the deferred checker's trips (0: immediate)
ENGINE_COUNTS = ("bodies", "new", "checker_trips")
COMMIT_LEAF_COLS = COMMIT_STAT_COLS + len(ENGINE_COUNTS) + LADDER_RUNGS


def commit_geometry(n_lanes: int, chunk: int) -> dict:
    """The static widths the counts of an engine of make_backend_engine
    are read against (fpset.commit_widths), as result_from_carry takes
    them: of `n_lanes` successor lanes a state (a backend's `n_lanes`;
    the hand kernel's spec.kernel.lane_layout) popped `chunk` at a
    time."""
    return commit_widths(chunk * n_lanes, probe_width(chunk, n_lanes))


def commit_result_fields(stat, widths: dict = None,
                         tail: tuple = ENGINE_COUNTS,
                         hist: str = "enqueue_rung") -> dict:
    """CheckResult's `commit_*` fields from a host `commit_stat` leaf:
    fpset's block, then the engine's `tail` counts and what follows
    them as `hist` (None: nothing); with `widths`, the statics beside
    them and each histogram cut to its ladder's rungs."""
    stat = np.asarray(stat)
    out = commit_stat_fields(stat[:COMMIT_STAT_COLS])
    out.update(commit_stat_fields(stat[COMMIT_STAT_COLS:], tail, hist))
    if widths is not None:
        out.update(widths)
        for rung, ladder in (("compact_rung", "compact_ladder"),
                             ("enqueue_rung", "enqueue_ladder")):
            if rung in out:
                out[rung] = out[rung][:len(out[ladder])]
            else:  # the mesh's enqueue has no ladder
                del out[ladder]
    return {f"commit_{k}": v for k, v in out.items()}


def with_step_counters(result: CheckResult, backend) -> CheckResult:
    """`result` with the struct-compiled step's counters, where
    `backend` is one (struct.backend marks its codec with the static
    lane fan); any other backend's result comes back as it is."""
    static = getattr(getattr(backend, "cdc", None), "static_lanes", None)
    if static is None:
        return result
    if getattr(backend, "constraint", None) is not None:
        result = result._replace(
            constraint_names=tuple(backend.constraint_names))
    ap = getattr(backend, "action_prop", None)
    if ap is not None:
        result = result._replace(
            action_prop_names=tuple(ap.names),
            action_prop_src_cols=len(ap.src_cols))
    plan = getattr(backend.reduce, "plan", None)
    if plan is not None:
        result = result._replace(sym_perms=plan.n_perms,
                                 sym_sets=len(plan.sym_sets))
    return result._replace(
        step_lanes=static,
        step_slots=backend.n_lanes,
        state_words=backend.cdc.n_words,
        state_bits=backend.cdc.nbits,
        seq_slots=getattr(backend.cdc, "seq_slots", 0),
        seq_cap_from=getattr(backend.cdc, "seq_cap_from", None),
        seq_widen=getattr(backend.cdc, "seq_widen", 0),
        states_expanded=result.distinct - result.queue_left,
        lane_fires=sum(result.action_generated.values()),
        struct_traps=int(result.violation == VIOL_SLOT_OVERFLOW),
        **lookup_counters(backend),
    )


def lookup_counters(backend) -> dict:
    """`lookup_const` / `lookup_arith` / `lookup_gather` of a struct
    backend as they stand: read after the functions that count were
    traced (the engine's build; the liveness route's predicates)."""
    return {f"lookup_{form}": n
            for form, n in backend.cdc.lookup_counts().items()}


def carry_done(carry: EngineCarry) -> bool:
    """Host-side termination check (used by the checkpointed driver):
    one batched read of the five scalars, not five blocking pulls - it
    sits between two segments, where the device waits for the host."""
    st = carry.st_n if carry.st_n is not None else 0
    viol, level_n, qhead, next_n, st_n = jax.device_get(
        (carry.viol, carry.level_n, carry.qhead, carry.next_n, st)
    )
    if int(viol) != OK:
        return True
    return (
        int(level_n) - int(qhead) <= 0
        and int(next_n) == 0
        and int(st_n) <= 0
    )


DEFAULT_FP_HIGHWATER = 0.85


# -deferred-inv auto threshold (ISSUE 15): deferring the invariant
# sweep from the chunk*L candidate lanes to the ~2*chunk fresh-insert
# claimants is the distinct-first collapse.  Each side runs in one
# benchmark cell - immediate at chunk 1024 in `kubeapi-model1.recheck`,
# deferred at 16,384 in the wide and four-chip cells; the two were
# never compared on a chip (ROADMAP A3).
DEFERRED_AUTO_CHUNK = 2048


def resolve_deferred(deferred, chunk: int) -> bool:
    """Resolve the tri-state -deferred-inv flag (None = auto) for an
    engine popping `chunk` states per step.  Deterministic in the
    geometry alone, so engine memos, EnginePool keys, checkpoint meta,
    resume commands and journal run_start params all compute the same
    answer without coordination."""
    if deferred is not None:
        return bool(deferred)
    return chunk >= DEFERRED_AUTO_CHUNK


def resolve_symmetry(symmetry, chunk: int = 0) -> bool:
    """Resolve the tri-state -symmetry flag (None = auto).  Auto is
    OFF: orbit dedup legitimately SHRINKS the distinct-state count, so
    unlike deferred it is not a pure performance mode and must be
    opted into.  Same resolver shape as resolve_deferred so engine
    memos, checkpoint meta, resume commands and journal params all
    agree without coordination (`chunk` is accepted for signature
    symmetry; the answer does not depend on it)."""
    if symmetry is not None:
        return bool(symmetry)
    return False


def resolve_por(por, chunk: int = 0) -> bool:
    """Resolve the tri-state -por flag (None = auto).  Auto is OFF for
    the same reason as resolve_symmetry: ample-set pruning changes the
    explored-state counts (verdicts are preserved, counts are not)."""
    if por is not None:
        return bool(por)
    return False


def make_engine(
    cfg: ModelConfig,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    pipeline: bool = False,
    donate: bool = True,
    obs_slots: int = 0,
    coverage: bool = False,
    deferred: bool = None,
):
    """Build (init_fn, run_fn, step_fn) for one KubeAPI configuration.

    The hand-tuned KubeAPI path of make_backend_engine: the factorized
    per-action counters and the rest of the v4 loop now come through the
    SpecBackend seam, so this is a specialization, not a privilege.
    `coverage` compiles the device per-site coverage plane in
    (spec.coverage_device; the carry layout changes, so checkpoints
    record the flag)."""
    from .backend import kubeapi_backend

    return make_backend_engine(
        kubeapi_backend(cfg, coverage=coverage), chunk, queue_capacity,
        fp_capacity, fp_index, seed, fp_highwater=fp_highwater,
        pipeline=pipeline, donate=donate, obs_slots=obs_slots,
        deferred=deferred,
    )


def probe_width(ck: int, n_lanes: int) -> int:
    """Rows of one segment of the commit's probe / claim at pop width
    `ck`: steady-state new-per-chunk == chunk, so 2x covers bursts and
    the segment loops keep worst cases exact."""
    return min(2 * ck, ck * n_lanes)


def make_stage_pair(
    backend,
    ck: int,
    *,
    queue_capacity: int,
    fp_capacity: int,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    check_deadlock: bool = None,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    obs_slots: int = 0,
    spill: bool = False,
    deferred: bool = False,
):
    """(pop_expand, commit) at pop width `ck` - the two halves of one
    BFS step, shared by every composition: the unpipelined body runs
    them back to back, the pipelined body runs commit on the PREVIOUS
    block's staged ExpandOut while pop_expand works on the next block,
    and the host spill driver (engine.spill) interleaves a host-tier
    membership check between them.

    deferred=True (a RESOLVED bool; factories resolve the tri-state
    flag via resolve_deferred) moves invariant + certificate
    evaluation from the expand stage to THIS commit, running them only
    on the fresh-insert claimants (backend.make_deferred_checker: TLC
    checks a state when first generated, and first generation is the
    distinct insert) - ~probe-width rows instead of chunk*L candidate
    lanes (ISSUE 15).  Verdict, counters, fpset TABLE words and
    rendered traces are bit-for-bit the immediate path's; only the
    violation-LANE attribution changes, to the pinned highest-lane
    rule (the checker docstring).  Because both modes meet at this one
    seam, every composed engine - fused, pipelined, spill,
    narrowed, covered - inherits the mode with no per-engine code.

    spill=True builds the commit for spill mode: it takes an extra
    `veto` mask ([ck * n_lanes] bool, candidates the HOST fingerprint
    store already holds - treated exactly like a device-table hit: not
    new, not enqueued, no stat credit), skips the fp-capacity halt (the
    host driver flushes the device table before dispatching a chunk
    that could overflow it, so the halt can never be needed), and
    accumulates the cumulative `spill_hits` carry counter (obs ring
    COL_SPILL).  Dedup verdicts are unchanged otherwise, so a spill-
    mode run's final statistics are bit-for-bit a correctly-sized clean
    run's (tests/test_spill.py pins this through the chaos matrix)."""
    from ..obs.counters import (
        pack_row,
        ring_update,
        sticky_overflow,
        wrapped_any,
    )
    from .backend import make_expand_stage

    cdc = backend.cdc
    W = (cdc.nbits + 31) // 32
    L = backend.n_lanes
    n_labels = len(backend.labels)
    qcap = queue_capacity
    label_ids = jnp.arange(n_labels, dtype=jnp.int32)
    ncand = ck * L
    # compaction widths: probe/claim/enqueue touch only this many rows
    # per segment; steady-state new-per-chunk == chunk, so 2x covers
    # bursts and the segment loops keep worst cases exact
    R = probe_width(ck, L)  # fpset probe width
    CW = min(2 * ck, R)  # fpset round-0 claim width
    A = min(2 * ck, ncand)  # enqueue/stat segment width
    expand_fn = make_expand_stage(
        backend, ck, check_deadlock, fp_index, seed, deferred=deferred
    )
    # deferred-evaluation checker (ISSUE 15): invariants + certificate
    # over the fresh-insert claimants, at the probe width the insert
    # already compacts to.  None when there is nothing to check.
    checker = None
    if deferred and (backend.inv_codes or backend.cert_check is not None):
        from .backend import make_deferred_checker

        checker = make_deferred_checker(backend, ncand, probe_width=R)

    def pop_expand(c: EngineCarry):
        """Expand stage: contiguous pop + backend expand.  Reads only
        the pre-commit carry (queue buffer `parity`, which the commit
        stage never writes), so XLA may schedule it alongside the
        commit of the previous block."""
        # device scopes (jax.named_scope, trace-time metadata only):
        # the same layer names a profiler trace shows for the host
        # spans of obs.spans - expand, pack_fp (inside the backend's
        # expand), dedup and fpset (the commit's insert), enqueue, level
        with jax.named_scope("jaxtlc.expand"):
            avail = c.level_n - c.qhead
            n = jnp.clip(avail, 0, ck)
            rows = jnp.arange(ck, dtype=jnp.int32)
            mask = rows < n
            # contiguous pop (the buffer is chunk-padded: no OOB
            # clamping)
            block = lax.dynamic_slice(
                c.queue, (c.parity, c.qhead, jnp.int32(0)), (1, ck, W)
            )[0]
            batch = cdc.unpack(block)
            return expand_fn(batch, mask), n

    def commit(c: EngineCarry, ex, n, qhead_pop, qhead_out, veto=None):
        """Commit stage for one block's ExpandOut `ex` (`n` popped
        rows): fpset probe/claim over the sort-compacted candidates,
        contiguous enqueue, counters, violation merge and level
        fencing.  `qhead_pop` is the pop cursor right after `ex`'s
        block was popped (the level-done basis); `qhead_out` is the
        cursor to keep when the level does not flip (the pipelined
        fused body passes the post-expand cursor here)."""
        if spill:
            # the host driver enforces device-tier residency, so the
            # capacity halt is off; host-vetoed candidates dedup
            # exactly like a device-table hit
            fp_full = jnp.bool_(False)
            insert_mask = ex.valid & ~veto
        else:
            fp_full = (c.distinct.astype(jnp.int32) + ncand) > int(
                fp_capacity * fp_highwater
            )
            insert_mask = ex.valid & ~fp_full
        # the in-batch dedup (the grouping sort at candidate width, the
        # compaction at the width of the valid lanes); the probe /
        # claim inside it is `jaxtlc.fpset` (_probe_block), so a trace
        # attributes an op to the innermost of the two
        with jax.named_scope("jaxtlc.dedup"):
            fps, is_new_c, c_idx, nreps, cstat = fpset_insert_sorted(
                c.fps, ex.lo, ex.hi, insert_mask,
                probe_width=R, claim_width=CW,
                stat_cols=COMMIT_LEAF_COLS,
            )
        n_new = is_new_c.sum().astype(jnp.int32)
        q_full = c.next_n + n_new > qcap

        with jax.named_scope("jaxtlc.enqueue"):
            # enqueue + per-new-state stats: bring new entries to the
            # front ordered by original lane index (2-key sort) - the
            # same append order as the v3 scatter engine, so pop order
            # and therefore in-batch attribution statistics (outdegree
            # min/max, MC.out:1104) are preserved bit-for-bit.  All new
            # entries sit in the first nreps compacted positions, so
            # the sort runs at the rung that holds nreps - the probe
            # width R first (~6x less comparator traffic than ncand),
            # the whole array last: all-distinct bursts stay exact.
            e_idx, e_at = enqueue_order(is_new_c, c_idx, nreps, R)
            e_idx_p = jnp.concatenate([e_idx, jnp.zeros(A, jnp.uint32)])

            def enq_cond(st):
                _, _, s = st
                return s * A < n_new

            def enq_body(st):
                queue, act_dist, s = st
                offs = s * A
                idx_a = lax.dynamic_slice(e_idx_p, (offs,), (A,)).astype(
                    jnp.int32
                )
                active = (jnp.arange(A) + offs) < n_new
                rows_a = ex.packed[idx_a]  # [A, W] row gather (the only one)
                woff = jnp.minimum(c.next_n + offs, qcap)
                queue = lax.dynamic_update_slice(
                    queue, rows_a[None], (1 - c.parity, woff, jnp.int32(0))
                )
                # per-action distinct counts by [A, n_labels] compare-
                # reduce: dense work in place of an A-element scatter-add
                acts_a = ex.action[idx_a]
                act_dist = act_dist.at[:n_labels].add(
                    (
                        (acts_a[:, None] == label_ids[None, :])
                        & active[:, None]
                    ).sum(axis=0).astype(jnp.uint32)
                )
                return queue, act_dist, s + 1

            queue, act_dist, _ = lax.while_loop(
                enq_cond, enq_body, (c.queue, c.act_dist, jnp.int32(0))
            )

        with jax.named_scope("jaxtlc.level"):
            # outdegree histogram of the popped states (TLC's outdegree =
            # distinct new successors per expansion, MC.out:1104) via run
            # lengths: e_idx's active prefix is ascending in source row,
            # so each row's new-child count is a run length - no
            # [chunk+1]-bin scatter-add
            pos = jnp.arange(ncand)
            active_new = pos < n_new
            src_e = jnp.where(active_new, e_idx.astype(jnp.int32) // L, -1)
            startf = jnp.concatenate(
                [jnp.ones(1, bool), src_e[1:] != src_e[:-1]]
            ) & active_new
            endf = jnp.concatenate(
                [src_e[1:] != src_e[:-1], jnp.ones(1, bool)]
            ) & active_new
            run0 = lax.cummax(jnp.where(startf, pos, 0))
            run_len = jnp.where(endf, pos - run0 + 1, 0)
            nruns = startf.sum()
            deg_hist = (
                (run_len[:, None] == jnp.arange(1, L + 1)[None, :])
                .sum(axis=0)
                .astype(jnp.uint32)
            )
            outdeg_hist = c.outdeg_hist.at[1 : L + 1].add(deg_hist)
            outdeg_hist = outdeg_hist.at[0].add(
                (n - nruns).astype(jnp.uint32)
            )

            act_gen = c.act_gen.at[:n_labels].add(ex.gen)
            generated = c.generated + ex.valid.sum().astype(jnp.uint32)
            if ex.con_stat is not None:
                # what the state constraint rejected was generated too
                generated = generated + ex.con_stat[1]
            distinct = c.distinct + n_new.astype(jnp.uint32)

            # violations, first wins: carried > deferred invariant (when
            # evaluation is deferred, checked on the fresh claimants just
            # inserted - outranking the kernel-derived codes exactly as
            # the immediate reduce orders invariant > assert) >
            # expand-stage (invariant > assert > deadlock > slot,
            # pre-reduced in ex) > capacity
            viol = c.viol
            viol_state = c.viol_state
            viol_action = c.viol_action
            d_cert = None
            chk_trips = jnp.int32(0)
            if checker is not None:
                d_viol, d_state, d_action, d_cert, chk_trips = checker(
                    ex.flat, ex.action, is_new_c, c_idx, nreps
                )
                hit = (d_viol != OK) & (viol == OK)
                viol = jnp.where(hit, d_viol, viol)
                viol_state = jnp.where(hit, d_state, viol_state)
                viol_action = jnp.where(hit, d_action, viol_action)
            hit = (ex.viol != OK) & (viol == OK)
            viol = jnp.where(hit, ex.viol, viol)
            viol_state = jnp.where(hit, ex.viol_state, viol_state)
            viol_action = jnp.where(hit, ex.viol_action, viol_action)
            if not spill:
                hit = fp_full & ex.valid.any() & (viol == OK)
                viol = jnp.where(hit, VIOL_FPSET_FULL, viol)
            hit = q_full & (viol == OK)
            viol = jnp.where(hit, VIOL_QUEUE_FULL, viol)

            # level bookkeeping: ping-pong at the level boundary
            next_n = jnp.minimum(c.next_n + n_new, qcap)
            level_done = qhead_pop >= c.level_n
            advance = level_done & (next_n > 0)
            parity = jnp.where(level_done, 1 - c.parity, c.parity)
            level_n = jnp.where(level_done, next_n, c.level_n)
            next_n = jnp.where(level_done, 0, next_n)
            qhead = jnp.where(level_done, 0, qhead_out)
            level = jnp.where(advance, c.level + 1, c.level)
            depth = jnp.maximum(c.depth, level)

            extra = {}
            if spill:
                extra["spill_hits"] = c.spill_hits + (
                    veto & ex.valid
                ).sum().astype(jnp.uint32)
            cert_now = None
            cert_src = d_cert if deferred else ex.cert
            if cert_src is not None and c.cert_viol is not None:
                # sticky: once any block's certificate check fired, every
                # later carry (and ring row) carries the flag (deferred
                # mode latches it from the commit-site checker instead of
                # the staged expand bit - same column, same stickiness)
                cert_now = c.cert_viol | cert_src
                extra["cert_viol"] = cert_now
            sym_now = None
            if ex.sym is not None and c.sym_viol is not None:
                # orbit certification (ISSUE 18): same sticky latch as the
                # certificate bit - computed at expand on the canonical
                # fields, so the deferred mode needs no commit-site variant
                sym_now = c.sym_viol | ex.sym
                extra["sym_viol"] = sym_now
                extra["sym_stat"] = c.sym_stat + ex.sym_stat
            if ex.pruned is not None and c.por_pruned is not None:
                extra["por_pruned"] = c.por_pruned + ex.pruned
            if ex.con_stat is not None and c.con_stat is not None:
                extra["con_stat"] = c.con_stat + ex.con_stat
            if ex.ap_stat is not None and c.ap_stat is not None:
                extra["ap_stat"] = c.ap_stat + ex.ap_stat
                # the failing edge's source, latched with its violation
                # (first wins, as `viol_state`: ex.viol is the property's
                # code only where ex.ap_src was set)
                extra["ap_src"] = jnp.where(
                    (c.viol == OK) & (viol == ex.viol) & (ex.viol != OK),
                    ex.ap_src, c.ap_src)
            if ex.cov is not None and c.cov_counts is not None:
                # device coverage plane: fold this block's per-site visit
                # increments into the cumulative counters (telemetry only)
                extra["cov_counts"] = c.cov_counts + ex.cov
            if c.commit_stat is not None:
                # what this commit did, as counts: the insert's block,
                # then the loop's own and the rung the enqueue sorted at
                extra["commit_stat"] = c.commit_stat + cstat + count_block(
                    ENGINE_COUNTS, LADDER_RUNGS, e_at, lead=COMMIT_STAT_COLS,
                    bodies=1, new=n_new, checker_trips=chk_trips)
            obs = {}
            if obs_slots:
                # one telemetry row per completed level (post-commit
                # cumulative counters; the dump row absorbs non-flip
                # bodies so the store is unconditional).  The sticky
                # COL_OVERFLOW flag marks any uint32 wrap so saturated
                # counters are detected, never silently wrong
                obs_bodies = c.obs_bodies + jnp.uint32(1)
                obs_expanded = c.obs_expanded + n.astype(jnp.uint32)
                wrap_pairs = [
                    (generated, c.generated), (distinct, c.distinct),
                    (act_gen, c.act_gen), (act_dist, c.act_dist),
                    (obs_bodies, c.obs_bodies),
                    (obs_expanded, c.obs_expanded),
                ]
                if spill:
                    wrap_pairs.append((extra["spill_hits"], c.spill_hits))
                if "cov_counts" in extra:
                    wrap_pairs.append((extra["cov_counts"], c.cov_counts))
                wrapped = wrapped_any(wrap_pairs)
                row = pack_row(
                    c.level, generated, distinct, level_n, obs_bodies,
                    obs_expanded, act_gen[:n_labels],
                    act_dist[:n_labels],
                    overflow=sticky_overflow(c.obs_ring, wrapped),
                    spill=extra.get("spill_hits"),
                    cert=cert_now,
                    sym=sym_now,
                )
                ring, head = ring_update(
                    c.obs_ring, c.obs_head, row, level_done
                )
                obs = dict(obs_ring=ring, obs_head=head,
                           obs_bodies=obs_bodies,
                           obs_expanded=obs_expanded)

        return c._replace(
            fps=fps,
            queue=queue,
            parity=parity,
            qhead=qhead,
            level_n=level_n,
            next_n=next_n,
            level=level,
            depth=depth,
            generated=generated,
            distinct=distinct,
            act_gen=act_gen,
            act_dist=act_dist,
            outdeg_hist=outdeg_hist,
            viol=viol,
            viol_state=viol_state,
            viol_action=viol_action,
            **extra,
            **obs,
        )

    return pop_expand, commit


def run_steps(cond, body, c, steps=None, small_body=None, big=None):
    """Up to `steps` steps of `body` on the carry `c` (None: until
    `cond(c)` is false), stopping when `cond` does.  With a `small_body`
    each step is `body` where `big(c)` holds and `small_body` where it
    does not.  While loops only: a while loop writes its carry in
    place, where a conditional that takes the carry (an identity branch
    for a finished check, a branch per tier) cost a copy of the queue a
    step on the chip (PERF.md PR 26, Step 0).  So the two tiers are two
    inner loops, each running its body for as long as that tier is the
    one to take: the same bodies in the same order as a choice made
    step by step, and one step counter for both."""

    def live(st):
        i, cc = st
        return cond(cc) if steps is None else cond(cc) & (i < steps)

    def stepping(tier_body, wanted=None):
        go = live if wanted is None else (
            lambda st: live(st) & wanted(st[1]))
        return lambda st: lax.while_loop(
            go, lambda s: (s[0] + 1, tier_body(s[1])), st)

    start = (jnp.int32(0), c)
    if small_body is None:
        return stepping(body)(start)[1]
    big_steps = stepping(body, big)
    small_steps = stepping(small_body, lambda cc: ~big(cc))
    return lax.while_loop(
        live, lambda st: small_steps(big_steps(st)), start)[1]


def make_backend_engine(
    backend,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    check_deadlock: bool = None,
    pipeline: bool = False,
    donate: bool = True,
    obs_slots: int = 0,
    deferred: bool = None,
):
    """Build (init_fn, run_fn, step_fn) over any SpecBackend.

    init_fn() -> EngineCarry seeded with the Init states.
    run_fn(carry) -> EngineCarry after exhaustion/violation (jitted, fused).
    step_fn(carry) -> EngineCarry after ONE chunk (jitted; for checkpointed
    / incremental runs).

    queue_capacity bounds the width of a single BFS level (the frontier),
    not the total state count: levels ping-pong between two buffers.

    fp_highwater is the fingerprint-table load fraction at which the run
    halts with VIOL_FPSET_FULL instead of degrading into long straggler
    walks (open addressing past ~0.85 load is where probe cost blows up);
    the supervisor's auto-regrow doubles fp_capacity at this trigger.

    check_deadlock overrides the backend's default (TLC's -deadlock
    switch; None takes backend.check_deadlock).

    pipeline=True software-pipelines the step: the body is split into an
    expand stage (unpack -> kernel -> invariants -> fingerprints) and a
    commit stage (sort-compact dedup -> fpset probe/claim -> enqueue +
    counters), and the carry stages block k's ExpandOut so body i
    commits block k-1 WHILE expanding block k - two blocks in flight,
    giving the XLA scheduler overlap freedom across the stages (SURVEY
    §2.4 level pipelining; PERF.md round 7).  The pop sequence and every
    arbitration decision are unchanged, so a pipelined run is bit-for-bit
    identical to the unpipelined engine at the same chunk (full
    signature: counts, depth, per-action, outdegree, fpset content) for
    chunks below the two-tier threshold; at chunk >= 2^14 the pipelined
    engine runs single-tier (full-width stages) where the unpipelined
    engine would switch to small bodies, so exact counts still match but
    in-batch attribution may not.  For overlap in the one-step-per-level
    regime, run the pipelined engine at HALF the unpipelined sweet-spot
    chunk so every level spans >= 2 blocks (PERF.md round 7 sizing).

    donate=True (ignored on CPU, where XLA has no donation) marks the
    carry argument of run_fn/step_fn donated so XLA aliases the ping-pong
    queue/candidate buffers across iterations instead of copying.  Pass
    donate=False when the SAME carry value is fed to the engine twice
    (profilers, the resil supervisor's retry-from-last-good loop).

    obs_slots > 0 carries the observability counter ring (obs.counters):
    one cumulative-counter row per completed BFS level, written with a
    single contiguous row store per body (the dump-row trick makes the
    write unconditional).  The ring is pure telemetry - it feeds no
    control flow and no arbitration - so check results with obs on are
    bit-for-bit those of an obs-off run (tests/test_obs.py::
    test_obs_bit_identical_and_ring pins it).

    deferred (tri-state: None = auto, resolve_deferred) moves
    invariant + certificate evaluation to the commit stage, over the
    fresh-insert claimants only (ISSUE 15; make_stage_pair docstring).
    Verdict, full counter signature, fpset TABLE words and rendered
    traces are bit-for-bit the immediate path's (tests/test_deferred.py::
    test_ff_bit_for_bit pins it); violation-LANE attribution follows the pinned
    highest-lane rule.  The resolved mode is engine-memo and
    checkpoint-meta material - a wrong-mode -recover is a loud
    pre-build rejection - because the pipelined staged-block layout
    changes (st_flat replaces st_cert) and attribution must never
    silently flip across a resume.
    """
    from ..obs.counters import ring_new
    from .backend import ExpandOut

    assert 0.0 < fp_highwater <= 1.0, "fp_highwater must be in (0, 1]"
    deferred = resolve_deferred(deferred, chunk)
    has_cert = backend.cert_check is not None
    # in deferred mode the staged ExpandOut carries the raw fields
    # (st_flat) and no cert bit (the commit-site checker derives it)
    stage_cert = has_cert and not deferred
    # state-space reduction (ISSUE 18): presence of the plan / POR
    # rights decides the carry leaves, mirroring make_expand_stage's
    # own gating exactly so staged blocks and ExpandOut always agree
    red = backend.reduce
    has_sym = red is not None and red.plan is not None
    has_por = bool(
        red is not None and red.por and red.safe_ids
        and backend.lane_action is not None
    )
    cov_plane = backend.coverage
    n_sites = cov_plane.n_sites if cov_plane is not None else 0
    has_con = backend.constraint is not None
    ap = backend.action_prop
    has_ap = ap is not None
    cdc = backend.cdc
    F = cdc.n_fields
    W = (cdc.nbits + 31) // 32
    L = backend.n_lanes
    inv_check = backend.inv_check
    inv_codes = backend.inv_codes
    n_labels = len(backend.labels)
    nbits = cdc.nbits
    qcap = queue_capacity
    if check_deadlock is None:
        check_deadlock = backend.check_deadlock
    # two-tier adaptive stepping: a step's cost is dominated by fixed
    # chunk-sized work regardless of how few states it pops, so narrow
    # levels (the BFS ramp/tail) and level remainders run a small body
    # instead of paying a full big-chunk step.  The pipelined engine is
    # single-tier: its staged-block carry has one static width, and
    # mixing widths would change the pop sequence vs the bit-exactness
    # contract above.
    small = chunk // 16 if (chunk >= 1 << 14 and not pipeline) else 0

    label_ids = jnp.arange(n_labels, dtype=jnp.int32)
    ncand_full = chunk * L

    def init_fn(inits=None) -> EngineCarry:
        # `inits` overrides the backend's Init set ([n0, F] int32 field
        # vectors): the constant-config sweep engine (jaxtlc.serve.sweep)
        # seeds one carry per configuration through the same packing /
        # fpset-insert / init-invariant path, so a seeded carry is
        # exactly what a backend with that Init would have produced
        if inits is None:
            inits = backend.initial_vectors()
        inits = jnp.asarray(inits)
        if has_sym:
            # seed the frontier with orbit representatives: Init is
            # permutation-closed (symfind verified init_ast mentions no
            # symmetric atom), so every reachable orbit stays reachable
            # from the canonicalized seeds
            inits = red.plan.canon(inits)
        n0 = inits.shape[0]
        assert n0 <= qcap, "raise queue_capacity"
        kept0 = jnp.ones(n0, bool)
        if has_con:
            # an initial state outside the cfg's CONSTRAINT counts as
            # generated and is invariant-checked below like the others,
            # and is then not kept: the kept ones move to the front of
            # the first level, in their order
            kept0 = backend.constraint(inits)
            inits = inits[jnp.argsort(~kept0, stable=True)]
            kept0 = jnp.arange(n0) < kept0.sum()
        packed0 = cdc.pack(inits)
        queue = (
            jnp.zeros((2, qcap + 2 * chunk, W), jnp.uint32)
            .at[0, :n0]
            .set(packed0)
        )
        lo, hi = fp64_words_mxu(packed0, nbits, fp_index, seed)
        fps, is_new_c, _, _, _ = fpset_insert_sorted(
            fpset_new(fp_capacity), lo, hi, kept0
        )
        distinct0 = is_new_c.sum().astype(jnp.uint32)
        # invariants hold on the initial states too (TLC checks them
        # before the first Next application)
        inv0 = jax.vmap(inv_check)(inits)
        viol = jnp.int32(OK)
        viol_state = jnp.zeros(F, jnp.int32)
        for k, code in enumerate(inv_codes):
            bad = (inv0 & (1 << k)) == 0
            hit = bad.any() & (viol == OK)
            viol = jnp.where(hit, code, viol)
            viol_state = jnp.where(hit, inits[jnp.argmax(bad)], viol_state)
        if has_ap:
            # the first half of an action property `I /\\ [][A]_v`: I on
            # every initial state
            init_ok = ap.init(inits)
            for k, code in enumerate(ap.init_codes):
                bad = ~init_ok[k]
                hit = bad.any() & (viol == OK)
                viol = jnp.where(hit, code, viol)
                viol_state = jnp.where(hit, inits[jnp.argmax(bad)],
                                       viol_state)
        staged = {}
        if pipeline:
            staged = dict(
                st_packed=jnp.zeros((ncand_full, W), jnp.uint32),
                st_lo=jnp.zeros(ncand_full, jnp.uint32),
                st_hi=jnp.zeros(ncand_full, jnp.uint32),
                st_valid=jnp.zeros(ncand_full, bool),
                st_action=jnp.zeros(ncand_full, jnp.int32),
                st_gen=jnp.zeros(n_labels, jnp.uint32),
                st_n=jnp.int32(0),
                st_viol=jnp.int32(OK),
                st_viol_state=jnp.zeros(F, jnp.int32),
                st_viol_action=jnp.int32(-1),
            )
            if stage_cert:
                staged["st_cert"] = jnp.bool_(False)
            if cov_plane is not None:
                staged["st_cov"] = jnp.zeros(n_sites, jnp.uint32)
            if deferred:
                staged["st_flat"] = jnp.zeros((ncand_full, F), jnp.int32)
            if has_sym:
                staged["st_sym"] = jnp.bool_(False)
                staged["st_sym_stat"] = jnp.zeros(4, jnp.uint32)
            if has_por:
                staged["st_pruned"] = jnp.uint32(0)
            if has_con:
                staged["st_con_stat"] = jnp.zeros(2, jnp.uint32)
            if has_ap:
                staged["st_ap_stat"] = jnp.zeros(2, jnp.uint32)
                staged["st_ap_src"] = jnp.zeros(F, jnp.int32)
        if has_con:
            staged["con_stat"] = jnp.zeros(2, jnp.uint32)
        if has_ap:
            staged["ap_stat"] = jnp.zeros(2, jnp.uint32)
            staged["ap_src"] = jnp.zeros(F, jnp.int32)
        if has_cert:
            staged["cert_viol"] = jnp.bool_(False)
        if has_sym:
            staged["sym_viol"] = jnp.bool_(False)
            staged["sym_stat"] = jnp.zeros(4, jnp.uint32)
        if has_por:
            staged["por_pruned"] = jnp.uint32(0)
        if cov_plane is not None:
            # coverage counters seeded with the Init-site visits (the
            # host-side charge for the seed states; zero when the plane
            # tracks no Init sites)
            staged["cov_counts"] = jnp.asarray(
                cov_plane.seed(np.asarray(inits))
            )
        obs = {}
        if obs_slots:
            ring, head = ring_new(obs_slots, n_labels)
            obs = dict(
                obs_ring=ring, obs_head=head,
                obs_bodies=jnp.uint32(0), obs_expanded=jnp.uint32(0),
            )
        return EngineCarry(
            fps=fps,
            queue=queue,
            parity=jnp.int32(0),
            qhead=jnp.int32(0),
            level_n=kept0.sum().astype(jnp.int32),
            next_n=jnp.int32(0),
            level=jnp.int32(1),
            depth=jnp.int32(1),
            generated=jnp.uint32(n0),
            distinct=distinct0,
            act_gen=jnp.zeros(n_labels + 1, jnp.uint32),
            act_dist=jnp.zeros(n_labels + 1, jnp.uint32),
            outdeg_hist=jnp.zeros(L + 2, jnp.uint32),
            viol=viol,
            viol_state=viol_state,
            viol_action=jnp.int32(-1),
            commit_stat=jnp.zeros(COMMIT_LEAF_COLS, jnp.uint32),
            **staged,
            **obs,
        )

    def make_stages(ck: int):
        """(pop_expand, commit) at pop width `ck` - the module-level
        make_stage_pair specialized to this engine's geometry (the
        lift that lets the host spill driver, engine.spill, reuse the
        exact commit the fused/pipelined bodies run)."""
        return make_stage_pair(
            backend, ck, queue_capacity=qcap, fp_capacity=fp_capacity,
            fp_highwater=fp_highwater, check_deadlock=check_deadlock,
            fp_index=fp_index, seed=seed, obs_slots=obs_slots,
            deferred=deferred,
        )

    def make_body(ck: int):
        """One fused BFS step popping up to `ck` states: expand + commit
        of the SAME block, back to back (the unpipelined body)."""
        pop_expand, commit = make_stages(ck)

        def body(c: EngineCarry) -> EngineCarry:
            ex, n = pop_expand(c)
            return commit(c, ex, n, c.qhead + n, c.qhead + n)

        return body

    small_body = None
    if pipeline:
        pop_expand, commit = make_stages(chunk)

        def with_staged(c: EngineCarry, ex, n) -> EngineCarry:
            extra = {"st_cert": ex.cert} if stage_cert else {}
            if cov_plane is not None:
                extra["st_cov"] = ex.cov
            if deferred:
                extra["st_flat"] = ex.flat
            if has_sym:
                extra["st_sym"] = ex.sym
                extra["st_sym_stat"] = ex.sym_stat
            if has_por:
                extra["st_pruned"] = ex.pruned
            if has_con:
                extra["st_con_stat"] = ex.con_stat
            if has_ap:
                extra["st_ap_stat"] = ex.ap_stat
                extra["st_ap_src"] = ex.ap_src
            return c._replace(
                st_packed=ex.packed, st_lo=ex.lo, st_hi=ex.hi,
                st_valid=ex.valid, st_action=ex.action, st_gen=ex.gen,
                st_n=n, st_viol=ex.viol, st_viol_state=ex.viol_state,
                st_viol_action=ex.viol_action, **extra,
            )

        def staged_ex(c: EngineCarry) -> ExpandOut:
            return ExpandOut(
                packed=c.st_packed, lo=c.st_lo, hi=c.st_hi,
                valid=c.st_valid, action=c.st_action, gen=c.st_gen,
                viol=c.st_viol, viol_state=c.st_viol_state,
                viol_action=c.st_viol_action,
                cert=c.st_cert if stage_cert else None,
                cov=c.st_cov if cov_plane is not None else None,
                flat=c.st_flat if deferred else None,
                sym=c.st_sym if has_sym else None,
                sym_stat=c.st_sym_stat if has_sym else None,
                pruned=c.st_pruned if has_por else None,
                con_stat=c.st_con_stat if has_con else None,
                ap_stat=c.st_ap_stat if has_ap else None,
                ap_src=c.st_ap_src if has_ap else None,
            )

        # The two-deep pipeline body, bubble-free: the staged block k-1
        # commits WHILE block k expands from the PRE-commit carry (the
        # commit stage never writes the current-level buffer, so the two
        # halves are data-independent and XLA may overlap them).  At a
        # level boundary (will_flip: the staged block was the level's
        # last pop) the expansion instead reads the POST-commit carry -
        # the freshly flipped level - which serializes that one body but
        # keeps the body count equal to the unpipelined engine's (no
        # idle half-bodies: two earlier formulations paid an
        # fpset-table copy per body through conditional pass-through,
        # or a full-width empty-commit sort set per level bubble).  The
        # expand conditional's results are only the staged ExpandOut -
        # never the table/queue - so the untaken branch costs nothing.
        def body(c: EngineCarry) -> EngineCarry:
            will_flip = c.qhead >= c.level_n
            c2 = commit(c, staged_ex(c), c.st_n, c.qhead, c.qhead)

            def expand_pre(_):
                return pop_expand(c)

            def expand_post(_):
                return pop_expand(c2)

            ex, n = lax.cond(will_flip, expand_post, expand_pre, 0)
            return with_staged(c2._replace(qhead=c2.qhead + n), ex, n)

        def cond(c: EngineCarry):
            return (
                (c.qhead < c.level_n) | (c.next_n > 0) | (c.st_n > 0)
            ) & (c.viol == OK)

    else:
        body = make_body(chunk)
        if small:
            counted = make_body(small)

            def small_body(c: EngineCarry) -> EngineCarry:
                # the commit's counts are the chunk-wide bodies': they
                # are read against one set of static widths
                return counted(c)._replace(commit_stat=c.commit_stat)

        def cond(c: EngineCarry):
            return ((c.qhead < c.level_n) | (c.next_n > 0)) & (c.viol == OK)

    # break-even: a big step costs ~what chunk/small small steps cost,
    # so take the big body only when the level remainder mostly fills it
    def big(c: EngineCarry):
        return c.level_n - c.qhead >= chunk // 2

    # donate the carry so XLA aliases the ping-pong queue / staged
    # candidate buffers in place of copies (CPU has no donation support;
    # requesting it there only emits warnings)
    donate_ok = bool(donate) and jax.devices()[0].platform != "cpu"
    jit_kw = {"donate_argnums": (0,)} if donate_ok else {}

    def steps_fn(n):
        return jax.jit(
            lambda c: run_steps(cond, body, c, n, small_body, big),
            **jit_kw)

    run_fn = steps_fn(None)
    step_fn = steps_fn(1)
    # a checkpointed driver's program: `n` steps a call
    step_fn.segment = steps_fn
    # donation metadata for the preflight audit (analysis.engine_audit):
    # donate_requested is the factory intent, donates_carry what XLA
    # will actually do on this platform - the gap is the class of bug
    # that only reproduces on device
    for fn in (run_fn, step_fn):
        fn.donate_requested = bool(donate)
        fn.donates_carry = donate_ok
    # JAXTLC_DEBUG_DONATION=1: simulate donation semantics on every
    # backend by poisoning the input carry after each call, so a
    # use-after-donate fails fast on CPU instead of only on TPU
    from ..analysis.donation import wrap_if_debugging

    run_fn = wrap_if_debugging(run_fn, bool(donate))
    step_fn = wrap_if_debugging(step_fn, bool(donate))
    return init_fn, run_fn, step_fn


def check(
    cfg: ModelConfig,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    pipeline: bool = False,
    obs_slots: int = 0,
    coverage: bool = False,
    deferred: bool = None,
) -> CheckResult:
    """Run an exhaustive check; the single-device engine entry point.

    The fused loop is AOT-compiled (`lower().compile()`) before timing, so
    wall_s measures execution only - the honest time-to-exhaustive figure
    (compilation is a one-time cost, amortized in TLC by the JVM the same
    way)."""
    from .backend import kubeapi_backend

    backend = kubeapi_backend(cfg, coverage=coverage)
    init_fn, run_fn, _ = make_backend_engine(
        backend, chunk, queue_capacity, fp_capacity, fp_index, seed,
        fp_highwater=fp_highwater, pipeline=pipeline, obs_slots=obs_slots,
        deferred=deferred,
    )
    carry = init_fn()
    compiled = run_fn.lower(carry).compile()
    t0 = time.time()
    carry = jax.block_until_ready(compiled(carry))
    wall = time.time() - t0
    from .fpset import fpset_actual_collision

    afc = float(fpset_actual_collision(carry.fps))
    sites = backend.coverage.sites if backend.coverage else None
    return result_from_carry(
        carry, wall, fp_capacity=fp_capacity, sites=sites,
        commit=commit_geometry(backend.n_lanes, chunk),
    )._replace(actual_fp_collision=afc)


def obs_rows(carry, labels: tuple = None, since: int = 0,
             fp_capacity: int = 0):
    """Decode the carry's observability ring into journal-`level`-event
    dicts (oldest first) plus the new head cursor.  ([], since) when obs
    is off - callers need no obs-awareness of their own."""
    from ..obs.counters import rows_from_ring

    if getattr(carry, "obs_ring", None) is None:
        return [], int(since)
    head = int(carry.obs_head)
    return (
        rows_from_ring(
            np.asarray(carry.obs_ring), head, labels=labels,
            since=since, fp_capacity=fp_capacity,
        ),
        head,
    )


class EnumCarry(NamedTuple):
    """Carry of the fused state enumerator (liveness edge-capture pass 1).

    Unlike EngineCarry's ping-pong level buffers, `states` is APPEND-ONLY:
    a state's row index is its permanent id (BFS append order), which is
    exactly what the device-resident liveness subsystem (jaxtlc.live)
    needs - the edge relation is expressed over these ids."""

    fps: tuple  # fpset.FPSet
    states: jnp.ndarray  # [cap + A, W] uint32 packed states, id = row
    head: jnp.ndarray  # int32: next id to expand
    tail: jnp.ndarray  # int32: number of distinct states stored
    viol: jnp.ndarray  # int32: OK or a capacity/overflow code
    # observability ring (None when obs is off): the enumerator is
    # level-less, so one row per BODY (ring wraps; cumulative counters
    # keep totals exact) - queue col = unexpanded backlog
    obs_ring: jnp.ndarray = None  # [obs_slots + 1, cols] uint32
    obs_head: jnp.ndarray = None  # int32 rows ever written


def make_enumerator(
    backend,
    chunk: int = 1024,
    state_capacity: int = 1 << 20,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    obs_slots: int = 0,
):
    """Build (init_fn, run_fn) for the fused distinct-state enumerator.

    The optional capture mode of the BFS core: the same vmapped kernel +
    MXU fingerprints + sort-compacted dedup as the exhaustive engine, but
    the frontier is the append-only `states` array itself (a work-list
    pop cursor instead of level fencing), so after one fused
    `lax.while_loop` the whole reachable set sits on device in id order.
    `backend` is any engine.sharded.SpecBackend (kubeapi_backend /
    gen_backend), so every frontend that can run sharded can be
    enumerated - the seam the liveness capture (jaxtlc.live.capture)
    feeds on.

    Halts loudly with VIOL_QUEUE_FULL when `state_capacity` is exceeded
    (the caller's cue to raise it or spill), VIOL_FPSET_FULL /
    VIOL_SLOT_OVERFLOW as in the exhaustive engine.
    """
    from .backend import require_unconstrained

    require_unconstrained(backend, "the reachable-set enumerator")

    from ..obs.counters import pack_row, ring_new, ring_update

    cdc = backend.cdc
    F = cdc.n_fields
    W = (cdc.nbits + 31) // 32
    step = backend.step
    L = backend.n_lanes
    n_labels = len(backend.labels)
    nbits = cdc.nbits
    cap = state_capacity
    ncand = chunk * L
    R = min(2 * chunk, ncand)
    A = min(2 * chunk, ncand)

    def init_fn() -> EnumCarry:
        inits = jnp.asarray(backend.initial_vectors())
        n0 = inits.shape[0]
        assert n0 <= cap, "raise state_capacity"
        packed0 = cdc.pack(inits)
        states = jnp.zeros((cap + A, W), jnp.uint32).at[:n0].set(packed0)
        lo, hi = fp64_words_mxu(packed0, nbits, fp_index, seed)
        fps, _, _, _, _ = fpset_insert_sorted(
            fpset_new(fp_capacity), lo, hi, jnp.ones(n0, bool)
        )
        obs = {}
        if obs_slots:
            ring, rhead = ring_new(obs_slots, n_labels)
            obs = dict(obs_ring=ring, obs_head=rhead)
        return EnumCarry(
            fps=fps,
            states=states,
            head=jnp.int32(0),
            tail=jnp.int32(n0),
            viol=jnp.int32(OK),
            **obs,
        )

    def body(c: EnumCarry) -> EnumCarry:
        avail = c.tail - c.head
        n = jnp.minimum(chunk, avail)
        rows = jnp.arange(chunk, dtype=jnp.int32)
        mask = rows < n

        block = lax.dynamic_slice(
            c.states, (c.head, jnp.int32(0)), (chunk, W)
        )
        batch = cdc.unpack(block)
        succs, valid, _action, _afail, ovf = jax.vmap(step)(batch)
        valid = valid & mask[:, None]
        ovf = ovf & valid

        flat = succs.reshape(ncand, F)
        fvalid = valid.reshape(-1)
        packed = cdc.pack(flat)
        lo, hi = fp64_words_mxu(packed, nbits, fp_index, seed)

        fp_full = (c.tail + ncand) > int(fp_capacity * fp_highwater)
        fps, is_new_c, c_idx, nreps, _ = fpset_insert_sorted(
            c.fps, lo, hi, fvalid & ~fp_full, probe_width=R, claim_width=R
        )
        n_new = is_new_c.sum().astype(jnp.int32)
        s_full = c.tail + n_new > cap

        # append new states at the tail in candidate order (the engines'
        # sort-compact + A-wide contiguous-write pattern)
        e_idx, _ = enqueue_order(is_new_c, c_idx, nreps, R)
        e_idx_p = jnp.concatenate([e_idx, jnp.zeros(A, jnp.uint32)])

        def enq_cond(st):
            _, s = st
            return s * A < n_new

        def enq_body(st):
            states, s = st
            offs = s * A
            idx_a = lax.dynamic_slice(e_idx_p, (offs,), (A,)).astype(
                jnp.int32
            )
            rows_a = packed[idx_a]
            woff = jnp.minimum(c.tail + offs, cap)
            states = lax.dynamic_update_slice(
                states, rows_a, (woff, jnp.int32(0))
            )
            return states, s + 1

        states, _ = lax.while_loop(
            enq_cond, enq_body, (c.states, jnp.int32(0))
        )

        viol = c.viol
        viol = jnp.where(ovf.any() & (viol == OK), VIOL_SLOT_OVERFLOW, viol)
        viol = jnp.where(
            fp_full & fvalid.any() & (viol == OK), VIOL_FPSET_FULL, viol
        )
        viol = jnp.where(s_full & (viol == OK), VIOL_QUEUE_FULL, viol)
        tail = jnp.where(s_full, c.tail, c.tail + n_new)
        obs = {}
        if obs_slots:
            # one row per body (the enumerator has no levels): distinct
            # doubles as generated-distinct, queue = unexpanded backlog
            from ..obs.counters import sticky_overflow, wrapped_any

            zeros = jnp.zeros(n_labels, jnp.uint32)
            wrapped = wrapped_any([(tail.astype(jnp.uint32),
                                    c.tail.astype(jnp.uint32))])
            row = pack_row(
                jnp.int32(0), tail, tail, tail - (c.head + n),
                c.obs_head + 1, c.head + n, zeros, zeros,
                overflow=sticky_overflow(c.obs_ring, wrapped),
            )
            ring, rhead = ring_update(
                c.obs_ring, c.obs_head, row, jnp.bool_(True)
            )
            obs = dict(obs_ring=ring, obs_head=rhead)
        return EnumCarry(
            fps=fps, states=states, head=c.head + n, tail=tail,
            viol=viol, **obs,
        )

    def cond(c: EnumCarry):
        return (c.head < c.tail) & (c.viol == OK)

    @jax.jit
    def run_fn(c: EnumCarry) -> EnumCarry:
        return lax.while_loop(cond, body, c)

    return init_fn, run_fn


def outdegree_from_hist(hist: np.ndarray):
    """(avg, min, max, p95) of TLC's outdegree from a new-children
    histogram (hist[d] = #expanded states with d new successors); None if
    empty.  Matches MC.out:1104's reporting convention."""
    hist = np.asarray(hist, dtype=np.int64)
    total = hist.sum()
    if total == 0:
        return None
    degs = np.arange(len(hist))
    nz = np.flatnonzero(hist)
    cum = np.cumsum(hist)
    p95 = int(degs[np.searchsorted(cum, 0.95 * total)])
    return (
        int(round((degs * hist).sum() / total)),
        int(nz[0]),
        int(nz[-1]),
        p95,
    )


def cov_totals(carry) -> "np.ndarray | None":
    """Cumulative per-site coverage counters of a carry ([n_sites]
    int64 host array; shard carries sum their device partials), or
    None when no coverage plane rides the carry."""
    counts = getattr(carry, "cov_counts", None)
    if counts is None:
        return None
    counts = np.asarray(counts).astype(np.int64)
    if counts.ndim == 2:  # sharded: [D, n_sites] partials
        counts = counts.sum(axis=0)
    return counts


def result_from_carry(
    carry: EngineCarry, wall_s: float, iterations: int = -1,
    fp_capacity: int = 0, labels: tuple = LABELS, viol_names: dict = None,
    sites: tuple = None, commit: dict = None,
) -> CheckResult:
    """Pull a finished (or interrupted) carry to host as a CheckResult.
    `commit` (commit_geometry of the engine that ran the carry) puts the
    static widths beside the commit's counts."""
    act_gen = np.asarray(carry.act_gen)[: len(labels)]
    act_dist = np.asarray(carry.act_dist)[: len(labels)]
    hist = np.asarray(carry.outdeg_hist)[:-1].astype(np.int64)  # drop dump
    outdegree = outdegree_from_hist(hist)
    occupancy = (
        int(carry.distinct) / fp_capacity if fp_capacity else None
    )
    viol = int(carry.viol)
    vname = (viol_names or {}).get(viol) or VIOLATION_NAMES.get(
        viol, f"violation {viol}"
    )
    # a pipelined carry's staged block is popped but uncommitted work -
    # still "on queue" in TLC's sense (states handed to a worker)
    staged_n = int(carry.st_n) if carry.st_n is not None else 0
    cert = getattr(carry, "cert_viol", None)
    cert_violated = bool(cert) if cert is not None else None
    sym = getattr(carry, "sym_viol", None)
    sym_violated = bool(sym) if sym is not None else None
    stat = getattr(carry, "sym_stat", None)
    sym_counts = {} if stat is None else dict(zip(
        ("canon_rows", "canon_moved", "sym_cert_checks",
         "sym_cert_trips"), map(int, np.asarray(stat))))
    con = getattr(carry, "con_stat", None)
    if con is not None:
        sym_counts.update(zip(
            ("constraint_rows", "constraint_discarded"),
            map(int, np.asarray(con))))
    aps = getattr(carry, "ap_stat", None)
    if aps is not None:
        sym_counts.update(zip(
            ("action_prop_edges", "action_prop_moved"),
            map(int, np.asarray(aps))))
        sym_counts["action_prop_source"] = np.asarray(carry.ap_src)
    if getattr(carry, "commit_stat", None) is not None:
        sym_counts.update(commit_result_fields(carry.commit_stat, commit))
    pruned = getattr(carry, "por_pruned", None)
    if pruned is not None:
        pruned = int(np.asarray(pruned).sum())  # shards carry partials
    por_pruned = pruned
    site_coverage = None
    totals = cov_totals(carry)
    if totals is not None and sites is not None:
        from ..obs.coverage import site_totals_dict

        site_coverage = site_totals_dict(sites, totals)
    return CheckResult(
        generated=int(carry.generated),
        distinct=int(carry.distinct),
        depth=int(carry.depth),
        queue_left=(
            int(carry.level_n) - int(carry.qhead) + int(carry.next_n)
            + staged_n
        ),
        violation=viol,
        violation_name=vname,
        violation_state=np.asarray(carry.viol_state),
        violation_action=int(carry.viol_action),
        action_generated={
            labels[i]: int(v) for i, v in enumerate(act_gen) if v
        },
        action_distinct={
            labels[i]: int(v) for i, v in enumerate(act_dist) if v
        },
        wall_s=wall_s,
        iterations=iterations,
        outdegree=outdegree,
        fp_occupancy=occupancy,
        cert_violated=cert_violated,
        site_coverage=site_coverage,
        sym_violated=sym_violated,
        por_pruned=por_pruned,
        **sym_counts,
    )
