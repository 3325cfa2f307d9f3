"""Spec backends: the frontend -> engine seam.

Everything an exhaustive engine (the fused single-device loop in
engine.bfs, the mesh-sharded loop in engine.sharded, the fused
enumerator) needs from a spec frontend, packaged as one NamedTuple so
the hand-tuned KubeAPI kernel, the generic compiled lanes, and the
structural lane compiler all plug into the same production machinery -
TLC's engine working on any spec (launch:4-7) made literal.

Optional capabilities degrade gracefully:

* `gen_counts` - a factorized per-action generated-counter hook (the
  KubeAPI kernel counts through its dispatch structure instead of
  scatter-adds over all candidates, PERF.md item 5).  Backends without
  one fall back to `lane_action` folding or a per-candidate reduce.
* `lane_action` - a static lane -> action-id map for frontends whose
  lane dispatch is static (gen + struct compilers emit one lane per
  action binding); lets the engine fold per-action counters with a
  [L, n_actions] compare-reduce instead of touching all chunk*L
  candidates.
* `check_deadlock` - TLC's -deadlock switch; backends for specs with
  intended terminal states turn it off.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..spec.codec import get_codec
from ..spec.invariants import make_invariant_kernel
from ..spec.kernel import initial_vectors, lane_layout, make_kernel
from ..spec.labels import LABEL_ID, LABELS
from .bfs import (
    OK,
    VIOL_ASSERT,
    VIOL_DEADLOCK,
    VIOL_ONLYONEVERSION,
    VIOL_SLOT_OVERFLOW,
    VIOL_TYPEOK,
)
from .fingerprint import fp64_words_mxu


class SpecBackend(NamedTuple):
    """Everything the production engines need from a spec frontend - the
    hand-tuned KubeAPI pieces, the generic compiled lanes and the
    structural lane compiler plug in through the same seam, so
    distribution, segmented execution and the resil supervisor are
    spec-agnostic (TLC's distributed mode works on any spec;
    launch:4-7)."""

    cdc: object  # pack/unpack/n_fields/nbits
    step: object  # [F] -> (succ [L,F], valid, action, afail, ovf)
    n_lanes: int
    inv_check: object  # [F] -> ok_bits int32 (bit k = invariant k holds)
    inv_codes: tuple  # bit k failing reports this violation code
    initial_vectors: object  # () -> [n0, F] numpy
    labels: tuple  # action id -> display name
    viol_names: dict  # code -> name overrides (VIOLATION_NAMES fallback)
    # optional capabilities (defaults preserve pre-seam constructors)
    gen_counts: object = None  # fn(batch, valid) -> [n_labels] uint32
    lane_action: object = None  # static [L] int32 lane -> action id
    check_deadlock: bool = True  # TLC -deadlock switch
    # optional expand-stage override: fn with make_expand_stage's
    # signature, for backends that can fuse their own expansion half of
    # the pipelined step (the commit half - dedup/enqueue/counters - is
    # engine-owned and backend-independent)
    expand: object = None
    # optional runtime certificate check (certified-bound narrowing,
    # analysis.absint): fn(flat [N, F] int32, valid [N] bool) -> bool
    # scalar "some valid successor violates a claimed bound".  Pure
    # telemetry into the sticky certificate carry/ring column - it
    # feeds no arbitration, so narrowed counts stay comparable
    cert_check: object = None
    # optional device coverage plane (obs.coverage.CoveragePlane,
    # ISSUE 11): a stable per-site table + a count hook the expand
    # stage folds into the cumulative [n_sites] uint32 coverage leaf.
    # Pure telemetry - feeds no control flow, so coverage-on results
    # are bit-for-bit coverage-off results
    coverage: object = None
    # optional state-space reduction (engine.reduce.ReduceOps, ISSUE
    # 18): symmetry canonicalization + POR ample-set pruning applied
    # inside the expand stage - every make_stage_pair consumer inherits
    # both.  None keeps pre-reduction pytree layouts exactly
    reduce: object = None
    # optional state constraint (a cfg's CONSTRAINT, ISSUE 39):
    # fn([N, F] int32 RAW successor rows) -> [N] bool "kept", at batch
    # width: the stage calls it on the candidate rows as they lie (a
    # per-row predicate mapped over them left a unit axis and cost a
    # second relayout of the whole array a step: PERF.md section 6, PR
    # 47).  TLC's rule at
    # the expand / commit seam (make_expand_stage): a successor that
    # fails it counts as generated and is then dropped - never
    # fingerprinted, enqueued or checked.  An engine that does not run
    # make_expand_stage calls require_unconstrained and refuses the
    # model by name: a constrained model never runs unconstrained
    constraint: object = None
    constraint_names: tuple = ()  # the cfg's names, for messages
    # optional action properties (a cfg PROPERTY `I /\\ [][A]_v`: a
    # specification as a property, ISSUE 48): an ActionPropSeam.  TLC's
    # rule for an action property: it is a property of TRANSITIONS, so
    # it is judged on every edge the search generates, to new and to
    # seen states alike - at candidate width in make_expand_stage,
    # before the dedup, beside the constraint.  The same rule as for
    # the constraint: an engine without that stage refuses the model
    # by name (require_unconstrained)
    action_prop: object = None


class ActionPropSeam(NamedTuple):
    """What the expand stage needs of a model's action properties."""

    names: tuple  # the cfg's names, in its order (P of them)
    # fn(src [N, K] int32, succ [N, F] int32) -> (ok, moved), [P, N]
    # bool each: `[A]_v` on the edge, and `v' # v`
    step: object
    # fn(rows [N, F] int32) -> [P, N] bool: I on an initial state
    init: object
    # the K source columns `step` reads (the columns of the variables A
    # and v read unprimed): the block's other columns are not broadcast
    # to candidate width
    src_cols: tuple
    # violation codes, P each: I fails on an initial state / an edge
    # fails `[A]_v`
    init_codes: tuple
    step_codes: tuple


class ConstraintUnsupported(ValueError):
    """A route that cannot honour what a cfg declares for the expand
    stage alone (CONSTRAINT, an action property) was asked to run such
    a model."""


def seam_only(constraint_names=(), action_props=()) -> str:
    """What of a model only the single-device expand stage honours, as
    the refusals name it; "" where there is nothing."""
    return " and ".join(x for x in (
        constraint_names and "CONSTRAINT " + " ".join(constraint_names),
        action_props and "the action property PROPERTY "
        + " ".join(action_props) + " (I /\\ [][A]_v)") if x)


def require_unconstrained(backend, route: str) -> None:
    """Called by every engine and driver that expands states without
    make_expand_stage: refuses a backend with a constraint or an action
    property, naming the route and what the cfg declares, instead of
    visiting states the cfg excludes or leaving edges unjudged."""
    ap = getattr(backend, "action_prop", None)
    what = seam_only(
        getattr(backend, "constraint", None) is not None
        and backend.constraint_names or (),
        ap.names if ap is not None else ())
    if what:
        raise ConstraintUnsupported(
            f"{route} does not honour the cfg's {what}: such a model "
            "runs only on the single-device exhaustive engine (drop "
            f"the option that selected {route})")


class ExpandOut(NamedTuple):
    """Output of the expand stage of one engine step: everything the
    commit stage (sort-compact dedup -> fpset probe/claim -> enqueue +
    counters) needs from a popped block, with the per-candidate kernel /
    invariant work already reduced.  This is the unit the pipelined
    engine stages in its carry so block k's expansion can overlap block
    k-1's commit (PERF.md round 7)."""

    packed: jnp.ndarray  # [chunk*L, W] uint32 packed candidate states
    lo: jnp.ndarray  # [chunk*L] uint32 fingerprint low words
    hi: jnp.ndarray  # [chunk*L] uint32 fingerprint high words
    valid: jnp.ndarray  # [chunk*L] bool
    action: jnp.ndarray  # [chunk*L] int32
    gen: jnp.ndarray  # [n_labels] uint32 per-action generated counts
    viol: jnp.ndarray  # int32 first-wins expand-stage violation code
    viol_state: jnp.ndarray  # [F] int32
    viol_action: jnp.ndarray  # int32
    # bool scalar: some valid successor of this block violated a
    # certified bound (None on backends without a cert_check, so
    # pre-certificate carries/stages keep their exact pytree layout)
    cert: jnp.ndarray = None
    # [n_sites] uint32 per-site coverage visit increments of this block
    # (None on backends without a coverage plane, so coverage-off
    # carries/stages keep their exact pytree layout)
    cov: jnp.ndarray = None
    # [chunk*L, F] int32 RAW (pre-pack) successor fields - present only
    # in deferred-evaluation mode (ISSUE 15), where the commit stage
    # gathers the fresh-insert claimants from it and runs invariants +
    # the certificate there, at probe width instead of candidate width.
    # None in immediate mode, so pre-deferred carries/stages keep their
    # exact pytree layout.
    flat: jnp.ndarray = None
    # bool scalar: the orbit-certification sample of this block failed
    # to re-canonicalize (engine.reduce.ReducePlan.orbit_check; None
    # when symmetry reduction is off, keeping pytree layouts exact)
    sym: jnp.ndarray = None
    # [4] uint32 beside it: this block's valid candidate rows that were
    # canonicalized, those whose representative differs from the
    # candidate, whether the orbit check had a row to sample (0 / 1),
    # and whether it tripped (0 / 1) - summed into the carry's
    # `sym_stat` (CheckResult.canon_rows, canon_moved, sym_cert_checks,
    # sym_cert_trips)
    sym_stat: jnp.ndarray = None
    # uint32 scalar: candidate transitions pruned by the POR ample-set
    # mask in this block (None when POR is off)
    pruned: jnp.ndarray = None
    # [2] uint32: this block's valid successors the backend's state
    # constraint judged, and those it rejected (None on a backend
    # without one).  `valid` above is then what is KEPT; what counts as
    # generated is `valid` plus the rejected, which the commit adds
    # (carry `con_stat`; CheckResult.constraint_rows,
    # constraint_discarded)
    con_stat: jnp.ndarray = None
    # [2] uint32: this block's edges the backend's action properties
    # judged (every kept successor, to new and to seen states alike)
    # and those on which a subscript changed, summed over the
    # properties (None on a backend without one; carry `ap_stat`;
    # CheckResult.action_prop_edges, action_prop_moved); and the SOURCE
    # row [F] int32 of the first edge of the block that fails one
    # (zeros where none does; `viol_state` is then its successor)
    ap_stat: jnp.ndarray = None
    ap_src: jnp.ndarray = None


def make_expand_stage(backend: SpecBackend, chunk: int, check_deadlock,
                      fp_index: int, seed: int, deferred: bool = False):
    """Build the expand half of an engine step over `backend`'s seam:
    unpack -> vmapped successor kernel -> invariants -> pack ->
    MXU fingerprints -> per-action generated counters -> first-wins
    expand-stage violation (invariant > assert > deadlock > slot).

    Returns expand(batch [chunk, F] int32, mask [chunk] bool) ->
    ExpandOut.  Both the fused (unpipelined) body and the pipelined
    body call this one function, so the split cannot drift; a backend
    may override it wholesale via SpecBackend.expand.

    deferred=True (ISSUE 15, a RESOLVED bool - factories resolve the
    tri-state flag via bfs.resolve_deferred) SKIPS the per-candidate
    invariant and certificate evaluation here: the commit stage runs
    them instead, over the fresh-insert claimants only (TLC checks a
    state when it is first generated, and first generation IS the
    distinct fpset insert), via make_deferred_checker.  The stage then
    carries the raw pre-pack fields in ExpandOut.flat for the commit-
    side gather, and its first-wins violation reduce covers only the
    kernel-derived codes (assert > deadlock > slot) - the deferred
    invariant verdict outranks them at the commit merge.  Everything
    else (kernel, packing, MXU fingerprints, per-action counters,
    coverage counting - guard-reach semantics stay pre-dedup) is
    unchanged."""
    if backend.expand is not None:
        if deferred:
            # an override must opt into the deferred contract
            # explicitly (return flat, skip inv/cert)
            return backend.expand(backend, chunk, check_deadlock,
                                  fp_index, seed, deferred=True)
        return backend.expand(backend, chunk, check_deadlock,
                              fp_index, seed)
    cdc = backend.cdc
    F = cdc.n_fields
    step = backend.step
    L = backend.n_lanes
    inv_check = backend.inv_check
    inv_codes = backend.inv_codes
    n_labels = len(backend.labels)
    nbits = cdc.nbits
    ncand = chunk * L
    label_ids = jnp.arange(n_labels, dtype=jnp.int32)
    lane_action = backend.lane_action
    gen_counts_fn = backend.gen_counts
    if check_deadlock is None:
        check_deadlock = backend.check_deadlock
    constraint = backend.constraint
    ap = backend.action_prop
    ap_cols = None if ap is None else jnp.asarray(ap.src_cols, jnp.int32)
    red = backend.reduce
    sym_plan = red.plan if red is not None else None
    por_on = bool(
        red is not None and red.por and red.safe_ids
        and lane_action is not None
    )
    if por_on:
        from .reduce import por_keep

        safe_vec = jnp.asarray(np.array(
            [a in red.safe_ids for a in range(n_labels)], bool
        ))

    def expand(batch, mask):
        succs, valid, action, afail, ovf = jax.vmap(step)(batch)
        valid = valid & mask[:, None]
        afail = afail & valid
        ovf = ovf & valid
        dead = (
            mask & ~valid.any(axis=1) if check_deadlock
            else jnp.zeros(chunk, bool)
        )

        # POR ample-set pruning: AFTER the deadlock test (pruning must
        # never fabricate a deadlock) and after afail/ovf masking (a
        # trapped or asserting transition still halts when postponed)
        pruned = None
        if por_on:
            keep = por_keep(valid, lane_action, safe_vec, n_labels)
            pruned = (valid & ~keep).sum().astype(jnp.uint32)
            valid = keep

        flat = succs.reshape(ncand, F)
        # the state constraint: `counted` is what was generated, `valid`
        # from here on what is kept
        counted = valid
        con_stat = None
        if constraint is not None:
            with jax.named_scope("jaxtlc.constraint"):
                keep = constraint(flat).reshape(chunk, L)
                con_stat = jnp.stack(
                    [valid.sum(), (valid & ~keep).sum()]
                ).astype(jnp.uint32)
                valid = valid & keep
                ovf = ovf & keep
        fvalid = valid.reshape(-1)
        faction = action.reshape(-1)

        # the action properties, on every kept edge (source row
        # `batch[c // L]` of candidate c: the columns the predicate
        # reads, and only those, at candidate width)
        ap_stat = ap_src = None
        ap_bad = []
        if ap is not None:
            ap_src = jnp.zeros(F, jnp.int32)
            with jax.named_scope("jaxtlc.actionprop"):
                src = jnp.repeat(batch[:, ap_cols], L, axis=0)
                ok, moved = ap.step(src, flat)
                ap_stat = jnp.stack([
                    fvalid.sum(), (moved & fvalid[None, :]).sum(),
                ]).astype(jnp.uint32)
                ap_bad = [fvalid & ~ok[k] for k in range(len(ap.names))]

        # symmetry reduction: replace every successor by its orbit
        # representative BEFORE invariants/pack/fingerprints, so the
        # fpset dedups orbits and everything downstream (including the
        # deferred commit-side checker reading ExpandOut.flat) sees
        # canonical states - sound because symfind verified the spec
        # cannot distinguish orbit members
        moved = None
        if sym_plan is not None:
            raw = flat
            flat = sym_plan.canon(raw)
            moved = (fvalid & (flat != raw).any(axis=1)).sum()

        # deferred mode: invariants + certificate run at the commit
        # stage on the fresh-insert claimants only (the distinct-first
        # collapse this stage exists to enable - chunk*L candidate
        # lanes down to ~probe-width rows)
        inv_bad = []
        if not deferred:
            inv = jax.vmap(inv_check)(flat)
            inv_bad = [
                fvalid & ((inv & (1 << k)) == 0)
                for k in range(len(inv_codes))
            ]

        with jax.named_scope("jaxtlc.pack_fp"):
            packed = cdc.pack(flat)
            lo, hi = fp64_words_mxu(packed, nbits, fp_index, seed)

        # runtime certificate: verify the claimed bounds on the RAW
        # (pre-pack) fields of every valid successor - escapes that
        # would wrap into a legal-looking packed word are still caught
        # (deferred mode keeps the pre-pack property by gathering from
        # the raw ExpandOut.flat rows at the commit site)
        cert = None
        if not deferred and backend.cert_check is not None:
            cert = backend.cert_check(flat, fvalid)

        # device coverage plane (ISSUE 11): this block's per-site
        # visit increments, folded into the cumulative carry leaf by
        # the commit stage.  `valid` already carries the pop mask, so
        # the hook sees exactly the lane validity the counters see
        cov = None
        if backend.coverage is not None:
            cov = backend.coverage.count(batch, mask, counted).astype(
                jnp.uint32
            )

        # runtime orbit certification (COL_SYM): one sampled canonical
        # row per body, re-canonicalized through a content-selected
        # permutation - a mismatch means the symmetry plan is not
        # acting as a permutation group and the run's dedup cannot be
        # trusted; the engine latches it into an error verdict
        sym = sym_stat = None
        if sym_plan is not None:
            sym = sym_plan.orbit_check(flat, fvalid)
            sym_stat = jnp.stack(
                [fvalid.sum(), moved, fvalid.any(), sym]
            ).astype(jnp.uint32)

        # per-action generated counters, scatter-free: the backend's
        # factorized hook (KubeAPI dispatch structure, PERF.md item 5)
        # when it has one, a [L, n_labels] fold for static lane
        # dispatches (gen/struct compilers), a per-candidate
        # compare-reduce otherwise
        if gen_counts_fn is not None:
            gen = gen_counts_fn(batch, counted)
        elif lane_action is not None:
            lane_counts = counted.sum(axis=0).astype(jnp.uint32)
            gen = (
                (lane_action[:, None] == label_ids[None, :])
                * lane_counts[:, None]
            ).sum(axis=0).astype(jnp.uint32)
        else:
            gen = (
                (faction[:, None] == label_ids[None, :])
                & counted.reshape(-1)[:, None]
            ).sum(axis=0).astype(jnp.uint32)

        # expand-stage violations, first wins (priority: invariant >
        # assert > deadlock > slot overflow); capacity violations are
        # commit-stage and merged after these by the engine
        viol = jnp.int32(OK)
        viol_state = jnp.zeros(F, jnp.int32)
        viol_action = jnp.int32(-1)
        # each mask with the rows its first hit reports and how many
        # candidates share one of them: a candidate's own row, or the
        # SOURCE state `at // L` of candidate `at` read off the block
        for code, vmask, states, per, acts in (
            *((code, bad, flat, 1, faction)
              for code, bad in zip(inv_codes, inv_bad)),
            *((code, bad, flat, 1, faction)
              for code, bad in zip(ap.step_codes if ap else (), ap_bad)),
            (VIOL_ASSERT, afail.reshape(-1), batch, L, faction),
            (VIOL_DEADLOCK, dead, batch, 1,
             jnp.full(chunk, -1, jnp.int32)),
            (VIOL_SLOT_OVERFLOW, ovf.reshape(-1), batch, L, faction),
        ):
            at = jnp.argmax(vmask)
            hit = vmask.any() & (viol == OK)
            viol = jnp.where(hit, code, viol)
            viol_state = jnp.where(hit, states[at // per], viol_state)
            viol_action = jnp.where(
                hit, acts[at].astype(jnp.int32), viol_action,
            )
            if ap is not None and code in ap.step_codes:
                ap_src = jnp.where(hit, batch[at // L], ap_src)
        return ExpandOut(
            packed=packed, lo=lo, hi=hi, valid=fvalid, action=faction,
            gen=gen, viol=viol, viol_state=viol_state,
            viol_action=viol_action, cert=cert, cov=cov,
            flat=flat if deferred else None,
            sym=sym, sym_stat=sym_stat, pruned=pruned, con_stat=con_stat,
            ap_stat=ap_stat, ap_src=ap_src,
        )

    return expand


def make_deferred_checker(backend: SpecBackend, n: int,
                          probe_width: int = 0,
                          with_cert: bool = True):
    """Commit-stage invariant + certificate evaluation over the fresh-
    insert claimants (ISSUE 15: distinct-first expand).

    Semantics: TLC checks a state's invariants when it is FIRST
    generated, and first generation is by definition a fresh fpset
    insert - so checking only the `is_new` claimant rows preserves the
    verdict of the immediate (per-candidate) evaluation.  The two
    deliberate narrowings, both the fingerprint-collision risk class
    TLC itself carries (MC.out:39-42): (a) a state whose fingerprint
    collides with an already-stored state is never re-checked (TLC
    never re-checks it either - it is not even enqueued), and (b) the
    certificate telemetry sees only fresh claimants, so a bound escape
    whose WRAPPED packed word fingerprints onto an already-seen class
    can evade the cert column for that block (interval lies still
    self-defend through the kept codec trap - analysis.absint; the
    cardinality-lie catch is pinned in tests/test_deferred.py).

    Violation-lane attribution rule (pinned, layout-independent): the
    reported state is the violating fresh claimant with the HIGHEST
    original candidate lane - the same rep convention as the in-batch
    dedup (duplicates resolve to the highest lane), defined on
    original lanes, not compacted positions.  The immediate path
    reports the FIRST violating candidate instead; everything else
    (verdict code, counters, table words, rendered traces) is
    bit-for-bit.

    Returns check(flat [n, F] int32, faction [n] int32 or None,
    is_new_c [n] bool, c_idx [n] int32, nreps) ->
    (viol, viol_state [F], viol_action, cert-or-None, the segments
    walked: the carry's `commit_stat` counts them): the claimant
    slice is walked in probe-width segments (one segment in steady
    state: new-per-chunk ~ chunk <= R), each an [R, F] row gather +
    one R-wide vmapped invariant kernel - the whole point: R ~ 2*chunk
    rows instead of chunk*L candidate lanes."""
    inv_check = backend.inv_check
    inv_codes = backend.inv_codes
    cert_fn = backend.cert_check if with_cert else None
    F = backend.cdc.n_fields
    n_codes = len(inv_codes)
    R = min(probe_width or n, n)
    nseg = (n + R - 1) // R
    pad = nseg * R - n

    def check(flat, faction, is_new_c, c_idx, nreps):
        idx_p = jnp.concatenate(
            [c_idx, jnp.full(pad, n, jnp.int32)]
        ) if pad else c_idx
        new_p = jnp.concatenate(
            [is_new_c, jnp.zeros(pad, bool)]
        ) if pad else is_new_c

        def cond(st):
            return (st[0] * R < nreps) & (st[0] < nseg)

        def body(st):
            seg, bad_any, bad_lane, cert_bad = st
            off = seg * R
            idx = lax.dynamic_slice(idx_p, (off,), (R,))
            fresh = lax.dynamic_slice(new_p, (off,), (R,))
            # this checker's own padding rows carry the sentinel lane
            # n (fresh is False there, so the clamped gather is never
            # consumed)
            lanes = jnp.clip(idx, 0, n - 1)
            rows = flat[lanes]  # [R, F]: the one per-claimant gather
            if n_codes:
                inv = jax.vmap(inv_check)(rows)
            for k in range(n_codes):
                bad = fresh & ((inv & (1 << k)) == 0)
                bad_any = bad_any.at[k].set(bad_any[k] | bad.any())
                bad_lane = bad_lane.at[k].max(
                    jnp.max(jnp.where(bad, idx, -1))
                )
            if cert_fn is not None:
                cert_bad = cert_bad | cert_fn(rows, fresh)
            return seg + 1, bad_any, bad_lane, cert_bad

        trips, bad_any, bad_lane, cert_bad = lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.zeros(n_codes, bool),
             jnp.full(n_codes, -1, jnp.int32), jnp.bool_(False)),
        )

        # first-wins across codes (inv_codes order, matching the
        # immediate reduce); within a code, the max-lane rule above
        viol = jnp.int32(OK)
        lane = jnp.int32(-1)
        for k, code in enumerate(inv_codes):
            hit = bad_any[k] & (viol == OK)
            viol = jnp.where(hit, jnp.int32(code), viol)
            lane = jnp.where(hit, bad_lane[k], lane)
        safe = jnp.clip(lane, 0, n - 1)
        hitv = viol != OK
        viol_state = jnp.where(hitv, flat[safe], jnp.zeros(F, jnp.int32))
        viol_action = jnp.where(
            hitv,
            faction[safe].astype(jnp.int32) if faction is not None
            else jnp.int32(-1),
            jnp.int32(-1),
        )
        cert = cert_bad if cert_fn is not None else None
        return viol, viol_state, viol_action, cert, trips

    return check


def kubeapi_backend(cfg: ModelConfig,
                    coverage: bool = False) -> SpecBackend:
    cdc = get_codec(cfg)
    step = make_kernel(cfg)
    CL, _ = lane_layout(cfg)
    nc = cdc.nc
    n_labels = len(LABELS)
    pc_off = cdc.offsets["pc"]
    label_ids = jnp.arange(n_labels, dtype=jnp.int32)
    APISTART_ID = LABEL_ID["APIStart"]

    def gen_counts(batch, valid):
        # per-action generated counters, factorized through the dispatch
        # structure: every lane of client ci fires that client's current
        # pc label; server lanes are always APIStart (PERF.md item 5 -
        # no scatter-adds over all chunk*L candidates)
        counts = jnp.zeros(n_labels, jnp.uint32)
        for ci in range(nc):
            vc = valid[:, ci * CL : (ci + 1) * CL].sum(axis=1)
            pcs = batch[:, pc_off + ci]
            counts = counts + (
                (pcs[:, None] == label_ids[None, :]) * vc[:, None]
            ).sum(axis=0).astype(jnp.uint32)
        return counts.at[APISTART_ID].add(
            valid[:, nc * CL :].sum().astype(jnp.uint32)
        )

    plane = None
    if coverage:
        # the device site table pinned span-for-span against the host
        # coverage walker (spec.coverage) on the tracked subset
        from ..spec.coverage_device import kubeapi_coverage_plane

        plane = kubeapi_coverage_plane(cfg)
    return SpecBackend(
        cdc=cdc,
        step=step,
        n_lanes=step.n_lanes,
        inv_check=make_invariant_kernel(cfg),
        inv_codes=(VIOL_TYPEOK, VIOL_ONLYONEVERSION),
        initial_vectors=lambda: initial_vectors(cfg),
        labels=LABELS,
        viol_names={},
        gen_counts=gen_counts,
        coverage=plane,
    )


def gen_backend(spec) -> SpecBackend:
    """Generic-frontend backend: the compiled lane kernel + codec feed
    the same engines (VERDICT r4 item 4: -sharded for gen specs)."""
    from ..gen.codec import GenCodec
    from ..gen.engine import VIOL_INVARIANT_BASE
    from ..gen.kernel import initial_field_vectors, make_gen_kernel

    cdc = GenCodec(spec)
    ker = make_gen_kernel(spec, cdc)
    lane_action = jnp.asarray(ker.lane_action, jnp.int32)

    def step(vec):
        succs, valid, ovf = ker.step(vec)
        afail = jnp.zeros_like(valid)  # the gen subset has no Assert
        return succs, valid, lane_action, afail, ovf

    def inv_check(vec):
        bits = jnp.int32(0)
        for k, (_, fn) in enumerate(ker.invariants):
            bits = bits | (fn(vec).astype(jnp.int32) << k)
        return bits

    inv_names = list(spec.invariants.keys())
    return SpecBackend(
        cdc=cdc,
        step=step,
        n_lanes=ker.n_lanes,
        inv_check=inv_check,
        inv_codes=tuple(
            VIOL_INVARIANT_BASE + k for k in range(len(inv_names))
        ),
        initial_vectors=lambda: np.asarray(
            initial_field_vectors(spec, cdc)
        ),
        labels=tuple(a.name for a in spec.actions),
        viol_names={
            VIOL_INVARIANT_BASE + k: f"Invariant {n} is violated"
            for k, n in enumerate(inv_names)
        },
        lane_action=lane_action,
    )
