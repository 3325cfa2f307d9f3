"""Phase-attribution timing tier: measured expand/commit walls.

TLC's MC.out proves where its time went; our trace exporter's per-level
expand/commit lanes were an admitted body-count-proportional SCHEMATIC
inside the host-observed segment wall (obs.trace docstring) - pretty,
not evidence.  ROADMAP #1 (the MXU commit rewrite) needs evidence: a
measured baseline of where commit time goes (sort vs fpset probe vs
enqueue), per BLEST's cost accounting.  This module is that instrument,
in three capture modes of increasing resolution and cost:

1. **Fence mode** (always on with the journal): the supervisor already
   pays a host sync at every segment fence; `segment_phases` turns the
   readback/checkpoint walls it already measures into schema-validated
   `phase` journal events (scope="segment").  Zero device work, zero
   extra syncs - pure host arithmetic, which is why the `--obs-ab`
   harness gates its overhead at <= 0.5%.  The pod driver
   (jaxtlc.dist, ISSUE 20) emits the same rows per host with a `host`
   field, so a merged pod journal's phase walls attribute per process.
2. **`-phase-timing`** (PhasedRuntime): the supervisor swaps its fused
   segment dispatch for a host-fenced step loop whose expand and commit
   halves are SEPARATELY jitted from the very `make_stage_pair` closures
   the fused body composes - so results stay bit-for-bit while every
   level gets measured expand/commit walls (scope="level" `phase`
   events; the trace exporter renders these as measured lanes instead
   of the schematic).  The per-step fences cost real wall time - that
   is the price of resolution, measured in PERF.md round 11 - hence the
   flag.  Unpipelined single-device engines only: fencing the pipelined
   body would serialize the overlap it exists to create, and the
   sharded body's halves live inside one shard_map.
3. **Differential sub-phase profiler** (`subphase_walls`): times nested
   partial jits on a warmed mid-run carry (the tools/profile_v4.py
   technique, packaged as a library) and attributes commit time to
   sort / fpset probe / enqueue+stats by subtraction.  This is the
   cost-model fitter's (tools/costmodel.py) input.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

# canonical phase names (the `phase` field vocabulary; extra names are
# allowed by the schema - views ignore what they don't know)
PHASE_EXPAND = "expand"
PHASE_COMMIT = "commit"
PHASE_DEVICE = "device"
PHASE_READBACK = "readback"


class PhaseRecorder:
    """Accumulates per-level expand/commit walls between fences.

    The phased step loop calls `step(level, expand_s, commit_s)` per
    engine step; the supervisor drains completed measurements at each
    segment fence and journals them as `phase` events.  `reset()` drops
    measurements of a segment that is about to be replayed (retry /
    regrow roll back the carry; its timings must not double-count)."""

    def __init__(self):
        self._levels: Dict[int, Dict[str, float]] = {}
        self._order: List[int] = []

    def step(self, level: int, expand_s: float, commit_s: float) -> None:
        row = self._levels.get(level)
        if row is None:
            row = {"expand": 0.0, "commit": 0.0, "bodies": 0}
            self._levels[level] = row
            self._order.append(level)
        row["expand"] += expand_s
        row["commit"] += commit_s
        row["bodies"] += 1

    def reset(self) -> None:
        self._levels.clear()
        self._order.clear()

    def drain(self) -> List[dict]:
        """Completed measurements as `phase`-event field dicts (oldest
        first, expand before commit per level), then reset.  A level
        spanning two segments yields one row per segment; walls are
        additive, so consumers sum by level."""
        out = []
        for lvl in self._order:
            row = self._levels[lvl]
            for phase in (PHASE_EXPAND, PHASE_COMMIT):
                out.append({
                    "scope": "level", "index": lvl, "phase": phase,
                    "wall_s": round(row[phase], 6),
                    "bodies": row["bodies"],
                })
        self.reset()
        return out


def segment_phases(index: int, wall_s: float,
                   readback_s: float = None) -> List[dict]:
    """Fence-mode `phase` event rows for one supervised segment: the
    device dispatch->fence wall plus the host readback wall the
    supervisor measures around the progress/ring device_get it already
    pays.  Pure host arithmetic over timestamps that already exist."""
    rows = [{"scope": "segment", "index": index, "phase": PHASE_DEVICE,
             "wall_s": round(wall_s, 6)}]
    if readback_s is not None:
        rows.append({"scope": "segment", "index": index,
                     "phase": PHASE_READBACK,
                     "wall_s": round(readback_s, 6)})
    return rows


class PhasedRuntime:
    """`-phase-timing` execution of the single-device engine: the same
    supervision contract as engine.spill.SpillRuntime (the supervisor
    swaps its segment function), but the host sits in the step loop to
    FENCE between the expand and commit halves, crediting each level's
    wall to the half that spent it.

    Bit-exactness: expand_fn/commit_fn are jitted directly from the
    `make_stage_pair` closures the fused body composes, with the same
    pop-cursor arithmetic and the same two-tier small-body dispatch,
    so the carry after N phased steps equals the carry after N fused
    steps bit-for-bit (tests/test_obs.py pins the full signature)."""

    def __init__(self, backend, chunk: int, queue_capacity: int,
                 fp_capacity: int, fp_index: int = None, seed: int = None,
                 fp_highwater: float = None, check_deadlock: bool = None,
                 obs_slots: int = 0, sort_free: bool = None,
                 deferred: bool = None,
                 recorder: Optional[PhaseRecorder] = None):
        import jax

        from ..engine.bfs import (
            DEFAULT_FP_HIGHWATER,
            make_backend_engine,
            make_stage_pair,
            resolve_deferred,
            resolve_sort_free,
        )
        from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED

        fp_index = DEFAULT_FP_INDEX if fp_index is None else fp_index
        seed = DEFAULT_SEED if seed is None else seed
        fp_highwater = (DEFAULT_FP_HIGHWATER if fp_highwater is None
                        else fp_highwater)
        sort_free = resolve_sort_free(sort_free, chunk)
        deferred = resolve_deferred(deferred, chunk)
        self.recorder = recorder if recorder is not None else PhaseRecorder()
        self.chunk = chunk
        # init template through the production factory (jits are lazy)
        init_fn, _, _ = make_backend_engine(
            backend, chunk, queue_capacity, fp_capacity, fp_index, seed,
            fp_highwater=fp_highwater, check_deadlock=check_deadlock,
            donate=False, obs_slots=obs_slots, sort_free=sort_free,
            deferred=deferred,
        )
        self._base_init = init_fn

        def stage_fns(ck):
            pop_expand, commit = make_stage_pair(
                backend, ck, queue_capacity=queue_capacity,
                fp_capacity=fp_capacity, fp_highwater=fp_highwater,
                check_deadlock=check_deadlock, fp_index=fp_index,
                seed=seed, obs_slots=obs_slots, sort_free=sort_free,
                deferred=deferred,
            )
            expand_fn = jax.jit(lambda c: pop_expand(c))
            commit_fn = jax.jit(
                lambda c, ex, n: commit(c, ex, n, c.qhead + n,
                                        c.qhead + n)
            )
            return expand_fn, commit_fn

        # two-tier small-body dispatch mirrors make_backend_engine:
        # big-chunk engines run a small body on narrow level remainders
        # (the host picks the tier from the scalars it fences anyway)
        self._small = chunk // 16 if chunk >= 1 << 14 else 0
        self._big_fns = stage_fns(chunk)
        self._small_fns = stage_fns(self._small) if self._small else None

        def audit_step(c):
            ex, n = self._big_fns[0](c)
            return self._big_fns[1](c, ex, n)

        # donation metadata for the preflight audit (selfcheck "phased")
        audit_step.donate_requested = False
        audit_step.donates_carry = False
        self.audit_step_fn = audit_step

    def init_fn(self):
        return self._base_init()

    def segment_fn(self, ckpt_every: int) -> Callable:
        """seg_fn(carry) -> carry after up to `ckpt_every` steps, fully
        fenced (the supervisor's block_until_ready at the fence is then
        a no-op), recording per-level expand/commit walls."""
        import jax

        rec = self.recorder

        def seg(carry):
            for _ in range(ckpt_every):
                viol, level, level_n, qhead, next_n = map(int, jax.device_get(
                    (carry.viol, carry.level, carry.level_n,
                     carry.qhead, carry.next_n)
                ))
                if viol != 0 or (level_n - qhead <= 0 and next_n == 0):
                    break
                avail = level_n - qhead
                expand_fn, commit_fn = (
                    self._big_fns if (not self._small
                                      or avail >= self.chunk // 2)
                    else self._small_fns
                )
                t0 = time.perf_counter()
                ex, n = expand_fn(carry)
                jax.block_until_ready((ex, n))
                t1 = time.perf_counter()
                carry = commit_fn(carry, ex, n)
                jax.block_until_ready(carry)
                t2 = time.perf_counter()
                rec.step(level, t1 - t0, t2 - t1)
            return carry

        return seg


def _fused_time(body, carry, K: int = 4, reps: int = 3) -> float:
    """Best-of-`reps` seconds per iteration of `body` run K times inside
    one jitted fori_loop (the profile_v4 technique: the loop amortizes
    the dispatch floor so small phases are not all floor)."""
    import jax
    from jax import lax

    @jax.jit
    def loop(c):
        return lax.fori_loop(0, K, lambda _, cc: body(cc), c)

    jax.block_until_ready(loop(carry))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(carry))
        best = min(best, time.perf_counter() - t0)
    return best / K


def subphase_walls(backend, chunk: int, queue_capacity: int,
                   fp_capacity: int, warm_steps: int = 8,
                   K: int = 4, reps: int = 3,
                   check_deadlock: bool = None,
                   sort_free: bool = False,
                   deferred: bool = False) -> Dict[str, float]:
    """Differential sub-phase attribution on a warmed mid-run carry.

    Drives the real engine `warm_steps` steps (realistic frontier block
    + realistic table load), then times nested partial jits and carves
    the step by subtraction:

        kernel        pop + unpack + vmap(step)           (measured)
        inv           the invariant + certificate MACHINERY at its
                      mode's site, measured as an ISOLATED body (not
                      a difference of stage walls - a sub-ms signal
                      drowns in the noise of two ~10 ms probes):
                      immediate = the chunk*L invariant sweep plus its
                      bad-mask and first-wins any/argmax/gather
                      consumers, composed exactly as the expand stage
                      composes them; deferred (ISSUE 15) = the
                      commit-site claimant checker over a real
                      insert's compacted verdicts - same column, so
                      the before/after of the distinct-first collapse
                      lines up
        fp            the expand-stage remainder: pack + MXU
                      fingerprints + counters + the violation reduce
        expand        the full expand stage                 (measured)
        sort          the in-batch dedup stage: the two full-width
                      stable sorts of fpset_insert_sorted, or (under
                      sort_free=True) the hash-slab dedup that
                      replaces them (fpset.slab_dedup) - same column,
                      so before/after cost models line up
        probe         insert - sort: the fpset probe/claim walk
        enqueue       step - expand - insert: enqueue + stats + fencing
        commit        step - expand (deferred mode: includes the
                      claimant checker, which the `inv` column then
                      attributes)
        step          the real fused step_fn                (measured)

    v2 reported `inv_fp` as one wall; v3 (ISSUE 15) splits it so the
    fit can see which half the deferred evaluation actually moves.
    Returns seconds/step per phase.  The committed COSTMODEL baseline
    is a CPU fit (not measured on the chip); the tool records the
    device either way."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..engine.backend import make_expand_stage
    from ..engine.bfs import make_backend_engine
    from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
    from ..engine.fpset import fpset_insert_dedup, slab_dedup

    cdc = backend.cdc
    W = (cdc.nbits + 31) // 32
    F = cdc.n_fields
    L = backend.n_lanes
    ncand = chunk * L
    R = min(2 * chunk, ncand)

    init_fn, _, step_fn = make_backend_engine(
        backend, chunk, queue_capacity, fp_capacity,
        check_deadlock=check_deadlock, donate=False,
        sort_free=sort_free, deferred=deferred,
    )
    carry = init_fn()
    for _ in range(warm_steps):
        carry = step_fn(carry)
    carry = jax.block_until_ready(carry)

    block = lax.dynamic_slice(
        carry.queue, (carry.parity, carry.qhead, jnp.int32(0)),
        (1, chunk, W),
    )[0]
    batch = cdc.unpack(block)
    mask_all = jnp.ones(chunk, bool)
    expand_stage = make_expand_stage(
        backend, chunk, check_deadlock, DEFAULT_FP_INDEX, DEFAULT_SEED,
        deferred=deferred,
    )
    ex = jax.block_until_ready(expand_stage(batch, mask_all))
    step = backend.step

    # kernel: pop + unpack + vmapped successor kernel only (all five
    # outputs folded so XLA cannot slice the kernel - see _consume)
    def b_kernel(c):
        b = cdc.unpack(block ^ c[None, :])
        s, v, a, af, ov = jax.vmap(step)(b)
        return c ^ (
            s.sum().astype(jnp.uint32) + v.sum().astype(jnp.uint32)
            + a.sum().astype(jnp.uint32) + af.sum().astype(jnp.uint32)
            + ov.sum().astype(jnp.uint32)
        )

    t_kernel = _fused_time(b_kernel, jnp.zeros(W, jnp.uint32), K, reps)

    # full-consumption fold: the inv/fp columns are DIFFERENCES of
    # expand-stage probes, so every probe must materialize everything
    # the real stage hands to commit - a partially-consumed ExpandOut
    # lets XLA slice the computation and understate the phase (the v2
    # inv_fp column partly suffered this)
    def _consume(e):
        return (e.packed.sum() + e.lo.sum() + e.hi.sum()
                + e.valid.sum().astype(jnp.uint32)
                + e.action.sum().astype(jnp.uint32) + e.gen.sum()
                + e.viol.astype(jnp.uint32))

    # the invariant-free expand stage (the deferred stage IS the
    # immediate stage minus the invariant/cert machinery); its wall
    # anchors the `fp` column, and its ExpandOut carries the raw
    # fields both isolated inv probes below consume
    stage_noinv = (expand_stage if deferred else make_expand_stage(
        backend, chunk, check_deadlock, DEFAULT_FP_INDEX, DEFAULT_SEED,
        deferred=True,
    ))

    def b_expand(c):
        e = expand_stage(cdc.unpack(block ^ c[None, :]), mask_all)
        return c ^ _consume(e)

    t_expand = _fused_time(b_expand, jnp.zeros(W, jnp.uint32), K, reps)

    if deferred:
        t_expand_noinv = t_expand
        ex_def = ex
    else:
        def b_expand_noinv(c):
            e = stage_noinv(cdc.unpack(block ^ c[None, :]), mask_all)
            return c ^ _consume(e)

        t_expand_noinv = _fused_time(
            b_expand_noinv, jnp.zeros(W, jnp.uint32), K, reps
        )
        ex_def = jax.block_until_ready(stage_noinv(batch, mask_all))

    # the `inv` column: BOTH sites measured as isolated machinery
    # bodies over the same candidate block, not as differences of
    # ~10x-larger stage walls (a diff of two noisy 9 ms measurements
    # drowns a sub-ms signal - the v3 design note).  Immediate: the
    # chunk*L invariant sweep plus its consumers exactly as
    # make_expand_stage composes them (bad masks + the first-wins
    # any/argmax/gather entries).  Deferred: the commit-site claimant
    # checker over a real insert's compacted verdicts.
    flat0 = ex_def.flat
    inv_check = backend.inv_check
    inv_codes = backend.inv_codes

    def b_inv_imm(x):
        fl = flat0 + x
        iv = jax.vmap(inv_check)(fl)
        viol = jnp.int32(0)
        vstate = jnp.zeros(F, jnp.int32)
        vact = jnp.int32(-1)
        for k, code in enumerate(inv_codes):
            bad = ex.valid & ((iv & (1 << k)) == 0)
            hit = bad.any() & (viol == 0)
            viol = jnp.where(hit, jnp.int32(code), viol)
            vstate = jnp.where(hit, fl[jnp.argmax(bad)], vstate)
            vact = jnp.where(
                hit, ex.action[jnp.argmax(bad)].astype(jnp.int32), vact
            )
        cert = jnp.int32(0)
        if backend.cert_check is not None:
            cert = backend.cert_check(fl, ex.valid).astype(jnp.int32)
        return x + viol + vstate.sum() + vact + cert

    t_inv_imm = _fused_time(b_inv_imm, jnp.int32(0), K, reps)

    # sort: the in-batch dedup stage - the two full-width stable sorts,
    # or the hash-slab dedup that replaces them under -sort-free
    idx = jnp.arange(ncand, dtype=jnp.uint32)

    if sort_free:
        def b_sort(x):
            c_lo, _c_hi, _c_ix, _nreps, _fb = slab_dedup(
                ex.lo ^ x, ex.hi, ex.valid, probe_width=R,
            )
            return x + c_lo[0]
    else:
        def b_sort(x):
            s_hi, s_lo, s_idx = lax.sort(
                (ex.hi, ex.lo ^ x, idx), num_keys=2, is_stable=True
            )
            last = jnp.concatenate(
                [(s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1]),
                 jnp.ones(1, bool)]
            )
            rep = ((s_hi != 0) | (s_lo != 0)) & last
            _, c_lo, c_hi, c_idx = lax.sort(
                ((~rep).astype(jnp.uint32), s_lo, s_hi, s_idx),
                num_keys=1, is_stable=True,
            )
            return x + c_lo[0]

    t_sort = _fused_time(b_sort, jnp.uint32(1), K, reps)

    # insert: dedup + probe/claim at real table load (vary lo so the
    # probes are honest; occupancy growth over K reps is negligible)
    def b_ins(c):
        fps_c, x = c
        f2, _, _, _ = fpset_insert_dedup(
            fps_c, ex.lo ^ x, ex.hi, ex.valid,
            probe_width=R, claim_width=R, sort_free=sort_free,
        )
        return (f2, x + jnp.uint32(1))

    t_ins = _fused_time(b_ins, (carry.fps, jnp.uint32(1)), K, reps)

    # deferred mode's inv site, isolated the same way: the claimant
    # checker alone, over the compacted verdicts of a REAL insert of
    # this block (computed once, held constant; the raw fields vary
    # per rep to defeat caching)
    t_inv_def = None
    if deferred:
        from ..engine.backend import make_deferred_checker

        checker = make_deferred_checker(backend, ncand, probe_width=R)
        _, is_new0, c_idx0, nreps0 = jax.block_until_ready(
            fpset_insert_dedup(
                carry.fps, ex.lo, ex.hi, ex.valid,
                probe_width=R, claim_width=R, sort_free=sort_free,
            )
        )

        def b_inv_def(x):
            dv, ds, da, dc = checker(
                flat0 + x, ex.action, is_new0, c_idx0, nreps0
            )
            y = x + dv + ds.sum() + da
            if dc is not None:
                y = y + dc.astype(jnp.int32)
            return y

        t_inv_def = _fused_time(b_inv_def, jnp.int32(0), K, reps)

    # step: the engine's own jitted step (one dispatch per call)
    jax.block_until_ready(step_fn(carry))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        c2 = carry
        for _ in range(K):
            c2 = step_fn(c2)
        jax.block_until_ready(c2)
        best = min(best, time.perf_counter() - t0)
    t_step = best / K

    t_probe = max(t_ins - t_sort, 0.0)
    t_commit = max(t_step - t_expand, 0.0)
    t_fp = max(t_expand_noinv - t_kernel, 0.0)
    if deferred:
        t_inv = t_inv_def
        t_enqueue = max(t_step - t_expand - t_ins - t_inv, 0.0)
    else:
        t_inv = t_inv_imm
        t_enqueue = max(t_step - t_expand - t_ins, 0.0)
    return {
        "kernel": t_kernel,
        "inv": t_inv,
        "fp": t_fp,
        "expand": t_expand,
        "sort": t_sort,
        "probe": t_probe,
        "enqueue": t_enqueue,
        "commit": t_commit,
        "step": t_step,
    }
