"""Device-resident random-walk engine (the simulation tier's core).

One ``lax.while_loop`` drives W walker lanes depth-D: every body vmaps
the backend's successor kernel over the lanes' CURRENT states, checks
the invariants on each lane's CHOSEN next state, and advances every
lane by one uniformly random enabled successor.  The choice bits are
counter-based threefry (``jax.random.fold_in``): transition ``d`` of
lane ``l`` under run seed ``s`` consumes exactly
``bits(fold_in(fold_in(PRNGKey(s), l), d))`` (``d = 0`` picks the
lane's initial state), so a lane's whole trajectory is a pure function
of ``(s, l)`` - the property ``sim.replay`` turns into exact host-side
violation replay with zero on-device trace storage.

The walk reuses the exhaustive engines' seam wholesale: any
``engine.backend.SpecBackend`` (struct-compiled, generic, the
hand-tuned KubeAPI kernel) plugs in unchanged - there is no second
compiler path.  The optional distinct-fingerprint estimate reuses the
existing device fpset as a SAMPLING FILTER: chosen states' fingerprints
insert each step, and the running distinct count is a lower-bound
estimate that saturates honestly (``fp_saturated``) instead of halting
the walk when the table fills.

Violation semantics (first wins, deterministic): invariant codes in
backend order > PlusCal assert > deadlock > codec slot overflow, ties
broken by lowest lane index - so the reported ``(lane, step)`` is a
pure function of the seed too, and replay cannot disagree with the
device about WHICH violation fired.
"""

from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..engine.backend import SpecBackend
from ..engine.bfs import (
    DEFAULT_FP_HIGHWATER,
    OK,
    VIOL_ASSERT,
    VIOL_DEADLOCK,
    VIOL_SLOT_OVERFLOW,
    VIOLATION_NAMES,
)
from ..engine.fingerprint import (
    DEFAULT_FP_INDEX,
    DEFAULT_SEED,
    fp64_words_mxu,
)
from ..engine.fpset import fpset_insert_sorted, fpset_new

DEFAULT_WALKERS = 256
DEFAULT_DEPTH = 100
# seed-batch width of a warm SimEngine (the smoke job class's vmapped
# dispatch, mirroring serve.sweep.DEFAULT_WIDTH)
DEFAULT_SIM_WIDTH = 4


class SimCarry(NamedTuple):
    """The whole walk state: W lanes' current states + cursors +
    counters.  Every leaf is fixed-shape, so the carry checkpoints
    through engine.checkpoint's generic pytree snapshots and vmaps
    over a seed batch axis unchanged."""

    key: jnp.ndarray  # uint32[2] threefry key of THIS run's seed
    states: jnp.ndarray  # [W, F] int32 current state per lane
    step_i: jnp.ndarray  # int32 transitions completed (the cursor)
    alive: jnp.ndarray  # [W] bool: lane still walking
    steps_taken: jnp.ndarray  # [W] int32 transitions this lane took
    generated: jnp.ndarray  # uint32 enabled successors examined
    transitions: jnp.ndarray  # uint32 transitions taken (all lanes)
    act_taken: jnp.ndarray  # [n_labels] uint32 actions taken
    viol: jnp.ndarray  # int32 first-wins violation code
    viol_lane: jnp.ndarray  # int32 lane that tripped it
    viol_step: jnp.ndarray  # int32 transition index (0 = an Init state)
    viol_state: jnp.ndarray  # [F] int32 the violating state
    viol_action: jnp.ndarray  # int32 action taken into it (-1 = none)
    # --- optional distinct-fp sampling filter (None = estimate off) ---
    fps: tuple = None  # engine.fpset.FPSet
    distinct: jnp.ndarray = None  # uint32 distinct fps sampled
    fp_sat: jnp.ndarray = None  # bool: filter full, estimate is a floor


class SimResult(NamedTuple):
    """Host-side result of one walk run.  Field names deliberately
    mirror engine.bfs.CheckResult where the fact is the same fact
    (violation / action_generated / wall_s), so the serve plane's
    result plumbing serves both engines; `distinct` is the SAMPLED
    estimate (0 when the filter is off) and `depth` the deepest step
    any lane took - neither claims exhaustiveness."""

    walkers: int
    depth: int  # requested walk depth
    seed: int
    steps: int  # transition rounds completed
    generated: int  # enabled successors examined
    transitions: int  # transitions actually taken
    distinct: int  # sampled distinct-state estimate (0 = filter off)
    fp_saturated: bool
    violation: int
    violation_name: str
    violation_state: np.ndarray
    violation_action: int
    violation_lane: int
    violation_step: int
    action_generated: dict  # {label: times taken} - walk composition
    action_distinct: dict  # always {} (walks do not dedup per action)
    depth_hist: tuple  # sorted (steps_taken, n_lanes) pairs
    halted: int  # lanes that stopped early (deadlock w/ -nodeadlock)
    wall_s: float
    queue_left: int = 0  # CheckResult-compat (walks carry no frontier)


def make_sim_engine(
    backend: SpecBackend,
    walkers: int = DEFAULT_WALKERS,
    depth: int = DEFAULT_DEPTH,
    fp_capacity: int = 0,
    fp_index: int = DEFAULT_FP_INDEX,
    fp_seed: int = DEFAULT_SEED,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    check_deadlock: bool = None,
):
    """Build ``(init_fn, run_fn, step_fn)`` for the random-walk engine.

    ``init_fn(seed, inits=None) -> SimCarry`` seeds every lane with a
    random member of the Init set (`inits` overrides it - the sweep
    path seeds per-config Inits exactly like serve.sweep).  The seed is
    DATA, not geometry: one compile serves every seed, and a stacked
    batch of carries with different seeds vmaps through ``run_fn`` in
    one dispatch.

    ``run_fn(carry)`` walks to depth / first violation / all-lanes-
    halted; ``step_fn(carry)`` advances ONE transition round (the
    supervised driver's segment unit - the (seed, step) cursor lives in
    the carry, so checkpoints are ordinary pytree snapshots).

    ``fp_capacity > 0`` carries the distinct-fp sampling filter: the
    existing device fpset, fed each round with the lanes' chosen
    states.  Pure telemetry - it feeds no control flow, and it
    SATURATES (sticky ``fp_sat``) instead of halting the walk.
    """
    from ..engine.backend import require_unconstrained

    require_unconstrained(backend, "random-walk simulation (-simulate)")

    cdc = backend.cdc
    F = cdc.n_fields
    L = backend.n_lanes
    W = int(walkers)
    n_labels = len(backend.labels)
    inv_check = backend.inv_check
    inv_codes = backend.inv_codes
    nbits = cdc.nbits
    label_ids = jnp.arange(n_labels, dtype=jnp.int32)
    lane_ids = jnp.arange(W, dtype=jnp.uint32)
    if check_deadlock is None:
        check_deadlock = backend.check_deadlock
    sample = fp_capacity > 0
    step = backend.step

    def lane_bits(key, step_i):
        """[W] uint32 choice bits for transition round `step_i`: the
        counter-based draw replay re-derives per lane host-side."""
        def one(lane):
            k = jax.random.fold_in(jax.random.fold_in(key, lane),
                                   step_i)
            return jax.random.bits(k, dtype=jnp.uint32)

        return jax.vmap(one)(lane_ids)

    def sample_insert(fps, distinct, sat, states, mask):
        """Feed the sampling filter; saturate instead of halting."""
        packed = cdc.pack(states)
        lo, hi = fp64_words_mxu(packed, nbits, fp_index, fp_seed)
        would_over = (distinct.astype(jnp.int32) + W) > int(
            fp_capacity * fp_highwater
        )
        sat = sat | would_over
        fps, is_new, _, _, _ = fpset_insert_sorted(
            fps, lo, hi, mask & ~sat
        )
        distinct = distinct + is_new.sum().astype(jnp.uint32)
        return fps, distinct, sat

    def init_fn(seed, inits=None) -> SimCarry:
        if inits is None:
            inits = backend.initial_vectors()
        inits = jnp.asarray(inits)
        n0 = inits.shape[0]
        key = jax.random.PRNGKey(seed)
        # round 0: each lane draws its initial state
        idx = (lane_bits(key, 0) % jnp.uint32(n0)).astype(jnp.int32)
        states = inits[idx]
        # invariants hold on the chosen Init states too (TLC checks
        # them before the first Next application)
        inv0 = jax.vmap(inv_check)(states)
        viol = jnp.int32(OK)
        viol_lane = jnp.int32(-1)
        viol_state = jnp.zeros(F, jnp.int32)
        for k, code in enumerate(inv_codes):
            bad = (inv0 & (1 << k)) == 0
            hit = bad.any() & (viol == OK)
            lane = jnp.argmax(bad).astype(jnp.int32)
            viol = jnp.where(hit, code, viol)
            viol_lane = jnp.where(hit, lane, viol_lane)
            viol_state = jnp.where(hit, states[lane], viol_state)
        extra = {}
        if sample:
            fps, distinct, sat = sample_insert(
                fpset_new(fp_capacity), jnp.uint32(0), jnp.bool_(False),
                states, jnp.ones(W, bool),
            )
            extra = dict(fps=fps, distinct=distinct, fp_sat=sat)
        return SimCarry(
            key=key,
            states=states,
            step_i=jnp.int32(0),
            alive=jnp.ones(W, bool),
            steps_taken=jnp.zeros(W, jnp.int32),
            generated=jnp.uint32(W),
            transitions=jnp.uint32(0),
            act_taken=jnp.zeros(n_labels, jnp.uint32),
            viol=viol,
            viol_lane=viol_lane,
            viol_step=jnp.int32(0),
            viol_state=viol_state,
            viol_action=jnp.int32(-1),
            **extra,
        )

    def body(c: SimCarry) -> SimCarry:
        succs, valid, action, afail, ovf = jax.vmap(step)(c.states)
        valid = valid & c.alive[:, None]
        n_enabled = valid.sum(axis=1).astype(jnp.uint32)
        dead = c.alive & (n_enabled == 0)

        # the uniform draw: idx-th ENABLED lane in lane order (modulo
        # bias at 2^32 is negligible and, crucially, deterministic -
        # the replay derives the identical index from the same bits)
        d = c.step_i + 1
        bits = lane_bits(c.key, d)
        idx = (bits % jnp.maximum(n_enabled, 1)).astype(jnp.int32)
        csum = jnp.cumsum(valid.astype(jnp.int32), axis=1)
        chosen = jnp.argmax(
            (csum == (idx + 1)[:, None]) & valid, axis=1
        ).astype(jnp.int32)
        take = c.alive & (n_enabled > 0)
        rows = jnp.arange(W)
        picked = succs[rows, chosen]
        new_states = jnp.where(take[:, None], picked, c.states)
        acts = action[rows, chosen].astype(jnp.int32)
        ch_afail = take & afail[rows, chosen]
        ch_ovf = take & ovf[rows, chosen]

        # invariants on the chosen next states only: the walk checks
        # the states it VISITS, exactly TLC simulation's discipline
        inv = jax.vmap(inv_check)(new_states)
        inv_bad = [
            take & ((inv & (1 << k)) == 0)
            for k in range(len(inv_codes))
        ]

        # first-wins violation, ties to the lowest lane: priority
        # mirrors the exhaustive expand stage (invariant > assert >
        # deadlock > slot) so the two tiers never name the same bug
        # differently
        viol = c.viol
        viol_lane = c.viol_lane
        viol_step = c.viol_step
        viol_state = c.viol_state
        viol_action = c.viol_action
        dead_mask = dead if check_deadlock else jnp.zeros(W, bool)
        for code, vmask, states_src, has_act in (
            *((code, bad, new_states, True)
              for code, bad in zip(inv_codes, inv_bad)),
            (VIOL_ASSERT, ch_afail, c.states, True),
            (VIOL_DEADLOCK, dead_mask, c.states, False),
            (VIOL_SLOT_OVERFLOW, ch_ovf, c.states, True),
        ):
            hit = vmask.any() & (viol == OK)
            lane = jnp.argmax(vmask).astype(jnp.int32)
            viol = jnp.where(hit, code, viol)
            viol_lane = jnp.where(hit, lane, viol_lane)
            viol_step = jnp.where(hit, d, viol_step)
            viol_state = jnp.where(hit, states_src[lane], viol_state)
            viol_action = jnp.where(
                hit, acts[lane] if has_act else jnp.int32(-1),
                viol_action,
            )

        act_taken = c.act_taken + (
            (acts[:, None] == label_ids[None, :]) & take[:, None]
        ).sum(axis=0).astype(jnp.uint32)
        extra = {}
        if sample:
            fps, distinct, sat = sample_insert(
                c.fps, c.distinct, c.fp_sat, new_states, take
            )
            extra = dict(fps=fps, distinct=distinct, fp_sat=sat)
        return c._replace(
            states=new_states,
            step_i=d,
            alive=c.alive & ~dead,
            steps_taken=jnp.where(take, d, c.steps_taken),
            generated=c.generated + valid.sum().astype(jnp.uint32),
            transitions=c.transitions + take.sum().astype(jnp.uint32),
            act_taken=act_taken,
            viol=viol,
            viol_lane=viol_lane,
            viol_step=viol_step,
            viol_state=viol_state,
            viol_action=viol_action,
            **extra,
        )

    def cond(c: SimCarry):
        return (
            (c.step_i < depth) & (c.viol == OK) & c.alive.any()
        )

    # donate=False throughout: carries are re-seeded per run (cheap at
    # walk sizes), the supervised driver snapshots the last-good carry
    # while the next segment runs, and SimEngine's sequential parity
    # baseline feeds the same carry value twice
    run_fn = jax.jit(lambda c: lax.while_loop(cond, body, c))
    step_fn = jax.jit(lambda c: lax.cond(cond(c), body, lambda x: x, c))
    for fn in (run_fn, step_fn):
        # donation metadata for the engine audit (analysis.engine_audit)
        fn.donate_requested = False
        fn.donates_carry = False
    return init_fn, run_fn, step_fn


def sim_done(carry: SimCarry, depth: int) -> bool:
    """Host-side termination check (the supervised driver's fence)."""
    if int(carry.viol) != OK:
        return True
    return int(carry.step_i) >= depth or not bool(
        np.asarray(carry.alive).any()
    )


def depth_histogram(steps_taken) -> tuple:
    """Sorted (steps, lanes) pairs of the walks' final depths."""
    vals, counts = np.unique(np.asarray(steps_taken), return_counts=True)
    return tuple((int(v), int(n)) for v, n in zip(vals, counts))


def result_from_sim_carry(
    carry: SimCarry, wall_s: float, backend: SpecBackend,
    walkers: int, depth: int, seed: int, viol_names: dict = None,
) -> SimResult:
    labels = backend.labels
    act = np.asarray(carry.act_taken)
    viol = int(carry.viol)
    vname = (viol_names or backend.viol_names or {}).get(viol) or \
        VIOLATION_NAMES.get(viol, f"violation {viol}")
    steps_taken = np.asarray(carry.steps_taken)
    return SimResult(
        walkers=int(walkers),
        depth=int(depth),
        seed=int(seed),
        steps=int(carry.step_i),
        generated=int(carry.generated),
        transitions=int(carry.transitions),
        distinct=int(carry.distinct) if carry.distinct is not None else 0,
        fp_saturated=(bool(carry.fp_sat)
                      if carry.fp_sat is not None else False),
        violation=viol,
        violation_name=vname,
        violation_state=np.asarray(carry.viol_state),
        violation_action=int(carry.viol_action),
        violation_lane=int(carry.viol_lane),
        violation_step=int(carry.viol_step),
        action_generated={
            labels[i]: int(v) for i, v in enumerate(act) if v
        },
        action_distinct={},
        depth_hist=depth_histogram(steps_taken),
        halted=int((~np.asarray(carry.alive)).sum()),
        wall_s=wall_s,
    )


# ---------------------------------------------------------------------------
# struct-model memo (the api / pool / test share one compiled walk)
# ---------------------------------------------------------------------------


def sim_engine_key(model, walkers: int, depth: int, fp_capacity: int,
                   check_deadlock: bool = True) -> tuple:
    """The sim-engine memo/pool key: spec meaning x walk geometry.
    The SEED is deliberately absent - it is run data, so one warm
    engine serves every seed (the smoke job class's whole economics)."""
    from ..struct.cache import model_key

    return ("sim", model_key(model), int(walkers), int(depth),
            int(fp_capacity), bool(check_deadlock))


_SIM_MEMO = None  # built lazily (struct.cache._LRUMemo, cap 8)


def get_sim_engine(model, walkers: int, depth: int,
                   fp_capacity: int = 0, check_deadlock: bool = True):
    """Memoized (backend, init_fn, run_fn, step_fn) for a struct model
    (the struct.cache discipline: repeated sim runs of one model in a
    process never re-trace; jax's jit cache keeps the compiled walk
    alive because the memo returns the SAME closures)."""
    from ..struct.cache import _LRUMemo, get_backend

    global _SIM_MEMO
    if _SIM_MEMO is None:
        _SIM_MEMO = _LRUMemo(8)
    key = sim_engine_key(model, walkers, depth, fp_capacity,
                         check_deadlock)
    hit = _SIM_MEMO.get(key)
    if hit is None:
        backend = get_backend(model, check_deadlock)
        hit = (backend,) + make_sim_engine(
            backend, walkers=walkers, depth=depth,
            fp_capacity=fp_capacity, check_deadlock=check_deadlock,
        )
        _SIM_MEMO.put(key, hit)
    return hit


class SimEngine:
    """A warm smoke-class engine: one compiled walk + one batched AOT
    executable that runs up to `width` (seed, config) lanes per device
    dispatch - serve.sweep.SweepEngine's shape applied to the seed
    axis.  `params` (swept constant domains) additionally promotes the
    swept CONSTANTs to state fields through the SAME sweep compiler, so
    seeds x configs batch in one dispatch with one compile per class."""

    def __init__(
        self,
        model,
        params: Optional[Dict[str, Tuple[int, int]]] = None,
        walkers: int = DEFAULT_WALKERS,
        depth: int = DEFAULT_DEPTH,
        fp_capacity: int = 0,
        check_deadlock: bool = True,
        width: int = DEFAULT_SIM_WIDTH,
    ):
        self.model = model
        self.params = (
            {c: (int(lo), int(hi)) for c, (lo, hi) in params.items()}
            if params else None
        )
        self.walkers = int(walkers)
        self.depth = int(depth)
        self.width = max(1, int(width))
        self.fp_capacity = int(fp_capacity)
        if self.params:
            from ..serve.sweep import sweep_backend

            self.backend = sweep_backend(model, self.params,
                                         check_deadlock)
            init_fn, run_fn, step_fn = make_sim_engine(
                self.backend, walkers=self.walkers, depth=self.depth,
                fp_capacity=self.fp_capacity,
                check_deadlock=check_deadlock,
            )
        else:
            self.backend, init_fn, run_fn, step_fn = get_sim_engine(
                model, self.walkers, self.depth,
                fp_capacity=self.fp_capacity,
                check_deadlock=check_deadlock,
            )
        self._init_jit = jax.jit(init_fn)
        self._run_fn = run_fn
        self._vrun = jax.jit(jax.vmap(run_fn))
        self._aot = None
        self._aot_seq = None

    # -- carries -----------------------------------------------------------

    def carry_for(self, seed: int,
                  values: Optional[Dict[str, int]] = None) -> SimCarry:
        """A fresh walk carry for one (seed, constants-config) lane."""
        if self.params:
            from ..serve.sweep import config_inits

            inits = config_inits(self.model, self.params,
                                 values or {}, self.backend.cdc)
            return self._init_jit(seed, jnp.asarray(inits))
        return self._init_jit(seed)

    def _stack(self, items: List[tuple]):
        if not items:
            raise ValueError("empty sim batch")
        if len(items) > self.width:
            raise ValueError(
                f"{len(items)} sim lanes > width {self.width} "
                "(the scheduler slices batches to width)"
            )
        pad = items + [items[-1]] * (self.width - len(items))
        carries = [self.carry_for(s, v) for s, v in pad]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *carries)

    def _result(self, carry, wall_s: float, seed: int) -> SimResult:
        return result_from_sim_carry(
            carry, wall_s, self.backend, self.walkers, self.depth,
            seed,
        )

    # -- execution ---------------------------------------------------------

    def run(self, items: List[tuple]) -> List[SimResult]:
        """Walk up to `width` (seed, config-values-or-None) lanes in
        ONE device dispatch; per-lane results in submission order."""
        stacked = self._stack(items)
        if self._aot is None:
            self._aot = self._vrun.lower(stacked).compile()
        t0 = time.time()
        out = jax.block_until_ready(self._aot(stacked))
        wall = time.time() - t0
        return [
            self._result(jax.tree.map(lambda x: x[k], out), wall,
                         items[k][0])
            for k in range(len(items))
        ]

    def run_sequential(self, items: List[tuple]) -> List[SimResult]:
        """The parity baseline: the SAME compiled walk, one lane at a
        time (tests pin run() bit-for-bit against this, fpset sampling
        table included)."""
        results = []
        for seed, values in items:
            carry = self.carry_for(seed, values)
            if self._aot_seq is None:
                self._aot_seq = self._run_fn.lower(carry).compile()
            t0 = time.time()
            out = jax.block_until_ready(self._aot_seq(carry))
            results.append(self._result(out, time.time() - t0, seed))
        return results
