"""Device BFS engine for generic specs (E1) - v4 skeleton, generic lanes.

Same fused design as the tuned KubeAPI engine (engine/bfs.py): ping-pong
packed level buffers, sort-compacted dedup against the bucketized
fingerprint table, contiguous enqueue - reusing fpset and the MXU
fingerprint path verbatim.  Per-action statistics use the static
lane -> action map (no scatters).  The step is compiled from the spec's
ASTs once (gen.kernel), so arbitrary subset specs get the same
single-dispatch exhaustive loop the hand-built KubeAPI kernel gets.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..engine.bfs import (
    OK,
    VIOL_DEADLOCK,
    VIOL_FPSET_FULL,
    VIOL_QUEUE_FULL,
    VIOL_SLOT_OVERFLOW,
    VIOLATION_NAMES,
    CheckResult,
)
from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED, fp64_words_mxu
from ..engine.fpset import enqueue_order, fpset_insert_sorted, fpset_new
from .codec import GenCodec
from .ir import GenSpec
from .kernel import GenKernel, initial_field_vectors, make_gen_kernel

VIOL_INVARIANT_BASE = 100  # violation code 100+k = k-th invariant


class GenCarry(NamedTuple):
    fps: tuple
    queue: jnp.ndarray  # [2, qcap + 2*chunk, W] uint32
    parity: jnp.ndarray
    qhead: jnp.ndarray
    level_n: jnp.ndarray
    next_n: jnp.ndarray
    level: jnp.ndarray
    depth: jnp.ndarray
    generated: jnp.ndarray
    distinct: jnp.ndarray
    act_gen: jnp.ndarray  # [n_actions] uint32
    act_dist: jnp.ndarray  # [n_actions] uint32 (new states per action)
    viol: jnp.ndarray
    viol_state: jnp.ndarray  # [F] int32


def make_gen_engine(
    spec: GenSpec,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    check_deadlock: bool = True,
):
    cdc = GenCodec(spec)
    ker = make_gen_kernel(spec, cdc)
    F = cdc.n_fields
    W = cdc.n_words
    L = ker.n_lanes
    nbits = cdc.nbits
    qcap = queue_capacity
    n_actions = len(spec.actions)
    lane_action = jnp.asarray(ker.lane_action, jnp.int32)
    inv_fns = ker.invariants

    def init_fn() -> GenCarry:
        inits = jnp.asarray(initial_field_vectors(spec, cdc))
        n0 = inits.shape[0]
        assert n0 <= chunk and n0 <= qcap
        packed0 = cdc.pack(inits)
        queue = (
            jnp.zeros((2, qcap + 2 * chunk, W), jnp.uint32)
            .at[0, :n0]
            .set(packed0)
        )
        lo, hi = fp64_words_mxu(packed0, nbits, fp_index, seed)
        fps, is_new_c, _, _, _ = fpset_insert_sorted(
            fpset_new(fp_capacity), lo, hi, jnp.ones(n0, bool)
        )
        # initial-state invariant check
        viol = jnp.int32(OK)
        viol_state = jnp.zeros(F, jnp.int32)
        for k, (_, fn) in enumerate(inv_fns):
            bad = ~jax.vmap(fn)(inits)
            hit = bad.any() & (viol == OK)
            viol = jnp.where(hit, VIOL_INVARIANT_BASE + k, viol)
            viol_state = jnp.where(hit, inits[jnp.argmax(bad)], viol_state)
        return GenCarry(
            fps=fps,
            queue=queue,
            parity=jnp.int32(0),
            qhead=jnp.int32(0),
            level_n=jnp.int32(n0),
            next_n=jnp.int32(0),
            level=jnp.int32(1),
            depth=jnp.int32(1),
            generated=jnp.uint32(n0),
            distinct=is_new_c.sum().astype(jnp.uint32),
            act_gen=jnp.zeros(n_actions, jnp.uint32),
            act_dist=jnp.zeros(n_actions, jnp.uint32),
            viol=viol,
            viol_state=viol_state,
        )

    ncand = chunk * L
    R = min(2 * chunk, ncand)
    A = min(2 * chunk, ncand)

    def body(c: GenCarry) -> GenCarry:
        avail = c.level_n - c.qhead
        n = jnp.minimum(chunk, avail)
        rows = jnp.arange(chunk, dtype=jnp.int32)
        mask = rows < n

        block = lax.dynamic_slice(
            c.queue, (c.parity, c.qhead, jnp.int32(0)), (1, chunk, W)
        )[0]
        batch = cdc.unpack(block)

        succs, valid, ovf = jax.vmap(ker.step)(batch)
        valid = valid & mask[:, None]
        ovf = ovf & mask[:, None]
        # deadlock = no successor AT ALL (valid lanes include stutter
        # self-loops, so a Terminating-style action suppresses this)
        dead = mask & ~valid.any(axis=1) if check_deadlock else (
            jnp.zeros(chunk, bool)
        )

        flat = succs.reshape(ncand, F)
        fvalid = valid.reshape(-1)

        # invariants on candidates
        viol = c.viol
        viol_state = c.viol_state
        for k, (_, fn) in enumerate(inv_fns):
            bad = fvalid & ~jax.vmap(fn)(flat)
            hit = bad.any() & (viol == OK)
            viol = jnp.where(hit, VIOL_INVARIANT_BASE + k, viol)
            viol_state = jnp.where(hit, flat[jnp.argmax(bad)], viol_state)

        packed = cdc.pack(flat)
        lo, hi = fp64_words_mxu(packed, nbits, fp_index, seed)

        fp_full = (c.distinct.astype(jnp.int32) + ncand) > int(
            fp_capacity * 0.85
        )
        insert_mask = fvalid & ~fp_full
        fps, is_new_c, c_idx, nreps, _ = fpset_insert_sorted(
            c.fps, lo, hi, insert_mask, probe_width=R, claim_width=R
        )
        n_new = is_new_c.sum().astype(jnp.int32)
        q_full = c.next_n + n_new > qcap

        # enqueue new states in original lane order (deterministic); the
        # A-wide segment loop covers bursts where one chunk yields more
        # than A distinct new states (same pattern as bfs.py enq_body -
        # a single A-wide write would silently drop the overflow)
        e_idx, _ = enqueue_order(is_new_c, c_idx, nreps, R)
        e_idx_p = jnp.concatenate([e_idx, jnp.zeros(A, jnp.uint32)])

        def enq_cond(st):
            _, s = st
            return s * A < n_new

        def enq_body(st):
            queue, s = st
            offs = s * A
            idx_a = lax.dynamic_slice(e_idx_p, (offs,), (A,)).astype(
                jnp.int32
            )
            rows_a = packed[idx_a]
            woff = jnp.minimum(c.next_n + offs, qcap)
            queue = lax.dynamic_update_slice(
                queue, rows_a[None], (1 - c.parity, woff, jnp.int32(0))
            )
            return queue, s + 1

        queue, _ = lax.while_loop(enq_cond, enq_body, (c.queue, jnp.int32(0)))

        # per-action generated counts: static lane -> action compare-reduce
        lane_onehot = (
            lane_action[:, None] == jnp.arange(n_actions)[None, :]
        )  # [L, n_actions]
        lane_counts = valid.sum(axis=0).astype(jnp.uint32)  # [L]
        act_gen = c.act_gen + (
            lane_onehot * lane_counts[:, None]
        ).sum(axis=0).astype(jnp.uint32)

        # per-action distinct counts: map each new entry's lane straight
        # to its action (tiny gather + [ncand, n_actions] compare-reduce,
        # the bfs.py enq_body pattern - no [ncand, L] intermediate)
        new_act = jnp.where(
            jnp.arange(ncand) < n_new,
            lane_action[e_idx.astype(jnp.int32) % L],
            -1,
        )
        act_dist = c.act_dist + (
            new_act[:, None] == jnp.arange(n_actions)[None, :]
        ).sum(axis=0).astype(jnp.uint32)

        generated = c.generated + valid.sum().astype(jnp.uint32)
        distinct = c.distinct + n_new.astype(jnp.uint32)

        # an overflowing lane reports its SOURCE state, `batch[at // L]`
        for code, vmask, per in (
            (VIOL_SLOT_OVERFLOW, ovf.reshape(-1), L),
            (VIOL_DEADLOCK, dead, 1),
        ):
            hit = vmask.any() & (viol == OK)
            viol = jnp.where(hit, code, viol)
            viol_state = jnp.where(
                hit, batch[jnp.argmax(vmask) // per], viol_state
            )
        hit = fp_full & fvalid.any() & (viol == OK)
        viol = jnp.where(hit, VIOL_FPSET_FULL, viol)
        hit = q_full & (viol == OK)
        viol = jnp.where(hit, VIOL_QUEUE_FULL, viol)

        qhead = c.qhead + n
        next_n = jnp.minimum(c.next_n + n_new, qcap)
        level_done = qhead >= c.level_n
        advance = level_done & (next_n > 0)
        parity = jnp.where(level_done, 1 - c.parity, c.parity)
        level_n = jnp.where(level_done, next_n, c.level_n)
        next_n = jnp.where(level_done, 0, next_n)
        qhead = jnp.where(level_done, 0, qhead)
        level = jnp.where(advance, c.level + 1, c.level)
        depth = jnp.maximum(c.depth, level)

        return GenCarry(
            fps=fps, queue=queue, parity=parity, qhead=qhead,
            level_n=level_n, next_n=next_n, level=level, depth=depth,
            generated=generated, distinct=distinct, act_gen=act_gen,
            act_dist=act_dist,
            viol=viol, viol_state=viol_state,
        )

    def cond(c: GenCarry):
        return ((c.qhead < c.level_n) | (c.next_n > 0)) & (c.viol == OK)

    @jax.jit
    def run_fn(c: GenCarry) -> GenCarry:
        return lax.while_loop(cond, body, c)

    return init_fn, run_fn, cdc, ker


def violation_name(spec: GenSpec, code: int) -> str:
    if code >= VIOL_INVARIANT_BASE:
        names = list(spec.invariants.keys())
        k = code - VIOL_INVARIANT_BASE
        if k < len(names):
            return f"Invariant {names[k]} is violated"
        return "Invariant violated"
    return VIOLATION_NAMES[code]


def check_gen(
    spec: GenSpec,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    check_deadlock: bool = True,
) -> CheckResult:
    """Exhaustive device check of a generic spec (AOT-timed like bfs.check)."""
    init_fn, run_fn, cdc, ker = make_gen_engine(
        spec, chunk, queue_capacity, fp_capacity, fp_index, seed,
        check_deadlock,
    )
    carry = init_fn()
    compiled = run_fn.lower(carry).compile()
    t0 = time.time()
    out = jax.block_until_ready(compiled(carry))
    wall = time.time() - t0
    act_gen = np.asarray(out.act_gen)
    code = int(out.viol)
    return CheckResult(
        generated=int(out.generated),
        distinct=int(out.distinct),
        depth=int(out.depth),
        queue_left=int(out.level_n) - int(out.qhead) + int(out.next_n),
        violation=code,
        violation_name=violation_name(spec, code),
        violation_state=np.asarray(out.viol_state),
        violation_action=-1,
        action_generated={
            spec.actions[i].name: int(v)
            for i, v in enumerate(act_gen) if v
        },
        action_distinct={
            spec.actions[i].name: int(v)
            for i, v in enumerate(np.asarray(out.act_dist)) if v
        },
        wall_s=wall,
        iterations=-1,
    )
