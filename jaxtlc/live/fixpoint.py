"""Tensorized survive-set greatest fixpoint over captured edge tensors.

engine.liveness computes the surviving set by Kahn-style peeling over a
host CSR graph - O(E) total work but pointer-chasing and host-resident.
Here the same greatest fixpoint

    survive(s) iff s in H and (terminal(s)
                               or some state-changing successor in survive)

is computed as converging vectorized sweeps: one masked scatter-reduce
over the (src, dst) index tensors per sweep, inside a `lax.while_loop`,
entirely on device.  Sweep count is bounded by the peel depth of H's
subgraph (<= its longest simple path), each sweep is O(E) streaming work
- the BLEST/tensor-BFS trade (arXiv:2512.21967): more total FLOPs, no
per-state host round trips, so multi-million-state zones are feasible.

With a mesh, the edge tensors shard over the same axis as the
fingerprint set and the sweep reduces with a psum
(engine.sharded.sharded_survive_fixpoint).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .capture import CapturedGraph


def has_nonself(graph: CapturedGraph) -> np.ndarray:
    """[V] bool: state has at least one state-changing successor."""
    out = np.zeros(graph.n_states, bool)
    out[graph.src[graph.changed]] = True
    return out


def surviving_set(
    graph: CapturedGraph,
    in_h: np.ndarray,
    mesh=None,
    nonself: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, int]:
    """Greatest fixpoint over the restricted subgraph H.

    Terminal H-states (no state-changing successor anywhere in G) may
    stutter forever under WF_vars(Next); every other survivor needs a
    surviving state-changing successor inside H.  Returns
    (alive bool [V], sweeps)."""
    V = graph.n_states
    if nonself is None:
        nonself = has_nonself(graph)
    terminal = in_h & ~nonself
    # edges that can support survival: state-changing, internal to H
    keep = graph.changed & in_h[graph.src] & in_h[graph.dst]
    src = graph.src[keep]
    dst = graph.dst[keep]
    if mesh is not None and mesh.devices.size > 1:
        from ..engine.sharded import sharded_survive_fixpoint

        return sharded_survive_fixpoint(mesh, V, src, dst, in_h, terminal)

    src_j = jnp.asarray(src)
    dst_j = jnp.asarray(dst)

    @jax.jit
    def run(in_h_j, term_j):
        def body(st):
            alive, _, sweeps = st
            support = jnp.zeros(V, jnp.int32).at[src_j].max(
                alive[dst_j].astype(jnp.int32), mode="drop"
            )
            alive2 = alive & (term_j | (support > 0))
            return alive2, (alive2 != alive).any(), sweeps + 1

        return lax.while_loop(
            lambda st: st[1],
            body,
            (in_h_j, jnp.bool_(True), jnp.int32(0)),
        )

    alive, _, sweeps = jax.block_until_ready(
        run(jnp.asarray(in_h, bool), jnp.asarray(terminal, bool))
    )
    return np.asarray(alive), int(sweeps)


# ---------------------------------------------------------------------------
# P ~> Q under WF_vars(A_1) /\ ... /\ WF_vars(A_K) (struct route, ISSUE 41)
# ---------------------------------------------------------------------------

PREFIX_BLOCK = 512  # rows of the prefix count's triangular product
# rows a block trip of a sweep reads (a multiple of PREFIX_BLOCK; the
# edge store is cut to whole blocks).  On a TPU v5e a trip is 2.3 us
# and 7.5 ns a row: 32 us here (PERF.md section 6, PR 42, Step 0)
SWEEP_BLOCK = 4096
# what a sweep gathers from: one word a state (an element gather's price
# is per element, whatever its width)
REACH_DTYPE = jnp.int32

# what the fair fixpoint gives beside Z (`fair_stats`)
FAIR_STATS = ("survivors", "z_states", "h_states", "p_states",
              "fair_edges", "outer", "sweeps", "swept_rows")


def fair_stats(stats) -> dict:
    """The program's stats vector on the host, by FAIR_STATS.  The
    device counts the rows it read in blocks of SWEEP_BLOCK (an int32
    of rows would wrap at 118 reads of the benchmark's whole store)."""
    out = dict(zip(FAIR_STATS, (int(x) for x in jax.device_get(stats))))
    out["swept_rows"] *= SWEEP_BLOCK
    return out


def prefix_counts(x) -> jnp.ndarray:
    """Exclusive prefix counts of a bool vector, [n + 1] int32 (out[i] =
    how many of x[:i]).  Blocks of PREFIX_BLOCK rows against an upper
    triangle of ones on the matrix unit (0/1 in bfloat16, sums in
    float32: exact), then the blocks' totals: no scan at edge width."""
    n = x.shape[0]
    m = -(-n // PREFIX_BLOCK)
    rows = jnp.zeros(m * PREFIX_BLOCK, jnp.bfloat16).at[:n].set(
        x.astype(jnp.bfloat16)).reshape(m, PREFIX_BLOCK)
    at = jnp.arange(PREFIX_BLOCK)
    upper = (at[:, None] <= at[None, :]).astype(jnp.bfloat16)
    within = jnp.dot(rows, upper,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    incl = (within + before[:, None]).reshape(-1)[:n]
    return jnp.concatenate([jnp.zeros(1, jnp.int32), incl])


def make_fair_fixpoint(n_states: int, n_rows: int, e_rows: int,
                       label_groups):
    """(init_fn, program) of the fair-cycle analysis, for
    `runtime.aot_build`.  The program takes (dst [e_rows], act [e_rows],
    row_start [n_states + 1], n_changed, p [n_rows], h [n_rows]) - the
    capture's changed rows cut to `e_rows` (whole SWEEP_BLOCKs), and the
    property's masks over the enumerator's rows - and gives (Z
    [n_states] bool, stats int32: `fair_stats`).  `label_groups[k]` are
    the label ids of A_k (static).

    G = the states, their changed rows and a stuttering self-loop at
    every state; a_k[e] = act[e] in A_k; en_k[s] = some row out of s
    has a_k; H = `h`.  Z is the greatest subset of H with

        Z = Z /\\ AND_k pre*_Z(acc_k),
        acc_k = the states of Z with ~en_k, and the sources of a_k rows
                that stay in Z

    (Emerson and Lei's nested fixpoint): the states of H that reach,
    inside H, a strongly connected component that is fair - for every k
    a state of it with ~en_k or an a_k row inside it.  With the one
    constraint WF_vars(Next) it is `surviving_set`'s set to the bit.
    `survivors` = |P /\\ Z|: 0 iff P ~> Q holds.

    A set is read at a row's destination (an element gather, the one
    expensive operation here: its price is per row) only for rows whose
    answer the equations use, `read_at`'s blocks of SWEEP_BLOCK rows:

    * acc_k reads Z at A_k's rows alone (`stays` is never used off an
      a_k row).  Their destinations are compacted once a call, in source
      order (one sort), with their own row bounds (the prefix counts of
      a_k at `row_start`); a pass reads them as far as there are any.
    * a sweep of pre* (r2 = r \\/ (z /\\ the sources of rows into r))
      reads r only in the blocks that hold a row out of a candidate, a
      state of z /\\ ~r: for s in r or s outside z the term adds nothing
      to r2, whatever its rows say.  Rows are in source order, so the
      blocks are found from the prefix counts of the candidates at each
      block's first and last source state - no scatter, no gather at
      row width.

    Where every block is read (fairness on every row; an accepting set
    so small that most of Z is a candidate) the trips cost 142.2 ms at
    18.2M rows against 137.1 for one gather at every row (the same
    Step 0; inside the program 9.5 ms a read, review round): no second
    path for that.  Z, `outer` and `sweeps` are those
    of the gather at every row, to the bit; `swept_rows` says how many
    rows were read (with one group, (outer + sweeps) * e_rows where
    every read takes every block).
    The by-source reduction is the rows' prefix counts (`prefix_counts`)
    gathered at the states' row bounds - no scatter."""
    V, E, B = n_states, e_rows, SWEEP_BLOCK
    if E % B:
        raise ValueError(f"e_rows {E} is not whole blocks of {B} rows")
    NB = E // B
    groups = tuple(tuple(int(a) for a in g) for g in label_groups)

    def init_fn():
        return (jnp.zeros(E, jnp.int32), jnp.zeros(E, jnp.int8),
                jnp.zeros(V + 1, jnp.int32), jnp.int32(0),
                jnp.zeros(n_rows, bool), jnp.zeros(n_rows, bool))

    def program(carry):
        with jax.named_scope("jaxtlc.live.fixpoint"):
            return analyse(*carry)

    def analyse(dst, act, row_start, n_changed, p, h):
        rows = jnp.arange(E, dtype=jnp.int32)
        lane = jnp.arange(B, dtype=jnp.int32)
        natural = jnp.arange(NB, dtype=jnp.int32)
        p, h = p[:V], h[:V]

        def by_source(rows_mask, bounds):
            """[V] bool: the state has a row of the mask between its
            bounds."""
            c = prefix_counts(rows_mask)[bounds]
            return c[1:] > c[:-1]

        def read_at(states_mask, at, n_live, blocks, n_blocks):
            """[E] bool: row j < n_live ends in the set (`at[j]` its
            destination), for the rows of the first `n_blocks` entries
            of `blocks`; False at the rows of every other block."""
            words = states_mask.astype(REACH_DTYPE)

            def trip(st):
                mask, t = st
                start = blocks[t] * B
                ends = words[lax.dynamic_slice(at, (start,), (B,))] != 0
                return lax.dynamic_update_slice(
                    mask, ends & (start + lane < n_live), (start,)), t + 1

            mask, _ = lax.while_loop(
                lambda st: st[1] < n_blocks, trip,
                (jnp.zeros(E, bool), jnp.int32(0)))
            return mask

        # site 1, once a call and group: A_k's rows' destinations to
        # the front in source order, and the states' bounds among them
        live = rows < n_changed
        fair = []
        for g in groups:
            a_k = live & functools.reduce(
                jnp.logical_or, [act == lab for lab in g],
                jnp.zeros(E, bool))
            bounds_k = prefix_counts(a_k)[row_start]
            _, dst_k = lax.sort(
                (jnp.where(a_k, rows, jnp.int32(E)), dst), num_keys=1,
                is_stable=False)
            fair.append((dst_k, bounds_k, bounds_k[V],
                         bounds_k[1:] > bounds_k[:-1]))

        # site 2, once a call: each block's first and last source state
        # (the V past the live rows), as bounds into the candidates'
        # prefix counts
        first = jnp.searchsorted(row_start, natural * B, side="right") - 1
        last = jnp.searchsorted(row_start, natural * B + (B - 1),
                                side="right") - 1
        lo, hi = jnp.minimum(first, V), jnp.minimum(last + 1, V)
        has_row = row_start[1:] > row_start[:-1]

        def reach_back(z, acc, sweeps, swept):
            """pre*_z(acc): the states of z with a path inside z to acc."""
            def body(st):
                r, _, n, swept = st
                cand = z & ~r & has_row
                c = prefix_counts(cand)
                hit = c[hi] > c[lo]
                n_hit = hit.sum(dtype=jnp.int32)
                into_r = read_at(
                    r, dst, n_changed,
                    lax.sort(jnp.where(hit, natural, jnp.int32(NB))), n_hit)
                r2 = r | (cand & by_source(into_r, row_start))
                return r2, (r2 != r).any(), n + 1, swept + n_hit

            r, _, sweeps, swept = lax.while_loop(
                lambda st: st[1], body,
                (acc, jnp.bool_(True), sweeps, swept))
            return r, sweeps, swept

        def outer(st):
            z, _, n_outer, sweeps, swept = st
            keep = z
            for dst_k, bounds_k, n_k, en_k in fair:
                n_fair = (n_k + (B - 1)) // B
                stays = read_at(z, dst_k, n_k, natural, n_fair)
                acc = z & (~en_k | by_source(stays, bounds_k))
                r, sweeps, swept = reach_back(z, acc, sweeps,
                                              swept + n_fair)
                keep = keep & r
            return keep, (keep != z).any(), n_outer + 1, sweeps, swept

        z, _, n_outer, sweeps, swept = lax.while_loop(
            lambda st: st[1], outer,
            (h, jnp.bool_(bool(groups)), jnp.int32(0), jnp.int32(0),
             jnp.int32(0)))
        stats = jnp.stack([
            (p & z).sum(dtype=jnp.int32), z.sum(dtype=jnp.int32),
            h.sum(dtype=jnp.int32), p.sum(dtype=jnp.int32),
            sum((n_k for _, _, n_k, _ in fair), jnp.int32(0)),
            n_outer, sweeps, swept])
        return z, stats

    return init_fn, jax.jit(program)
