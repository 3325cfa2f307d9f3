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
# what a sweep gathers from: one word a state (an element gather's price
# is per element, whatever its width)
REACH_DTYPE = jnp.int32

# the stats vector the fair fixpoint gives beside Z
FAIR_STATS = ("survivors", "z_states", "h_states", "p_states",
              "fair_edges", "outer", "sweeps")


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
    capture's changed rows cut to `e_rows`, and the property's masks
    over the enumerator's rows - and gives (Z [n_states] bool, stats
    [len(FAIR_STATS)] int32).  `label_groups[k]` are the label ids of
    A_k (static).

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

    A sweep is one element gather at row width (a state's word of the
    set at each row's destination), the rows' prefix counts
    (`prefix_counts`) and one gather of those at state width (rows are
    in source order: a state's support is the count between its row
    bounds) - no scatter.  An outer pass costs one more such gather
    (which rows stay in Z)."""
    V, E = n_states, e_rows
    groups = tuple(tuple(int(a) for a in g) for g in label_groups)

    def init_fn():
        return (jnp.zeros(E, jnp.int32), jnp.zeros(E, jnp.int8),
                jnp.zeros(V + 1, jnp.int32), jnp.int32(0),
                jnp.zeros(n_rows, bool), jnp.zeros(n_rows, bool))

    def program(carry):
        with jax.named_scope("jaxtlc.live.fixpoint"):
            return analyse(*carry)

    def analyse(dst, act, row_start, n_changed, p, h):
        live = jnp.arange(E, dtype=jnp.int32) < n_changed
        p, h = p[:V], h[:V]

        def by_source(rows_mask):
            """[V] bool: the state has a row of the mask."""
            c = prefix_counts(rows_mask)[row_start]
            return c[1:] > c[:-1]

        def at_dst(states_mask):
            """[E] bool: the row is live and ends in the set."""
            return live & (states_mask.astype(REACH_DTYPE)[dst] != 0)

        a = [live & functools.reduce(
            jnp.logical_or, [act == lab for lab in g],
            jnp.zeros(E, bool)) for g in groups]
        en = [by_source(a_k) for a_k in a]

        def reach_back(z, acc, sweeps):
            """pre*_z(acc): the states of z with a path inside z to acc."""
            def body(st):
                r, _, n = st
                r2 = r | (z & by_source(at_dst(r)))
                return r2, (r2 != r).any(), n + 1

            r, _, sweeps = lax.while_loop(
                lambda st: st[1], body, (acc, jnp.bool_(True), sweeps))
            return r, sweeps

        def outer(st):
            z, _, n_outer, sweeps = st
            stays = at_dst(z)
            keep = z
            for a_k, en_k in zip(a, en):
                acc = z & (~en_k | by_source(a_k & stays))
                r, sweeps = reach_back(z, acc, sweeps)
                keep = keep & r
            return keep, (keep != z).any(), n_outer + 1, sweeps

        z, _, n_outer, sweeps = lax.while_loop(
            lambda st: st[1], outer,
            (h, jnp.bool_(bool(groups)), jnp.int32(0), jnp.int32(0)))
        stats = jnp.stack([
            (p & z).sum(dtype=jnp.int32), z.sum(dtype=jnp.int32),
            h.sum(dtype=jnp.int32), p.sum(dtype=jnp.int32),
            sum((a_k.sum(dtype=jnp.int32) for a_k in a), jnp.int32(0)),
            n_outer, sweeps])
        return z, stats

    return init_fn, jax.jit(program)
