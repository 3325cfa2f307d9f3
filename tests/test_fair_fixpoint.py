"""The fair fixpoint (live/fixpoint.make_fair_fixpoint, ISSUE 42) against
Emerson and Lei's nested fixpoint in plain sets and loops: Z bit for
bit, every entry of FAIR_STATS - `outer` and `sweeps` counted as the
form that reads every row at every sweep counts them, `swept_rows` as
the blocks that hold a row the equations use.  ONE compiled shape (V, e_rows
and the three label groups fixed); the graphs, `n_changed`, P and H are
data."""

import numpy as np
import pytest

from jaxtlc.live.fixpoint import (
    FAIR_STATS, PREFIX_BLOCK, SWEEP_BLOCK, fair_stats, make_fair_fixpoint)

B = SWEEP_BLOCK
NB = 16
E_ROWS = NB * B
V = 4000
N_ROWS = V + 24  # the enumerator's array is longer than the states
GROUPS = ((0,), (1,), (2, 4))  # label 3: an action under no fairness


# -- the reference: sets and loops, nothing of jaxtlc ----------------------


def emerson_lei(rows, groups, h, p, block):
    """`rows`: the store, [(src, dst, act)] in source order.  Returns
    (Z as a set, stats by name, the most blocks one read took).  A sweep
    is synchronous (r2 from r); `outer` and `sweeps` count the last,
    unchanged round too.  A read of a set at rows' destinations costs
    the blocks of `block` rows that hold a row it asks about."""
    out = {}
    for j, (s, d, a) in enumerate(rows):
        out.setdefault(s, []).append((j, d, a))
    cost = dict(blocks=0, widest=0)

    def read(blocks):
        cost["blocks"] += len(blocks)
        cost["widest"] = max(cost["widest"], len(blocks))

    z, outer, sweeps = set(h), 0, 0
    while groups:
        outer += 1
        keep = set(z)
        for g in groups:
            n_k = sum(1 for _, _, a in rows if a in g)
            read(range(-(-n_k // block)))
            acc = set()
            for s in z:
                fair = [d for _, d, a in out.get(s, ()) if a in g]
                if not fair or any(d in z for d in fair):
                    acc.add(s)
            r = acc
            while True:
                sweeps += 1
                cand = [s for s in z - r if s in out]
                read({j // block for s in cand for j, _, _ in out[s]})
                r2 = r | {s for s in cand
                          if any(d in r for _, d, _ in out[s])}
                same, r = r2 == r, r2
                if same:
                    break
            keep &= r
        same, z = keep == z, keep
        if same:
            break
    stats = dict(
        survivors=len(p & z), z_states=len(z), h_states=len(h),
        p_states=len(p),
        fair_edges=sum(1 for _, _, a in rows for g in groups if a in g),
        outer=outer, sweeps=sweeps, swept_rows=cost["blocks"] * block)
    return z, stats, cost["widest"]


# -- graphs ------------------------------------------------------------------


class Build:
    """States in id order, each with its rows; `filler` pads the store
    with accepting states (rows of label 3 to the terminal state 0)."""

    def __init__(self):
        self.rows_of = [[]]  # state 0: no row, accepting for every k
        self.q = set()       # the states outside H

    def state(self, rows=(), q=False):
        self.rows_of.append(list(rows))
        if q:
            self.q.add(len(self.rows_of) - 1)
        return len(self.rows_of) - 1

    @property
    def next_id(self):
        return len(self.rows_of)

    @property
    def next_row(self):
        return sum(len(r) for r in self.rows_of)

    def filler(self, rows, each=24):
        while rows > 0:
            n = min(each, rows)
            self.state([(0, 3)] * n)
            rows -= n

    def fill_to_row(self, row):
        assert row >= self.next_row, (row, self.next_row)
        self.filler(row - self.next_row)

    def store(self):
        return [(s, d, a) for s, rows in enumerate(self.rows_of)
                for d, a in rows]


def chain(g, length, tempt=None):
    """s_1 -> ... -> s_length -> state 0 by rows of label 3; with `tempt`
    every state also has a row of that label into a Q state, so that it
    is no accepting state of that group and waits for pre*."""
    first = g.next_id + (1 if tempt is not None else 0)
    bad = g.state(q=True) if tempt is not None else None
    for i in range(length):
        nxt = first + i + 1 if i + 1 < length else 0
        rows = [(nxt, 3)]
        if tempt is not None:
            rows.append((bad, tempt))
        g.state(rows)
    return first


def case_groups(k):
    def make():
        g = Build()
        rng = np.random.default_rng(40 + k)
        labels = list(range(k)) + [3]
        n = 600
        base = g.next_id
        for _ in range(n):
            deg = int(rng.integers(0, 5))
            g.state([(base + int(rng.integers(0, n)),
                      int(rng.choice(labels))) for _ in range(deg)],
                    q=bool(rng.random() < 0.15))
        return g
    return make


def case_none():
    g = Build()
    first = chain(g, 30)
    g.state([(first, 3)], q=True)
    g.filler(2000)
    return g


def case_unfair_cycle():
    # a cycle of label-3 rows inside H whose every state has a label-0
    # row leaving H: enabled for ever, never taken inside Z, so no state
    # of it is accepting.  Every state keeps a successor in the set (a
    # peel by counts keeps the cycle); the fair fixpoint removes it and
    # the tail that leads only into it.
    g = Build()
    bad = g.state(q=True)
    first = g.next_id
    for i in range(7):
        g.state([(first + (i + 1) % 7, 3), (bad, 0)])
    g.state([(first, 3), (bad, 0)])  # leads into the cycle alone
    ok = g.next_id                   # a fair cycle beside it: stays
    for i in range(5):
        g.state([(ok + (i + 1) % 5, 0)])
    g.filler(500)
    return g


def case_chain():
    g = Build()
    chain(g, 40, tempt=0)
    g.filler(300)
    return g


def case_sparse():
    # a long store, a handful of candidates far apart
    g = Build()
    for at in (1000, 3 * B + 17, 7 * B + 2000, 12 * B + 5):
        g.fill_to_row(at)
        chain(g, 3, tempt=1)
    g.fill_to_row(14 * B + 100)
    return g


def case_dense():
    # candidates in every block of the store for several sweeps: chains
    # of 6 in each
    g = Build()
    for b in range(NB):
        g.fill_to_row(b * B + 50)
        for _ in range(3):
            chain(g, 6, tempt=0)
    g.fill_to_row(NB * B - 40)
    return g


def case_all_fair():
    # every row an A_k row (the WF_vars(Next) shape), over the whole
    # store
    g = Build()
    bad = g.state(q=True)
    # peeled in two passes: states that only leave H, and states that
    # only reach those
    for _ in range(40):
        g.state([(bad, 0)] * 5)
    for i in range(20):
        g.state([(2 + i, 0), (3 + i, 0)])
    n = 2000
    base = g.next_id
    rng = np.random.default_rng(7)
    for i in range(n):
        g.state([(base + int(rng.integers(0, n)), 0)
                 for _ in range(int(rng.integers(4, 60)))],
                q=bool(rng.random() < 0.1))
    assert E_ROWS >= g.next_row > (NB - 1) * B
    return g


def case_rowless():
    # states without rows: the first, the last, runs of them where a
    # block begins and ends, and between the states of a chain
    g = Build()
    for _ in range(5):
        g.state()
    g.fill_to_row(B - 3)
    bad = g.state(q=True)
    a = g.next_id
    # a -> (rowless) -> b -> c, rows straddling the block's end
    g.state([(a + 3, 3), (bad, 2)])
    g.state()
    g.state()
    g.state([(a + 6, 3), (a + 6, 3), (a + 6, 3), (bad, 2)])
    g.state()
    g.state()
    g.state([(0, 3), (bad, 4)])
    for _ in range(9):
        g.state()
    g.filler(700)
    for _ in range(4):
        g.state()
    return g


def case_ragged():
    # n_changed a multiple of neither block; the candidates' rows are
    # the store's last, in a block that is mostly dead rows
    g = Build()
    g.fill_to_row(3 * B + 700)
    chain(g, 4, tempt=0)
    assert g.next_row % PREFIX_BLOCK and g.next_row % B
    return g


def case_straddle():
    # one candidate's rows lie in two blocks and only its last row, in
    # the second block, leads into r
    g = Build()
    g.fill_to_row(2 * B - 5)
    bad = g.state(q=True)
    s = g.next_id
    g.state([(bad, 1)] * 4 + [(s, 3)] * 3 + [(s + 1, 3)])
    g.state([(0, 3), (bad, 1)])
    g.filler(200)
    return g


# blocks read, counted by hand.  A group with no row reads none and
# closes in one sweep with no candidate; the group whose rows tempt the
# chains reads its one block of A_k rows, then each sweep that has a
# candidate reads the blocks that hold one:
#   sparse   1 + 3 sweeps x 4 chains, a block each
#   straddle 1 + 2 sweeps x the candidate's 2 blocks
#   ragged   1 + 4 sweeps x the store's last block
SWEPT_BLOCKS = {"sparse": 13, "straddle": 5, "ragged": 5}

CASES = {
    "k1": case_groups(1), "k2": case_groups(2), "k3": case_groups(3),
    "no-group-has-a-row": case_none, "unfair-cycle": case_unfair_cycle,
    "chain": case_chain, "sparse": case_sparse, "dense": case_dense,
    "every-row-fair": case_all_fair, "rowless": case_rowless,
    "ragged": case_ragged, "straddle": case_straddle,
}


@pytest.fixture(scope="module")
def program():
    _, prog = make_fair_fixpoint(V, N_ROWS, E_ROWS, GROUPS)
    return prog


def arrays(g):
    """The capture's arrays for a graph: the dead rows of the store hold
    what a last block's sort leaves there (other rows' destinations, a
    -1, fair labels), the masks' rows past the states are False."""
    import jax.numpy as jnp

    rows = g.store()
    n = len(rows)
    assert g.next_id <= V and n <= E_ROWS, (g.next_id, n)
    rng = np.random.default_rng(n)
    dst = rng.integers(-1, V, E_ROWS).astype(np.int32)
    act = rng.integers(0, 5, E_ROWS).astype(np.int8)
    dst[:n] = [d for _, d, _ in rows]
    act[:n] = [a for _, _, a in rows]
    deg = np.zeros(V, np.int64)
    deg[:g.next_id] = [len(r) for r in g.rows_of]
    row_start = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    h = np.zeros(N_ROWS, bool)
    h[:g.next_id] = True
    h[list(g.q)] = False
    p = np.zeros(N_ROWS, bool)
    p[:g.next_id:3] = True
    carry = (jnp.asarray(dst), jnp.asarray(act), jnp.asarray(row_start),
             jnp.int32(n), jnp.asarray(p), jnp.asarray(h))
    return rows, carry, set(np.flatnonzero(h)), set(np.flatnonzero(p))


@pytest.mark.parametrize("name", list(CASES))
def test_z_and_every_stat_equal_emerson_and_lei(name, program):
    g = CASES[name]()
    rows, carry, h, p = arrays(g)
    z, stats = program(carry)
    stats = fair_stats(stats)
    assert tuple(stats) == FAIR_STATS
    want_z, want, widest = emerson_lei(rows, GROUPS, h, p, B)
    assert set(np.flatnonzero(np.asarray(z))) == want_z
    assert stats == want
    # what the case is there for
    every_row = (stats["outer"] + stats["sweeps"]) * E_ROWS
    if name == "no-group-has-a-row":
        assert want_z == h and stats["fair_edges"] == 0
    if name == "unfair-cycle":
        cycle = set(range(2, 10))
        assert cycle <= h and not cycle & want_z
        assert all(any(d in h for s, d, _ in rows if s == t)
                   for t in cycle)
    if name == "chain":
        assert stats["sweeps"] >= 40
    if name in ("sparse", "chain", "straddle", "ragged", "rowless"):
        assert 0 < stats["swept_rows"] < every_row / 4
    if name in SWEPT_BLOCKS:
        assert stats["swept_rows"] == SWEPT_BLOCKS[name] * B
    if name == "dense":
        assert widest == NB
    if name == "every-row-fair":
        # site 1 reads every block in every pass
        assert stats["outer"] >= 3 and stats["fair_edges"] == len(rows)
        assert stats["swept_rows"] >= stats["outer"] * E_ROWS
    if name == "straddle":
        assert len(want_z) == len(h)  # the candidate was reached
