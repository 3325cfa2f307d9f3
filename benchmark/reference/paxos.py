"""Plain reference for the Paxos configuration: the actions of Lamport's
Paxos.tla (tlaplus/Examples specifications/Paxos; the module body as
specs/Paxos.toolbox/Model_1/Paxos.tla holds it) written out by hand as
Python over tuples, and a level-synchronous BFS with TLC's accounting:
the initial state and every satisfying assignment of Next count as
generated (Phase1a(b) re-sending a present message included; each
quorum Q and each witness m of Phase2a that satisfies its conjuncts is
an assignment of its own), distinct = unique states, depth counts Init
as level 1.

    python benchmark/reference/paxos.py [<config name>] [--fp-bits N --salt S]

prints the pins of benchmark/configs/<config name>.json (default
paxos-mc) as one JSON line, as pin.py does for the references it knows
(pin.py dispatches on the reference's name and may not be edited).

It imports nothing of the program and shares no code with jaxtlc/struct.
A state is (maxBal, maxVBal, maxVal, msgs): three tuples over the
acceptors (ballots as ints with -1, values as indices with -1 for None)
and the message set as one Python integer, one bit a message of
`Message`.  Dedup is by the state itself.  `fp_bits` is the control:
dedup by a truncated salted hash instead.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time
from typing import Dict, List, NamedTuple, Tuple

ACTIONS = ("Phase1a", "Phase1b", "Phase2a", "Phase2b")


class Result(NamedTuple):
    generated: int
    distinct: int
    depth: int
    violations: List[Tuple[str, tuple]]
    action_generated: Dict[str, int]
    levels: List[int]
    universe_bits: int
    max_assignments: int  # the most satisfying assignments of one state


class Model(NamedTuple):
    """The bit of every message of `Message`, and the quorums."""
    n_acc: int
    n_val: int
    n_bal: int
    quorums: Tuple[Tuple[int, ...], ...]
    b1a: tuple  # [b] -> bit
    b1b: tuple  # [a][b][mbal + 1][mval + 1] -> bit
    b2a: tuple  # [b][v] -> bit
    b2b: tuple  # [a][b][v] -> bit
    universe_bits: int
    any2a: tuple  # [b] -> mask of the 2a messages of ballot b
    drop_maxbal: bool = False  # the seeded mutation of Phase2a


def make_model(n_acc: int, n_val: int, n_bal: int, quorum_size: int,
               drop_maxbal: bool = False) -> Model:
    bit = itertools.count()
    A, V, B = range(n_acc), range(n_val), range(n_bal)
    b1a = tuple(1 << next(bit) for _ in B)
    b1b = tuple(tuple(tuple(tuple(1 << next(bit) for _ in range(n_val + 1))
                            for _ in range(n_bal + 1)) for _ in B)
                for _ in A)
    b2a = tuple(tuple(1 << next(bit) for _ in V) for _ in B)
    b2b = tuple(tuple(tuple(1 << next(bit) for _ in V) for _ in B)
                for _ in A)
    any2a = tuple(sum(b2a[b]) for b in B)
    return Model(n_acc, n_val, n_bal,
                 tuple(itertools.combinations(A, quorum_size)),
                 b1a, b1b, b2a, b2b, next(bit), any2a, drop_maxbal)


def _setat(t: tuple, i: int, v) -> tuple:
    return t[:i] + (v,) + t[i + 1:]


def successors(st, m: Model):
    """Every (action, successor) of Next, one entry per satisfying
    assignment of its bound variables."""
    mb, mvb, mv, msgs = st
    out = []
    B, V = range(m.n_bal), range(m.n_val)
    for b in B:
        # Phase1a(b): Send([type |-> "1a", bal |-> b]), always enabled
        out.append(("Phase1a", (mb, mvb, mv, msgs | m.b1a[b])))
        # Phase2a(b, v)
        if msgs & m.any2a[b]:
            continue  # \E m \in msgs : m.type = "2a" /\ m.bal = b
        for v in V:
            t = (mb, mvb, mv, msgs | m.b2a[b][v])
            for q in m.quorums:
                # Q1b: the 1b messages of ballot b from acceptors of Q,
                # as (mbal, mval) pairs per acceptor
                q1b = [[(mbal, mval)
                        for mbal in range(-1, m.n_bal)
                        for mval in range(-1, m.n_val)
                        if msgs & m.b1b[a][b][mbal + 1][mval + 1]]
                       for a in q]
                if not all(q1b):  # \A a \in Q : \E m \in Q1b : m.acc = a
                    continue
                q1bv = [p for per_a in q1b for p in per_a if p[0] >= 0]
                if not q1bv:
                    out.append(("Phase2a", t))
                    continue
                top = max(p[0] for p in q1bv)
                for mbal, mval in q1bv:  # \E m \in Q1bv
                    if mval == v and (m.drop_maxbal or mbal >= top):
                        out.append(("Phase2a", t))
    for a in range(m.n_acc):
        for b in B:
            # Phase1b(a): \E m \in msgs : m.type = "1a" /\ m.bal > maxBal[a]
            if msgs & m.b1a[b] and b > mb[a]:
                out.append(("Phase1b", (
                    _setat(mb, a, b), mvb, mv,
                    msgs | m.b1b[a][b][mvb[a] + 1][mv[a] + 1])))
            # Phase2b(a): \E m \in msgs : m.type = "2a" /\ m.bal >= maxBal[a]
            if b >= mb[a]:
                for v in V:
                    if msgs & m.b2a[b][v]:
                        out.append(("Phase2b", (
                            _setat(mb, a, b), _setat(mvb, a, b),
                            _setat(mv, a, v), msgs | m.b2b[a][b][v])))
    return out


def invariants(st, m: Model):
    """Names of the MC.cfg invariants this state violates.  TypeOK: the
    representation holds nothing outside its types but a ballot out of
    range; Agreement: two values chosen (a quorum of 2b messages each)."""
    mb, mvb, mv, msgs = st
    bad = []
    if not (all(-1 <= x < m.n_bal for x in mb + mvb)
            and all(-1 <= x < m.n_val for x in mv)
            and 0 <= msgs < 1 << m.universe_bits):
        bad.append("TypeOK")
    chosen = {v for b in range(m.n_bal) for v in range(m.n_val)
              if any(all(msgs & m.b2b[a][b][v] for a in q)
                     for q in m.quorums)}
    if len(chosen) > 1:
        bad.append("Agreement")
    return bad


def bfs(n_acc: int = 3, n_val: int = 2, n_bal: int = 4,
        quorum_size: int = 2, fp_bits: int = 0, fp_salt: int = 0,
        drop_maxbal: bool = False, stop_on_violation: bool = False
        ) -> Result:
    m = make_model(n_acc, n_val, n_bal, quorum_size, drop_maxbal)
    if fp_bits:
        mask = (1 << fp_bits) - 1

        def key(s):
            return hash((fp_salt, s)) & mask
    else:
        def key(s):
            return s
    init = ((-1,) * n_acc, (-1,) * n_acc, (-1,) * n_acc, 0)
    seen = {key(init)}
    frontier = [init]
    generated, depth = 1, 1
    levels = [1]
    violations: List[Tuple[str, tuple]] = []
    by_action = dict.fromkeys(ACTIONS, 0)
    widest = 0
    while frontier and not (violations and stop_on_violation):
        nxt = []
        for s in frontier:
            succ = successors(s, m)
            generated += len(succ)
            widest = max(widest, len(succ))
            for action, t in succ:
                by_action[action] += 1
                k = key(t)
                if k not in seen:
                    seen.add(k)
                    nxt.append(t)
                    violations += [(name, t) for name in invariants(t, m)]
        frontier = nxt
        if frontier:
            depth += 1
            levels.append(len(frontier))
    return Result(generated, len(seen), depth, violations, by_action,
                  levels, m.universe_bits, widest)


def pins_of(config: dict, fp_bits: int = 0, fp_salt: int = 0) -> dict:
    dep = config["deployment"]
    r = bfs(len(dep["Acceptor"]), len(dep["Value"]), len(dep["Ballot"]),
            dep["quorum_size"], fp_bits=fp_bits, fp_salt=fp_salt)
    if r.violations:
        raise SystemExit(f"reference found violations: {r.violations[:3]}")
    return dict(generated=r.generated, distinct=r.distinct, depth=r.depth,
                action_generated=dict(sorted(r.action_generated.items())),
                universe_bits=r.universe_bits, widest_level=max(r.levels),
                max_assignments=r.max_assignments)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="paxos-mc")
    p.add_argument("--fp-bits", type=int, default=0)
    p.add_argument("--salt", type=int, default=0)
    args = p.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(os.path.dirname(here), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    t0 = time.time()
    pins = pins_of(config, args.fp_bits, args.salt)
    pins["reference_s"] = round(time.time() - t0, 1)
    print(json.dumps(pins))
    return 0


if __name__ == "__main__":
    sys.exit(main())
