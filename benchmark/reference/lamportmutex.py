"""Plain reference for the LamportMutex configuration: Lamport's 1978
distributed mutual exclusion over pairwise FIFO channels
(tlaplus/Examples, specifications/lamport_mutex/LamportMutex.tla), its
six actions written out by hand as Python over tuples, checked the way
its MCLamportMutex.cfg asks: under `CONSTRAINT ClockConstraint`.

    python benchmark/reference/lamportmutex.py [<config name>]
                                 [--n N] [--max-clock C] [--keep-discarded]

prints the pins of benchmark/configs/<config name>.json (default
lamportmutex-mc) as one JSON line (pin.py dispatches on names it knows
and may not be edited).  `--n` / `--max-clock` override the deployment's
constants (the tests' rung is maxClock = 3).

It imports nothing of the program.  A state is the tuple
(clock, req, ack, network, crit): `clock` a tuple of integers over the
processes 0..N-1 (the module's 1..N), `req` a tuple of N tuples of
integers, `ack` a tuple of frozensets, `network` a tuple of N tuples of
channels, a channel a tuple of messages oldest first, a message the pair
(type, clock) with type "req" / "ack" / "rel"; `crit` a frozenset.  The
search is a level-synchronous BFS with TLC's accounting as this repo
reads it for a constrained model:

* the initial state counts as generated; depth counts Init as level 1;
* EVERY successor of every kept state counts as generated and toward
  its action's total: one successor an enabled instance of Request(p),
  Enter(p), Exit(p), ReceiveRequest(p,q), ReceiveAck(p,q),
  ReceiveRelease(p,q).  Enter's `\\A q \\in Proc \\ {p} : beats(p,q)` is a
  boolean, not a branch: the disjunction inside beats sits under a
  universal quantifier, where TLC evaluates and does not enumerate;
* a successor that fails the constraint (some clock[p] > maxClock) is
  then DISCARDED: not kept, not expanded, its invariants not evaluated;
* the initial state lies inside the constraint.

Self-checks, run with every pin (an AssertionError instead of a line):
 (a) TypeOK, BoundedNetwork and Mutex on every kept state;
 (b) generated = the initial state + the sum of the per-action totals;
 (c) the kept set is closed: every successor of a kept state is kept or
     fails the constraint (the failing edges counted again);
 (d) the three invariants also hold on every DISCARDED successor, so a
     checker that evaluates invariants before the constraint gives the
     same verdict on this model;
 (e) the longest channel over kept states and discarded successors
     (BoundedNetwork says 3; the program's sequence capacity is read off
     that invariant);
 (f) at maxClock = 3 a second enumeration written another way
     (depth-first, states as dicts, the actions as guard / effect pairs
     over a message-kind table) gives the same kept set and the same
     set of discarded successors.

`--keep-discarded` is the control: a successor the constraint rejects is
kept all the same when it fits one step outside it (every clock <=
maxClock + 1) - the "cheaper" seam that skips the predicate.  More
states, and no pin.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

ACTIONS = ("Request", "ReceiveRequest", "ReceiveAck", "Enter", "Exit",
           "ReceiveRelease")
ACK = ("ack", 0)
REL = ("rel", 0)


def initial_state(n: int) -> tuple:
    return ((1,) * n, ((0,) * n,) * n, (frozenset(),) * n,
            (((),) * n,) * n, frozenset())


def _set(t: tuple, i: int, v) -> tuple:
    return t[:i] + (v,) + t[i + 1:]


def _set2(t: tuple, i: int, j: int, v) -> tuple:
    return _set(t, i, _set(t[i], j, v))


def _broadcast(network: tuple, s: int, m: tuple, n: int) -> tuple:
    return _set(network, s, tuple(
        network[s][r] if r == s else network[s][r] + (m,)
        for r in range(n)))


def beats(req: tuple, p: int, q: int) -> bool:
    return (req[p][q] == 0 or req[p][p] < req[p][q]
            or (req[p][p] == req[p][q] and p < q))


def successors(s: tuple, n: int) -> List[Tuple[str, tuple]]:
    clock, req, ack, network, crit = s
    out = []
    everyone = frozenset(range(n))
    for p in range(n):
        if req[p][p] == 0:
            out.append(("Request", (
                clock, _set2(req, p, p, clock[p]),
                _set(ack, p, frozenset({p})),
                _broadcast(network, p, ("req", clock[p]), n), crit)))
        if ack[p] == everyone and all(
                beats(req, p, q) for q in range(n) if q != p):
            out.append(("Enter", (clock, req, ack, network, crit | {p})))
        if p in crit:
            out.append(("Exit", (
                clock, _set2(req, p, p, 0), _set(ack, p, frozenset()),
                _broadcast(network, p, REL, n), crit - {p})))
    for p in range(n):
        for q in range(n):
            if q == p or not network[q][p]:
                continue
            kind, c = network[q][p][0]
            rest = _set2(network, q, p, network[q][p][1:])
            if kind == "req":
                out.append(("ReceiveRequest", (
                    _set(clock, p, c + 1 if c > clock[p] else clock[p] + 1),
                    _set2(req, p, q, c), ack,
                    _set2(rest, p, q, rest[p][q] + (ACK,)), crit)))
            elif kind == "ack":
                out.append(("ReceiveAck", (
                    clock, req, _set(ack, p, ack[p] | {q}), rest, crit)))
            else:
                out.append(("ReceiveRelease", (
                    clock, _set2(req, p, q, 0), ack, rest, crit)))
    return out


def in_constraint(s: tuple, max_clock: int) -> bool:
    return max(s[0]) <= max_clock


def longest_channel(s: tuple) -> int:
    return max(len(ch) for row in s[3] for ch in row)


def invariants_hold(s: tuple, n: int) -> bool:
    clock, req, ack, network, crit = s
    procs = frozenset(range(n))
    is_int = lambda x: isinstance(x, int) and not isinstance(x, bool)
    type_ok = (
        len(clock) == n and all(is_int(c) and c >= 1 for c in clock)
        and len(req) == n and all(
            len(row) == n and all(is_int(x) and x >= 0 for x in row)
            for row in req)
        and len(ack) == n and all(a <= procs for a in ack)
        and len(network) == n and all(
            len(row) == n and all(
                m == ACK or m == REL
                or (m[0] == "req" and is_int(m[1]) and m[1] >= 1)
                for ch in row for m in ch)
            for row in network)
        and crit <= procs)
    bounded = longest_channel(s) <= 3
    mutex = len(crit) <= 1
    return type_ok and bounded and mutex


def search(n: int, max_clock: int, keep=None, collect_discarded=False):
    """The constrained BFS.  `keep(state)` overrides the constraint as
    the rule for what is kept (the control)."""
    if keep is None:
        def keep(st):
            return in_constraint(st, max_clock)
    init = initial_state(n)
    assert invariants_hold(init, n) and keep(init)
    generated = 1
    seen = {init}
    frontier = [init]
    per_action = dict.fromkeys(ACTIONS, 0)
    discarded = 0
    discarded_set = set() if collect_discarded else None
    depth, widest, longest = 1, 1, 0
    levels = [1]
    while frontier:
        nxt = []
        for s in frontier:
            for name, t in successors(s, n):
                generated += 1
                per_action[name] += 1
                if t in seen:
                    continue
                # (a), (d)
                assert invariants_hold(t, n), (
                    f"an invariant fails on {t} (kept: {keep(t)})")
                longest = max(longest, longest_channel(t))
                if not keep(t):
                    discarded += 1
                    if collect_discarded:
                        discarded_set.add(t)
                    continue
                seen.add(t)
                nxt.append(t)
        frontier = nxt
        if frontier:
            depth += 1
            levels.append(len(frontier))
            widest = max(widest, len(frontier))
    return dict(generated=generated, distinct=len(seen), depth=depth,
                action_generated={k: v for k, v in per_action.items()
                                  if v},
                discarded=discarded, discarded_inits=0,
                widest_level=widest, longest_channel=longest,
                n_initial=1), seen, discarded_set


def closure_check(seen: set, n: int, max_clock: int) -> int:
    """(c): every successor of a kept state is kept or fails the
    constraint; returns the number that fail it, counted with
    multiplicity over ALL successors."""
    failed = 0
    for s in seen:
        for _, t in successors(s, n):
            if in_constraint(t, max_clock):
                assert t in seen, f"{t} satisfies the constraint, unkept"
            else:
                assert t not in seen
                failed += 1
    return failed


# -- (f): the second enumeration, written another way ----------------------


def _dfs_kept(n: int, max_clock: int):
    """Depth-first over states as dicts keyed like the module's
    variables (processes 1..N, channels keyed by (sender, receiver)),
    every action a (guard, effect) pair; a receive is ONE rule
    parameterised by a table of what each message kind does.  Returns
    (kept states, discarded successors) frozen to the first
    enumeration's tuples."""
    procs = list(range(1, n + 1))

    def freeze(d):
        return (
            tuple(d["clock"][p] for p in procs),
            tuple(tuple(d["req"][p, q] for q in procs) for p in procs),
            tuple(frozenset(x - 1 for x in d["ack"][p]) for p in procs),
            tuple(tuple(tuple(d["net"][p, q]) for q in procs)
                  for p in procs),
            frozenset(x - 1 for x in d["crit"]))

    def copy(d):
        return dict(clock=dict(d["clock"]), req=dict(d["req"]),
                    ack={p: set(a) for p, a in d["ack"].items()},
                    net={k: list(v) for k, v in d["net"].items()},
                    crit=set(d["crit"]))

    def on_req(e, p, q, c):
        e["req"][p, q] = c
        e["clock"][p] = max(c, e["clock"][p]) + 1
        e["net"][p, q].append(ACK)

    def on_ack(e, p, q, c):
        e["ack"][p].add(q)

    def on_rel(e, p, q, c):
        e["req"][p, q] = 0

    receive = {"req": on_req, "ack": on_ack, "rel": on_rel}

    def moves(d):
        for p in procs:
            others = [q for q in procs if q != p]
            if d["req"][p, p] == 0:
                e = copy(d)
                e["req"][p, p] = d["clock"][p]
                e["ack"][p] = {p}
                for q in others:
                    e["net"][p, q].append(("req", d["clock"][p]))
                yield e
            mine = d["req"][p, p]
            if d["ack"][p] == set(procs) and not any(
                    d["req"][p, q] != 0 and (
                        d["req"][p, q] < mine
                        or (d["req"][p, q] == mine and q < p))
                    for q in others):
                e = copy(d)
                e["crit"].add(p)
                yield e
            if p in d["crit"]:
                e = copy(d)
                e["crit"].discard(p)
                e["req"][p, p] = 0
                e["ack"][p] = set()
                for q in others:
                    e["net"][p, q].append(REL)
                yield e
            for q in others:
                if d["net"][q, p]:
                    e = copy(d)
                    kind, c = e["net"][q, p].pop(0)
                    receive[kind](e, p, q, c)
                    yield e

    d0 = dict(clock={p: 1 for p in procs},
              req={(p, q): 0 for p in procs for q in procs},
              ack={p: set() for p in procs},
              net={(p, q): [] for p in procs for q in procs},
              crit=set())
    kept = {freeze(d0)}
    dropped = set()
    stack = [d0]
    while stack:
        d = stack.pop()
        for e in moves(d):
            k = freeze(e)
            if max(e["clock"].values()) > max_clock:
                dropped.add(k)
            elif k not in kept:
                kept.add(k)
                stack.append(e)
    return kept, dropped


def pins_for(config: dict, n=None, max_clock=None,
             keep_discarded: bool = False) -> dict:
    dep = config["deployment"]
    n = int(dep["N"] if n is None else n)
    max_clock = int(dep["maxClock"] if max_clock is None else max_clock)
    t0 = time.time()
    keep = None
    if keep_discarded:
        def keep(st):
            return max(st[0]) <= max_clock + 1
    second_at = dep.get("second_enumeration_at")
    counts, seen, dropped = search(
        n, max_clock, keep,
        collect_discarded=not keep_discarded and max_clock == second_at)
    counts["seconds"] = round(time.time() - t0, 1)
    if keep_discarded:
        counts["control"] = "keep-discarded"
        return counts
    # (b)
    assert counts["generated"] == counts["n_initial"] + sum(
        counts["action_generated"].values())
    # (c): `discarded` counts a failing successor every time it is
    # generated, as the engine does, and so does the closure pass
    failed = closure_check(seen, n, max_clock)
    assert failed == counts["discarded"], (failed, counts["discarded"])
    # (e)
    assert counts["longest_channel"] <= 3
    counts["self_checks"] = ["invariants", "closure",
                             "discarded_invariants", "longest_channel"]
    if second_at:
        m = int(second_at)
        if max_clock == m:
            first, first_dropped = seen, dropped
        else:
            _, first, first_dropped = search(n, m, collect_discarded=True)
        second, second_dropped = _dfs_kept(n, m)
        assert second == first, (
            f"the two enumerations differ at maxClock = {m}")
        assert second_dropped == first_dropped
        counts["self_checks"].append(
            f"second enumeration at maxClock={m}: {len(second)} kept, "
            f"{len(second_dropped)} distinct discards")
    counts["seconds_with_checks"] = round(time.time() - t0, 1)
    return counts


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="lamportmutex-mc")
    p.add_argument("--n", type=int, default=None,
                   help="override the deployment's N")
    p.add_argument("--max-clock", type=int, default=None,
                   help="override the deployment's maxClock")
    p.add_argument("--keep-discarded", action="store_true",
                   help="the control: keep one step outside")
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    print(json.dumps(pins_for(config, args.n, args.max_clock,
                              args.keep_discarded)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
