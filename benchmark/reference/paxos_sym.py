"""Plain reference for the symmetric Paxos configuration: the model of
reference/paxos.py (Lamport's Paxos.tla, its actions written out by hand
as Python over tuples) checked the way its source's MCPaxos.cfg asks,
under `SYMMETRY Permutations(Acceptor) \\cup Permutations(Value)`.

    python benchmark/reference/paxos_sym.py [<config name>] [--self-check]
                                            [--generators-only]

prints the pins of benchmark/configs/<config name>.json (default
paxos-mc-sym) as one JSON line (pin.py dispatches on names it knows and
may not be edited).

It imports nothing of the program: the successor function is the sibling
reference's (`paxos.successors`, unedited), the rest is here.  The search
is a level-synchronous BFS over canonical representatives: every
successor of every expanded representative is generated (TLC's
accounting, as paxos.py has it: the initial state counts, depth counts
Init as level 1) and is replaced by the least of its images under an
explicit list of the group's elements - all |Acceptor|! x |Value|! of
them, 12 at 3 acceptors and 2 values: the closure under composition of
the 8 functions the cfg's set lists - before it is looked up.  A group
element acts on the three tuples over the acceptors (entries move with
their acceptor, values are renamed) and on `msgs` message by message:
the 1b and 2b messages move with their acceptor, the values in 1b, 2a
and 2b messages are renamed, 1a messages stay.  Images are compared as
(maxBal, maxVBal, maxVal, msgs) tuples; which member of an orbit is the
least depends on that order, the counts do not: the successors of an
image are the images of the successors, action by action.

Two self-checks make the pins trustworthy (`--self-check`; the second
runs with every pin):
 (a) at Ballot == 0..1 and 0..2 the unreduced reachable set (the sibling
     reference's own search) is closed under the group, and the number
     of its distinct canonical forms equals the number of
     representatives the reduced search finds;
 (b) the sizes of the representatives' orbits (12 over the size of the
     stabilizer) sum to the unreduced distinct count of
     configs/paxos-mc.json's pins at the same constants.

`--generators-only` is the control: the least image under the listed
functions alone (the 5 + 1 non-identity ones and the state itself)
instead of the 12 elements they generate - more "orbits" than there
are, and a count that depends on the search order.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time
from typing import Dict, List, NamedTuple, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import paxos  # noqa: E402  (the sibling plain reference)


class Element(NamedTuple):
    """One element of the group: where each acceptor's entry comes from,
    how a value index (shifted by one, 0 = None) is renamed, and the
    image of every message bit."""
    inv: Tuple[int, ...]   # image[i] = tuple[inv[i]]
    vmap: Tuple[int, ...]  # [v + 1] -> renamed v (None stays None)
    bits: Tuple[int, ...]  # [bit index] -> image mask
    identity: bool


def _bit(mask: int) -> int:
    return mask.bit_length() - 1


def element(m: paxos.Model, pa, pv) -> Element:
    """The action of (acceptor a -> pa[a], value v -> pv[v])."""
    vmap = (-1,) + tuple(pv)
    bits = [0] * m.universe_bits
    B = range(m.n_bal)
    for b in B:
        bits[_bit(m.b1a[b])] = m.b1a[b]
        for v in range(m.n_val):
            bits[_bit(m.b2a[b][v])] = m.b2a[b][pv[v]]
    for a in range(m.n_acc):
        for b in B:
            for mbal in range(-1, m.n_bal):
                for mval in range(-1, m.n_val):
                    bits[_bit(m.b1b[a][b][mbal + 1][mval + 1])] = (
                        m.b1b[pa[a]][b][mbal + 1][vmap[mval + 1] + 1])
            for v in range(m.n_val):
                bits[_bit(m.b2b[a][b][v])] = m.b2b[pa[a]][b][pv[v]]
    assert sorted(_bit(x) for x in bits) == list(range(m.universe_bits))
    inv = [0] * m.n_acc
    for a in range(m.n_acc):
        inv[pa[a]] = a
    ident = (tuple(pa) == tuple(range(m.n_acc))
             and tuple(pv) == tuple(range(m.n_val)))
    return Element(tuple(inv), vmap, tuple(bits), ident)


def group(m: paxos.Model, generators_only: bool = False) -> List[Element]:
    ida, idv = tuple(range(m.n_acc)), tuple(range(m.n_val))
    if generators_only:
        pairs = [(ida, idv)]
        pairs += [(pa, idv) for pa in itertools.permutations(ida)
                  if pa != ida]
        pairs += [(ida, pv) for pv in itertools.permutations(idv)
                  if pv != idv]
    else:
        pairs = list(itertools.product(itertools.permutations(ida),
                                       itertools.permutations(idv)))
    return [element(m, pa, pv) for pa, pv in pairs]


def permute_msgs(msgs: int, g: Element) -> int:
    """msgs with every message replaced by its image, one by one."""
    if g.identity:
        return msgs
    out, bits = 0, g.bits
    while msgs:
        low = msgs & -msgs
        out |= bits[low.bit_length() - 1]
        msgs ^= low
    return out


def _head(st, g: Element):
    """The three tuples over the acceptors of g's image of st."""
    mb, mvb, mv, _ = st
    inv, vmap = g.inv, g.vmap
    return (tuple(mb[i] for i in inv), tuple(mvb[i] for i in inv),
            tuple(vmap[mv[i] + 1] for i in inv))


def image(st, g: Element):
    return _head(st, g) + (permute_msgs(st[3], g),)


def canon(st, G: List[Element]):
    """The least image of st under G, as (maxBal, maxVBal, maxVal, msgs):
    the three tuples decide first, `msgs` is permuted only for the
    elements that tie on them."""
    best, ties = None, []
    for g in G:
        head = _head(st, g)
        if best is None or head < best:
            best, ties = head, [g]
        elif head == best:
            ties.append(g)
    return best + (min(permute_msgs(st[3], g) for g in ties),)


class Result(NamedTuple):
    generated: int
    distinct: int
    depth: int
    violations: List[Tuple[str, tuple]]
    action_generated: Dict[str, int]
    levels: List[int]
    moved: int  # successors that were not their orbit's least member
    orbit_size_sum: int  # sum over representatives of |orbit|
    group_order: int


def bfs(n_acc: int = 3, n_val: int = 2, n_bal: int = 4,
        quorum_size: int = 2, generators_only: bool = False,
        orbit_sizes: bool = True) -> Result:
    m = paxos.make_model(n_acc, n_val, n_bal, quorum_size)
    G = group(m, generators_only)
    full = G if not generators_only else group(m)
    init = canon(((-1,) * n_acc, (-1,) * n_acc, (-1,) * n_acc, 0), G)
    seen = {init}
    frontier = [init]
    generated, depth, moved = 1, 1, 0
    levels = [1]
    violations: List[Tuple[str, tuple]] = []
    by_action = dict.fromkeys(paxos.ACTIONS, 0)
    while frontier:
        nxt = []
        for s in frontier:
            succ = paxos.successors(s, m)
            generated += len(succ)
            for action, t in succ:
                by_action[action] += 1
                if t in seen and not generators_only:
                    continue  # seen holds least members only: t is one
                c = canon(t, G)
                moved += c != t
                if c in seen:
                    continue
                seen.add(c)
                nxt.append(c)
                violations += [(name, c)
                               for name in paxos.invariants(c, m)]
        frontier = nxt
        if frontier:
            depth += 1
            levels.append(len(frontier))
    size_sum = 0
    if orbit_sizes:
        for s in seen:
            size_sum += len({image(s, g) for g in full})
    return Result(generated, len(seen), depth, violations, by_action,
                  levels, moved, size_sum, len(full))


def unreduced(n_acc: int, n_val: int, n_bal: int, quorum_size: int):
    """The unreduced reachable set, by the sibling's successor function."""
    m = paxos.make_model(n_acc, n_val, n_bal, quorum_size)
    init = ((-1,) * n_acc, (-1,) * n_acc, (-1,) * n_acc, 0)
    seen = {init}
    frontier = [init]
    while frontier:
        nxt = []
        for s in frontier:
            for _, t in paxos.successors(s, m):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return m, seen


def self_check(n_acc: int, n_val: int, n_bal: int,
               quorum_size: int) -> dict:
    """Check (a) at one rung: closure of the unreduced set under the
    group, and its canonical forms against the reduced search."""
    m, states = unreduced(n_acc, n_val, n_bal, quorum_size)
    G = group(m)
    closed = all(image(s, g) in states for s in states for g in G)
    forms = {canon(s, G) for s in states}
    r = bfs(n_acc, n_val, n_bal, quorum_size)
    return dict(n_bal=n_bal, unreduced=len(states), closed=closed,
                canonical_forms=len(forms), reduced_distinct=r.distinct,
                orbit_size_sum=r.orbit_size_sum,
                ok=bool(closed and len(forms) == r.distinct
                        and r.orbit_size_sum == len(states)))


def pins_of(config: dict, generators_only: bool = False) -> dict:
    dep = config["deployment"]
    r = bfs(len(dep["Acceptor"]), len(dep["Value"]), len(dep["Ballot"]),
            dep["quorum_size"], generators_only=generators_only)
    if r.violations:
        raise SystemExit(f"reference found violations: {r.violations[:3]}")
    return dict(generated=r.generated, distinct=r.distinct, depth=r.depth,
                action_generated=dict(sorted(r.action_generated.items())),
                widest_level=max(r.levels), group_order=r.group_order,
                moved=r.moved, orbit_size_sum=r.orbit_size_sum)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="paxos-mc-sym")
    p.add_argument("--self-check", action="store_true",
                   help="check (a) at Ballot == 0..1 and 0..2 first")
    p.add_argument("--generators-only", action="store_true",
                   help="the control: the listed functions, not the "
                        "group they generate")
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    dep = config["deployment"]
    geo = (len(dep["Acceptor"]), len(dep["Value"]))
    if args.self_check:
        for n_bal in (2, 3):
            c = self_check(*geo, n_bal, dep["quorum_size"])
            print(json.dumps(c), flush=True)
            if not c["ok"]:
                return 1
    t0 = time.time()
    pins = pins_of(config, args.generators_only)
    pins["reference_s"] = round(time.time() - t0, 1)
    want = (config.get("deployment") or {}).get("unreduced_distinct")
    if want is not None and not args.generators_only:
        pins["orbit_sizes_ok"] = pins["orbit_size_sum"] == want
    print(json.dumps(pins))
    return 0 if pins.get("orbit_sizes_ok", True) else 1


if __name__ == "__main__":
    sys.exit(main())
