"""Plain reference for the EWD840 configuration: Dijkstra's termination
detection in a ring with a coloured token (tlaplus/Examples,
specifications/ewd840/EWD840.tla), its four actions written out by hand
as Python over tuples, checked the way its EWD840.cfg asks: three
invariants on every state, and `PROPERTY Liveness` under the module's
own `WF_vars(System)`.

    python benchmark/reference/ewd840.py [<config name>] [--n N]

prints the pins of benchmark/configs/<config name>.json (default
ewd840-live) as one JSON line (pin.py dispatches on names it knows and
may not be edited).  `--n` overrides the deployment's N (the tests' rungs
are N = 4 and 5).

It imports nothing of the program.  A state is the tuple (active, color,
tpos, tcolor): `active` and `color` bit sets over the nodes 0..N-1 as
integers (color bit set = "black"), the token's position, the token's
colour (1 = "black").

The safety half is a level-synchronous BFS with TLC's accounting as this
repo reads it (benchmark/configs/ewd998-mc.json, `assumed.accounting`):
the initial states count as generated and as level 1; every successor
counts as generated and toward its action; a disjunction in an action is
a branch per disjunct also where the disjuncts are guards, so
InitiateProbe's `tcolor = "black" \\/ color[0] = "black"` and PassToken's
`~ active[i] \\/ color[i] = "black" \\/ tcolor = "black"` generate their
one successor once per disjunct that holds.  TypeOK,
TerminationDetection and Inv are evaluated on every state.

The liveness half works on the behaviour graph G: the reachable states,
every successor row (src, dst, action) in the order the BFS made them,
and a stuttering self-loop at every state.  `changed` = src != dst.  For
a fairness constraint WF_vars(A_k): a_k = changed and action in A_k's
labels; en_k[s] = some row out of s has a_k.  H = ~Q.  Tarjan's strongly
connected components of the changed rows inside H (the self-loops make
every state a component); a component C is FAIR iff for every k some
state of C has ~en_k or some a_k row lies inside C; `P ~> Q` is violated
iff some state with P and H reaches, inside H, a fair component.  `Z` is
the set of H-states that reach one; `survivors` counts the states of Z
with P (0 iff the property holds).

Self-checks, run with every pin (an AssertionError instead of a line):
 (a) the three invariants on every state; generated = the initial states
     + the per-action totals; the graph's rows = generated - initial;
 (b) under WF_vars(Next) the fair-component rule gives the same Z as the
     plain peeling (the greatest fixpoint: a state of H survives iff it
     has no changed row at all, or a changed row into a survivor);
 (c) with the fairness removed, and with WF_vars(Environment) in its
     place, Liveness is violated, and the reference prints a lasso whose
     every step is a row of G, whose cycle stays in H and is fair;
 (d) at N = 3 and 4 a brute-force check agrees under all four fairness
     settings: the states a P-state reaches inside H, every simple cycle
     among them by depth-first enumeration and every stutter, each held
     to the fairness rule directly (with at most one constraint a fair
     closed walk contains a fair simple cycle or a fair stutter).
"""

from __future__ import annotations

import json
import os
import sys
import time
from array import array

HERE = os.path.dirname(os.path.abspath(__file__))

ACTIONS = ("InitiateProbe", "PassToken", "SendMsg", "Deactivate")
INITIATE, PASS, SEND, DEACT = range(4)
SYSTEM = (INITIATE, PASS)
ENVIRONMENT = (SEND, DEACT)


def initial_states(n: int):
    # Init: active, color any; tpos any; tcolor black - in the order the
    # module's conjuncts enumerate them
    return [(a, c, t, 1) for a in range(1 << n) for c in range(1 << n)
            for t in range(n)]


def successors(st, n: int):
    """[(action, next state)], one row a branch (a guard's disjunct is a
    branch)."""
    active, color, tpos, tcolor = st
    out = []
    if tpos == 0:
        nxt = (active, color & ~1, n - 1, 0)
        if tcolor:
            out.append((INITIATE, nxt))
        if color & 1:
            out.append((INITIATE, nxt))
    else:
        bit = 1 << tpos
        black = bool(color & bit)
        nxt = (active, color & ~bit, tpos - 1, 1 if black else tcolor)
        if not active & bit:
            out.append((PASS, nxt))
        if black:
            out.append((PASS, nxt))
        if tcolor:
            out.append((PASS, nxt))
    for i in range(n):
        if active >> i & 1:
            for j in range(n):
                if j != i:
                    out.append((SEND, (active | 1 << j,
                                       color | 1 << i if j > i else color,
                                       tpos, tcolor)))
            out.append((DEACT, (active & ~(1 << i), color, tpos, tcolor)))
    return out


def termination_detected(st) -> bool:
    active, color, tpos, tcolor = st
    return tpos == 0 and not tcolor and not color & 1 and not active & 1


def invariants_hold(st, n: int) -> bool:
    active, color, tpos, tcolor = st
    type_ok = (0 <= active < 1 << n and 0 <= color < 1 << n
               and 0 <= tpos < n and tcolor in (0, 1))
    detection = not termination_detected(st) or active == 0
    inv = (active >> (tpos + 1) == 0          # every i > tpos passive
           or color & ((2 << tpos) - 1) != 0  # some j <= tpos black
           or tcolor == 1)
    return type_ok and detection and inv


class Graph:
    """The reachable states in BFS order and their successor rows."""

    def __init__(self, n: int):
        self.n = n
        inits = initial_states(n)
        ids = {}
        order = []
        for st in inits:
            if st not in ids:
                ids[st] = len(order)
                order.append(st)
        self.n_init = len(inits)
        self.init_ids = len(order)
        self.generated = len(inits)
        self.action_generated = [0, 0, 0, 0]
        self.row_start = array("i", [0])
        self.dst = array("i")
        self.act = array("b")
        self.levels = []
        lo = 0
        while lo < len(order):
            hi = len(order)
            self.levels.append(hi - lo)
            for sid in range(lo, hi):
                st = order[sid]
                assert invariants_hold(st, n), st
                for a, nxt in successors(st, n):
                    self.action_generated[a] += 1
                    did = ids.get(nxt)
                    if did is None:
                        did = ids[nxt] = len(order)
                        order.append(nxt)
                    self.dst.append(did)
                    self.act.append(a)
                self.row_start.append(len(self.dst))
            lo = hi
        self.states = order
        self.generated += len(self.dst)
        assert self.generated == self.n_init + sum(self.action_generated)

    @property
    def distinct(self):
        return len(self.states)

    def rows(self, s):
        return range(self.row_start[s], self.row_start[s + 1])


def analyse(g: Graph, fairness, p_of, q_of):
    """The fair-component analysis of `P ~> Q` under `fairness` (a list
    of label tuples, one a WF_vars(A_k)).  Returns a dict of the pins'
    numbers and the sets a caller may want."""
    V = g.distinct
    dst, act, row_start = g.dst, g.act, g.row_start
    in_h = [not q_of(st) for st in g.states]
    in_p = [p_of(st) for st in g.states]
    K = len(fairness)
    labels = [frozenset(f) for f in fairness]
    changed_edges = 0
    fair_edges = 0
    en = [bytearray(V) for _ in range(K)]
    for s in range(V):
        for e in range(row_start[s], row_start[s + 1]):
            if dst[e] != s:
                changed_edges += 1
                for k in range(K):
                    if act[e] in labels[k]:
                        fair_edges += 1
                        en[k][s] = 1

    # Tarjan's components of the changed rows inside H, iteratively
    index = [-1] * V
    low = [0] * V
    on_stack = bytearray(V)
    comp = [-1] * V
    stack = []
    comps = []  # in order of completion: reverse topological
    counter = 0
    for root in range(V):
        if not in_h[root] or index[root] != -1:
            continue
        work = [(root, row_start[root])]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on_stack[root] = 1
        while work:
            v, e = work[-1]
            end = row_start[v + 1]
            pushed = False
            while e < end:
                w = dst[e]
                e += 1
                if w == v or not in_h[w]:
                    continue
                if index[w] == -1:
                    work[-1] = (v, e)
                    index[w] = low[w] = counter
                    counter += 1
                    stack.append(w)
                    on_stack[w] = 1
                    work.append((w, row_start[w]))
                    pushed = True
                    break
                if on_stack[w] and index[w] < low[v]:
                    low[v] = index[w]
            if pushed:
                continue
            work.pop()
            if work:
                u = work[-1][0]
                if low[v] < low[u]:
                    low[u] = low[v]
            if low[v] == index[v]:
                members = []
                while True:
                    w = stack.pop()
                    on_stack[w] = 0
                    comp[w] = len(comps)
                    members.append(w)
                    if w == v:
                        break
                comps.append(members)

    # a component is fair iff every constraint is met inside it; it
    # reaches a fair one iff it is fair or a row leaves it for one that
    # does (components complete after everything they reach)
    fair = bytearray(len(comps))
    reaches = bytearray(len(comps))
    for c, members in enumerate(comps):
        met = [False] * K
        down = False
        for s in members:
            for k in range(K):
                if not en[k][s]:
                    met[k] = True
            for e in range(row_start[s], row_start[s + 1]):
                w = dst[e]
                if w == s or not in_h[w]:
                    continue
                if comp[w] == c:
                    for k in range(K):
                        if act[e] in labels[k]:
                            met[k] = True
                elif reaches[comp[w]]:
                    down = True
        fair[c] = all(met)
        reaches[c] = fair[c] or down
    in_z = [in_h[s] and bool(reaches[comp[s]]) for s in range(V)]
    survivors = [s for s in range(V) if in_z[s] and in_p[s]]
    return dict(
        graph_states=V, graph_edges=len(dst), changed_edges=changed_edges,
        fair_edges=fair_edges, h_states=sum(in_h), p_states=sum(in_p),
        z_states=sum(in_z), survivors=len(survivors),
        holds=not survivors,
        _in_h=in_h, _in_z=in_z, _en=en, _labels=labels, _comp=comp,
        _comps=comps, _fair=fair, _bad=survivors)


def peel(g: Graph, in_h):
    """The plain peeling under WF_vars(Next): the greatest set of
    H-states each with no changed row at all, or a changed row into the
    set."""
    V = g.distinct
    alive = list(in_h)
    moved = True
    while moved:
        moved = False
        for s in range(V):
            if not alive[s]:
                continue
            has = ok = False
            for e in g.rows(s):
                w = g.dst[e]
                if w != s:
                    has = True
                    if alive[w]:
                        ok = True
                        break
            if has and not ok:
                alive[s] = False
                moved = True
    return alive


def _path(g: Graph, sources, is_target, allowed=None):
    """Shortest path over changed rows from any source to a target, as
    [(state, action into it | None)]."""
    prev = {s: None for s in sources}
    queue = list(sources)
    for s in queue:
        if is_target(s):
            return [(s, None)]
    qi = 0
    while qi < len(queue):
        v = queue[qi]
        qi += 1
        for e in g.rows(v):
            w = g.dst[e]
            if w == v or w in prev or (allowed is not None
                                       and not allowed(w)):
                continue
            prev[w] = (v, g.act[e])
            if is_target(w):
                out = [(w, g.act[e])]
                while prev[out[-1][0]] is not None:
                    u, _ = prev[out[-1][0]]
                    a = prev[u][1] if prev[u] is not None else None
                    out.append((u, a))
                out.reverse()
                return out
            queue.append(w)
    raise AssertionError("no path")


def lasso(g: Graph, res):
    """(prefix, cycle) for a violated analysis, each [(state id, action
    into it | None)]: from an initial state to a surviving P-state, on
    inside H to a fair component, and a fair cycle of that component."""
    in_h, comp, comps, fair = (res["_in_h"], res["_comp"], res["_comps"],
                               res["_fair"])
    en, labels = res["_en"], res["_labels"]
    bad = set(res["_bad"])
    prefix = _path(g, range(g.init_ids), lambda s: s in bad)
    down = _path(g, [prefix[-1][0]], lambda s: bool(fair[comp[s]]),
                 allowed=lambda s: in_h[s])
    prefix += down[1:]
    c = comp[prefix[-1][0]]
    inside = set(comps[c])
    start = prefix[-1][0]
    cycle = [(start, None)]

    def go(target):
        leg = _path(g, [cycle[-1][0]], target,
                    allowed=lambda s: s in inside)
        cycle.extend(leg[1:])

    for k in range(len(labels)):
        if any(not en[k][s] for s, _ in cycle):
            continue
        idle = [s for s in inside if not en[k][s]]
        if idle:
            go(lambda s: s in idle)
            continue
        edge = next((s, e) for s in inside for e in g.rows(s)
                    if g.dst[e] in inside and g.dst[e] != s
                    and g.act[e] in labels[k])
        go(lambda s: s == edge[0])
        cycle.append((g.dst[edge[1]], g.act[edge[1]]))
    if cycle[-1][0] != start:
        go(lambda s: s == start)
        cycle.pop()  # the start closes the cycle: not written twice
    return prefix[:-1], cycle


def check_lasso(g: Graph, res, prefix, cycle):
    """A lasso is a behaviour of the spec that violates the property
    fairly: rows of G from an initial state, the cycle in H and fair."""
    chain = prefix + cycle + [cycle[0]]
    assert chain[0][0] < g.init_ids
    for (u, _), (w, _) in zip(chain, chain[1:]):
        assert u == w or any(g.dst[e] == w for e in g.rows(u))
    ids = [s for s, _ in cycle]
    assert all(res["_in_h"][s] for s in ids)
    closed = list(zip(ids, ids[1:] + ids[:1]))
    for k, lab in enumerate(res["_labels"]):
        assert any(not res["_en"][k][s] for s in ids) or any(
            u != w and any(g.dst[e] == w and g.act[e] in lab
                           for e in g.rows(u)) for u, w in closed), k


def brute_force(g: Graph, fairness, p_of, q_of) -> bool:
    """Violated?  By enumeration: the states a P-state reaches inside
    H, every simple cycle among them and every stutter, each held to
    the rule directly.  At most one constraint."""
    assert len(fairness) <= 1
    lab = frozenset(fairness[0]) if fairness else None
    in_h = [not q_of(st) for st in g.states]

    def enabled(s):
        return any(g.dst[e] != s and g.act[e] in lab for e in g.rows(s))

    seen = [s for s in range(g.distinct)
            if in_h[s] and p_of(g.states[s])]
    reach = set(seen)
    while seen:
        v = seen.pop()
        for e in g.rows(v):
            w = g.dst[e]
            if in_h[w] and w not in reach:
                reach.add(w)
                seen.append(w)
    assert len(reach) < 5000, "the brute force is for small corners"
    if lab is None:
        return bool(reach)  # with no fairness every stutter is fair
    if any(not enabled(s) for s in reach):
        return True  # a fair stutter
    # a simple cycle holding a row of the constraint: depth-first from
    # each such row's head back to its tail
    budget = [2_000_000]
    for u in reach:
        for e in g.rows(u):
            w = g.dst[e]
            if w == u or w not in reach or g.act[e] not in lab:
                continue
            path, on = [w], {w}
            its = [iter(g.rows(w))]
            while its:
                budget[0] -= 1
                assert budget[0] > 0, "the brute force ran away"
                if path[-1] == u:
                    return True
                step = next(its[-1], None)
                if step is None:
                    on.discard(path.pop())
                    its.pop()
                    continue
                x = g.dst[step]
                if x in reach and x not in on:
                    path.append(x)
                    on.add(x)
                    its.append(iter(g.rows(x)))
    return False


def p_of(st) -> bool:
    return st[0] == 0  # every node passive


def render(g: Graph, prefix, cycle) -> str:
    def one(s, a):
        active, color, tpos, tcolor = g.states[s]
        n = g.n
        how = "<init>" if a is None else "<" + ACTIONS[a] + ">"
        return (f"  {how:16} active={active:0{n}b} black={color:0{n}b} "
                f"tpos={tpos} tcolor={'black' if tcolor else 'white'}")

    return "\n".join([one(*x) for x in prefix] + ["  -- cycle --"]
                     + [one(*x) for x in cycle])


def self_checks(g: Graph, verbose: bool = True):
    every = (tuple(range(4)),)
    res = analyse(g, every, p_of, termination_detected)
    assert res["_in_z"] == peel(g, res["_in_h"]), "WF(Next) != peeling"
    for name, fairness in (("no fairness", ()),
                           ("WF_vars(Environment)", (ENVIRONMENT,))):
        res = analyse(g, fairness, p_of, termination_detected)
        assert not res["holds"], name
        prefix, cycle = lasso(g, res)
        check_lasso(g, res, prefix, cycle)
        if verbose:
            print(f"{name}: Liveness violated, {res['survivors']} "
                  f"surviving P-states; a fair lasso:\n"
                  + render(g, prefix, cycle), file=sys.stderr)
    for n in (3, 4):
        small = g if g.n == n else Graph(n)
        for fairness in ((), every, (SYSTEM,), (ENVIRONMENT,)):
            res = analyse(small, fairness, p_of, termination_detected)
            assert (not res["holds"]) == brute_force(
                small, fairness, p_of, termination_detected), (n, fairness)


def pins_for(config: dict, n: int = None, checks: bool = True) -> dict:
    n = n or int(config["deployment"]["N"])
    t0 = time.time()
    g = Graph(n)
    t_search = time.time() - t0
    res = analyse(g, (SYSTEM,), p_of, termination_detected)
    assert res["graph_edges"] == g.generated - g.n_init
    if checks:
        self_checks(g)
    print(f"ewd840 N={n}: search {t_search:.1f} s, all "
          f"{time.time() - t0:.1f} s; widest level {max(g.levels)}, "
          f"initial states {g.n_init}, Z {res['z_states']} states",
          file=sys.stderr)
    return dict(
        generated=g.generated, distinct=g.distinct, depth=len(g.levels),
        action_generated={ACTIONS[a]: c for a, c in
                          enumerate(g.action_generated) if c},
        live=dict(
            properties={"Liveness": "holds" if res["holds"]
                        else "violated"},
            fairness=[["System", sorted(ACTIONS[a] for a in SYSTEM)]],
            graph_states=res["graph_states"],
            graph_edges=res["graph_edges"],
            changed_edges=res["changed_edges"],
            fair_edges=res["fair_edges"], h_states=res["h_states"],
            p_states=res["p_states"], survivors=res["survivors"]),
    )


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="ewd840-live")
    p.add_argument("--n", type=int, default=None,
                   help="override the deployment's N")
    p.add_argument("--no-self-check", action="store_true")
    args = p.parse_args(argv)
    config = {"deployment": {"N": args.n}}
    if args.n is None:
        path = os.path.join(os.path.dirname(HERE), "configs",
                            args.config + ".json")
        with open(path) as f:
            config = json.load(f)
    print(json.dumps(pins_for(config, args.n, not args.no_self_check)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
