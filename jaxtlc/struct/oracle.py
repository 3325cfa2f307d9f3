"""BFS model checker over the structurally-interpreted relation (E1).

Same accounting as TLC and the hand oracle (spec.oracle.bfs): initial
states count toward generated and distinct (MC.out:29-32); every
enumerated successor counts as generated; depth = BFS levels with Init
at level 1 (MC.out:1101); deadlock = a state with no successor at all
(self-loops count as successors); invariants are checked on every
distinct state.  Action attribution uses the PlusCal label names
(MC.out:44-1092), so per-action generated counts diff directly against
the hand oracle and the TLC log.

Under a cfg's CONSTRAINT (`constraints`, the loader's name -> AST) a
successor that fails the conjunction counts as generated and toward its
action's total and is then dropped: not kept, not checked, never
expanded; deadlock is judged on the successors before the constraint;
an initial state outside it is checked and not kept (the rule of
engine.backend's expand stage, which this search is the host oracle
of).

Under action properties (`action_props`, the loader's name ->
ActionProperty: a cfg PROPERTY `I /\\ [][A]_v`) I is judged on every
initial state and `A \\/ v' = v` on EVERY kept successor row, to new and
to seen states alike, A read as a formula over the pair of states by
the evaluator itself (a primed variable is the successor's value); the
first edge that fails is kept beside the violation (`bad_edge`).
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

from .actions import ActionSystem
from .eval import StructEvalError, TlaAssertionError


class StructBFSResult(NamedTuple):
    generated: int
    distinct: int
    depth: int
    max_outdegree: int
    min_outdegree: int
    violations: List[Tuple[str, tuple]]
    action_generated: Dict[str, int]
    action_distinct: Dict[str, int]
    levels: List[int]
    parents: Optional[Dict[tuple, Tuple[Optional[tuple], Optional[str]]]]
    states: Optional[Dict[tuple, int]]  # state -> level (collect_states)
    # successors the cfg's CONSTRAINT rejected (0 without one), and the
    # initial states it rejected (checked, never kept)
    discarded: int = 0
    discarded_inits: int = 0
    # under action properties: the edges judged, those on which a
    # property's subscript changed, and the first edge that failed one,
    # (source, label, successor) - its violation names the property
    edges: int = 0
    moved: int = 0
    bad_edge: Optional[Tuple[tuple, str, tuple]] = None


def bfs(
    system: ActionSystem,
    invariants: Dict[str, tuple],
    check_deadlock: bool = True,
    max_states: int = 10_000_000,
    keep_parents: bool = False,
    stop_on_violation: bool = True,
    collect_states: bool = False,
    constraints: Optional[Dict[str, tuple]] = None,
    action_props: Optional[Dict[str, object]] = None,
) -> StructBFSResult:
    ev = system.ev
    inits = system.initial_states()
    seen: Dict[tuple, int] = {}
    parents: Optional[Dict] = {} if keep_parents else None
    generated = 0
    violations: List[Tuple[str, tuple]] = []
    frontier: List[tuple] = []
    act_gen: Dict[str, int] = {}
    act_dist: Dict[str, int] = {}

    def check_invs(st: tuple):
        env = dict(ev.constants)
        env.update(zip(system.variables, st))
        for name, ast in invariants.items():
            try:
                ok = ev.eval(ast, env) is True
            except StructEvalError as e:
                # TLC reports an invariant that cannot be evaluated on a
                # reachable state (e.g. an out-of-range index) as an
                # error with a trace; same here, as a violation kind
                violations.append((f"{name} (evaluation error: {e})",
                                   st))
                continue
            if not ok:
                violations.append((name, st))

    def kept(st: tuple) -> bool:
        if not constraints:
            return True
        env = dict(ev.constants)
        env.update(zip(system.variables, st))
        return all(ev.eval(ast, env) is True
                   for ast in constraints.values())

    edges = moved = 0
    bad_edge = None

    def judge_edge(s: tuple, label: str, t: tuple):
        nonlocal edges, moved, bad_edge
        edges += 1
        env = dict(ev.constants)
        env.update(zip(system.variables, s))
        primed = dict(zip(system.variables, t))
        for prop in action_props.values():
            if all(env[v] == primed[v] for v in prop.sub):
                continue
            moved += 1
            if ev.eval(prop.action, env, primed) is not True:
                violations.append((
                    f"Property {prop.name} is violated: a step is not "
                    f"a step of {prop.text}", t))
                if bad_edge is None:
                    bad_edge = (s, label, t)

    discarded = discarded_inits = 0
    for s in inits:
        generated += 1
        if not kept(s):
            discarded_inits += 1
            if keep_parents:
                parents.setdefault(s, (None, None))
            check_invs(s)
            continue
        if s not in seen:
            seen[s] = 1
            frontier.append(s)
            if keep_parents:
                parents[s] = (None, None)
            check_invs(s)
            for prop in (action_props or {}).values():
                env = dict(ev.constants)
                env.update(zip(system.variables, s))
                if ev.eval(prop.init, env) is not True:
                    violations.append((
                        f"Property {prop.name} is violated: an initial "
                        f"state does not satisfy {prop.text}", s))
    depth = 1
    levels = [len(frontier)]
    max_out, min_out = 0, 1 << 30
    while frontier and not (violations and stop_on_violation):
        nxt: List[tuple] = []
        for s in frontier:
            try:
                succs = system.successors(s)
            except TlaAssertionError as e:
                violations.append((f"assert:{e.tla_msg}", s))
                if stop_on_violation:
                    break
                continue
            generated += len(succs)
            distinct_succs = {t for _, t in succs}
            outdeg = len(distinct_succs)
            max_out = max(max_out, outdeg)
            min_out = min(min_out, outdeg)
            if outdeg == 0 and check_deadlock:
                violations.append(("deadlock", s))
            for label, t in succs:
                act_gen[label] = act_gen.get(label, 0) + 1
                if t not in seen and not kept(t):
                    discarded += 1
                    continue
                if action_props:
                    judge_edge(s, label, t)
                if t not in seen:
                    if len(seen) >= max_states:
                        raise RuntimeError("state-space bound exceeded")
                    seen[t] = depth + 1
                    nxt.append(t)
                    act_dist[label] = act_dist.get(label, 0) + 1
                    if keep_parents:
                        parents[t] = (s, label)
                    check_invs(t)
        frontier = nxt
        if frontier:
            depth += 1
            levels.append(len(frontier))
    return StructBFSResult(
        generated=generated,
        distinct=len(seen),
        depth=depth,
        max_outdegree=max_out,
        min_outdegree=min_out if min_out != 1 << 30 else 0,
        violations=violations,
        action_generated=act_gen,
        action_distinct=act_dist,
        levels=levels,
        parents=parents,
        states=seen if collect_states else None,
        discarded=discarded,
        discarded_inits=discarded_inits,
        edges=edges,
        moved=moved,
        bad_edge=bad_edge,
    )


def state_env(system: ActionSystem, st: tuple) -> dict:
    env = dict(system.ev.constants)
    env.update(zip(system.variables, st))
    return env


def state_to_tla(system: ActionSystem, st: tuple) -> str:
    """TLA-conjunct rendering of a structural state (TLC trace style)."""
    from ..spec.pretty import value_to_tla

    return "\n".join(
        f"/\\ {v} = {value_to_tla(val)}"
        for v, val in zip(system.variables, st)
    )


class LivenessResult(NamedTuple):
    name: str
    holds: bool
    lasso_prefix: Optional[List[tuple]]
    lasso_cycle: Optional[List[tuple]]


def check_leads_to(system: ActionSystem, p_ast, q_ast, name: str = "",
                   max_states: int = 1_000_000,
                   fairness=None) -> LivenessResult:
    """P ~> Q over the structural relation under the spec's fairness:
    `fairness` is StructModel.fairness, ((A, labels), ...) for
    WF_vars(A_1) /\\ ... /\\ WF_vars(A_K); None is WF_vars(Next), () no
    fairness at all.  The CPU tests' oracle of the device route
    (live.check.check_struct_properties), by the same rule in plain
    sets: H = ~Q; Z, the states of H that reach inside H a fair cycle,
    is the greatest Z
    with Z = Z /\\ AND_k pre*_Z(acc_k), acc_k the states of Z where A_k
    is not enabled and the sources of A_k steps that stay in Z; a
    violation is a reachable P-state of Z, shown as a lasso whose cycle
    is fair (live.lasso.fair_lasso)."""
    ev = system.ev

    def holds(ast, st) -> bool:
        env = dict(ev.constants)
        env.update(zip(system.variables, st))
        return ev.eval(ast, env) is True

    states: Dict[tuple, int] = {}
    order: List[tuple] = []
    edges: List[Tuple[int, int, str]] = []  # state-changing (src, dst, label)
    for st in system.initial_states():
        if st not in states:
            states[st] = len(order)
            order.append(st)
    n_init = len(order)
    head = 0
    while head < len(order):
        st = order[head]
        for label, nxt in system.successors(st):
            if nxt == st:
                continue
            if nxt not in states:
                if len(states) >= max_states:
                    raise RuntimeError("liveness graph bound exceeded")
                states[nxt] = len(order)
                order.append(nxt)
            edges.append((head, states[nxt], label))
        head += 1
    n = len(order)
    every = sorted({lab for _, _, lab in edges})
    groups = ([frozenset(every)] if fairness is None
              else [frozenset(labels) for _, labels in fairness])
    preds: List[List[int]] = [[] for _ in range(n)]
    for u, v, _ in edges:
        preds[v].append(u)
    enabled = [{u for u, _, lab in edges if lab in g} for g in groups]
    alive = {i for i in range(n) if not holds(q_ast, order[i])}
    while True:
        keep = set(alive)
        for g, en in zip(groups, enabled):
            reach = (alive - en) | {u for u, v, lab in edges if lab in g
                                    and u in alive and v in alive}
            stack = list(reach)
            while stack:
                for u in preds[stack.pop()]:
                    if u in alive and u not in reach:
                        reach.add(u)
                        stack.append(u)
            keep &= reach
        if keep == alive:
            break
        alive = keep
    bad = [i for i in sorted(alive) if holds(p_ast, order[i])]
    if not bad:
        return LivenessResult(name, True, None, None)
    import numpy as np

    from ..live.lasso import fair_lasso

    mask = np.zeros(n, bool)
    mask[sorted(alive)] = True
    trigger = np.zeros(n, bool)
    trigger[bad] = True
    prefix, cycle, _, _ = fair_lasso(
        n, n_init,
        np.asarray([e[0] for e in edges], np.int32),
        np.asarray([e[1] for e in edges], np.int32),
        np.asarray([every.index(e[2]) for e in edges], np.int32),
        mask, trigger,
        [[every.index(lab) for lab in g if lab in every] for g in groups])
    return LivenessResult(name, False, [order[j] for j in prefix],
                          [order[j] for j in cycle])


def violation_trace(system: ActionSystem, invariants: Dict[str, tuple],
                    check_deadlock: bool = True,
                    max_states: int = 10_000_000,
                    constraints: Optional[Dict[str, tuple]] = None,
                    action_props: Optional[Dict[str, object]] = None):
    """(kind, [(state, label|None), ...]) for the first violation, or
    None - the trace-explorer re-run over the structural relation.  An
    action property that fails on an edge ends the trace with that
    edge: the path to its source, then its successor."""
    r = bfs(system, invariants, check_deadlock=check_deadlock,
            max_states=max_states, keep_parents=True,
            constraints=constraints, action_props=action_props)
    if not r.violations:
        return None
    kind, bad = r.violations[0]
    chain = []
    cur: Optional[tuple] = bad
    if r.bad_edge is not None and bad == r.bad_edge[2] \
            and kind.startswith("Property "):
        # the successor may have been reached before, by another path:
        # the trace is the failing edge's own
        chain.append((bad, r.bad_edge[1]))
        cur = r.bad_edge[0]
    while cur is not None:
        parent, label = r.parents[cur]
        chain.append((cur, label))
        cur = parent
    chain.reverse()
    return kind, chain
