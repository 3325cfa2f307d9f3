"""Plain reference for the RaftReplication configuration: the actions of
specs/RaftReplication.toolbox/Model_1/RaftReplication.tla written out by
hand as Python over tuples, and a level-synchronous BFS with TLC's
accounting (initial states and every satisfying assignment of Next count
as generated; distinct = unique states; depth counts Init as level 1).

It imports nothing of the program and shares no code with
jaxtlc/struct (whose interpreter, struct/oracle.py, reads the spec
through the program's own parser).  Dedup is by the state itself.
`fp_bits` is the control: dedup by a truncated salted hash instead.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple


class Result(NamedTuple):
    generated: int
    distinct: int
    depth: int
    violations: List[Tuple[str, tuple]]
    action_generated: Dict[str, int]


def _last_term(log: tuple) -> int:
    return log[-1] if log else 0


def successors(st, n_nodes: int, max_log: int, max_term: int):
    """Every (action, successor) of Next, one entry per satisfying
    assignment of its bound variables."""
    role, term, log, commit = st
    nodes = range(n_nodes)
    out = []

    def up_to_date(c, v):
        lc, lv = _last_term(log[c]), _last_term(log[v])
        return lc > lv or (lc == lv and len(log[c]) >= len(log[v]))

    def setat(t, i, v):
        return t[:i] + (v,) + t[i + 1:]

    for n in nodes:
        # Elect(n)
        if (term[n] < max_term and all(term[m] <= term[n] for m in nodes)
                and 2 * sum(up_to_date(n, m) for m in nodes) > n_nodes):
            out.append(("Elect", (
                tuple("leader" if m == n else "follower" for m in nodes),
                setat(term, n, term[n] + 1), log, commit)))
        # ClientRequest(n)
        if role[n] == "leader" and len(log[n]) < max_log:
            out.append(("ClientRequest", (
                role, term, setat(log, n, log[n] + (term[n],)), commit)))
        # AdvanceCommit(n)
        if role[n] == "leader" and commit[n] < len(log[n]):
            quorum = sum(
                m == n or (len(log[m]) >= commit[n] + 1
                           and log[m] == log[n])
                for m in nodes)
            if 2 * quorum > n_nodes:
                out.append(("AdvanceCommit", (
                    role, term, log, setat(commit, n, commit[n] + 1))))
    for n in nodes:
        for f in nodes:
            if role[n] != "leader" or n == f:
                continue
            # Replicate(n, f)
            if term[f] <= term[n] and log[f] != log[n]:
                out.append(("Replicate", (
                    role, setat(term, f, term[n]),
                    setat(log, f, log[n]), commit)))
            # LearnCommit(n, f)
            if log[f] == log[n] and commit[f] < commit[n]:
                out.append(("LearnCommit", (
                    role, term, log, setat(commit, f, commit[f] + 1))))
    return out


def invariants(st, n_nodes: int, max_log: int, max_term: int):
    """Names of the MC.cfg invariants this state violates."""
    role, term, log, commit = st
    nodes = range(n_nodes)
    bad = []
    if not (all(r in ("leader", "follower") for r in role)
            and all(0 <= t <= max_term for t in term)
            and all(0 <= c <= max_log for c in commit)
            and all(len(lg) <= max_log
                    and all(1 <= e <= max_term for e in lg)
                    for lg in log)):
        bad.append("TypeOK")
    if sum(r == "leader" for r in role) > 1:
        bad.append("AtMostOneLeader")
    if any(commit[n] > len(log[n]) for n in nodes):
        bad.append("CommitWithinLog")
    for m in nodes:
        for n in nodes:
            for i in range(min(commit[m], commit[n])):
                # an index past either log is CommitWithinLog's finding
                if (i < len(log[m]) and i < len(log[n])
                        and log[m][i] != log[n][i]):
                    bad.append("CommittedAgree")
    return bad


def bfs(n_nodes: int = 3, max_log: int = 2, max_term: int = 3,
        fp_bits: int = 0, fp_salt: int = 0) -> Result:
    if fp_bits:
        mask = (1 << fp_bits) - 1

        def key(s):
            return hash((fp_salt, s)) & mask
    else:
        def key(s):
            return s
    init = (("follower",) * n_nodes, (0,) * n_nodes, ((),) * n_nodes,
            (0,) * n_nodes)
    seen = {key(init)}
    frontier = [init]
    generated, depth = 1, 1
    violations: List[Tuple[str, tuple]] = []
    by_action: Dict[str, int] = {}
    while frontier:
        nxt = []
        for s in frontier:
            for action, t in successors(s, n_nodes, max_log, max_term):
                generated += 1
                by_action[action] = by_action.get(action, 0) + 1
                k = key(t)
                if k not in seen:
                    seen.add(k)
                    nxt.append(t)
                    violations += [
                        (name, t) for name in
                        invariants(t, n_nodes, max_log, max_term)]
        frontier = nxt
        if frontier:
            depth += 1
    return Result(generated, len(seen), depth, violations, by_action)
