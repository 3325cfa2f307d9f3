"""Plain reference for the PaxosCommit configuration: Gray and Lamport's
Paxos Commit (tlaplus/Examples, specifications/transaction_commit/
PaxosCommit.tla) checked against the specification it implements
(TCommit.tla), the module's own closing theorem `PCSpec => TC!TCSpec`.

    python benchmark/reference/paxoscommit.py [<config name>]
                [--rm N] [--ballots B] [--mutant commit-on-any]

prints the pins of benchmark/configs/<config name>.json (default
paxoscommit-mc) as one JSON line (pin.py dispatches on names it knows
and may not be edited).  `--rm` / `--ballots` override the deployment's
constants (the tests' rungs are `--rm 1` and `--ballots 1`).

It imports nothing of the program.  The module's values are written as
Python's: a record is a dict frozen to the sorted tuple of its items
(`rec`), a function likewise, a set a frozenset.  A state is the triple
(rmState, aState, msgs): `rmState` the function RM -> string, `aState`
the function RM -> (Acceptor -> the record [mbal, bal, val]), `msgs` a
frozenset of message records.  Each action below is the module's text
line for line; `Maximum` is the module's recursion over subsets.

The search is a level-synchronous BFS with TLC's accounting as this
repo reads it:

* the initial state counts as generated; `depth` counts Init as level 1
  (TLC's count, and the engine's);
* EVERY successor of every state counts as generated and toward its
  action's total, one successor a WITNESS of an action's existential
  quantifiers and disjuncts: RMPrepare(rm), ... one an rm; Phase1a and
  Phase2a one a (bal, rm) - and Phase2a one more a majority MS that
  has answered (three majorities that all answered are three
  successors, the same state three times); Decide one for its first
  disjunct where every instance chose "prepared" (`\\A rm` is a
  boolean) and one for EACH rm whose instance chose "aborted" (`\\E rm
  \\in RM` in action position is a branch a witness, as this repo reads
  TLC: struct/actions.py; `Decided(rm, v)`, an operator without primes,
  is a boolean under it, its own `\\E b, MS` not enumerated);
  Phase1b / Phase2b one an (acc, m).
  A Send of a message already in msgs IS a successor (a stuttering
  one: the successor is the source);
* the refinement is judged on every one of those edges, to new and to
  seen states alike: `TCNext \\/ UNCHANGED rmState` with TCNext
  evaluated as TCommit.tla states it on (rmState, rmState'); `moved`
  counts the edges on which rmState' # rmState (where TCNext itself
  decides), `edges` all of them; TCInit is judged on the initial state.

Self-checks, run with every pin (an AssertionError instead of a line):
 (a) PCTypeOK and TCConsistent on every state;
 (b) generated = the initial state + the sum of the per-action totals;
 (c) the set is closed: every successor of a state of it is in it;
 (d) the CHOOSE in Phase2a never has two candidates of different val;
 (e) at RM = {r1} a second enumeration written another way (depth-
     first, states as plain dicts, actions as guard / effect pairs)
     gives the same set of states.

`--mutant commit-on-any` is the control: Decide's first disjunct with
`\\E rm` for `\\A rm` (commit as soon as ONE instance chose "prepared").
It must come out violated in both TCConsistent and TCSpec; the line
names the first failing edge, and has no pins.
"""

from __future__ import annotations

import json
import os
import sys
import time
from itertools import product

HERE = os.path.dirname(os.path.abspath(__file__))

ACTIONS = ("RMPrepare", "RMChooseToAbort", "RMRcvCommitMsg",
           "RMRcvAbortMsg", "Phase1a", "Phase2a", "Decide", "Phase1b",
           "Phase2b")
RM_STATES = ("working", "prepared", "committed", "aborted")


def rec(**kw) -> tuple:
    """A record (or a function over strings): its items, sorted."""
    return tuple(sorted(kw.items()))


def fn(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def excpt(f: tuple, key, val) -> tuple:
    """[f EXCEPT ![key] = val]"""
    return tuple((k, val if k == key else v) for k, v in f)


_DICTS: dict = {}


def fields(r: tuple) -> dict:
    """A record's fields by name (one dict a distinct record, kept)."""
    d = _DICTS.get(r)
    if d is None:
        d = _DICTS[r] = dict(r)
    return d


def at(f: tuple, key):
    for k, v in f:
        if k == key:
            return v
    raise KeyError(key)


class Constants:
    def __init__(self, rm: int, acceptors: int, ballots: int):
        self.RM = tuple(f"r{i + 1}" for i in range(rm))
        self.Acceptor = tuple(f"a{i + 1}" for i in range(acceptors))
        self.Ballot = tuple(range(ballots))
        n = acceptors // 2 + 1
        # the majorities, as the cfg writes them for three acceptors:
        # every subset of floor(n/2) + 1 acceptors
        self.Majority = tuple(
            frozenset(c) for c in _combinations(self.Acceptor, n))
        # the module's ASSUME
        assert 0 in self.Ballot and all(b >= 0 for b in self.Ballot)
        assert all(a & b for a in self.Majority for b in self.Majority)

    def message_set(self) -> frozenset:
        """The module's `Message`."""
        out = set()
        nz = [b for b in self.Ballot if b != 0]
        for ins, bal in product(self.RM, nz):
            out.add(rec(type="phase1a", ins=ins, bal=bal))
        for ins, mbal, bal, val, acc in product(
                self.RM, self.Ballot, self.Ballot + (-1,),
                ("prepared", "aborted", "none"), self.Acceptor):
            out.add(rec(type="phase1b", ins=ins, mbal=mbal, bal=bal,
                        val=val, acc=acc))
        for ins, bal, val in product(self.RM, self.Ballot,
                                     ("prepared", "aborted")):
            out.add(rec(type="phase2a", ins=ins, bal=bal, val=val))
        for acc, ins, bal, val in product(self.Acceptor, self.RM,
                                          self.Ballot,
                                          ("prepared", "aborted")):
            out.add(rec(type="phase2b", acc=acc, ins=ins, bal=bal,
                        val=val))
        out.add(rec(type="Commit"))
        out.add(rec(type="Abort"))
        return frozenset(out)


def _combinations(items, n):
    if n == 0:
        yield ()
        return
    for i, x in enumerate(items):
        for rest in _combinations(items[i + 1:], n - 1):
            yield (x,) + rest


def maximum(s: frozenset) -> int:
    """The module's Maximum(S): Max[T \\in SUBSET S], by its recursion."""
    def mx(t: frozenset) -> int:
        if not t:
            return -1
        n = min(t)  # CHOOSE n \in T : TRUE - any element will do
        rmax = mx(t - {n})
        return n if n >= rmax else rmax
    return mx(s)


def initial_state(c: Constants) -> tuple:
    return (fn({rm: "working" for rm in c.RM}),
            fn({ins: fn({ac: rec(mbal=0, bal=-1, val="none")
                         for ac in c.Acceptor}) for ins in c.RM}),
            frozenset())


CHOOSE_CLASH = []  # (d): filled if Phase2a's CHOOSE is ever ambiguous


def successors(s: tuple, c: Constants, mutant=None) -> list:
    """[(action, successor)], one entry a witness (the docstring)."""
    rmState, aState, msgs = s
    out = []

    def send(m):
        return msgs | {m}

    for rm in c.RM:
        # RMPrepare(rm), RMChooseToAbort(rm)
        if at(rmState, rm) == "working":
            out.append(("RMPrepare", (
                excpt(rmState, rm, "prepared"), aState,
                send(rec(type="phase2a", ins=rm, bal=0,
                         val="prepared")))))
            out.append(("RMChooseToAbort", (
                excpt(rmState, rm, "aborted"), aState,
                send(rec(type="phase2a", ins=rm, bal=0,
                         val="aborted")))))
        # RMRcvCommitMsg(rm), RMRcvAbortMsg(rm)
        if rec(type="Commit") in msgs:
            out.append(("RMRcvCommitMsg", (
                excpt(rmState, rm, "committed"), aState, msgs)))
        if rec(type="Abort") in msgs:
            out.append(("RMRcvAbortMsg", (
                excpt(rmState, rm, "aborted"), aState, msgs)))
    for bal in c.Ballot:
        if bal == 0:
            continue
        for rm in c.RM:
            # Phase1a(bal, rm)
            out.append(("Phase1a", (rmState, aState, send(
                rec(type="phase1a", ins=rm, bal=bal)))))
            # Phase2a(bal, rm)
            if any(fields(m)["type"] == "phase2a" and fields(m)["bal"] == bal
                   and fields(m)["ins"] == rm for m in msgs):
                continue
            for MS in c.Majority:
                mset = frozenset(
                    m for m in msgs
                    if fields(m)["type"] == "phase1b"
                    and fields(m)["ins"] == rm and fields(m)["mbal"] == bal
                    and fields(m)["acc"] in MS)
                maxbal = maximum(frozenset(fields(m)["bal"] for m in mset))
                if maxbal == -1:
                    val = "aborted"
                else:
                    cands = {fields(m)["val"] for m in mset
                             if fields(m)["bal"] == maxbal}
                    if len(cands) != 1:
                        CHOOSE_CLASH.append((s, bal, rm, MS))
                    val = min(cands)
                if all(any(fields(m)["acc"] == ac for m in mset)
                       for ac in MS):
                    out.append(("Phase2a", (rmState, aState, send(
                        rec(type="phase2a", ins=rm, bal=bal, val=val)))))

    # Decide
    def decided(rm, v):
        return any(
            all(rec(type="phase2b", ins=rm, bal=b, val=v, acc=ac) in msgs
                for ac in MS)
            for b in c.Ballot for MS in c.Majority)

    quant = any if mutant == "commit-on-any" else all
    if quant(decided(rm, "prepared") for rm in c.RM):
        out.append(("Decide", (rmState, aState, send(rec(type="Commit")))))
    for rm in c.RM:
        # `\E rm \in RM` in action position: a successor a witness
        if decided(rm, "aborted"):
            out.append(("Decide", (rmState, aState,
                                   send(rec(type="Abort")))))

    for acc in c.Acceptor:
        for m in sorted(msgs):
            d = fields(m)
            if d["type"] == "phase1a":
                # Phase1b(acc)
                a = fields(at(at(aState, d["ins"]), acc))
                if a["mbal"] < d["bal"]:
                    new = rec(mbal=d["bal"], bal=a["bal"], val=a["val"])
                    out.append(("Phase1b", (
                        rmState,
                        excpt(aState, d["ins"],
                              excpt(at(aState, d["ins"]), acc, new)),
                        send(rec(type="phase1b", ins=d["ins"],
                                 mbal=d["bal"], bal=a["bal"],
                                 val=a["val"], acc=acc)))))
            elif d["type"] == "phase2a":
                # Phase2b(acc)
                a = fields(at(at(aState, d["ins"]), acc))
                if a["mbal"] <= d["bal"]:
                    new = rec(mbal=d["bal"], bal=d["bal"], val=d["val"])
                    out.append(("Phase2b", (
                        rmState,
                        excpt(aState, d["ins"],
                              excpt(at(aState, d["ins"]), acc, new)),
                        send(rec(type="phase2b", ins=d["ins"],
                                 bal=d["bal"], val=d["val"], acc=acc)))))
    return out


# -- TCommit.tla, on the pair (rmState, rmState') -------------------------


def tc_init(rmState, c) -> bool:
    return rmState == fn({r: "working" for r in c.RM})


def tc_next(rm0, rm1, c) -> bool:
    """TCNext == \\E r \\in RM : Prepare(r) \\/ Decide(r), as stated."""
    can_commit = all(at(rm0, r) in ("prepared", "committed") for r in c.RM)
    not_committed = all(at(rm0, r) != "committed" for r in c.RM)
    for r in c.RM:
        if at(rm0, r) == "working" and rm1 == excpt(rm0, r, "prepared"):
            return True
        if at(rm0, r) == "prepared" and can_commit \
                and rm1 == excpt(rm0, r, "committed"):
            return True
        if at(rm0, r) in ("working", "prepared") and not_committed \
                and rm1 == excpt(rm0, r, "aborted"):
            return True
    return False


def tc_consistent(rmState, c) -> bool:
    return not any(at(rmState, r1) == "aborted"
                   and at(rmState, r2) == "committed"
                   for r1 in c.RM for r2 in c.RM)


def pc_type_ok(s, c, message) -> bool:
    rmState, aState, msgs = s
    return (tuple(k for k, _ in rmState) == tuple(sorted(c.RM))
            and all(v in RM_STATES for _, v in rmState)
            and tuple(k for k, _ in aState) == tuple(sorted(c.RM))
            and all(tuple(k for k, _ in f) == tuple(sorted(c.Acceptor))
                    and all(dict(a)["mbal"] in c.Ballot
                            and dict(a)["bal"] in c.Ballot + (-1,)
                            and dict(a)["val"] in ("prepared", "aborted",
                                                   "none")
                            and len(a) == 3 for _, a in f)
                    for _, f in aState)
            and msgs <= message)


def search(c: Constants, mutant=None, stop_on_violation=True):
    message = c.message_set()
    s0 = initial_state(c)
    seen = {s0}
    level = [s0]
    generated = 1
    per_action = dict.fromkeys(ACTIONS, 0)
    edges = moved = stutter = 0
    depth = 1
    widest = 1
    sent = set()
    violations = []  # (what, source | None, successor)
    if not tc_init(s0[0], c):
        violations.append(("TCSpec: TCInit", None, s0))
    for name, ok in (("PCTypeOK", pc_type_ok(s0, c, message)),
                     ("TCConsistent", tc_consistent(s0[0], c))):
        if not ok:
            violations.append((name, None, s0))
    while level and not (violations and stop_on_violation):
        nxt = []
        for s in level:
            for action, t in successors(s, c, mutant):
                generated += 1
                per_action[action] += 1
                edges += 1
                if t[0] != s[0]:
                    moved += 1
                    if not tc_next(s[0], t[0], c):
                        violations.append(
                            ("TCSpec: [TCNext]_rmState", s, t))
                if t == s:
                    stutter += 1
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
                    sent |= t[2]
                    if not pc_type_ok(t, c, message):
                        violations.append(("PCTypeOK", s, t))
                    if not tc_consistent(t[0], c):
                        violations.append(("TCConsistent", s, t))
        level = nxt
        if level:
            depth += 1
            widest = max(widest, len(level))
    counts = dict(
        generated=generated, distinct=len(seen), depth=depth,
        n_initial=1, widest_level=widest,
        action_generated={a: per_action[a] for a in sorted(ACTIONS)},
        refine=dict(properties={"TCSpec": "holds" if not any(
            v[0].startswith("TCSpec") for v in violations) else "violated"},
            edges=edges, moved=moved, init_states=1),
        stuttering=stutter,
        messages_sent=len(sent), messages=len(message),
    )
    return counts, seen, violations


def closure_check(seen: set, c: Constants) -> None:
    for s in seen:
        for _, t in successors(s, c):
            assert t in seen, "the set is not closed"


# -- (e): the second enumeration, written another way ---------------------


def _dfs_states(c: Constants) -> set:
    """Depth-first, a state a plain dict of plain dicts and a Python set
    of message tuples (type, ins, bal, mbal, val, acc), the actions a
    table of (guard, effect) pairs over copies."""
    import copy

    def freeze(d):
        return (fn(d["rm"]),
                fn({i: fn({a: rec(**d["a"][i][a]) for a in d["a"][i]})
                    for i in d["a"]}),
                frozenset(rec(**{k: v for k, v in zip(
                    ("type", "ins", "bal", "mbal", "val", "acc"), m)
                    if v is not None}) for m in d["msgs"]))

    def msg(type, ins=None, bal=None, mbal=None, val=None, acc=None):
        return (type, ins, bal, mbal, val, acc)

    def chosen(d, rm, v):
        return any(all(msg("phase2b", rm, b, None, v, ac) in d["msgs"]
                       for ac in MS)
                   for b in c.Ballot for MS in c.Majority)

    def moves(d):
        table = []
        for rm in c.RM:
            def prep(e, rm=rm):
                e["rm"][rm] = "prepared"
                e["msgs"].add(msg("phase2a", rm, 0, None, "prepared"))

            def abort(e, rm=rm):
                e["rm"][rm] = "aborted"
                e["msgs"].add(msg("phase2a", rm, 0, None, "aborted"))

            def committed(e, rm=rm):
                e["rm"][rm] = "committed"

            def aborted(e, rm=rm):
                e["rm"][rm] = "aborted"

            table += [(d["rm"][rm] == "working", prep),
                      (d["rm"][rm] == "working", abort),
                      (msg("Commit") in d["msgs"], committed),
                      (msg("Abort") in d["msgs"], aborted)]
            for bal in c.Ballot[1:]:
                def p1a(e, rm=rm, bal=bal):
                    e["msgs"].add(msg("phase1a", rm, bal))

                table.append((True, p1a))
                free = not any(m[0] == "phase2a" and m[1] == rm
                               and m[2] == bal for m in d["msgs"])
                for MS in c.Majority:
                    got = [m for m in d["msgs"] if m[0] == "phase1b"
                           and m[1] == rm and m[3] == bal and m[5] in MS]
                    best = max([m[2] for m in got], default=-1)
                    val = "aborted" if best == -1 else next(
                        m[4] for m in got if m[2] == best)

                    def p2a(e, rm=rm, bal=bal, val=val):
                        e["msgs"].add(msg("phase2a", rm, bal, None, val))

                    table.append((free and {m[5] for m in got} == set(MS),
                                  p2a))
        table.append((all(chosen(d, rm, "prepared") for rm in c.RM),
                      lambda e: e["msgs"].add(msg("Commit"))))
        table.append((any(chosen(d, rm, "aborted") for rm in c.RM),
                      lambda e: e["msgs"].add(msg("Abort"))))
        for acc in c.Acceptor:
            for m in d["msgs"]:
                if m[0] == "phase1a":
                    def p1b(e, m=m, acc=acc):
                        a = e["a"][m[1]][acc]
                        e["msgs"].add(msg("phase1b", m[1], a["bal"], m[2],
                                          a["val"], acc))
                        a["mbal"] = m[2]

                    table.append((d["a"][m[1]][acc]["mbal"] < m[2], p1b))
                if m[0] == "phase2a":
                    def p2b(e, m=m, acc=acc):
                        e["a"][m[1]][acc] = dict(mbal=m[2], bal=m[2],
                                                 val=m[4])
                        e["msgs"].add(msg("phase2b", m[1], m[2], None,
                                          m[4], acc))

                    table.append((d["a"][m[1]][acc]["mbal"] <= m[2], p2b))
        for guard, effect in table:
            if guard:
                e = copy.deepcopy(d)
                effect(e)
                yield e

    d0 = dict(rm={rm: "working" for rm in c.RM},
              a={i: {a: dict(mbal=0, bal=-1, val="none")
                     for a in c.Acceptor} for i in c.RM},
              msgs=set())
    out = {freeze(d0)}
    stack = [d0]
    while stack:
        for e in moves(stack.pop()):
            k = freeze(e)
            if k not in out:
                out.add(k)
                stack.append(e)
    return out


def pins_for(config: dict, rm=None, ballots=None, mutant=None) -> dict:
    dep = config["deployment"]
    c = Constants(int(len(dep["RM"]) if rm is None else rm),
                  len(dep["Acceptor"]),
                  int(len(dep["Ballot"]) if ballots is None else ballots))
    t0 = time.time()
    counts, seen, violations = search(c, mutant)
    counts["seconds"] = round(time.time() - t0, 1)
    if mutant:
        # the control: no pins, the verdicts and the first failing edge
        _, _, every = search(c, mutant, stop_on_violation=False)
        kinds = sorted({v[0] for v in every})
        edge = next(v for v in every if v[0].startswith("TCSpec"))
        return dict(
            control=mutant, violated=kinds,
            edge=dict(source=dict(rmState=dict(edge[1][0]),
                                  msgs=len(edge[1][2])),
                      successor=dict(rmState=dict(edge[2][0]),
                                     msgs=len(edge[2][2]))),
            distinct_until_halt=counts["distinct"])
    assert not violations, violations[:2]  # (a), and both halves of TCSpec
    # (b)
    assert counts["generated"] == 1 + sum(
        counts["action_generated"].values())
    assert counts["refine"]["edges"] == counts["generated"] - 1
    closure_check(seen, c)  # (c)
    assert not CHOOSE_CLASH, CHOOSE_CLASH[:1]  # (d)
    counts["self_checks"] = ["invariants", "refinement", "closure",
                             "choose_unique"]
    second_at = dep.get("second_enumeration_at")
    if second_at:
        c1 = Constants(int(second_at), len(dep["Acceptor"]),
                       len(dep["Ballot"]))
        first = seen if len(c.RM) == int(second_at) and \
            len(c.Ballot) == len(dep["Ballot"]) else search(c1)[1]
        second = _dfs_states(c1)
        assert second == first, (
            f"the two enumerations differ at RM = {second_at}")
        counts["self_checks"].append(
            f"second enumeration at RM={second_at}: {len(second)} states")
    counts["seconds_with_checks"] = round(time.time() - t0, 1)
    return counts


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="paxoscommit-mc")
    p.add_argument("--rm", type=int, default=None,
                   help="override the number of resource managers")
    p.add_argument("--ballots", type=int, default=None,
                   help="override the number of ballots (Ballot = 0..B-1)")
    p.add_argument("--mutant", choices=("commit-on-any",), default=None,
                   help="the control: Decide commits on ANY instance")
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    print(json.dumps(pins_for(config, args.rm, args.ballots, args.mutant)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
