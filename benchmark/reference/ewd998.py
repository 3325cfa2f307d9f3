"""Plain reference for the EWD998 configuration: Dijkstra's / Safra's
termination detection in a ring with asynchronous messages
(tlaplus/Examples, specifications/ewd998/EWD998.tla), its five actions
written out by hand as Python over tuples, checked the way its
EWD998.cfg asks: under `CONSTRAINT StateConstraint`.

    python benchmark/reference/ewd998.py [<config name>] [--n N]
                                         [--keep-discarded]

prints the pins of benchmark/configs/<config name>.json (default
ewd998-mc) as one JSON line (pin.py dispatches on names it knows and may
not be edited).  `--n` overrides the deployment's N (the tests' rung is
N = 2).

It imports nothing of the program.  A state is the tuple
(active, color, counter, pending, pos, q, tcolor): `active` and `color`
tuples of booleans over the nodes 0..N-1 (color True = "black"),
`counter` and `pending` tuples of integers, the token's three fields
flat.  The search is a level-synchronous BFS with TLC's accounting as
this repo reads it for a constrained model:

* the initial states count as generated; depth counts Init as level 1;
* EVERY successor of every kept state counts as generated and toward
  its action's total;
* a disjunction in an action is a branch per disjunct, also where the
  disjuncts are guards: InitiateProbe's "previous round not conclusive"
  is `\\/ token.color = "black" \\/ color[0] = "black" \\/ counter[0] +
  token.q > 0`, and a state in which k of the three hold generates
  InitiateProbe's one successor k times (TLC's next-state enumeration
  walks the disjuncts, and so do this repo's evaluator and compiled
  step; the planner's count in ISSUE 39, one successor a state, is
  756,158 for this action and 9,486,477 in all);
* a successor that fails the constraint (some counter[i] > 3, some
  pending[i] > 3, or token.q > 9) is then DISCARDED: not kept, not
  expanded, and its invariants are not evaluated;
* an initial state outside the constraint would be checked and not kept
  (none of EWD998's 2^N x 2^N initial states is).

Self-checks, run with every pin (an AssertionError instead of a line):
 (a) TypeOK, Inv (Safra's P0 /\\ (P1 \\/ P2 \\/ P3 \\/ P4)) and
     TerminationDetection on every kept state, and
     Sum(counter) = Sum(pending) beside them;
 (b) generated = the initial states + the sum of the per-action totals;
 (c) the kept set is closed: every successor of a kept state is kept or
     fails the constraint (the counts of the second pass equal the
     first's);
 (d) the three invariants also hold on every DISCARDED successor, so a
     checker that evaluates invariants before the constraint (the other
     reading of TLC's rule, Specifying Systems 14.3.1) gives the same
     verdict on this model;
 (e) at N = 2 a second enumeration written another way (depth-first,
     states as dicts, the actions as guard / effect pairs) gives the
     same kept set and the same number of discards.

`--keep-discarded` is the control: a successor the constraint rejects is
kept all the same when it fits the codec's ranges (every counter within
-CODEC_LO..4, every pending within 0..4, q within -CODEC_LO..12: one
step outside the constraint) - the "cheaper" seam that skips the
predicate.  More states, and no pin.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ACTIONS = ("InitiateProbe", "PassToken", "SendMsg", "RecvMsg",
           "Deactivate")


def initial_states(n: int) -> List[tuple]:
    """active \\in [Node -> BOOLEAN], color \\in [Node -> Color], the
    rest fixed: 2^n x 2^n states."""
    out = []
    zeros = (0,) * n
    for a in range(1 << n):
        active = tuple(bool(a >> i & 1) for i in range(n))
        for c in range(1 << n):
            color = tuple(bool(c >> i & 1) for i in range(n))
            out.append((active, color, zeros, zeros, 0, 0, True))
    return out


def _set(t: tuple, i: int, v) -> tuple:
    return t[:i] + (v,) + t[i + 1:]


def successors(s: tuple, n: int) -> List[Tuple[str, tuple]]:
    """Every (action, successor) of `s`, one entry per witness, in the
    module's order: System (InitiateProbe, PassToken(i) for i in
    Node \\ {0}), then Environment (for each i: SendMsg(i) once per
    receiver j # i, RecvMsg(i), Deactivate(i))."""
    active, color, counter, pending, pos, q, tcolor = s
    out = []
    if pos == 0:
        probe = (active, _set(color, 0, False), counter, pending,
                 n - 1, 0, False)
        # the guard is a disjunction of three: one successor per
        # disjunct that holds (see the module docstring)
        out += [("InitiateProbe", probe)] * (
            int(tcolor) + int(color[0]) + int(counter[0] + q > 0))
    for i in range(1, n):
        if not active[i] and pos == i:
            out.append(("PassToken",
                        (active, _set(color, i, False), counter, pending,
                         pos - 1, q + counter[i], True if color[i]
                         else tcolor)))
    for i in range(n):
        if active[i]:
            up = _set(counter, i, counter[i] + 1)
            for j in range(n):
                if j != i:
                    out.append(("SendMsg",
                                (active, color, up,
                                 _set(pending, j, pending[j] + 1), pos, q,
                                 tcolor)))
        if pending[i] > 0:
            out.append(("RecvMsg",
                        (_set(active, i, True), _set(color, i, True),
                         _set(counter, i, counter[i] - 1),
                         _set(pending, i, pending[i] - 1), pos, q,
                         tcolor)))
        if active[i]:
            out.append(("Deactivate",
                        (_set(active, i, False), color, counter, pending,
                         pos, q, tcolor)))
    return out


def in_constraint(s: tuple, bounds: dict) -> bool:
    return (max(s[2]) <= bounds["counter"]
            and max(s[3]) <= bounds["pending"]
            and s[5] <= bounds["token_q"])


def invariants_hold(s: tuple, n: int) -> bool:
    """TypeOK, Inv and TerminationDetection, and P0's two sums."""
    active, color, counter, pending, pos, q, tcolor = s
    type_ok = (all(isinstance(x, int) for x in counter)
               and all(isinstance(x, int) and x >= 0 for x in pending)
               and 0 <= pos < n)
    b = sum(pending)
    p0 = b == sum(counter)
    p1 = (not any(active[pos + 1:])
          and q == (0 if pos == n - 1 else sum(counter[pos + 1:])))
    p2 = sum(counter[:pos + 1]) + q > 0
    p3 = any(color[:pos + 1])
    p4 = tcolor
    inv = p0 and (p1 or p2 or p3 or p4)
    detected = (pos == 0 and not tcolor and q + counter[0] == 0
                and not color[0] and not active[0])
    termination = not any(active) and b == 0
    return type_ok and inv and (not detected or termination)


def search(n: int, bounds: dict, keep=None):
    """The constrained BFS.  `keep(state)` overrides the constraint as
    the rule for what is kept (the control)."""
    if keep is None:
        def keep(st):
            return in_constraint(st, bounds)
    inits = initial_states(n)
    generated = len(inits)
    seen = set()
    frontier = []
    discarded_inits = 0
    for s in inits:
        assert invariants_hold(s, n), f"invariant fails on initial {s}"
        if not keep(s):
            discarded_inits += 1
        elif s not in seen:
            seen.add(s)
            frontier.append(s)
    per_action = dict.fromkeys(ACTIONS, 0)
    discarded = 0
    depth, widest = 1, len(frontier)
    lo = {"counter": 0, "pending": 0, "token_q": 0}
    hi = {"counter": 0, "pending": 0, "token_q": 0}
    while frontier:
        nxt = []
        for s in frontier:
            for name, t in successors(s, n):
                generated += 1
                per_action[name] += 1
                if t in seen:
                    continue
                if not keep(t):
                    discarded += 1
                    # (d)
                    assert invariants_hold(t, n), (
                        f"an invariant fails on the discarded {t}")
                    continue
                assert invariants_hold(t, n), f"invariant fails on {t}"
                seen.add(t)
                nxt.append(t)
                for k, vals in (("counter", t[2]), ("pending", t[3]),
                                ("token_q", (t[5],))):
                    lo[k] = min(lo[k], min(vals))
                    hi[k] = max(hi[k], max(vals))
        frontier = nxt
        if frontier:
            depth += 1
            widest = max(widest, len(frontier))
    return dict(generated=generated, distinct=len(seen), depth=depth,
                action_generated={k: v for k, v in per_action.items()
                                  if v},
                discarded=discarded, discarded_inits=discarded_inits,
                widest_level=widest, kept_ranges=dict(lo=lo, hi=hi),
                n_initial=len(inits)), seen


def closure_check(seen: set, n: int, bounds: dict) -> int:
    """(c): every successor of a kept state is kept or fails the
    constraint; returns the number that fail it, counted with
    multiplicity over ALL successors (seen or not)."""
    failed = 0
    for s in seen:
        for _, t in successors(s, n):
            if in_constraint(t, bounds):
                assert t in seen, f"{t} satisfies the constraint, unkept"
            else:
                assert t not in seen
                failed += 1
    return failed


# -- (e): the second enumeration, written another way ----------------------


def _dfs_kept(n: int, bounds: dict):
    """Depth-first over states as dicts; the actions as (guard, effect)
    pairs over a mutable copy.  Returns (kept set as frozen tuples, the
    number of successor edges that fail the constraint)."""
    def freeze(d):
        return (tuple(d["active"]),
                tuple(c == "black" for c in d["color"]),
                tuple(d["counter"]), tuple(d["pending"]),
                d["token"]["pos"], d["token"]["q"],
                d["token"]["color"] == "black")

    def thaw(s):
        return dict(active=list(s[0]),
                    color=["black" if c else "white" for c in s[1]],
                    counter=list(s[2]), pending=list(s[3]),
                    token=dict(pos=s[4], q=s[5],
                               color="black" if s[6] else "white"))

    def copy(d):
        return dict(active=list(d["active"]), color=list(d["color"]),
                    counter=list(d["counter"]),
                    pending=list(d["pending"]), token=dict(d["token"]))

    def moves(d):
        tok = d["token"]
        if tok["pos"] == 0 and (tok["color"] == "black"
                                or d["color"][0] == "black"
                                or d["counter"][0] + tok["q"] > 0):
            e = copy(d)
            e["token"] = dict(pos=n - 1, q=0, color="white")
            e["color"][0] = "white"
            yield e
        for i in range(n):
            if i != 0 and not d["active"][i] and tok["pos"] == i:
                e = copy(d)
                e["token"]["pos"] -= 1
                e["token"]["q"] += d["counter"][i]
                if d["color"][i] == "black":
                    e["token"]["color"] = "black"
                e["color"][i] = "white"
                yield e
            if d["active"][i]:
                for j in range(n):
                    if j == i:
                        continue
                    e = copy(d)
                    e["counter"][i] += 1
                    e["pending"][j] += 1
                    yield e
                e = copy(d)
                e["active"][i] = False
                yield e
            if d["pending"][i] > 0:
                e = copy(d)
                e["pending"][i] -= 1
                e["counter"][i] -= 1
                e["color"][i] = "black"
                e["active"][i] = True
                yield e

    def ok(d):
        return (all(c <= bounds["counter"] for c in d["counter"])
                and all(p <= bounds["pending"] for p in d["pending"])
                and d["token"]["q"] <= bounds["token_q"])

    kept, failed = set(), 0
    stack = [s for s in initial_states(n)]
    kept.update(stack)
    while stack:
        d = thaw(stack.pop())
        for e in moves(d):
            if not ok(e):
                failed += 1
                continue
            f = freeze(e)
            if f not in kept:
                kept.add(f)
                stack.append(f)
    return kept, failed


def pins_for(config: dict, n=None, keep_discarded: bool = False) -> dict:
    dep = config["deployment"]
    n = int(dep["N"] if n is None else n)
    bounds = dep["constraint_bounds"]
    t0 = time.time()
    keep = None
    if keep_discarded:
        codec = dep["control_codec_hi"]

        def keep(st):
            return (max(st[2]) <= codec["counter"]
                    and max(st[3]) <= codec["pending"]
                    and st[5] <= codec["token_q"])
    counts, seen = search(n, bounds, keep)
    counts["seconds"] = round(time.time() - t0, 1)
    if keep_discarded:
        counts["control"] = "keep-discarded"
        return counts
    # (b)
    assert counts["generated"] == counts["n_initial"] + sum(
        counts["action_generated"].values())
    # (c): multiplicity differs from `discarded` (which counts a failing
    # successor every time it is generated, as the engine does): both
    # count edges, so they are equal
    failed = closure_check(seen, n, bounds)
    assert failed == counts["discarded"], (failed, counts["discarded"])
    counts["self_checks"] = ["invariants", "sums", "closure",
                             "discarded_invariants"]
    if n == 2 or dep.get("second_enumeration_at"):
        m = 2
        first = seen if n == m else search(m, bounds)[1]
        first_failed = failed if n == m else closure_check(first, m, bounds)
        second, second_failed = _dfs_kept(m, bounds)
        assert second == first, "the two enumerations differ at N = 2"
        assert second_failed == first_failed
        counts["self_checks"].append(
            f"second enumeration at N=2: {len(second)} kept, "
            f"{second_failed} discards")
    counts["seconds_with_checks"] = round(time.time() - t0, 1)
    return counts


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("config", nargs="?", default="ewd998-mc")
    p.add_argument("--n", type=int, default=None,
                   help="override the deployment's N")
    p.add_argument("--keep-discarded", action="store_true",
                   help="the control: keep what fits the codec")
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    print(json.dumps(pins_for(config, args.n, args.keep_discarded)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
