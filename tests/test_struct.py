"""Structural frontend (E1): execute the reference's own KubeAPI.tla.

The decisive round-5 capability: the generic engine no longer needs a
hand-written kernel to run the reference spec - jaxtlc.struct parses the
committed translation (/root/reference/KubeAPI.tla:373-768) and executes
it.  Ground truth: the hand oracle (itself pinned to MC.out) and the TLC
log's exact counts (MC.out:32,1098,1101) and per-action coverage totals
(MC.out:78-621).
"""

import dataclasses
import os

import pytest

from jaxtlc.config import MODEL_1
from jaxtlc.spec import oracle as H
from jaxtlc.spec.texpr import state_env as hand_env
from jaxtlc.struct.eval import Evaluator, TlaAssertionError
from jaxtlc.struct.loader import load
from jaxtlc.struct.oracle import bfs, violation_trace
from jaxtlc.struct.parser import parse_expression, parse_module

# tests/ is not a package: shared expectation constants live in the
# plain module mc_expect (importable as top-level from any test module)
from mc_expect import MC_OUT_ACTIONS, REF_CFG  # noqa: F401

# skip (not fail) when the reference toolbox isn't mounted, so tier-1
# red always means a real regression (matches the guards on the struct
# engine tests PR 3 added)
needs_reference = pytest.mark.skipif(
    not os.path.exists(REF_CFG), reason="reference toolbox not mounted"
)


def _load(fail: bool, timeout: bool):
    return load(REF_CFG, const_overrides={
        "REQUESTS_CAN_FAIL": fail, "REQUESTS_CAN_TIMEOUT": timeout,
    })


# ---------------------------------------------------------------------------
# Parser / evaluator units
# ---------------------------------------------------------------------------


@needs_reference
def test_parse_reference_module():
    with open("/root/reference/KubeAPI.tla") as f:
        mod = parse_module(f.read())
    assert mod.name == "KubeAPI"
    assert mod.variables == (
        "apiState", "requests", "listRequests", "pc", "stack",
        "op", "obj", "kind", "shouldReconcile",
    )
    # every PlusCal label action is a definition
    for a in MC_OUT_ACTIONS:
        assert a in mod.defs, a
    assert mod.defs["Spec"].body[:3] == ("spec", "Init", "Next")


def _ev(src, env=None, defs=None):
    return Evaluator(defs or {}, {}).eval(parse_expression(src), env or {})


def test_eval_core_forms():
    # :> binds tighter than @@ ; @@ is left-biased (Write semantics)
    assert _ev('"vv" :> {} @@ [n |-> "foo", vv |-> {"c"}]') == (
        ("n", "foo"), ("vv", frozenset()),
    )
    assert _ev('DOMAIN [n |-> 1, k |-> 2]') == frozenset({"n", "k"})
    assert _ev('{"n", "k"} \\subseteq DOMAIN [n |-> 1, k |-> 2, s |-> 3]')
    assert _ev('[x \\in {} |-> {}]') == ()
    assert _ev('Head(<<1, 2, 3>>)') == 1
    assert _ev('Tail(<<1, 2, 3>>)') == (2, 3)
    assert _ev('<<1>> \\o <<2, 3>>') == (1, 2, 3)
    assert _ev('{x \\in {1, 2, 3, 4} : x > 2}') == frozenset({3, 4})
    assert _ev('{x + 10 : x \\in {1, 2}}') == frozenset({11, 12})
    assert _ev('CHOOSE x \\in {3, 1, 2} : x > 1') == 2
    assert _ev('[f EXCEPT !["a"].b = @ + 1]',
               {"f": (("a", (("b", 1),)),)}) == (("a", (("b", 2),)),)
    assert _ev('Cardinality([{"u"} -> BOOLEAN])') == 2
    assert _ev('IF 1 > 2 THEN "a" ELSE "b"') == "b"
    assert _ev('CASE 1 > 2 -> "a" [] 2 > 1 -> "b"') == "b"
    assert _ev('LET two == 2 sq(x) == x + x IN sq(two)') == 4


def test_junction_list_alignment():
    src = (
        "  /\\ \\/ /\\ 1 > 2\n"
        "        /\\ 2 > 3\n"
        "     \\/ /\\ 2 > 1\n"
        "        /\\ 3 > 2\n"
        "  /\\ 4 > 3\n"
    )
    assert _ev(src) is True


def test_assert_raises():
    with pytest.raises(TlaAssertionError):
        _ev('Assert(FALSE, "boom")')


# ---------------------------------------------------------------------------
# The reference model through the structural path
# ---------------------------------------------------------------------------


@needs_reference
def test_reference_initial_states():
    m = load(REF_CFG)
    assert m.root_name == "KubeAPI"
    assert [a for a, _ in m.fairness] == ["Next"]  # WF_vars(Next)
    assert m.constants["REQUESTS_CAN_FAIL"] is True
    assert m.constants["REQUESTS_CAN_TIMEOUT"] is True
    inits = m.system.initial_states()
    assert len(inits) == 2  # MC.out:32
    assert set(m.invariants) == {"TypeOK", "OnlyOneVersion"}


@needs_reference
def test_ff_corner_counts_and_state_set():
    """FF corner: exact counts AND state-set equality vs the hand oracle
    (the same differential that pinned the hand kernel, SURVEY.md §4)."""
    m = _load(False, False)
    r = bfs(m.system, m.invariants, collect_states=True)
    seen = r.states
    assert (r.generated, r.distinct, r.depth) == (17020, 8203, 109)
    assert not r.violations

    cfg = dataclasses.replace(
        MODEL_1, requests_can_fail=False, requests_can_timeout=False
    )
    frontier = list(dict.fromkeys(H.initial_states(cfg)))
    seen_h = set(frontier)
    while frontier:
        nxt = []
        for s in frontier:
            for x in H.successors(s, cfg):
                if x.state not in seen_h:
                    seen_h.add(x.state)
                    nxt.append(x.state)
        frontier = nxt
    vars_ = m.system.variables
    hand_states = {
        tuple(hand_env(s, cfg)[v] for v in vars_) for s in seen_h
    }
    assert hand_states == set(seen)


@pytest.mark.slow
def test_tf_corner():
    m = _load(True, False)
    r = bfs(m.system, m.invariants)
    assert (r.generated, r.distinct, r.depth) == (232363, 89084, 128)
    assert not r.violations


@pytest.mark.slow
def test_model1_full_parity_with_mc_out():
    """The round-5 E1 exit criterion: the generic (structural) path runs
    the UNMODIFIED reference model and reproduces TLC's run exactly -
    counts (MC.out:1098,1101) and per-action generated totals
    (MC.out:78-621, order-independent so comparable across engines)."""
    m = load(REF_CFG)
    r = bfs(m.system, m.invariants)
    assert (r.generated, r.distinct, r.depth) == (577736, 163408, 124)
    assert not r.violations
    assert r.max_outdegree == 4
    for act, (_, gen) in MC_OUT_ACTIONS.items():
        assert r.action_generated.get(act) == gen, (
            act, r.action_generated.get(act), gen,
        )
    # distinct attribution order differs between engines; the sum is exact
    assert sum(r.action_distinct.values()) == 163408 - 2


# ---------------------------------------------------------------------------
# Violation machinery through the structural path
# ---------------------------------------------------------------------------

_COUNTER_MODULE = """
---- MODULE Counter ----
EXTENDS Naturals
VARIABLES x

Init == x = 0

Up == /\\ x < 4
      /\\ x' = x + 1

Next == Up

Spec == Init /\\ [][Next]_x

Small == x < 3
====
"""


def test_struct_invariant_violation_and_trace(tmp_path):
    d = tmp_path / "m"
    d.mkdir()
    (d / "Counter.tla").write_text(_COUNTER_MODULE)
    (d / "Counter.cfg").write_text(
        "SPECIFICATION\nSpec\nINVARIANT\nSmall\n"
    )
    m = load(str(d / "Counter.cfg"))
    r = bfs(m.system, m.invariants)
    assert r.violations and r.violations[0][0] == "Small"
    found = violation_trace(m.system, m.invariants)
    kind, chain = found
    assert kind == "Small"
    xs = [dict(zip(m.system.variables, st))["x"] for st, _ in chain]
    assert xs == [0, 1, 2, 3]
    assert chain[-1][1] == "Up"
    # deadlock at x = 4 once the invariant is dropped
    r2 = bfs(m.system, {})
    assert r2.violations and r2.violations[0][0] == "deadlock"
    r3 = bfs(m.system, {}, check_deadlock=False)
    assert not r3.violations
