"""Regression tests for the struct frontend correctness fixes (ISSUE 1
satellites; reproducers from ADVICE.md):

1. Set equality against a constant set with out-of-universe elements must
   be constant False, not a comparison against K∩universe - the silent
   drop made `s = K` guards fire on states where they are semantically
   false and `s # K` invariants report false violations.
2. CHOOSE witness order: the device kernel must pick the same witness as
   the host evaluator (the _SORT_KEY-least satisfying element) or the
   two engines' state spaces drift apart on non-unique predicates.
3. Dynamic sequence indexing s[i] with i outside 1..Len(s) must emit the
   -1 trap (loud halt), never the where-chain default slot.
4. canon() must refuse a sequence of string-first 2-tuples it would
   silently reorder into a string-keyed function.
"""

import pytest

from jaxtlc.struct.engine import check_struct
from jaxtlc.struct.eval import StructEvalError, canon
from jaxtlc.struct.loader import load
from jaxtlc.struct.oracle import bfs


def _write_model(tmp_path, name, module, cfg):
    d = tmp_path / name
    d.mkdir()
    (d / f"{name}.tla").write_text(module)
    (d / f"{name}.cfg").write_text(cfg)
    return str(d / f"{name}.cfg")


# ---------------------------------------------------------------------------
# 1. set equality vs out-of-universe constants (ADVICE.md, compile.py:497)
# ---------------------------------------------------------------------------

_SETEQ = """
---- MODULE SetEq ----
VARIABLES s

Init == s = {"a"}

Add == /\\ "b" \\notin s
       /\\ s' = s \\cup {"b"}

Next == Add

Spec == Init /\\ [][Next]_s

Inv == s # {"a", "c"}
====
"""

_SETEQ_GUARD = """
---- MODULE SetEqG ----
VARIABLES s

Init == s = {"a"}

Grow == /\\ s = {"a", "c"}
        /\\ s' = s \\cup {"b"}

Shrink == /\\ s = {"a"}
          /\\ s' = {}

Next == Grow \\/ Shrink

Spec == Init /\\ [][Next]_s
====
"""


def test_set_neq_constant_outside_universe_not_violated(tmp_path):
    """ADVICE.md reproducer: Inv == s # {"a","c"} with "c" unreachable.
    The host oracle reports no violation; the device engine used to
    compare s against {"a","c"}∩universe = {"a"} and report a false
    positive."""
    cfg = _write_model(tmp_path, "SetEq", _SETEQ,
                       "SPECIFICATION\nSpec\nINVARIANT\nInv\n")
    m = load(cfg)
    ro = bfs(m.system, m.invariants, check_deadlock=False)
    assert not ro.violations
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation == 0
    assert (rd.generated, rd.distinct) == (ro.generated, ro.distinct)


def test_set_eq_constant_outside_universe_guard_never_fires(tmp_path):
    """Mirror case: a guard `s = {"a","c"}` must never fire (host: it is
    False at every reachable state), so only Shrink runs - the silent
    drop used to fire Grow at s={"a"} and corrupt exploration."""
    cfg = _write_model(tmp_path, "SetEqG", _SETEQ_GUARD,
                       "SPECIFICATION\nSpec\n")
    m = load(cfg)
    ro = bfs(m.system, m.invariants, check_deadlock=False)
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation == 0
    assert (rd.generated, rd.distinct, rd.depth) == (
        ro.generated, ro.distinct, ro.depth,
    )
    # s={"a"} -> {} via Shrink only: exactly 2 distinct states
    assert rd.distinct == 2


# ---------------------------------------------------------------------------
# 2. CHOOSE witness parity (ADVICE.md, compile.py:1343 vs eval.py:219)
# ---------------------------------------------------------------------------

# the pool's element universe (SInt(2..14), 13 values) is past
# UNROLL_LIMIT, so CHOOSE compiles through the mask path whose witness
# pick used to be universe-order (2 first) while the evaluator picks
# repr-least ("14" < "2"): state spaces diverged at Pick
_CHOOSY = """
---- MODULE Choosy ----
EXTENDS Naturals
VARIABLES pool, v

Init == /\\ pool = {2, 14}
        /\\ v = 0

Pick == /\\ v = 0
        /\\ v' = CHOOSE x \\in pool : x > 1
        /\\ UNCHANGED pool

Bump == /\\ v = 14
        /\\ v' = 1
        /\\ UNCHANGED pool

Next == Pick \\/ Bump

Spec == Init /\\ [][Next]_<<pool, v>>
====
"""


def test_choose_witness_matches_host_evaluator(tmp_path):
    """Non-unique CHOOSE predicate: both engines must pick the same
    witness (14, the repr-least of {2,14}), making Bump reachable on
    both paths."""
    cfg = _write_model(tmp_path, "Choosy", _CHOOSY,
                       "SPECIFICATION\nSpec\n")
    m = load(cfg)
    ro = bfs(m.system, m.invariants, check_deadlock=False)
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation == 0
    assert (rd.generated, rd.distinct, rd.depth) == (
        ro.generated, ro.distinct, ro.depth,
    )
    # the witness is 14: Bump fires, so v reaches 1 -> 3 distinct states
    assert rd.distinct == 3


# ---------------------------------------------------------------------------
# 3. dynamic sequence index out of range -> -1 trap (compile.py:681)
# ---------------------------------------------------------------------------

_SEQ_OOB = """
---- MODULE SeqOob ----
EXTENDS Naturals, Sequences
VARIABLES s, v

Init == /\\ s = <<5>>
        /\\ v = 0

Step == /\\ v = 0
        /\\ v' = s[v + 2]
        /\\ UNCHANGED s

Next == Step

Spec == Init /\\ [][Next]_<<s, v>>
====
"""

_SEQ_OK = """
---- MODULE SeqOk ----
EXTENDS Naturals, Sequences
VARIABLES s, v

Init == /\\ s = <<5>>
        /\\ v = 0

Step == /\\ v = 0
        /\\ v' = s[v + 1]
        /\\ UNCHANGED s

Next == Step

Spec == Init /\\ [][Next]_<<s, v>>
====
"""


def test_dynamic_seq_index_out_of_range_traps(tmp_path):
    """s[2] with Len(s)=1: the host evaluator raises; the device engine
    must halt loudly (trap) - it used to clamp to the last slot and
    silently produce v'=5."""
    cfg = _write_model(tmp_path, "SeqOob", _SEQ_OOB,
                       "SPECIFICATION\nSpec\n")
    m = load(cfg)
    with pytest.raises(StructEvalError):
        bfs(m.system, m.invariants, check_deadlock=False)
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    # loud halt (trap surfaces as the slot-overflow code), never a
    # silent wrong value
    assert rd.violation != 0
    assert "overflow" in rd.violation_name


def test_dynamic_seq_index_in_range_unaffected(tmp_path):
    """The trap must not fire for in-range dynamic reads: s[1] with
    Len(s)=1 still evaluates and both engines agree."""
    cfg = _write_model(tmp_path, "SeqOk", _SEQ_OK,
                       "SPECIFICATION\nSpec\n")
    m = load(cfg)
    ro = bfs(m.system, m.invariants, check_deadlock=False)
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation == 0
    assert (rd.generated, rd.distinct, rd.depth) == (
        ro.generated, ro.distinct, ro.depth,
    )
    assert rd.distinct == 2  # v: 0 -> 5


# ---------------------------------------------------------------------------
# 4. canon() ambiguity guard (eval.py:75)
# ---------------------------------------------------------------------------


def test_canon_rejects_misclassified_pair_sequence():
    # a sequence of string-first pairs canon would REORDER: loud error
    with pytest.raises(StructEvalError, match="ambiguous"):
        canon((("b", 1), ("a", 2)))
    # duplicate keys prove it is not a function either
    with pytest.raises(StructEvalError, match="ambiguous"):
        canon((("a", 1), ("a", 2)))


def test_canon_unaffected_cases():
    # genuine records/functions arrive key-sorted with distinct keys
    assert canon((("a", 1), ("b", 2))) == (("a", 1), ("b", 2))
    # sequences whose elements are not string-first pairs pass through
    assert canon(((1, "a"), (2, "b"))) == ((1, "a"), (2, "b"))
    assert canon((("a",), ("b",))) == (("a",), ("b",))
    # nested canonicalization still recurses into values
    assert canon((("k", frozenset({2, 1})),)) == (("k", frozenset({1, 2})),)
    # the empty tuple stays the empty function/sequence
    assert canon(()) == ()


# ---------------------------------------------------------------------------
# 5. what Dijkstra's EWD840 needs of the compiler (ISSUE 41): a
#    quantifier over a range with a bound read off the state; set
#    difference with a bound variable; `@` under IF inside EXCEPT
# ---------------------------------------------------------------------------

_RING = """
---- MODULE Ring ----
EXTENDS Naturals
VARIABLES pos, mark

Nodes == 0 .. 3

Init == pos = 3 /\\ mark = [i \\in Nodes |-> FALSE]

Step(i) == /\\ pos = i
           /\\ pos' = i - 1
           /\\ \\E j \\in Nodes \\ {i} :
                 mark' = [mark EXCEPT ![j] = IF j > i THEN TRUE ELSE @]

Next == \\E i \\in Nodes \\ {0} : Step(i)

Spec == Init /\\ [][Next]_<<pos, mark>>

Below == \\E j \\in 0 .. pos : ~ mark[j]
Above == \\A j \\in pos + 1 .. 3 : j > pos
Pair == \\A i, j \\in pos .. 2 : i + j >= 2 * pos
====
"""


def test_quantifier_over_a_range_with_a_state_bound(tmp_path):
    """`\\E j \\in 0 .. pos`, `\\A j \\in pos + 1 .. 3` and a two-name
    binder over `pos .. 2` (which is empty at pos = 3) compile to the
    quantifier over the bounds' constant hull with membership as a
    guard; the step's `Nodes \\ {i}` under a binder and `IF .. ELSE @` under EXCEPT
    ride along.  Counts and verdict equal the host evaluator's."""
    cfg = _write_model(tmp_path, "Ring", _RING,
                       "SPECIFICATION\nSpec\nINVARIANT\nBelow\nAbove\n"
                       "Pair\n")
    m = load(cfg)
    ro = bfs(m.system, m.invariants, check_deadlock=False)
    assert not ro.violations and ro.distinct > 4
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation == 0
    assert (rd.generated, rd.distinct, rd.depth) == (
        ro.generated, ro.distinct, ro.depth)


def test_a_false_range_quantifier_is_reported(tmp_path):
    """The guard is real: `\\A j \\in 0 .. pos : mark[j]` fails on the
    initial state, on the device as on the host."""
    module = _RING.replace("Below ==", "Bad == \\A j \\in 0 .. pos : "
                           "mark[j]\nBelow ==")
    cfg = _write_model(tmp_path, "Ring", module,
                       "SPECIFICATION\nSpec\nINVARIANT\nBad\n")
    m = load(cfg)
    assert bfs(m.system, m.invariants, check_deadlock=False).violations
    rd = check_struct(m, chunk=16, queue_capacity=64, fp_capacity=1024,
                      check_deadlock=False)
    assert rd.violation != 0
