"""Lamport's Paxos (specs/Paxos.toolbox/Model_1) through the structural
frontend: what the module needed of the parser and the loader, the
declared universe of `msgs`, the universe-lane form of `\\E m \\in msgs`
against the host evaluator, the counts of the plain reference
(benchmark/reference/paxos.py) against struct/oracle.py and against the
compiled engine through api.run_check, the struct route's spans and
counters, and a seeded mutation that breaks agreement.

One module fixture loads and compiles the model at Ballot == 0..1 once
(80 static lanes, 32 slots); the 0..2 rung (185,369 states) is `slow`.
"""

import collections
import importlib.util
import io
import json
import os
import random

import numpy as np
import pytest

from jaxtlc.struct.eval import Evaluator, StructEvalError
from jaxtlc.struct.loader import load
from jaxtlc.struct.oracle import bfs
from jaxtlc.struct.parser import parse_expression, parse_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = os.path.join(ROOT, "specs", "Paxos.toolbox", "Model_1")
CFG = os.path.join(MODEL, "MC.cfg")

# (generated, distinct, depth, per-action generated) of the plain
# reference, TLC's accounting: every satisfying (Q, m) of Phase2a counts
ROW_01 = (23563, 3921, 17, {"Phase1a": 7842, "Phase1b": 2448,
                            "Phase2a": 1560, "Phase2b": 11712})
ROW_02 = (1361380, 185369, 25, {"Phase1a": 556107, "Phase1b": 125556,
                                "Phase2a": 118308, "Phase2b": 561408})


def ballots(n):
    return {"Ballot": frozenset(range(n))}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "paxos_reference",
        os.path.join(ROOT, "benchmark", "reference", "paxos.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    return load(CFG, const_overrides=ballots(2))


@pytest.fixture(scope="module")
def host(model):
    """struct/oracle.py's BFS of the 0..1 rung, once (~10 s)."""
    return bfs(model.system, model.invariants, check_deadlock=False,
               collect_states=True)


@pytest.fixture(scope="module")
def backend(model):
    from jaxtlc.struct.cache import get_backend

    return get_backend(model, False)


# -- parser and loader repairs, a three-line module each -------------------


def test_unbounded_choose_parses_and_is_not_evaluated():
    m = parse_module("---- MODULE M ----\nCONSTANT Value\n"
                     "None == CHOOSE v : v \\notin Value\n====\n")
    assert m.defs["None"].body[:3] == ("choose", "v", None)
    with pytest.raises(StructEvalError, match="unbounded CHOOSE"):
        Evaluator(m.defs, {"Value": frozenset({"v1"})}).eval(
            m.defs["None"].body, {})
    # a model overrides it: the constant wins over the definition
    assert Evaluator(m.defs, {"None": "None"}).eval(("name", "None"),
                                                    {}) == "None"


def test_record_set_constructor():
    ast = parse_expression('[type : {"1a"}, bal : 0..1]')
    assert ast[0] == "recset"
    assert Evaluator({}, {}).eval(ast, {}) == frozenset({
        (("bal", 0), ("type", "1a")), (("bal", 1), ("type", "1a"))})


@pytest.mark.parametrize("sym,want", [
    ("\\geq", ">="), ("\\leq", "<="), ("=<", "<="), (">=", ">=")])
def test_order_symbols(sym, want):
    assert parse_expression(f"m.mbal {sym} 0")[:2] == ("cmp", want)
    assert parse_expression(f"{{x \\in S : x {sym} 0}}")[3][1] == want


def test_quantifier_with_several_binder_groups():
    ast = parse_expression("\\A b1, b2 \\in B, v \\in V : b1 = b2")
    assert ast[:2] == ("forall", ["b1", "b2"])
    assert ast[3][:2] == ("forall", ["v"])
    ev = Evaluator({}, {"B": frozenset({0}), "V": frozenset({"x"})})
    assert ev.eval(ast, {}) is True


def test_model_values_of_the_model_module_reach_a_replacement(tmp_path):
    (tmp_path / "S.tla").write_text(
        "---- MODULE S ----\nCONSTANT Acceptor, Quorum\nVARIABLE x\n"
        "Init == x = 0\nNext == x' = x\nSpec == Init /\\ [][Next]_x\n"
        "====\n")
    (tmp_path / "MC.tla").write_text(
        "---- MODULE MC ----\nEXTENDS S\nCONSTANTS a1, a2\n"
        "MCQuorum == {{a1}, {a1, a2}}\n====\n")
    (tmp_path / "MC.cfg").write_text(
        "CONSTANTS\na1 = a1\na2 = a2\nAcceptor = {a1, a2}\n"
        "Quorum <- MCQuorum\nSPECIFICATION\nSpec\n")
    m = load(str(tmp_path / "MC.cfg"))
    assert m.constants["Quorum"] == frozenset({
        frozenset({"a1"}), frozenset({"a1", "a2"})})


# -- the model as shipped --------------------------------------------------


def test_module_loads_unmodified():
    m = load(CFG)
    assert m.constants["Ballot"] == frozenset(range(4))
    assert m.constants["None"] == "None"
    assert len(m.constants["Quorum"]) == 3
    assert tuple(m.module.variables) == ("maxBal", "maxVBal", "maxVal",
                                         "msgs")
    assert list(m.invariants) == ["TypeOK", "Agreement"]


@pytest.mark.parametrize("n_ballots,bits", [(2, 72), (3, 135), (4, 216)])
def test_msgs_universe_is_the_declared_message_set(n_ballots, bits):
    """Shapes only, no compile: `msgs \\subseteq Message` makes Message
    the mask's universe (the product of every field's values would be
    1,536 at 0..1).  ISSUE 31's 48 / 90 / 144 counts the (mbal, mval)
    pairs a run reaches; `Message` as the module defines it is this."""
    from jaxtlc.struct.shapes import SEnum, infer_shapes, typeok_hints

    m = load(CFG, const_overrides=ballots(n_ballots))
    sy = m.system
    sh = infer_shapes(sy.ev, sy.variables, sy.init_ast, sy.next_ast,
                      hints=typeok_hints(sy.ev, m.invariants,
                                         sy.variables))
    elem = sh["msgs"].elem
    assert isinstance(elem, SEnum)
    assert set(elem.values) == sy.ev.eval(("name", "Message"), {})
    assert len(elem.values) == bits


def test_reference_rows_and_host_oracle(reference, host):
    r = reference.bfs(3, 2, 2, 2)
    assert (r.generated, r.distinct, r.depth,
            r.action_generated) == ROW_01
    assert (max(r.levels), r.universe_bits) == (780, 72)
    r2 = reference.bfs(3, 2, 3, 2)
    assert (r2.generated, r2.distinct, r2.depth,
            r2.action_generated) == ROW_02
    assert (max(r2.levels), r2.max_assignments) == (26106, 21)
    assert not r.violations and not r2.violations
    assert not host.violations
    assert (host.generated, host.distinct, host.depth,
            host.action_generated) == ROW_01
    assert host.levels == r.levels


def test_universe_lanes_and_compaction(backend):
    # 2 Phase1a + 3*2 Phase1b + 3*4 Phase2b + 4*3*(1 + 2*2) Phase2a
    assert backend.cdc.static_lanes == 80
    assert backend.n_lanes == 32 and backend.lane_action is None
    assert backend.labels == ("Phase1a", "Phase1b", "Phase2a", "Phase2b")


def test_compiled_step_equals_the_host_evaluator(model, host, backend):
    """Seeded random reachable states: the compiled step's successors,
    with their action labels, are the host evaluator's, as multisets;
    no trap, no assertion."""
    import jax

    states = random.Random(31).sample(sorted(host.states, key=repr), 96)
    cdc = backend.cdc
    batch = np.stack([cdc.encode(s) for s in states])
    succs, valid, action, afail, ovf = jax.jit(jax.vmap(backend.step))(
        batch)
    succs, valid, action = map(np.asarray, (succs, valid, action))
    assert not np.asarray(afail).any() and not np.asarray(ovf).any()
    fired = 0
    for i, st in enumerate(states):
        want = collections.Counter(model.system.successors(st))
        got = collections.Counter(
            (backend.labels[action[i, k]], cdc.decode(succs[i, k]))
            for k in np.flatnonzero(valid[i]))
        assert got == want, st
        fired += sum(want.values())
    assert fired > 4 * len(states)


def test_compaction_keeps_every_live_lane_or_halts():
    import jax.numpy as jnp

    from jaxtlc.struct.compile import compact_lanes, compact_width

    assert [compact_width(n) for n in (12, 64, 80, 256, 512)] == [
        12, 64, 32, 32, 64]
    succs = jnp.arange(10 * 3, dtype=jnp.int32).reshape(10, 3) + 1
    valid = jnp.asarray([0, 1, 0, 0, 1, 1, 0, 0, 0, 1], bool)
    action = jnp.arange(10, dtype=jnp.int32)
    flags = jnp.zeros(10, bool)
    s, v, a, af, ov = compact_lanes(succs, valid, action,
                                    flags.at[4].set(True), flags, 4)
    assert v.tolist() == [True] * 4 and a.tolist() == [1, 4, 5, 9]
    assert s.tolist() == succs[jnp.asarray([1, 4, 5, 9])].tolist()
    assert af.tolist() == [False, True, False, False] and not ov.any()
    # a fifth live lane does not fit four slots: the state halts the run
    s, v, a, af, ov = compact_lanes(succs, valid.at[0].set(True), action,
                                    flags, flags, 4)
    assert ov.tolist() == [True, False, False, False]
    # nothing live: nothing valid, nothing raised
    s, v, a, af, ov = compact_lanes(succs, flags, action, flags, flags, 4)
    assert not v.any() and not ov.any()


def test_run_check_counts_spans_and_counters(tmp_path, model, backend):
    """The route a large struct check takes: api.run_check, segments,
    journal, supervisor.  Counts exact against the reference's row, the
    struct spans in the journal's `spans` event, the step's counters on
    CheckResult and on `final`."""
    from jaxtlc.api import CheckRequest, run_check

    out = io.StringIO()
    journal = str(tmp_path / "check.jsonl")
    outcome = run_check(CheckRequest(
        config=CFG, frontend="struct", workers="cpu", noTool=True,
        nodeadlock=True, constants=ballots(2), chunk=256, qcap=1 << 12,
        fpcap=1 << 14, journal=journal, out=out, err=out))
    assert outcome.verdict == "ok", out.getvalue()[-2000:]
    r = outcome.result
    assert (r.generated, r.distinct, r.depth,
            r.action_generated) == ROW_01
    assert (r.step_lanes, r.step_slots, r.state_words) == (80, 32, 3)
    assert r.states_expanded == r.distinct
    assert r.lane_fires == r.generated - 1 and r.struct_traps == 0
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    kinds = {e["event"] for e in events}
    assert not kinds & {"regrow", "retry", "degrade", "spill"}
    start = next(e for e in events if e["event"] == "run_start")
    assert start["engine"] == "single"
    final = next(e for e in events if e["event"] == "final")
    assert (final["generated"], final["distinct"], final["depth"],
            final["queue"]) == ROW_01[:3] + (0,)
    assert {k: final[k] for k in ("step_lanes", "step_slots",
                                  "state_words", "states_expanded",
                                  "lane_fires", "struct_traps")} == dict(
        step_lanes=80, step_slots=32, state_words=3,
        states_expanded=3921, lane_fires=23562, struct_traps=0)
    # maxBal, maxVBal, maxVal at three acceptors: digits of their codes
    assert (final["lookup_const"], final["lookup_arith"],
            final["lookup_gather"]) == (21, 18, 0) == (
        r.lookup_const, r.lookup_arith, r.lookup_gather)
    names = [row[0] for e in events if e["event"] == "spans"
             for row in e["rows"]]
    for want in ("build.struct.load", "build.struct", "build",
                 "build.trace", "loop", "loop.wait"):
        assert want in names, (want, names)


WIDE = """---- MODULE Wide ----
EXTENDS Integers
CONSTANT N
Msg == [type : {"m"}, n : 0..(N - 1)]
VARIABLES msgs, got
TypeOK == /\\ msgs \\subseteq Msg
          /\\ got \\in -1..(N - 1)
Init == /\\ msgs = {}
        /\\ got = -1
Fill == /\\ msgs = {}
        /\\ msgs' = Msg
        /\\ UNCHANGED got
Pick == \\E m \\in msgs : /\\ got = -1
                        /\\ got' = m.n
                        /\\ UNCHANGED msgs
Next == Fill \\/ Pick
Spec == Init /\\ [][Next]_<<msgs, got>>
====
"""


@pytest.mark.parametrize("route", ["supervised", "fused"])
def test_a_state_with_more_live_lanes_than_slots_widens_the_step(
        tmp_path, route):
    """81 static lanes leave the step compacted to 32 slots; the state
    whose `msgs` holds all 80 records fires 80 of them.  The run halts
    on it, the backend is rebuilt with 64 slots, then with the whole
    fan, and the check ends with the oracle's counts - on the route
    run_check takes by default and on the fused one."""
    from jaxtlc.api import CheckRequest, run_check
    from jaxtlc.struct import cache

    (tmp_path / "Wide.tla").write_text(WIDE)
    (tmp_path / "MC.cfg").write_text(
        "CONSTANT N = 80\nSPECIFICATION Spec\nINVARIANT TypeOK\n")
    cfg = str(tmp_path / "MC.cfg")
    wide = load(cfg)
    cache._FLOORS.pop((cache.model_key(wide), "step_slots"),
                      None)  # the other route's
    want = bfs(wide.system, wide.invariants, check_deadlock=False)
    assert (want.generated, want.distinct, want.depth) == (82, 82, 3)
    out = io.StringIO()
    journal = str(tmp_path / "check.jsonl")
    outcome = run_check(CheckRequest(
        config=cfg, frontend="struct", workers="cpu", noTool=True,
        nodeadlock=True, chunk=64, qcap=1 << 10, fpcap=1 << 14,
        autogrow=route == "supervised", journal=journal, out=out,
        err=out))
    assert outcome.verdict == "ok", out.getvalue()[-2000:]
    r = outcome.result
    assert (r.generated, r.distinct, r.depth) == (82, 82, 3)
    assert r.action_generated == {"Fill": 1, "Pick": 80}
    assert (r.step_lanes, r.step_slots, r.struct_traps) == (81, 81, 0)
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    assert [(e["rung"], e["resource"], e["action"]) for e in events
            if e["event"] == "degrade"] == [
        ("widen", "step_slots", "32->64"),
        ("widen", "step_slots", "64->81")]
    # the model's next backend starts as wide; an uncompacted step has
    # no wider form: its overflow is the codec's
    backend = cache.get_backend(load(cfg), False)
    assert backend.n_lanes == 81
    assert cache.widen_slots(load(cfg), backend) is None


def test_backend_build_is_split_by_spans(model):
    """A memo miss records shapes and lanes inside `build.struct`."""
    from jaxtlc.obs import spans
    from jaxtlc.struct import cache

    m = load(CFG, const_overrides=ballots(1))
    before = len(spans.snapshot())
    cache.get_backend(m, False)
    cache.get_backend(m, False)
    rows = [dict(zip(("id", "name", "t0", "t1", "parent", "job", "thread",
                      "attrs"), r)) for r in spans.snapshot()[before:]]
    by_name = collections.Counter(r["name"] for r in rows)
    assert by_name["build.struct"] == 2
    assert by_name["build.struct.shapes"] == 1
    assert by_name["build.struct.lanes"] == 1
    outer = [r for r in rows if r["name"] == "build.struct"]
    assert [r["attrs"]["memo"] for r in outer] == ["miss", "hit"]
    inner = [r for r in rows if r["name"].startswith("build.struct.")]
    assert all(r["parent"] == outer[0]["id"] for r in inner)


# -- a seeded mutation: Phase2a without its max-ballot conjunct ------------

MAXBAL = "                    /\\ \\A mm \\in Q1bv : m.mbal \\geq mm.mbal\n"


def mutated(tmp_path, n_ballots):
    src = open(os.path.join(MODEL, "Paxos.tla")).read()
    assert src.count(MAXBAL) == 1
    for name in ("MC.tla", "MC.cfg"):
        (tmp_path / name).write_text(
            open(os.path.join(MODEL, name)).read())
    (tmp_path / "Paxos.tla").write_text(src.replace(MAXBAL, ""))
    return load(str(tmp_path / "MC.cfg"),
                const_overrides=ballots(n_ballots))


def test_mutation_breaks_agreement_in_the_reference(reference):
    assert not reference.bfs(3, 2, 2, 2, drop_maxbal=True).violations
    r = reference.bfs(3, 2, 3, 2, drop_maxbal=True,
                      stop_on_violation=True)
    assert {name for name, _ in r.violations} == {"Agreement"}
    assert r.depth == 18


@pytest.mark.slow
def test_mutation_breaks_agreement_in_the_engine(tmp_path):
    from jaxtlc.struct.engine import check_struct

    r = check_struct(mutated(tmp_path, 3), chunk=1024,
                     queue_capacity=1 << 17, fp_capacity=1 << 20,
                     check_deadlock=False)
    assert r.violation_name == "Invariant Agreement is violated"


@pytest.mark.slow
def test_engine_at_ballots_0_to_2():
    from jaxtlc.struct.engine import check_struct

    r = check_struct(load(CFG, const_overrides=ballots(3)), chunk=1024,
                     queue_capacity=1 << 17, fp_capacity=1 << 20,
                     check_deadlock=False)
    assert r.violation == 0
    assert (r.generated, r.distinct, r.depth,
            r.action_generated) == ROW_02
    assert (r.step_lanes, r.step_slots) == (156, 32)
