"""A protocol checked against its specification (ISSUE 48): Gray and
Lamport's Paxos Commit as `tlaplus/Examples` publishes it,
specs/PaxosCommit.toolbox/Model_1, with the module's own closing theorem
`PCSpec => TC!TCSpec` as the cfg's PROPERTY - `TC == INSTANCE TCommit`
loaded unmodified, and `TC!TCInit /\\ [][TC!TCNext]_rmState` judged as an
ACTION property: the first half on the initial states, `[A]_v` on every
edge the search generates, at the engine's expand seam.  Three
implementations agree - the device engine, the structural interpreter
(struct/oracle.py) and the plain reference
(benchmark/reference/paxoscommit.py) - at the two small rungs on the CPU
(the published constants, 1,321,761 states, are the benchmark cell's);
a mutant is refused with the property's name and an edge; a property
whose first half fails; what the loader refuses; every other route
refuses the model by name; and a PROPERTY the struct path cannot judge
is named on the verdict, never a bare `ok`.
"""

import io
import json
import os
import shutil
import sys

import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.struct import loader
from jaxtlc.struct.loader import StructLoadError, load
from jaxtlc.struct.oracle import bfs
from jaxtlc.struct.parser import StructParseError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = os.path.join(REPO, "specs", "PaxosCommit.toolbox", "Model_1")
CFG = os.path.join(MODEL, "MC.cfg")
FILES = ("PaxosCommit.tla", "TCommit.tla", "MC.tla", "MC.cfg")
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))

GEOMETRY = dict(chunk=256, qcap=4096, fpcap=16384)
# the two rungs: the constants' override, the reference's arguments, and
# the planner's counts (ISSUE 48; `Decide` one successor an rm that
# chose "aborted": configs/paxoscommit-mc.json, assumed.accounting)
RUNGS = {
    "one-rm": (dict(RM=frozenset({"r1"})), dict(rm=1),
               (8844, 1461, 15), 370),
    "ballot-0": (dict(Ballot=frozenset({0})), dict(ballots=1),
                 (4141, 545, 12), 220),
}


def check(cfg=CFG, journal=None, want="ok", **kw):
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=cfg, frontend="struct", workers="cpu", noTool=True,
        out=out, err=out, journal=journal, **{**GEOMETRY, **kw}))
    assert o.verdict == want, out.getvalue()[-800:]
    return o, out.getvalue()


def copy_model(dst, edit=None):
    """The shipped files under `dst`, `edit` {file: fn(text) -> text}."""
    for name in FILES:
        shutil.copy(os.path.join(MODEL, name), dst / name)
    for name, fn in (edit or {}).items():
        (dst / name).write_text(fn((dst / name).read_text()))
    return str(dst / "MC.cfg")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "paxoscommit-mc.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mutant_cfg(tmp_path_factory):
    """Decide's first disjunct with `\\E rm` for `\\A rm`: commit as soon
    as ONE instance chose "prepared" (the reference's
    `--mutant commit-on-any`)."""
    was = r'\/ /\ \A rm \in RM : Decided(rm, "prepared")'

    def mutate(text):
        assert was in text
        return text.replace(was, was.replace(r"\A rm", r"\E rm"))

    return copy_model(tmp_path_factory.mktemp("pc-mutant"),
                      {"PaxosCommit.tla": mutate})


def test_the_shipped_files_load_unmodified_as_the_sources_model():
    m = load(CFG)
    assert m.root_name == "PaxosCommit"
    assert m.system.variables == ("rmState", "aState", "msgs")
    assert m.constants["RM"] == frozenset({"r1", "r2"})
    assert m.constants["Ballot"] == frozenset({0, 1})
    # a set of sets of model values, written in the cfg itself
    assert m.constants["Majority"] == frozenset(
        frozenset(q) for q in (("a1", "a2"), ("a1", "a3"), ("a2", "a3")))
    assert list(m.invariants) == ["PCTypeOK", "TCConsistent"]
    # the theorem is the model's action property, not a temporal one
    assert list(m.action_props) == ["TCSpec"] and not m.properties
    prop = m.action_props["TCSpec"]
    assert prop.text == "TC!TCInit /\\ [][TC!TCNext]_rmState"
    assert prop.sub == ("rmState",)
    # `TC == INSTANCE TCommit`: every definition of TCommit under TC!
    assert {"TC!TCInit", "TC!TCNext", "TC!Prepare", "TC!Decide",
            "TC!canCommit", "TC!TCConsistent", "TC!TCSpec"} <= set(
                m.module.defs)
    # ... whose own references are prefixed alike, PaxosCommit's Decide
    # and TCommit's staying two definitions
    assert m.module.defs["TC!TCNext"].body[3] == (
        "or", [("call", "TC!Prepare", [("name", "r")]),
               ("call", "TC!Decide", [("name", "r")])])
    assert m.module.defs["Decide"].params == ()
    assert len(m.system.initial_states()) == 1
    with open(os.path.join(MODEL, "PaxosCommit.tla")) as f:
        text = f.read()
    for form in ("TC == INSTANCE TCommit", "THEOREM PCSpec => TC!TCSpec",
                 r"LET Max[T \in SUBSET S] ==", r"\E MS \in Majority :",
                 "![m.ins][acc].mbal = m.bal",
                 "(CHOOSE m \\in mset : m.bal = maxbal).val"):
        assert form in text
    with open(CFG) as f:
        cfg = f.read()
    assert "Majority = {{a1, a2}, {a1, a3}, {a2, a3}}" in cfg
    assert "PROPERTY" in cfg and "CHECK_DEADLOCK" not in cfg


@pytest.mark.parametrize("rung", sorted(RUNGS))
def test_engine_interpreter_and_reference_agree(rung, config, tmp_path):
    """generated, distinct, depth, the per-action totals, and the
    refinement's edges and moved edges, three ways."""
    import paxoscommit

    consts, ref_args, (generated, distinct, depth), moved = RUNGS[rung]
    want = paxoscommit.pins_for(config, **ref_args)
    assert (want["generated"], want["distinct"], want["depth"]) == (
        generated, distinct, depth)
    assert want["refine"] == dict(properties={"TCSpec": "holds"},
                                  edges=generated - 1, moved=moved,
                                  init_states=1)
    actions = {a: n for a, n in want["action_generated"].items() if n}
    m = load(CFG, const_overrides=consts)
    host = bfs(m.system, m.invariants, check_deadlock=True,
               action_props=m.action_props)
    assert host.violations == [] and host.bad_edge is None
    assert (host.generated, host.distinct, host.depth) == (
        generated, distinct, depth)
    assert host.action_generated == actions
    assert (host.edges, host.moved) == (generated - 1, moved)
    journal = str(tmp_path / "check.jsonl")
    o, text = check(journal=journal, constants=consts)
    r = o.result
    assert (r.generated, r.distinct, r.depth) == (
        generated, distinct, depth)
    assert dict(r.action_generated) == actions
    assert r.action_prop_names == ("TCSpec",)
    assert (r.action_prop_edges, r.action_prop_moved,
            r.action_prop_init_states) == (generated - 1, moved, 1)
    # only rmState's column is broadcast beside the candidates
    assert r.action_prop_src_cols == 1
    assert r.properties_skipped is None and r.struct_traps == 0
    assert "skipped" not in text
    assert "Action property TCSpec holds on all" in text
    with open(journal) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    kinds = [e["event"] for e in events]
    mine = [e for e in events if e["event"] == "action_property"]
    assert len(mine) == 1
    assert kinds.index("action_property") < kinds.index("final")
    assert {k: mine[0][k] for k in (
        "property", "holds", "route", "edges", "moved",
        "init_states")} == dict(
            property="TCSpec", holds=True, route="device",
            edges=generated - 1, moved=moved, init_states=1)
    final = events[kinds.index("final")]
    assert final["verdict"] == "ok"
    assert (final["action_prop_edges"], final["action_prop_moved"],
            final["action_prop_src_cols"]) == (generated - 1, moved, 1)
    assert "liveness" not in kinds


def test_the_mutant_is_refused_with_the_propertys_name_and_an_edge(
        mutant_cfg, config):
    """On the device, by the interpreter and by the reference: an edge
    that changes rmState and is no TCNext step."""
    import paxoscommit
    from jaxtlc.struct.cache import get_backend

    consts = dict(Ballot=frozenset({0}))
    got = paxoscommit.pins_for(config, ballots=1, mutant="commit-on-any")
    assert got["violated"] == ["TCConsistent", "TCSpec: [TCNext]_rmState"]
    o, text = check(mutant_cfg, want="violation", constants=consts)
    r = o.result
    assert o.exit_code == 12
    assert r.violation_name.startswith("Property TCSpec is violated: a "
                                       "step is neither a TC!TCNext step")
    assert "[][TC!TCNext]_rmState" in r.violation_name
    # the edge the device names: source and successor rows, decoded
    m = load(mutant_cfg, const_overrides=consts)
    cdc = get_backend(m, True).cdc
    src = dict(zip(m.system.variables, cdc.decode(r.action_prop_source)))
    dst = dict(zip(m.system.variables, cdc.decode(r.violation_state)))
    assert src["rmState"] != dst["rmState"]
    c = paxoscommit.Constants(2, 3, 1)
    assert not paxoscommit.tc_next(src["rmState"], dst["rmState"], c)
    # it is an edge of the mutant: the interpreter generates it
    assert tuple(dst[v] for v in m.system.variables) in [
        t for _, t in m.system.successors(
            tuple(src[v] for v in m.system.variables))]
    # the transcript: the property, then a trace that ends in the edge
    assert "Property TCSpec is violated" in text
    host = bfs(m.system, m.invariants, check_deadlock=True,
               action_props=m.action_props)
    assert host.bad_edge is not None
    assert host.violations[0][0].startswith("Property TCSpec")
    s, label, t = host.bad_edge
    assert label == "RMRcvCommitMsg"
    assert not paxoscommit.tc_next(s[0], t[0], c)
    assert f"<{label}>" in text


def test_a_property_whose_first_half_fails_ends_at_the_initial_state(
        tmp_path):
    def add(text):
        return text.replace("====", (
            'Early == rmState = [r \\in RM |-> "prepared"]\n'
            "EarlySpec == Early /\\ [][TC!TCNext]_rmState\n===="))

    cfg = copy_model(tmp_path, {
        "MC.tla": add,
        "MC.cfg": lambda t: t.replace("PROPERTY\nTCSpec",
                                      "PROPERTY\nEarlySpec")})
    assert list(load(cfg).action_props) == ["EarlySpec"]
    o, text = check(cfg, want="violation",
                    constants=dict(RM=frozenset({"r1"})))
    r = o.result
    assert r.violation_name == (
        "Property EarlySpec is violated: an initial state does not "
        "satisfy Early")
    assert (r.generated, r.distinct) == (1, 1)
    assert "State 1: <Initial predicate>" in text
    assert "State 2" not in text


def test_a_byte_changed_in_the_instanced_module_is_a_model_memo_miss(
        tmp_path):
    cfg = copy_model(tmp_path)
    first, _ = loader._load(cfg, None)  # kept by what the texts say
    again, memo = loader._load(cfg, None)
    assert memo == "hit" and again is first
    # the same texts from the shipped directory: the same model
    assert loader._load(CFG, None)[0] is first
    with open(tmp_path / "TCommit.tla", "a") as f:
        f.write("\\* a byte\n")
    changed, memo = loader._load(cfg, None)
    assert memo == "miss"
    assert changed.source_digest != first.source_digest
    assert list(changed.action_props) == ["TCSpec"]


@pytest.mark.parametrize("case,edit,err,says", [
    ("missing-module",
     {"PaxosCommit.tla": lambda t: t.replace("INSTANCE TCommit",
                                             "INSTANCE TCommitted")},
     StructLoadError, "INSTANCE TCommitted: no TCommitted.tla"),
    ("with-clause",
     {"PaxosCommit.tla": lambda t: t.replace(
         "INSTANCE TCommit", "INSTANCE TCommit WITH RM <- RM")},
     StructParseError, "a WITH clause is not supported"),
    ("unknown-instance",
     {"MC.tla": lambda t: t.replace("TC!TCSpec", "TD!TCSpec")},
     StructLoadError, "TCSpec: TD!TCSpec: no `TD == INSTANCE ...` in the "
                      "module (it has ['TC'])"),
    ("unknown-definition",
     {"MC.tla": lambda t: t.replace("TC!TCSpec", "TC!TCSpecification")},
     StructLoadError, "the module TC instances has no such definition"),
    ("undeclared-variable",
     {"TCommit.tla": lambda t: t.replace("VARIABLE rmState",
                                         "VARIABLES rmState, tmState")},
     StructLoadError, "tmState of TCommit has no constant, variable or "
                      "definition of that name in PaxosCommit"),
])
def test_what_the_loader_refuses_says_so(tmp_path, case, edit, err, says):
    cfg = copy_model(tmp_path, edit)
    with pytest.raises(err) as e:
        load(cfg)
    assert says in str(e.value)


@pytest.mark.parametrize("route,kw,says", [
    ("sharded", dict(sharded=1), "-sharded"),
    ("simulate", dict(simulate=True), "-simulate"),
    ("infer", dict(infer=True), "-infer"),
    ("liveness", dict(liveness=True), "-liveness"),
    ("narrow", dict(narrow=True), "-narrow"),
    ("symmetry", dict(symmetry=True), "-symmetry"),
    ("por", dict(por=True), "-por"),
    ("hand", dict(frontend="hand"), "only the structural frontend"),
    ("gen", dict(frontend="gen"), "only the structural frontend"),
])
def test_every_other_route_refuses_the_model_by_name(route, kw, says):
    err = io.StringIO()
    o = run_check(CheckRequest(**{**dict(
        config=CFG, frontend="struct", workers="cpu", noTool=True,
        constants=dict(RM=frozenset({"r1"})), out=io.StringIO(),
        err=err, **GEOMETRY), **kw}))
    assert (o.exit_code, o.verdict) == (1, "error")
    text = err.getvalue()
    assert "PROPERTY TCSpec" in text and "action property" in text
    assert says in text
    assert o.result is None  # refused before any engine ran


def test_an_engine_without_the_seam_refuses_the_backend_by_name():
    from jaxtlc.engine.backend import (
        ConstraintUnsupported, require_unconstrained)
    from jaxtlc.struct.cache import get_backend

    m = load(CFG, const_overrides=dict(RM=frozenset({"r1"})))
    with pytest.raises(ConstraintUnsupported,
                       match="action property PROPERTY TCSpec"):
        require_unconstrained(get_backend(m, True), "some other engine")


def test_a_property_the_struct_path_cannot_judge_is_named_on_the_verdict(
        tmp_path):
    """Not a bare `ok`: the result, the `final` event and the verdict
    line carry the names of skipped properties."""
    two = os.path.join(REPO, "specs", "TwoPhase.toolbox", "Model_1")
    shutil.copy(os.path.join(two, "TwoPhase.tla"), tmp_path)
    text = (tmp_path / "TwoPhase.tla").read_text().replace("====", (
        'Settles == [](tmState = "running") ~> (tmState # "running")\n'
        "===="))
    (tmp_path / "TwoPhase.tla").write_text(text)
    with open(os.path.join(two, "MC.cfg")) as f:
        (tmp_path / "MC.cfg").write_text(f.read()
                                         + "PROPERTY\nSettles\n")
    journal = str(tmp_path / "check.jsonl")
    o, said = check(str(tmp_path / "MC.cfg"), journal=journal,
                    nodeadlock=True)  # the protocol ends
    assert o.result.properties_skipped == ("Settles",)
    assert ("No error has been found. NOT JUDGED: PROPERTY Settles "
            "(skipped).") in said
    assert "Temporal property Settles skipped" in said
    with open(journal) as f:
        final = [json.loads(ln) for ln in f if '"final"' in ln][-1]
    assert final["verdict"] == "ok"
    assert final["properties_skipped"] == ["Settles"]
