"""Lamport's MCPaxos as its source publishes it (ISSUE 33): the cfg's
`SYMMETRY MCSymmetry` through the cfg parser and the struct loader,
`Permutations` in the evaluator, the static verification of the sets
(analysis/symfind), the orbit canonicalization's array form
(engine/reduce) against the per-bit form it replaced, against the host
twin and against the plain reference's tuple permutation, and the counts
of benchmark/reference/paxos_sym.py against the compiled engine through
api.run_check.

One module fixture loads and compiles specs/Paxos.toolbox/Model_sym at
Ballot == 0..1 once (the reduced backend: 80 static lanes, 32 slots, 12
group elements); the 0..2 rung (17,153 orbits) is `slow`.
"""

import importlib.util
import io
import itertools
import json
import os
import random

import numpy as np
import pytest

import perbit_canon
from jaxtlc.analysis.symfind import (
    SymmetryError,
    find_symmetric_sets,
    require_declared,
)
from jaxtlc.frontend.mc_cfg import CfgError, parse_cfg
from jaxtlc.struct.eval import Evaluator, StructEvalError
from jaxtlc.struct.loader import StructLoadError, load
from jaxtlc.struct.parser import parse_expression

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(ROOT, "specs")
MODEL_SYM = os.path.join(SPECS, "Paxos.toolbox", "Model_sym")
MODEL_1 = os.path.join(SPECS, "Paxos.toolbox", "Model_1")
CFG = os.path.join(MODEL_SYM, "MC.cfg")

# (generated, distinct, depth, per-action generated) of the plain orbit
# BFS (benchmark/reference/paxos_sym.py): one representative an orbit
# under the 12 elements of S3 x S2
ROW_01 = (2697, 443, 17, {"Phase1a": 886, "Phase1b": 298,
                          "Phase2a": 268, "Phase2b": 1244})
ROW_02 = (127002, 17153, 25, {"Phase1a": 51459, "Phase1b": 11860,
                              "Phase2a": 12508, "Phase2b": 51174})
KW = dict(frontend="struct", workers="cpu", noTool=True, nodeadlock=True,
          chunk=256, qcap=1 << 12, fpcap=1 << 14)


def ballots(n):
    return {"Ballot": frozenset(range(n))}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "paxos_sym_reference",
        os.path.join(ROOT, "benchmark", "reference", "paxos_sym.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    return load(CFG, const_overrides=ballots(2))


@pytest.fixture(scope="module")
def backend(model):
    """The module's one fresh lane compile: the reduced backend."""
    from jaxtlc.struct.cache import get_backend

    return get_backend(model, False, symmetry=True)


@pytest.fixture(scope="module")
def checked(model, backend, tmp_path_factory):
    """api.run_check on the unmodified Model_sym files at 0..1, once:
    (outcome, journal events, transcript)."""
    from jaxtlc.api import CheckRequest, run_check

    out = io.StringIO()
    journal = str(tmp_path_factory.mktemp("sym") / "check.jsonl")
    outcome = run_check(CheckRequest(
        config=CFG, constants=ballots(2), journal=journal, out=out,
        err=out, **KW))
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    return outcome, events, out.getvalue()


# -- the cfg language --------------------------------------------------------


@pytest.mark.parametrize("text,check", [
    ("INVARIANT\nTypeOK\nSYMMETRY MCSymmetry\n",
     lambda c: c.symmetry == "MCSymmetry" and c.invariants == ["TypeOK"]),
    ("SYMMETRY\n  Perms\nINVARIANT Inv\n",
     lambda c: c.symmetry == "Perms" and c.invariants == ["Inv"]),
    ("INVARIANT A\nCHECK_DEADLOCK FALSE\n",
     lambda c: c.check_deadlock is False and c.invariants == ["A"]),
    ("CHECK_DEADLOCK\nTRUE\nSPECIFICATION Spec\n",
     lambda c: c.check_deadlock is True and c.specification == "Spec"),
    ("SPECIFICATION Spec\n",
     lambda c: c.symmetry is None and c.check_deadlock is None),
    ("INVARIANT A\nCONSTRAINT Bound\n",
     lambda c: c.constraints == ["Bound"] and c.invariants == ["A"]),
    ("INVARIANT A\nCONSTRAINTS\nBound Other\nThird\n",
     lambda c: c.constraints == ["Bound", "Other", "Third"]),
    ("INVARIANT A\nACTION_CONSTRAINT Step\n",
     "not supported: ACTION_CONSTRAINT"),
    ("INVARIANT A\nVIEW v\n", "not supported: VIEW"),
    ("CHECK_DEADLOCK maybe\n", "TRUE or FALSE"),
    ("SYMMETRY A\nSYMMETRY B\n", "names one definition"),
])
def test_parse_cfg_sections(text, check):
    """SYMMETRY, CHECK_DEADLOCK and CONSTRAINT are sections;
    ACTION_CONSTRAINT and VIEW are recognised and refused by name, not
    read as members of the section above them."""
    if isinstance(check, str):
        with pytest.raises(CfgError, match=check):
            parse_cfg(text)
    else:
        assert check(parse_cfg(text))


def test_check_deadlock_false_in_the_cfg_switches_the_check_off(tmp_path):
    from jaxtlc.frontend.model import resolve

    (tmp_path / "D.tla").write_text(
        "---- MODULE D ----\nVARIABLE x\nInit == x = 0\n"
        "Next == x = 0 /\\ x' = 1\nSpec == Init /\\ [][Next]_x\n====\n")
    for line, want in (("", True), ("CHECK_DEADLOCK TRUE\n", True),
                       ("CHECK_DEADLOCK FALSE\n", False)):
        (tmp_path / "D.cfg").write_text("SPECIFICATION Spec\n" + line)
        spec = resolve(str(tmp_path / "D.cfg"), workers="cpu",
                       frontend="struct")
        assert spec.check_deadlock is want
    # the flag still switches it off against a cfg that says TRUE
    (tmp_path / "D.cfg").write_text(
        "SPECIFICATION Spec\nCHECK_DEADLOCK TRUE\n")
    assert resolve(str(tmp_path / "D.cfg"), workers="cpu",
                   frontend="struct",
                   check_deadlock=False).check_deadlock is False


def test_permutations_in_the_evaluator():
    ev = Evaluator({}, {"S": frozenset({"a", "b", "c"}),
                        "T": frozenset({"v"})})
    perms = ev.eval(parse_expression("Permutations(S)"), {})
    assert len(perms) == 6
    assert (("a", "b"), ("b", "c"), ("c", "a")) in perms
    assert all(sorted(v for _, v in f) == ["a", "b", "c"] for f in perms)
    both = ev.eval(parse_expression(
        "Permutations(S) \\cup Permutations(T)"), {})
    assert len(both) == 7 and (("v", "v"),) in both
    with pytest.raises(StructEvalError, match="expects a set"):
        ev.eval(parse_expression("Permutations(3)"), {})


def test_model_sym_files_are_model_1_plus_the_two_lines():
    with open(os.path.join(MODEL_SYM, "Paxos.tla"), "rb") as a, \
            open(os.path.join(MODEL_1, "Paxos.tla"), "rb") as b:
        assert a.read() == b.read()
    with open(os.path.join(MODEL_1, "MC.cfg")) as f:
        cfg1 = f.read()
    with open(CFG) as f:
        assert f.read() == cfg1 + "SYMMETRY MCSymmetry\n"
    with open(os.path.join(MODEL_SYM, "MC.tla")) as f:
        mc = f.read()
    assert ("MCSymmetry == Permutations(Acceptor) \\cup "
            "Permutations(Value)") in mc


def test_loader_resolves_the_declaration_to_constant_sets(model):
    assert model.symmetry == (("Acceptor", ("a1", "a2", "a3")),
                              ("Value", ("v1", "v2")))
    assert load(os.path.join(MODEL_1, "MC.cfg")).symmetry == ()


_SYM = """---- MODULE S ----
EXTENDS TLC
CONSTANTS RM, a, b, c
VARIABLE voted
Init == voted = {}
Next == \\E r \\in RM \\ voted : voted' = voted \\cup {r}
Spec == Init /\\ [][Next]_voted
Swap == {[x \\in RM |-> x], (a :> b) @@ (b :> a) @@ (c :> c)}
Pairs == Permutations({<<a, b>>})
Nums == Permutations({1, 2})
Loose == Permutations({a, b})
Inv == voted # {a}
Atoms == RM
====
"""
_SYM_CFG = ("CONSTANTS\na = a\nb = b\nc = c\nRM = {a, b, c}\n"
            "SPECIFICATION Spec\n")


@pytest.mark.parametrize("tail,why", [
    ("SYMMETRY Swap\n", "2 of the 6 permutations of {a, b, c}"),
    ("SYMMETRY Pairs\n", "SYMMETRY Pairs: function domains must be"),
    ("SYMMETRY Nums\n", "not a set of functions over model values"),
    ("SYMMETRY Loose\n", "{a, b} is not the value of a CONSTANT set"),
    ("SYMMETRY Gone\n", "SYMMETRY Gone: no such definition"),
    ("SYMMETRY Inv\n", "SYMMETRY Inv: unknown name 'voted'"),
    ("SYMMETRY Atoms\n", "not a set of functions"),
    ("ACTION_CONSTRAINT Inv\n", "not supported: ACTION_CONSTRAINT"),
])
def test_a_symmetry_set_the_loader_cannot_take_is_a_load_error(
        tmp_path, tail, why):
    (tmp_path / "S.tla").write_text(_SYM)
    (tmp_path / "S.cfg").write_text(_SYM_CFG + tail)
    with pytest.raises(StructLoadError, match=why.replace("{", "\\{")):
        load(str(tmp_path / "S.cfg"))


# -- the verification --------------------------------------------------------


def test_symfind_keeps_acceptor_and_value_on_the_paxos_model(model):
    """The two repairs: the module's `None == CHOOSE ...` is overridden
    by the cfg's `None = None` and so is not on the surface; `Quorum`,
    which embeds the acceptors, is mapped to itself by every permutation
    of them, and the model values `a1 = a1` are never read."""
    want = {"Acceptor": ("a1", "a2", "a3"), "Value": ("v1", "v2")}
    assert find_symmetric_sets(model) == (want, {})
    plain = load(os.path.join(MODEL_1, "MC.cfg"),
                 const_overrides=ballots(2))
    assert find_symmetric_sets(plain) == (want, {})


_PIN = """---- MODULE P ----
EXTENDS TLC
CONSTANTS RM, Leader, Pool
VARIABLE voted
Init == voted = {}
Next == \\E r \\in RM \\ voted :
          voted' = voted \\cup {IF r = Leader THEN r ELSE PICK}
Spec == Init /\\ [][Next]_voted
Perms == Permutations(RM)
====
"""


@pytest.mark.parametrize("pick,consts,sym_line,reason", [
    # a constant the spec reads whose value a permutation changes: pins
    ("r", "Leader = r1\nPool = {r1}\n", "",
     "pinned through constant Leader"),
    # a reachable CHOOSE that is really evaluated
    ("CHOOSE x \\in RM : TRUE", "Leader = r1\nPool = {r1}\n", "",
     "reaches a CHOOSE"),
    # the same two, declared by the cfg: an error, not a rejection
    ("r", "Leader = r1\nPool = {r1}\n", "SYMMETRY Perms\n",
     "pinned through constant Leader"),
    ("CHOOSE x \\in RM : TRUE", "Leader = r1\nPool = {r1}\n",
     "SYMMETRY Perms\n", "reaches a CHOOSE"),
])
def test_symfind_still_rejects_what_breaks_symmetry(
        tmp_path, pick, consts, sym_line, reason):
    (tmp_path / "P.tla").write_text(_PIN.replace("PICK", pick))
    (tmp_path / "P.cfg").write_text(
        "CONSTANTS\nRM = {r1, r2, r3}\n" + consts
        + "SPECIFICATION Spec\n" + sym_line)
    m = load(str(tmp_path / "P.cfg"))
    kept, rejected = find_symmetric_sets(m)
    assert "RM" not in kept and reason in rejected["RM"]
    if sym_line:
        with pytest.raises(SymmetryError, match=reason):
            require_declared(m, rejected)
    else:
        require_declared(m, rejected)  # nothing declared: nothing held


def test_a_constant_invariant_as_a_value_does_not_pin(tmp_path):
    """`Pool`, read by the spec, holds RM's atoms and is mapped to itself
    by every permutation of them: admissible.  `Leader = r1` would pin,
    and is never read here."""
    (tmp_path / "P.tla").write_text(
        _PIN.replace("IF r = Leader THEN r ELSE PICK",
                     "IF \\E S \\in Pool : r \\in S THEN r ELSE r")
        .replace("====", "MCPool == {RM, {}}\n===="))
    (tmp_path / "P.cfg").write_text(
        "CONSTANTS\nRM = {r1, r2, r3}\nLeader = r1\n"
        "Pool <- MCPool\nSPECIFICATION Spec\nSYMMETRY Perms\n")
    m = load(str(tmp_path / "P.cfg"))
    assert find_symmetric_sets(m) == ({"RM": ("r1", "r2", "r3")}, {})


def test_a_declared_set_that_fails_is_an_error_at_the_front_door(tmp_path):
    """No path on which a cfg with SYMMETRY yields an unreduced verdict:
    a set that fails verification, and -no-symmetry against the line."""
    from jaxtlc.api import CheckRequest, run_check

    (tmp_path / "P.tla").write_text(_PIN.replace("PICK", "r"))
    (tmp_path / "P.cfg").write_text(
        "CONSTANTS\nRM = {r1, r2, r3}\nLeader = r1\nPool = {r1}\n"
        "SPECIFICATION Spec\nSYMMETRY Perms\n")
    out = io.StringIO()
    o = run_check(CheckRequest(config=str(tmp_path / "P.cfg"), out=out,
                               err=out, **KW))
    assert o.exit_code == 1 and o.result is None
    assert "SYMMETRY over RM cannot be reduced" in out.getvalue()
    assert "pinned through constant Leader" in out.getvalue()
    out = io.StringIO()
    o = run_check(CheckRequest(config=CFG, constants=ballots(2),
                               symmetry=False, out=out, err=out, **KW))
    assert o.exit_code == 1 and o.result is None
    assert "the cfg declares SYMMETRY" in out.getvalue()
    out = io.StringIO()
    o = run_check(CheckRequest(config=CFG, constants=ballots(2), out=out,
                               err=out, **{**KW, "frontend": "gen"}))
    assert o.exit_code == 1 and "-frontend struct" in out.getvalue()


# -- the canonicalization ----------------------------------------------------


def _reachable_rows(backend, levels, cap=400):
    """Flat rows of reachable states: a host-driven BFS over the
    backend's own step function, UNreduced (every successor kept)."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(backend.step)
    seen = {tuple(int(v) for v in row): True
            for row in np.asarray(backend.initial_vectors())}
    frontier = list(seen)
    for _ in range(levels):
        nxt = []
        for row in frontier:
            succs, valid = step(jnp.asarray(row, jnp.int32))[:2]
            for s, v in zip(np.asarray(succs), np.asarray(valid)):
                t = tuple(int(x) for x in s)
                if v and t not in seen:
                    seen[t] = True
                    nxt.append(t)
        frontier = nxt[:cap]
    return np.asarray(sorted(seen), np.int32)


@pytest.fixture(scope="module")
def paxos_rows(backend):
    rows = _reachable_rows(backend, 6)
    assert len(rows) >= 100
    return rows


def test_field_programs_equal_the_reference_on_reachable_states(
        model, backend, reference, paxos_rows):
    """Every group element's field program against the plain reference's
    tuple permutation (acceptors' entries moved, values renamed, `msgs`
    permuted message by message), on decoded reachable states."""
    plan = backend.reduce.plan
    cdc = backend.cdc
    assert plan.n_perms == 12 and len(plan.programs) == 11
    m = reference.paxos.make_model(3, 2, 2, 2)
    acc, val = ("a1", "a2", "a3"), ("v1", "v2")

    def msg_bit(rec):
        r = dict(rec)
        if r["type"] == "1a":
            return m.b1a[r["bal"]]
        if r["type"] == "2a":
            return m.b2a[r["bal"]][val.index(r["val"])]
        a = acc.index(r["acc"])
        if r["type"] == "2b":
            return m.b2b[a][r["bal"]][val.index(r["val"])]
        mv = -1 if r["mval"] == "None" else val.index(r["mval"])
        return m.b1b[a][r["bal"]][r["mbal"] + 1][mv + 1]

    def to_ref(row):
        st = dict(zip(model.system.variables, cdc.decode(row)))
        mv = dict(st["maxVal"])
        return (tuple(dict(st["maxBal"])[a] for a in acc),
                tuple(dict(st["maxVBal"])[a] for a in acc),
                tuple(-1 if mv[a] == "None" else val.index(mv[a])
                      for a in acc),
                sum(msg_bit(r) for r in st["msgs"]))

    # the plan's programs in its own order: the product of both sets'
    # permutations, identity left out (engine/reduce.ReducePlan)
    pairs = [(pa, pv) for pa in itertools.permutations(range(3))
             for pv in itertools.permutations(range(2))][1:]
    rng = random.Random(33)
    rows = paxos_rows[rng.sample(range(len(paxos_rows)), 60)]
    images = plan.images_host(rows)  # [11, N, F]
    for k, (pa, pv) in enumerate(pairs):
        g = reference.element(m, pa, pv)
        for i, row in enumerate(rows):
            assert to_ref(images[k, i]) == reference.image(to_ref(row), g)


# every shipped spec whose verified sets give a plan, beside the Paxos
# fixture
SHIPPED_SYMMETRIC = [
    os.path.join(SPECS, "TwoPhase.toolbox", "Model_sym", "MC.cfg"),
    os.path.join(SPECS, "TwoPhase.toolbox", "Model_1", "MC.cfg"),
    os.path.join(SPECS, "RaftReplication.toolbox", "Model_1", "MC.cfg"),
]


def _check_forms_agree(plan, rows):
    import jax.numpy as jnp

    host = plan.canon_host(rows)
    assert (np.asarray(plan.canon(jnp.asarray(rows))) == host).all()
    assert (perbit_canon.canon(plan, rows) == host).all()
    # each program's image, array form against per-bit form
    images = plan.images_host(rows)
    for k, prog in enumerate(plan.programs):
        want = np.stack(perbit_canon.apply_program(prog, rows), axis=-1)
        assert (images[k] == want).all()
    # canonical forms are fixed points, and constant on every orbit
    assert (plan.canon_host(host) == host).all()
    for k in range(len(plan.programs)):
        assert (plan.canon_host(images[k]) == host).all()
    return host


def test_array_form_equals_per_bit_form_and_host_twin_on_paxos(
        backend, paxos_rows):
    plan = backend.reduce.plan
    host = _check_forms_agree(plan, paxos_rows)
    assert (host != paxos_rows).any(axis=1).sum() > 10  # it does move rows
    # seeded random rows too: arbitrary codes and mask bits in range
    rng = np.random.default_rng(33)
    top = np.asarray(backend.cdc.max_codes(), np.int64) + 1
    rand = (rng.integers(0, 1 << 30, (256, len(top))) % top).astype(
        np.int32)
    _check_forms_agree(plan, rand)


@pytest.mark.parametrize("cfg", SHIPPED_SYMMETRIC, ids=lambda p: "-".join(
    p.split(os.sep)[-3:-1]))
def test_array_form_equals_per_bit_form_on_shipped_symmetric_specs(cfg):
    """Seeded rows of every shipped symmetric spec's codec (shape
    inference only: no lane walk, no engine)."""
    from jaxtlc.engine.reduce import build_plan
    from jaxtlc.struct.codec import StructCodec
    from jaxtlc.struct.shapes import infer_shapes, typeok_hints

    m = load(cfg)
    system = m.system
    shapes = infer_shapes(system.ev, system.variables, system.init_ast,
                          system.next_ast, hints=typeok_hints(
                              system.ev, m.invariants, system.variables))
    cdc = StructCodec(system.variables, shapes)
    plan, dropped = build_plan(cdc, find_symmetric_sets(m)[0])
    assert plan is not None and not dropped
    rng = np.random.default_rng(33)
    top = np.asarray(cdc.max_codes(), np.int64) + 1
    rows = (rng.integers(0, 1 << 30, (512, len(top))) % top).astype(
        np.int32)
    inits = np.stack([cdc.encode(st) for st in system.initial_states()])
    _check_forms_agree(plan, np.concatenate([inits.astype(np.int32), rows]))


def test_array_form_moves_record_blocks_and_the_masks_inside_them():
    """A function over the symmetric set whose values are sets of it (a
    vote map), too wide to enumerate: a RecNode whose field blocks move
    with their names while each block's mask bits are permuted, beside a
    sequence of atoms with its length guard."""
    from jaxtlc.engine.reduce import ReducePlan
    from jaxtlc.struct.codec import RecNode, SeqNode, StructCodec
    from jaxtlc.struct.shapes import SAtoms, SRec, SSeq, SSet

    rm = ("r1", "r2", "r3")
    wide = SSet(SAtoms(frozenset(rm + tuple(f"x{i}" for i in range(5)))))
    cdc = StructCodec(("votes", "log"), {
        "votes": SRec(tuple((r, wide, False) for r in rm)),
        "log": SSeq(SAtoms(frozenset(rm)), 3)})
    assert isinstance(cdc.layouts[0], RecNode)
    assert isinstance(cdc.layouts[1], SeqNode)
    plan = ReducePlan(cdc, {"RM": rm}, lie=False)
    assert plan.form.src is not None and plan.form.masks
    rng = np.random.default_rng(33)
    top = np.asarray(cdc.max_codes(), np.int64) + 1
    rows = (rng.integers(0, 1 << 30, (512, len(top))) % top).astype(
        np.int32)
    # a sequence's slots past its length are canonical zeros
    n = cdc.offsets["log"]
    for k in range(3):
        rows[:, n + 1 + k] *= rows[:, n] > k
    host = _check_forms_agree(plan, rows)
    assert (host != rows).any(axis=1).sum() > 100
    # and against the evaluator's own value permutation, state by state
    from jaxtlc.struct.eval import permute_value

    for row in rows[:40]:
        votes, log = cdc.decode(row)
        orbit = [cdc.encode((permute_value(votes, pm),
                             permute_value(log, pm)))
                 for perm in itertools.permutations(rm)
                 for pm in [dict(zip(rm, perm))]]
        want = min(tuple(int(x) for x in o) for o in orbit)
        assert tuple(int(x) for x in plan.canon_host(row[None])[0]) == want


def test_canon_is_a_few_hundred_equations_not_thousands(backend):
    """The per-bit form was 4,160 equations on this codec (80 mask bits,
    11 programs) and ~11k on the cell's 216 bits."""
    import jax
    import jax.numpy as jnp

    plan = backend.reduce.plan
    x = jnp.zeros((64, backend.cdc.n_fields), jnp.int32)
    n = len(jax.make_jaxpr(plan.canon)(x).jaxpr.eqns)
    assert n < 300, n


# -- the route ---------------------------------------------------------------


def test_run_check_equals_the_plain_orbit_search(checked, reference):
    """api.run_check on the unmodified Model_sym files: generated,
    distinct, depth and per-action totals equal
    benchmark/reference/paxos_sym.py's, in the result and in the
    journal; `distinct` is the number of canonical forms of the
    unreduced 3,921 states."""
    outcome, events, text = checked
    assert outcome.verdict == "ok", text[-2000:]
    r = outcome.result
    ref = reference.bfs(3, 2, 2, 2)
    assert (ref.generated, ref.distinct, ref.depth,
            ref.action_generated) == ROW_01
    assert (r.generated, r.distinct, r.depth,
            r.action_generated) == ROW_01
    check = reference.self_check(3, 2, 2, 2)
    assert check["ok"] and check["unreduced"] == 3921
    assert check["canonical_forms"] == r.distinct
    final = next(e for e in events if e["event"] == "final")
    assert (final["generated"], final["distinct"], final["depth"],
            final["queue"]) == ROW_01[:3] + (0,)
    kinds = {e["event"] for e in events}
    assert not kinds & {"regrow", "retry", "degrade", "spill"}
    assert next(e for e in events
                if e["event"] == "run_start")["engine"] == "single"


def test_generators_only_miscounts_in_the_reference(reference):
    """The relaxation the configuration's guarantee excludes: the least
    image under the listed functions, not the group they generate."""
    r = reference.bfs(3, 2, 2, 2, generators_only=True)
    assert (r.generated, r.distinct) == (2767, 457)
    assert r.distinct > ROW_01[1]


def test_spans_counters_and_run_start(checked):
    outcome, events, _ = checked
    r = outcome.result
    assert (r.sym_perms, r.sym_sets) == (12, 2)
    assert r.canon_rows == r.generated - 1
    assert 0 < r.canon_moved < r.canon_rows
    assert r.sym_cert_checks >= r.depth - 1 and r.sym_cert_trips == 0
    assert r.sym_violated is False and r.struct_traps == 0
    final = next(e for e in events if e["event"] == "final")
    for k in ("sym_perms", "sym_sets", "canon_rows", "canon_moved",
              "sym_cert_checks", "sym_cert_trips"):
        assert final[k] == getattr(r, k), k
    start = next(e for e in events if e["event"] == "run_start")
    assert start["params"]["symmetry"] is True
    assert start["params"]["sym_perms"] == 12
    assert start["params"]["symmetric_sets"] == {
        "Acceptor": ["a1", "a2", "a3"], "Value": ["v1", "v2"]}
    rows = [row for e in events if e["event"] == "spans"
            for row in e["rows"]]
    names = [row[0] for row in rows]
    assert "build.struct.symmetry" in names and "build.struct" in names


def test_canon_scope_is_in_the_expand_stage(backend):
    """`jaxtlc.canon` names the tournament's instructions inside
    `jaxtlc.expand` (the op_name metadata a trace is joined on)."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine.backend import make_expand_stage
    from jaxtlc.engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED

    expand = make_expand_stage(backend, 8, False, DEFAULT_FP_INDEX,
                               DEFAULT_SEED)

    def body(batch, mask):
        with jax.named_scope("jaxtlc.expand"):
            return expand(batch, mask)

    text = jax.jit(body).lower(
        jnp.zeros((8, backend.cdc.n_fields), jnp.int32),
        jnp.ones(8, bool)).as_text(debug_info=True)
    assert "jaxtlc.expand/jaxtlc.canon" in text


def test_symmetry_flag_on_model_1_equals_the_cfg_line(checked):
    """-symmetry (auto-discovery) on Model_1, which has no SYMMETRY
    line, finds the same two sets and gives the same counts."""
    from jaxtlc.api import CheckRequest, run_check

    out = io.StringIO()
    o = run_check(CheckRequest(
        config=os.path.join(MODEL_1, "MC.cfg"), constants=ballots(2),
        symmetry=True, out=out, err=out, **KW))
    assert o.verdict == "ok", out.getvalue()[-2000:]
    r, want = o.result, checked[0].result
    assert (r.generated, r.distinct, r.depth, r.action_generated,
            r.sym_perms, r.canon_rows, r.canon_moved) == (
        want.generated, want.distinct, want.depth, want.action_generated,
        want.sym_perms, want.canon_rows, want.canon_moved)


def test_a_set_the_flag_cannot_take_is_named_with_its_reason(tmp_path):
    """-symmetry on a spec whose set is pinned: today's behaviour (the
    run goes on, unreduced) plus a line that says why."""
    from jaxtlc.api import CheckRequest, run_check

    (tmp_path / "P.tla").write_text(_PIN.replace("PICK", "r"))
    (tmp_path / "P.cfg").write_text(
        "CONSTANTS\nRM = {r1, r2, r3}\nLeader = r1\nPool = {r1}\n"
        "SPECIFICATION Spec\n")
    out = io.StringIO()
    journal = str(tmp_path / "j.jsonl")
    o = run_check(CheckRequest(config=str(tmp_path / "P.cfg"),
                               symmetry=True, journal=journal, out=out,
                               err=out, **KW))
    assert o.verdict == "ok", out.getvalue()[-2000:]
    assert o.result.distinct == 8 and o.result.sym_perms is None
    assert ("-symmetry: constant RM is not reduced: element(s) r1 are "
            "pinned through constant Leader") in out.getvalue()
    with open(journal) as f:
        red = next(json.loads(line) for line in f
                   if '"reduce"' in line)
    assert "pinned through constant Leader" in red["dropped_sets"]["RM"]


def test_sym_lie_trips_the_certificate_on_this_model(tmp_path, monkeypatch):
    """JAXTLC_DEBUG_SYM_LIE=1 on a digest-perturbed copy of Model_sym
    (so the lying backend stays out of the memo the module shares): the
    run ends in the certificate's violation, not in a verdict."""
    import shutil

    from jaxtlc.api import CheckRequest, run_check

    for f in os.listdir(MODEL_SYM):
        shutil.copy(os.path.join(MODEL_SYM, f), tmp_path)
    with open(tmp_path / "MC.tla", "a") as f:
        f.write("\n\\* orbit-lie test copy\n")
    monkeypatch.setenv("JAXTLC_DEBUG_SYM_LIE", "1")
    out = io.StringIO()
    o = run_check(CheckRequest(config=str(tmp_path / "MC.cfg"),
                               constants=ballots(2), out=out, err=out,
                               **KW))
    assert o.exit_code == 1, out.getvalue()[-2000:]
    assert "orbit-certificate violation" in out.getvalue()
    assert o.result.sym_cert_trips > 0


@pytest.mark.slow
def test_engine_at_ballots_0_to_2():
    from jaxtlc.struct.engine import check_struct

    r = check_struct(load(CFG, const_overrides=ballots(3)), chunk=1024,
                     queue_capacity=1 << 16, fp_capacity=1 << 18,
                     check_deadlock=False)
    assert r.violation == 0 and r.sym_cert_trips == 0
    assert (r.generated, r.distinct, r.depth,
            r.action_generated) == ROW_02
