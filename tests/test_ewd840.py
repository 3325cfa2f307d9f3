"""Dijkstra's EWD840 as tlaplus/Examples publishes it, with its cfg's
`PROPERTY Liveness` (ISSUE 41): the unmodified specs/EWD840.toolbox/
Model_1 files through `api.run_check -frontend struct` - the safety
search, then `Liveness` on the device liveness route under the spec's
own `WF_vars(System)` - against the host oracle (struct/oracle.py) and
the plain reference (benchmark/reference/ewd840.py), at N = 4 and 5 on
the CPU (N = 8 is the benchmark cell's rung)."""

import importlib.util
import io
import json
import os

import numpy as np
import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.live.fixpoint import SWEEP_BLOCK
from jaxtlc.struct.loader import StructLoadError, load
from jaxtlc.struct import oracle as so

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = os.path.join(REPO, "specs", "EWD840.toolbox", "Model_1")
CFG = os.path.join(MODEL, "MC.cfg")
SYSTEM = (("System", ("InitiateProbe", "PassToken")),)
ENVIRONMENT = (("Environment", ("Deactivate", "SendMsg")),)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "ref_ewd840", os.path.join(REPO, "benchmark", "reference",
                                   "ewd840.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


@pytest.fixture(scope="module")
def pins():
    return {n: REF.pins_for({}, n=n, checks=(n == 4)) for n in (4, 5)}


def check(cfg, n, tmp_path, **kw):
    out = io.StringIO()
    journal = os.path.join(str(tmp_path), f"check-{n}.jsonl")
    o = run_check(CheckRequest(
        config=cfg, frontend="struct", workers="cpu", noTool=True, out=out,
        err=out, journal=journal,
        **{**dict(constants={"N": n}, chunk=256, qcap=16384, fpcap=32768),
           **kw}))
    with open(journal) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return o, out.getvalue(), events


def mutated(tmp_path, name, swap, properties=True):
    """A copy of the model with `swap` (old -> new) applied to the
    module; the cfg as shipped, with or without its PROPERTY."""
    d = tmp_path / name
    d.mkdir()
    with open(os.path.join(MODEL, "EWD840.tla")) as f:
        text = f.read()
    for old, new in swap.items():
        assert old in text, old
        text = text.replace(old, new)
    (d / "EWD840.tla").write_text(text)
    with open(os.path.join(MODEL, "MC.tla")) as f:
        (d / "MC.tla").write_text(f.read())
    with open(CFG) as f:
        cfg = f.read()
    if not properties:
        cfg = cfg.replace("PROPERTY\n    Liveness\n", "")
        assert "PROPERTY" not in cfg
    (d / "MC.cfg").write_text(cfg)
    return str(d / "MC.cfg")


def lasso_is_a_fair_violation(model, res):
    """By the rule, through the evaluator: the lasso is a behaviour (an
    initial state, then steps of Next or stutters), the cycle never
    reaches Q, and for every WF_vars(A) the cycle takes an <A>_vars step
    or passes a state where none is enabled."""
    system = model.system
    q_ast = model.properties["Liveness"][2]
    chain = res.lasso_prefix + res.lasso_cycle + [res.lasso_cycle[0]]
    assert chain[0] in set(system.initial_states())
    for a, b in zip(chain, chain[1:]):
        assert a == b or b in {nxt for _, nxt in system.successors(a)}
    ev = system.ev
    for st in res.lasso_cycle:
        env = dict(ev.constants)
        env.update(zip(system.variables, st))
        assert ev.eval(q_ast, env) is False
    closed = list(zip(res.lasso_cycle,
                      res.lasso_cycle[1:] + res.lasso_cycle[:1]))
    for _, labels in model.fairness:
        def steps(st):
            return {nxt for lab, nxt in system.successors(st)
                    if lab in labels and nxt != st}
        assert any(not steps(a) for a in res.lasso_cycle) or any(
            b in steps(a) for a, b in closed)
    return True


def test_the_shipped_files_are_the_sources_model():
    m = load(CFG)
    assert m.constants["N"] == 8 and m.root_name == "EWD840"
    assert m.system.variables == ("active", "color", "tpos", "tcolor")
    assert list(m.invariants) == ["TypeOK", "TerminationDetection", "Inv"]
    assert list(m.properties) == ["Liveness"]
    assert m.properties["Liveness"][0] == "leadsto"
    assert m.fairness == SYSTEM
    assert m.system.initial_count() == 2 ** 8 * 2 ** 8 * 8
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ewd840-live.json")) as f:
        config = json.load(f)
    assert config["deployment"]["N"] == 8
    assert config["pins"]["live"]["fairness"] == [
        ["System", ["InitiateProbe", "PassToken"]]]


def test_init_as_a_product_is_the_enumeration():
    m = load(CFG, {"N": 3})
    doms = m.system.init_product()
    assert [v for v, _ in doms] == ["active", "color", "tpos", "tcolor"]
    fast = m.system.initial_states()
    slow = []
    m.system._enum_init(m.system.init_ast, {}, slow)
    assert fast == [tuple(a[v] for v in m.system.variables) for a in slow]
    assert m.system.initial_count() == len(fast) == 192


@pytest.mark.parametrize("n", [4, 5])
def test_counts_and_liveness_equal_the_reference(n, pins, tmp_path):
    want = pins[n]
    o, text, events = check(CFG, n, tmp_path)
    r = o.result
    assert o.verdict == "ok" and o.exit_code == 0, text[-600:]
    assert (r.generated, r.distinct, r.depth) == (
        want["generated"], want["distinct"], want["depth"])
    assert {k: v for k, v in r.action_generated.items() if v} == want[
        "action_generated"]
    assert ("Temporal property Liveness holds (fairness: "
            "WF_vars(System))") in text
    assert "(device liveness engine)" in text
    live = want["live"]
    (ev,) = [e for e in events if e["event"] == "liveness"]
    assert ev["property"] == "Liveness" and ev["holds"] is True
    assert ev["route"] == "device"
    assert ev["fairness"] == live["fairness"]
    for pin, counter in (("graph_states", "live_states"),
                         ("graph_edges", "live_edges"),
                         ("changed_edges", "live_changed_edges"),
                         ("fair_edges", "live_fair_edges"),
                         ("h_states", "live_h_states"),
                         ("p_states", "live_p_states"),
                         ("survivors", "live_survivors")):
        assert ev[counter] == live[pin] == getattr(r, counter), counter
    assert ev["live_host_bytes"] == 0 == r.live_host_bytes
    final = next(e for e in events if e["event"] == "final")
    assert final["verdict"] == "ok"
    # 3N - 1 passes peel the ring back from terminationDetected, each
    # pre* closing in its one sweep; the rows read are counted once and
    # carried to all three places, well under a read of every row a
    # sweep (ISSUE 42)
    assert final["live_sweeps"] == r.live_sweeps == 3 * n - 1
    assert final["live_outer"] == r.live_outer == ev["live_outer"] \
        == 3 * n - 1
    e_rows = -(-r.live_changed_edges // SWEEP_BLOCK) * SWEEP_BLOCK
    assert 0 < r.live_swept_rows < (r.live_sweeps + r.live_outer) * e_rows
    assert final["live_swept_rows"] == ev["live_swept_rows"] \
        == r.live_swept_rows
    assert r.live_swept_rows % SWEEP_BLOCK == 0
    assert final["live_edges"] == r.generated - 2 ** (2 * n) * n
    # every field read of `active` / `color` is a digit of its code
    # (ISSUE 43): the step's, the invariants' and P's and Q's, which
    # are compiled after the safety run and counted all the same
    assert (final["lookup_const"], final["lookup_arith"],
            final["lookup_gather"]) == (
        r.lookup_const, r.lookup_arith, r.lookup_gather)
    assert r.lookup_gather == 0 and r.lookup_arith > 2 * n
    # the spans of the route, inside the journal's one `spans` event and
    # in order: `live` after `loop`
    names = [row[0] for e in events if e["event"] == "spans"
             for row in e["rows"]]
    for name in ("build.struct.fairness", "live.enumerate", "live.capture",
                 "live.masks", "live.fixpoint", "live.verdict", "live"):
        assert name in names, name
    assert names.index("loop") < names.index("live.enumerate")
    # the host oracle, by the same rule in plain sets
    m = load(CFG, {"N": n})
    ast = m.properties["Liveness"]
    if n == 4:
        assert so.check_leads_to(m.system, ast[1], ast[2], "Liveness",
                                 fairness=m.fairness).holds


@pytest.fixture(scope="module")
def base4():
    """The model at N = 4, its backend and what the safety run counts
    (from the reference: the route is sized from them)."""
    from jaxtlc.struct.cache import get_backend

    m = load(CFG, {"N": 4})
    g = REF.Graph(4)
    return m, get_backend(m, False), g.distinct, g.generated - g.n_init


def route(model, backend, v, e, **kw):
    from jaxtlc.live import check_struct_properties

    ast = model.properties["Liveness"]
    (res,) = check_struct_properties(
        model, backend, [("Liveness", ast[1], ast[2])], v, e, chunk=256,
        fp_capacity=1 << 15, **kw)
    return res


@pytest.mark.parametrize("name,swap,fairness", [
    ("nofair", {" /\\ WF_vars(System)": ""}, ()),
    ("wfenv", {"WF_vars(System)": "WF_vars(Environment)"}, ENVIRONMENT),
])
def test_another_fairness_is_violated_on_both_routes(
        name, swap, fairness, base4, tmp_path):
    """The module's Next under no fairness, and under
    WF_vars(Environment) in place of WF_vars(System): the loader reads
    the formula, and `Liveness` does not hold (the token may rest for
    ever).  The step is the base model's, so its backend serves."""
    m, backend, v, e = base4
    mut = load(mutated(tmp_path, name, swap), {"N": 4})
    assert mut.fairness == fairness
    mut = m._replace(fairness=mut.fairness)
    res = route(mut, backend, v, e)
    assert not res.holds and res.counters["survivors"] > 0
    assert res.counters["host_bytes"] > 0  # the lasso came to the host
    assert lasso_is_a_fair_violation(mut, res)
    ast = m.properties["Liveness"]
    host = so.check_leads_to(m.system, ast[1], ast[2], "Liveness",
                             fairness=mut.fairness)
    assert not host.holds
    assert lasso_is_a_fair_violation(mut, host)


def test_a_node_that_is_never_whitened_is_violated(tmp_path):
    """PassToken that forgets to whiten the node it leaves (`color' =
    color`): a node once black stains every round, no probe is ever
    conclusive, and the token circles for ever - a fair cycle of System
    steps inside ~terminationDetected.  Another Next, so the whole path:
    the safety half is clean, the verdict is `liveness_violation`, the
    transcript shows the lasso, and the oracle agrees.  (ISSUE 41's
    third mutant, a token that is never blackened, breaks
    TerminationDetection and leaves Liveness true: not a case of this
    test.)"""
    cfg = mutated(tmp_path, "nowhite", {
        "color' = [color EXCEPT ![i] = \"white\"]": "color' = color"})
    o, out, events = check(cfg, 4, tmp_path)
    assert o.verdict == "liveness_violation" and o.exit_code == 13, out[
        -600:]
    assert "Temporal properties were violated: Liveness" in out
    assert "form a cycle" in out
    (ev,) = [e for e in events if e["event"] == "liveness"]
    assert ev["holds"] is False and ev["route"] == "device"
    assert ev["live_host_bytes"] > 0 and ev["live_survivors"] > 0
    final = next(e for e in events if e["event"] == "final")
    assert final["verdict"] == "liveness_violation"
    assert final["live_survivors"] == ev["live_survivors"]
    m = load(cfg, {"N": 4})
    ast = m.properties["Liveness"]
    host = so.check_leads_to(m.system, ast[1], ast[2], "Liveness",
                             fairness=m.fairness)
    assert not host.holds
    assert lasso_is_a_fair_violation(m, host)
    # the route's own lasso, by the rule (the check above replayed it
    # inside the route; here it is held to the evaluator from outside)
    from jaxtlc.struct.cache import get_backend

    res = route(m, get_backend(m, False), o.result.distinct,
                o.result.live_edges)
    assert not res.holds and lasso_is_a_fair_violation(m, res)


def test_wf_next_is_surviving_sets_set_to_the_bit(base4):
    """With the one constraint WF_vars(Next) the fair fixpoint is the
    survive-set peeling (live.fixpoint.surviving_set), bit for bit."""
    from jaxtlc.live.capture import CapturedGraph
    from jaxtlc.live.fixpoint import surviving_set

    m, backend, v, e = base4
    g = REF.Graph(4)
    every = m._replace(fairness=(("Next", tuple(backend.labels)),))
    res = route(every, backend, v, e, keep_alive=True)
    assert res.holds and res.counters["fair_edges"] == res.counters[
        "changed_edges"]
    # the same graph from the reference's rows, ids in BFS order: the
    # enumerator's ids may differ, so the sets are compared as states
    src = np.repeat(np.arange(v, dtype=np.int32),
                    np.diff(np.asarray(g.row_start)))
    dst = np.asarray(g.dst, np.int32)
    graph = CapturedGraph(
        n_states=v, init_count=g.init_ids, states=None, src=src, dst=dst,
        action=np.asarray(g.act, np.int32), changed=src != dst)
    in_h = np.array([not REF.termination_detected(st) for st in g.states])
    alive, _ = surviving_set(graph, in_h)
    want = {g.states[i] for i in np.flatnonzero(alive)}
    assert len(want) == int(res.alive.sum()) > 0
    # the route's ids -> states, through its own enumerator
    from jaxtlc.live.capture import make_scoped_enumerator

    init_fn, enum = make_scoped_enumerator(backend, 256, v, 1 << 15)
    states = np.asarray(backend.cdc.unpack(enum(init_fn()).states[:v]))
    got = set()
    for i in np.flatnonzero(res.alive):
        active, color, tpos, tcolor = backend.cdc.decode(states[i])
        got.add((sum(1 << k for k, on in active if on),
                 sum(1 << k for k, c in color if c == "black"),
                 tpos, int(tcolor == "black")))
    assert got == want


@pytest.mark.parametrize("conjunct,says", [
    ("SF_vars(System)", "strong fairness"),
    ("WF_vars(terminationDetected)", "not a disjunction of the spec's "
                                     "actions"),
    ("WF_tpos(System)", "subscript"),
    ("<>[](tpos = 0)", "not a fairness condition"),
])
def test_a_conjunct_the_loader_cannot_honour_is_named(
        conjunct, says, tmp_path):
    swap = {"WF_vars(System)": conjunct}
    with pytest.raises(StructLoadError) as err:
        load(mutated(tmp_path, "with", swap), {"N": 3})
    assert says in str(err.value)
    assert conjunct.replace(" ", "") in str(err.value).replace(" ", "")
    # a safety-only check does not read fairness: it loads as before
    m = load(mutated(tmp_path, "without", swap, properties=False),
             {"N": 3})
    assert m.fairness == () and not m.properties


def test_fairness_flag_is_refused_on_a_spec_that_states_its_own(tmp_path):
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=CFG, frontend="struct", workers="cpu", noTool=True,
        constants={"N": 3}, fairness="wf_process", out=out, err=out))
    assert o.exit_code == 1
    assert "-fairness wf_process" in out.getvalue()
    assert "WF_vars(System)" in out.getvalue()


def test_a_queue_narrower_than_init_is_regrown_before_the_build(tmp_path):
    """Init alone is 2^(2N) N states: a `-qcap` (the default, at the
    cell's N) that cannot seat them is raised to the power of two that
    can before the engine is built - one `regrow` event, the same
    counts, the property still checked."""
    o, text, events = check(CFG, 4, tmp_path, qcap=512)
    (ev,) = [e for e in events if e["event"] == "regrow"]
    assert (ev["resource"], ev["old"], ev["new"]) == (
        "queue_capacity", 512, 1024)
    assert o.verdict == "ok", text[-600:]
    assert (o.result.generated, o.result.distinct) == (15986, 1566)
    assert "Liveness holds" in text
