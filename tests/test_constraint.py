"""A cfg's CONSTRAINT at the expand / commit seam (ISSUE 39), on a
ten-line counter model against the host interpreter, and on a small
model of functions over an integer interval bounded by its constants.

TLC's rule as this repo reads it: a successor that fails the constraint
counts as generated (and toward its action) and is then dropped - never
fingerprinted, enqueued or invariant-checked; deadlock is judged on the
successors before the constraint; an initial state outside it is
checked and not kept; a dropped candidate never trips a range trap, a
kept one still does.  Every route other than the single-device
exhaustive engine refuses a constrained model by name.
"""

import io
import os

import numpy as np
import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.struct.loader import StructLoadError, load
from jaxtlc.struct.oracle import bfs

GEO = dict(chunk=16, qcap=128, fpcap=512)

CTR = """---- MODULE Ctr ----
EXTENDS Naturals
VARIABLES x, y
Init == x \\in {INITS} /\\ y = 0
Up == x' = x + 1 /\\ y' = y
Side == y' = 1 - y /\\ x' = x
Next == NEXT
Bound == x <= 3
AlsoY == y <= 1
Small == x <= 3
NotFive == x # 5
Primed == x' <= 3
Param(k) == x <= k
====
"""


def ctr(tmp_path, cfg_tail, inits="0", nxt="Up \\/ Side", name="Ctr"):
    d = tmp_path / name
    d.mkdir(exist_ok=True)
    (d / "Ctr.tla").write_text(
        CTR.replace("INITS", inits).replace("NEXT", nxt))
    (d / "Ctr.cfg").write_text("INIT Init\nNEXT Next\n" + cfg_tail)
    return str(d / "Ctr.cfg")


def check(cfg, **kw):
    out = io.StringIO()
    o = run_check(CheckRequest(config=cfg, frontend="struct",
                               workers="cpu", noTool=True, out=out,
                               err=out, **{**GEO, **kw}))
    return o, out.getvalue()


def oracle(cfg, deadlock=True):
    m = load(cfg)
    return bfs(m.system, m.invariants, check_deadlock=deadlock,
               constraints=m.constraints)


@pytest.mark.parametrize("deferred", [False, True])
def test_a_discarded_successor_counts_and_is_never_checked(
        tmp_path, deferred):
    """x runs 0..3 under `CONSTRAINT Bound`; the invariant `x <= 3`
    fails only on the successor x = 4, which is generated, counted
    toward Up, discarded and never checked: the run ends ok, in the
    immediate and in the deferred-invariant mode alike, with the host
    interpreter's counts."""
    cfg = ctr(tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n")
    want = oracle(cfg)
    assert (want.generated, want.distinct, want.depth, want.discarded,
            want.violations) == (17, 8, 5, 2, [])
    o, text = check(cfg, deferredinv=deferred)
    r = o.result
    assert (o.verdict, o.exit_code) == ("ok", 0), text
    assert (r.generated, r.distinct, r.depth) == (17, 8, 5)
    assert r.action_generated == want.action_generated == {
        "Up": 8, "Side": 8}
    assert (r.constraint_rows, r.constraint_discarded) == (16, 2)
    assert r.lane_fires == 16 and r.struct_traps == 0
    assert r.constraint_names == ("Bound",)


def test_several_constraints_are_a_conjunction_and_the_journal_names_them(
        tmp_path):
    import json

    cfg = ctr(tmp_path, "CONSTRAINTS Bound\nAlsoY\nINVARIANT Small\n")
    journal = str(tmp_path / "j.jsonl")
    o, text = check(cfg, journal=journal)
    assert o.verdict == "ok", text
    events = [json.loads(ln) for ln in open(journal)]
    start = next(e for e in events if e["event"] == "run_start")
    final = next(e for e in events if e["event"] == "final")
    assert start["params"]["constraints"] == ["Bound", "AlsoY"]
    assert (final["constraint_rows"], final["constraint_discarded"]) == (
        16, 2)
    assert final["constraint_names"] == ["Bound", "AlsoY"]
    spans = next(e for e in events if e["event"] == "spans")
    assert "build.struct.constraint" in json.dumps(spans)


@pytest.mark.parametrize("inv,verdict", [("Small", "violation"),
                                         ("NotFive", "violation"),
                                         ("AlsoY", "ok")])
def test_an_initial_state_outside_the_constraint_is_checked_not_kept(
        tmp_path, inv, verdict):
    """Init has x = 0 and x = 5; 5 fails `Bound`.  It is generated and
    invariant-checked (an invariant it fails is a violation) and never
    kept: with an invariant it satisfies the run is the x = 0 run plus
    one generated state."""
    cfg = ctr(tmp_path, f"CONSTRAINT Bound\nINVARIANT {inv}\n",
              inits="0, 5")
    want = oracle(cfg)
    o, text = check(cfg)
    if verdict == "ok":
        r = o.result
        assert o.verdict == "ok", text
        assert (r.generated, r.distinct, r.depth) == (18, 8, 5) == (
            want.generated, want.distinct, want.depth)
        assert want.discarded_inits == 1
        assert (r.constraint_rows, r.constraint_discarded) == (16, 2)
    else:
        assert o.exit_code == 12 and want.violations, text
        assert want.violations[0][0] == inv


def test_deadlock_is_judged_before_the_constraint(tmp_path):
    """With Up alone, x = 3 has one successor and the constraint drops
    it: not a deadlock (TLC judges deadlock on the successors before
    the constraint), with deadlock checking on."""
    cfg = ctr(tmp_path, "CONSTRAINT Bound\nINVARIANT AlsoY\n", nxt="Up")
    want = oracle(cfg)
    assert (want.generated, want.distinct, want.violations) == (5, 4, [])
    o, text = check(cfg)
    assert (o.verdict, o.result.generated, o.result.distinct) == (
        "ok", 5, 4), text
    assert o.result.constraint_discarded == 1


def test_trap_against_discard_has_an_order(tmp_path):
    """At the seam itself: a candidate the constraint rejects never
    trips a range / slot trap, a kept one does."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine.backend import make_expand_stage
    from jaxtlc.engine.bfs import OK, VIOL_SLOT_OVERFLOW
    from jaxtlc.engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
    from jaxtlc.struct.cache import get_backend

    backend = get_backend(load(ctr(
        tmp_path, "CONSTRAINT Bound\nINVARIANT AlsoY\n")))
    x_col = backend.cdc.offsets["x"]
    x_lo = backend.cdc.layouts[0].shape.lo

    def trapping(x_value):
        def step(vec):
            succs, valid, action, afail, ovf = backend.step(vec)
            return succs, valid, action, afail, valid & (
                succs[:, x_col] + x_lo == x_value)
        return backend._replace(step=step)

    batch = jnp.asarray(np.stack([
        backend.cdc.encode((3, 0)), backend.cdc.encode((1, 1))]))
    mask = jnp.ones(2, bool)

    def viol(b):
        ex = jax.jit(make_expand_stage(
            b, 2, None, DEFAULT_FP_INDEX, DEFAULT_SEED))(batch, mask)
        return int(ex.viol), None if ex.con_stat is None else [
            int(v) for v in ex.con_stat]

    # x' = 4 is outside the constraint: its trap does not fire
    assert viol(trapping(4)) == (OK, [4, 1])
    # x' = 2 is kept: its trap halts the run
    assert viol(trapping(2)) == (VIOL_SLOT_OVERFLOW, [4, 1])
    # and with no constraint the first one fires too
    assert viol(trapping(4)._replace(constraint=None))[0] == (
        VIOL_SLOT_OVERFLOW)


@pytest.mark.parametrize("tail,why", [
    ("CONSTRAINT Gone\n", "CONSTRAINT Gone: no such definition"),
    ("CONSTRAINT Primed\n", "CONSTRAINT Primed: mentions a primed"),
    ("CONSTRAINT Param\n", "CONSTRAINT Param: an operator with "
                           "parameters"),
    ("ACTION_CONSTRAINT Primed\n", "not supported: ACTION_CONSTRAINT"),
    ("VIEW Small\n", "not supported: VIEW"),
])
def test_a_constraint_the_loader_cannot_take_is_a_load_error(
        tmp_path, tail, why):
    with pytest.raises(StructLoadError, match=why):
        load(ctr(tmp_path, tail))


@pytest.mark.parametrize("option,names", [
    (dict(sharded=2), "-sharded"),
    (dict(simulate=True), "-simulate"),
    (dict(infer=True), "-infer"),
    (dict(liveness=True), "-liveness"),
    (dict(narrow=True), "-narrow"),
    (dict(symmetry=True), "-symmetry"),
    (dict(por=True), "-por"),
])
def test_every_other_route_refuses_a_constrained_model_by_name(
        tmp_path, option, names):
    cfg = ctr(tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n")
    o, text = check(cfg, **option)
    assert o.exit_code == 1 and o.result is None
    assert "the cfg declares CONSTRAINT Bound" in text and names in text


@pytest.mark.parametrize("frontend", ["hand", "gen"])
def test_the_other_frontends_refuse_the_cfg_line(tmp_path, frontend):
    from jaxtlc.frontend.model import resolve

    cfg = ctr(tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n")
    with pytest.raises(ValueError, match="declares CONSTRAINT Bound"):
        resolve(cfg, frontend=frontend)
    assert type(resolve(cfg, frontend="auto")).__name__ == "StructRunSpec"


@pytest.mark.parametrize("route", [
    "sharded", "sim", "replay", "capture", "certify", "enumerator"])
def test_the_engines_refuse_where_they_are_built(tmp_path, route):
    """Beneath the API: an engine that expands states without the
    shared expand stage refuses a constrained backend naming itself."""
    import jax

    from jaxtlc.engine.backend import ConstraintUnsupported
    from jaxtlc.struct.cache import get_backend

    backend = get_backend(load(ctr(
        tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n")))
    with pytest.raises(ConstraintUnsupported, match="CONSTRAINT Bound"):
        if route == "sharded":
            from jax.sharding import Mesh

            from jaxtlc.engine.sharded import make_sharded_engine

            make_sharded_engine(None, Mesh(np.array(jax.devices()[:2]),
                                           ("d",)), backend=backend)
        elif route == "sim":
            from jaxtlc.sim.engine import make_sim_engine

            make_sim_engine(backend)
        elif route == "replay":
            from jaxtlc.sim.replay import replay_lane

            replay_lane(backend, 0, 0, 1)
        elif route == "capture":
            from jaxtlc.live.capture import capture_edges

            capture_edges(backend)
        elif route == "certify":
            from jaxtlc.infer.certify import make_certify_fn

            make_certify_fn(backend, [])
        else:
            from jaxtlc.engine.bfs import make_enumerator

            make_enumerator(backend)


def test_preflight_reports_the_constraint_leaf_by_leaf(tmp_path):
    from jaxtlc.analysis.preflight import preflight_struct

    rep = preflight_struct(load(ctr(
        tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n")),
        fp_capacity=512, chunk=16, queue_capacity=128)
    text = "\n".join(rep.constraint_lines)
    assert "CONSTRAINT Bound" in text
    assert "x: <= 3 by Bound; codec" in text
    assert "y: " in text and "by inference alone" in text
    assert not rep.errors


def test_a_leaf_neither_bounds_is_refused_before_a_build(tmp_path):
    """`AlsoY` bounds y alone: x grows without a bound from the
    constraint or from inference, and the run is refused by the leaf's
    name before anything is built."""
    cfg = ctr(tmp_path, "CONSTRAINT AlsoY\nINVARIANT AlsoY\n")
    o, text = check(cfg)
    assert o.result is None and o.exit_code != 0
    assert "integer leaf x is bounded neither by CONSTRAINT AlsoY" in text


RING = """---- MODULE Ring ----
EXTENDS Integers, FiniteSets, Functions
CONSTANTS N, K
Node == 0 .. N-1
VARIABLES tok, cnt, on
TypeOK == /\\ tok \\in Node
          /\\ cnt \\in [Node -> 0 .. K]
          /\\ on \\in [Node -> BOOLEAN]
Init == /\\ tok = 0
        /\\ cnt = [i \\in Node |-> 0]
        /\\ on \\in [Node -> BOOLEAN]
Rng(a, b) == {i \\in Node : a <= i /\\ i <= b}
Sum(f, S) == FoldFunctionOnSet(+, 0, f, S)
Bump(i) == /\\ on[i] /\\ cnt[i] < K /\\ tok >= i
           /\\ cnt' = [cnt EXCEPT ![i] = @ + 1]
           /\\ UNCHANGED <<tok, on>>
Flip(i) == /\\ on' = [on EXCEPT ![i] = ~ @]
           /\\ UNCHANGED <<tok, cnt>>
Move == /\\ tok' = IF tok = N - 1 THEN 0 ELSE tok + 1
        /\\ UNCHANGED <<cnt, on>>
Next == Move \\/ \\E i \\in Node : Bump(i) \\/ Flip(i)
Masked == /\\ Sum(cnt, Rng(0, tok)) <= K * (tok + 1)
          /\\ Sum(cnt, Node) = FoldFunction(+, 0, cnt)
          /\\ \\A i \\in Rng(tok + 1, N - 1) : cnt[i] <= Sum(cnt, Node)
          /\\ Sum(cnt, Node \\ {0}) + cnt[0] = Sum(cnt, Rng(0, N - 1))
Tight == Sum(cnt, Rng(1, tok)) < K * (N - 1)
====
"""


def ring(tmp_path, invs):
    d = tmp_path / "Ring"
    d.mkdir(exist_ok=True)
    (d / "Ring.tla").write_text(RING)
    (d / "Ring.cfg").write_text(
        "CONSTANTS N = 3\nK = 2\nINIT Init\nNEXT Next\n"
        f"INVARIANT {invs}\n")
    return str(d / "Ring.cfg")


def test_functions_over_an_integer_interval_and_the_masked_sum(tmp_path):
    """`[0 .. N-1 -> ...]` variables (dense: one slot a key), keys as
    numbers (`cnt[i]` under `\\E i \\in Node`, `Node \\ {0}`), a filter
    over the interval with a state-dependent bound, and the fold of a
    function over it: the engine against the host interpreter."""
    cfg = ring(tmp_path, "TypeOK Masked")
    m = load(cfg)
    state = m.system.initial_states()[0]
    assert dict(zip(m.system.variables, state))["cnt"] == (
        (0, 0), (1, 0), (2, 0))
    want = bfs(m.system, m.invariants)
    assert want.violations == [] and want.distinct == 3 * 27 * 8
    o, text = check(cfg, chunk=64, qcap=1024, fpcap=4096)
    r = o.result
    assert o.verdict == "ok", text
    assert (r.generated, r.distinct, r.depth) == (
        want.generated, want.distinct, want.depth)
    assert r.action_generated == want.action_generated
    assert r.struct_traps == 0


def test_the_masked_sum_finds_the_violation_the_interpreter_finds(tmp_path):
    cfg = ring(tmp_path, "Tight")
    m = load(cfg)
    want = bfs(m.system, m.invariants)
    assert want.violations and want.violations[0][0] == "Tight"
    o, text = check(cfg, chunk=64, qcap=1024, fpcap=4096)
    assert o.exit_code == 12 and "Tight" in text


def test_the_reachable_set_artifact_is_keyed_on_the_constraint(tmp_path):
    """An edit of the cfg's CONSTRAINT changes the reachable set: the
    behavior digest (the reach tier's key) has to move with it, and
    does not move with an invariant-only edit."""
    from jaxtlc.struct.artifacts import behavior_digest

    both = behavior_digest(load(ctr(
        tmp_path, "CONSTRAINTS Bound AlsoY\nINVARIANT Small\n", name="a")))
    one = behavior_digest(load(ctr(
        tmp_path, "CONSTRAINT Bound\nINVARIANT Small\n", name="b")))
    other_inv = behavior_digest(load(ctr(
        tmp_path, "CONSTRAINT Bound\nINVARIANT AlsoY\n", name="c")))
    none = behavior_digest(load(ctr(tmp_path, "INVARIANT Small\n",
                                    name="d")))
    assert len({both, one, none}) == 3 and one == other_inv
