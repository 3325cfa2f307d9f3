"""State-space reduction tests (ISSUE 18): device-resident symmetry
canonicalization + POR ample-set pruning explore FEWER states with the
IDENTICAL verdict, invariant outcomes and rendered violation trace -
and the runtime orbit certificate (sticky COL_SYM) catches a lying
canonicalization instead of letting it silently merge real states.

Compile budget (tier-1 runs near its 870 s hard timeout): ONE
module-scoped fixture owns the two Model_sym engine compiles (full vs
symmetry-reduced); the canon-oracle test reuses the reduced backend's
plan with host numpy only; the exit-12 / POR / lie tests run tiny
synthetic struct engines (seconds); the supervised-interrupt and
2-dev sharded tests each pay their own small compile like
tests/test_deferred.py does."""

import io
import os
import re
import shutil

import numpy as np
import pytest

from jaxtlc.engine import checkpoint as ck
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised
from jaxtlc.struct import cache
from jaxtlc.struct.backend import struct_meta_config
from jaxtlc.struct.engine import check_struct, check_struct_sharded
from jaxtlc.struct.loader import load

SPECS = os.path.join(os.path.dirname(__file__), os.pardir, "specs")
SYM_CFG = os.path.join(SPECS, "TwoPhase.toolbox", "Model_sym", "MC.cfg")
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)

# Model_sym: TwoPhase with RM = {r1, r2, r3} (6 orbit permutations);
# the full space and the >= 2x acceptance floor on the reduced one
EXPECT_FULL = (810, 288, 11)
EXPECT_REDUCED = (228, 80, 11)


def signature(r):
    """Full exactness signature of a CheckResult."""
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())),
            r.outdegree)


@pytest.fixture(scope="module")
def model():
    return load(SYM_CFG)


@pytest.fixture(scope="module")
def ab_runs(model):
    """The module's ONLY full engine compiles: Model_sym through the
    full engine and the symmetry-reduced one (orbit canonicalization +
    the COL_SYM certificate column, obs ring on)."""
    out = {}
    for sym in (False, True):
        out[sym] = check_struct(model, check_deadlock=False,
                                obs_slots=8, symmetry=sym, **KW)
    return out


# ---------------------------------------------------------------------------
# the acceptance contract: fewer states, same answers
# ---------------------------------------------------------------------------


def test_reduction_factor_and_verdict_parity(ab_runs):
    """>= 2x fewer distinct states (3.6x here: 288 -> 80 under the
    6-element orbit group), identical verdict, invariant outcome and
    BFS depth - and the orbit-certificate column ACTIVE (False, not
    None) on the reduced run, absent on the full one."""
    full, red = ab_runs[False], ab_runs[True]
    assert (full.generated, full.distinct, full.depth) == EXPECT_FULL
    assert (red.generated, red.distinct, red.depth) == EXPECT_REDUCED
    assert red.distinct * 2 <= full.distinct
    assert (red.violation, red.violation_name) == (
        full.violation, full.violation_name)
    assert red.sym_violated is False  # the certificate ran, clean
    assert full.sym_violated is None  # no plan, no column


def test_canon_matches_host_permutation_oracle(model):
    """The device canon kernel equals the host oracle on reachable
    states: for every state, enumerate its FULL orbit by applying
    every stored permutation program on host, and the canonical form
    must be the lexicographic minimum of that orbit (independent
    tuple-compare arithmetic, not the masked tournament) - and
    constant across every orbit member."""
    import jax
    import jax.numpy as jnp

    from perbit_canon import apply_program

    b = cache.get_backend(model, check_deadlock=False, symmetry=True)
    plan = b.reduce.plan
    assert plan is not None and plan.programs

    # reachable flat states: a 3-level host-driven BFS over the
    # backend's own step function (tiny - TwoPhase fans out ~3/state)
    step = jax.jit(b.step)
    seen = {}
    frontier = [tuple(int(v) for v in row)
                for row in np.asarray(b.initial_vectors())]
    for row in frontier:
        seen[row] = True
    for _ in range(3):
        nxt = []
        for row in frontier:
            succs, valid, _, _, _ = step(jnp.asarray(row, jnp.int32))
            for s, v in zip(np.asarray(succs), np.asarray(valid)):
                t = tuple(int(x) for x in s)
                if v and t not in seen:
                    seen[t] = True
                    nxt.append(t)
        frontier = nxt
    states = np.asarray(sorted(seen), np.int32)
    assert len(states) >= 10

    def orbit(row):
        mem = {tuple(int(v) for v in row)}
        for p in plan.programs:
            cols = apply_program(p, row[None, :])
            mem.add(tuple(int(c[0]) for c in cols))
        return mem

    canon_dev = np.asarray(plan.canon(jnp.asarray(states)))
    canon_host = plan.canon_host(states)
    assert (canon_dev == canon_host).all()
    for i, row in enumerate(states):
        o = orbit(row)
        want = min(o)  # lexicographic minimum, tuple compare
        assert tuple(int(v) for v in canon_host[i]) == want
        # constant on the orbit: every member canonicalizes the same
        members = np.asarray(sorted(o), np.int32)
        cm = plan.canon_host(members)
        assert (cm == np.asarray(want, np.int32)).all()


# ---------------------------------------------------------------------------
# seeded violation: same verdict, same rendered trace
# ---------------------------------------------------------------------------


_SYMV = """---- MODULE SymV ----
EXTENDS Naturals, FiniteSets
CONSTANTS RM
VARIABLES voted, n
Init == voted = {} /\\ n = 0
Vote == /\\ \\E r \\in RM \\ voted : voted' = voted \\cup {r}
        /\\ n' = n + 1
Next == Vote
Small == n < 2
====
"""
_SYMV_CFG = "CONSTANT RM = {r1, r2, r3}\nINVARIANT\nSmall\n"


def test_exit12_trace_identical(tmp_path):
    """A seeded invariant violation renders the IDENTICAL exit-12
    counterexample trace with and without -symmetry: the invariant
    cannot distinguish orbit members (the static verification
    guarantees it), so the host re-walk reconstructs the same
    transcript.  Progress counters legitimately differ (the reduced
    run explored fewer states) and the unreduced-symmetry preflight
    nudge only fires on the full run - everything from the violation
    banner through the last trace state must match byte-for-byte."""
    from jaxtlc.api import CheckRequest, run_check

    (tmp_path / "SymV.tla").write_text(_SYMV)
    cfg = tmp_path / "SymV.cfg"
    cfg.write_text(_SYMV_CFG)

    traces = {}
    for sym in (False, True):
        out = io.StringIO()
        outcome = run_check(CheckRequest(
            config=str(cfg), workers="cpu", frontend="struct",
            noTool=True, autogrow=False, obs=False,
            chunk=64, qcap=1 << 10, fpcap=1 << 12,
            symmetry=sym, out=out, err=out,
        ))
        assert outcome.exit_code == 12, out.getvalue()
        t = out.getvalue()
        assert "Small is violated" in t
        # the rendered counterexample: violation banner up to (not
        # including) the wall-clock progress line
        start = t.index("Invariant Small is violated")
        end = t.index("Progress(")
        traces[sym] = t[start:end]
    assert traces[False] == traces[True]
    # the full run got nudged toward -symmetry; the reduced one not
    # (it already took the reduction)


# ---------------------------------------------------------------------------
# POR: fewer states on the synthetic safe-action spec, same verdict
# ---------------------------------------------------------------------------


_PORV = """---- MODULE PorV ----
EXTENDS Naturals
VARIABLES x, y

Init == x = 0 /\\ y = 0

IncX == /\\ x < 4
        /\\ x' = x + 1
        /\\ UNCHANGED <<y>>

IncY == /\\ y < 4
        /\\ y' = y + 1
        /\\ UNCHANGED <<x>>

Next == IncX \\/ IncY

Spec == Init /\\ [][Next]_<<x, y>>

InRange == x <= 4
====
"""
_PORV_CFG = "SPECIFICATION\nSpec\nINVARIANT\nInRange\n"


def test_por_prunes_with_identical_verdict(tmp_path):
    """-por on the two-counter spec with one ample-safe action (IncY:
    independent of IncX, invisible to InRange, monotone): the 5x5
    grid collapses to the 9-state staircase - same verdict, and the
    pruned-transition counter reports what the ample sets cut."""
    (tmp_path / "PorV.tla").write_text(_PORV)
    cfg = tmp_path / "PorV.cfg"
    cfg.write_text(_PORV_CFG)
    model = load(str(cfg))

    b = cache.get_backend(model, check_deadlock=False, por=True)
    assert b.reduce is not None and b.reduce.safe_ids == (1,)

    geo = dict(chunk=64, queue_capacity=1 << 10, fp_capacity=1 << 12)
    full = check_struct(model, check_deadlock=False, **geo)
    red = check_struct(model, check_deadlock=False, por=True, **geo)
    assert (full.violation, full.distinct) == (0, 25)
    assert (red.violation, red.distinct) == (0, 9)
    assert red.por_pruned == 4
    assert full.por_pruned is None


# ---------------------------------------------------------------------------
# checkpoint mode continuity (supervised, SIGTERM -> -recover)
# ---------------------------------------------------------------------------


def test_sigterm_recover_mode_continuity(tmp_path, model, ab_runs):
    p = str(tmp_path / "ck.npz")
    events = []
    sr = check_supervised(
        None,
        backend=cache.get_backend(model, check_deadlock=False,
                                  symmetry=True),
        meta_config=struct_meta_config(model), check_deadlock=False,
        opts=SupervisorOptions(
            ckpt_path=p, ckpt_every=1,
            faults=FaultPlan.parse("sigterm@2"),
            on_event=lambda k, i: events.append(k),
        ),
        **KW,
    )
    assert sr.interrupted and "interrupted" in events
    gens = ck.list_generations(p)
    assert gens
    meta = ck.read_checkpoint_meta(gens[-1][1])
    assert meta["symmetry"] is True  # the mode travels in the meta
    assert meta["por"] is False

    # wrong-mode recover is LOUD - a full-space resume would re-visit
    # states the reduced run canonicalized away (and vice versa), so
    # the meta check rejects it before any engine build
    with pytest.raises(ValueError, match="symmetry mismatch"):
        check_supervised(
            None,
            backend=cache.get_backend(model, check_deadlock=False),
            meta_config=struct_meta_config(model),
            check_deadlock=False,
            opts=SupervisorOptions(ckpt_path=p, resume=True),
            **KW,
        )

    # same mode resumes to the exact clean-run statistics
    sr2 = check_supervised(
        None,
        backend=cache.get_backend(model, check_deadlock=False,
                                  symmetry=True),
        meta_config=struct_meta_config(model), check_deadlock=False,
        opts=SupervisorOptions(ckpt_path=p, ckpt_every=64, resume=True),
        **KW,
    )
    assert not sr2.interrupted
    assert signature(sr2.result) == signature(ab_runs[True])


# ---------------------------------------------------------------------------
# sharded inheritance (one 2-dev compile)
# ---------------------------------------------------------------------------


def test_sharded_2dev_parity(model, ab_runs):
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("fp",))
    r = check_struct_sharded(model, mesh, check_deadlock=False,
                             symmetry=True, **KW)
    ref = ab_runs[True]
    assert (r.violation, r.distinct, r.generated, r.depth) == (
        ref.violation, ref.distinct, ref.generated, ref.depth)
    assert r.queue_left == 0
    assert r.action_generated == ref.action_generated


# ---------------------------------------------------------------------------
# the orbit certificate catches a lying canonicalization
# ---------------------------------------------------------------------------


def test_sym_lie_trips_certificate_exit1(tmp_path, monkeypatch):
    """JAXTLC_DEBUG_SYM_LIE=1 corrupts one remap table of the built
    plan (the debug seam): the canonical form stops being constant on
    reachable orbits, the sticky COL_SYM column latches, and the front
    door escalates to verdict=error / exit 1 instead of reporting
    counts from a silently-merged state space.  A digest-perturbed
    copy of Model_sym keeps the lying backend out of the process-wide
    memo every other test shares."""
    from jaxtlc.api import CheckRequest, run_check

    src = os.path.dirname(SYM_CFG)
    for f in os.listdir(src):
        shutil.copy(os.path.join(src, f), tmp_path)
    with open(tmp_path / "TwoPhase.tla", "a") as f:
        f.write("\n\\* orbit-lie test copy\n")
    monkeypatch.setenv("JAXTLC_DEBUG_SYM_LIE", "1")

    out = io.StringIO()
    outcome = run_check(CheckRequest(
        config=str(tmp_path / "MC.cfg"), workers="cpu",
        frontend="struct", noTool=True, autogrow=False, obs=False,
        nodeadlock=True, chunk=128, qcap=1 << 12, fpcap=1 << 14,
        symmetry=True, out=out, err=out,
    ))
    t = out.getvalue()
    assert outcome.exit_code == 1, t
    assert "orbit-certificate violation" in t, t


def test_reduced_run_regrows_like_any_other(ab_runs):
    """A reduced run whose queue is too small regrows and ends in the
    reduced counts: resil.regrow carries the reduction's carry leaves
    (sticky orbit flag, canon counters) into the wider geometry."""
    from jaxtlc.api import CheckRequest, run_check

    out = io.StringIO()
    outcome = run_check(CheckRequest(
        config=SYM_CFG, workers="cpu", frontend="struct", noTool=True,
        nodeadlock=True, chunk=128, qcap=8, fpcap=1 << 14,
        symmetry=True, out=out, err=out,
    ))
    t = out.getvalue()
    assert outcome.verdict == "ok", t
    assert "regrowing queue_capacity" in t, t
    r, ref = outcome.result, ab_runs[True]
    assert (r.generated, r.distinct, r.depth) == EXPECT_REDUCED
    assert (r.canon_rows, r.canon_moved, r.sym_cert_trips) == (
        ref.canon_rows, ref.canon_moved, 0)


# ---------------------------------------------------------------------------
# mode resolution + memo identity (host-only)
# ---------------------------------------------------------------------------


def test_flags_ride_engine_memo_key(model):
    """-symmetry / -por are engine-identity: the memo key must split on
    them (a reduced engine answering a full-space request would be a
    silent soundness hole), and both resolve auto -> OFF (reduction is
    opt-in: counts legitimately shrink)."""
    from jaxtlc.engine.bfs import resolve_por, resolve_symmetry
    from jaxtlc.struct.cache import engine_key

    assert resolve_symmetry(None, 64) is False
    assert resolve_por(None, 1 << 20) is False
    assert resolve_symmetry(True, 64) is True
    assert resolve_por(True, 64) is True

    base = dict(chunk=64, queue_capacity=1 << 10, fp_capacity=1 << 12,
                fp_index=0, seed=0, fp_highwater=0.85)
    k_auto = engine_key(model, **base, symmetry=None, por=None)
    k_off = engine_key(model, **base, symmetry=False, por=False)
    k_sym = engine_key(model, **base, symmetry=True, por=None)
    k_por = engine_key(model, **base, symmetry=None, por=True)
    assert k_auto == k_off  # auto resolves to off
    assert len({k_off, k_sym, k_por}) == 3
