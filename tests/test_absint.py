"""Certified abstract interpretation (ISSUE 10 acceptance criteria).

- bound-report soundness: for TwoPhase and RaftElection, the ACTUAL
  reachable sets (host oracle enumeration) lie inside the certified
  bounds - every reachable state encodes under the narrowed codec and
  every variable value is contained in its certified shape;
- codec narrowing: a guard-bounded synthetic spec narrows from the
  widened baseline to the exact reachable ranges, the packed word
  count strictly drops, and the narrowed engine's per-action
  generated/distinct counts and verdict are identical to the baseline
  engine's with the runtime certificate active and clean;
- seeded unsound bounds turn LOUD, never silent: an interval lie halts
  on the kept codec trap (violation verdict), a cardinality lie - the
  one narrowing that has no trap - trips the runtime certificate
  column, and through the full api.run_check path the verdict is
  "error" with a nonzero exit;
- the sweep-class audit covers the whole constants class (lo..hi),
  not just the anchor configuration;
- the engine-free lint gate (tools/lintgate.py / --gate) passes the
  committed specs tree and fails on error-severity findings.

Budget: one module-scoped synthetic engine pair + one unsound-bound
engine; the TwoPhase/RaftElection work is host-only Python.
"""

import dataclasses
import io

import pytest

from jaxtlc.analysis.absint import analyze_bounds
from jaxtlc.struct.loader import load
from jaxtlc.struct.shapes import SInt, shape_leq, shape_of_value

# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def twophase():
    return load("specs/TwoPhase.toolbox/Model_1/MC.cfg")


@pytest.fixture(scope="module")
def twophase_bounds(twophase):
    return analyze_bounds(twophase)


def _write_model(tmp_path, name, module, cfg):
    d = tmp_path / name
    d.mkdir()
    (d / f"{name}.tla").write_text(module)
    (d / f"{name}.cfg").write_text(cfg)
    return str(d / f"{name}.cfg")


# five guard-bounded counters: the ascending widening ladder + TypeOK
# slack over-approximates each to 0..127 (7 bits), the certified
# narrowing recovers the exact 0..3 (2 bits) - 35 -> 10 bits, so the
# packed word count STRICTLY drops 2 -> 1 (the acceptance criterion,
# demonstrated without the reference mount)
_WIDE = """---- MODULE Wide ----
EXTENDS Naturals
VARIABLES a, b, c, d, e
Init == /\\ a = 0 /\\ b = 0 /\\ c = 0 /\\ d = 0 /\\ e = 0
UpA == /\\ a < 3 /\\ a' = a + 1 /\\ UNCHANGED <<b, c, d, e>>
UpB == /\\ b < 3 /\\ b' = b + 1 /\\ UNCHANGED <<a, c, d, e>>
UpC == /\\ c < 3 /\\ c' = c + 1 /\\ UNCHANGED <<a, b, d, e>>
UpD == /\\ d < 3 /\\ d' = d + 1 /\\ UNCHANGED <<a, b, c, e>>
UpE == /\\ e < 3 /\\ e' = e + 1 /\\ UNCHANGED <<a, b, c, d>>
Next == UpA \\/ UpB \\/ UpC \\/ UpD \\/ UpE
TypeOK == /\\ a \\in 0..100 /\\ b \\in 0..100 /\\ c \\in 0..100
          /\\ d \\in 0..100 /\\ e \\in 0..100
====
"""
_WIDE_CFG = "INVARIANT\nTypeOK\n"


@pytest.fixture(scope="module")
def wide_model(tmp_path_factory):
    cfg = _write_model(tmp_path_factory.mktemp("wide"), "Wide",
                       _WIDE, _WIDE_CFG)
    return load(cfg)


@pytest.fixture(scope="module")
def wide_bounds(wide_model):
    return analyze_bounds(wide_model)


# a 13-element record universe forces the slot-lane path on Drop; the
# honest cardinality fixpoint cannot bound |msgs| below the universe
# (the transfer sees the \\cup, not the n < 2 guard), so the honest
# run keeps its slot traps - the LIE below then exercises exactly the
# narrowing that has NO trap
_SLOTC = """---- MODULE SlotC ----
EXTENDS Naturals, FiniteSets
CONSTANTS RM
VARIABLES msgs, n
Init == /\\ msgs = {} /\\ n = 0
Send == /\\ n < 2
        /\\ \\E r \\in RM : msgs' = msgs \\cup {[kind |-> "a", from |-> r]}
        /\\ n' = n + 1
Drop == /\\ \\E m \\in msgs : msgs' = msgs \\ {m}
        /\\ UNCHANGED n
Next == Send \\/ Drop
TypeOK == /\\ \\A m \\in msgs : m.from \\in RM /\\ n \\in 0..5
====
"""
_SLOTC_CFG = ("CONSTANT RM = {r1, r2, r3, r4, r5, r6, r7, r8, r9, "
              "ra, rb, rc, rd}\nINVARIANT\nTypeOK\n")

_SLOTC_GEOM = dict(chunk=64, queue_capacity=1024, fp_capacity=8192)


@pytest.fixture(scope="module")
def slotc_cfg(tmp_path_factory):
    return _write_model(tmp_path_factory.mktemp("slotc"), "SlotC",
                        _SLOTC, _SLOTC_CFG)


# ---------------------------------------------------------------------------
# bound-report soundness against the real reachable sets
# ---------------------------------------------------------------------------


def _assert_reachable_inside_bounds(model, rep):
    from jaxtlc.struct.codec import StructCodec
    from jaxtlc.struct.oracle import bfs

    assert rep.certified
    cdc = StructCodec(model.system.variables, rep.bounds)
    r = bfs(model.system, model.invariants, check_deadlock=False,
            collect_states=True)
    assert r.states, "oracle must enumerate the reachable set"
    for st in r.states:
        # every value of every reachable state is inside its certified
        # shape AND encodes under the narrowed codec (encode raises on
        # any value outside the claimed universes)
        for v, val in zip(model.system.variables, st):
            assert shape_leq(shape_of_value(val), rep.bounds[v]), \
                f"{v} = {val!r} escapes {rep.bounds[v]}"
        cdc.encode(st)
    return len(r.states)


def test_bound_soundness_twophase(twophase, twophase_bounds):
    n = _assert_reachable_inside_bounds(twophase, twophase_bounds)
    assert n == 56  # the full reachable set was actually checked


def test_bound_soundness_wide_narrowing_bites(wide_model, wide_bounds):
    """Soundness of a narrowing that BITES (0..127 widened down to the
    exact 0..3): the full 1024-state reachable lattice lies inside the
    certified bounds and encodes under the 1-word narrowed codec."""
    n = _assert_reachable_inside_bounds(wide_model, wide_bounds)
    assert n == 4 ** 5


def test_raftelection_certifies_and_narrows():
    """RaftElection certifies through the field-guard refinement
    (`term[n] < MaxTerm` constraining the dynamic EXCEPT's `@`) and
    narrows term 0..3 -> 0..2.  (Reachable-set enumeration needs the
    host oracle, which cannot expand its `UNCHANGED vars` form - the
    device-parity story for a biting narrowing is the slow
    RaftReplication test.)"""
    model = load("specs/RaftElection.toolbox/Model_1/MC.cfg")
    rep = analyze_bounds(model)
    assert rep.certified
    assert rep.narrowed_nbits < rep.baseline_nbits
    term = rep.bounds["term"]
    assert all(s == SInt(0, 2) for _f, s, _o in term.fields)


@pytest.mark.slow
def test_bound_soundness_raftreplication_and_device_parity():
    """The word-reducing case (40 -> 28 bits, 2 -> 1 packed words):
    reachable-set soundness plus full narrowed-vs-baseline device
    parity at Model_1 scale with the certificate active."""
    from jaxtlc.struct.cache import get_backend
    from jaxtlc.struct.engine import check_struct

    model = load("specs/RaftReplication.toolbox/Model_1/MC.cfg")
    rep = analyze_bounds(model)
    assert (rep.baseline_words, rep.narrowed_words) == (2, 1)
    _assert_reachable_inside_bounds(model, rep)
    assert get_backend(model, False, bounds=rep).cdc.n_words == 1
    r0 = check_struct(model, chunk=256, queue_capacity=1 << 13,
                      fp_capacity=1 << 15, check_deadlock=False,
                      obs_slots=16)
    r1 = check_struct(model, chunk=256, queue_capacity=1 << 13,
                      fp_capacity=1 << 15, check_deadlock=False,
                      obs_slots=16, bounds=rep)
    assert (r1.generated, r1.distinct, r1.depth) == (17431, 7279, 14)
    assert (r0.generated, r0.distinct, r0.depth) == (17431, 7279, 14)
    assert r1.action_generated == r0.action_generated
    assert r1.action_distinct == r0.action_distinct
    assert r1.violation == 0 and r1.cert_violated is False


@pytest.mark.skipif(
    not __import__("os").path.exists(
        "/root/reference/KubeAPI.toolbox/Model_1/MC.cfg"),
    reason="reference KubeAPI model not mounted",
)
@pytest.mark.slow
def test_bound_soundness_kubeapi_model1():
    import mc_expect

    model = load(mc_expect.REF_CFG)
    rep = analyze_bounds(model)
    _assert_reachable_inside_bounds(model, rep)


# ---------------------------------------------------------------------------
# narrowing precision + report contract
# ---------------------------------------------------------------------------


def test_guard_refined_narrowing_recovers_exact_ranges(wide_model,
                                                       wide_bounds):
    rep = wide_bounds
    assert rep.certified
    for v in "abcde":
        assert rep.bounds[v] == SInt(0, 3), rep.bounds[v]
        assert rep.baseline[v].hi > 3  # widening over-approximated
    # packed words STRICTLY reduced (the acceptance criterion)
    assert rep.narrowed_nbits < rep.baseline_nbits
    assert (rep.baseline_words, rep.narrowed_words) == (2, 1)
    assert rep.narrowed() and rep.digest()
    # the render contract: one line per variable + the header
    lines = rep.render_lines()
    assert lines[0].startswith("certified reachable bounds: ")
    assert len(lines) == 1 + len(rep.variables)
    # narrowing surfaces as an info finding; certification never warns
    checks = {(f.check, f.severity) for f in rep.findings()}
    assert checks == {("bound-narrowing", "info")}


def test_twophase_bounds_exact_no_narrowing(twophase_bounds):
    """TwoPhase's widened shapes are already exact (atoms + masks, no
    int widening): certified, no bit reduction, stable digest."""
    rep = twophase_bounds
    assert rep.certified
    assert rep.baseline_nbits == rep.narrowed_nbits == 17
    assert not rep.narrowed()
    assert rep.digest() == analyze_bounds(
        load("specs/TwoPhase.toolbox/Model_1/MC.cfg")
    ).digest()


def test_narrowed_engine_count_identical_with_certificate(wide_model,
                                                          wide_bounds):
    """The tier-1 parity gate: baseline vs narrowed engine on the
    word-reducing synthetic - generated/distinct/depth and per-action
    counts identical, certificate active and clean, traps elided."""
    from jaxtlc.struct.cache import get_backend
    from jaxtlc.struct.engine import check_struct

    geom = dict(chunk=64, queue_capacity=2048, fp_capacity=4096)
    r0 = check_struct(wide_model, check_deadlock=False, obs_slots=8,
                      **geom)
    r1 = check_struct(wide_model, check_deadlock=False, obs_slots=8,
                      bounds=wide_bounds, **geom)
    assert (r0.generated, r0.distinct, r0.depth) == (
        r1.generated, r1.distinct, r1.depth,
    )
    assert r1.distinct == 4 ** 5  # the full counter lattice
    assert r1.action_generated == r0.action_generated
    assert r1.action_distinct == r0.action_distinct
    assert r0.cert_violated is None  # baseline carries no certificate
    assert r1.cert_violated is False  # narrowed: active and clean
    # the narrowed compile proved + elided every range trap (the write
    # x' = x + 1 under x < 3 is in-range by the refined interval), and
    # moved one fewer packed word per state through the sort path
    b0 = get_backend(wide_model, False)
    b1 = get_backend(wide_model, False, bounds=wide_bounds)
    assert b0.cdc.n_words == 2 and b1.cdc.n_words == 1
    sites0, elided0 = b0.cdc.trap_stats
    sites1, elided1 = b1.cdc.trap_stats
    assert elided0 == 0 and sites1 == sites0
    assert elided1 == sites1 > 0
    assert b1.cert_check is not None and b0.cert_check is None


# ---------------------------------------------------------------------------
# seeded unsound bounds turn LOUD
# ---------------------------------------------------------------------------


def test_unsound_interval_bound_halts_on_kept_trap(wide_model,
                                                   wide_bounds):
    """An interval lie (claim a <= 1, reachable 3) cannot elide its
    own escape: the compiler re-derives the write range from the lie
    plus the guard, keeps the trap, and the run HALTS loudly instead
    of exploring a corrupted space."""
    from jaxtlc.engine.bfs import VIOL_SLOT_OVERFLOW
    from jaxtlc.struct.engine import check_struct

    lie = dataclasses.replace(
        wide_bounds, bounds={**wide_bounds.bounds, "a": SInt(0, 1)}
    )
    assert lie.certified  # the corrupted report still CLAIMS certified
    r = check_struct(wide_model, check_deadlock=False, obs_slots=8,
                     chunk=64, queue_capacity=2048, fp_capacity=4096,
                     bounds=lie)
    assert r.violation == VIOL_SLOT_OVERFLOW
    assert "certified-bound escape" in r.violation_name


def test_unsound_cardinality_bound_trips_certificate(slotc_cfg):
    """The cardinality lie is the narrowing with NO trap (slot lanes
    silently shrink): only the runtime certificate column can catch
    it - and through the full api.run_check path the verdict is a
    nonzero ERROR, never a silently-wrong count."""
    import jaxtlc.struct.cache as cache
    from jaxtlc.api import CheckRequest, run_check
    from jaxtlc.struct.engine import check_struct

    model = load(slotc_cfg)
    honest = analyze_bounds(model)
    assert honest.certified
    # the honest fixpoint cannot bound |msgs| below its universe (the
    # \\cup transfer is unguarded), so honest narrowing keeps 4 lanes
    assert honest.card_bounds["msgs"] == honest.card_universe["msgs"]
    lie = dataclasses.replace(
        honest, card_bounds={**honest.card_bounds, "msgs": 1}
    )
    r = check_struct(model, check_deadlock=False, obs_slots=8,
                     bounds=lie, **_SLOTC_GEOM)
    assert r.cert_violated is True

    # full front-door proof: run_check with the lying bound report
    # (same model/geometry - the engine memo makes this compile-free)
    real_get_bounds = cache.get_bounds
    cache.get_bounds = lambda m: lie
    try:
        out = io.StringIO()
        outcome = run_check(CheckRequest(
            config=slotc_cfg, workers="cpu", frontend="struct",
            narrow=True, nodeadlock=True, noTool=True,
            autogrow=False, obsslots=8, chunk=_SLOTC_GEOM["chunk"],
            qcap=_SLOTC_GEOM["queue_capacity"],
            fpcap=_SLOTC_GEOM["fp_capacity"], out=out, err=out,
        ))
    finally:
        cache.get_bounds = real_get_bounds
    assert outcome.exit_code == 1
    assert outcome.verdict == "error"
    assert "runtime certificate violation" in out.getvalue()


# ---------------------------------------------------------------------------
# sweep-class audit (the --sweep satellite)
# ---------------------------------------------------------------------------


_SWEEPT = """---- MODULE SweepT ----
EXTENDS Naturals
CONSTANTS MAX
VARIABLES x
Init == x = 0
Up == /\\ x < MAX
      /\\ x' = x + 1
Never == /\\ MAX > 2 /\\ x' = 0
Next == Up \\/ Never
InRange == x <= MAX
====
"""
_SWEEPT_CFG = "CONSTANT MAX = 1\nINVARIANT\nInRange\n"


def test_sweep_class_audit_covers_whole_range(tmp_path):
    """--sweep folds the swept constant's lo..hi into the bound
    environment: the class bound covers every configuration, and a
    guard FALSE only at the anchor no longer flags the action as
    unreachable for the class."""
    from jaxtlc.analysis.preflight import preflight_struct
    from jaxtlc.analysis.speclint import analyze_spec

    cfg = _write_model(tmp_path, "SweepT", _SWEEPT, _SWEEPT_CFG)
    model = load(cfg)

    # anchor-only view: x is 0..1 and Never (MAX > 2) is unreachable
    anchor = analyze_bounds(model)
    assert anchor.bounds["x"] == SInt(0, 1)
    sa = analyze_spec(model)
    assert [f.subject for f in sa.findings
            if f.check == "unreachable-action"] == ["Never"]

    # class view (MAX swept 1..3): the bound env covers x 0..3 and the
    # unreachable-action lint is silenced for the swept guard
    hints = {"MAX": SInt(1, 3)}
    systems = tuple(
        model.system.with_constants({**model.constants, "MAX": v})
        for v in (1, 2, 3)
    )
    rep = preflight_struct(
        model, fp_capacity=1 << 16, chunk=64, queue_capacity=1 << 10,
        const_hints=hints, extra_init_systems=systems,
    )
    assert any("x: int 0..3" in ln for ln in rep.bound_lines), \
        rep.bound_lines
    assert not [f for f in rep.findings
                if f.check == "unreachable-action"]


# ---------------------------------------------------------------------------
# the lint gate (tools/lintgate.py / python -m jaxtlc.analysis --gate)
# ---------------------------------------------------------------------------


def test_lintgate_specs_tree_clean():
    """The committed specs/ tree passes the engine-free gate (exit 0 -
    info/warning findings allowed, errors are not)."""
    from jaxtlc.analysis.gate import run_gate

    out = io.StringIO()
    rc = run_gate("specs", out=out)
    text = out.getvalue()
    assert rc == 0, text
    assert "lint gate: 13 spec(s)" in text
    # a model bounded by its cfg's CONSTRAINT passes with no finding
    assert "EWD998.toolbox/Model_1/MC.cfg: ok" in text
    # so does one whose state is FIFO channels of records (ISSUE 45)
    assert "LamportMutex.toolbox/Model_1/MC.cfg: ok" in text
    assert "0 new error(s)" in text
    # the hand-kernel model boundary ships without KubeAPI.tla: the
    # struct-frontend gate says so instead of failing or hiding it
    assert "KubeAPI.toolbox/Model_1/MC.cfg: SKIPPED" in text
    # the gate genuinely ran absint: the word-reducing RaftReplication
    # narrowing shows up as its info finding
    assert "40 to 28 bits" in text


def test_lintgate_fails_on_error_finding(monkeypatch, tmp_path):
    """An error-severity finding makes the gate exit nonzero; a
    baseline of known (check, subject) pairs is tolerated."""
    from jaxtlc.analysis import SEV_ERROR, Finding
    from jaxtlc.analysis import speclint
    from jaxtlc.analysis.gate import run_gate

    cfg = _write_model(tmp_path, "Wide", _WIDE, _WIDE_CFG)
    import os
    import shutil

    root = str(tmp_path / "tree")
    os.makedirs(os.path.join(root, "m"))
    shutil.copy(cfg, os.path.join(root, "m", "MC.cfg"))
    shutil.copy(os.path.join(os.path.dirname(cfg), "Wide.tla"),
                os.path.join(root, "m", "Wide.tla"))

    real = speclint.analyze_spec

    def seeded(model, **kw):
        sa = real(model, **kw)
        sa.findings.append(Finding(
            layer="spec", check="seeded-error", severity=SEV_ERROR,
            subject="X", detail="seeded",
        ))
        return sa

    monkeypatch.setattr(speclint, "analyze_spec", seeded)
    out = io.StringIO()
    assert run_gate(root, out=out) == 1
    assert "1 NEW error(s)" in out.getvalue()
    # the same finding in the committed baseline is tolerated
    out2 = io.StringIO()
    assert run_gate(root, out=out2,
                    baseline={("seeded-error", "X")}) == 0


def test_lintgate_tool_standalone(tmp_path):
    """tools/lintgate.py is importable and gates an arbitrary tree."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "lintgate", os.path.join("tools", "lintgate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = _write_model(tmp_path, "Wide", _WIDE, _WIDE_CFG)
    os.rename(cfg, os.path.join(os.path.dirname(cfg), "MC.cfg"))
    assert mod.main([str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# plumbing contracts
# ---------------------------------------------------------------------------


def test_narrowed_meta_and_cache_identity(twophase, twophase_bounds):
    """A narrowed run is a DIFFERENT cache/checkpoint identity: the
    engine-memo key and the checkpoint meta both carry the bound
    digest, and the memoized bound report is stable."""
    from jaxtlc.struct.backend import struct_meta_config
    from jaxtlc.struct.cache import engine_key, get_bounds

    b = get_bounds(twophase)
    assert get_bounds(twophase) is b  # memoized
    geom = dict(chunk=64, queue_capacity=512, fp_capacity=4096,
                fp_index=51, seed=7, fp_highwater=0.85)
    k0 = engine_key(twophase, **geom)
    k1 = engine_key(twophase, bounds=b, **geom)
    assert k0 != k1
    meta = struct_meta_config(twophase, bounds=b)
    assert meta["bound_digest"] == b.digest()
    assert "bound_digest" not in struct_meta_config(twophase)


def test_cert_violation_renders_loud_banner_once():
    """The level-event view escalates the sticky COL_CERT decode to an
    error banner, once per run."""
    from jaxtlc.obs.schema import SCHEMA_VERSION
    from jaxtlc.obs.views import render_tlc_event

    class Log:
        def __init__(self):
            self.msgs = []

        def msg(self, code, text, severity=0):
            self.msgs.append(text)

    log = Log()
    base = dict(v=SCHEMA_VERSION, t=0.0, event="level", level=1,
                generated=1, distinct=1, queue=0, bodies=1, expanded=1)
    render_tlc_event(log, base)
    assert log.msgs == []
    render_tlc_event(log, {**base, "cert_violation": True})
    render_tlc_event(log, {**base, "cert_violation": True})
    assert len(log.msgs) == 1
    assert "certificate violation" in log.msgs[0]
