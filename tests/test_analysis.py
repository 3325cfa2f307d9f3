"""Preflight analysis plane (ISSUE 6 acceptance criteria).

- golden reports: the TwoPhase spec-layer report (read/write sets,
  independence pairs) and the KubeAPI Model_1 engine-layer report are
  pinned BYTE-FOR-BYTE with zero findings - report drift is a loud
  tier-1 failure, and both are produced by tracing only (no fresh
  engine compiles: the struct backend comes from the shared memo, the
  Model_1 audit never calls init concretely);
- seeded defects: a vacuous invariant, a statically-disabled action, a
  slot-over-budget binder, a saturating counter config, a host callback
  in a hot body and a donated-carry reuse are each flagged at their
  documented severity, with schema-valid `analysis` journal events;
  error severity exits nonzero;
- use-after-donate is loud on CPU: JAXTLC_DEBUG_DONATION poisons a
  donated carry after run/step so reuse raises immediately;
- the sticky counter-overflow ring column decodes as a
  `counter_overflow` warning;
- `python -m jaxtlc.analysis --self-check --tiny` audits every shipped
  engine factory, and the factory registry itself is pinned so a new
  engine path cannot ship unaudited.
"""

import io
import json

import numpy as np
import pytest

from jaxtlc.analysis import AnalysisReport, Finding, sorted_findings
from jaxtlc.analysis.engine_audit import (
    audit_counter_width,
    audit_donation,
    audit_engine,
    audit_purity,
    carry_shapes,
    describe_engine,
)
from jaxtlc.analysis.report import emit_to_journal, render_report
from jaxtlc.analysis.speclint import analyze_spec
from jaxtlc.obs.journal import RunJournal
from jaxtlc.obs.schema import validate_event
from jaxtlc.struct.loader import load

# ---------------------------------------------------------------------------
# shared fixtures (tier-1 budget: the struct backend memo is shared with
# every other struct test in the process; nothing here compiles XLA)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def twophase():
    return load("specs/TwoPhase.toolbox/Model_1/MC.cfg")


@pytest.fixture(scope="module")
def twophase_analysis(twophase):
    return analyze_spec(twophase)


def _write_model(tmp_path, name, module, cfg):
    d = tmp_path / name
    d.mkdir()
    (d / f"{name}.tla").write_text(module)
    (d / f"{name}.cfg").write_text(cfg)
    return str(d / f"{name}.cfg")


# ---------------------------------------------------------------------------
# golden reports (byte-for-byte)
# ---------------------------------------------------------------------------


TWOPHASE_GOLDEN = """\
preflight analysis: struct:TwoPhase
spec: TwoPhase  variables={msgs, rmState, tmPrepared, tmState}  \
codec_fields=4
actions (7):
  CallOff: reads={msgs, tmState} writes={msgs, tmState} branches=1
  Collect: reads={msgs, tmPrepared, tmState} writes={tmPrepared} \
branches=1
  Decide: reads={msgs, tmPrepared, tmState} writes={msgs, tmState} \
branches=1
  ObeyAbort: reads={msgs, rmState} writes={rmState} branches=1
  ObeyCommit: reads={msgs, rmState} writes={rmState} branches=1
  Renege: reads={rmState} writes={rmState} branches=1
  Vote: reads={msgs, rmState} writes={msgs, rmState} branches=1
invariants (3):
  Agreement: reads={rmState}
  CommitVoted: reads={tmPrepared, tmState}
  TypeOK: reads={msgs, rmState, tmPrepared, tmState}
independent action pairs (5):
  CallOff || Renege
  Collect || ObeyAbort
  Collect || ObeyCommit
  Collect || Renege
  Decide || Renege
findings: none
"""


def test_twophase_spec_report_golden(twophase_analysis):
    """The spec-layer report - per-action read/write sets, the
    independence pairs (the POR/invariant-inference groundwork) and
    ZERO findings - pinned byte-for-byte."""
    rep = AnalysisReport(name="struct:TwoPhase",
                         spec=twophase_analysis,
                         findings=list(twophase_analysis.findings))
    assert render_report(rep) == TWOPHASE_GOLDEN
    assert rep.exit_code == 0


MODEL1_GOLDEN = """\
preflight analysis: kubeapi:Model_1
engine layer:
  kubeapi-engine.run_fn: while+cond+sort+gather  lanes=10
findings: none
"""


def test_model1_engine_report_golden():
    """The Model_1 engine-layer report: donation, purity (jaxpr trace
    of the real run/step functions) and counter-width audits all come
    back clean, pinned byte-for-byte.  Tracing only: the engine is
    never compiled or run."""
    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.bfs import make_engine
    from jaxtlc.spec.kernel import lane_layout

    init_fn, run_fn, step_fn = make_engine(
        MODEL_1, chunk=64, queue_capacity=1 << 12,
        fp_capacity=1 << 20, donate=False,
    )
    carry = carry_shapes(init_fn)
    _, n_lanes = lane_layout(MODEL_1)
    rep = AnalysisReport(name="kubeapi:Model_1")
    rep.extend(audit_engine(
        "kubeapi-engine", init_fn, run_fn, step_fn,
        reuses_carry=False, fp_capacity=1 << 20, n_lanes=n_lanes,
        trace=True, carry=carry,
    ))
    rep.engine_lines.append(describe_engine(
        "kubeapi-engine.run_fn", run_fn, carry,
        extras=(f"lanes={n_lanes}",),
    ))
    assert render_report(rep) == MODEL1_GOLDEN
    assert rep.exit_code == 0


# NOTE: the struct engine's own audit (same factory, tiny geometry,
# zero findings) is covered by test_selfcheck_tiny_smoke below - the
# self-check builds and traces it through the same code path, so a
# standalone duplicate here would only spend tier-1 budget re-tracing.

# ---------------------------------------------------------------------------
# seeded defects, each at its documented severity
# ---------------------------------------------------------------------------


_VAC = """---- MODULE Vac ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Inc == /\\ x < 2 /\\ x' = x + 1
Stay == x' = x
Next == Inc \\/ Stay
Vacuous == 1 + 1 = 2
TypeOK == x \\in 0..2
====
"""


def test_seeded_vacuous_invariant(tmp_path):
    m = load(_write_model(tmp_path, "Vac", _VAC,
                          "INVARIANT\nVacuous\nTypeOK\n"))
    sa = analyze_spec(m)
    vac = [f for f in sa.findings if f.check == "invariant-vacuity"]
    assert [f.subject for f in vac] == ["Vacuous"]
    assert vac[0].severity == "warning"
    assert sa.invariant_reads["Vacuous"] == set()
    assert sa.invariant_reads["TypeOK"] == {"x"}


_DEAD = """---- MODULE Dead ----
EXTENDS Naturals
CONSTANTS FLAG
VARIABLES x
Init == x = 0
Go == /\\ x < 2 /\\ x' = x + 1
Never == /\\ FLAG /\\ x' = 0
Next == Go \\/ Never
TypeOK == x \\in 0..2
====
"""


def test_seeded_unreachable_action(tmp_path):
    """A guard that is statically FALSE under the cfg constant
    overrides (TLC's level-0 evaluation) makes the action unreachable -
    a named preflight warning, not a mystery zero in coverage."""
    m = load(_write_model(
        tmp_path, "Dead", _DEAD,
        "CONSTANT FLAG = FALSE\nINVARIANT\nTypeOK\n",
    ))
    sa = analyze_spec(m)
    dead = [f for f in sa.findings if f.check == "unreachable-action"]
    assert [f.subject for f in dead] == ["Never"]
    assert dead[0].severity == "warning"
    assert sa.actions["Never"].n_disabled == 1
    # flipping the constant clears the finding
    m2 = load(_write_model(
        tmp_path, "Dead2", _DEAD.replace("MODULE Dead", "MODULE Dead2"),
        "CONSTANT FLAG = TRUE\nINVARIANT\nTypeOK\n",
    ))
    assert not [f for f in analyze_spec(m2).findings
                if f.check == "unreachable-action"]


_SLOT = """---- MODULE Slot ----
EXTENDS Naturals, FiniteSets
CONSTANTS RM
VARIABLES msgs
Init == msgs = {}
SendA == \\E r \\in RM : msgs' = msgs \\cup {[kind |-> "a", from |-> r]}
SendB == \\E r \\in RM : msgs' = msgs \\cup {[kind |-> "b", from |-> r]}
Drop == \\E m \\in msgs : msgs' = msgs \\ {m}
Next == SendA \\/ SendB \\/ Drop
TypeOK == \\A m \\in msgs : m.from \\in RM
====
"""


def test_seeded_universe_lane_fan(tmp_path):
    """An action-position \\E over a state-dependent set whose element
    universe exceeds the unroll limit fans into universe lanes, one an
    element: named at preflight with the universe's size (the slot-lane
    form and its overflow class went in PR 31)."""
    m = load(_write_model(
        tmp_path, "Slot", _SLOT,
        "CONSTANT RM = {r1, r2, r3, r4, r5, r6, r7}\n"
        "INVARIANT\nTypeOK\n",
    ))
    sa = analyze_spec(m)
    slot = [f for f in sa.findings if f.check == "lane-fan"]
    assert [f.subject for f in slot] == ["Drop"]
    assert slot[0].severity == "info"
    assert sa.actions["Drop"].slot_binders == [("m", 14)]
    # constant-set binders (SendA/SendB over RM) never use slots
    assert sa.actions["SendA"].slot_binders == []


def test_seeded_counter_saturation():
    """ROADMAP #3 geometry: a billion-state fp table times the lane
    fan-out crosses 2^32 - flagged before a single device step."""
    assert audit_counter_width("m", fp_capacity=1 << 20,
                               n_lanes=12) == []
    f = audit_counter_width("m", fp_capacity=1 << 28, n_lanes=32)
    assert len(f) == 1 and f[0].check == "counter-width"
    assert f[0].severity == "warning"
    assert "sticky" in f[0].detail


def test_seeded_purity_violation():
    """A host callback inside a jitted hot body is an error finding."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def dirty(c):
        def body(x):
            jax.debug.print("x={x}", x=x)
            return x + 1

        return lax.while_loop(lambda x: x < 3, body, c)

    f = audit_purity("dirty-engine", jax.jit(dirty), jnp.int32(0))
    assert len(f) == 1
    assert (f[0].check, f[0].severity) == ("hot-body-purity", "error")
    assert "debug_print" in f[0].detail


def test_seeded_donation_reuse_is_error():
    """A donated carry fed twice (the supervisor-retry/profiler hazard)
    is an ERROR finding - checkable on CPU where the real failure
    cannot reproduce - and error severity exits nonzero."""

    class FakeFn:
        donate_requested = True
        donates_carry = False  # cpu: which is exactly the trap

    f = audit_donation("engine.run_fn", FakeFn(), reuses_carry=True)
    assert len(f) == 1
    assert (f[0].check, f[0].severity) == ("donation-reuse", "error")
    rep = AnalysisReport(name="x", findings=f)
    assert rep.exit_code != 0
    assert audit_donation("engine.run_fn", FakeFn(),
                          reuses_carry=False) == []


# ---------------------------------------------------------------------------
# journal pipeline
# ---------------------------------------------------------------------------


def test_analysis_events_schema_valid(twophase_analysis):
    """Every finding journals as a schema-valid `analysis` event plus
    one `analysis_summary` - validated by the same versioned schema
    the run journal enforces."""
    findings = list(twophase_analysis.findings) + [
        Finding("engine", "counter-width", "warning", "m", "d"),
        Finding("engine", "donation-reuse", "error", "e", "d"),
    ]
    rep = AnalysisReport(name="t", findings=findings, wall_s=0.123)
    j = RunJournal()  # in-memory
    emit_to_journal(j, rep)
    kinds = [e["event"] for e in j.events]
    assert kinds == ["analysis", "analysis", "analysis_summary"]
    for e in j.events:
        validate_event(e)
    assert j.events[0]["severity"] == "error"  # errors sort first
    summary = j.events[-1]
    assert (summary["errors"], summary["warnings"]) == (1, 1)


def test_preflight_gate_error_exits_nonzero(tmp_path):
    """The CLI gate: error-severity findings journal a final
    verdict=error event and abort with a nonzero code; warnings let
    the run proceed."""
    import argparse

    from jaxtlc.cli import _preflight_gate
    from jaxtlc.io.tlc_log import TLCLog

    path = str(tmp_path / "j.jsonl")
    j = RunJournal(path)
    args = argparse.Namespace(preflight=True, analyze=False,
                              _journal=j, traceout="")
    log = TLCLog(tool_mode=False)

    def bad_report(deep):
        return AnalysisReport(name="x", findings=[
            Finding("engine", "donation-reuse", "error", "e", "boom"),
        ])

    rc = _preflight_gate(args, log, bad_report)
    assert rc not in (None, 0)
    events = [json.loads(l) for l in open(path) if l.strip()]
    assert [e["event"] for e in events][-1] == "final"
    assert events[-1]["verdict"] == "error"

    args2 = argparse.Namespace(preflight=True, analyze=False,
                               _journal=None, traceout="")

    def warn_report(deep):
        return AnalysisReport(name="x", findings=[
            Finding("spec", "invariant-vacuity", "warning", "I", "d"),
        ])

    assert _preflight_gate(args2, log, warn_report) is None
    args3 = argparse.Namespace(preflight=False, analyze=False)
    assert _preflight_gate(args3, log, bad_report) is None  # escape


def test_cli_preflight_end_to_end(tmp_path, capsys):
    """The whole CLI pipe on a seeded vacuous invariant: the warning
    banner renders (derived from the journal event), the `analysis`
    events land schema-valid in the journal, the run still proceeds
    (warnings never abort), and -no-preflight silences all of it."""
    from jaxtlc.cli import main

    cfg = _write_model(tmp_path, "Vac", _VAC,
                       "INVARIANT\nVacuous\nTypeOK\n")
    jpath = str(tmp_path / "run.journal.jsonl")
    # -analyze = deep mode: the engine jaxpr purity trace rides along
    # (the struct backend comes from the same memo the run uses)
    rc = main(["check", cfg, "-noTool", "-frontend", "struct",
               "-analyze", "-chunk", "16", "-qcap", "64",
               "-fpcap", "1024", "-journal", jpath])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "Preflight warning [spec/invariant-vacuity] Vacuous" in out
    events = [json.loads(l) for l in open(jpath) if l.strip()]
    for e in events:
        validate_event(e)
    kinds = [e["event"] for e in events]
    assert "analysis" in kinds and "analysis_summary" in kinds
    assert kinds[-1] == "final" and events[-1]["verdict"] == "ok"
    an = [e for e in events if e["event"] == "analysis"]
    assert {(e["check"], e["severity"]) for e in an} == {
        ("invariant-vacuity", "warning")
    }
    # the escape hatch: -no-preflight emits nothing
    rc2 = main(["check", cfg, "-noTool", "-frontend", "struct",
                "-no-preflight", "-chunk", "16", "-qcap", "64",
                "-fpcap", "1024"])
    out2 = capsys.readouterr().out
    assert rc2 == 0
    assert "Preflight" not in out2


# ---------------------------------------------------------------------------
# use-after-donate is loud on CPU (JAXTLC_DEBUG_DONATION)
# ---------------------------------------------------------------------------


def test_debug_donation_poisons_reused_carry():
    """With the debug env on (tests/conftest.py), a donate=True jitted
    fn's input carry dies after the call: reuse raises immediately
    instead of corrupting a TPU run; chained fresh carries still work,
    and donate=False functions stay reusable."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.analysis.donation import (
        PoisoningFn,
        debug_donation_enabled,
        wrap_if_debugging,
    )

    assert debug_donation_enabled()  # conftest sets the env
    step = wrap_if_debugging(jax.jit(lambda c: c + 1), True)
    assert isinstance(step, PoisoningFn)
    c0 = jnp.arange(4)
    c1 = step(c0)
    with pytest.raises(RuntimeError, match="deleted"):
        step(c0)  # use-after-donate
    c2 = step(c1)  # fresh carry: fine
    assert int(c2[0]) == 2
    safe = wrap_if_debugging(jax.jit(lambda c: c + 1), False)
    assert not isinstance(safe, PoisoningFn)
    d0 = jnp.arange(4)
    safe(d0)
    safe(d0)  # donate=False: reuse is part of the contract


def test_engine_factory_applies_poisoning_and_tags():
    """make_backend_engine tags run/step with the donation metadata the
    audit reads, and wraps them in the poisoning debug mode iff
    donation was requested.  Factory-build only: nothing is traced,
    compiled or run."""
    from jaxtlc.analysis.donation import PoisoningFn
    from jaxtlc.config import ModelConfig
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.bfs import make_backend_engine

    b = kubeapi_backend(ModelConfig(False, False))
    _, run_fn, step_fn = make_backend_engine(
        b, chunk=16, queue_capacity=1 << 8, fp_capacity=1 << 10,
    )
    for fn in (run_fn, step_fn):
        assert isinstance(fn, PoisoningFn)
        assert fn.donate_requested is True
        assert fn.donates_carry is False  # cpu has no donation
    _, run2, step2 = make_backend_engine(
        b, chunk=16, queue_capacity=1 << 8, fp_capacity=1 << 10,
        donate=False,
    )
    for fn in (run2, step2):
        assert not isinstance(fn, PoisoningFn)
        assert fn.donate_requested is False


# ---------------------------------------------------------------------------
# sticky counter-overflow ring column
# ---------------------------------------------------------------------------


def test_ring_overflow_column_sticky_and_decoded():
    """The COL_OVERFLOW column: wrap detection feeds a sticky flag
    (once set, every later row carries it), and the decoder surfaces
    it as a `counter_overflow` warning key on the level event."""
    import jax.numpy as jnp

    from jaxtlc.obs.counters import (
        COL_OVERFLOW,
        pack_row,
        ring_new,
        ring_update,
        rows_from_ring,
        sticky_overflow,
        wrapped_any,
    )

    # wrap detection: a cumulative uint32 add past 2^32 goes backwards
    old = jnp.uint32(0xFFFFFFF0)
    new = old + jnp.uint32(0x20)  # wraps
    assert bool(wrapped_any([(new, old)]))
    assert not bool(wrapped_any([(old + jnp.uint32(1), old)]))

    ring, head = ring_new(4, 1)
    z = jnp.uint32(0)
    a = jnp.zeros(1, jnp.uint32)
    row0 = pack_row(jnp.int32(1), z + 5, z + 3, z, z + 1, z + 1, a, a,
                    overflow=sticky_overflow(ring, jnp.bool_(False)))
    ring, head = ring_update(ring, head, row0, jnp.bool_(True))
    assert int(ring[0, COL_OVERFLOW]) == 0
    # a wrap this body sets the flag...
    row1 = pack_row(jnp.int32(2), z + 9, z + 4, z, z + 2, z + 2, a, a,
                    overflow=sticky_overflow(ring, jnp.bool_(True)))
    ring, head = ring_update(ring, head, row1, jnp.bool_(True))
    # ...and stays sticky on later clean bodies
    row2 = pack_row(jnp.int32(3), z + 12, z + 5, z, z + 3, z + 3, a, a,
                    overflow=sticky_overflow(ring, jnp.bool_(False)))
    ring, head = ring_update(ring, head, row2, jnp.bool_(True))
    rows = rows_from_ring(np.asarray(ring), int(head))
    assert "counter_overflow" not in rows[0]
    assert rows[1]["counter_overflow"] is True
    assert rows[2]["counter_overflow"] is True


def test_counter_overflow_renders_warning_once():
    """The level-event view warns on the first flagged row only (the
    flag is sticky, the banner must not spam)."""
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
    render_tlc_event(log, {**base, "counter_overflow": True})
    render_tlc_event(log, {**base, "counter_overflow": True})
    assert len(log.msgs) == 1
    assert "saturated" in log.msgs[0]


# ---------------------------------------------------------------------------
# self-check: every shipped engine factory is audited
# ---------------------------------------------------------------------------


def test_selfcheck_registry_pinned():
    """The registry IS the definition of "shipped": a new engine path
    must register here (and thereby get audited) before it can ship."""
    from jaxtlc.analysis.selfcheck import FACTORIES

    assert sorted(FACTORIES) == [
        "covered", "covsharded", "deferred", "enumerator", "fused",
        "infer", "narrowed", "pipelined", "por", "sharded",
        "shardspill", "sim", "spill", "struct", "sweep", "symmetry",
    ]


def test_selfcheck_tiny_smoke():
    """`python -m jaxtlc.analysis --self-check --tiny` in-process:
    builds + traces + audits every factory, clean, exit 0."""
    from jaxtlc.analysis.__main__ import main

    buf = io.StringIO()
    import contextlib

    with contextlib.redirect_stdout(buf):
        rc = main(["--self-check", "--tiny"])
    out = buf.getvalue()
    assert rc == 0, out
    for name in ("fused", "pipelined", "sharded", "spill", "struct",
                 "narrowed", "enumerator", "sim"):
        assert f"audit {name}: ok" in out, out


def test_selfcheck_exits_nonzero_on_bad_factory(monkeypatch):
    """A factory with an audit error makes the self-check (and so the
    CI smoke) fail loudly."""
    import jax

    from jaxtlc.analysis import selfcheck

    def bad():
        def init_fn():
            import jax.numpy as jnp

            return jnp.int32(0)

        def body(c):
            jax.debug.print("c={c}", c=c)
            return c + 1

        run_fn = jax.jit(body)
        run_fn.donate_requested = True
        return dict(init_fn=init_fn, run_fn=run_fn,
                    reuses_carry=True, n_lanes=4,
                    fp_capacity=1 << 10)

    monkeypatch.setattr(selfcheck, "FACTORIES", {"bad": bad})
    from jaxtlc.analysis.__main__ import main

    buf = io.StringIO()
    import contextlib

    with contextlib.redirect_stdout(buf):
        rc = main(["--self-check", "--tiny"])
    assert rc != 0
    out = buf.getvalue()
    assert "donation-reuse" in out or "hot-body-purity" in out
