"""Preflight static-analysis plane (spec IR lints + engine jaxpr audits).

TLC front-loads whole classes of failures before the expensive search
starts (config/spec sanity checks, the level-0 evaluation pass -
PAPER.md §L4, §2.3); jaxtlc historically discovered its equivalents at
runtime, on device, mid-run.  This package is the preflight analog:

* **Spec layer** (`speclint`, over the struct frontend's IR - parsed
  ASTs + inferred shapes + codec layout): per-action read/write
  variable sets and the action independence graph, unreachable-action
  and invariant-vacuity lints, and a static codec-slot/trap budget
  audit (the RaftReplication "codec slot overflow" class becomes a
  named compile-time diagnostic instead of a device mystery).
* **Engine layer** (`engine_audit`, over jaxprs traced from the
  engine factories): a donation-safety audit (a donated run_fn/step_fn
  carry fed twice breaks only on TPU; the audit catches it on CPU), a
  hot-body purity audit (no host callbacks inside engine loop bodies),
  and a dtype-overflow audit for the uint32 cumulative counter ring.
* **Pipeline** (`report`, `__main__`): findings render as a TLC-style
  warnings banner, journal as schema-validated `analysis` events
  (obs/schema.py), and error severity exits nonzero.  `python -m
  jaxtlc.analysis MC.cfg` runs the suite standalone; `--self-check`
  audits every shipped engine factory.

Severities: ``error`` (the run would be wrong or die - preflight exits
nonzero), ``warning`` (the run proceeds but something will bite at
scale), ``info`` (report-only context).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

SEV_ERROR = "error"
SEV_WARNING = "warning"
SEV_INFO = "info"
_SEV_RANK = {SEV_INFO: 0, SEV_WARNING: 1, SEV_ERROR: 2}

# exit code of a preflight abort (TLC's EC convention reserves 10-13
# for spec-level verdicts; preflight failures are config/tooling errors)
EXIT_PREFLIGHT = 1


@dataclasses.dataclass(frozen=True)
class Finding:
    """One preflight diagnostic: which layer/check fired, on what, why."""

    layer: str  # "spec" | "engine"
    check: str  # kebab-case check id, e.g. "invariant-vacuity"
    severity: str  # SEV_ERROR | SEV_WARNING | SEV_INFO
    subject: str  # the action/invariant/engine/counter concerned
    detail: str  # one human-readable sentence

    def as_event(self) -> dict:
        """The journal `analysis` event payload (obs/schema.py)."""
        return dict(layer=self.layer, check=self.check,
                    severity=self.severity, subject=self.subject,
                    detail=self.detail)


@dataclasses.dataclass
class AnalysisReport:
    """The preflight result: findings + the report sections that back
    them (rendered byte-stably by `report.render_report`)."""

    name: str
    findings: List[Finding] = dataclasses.field(default_factory=list)
    spec: Optional[object] = None  # speclint.SpecAnalysis
    engine_lines: List[str] = dataclasses.field(default_factory=list)
    # certified-bound report section (absint.BoundReport.render_lines);
    # empty on reports that did not run the abstract interpretation, so
    # pre-existing golden reports render byte-identically
    bound_lines: List[str] = dataclasses.field(default_factory=list)
    # the cfg's CONSTRAINT, leaf by leaf (speclint.constraint_report);
    # empty for a model without one
    constraint_lines: List[str] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0

    def extend(self, findings) -> None:
        self.findings.extend(findings)

    @property
    def errors(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == SEV_ERROR)

    @property
    def warnings(self) -> Tuple[Finding, ...]:
        return tuple(f for f in self.findings
                     if f.severity == SEV_WARNING)

    @property
    def max_severity(self) -> Optional[str]:
        if not self.findings:
            return None
        return max((f.severity for f in self.findings),
                   key=_SEV_RANK.__getitem__)

    @property
    def exit_code(self) -> int:
        """Nonzero iff an error-severity finding survived."""
        return EXIT_PREFLIGHT if self.errors else 0


def sorted_findings(findings) -> List[Finding]:
    """Deterministic order: severity (errors first), layer, check,
    subject - the rendering and journaling order."""
    return sorted(
        findings,
        key=lambda f: (-_SEV_RANK[f.severity], f.layer, f.check,
                       f.subject),
    )


from .report import emit_to_journal, render_banner, render_report  # noqa: E402,F401
