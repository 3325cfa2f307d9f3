"""Engine-free lint gate over a specs tree (CI tooling, ISSUE 10).

Runs the spec-layer lints (speclint) plus the certified abstract
interpretation (absint) over every ``MC.cfg`` under a directory -
milliseconds per spec, no jax, no XLA - and fails (nonzero) on any
error-severity finding.  The committed ``specs/`` tree is gated in
tier-1 (tests/test_absint.py) so a spec edit that introduces an
error-class lint cannot land silently; ``tools/lintgate.py`` and
``python -m jaxtlc.analysis --gate`` run the same pass standalone.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional, Tuple

from . import SEV_ERROR, AnalysisReport, Finding, sorted_findings


def find_configs(root: str) -> List[str]:
    """Every MC.cfg under `root`, sorted for stable output."""
    out = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f == "MC.cfg":
                out.append(os.path.join(dirpath, f))
    return sorted(out)


def gate_one(cfg_path: str) -> Tuple[str, Optional[AnalysisReport], str]:
    """(spec label, report-or-None, skip reason).  Specs the struct
    frontend cannot load are SKIPPED, not failed - the gate audits what
    the struct IR can see; the other frontends have their own tests."""
    from ..struct.loader import StructLoadError, load
    from ..struct.parser import StructParseError
    from ..struct.shapes import ShapeError
    from .absint import analyze_bounds
    from .speclint import analyze_spec, constraint_report, inferred_shapes

    label = os.path.relpath(cfg_path)
    try:
        model = load(cfg_path)
        shapes = inferred_shapes(model)
        spec = analyze_spec(model, var_shapes=shapes)
        # a model bounded by its cfg's CONSTRAINT is not narrowed (the
        # certified bounds know nothing of the constraint): its section
        # is the constraint's own, leaf by leaf
        bounds = None if model.constraints else analyze_bounds(model)
        con_lines, con_findings = (
            constraint_report(model, var_shapes=shapes)
            if model.constraints else ([], []))
    except (StructLoadError, StructParseError, ShapeError,
            RecursionError, ValueError, OSError) as e:
        return label, None, f"{type(e).__name__}: {e}"
    rep = AnalysisReport(name=f"struct:{model.root_name}",
                         spec=spec,
                         findings=list(spec.findings))
    if bounds is not None:
        rep.bound_lines = bounds.render_lines()
        rep.extend(bounds.findings())
    rep.constraint_lines = con_lines
    rep.extend(con_findings)
    return label, rep, ""


def run_gate(root: str, out=None, baseline: Optional[set] = None) -> int:
    """Gate every spec under `root`.  Returns the exit code: nonzero
    iff a NEW error-severity finding appeared (a `baseline` set of
    (check, subject) pairs - the committed, known findings - is
    tolerated, so the gate flags regressions, not history)."""
    out = out or sys.stdout
    t0 = time.time()
    baseline = baseline or set()
    configs = find_configs(root)
    if not configs:
        out.write(f"lint gate: no MC.cfg under {root}\n")
        return 2
    new_errors: List[Tuple[str, Finding]] = []
    n_findings = 0
    for cfg in configs:
        label, rep, skip = gate_one(cfg)
        if rep is None:
            out.write(f"gate {label}: SKIPPED ({skip})\n")
            continue
        fs = sorted_findings(rep.findings)
        n_findings += len(fs)
        errs = [f for f in fs if f.severity == SEV_ERROR
                and (f.check, f.subject) not in baseline]
        new_errors.extend((label, f) for f in errs)
        status = "ok" if not fs else (
            f"{len(fs)} finding(s)"
            + (f", {len(errs)} NEW error(s)" if errs else "")
        )
        out.write(f"gate {label}: {status}\n")
        for f in fs:
            out.write(f"  [{f.severity}] {f.layer}/{f.check} "
                      f"{f.subject}: {f.detail}\n")
    out.write(
        f"lint gate: {len(configs)} spec(s), {n_findings} finding(s), "
        f"{len(new_errors)} new error(s), {time.time() - t0:.2f}s\n"
    )
    return 1 if new_errors else 0
