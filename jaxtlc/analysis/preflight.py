"""Preflight orchestration: the suite the CLI runs before a check.

Lite mode (default, `-no-preflight` disables) costs milliseconds: the
spec-layer lints (struct specs - pure host Python over the IR) and the
static counter-width arithmetic.  Deep mode (`-analyze`) adds the
jaxpr purity trace of the engine the run is about to use - tracing
only, never an extra XLA compile (struct backends come from the same
memo the run uses, so even the Python lane-compile is shared).
"""

from __future__ import annotations

import time
from typing import Optional

from . import AnalysisReport
from .engine_audit import audit_counter_width, audit_engine


def preflight_struct(model, *, fp_capacity: int, chunk: int,
                     queue_capacity: int, check_deadlock: bool = True,
                     deep: bool = False,
                     backend=None, bounds=None, narrow: bool = False,
                     symmetry: bool = False,
                     const_hints=None,
                     extra_init_systems=()) -> AnalysisReport:
    """Struct-path preflight: spec lints + engine-layer arithmetic;
    deep mode traces the (memoized) struct engine's step.  `bounds`
    (absint.BoundReport - or True to compute one here) adds the
    certified-bound report section and its findings; `narrow` marks
    that the run intends to use the narrowed codec, which escalates an
    uncertified report to a visible warning; `symmetry` marks that the
    run already reduces by symmetry, which silences the unreduced-
    symmetry nudge.  `const_hints` / `extra_init_systems` widen the
    analysis over a sweep constants CLASS (jaxtlc.analysis --sweep)."""
    from .speclint import analyze_spec, inferred_shapes

    t0 = time.time()
    report = AnalysisReport(name=f"struct:{model.root_name}")
    dynamic = frozenset(const_hints or ())
    # inferred once: the lints and the constraint report both read them
    shapes = inferred_shapes(model, const_hints)
    spec = analyze_spec(model, var_shapes=shapes, dynamic_consts=dynamic,
                        const_hints=const_hints)
    report.spec = spec
    report.extend(spec.findings)
    if model.constraints:
        # the cfg's CONSTRAINT: its names, the bound it gives each
        # integer leaf, the leaves inference alone bounds, and an ERROR
        # (the run is refused before a build) for a leaf neither bounds
        from .speclint import constraint_report

        report.constraint_lines, refused = constraint_report(
            model, var_shapes=shapes)
        report.extend(refused)
    if not symmetry:
        # the spec qualifies for orbit dedup but the run is not taking
        # it: one warning per SYMMETRY-eligible constant set (ISSUE 18)
        from .symfind import unreduced_symmetry_findings

        report.extend(unreduced_symmetry_findings(model))
    if bounds is True or (bounds is None and (const_hints
                                              or extra_init_systems)):
        from .absint import analyze_bounds

        bounds = analyze_bounds(model, const_hints=const_hints,
                                extra_init_systems=extra_init_systems)
    if bounds is not None:
        report.bound_lines = bounds.render_lines()
        report.extend(bounds.findings())
        if narrow and not bounds.certified:
            # the -narrow request could not be honored; the run
            # proceeds on the baseline layout - say so loudly enough
            # that the user notices the flag did nothing
            from . import SEV_WARNING, Finding

            report.findings.append(Finding(
                layer="spec", check="narrow-refused",
                severity=SEV_WARNING, subject=model.root_name,
                detail=("-narrow requested but the bound report is "
                        "not certified; running with the baseline "
                        "(un-narrowed) codec"),
            ))
    n_lanes = None
    if backend is None and deep:
        from ..struct.cache import get_backend

        backend = get_backend(model, check_deadlock)
    if backend is not None:
        n_lanes = backend.n_lanes
    else:
        # lite bound without building the backend: every action branch
        # is at least one lane, action-position binders multiply - use
        # the branch count as the static lower bound
        n_lanes = sum(a.n_branches for a in spec.actions.values()) or 1
    report.extend(audit_counter_width(
        f"struct:{model.root_name}", fp_capacity, n_lanes
    ))
    if deep and backend is not None:
        from ..engine.bfs import make_backend_engine

        init_fn, run_fn, step_fn = make_backend_engine(
            backend, chunk=chunk, queue_capacity=queue_capacity,
            fp_capacity=fp_capacity, donate=False,
        )
        report.extend(audit_engine(
            "struct-engine", init_fn, run_fn, step_fn,
            reuses_carry=False, trace=True,
        ))
        from .engine_audit import carry_shapes, describe_engine

        report.engine_lines.append(describe_engine(
            "struct-engine.run_fn", run_fn, carry_shapes(init_fn),
            extras=(f"lanes={backend.n_lanes}",
                    f"labels={len(backend.labels)}"),
        ))
    report.wall_s = time.time() - t0
    return report


def preflight_kubeapi(cfg, *, fp_capacity: int, chunk: int,
                      queue_capacity: int,
                      deep: bool = False) -> AnalysisReport:
    """Hand-kernel (KubeAPI) preflight: the spec layer does not apply
    (no struct IR); the engine layer audits counter widths from the
    static lane layout, plus the traced engine in deep mode."""
    from ..spec.kernel import lane_layout

    t0 = time.time()
    _, n_lanes = lane_layout(cfg)
    report = AnalysisReport(name="kubeapi:Model")
    report.extend(audit_counter_width("kubeapi", fp_capacity, n_lanes))
    if deep:
        from ..engine.bfs import make_engine

        init_fn, run_fn, step_fn = make_engine(
            cfg, chunk=chunk, queue_capacity=queue_capacity,
            fp_capacity=fp_capacity, donate=False,
        )
        report.extend(audit_engine(
            "kubeapi-engine", init_fn, run_fn, step_fn,
            reuses_carry=False, trace=True,
        ))
        from .engine_audit import carry_shapes, describe_engine

        report.engine_lines.append(describe_engine(
            "kubeapi-engine.run_fn", run_fn, carry_shapes(init_fn),
            extras=(f"lanes={n_lanes}",),
        ))
    report.wall_s = time.time() - t0
    return report


def preflight_gen(genspec, *, fp_capacity: int,
                  deep: bool = False) -> AnalysisReport:
    """Generic-frontend preflight: counter-width arithmetic only (the
    gen IR predates the struct IR the spec lints read; its subset specs
    are small enough that the runtime traps cover the rest)."""
    t0 = time.time()
    report = AnalysisReport(name=f"gen:{getattr(genspec, 'name', '?')}")
    n_lanes = max(len(getattr(genspec, "actions", ())), 1)
    report.extend(audit_counter_width("gen", fp_capacity, n_lanes))
    report.wall_s = time.time() - t0
    return report
