"""Engine-as-a-library: the check-orchestration layer (ISSUE 9).

`run_check(CheckRequest) -> CheckOutcome` is the one entrypoint every
front door shares: the CLI (`python -m jaxtlc.cli check`, a thin
argparse shim now), the checking service (`jaxtlc.serve` - a long-lived
server submitting many jobs per process), and tests all orchestrate a
check through this module.  Until round 9 the CLI owned all of this
(cli.py at 1331 lines); a serving process cannot shell out to argparse,
so the orchestration moved here wholesale - frontend resolution,
preflight gating, engine dispatch (fused / sharded / hybrid / struct /
gen, supervised or raw), liveness, trace reconstruction, journal
lifecycle and the TLC log protocol.

The TLC transcript is written to `CheckRequest.out` (default: the
process stdout, which is what keeps the CLI's pinned transcripts
byte-identical); a server passes an io.StringIO per job and stores the
transcript as the job's output.  Exit-code conventions are unchanged
(0 ok / 12 safety / 13 liveness / 75 interrupted-or-exhausted /
1 usage+error); `CheckOutcome.verdict` is the same fact as a string.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import time
from typing import Optional, TextIO

from . import __version__
from .config import ModelConfig
from .engine.fingerprint import DEFAULT_SEED
from .frontend.model import RunSpec, resolve
from .io.tlc_log import TLCLog
from .obs import spans
from .obs.spans import span


@dataclasses.dataclass
class CheckRequest:
    """One check, fully specified - the library form of the CLI flag
    set (field names match the argparse dests on purpose: the CLI
    builds a request with `CheckRequest.from_args(args)` and every
    default below mirrors the flag default, so flag semantics are
    documented once, in cli.py)."""

    config: str
    workers: str = "tpu"
    frontend: str = "auto"
    fpset: str = "JaxFPSet"
    fp: Optional[int] = None
    sharded: int = 0
    chunk: int = 1024
    pipeline: bool = False
    # tri-state -deferred-inv/-no-deferred-inv (ISSUE 15): None = auto
    # (resolved against the chunk, engine.bfs.resolve_deferred) -
    # invariant/certificate evaluation on the fresh-insert claimants
    # at the commit stage instead of every chunk*L candidate lane.
    # The -simulate tier ignores it: every walker state is "fresh", so
    # the sim engines keep their immediate per-walker invariant path.
    deferredinv: Optional[bool] = None
    # tri-state -symmetry/-no-symmetry and -por/-no-por (ISSUE 18):
    # None = auto (resolve_symmetry/resolve_por - OFF: both reductions
    # legitimately shrink the state counts, so they are opt-in, not
    # auto-on perf modes).  -symmetry canonicalizes every successor to
    # its orbit representative over every constant set of model values
    # that analysis.symfind can verify symmetric (runtime orbit
    # certificate on single device); a set it rejects stays unreduced
    # and the transcript and the journal's `reduce` event say what it
    # was rejected for (a string literal naming an element, a constant
    # the spec reads whose value a permutation changes, a reachable
    # CHOOSE, the group past PERM_LIMIT).  A cfg's own `SYMMETRY <def>`
    # is the second way in and needs no flag: it resolves to the same
    # bool (struct.cache.wants_symmetry), reduces exactly the sets it
    # declares, and a declared set that fails verification is an
    # error.  -por prunes commutative interleavings of provably safe
    # actions.  Struct frontend only.
    symmetry: Optional[bool] = None
    por: Optional[bool] = None
    routefactor: float = 2.0
    qcap: int = 1 << 15
    fpcap: int = 1 << 20
    checkpoint: str = ""
    checkpointevery: int = 256
    recover: bool = False
    autogrow: bool = True
    spill: str = "auto"
    maxregrow: int = 8
    retry: int = 2
    faults: str = ""
    obs: bool = True
    obsslots: int = 256
    journal: str = ""
    serve: int = 0
    traceout: str = ""
    xprof: str = ""
    analyze: bool = False
    preflight: bool = True
    narrow: bool = False
    coverage: bool = False
    liveness: bool = False
    liveness_host: bool = False
    fairness: str = "wf_next"
    nodeadlock: bool = False
    noTool: bool = False
    traceExpressions: str = ""
    mutation: str = ""
    # incremental re-checking (struct.artifacts, ISSUE 13): the
    # content-addressed verdict + reachable-set cache.  artifactcache
    # overrides the store directory ("" = JAXTLC_ARTIFACT_CACHE or
    # ~/.cache/jaxtlc/artifacts); noartifactcache disables both tiers
    # for this run; recheck forces a cache BYPASS on read (the run
    # still refreshes the artifacts it produces)
    artifactcache: str = ""
    noartifactcache: bool = False
    recheck: bool = False
    # simulation tier (jaxtlc.sim, ISSUE 14): -simulate swaps the
    # exhaustive BFS for W vmapped random walks of depth N - the cheap
    # smoke-check job class.  Every walk lane is a pure function of
    # (simseed, lane), so violations replay host-side from the seed
    # alone (sim.replay); a clean sim verdict is a SMOKE verdict and
    # never publishes to the artifact-cache verdict tier
    simulate: bool = False
    depth: int = 100
    walkers: int = 256
    simseed: int = 0
    # invariant inference (jaxtlc.infer, ISSUE 16): -infer swaps
    # checking for the conjecture -> filter -> certify loop - a third
    # verdict class beside exhaustive and smoke.  Like sim it never
    # publishes to the artifact-cache verdict tier (its verdict is
    # about CANDIDATES, not the spec's stated invariants); unlike sim
    # it READS the reachable-set artifact as exact filter evidence
    infer: bool = False
    inferbudget: int = 64
    # -- library-only knobs (no CLI flag) -------------------------------
    # MC.cfg-style constant overrides applied on top of the config's
    # baked values (the serve path: a job's constants must shape the
    # checked configuration on EVERY route, supervised included)
    constants: dict = dataclasses.field(default_factory=dict)
    # programmatic drain request (ISSUE 17): a threading.Event the
    # caller sets to preempt THIS run at the next segment boundary -
    # the in-process twin of SIGTERM, riding the same checkpoint +
    # exit-75 machinery (resil.supervisor / sim.driver honor it).  The
    # serve scheduler's deadline/priority/cancel preemptions all route
    # through here, so preempting one job never signals the server
    drain: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # transcript / error sinks; None = the process stdout / stderr (the
    # CLI path - pinned transcripts depend on it)
    out: Optional[TextIO] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    err: Optional[TextIO] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @classmethod
    def from_args(cls, args) -> "CheckRequest":
        """Build a request from an argparse namespace (unknown request
        fields keep their defaults, extra namespace attrs are ignored -
        the two sides may evolve independently)."""
        kw = {}
        for f in dataclasses.fields(cls):
            if f.name in ("out", "err"):
                continue
            if hasattr(args, f.name):
                kw[f.name] = getattr(args, f.name)
        return cls(**kw)


VERDICT_BY_EXIT = {
    0: "ok",
    1: "error",
    12: "violation",
    13: "liveness_violation",
    75: "interrupted",
}


@dataclasses.dataclass
class CheckOutcome:
    """What a check did: the exit-code fact, its string form, the
    engine-level result (None when resolution/preflight failed before
    any engine ran), and where the run journal landed."""

    exit_code: int
    verdict: str
    result: object = None  # engine.bfs.CheckResult | None
    journal_path: str = ""


@spans.in_check
def run_check(req: CheckRequest) -> CheckOutcome:
    """Run one check end to end.  Everything the CLI `check` subcommand
    did - resolution, preflight, dispatch, liveness, traces, journal -
    against the request's sinks instead of the process streams."""
    rc = _run_check(req)
    return CheckOutcome(
        exit_code=rc,
        verdict=VERDICT_BY_EXIT.get(rc, f"exit_{rc}"),
        result=getattr(req, "_result", None),
        journal_path=getattr(req, "_journal_path", "") or "",
    )


def _err(args) -> TextIO:
    return getattr(args, "err", None) or sys.stderr


def _run_check(args) -> int:
    from .runtime import (
        PlatformError,
        enable_compile_cache,
        fp_mesh,
        require_platform,
    )

    with span("check.resolve"):
        try:
            require_platform(args.workers)
            if args.sharded and args.fpset != "DiskFPSet":
                fp_mesh(args.sharded)  # fewer devices than asked: an error
        except PlatformError as e:
            print(f"Error: {e}", file=_err(args))
            return 1
        enable_compile_cache()
        try:
            spec: RunSpec = resolve(
                args.config,
                workers=args.workers,
                fp_index=args.fp,
                check_deadlock=not args.nodeadlock,
                frontend=args.frontend,
                const_overrides=getattr(args, "constants", None) or None,
            )
        except (ValueError, OSError) as e:
            print(f"Error: {e}", file=_err(args))
            return 1
    from .frontend.model import GenRunSpec, StructRunSpec

    if getattr(args, "simulate", False) and not isinstance(
            spec, StructRunSpec):
        # the simulation tier rides the struct frontend (the host
        # interpreter renders its replayed traces); -frontend struct
        # runs ANY spec, so this is a spelling, not a capability, gap
        print("Error: -simulate requires the structural frontend "
              "(re-run with -frontend struct)", file=_err(args))
        return 1
    if getattr(args, "infer", False):
        if getattr(args, "simulate", False):
            print("Error: -infer and -simulate are distinct job "
                  "classes (pick one)", file=_err(args))
            return 1
        if not isinstance(spec, StructRunSpec):
            # inference conjectures over the struct IR's shapes; like
            # -simulate this is a spelling, not a capability, gap
            print("Error: -infer requires the structural frontend "
                  "(re-run with -frontend struct)", file=_err(args))
            return 1
    if isinstance(spec, GenRunSpec):
        return _run_check_gen(args, spec)
    if isinstance(spec, StructRunSpec):
        return _run_check_struct(args, spec)
    from .frontend.model import KNOWN_PROPERTIES

    unknown = [q for q in spec.properties if q not in KNOWN_PROPERTIES]
    if unknown:
        print(
            f"Error: unknown PROPERTY {', '.join(unknown)} "
            f"(supported: {', '.join(KNOWN_PROPERTIES)})",
            file=_err(args),
        )
        return 1
    if args.mutation:
        spec.model = dataclasses.replace(spec.model, mutation=args.mutation)
    if args.recover and not args.checkpoint:
        print("Error: -recover requires -checkpoint PATH", file=_err(args))
        return 1

    log = TLCLog(out=args.out, tool_mode=not args.noTool,
                 **_render_sources(args.config, spec.spec_name))
    import jax

    device = str(jax.devices()[0])
    log.version(__version__)
    log.banner(spec.fp_index, DEFAULT_SEED, jax.devices()[0].platform,
               device)
    log.sany(*_sany_inputs(args.config, spec.spec_name))
    log.starting()
    log.computing_init()

    _open_journal(
        args, workload=spec.spec_name,
        engine=("hybrid" if args.fpset == "DiskFPSet"
                else "sharded" if args.sharded else "single"),
        device=device,
        params=dict(chunk=args.chunk, queue_capacity=args.qcap,
                    fp_capacity=args.fpcap, sharded=args.sharded,
                    pipeline=args.pipeline,
                    deferred=_deferred(args),
                    obs_slots=_obs_slots(args)),
        # handed over whole, after the dict above: this frame lies
        # under every build, whose trace time on the chip's host moves
        # with the frame's size (PERF.md section 6, PR 24 and PR 27)
        model=spec.model,
    )

    def _kubeapi_preflight(deep):
        from .analysis.preflight import preflight_kubeapi

        return preflight_kubeapi(
            spec.model, fp_capacity=args.fpcap, chunk=args.chunk,
            queue_capacity=args.qcap, deep=deep,
        )

    rc = _preflight_gate(args, log, _kubeapi_preflight)
    if rc is not None:
        return rc
    t0 = time.time()
    from .resil import SlotOverflowError

    sup = None  # SupervisedResult when the resil supervisor ran
    try:
        with _xprof(args, log):
            r, sup = _dispatch_check(args, spec, log)
    except SlotOverflowError as e:
        log.msg(1000, f"Run stopped: {e}", severity=1)
        _finish_journal(args, log)
        return 1
    except FileNotFoundError as e:
        print(f"Error: {e}", file=_err(args))
        _finish_journal(args, log)
        return 1
    args._result = r
    log.init_done(2 ** spec.model.n_reconcilers)

    if sup is not None and sup.interrupted:
        # the interrupted banner (with the resume command) was already
        # emitted by the supervisor's event hook
        from .resil import EXIT_INTERRUPTED

        log.progress(r.depth, r.generated, r.distinct, r.queue_left)
        log.final_counts(r.generated, r.distinct, r.queue_left)
        _finish_journal(args, log, r=None, sup=sup)
        return EXIT_INTERRUPTED

    with span("check.verdict"):
        violated, liveness_violated = _render_verdict(args, spec, log, r,
                                                      t0)
    _report_scopes(args, log)
    _finish_journal(
        args, log, r=r, sup=sup,
        verdict="liveness_violation" if liveness_violated else None,
        wall_s=time.time() - t0,
    )
    if violated:
        return 12
    return 13 if liveness_violated else 0  # TLC liveness exit convention


def _render_verdict(args, spec, log, r, t0):
    """The KubeAPI path after the engine: temporal properties, the
    violation banner and trace or the success report, final counts.
    Returns (violated, liveness_violated)."""
    from .engine.bfs import (
        VIOL_ASSERT,
        VIOL_DEADLOCK,
        VIOL_ONLYONEVERSION,
        VIOL_TYPEOK,
    )

    violated = r.violation != 0
    liveness_violated = False
    if not violated and (args.liveness or spec.properties):
        from .live.check import check_properties_device, use_device_path
        from .spec.codec import get_codec
        from .spec.pretty import state_to_tla

        props = spec.properties or ["ReconcileCompletes", "CleansUpProperly"]
        device_path = use_device_path(
            r.distinct, args.fairness, args.liveness_host
        )
        log.checking_temporal(
            r.distinct, "device" if device_path else "host"
        )
        if device_path:
            mesh = None
            if args.sharded:
                from .runtime import fp_mesh

                mesh = fp_mesh(args.sharded)
            results = check_properties_device(
                spec.model, props, chunk=args.chunk,
                state_capacity=args.fpcap, fp_capacity=args.fpcap,
                mesh=mesh,
                spill_path=args.checkpoint or None,
            )
        else:
            from .engine.liveness import build_graph, check_properties

            graph = build_graph(spec.model, chunk=args.chunk)
            results = check_properties(
                spec.model, props, graph=graph,
                fairness=args.fairness,
            )
        decode = get_codec(spec.model).decode
        for res in results:
            if res.holds:
                log.msg(1000, f"Temporal property {res.name} holds "
                              f"(fairness: {args.fairness}).")
                continue
            liveness_violated = True
            log.msg(2116, f"Temporal properties were violated: {res.name} "
                          f"(fairness: {args.fairness})", severity=1)
            idx = 1
            for enc, act in zip(res.prefix, res.prefix_actions):
                log.trace_state(idx, act, state_to_tla(decode(enc), spec.model))
                idx += 1
            log.msg(1000, "-- The following states form a cycle "
                          "(back to the first of them) --")
            for enc, act in zip(res.cycle, res.cycle_actions):
                log.trace_state(idx, act, state_to_tla(decode(enc), spec.model))
                idx += 1
    if violated:
        if r.violation == VIOL_TYPEOK and "TypeOK" in spec.invariants:
            log.invariant_violated("TypeOK")
        elif r.violation == VIOL_ONLYONEVERSION and (
            "OnlyOneVersion" in spec.invariants
        ):
            log.invariant_violated("OnlyOneVersion")
        elif r.violation == VIOL_ASSERT:
            log.assertion_failed("Failure of PlusCal assertion.")
        elif r.violation == VIOL_DEADLOCK and spec.check_deadlock:
            log.deadlock()
        else:
            log.msg(1000, f"Run stopped: {r.violation_name}", severity=1)
        _print_trace(log, spec.model, args.chunk,
                     trace_expr_file=args.traceExpressions,
                     check_deadlock=spec.check_deadlock)
    elif not liveness_violated:
        log.success(r.generated, r.distinct,
                    getattr(r, "actual_fp_collision", None),
                    occupancy=getattr(r, "fp_occupancy", None))
        if args.coverage:
            # full per-expression dump (MC.out:44-1092): re-walk the space
            # with the instrumented evaluator (host-side; slow for large
            # configs - TLC's coverage mode pays a similar tax)
            from .spec.coverage import render_coverage, run_coverage

            cov = run_coverage(spec.model)
            stamp = time.strftime("%Y-%m-%d %H:%M:%S")
            for line in render_coverage(cov, stamp, tool_mode=log.tool):
                log.raw(line)
        else:
            log.coverage(2, r.action_generated, r.action_distinct)

    log.progress(r.depth, r.generated, r.distinct, r.queue_left)
    log.final_counts(r.generated, r.distinct, r.queue_left)
    log.depth(r.depth)
    if r.outdegree is not None:
        log.outdegree(*r.outdegree)
    log.finished(int((time.time() - t0) * 1000))
    return violated, liveness_violated


@contextlib.contextmanager
def _xprof(args, log):
    """`-xprof DIR`: the check under jax.profiler (the ground-truth
    device timeline; the journal's -trace-out is the cheap host view),
    and the trace finished when the profiler has stopped.  On a TPU the
    engine's `jaxtlc.*` scopes are not in the trace, only in the loaded
    executables, so (obs.scopes): the instruction -> scope tables of the
    engines this process holds go beside the trace
    (`DIR/jaxtlc_scopes.json`: the join survives the process), the trace
    is reduced by scope into ONE `device_scopes` journal event, and the
    table is kept for the verdict to print (`_report_scopes`).  The
    table is optional reporting and the verdict is not: whatever fails
    after the profiler has stopped (a full disk under the sidecar, a
    trace the reader cannot parse) is a warning, and the check goes on
    to its verdict without the table.  A no-op when the flag is off; a
    check that raises stops the profiler and reduces nothing."""
    if not args.xprof:
        yield
        return
    import jax

    from .obs import scopes

    t0 = time.time()
    with jax.profiler.trace(args.xprof):
        yield
    t1 = time.time()
    try:
        sidecar = scopes.write_sidecar(args.xprof)
        t2 = time.time()
        reduced = scopes.reduce_dir(args.xprof, scopes.tables())
        reduced.update(sidecar=sidecar, tables_s=round(t2 - t1, 6),
                       reduce_s=round(time.time() - t2, 6))
        j = getattr(args, "_journal", None)
        if j is not None:
            j.event("device_scopes", t0=t0, t1=t1, **reduced)
    except Exception as e:  # noqa: BLE001 - must not fail the verdict
        log.msg(1000, "Warning: no device scope table for the trace in "
                f"{args.xprof}: {type(e).__name__}: {e}", severity=1)
        return
    args._device_scopes = reduced


def _report_scopes(args, log) -> None:
    """Under the verdict of an `-xprof` run: the device's time by
    scope."""
    reduced = getattr(args, "_device_scopes", None)
    if reduced is not None:
        from .obs.scopes import render

        log.msg(1000, "\n".join(render(reduced)))


def _dispatch_check(args, spec, log):
    """Run the KubeAPI-path engine picked by the flags.  Returns
    (CheckResult, SupervisedResult-or-None).

    Dispatch priority: DiskFPSet routes to the host tier even when
    -sharded is given (sharding then means fingerprint-space partitions).
    The resil supervisor wraps the device engines whenever -auto-grow
    (default) or -checkpoint is in play; -no-auto-grow without
    -checkpoint keeps the raw fused single-dispatch path."""
    if args.sharded and args.fpset != "DiskFPSet":
        from .engine.sharded import check_sharded
        from .runtime import fp_mesh

        mesh = fp_mesh(args.sharded)
        if args.checkpoint or args.autogrow:
            from .resil import check_sharded_supervised

            sup = check_sharded_supervised(
                spec.model,
                mesh,
                chunk=args.chunk,
                queue_capacity=args.qcap,
                fp_capacity=args.fpcap,
                route_factor=args.routefactor,
                pipeline=args.pipeline,
                obs_slots=_obs_slots(args),
                coverage=args.coverage,
                deferred=args.deferredinv,
                opts=_sup_opts(args, log),
            )
            return sup.result, sup
        from .engine.backend import kubeapi_backend

        return check_sharded(
            spec.model,
            mesh,
            chunk=args.chunk,
            queue_capacity=args.qcap,
            fp_capacity=args.fpcap,
            route_factor=args.routefactor,
            backend=kubeapi_backend(spec.model,
                                    coverage=args.coverage),
            pipeline=args.pipeline,
            obs_slots=_obs_slots(args),
            deferred=args.deferredinv,
        ), None
    if args.fpset == "DiskFPSet":
        # the OffHeapDiskFPSet/DiskStateQueue analog: authoritative dedup +
        # frontier in the native (C++, disk-bounded) host tier.  Composes
        # with -checkpoint (the disk tier's files ARE the snapshot, as in
        # TLC) and with -sharded N (N fingerprint-space partitions - the
        # distributed-fingerprint-server analog, launch:4)
        from .engine.hybrid import check_hybrid

        nparts = max(args.sharded, 1)
        if nparts & (nparts - 1):
            raise FileNotFoundError(
                "-sharded with -fpset DiskFPSet needs a power-of-two "
                f"partition count, got {nparts}"
            )
        return check_hybrid(
            spec.model,
            chunk=args.chunk,
            fp_index=spec.fp_index,
            fp_partitions=nparts,
            ckpt_path=args.checkpoint or None,
            ckpt_every=args.checkpointevery,
            resume=args.recover,
        ), None
    if args.checkpoint or args.autogrow:
        from .resil import check_supervised

        sup = check_supervised(
            spec.model,
            chunk=args.chunk,
            queue_capacity=args.qcap,
            fp_capacity=args.fpcap,
            fp_index=spec.fp_index,
            pipeline=args.pipeline,
            obs_slots=_obs_slots(args),
            coverage=args.coverage,
            deferred=args.deferredinv,
            opts=_sup_opts(args, log),
        )
        return sup.result, sup
    from .engine.bfs import check

    return check(
        spec.model,
        chunk=args.chunk,
        queue_capacity=args.qcap,
        fp_capacity=args.fpcap,
        fp_index=spec.fp_index,
        pipeline=args.pipeline,
        obs_slots=_obs_slots(args),
        coverage=args.coverage,
        deferred=args.deferredinv,
    ), None


def _preflight_gate(args, log, build_report):
    """Run the preflight suite before a check (ISSUE 6 pipeline).

    -no-preflight skips entirely; -analyze runs the deep mode (adds
    the engine jaxpr purity trace - tracing only, no XLA compile).
    Findings journal as schema-validated `analysis` events and render
    as TLC-style warning banners (derived views of the same events, so
    they cannot disagree); a clean preflight is silent.  Returns the
    nonzero exit code on error-severity findings, None to proceed."""
    if not args.preflight:
        return None
    with span("check.preflight"):
        return _preflight(args, log, build_report)


def _preflight(args, log, build_report):
    from .analysis.report import emit_to_journal
    from .obs.views import render_tlc_event

    journal = getattr(args, "_journal", None)
    try:
        report = build_report(args.analyze)
    except Exception as e:
        if not args.analyze:
            # a broken default lint must never block a run
            log.msg(1000, f"Preflight analysis skipped: {e}", severity=1)
            return None
        # the deep audit was asked for by name: a crash inside it is a
        # failed run, not a skipped check
        if journal is not None:
            journal.event("final", verdict="error", generated=0,
                          distinct=0, depth=0, queue=0, wall_s=0.0,
                          interrupted=False)
        log.msg(1000, f"Preflight analysis (-analyze) crashed: "
                      f"{type(e).__name__}: {e}; run aborted "
                      "(-no-preflight to override).", severity=1)
        _finish_journal(args, log)
        return 1

    def on_event(kind, info):
        import time as _time

        from .obs.schema import SCHEMA_VERSION

        render_tlc_event(log, {"v": SCHEMA_VERSION, "t": _time.time(),
                               "event": kind, **info})

    emit_to_journal(journal, report, on_event=on_event)
    if report.errors:
        if journal is not None:
            journal.event("final", verdict="error", generated=0,
                          distinct=0, depth=0, queue=0, wall_s=0.0,
                          interrupted=False)
        log.msg(1000, "Preflight analysis found error-severity "
                      "findings; run aborted (-no-preflight to "
                      "override).", severity=1)
        _finish_journal(args, log)
        return report.exit_code
    return None


def _sup_opts(args, log, capture_fps: bool = False, finish=None):
    """SupervisorOptions from the request.  Every supervisor event is
    written to the run journal FIRST (the single source of truth), then
    the TLC-style banner is rendered as a derived view of that journal
    event (obs.views.render_tlc_event) - the 2200 Progress line and the
    checkpoint/recovery/regrow banners cannot drift from what the
    journal records."""
    from .obs.views import render_tlc_event
    from .resil import FaultPlan, SupervisorOptions

    journal = getattr(args, "_journal", None)
    resume_cmd = _resume_command(args)

    def on_event(kind, info):
        if journal is not None:
            ev = journal.event(kind, **info)
        else:
            import time as _time

            from .obs.schema import SCHEMA_VERSION

            ev = {"v": SCHEMA_VERSION, "t": _time.time(),
                  "event": kind, **info}
        render_tlc_event(log, ev, resume_cmd=resume_cmd)

    return SupervisorOptions(
        auto_grow=args.autogrow,
        max_regrow=args.maxregrow,
        retries=args.retry,
        ckpt_path=args.checkpoint or None,
        ckpt_every=args.checkpointevery,
        resume=args.recover,
        spill=args.spill,
        faults=FaultPlan.parse(args.faults) if args.faults else None,
        capture_fps=capture_fps,
        on_event=on_event,
        drain=getattr(args, "drain", None),
        finish=finish,
    )


def _obs_slots(args) -> int:
    """Counter-ring depth in effect: -no-obs disables the device tier
    entirely (the A/B baseline; also the shape pre-obs checkpoints
    expect), otherwise -obs-slots levels of history ride the carry."""
    return args.obsslots if args.obs else 0


def _deferred(args) -> bool:
    """The RESOLVED -deferred-inv mode this run's engines will use
    (journal manifests record the fact, not the tri-state; the same
    resolve the engine factories / memos / checkpoint meta compute)."""
    from .engine.bfs import resolve_deferred

    return resolve_deferred(getattr(args, "deferredinv", None),
                            args.chunk)


def _symmetry(args) -> bool:
    """The RESOLVED -symmetry mode this run's engines will use (journal
    manifests record the fact, not the tri-state): the flag's, or on
    where the struct model's cfg declares SYMMETRY
    (`_resolve_struct_symmetry` has then left its mark on `args`)."""
    from .engine.bfs import resolve_symmetry

    if getattr(args, "_cfg_symmetry", False):
        return True
    return resolve_symmetry(getattr(args, "symmetry", None), args.chunk)


def _resolve_struct_symmetry(args, spec, sm, bounds):
    """Settle a struct run's symmetry before anything is built on it:
    the cfg's declaration or the flag (struct.cache.wants_symmetry),
    the sets' verification and the plan (the reduced backend's build:
    the memo's first look-up of the run).  Leaves on `args` the
    resolved mode and the reduction itself (`_reduce_ops`: what
    `run_start` says of it, and the sets `-symmetry` could not take,
    each with its reason).  An error text where the run may not start:
    a declared set that fails, or -no-symmetry against a cfg that
    declares one."""
    from .analysis.symfind import SymmetryError
    from .struct.cache import get_backend, wants_symmetry

    try:
        sym = wants_symmetry(sm, getattr(args, "symmetry", None),
                             args.chunk)
        args._cfg_symmetry = bool(sm.symmetry)
        if not sym:
            return None
        red = get_backend(
            sm, spec.check_deadlock, bounds=bounds,
            elide=not args.sharded, coverage=args.coverage,
            symmetry=True, por=_por(args),
        ).reduce
    except SymmetryError as e:
        return str(e)
    args._reduce_ops = red
    return None


def _constraint_refusal(args, spec, sm):
    """A model whose cfg declares CONSTRAINT, or a PROPERTY that is an
    action property `I /\\ [][A]_v`, runs on the single-device
    exhaustive engine, whose expand stage applies the one and judges
    the other (engine.backend.make_expand_stage), and nowhere else:
    every other route is refused here BY NAME, before anything is built
    - none ever runs the model unconstrained, or with its edges
    unjudged.  (The engines refuse again where they are built:
    engine.backend.require_unconstrained.)"""
    from .engine.backend import seam_only

    what = seam_only(tuple(sm.constraints), tuple(sm.action_props))
    if not what:
        return None
    con = bool(sm.constraints)
    routes = [flag for flag, on in (
        ("-sharded (the mesh engine's own expand half neither parts "
         "kept from counted nor judges an edge)", args.sharded),
        ("-simulate (a random walk has no constrained frontier, and "
         "walks a sample of the edges)", getattr(args, "simulate", False)),
        ("-infer (the evidence walks and the induction step are "
         "unconstrained, and judge no edge)", getattr(args, "infer", False)),
        ("-liveness / the cfg's temporal PROPERTY lines (the behavior "
         "graph is captured unconstrained, by an engine of its own)",
         args.liveness or spec.properties),
        ("-narrow (the certified bounds know nothing of the "
         "constraint, nor of the property's reads)", args.narrow),
        ("-symmetry / the cfg's SYMMETRY (neither the constraint nor "
         "the property is verified symmetric)",
         getattr(args, "symmetry", None) or sm.symmetry),
        ("-por (the ample sets ignore the constraint, and prune edges "
         "the property has to see)", getattr(args, "por", None)),
        # a failing edge is reported beside its source: the deferred
        # checker sees fresh states, not edges; and the two together
        # have no defined order of judgement here
        ("CONSTRAINT together with an action property (which edges "
         "are judged is not settled here)", con and sm.action_props),
    ) if on]
    if not routes:
        return None
    return (f"the cfg declares {what}, which is not honoured by "
            + "; ".join(routes)
            + ": such a model runs on the single-device exhaustive "
            "engine only")


def _action_property_events(args, sm, r, n_init: int):
    """The verdict of the model's action properties (sm.action_props),
    which the safety search itself judged (engine.backend.
    make_expand_stage, on every edge it generated): where the search
    ran to its end without a violation, ONE `action_property` journal
    event a property, before `final` - `holds`, the route, the edges
    judged, those on which the subscript changed, the initial states I
    was judged on.  Returns `r` with `action_prop_init_states`."""
    if not sm.action_props or r.action_prop_edges is None \
            or r.action_prop_init_states is not None:
        # none, a result the engine did not make, or said already (a
        # supervised route runs this before its `final` event, and the
        # runner calls it again)
        return r
    r = r._replace(action_prop_init_states=int(n_init))
    j = getattr(args, "_journal", None)
    if j is None or r.violation != 0 or r.queue_left:
        return r
    for prop in sm.action_props.values():
        j.event("action_property", property=prop.name, holds=True,
                route="device", formula=prop.text,
                edges=r.action_prop_edges, moved=r.action_prop_moved,
                init_states=int(n_init),
                src_cols=r.action_prop_src_cols)
    return r


def _por(args) -> bool:
    """The RESOLVED -por mode this run's engines will use."""
    from .engine.bfs import resolve_por

    return resolve_por(getattr(args, "por", None), args.chunk)


def _open_journal(args, workload: str, engine: str, device: str,
                  params: dict, model: ModelConfig = None):
    """Create the run journal and stamp the manifest (`model`: the hand
    frontend's process set and fault constants as resolved from MC.cfg,
    MC.tla and CheckRequest.constants, recorded under params).

    Path resolution: -journal PATH wins; else a -checkpoint run
    journals beside its snapshots (PATH.journal.jsonl) so preemption
    and -recover find it; else the journal is in-memory only (still
    powers -trace-out).  A -recover run APPENDS and stamps run_resume:
    one continuous journal per logical run, not one per attempt."""
    from . import __version__ as _v
    from .obs.journal import RunJournal

    path = args.journal or (
        args.checkpoint + ".journal.jsonl" if args.checkpoint else ""
    )
    if not path and args.serve:
        # the monitor serves journal FILES; an unjournaled -serve run
        # gets one beside the temp dir (printed below via the server)
        import tempfile

        path = os.path.join(
            tempfile.gettempdir(),
            f"jaxtlc-{os.getpid()}.journal.jsonl",
        )
    if model is not None:
        params = dict(params, model=dict(
            n_reconcilers=model.n_reconcilers,
            n_binders=model.n_clients - model.n_reconcilers,
            requests_can_fail=model.requests_can_fail,
            requests_can_timeout=model.requests_can_timeout,
            clients=list(model.clients)))
    resume = bool(args.recover and path and os.path.exists(path))
    j = RunJournal(path or None, resume=resume)
    if resume:
        j.event("run_resume", version=_v, path=path)
    else:
        j.event("run_start", version=_v, workload=workload,
                engine=engine, device=device, params=params)
    args._journal = j
    args._journal_path = path or ""
    if args.serve:
        # live ops plane: /metrics + /events (SSE) + /runs over this
        # run's journal directory for the run's whole lifetime
        from .obs.serve import start_server

        args._server = start_server(
            os.path.dirname(os.path.abspath(path)) or ".",
            port=args.serve,
        )
        print(f"jaxtlc monitor at {args._server.url} "
              "(/runs /metrics /events /journal)", file=_err(args))
    return j


def _finish_journal(args, log, r=None, sup=None, verdict: str = None,
                    wall_s: float = 0.0) -> None:
    """Close out the journal: the final event (when the supervisor did
    not already emit one), the violation record, and the -trace-out
    export (reading the WHOLE journal file so a resumed run's trace
    covers both attempts)."""
    j = getattr(args, "_journal", None)
    if j is None:
        return
    with span("check.journal_close") as s:
        try:
            _close_journal(args, log, j, r, sup, verdict, wall_s)
        finally:
            s.attrs.update(j.cost())


def _close_journal(args, log, j, r, sup, verdict, wall_s) -> None:
    try:
        if r is not None and r.violation != 0:
            j.event("violation", code=int(r.violation),
                    name=r.violation_name)
        if verdict == "liveness_violation":
            j.event("violation", code=13,
                    name="Temporal properties were violated")
        if sup is None and r is not None:
            v = verdict or ("violation" if r.violation != 0 else "ok")
            from .engine.bfs import mesh_counters

            j.event("spans", **spans.journal_event())
            j.event("final", verdict=v, generated=r.generated,
                    distinct=r.distinct, depth=r.depth,
                    queue=r.queue_left, wall_s=round(wall_s, 6),
                    interrupted=False, **mesh_counters(r))
        if args.traceout:
            from .obs.journal import read as read_journal
            from .obs.trace import export_chrome_trace

            events = read_journal(j.path, validate=False) if j.path \
                else j.events
            n = export_chrome_trace(events, args.traceout)
            j.event("trace_export", path=args.traceout, events=n)
            log.msg(1000, f"Timeline trace written to {args.traceout} "
                          f"({n} events; open in ui.perfetto.dev).")
    finally:
        j.close()
        args._journal = None
        server = getattr(args, "_server", None)
        if server is not None:
            server.shutdown()
            args._server = None


def _resume_command(args) -> str:
    """The command an interrupted run prints (geometry travels inside the
    checkpoint meta, so only the run-shaping flags need repeating)."""
    parts = ["python -m jaxtlc.cli check", args.config]
    if args.checkpoint:
        parts += ["-checkpoint", args.checkpoint, "-recover"]
    if args.chunk != 1024:
        parts += ["-chunk", str(args.chunk)]
    if args.sharded:
        parts += ["-sharded", str(args.sharded)]
    if args.pipeline:
        parts += ["-pipeline"]  # checkpoints only resume in the same mode
    if getattr(args, "deferredinv", None) is not None:
        # auto re-resolves identically from the chunk; only an explicit
        # override must travel
        parts += ["-deferred-inv" if args.deferredinv
                  else "-no-deferred-inv"]
    if getattr(args, "symmetry", None) is not None:
        # same contract: a reduced frontier is a different exploration,
        # the resume must repeat the mode or the meta check rejects it
        parts += ["-symmetry" if args.symmetry else "-no-symmetry"]
    if getattr(args, "por", None) is not None:
        parts += ["-por" if args.por else "-no-por"]
    if getattr(args, "narrow", False):
        parts += ["-narrow"]  # the narrowed codec is a different layout
    if getattr(args, "coverage", False):
        parts += ["-coverage"]  # the covered carry is a different layout
    if getattr(args, "simulate", False):
        # a walk is a pure function of (seed, walkers, depth): the
        # resume must repeat all three or the cursor meta mismatches
        parts += ["-simulate", "-depth", str(args.depth),
                  "-walkers", str(args.walkers),
                  "-sim-seed", str(args.simseed)]
    if args.frontend != "auto":
        parts += ["-frontend", args.frontend]
    if not args.checkpoint:
        return ("re-run from scratch (no -checkpoint was set): "
                + " ".join(parts))
    return " ".join(parts)


def _render_sources(cfg_path: str, spec_name: str) -> dict:
    """Rendering inputs derived from the model directory (M4): the
    action-line table scanned from the spec's committed translation, and
    the Toolbox .pmap (generated-TLA -> PlusCal source map) when present."""
    out = {}
    model_dir = os.path.dirname(os.path.abspath(cfg_path))
    tla = os.path.join(model_dir, f"{spec_name}.tla")
    if os.path.exists(tla):
        from .io.tlc_log import action_lines_from_spec

        out["action_lines"] = action_lines_from_spec(tla)
    pmap_path = os.path.join(
        os.path.dirname(model_dir), f"{spec_name}.tla.pmap"
    )
    if os.path.exists(pmap_path):
        from .frontend.pmap import PmapError, parse_pmap_file

        try:
            out["pcal_map"] = parse_pmap_file(pmap_path)
        except PmapError:
            pass  # a corrupt pmap must not break the run (Toolbox parity)
    return out


def _sany_inputs(cfg_path: str, spec_name: str):
    """Files actually read + modules resolved, for the SANY log section."""
    model_dir = os.path.dirname(os.path.abspath(cfg_path))
    files, modules = [], []
    # TLC's order (MC.out:8-24): the root MC.tla parses first, semantic
    # processing finishes with the root module last
    mc = os.path.join(model_dir, "MC.tla")
    if os.path.exists(mc):
        files.append(mc)
    sp = os.path.join(model_dir, f"{spec_name}.tla")
    if os.path.exists(sp):
        files.append(sp)
        modules.append(spec_name)
    if os.path.exists(mc):
        modules.append("MC")
    return files, modules


def _run_check_gen(args, spec) -> int:
    """Check a generic-frontend spec (E1): device engine + host liveness.

    -sharded runs the gen lane kernel through the mesh engine (the same
    fp-space partition + all_to_all routing as the KubeAPI path);
    -checkpoint/-recover snapshot the whole sharded carry (a 1-device
    mesh when -sharded is not given), mirroring TLC applying its
    distribution/checkpoint machinery to any spec."""
    from .gen import oracle as go
    from .gen.engine import check_gen

    g = spec.genspec

    def props():
        for name, (p_ast, q_ast) in g.properties.items():
            yield name, p_ast, q_ast, None

    def check():
        if not (args.sharded or args.checkpoint):
            return check_gen(
                g,
                chunk=args.chunk,
                queue_capacity=args.qcap,
                fp_capacity=args.fpcap,
                fp_index=spec.fp_index,
                check_deadlock=spec.check_deadlock,
            )
        from .engine.sharded import (
            check_sharded,
            check_sharded_with_checkpoints,
            gen_backend,
        )
        from .runtime import fp_mesh

        mesh = fp_mesh(args.sharded or 1)
        backend = gen_backend(g)
        kw = dict(
            chunk=args.chunk,
            queue_capacity=args.qcap,
            fp_capacity=args.fpcap,
            route_factor=args.routefactor,
            backend=backend,
            pipeline=args.pipeline,
            obs_slots=_obs_slots(args),
            deferred=args.deferredinv,
        )
        if args.checkpoint:
            meta_config = {
                "spec": spec.spec_name,
                "constants": {
                    k: sorted(v) if isinstance(v, frozenset) else v
                    for k, v in g.constants.items()
                },
            }
            return check_sharded_with_checkpoints(
                None, mesh, ckpt_path=args.checkpoint,
                ckpt_every=args.checkpointevery, resume=args.recover,
                meta_config=meta_config, **kw,
            )
        return check_sharded(None, mesh, **kw)

    def leads_to(name, p, q, distinct=0):
        from .live.check import check_leads_to_device, use_device_path

        if use_device_path(distinct, args.fairness, args.liveness_host):
            mesh = None
            if args.sharded:
                from .runtime import fp_mesh

                mesh = fp_mesh(args.sharded)
            return check_leads_to_device(
                g, p, q, name, chunk=args.chunk,
                state_capacity=args.fpcap, fp_capacity=args.fpcap,
                mesh=mesh, spill_path=args.checkpoint or None,
            )
        return go.check_leads_to(g, p, q, name, fairness=args.fairness)

    kit = _InterpKit(
        kind="generic",
        extra_unsupported=(
            ("-nodeadlock with -sharded/-checkpoint",
             (args.sharded or args.checkpoint)
             and not spec.check_deadlock),
        ),
        check=lambda: (check(), None),
        init_count=lambda: 1,
        properties=props,
        check_leads_to=leads_to,
        fairness_label=args.fairness,
        state_to_tla=lambda st: go.state_to_tla(g, st),
        state_env=lambda st: go.state_env(g, st),
        violation_trace=lambda: go.violation_trace(
            g, check_deadlock=spec.check_deadlock
        ),
        coverage=lambda: _gen_coverage_lines(spec, g),
        preflight=lambda deep: _gen_preflight(args, g, deep),
    )
    return _run_check_interp(args, spec, kit)


def _gen_preflight(args, g, deep):
    from .analysis.preflight import preflight_gen

    return preflight_gen(g, fp_capacity=args.fpcap, deep=deep)


def _gen_coverage_lines(spec, g):
    from .gen.coverage import coverage_walk, render_coverage

    text = ""
    if spec.tla_path:
        try:
            with open(spec.tla_path) as f:
                text = f.read()
        except OSError:
            pass
    init_count, cov = coverage_walk(g, text)
    return render_coverage(
        spec.spec_name, init_count, cov,
        time.strftime("%Y-%m-%d %H:%M:%S"),
    )


def _run_check_struct(args, spec) -> int:
    """Check a structural-frontend spec (E1): the full-module path that
    runs specs outside the gen subset - the reference's own KubeAPI.tla
    included.  The LaneCompiler step is a first-class engine kernel now:
    struct runs ride the production engines - segmented + supervised by
    default (auto-regrow, checkpoints, SIGTERM drain), mesh-sharded
    with -sharded - with the persistent step-compile cache warm-starting
    repeated runs.  Host graph for liveness, host re-run for traces;
    same log protocol and exit conventions."""
    from .struct import oracle as so
    from .struct.backend import struct_meta_config
    from .struct.cache import get_backend
    from .struct.engine import check_struct, check_struct_sharded

    sm = spec.structmodel
    system = sm.system
    if args.recover and not args.checkpoint:
        print("Error: -recover requires -checkpoint PATH", file=_err(args))
        return 1
    refusal = _constraint_refusal(args, spec, sm)
    if refusal:
        print(f"Error: {refusal}", file=_err(args))
        return 1
    if getattr(args, "simulate", False):
        # the simulation tier (jaxtlc.sim, ISSUE 14): random-walk
        # smoke checking instead of exhaustive BFS
        return _run_sim_struct(args, spec)
    if getattr(args, "infer", False):
        # invariant inference (jaxtlc.infer, ISSUE 16): conjecture ->
        # filter -> certify instead of checking
        return _run_infer_struct(args, spec)
    log_holder = []

    # -narrow: the certified-bound narrowed codec (analysis.absint).
    # Only a CERTIFIED report narrows; an uncertified one keeps the
    # baseline layout and says so up front (the run stays correct
    # either way - runtime traps / the certificate column enforce it)
    bounds = None
    if args.narrow:
        from .struct.cache import get_bounds

        bounds = get_bounds(sm)
        if not bounds.certified:
            bounds = None

    # incremental re-checking (ISSUE 13): the artifact plan decides,
    # BEFORE any engine build, whether this check can be answered from
    # the verdict tier (unchanged spec -> cached CheckOutcome) or the
    # reachable-set tier (invariant-only edit -> BFS-free vmapped
    # invariant pass).  Resume/fault/mutation/coverage/profiling runs
    # opt out - they exist to exercise the engines themselves.
    sym_error = _resolve_struct_symmetry(args, spec, sm, bounds)
    if sym_error:
        print(f"Error: {sym_error}", file=_err(args))
        return 1
    art_plan = _artifact_plan(args, spec, sm, bounds)
    capture = art_plan is not None and not args.sharded

    def check():
        # a run that halts on a trap of its step (an Append on a full
        # sequence, more live lanes than the compacted step's slots, a
        # value out of a guessed range) takes the rung that cures it -
        # struct.cache.widen: every backend of the model rebuilt wider -
        # and the check starts again from its initial states; where
        # none does, the trap is the codec's
        from .engine.bfs import VIOL_SLOT_OVERFLOW
        from .resil import SlotOverflowError
        from .struct.cache import widen

        while True:
            halt = None
            try:
                r, sup = check_once()
            except SlotOverflowError as e:
                halt = e
            if halt is None and r.violation != VIOL_SLOT_OVERFLOW:
                return r, sup
            rung = widen(sm, get_backend(
                sm, spec.check_deadlock, bounds=bounds,
                elide=not args.sharded, coverage=args.coverage,
                symmetry=_symmetry(args), por=_por(args)),
                halt.state if halt is not None else r.violation_state)
            if rung is None:
                if halt is not None:
                    raise halt
                return r, sup
            resource, step, reason = rung
            _sup_opts(args, log_holder[0]).on_event("degrade", dict(
                rung="widen", resource=resource, action="%d->%d" % step,
                reason=reason))

    qcap = []

    def queue_capacity():
        """-qcap, or the power of two that holds Init where Init alone
        is wider (EWD840's is 2^(2N) N states: the engine's init_fn
        seats them in one level buffer before any segment could ask the
        ladder for room): the ladder's regrow rung taken before the
        build, said as one `regrow` event.  Under -no-auto-grow the
        engine's own refusal stands."""
        if not qcap:
            n0 = system.initial_count()
            qcap.append(args.qcap)
            if n0 > args.qcap and args.autogrow:
                qcap[0] = 1 << (n0 - 1).bit_length()
                _sup_opts(args, log_holder[0]).on_event("regrow", dict(
                    resource="queue_capacity", old=args.qcap, new=qcap[0],
                    violation="initial states exceed the queue",
                    regrows=0, seconds=0.0))
        return qcap[0]

    def check_once():
        log = log_holder[0]
        ckd = spec.check_deadlock
        cov = args.coverage
        sym, por = _symmetry(args), _por(args)
        kw = dict(chunk=args.chunk, queue_capacity=queue_capacity(),
                  fp_capacity=args.fpcap)
        if args.sharded:
            from .runtime import fp_mesh

            mesh = fp_mesh(args.sharded)
            if args.checkpoint or args.autogrow:
                from .resil import check_sharded_supervised

                sup = check_sharded_supervised(
                    None, mesh,
                    backend=get_backend(sm, ckd, bounds=bounds,
                                        elide=False, coverage=cov,
                                        symmetry=sym, por=por),
                    meta_config=struct_meta_config(sm, bounds=bounds),
                    route_factor=args.routefactor,
                    pipeline=args.pipeline,
                    obs_slots=_obs_slots(args),
                    deferred=args.deferredinv,
                    opts=_sup_opts(args, log, finish=liveness), **kw,
                )
                return sup.result, sup
            return check_struct_sharded(
                sm, mesh, route_factor=args.routefactor,
                check_deadlock=ckd, pipeline=args.pipeline,
                obs_slots=_obs_slots(args), bounds=bounds,
                coverage=cov, deferred=args.deferredinv,
                symmetry=args.symmetry, por=args.por, **kw,
            ), None
        if args.checkpoint or args.autogrow:
            from .resil import check_supervised

            sup = check_supervised(
                None, fp_index=spec.fp_index,
                backend=get_backend(sm, ckd, bounds=bounds,
                                    coverage=cov, symmetry=sym,
                                    por=por),
                meta_config=struct_meta_config(sm, bounds=bounds),
                check_deadlock=ckd,
                pipeline=args.pipeline,
                obs_slots=_obs_slots(args),
                deferred=args.deferredinv,
                opts=_sup_opts(args, log, capture_fps=capture,
                               finish=liveness), **kw,
            )
            return sup.result, sup
        return check_struct(
            sm, fp_index=spec.fp_index, check_deadlock=ckd,
            pipeline=args.pipeline, obs_slots=_obs_slots(args),
            bounds=bounds, coverage=cov, deferred=args.deferredinv,
            symmetry=args.symmetry, por=args.por, capture_fps=capture,
            **kw,
        ), None

    def props():
        for name in spec.properties:
            ast = sm.properties[name]
            if ast[0] != "leadsto" or ast[1][0] == "box":
                yield name, None, None, (
                    "only plain P ~> Q and a specification I /\\ [][A]_v "
                    "are checked on the structural path"
                )
                continue
            yield name, ast[1], ast[2], None

    def action_order():
        # MC.out prints actions in module-definition order; lane labels
        # ARE definition names, so def_order is the rendering order
        names = set(get_backend(sm, spec.check_deadlock).labels)
        ordered = [n for n in sm.module.def_order if n in names]
        return ordered + [n for n in sorted(names) if n not in ordered]

    def coverage_device(r, n_init):
        # the device coverage plane's end-of-run dump (MC.out format):
        # counts straight off the carry - no host re-walk
        if getattr(r, "site_coverage", None) is None:
            return None
        from .obs.coverage import render_site_dump

        plane = get_backend(sm, spec.check_deadlock, bounds=bounds,
                            coverage=True).coverage
        counts = [r.site_coverage.get(s.key, 0) for s in plane.sites]
        return render_site_dump(
            plane.sites, counts, plane.module or spec.spec_name,
            time.strftime("%Y-%m-%d %H:%M:%S"), init_count=n_init,
            act_gen=r.action_generated, act_dist=r.action_distinct,
            order=action_order(),  # module-definition (MC.out) order
        )

    def dead_site_lint(r):
        # zero-visit sites cross-checked against the static
        # unreachable-action lint: a statically-REACHABLE site that
        # never fired is the dynamic counterpart of the PR 6 lint
        return _struct_dead_sites(args, spec, sm, bounds, r)

    def reduce_info():
        # the journal `reduce` event's static half (ISSUE 18): what
        # the reduction machinery resolved for this run (the backend
        # memo makes this a cache hit, not a recompile)
        sym, por = _symmetry(args), _por(args)
        if not (sym or por):
            return None
        red = get_backend(
            sm, spec.check_deadlock, bounds=bounds,
            elide=not args.sharded, coverage=args.coverage,
            symmetry=sym, por=por,
        ).reduce
        if red is None:
            return None
        return dict(
            symmetry=sym, por=por,
            orbit_factor=red.orbit_factor,
            symmetric_sets={k: list(v) for k, v in red.sym_sets},
            dropped_sets=dict(red.dropped_sets),
            safe_actions=len(red.safe_ids),
        )

    def liveness(r):
        """The cfg's PROPERTY lines, once the safety search is clean
        (SupervisorOptions.finish on the supervised routes, else right
        after the check): `P ~> Q` under the fairness the SPECIFICATION
        formula states (sm.fairness), on the device route
        (live.check.check_struct_properties, sized from what `r` has
        just counted) - or through struct.oracle where the flags ask
        for the host, the run was reduced (its counts are not the
        graph's) or the relation does not fit the device.  One
        `liveness` journal event a property; the results wait on the
        kit for the transcript.  Returns (r with the route's counters,
        "liveness_violation" | None)."""
        r = _action_property_events(args, sm, r, system.initial_count())
        if kit.live_results is not None or not spec.properties \
                or r.violation != 0 or r.queue_left:
            return r, None
        from .engine.bfs import lookup_counters
        from .live.check import (
            LIVE_COUNTERS, LiveTooLarge, check_struct_properties)

        listed = list(props())
        todo = [(name, p_ast, q_ast)
                for name, p_ast, q_ast, skip in listed if skip is None]
        route = "device"
        why_host = ("-liveness-host" if args.liveness_host else
                    "a reduced run's counts are not the graph's"
                    if _symmetry(args) or _por(args) else None)
        found = None
        if why_host is None and todo:
            try:
                with span("live"):
                    backend = get_backend(sm, spec.check_deadlock,
                                          bounds=bounds,
                                          coverage=args.coverage)
                    found = check_struct_properties(
                        sm, backend, todo, n_states=r.distinct,
                        n_edges=r.generated - system.initial_count(),
                        chunk=args.chunk, fp_capacity=args.fpcap,
                        fp_index=spec.fp_index)
            except LiveTooLarge as e:
                why_host = str(e)
        if found is None:
            route = "host"
            if why_host and log_holder:
                log_holder[0].msg(
                    1000, f"Temporal properties on the host: {why_host}.")
            found = [so.check_leads_to(system, p, q, name,
                                       fairness=sm.fairness)
                     for name, p, q in todo]
        by_name = {res.name: res for res in found}
        kit.live_results = [(name, skip, by_name.get(name))
                            for name, _p, _q, skip in listed]
        kit.live_route = route
        unjudged = tuple(name for name, _p, _q, skip in listed if skip)
        if unjudged:
            # on the result and the `final` event: a clean verdict says
            # nothing of these
            r = r._replace(properties_skipped=unjudged)
        j = getattr(args, "_journal", None)
        fairness = [[a, list(labels)] for a, labels in sm.fairness]
        for res in found:
            counters = getattr(res, "counters", None) or {}
            if j is not None:
                j.event("liveness", property=res.name,
                        holds=bool(res.holds), route=route,
                        fairness=fairness,
                        **{f"live_{k}": counters[k] for k in LIVE_COUNTERS
                           if k in counters})
        if route == "device" and found:
            once = ("states", "edges", "changed_edges", "edge_bytes")
            r = r._replace(**{
                f"live_{k}": found[0].counters[k] if k in once
                else sum(res.counters[k] for res in found)
                for k in LIVE_COUNTERS},
                # P and Q were compiled by now: their field reads count
                **lookup_counters(backend))
        violated = any(not res.holds for res in found)
        return r, ("liveness_violation" if violated else None)

    stated = " /\\ ".join(f"WF_vars({a})" for a, _ in sm.fairness)
    kit = _InterpKit(
        kind="structural",
        # the struct route takes its fairness from the SPECIFICATION
        # formula; -fairness is the hand path's flag
        extra_unsupported=(
            ("-fairness wf_process (a struct spec is judged under the "
             "fairness its SPECIFICATION formula states"
             + (f": {stated})" if stated else ": none)"),
             args.fairness == "wf_process"),
        ),
        liveness=liveness,
        check=check,
        # lazy: Init enumeration is real work on struct specs and must
        # not run when the flags are about to be rejected
        init_count=system.initial_count,
        properties=props,
        check_leads_to=lambda name, p, q, **_kw: so.check_leads_to(
            system, p, q, name, fairness=sm.fairness
        ),
        fairness_label=stated or "none",
        state_to_tla=lambda st: so.state_to_tla(system, st),
        state_env=lambda st: so.state_env(system, st),
        violation_trace=lambda: so.violation_trace(
            system, sm.invariants, check_deadlock=spec.check_deadlock,
            constraints=sm.constraints, action_props=sm.action_props,
        ),
        action_order=action_order,
        preflight=lambda deep: _struct_preflight(args, spec, sm, deep),
        coverage_device=coverage_device,
        dead_site_lint=dead_site_lint,
        artifact_plan=art_plan,
        reduce_info=reduce_info,
        constraints=tuple(sm.constraints),
    )
    return _run_check_interp(args, spec, kit, log_holder=log_holder)


def _run_sim_struct(args, spec) -> int:
    """The simulation tier (jaxtlc.sim, ISSUE 14): W vmapped random
    walks of depth N through the struct backend's own kernels, with
    seed-exact host replay for violations.

    The transcript discipline mirrors the exhaustive struct path - the
    same banner/journal/preflight plumbing, the same violation message
    and 2217 trace rendering - but the success message says SMOKE, not
    "model checking completed": a clean walk proves nothing about
    unsampled behaviors, which is also why this path journals an
    artifact-cache BYPASS instead of writing a verdict artifact."""
    from .resil import EXIT_INTERRUPTED, FaultPlan
    from .sim.driver import run_sim_supervised
    from .sim.replay import replay_lane, walk_trace
    from .struct import artifacts as _arts
    from .struct import oracle as so
    from .struct.cache import get_backend

    sm = spec.structmodel
    unsupported = [
        flag for flag, on in (
            ("-sharded", args.sharded),
            ("-pipeline", args.pipeline),
            ("-liveness", args.liveness),
            ("-coverage", args.coverage),
            ("-narrow", args.narrow),
            ("-mutation", args.mutation),
            ("-symmetry", getattr(args, "symmetry", None)),
            ("-por", getattr(args, "por", None)),
            ("-fpset DiskFPSet", args.fpset != "JaxFPSet"),
        ) if on
    ]
    if unsupported:
        print(
            f"Error: {', '.join(unsupported)} not supported with "
            "-simulate (walks carry no frontier/liveness machinery)",
            file=_err(args),
        )
        return 1
    log = TLCLog(out=args.out, tool_mode=not args.noTool)
    import jax

    device = str(jax.devices()[0])
    log.version(__version__)
    log.banner(spec.fp_index, DEFAULT_SEED, jax.devices()[0].platform,
               device)
    log.sany(*_sany_inputs(args.config, spec.spec_name))
    log.starting()
    log.computing_init()
    _open_journal(
        args, workload=spec.spec_name, engine="sim", device=device,
        params=dict(walkers=args.walkers, depth=args.depth,
                    sim_seed=args.simseed, fp_capacity=args.fpcap,
                    frontend="struct"),
    )
    j = getattr(args, "_journal", None)
    # artifact-cache honesty (ISSUE 14 satellite): when a store is
    # configured, this run journals an explicit BYPASS - a poisoned
    # verdict tier would silently answer later EXHAUSTIVE queries with
    # an incomplete-search verdict
    if _arts.store_for(args) is not None and j is not None:
        j.event("cache", tier="verdict", outcome="bypass", key="",
                reason="simulation verdicts are from incomplete "
                       "search and never publish")
    rc = _preflight_gate(
        args, log, lambda deep: _struct_preflight(args, spec, sm, deep)
    )
    if rc is not None:
        return rc
    log.msg(1000, f"Running random simulation: {args.walkers} walks "
                  f"to depth {args.depth} (seed {args.simseed}).")
    from .sim.liveness import expressible as _live_expressible

    live_props = []
    for name in spec.properties:
        # cfg-declared temporal properties: plain P ~> Q is checked on
        # the sampled behaviors after the walk (lasso detection, TLC's
        # -simulate analog); shapes the trace checker cannot express
        # keep the skip notice
        skip = _live_expressible(sm.properties[name])
        if skip is not None:
            log.msg(1000, f"Temporal property {name} skipped: {skip}.",
                    severity=1)
        else:
            live_props.append(name)
    t0 = time.time()
    resume_cmd = _resume_command(args)

    def on_event(kind, info):
        if j is not None:
            ev = j.event(kind, **info)
        else:
            from .obs.schema import SCHEMA_VERSION

            ev = {"v": SCHEMA_VERSION, "t": time.time(),
                  "event": kind, **info}
        from .obs.views import render_tlc_event

        render_tlc_event(log, ev, resume_cmd=resume_cmd)

    try:
        sup = run_sim_supervised(
            sm, seed=args.simseed, walkers=args.walkers,
            depth=args.depth, fp_capacity=args.fpcap,
            check_deadlock=spec.check_deadlock,
            ckpt_path=args.checkpoint or None,
            ckpt_every=args.checkpointevery, resume=args.recover,
            faults=(FaultPlan.parse(args.faults) if args.faults
                    else None),
            on_event=on_event,
            drain=getattr(args, "drain", None),
        )
    except (FileNotFoundError, ValueError) as e:
        print(f"Error: {e}", file=_err(args))
        _finish_journal(args, log)
        return 1
    r = sup.result
    args._result = r
    log.init_done(len(sm.system.initial_states()))
    if j is not None:
        j.event("sim", phase="summary", walkers=r.walkers,
                depth=r.depth, steps=r.steps,
                transitions=r.transitions, seed=r.seed,
                distinct_est=r.distinct,
                fp_saturated=r.fp_saturated, halted=r.halted,
                depth_hist=[list(p) for p in r.depth_hist],
                violation=r.violation)
    if sup.interrupted:
        if j is not None:
            j.event("final", verdict="interrupted",
                    generated=r.generated, distinct=r.distinct,
                    depth=r.steps, queue=0,
                    wall_s=round(time.time() - t0, 6),
                    interrupted=True)
        _finish_journal(args, log)
        return EXIT_INTERRUPTED
    violated = r.violation != 0
    if violated:
        log.msg(2110 if r.violation >= 100 else 1000,
                r.violation_name, severity=1)
        # seed-exact replay: the lane's walk IS the counterexample -
        # re-derived host-side from (seed, lane) alone, decoded through
        # the struct codec, rendered through the same 2217 path the
        # BFS trace uses (byte-for-byte transcripts on a forced path)
        backend = get_backend(sm, spec.check_deadlock)
        walk = replay_lane(
            backend, r.seed, r.violation_lane,
            max(r.violation_step, 0),
            check_deadlock=spec.check_deadlock,
        )
        if j is not None:
            j.event("sim", phase="replay", walkers=r.walkers,
                    depth=r.depth, steps=len(walk.fields) - 1,
                    transitions=len(walk.fields) - 1,
                    lane=r.violation_lane, seed=r.seed,
                    violation=walk.violation)
        if walk.violation != r.violation:
            log.msg(1000, "Violation was not reproducible in host "
                          "replay", severity=1)
        else:
            for i, (st, act) in enumerate(
                    walk_trace(walk, backend.cdc), start=1):
                head = (f"State {i}: <Initial predicate>" if act is None
                        else f"State {i}: <{act}>")
                log.msg(2217,
                        head + "\n" + so.state_to_tla(sm.system, st),
                        severity=1)
    else:
        sat = " (sampling filter saturated: estimate is a floor)" \
            if r.fp_saturated else ""
        log.msg(1000, f"Simulation complete: {r.walkers} walks, "
                      f"{r.transitions} transitions taken to depth "
                      f"{r.steps}, ~{r.distinct} distinct states "
                      f"sampled{sat}.")
        log.msg(1000, "No violation found in the sampled behaviors "
                      "(simulation is NOT exhaustive - this is a "
                      "smoke verdict).")
    liveness_violated = False
    if not violated and live_props:
        # liveness on the sampled traces (ISSUE 16 satellite): lasso
        # detection over the walk trajectories, re-derived from the
        # seed (a lane is a pure function of (seed, lane) - the same
        # replay guarantee the safety trace uses)
        from .sim.liveness import check_walk_leads_to, walk_trajectories

        trajs = walk_trajectories(
            sm, args.walkers, args.depth, args.simseed,
            check_deadlock=spec.check_deadlock,
        )
        for name in live_props:
            ast = sm.properties[name]
            res = check_walk_leads_to(sm, ast[1], ast[2], name, trajs)
            if j is not None:
                j.event("sim", phase="liveness", walkers=args.walkers,
                        depth=args.depth, steps=r.steps,
                        transitions=r.transitions, property=name,
                        lassos=res.lassos, holds=res.holds)
            if res.holds:
                log.msg(1000, f"Temporal property {name}: no "
                              f"violating lasso in the sampled "
                              f"behaviors ({res.lassos} lasso(s) "
                              f"examined; sampling is NOT "
                              f"exhaustive).")
                continue
            liveness_violated = True
            log.msg(2116, f"Temporal properties were violated: {name}",
                    severity=1)
            idx = 1
            for st in res.prefix:
                log.trace_state(idx, None,
                                so.state_to_tla(sm.system, st))
                idx += 1
            log.msg(1000, "-- The following states form a cycle "
                          "(back to the first of them) --")
            for st in res.cycle:
                log.trace_state(idx, None,
                                so.state_to_tla(sm.system, st))
                idx += 1
    log.progress(r.steps, r.generated, r.distinct, 0)
    log.final_counts(r.generated, r.distinct, 0)
    log.finished(int((time.time() - t0) * 1000))
    if j is not None:
        if violated:
            j.event("violation", code=int(r.violation),
                    name=r.violation_name)
        elif liveness_violated:
            j.event("violation", code=13,
                    name="Temporal properties were violated")
        j.event("final",
                verdict=("violation" if violated else
                         "liveness_violation" if liveness_violated
                         else "ok"),
                generated=r.generated, distinct=r.distinct,
                depth=r.steps, queue=0,
                wall_s=round(time.time() - t0, 6), interrupted=False)
    _finish_journal(args, log)
    if violated:
        return 12
    return 13 if liveness_violated else 0


def _run_infer_struct(args, spec) -> int:
    """The inference job class (jaxtlc.infer, ISSUE 16): conjecture
    candidate invariants over the struct IR, kill the ones reachable
    evidence refutes in vmapped [P, S] filter dispatches, certify the
    survivors inductive - the same banner/journal/preflight plumbing
    as a check, but the product is a transcript of CERTIFIED candidate
    invariants (and an honest "consistent with evidence only" list),
    not a pass/fail verdict about the spec.  The run exits 12 only
    when EXACT evidence kills a cfg-named invariant - a real reachable
    violation - and never publishes to the artifact-cache verdict
    tier."""
    from .infer.driver import run_infer
    from .struct import artifacts as _arts

    sm = spec.structmodel
    unsupported = [
        flag for flag, on in (
            ("-sharded", args.sharded),
            ("-pipeline", args.pipeline),
            ("-liveness", args.liveness),
            ("-coverage", args.coverage),
            ("-narrow", args.narrow),
            ("-mutation", args.mutation),
            ("-checkpoint", args.checkpoint),
            ("-recover", args.recover),
            ("-faults", args.faults),
            ("-symmetry", getattr(args, "symmetry", None)),
            ("-por", getattr(args, "por", None)),
            ("-fpset DiskFPSet", args.fpset != "JaxFPSet"),
        ) if on
    ]
    if unsupported:
        print(
            f"Error: {', '.join(unsupported)} not supported with "
            "-infer (inference carries no frontier/checkpoint "
            "machinery)",
            file=_err(args),
        )
        return 1
    log = TLCLog(out=args.out, tool_mode=not args.noTool)
    import jax

    device = str(jax.devices()[0])
    log.version(__version__)
    log.banner(spec.fp_index, DEFAULT_SEED, jax.devices()[0].platform,
               device)
    log.sany(*_sany_inputs(args.config, spec.spec_name))
    log.starting()
    log.computing_init()
    _open_journal(
        args, workload=spec.spec_name, engine="infer", device=device,
        params=dict(budget=args.inferbudget, walkers=args.walkers,
                    depth=args.depth, sim_seed=args.simseed,
                    frontend="struct"),
    )
    j = getattr(args, "_journal", None)
    # artifact-cache honesty: inference READS the reachable-set tier
    # as filter evidence but its verdict is about candidates, not the
    # stated invariants - it never publishes to the verdict tier
    if _arts.store_for(args) is not None and j is not None:
        j.event("cache", tier="verdict", outcome="bypass", key="",
                reason="inference verdicts are about candidate "
                       "invariants and never publish")
    rc = _preflight_gate(
        args, log, lambda deep: _struct_preflight(args, spec, sm, deep)
    )
    if rc is not None:
        return rc
    log.msg(1000, f"Running invariant inference: budget "
                  f"{args.inferbudget} candidates "
                  f"(walk geometry {args.walkers}x{args.depth}, "
                  f"seed {args.simseed}).")
    t0 = time.time()
    resume_cmd = _resume_command(args)

    def on_event(kind, info):
        if j is not None:
            ev = j.event(kind, **info)
        else:
            from .obs.schema import SCHEMA_VERSION

            ev = {"v": SCHEMA_VERSION, "t": time.time(),
                  "event": kind, **info}
        from .obs.views import render_tlc_event

        render_tlc_event(log, ev, resume_cmd=resume_cmd)

    running = {"killed": 0}

    def on_round(row):
        running["killed"] += row["killed"]
        on_event("infer", dict(
            phase="round",
            candidates=row["survivors"] + running["killed"],
            killed=running["killed"], survivors=row["survivors"],
            certified=0, round=row["round"],
            evidence=row["evidence"], n_states=row["n_states"],
        ))

    try:
        rep = run_infer(
            sm, budget=args.inferbudget, walkers=args.walkers,
            depth=args.depth, seed=args.simseed,
            check_deadlock=spec.check_deadlock, on_round=on_round,
        )
    except (FileNotFoundError, ValueError) as e:
        print(f"Error: {e}", file=_err(args))
        _finish_journal(args, log)
        return 1
    args._result = rep
    log.init_done(len(sm.system.initial_states()))
    on_event("infer", dict(
        phase="summary", candidates=rep.candidates, killed=rep.killed,
        survivors=len(rep.survivors), certified=len(rep.certified),
        certified_names=[c.name for c in rep.certified],
        evidence=rep.evidence, n_states=rep.n_states,
        dropped=rep.dropped,
    ))
    violated = bool(rep.cfg_killed)
    if violated:
        for name in rep.cfg_killed:
            log.msg(2110, f"Invariant {name} is violated (refuted by "
                          f"a reachable state in the exact evidence "
                          f"set).", severity=1)
    evid = (f"exact {rep.evidence} evidence ({rep.n_states} states)"
            if rep.exact else
            f"sampled walk evidence ({rep.n_states} states - "
            f"NOT exhaustive)")
    log.msg(1000, f"Inference complete: {rep.candidates} candidates "
                  f"({rep.dropped} beyond budget), {rep.killed} killed "
                  f"by {evid}.")
    for c, basis in zip(rep.certified, rep.cert_basis):
        line = c.name if c.source == "cfg" else f"{c.name} == {c.text}"
        log.msg(1000, f"Certified inductive invariant [{basis}]: "
                      f"{line}")
    uncert = [c for c in rep.survivors if c not in rep.certified]
    for c in uncert:
        line = c.name if c.source == "cfg" else f"{c.name} == {c.text}"
        log.msg(1000, f"Consistent with evidence only (NOT certified): "
                      f"{line}", severity=1)
    for name in rep.uncompiled:
        log.msg(1000, f"Candidate {name} skipped: outside the lane-"
                      f"compilable subset.", severity=1)
    log.progress(0, rep.n_states, rep.n_states, 0)
    log.final_counts(rep.n_states, rep.n_states, 0)
    log.finished(int((time.time() - t0) * 1000))
    if j is not None:
        if violated:
            j.event("violation", code=100,
                    name=f"Invariant {rep.cfg_killed[0]} is violated.")
        j.event("final",
                verdict="violation" if violated else "ok",
                generated=rep.n_states, distinct=rep.n_states,
                depth=0, queue=0,
                wall_s=round(time.time() - t0, 6), interrupted=False)
    _finish_journal(args, log)
    return 12 if violated else 0


def _artifact_plan(args, spec, sm, bounds):
    """The incremental-re-checking plan for a struct run (ISSUE 13), or
    None when the run is ineligible: resume/fault/mutation runs exist
    to exercise the engines, coverage/xprof runs produce
    run-shaped artifacts a cached verdict cannot, and -no-artifact-cache
    (or JAXTLC_ARTIFACT_CACHE=off) disables the store outright."""
    if (args.recover or args.faults or args.mutation or args.coverage
            or args.xprof
            or getattr(args, "simulate", False)
            or getattr(args, "infer", False)):
        # simulate/infer are unreachable here (both paths branch off
        # before plans are built) but stay on the list as defense in
        # depth: a simulation verdict is from INCOMPLETE search, an
        # inference verdict is about CANDIDATES - neither may publish
        # to the verdict tier
        return None
    if sm.action_props:
        # an action property is judged on EDGES, by the search itself:
        # the reachable-set tier replays states and a cached verdict
        # replays nothing, so such a model always reaches the engine
        return None
    if _symmetry(args) or _por(args):
        # a reduced run's fp table is the REDUCED reachable set: its
        # verdict is sound but its reachable-set tier would silently
        # under-cover an invariant-only re-check whose NEW invariant
        # the symmetry verifier never saw - reduced runs neither read
        # nor publish artifacts
        return None
    from .struct import artifacts as _arts

    store = _arts.store_for(args)
    if store is None:
        return None
    return _arts.ArtifactPlan(
        store, sm,
        check_deadlock=spec.check_deadlock,
        properties=tuple(spec.properties),
        fp_capacity=args.fpcap,
        bounds=bounds,
        fp_index=spec.fp_index,
        bypass_read=bool(args.recheck),
    )


def _struct_dead_sites(args, spec, sm, bounds, r):
    """The dead-site lint closure (ISSUE 11 satellite): at final
    verdict, sites with zero visits are cross-checked against
    speclint's unreachable-action findings - a statically-REACHABLE
    site that never fired becomes a warning-severity `analysis`
    journal event (the end-of-run dynamic counterpart of the PR 6
    static lint).  Returns the (layer, check, severity, subject,
    detail) event dicts; the interp runner journals + renders them."""
    if getattr(r, "site_coverage", None) is None:
        return []
    from .analysis.speclint import analyze_spec
    from .obs.coverage import zero_sites
    from .struct.cache import get_backend

    plane = get_backend(sm, spec.check_deadlock, bounds=bounds,
                        coverage=True).coverage
    counts = [r.site_coverage.get(s.key, 0) for s in plane.sites]
    dead = zero_sites(plane.sites, counts)
    if not dead:
        return []
    try:
        static_dead = {
            f.subject for f in analyze_spec(sm).findings
            if f.check == "unreachable-action"
        }
    except Exception:  # a broken lint must never block the verdict
        static_dead = set()
    events = []
    reachable_dead = [s for s in dead if s.action not in static_dead]
    for s in reachable_dead[:20]:
        what = s.loc or s.kind
        events.append(dict(
            layer="spec", check="dead-site", severity="warning",
            subject=s.key,
            detail=(f"site never fired in this run ({s.action}: {what})"
                    " although the action is statically reachable; the"
                    " configuration may be too small to exercise it"),
        ))
    if len(reachable_dead) > 20:
        events.append(dict(
            layer="spec", check="dead-site", severity="warning",
            subject=sm.root_name,
            detail=(f"{len(reachable_dead) - 20} further zero-visit "
                    "sites suppressed (see /coverage for the full "
                    "table)"),
        ))
    return events


def _struct_preflight(args, spec, sm, deep):
    """The struct path's preflight report.  The lite one is a pure
    function of the model and the request integers `preflight_struct`
    reads, so it is kept under them (struct.cache's `preflight` memo)
    and `_preflight` journals and renders the kept report into THIS
    check's journal; `check.preflight` says `memo` = hit | miss.  What
    is handed out is a copy (its `wall_s` the look-up's own): nothing
    writes to the kept one.  The deep audit (-analyze) traces the
    engine and always builds; a report that raised is not kept."""
    from .analysis.preflight import preflight_struct
    from .struct import cache

    key = None
    if not deep and sm.source_digest:
        t0 = time.time()
        key = (cache.model_key(sm), args.fpcap, args.chunk, args.qcap,
               spec.check_deadlock, bool(args.narrow), _symmetry(args))
        kept = cache.spec_kept("preflight", key)
        spans.annotate(memo="miss" if kept is None else "hit")
        if kept is not None:
            return dataclasses.replace(
                kept, wall_s=time.time() - t0,
                findings=list(kept.findings),
                engine_lines=list(kept.engine_lines),
                bound_lines=list(kept.bound_lines),
                constraint_lines=list(kept.constraint_lines))
    backend = None
    if deep:
        # the same memoized backend the run is about to use: the deep
        # audit adds a jaxpr trace, never a second lane compile
        backend = cache.get_backend(sm, spec.check_deadlock)
    # the certified bound report rides along in deep mode (-analyze)
    # and whenever -narrow is in play (the user should see what the
    # narrowed codec is built from / why narrowing was refused)
    bounds = cache.get_bounds(sm) if deep or args.narrow else None
    report = preflight_struct(
        sm, fp_capacity=args.fpcap, chunk=args.chunk,
        queue_capacity=args.qcap, check_deadlock=spec.check_deadlock,
        deep=deep, backend=backend, bounds=bounds,
        narrow=args.narrow, symmetry=_symmetry(args),
    )
    if key is not None:
        cache.spec_keep("preflight", key, report)
    return report


class _InterpKit:
    """Everything the shared interpreted-spec runner needs from a
    frontend: one object so the gen/struct runners cannot drift."""

    def __init__(self, kind, extra_unsupported, check, init_count,
                 properties, check_leads_to, fairness_label,
                 state_to_tla, state_env, violation_trace,
                 coverage=None, action_order=None, preflight=None,
                 coverage_device=None, dead_site_lint=None,
                 artifact_plan=None, reduce_info=None, constraints=(),
                 liveness=None):
        self.kind = kind
        # (r) -> (r, "liveness_violation" | None): the temporal
        # properties, run once a clean safety verdict is in hand and
        # before the `final` event (the struct route); its results
        # [(name, skip reason | None, result | None), ...] and route
        # wait here for the transcript.  None: check_leads_to, after
        self.liveness = liveness
        self.live_results = None
        self.live_route = None
        # the cfg's CONSTRAINT names (run_start.params names them)
        self.constraints = constraints
        self.extra_unsupported = extra_unsupported
        self.check = check  # () -> (CheckResult, SupervisedResult | None)
        self.init_count = init_count
        self.properties = properties
        self.check_leads_to = check_leads_to
        self.fairness_label = fairness_label
        self.state_to_tla = state_to_tla
        self.state_env = state_env
        self.violation_trace = violation_trace
        self.coverage = coverage  # () -> dump lines, or None
        self.action_order = action_order  # () -> coverage line order
        self.preflight = preflight  # (deep) -> AnalysisReport, or None
        # (r, n_init) -> device site-dump lines | None (obs.coverage)
        self.coverage_device = coverage_device
        # (r) -> analysis-event dicts for zero-visit reachable sites
        self.dead_site_lint = dead_site_lint
        # struct.artifacts.ArtifactPlan | None: the incremental
        # re-checking seam (verdict/reach lookup before any engine
        # build, clean-verdict artifact write after)
        self.artifact_plan = artifact_plan
        # () -> dict | None: state-space reduction facts for the
        # journal `reduce` event (struct frontend, ISSUE 18)
        self.reduce_info = reduce_info


def _run_check_interp(args, spec, kit: "_InterpKit",
                      log_holder: list = None) -> int:
    """Shared runner for the interpreted frontends (gen + struct): the
    KubeAPI-engine knobs are rejected, the device engine checks safety,
    the host graph checks liveness, and violations re-run on the host
    interpreter for the trace.  TLC log protocol + exit conventions."""
    unsupported = [
        flag for flag, on in (
            ("-fpset DiskFPSet", args.fpset != "JaxFPSet"),
            ("-mutation", args.mutation),
            *kit.extra_unsupported,
        ) if on
    ]
    if unsupported:
        print(
            f"Error: {', '.join(unsupported)} not supported for "
            f"{kit.kind}-frontend specs yet",
            file=_err(args),
        )
        return 1
    log = TLCLog(out=args.out, tool_mode=not args.noTool)
    if log_holder is not None:
        log_holder.append(log)
    import jax

    device = str(jax.devices()[0])
    log.version(__version__)
    log.banner(spec.fp_index, DEFAULT_SEED, jax.devices()[0].platform,
               device)
    log.sany(*_sany_inputs(args.config, spec.spec_name))
    log.starting()
    log.computing_init()
    red = getattr(args, "_reduce_ops", None)
    _open_journal(
        args, workload=spec.spec_name,
        engine="sharded" if args.sharded else "single",
        device=device,
        params=dict(chunk=args.chunk, queue_capacity=args.qcap,
                    fp_capacity=args.fpcap, sharded=args.sharded,
                    pipeline=args.pipeline, frontend=kit.kind,
                    deferred=_deferred(args),
                    symmetry=_symmetry(args), por=_por(args),
                    obs_slots=_obs_slots(args),
                    # a constrained run names the cfg's constraints
                    **({"constraints": list(kit.constraints)}
                       if kit.constraints else {}),
                    # a reduced run names its sets and the group's order
                    **({} if red is None else dict(
                        symmetric_sets={k: list(v)
                                        for k, v in red.sym_sets},
                        sym_perms=red.orbit_factor))),
    )
    for name, why in (() if red is None else red.dropped_sets):
        log.msg(1000, f"-symmetry: constant {name} is not reduced: {why}")
    # incremental re-checking (ISSUE 13): try the artifact tiers BEFORE
    # preflight or any engine build.  A verdict hit swaps the engine
    # dispatch for the cached result (and stands in for the temporal
    # checks the cached clean verdict already attests); a reach hit
    # swaps it for the BFS-free invariant pass.  Everything downstream
    # - transcript, journal, violation traces - runs unchanged, so a
    # cached answer renders exactly like a fresh run.
    cache_tier = None
    plan = kit.artifact_plan
    if plan is not None:
        fast = plan.fast_check(getattr(args, "_journal", None), log)
        if fast is not None:
            cache_tier, fast_fn, n_init_cached = fast
            kit.check = fast_fn
            kit.init_count = lambda: n_init_cached
            if cache_tier == "verdict":
                from .struct.artifacts import _PropertyHolds

                kit.check_leads_to = (
                    lambda name, p, q, **_kw: _PropertyHolds()
                )
                kit.liveness = None
    if kit.preflight is not None and cache_tier != "verdict":
        rc = _preflight_gate(args, log, kit.preflight)
        if rc is not None:
            return rc
    t0 = time.time()
    from .resil import SlotOverflowError

    try:
        with _xprof(args, log):
            r, sup = kit.check()
    except SlotOverflowError as e:
        log.msg(1000, f"Run stopped: {e}", severity=1)
        _finish_journal(args, log)
        return 1
    except FileNotFoundError as e:
        print(f"Error: {e}", file=_err(args))
        _finish_journal(args, log)
        return 1
    if kit.liveness is not None and not (
            sup is not None and (sup.interrupted
                                 or getattr(sup, "exhausted", False))):
        # the supervised routes ran it before their `final` event
        # (SupervisorOptions.finish); any other, here
        r, _ = kit.liveness(r)
    args._result = r
    n_init = kit.init_count()
    log.init_done(n_init)
    if sup is not None and sup.interrupted:
        # the interrupted banner (with the resume command) was emitted
        # by the supervisor's event hook
        from .resil import EXIT_INTERRUPTED

        log.progress(r.depth, r.generated, r.distinct, r.queue_left)
        log.final_counts(r.generated, r.distinct, r.queue_left)
        _finish_journal(args, log, r=None, sup=sup)
        return EXIT_INTERRUPTED
    red_info = kit.reduce_info() if kit.reduce_info is not None else None
    if red_info is not None:
        # the `reduce` journal event (schema v1, ISSUE 18): how much
        # the reduction actually bought this run.  ample_hit_rate is
        # pruned/(generated+pruned) - the share of candidate
        # transitions the singleton ample sets cut before dedup
        pruned = int(getattr(r, "por_pruned", None) or 0)
        total = int(r.generated) + pruned
        j = getattr(args, "_journal", None)
        if j is not None:
            j.event(
                "reduce",
                states_pruned=pruned,
                ample_hit_rate=(round(pruned / total, 6) if total
                                else 0.0),
                generated=int(r.generated),
                distinct=int(r.distinct),
                **red_info,
            )
    if getattr(r, "sym_violated", False):
        # the runtime orbit certificate tripped: the canonicalization
        # was NOT constant on some reachable orbit, so the symmetry
        # reduction may have merged states it had no right to merge -
        # every count (and the clean verdict) is untrustworthy.  Loud
        # error verdict, same discipline as the bound certificate
        detail = ("runtime orbit-certificate violation: the symmetry "
                  "canonicalization is not orbit-invariant on a "
                  "reachable state; re-run with -no-symmetry and "
                  "report the spec (the symmetry verification is "
                  "unsound)")
        j = getattr(args, "_journal", None)
        if j is not None:
            j.event("analysis", layer="spec", check="orbit-certificate",
                    severity="error", subject=spec.spec_name,
                    detail=detail)
            j.event("final", verdict="error", generated=r.generated,
                    distinct=r.distinct, depth=r.depth,
                    queue=r.queue_left,
                    wall_s=round(time.time() - t0, 6),
                    interrupted=False)
        log.msg(1000, f"ERROR: {detail}", severity=1)
        _finish_journal(args, log)
        return 1
    if getattr(r, "cert_violated", False):
        # the runtime certificate tripped: a reachable state violated a
        # bound the certified abstract interpretation claimed, so every
        # count this narrowed run produced is untrustworthy.  Loud
        # error verdict, never a silent narrowing (the views banner
        # already fired at the level event; this is the structured
        # record + the exit code)
        detail = ("runtime certificate violation: a reachable state "
                  "lies outside the certified bounds the narrowed "
                  "codec was built from; re-run with -no-narrow and "
                  "report the spec (the bound certification is "
                  "unsound)")
        j = getattr(args, "_journal", None)
        if j is not None:
            j.event("analysis", layer="spec", check="bound-certificate",
                    severity="error", subject=spec.spec_name,
                    detail=detail)
            j.event("final", verdict="error", generated=r.generated,
                    distinct=r.distinct, depth=r.depth,
                    queue=r.queue_left,
                    wall_s=round(time.time() - t0, 6),
                    interrupted=False)
        log.msg(1000, f"ERROR: {detail}", severity=1)
        _finish_journal(args, log)
        return 1
    with span("check.verdict"):
        violated, liveness_violated = _render_verdict_interp(
            args, spec, kit, log, r, n_init, t0)
    if (plan is not None and not violated and not liveness_violated
            and (sup is None or not (sup.interrupted
                                     or getattr(sup, "exhausted",
                                                False)))):
        # the clean-final-verdict write point: error/violation/
        # interrupted/exhausted runs never reach this branch, and
        # record() re-checks violation + certificate itself
        try:
            plan.record(
                r, n_init=n_init,
                journal=getattr(args, "_journal", None),
                action_order=(kit.action_order()
                              if kit.action_order is not None else None),
            )
        except OSError as e:  # a full disk must not fail the verdict
            log.msg(1000, f"Warning: artifact cache write failed: {e}",
                    severity=1)
    _report_scopes(args, log)
    _finish_journal(
        args, log, r=r, sup=sup,
        verdict="liveness_violation" if liveness_violated else None,
        wall_s=time.time() - t0,
    )
    if violated:
        return 12
    return 13 if liveness_violated else 0


def _render_lasso(log, kit, name, res) -> None:
    """A violated temporal property's transcript: the prefix, then the
    cycle."""
    log.msg(2116, f"Temporal properties were violated: {name}",
            severity=1)
    idx = 1
    for st in res.lasso_prefix:
        log.trace_state(idx, None, kit.state_to_tla(st))
        idx += 1
    log.msg(1000, "-- The following states form a cycle "
                  "(back to the first of them) --")
    for st in res.lasso_cycle:
        log.trace_state(idx, None, kit.state_to_tla(st))
        idx += 1


def _render_verdict_interp(args, spec, kit, log, r, n_init, t0):
    """The interpreted frontends after the engine: temporal properties,
    the violation trace or the success and coverage report, final
    counts.  Returns (violated, liveness_violated)."""
    violated = r.violation != 0
    liveness_violated = False
    if not violated and spec.properties:
        if kit.live_results is not None:
            # judged before the `final` event (the struct route)
            route = kit.live_route
        else:
            from .live.check import use_device_path

            route = "device" if kit.kind == "generic" and use_device_path(
                r.distinct, args.fairness, args.liveness_host
            ) else "host"

        def judged():
            if kit.live_results is not None:
                yield from kit.live_results
                return
            for name, p_ast, q_ast, skip in kit.properties():
                yield name, skip, (None if skip is not None else
                                   kit.check_leads_to(name, p_ast, q_ast,
                                                      distinct=r.distinct))

        log.checking_temporal(r.distinct, route)
        for name, skip, res in judged():
            if skip is not None:
                log.msg(1000, f"Temporal property {name} skipped: "
                              f"{skip}.", severity=1)
                continue
            if res.holds:
                log.msg(1000, f"Temporal property {name} holds "
                              f"(fairness: {kit.fairness_label}).")
                continue
            liveness_violated = True
            _render_lasso(log, kit, name, res)
    if violated:
        log.msg(2110 if r.violation >= 100 else 1000,
                r.violation_name, severity=1)
        found = kit.violation_trace()
        if found is None:
            log.msg(1000, "Violation was not reproducible in host mode",
                    severity=1)
        else:
            expr_rows = None
            if args.traceExpressions:
                # trace-explorer re-evaluation over interpreted states
                from .spec.texpr import (
                    TexprError,
                    eval_over_envs,
                    parse_expressions,
                )

                try:
                    with open(args.traceExpressions) as f:
                        exprs = parse_expressions(f.read())
                    expr_rows = eval_over_envs(
                        exprs,
                        [kit.state_env(st) for st, _ in found[1]],
                    )
                except (OSError, TexprError) as e:
                    log.msg(1000, f"Trace expressions skipped: {e}",
                            severity=1)
            for i, (st, act) in enumerate(found[1], start=1):
                head = (f"State {i}: <Initial predicate>" if act is None
                        else f"State {i}: <{act}>")
                text = kit.state_to_tla(st)
                if expr_rows is not None:
                    from .spec.pretty import value_to_tla

                    text += "".join(
                        f"\n/\\ {res.name} = "
                        + (f"<evaluation failed: {res.value}>"
                           if res.failed else value_to_tla(res.value))
                        for res in expr_rows[i - 1]
                    )
                log.msg(2217, head + "\n" + text, severity=1)
    elif not liveness_violated:
        for name in getattr(r, "action_prop_names", None) or ():
            log.msg(1000, f"Action property {name} holds on all "
                          f"{r.action_prop_edges} generated edges "
                          f"({r.action_prop_moved} change its subscript) "
                          f"and {r.action_prop_init_states} initial "
                          "state(s).")
        log.success(r.generated, r.distinct,
                    getattr(r, "actual_fp_collision", None),
                    occupancy=getattr(r, "fp_occupancy", None),
                    unjudged=getattr(r, "properties_skipped", None) or ())
        dev_lines = None
        if args.coverage and kit.coverage_device is not None:
            dev_lines = kit.coverage_device(r, n_init)
        if dev_lines is not None:
            # the DEVICE per-site dump (MC.out format): counts came off
            # the carry live - no host re-walk (ISSUE 11)
            log.coverage_site_dump(dev_lines)
            j = getattr(args, "_journal", None)
            if j is not None and not any(
                e["event"] == "coverage" for e in j.events
            ):
                # unsupervised (raw-engine) runs have no segment
                # fences: journal the cumulative table once so the
                # serve plane / covdiff see this run's coverage too
                j.event(
                    "coverage",
                    visited=sum(1 for v in r.site_coverage.values()
                                if v),
                    sites=len(r.site_coverage),
                    delta={k: v for k, v in r.site_coverage.items()
                           if v},
                )
            if kit.dead_site_lint is not None:
                from .obs.views import render_tlc_event

                j = getattr(args, "_journal", None)
                for info in kit.dead_site_lint(r):
                    if j is not None:
                        ev = j.event("analysis", **info)
                    else:
                        from .obs.schema import SCHEMA_VERSION

                        ev = {"v": SCHEMA_VERSION, "t": time.time(),
                              "event": "analysis", **info}
                    render_tlc_event(log, ev)
        elif args.coverage and kit.coverage is not None:
            # full per-expression dump: host re-walk with instrumented
            # evaluation, the KubeAPI path's discipline applied to the
            # generic frontend (slow for large configs, like TLC's own
            # coverage mode)
            log.coverage_gen_dump(kit.coverage())
        else:
            act_gen, act_dist = r.action_generated, r.action_distinct
            if kit.action_order is not None:
                # per-action lines in module-definition (MC.out) order,
                # zero-fire actions printed 0:0 exactly as TLC does
                order = kit.action_order()
                act_gen = {a: act_gen.get(a, 0) for a in order}
                act_dist = {a: act_dist.get(a, 0) for a in order}
            log.coverage_generic(spec.spec_name, n_init,
                                 act_gen, act_dist)
    log.progress(r.depth, r.generated, r.distinct, r.queue_left)
    log.final_counts(r.generated, r.distinct, r.queue_left)
    log.depth(r.depth)
    log.finished(int((time.time() - t0) * 1000))
    return violated, liveness_violated


def _print_trace(log: TLCLog, model: ModelConfig, chunk: int,
                 trace_expr_file: str = "",
                 check_deadlock: bool = True) -> None:
    from .engine.trace import find_violation_trace
    from .spec.pretty import state_to_tla

    found = find_violation_trace(model, chunk=chunk,
                                 check_deadlock=check_deadlock)
    if found is None:
        log.msg(1000, "Violation was not reproducible in host mode", severity=1)
        return
    _, trace = found
    expr_rows = None
    if trace_expr_file:
        # the Toolbox trace-explorer pass (MC_TE.out slot): evaluate each
        # user expression in every trace state, shown as extra conjuncts.
        # A bad/missing expression file must never lose the trace itself.
        from .spec.pretty import value_to_tla
        from .spec.texpr import TexprError, eval_over_trace, parse_expressions

        try:
            with open(trace_expr_file) as f:
                exprs = parse_expressions(f.read())
            expr_rows = eval_over_trace(exprs, trace, model)
        except (OSError, TexprError) as e:
            log.msg(1000, f"Trace expressions skipped: {e}", severity=1)
    for i, (st, act) in enumerate(trace, start=1):
        text = state_to_tla(st, model)
        if expr_rows is not None:
            text += "".join(
                f"\n/\\ {res.name} = "
                + (f"<evaluation failed: {res.value}>" if res.failed
                   else value_to_tla(res.value))
                for res in expr_rows[i - 1]
            )
        log.trace_state(i, act, text)
