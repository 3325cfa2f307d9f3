"""Command-line interface - the TLC invocation contract (E14).

Replaces `java tlc2.TLC -config MC.cfg ...` for the KubeAPI spec family:

    python -m jaxtlc.cli check /path/to/Model_1/MC.cfg \\
        [-workers tpu] [-fpset JaxFPSet] [-fp 51] [-sharded N] \\
        [-chunk 1024] [-nodeadlock] [-noTool]

Reads the unmodified reference artifacts (MC.cfg + sibling MC.tla + the
toolbox .launch if present - BASELINE.json's `-fpset JaxFPSet -workers tpu`
contract), runs the exhaustive check on the fused device engine (or the
sharded multi-device engine with -sharded), and emits the TLC structured
log protocol.  On violation it re-runs in host mode to reconstruct the
counterexample trace and prints it TLC-style with PlusCal action labels.

Exit codes: 0 = no error; 12 = safety violation (TLC's EC.ExitStatus
convention for violations); 13 = liveness violation; 75 = interrupted
(SIGTERM/SIGINT) OR capacity-exhausted (the degradation ladder's final
rung) with a final checkpoint written - resume with -recover;
1 = usage/config error (including non-regrowable codec slot overflow).

Robustness (the resil supervisor wraps the KubeAPI-path engines):
capacity exhaustion walks a degradation ladder instead of aborting -
-auto-grow (default) doubles a saturated fpset/queue/route resource
after a probe allocation confirms it fits; when the probe is denied,
-spill (default auto) activates the host-RAM fingerprint spill tier so
the run completes inside the device memory it has; then chunk shrink;
then checkpoint + exit 75.  -retry N retries segments around transient
device errors (RESOURCE_EXHAUSTED is classified as deterministic and
goes to the ladder, never the retry budget); -checkpoint writes
CRC-verified generation-numbered snapshots (spilling runs pair each
with a host-tier .spill sibling) and -recover loads the newest intact
one (auto-grown geometry and the host tier travel with the checkpoint).
"""

from __future__ import annotations

import argparse
import sys

# The check orchestration lives in jaxtlc.api now (the engine-as-a-
# library refactor, ISSUE 9): this module is the argparse shim.  The
# names below are re-exported for callers that grew up against the old
# CLI-owns-everything layout (tests, tools).
from .api import (  # noqa: F401 - compatibility re-exports
    CheckRequest,
    CheckOutcome,
    run_check,
    _dispatch_check,
    _finish_journal,
    _open_journal,
    _preflight_gate,
    _resume_command,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="jaxtlc")
    sub = p.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("check", help="exhaustively check a TLC model config")
    c.add_argument("config", help="path to MC.cfg (sibling MC.tla is read)")
    c.add_argument("-workers", default="tpu",
                   help="TLC contract knob.  `cpu` runs on the CPU; "
                        "any other value requires an accelerator (the "
                        "run exits 1 rather than drop to CPU unasked - "
                        "JAX_PLATFORMS=cpu is the other way to ask)")
    c.add_argument("-frontend", default="auto",
                   choices=["auto", "hand", "gen", "struct"],
                   help="spec frontend: auto picks hand-tuned KubeAPI / "
                        "gen-subset / structural as applicable; struct "
                        "forces the full-module structural path (runs "
                        "ANY spec, KubeAPI included)")
    c.add_argument("-fpset", default="JaxFPSet",
                   choices=["JaxFPSet", "DiskFPSet"],
                   help="JaxFPSet = device-resident fingerprint table; "
                        "DiskFPSet = native host tier (disk-bounded, the "
                        "OffHeapDiskFPSet analog)")
    c.add_argument("-fp", type=int, default=None, help="fp polynomial index")
    c.add_argument("-sharded", type=int, default=0, metavar="N",
                   help="run the sharded engine over N devices")
    c.add_argument("-chunk", type=int, default=1024)
    c.add_argument("-pipeline", dest="pipeline", action="store_true",
                   default=False,
                   help="software-pipeline the device engines: commit "
                        "(dedup/enqueue) of block k-1 overlaps expansion "
                        "of block k, with the sharded verdict-return "
                        "all_to_all deferred behind the next routing "
                        "collective.  Bit-for-bit identical counts; for "
                        "maximum overlap run with HALF the unpipelined "
                        "sweet-spot -chunk (PERF.md round 7).  A "
                        "checkpoint records this setting: -recover "
                        "must use the same mode")
    c.add_argument("-no-pipeline", dest="pipeline", action="store_false",
                   help="(default) the fused single-stage step bodies")
    c.add_argument("-deferred-inv", dest="deferredinv",
                   action="store_const", const=True, default=None,
                   help="distinct-first expand (ISSUE 15): evaluate "
                        "invariants and the certified-bound check at "
                        "the commit stage, on the fresh-insert "
                        "claimants only, instead of on every chunk*L "
                        "candidate lane - TLC checks a state when it "
                        "is first generated, and first generation IS "
                        "the distinct fpset insert.  Inherited by "
                        "every engine at the expand/commit seam "
                        "(fused, -pipeline, -sharded owner-side, "
                        "spill, -narrow, -coverage); "
                        "-simulate ignores it (every walker state is "
                        "fresh - the sim tier keeps its immediate "
                        "per-walker invariant path).  Verdict, "
                        "counters, fpset table words and rendered "
                        "traces are bit-for-bit the immediate "
                        "path's (tests/test_deferred.py::"
                        "test_ff_bit_for_bit pins it); the "
                        "reported violating LANE follows the pinned "
                        "highest-lane rule (the PR 12 dedup rep "
                        "convention) instead of first-lane.  Default "
                        "auto: on at -chunk >= 2048, off below "
                        "(each side runs in one benchmark cell; never "
                        "compared on a chip: ROADMAP A3).  A "
                        "checkpoint records the "
                        "resolved mode: -recover must match")
    c.add_argument("-no-deferred-inv", dest="deferredinv",
                   action="store_const", const=False,
                   help="force immediate per-candidate invariant/cert "
                        "evaluation at any chunk")
    c.add_argument("-symmetry", dest="symmetry", action="store_const",
                   const=True, default=None,
                   help="device-resident symmetry reduction (ISSUE "
                        "18): statically verify which CONSTANT sets "
                        "the spec treats as fully permutation-"
                        "symmetric (the TLC SYMMETRY condition, "
                        "checked against the spec text - no "
                        "annotation needed), then canonicalize every "
                        "successor to its orbit representative on "
                        "device before fingerprinting, so the fpset "
                        "dedups orbits.  Same verdict, same rendered "
                        "trace, legitimately fewer DISTINCT/"
                        "GENERATED states (up to the product of "
                        "|S|! over the reduced sets).  A runtime "
                        "orbit certificate re-checks canonicalization "
                        "on every iteration (single device): a trip "
                        "is a loud error verdict, never a silently "
                        "wrong count.  Struct frontend only; "
                        "inherited by every engine at the expand/"
                        "commit seam.  Default off (counts shrink - "
                        "this is not a transparent perf mode).  A "
                        "checkpoint records the mode: -recover must "
                        "match")
    c.add_argument("-no-symmetry", dest="symmetry", action="store_const",
                   const=False,
                   help="force the unreduced full state space")
    c.add_argument("-por", dest="por", action="store_const",
                   const=True, default=None,
                   help="partial-order pruning (ISSUE 18): where a "
                        "provably safe action is enabled (independent "
                        "of every other action, invisible to every "
                        "invariant, and a monotone counter - so no "
                        "all-ample cycle can starve the rest), expand "
                        "only that action's transitions instead of "
                        "every commutative interleaving.  Same "
                        "verdict, legitimately fewer states; the "
                        "journal `reduce` event reports transitions "
                        "pruned.  Struct frontend only; default off.  "
                        "A checkpoint records the mode: -recover "
                        "must match")
    c.add_argument("-no-por", dest="por", action="store_const",
                   const=False,
                   help="force full interleaving expansion")
    c.add_argument("-routefactor", type=float, default=2.0,
                   help="sharded all_to_all bucket size as a multiple of "
                        "the mean per-owner candidate count (raise after "
                        "a routing-bucket-overflow halt)")
    c.add_argument("-qcap", type=int, default=1 << 15)
    c.add_argument("-fpcap", type=int, default=1 << 20)
    c.add_argument("-checkpoint", default="", metavar="PATH",
                   help="periodic engine snapshots to PATH (TLC checkpoint "
                        "analog); resume with -recover")
    c.add_argument("-checkpointevery", type=int, default=256, metavar="N",
                   help="chunks between checkpoints")
    c.add_argument("-recover", action="store_true",
                   help="resume from -checkpoint PATH (TLC -recover "
                        "analog); the newest intact generation is loaded, "
                        "with fallback past a torn newest file")
    c.add_argument("-auto-grow", dest="autogrow", action="store_true",
                   default=True,
                   help="(default) on fpset/queue/route saturation, double "
                        "the saturated resource, migrate the carry, and "
                        "resume instead of aborting")
    c.add_argument("-no-auto-grow", dest="autogrow", action="store_false",
                   help="disable auto-regrow: capacity exhaustion aborts "
                        "with the sizing hint (the pre-supervisor "
                        "behavior); without -checkpoint this also "
                        "restores the raw fused single-dispatch engine")
    c.add_argument("-spill", dest="spill", action="store_const",
                   const="on", default="auto",
                   help="prefer the host-RAM fingerprint spill tier at "
                        "the FIRST fpset saturation (skip the regrow "
                        "attempt).  Default auto: regrow first, spill "
                        "when the doubled table's probe allocation is "
                        "denied (RESOURCE_EXHAUSTED) or -max-regrow is "
                        "reached.  Cold fingerprints migrate to a host "
                        "store behind an on-device membership filter; "
                        "results stay bit-for-bit exact, at a host "
                        "sync per chunk (PERF.md round 10)")
    c.add_argument("-no-spill", dest="spill", action="store_const",
                   const="off",
                   help="remove the spill rung from the degradation "
                        "ladder: a denied fpset regrow then falls "
                        "through to chunk shrink / checkpoint + exit 75")
    c.add_argument("-max-regrow", dest="maxregrow", type=int, default=8,
                   metavar="N",
                   help="max auto-regrow events per run (each doubles one "
                        "resource, so 8 allows 256x growth)")
    c.add_argument("-retry", type=int, default=2, metavar="N",
                   help="retries per segment around transient device/XLA "
                        "errors (exponential backoff with jitter, "
                        "restoring the last good state)")
    c.add_argument("-faults", default="", metavar="PLAN",
                   help="self-test: deterministic fault plan for the "
                        "supervisor (e.g. 'transient@1,sigterm@3,"
                        "write_fail@2,truncate@1'; tools/chaos.py drives "
                        "this end-to-end)")
    c.add_argument("-artifact-cache", dest="artifactcache", default="",
                   metavar="DIR",
                   help="incremental re-checking artifact store "
                        "(struct frontend): cached VERDICTS keyed on "
                        "the spec's semantic digest (an unchanged spec "
                        "returns its verdict without building an "
                        "engine) and cached REACHABLE SETS keyed on "
                        "the behavior digest (an invariant-only edit "
                        "skips BFS and re-evaluates just the "
                        "invariants).  Default ~/.cache/jaxtlc/"
                        "artifacts, or $JAXTLC_ARTIFACT_CACHE (=off "
                        "disables); artifacts are CRC-verified and "
                        "written only on clean verdicts - "
                        "tools/cachectl.py lists/verifies/GCs them")
    c.add_argument("-no-artifact-cache", dest="noartifactcache",
                   action="store_true",
                   help="disable the artifact cache (both tiers) for "
                        "this run")
    c.add_argument("-recheck", action="store_true",
                   help="force a full re-check: bypass the artifact "
                        "cache on read (the run still refreshes the "
                        "artifacts it produces)")
    c.add_argument("-obs", dest="obs", action="store_true", default=True,
                   help="(default) carry the on-device observability "
                        "counter ring: one per-level telemetry row "
                        "(generated/distinct/queue/occupancy/per-action "
                        "counts), read back at segment fences and "
                        "journaled as `level` events.  Pure telemetry: "
                        "results are bit-for-bit identical to -no-obs "
                        "(tests/test_obs.py::"
                        "test_obs_bit_identical_and_ring pins it)")
    c.add_argument("-no-obs", dest="obs", action="store_false",
                   help="disable the device counter ring (also the "
                        "carry shape pre-obs checkpoints expect)")
    c.add_argument("-obs-slots", dest="obsslots", type=int, default=256,
                   metavar="N",
                   help="counter-ring depth: per-level rows retained on "
                        "device between fences (wrap loses per-level "
                        "resolution, never totals - rows are cumulative)")
    c.add_argument("-journal", default="", metavar="PATH",
                   help="append-only JSONL run journal (fsync'd per "
                        "event, schema-versioned: obs/schema.py).  "
                        "Defaults to CHECKPOINT.journal.jsonl when "
                        "-checkpoint is set; -recover APPENDS, so an "
                        "interrupted+resumed run has ONE journal.  "
                        "tools/tlcstat.py tails it live")
    c.add_argument("-serve", dest="serve", type=int, default=0,
                   metavar="PORT",
                   help="run the live monitor server on PORT for the "
                        "whole run: /metrics (Prometheus text), "
                        "/events (SSE journal tail, survives "
                        "interrupt+-recover as one stream), /runs "
                        "(registry), /journal (raw; tools/tlcstat.py "
                        "--connect renders it).  python -m "
                        "jaxtlc.obs.serve serves existing journals "
                        "standalone")
    c.add_argument("-trace-out", dest="traceout", default="",
                   metavar="FILE",
                   help="export the run timeline as a Chrome-trace JSON "
                        "(open in ui.perfetto.dev): segment slices, "
                        "per-level expand/commit lanes, checkpoint "
                        "writes, regrow/retry/interrupt markers, "
                        "counter tracks")
    c.add_argument("-xprof", default="", metavar="DIR",
                   help="wrap the check in a jax.profiler trace writing "
                        "to DIR (the ground-truth device timeline; "
                        "view with TensorBoard/XProf); the run ends in "
                        "the device's time by jaxtlc.* scope (one "
                        "`device_scopes` journal event, the tables in "
                        "DIR/jaxtlc_scopes.json; again later: python -m "
                        "jaxtlc.obs.scopes DIR)")
    c.add_argument("-narrow", dest="narrow", action="store_true",
                   default=False,
                   help="struct frontend: run on the certified-bound "
                        "NARROWED codec (jaxtlc.analysis.absint): enum "
                        "universes, mask bit counts and sequence caps "
                        "shrink to the certified reachable ranges, "
                        "cutting packed uint32 words through the "
                        "fingerprint/sort/probe path.  Counts and "
                        "verdict are identical to an un-narrowed run "
                        "(fingerprints differ - a different packing); "
                        "the on-device runtime certificate re-verifies "
                        "every claimed bound on every generated state "
                        "and escalates any violation to an error "
                        "verdict.  Refused (baseline layout, with a "
                        "warning) when the bound report cannot be "
                        "certified")
    c.add_argument("-no-narrow", dest="narrow", action="store_false",
                   help="(default) the baseline widened codec layout")
    c.add_argument("-analyze", action="store_true",
                   help="deep preflight: in addition to the default "
                        "spec-IR lints and counter-width arithmetic, "
                        "trace the engine jaxpr and audit hot-body "
                        "purity and donation safety (tracing only - "
                        "no extra XLA compile; python -m "
                        "jaxtlc.analysis runs the same suite "
                        "standalone)")
    c.add_argument("-no-preflight", dest="preflight",
                   action="store_false", default=True,
                   help="skip the preflight analysis suite (the "
                        "escape hatch when a lint is wrong; error-"
                        "severity findings otherwise abort the run "
                        "with a nonzero exit)")
    c.add_argument("-coverage", action="store_true",
                   help="compile per-site coverage counters into the "
                        "kernels (live `coverage` journal events, "
                        "GET /coverage + Prometheus coverage_site_total "
                        "on -serve, MC.out-format end-of-run dump; the "
                        "KubeAPI path additionally renders the full "
                        "host-walker dump for exact MC.out parity)")
    c.add_argument("-simulate", action="store_true",
                   help="randomized simulation instead of exhaustive "
                        "BFS (jaxtlc.sim, the TLC -simulate analog): "
                        "-walkers W device-resident random walks of "
                        "depth -depth N through the same compiled "
                        "spec kernels, each lane a pure function of "
                        "(-sim-seed, lane) - a violation replays "
                        "host-side from the seed alone and renders "
                        "the standard exit-12 trace.  A clean result "
                        "is a SMOKE verdict (sampled, not "
                        "exhaustive); the artifact cache is bypassed. "
                        " Composes with -checkpoint/-recover (the "
                        "(seed, step) cursor checkpoints) and "
                        "-frontend struct runs any spec this way")
    c.add_argument("-depth", type=int, default=100,
                   help="simulation walk depth (transitions per "
                        "walker; TLC's -depth)")
    c.add_argument("-walkers", type=int, default=256,
                   help="simulation walker lanes stepped in one "
                        "vmapped device dispatch")
    c.add_argument("-sim-seed", dest="simseed", type=int, default=0,
                   help="simulation run seed: every walk trajectory "
                        "(and any violation it finds) is an exact "
                        "pure function of this value")
    c.add_argument("-infer", action="store_true",
                   help="inductive invariant inference instead of "
                        "checking (jaxtlc.infer): conjecture up to "
                        "-infer-budget candidate predicates over the "
                        "spec's shapes, kill the ones reachable "
                        "evidence refutes in one vmapped "
                        "predicates-x-states device kernel, certify "
                        "the survivors inductive over the reachable "
                        "set's one-step successors.  Exact evidence "
                        "comes from the reachable-set artifact or a "
                        "host BFS; intractable configs sample "
                        "-walkers x -depth walk states (survivors are "
                        "then 'consistent with evidence only').  "
                        "Exits 12 only when exact evidence refutes a "
                        "cfg-named invariant; requires -frontend "
                        "struct")
    c.add_argument("-infer-budget", dest="inferbudget", type=int,
                   default=64,
                   help="candidate pool cap for -infer (conjectures "
                        "beyond it are counted as dropped in the "
                        "journal)")
    c.add_argument("-liveness", action="store_true",
                   help="check the declared temporal properties even when "
                        "the launch config disables them (E8); above "
                        "the host-path size threshold the device-resident "
                        "liveness engine (edge capture + tensorized "
                        "fixpoint) is picked automatically")
    c.add_argument("-liveness-host", action="store_true",
                   dest="liveness_host",
                   help="force the host-resident liveness path (explicit "
                        "graph construction) regardless of state count")
    c.add_argument("-fairness", default="wf_next",
                   choices=["wf_next", "wf_process"],
                   help="wf_next = the spec's literal WF_vars(Next); "
                        "wf_process = per-process weak fairness.  The "
                        "fairness unit of wf_process is BY CONVENTION the "
                        "FIRST bound parameter of each action (e.g. "
                        "RequestVote(self, voter) is weakly fair per "
                        "`self`); specs whose actions bind a non-process "
                        "value first get a wrong partition - reorder the "
                        "parameters or stay with wf_next")
    c.add_argument("-nodeadlock", action="store_true")
    c.add_argument("-noTool", action="store_true",
                   help="plain text output (no @!@!@ framing)")
    c.add_argument("-traceExpressions", default="", metavar="FILE",
                   help="trace-explorer expression file (one TLA+ "
                        "expression per line, `Name == Expr` to name it); "
                        "each is evaluated in every counterexample trace "
                        "state and printed as an extra conjunct (the "
                        "Toolbox MC_TE capability)")
    c.add_argument("-mutation", default="",
                   help="self-test: run with a deliberately broken "
                        "transition rule (e.g. delete_noop) to exercise "
                        "violation detection + trace reconstruction")
    args = p.parse_args(argv)
    if args.cmd == "check":
        return run_check(CheckRequest.from_args(args)).exit_code
    return 1


if __name__ == "__main__":
    sys.exit(main())
