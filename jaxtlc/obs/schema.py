"""Versioned schema of the run journal (the telemetry contract).

Every line of a run journal (obs.journal.RunJournal) is one JSON event
validated against this module BEFORE it is written, and the tier-1
golden test re-validates every line of a real run's journal after the
fact - so event-shape drift is a loud failure in both directions
(producer and consumer), never a silently-changed dashboard.

The schema is deliberately dependency-free (no jsonschema package in
the image): each event kind declares its REQUIRED fields with python
type tuples; extra fields are allowed (views ignore what they don't
know), missing/badly-typed required fields raise JournalSchemaError.

Bump SCHEMA_VERSION whenever a required field is added, removed, or
changes meaning; readers (tools/tlcstat.py, obs.trace) check it and
refuse journals from the future.
"""

from __future__ import annotations

SCHEMA_VERSION = 1

_NUM = (int, float)
_STR = (str,)
_BOOL = (bool,)
_OPT_STR = (str, type(None))
_OPT_NUM = (int, float, type(None))

# common envelope fields stamped by RunJournal.event on every line
ENVELOPE = {
    "v": (int,),  # SCHEMA_VERSION of the writer
    "t": _NUM,  # host wall-clock (epoch seconds) at write time
    "event": _STR,  # the event kind (a key of EVENTS)
}

# event kind -> {required field: accepted types}
EVENTS = {
    # -- run lifecycle -----------------------------------------------------
    # the run manifest: first event of a fresh journal
    "run_start": {"version": _STR, "workload": _STR, "engine": _STR,
                  "device": _STR, "params": (dict,)},
    # -recover appended to an existing journal (one continuous history)
    "run_resume": {"version": _STR, "path": _STR},
    # one supervised segment fenced: host-observed dispatch/fence times.
    # Written last of its fence's events (after the `progress`, `level`
    # and `coverage` rows read back there), so the supervisor's carries
    # `readback_s` (extra field): the host wall of that readback, device
    # reads and journal writes, behind `t_fence`.  views.phase_totals
    # folds `wall_s` / `readback_s` into the phases `device` / `readback`
    "segment": {"index": _NUM, "t_dispatch": _NUM, "t_fence": _NUM,
                "wall_s": _NUM},
    # one BFS level completed (decoded from the device counter ring).
    # Pod journals (jaxtlc.dist, ISSUE 20) tag these with an extra
    # `host` field and PARTIAL counters - each host decodes its own
    # ring, and obs.views.fold_pod_levels sums the {base}.hN siblings
    # back to pod-global rows (last row per (host, level) wins: the
    # ring re-records the final level on empty-queue trailing steps)
    "level": {"level": _NUM, "generated": _NUM, "distinct": _NUM,
              "queue": _NUM, "bodies": _NUM, "expanded": _NUM},
    # the TLC 2200 Progress-line source (segment-boundary counters)
    "progress": {"depth": _NUM, "generated": _NUM, "distinct": _NUM,
                 "queue": _NUM},
    # -- resilience --------------------------------------------------------
    "checkpoint": {"path": _STR, "seconds": _NUM, "label": _STR},
    "ckpt_write_failed": {"error": _STR},
    "ckpt_fallback": {"path": _STR, "error": _STR},
    "recovery": {"path": _STR, "depth": _NUM, "generated": _NUM,
                 "distinct": _NUM, "queue": _NUM},
    "regrow": {"resource": _STR, "old": _NUM, "new": _NUM,
               "violation": _STR, "seconds": _NUM},
    "retry": {"attempt": _NUM, "delay_s": _NUM, "error": _STR},
    "fault": {"kind": _STR, "at": _NUM},
    "interrupted": {"signum": _OPT_NUM, "path": _OPT_STR,
                    "generated": _NUM, "distinct": _NUM, "queue": _NUM,
                    "wall_s": _NUM},
    # one per degradation-ladder transition (resil.supervisor): rung in
    # ("regrow", "spill", "shrink", "oom", "halt"), or "widen" from
    # api._run_check_struct (a compacted struct step rebuilt with twice
    # the slots, struct.cache.widen_slots)
    "degrade": {"rung": _STR, "resource": _STR, "action": _STR,
                "reason": _STR},
    # host spill tier lifecycle (engine.spill): phase in
    # ("activate", "flush"); resident = device-tier occupancy after,
    # spilled = host-store count, hits/probes = cumulative host traffic
    "spill": {"phase": _STR, "resident": _NUM, "spilled": _NUM,
              "capacity": _NUM, "hits": _NUM, "probes": _NUM},
    # ladder rung 4: capacity unrecoverable, final checkpoint written
    # (or path None = progress kept only in this journal), resume me
    "exhausted": {"resource": _STR, "path": _OPT_STR,
                  "generated": _NUM, "distinct": _NUM, "queue": _NUM,
                  "wall_s": _NUM},
    # -- verdicts ----------------------------------------------------------
    "violation": {"code": _NUM, "name": _STR},
    # one per temporal property of a struct check (ISSUE 41), before
    # the `final` event: its verdict, the route that gave it (`device`:
    # live.check.check_struct_properties; `host`: struct.oracle), and
    # the fairness it was judged under, [[A, [labels]], ...] as the
    # SPECIFICATION formula states it.  The device route adds its
    # counters (extra fields `live_states` .. `live_host_bytes`:
    # live.check.LIVE_COUNTERS)
    "liveness": {"property": _STR, "holds": _BOOL, "route": _STR,
                 "fairness": (list,)},
    # one per action property of a struct check (a cfg PROPERTY `I /\\
    # [][A]_v`, ISSUE 48) that holds, before the `final` event: the
    # safety search judged it itself (`route` "device": the expand
    # stage, on every generated edge).  `edges` = the edges judged,
    # `moved` = those on which the subscript changed, `init_states` =
    # the initial states I was judged on; `formula`, `src_cols` extra
    "action_property": {"property": _STR, "holds": _BOOL, "route": _STR,
                        "edges": _NUM, "moved": _NUM,
                        "init_states": _NUM},
    # the structured final event: EVERY run (clean, violated, interrupted,
    # progress-lost) ends its journal with exactly one of these.  Mesh
    # runs add shard_distinct (extra field): per-device table occupancy
    "final": {"verdict": _STR, "generated": _NUM, "distinct": _NUM,
              "depth": _NUM, "queue": _NUM, "wall_s": _NUM,
              "interrupted": _BOOL},
    # -- device coverage plane (obs.coverage, ISSUE 11) --------------------
    # one per segment fence with coverage movement: nonzero per-site
    # visit DELTAS since the previous event (cumulative totals are the
    # fold of all deltas - obs.coverage.coverage_from_events), plus the
    # visited-site header.  An event with saturated=true (extra field)
    # is the "no new site for N levels" signal.  Pod journals carry a
    # `host` field with per-host partial deltas; coverage_from_events
    # folds siblings into one summed site table (visited/saturation
    # recomputed from the folded totals)
    "coverage": {"visited": _NUM, "sites": _NUM, "delta": (dict,)},
    # -- phase attribution -------------------------------------------------
    # no writer since ISSUE 37 (a segment's walls are on its `segment`
    # event); the kind stays so that older journals still validate
    "phase": {"scope": _STR, "index": _NUM, "phase": _STR,
              "wall_s": _NUM},
    # -- host spans (obs.spans) --------------------------------------------
    # ONE per check, right before its `final` event: the recorder's
    # spans of this check that have closed by then, as rows
    # [name, t0 (epoch s), dur_s, parent_index] (parent_index -1 = the
    # parent is still open - `check`, `sched.run` - or is not a row).
    # views.phase_totals folds them into the per-phase totals by name;
    # the trace exporter renders them as host slices.  `attrs` (extra
    # field): {str(row index): the span's attributes}, where it has any
    # (`build`: engine_cache hit | miss | off)
    "spans": {"rows": (list,)},
    # -- device time by scope (obs.scopes, ISSUE 37) -----------------------
    # ONE per check run under `-xprof DIR`, written when the profiler
    # has stopped: the trace reduced by `jaxtlc.*` scope.  t0 / t1 = the
    # profiled interval on the recorder's clock (time.time()); window_s
    # / busy_s in device seconds (mean over n_devices); scopes = rows
    # {scope, own_s, pct_of_busy, incl_s, events, top}, longest first,
    # the rows "unscoped" and "unmatched" among them, their seconds
    # also unscoped_s / unmatched_s; fallback_s = the scoped seconds
    # placed through a fusion's agreeing instructions; chains =
    # inclusive seconds by chain.  Extra fields: devices (a mesh),
    # sidecar (the tables' path), tables_s / reduce_s (what the tables
    # and the reduction cost)
    "device_scopes": {"t0": _NUM, "t1": _NUM, "window_s": _NUM,
                      "busy_s": _NUM, "n_devices": _NUM,
                      "unscoped_s": _NUM, "unmatched_s": _NUM,
                      "fallback_s": _NUM, "scopes": (list,),
                      "chains": (dict,)},
    # -- preflight analysis (jaxtlc.analysis) ------------------------------
    # one event per finding, severity in ("error", "warning", "info")
    "analysis": {"layer": _STR, "check": _STR, "severity": _STR,
                 "subject": _STR, "detail": _STR},
    # one per preflight run: the banner-level totals
    "analysis_summary": {"name": _STR, "findings": _NUM,
                         "errors": _NUM, "warnings": _NUM,
                         "wall_s": _NUM},
    # -- incremental re-checking (struct.artifacts, ISSUE 13) --------------
    # one per artifact-cache decision: tier in ("verdict", "reach"),
    # outcome in ("hit", "miss", "write", "bypass", "skip", "corrupt"),
    # key = the content-address digest.  A "hit" on the verdict tier
    # means the run's result was replayed from the cache (no engine was
    # built); on the reach tier it means BFS was skipped and only the
    # invariants were re-evaluated over the stored reachable set
    "cache": {"tier": _STR, "outcome": _STR, "key": _STR},
    # -- simulation tier (jaxtlc.sim, ISSUE 14) ----------------------------
    # phase in ("progress", "summary", "replay"): progress rows at the
    # supervised driver's segment fences, one summary per run (extra
    # fields: seed, distinct_est, fp_saturated, halted, depth_hist - a
    # [steps, lanes] histogram of final walk depths), and one replay
    # row when a violating lane was re-walked host-side (extra fields:
    # lane, violation).  `steps` is the walk cursor, `transitions` the
    # cumulative transitions taken across all lanes
    "sim": {"phase": _STR, "walkers": _NUM, "depth": _NUM,
            "steps": _NUM, "transitions": _NUM},
    # one inference progress row: a filter round (phase "round", extra
    # fields: round, evidence, n_states) or the run summary (phase
    # "summary", extra fields: certified_names, evidence, n_states,
    # dropped).  `candidates` is the conjectured pool size, `killed`
    # the cumulative evidence refutations, `certified` the survivors
    # with a machine-checked inductive basis
    "infer": {"phase": _STR, "candidates": _NUM, "killed": _NUM,
              "survivors": _NUM, "certified": _NUM},
    # -- state-space reduction (engine.reduce, ISSUE 18) -------------------
    # one per reduced run, before the final event: what the symmetry/
    # POR reduction bought.  states_pruned = transitions the singleton
    # ample sets cut pre-dedup, ample_hit_rate = pruned/(generated+
    # pruned), orbit_factor = the group order (product of |S|! over
    # the reduced sets; 1 = symmetry off or no realisable set).  Extra
    # fields: symmetry/por (resolved bools), symmetric_sets,
    # dropped_sets, safe_actions
    "reduce": {"states_pruned": _NUM, "ample_hit_rate": _NUM,
               "orbit_factor": _NUM, "generated": _NUM,
               "distinct": _NUM},
    # -- multi-host pods (jaxtlc.dist, ISSUE 19) ---------------------------
    # host membership + per-host shard telemetry on the writing HOST's
    # journal: phase in ("join", "leave", "reshard", "stats"); host =
    # the jax process index, hosts = pod width at the event.  "stats"
    # rows carry the per-host gauges obs.views surfaces as
    # jaxtlc_host_* (extra fields: shard_occupancy, spill_bytes,
    # exchange_us); "leave" rows carry the checkpoint path; "reshard"
    # rows carry old_hosts/new_hosts
    "pod": {"phase": _STR, "host": _NUM, "hosts": _NUM},
    # -- serve-plane scheduling (serve.scheduler, ISSUE 17) ----------------
    # one per scheduler decision, written to the scheduler's own
    # journal (root/sched.journal.jsonl): action in ("admit", "reject",
    # "expire", "preempt", "requeue", "retry", "quarantine", "cancel",
    # "dispatch").  Extra fields carry the decision's facts (tenant,
    # priority, reason, retry_after_s, queued = queue depth after)
    "sched": {"action": _STR, "job": _STR},
    # -- derived artifacts -------------------------------------------------
    "trace_export": {"path": _STR, "events": _NUM},
}

# the verdict vocabulary of the "final" event.  The last three are
# scheduler-terminal verdicts (ISSUE 17): a job that never got (or
# never finished) an engine run still ends its journal with exactly one
# final event - deadline-expired, client-canceled, or breaker-
# quarantined - so SSE followers terminate on every outcome
VERDICTS = ("ok", "violation", "liveness_violation", "interrupted",
            "exhausted", "error", "expired", "canceled", "quarantined")


class JournalSchemaError(ValueError):
    """A journal event does not satisfy the versioned schema."""


def validate_event(ev: dict) -> dict:
    """Validate one journal event dict; returns it unchanged on success.

    Checks the envelope (v/t/event), that the kind is known, and that
    every required field of the kind is present with an accepted type.
    Extra fields pass - views ignore what they don't know."""
    if not isinstance(ev, dict):
        raise JournalSchemaError(f"event is not an object: {ev!r}")
    for field, types in ENVELOPE.items():
        if field not in ev:
            raise JournalSchemaError(f"event missing envelope {field!r}: {ev!r}")
        if not isinstance(ev[field], types) or isinstance(ev[field], bool):
            # bool is an int subclass; envelope fields are never bools
            raise JournalSchemaError(
                f"envelope {field!r} has type {type(ev[field]).__name__}, "
                f"want one of {[t.__name__ for t in types]}: {ev!r}"
            )
    if ev["v"] > SCHEMA_VERSION:
        raise JournalSchemaError(
            f"journal schema v{ev['v']} is newer than this reader "
            f"(v{SCHEMA_VERSION})"
        )
    kind = ev["event"]
    spec = EVENTS.get(kind)
    if spec is None:
        raise JournalSchemaError(f"unknown event kind {kind!r}: {ev!r}")
    for field, types in spec.items():
        if field not in ev:
            raise JournalSchemaError(
                f"{kind!r} event missing required field {field!r}: {ev!r}"
            )
        v = ev[field]
        if isinstance(v, bool) and bool not in types:
            raise JournalSchemaError(
                f"{kind!r} field {field!r} is bool, want "
                f"{[t.__name__ for t in types]}: {ev!r}"
            )
        if not isinstance(v, types):
            raise JournalSchemaError(
                f"{kind!r} field {field!r} has type {type(v).__name__}, "
                f"want one of {[t.__name__ for t in types]}: {ev!r}"
            )
    if kind == "spans":
        for row in ev["rows"]:
            if not (isinstance(row, (list, tuple)) and len(row) == 4
                    and isinstance(row[0], str)
                    and all(isinstance(x, _NUM)
                            and not isinstance(x, bool)
                            for x in row[1:])):
                raise JournalSchemaError(
                    f"'spans' row is not [name, t0, dur_s, "
                    f"parent_index]: {row!r}"
                )
    if kind == "final" and ev["verdict"] not in VERDICTS:
        raise JournalSchemaError(
            f"final verdict {ev['verdict']!r} not in {VERDICTS}"
        )
    return ev
