"""Entry `run_check_live`: entry `run_check`'s job - api.run_check on a
model's MC.cfg as `cli check` calls it - for a configuration whose cfg
has a PROPERTY line, and before a job returns it holds the check's
temporal half to the configuration's `pins.live` by exact equality:

* the run journal has one `liveness` event for every property the pins
  name, with the pinned verdict, from the device route, judged under
  exactly the pinned fairness ([[A, [labels]], ...] as the
  SPECIFICATION formula states it);
* that event's counters and the caller's CheckResult both equal the
  plain reference's graph: states, successor rows, state-changing rows,
  rows of the fairness constraints' actions, the states of H and of P,
  and the P-states the fair fixpoint kept (0: the property holds).

Any difference, or no such event, returns `ok: False` with the numbers
beside their pins in `why`, which gate.py counts as `no verdict`: a
program that skips the property, checks it under another fairness or on
a partial graph is `correct: false`.

Set-up probes for the struct liveness entry point BEFORE the warm job: a
program without it (a commit before PR 41) would grind through the host
oracle's Python loop, so the cell fails there at once, with a line that
says so."""

from __future__ import annotations

import io
import json
import os

# pins.live key -> the counter's name on the `liveness` event and on
# CheckResult
COUNTERS = dict(graph_states="live_states", graph_edges="live_edges",
                changed_edges="live_changed_edges",
                fair_edges="live_fair_edges", h_states="live_h_states",
                p_states="live_p_states", survivors="live_survivors")


def setup(ctx):
    try:
        from jaxtlc.live import check_struct_properties  # noqa: F401
    except ImportError:
        raise SystemExit(
            "benchmark/entries/run_check_live.py: this program has no "
            "struct liveness entry point (jaxtlc.live."
            "check_struct_properties): it would check the PROPERTY in a "
            "Python loop on the host, so the cell does not run on it")
    from jaxtlc.api import CheckRequest, run_check

    req = dict(ctx["config"]["request"])
    req["config"] = os.path.join(ctx["root"], req["config"])
    handle = dict(CheckRequest=CheckRequest, run_check=run_check, req=req,
                  workdir=ctx["workdir"], n=0,
                  live=ctx["config"]["pins"]["live"])
    run_job(handle, None, ctx["annotate"])  # the untimed warm job
    return handle


def live_findings(pins: dict, events: list, result) -> list:
    """Where the check's temporal half differs from `pins` (the
    configuration's pins.live): texts, empty where it does not."""
    bad = []
    by_name = {e.get("property"): e for e in events
               if e.get("event") == "liveness"}
    for name, want in pins["properties"].items():
        ev = by_name.get(name)
        if ev is None:
            bad.append(f"no liveness event for {name}")
            continue
        if ev.get("holds") is not (want == "holds"):
            bad.append(f"{name} holds={ev.get('holds')}, want {want}")
        if ev.get("route") != "device":
            bad.append(f"{name} on the {ev.get('route')} route, want "
                       "device")
        if ev.get("fairness") != pins["fairness"]:
            bad.append(f"{name} judged under {ev.get('fairness')}, want "
                       f"{pins['fairness']}")
        for key, counter in COUNTERS.items():
            if ev.get(counter) != pins[key]:
                bad.append(f"{name} {counter} {ev.get(counter)}, want "
                           f"{pins[key]}")
    if len(pins["properties"]) == 1:  # the result's sums are the one's
        for key, counter in COUNTERS.items():
            got = getattr(result, counter, None)
            if got != pins[key]:
                bad.append(f"result {counter} {got}, want {pins[key]}")
    return bad


def run_job(handle, draw, annotate):
    handle["n"] += 1
    journal = os.path.join(handle["workdir"], f"check-{handle['n']}.jsonl")
    out = io.StringIO()
    with annotate("bench:run_check"):
        outcome = handle["run_check"](handle["CheckRequest"](
            journal=journal, out=out, err=out, **handle["req"]))
    r = outcome.result
    with open(journal) as f:
        events = [json.loads(line) for line in f if line.strip()]
    os.unlink(journal)
    if r is None:
        return dict(ok=False, events=events,
                    why=f"exit {outcome.exit_code}: {out.getvalue()[-300:]}")
    bad = live_findings(handle["live"], events, r)
    if bad:
        return dict(ok=False, events=events,
                    why="liveness differs from pins.live: "
                        + "; ".join(bad[:6]))
    final = next((e for e in events if e["event"] == "final"), {})
    return dict(
        ok=True,
        result=dict(verdict=outcome.verdict, generated=r.generated,
                    distinct=r.distinct, depth=r.depth, queue=r.queue_left,
                    action_generated=r.action_generated,
                    engine=next((e["engine"] for e in events
                                 if e["event"] == "run_start"), None)),
        events=[e for e in events if e["event"] != "level"],
        engine_wall_s=final.get("wall_s"), steps=None,
        fp_load=r.fp_occupancy,
    )


def collect(handle, records):
    pass


def close(handle):
    pass
