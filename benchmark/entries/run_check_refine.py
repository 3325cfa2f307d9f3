"""Entry `run_check_refine`: entry `run_check`'s job - api.run_check on a
model's MC.cfg as `cli check` calls it - for a configuration whose cfg
states a REFINEMENT as its PROPERTY (a specification `I /\\ [][A]_v`:
an action property), and before a job returns it holds the check's
refinement half to the configuration's `pins.refine` by exact equality:

* the run journal has one `action_property` event for every property
  the pins name, with the pinned verdict, from the device route (the
  engine's expand stage judged every generated edge itself);
* that event's counters and the caller's CheckResult both equal the
  plain reference's: `edges` (every successor generated: generated less
  the initial states), `moved` (the edges on which the subscript
  changed, where A itself decides) and `init_states` (where I was
  judged).

Any difference, or no such event, returns `ok: False` with the numbers
beside their pins in `why`, which gate.py counts as `no verdict`: a
program that skips the property, judges it on the distinct states'
first edges alone, or on another route, is `correct: false`, not fast.

Set-up probes for the action-property seam BEFORE the warm job: a
program without it (a commit before PR 48) cannot load the model's
`INSTANCE` and would call the PROPERTY "skipped" if it could, so the
cell fails there at once, with a line that says so."""

from __future__ import annotations

import io
import json
import os

# pins.refine key -> the counter's name on CheckResult (the
# `action_property` event carries the pins' own keys)
COUNTERS = dict(edges="action_prop_edges", moved="action_prop_moved",
                init_states="action_prop_init_states")


def setup(ctx):
    try:
        from jaxtlc.engine.backend import ActionPropSeam  # noqa: F401
    except ImportError:
        raise SystemExit(
            "benchmark/entries/run_check_refine.py: this program has no "
            "action-property seam (jaxtlc.engine.backend.ActionPropSeam): "
            "it would skip the cfg's refinement PROPERTY and read `ok`, "
            "so the cell does not run on it")
    from jaxtlc.api import CheckRequest, run_check

    req = dict(ctx["config"]["request"])
    req["config"] = os.path.join(ctx["root"], req["config"])
    handle = dict(CheckRequest=CheckRequest, run_check=run_check, req=req,
                  workdir=ctx["workdir"], n=0,
                  refine=ctx["config"]["pins"]["refine"])
    run_job(handle, None, ctx["annotate"])  # the untimed warm job
    return handle


def refine_findings(pins: dict, events: list, result) -> list:
    """Where the check's refinement half differs from `pins` (the
    configuration's pins.refine): texts, empty where it does not."""
    bad = []
    mine = [e for e in events if e.get("event") == "action_property"]
    by_name = {e.get("property"): e for e in mine}
    if len(mine) != len(by_name):
        bad.append(f"{len(mine)} action_property events for "
                   f"{len(by_name)} properties")
    for name, want in pins["properties"].items():
        ev = by_name.get(name)
        if ev is None:
            bad.append(f"no action_property event for {name}")
            continue
        if ev.get("holds") is not (want == "holds"):
            bad.append(f"{name} holds={ev.get('holds')}, want {want}")
        if ev.get("route") != "device":
            bad.append(f"{name} on the {ev.get('route')} route, want "
                       "device")
        for key in COUNTERS:
            if ev.get(key) != pins[key]:
                bad.append(f"{name} {key} {ev.get(key)}, want {pins[key]}")
    if tuple(getattr(result, "action_prop_names", None) or ()) != tuple(
            pins["properties"]):
        bad.append(f"result action_prop_names "
                   f"{getattr(result, 'action_prop_names', None)}, want "
                   f"{list(pins['properties'])}")
    if len(pins["properties"]) == 1:  # the result's sums are the one's
        for key, counter in COUNTERS.items():
            got = getattr(result, counter, None)
            if got != pins[key]:
                bad.append(f"result {counter} {got}, want {pins[key]}")
    skipped = getattr(result, "properties_skipped", None)
    if skipped:
        bad.append(f"properties skipped: {list(skipped)}")
    return bad


def run_job(handle, draw, annotate):
    handle["n"] += 1
    journal = os.path.join(handle["workdir"], f"check-{handle['n']}.jsonl")
    out = io.StringIO()
    with annotate("bench:run_check"):
        outcome = handle["run_check"](handle["CheckRequest"](
            journal=journal, out=out, err=out, **handle["req"]))
    r = outcome.result
    events = []
    if os.path.exists(journal):
        with open(journal) as f:
            events = [json.loads(line) for line in f if line.strip()]
        os.unlink(journal)
    if r is None:
        return dict(ok=False, events=events,
                    why=f"exit {outcome.exit_code}: {out.getvalue()[-300:]}")
    bad = refine_findings(handle["refine"], events, r)
    if bad:
        return dict(ok=False, events=events,
                    why="the refinement differs from pins.refine: "
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
