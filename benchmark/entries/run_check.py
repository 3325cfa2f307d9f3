"""Entry `run_check`: api.run_check on a model's MC.cfg, as `cli check`
calls it - resolution, preflight, supervisor route, transcript, one run
journal per check.  The configuration's `request` holds CheckRequest
fields (frontend, sharded, chunk, ...), so a mesh or struct
configuration is data.
"""

from __future__ import annotations

import io
import json
import os


def setup(ctx):
    from jaxtlc.api import CheckRequest, run_check

    req = dict(ctx["config"]["request"])
    req["config"] = os.path.join(ctx["root"], req["config"])
    handle = dict(CheckRequest=CheckRequest, run_check=run_check, req=req,
                  workdir=ctx["workdir"], n=0)
    run_job(handle, None, ctx["annotate"])  # the untimed warm job
    return handle


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
