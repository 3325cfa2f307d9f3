"""Entry `check_with_checkpoints`: the call chip_smoke.py L2 makes for a
scaled KubeAPI configuration -
engine.checkpoint.check_with_checkpoints(make_scaled(...), geometry) -
with no run journal: the job's facts are the CheckResult alone (config
`"journal": false`).  (Since PR 27 the hand frontend also takes scaled
constants from an MC.cfg through api.run_check; this cell keeps the
direct call.)

Every call re-traces and re-lowers its segment program and takes the
executable from the persistent cache; the caller's wall includes that.
"""

from __future__ import annotations

# set-up's warm job is one segment, not a check: its `loop` span says
# nothing of a whole job's, and a traced run places no slice by it
WARM_JOB_IS_WHOLE = False


def setup(ctx):
    from jaxtlc.config import make_scaled
    from jaxtlc.engine.checkpoint import check_with_checkpoints

    req = dict(ctx["config"]["request"])
    model = make_scaled(**req.pop("make_scaled"))
    handle = dict(call=check_with_checkpoints, model=model, kw=req)
    # warm: one segment through the cell's own program (the cold compile
    # of a first run lands here, in set-up)
    check_with_checkpoints(model, **dict(req, max_segments=1))
    return handle


def run_job(handle, draw, annotate):
    with annotate("bench:check_with_checkpoints"):
        r = handle["call"](handle["model"], **handle["kw"])
    steps = r.iterations * handle["kw"]["ckpt_every"]
    load = r.fp_occupancy  # this driver leaves it unset: the definition
    if load is None:
        load = r.distinct / handle["kw"]["fp_capacity"]
    return dict(
        ok=True,
        result=dict(
            verdict="ok" if r.violation == 0 else r.violation_name,
            generated=r.generated, distinct=r.distinct, depth=r.depth,
            queue=r.queue_left, action_generated=r.action_generated,
            engine="single"),
        events=None,
        engine_wall_s=r.wall_s, steps=steps, fp_load=load,
    )


def collect(handle, records):
    pass


def close(handle):
    pass
