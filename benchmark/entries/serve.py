"""Entry `serve`: an in-process jaxtlc.serve server on 127.0.0.1 with
every default of start_server, driven over HTTP with serve.client as
shipped: two requests a job, the POST and one `GET /jobs/<id>?wait=`
that the server holds until the verdict exists (PR 32; until then the
client polled every 50 ms).  A job's class options (chunk,
qcap, fpcap, ...) come from the traffic mix; set-up sends two jobs of
each class the mix has, so the engine each class runs on is warm before
the window.
"""

from __future__ import annotations

import json
import os

from loadgen import Draw


def setup(ctx):
    from jaxtlc.serve import client, start_server

    req = ctx["config"]["request"]
    with open(os.path.join(ctx["root"], req["spec"])) as f:
        spec = f.read()
    with open(os.path.join(ctx["root"], req["config"])) as f:
        cfg = f.read()
    root = os.path.join(ctx["workdir"], "serve-root")
    srv = start_server(root)
    handle = dict(client=client, srv=srv, url=srv.url, spec=spec, cfg=cfg,
                  root=root, timeout=float(req.get("timeout_s", 120.0)))
    try:
        for k in ctx["traffic"]["classes"]:
            warm = Draw(-1, None, k["name"], "warm",
                        dict(k.get("options") or {}))
            for _ in range(2):  # the second one takes the warm path itself
                run_job(handle, warm, ctx["annotate"])
    except BaseException:
        srv.shutdown()
        raise
    return handle


def run_job(handle, draw, annotate):
    client = handle["client"]
    with annotate("bench:submit"):
        # retries=0: a 429 is a failed job, not a later one
        job_id = client.submit(handle["url"], handle["spec"], handle["cfg"],
                               name=f"{draw.klass}-{draw.index}",
                               options=draw.options, tenant=draw.tenant,
                               retries=0)
    with annotate("bench:wait"):
        st = client.wait(handle["url"], job_id, timeout=handle["timeout"])
    res = dict(st.get("result") or {})
    res.pop("transcript", None)
    if st["state"] != "done":
        return dict(ok=False, job_id=job_id,
                    why=f"state {st['state']}: {st.get('error')}")
    return dict(ok=True, job_id=job_id, result=res,
                engine_wall_s=res.get("wall_s"), steps=None, fp_load=None,
                server=dict(submitted_t=st["submitted_t"],
                            started_t=st["started_t"],
                            finished_t=st["finished_t"]))


def collect(handle, records):
    """After the window: each job's run journal, and the scheduler's own
    journal (admit / dispatch times), read from the server's root."""
    for r in records:
        jid = r.get("job_id")
        path = os.path.join(handle["root"], f"{jid}.journal.jsonl")
        if jid and os.path.exists(path):
            with open(path) as f:
                r["events"] = [e for e in map(json.loads, filter(
                    str.strip, f)) if e["event"] != "level"]
    sched = os.path.join(handle["root"], "sched.journal.jsonl")
    by_job = {}
    if os.path.exists(sched):
        with open(sched) as f:
            for line in f:
                if not line.strip():
                    continue
                e = json.loads(line)
                if e.get("event") == "sched":
                    by_job.setdefault(e.get("job"), {}).setdefault(
                        e["action"], e["t"])
    for r in records:
        r["sched"] = by_job.get(r.get("job_id"), {})


def close(handle):
    handle["srv"].shutdown()
