"""Load generator for the checking service (ISSUE 9 CI tooling).

Submits N jobs against a live `jaxtlc.serve` server (or an in-process
one it starts itself), asserts the pool-reuse contract - every submit
after the first of a (spec, constants-class, geometry) is a pool HIT
and the warm path performs ZERO fresh XLA compiles - and reports
latency percentiles for the warm path plus the batched-sweep
throughput ratio.

    python tools/loadgen.py --url http://HOST:PORT --jobs 32
    python tools/loadgen.py --tiny     # self-contained; wired into
                                       # tier-1 next to the serve and
                                       # costmodel tiny smokes

The tiny mode is the serving analog of `tools/chaos.py --matrix`: one
driver invocation that exercises submit -> schedule -> pool ->
sweep-batch -> journal -> /runs end to end and fails loudly if the
warm path regresses into recompiles.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

_REPO = __file__.rsplit("/", 2)[0]
if _REPO not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO)

_SPEC = """---- MODULE LoadTiny ----
EXTENDS Naturals
CONSTANTS MAX
VARIABLES x, y

Init == /\\ x = 0
        /\\ y = 0

Up == /\\ x < MAX
      /\\ x' = x + 1
      /\\ y' = y

Flip == /\\ x > 0
        /\\ y' = 1 - y
        /\\ x' = x

Next == Up \\/ Flip

Spec == Init /\\ [][Next]_<<x, y>>

InRange == x <= MAX
====
"""

_CFG = """CONSTANT MAX = 4
SPECIFICATION
Spec
INVARIANT
InRange
"""


# a long-running chain model (depth = MAX+1 levels): the overload
# mode's "heavy" job class - wide enough in time for deterministic
# preemption windows, tiny in state space
_SLOW_SPEC = """---- MODULE LoadChain ----
EXTENDS Naturals
CONSTANTS MAX
VARIABLES x

Init == x = 0

Up == /\\ x < MAX
      /\\ x' = x + 1

Next == Up

Spec == Init /\\ [][Next]_x

InRange == x <= MAX
====
"""

_SLOW_CFG = """CONSTANT MAX = 600
SPECIFICATION
Spec
INVARIANT
InRange
"""


def _pct(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    k = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[k]


def run_load(url: str, jobs: int, sweep_jobs: int,
             out=sys.stdout) -> dict:
    """Drive `url`: one cold submit, `jobs - 1` warm resubmits, then
    `sweep_jobs` batched sweep submits.  Returns the report dict."""
    from jaxtlc.serve import client
    from jaxtlc.serve.pool import xla_compiles

    opts = dict(chunk=16, qcap=256, fpcap=1024)
    t0 = time.time()
    cold = client.check(url, _SPEC, _CFG, name="load-cold",
                        options=opts)
    cold_s = time.time() - t0
    assert cold["state"] == "done", cold
    assert cold["result"]["verdict"] == "ok", cold

    warm_lat = []
    pre_compiles = xla_compiles()
    for i in range(max(0, jobs - 1)):
        t0 = time.time()
        st = client.check(url, _SPEC, _CFG, name=f"load-warm-{i}",
                          options=opts)
        warm_lat.append(time.time() - t0)
        assert st["state"] == "done", st
        assert st["result"]["pool_hit"] is True, st
        assert st["result"]["generated"] == cold["result"]["generated"]
    fresh = xla_compiles() - pre_compiles
    assert fresh == 0, f"warm path paid {fresh} fresh XLA compiles"

    # batched sweep: K configs of the class through one dispatch
    sweep = {"const": "MAX", "lo": 1, "hi": 4}
    ids = [
        client.submit(url, _SPEC, _CFG, name=f"load-sweep-{v}",
                      constants={"MAX": 1 + (v % 4)}, sweep=sweep,
                      options=opts)
        for v in range(sweep_jobs)
    ]
    t0 = time.time()
    sts = [client.wait(url, i, timeout=600) for i in ids]
    sweep_s = time.time() - t0
    for st in sts:
        assert st["state"] == "done", st
        assert st["result"]["engine"] == "sweep", st

    stats = client.pool_stats(url)
    report = dict(
        jobs=jobs, sweep_jobs=sweep_jobs,
        cold_s=round(cold_s, 4),
        warm_p50_s=round(_pct(warm_lat, 0.50), 4),
        warm_p95_s=round(_pct(warm_lat, 0.95), 4),
        warm_fresh_xla_compiles=fresh,
        sweep_wall_s=round(sweep_s, 4),
        pool=dict(hits=stats["pool"]["hits"],
                  misses=stats["pool"]["misses"],
                  size=stats["pool"]["size"],
                  compiles=stats["pool"]["compiles"]),
        scheduler=dict(
            batches_run=stats["scheduler"]["batches_run"],
            batched_jobs=stats["scheduler"]["batched_jobs"],
        ),
    )
    out.write(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report


def run_cache(url: str, jobs: int, in_process: bool,
              out=sys.stdout) -> dict:
    """The --cache mode (ISSUE 13): N IDENTICAL submits against the
    artifact cache.  Submit 1 is the cold population run; submits 2..N
    must be verdict-tier hits - ZERO fresh XLA compiles (CompileMeter)
    AND zero engine dispatches (the pool entry's use count freezes) -
    and their p50/p95 latency is the O(HTTP) number PERF.md round 16
    compares against the 54 ms warm-pool submit."""
    from jaxtlc.serve import client
    from jaxtlc.serve.pool import xla_compiles

    opts = dict(chunk=16, qcap=256, fpcap=1024)
    t0 = time.time()
    cold = client.check(url, _SPEC, _CFG, name="cache-cold",
                        options=opts)
    cold_s = time.time() - t0
    assert cold["state"] == "done", cold
    assert cold["result"]["verdict"] == "ok", cold
    assert cold["result"]["engine"] == "pool", cold

    def pool_uses():
        # every pooled dispatch is preceded by exactly one pool lookup
        # (uses counts hits; the cold build's own run is covered by
        # the miss/build counters): frozen uses == zero dispatches
        st = client.pool_stats(url)
        return (sum(e["uses"] for e in st["pool"]["entries"])
                + st["pool"]["misses"])

    uses0 = pool_uses()
    pre = xla_compiles() if in_process else None
    hit_lat = []
    for i in range(max(0, jobs - 1)):
        t0 = time.time()
        st = client.wait(
            url,
            client.submit(url, _SPEC, _CFG, name=f"cache-hit-{i}",
                          options=opts),
        )
        hit_lat.append(time.time() - t0)
        assert st["state"] == "done", st
        assert st["result"]["engine"] == "cache", st
        assert st["result"].get("cache_hit") is True, st
        assert st["result"]["generated"] == cold["result"]["generated"]
    fresh = (xla_compiles() - pre) if in_process else 0
    assert fresh == 0, f"cache-hit path paid {fresh} fresh XLA compiles"
    dispatches = pool_uses() - uses0
    assert dispatches == 0, (
        f"cache-hit path dispatched {dispatches} engine run(s)"
    )
    stats = client.pool_stats(url)
    cache = client._get(url + "/cache")
    report = dict(
        jobs=jobs,
        cold_s=round(cold_s, 4),
        hit_p50_s=round(_pct(hit_lat, 0.50), 4),
        hit_p95_s=round(_pct(hit_lat, 0.95), 4),
        hit_fresh_xla_compiles=fresh,
        hit_engine_dispatches=dispatches,
        scheduler_cache_hits=stats["scheduler"]["cache_hits"],
        store=cache["stats"] if cache.get("enabled") else None,
    )
    out.write(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report


def run_sim_load(url: str, jobs: int, in_process: bool,
                 out=sys.stdout) -> dict:
    """The --sim mode (ISSUE 14): the smoke job class under load.
    Submit 1 cold + N-1 warm sim jobs (same spec, DIFFERENT seeds -
    the seed is a batch lane, not key material, so every resubmit
    after the first must be a pool HIT with ZERO fresh XLA compiles),
    then one folded burst submitted together to exercise the vmapped
    seed batch."""
    from jaxtlc.serve import client
    from jaxtlc.serve.pool import xla_compiles

    opts = dict(simulate=True, walkers=16, depth=32, fpcap=1024,
                nodeadlock=True)
    t0 = time.time()
    cold = client.check(url, _SPEC, _CFG, name="sim-cold",
                        options=dict(opts, simseed=0))
    cold_s = time.time() - t0
    assert cold["state"] == "done", cold
    assert cold["result"]["engine"] == "sim", cold
    assert cold["result"]["verdict"] == "ok", cold

    warm_lat = []
    pre = xla_compiles() if in_process else None
    for i in range(max(0, jobs - 1)):
        t0 = time.time()
        st = client.check(url, _SPEC, _CFG, name=f"sim-warm-{i}",
                          options=dict(opts, simseed=i + 1))
        warm_lat.append(time.time() - t0)
        assert st["state"] == "done", st
        assert st["result"]["engine"] == "sim", st
        assert st["result"]["pool_hit"] is True, st
    fresh = (xla_compiles() - pre) if in_process else 0
    assert fresh == 0, f"warm sim path paid {fresh} fresh XLA compiles"

    # a burst submitted together folds into vmapped seed batches
    ids = [client.submit(url, _SPEC, _CFG, name=f"sim-burst-{i}",
                         options=dict(opts, simseed=100 + i))
           for i in range(jobs)]
    t0 = time.time()
    sts = [client.wait(url, i, timeout=600) for i in ids]
    burst_s = time.time() - t0
    for st in sts:
        assert st["state"] == "done", st
        assert st["result"]["engine"] == "sim", st

    stats = client.pool_stats(url)
    report = dict(
        jobs=jobs,
        cold_s=round(cold_s, 4),
        sim_p50_s=round(_pct(warm_lat, 0.50), 4),
        sim_p95_s=round(_pct(warm_lat, 0.95), 4),
        sim_fresh_xla_compiles=fresh,
        burst_wall_s=round(burst_s, 4),
        transitions=cold["result"]["sim"]["transitions"],
        pool=dict(hits=stats["pool"]["hits"],
                  misses=stats["pool"]["misses"],
                  size=stats["pool"]["size"]),
        scheduler=dict(
            batches_run=stats["scheduler"]["batches_run"],
            batched_jobs=stats["scheduler"]["batched_jobs"],
        ),
    )
    out.write(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report


def run_infer_load(url: str, jobs: int, in_process: bool,
                   out=sys.stdout) -> dict:
    """The --infer mode (ISSUE 16): the inference job class under
    load.  Submit 1 cold + N-1 warm infer jobs (same spec, DIFFERENT
    seeds - the seed only drives sampled evidence, not key material,
    so every resubmit after the first must be a pool HIT with ZERO
    fresh XLA compiles)."""
    from jaxtlc.serve import client
    from jaxtlc.serve.pool import xla_compiles

    opts = dict(infer=True, inferbudget=16, walkers=16, depth=32,
                nodeadlock=True)
    t0 = time.time()
    cold = client.check(url, _SPEC, _CFG, name="infer-cold",
                        options=dict(opts, simseed=0))
    cold_s = time.time() - t0
    assert cold["state"] == "done", cold
    assert cold["result"]["engine"] == "infer", cold
    assert cold["result"]["verdict"] == "ok", cold
    funnel = cold["result"]["infer"]
    assert funnel["candidates"] > 0, funnel

    warm_lat = []
    pre = xla_compiles() if in_process else None
    for i in range(max(0, jobs - 1)):
        t0 = time.time()
        st = client.check(url, _SPEC, _CFG, name=f"infer-warm-{i}",
                          options=dict(opts, simseed=i + 1))
        warm_lat.append(time.time() - t0)
        assert st["state"] == "done", st
        assert st["result"]["engine"] == "infer", st
        assert st["result"]["pool_hit"] is True, st
    fresh = (xla_compiles() - pre) if in_process else 0
    assert fresh == 0, (
        f"warm infer path paid {fresh} fresh XLA compiles"
    )

    stats = client.pool_stats(url)
    report = dict(
        jobs=jobs,
        cold_s=round(cold_s, 4),
        infer_p50_s=round(_pct(warm_lat, 0.50), 4),
        infer_p95_s=round(_pct(warm_lat, 0.95), 4),
        infer_fresh_xla_compiles=fresh,
        candidates=funnel["candidates"],
        survivors=funnel["survivors"],
        certified=len(funnel["certified"]),
        evidence=funnel["evidence"],
        pool=dict(hits=stats["pool"]["hits"],
                  misses=stats["pool"]["misses"],
                  size=stats["pool"]["size"]),
        scheduler=dict(
            batches_run=stats["scheduler"]["batches_run"],
            batched_jobs=stats["scheduler"]["batched_jobs"],
        ),
    )
    out.write(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report


def run_overload(url: str, jobs: int, in_process: bool,
                 tiny: bool = False, out=sys.stdout) -> dict:
    """The --overload mode (ISSUE 17): the service under sustained
    over-capacity load.  Phases:

    1. clean warm latency - the regression gate against the PR 12
       54 ms warm-submit baseline (zero fresh XLA compiles asserted);
    2. priority preemption - a low-priority checkpointed heavy job is
       preempted by a high-priority arrival, requeued as a -recover
       resume, and its final counters must be BIT-FOR-BIT the
       uninterrupted reference run's (the PR 2/7 contract);
    3. the storm - a heavy "plug" job occupies the worker while a
       burst overruns the admission bound: every rejection must be a
       429 with a Retry-After hint, every accepted job must reach a
       terminal state, a deadlined job expires, a canceled job
       cancels, and a rejected submit resubmitted through the client
       backoff eventually lands;
    4. (full mode) the mixed classes - smoke, sweep, infer, and
       artifact-cache hits - ride the same overloaded server.

    Wants a server with a SMALL admission bound (the in-process
    default here is queue_bound=4; external servers should be started
    with --queue-bound 4)."""
    import os
    import tempfile

    from jaxtlc.serve import client
    from jaxtlc.serve.pool import xla_compiles

    opts = dict(chunk=16, qcap=256, fpcap=1024, noartifactcache=True)
    heavy = dict(chunk=16, qcap=256, fpcap=1024, nodeadlock=True,
                 checkpointevery=8, noartifactcache=True)
    ckdir = tempfile.mkdtemp(prefix="jaxtlc-loadgen-overload-")

    bound = client.pool_stats(url)["scheduler"]["queue_bound"]
    assert bound <= 32, (
        f"--overload wants a small admission bound (queue_bound="
        f"{bound}); start the server with --queue-bound 4"
    )

    # -- 1. clean warm latency -------------------------------------------
    t0 = time.time()
    cold = client.check(url, _SPEC, _CFG, name="over-cold",
                        options=opts)
    cold_s = time.time() - t0
    assert cold["state"] == "done", cold
    assert cold["result"]["verdict"] == "ok", cold
    warm_lat = []
    pre = xla_compiles() if in_process else None
    for i in range(max(0, jobs - 1)):
        t0 = time.time()
        st = client.check(url, _SPEC, _CFG, name=f"over-warm-{i}",
                          options=opts)
        warm_lat.append(time.time() - t0)
        assert st["state"] == "done", st
        assert st["result"]["pool_hit"] is True, st
    fresh = (xla_compiles() - pre) if in_process else 0
    assert fresh == 0, f"warm path paid {fresh} fresh XLA compiles"

    # -- 2. preemption + bit-for-bit resume ------------------------------
    ref = client.check(
        url, _SLOW_SPEC, _SLOW_CFG, name="over-ref",
        options=dict(heavy, checkpoint=os.path.join(ckdir, "ref.npz")),
        timeout=600,
    )
    assert ref["state"] == "done", ref
    assert ref["result"]["verdict"] == "ok", ref

    low = {}
    attempts = 0
    for attempt in range(3):
        attempts = attempt + 1
        low_id = client.submit(
            url, _SLOW_SPEC, _SLOW_CFG, name=f"over-low-{attempt}",
            options=dict(heavy, priority=0, checkpoint=os.path.join(
                ckdir, f"low{attempt}.npz")),
        )
        deadline = time.time() + 120
        while client.status(url, low_id)["state"] == "queued":
            assert time.time() < deadline, "heavy job never started"
            time.sleep(0.005)
        hi = client.check(url, _SPEC, _CFG, name=f"over-hi-{attempt}",
                          options=dict(opts, priority=10))
        assert hi["state"] == "done", hi
        low = client.wait(url, low_id, timeout=600)
        assert low["state"] == "done", low
        if low.get("requeues", 0) >= 1:
            break
    assert low.get("requeues", 0) >= 1, (
        f"preemption never landed in {attempts} attempt(s): {low}"
    )
    for k in ("generated", "distinct", "depth", "violation",
              "action_generated"):
        assert low["result"][k] == ref["result"][k], (
            f"resumed {k} diverged: {low['result'][k]} != "
            f"{ref['result'][k]}"
        )

    # -- 3. the storm ----------------------------------------------------
    plug_id = client.submit(
        url, _SLOW_SPEC, _SLOW_CFG, name="over-plug",
        options=dict(heavy, checkpoint=os.path.join(ckdir, "plug.npz")),
    )
    deadline = time.time() + 120
    while client.status(url, plug_id)["state"] == "queued":
        assert time.time() < deadline, "plug job never started"
        time.sleep(0.005)
    # the worker is pinned for the plug's whole wall: a deterministic
    # overload window
    exp_id = client.submit(url, _SPEC, _CFG, name="over-deadline",
                           options=dict(opts, deadline_s=0.25))
    can_id = client.submit(url, _SPEC, _CFG, name="over-cancel",
                           options=opts)
    assert client.cancel(url, can_id)["state"] == "canceled"
    accepted, rejections = [], []
    for i in range(bound + 6):
        try:
            accepted.append(
                client.submit(url, _SPEC, _CFG, name=f"over-burst-{i}",
                              options=opts, retries=0)
            )
        except client.ClientError as e:
            assert e.code == 429, f"rejection was {e.code}, not 429"
            assert (e.retry_after or 0) >= 1, (
                f"429 without a usable Retry-After: {e.retry_after}"
            )
            rejections.append(e.retry_after)
    assert rejections, "overload burst produced no 429 rejections"
    # a rejected submit THROUGH the client's 429 backoff must land
    t0 = time.time()
    retry_id = client.submit(url, _SPEC, _CFG, name="over-retry",
                             options=opts, retries=6)
    resubmit_s = time.time() - t0
    for jid in accepted + [plug_id, retry_id]:
        st = client.wait(url, jid, timeout=600)
        assert st["state"] == "done", st
    exp = client.wait(url, exp_id, timeout=30)
    assert exp["state"] == "expired", exp

    # -- 4. the mixed classes (full mode) --------------------------------
    mixed = {}
    if not tiny:
        sim = client.check(
            url, _SPEC, _CFG, name="over-sim",
            options=dict(simulate=True, walkers=16, depth=32,
                         fpcap=1024, nodeadlock=True, simseed=7),
        )
        assert sim["state"] == "done", sim
        assert sim["result"]["engine"] == "sim", sim
        sweep_ids = [
            client.submit(url, _SPEC, _CFG, name=f"over-sweep-{v}",
                          constants={"MAX": 1 + (v % 4)},
                          sweep={"const": "MAX", "lo": 1, "hi": 4},
                          options=opts)
            for v in range(4)
        ]
        sweeps = [client.wait(url, i, timeout=600) for i in sweep_ids]
        assert all(s["state"] == "done"
                   and s["result"]["engine"] == "sweep"
                   for s in sweeps), sweeps
        inf = client.check(
            url, _SPEC, _CFG, name="over-infer",
            options=dict(infer=True, inferbudget=16, walkers=16,
                         depth=32, nodeadlock=True, simseed=0),
        )
        assert inf["state"] == "done", inf
        assert inf["result"]["engine"] == "infer", inf
        mixed["mixed_classes"] = dict(sim="done", sweep=len(sweeps),
                                      infer="done")
        if in_process:
            cache_opts = dict(chunk=16, qcap=256, fpcap=1024)
            c0 = client.check(url, _SPEC, _CFG, name="over-cache-0",
                              options=cache_opts)
            c1 = client.check(url, _SPEC, _CFG, name="over-cache-1",
                              options=cache_opts)
            assert c1["result"]["engine"] == "cache", c1
            mixed["mixed_classes"]["cache"] = "hit"

    h = client.health(url)
    assert h["status"] == "ok" and h["queued"] == 0, h
    stats = client.pool_stats(url)
    report = dict(
        jobs=jobs, queue_bound=bound,
        cold_s=round(cold_s, 4),
        warm_p50_s=round(_pct(warm_lat, 0.50), 4),
        warm_p95_s=round(_pct(warm_lat, 0.95), 4),
        warm_fresh_xla_compiles=fresh,
        preempt=dict(attempts=attempts,
                     requeues=low.get("requeues", 0), parity=True),
        burst=dict(submitted=bound + 6, accepted=len(accepted),
                   rejected=len(rejections),
                   retry_after_s=[min(rejections), max(rejections)],
                   resubmit_backoff_s=round(resubmit_s, 4)),
        expired=1, canceled=1,
        counters=stats["scheduler"]["sched"],
        **mixed,
    )
    out.write(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="loadgen")
    p.add_argument("--url", default="",
                   help="a live jaxtlc.serve server; default: start "
                        "one in-process")
    p.add_argument("--jobs", type=int, default=8,
                   help="plain submits of one model (1 cold + N-1 warm)")
    p.add_argument("--sweep-jobs", type=int, default=4,
                   help="sweep submits folded into batched dispatches")
    p.add_argument("--sim", action="store_true",
                   help="smoke job class mode (ISSUE 14): 1 cold + "
                        "N-1 warm sim submits (different seeds, same "
                        "warm engine - zero fresh XLA compiles "
                        "asserted) plus a folded seed-batch burst; "
                        "reports warm sim p50/p95")
    p.add_argument("--infer", action="store_true",
                   help="inference job class mode (ISSUE 16): 1 cold "
                        "+ N-1 warm infer submits (different evidence "
                        "seeds, same warm engine - zero fresh XLA "
                        "compiles asserted); reports warm infer "
                        "p50/p95 and the candidate funnel")
    p.add_argument("--cache", action="store_true",
                   help="incremental re-checking mode (ISSUE 13): N "
                        "identical submits; 1 cold population run, "
                        "N-1 verdict-tier hits asserted to perform "
                        "ZERO fresh XLA compiles and ZERO engine "
                        "dispatches; reports hit p50/p95.  In-process "
                        "servers get a temp store so the run is "
                        "self-contained")
    p.add_argument("--overload", action="store_true",
                   help="overload mode (ISSUE 17): warm-latency gate, "
                        "priority preemption with bit-for-bit resume, "
                        "an admission-bound storm (429 + Retry-After "
                        "on every rejection, client backoff resubmit), "
                        "deadline expiry + cancel, and - without "
                        "--tiny - the mixed job classes on the same "
                        "overloaded server.  In-process servers start "
                        "with queue_bound=4")
    p.add_argument("--tiny", action="store_true",
                   help="tier-1 smoke: in-process server, 4 plain + 4 "
                        "sweep jobs, pool-reuse + zero-compile "
                        "assertions (with --cache: 4 identical "
                        "submits through the artifact cache; with "
                        "--overload: the storm matrix minus the mixed "
                        "classes)")
    args = p.parse_args(argv)
    if args.tiny:
        args.jobs, args.sweep_jobs, args.url = 4, 4, ""

    srv = None
    url = args.url
    token = None
    try:
        if not url:
            if args.cache or args.overload:
                # self-contained store: the assertions need a cache
                # that starts empty and nothing else writes to
                import tempfile

                from jaxtlc.struct import artifacts as arts

                token = arts.configure(
                    tempfile.mkdtemp(prefix="jaxtlc-loadgen-cache-")
                )
            from jaxtlc.serve.server import start_server

            srv = start_server(
                sweep_width=4,
                **(dict(queue_bound=4) if args.overload else {}),
            )
            url = srv.url
        if args.overload:
            report = run_overload(url, args.jobs,
                                  in_process=srv is not None,
                                  tiny=args.tiny)
            ok = (report["warm_fresh_xla_compiles"] == 0
                  and report["burst"]["rejected"] >= 1
                  and report["preempt"]["requeues"] >= 1)
            print(f"loadgen {'OK' if ok else 'FAILED'}: overload - "
                  f"{report['burst']['accepted']} accepted + "
                  f"{report['burst']['rejected']} rejected (429 + "
                  f"Retry-After) of {report['burst']['submitted']} "
                  f"burst submits, preempted heavy job resumed "
                  f"bit-for-bit after {report['preempt']['requeues']} "
                  f"requeue(s), 1 expired + 1 canceled, warm p50 "
                  f"{report['warm_p50_s'] * 1000:.1f} ms / p95 "
                  f"{report['warm_p95_s'] * 1000:.1f} ms, 0 fresh "
                  f"compiles on the warm path")
            return 0 if ok else 1
        if args.sim:
            report = run_sim_load(url, args.jobs,
                                  in_process=srv is not None)
            ok = (report["sim_fresh_xla_compiles"] == 0
                  and report["pool"]["hits"] >= args.jobs - 1)
            print(f"loadgen {'OK' if ok else 'FAILED'}: "
                  f"{args.jobs} sim submits (1 cold + "
                  f"{args.jobs - 1} warm) + {args.jobs} burst, "
                  f"warm sim p50 {report['sim_p50_s'] * 1000:.1f} ms "
                  f"/ p95 {report['sim_p95_s'] * 1000:.1f} ms, "
                  f"0 fresh compiles on the warm path, "
                  f"{report['scheduler']['batched_jobs']} jobs "
                  f"through {report['scheduler']['batches_run']} "
                  "dispatches")
            return 0 if ok else 1
        if args.infer:
            report = run_infer_load(url, args.jobs,
                                    in_process=srv is not None)
            ok = (report["infer_fresh_xla_compiles"] == 0
                  and report["pool"]["hits"] >= args.jobs - 1)
            print(f"loadgen {'OK' if ok else 'FAILED'}: "
                  f"{args.jobs} infer submits (1 cold + "
                  f"{args.jobs - 1} warm), "
                  f"{report['candidates']} candidates -> "
                  f"{report['survivors']} survive -> "
                  f"{report['certified']} certified "
                  f"[{report['evidence']} evidence], "
                  f"warm infer p50 "
                  f"{report['infer_p50_s'] * 1000:.1f} ms "
                  f"/ p95 {report['infer_p95_s'] * 1000:.1f} ms, "
                  f"0 fresh compiles on the warm path")
            return 0 if ok else 1
        if args.cache:
            report = run_cache(url, args.jobs, in_process=srv is not None)
            ok = (report["hit_fresh_xla_compiles"] == 0
                  and report["hit_engine_dispatches"] == 0
                  and report["scheduler_cache_hits"] >= args.jobs - 1)
            print(f"loadgen {'OK' if ok else 'FAILED'}: "
                  f"{args.jobs} identical submits, 1 cold + "
                  f"{args.jobs - 1} verdict-tier hits, hit p50 "
                  f"{report['hit_p50_s'] * 1000:.1f} ms / p95 "
                  f"{report['hit_p95_s'] * 1000:.1f} ms, 0 fresh "
                  f"compiles and 0 engine dispatches on the hit path")
            return 0 if ok else 1
        report = run_load(url, args.jobs, args.sweep_jobs)
    finally:
        if srv is not None:
            srv.shutdown()
        if token is not None:
            from jaxtlc.struct import artifacts as arts

            arts.restore(token)
    ok = (report["warm_fresh_xla_compiles"] == 0
          and report["pool"]["hits"] >= args.jobs - 1)
    print(f"loadgen {'OK' if ok else 'FAILED'}: "
          f"{args.jobs} plain + {args.sweep_jobs} sweep jobs, "
          f"warm p50 {report['warm_p50_s'] * 1000:.1f} ms / "
          f"p95 {report['warm_p95_s'] * 1000:.1f} ms, "
          f"0 fresh compiles on the warm path, "
          f"{report['scheduler']['batched_jobs']} jobs through "
          f"{report['scheduler']['batches_run']} sweep dispatches")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
