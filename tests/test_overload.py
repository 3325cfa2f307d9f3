"""Overload control-plane tests (ISSUE 17) - ZERO engine compiles.

Scheduling policy is host Python, so it is tested at policy speed: ONE
module-scoped CheckServer over a STUB engine pool, with the
scheduler's `_run_batch` replaced by a name-keyed stub runner
(`slow:<s>-*` sleeps, `boom*` raises a deterministic non-transient
error, `die-once*` raises a TransientFault on its first dispatch
only).  Every request still rides the real HTTP surface - admission
429s with Retry-After headers, DELETE cancels, /health, the sched
journal, SSE termination - but no dispatch ever compiles or runs an
engine, and a module-wide CompileMeter guard proves it.

The real-engine halves of ISSUE 17 (supervised preemption with
bit-for-bit resume parity, running-deadline expiry, running cancel)
live in tests/test_service.py against its shared warm server.
"""

import json
import os
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from jaxtlc.obs import journal as obs_journal
from jaxtlc.resil.faults import TransientFault
from jaxtlc.serve import client
from jaxtlc.serve.scheduler import TERMINAL_STATES, DrainTimeout, Job
from jaxtlc.serve.server import CheckServer

OK_SPEC = ("---- MODULE OverloadOK ----\nVARIABLE x\nInit == x = 0\n"
           "Next == x' = x\n====\n")
BOOM_SPEC = ("---- MODULE OverloadBoom ----\nVARIABLE x\n"
             "Init == x = 0\nNext == x' = x\n====\n")
CFG = "SPECIFICATION\nSpec\n"

QUEUE_BOUND = 3
TENANT_QUOTA = 2
BREAKER_THRESHOLD = 2
BREAKER_COOLDOWN_S = 0.4


class _StubPool:
    """Engine-pool stand-in: policy tests must cost microseconds."""

    sweep_width = 4

    def stats(self):
        return dict(hits=0, misses=0, size=0, compiles=0, entries=[])

    def shutdown(self):
        pass


@pytest.fixture(scope="module")
def server():
    srv = CheckServer(
        pool=_StubPool(), queue_bound=QUEUE_BOUND,
        tenant_quota=TENANT_QUOTA, breaker_threshold=BREAKER_THRESHOLD,
        breaker_cooldown_s=BREAKER_COOLDOWN_S,
    )
    sch = srv.scheduler

    def stub_run(batch):
        for j in batch:
            if j.name.startswith("boom"):
                raise ValueError("injected poison dispatch")
            if j.name.startswith("die-once") and j.retries == 0:
                raise TransientFault("injected runner death")
            if j.name.startswith("slow:"):
                time.sleep(float(j.name.split(":")[1].split("-")[0]))
            with sch._journal(j) as jr:
                jr.event("run_start", version="test-overload",
                         workload=j.name, engine="stub", device="host",
                         params={})
                jr.event("final", verdict="ok", generated=1,
                         distinct=1, depth=1, queue=0, wall_s=0.0,
                         interrupted=False)
            sch._finish_ok(j, dict(verdict="ok", engine="stub",
                                   generated=1, distinct=1, depth=1,
                                   wall_s=0.0))

    sch._run_batch = stub_run
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module", autouse=True)
def _no_compiles(server):
    """The whole module is policy: zero fresh XLA compiles allowed."""
    from jaxtlc.serve.pool import xla_compiles

    pre = xla_compiles()
    yield
    assert xla_compiles() - pre == 0, (
        "overload policy tests compiled an engine"
    )


def _stall(server, secs=0.5, name="slow"):
    """Occupy the single worker for `secs`: the deterministic window
    every queued-state scenario needs.  Returns the stall job id."""
    jid = client.submit(server.url, OK_SPEC, CFG,
                        name=f"slow:{secs}-{name}")
    deadline = time.time() + 10
    while client.status(server.url, jid)["state"] != "running":
        assert time.time() < deadline, "stall job never dispatched"
        time.sleep(0.005)
    return jid


def _sched_events(server):
    path = os.path.join(server.root, "sched.journal.jsonl")
    return [e for e in obs_journal.read(path) if e["event"] == "sched"]


def _raw_submit(url, payload):
    req = urllib.request.Request(
        url.rstrip("/") + "/jobs", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, dict(r.headers), r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read().decode()


# ---------------------------------------------------------------------------
# admission control: bound, 429 + Retry-After, client backoff


def test_admission_429_with_retry_after(server):
    stall = _stall(server, 0.5, "admission")
    fills = [
        client.submit(server.url, OK_SPEC, CFG, name=f"fill-{i}",
                      tenant=t)
        for i, t in enumerate(("alpha", "beta", "alpha"))
    ]
    # over the bound: the raw HTTP response is a 429 whose
    # Retry-After header the stdlib client can parse
    code, headers, body = _raw_submit(server.url, {
        "spec": OK_SPEC, "cfg": CFG, "name": "over-bound",
        "tenant": "gamma",
    })
    assert code == 429
    assert int(headers["Retry-After"]) >= 1
    payload = json.loads(body)
    assert payload["retry_after"] == int(headers["Retry-After"])
    assert "queue full" in payload["error"]
    # the client surface: retries=0 raises with the hint attached...
    with pytest.raises(client.ClientError) as ei:
        client.submit(server.url, OK_SPEC, CFG, name="over-bound-2",
                      tenant="gamma", retries=0)
    assert ei.value.code == 429
    assert ei.value.retry_after >= 1
    # ...and the default backoff retries until capacity frees
    landed = client.submit(server.url, OK_SPEC, CFG, name="backoff-in",
                           tenant="gamma")
    for jid in fills + [stall, landed]:
        assert client.wait(server.url, jid, timeout=30)["state"] == "done"


def test_tenant_quota_and_wrr_fairness(server):
    stall = _stall(server, 0.5, "wrr")
    hog1 = client.submit(server.url, OK_SPEC, CFG, name="hog-1",
                         tenant="hog")
    hog2 = client.submit(server.url, OK_SPEC, CFG, name="hog-2",
                         tenant="hog")
    with pytest.raises(client.ClientError) as ei:
        client.submit(server.url, OK_SPEC, CFG, name="hog-3",
                      tenant="hog", retries=0)
    assert ei.value.code == 429  # per-tenant quota, queue NOT full
    meek = client.submit(server.url, OK_SPEC, CFG, name="meek-1",
                         tenant="meek")
    for jid in (stall, hog1, hog2, meek):
        assert client.wait(server.url, jid, timeout=30)["state"] == "done"
    # weighted round-robin: the meek tenant is served within the first
    # rotation, never starved behind the hog's whole backlog
    order = [e["job"] for e in _sched_events(server)
             if e["action"] == "dispatch"
             and e["job"] in (hog1, hog2, meek)]
    assert len(order) == 3
    assert order.index(meek) < 2, f"meek starved: {order}"


# ---------------------------------------------------------------------------
# deadlines, cancel, priorities


def test_queued_deadline_expires(server):
    stall = _stall(server, 0.4, "deadline")
    jid = client.submit(server.url, OK_SPEC, CFG, name="doomed",
                        options={"deadline_s": 0.05})
    st = client.wait(server.url, jid, timeout=10)
    assert st["state"] == "expired"
    assert st["deadline_s"] == 0.05
    assert "deadline" in st["error"]
    # a never-ran job still journals (run_start engine="sched" +
    # final) so /runs lists it and an SSE follower terminates; the
    # new terminal verdict validates against schema v1
    events = obs_journal.read(
        os.path.join(server.root, f"{jid}.journal.jsonl"))
    assert events[0]["engine"] == "sched"
    assert events[-1]["event"] == "final"
    assert events[-1]["verdict"] == "expired"
    sse = list(client.stream(server.url, jid, timeout=10))
    assert sse[-1]["event"] == "final"
    assert sse[-1]["verdict"] == "expired"
    assert client.wait(server.url, stall, timeout=30)["state"] == "done"


def test_cancel_queued_and_delete_404(server):
    stall = _stall(server, 0.4, "cancel")
    jid = client.submit(server.url, OK_SPEC, CFG, name="regret")
    st = client.cancel(server.url, jid)
    assert st["state"] == "canceled"
    assert client.status(server.url, jid)["state"] == "canceled"
    with pytest.raises(client.ClientError) as ei:
        client.cancel(server.url, "no-such-job")
    assert ei.value.code == 404
    # Job.state's docstring documents the full state machine,
    # scheduler-terminal states included
    for state in ("queued", "running") + TERMINAL_STATES:
        assert state in Job.__doc__, f"Job docstring lost {state!r}"
    assert client.wait(server.url, stall, timeout=30)["state"] == "done"


def test_priority_dispatch_order(server):
    stall = _stall(server, 0.4, "priority")
    lo = client.submit(server.url, OK_SPEC, CFG, name="prio-lo",
                       options={"priority": 0})
    hi = client.submit(server.url, OK_SPEC, CFG, name="prio-hi",
                       options={"priority": 5})
    for jid in (stall, lo, hi):
        assert client.wait(server.url, jid, timeout=30)["state"] == "done"
    order = [e["job"] for e in _sched_events(server)
             if e["action"] == "dispatch" and e["job"] in (lo, hi)]
    assert order == [hi, lo], "higher priority did not dispatch first"


# ---------------------------------------------------------------------------
# retry + circuit breaker


def test_transient_dispatch_retries_to_done(server):
    jid = client.submit(server.url, OK_SPEC, CFG, name="die-once-a")
    st = client.wait(server.url, jid, timeout=30)
    assert st["state"] == "done"
    assert st["retries"] == 1
    retries = [e for e in _sched_events(server)
               if e["action"] == "retry" and e["job"] == jid]
    assert len(retries) == 1
    assert retries[0]["attempt"] == 1
    assert retries[0]["delay_s"] > 0
    assert "TransientFault" in retries[0]["error"]


def test_breaker_trip_cooldown_half_open(server):
    # two deterministic failures of one spec digest trip the breaker
    for i in (1, 2):
        st = client.check(server.url, BOOM_SPEC, CFG, name=f"boom-{i}")
        assert st["state"] == "error"
    assert client.health(server.url)["open_breakers"] == 1
    # open circuit: the next submit of that digest never runs
    st = client.check(server.url, BOOM_SPEC, CFG, name="boom-3")
    assert st["state"] == "quarantined"
    assert "circuit open" in st["error"]
    sse = list(client.stream(server.url, st["id"], timeout=10))
    assert sse[-1]["verdict"] == "quarantined"
    # other digests are untouched by the open breaker
    ok = client.check(server.url, OK_SPEC, CFG, name="bystander")
    assert ok["state"] == "done"
    time.sleep(BREAKER_COOLDOWN_S + 0.05)
    # cooldown elapsed: exactly ONE half-open probe runs; a second
    # submit while the probe is in flight stays quarantined
    probe = client.submit(server.url, BOOM_SPEC, CFG,
                          name="slow:0.3-probe")
    held = client.check(server.url, BOOM_SPEC, CFG, name="held-back")
    assert held["state"] == "quarantined"
    assert client.wait(server.url, probe, timeout=30)["state"] == "done"
    # the succeeding probe closed the circuit
    assert client.health(server.url)["open_breakers"] == 0
    st = client.check(server.url, BOOM_SPEC, CFG, name="ok-again")
    assert st["state"] == "done"


# ---------------------------------------------------------------------------
# GET /jobs/<id>?wait=: the verdict is delivered when it exists


class _GetCounter:
    """client._get with each job request's URL recorded."""

    def __init__(self, monkeypatch):
        self.urls = []
        real = client._get

        def counting(url, timeout=30.0):
            if "/jobs/" in url:
                self.urls.append(url)
            return real(url, timeout=timeout)

        monkeypatch.setattr(client, "_get", counting)


def _client_sleep(monkeypatch, sleep):
    """Replace the CLIENT's `time.sleep` only: the server's threads in
    this process sleep as they must."""
    monkeypatch.setattr(client, "time", types.SimpleNamespace(
        time=time.time, monotonic=time.monotonic, sleep=sleep))


def _no_sleep(monkeypatch):
    def fail(secs):
        raise AssertionError(f"client slept {secs}s: it polled")

    _client_sleep(monkeypatch, fail)


def _wait_counters(server):
    sched = client.pool_stats(server.url)["scheduler"]["sched"]
    return {k: sched[k] for k in ("wait_blocked", "wait_ready",
                                  "wait_timeout")}


def test_wait_costs_one_get_and_no_sleep(server, monkeypatch):
    """client.wait on a job still running: ONE request, answered by
    the job's completion, and no pause in the client."""
    before = _wait_counters(server)
    jid = client.submit(server.url, OK_SPEC, CFG, name="slow:0.2-wait")
    gets = _GetCounter(monkeypatch)
    _no_sleep(monkeypatch)
    st = client.wait(server.url, jid, timeout=30)
    assert st["state"] == "done" and st["finished_t"], st
    assert len(gets.urls) == 1 and "?wait=" in gets.urls[0], gets.urls
    after = _wait_counters(server)
    assert after == dict(before, wait_blocked=before["wait_blocked"] + 1)
    # already terminal on arrival: answered at once, counted apart
    assert client.wait(server.url, jid, timeout=30) == st
    assert len(gets.urls) == 2
    assert _wait_counters(server) == dict(
        after, wait_ready=after["wait_ready"] + 1)


def test_wait_on_a_held_job_answers_unfinished_at_its_end(server):
    stall = _stall(server, 0.6, "hold")
    jid = client.submit(server.url, OK_SPEC, CFG, name="held")
    before = _wait_counters(server)
    t0 = time.monotonic()
    st = client._get(f"{server.url}/jobs/{jid}?wait=0.2")
    took = time.monotonic() - t0
    assert st["state"] == "queued" and st["finished_t"] is None, st
    assert 0.2 <= took < 0.55, took
    assert _wait_counters(server) == dict(
        before, wait_timeout=before["wait_timeout"] + 1)
    # no `wait`, wait=0 and a wait that is no number: the record as it
    # stands, at once, none of them counted
    t0 = time.monotonic()
    for q in ("", "?wait=0", "?wait=soon", "?wait=-1", "?wait=nan"):
        assert client._get(
            f"{server.url}/jobs/{jid}{q}")["state"] == "queued", q
    assert time.monotonic() - t0 < 0.2
    assert _wait_counters(server)["wait_timeout"] == \
        before["wait_timeout"] + 1
    for j in (stall, jid):
        assert client.wait(server.url, j, timeout=30)["state"] == "done"


def _arrange_done(server):
    return client.submit(server.url, OK_SPEC, CFG, name="w-done")


def _arrange_error(server):
    # a digest of its own: one failure stays under the breaker's two
    return client.submit(server.url, OK_SPEC + "\\* w-error\n", CFG,
                         name="boom-w")


def _arrange_canceled(server):
    jid = client.submit(server.url, OK_SPEC, CFG, name="w-canceled")
    # the waiter must be blocked first: cancel from a timer
    t = threading.Timer(0.1, client.cancel, (server.url, jid))
    t.daemon = True
    t.start()
    return jid


def _arrange_expired(server):
    return client.submit(server.url, OK_SPEC, CFG, name="w-expired",
                         options={"deadline_s": 0.1})


def _arrange_quarantined(server):
    spec = OK_SPEC + "\\* w-quarantined\n"
    for i in (1, 2):  # trip this digest's breaker first
        assert client.check(server.url, spec, CFG,
                            name=f"boom-q{i}")["state"] == "error"
    return client.submit(server.url, spec, CFG, name="w-quarantined")


_ARRANGE = dict(done=_arrange_done, error=_arrange_error,
                canceled=_arrange_canceled, expired=_arrange_expired,
                quarantined=_arrange_quarantined)


@pytest.mark.parametrize("state", TERMINAL_STATES)
def test_every_terminal_state_releases_a_waiter(server, state,
                                                monkeypatch):
    """One blocked GET per terminal state, each answered by the
    completion itself: the three _finish_* all set the job's event."""
    # the job under test is held in the queue until the stall ends
    # (a quarantined job never queues: its waiter finds it terminal)
    stall = _stall(server, 0.3, f"w-{state}")
    jid = _ARRANGE[state](server)
    gets = _GetCounter(monkeypatch)
    _no_sleep(monkeypatch)
    st = client.wait(server.url, jid, timeout=30)
    assert st["state"] == state and st["finished_t"], st
    assert len(gets.urls) == 1, gets.urls
    monkeypatch.undo()
    assert client.wait(server.url, stall, timeout=30)["state"] == "done"


def test_many_waiters_on_one_job_all_released_and_counted(server):
    """More waiters than cores on ONE job, a short switch interval:
    every Scheduler.wait returns the finished job and every one is
    counted (the counters are read-modify-write under _cond)."""
    import sys

    n = 4 * (os.cpu_count() or 8)
    stall = _stall(server, 0.3, "crowd")
    jid = client.submit(server.url, OK_SPEC, CFG, name="crowded")
    before = _wait_counters(server)
    got = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        waiters = [threading.Thread(
            target=lambda: got.append(server.scheduler.wait(jid, 20)),
            daemon=True) for _ in range(n)]
        for t in waiters:
            t.start()
        for t in waiters:
            t.join(20)
        assert not any(t.is_alive() for t in waiters)
    finally:
        sys.setswitchinterval(old)
    assert len(got) == n and {j.state for j in got} == {"done"}
    after = _wait_counters(server)
    assert (after["wait_blocked"] + after["wait_ready"]
            == before["wait_blocked"] + before["wait_ready"] + n)
    assert after["wait_blocked"] > before["wait_blocked"]
    assert after["wait_timeout"] == before["wait_timeout"]
    assert client.wait(server.url, stall, timeout=30)["state"] == "done"


def test_a_retried_job_holds_its_waiter_until_it_is_done(
        server, monkeypatch):
    """A dispatch that dies requeues its job: the waiter is not woken
    for that (the job is not terminal), and the ONE held GET is
    answered by the retry's completion."""
    before = _wait_counters(server)
    jid = client.submit(server.url, OK_SPEC, CFG, name="die-once-w")
    gets = _GetCounter(monkeypatch)
    _no_sleep(monkeypatch)
    st = client.wait(server.url, jid, timeout=30)
    assert st["state"] == "done" and st["retries"] == 1, st
    assert len(gets.urls) == 1, gets.urls
    assert _wait_counters(server) == dict(
        before, wait_blocked=before["wait_blocked"] + 1)


def test_a_waiter_is_woken_once_sched_run_has_closed(server):
    """The wake is no part of the job's service time: at the moment
    the job's event is set, the dispatch's `sched.run` row is already
    in the recorder (test_service holds its children to 95 % of it)."""
    from jaxtlc.obs import spans

    t = time.time()
    jid = client.submit(server.url, OK_SPEC, CFG, name="slow:0.2-span")

    class Probe(threading.Event):
        rows = None

        def set(self):
            if self.rows is None:
                self.rows = [r.name for r in spans.snapshot(since=t)
                             if r.job == jid]
            super().set()

    probe = server.scheduler.get(jid)._done = Probe()
    assert client.wait(server.url, jid, timeout=30)["state"] == "done"
    assert probe.rows == ["sched.run"]


def test_the_cap_is_the_servers_and_covers_what_the_client_asks():
    from jaxtlc.serve import server as srv

    assert srv._wait_seconds("wait=1e9") == srv.WAIT_CAP_S
    assert srv._wait_seconds("wait=0.2&x=1") == 0.2
    # a full wait of the client's is a full wait at the server too:
    # a capped answer would read as "sooner than asked" and be paused on
    assert client._WAIT_ASK_S <= srv.WAIT_CAP_S
    assert client._WAIT_ASK_S + client._WAIT_MARGIN_S \
        <= client._SOCKET_TIMEOUT_S


def test_unknown_job_is_404_with_and_without_wait(server):
    for q in ("", "?wait=0.2"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            client._get(f"{server.url}/jobs/job-nonesuch{q}")
        assert ei.value.code == 404


def test_shutdown_releases_a_blocked_waiter():
    """A server of its own (shutdown is final): a job that will never
    finish, a waiter asking for far longer than the test may take."""
    srv = CheckServer(pool=_StubPool())
    release = threading.Event()
    srv.scheduler._run_batch = lambda batch: release.wait(30)
    got = {}
    try:
        jid = client.submit(srv.url, OK_SPEC, CFG, name="never")
        waiter = threading.Thread(
            target=lambda: got.update(
                client._get(f"{srv.url}/jobs/{jid}?wait=20")),
            daemon=True)
        waiter.start()
        time.sleep(0.2)
        assert waiter.is_alive(), "the waiter did not block"
        t0 = time.monotonic()
        threading.Timer(0.3, release.set).start()
        srv.shutdown()
        waiter.join(5)
        assert not waiter.is_alive(), "shutdown left the waiter blocked"
        assert time.monotonic() - t0 < 5
        assert got["state"] in ("queued", "running"), got
    finally:
        release.set()


# ---------------------------------------------------------------------------
# drain, surfaces


def test_drain_timeout_is_loud(server):
    jid = client.submit(server.url, OK_SPEC, CFG, name="slow:0.6-drain")
    with pytest.raises(DrainTimeout) as ei:
        server.scheduler.drain(timeout=0.05)
    assert jid in ei.value.pending
    assert jid in str(ei.value)
    assert client.wait(server.url, jid, timeout=30)["state"] == "done"
    assert server.scheduler.drain(timeout=10) is True


def test_health_stats_and_metrics_surfaces(server):
    h = client.health(server.url)
    assert h["status"] == "ok"
    assert h["queued"] == 0 and h["running"] == []
    assert h["uptime_s"] > 0
    for k in ("admitted", "rejected", "expired", "canceled",
              "quarantined", "retried"):
        assert h["counters"][k] >= 1, k
    stats = client.pool_stats(server.url)["scheduler"]
    assert stats["queue_bound"] == QUEUE_BOUND
    assert stats["tenant_quota"] == TENANT_QUOTA
    assert stats["dispatches"] >= 1
    assert stats["sched"] == h["counters"]
    for k in ("wait_blocked", "wait_ready", "wait_timeout"):
        assert stats["sched"][k] >= 1, k
    # every control-plane decision renders as a Prometheus gauge off
    # the sched journal (obs.views.metrics_from_events)
    with urllib.request.urlopen(
        server.url + "/metrics?run=sched", timeout=10
    ) as r:
        text = r.read().decode()
    for needle in ("sched_admit_total", "sched_reject_total",
                   "sched_expire_total", "sched_retry_total",
                   "sched_quarantine_total", "sched_cancel_total",
                   "sched_queue_depth"):
        assert needle in text, f"/metrics lost {needle}:\n{text}"
    # the scheduler's own journal is schema-valid end to end
    events = obs_journal.read(
        os.path.join(server.root, "sched.journal.jsonl"))
    assert events[0]["event"] == "run_start"
    assert events[0]["engine"] == "sched"
    # every job the module created reached a terminal state: the
    # queue never wedged
    assert all(j["state"] in TERMINAL_STATES
               for j in server.scheduler.list())
