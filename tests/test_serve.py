"""Run-monitoring server tests (ISSUE 8): the live ops plane's serving
surface, exercised entirely on synthetic journals - no engine, no jax
compiles (tier-1 runs at ~800 s of its 870 s budget).

- SSE tail semantics: events stream exactly once, in order; a TORN
  trailing line (the fsync-append crash window) is held back until the
  writer completes it - never emitted partial, never emitted twice;
- the run registry multiplexes several journals through one server,
  with ?run= selection on every endpoint;
- `python -m jaxtlc.obs.serve --tiny` smokes the whole pipeline;
- tools/tlcstat.py --connect renders its dashboard from a remote
  monitor (a client of the same views).
"""

import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from jaxtlc.obs import journal as jr
from jaxtlc.obs import serve as obs_serve


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _progress(j, depth):
    return j.event("progress", depth=depth, generated=10 * depth,
                   distinct=5 * depth, queue=depth)


def test_sse_tail_survives_torn_trailing_line(tmp_path):
    """The mid-tail crash window: a partially-appended final line must
    be invisible to the SSE subscriber until the writer completes it,
    and then arrive exactly once."""
    path = str(tmp_path / "run.journal.jsonl")
    with jr.RunJournal(path) as j:
        _progress(j, 1)
        _progress(j, 2)
    srv = obs_serve.start_server(str(tmp_path))
    got = []

    def subscribe():
        try:
            with urllib.request.urlopen(srv.url + "/events",
                                        timeout=30) as r:
                while True:
                    line = r.readline()
                    if not line:
                        return
                    if line.startswith(b"data: "):
                        got.append(json.loads(line[6:].decode()))
        except OSError:
            pass

    sub = threading.Thread(target=subscribe, daemon=True)
    sub.start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline and len(got) < 2:
            time.sleep(0.05)
        assert [e["depth"] for e in got] == [1, 2]

        # tear a line mid-append: the subscriber must NOT see it
        def line(depth):
            return json.dumps(
                {"v": 1, "t": float(depth), "event": "progress",
                 "depth": depth, "generated": 10 * depth,
                 "distinct": 5 * depth, "queue": depth},
                sort_keys=True)

        whole = line(3)
        with open(path, "a") as f:
            f.write(whole[:25])
            f.flush()
        time.sleep(4 * obs_serve.POLL_S)
        assert len(got) == 2  # partial line held back

        # the writer completes the line (and appends another): both
        # arrive, exactly once, in order
        with open(path, "a") as f:
            f.write(whole[25:] + "\n")
            f.write(line(4) + "\n")
        deadline = time.time() + 10
        while time.time() < deadline and len(got) < 4:
            time.sleep(0.05)
    finally:
        srv.shutdown()
    sub.join(timeout=10)
    assert [e["depth"] for e in got] == [1, 2, 3, 4]


def test_runs_registry_multiplexes(tmp_path):
    """Two concurrent journals, one server: /runs lists both, ?run=
    selects each on /metrics and /journal."""
    for name, depth, done in (("alpha", 3, True), ("beta", 7, False)):
        with jr.RunJournal(str(tmp_path / f"{name}.journal.jsonl")) as j:
            j.event("run_start", version="t", workload=name.upper(),
                    engine="single", device="cpu", params={})
            _progress(j, depth)
            if done:
                j.event("final", verdict="ok", generated=30,
                        distinct=15, depth=depth, queue=0, wall_s=0.1,
                        interrupted=False)
    srv = obs_serve.start_server(str(tmp_path))
    try:
        runs = json.loads(_get(srv.url + "/runs"))["runs"]
        assert {r["run"] for r in runs} == {"alpha", "beta"}
        by_name = {r["run"]: r for r in runs}
        assert by_name["alpha"]["verdict"] == "ok"
        assert by_name["beta"]["verdict"] == "running"
        assert by_name["beta"]["workload"] == "BETA"
        m_a = _get(srv.url + "/metrics?run=alpha")
        assert 'workload="ALPHA"' in m_a and 'verdict="ok"' in m_a
        m_b = _get(srv.url + "/metrics?run=beta")
        assert 'verdict="running"' in m_b
        assert "jaxtlc_depth 7" in m_b
        raw = _get(srv.url + "/journal?run=beta")
        assert len(raw.splitlines()) == 2
        # an unknown run is a clean 404, not a traceback
        try:
            _get(srv.url + "/metrics?run=nope")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.shutdown()


def test_serve_tiny_smoke(capsys):
    """`python -m jaxtlc.obs.serve --tiny`: synthesize, serve, query
    every endpoint, assert - the tier-1 wiring of the server."""
    assert obs_serve.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    assert "serve tiny OK" in out


def test_tlcstat_connect_renders_remote_run(tmp_path, capsys):
    """tlcstat --connect URL: the same dashboard, rendered from a
    remote monitor's /journal endpoint."""
    from jaxtlc.obs.trace import _tiny_journal

    _tiny_journal(str(tmp_path / "tiny.journal.jsonl"))
    srv = obs_serve.start_server(str(tmp_path))
    try:
        spec = importlib.util.spec_from_file_location(
            "tlcstat",
            os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                         "tlcstat.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main(["--connect", srv.url, "--run", "tiny"]) == 0
        out = capsys.readouterr().out
        for needle in ("ds/min", "VERDICT: interrupted",
                       "phase walls:", "spill tier:"):
            assert needle in out, (needle, out)
    finally:
        srv.shutdown()


def test_spans_event_reaches_metrics_and_tlcstat(tmp_path):
    """ISSUE 24: a check's host spans (the journal's one `spans` event)
    fold into the per-phase wall totals, so /metrics and tlcstat show
    them by name with no exporter of their own."""
    from jaxtlc.obs.views import phase_totals

    path = str(tmp_path / "run.journal.jsonl")
    with jr.RunJournal(path) as j:
        j.event("run_start", version="t", workload="FF", engine="single",
                device="cpu", params={})
        t = time.time()
        j.event("segment", index=0, t_dispatch=t - 0.75, t_fence=t - 0.5,
                wall_s=0.25, readback_s=0.125)
        j.event("spans", rows=[["build.trace", t - 3.0, 1.5, 2],
                               ["build.lower", t - 1.5, 0.5, 2],
                               ["build", t - 3.0, 2.25, -1],
                               ["loop.wait", t - 0.5, 0.125, 5],
                               ["loop.wait", t - 0.25, 0.125, 5],
                               ["loop", t - 0.75, 0.75, -1]])
        j.event("final", verdict="ok", generated=1, distinct=1, depth=1,
                queue=0, wall_s=0.75, interrupted=False)
    totals = phase_totals(jr.read(path))
    assert totals == {"device": 0.25, "readback": 0.125,
                      "build.trace": 1.5,
                      "build.lower": 0.5, "build": 2.25,
                      "loop.wait": 0.25, "loop": 0.75}
    srv = obs_serve.start_server(str(tmp_path))
    try:
        metrics = _get(srv.url + "/metrics")
    finally:
        srv.shutdown()
    for needle in ('jaxtlc_phase_wall_seconds{phase="build.trace"} 1.5',
                   'jaxtlc_phase_wall_seconds{phase="loop.wait"} 0.25',
                   'jaxtlc_phase_wall_seconds{phase="device"} 0.25',
                   'jaxtlc_phase_wall_seconds{phase="readback"} 0.125'):
        assert needle in metrics, (needle, metrics)
    spec = importlib.util.spec_from_file_location(
        "tlcstat", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "tlcstat.py"))
    tlcstat = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tlcstat)
    board = tlcstat.render(jr.read(path))
    assert "build.trace 1.500s" in board and "loop.wait 0.250s" in board
    # a malformed row is a schema error at write time
    from jaxtlc.obs.schema import JournalSchemaError

    with pytest.raises(JournalSchemaError):
        jr.RunJournal().event("spans", rows=[["build", t, "long"]])


def test_client_wait_polls_a_server_that_does_not_block(monkeypatch):
    """ISSUE 32: against a handler that ignores `?wait=` (an older
    build, a proxy) client.wait still pauses `poll_s` between
    requests, returns the terminal record, and raises after
    `timeout`."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from test_overload import _client_sleep

    from jaxtlc.serve import client

    seen = []

    class Stub(BaseHTTPRequestHandler):
        finish_at = 3  # the request that finds the job done

        def do_GET(self):  # noqa: N802
            seen.append(self.path)
            state = "done" if len(seen) >= self.finish_at else "running"
            body = json.dumps({"id": "job-x", "state": state}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    pauses = []
    _client_sleep(monkeypatch,
                  lambda s: (pauses.append(s), time.sleep(s)))
    try:
        st = client.wait(url, "job-x", timeout=30, poll_s=0.01)
        assert st["state"] == "done"
        assert len(seen) == 3 and pauses == [0.01, 0.01], (seen, pauses)
        assert all(p.startswith("/jobs/job-x?wait=") for p in seen)
        # never terminal: a pause after every request, then the raise
        Stub.finish_at = 10 ** 9
        del seen[:], pauses[:]
        with pytest.raises(client.ClientError, match="still running"):
            client.wait(url, "job-x", timeout=0.15, poll_s=0.02)
        # (the last request has no pause; nor has one whose few ms
        # used up an `ask` rounded down to the millisecond)
        assert len(seen) >= 3 and len(pauses) >= len(seen) - 2, (
            seen, pauses)
        assert set(pauses) == {0.02}
    finally:
        httpd.shutdown()
        httpd.server_close()
