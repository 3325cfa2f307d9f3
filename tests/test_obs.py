"""Observability-plane tests (ISSUE 5 acceptance criteria).

- golden journal: a real supervised FF run's journal validates line by
  line against the versioned schema (obs/schema.py) - event-shape drift
  is a loud tier-1 failure;
- bit-for-bit: the counter ring is pure telemetry - an obs-on run's
  full signature (counts, per-action, outdegree, fpset table words)
  equals the obs-off engine's exactly;
- SIGTERM'd -checkpoint run + -recover -> ONE continuous journal (the
  resumed run APPENDS), trace export renders both attempts' host spans;
- "progress lost" (SIGTERM with no checkpoint path) still ends the
  journal with a structured final event (verdict, counters, wall);
- the 2200 Progress line's interval rates are pinned byte-for-byte.
"""

import json
import os
import time as _time

import numpy as np
import pytest

from jaxtlc.config import ModelConfig
from jaxtlc.engine.bfs import check, obs_rows
from jaxtlc.obs import journal as jr
from jaxtlc.obs.schema import (
    SCHEMA_VERSION,
    JournalSchemaError,
    validate_event,
)
from jaxtlc.obs.trace import chrome_trace_events, export_chrome_trace
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised

FF = ModelConfig(False, False)
EXPECT_FF = (17020, 8203, 109)
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)


RECORDED = {}  # filled by obs_run


def signature(r):
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())),
            r.outdegree)


@pytest.fixture(scope="module")
def clean_ff():
    """The obs-off ground truth (raw fused engine)."""
    return check(FF, **KW)


def _http_get(url, timeout=10.0):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def obs_run(tmp_path_factory):
    """ONE supervised obs-on FF run journaling to disk: the golden
    input shared by the schema/ring/trace tests below.  An obs.serve
    monitor runs over the journal directory for the run's duration,
    and /metrics + /events + /runs are queried FROM INSIDE the event
    hook mid-run - the live-serving acceptance criterion with zero
    extra engine compiles."""
    from jaxtlc.obs.serve import start_server

    from jaxtlc.obs import spans

    d = tmp_path_factory.mktemp("obs")
    path = str(d / "run.journal.jsonl")
    server = start_server(str(d))
    live = {}
    seen = [0]
    t_start = _time.time()

    def hook(j, kind, info):
        j.event(kind, **info)
        seen[0] += 1
        if seen[0] == 40:  # mid-run: the endpoints must answer NOW
            live["metrics"] = _http_get(server.url + "/metrics")
            live["runs"] = _http_get(server.url + "/runs")
            live["events"] = _http_get(server.url + "/events?once=1")

    try:
        with jr.RunJournal(path) as j:
            j.event("run_start", version="test", workload="FF",
                    engine="single", device="cpu",
                    params={**KW, "obs_slots": 64, "pipeline": False})
            sr = check_supervised(
                FF, obs_slots=64,
                opts=SupervisorOptions(
                    ckpt_every=16,
                    on_event=lambda k, i: hook(j, k, i),
                ),
                **KW,
            )
    finally:
        server.shutdown()
    # the recorder's rows of this run and what the journal counted of
    # itself, for test_supervised_check_leaves_every_span
    RECORDED.update(rows=spans.snapshot(since=t_start), fsyncs=j.fsyncs,
                    events=len(j.events), seconds=j.seconds)
    return sr, path, live


def test_journal_schema_golden(obs_run):
    """Every line of a real run's journal validates against the
    versioned schema; the run ends with exactly one final event."""
    sr, path, _ = obs_run
    events = jr.read(path)  # validate=True: schema-checks every line
    assert events, "journal must not be empty"
    for ev in events:
        assert ev["v"] == SCHEMA_VERSION
        validate_event(ev)  # belt and braces (read() already did)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start"
    assert kinds.count("final") == 1 and kinds[-1] == "final"
    # one interval, one record (ISSUE 37): the device and readback
    # walls of a fence are on its `segment` event, the last of the
    # fence's rows; no `phase` row repeats them
    assert "phase" not in kinds
    segs = [e for e in events if e["event"] == "segment"]
    assert len(segs) == sr.segments
    assert all(e["wall_s"] > 0 and e["readback_s"] > 0 for e in segs)
    last = len(kinds) - 1 - kinds[::-1].index("segment")
    assert not {"level", "progress", "coverage"} & set(kinds[last:])
    assert kinds.index("level") < kinds.index("segment")
    fin = events[-1]
    assert fin["verdict"] == "ok" and not fin["interrupted"]
    assert (fin["generated"], fin["distinct"], fin["depth"]) == EXPECT_FF
    assert fin["wall_s"] > 0


def test_supervised_check_leaves_every_span(obs_run):
    """ISSUE 24: the supervised check's host spans - every name of the
    vocabulary once (four a segment in the loop), one identifier,
    children inside parents, top-level children covering the check -
    and their ONE durable copy in the journal: a `spans` event right
    before `final`, equal to the recorder's rows closed by then, for
    exactly one fsync."""
    from test_spans import BUILD, SUPERVISED, assert_tree

    sr, path, _ = obs_run
    rows = [r for r in RECORDED["rows"] if r.job is not None]
    root = assert_tree(rows, "check")
    names = [r.name for r in rows]
    for n in ("build", "loop", "check.result", *BUILD):
        assert names.count(n) == 1, n
    for n in SUPERVISED:
        assert names.count(n) == sr.segments, n
    assert len(rows) <= 13 + 6 * sr.segments  # the budget
    # ISSUE 37: the readback's two halves lie inside it, and every
    # `level` row was written inside an `emit`
    by_id = {r.id: r for r in rows}
    halves = [r for r in rows if r.name in ("loop.readback.get",
                                            "loop.readback.emit")]
    assert all(by_id[r.parent].name == "loop.readback" for r in halves)
    events = jr.read(path)  # validates the new kind too
    kinds = [e["event"] for e in events]
    assert kinds.count("spans") == 1
    assert kinds[-2:] == ["spans", "final"]
    ev = events[-2]
    closed = [r for r in rows if r.t1 <= ev["t"]]
    assert [r.name for r in closed] == [row[0] for row in ev["rows"]]
    assert {r.name for r in rows} - {r.name for r in closed} == {"check"}
    index = {r.id: i for i, r in enumerate(closed)}
    for r, (name, t0, dur_s, parent) in zip(closed, ev["rows"]):
        assert t0 == r.t0 and dur_s == pytest.approx(r.t1 - r.t0,
                                                     abs=1e-6)
        assert parent == index.get(r.parent, -1)
        assert (parent == -1) == (r.parent == root.id)
    # per-event fsync: as many barriers as events, so the spans event
    # cost exactly one - and the journal says what it cost itself
    assert RECORDED["fsyncs"] == RECORDED["events"] == len(events)
    assert 0 < RECORDED["seconds"] < root.t1 - root.t0
    # the same names reach /metrics and tlcstat through phase_totals
    from jaxtlc.obs.views import metrics_from_events, phase_totals

    totals = phase_totals(events)
    by_name = {r.name: r for r in rows}
    assert totals["build"] == pytest.approx(
        by_name["build"].t1 - by_name["build"].t0, abs=1e-5)
    assert totals["loop.wait"] == pytest.approx(
        sum(r.t1 - r.t0 for r in rows if r.name == "loop.wait"),
        abs=1e-4)
    # the segment events' walls fold as the `phase` rows did
    segs = [e for e in events if e["event"] == "segment"]
    assert totals["device"] == pytest.approx(
        sum(e["wall_s"] for e in segs))
    assert totals["readback"] == pytest.approx(
        sum(e["readback_s"] for e in segs))
    # ... and are the `loop.readback` spans less each `segment` event's
    # own write, which closes its `emit` (one fsync: no fixed time)
    emits = [r for r in rows if r.name == "loop.readback.emit"]
    own_write = totals["loop.readback"] - totals["readback"]
    assert -1e-5 * len(segs) <= own_write < sum(r.t1 - r.t0
                                                 for r in emits)
    assert all(any(r.t0 <= e["t"] <= r.t1 + 1e-3 for r in emits)
               for e in events if e["event"] in ("level", "segment"))
    assert "build.compile" in metrics_from_events(events)[
        "phase_wall_seconds"]


def test_serve_endpoints_answer_during_live_run(obs_run):
    """ISSUE 8 acceptance: /metrics, /events and /runs answered WHILE
    the supervised run was mid-flight (queried from inside the event
    hook at event 40 - the run was nowhere near done)."""
    _, path, live = obs_run
    assert set(live) == {"metrics", "runs", "events"}
    m = live["metrics"]
    for needle in ("jaxtlc_run_info", 'workload="FF"',
                   'verdict="running"', "jaxtlc_generated_total",
                   "jaxtlc_distinct_total",
                   "jaxtlc_phase_wall_seconds{phase="):
        assert needle in m, (needle, m)
    import json as _json

    runs = _json.loads(live["runs"])["runs"]
    assert len(runs) == 1 and runs[0]["verdict"] == "running"
    datas = [ln for ln in live["events"].splitlines()
             if ln.startswith("data: ")]
    assert len(datas) >= 40  # the SSE snapshot saw the live history
    assert '"event": "run_start"' in datas[0]


def test_obs_bit_identical_and_ring(obs_run, clean_ff):
    """Acceptance: obs-on results == obs-off engine bit-for-bit, and
    the ring's per-level rows are exact cumulative telemetry."""
    sr, path, _ = obs_run
    assert signature(sr.result) == signature(clean_ff)
    levels = [e for e in jr.read(path) if e["event"] == "level"]
    assert len(levels) == EXPECT_FF[2]  # one row per BFS level
    lvls = [e["level"] for e in levels]
    assert lvls == list(range(1, EXPECT_FF[2] + 1))
    last = levels[-1]
    assert last["generated"] == EXPECT_FF[0]
    assert last["distinct"] == EXPECT_FF[1]
    assert last["queue"] == 0
    assert last["expanded"] == EXPECT_FF[1]  # every distinct expanded
    assert last["fp_load"] == pytest.approx(8203 / (1 << 14), rel=1e-3)
    # cumulative counters are monotone
    for a, b in zip(levels, levels[1:]):
        assert b["generated"] >= a["generated"]
        assert b["distinct"] >= a["distinct"]
        assert b["bodies"] > a["bodies"]


def test_obs_imports_nothing_of_the_engine_step():
    """Layering (AST scan, no compile): the observability package reads
    journals, spans and counter rows; how the engine steps is not its
    business.  The one thing a module under jaxtlc/obs may import from
    jaxtlc.engine is checkpoint.fsync_replace (the durable rename)."""
    import ast
    import glob

    import jaxtlc.obs

    root = os.path.dirname(jaxtlc.obs.__file__)
    found = set()
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        pkg = ["jaxtlc", "obs"]
        for node in ast.walk(ast.parse(open(path).read(), path)):
            if isinstance(node, ast.Import):
                mods = [(a.name, None) for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = pkg[:len(pkg) - node.level + 1] if node.level else []
                mod = ".".join(base + ([node.module] if node.module else []))
                mods = [(mod, a.name) for a in node.names]
            else:
                continue
            for mod, name in mods:
                full = mod if name is None else f"{mod}.{name}"
                if full.startswith("jaxtlc.engine"):
                    found.add((os.path.basename(path), full))
    assert found == {("trace.py", "jaxtlc.engine.checkpoint.fsync_replace")}


def test_obs_ring_survives_regrow(clean_ff):
    """Undersized run: auto-regrow migrates the ring verbatim, the
    final statistics still match the clean run exactly and the ring's
    last row matches the final counters."""
    sr = check_supervised(
        FF, chunk=128, queue_capacity=1 << 8, fp_capacity=1 << 11,
        obs_slots=64, opts=SupervisorOptions(ckpt_every=8),
    )
    assert sr.regrows >= 1
    assert signature(sr.result) == signature(clean_ff)


def test_trace_export_from_golden_journal(obs_run, tmp_path):
    """The journal renders to a Perfetto-loadable Chrome trace with the
    segment slices, the check's host spans as real slices and the
    counter tracks - and no schematic per-level lane."""
    _, path, _ = obs_run
    out = str(tmp_path / "run.trace.json")
    n = export_chrome_trace(jr.read(path), out)
    doc = json.load(open(out))
    assert len(doc["traceEvents"]) == n > 0
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(s.startswith("segment") for s in names)
    # no schematic lane: an unmeasured run draws nothing inside its
    # segments; the host slices are the recorder's spans, as measured
    assert not any(s.startswith(("expand L", "commit L")) for s in names)
    assert not any("schematic" in json.dumps(e)
                   for e in doc["traceEvents"])
    spans_ev = [e for e in jr.read(path) if e["event"] == "spans"]
    assert len(spans_ev) == 1
    slices = {(e["name"], round(e["dur"])): e
              for e in doc["traceEvents"]
              if e.get("ph") == "X" and e.get("tid") == 3
              and e.get("pid") == 2}
    for name, _t0, dur_s, _parent in spans_ev[0]["rows"]:
        assert (name, round(max(dur_s * 1e6, 1.0))) in slices, name
    assert {"build", "build.compile", "loop", "loop.wait"} <= {
        n for n, _ in slices}
    assert min(e["ts"] for e in doc["traceEvents"] if "ts" in e) >= 0
    assert "states" in names  # counter track (ph: C)
    phases = {e.get("ph") for e in doc["traceEvents"]}
    assert {"X", "C", "M"} <= phases


@pytest.mark.parametrize("segment_first", [False, True],
                         ids=["levels-then-segment", "pre-37-journal"])
def test_trace_export_places_levels_at_their_own_fence(segment_first):
    """A fence's level rows journal before its `segment` event; a
    journal from before ISSUE 37 wrote the segment first, then its
    `phase` rows, then the levels.  Either way each level's counters
    are drawn at its OWN segment's fence, the last fence's included."""
    events = [dict(v=1, t=100.0, event="run_start")]
    for s in range(2):
        td = 100.0 + s
        seg = [dict(v=1, t=td + 0.9, event="segment", index=s,
                    t_dispatch=td, t_fence=td + 0.9, wall_s=0.9)]
        lvl = [dict(v=1, t=td + 0.9, event="level", level=s + 1,
                    generated=10, distinct=10 * (s + 1), queue=1,
                    outdegree_sum=1, expanded=1)]
        if segment_first:
            seg.append(dict(v=1, t=td + 0.9, event="phase",
                            scope="segment", index=s, phase="readback",
                            wall_s=0.001))
        else:
            seg[0]["readback_s"] = 0.001
        events += seg + lvl if segment_first else lvl + seg
    got = {e["args"]["distinct"]: e["ts"]
           for e in chrome_trace_events(events) if e["name"] == "states"}
    assert got == {10: 0.9e6, 20: 1.9e6}


def test_progress_lost_still_emits_final(tmp_path):
    """Satellite: SIGTERM with NO checkpoint path ("progress lost")
    still ends the journal with the structured final event - verdict,
    counters, wall time - via the faults DSL sigterm@K plan."""
    path = str(tmp_path / "lost.journal.jsonl")
    with jr.RunJournal(path) as j:
        sr = check_supervised(
            FF, obs_slots=64,
            opts=SupervisorOptions(
                ckpt_every=8,
                faults=FaultPlan.parse("sigterm@2"),
                on_event=lambda k, i: j.event(k, **i),
            ),
            **KW,
        )
    assert sr.interrupted
    events = jr.read(path)  # schema-validates
    ints = [e for e in events if e["event"] == "interrupted"]
    assert len(ints) == 1
    # no checkpoint configured: path is None but the counters are there
    assert ints[0]["path"] is None
    assert ints[0]["generated"] > 0 and ints[0]["wall_s"] > 0
    fin = events[-1]
    assert fin["event"] == "final" and fin["verdict"] == "interrupted"
    assert fin["interrupted"] and fin["queue"] > 0
    assert fin["distinct"] == sr.result.distinct


def test_cli_sigterm_recover_one_continuous_journal(tmp_path, capsys):
    """Acceptance: a SIGTERM'd -checkpoint CLI run followed by -recover
    produces ONE continuous journal (run_start ... interrupted ...
    run_resume ... final ok) that validates, and whose trace export
    carries the host spans of both attempts."""
    from jaxtlc.cli import main

    d = tmp_path / "m"
    d.mkdir()
    (d / "MC.tla").write_text(
        "---- MODULE MC ----\nEXTENDS KubeAPI, TLC\n\n"
        "\\* CONSTANT definitions @modelParameterConstants:1"
        "REQUESTS_CAN_FAIL\nconst_fail ==\nFALSE\n\n"
        "\\* CONSTANT definitions @modelParameterConstants:2"
        "REQUESTS_CAN_TIMEOUT\nconst_to ==\nFALSE\n====\n"
    )
    (d / "MC.cfg").write_text(
        "CONSTANT defaultInitValue = defaultInitValue\n"
        "CONSTANT REQUESTS_CAN_FAIL <- const_fail\n"
        "CONSTANT REQUESTS_CAN_TIMEOUT <- const_to\n"
        "SPECIFICATION Spec\nINVARIANT TypeOK\nINVARIANT OnlyOneVersion\n"
    )
    ck = str(d / "ck.npz")
    trace = str(d / "run.trace.json")
    flags = ["-noTool", "-chunk", "128", "-qcap", "4096",
             "-fpcap", "16384", "-checkpoint", ck,
             "-checkpointevery", "8"]
    rc = main(["check", str(d / "MC.cfg"), *flags,
               "-faults", "sigterm@2"])
    assert rc == 75  # EXIT_INTERRUPTED
    jpath = ck + ".journal.jsonl"
    assert os.path.exists(jpath)  # journals beside the checkpoint
    # ISSUE 8 satellite: an SSE subscriber attached across the
    # interrupt->-recover boundary sees ONE continuous event stream
    # (the resumed run APPENDS to the same journal the tail follows)
    import threading

    from jaxtlc.obs.serve import start_server

    server = start_server(str(d))
    sse_lines = []

    def subscribe():
        import urllib.request

        try:
            with urllib.request.urlopen(server.url + "/events",
                                        timeout=60) as r:
                while True:
                    line = r.readline()
                    if not line:
                        return
                    if line.startswith(b"data: "):
                        sse_lines.append(line[6:].decode())
        except OSError:
            pass

    sub = threading.Thread(target=subscribe, daemon=True)
    sub.start()
    try:
        rc = main(["check", str(d / "MC.cfg"), *flags, "-recover",
                   "-trace-out", trace])
        assert rc == 0
        # the run is over and the journal closed: wait for the tail to
        # drain the remaining appended events
        want = len(jr.read(jpath, validate=False))
        deadline = _time.time() + 10
        while _time.time() < deadline and len(sse_lines) < want:
            _time.sleep(0.1)
    finally:
        server.shutdown()
    sub.join(timeout=10)
    capsys.readouterr()
    events = jr.read(jpath)  # every line of BOTH attempts validates
    # the subscriber's stream IS the journal: every event exactly once,
    # in order, spanning SIGTERM -> 75 -> -recover -> verdict
    stream = [json.loads(s) for s in sse_lines]
    assert [e["event"] for e in stream] == [e["event"] for e in events]
    skinds = [e["event"] for e in stream]
    for needle in ("run_start", "interrupted", "run_resume", "final"):
        assert needle in skinds
    assert skinds.index("interrupted") < skinds.index("run_resume")
    finals_stream = [e for e in stream if e["event"] == "final"]
    assert [f["verdict"] for f in finals_stream] == ["interrupted",
                                                    "ok"]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start"
    for needle in ("interrupted", "run_resume", "recovery", "level"):
        assert needle in kinds, f"journal lost {needle}: {kinds}"
    finals = [e for e in events if e["event"] == "final"]
    assert [f["verdict"] for f in finals] == ["interrupted", "ok"]
    assert finals[-1]["distinct"] == EXPECT_FF[1]
    # the resumed run continues level numbering, never restarts it
    levels = [e["level"] for e in events if e["event"] == "level"]
    assert levels == sorted(levels) and len(levels) == len(set(levels))
    doc = json.load(open(trace))
    names = [e.get("name", "") for e in doc["traceEvents"]]
    assert any(s.startswith("interrupted") for s in names)
    # both attempts' host spans are in the one timeline (a `spans`
    # event before each final), and no schematic per-level lane
    assert [e["event"] for e in events].count("spans") == 2
    assert names.count("build") == 2 and names.count("loop") == 2
    assert {"check.resolve", "check.preflight", "loop.wait"} <= set(names)
    assert not any(s.startswith(("expand L", "commit L")) for s in names)


def test_schema_rejects_drift():
    """Shape drift is loud: unknown kinds, missing fields, wrong types
    and future schema versions all raise."""
    ok = {"v": SCHEMA_VERSION, "t": 1.0, "event": "progress",
          "depth": 1, "generated": 2, "distinct": 2, "queue": 0}
    validate_event(ok)
    with pytest.raises(JournalSchemaError):
        validate_event({**ok, "event": "no_such_kind"})
    with pytest.raises(JournalSchemaError):
        validate_event({k: v for k, v in ok.items() if k != "depth"})
    with pytest.raises(JournalSchemaError):
        validate_event({**ok, "generated": "lots"})
    with pytest.raises(JournalSchemaError):
        validate_event({**ok, "v": SCHEMA_VERSION + 1})
    with pytest.raises(JournalSchemaError):
        validate_event({"v": SCHEMA_VERSION, "t": 1.0, "event": "final",
                        "verdict": "maybe", "generated": 1,
                        "distinct": 1, "depth": 1, "queue": 0,
                        "wall_s": 0.1, "interrupted": False})


def test_journal_tolerates_torn_tail(tmp_path):
    """The crash window: an append cut mid-write leaves a partial final
    line, which the reader skips; a torn line mid-file is corruption."""
    path = str(tmp_path / "j.jsonl")
    with jr.RunJournal(path) as j:
        j.event("progress", depth=1, generated=2, distinct=2, queue=0)
        j.event("progress", depth=2, generated=4, distinct=3, queue=1)
    with open(path, "a") as f:
        f.write('{"v": 1, "t": 3.0, "event": "prog')  # torn append
    events = jr.read(path)
    assert len(events) == 2 and events[-1]["depth"] == 2
    # mid-file tear = corruption, must raise
    lines = open(path).read().splitlines()
    torn = [lines[0], '{"torn mid-file'] + lines[1:]
    with open(path, "w") as f:
        f.write("\n".join(torn) + "\n")
    with pytest.raises(JournalSchemaError):
        jr.read(path)


def test_progress_line_interval_rates_pinned(capsys, monkeypatch):
    """Satellite: the 2200 Progress line's interval rates, rendered
    byte-for-byte.  First report prints the raw counts as rates (TLC's
    convention, MC.out:35); the second prints true per-minute rates
    from the stored _prev_progress tuple."""
    from jaxtlc.io.tlc_log import TLCLog

    clock = {"now": 1_000.0}
    monkeypatch.setattr(_time, "time", lambda: clock["now"])
    monkeypatch.setattr(
        _time, "strftime", lambda fmt, *a: "2026-08-04 12:00:00"
    )
    log = TLCLog(tool_mode=False)
    log.progress(10, 1000, 600, 50)
    clock["now"] = 1_030.0  # 30 s later
    log.progress(20, 31_000, 15_600, 70)
    out = capsys.readouterr().out.splitlines()
    assert out[0] == (
        "Progress(10) at 2026-08-04 12:00:00: 1,000 states generated "
        "(1,000 s/min), 600 distinct states found (600 ds/min), "
        "50 states left on queue."
    )
    # (31,000-1,000)*60/30 = 60,000 s/min; (15,600-600)*60/30 = 30,000
    assert out[1] == (
        "Progress(20) at 2026-08-04 12:00:00: 31,000 states generated "
        "(60,000 s/min), 15,600 distinct states found (30,000 ds/min), "
        "70 states left on queue."
    )
    assert log._prev_progress == (1_030.0, 31_000, 15_600)
