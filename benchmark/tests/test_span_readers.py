"""The nine span readers (layers/ + span_read.py) on a recorded run_view:
benchmark/testdata/run_view-spans.json holds the jobs and the program's
recorder rows of one CPU process at tiny sizes - two api.run_check
checks and one check_with_checkpoints call of the KubeAPI FF corner, and
three pooled served jobs of the Raft model (each after its warm job) -
under the keys a live run_view has, plus `spans` / `spans_dropped`, which
span_read.py prefers to asking the program.
"""

from __future__ import annotations

import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
import span_read  # noqa: E402
from run import load_module  # noqa: E402

BATCH = ("build_ms", "build_trace_ms", "build_load_ms", "loop_wait_pct")
RECHECK = BATCH + ("entry_self_ms", "journal_ms")
SERVED = ("journal_ms", "service_ms", "spec_load_ms", "pool_device_ms")
ALL = sorted(set(RECHECK + SERVED))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-spans.json")) as f:
        return json.load(f)


def view(recorded, cell):
    return dict(recorded,
                jobs=[j for j in recorded["jobs"] if j["cell"] == cell])


def read(name, run):
    return load_module("layers", name).read(run)


@pytest.mark.parametrize("cell,names", [
    ("exhaustive", BATCH), ("recheck", RECHECK), ("served", SERVED)])
def test_every_reader_of_the_cell_gives_a_number(recorded, cell, names):
    run = view(recorded, cell)
    for name in names:
        v = read(name, run)
        assert isinstance(v, float) and v > 0, (cell, name, v)


@pytest.mark.parametrize("name", ALL)
def test_reader_gives_none_without_spans(recorded, name):
    """A program without the recorder (the parent), a window whose rows
    the recorder dropped, and a window with no correct job."""
    for cell in ("recheck", "served"):
        run = view(recorded, cell)
        assert read(name, dict(run, spans=[])) is None
        early = min(r[3] for r in run["spans"]) - 1.0
        late = [dict(j, start_t=early) for j in run["jobs"]]
        assert read(name, dict(run, jobs=late, spans_dropped=7)) is None
        bad = [dict(j, findings=["distinct 1, want 2"])
               for j in run["jobs"]]
        assert read(name, dict(run, jobs=bad)) is None


def test_reader_asks_the_program_and_takes_its_absence(recorded,
                                                       monkeypatch):
    """With no `spans` key the reader imports the program's recorder;
    where that fails (a commit before it existed) the metric is left
    out of the line."""
    run = view(recorded, "recheck")
    del run["spans"]
    monkeypatch.setitem(sys.modules, "jaxtlc", None)
    for name in RECHECK:
        assert read(name, run) is None


def test_jobs_are_found_by_id_and_by_containment(recorded):
    served = span_read.job_spans(view(recorded, "served"))
    assert len(served) == 3
    for rows, job in zip(served, view(recorded, "served")["jobs"]):
        assert {r["job"] for r in rows} == {job["job_id"]}
        names = [r["name"] for r in rows]
        assert names.count("sched.run") == 1 and "pool.run" in names
    checks = span_read.job_spans(view(recorded, "recheck"))
    assert len(checks) == 2
    for rows, job in zip(checks, view(recorded, "recheck")["jobs"]):
        assert len({r["job"] for r in rows}) == 1
        assert [r["name"] for r in rows].count("check") == 1
        assert all(job["start_t"] <= r["t0"] <= r["t1"] <= job["done_t"]
                   for r in rows)


def test_the_arithmetic_is_what_the_docstrings_say(recorded):
    run = view(recorded, "recheck")
    per = []
    for rows in span_read.job_spans(run):
        d = {n: span_read.seconds(rows, n) for n in
             ("check", "build", "loop", "loop.wait", "build.trace",
              "build.lower", "build.compile")}
        per.append(d)
        # build's children lie inside it, build and loop inside check
        assert d["build.trace"] + d["build.lower"] + d[
            "build.compile"] <= d["build"] <= d["check"]
        assert d["build"] + d["loop"] <= d["check"]
    from stats import median

    assert read("build_ms", run) == pytest.approx(
        1e3 * median([d["build"] for d in per]))
    assert read("entry_self_ms", run) == pytest.approx(
        1e3 * median([d["check"] - d["build"] - d["loop"] for d in per]))
    assert read("loop_wait_pct", run) == pytest.approx(
        100 * median([d["loop.wait"] / d["loop"] for d in per]))
    # journal_ms is the journal's own counter, off its closing span
    closing = [r for rows in span_read.job_spans(run) for r in rows
               if r["name"] == "check.journal_close"]
    assert read("journal_ms", run) == pytest.approx(
        1e3 * median([r["attrs"]["seconds"] for r in closing]))
    # served: the three parts lie inside sched.run
    srv = view(recorded, "served")
    assert read("spec_load_ms", srv) + read("pool_device_ms", srv) <= read(
        "service_ms", srv)


# which entries run code that closes the spans a metric reads
BUILDS = {"run_check", "check_with_checkpoints"}  # runtime.aot_build, loop
CAN_REPORT = {
    "build_ms": BUILDS, "build_trace_ms": BUILDS, "build_load_ms": BUILDS,
    "loop_wait_pct": BUILDS,
    "entry_self_ms": {"run_check"},  # api.run_check's own `check` span
    "journal_ms": {"run_check", "serve"},  # the entries that journal
    "service_ms": {"serve"}, "spec_load_ms": {"serve"},
    "pool_device_ms": {"serve"},
}


def test_benchmark_json_lists_the_nine_where_their_cells_report(recorded):
    """Each span metric lists exactly the cells that can report it: those
    whose entry runs the code that closes its spans and that report the
    end-to-end metric it moves.  Holds for any cell or metric a later PR
    appends."""
    assert sorted(CAN_REPORT) == ALL
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = {}
    for w in bench["workloads"]:
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        with open(os.path.join(REPO, conf["file"])) as f:
            entry[w["name"]] = json.load(f)["entry"]
    e2e = {m["name"]: set(m.get("workloads", entry))
           for m in bench["end_to_end"]}
    for n in ALL:
        m = by_name[n]
        want = {cell for cell in entry
                if entry[cell] in CAN_REPORT[n] and cell in e2e[m["moves"]]}
        assert set(m["workloads"]) == want, n
        assert m["source"] in ("program_span", "program_counter")
        assert os.path.exists(os.path.join(BENCH, "layers", n + ".py"))
