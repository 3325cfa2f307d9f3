"""The six liveness readers (layers/live_share_pct, live_capture_ms,
live_fixpoint_ms, live_sweeps, live_edges_per_s, live_fixpoint_hbm_pct)
and the entry that holds a check's temporal half to its pins
(entries/run_check_live.py) on a recorded run_view:
benchmark/testdata/run_view-live.json holds two api.run_check checks of
the EWD840 model with its cfg's PROPERTY at N = 3 (302 states, 1,809
successor rows) on the CPU, as entries/run_check_live.py returns them,
with the program's recorder rows and the plain reference's pins at that
rung.  Only spans and counters are checked; the walls in them are a
CPU's.  The cell's configuration, traffic and reference files are held
to the contract here too."""

from __future__ import annotations

import copy
import json
import os
import sys
from types import SimpleNamespace

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("live_share_pct", "live_capture_ms", "live_fixpoint_ms",
         "live_sweeps", "live_edges_per_s", "live_fixpoint_hbm_pct")
CELL = "ewd840-live.struct-liveness"
COUNTERS = ("live_states", "live_edges", "live_changed_edges",
            "live_fair_edges", "live_h_states", "live_p_states",
            "live_survivors", "live_outer", "live_sweeps",
            "live_edge_bytes", "live_host_bytes")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-live.json")) as f:
        run = json.load(f)
    # a device the peaks know, for the one reader that needs a peak
    run["device"]["kind"] = "TPU v5 lite"
    return run


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def spans_of(run, job, *names):
    rows = [dict(zip(("id", "name", "t0", "t1"), r)) for r in run["spans"]]
    return [r for r in rows if r["name"] in names
            and r["t0"] >= job["start_t"] and r["t1"] <= job["done_t"]]


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["generated"], final["distinct"], final["depth"]) == (
        2001, 302, 9)
    assert final["live_states"] == 302
    assert final["live_edges"] == final["generated"] - 192 == 1809
    assert final["live_changed_edges"] == 1468
    assert final["live_fair_edges"] == 462
    assert (final["live_h_states"], final["live_p_states"],
            final["live_survivors"], final["live_host_bytes"]) == (
        301, 39, 0, 0)
    assert final["live_sweeps"] == final["live_outer"] == 8
    assert read("live_sweeps", recorded) == 8
    per = []
    for j in recorded["jobs"]:
        d = {}
        for n in ("check", "live", "live.capture", "live.fixpoint",
                  "live.enumerate", "live.masks", "live.verdict", "loop"):
            rows = spans_of(recorded, j, n)
            assert len(rows) == 1, n
            d[n] = rows[0]["t1"] - rows[0]["t0"]
        # the route's stages lie inside `live`, `live` and `loop` in check
        assert (d["live.enumerate"] + d["live.capture"] + d["live.masks"]
                + d["live.fixpoint"] + d["live.verdict"]) <= d["live"]
        assert d["live"] + d["loop"] <= d["check"]
        per.append(d)
    from stats import median

    assert read("live_share_pct", recorded) == pytest.approx(
        100 * median([d["live"] / d["check"] for d in per]))
    assert read("live_capture_ms", recorded) == pytest.approx(
        1e3 * median([d["live.capture"] for d in per]))
    assert read("live_fixpoint_ms", recorded) == pytest.approx(
        1e3 * median([d["live.fixpoint"] for d in per]))
    assert read("live_edges_per_s", recorded) == pytest.approx(
        1809 / median([d["live"] for d in per]))
    hbm = load_module("layers", "live_fixpoint_hbm_pct")
    assert read("live_fixpoint_hbm_pct", recorded) == pytest.approx(
        100 * hbm.sweep_bytes(1468, 302, 8, 8)
        / median([d["live.fixpoint"] for d in per]) / 819e9)


def test_the_byte_function_against_a_hand_count_at_n_3():
    """N = 3: 1,468 state-changing rows, 302 states, 8 sweeps in 8 outer
    passes.  A pass reads a row's destination id and the gathered word
    of the set there, and writes and reads its prefix count: 4 + 4 + 4 +
    4 B; and a state's row bound and the gathered count there, the two
    sets joined and the set written: 4 + 4 + 1 + 1 + 1 B.  Sixteen
    passes (a sweep is one, an outer pass one more)."""
    hbm = load_module("layers", "live_fixpoint_hbm_pct")
    a_pass = 1468 * (4 + 4 + 4 + 4) + 302 * (4 + 4 + 1 + 1 + 1)
    assert a_pass == 26810
    assert hbm.sweep_bytes(1468, 302, 8, 8) == 16 * a_pass == 428960
    assert hbm.sweep_bytes(1468, 302, 0, 0) == 0


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the spans and the counters (the parent), a cfg
    without a PROPERTY, a window with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"]
                     if not r[1].startswith("live")]
    for j in bare["jobs"]:
        for k in COUNTERS:
            final_of(j).pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_hbm_reader_needs_a_device_the_peaks_know(recorded):
    recorded["device"]["kind"] = "cpu"
    assert read("live_fixpoint_hbm_pct", recorded) is None


# -- the entry: a check's temporal half against pins.live ------------------


def findings(recorded, mutate=None, result=None):
    entry = load_module("entries", "run_check_live")
    job = copy.deepcopy(recorded["jobs"][0])
    pins = recorded["pins"]["live"]
    if result is None:
        final = final_of(job)
        result = SimpleNamespace(**{k: final[k] for k in COUNTERS})
    if mutate is not None:
        mutate(job["events"], result)
    return entry.live_findings(pins, job["events"], result)


def test_entry_takes_the_recorded_check(recorded):
    assert recorded["pins"]["live"]["properties"] == {"Liveness": "holds"}
    assert recorded["pins"]["live"]["survivors"] == 0
    assert findings(recorded) == []


def test_entry_turns_a_missing_event_into_not_ok(recorded):
    def drop(events, result):
        events[:] = [e for e in events if e["event"] != "liveness"]

    assert findings(recorded, drop) == ["no liveness event for Liveness"]


def test_entry_turns_a_wrong_fairness_into_not_ok(recorded):
    def wf_next(events, result):
        ev = next(e for e in events if e["event"] == "liveness")
        ev["fairness"] = [["Next", ["Deactivate", "InitiateProbe",
                                    "PassToken", "SendMsg"]]]

    (text,) = findings(recorded, wf_next)
    assert "judged under [['Next'" in text and "want [['System'" in text

    def none(events, result):
        next(e for e in events if e["event"] == "liveness")["fairness"] = []

    assert len(findings(recorded, none)) == 1


def test_entry_turns_survivors_into_not_ok(recorded):
    def survive(events, result):
        ev = next(e for e in events if e["event"] == "liveness")
        ev["live_survivors"] = 3
        ev["holds"] = False
        result.live_survivors = 3

    bad = findings(recorded, survive)
    assert "Liveness holds=False, want holds" in bad
    assert "Liveness live_survivors 3, want 0" in bad
    assert "result live_survivors 3, want 0" in bad


def test_entry_turns_a_partial_graph_or_the_host_route_into_not_ok(
        recorded):
    def partial(events, result):
        next(e for e in events
             if e["event"] == "liveness")["live_edges"] = 1000

    assert findings(recorded, partial) == [
        "Liveness live_edges 1000, want 1809"]

    def host(events, result):
        ev = next(e for e in events if e["event"] == "liveness")
        ev["route"] = "host"
        for k in COUNTERS:
            ev.pop(k, None)
            setattr(result, k, None)

    bad = findings(recorded, host)
    assert "Liveness on the host route, want device" in bad
    assert len(bad) == 1 + 7 + 7  # every pinned counter, twice


def test_entry_run_job_returns_not_ok_with_the_numbers_in_why(
        recorded, tmp_path):
    """run_job itself, on a stub of the program: a journal whose
    liveness event says another fairness gives ok False and a `why`
    that gate.py counts as `no verdict`."""
    entry = load_module("entries", "run_check_live")
    job = copy.deepcopy(recorded["jobs"][0])
    next(e for e in job["events"]
         if e["event"] == "liveness")["fairness"] = []
    final = final_of(job)

    def run_check(req):
        with open(req.journal, "w") as f:
            for e in job["events"]:
                f.write(json.dumps(e) + "\n")
        return SimpleNamespace(
            result=SimpleNamespace(**{k: final[k] for k in COUNTERS}),
            exit_code=0, verdict="ok")

    import contextlib

    handle = dict(CheckRequest=lambda **kw: SimpleNamespace(**kw),
                  run_check=run_check, req={}, workdir=str(tmp_path), n=0,
                  live=recorded["pins"]["live"])
    rec = entry.run_job(handle, None, lambda name: contextlib.nullcontext())
    assert rec["ok"] is False
    assert rec["why"].startswith("liveness differs from pins.live: "
                                 "Liveness judged under []")
    sys.path.insert(0, BENCH)
    import gate

    kinds = gate.job_findings(rec, recorded["pins"])
    assert [k for k, _ in kinds] == ["no verdict"]


# -- the cell's files ---------------------------------------------------------


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "ewd840-live", "struct-liveness")
    conf = next(c for c in bench["configs"] if c["name"] == "ewd840-live")
    want = ("tlaplus/Examples specifications/ewd840/EWD840.tla + "
            "EWD840.cfg")
    assert conf["source"].startswith(want)
    assert config["source"].startswith(want)
    assert conf["reduced"] == config["reduced"] == ["scale"]
    for key in ("source", "reduced_why", "assumed", "guarantees",
                "deployment", "pins", "pins_from", "request"):
        assert config.get(key), key
    assert "TO BE SET" not in json.dumps(config)
    assert "TO BE SET" not in json.dumps(traffic)
    assert config["architecture"] is None
    for key in ("module", "model", "geometry", "accounting"):
        assert config["assumed"][key], key
    dep = config["deployment"]
    assert dep["fairness"] == "WF_vars(System)"
    assert dep["invariants"] == ["TypeOK", "TerminationDetection", "Inv"]
    assert dep["properties"] == ["Liveness"]
    assert config["entry"] == "run_check_live"
    assert config["reference"] == "ewd840"
    assert config["request"]["frontend"] == "struct"
    # the spec's own fairness and the cfg's own PROPERTY, no flag
    assert not {"fairness", "liveness", "liveness_host"} & set(
        config["request"])
    cfg = os.path.join(REPO, config["request"]["config"])
    with open(cfg) as f:
        text = f.read()
    assert "PROPERTY" in text and "Liveness" in text
    assert f"N = {dep['N']}" in text
    with open(os.path.join(os.path.dirname(cfg), "EWD840.tla")) as f:
        module = f.read()
    assert "Spec == Init /\\ [][Next]_vars /\\ WF_vars(System)" in module
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert config["engines"] == ["single"]
    pins = config["pins"]
    assert set(pins["action_generated"]) == {
        "InitiateProbe", "PassToken", "SendMsg", "Deactivate"}
    assert sum(pins["action_generated"].values()) == (
        pins["generated"] - dep["initial_states"])
    live = pins["live"]
    assert live["properties"] == {"Liveness": "holds"}
    assert live["fairness"] == [["System", ["InitiateProbe", "PassToken"]]]
    assert live["graph_states"] == pins["distinct"]
    assert live["graph_edges"] == pins["generated"] - dep["initial_states"]
    assert live["survivors"] == 0
    assert 0 < live["fair_edges"] < live["changed_edges"] < live[
        "graph_edges"]
    assert traffic["loop"] == "closed"
    assert "whole" in traffic["trace_why"]
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert layers == set(NAMES) | {
        "fp_load_pct", "call_host_pct", "device_idle_pct.batch",
        "hbm_peak_bytes", "struct_build_ms", "lane_live_pct",
        "slot_live_pct"}
    for name in layers:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert (m["layer"], m["moves"]) == ("liveness", "states_per_s")
            assert m["workloads"] == [CELL]


def test_reference_prints_the_small_rungs_pins_and_passes_its_checks(
        recorded):
    """benchmark/reference/ewd840.py, which made the configuration's
    pins, at N = 3, with every self-check."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import ewd840

    pins = ewd840.pins_for({}, n=3)
    assert pins == recorded["pins"]
    assert (pins["generated"], pins["distinct"], pins["depth"]) == (
        2001, 302, 9)
