"""The two refinement readers (layers/action_prop_build_ms,
action_prop_moved_pct) and the entry that holds a check to its
refinement pins (entries/run_check_refine.py) on a recorded run_view:
benchmark/testdata/run_view-refine.json holds two api.run_check checks of
the PaxosCommit model at Ballot = {0} (545 states, 4,141 generated; the
cfg's PROPERTY TCSpec, `TC!TCInit /\\ [][TC!TCNext]_rmState`, judged on all
4,140 edges, 220 of which change rmState) on the CPU, as
entries/run_check_refine.py returns them, with the program's recorder
rows.  Only spans and counters are checked; the walls in them are a
CPU's.  The cell's configuration, traffic and reference files are held
to the contract here too - by membership, never by position or by an
exact list."""

from __future__ import annotations

import copy
import json
import os
import sys
import types

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("action_prop_build_ms", "action_prop_moved_pct")
CELL = "paxoscommit-mc.struct-refinement"
COUNTERS = ("action_prop_edges", "action_prop_moved")
SPANS = ("build.struct.instance", "build.struct.actionprop")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-refine.json")) as f:
        return json.load(f)


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["generated"], final["distinct"], final["depth"]) == (
        4141, 545, 12)
    assert (final["action_prop_edges"], final["action_prop_moved"],
            final["action_prop_init_states"]) == (4140, 220, 1)
    assert final["action_prop_names"] == ["TCSpec"]
    # rmState's one column is what the predicate reads of the source
    assert final["action_prop_src_cols"] == 1
    assert final["struct_traps"] == 0
    assert "properties_skipped" not in final
    assert read("action_prop_moved_pct", recorded) == pytest.approx(
        100.0 * 220 / 4140)
    # one event a property, before `final`, from the device route
    for j in recorded["jobs"]:
        kinds = [e["event"] for e in j["events"]]
        assert kinds.count("action_property") == 1
        assert kinds.index("action_property") < kinds.index("final")
        ev = j["events"][kinds.index("action_property")]
        assert (ev["property"], ev["holds"], ev["route"], ev["edges"],
                ev["moved"], ev["init_states"]) == (
                    "TCSpec", True, "device", 4140, 220, 1)
        assert ev["formula"] == "TC!TCInit /\\ [][TC!TCNext]_rmState"
    # a warm check pays the instanced module's read and hash, and finds
    # the substitution kept; the predicate's compile is the backend
    # memo's, paid by the warm job of set-up alone
    rows = [dict(zip(("id", "name", "t0", "t1", "parent", "job",
                      "thread", "attrs"), r)) for r in recorded["spans"]]
    per_job = []
    for j in recorded["jobs"]:
        mine = [r for r in rows if r["t0"] >= j["start_t"]
                and r["t1"] <= j["done_t"] and r["name"] in SPANS]
        assert [r["name"] for r in mine] == ["build.struct.instance"]
        assert mine[0]["attrs"] == {"module": "TCommit", "memo": "hit"}
        per_job.append(sum(r["t1"] - r["t0"] for r in mine))
    got = read("action_prop_build_ms", recorded)
    assert min(per_job) * 1e3 <= got <= max(per_job) * 1e3
    # the readers the cell shares read this run too
    assert read("lane_live_pct", recorded) == pytest.approx(
        100.0 * 4140 / (545 * final["step_lanes"]))
    assert read("state_row_fill_pct", recorded) == pytest.approx(
        100.0 * final["state_bits"] / (32 * final["state_words"]))
    assert read("struct_build_ms", recorded) is not None


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the spans and the counters (the parent: nothing
    to read, and no error), a window with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"] if r[1] not in SPANS]
    for j in bare["jobs"]:
        for k in COUNTERS:
            final_of(j).pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_moved_share_follows_the_counters(recorded):
    for j in recorded["jobs"]:
        final_of(j).update(action_prop_edges=1000, action_prop_moved=48)
    assert read("action_prop_moved_pct", recorded) == pytest.approx(4.8)
    for j in recorded["jobs"]:
        final_of(j).update(action_prop_edges=0)
    assert read("action_prop_moved_pct", recorded) is None


def result_of(job):
    """What CheckResult the recorded job's `final` event was written
    from, as far as the entry reads it."""
    final = final_of(job)
    return types.SimpleNamespace(
        action_prop_names=tuple(final.get("action_prop_names") or ()),
        properties_skipped=final.get("properties_skipped"),
        **{k: final.get(k) for k in (
            "action_prop_edges", "action_prop_moved",
            "action_prop_init_states")})


PINS = dict(properties={"TCSpec": "holds"}, edges=4140, moved=220,
            init_states=1)


def test_the_entry_holds_a_check_to_its_refinement_pins(recorded):
    entry = load_module("entries", "run_check_refine")
    job = recorded["jobs"][0]
    assert entry.refine_findings(PINS, job["events"], result_of(job)) == []


@pytest.mark.parametrize("case,says", [
    ("no-event", "no action_property event for TCSpec"),
    ("another-route", "on the host route, want device"),
    ("another-count", "edges 545, want 4140"),
    ("another-verdict", "holds=False, want holds"),
    ("the-result-differs", "result action_prop_moved 0, want 220"),
    ("skipped", "properties skipped: ['TCSpec']"),
    ("twice", "2 action_property events for 1 properties"),
])
def test_the_entry_says_what_differs_beside_its_pin(recorded, case, says):
    """A program that skips the property (the parent, could it load the
    model), judges it once a distinct state, elsewhere, or not at all:
    `ok: False`, which gate.py counts as no verdict."""
    entry = load_module("entries", "run_check_refine")
    job = copy.deepcopy(recorded["jobs"][0])
    events, result = job["events"], result_of(job)
    ev = next(e for e in events if e["event"] == "action_property")
    if case == "no-event":
        events.remove(ev)
    elif case == "another-route":
        ev["route"] = "host"
    elif case == "another-count":
        ev["edges"] = 545  # the invariants' seam: a distinct state once
    elif case == "another-verdict":
        ev["holds"] = False
    elif case == "the-result-differs":
        result.action_prop_moved = 0
    elif case == "skipped":
        result.properties_skipped = ("TCSpec",)
    elif case == "twice":
        events.append(dict(ev))
    bad = entry.refine_findings(PINS, events, result)
    assert any(says in b for b in bad), bad


def test_the_entry_refuses_a_program_without_the_seam(monkeypatch):
    """The parent: set-up ends at once with a line that says why, before
    any warm job (a SystemExit: another exit code than 0, soon)."""
    import jaxtlc.engine.backend as backend

    entry = load_module("entries", "run_check_refine")
    monkeypatch.delattr(backend, "ActionPropSeam")
    with pytest.raises(SystemExit, match="no action-property seam"):
        entry.setup(dict(config={}, root=REPO, workdir="", annotate=None))


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "paxoscommit-mc", "struct-refinement")
    assert len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"]
                if c["name"] == "paxoscommit-mc")
    want = ("tlaplus/Examples specifications/transaction_commit "
            "PaxosCommit.tla + TCommit.tla, PaxosCommit.cfg")
    assert conf["source"].startswith(want) and len(conf["source"]) <= 200
    assert config["source"] == conf["source"]
    assert conf["reduced"] == config["reduced"] == []  # nothing is cut
    assert config["architecture"] is None  # the system runs no model
    for key in ("source", "stands_for", "reduced_why", "assumed",
                "guarantees", "deployment", "pins", "pins_from",
                "request"):
        assert config.get(key), key
    assert "PLACEHOLDER" not in json.dumps(config)
    for key in ("module", "model", "geometry", "accounting"):
        assert config["assumed"][key], key
    for key in ("search", "counts", "invariants", "refinement", "deadlock",
                "dedup", "device_path", "journal", "artifact_cache"):
        assert config["guarantees"][key], key
    # both halves, every generated edge, on the device, before the dedup
    for phrase in ("both halves", "EVERY edge", "on the device",
                   "BEFORE the dedup"):
        assert phrase in config["guarantees"]["refinement"], phrase
    assert "not by injectivity" in config["guarantees"]["dedup"]
    dep = config["deployment"]
    assert (dep["RM"], dep["Ballot"]) == (["r1", "r2"], [0, 1])
    assert len(dep["Acceptor"]) == len(dep["Majority"]) == 3
    assert dep["invariants"] == ["PCTypeOK", "TCConsistent"]
    assert dep["properties"] == ["TCSpec"]
    assert dep["instances"] == {"TC": "TCommit"}
    assert config["entry"] == "run_check_refine"
    assert config["reference"] == "paxoscommit"
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert config["engines"] == ["single"]
    assert config["request"]["frontend"] == "struct"
    # the cfg's lines, no flag: nothing in the request names the property
    assert not {"property", "properties", "liveness", "constants"} & set(
        config["request"])
    assert config["request"]["config"].endswith(
        "PaxosCommit.toolbox/Model_1/MC.cfg")
    cfg = os.path.join(REPO, config["request"]["config"])
    with open(cfg) as f:
        text = f.read()
    for word in ("RM = {r1, r2}", "Acceptor = {a1, a2, a3}",
                 "Majority = {{a1, a2}, {a1, a3}, {a2, a3}}",
                 "Ballot = {0, 1}", "SPECIFICATION", "PCSpec", "PCTypeOK",
                 "PROPERTY", "TCSpec"):
        assert word in text
    assert "CHECK_DEADLOCK" not in text
    model = os.path.dirname(cfg)
    with open(os.path.join(model, "PaxosCommit.tla")) as f:
        module = f.read()
    for form in ("TC == INSTANCE TCommit", "THEOREM PCSpec => TC!TCSpec",
                 r"Max[T \in SUBSET S]", r"\E MS \in Majority"):
        assert form in module
    with open(os.path.join(model, "MC.tla")) as f:
        assert "TCSpec == TC!TCSpec" in f.read()
    pins = config["pins"]
    assert set(pins["action_generated"]) == {
        "RMPrepare", "RMChooseToAbort", "RMRcvCommitMsg", "RMRcvAbortMsg",
        "Phase1a", "Phase2a", "Decide", "Phase1b", "Phase2b"}
    assert sum(pins["action_generated"].values()) == (
        pins["generated"] - dep["initial_states"])
    # an action property sees every edge; the invariants see a state once
    refine = pins["refine"]
    assert refine["properties"] == {"TCSpec": "holds"}
    assert refine["edges"] == pins["generated"] - refine["init_states"]
    assert 0 < refine["moved"] < pins["distinct"] < refine["edges"]
    assert traffic["loop"] == "closed"
    assert traffic["trace"]["busy_budget_s"] == 2.0
    assert traffic["trace"]["loop_share"] == 0.3
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e >= {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert layers >= set(NAMES) | {
        "level_ms", "fp_load_pct", "call_host_pct", "device_idle_pct.batch",
        "hbm_peak_bytes", "struct_build_ms", "lane_live_pct",
        "readback_ms", "readback_emit_ms", "scope_cover_pct"}
    for name in layers:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert (m["layer"], m["moves"]) == ("struct compile",
                                                "states_per_s")
            assert CELL in m["workloads"]


def test_reference_prints_the_small_rungs_pins_and_passes_its_checks():
    """benchmark/reference/paxoscommit.py, which made the
    configuration's pins, at the two small rungs, with every self-check,
    and the mutant control."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import paxoscommit

    config = load_json(os.path.join(BENCH, "configs",
                                    "paxoscommit-mc.json"))
    got = paxoscommit.pins_for(config, rm=1)
    for k in ("seconds", "seconds_with_checks"):
        got.pop(k)
    assert got == dict(
        generated=8844, distinct=1461, depth=15, n_initial=1,
        widest_level=304,
        action_generated={"Decide": 1224, "Phase1a": 1461, "Phase1b": 738,
                          "Phase2a": 174, "Phase2b": 4392,
                          "RMChooseToAbort": 57, "RMPrepare": 57,
                          "RMRcvAbortMsg": 340, "RMRcvCommitMsg": 400},
        refine=dict(properties={"TCSpec": "holds"}, edges=8843, moved=370,
                    init_states=1),
        stuttering=5110, messages_sent=28, messages=73,
        self_checks=["invariants", "refinement", "closure",
                     "choose_unique",
                     "second enumeration at RM=1: 1461 states"])
    small = paxoscommit.pins_for(config, ballots=1)
    assert (small["generated"], small["distinct"], small["depth"],
            small["refine"]["moved"], small["stuttering"]) == (
                4141, 545, 12, 220, 2436)
    control = paxoscommit.pins_for(config, ballots=1,
                                   mutant="commit-on-any")
    assert control["control"] == "commit-on-any"
    assert control["violated"] == ["TCConsistent",
                                   "TCSpec: [TCNext]_rmState"]
    assert control["edge"]["source"]["rmState"] == {
        "r1": "prepared", "r2": "working"}
    assert control["edge"]["successor"]["rmState"] == {
        "r1": "committed", "r2": "working"}
    with open(os.path.join(BENCH, "reference", "paxoscommit.py")) as f:
        text = f.read()
    assert "import jaxtlc" not in text and "from jaxtlc" not in text


def test_the_cell_runs_end_to_end_through_run_py_at_the_small_rung(
        checkout):
    """The cell under its own name through run.py - entry, gate, the
    two readers - with the model at `Ballot = {0}` (the cfg edited in
    the checkout: a set constant is no JSON value) and the rung's pins;
    then the mutant module through the same entry: not correct."""
    import shutil

    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import paxoscommit

    co = checkout
    src = os.path.join(REPO, "specs", "PaxosCommit.toolbox", "Model_1")
    model = os.path.join(co.root, "specs_pc")
    shutil.copytree(src, model)
    cfg_path = os.path.join(model, "MC.cfg")
    with open(cfg_path) as f:
        cfg = f.read()
    with open(cfg_path, "w") as f:
        f.write(cfg.replace("Ballot = {0, 1}", "Ballot = {0}"))
    config = load_json(co.path("configs", "paxoscommit-mc.json"))
    pins = paxoscommit.pins_for(config, ballots=1)
    config["pins"] = dict(
        generated=pins["generated"], distinct=pins["distinct"],
        depth=pins["depth"], refine=pins["refine"],
        action_generated={a: n for a, n in
                          pins["action_generated"].items() if n})
    config["request"] = dict(config["request"], config=cfg_path,
                             chunk=256, qcap=4096, fpcap=16384)
    with open(co.path("configs", "paxoscommit-mc.json"), "w") as f:
        json.dump(config, f)
    rc, line, text = co.run(CELL, seed=2**31 + 353, seconds=1.0)
    assert rc == 0 and line["correct"] is True, text[-2000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"states_per_s", "setup_s"}
    rc, line, text = co.run(CELL, seed=2**31 + 359, seconds=1.0, trace=1)
    assert rc == 0 and line["failed"] == 0, text[-2000:]
    assert line["metrics"]["action_prop_moved_pct"] == dict(
        value=pytest.approx(100.0 * 220 / 4140), unit="%")
    assert line["metrics"]["action_prop_build_ms"]["value"] > 0
    assert {"lane_live_pct", "state_row_fill_pct", "struct_build_ms",
            "level_ms", "fp_load_pct"} <= set(line["metrics"])
    # the mutant: every job a violation, which the gate refuses
    with open(os.path.join(model, "PaxosCommit.tla")) as f:
        module = f.read()
    was = r'\/ /\ \A rm \in RM : Decided(rm, "prepared")'
    assert was in module
    with open(os.path.join(model, "PaxosCommit.tla"), "w") as f:
        f.write(module.replace(was, was.replace(r"\A rm", r"\E rm")))
    rc, line, text = co.run(CELL, seed=2**31 + 367, seconds=1.0)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert "no action_property event for TCSpec" in text
