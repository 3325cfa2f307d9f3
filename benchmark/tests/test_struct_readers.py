"""The three struct-compile readers (layers/struct_build_ms, lane_live_pct,
slot_live_pct) on a recorded run_view: benchmark/testdata/run_view-struct.json
holds two api.run_check checks of the Paxos model at Ballot == 0..1
(3,921 states) on the CPU, as entries/run_check.py returns them, with the
program's recorder rows.  Only spans and counters are checked; the walls
in them are a CPU's.  The cell's configuration and traffic files are held
to the contract here too."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("struct_build_ms", "lane_live_pct", "slot_live_pct")
CELL = "paxos-mc.struct-exhaustive"


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-struct.json")) as f:
        run = json.load(f)
    return run


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["step_lanes"], final["step_slots"],
            final["state_words"]) == (80, 32, 3)
    assert final["states_expanded"] == final["distinct"] == 3921
    assert final["lane_fires"] == final["generated"] - 1 == 23562
    assert read("lane_live_pct", recorded) == pytest.approx(
        100.0 * 23562 / (3921 * 80))
    assert read("slot_live_pct", recorded) == pytest.approx(
        100.0 * 23562 / (3921 * 32))
    # a check's struct spans: one load and two memo look-ups, all hits
    rows = [dict(zip(("id", "name", "t0", "t1"), r))
            for r in recorded["spans"]]
    per_job = []
    for j in recorded["jobs"]:
        mine = [r for r in rows if r["t0"] >= j["start_t"]
                and r["t1"] <= j["done_t"]
                and r["name"] in ("build.struct.load", "build.struct")]
        assert [r["name"] for r in mine].count("build.struct.load") == 1
        per_job.append(sum(r["t1"] - r["t0"] for r in mine))
    got = read("struct_build_ms", recorded)
    assert min(per_job) * 1e3 <= got <= max(per_job) * 1e3
    assert 0 < got < 1e3 * min(j["done_t"] - j["start_t"]
                               for j in recorded["jobs"])


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the spans and counters (the parent), a hand
    kernel's final event, a window with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"]
                     if not r[1].startswith("build.struct")]
    for j in bare["jobs"]:
        final = final_of(j)
        for k in ("step_lanes", "step_slots", "state_words",
                  "states_expanded", "lane_fires", "struct_traps"):
            final.pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_slot_live_pct_of_an_uncompacted_step_is_lane_live_pct(recorded):
    for j in recorded["jobs"]:
        final_of(j)["step_slots"] = final_of(j)["step_lanes"]
    assert read("slot_live_pct", recorded) == read("lane_live_pct", recorded)


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "paxos-mc", "struct-exhaustive")
    conf = next(c for c in bench["configs"] if c["name"] == "paxos-mc")
    assert "tlaplus/Examples specifications/Paxos/Paxos.tla" in conf["source"]
    assert conf["reduced"] == config["reduced"] == [
        "refinement", "SYMMETRY", "scale"]
    for key in ("source", "reduced_why", "assumed", "guarantees",
                "deployment", "pins", "pins_from", "request"):
        assert config.get(key), key
    assert config["entry"] == "run_check" and config["reference"] == "paxos"
    assert config["request"]["frontend"] == "struct"
    assert os.path.exists(os.path.join(REPO, config["request"]["config"]))
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert set(config["pins"]["action_generated"]) == {
        "Phase1a", "Phase1b", "Phase2a", "Phase2b"}
    assert sum(config["pins"]["action_generated"].values()) == (
        config["pins"]["generated"] - 1)
    assert traffic["loop"] == "closed" and traffic["trace"]["busy_budget_s"] == 2.0
    assert "inside" in traffic["trace_why"]
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert layers == set(NAMES) | {
        "level_ms", "fp_load_pct", "call_host_pct", "device_idle_pct.batch",
        "hbm_peak_bytes", "build_ms", "build_trace_ms", "build_load_ms",
        "loop_wait_pct"}
    for name in layers:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert (m["layer"], m["moves"]) == ("struct compile",
                                                "states_per_s")


def test_reference_prints_the_small_rungs_pins():
    """benchmark/reference/paxos.py, which made the configuration's pins
    (547.9 s at the cell's rung), on the two small rungs."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import paxos

    small = dict(deployment=dict(Acceptor=[1, 2, 3], Value=[1, 2],
                                 Ballot=[0, 1], quorum_size=2))
    assert paxos.pins_of(small) == dict(
        generated=23563, distinct=3921, depth=17,
        action_generated={"Phase1a": 7842, "Phase1b": 2448,
                          "Phase2a": 1560, "Phase2b": 11712},
        universe_bits=72, widest_level=780, max_assignments=14)
    # the control: dedup by 12 bits of a salted hash loses states
    assert paxos.pins_of(small, fp_bits=12, fp_salt=7)["distinct"] < 3921
