"""The two symmetry readers (layers/canon_moved_pct, symmetry_build_ms) on a
recorded run_view: benchmark/testdata/run_view-sym.json holds two
api.run_check checks of the Paxos model under its cfg's SYMMETRY at
Ballot == 0..1 (443 orbits of 3,921 states) on the CPU, as
entries/run_check.py returns them, with the program's recorder rows.  Only
spans and counters are checked; the walls in them are a CPU's.  The cell's
configuration, traffic and reference files are held to the contract here
too."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("canon_moved_pct", "symmetry_build_ms")
CELL = "paxos-mc-sym.struct-exhaustive"
SYM_COUNTERS = ("sym_perms", "sym_sets", "canon_rows", "canon_moved",
                "sym_cert_checks", "sym_cert_trips")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-sym.json")) as f:
        return json.load(f)


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["generated"], final["distinct"], final["depth"]) == (
        2697, 443, 17)
    assert (final["sym_perms"], final["sym_sets"]) == (12, 2)
    assert final["canon_rows"] == final["generated"] - 1
    assert final["sym_cert_trips"] == 0 < final["sym_cert_checks"]
    assert read("canon_moved_pct", recorded) == pytest.approx(
        100.0 * final["canon_moved"] / final["canon_rows"])
    assert 0 < read("canon_moved_pct", recorded) < 100
    # a warm check's symmetry spans: the loader's evaluation of the cfg's
    # definition; the memo hits, so no verification and no plan build
    rows = [dict(zip(("id", "name", "t0", "t1"), r))
            for r in recorded["spans"]]
    per_job = []
    for j in recorded["jobs"]:
        mine = [r for r in rows if r["t0"] >= j["start_t"]
                and r["t1"] <= j["done_t"]
                and r["name"] == "build.struct.symmetry"]
        assert len(mine) == 1
        per_job.append(sum(r["t1"] - r["t0"] for r in mine))
    got = read("symmetry_build_ms", recorded)
    assert min(per_job) * 1e3 <= got <= max(per_job) * 1e3
    assert got < read("struct_build_ms", recorded)  # it lies inside them
    # the shared struct readers read the reduced run too
    assert read("lane_live_pct", recorded) == pytest.approx(
        100.0 * 2696 / (443 * 80))
    assert read("slot_live_pct", recorded) == pytest.approx(
        100.0 * 2696 / (443 * 32))


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the span and the counters (the parent), an
    unreduced run, a window with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"]
                     if r[1] != "build.struct.symmetry"]
    for j in bare["jobs"]:
        for k in SYM_COUNTERS:
            final_of(j).pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_canon_moved_pct_reads_zero_when_the_reduction_does_not_engage(
        recorded):
    for j in recorded["jobs"]:
        final_of(j)["canon_moved"] = 0
    assert read("canon_moved_pct", recorded) == 0.0


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "paxos-mc-sym", "struct-sym-exhaustive")
    conf = next(c for c in bench["configs"] if c["name"] == "paxos-mc-sym")
    want = ("tlaplus/Examples specifications/Paxos/Paxos.tla + "
            "MCPaxos.tla/.cfg (SYMMETRY kept)")
    assert conf["source"].startswith(want)
    assert config["source"].startswith(want)
    assert conf["reduced"] == config["reduced"] == ["refinement", "scale"]
    for key in ("source", "reduced_why", "assumed", "guarantees",
                "deployment", "pins", "pins_from", "request"):
        assert config.get(key), key
    assert "PLACEHOLDER" not in json.dumps(config)
    assert "12" in config["guarantees"]["symmetry"]
    assert "generators" in config["assumed"]["accounting"] or (
        "listed functions" in config["assumed"]["accounting"])
    assert config["deployment"]["symmetry"]["group_order"] == 12
    assert config["entry"] == "run_check"
    assert config["reference"] == "paxos_sym"
    assert config["request"]["frontend"] == "struct"
    assert "symmetry" not in config["request"]  # the cfg's line, no flag
    assert config["request"]["config"].endswith("Model_sym/MC.cfg")
    assert os.path.exists(os.path.join(REPO, config["request"]["config"]))
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert config["engines"] == ["single"]
    assert set(config["pins"]["action_generated"]) == {
        "Phase1a", "Phase1b", "Phase2a", "Phase2b"}
    assert sum(config["pins"]["action_generated"].values()) == (
        config["pins"]["generated"] - 1)
    # one representative an orbit: fewer than the unreduced cell's pins
    plain = load_json(os.path.join(BENCH, "configs", "paxos-mc.json"))
    assert config["deployment"]["unreduced_distinct"] == (
        plain["pins"]["distinct"])
    assert config["pins"]["distinct"] * 12 >= plain["pins"]["distinct"]
    assert config["pins"]["distinct"] < plain["pins"]["distinct"]
    assert config["pins"]["depth"] == plain["pins"]["depth"]
    # a loop of ~2 s fits the budget, so the slice is a whole job: the
    # check's duty cycle, whatever the build takes (placement.py)
    assert traffic["loop"] == "closed"
    assert traffic["trace"]["busy_budget_s"] == 2.0
    assert "whole job" in traffic["trace_why"]
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert layers == set(NAMES) | {
        "level_ms", "fp_load_pct", "call_host_pct", "device_idle_pct.batch",
        "hbm_peak_bytes", "build_ms", "build_trace_ms", "build_load_ms",
        "loop_wait_pct", "struct_build_ms", "lane_live_pct",
        "slot_live_pct"}
    for name in layers:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert (m["layer"], m["moves"]) == ("struct compile",
                                                "states_per_s")
            assert m["workloads"] == [CELL]


def test_reference_prints_the_small_rungs_pins_and_passes_its_checks():
    """benchmark/reference/paxos_sym.py, which made the configuration's
    pins (174.1 s at the cell's rung), on the smallest rung, with its
    self-check (a) and the generators-only control."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import paxos_sym

    small = dict(deployment=dict(Acceptor=[1, 2, 3], Value=[1, 2],
                                 Ballot=[0, 1], quorum_size=2))
    assert paxos_sym.pins_of(small) == dict(
        generated=2697, distinct=443, depth=17,
        action_generated={"Phase1a": 886, "Phase1b": 298,
                          "Phase2a": 268, "Phase2b": 1244},
        widest_level=78, group_order=12, moved=547, orbit_size_sum=3921)
    check = paxos_sym.self_check(3, 2, 2, 2)
    assert check == dict(n_bal=2, unreduced=3921, closed=True,
                         canonical_forms=443, reduced_distinct=443,
                         orbit_size_sum=3921, ok=True)
    assert paxos_sym.pins_of(small, generators_only=True)["distinct"] == 457
    with open(os.path.join(BENCH, "reference", "paxos_sym.py")) as f:
        assert "jaxtlc" not in f.read().replace("jaxtlc/struct", "")
