"""The two constraint readers (layers/constraint_discard_pct,
constraint_build_ms) on a recorded run_view:
benchmark/testdata/run_view-constrained.json holds two api.run_check
checks of the EWD998 model under its cfg's CONSTRAINT at N = 2 (6,236
kept states, 2,032 of 31,168 successors discarded) on the CPU, as
entries/run_check.py returns them, with the program's recorder rows.
Only spans and counters are checked; the walls in them are a CPU's.  The
cell's configuration, traffic and reference files are held to the
contract here too."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("constraint_discard_pct", "constraint_build_ms")
CELL = "ewd998-mc.struct-constrained"
COUNTERS = ("constraint_rows", "constraint_discarded", "constraint_names")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata",
                           "run_view-constrained.json")) as f:
        return json.load(f)


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["generated"], final["distinct"], final["depth"]) == (
        31184, 6236, 29)
    assert final["constraint_rows"] == final["generated"] - 16
    assert final["constraint_discarded"] == 2032
    assert final["constraint_names"] == ["StateConstraint"]
    assert final["struct_traps"] == 0
    start = next(e for e in recorded["jobs"][0]["events"]
                 if e["event"] == "run_start")
    assert start["params"]["constraints"] == ["StateConstraint"]
    assert read("constraint_discard_pct", recorded) == pytest.approx(
        100.0 * 2032 / 31168)
    # a warm check's constraint spans: the loader's resolution of the
    # cfg's names; the backend memo hits, so no compile of the predicate
    rows = [dict(zip(("id", "name", "t0", "t1"), r))
            for r in recorded["spans"]]
    per_job = []
    for j in recorded["jobs"]:
        mine = [r for r in rows if r["t0"] >= j["start_t"]
                and r["t1"] <= j["done_t"]
                and r["name"] == "build.struct.constraint"]
        assert len(mine) == 1
        per_job.append(sum(r["t1"] - r["t0"] for r in mine))
    got = read("constraint_build_ms", recorded)
    assert min(per_job) * 1e3 <= got <= max(per_job) * 1e3
    assert got < read("struct_build_ms", recorded)  # it lies inside them
    # the shared struct reader reads the constrained run too: the lanes
    # that fired count the discarded successors
    assert read("lane_live_pct", recorded) == pytest.approx(
        100.0 * 31168 / (6236 * 10))


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the span and the counters (the parent), a model
    without a CONSTRAINT, a window with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"]
                     if r[1] != "build.struct.constraint"]
    for j in bare["jobs"]:
        for k in COUNTERS:
            final_of(j).pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_discard_pct_reads_zero_when_the_constraint_does_not_engage(
        recorded):
    for j in recorded["jobs"]:
        final_of(j)["constraint_discarded"] = 0
    assert read("constraint_discard_pct", recorded) == 0.0


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "ewd998-mc", "struct-constrained")
    conf = next(c for c in bench["configs"] if c["name"] == "ewd998-mc")
    want = ("tlaplus/Examples specifications/ewd998/EWD998.tla + "
            "EWD998.cfg")
    assert conf["source"].startswith(want)
    assert config["source"].startswith(want)
    assert conf["reduced"] == config["reduced"] == [
        "refinement", "properties", "scale"]
    for key in ("source", "reduced_why", "assumed", "guarantees",
                "deployment", "pins", "pins_from", "request"):
        assert config.get(key), key
    assert "PLACEHOLDER" not in json.dumps(config)
    for key in ("module", "model", "geometry", "accounting"):
        assert config["assumed"][key], key
    dep = config["deployment"]
    assert (dep["N"], dep["constraint"]) == (3, "StateConstraint")
    # the source's bounds, unchanged
    assert dep["constraint_bounds"] == dict(counter=3, pending=3,
                                            token_q=9)
    assert dep["invariants"] == ["TypeOK", "Inv", "TerminationDetection"]
    assert config["entry"] == "run_check"
    assert config["reference"] == "ewd998"
    assert config["request"]["frontend"] == "struct"
    # the cfg's line, no flag
    assert not {"constraint", "constraints"} & set(config["request"])
    assert config["request"]["config"].endswith("EWD998.toolbox/Model_1/"
                                                "MC.cfg")
    cfg = os.path.join(REPO, config["request"]["config"])
    with open(cfg) as f:
        text = f.read()
    assert "CONSTRAINT" in text and "StateConstraint" in text
    with open(os.path.join(os.path.dirname(cfg), "EWD998.tla")) as f:
        module = f.read()
    assert "counter[i] <= 3 /\\ pending[i] <= 3" in module
    assert "token.q <= 9" in module
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert config["engines"] == ["single"]
    pins = config["pins"]
    assert set(pins["action_generated"]) == {
        "InitiateProbe", "PassToken", "SendMsg", "RecvMsg", "Deactivate"}
    assert sum(pins["action_generated"].values()) == (
        pins["generated"] - dep["initial_states"])
    assert traffic["loop"] == "closed"
    assert traffic["trace"]["busy_budget_s"] == 2.0
    assert traffic["trace"]["loop_share"] == 0.3
    assert "whole job" in traffic["trace_why"]
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert layers == set(NAMES) | {
        "level_ms", "fp_load_pct", "call_host_pct", "device_idle_pct.batch",
        "hbm_peak_bytes", "build_ms", "build_trace_ms", "build_load_ms",
        "loop_wait_pct", "struct_build_ms", "lane_live_pct",
        "readback_ms", "readback_emit_ms", "scope_cover_pct"}
    for name in layers:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] in NAMES:
            assert (m["layer"], m["moves"]) == ("struct compile",
                                                "states_per_s")
            assert m["workloads"] == [CELL]


def test_reference_prints_the_small_rungs_pins_and_passes_its_checks():
    """benchmark/reference/ewd998.py, which made the configuration's
    pins (24 s at the cell's rung, 44 s with its self-checks), at
    N = 2, with every self-check, and the keep-discarded control."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import ewd998

    config = load_json(os.path.join(BENCH, "configs", "ewd998-mc.json"))
    got = ewd998.pins_for(config, n=2)
    for k in ("seconds", "seconds_with_checks", "kept_ranges"):
        got.pop(k)
    assert got == dict(
        generated=31184, distinct=6236, depth=29,
        action_generated={"InitiateProbe": 10562, "PassToken": 486,
                          "SendMsg": 5950, "RecvMsg": 8220,
                          "Deactivate": 5950},
        discarded=2032, discarded_inits=0, widest_level=602,
        n_initial=16,
        self_checks=["invariants", "sums", "closure",
                     "discarded_invariants",
                     "second enumeration at N=2: 6236 kept, 2032 "
                     "discards"])
    control = ewd998.pins_for(config, n=2, keep_discarded=True)
    assert control["distinct"] > 6236 and control["control"]
    with open(os.path.join(BENCH, "reference", "ewd998.py")) as f:
        assert "import jaxtlc" not in f.read()
