"""The two channel readers (layers/seq_cap_build_ms, state_row_fill_pct)
on a recorded run_view: benchmark/testdata/run_view-channels.json holds
two api.run_check checks of the LamportMutex model at maxClock = 3
(10,209 kept states under the cfg's CONSTRAINT, 10,042 of 41,532
successors discarded; six FIFO channels of three record slots) on the
CPU, as entries/run_check.py returns them, with the program's recorder
rows.  Only spans and counters are checked; the walls in them are a
CPU's.  The cell's configuration, traffic and reference files are held to
the contract here too."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import find_cell, load_json, load_module, metrics_of  # noqa: E402

NAMES = ("seq_cap_build_ms", "state_row_fill_pct")
CELL = "lamportmutex-mc.struct-constrained"
COUNTERS = ("state_bits", "seq_slots", "seq_cap_from", "seq_widen")
SPAN = "build.struct.seqcap"


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata",
                           "run_view-channels.json")) as f:
        return json.load(f)


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_run(recorded):
    final = final_of(recorded["jobs"][0])
    assert (final["generated"], final["distinct"], final["depth"]) == (
        41533, 10209, 31)
    assert final["constraint_discarded"] == 10042
    assert final["struct_traps"] == 0
    # at maxClock = 3: 123 bits in four words; six channels of three
    # slots, the capacity BoundedNetwork declares, no rung taken
    assert (final["state_bits"], final["state_words"]) == (123, 4)
    assert (final["seq_slots"], final["seq_cap_from"],
            final["seq_widen"]) == (18, "declared", 0)
    assert read("state_row_fill_pct", recorded) == pytest.approx(
        100.0 * 123 / 128)
    # a warm check pays the walk too: the loader settles the capacities
    # on every load, nine declared bounds (the diagonal's among them)
    rows = [dict(zip(("id", "name", "t0", "t1", "parent", "job",
                      "thread", "attrs"), r)) for r in recorded["spans"]]
    per_job = []
    for j in recorded["jobs"]:
        mine = [r for r in rows if r["t0"] >= j["start_t"]
                and r["t1"] <= j["done_t"] and r["name"] == SPAN]
        assert len(mine) == 1 and mine[0]["attrs"] == {"declared": 9}
        per_job.append(sum(r["t1"] - r["t0"] for r in mine))
    got = read("seq_cap_build_ms", recorded)
    assert min(per_job) * 1e3 <= got <= max(per_job) * 1e3
    assert got < read("struct_build_ms", recorded)  # it lies inside them
    # the readers the cell shares read this run too; the two constraint
    # readers, which BENCHMARK.json does not list it under, would as well
    assert read("lane_live_pct", recorded) == pytest.approx(
        100.0 * 41532 / (10209 * 27))
    assert read("constraint_discard_pct", recorded) == pytest.approx(
        100.0 * 10042 / 41532)


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the span and the counters (the parent), a window
    with no correct job."""
    bare = copy.deepcopy(recorded)
    bare["spans"] = [r for r in bare["spans"] if r[1] != SPAN]
    for j in bare["jobs"]:
        for k in COUNTERS:
            final_of(j).pop(k)
    assert read(name, bare) is None
    broken = copy.deepcopy(recorded)
    for j in broken["jobs"]:
        j["findings"] = ["distinct 1, want 2"]
    assert read(name, broken) is None


def test_row_fill_follows_the_layout_not_the_counts(recorded):
    for j in recorded["jobs"]:
        final_of(j).update(state_bits=150, state_words=5)
    assert read("state_row_fill_pct", recorded) == pytest.approx(93.75)


def test_cell_configuration_and_traffic_follow_the_contract():
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "lamportmutex-mc", "struct-constrained")
    conf = next(c for c in bench["configs"]
                if c["name"] == "lamportmutex-mc")
    want = ("tlaplus/Examples specifications/lamport_mutex/"
            "LamportMutex.tla + MCLamportMutex")
    assert conf["source"].startswith(want) and len(conf["source"]) <= 200
    assert config["source"].startswith(want)
    assert conf["reduced"] == config["reduced"] == []  # nothing is cut
    for key in ("source", "stands_for", "reduced_why", "assumed",
                "guarantees", "deployment", "pins", "pins_from",
                "request"):
        assert config.get(key), key
    assert "PLACEHOLDER" not in json.dumps(config)
    for key in ("module", "model", "geometry", "accounting"):
        assert config["assumed"][key], key
    for key in ("search", "counts", "invariants", "deadlock", "constraint",
                "sequences", "dedup", "device_path"):
        assert config["guarantees"][key], key
    # past 64 bits the guarantee is equality with the reference's count
    assert "not by injectivity" in config["guarantees"]["dedup"]
    dep = config["deployment"]
    assert (dep["N"], dep["maxClock"], dep["constraint"]) == (
        3, 6, "ClockConstraint")
    assert dep["invariants"] == ["TypeOK", "BoundedNetwork", "Mutex"]
    assert (dep["channels"], dep["channel_capacity"]) == (6, 3)
    assert config["entry"] == "run_check"
    assert config["reference"] == "lamportmutex"
    assert config["request"]["frontend"] == "struct"
    # the cfg's lines, no flag: neither the constraint nor a capacity
    assert not {"constraint", "constraints", "seq_cap", "constants"} & set(
        config["request"])
    assert config["request"]["config"].endswith(
        "LamportMutex.toolbox/Model_1/MC.cfg")
    cfg = os.path.join(REPO, config["request"]["config"])
    with open(cfg) as f:
        text = f.read()
    for word in ("N = 3", "maxClock = 6", "CONSTRAINT", "ClockConstraint",
                 "TypeOK", "BoundedNetwork", "Mutex"):
        assert word in text
    assert "CHECK_DEADLOCK" not in text
    with open(os.path.join(os.path.dirname(cfg), "LamportMutex.tla")) as f:
        module = f.read()
    for form in (r"Len(network[p][q]) <= 3", r"clock[p] <= maxClock",
                 r"\union", "SUBSET Proc", "Seq(Message)",
                 "Append(network[s][r], m)"):
        assert form in module
    assert config["env"] == {"JAXTLC_ARTIFACT_CACHE": "off"}
    assert config["engines"] == ["single"]
    pins = config["pins"]
    assert set(pins["action_generated"]) == {
        "Request", "ReceiveRequest", "ReceiveAck", "Enter", "Exit",
        "ReceiveRelease"}
    assert sum(pins["action_generated"].values()) == (
        pins["generated"] - dep["initial_states"])
    assert traffic["loop"] == "closed"
    assert traffic["trace"]["busy_budget_s"] == 2.0
    e2e = {m["name"] for m in metrics_of(bench, "end_to_end", CELL)}
    assert e2e >= {"states_per_s", "setup_s"}
    layers = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    # at least the lists ewd998-mc.struct-constrained is in (a later
    # benchmark PR may append the cell to more, the two constraint_*
    # lists first)
    assert layers >= set(NAMES) | {
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
            assert CELL in m["workloads"]


def test_reference_prints_the_small_rungs_pins_and_passes_its_checks():
    """benchmark/reference/lamportmutex.py, which made the
    configuration's pins, at maxClock = 3, with every self-check, and
    the keep-discarded control."""
    sys.path.insert(0, os.path.join(BENCH, "reference"))
    import lamportmutex

    config = load_json(os.path.join(BENCH, "configs",
                                    "lamportmutex-mc.json"))
    got = lamportmutex.pins_for(config, max_clock=3)
    for k in ("seconds", "seconds_with_checks"):
        got.pop(k)
    assert got == dict(
        generated=41533, distinct=10209, depth=31,
        action_generated={"Request": 6275, "ReceiveRequest": 16793,
                          "ReceiveAck": 9267, "Enter": 1416, "Exit": 708,
                          "ReceiveRelease": 7073},
        discarded=10042, discarded_inits=0, widest_level=912,
        longest_channel=3, n_initial=1,
        self_checks=["invariants", "closure", "discarded_invariants",
                     "longest_channel",
                     "second enumeration at maxClock=3: 10209 kept, "
                     "9058 distinct discards"])
    control = lamportmutex.pins_for(config, max_clock=3,
                                    keep_discarded=True)
    assert control["distinct"] > 10209 and control["control"]
    with open(os.path.join(BENCH, "reference", "lamportmutex.py")) as f:
        text = f.read()
    assert "import jaxtlc" not in text and "from jaxtlc" not in text
