"""The three mesh-engine readers (layers/shard_skew_pct, route_fill_pct,
route_ici_pct + mesh_read.py) on a recorded run_view:
benchmark/testdata/run_view-mesh.json holds two api.run_check -sharded 4
checks of the KubeAPI 1x1 FF rung (8,203 states) at tiny sizes on the
CPU's four-device virtual mesh, as entries/run_check.py returns them.
Only the counters are checked; the wall in them is a CPU's."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH

sys.path.insert(0, BENCH)
from run import load_module  # noqa: E402

NAMES = ("shard_skew_pct", "route_fill_pct", "route_ici_pct")
COUNTERS = ("shard_distinct", "shard_generated", "route_max_fill",
            "route_bucket", "route_bytes")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-mesh.json")) as f:
        run = json.load(f)
    # the peaks are a TPU's: the recorded device is a CPU
    run["device"]["kind"] = "TPU v5 lite"
    return run


def read(name, run):
    return load_module("layers", name).read(run)


def final_of(job):
    return next(e for e in job["events"] if e["event"] == "final")


def test_readers_give_the_numbers_of_the_recorded_counters(recorded):
    final = final_of(recorded["jobs"][0])
    shards = final["shard_distinct"]
    assert sum(shards) == final["distinct"] == 8203
    assert read("shard_skew_pct", recorded) == pytest.approx(
        100.0 * (max(shards) / (8203 / 4) - 1.0))
    assert read("route_fill_pct", recorded) == pytest.approx(
        100.0 * final["route_max_fill"] / final["route_bucket"])
    walls = sorted(final_of(j)["wall_s"] for j in recorded["jobs"])
    lo, hi = (100.0 * final["route_bytes"] * 8 / w / 1600e9 for w in
              (walls[-1], walls[0]))
    assert lo <= read("route_ici_pct", recorded) <= hi
    assert 0 < read("route_ici_pct", recorded) <= 100


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_on_a_parent_style_view(recorded, name):
    """A commit before the counters (the parent), a one-chip engine, a
    window with no correct job, a device the peaks do not know."""
    parent = copy.deepcopy(recorded)
    for j in parent["jobs"]:
        for k in COUNTERS:
            final_of(j).pop(k, None)
    assert read(name, parent) is None
    failed = copy.deepcopy(recorded)
    for j in failed["jobs"]:
        j["ok"] = False
    assert read(name, failed) is None
    if name == "route_ici_pct":
        recorded["device"]["kind"] = "cpu"
        assert read(name, recorded) is None
