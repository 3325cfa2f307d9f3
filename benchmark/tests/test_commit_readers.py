"""The seven readers of the commit's own counts (layers/
compact_sort_fill_pct, enqueue_sort_fill_pct, sort_whole_width_pct,
probe_fill_pct, claim_fill_pct, straggler_rounds_per_body,
checker_fresh_pct; commit_read.py has what they share) on a recorded
run_view: benchmark/testdata/run_view-commit.json holds two
api.run_check checks of the LamportMutex model at maxClock = 3 (10,209
kept states, 41,533 generated) at chunk 2,048 on the CPU - 55,296
candidate lanes a body, so both ladders have rungs, and the invariants
deferred - as entries/run_check.py returns them, with the program's
recorder rows.  Each reader gives the number worked out by hand from the
`check.result` span's attributes; only counts are checked, the walls in
the view are a CPU's.  BENCHMARK.json is held to membership, never to an
exact list."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest
from conftest import BENCH, REPO

sys.path.insert(0, BENCH)
from run import load_json, load_module, metrics_of  # noqa: E402

NAMES = ("compact_sort_fill_pct", "enqueue_sort_fill_pct",
         "sort_whole_width_pct", "probe_fill_pct", "claim_fill_pct",
         "straggler_rounds_per_body", "checker_fresh_pct")
# every cell a reader finds something in, as BENCHMARK.json lists them
WIDE, RECHECK, MESH, CHANNELS, REFINE = (
    "kubeapi-1x2ff.exhaustive", "kubeapi-model1.recheck",
    "kubeapi-2x1ff.sharded4", "lamportmutex-mc.struct-constrained",
    "paxoscommit-mc.struct-refinement")


@pytest.fixture
def recorded():
    with open(os.path.join(BENCH, "testdata", "run_view-commit.json")) as f:
        return json.load(f)


def read(name, run):
    return load_module("layers", name).read(run)


def blocks(run):
    """The `check.result` rows' attributes, in the view's order."""
    return [r[7] for r in run["spans"] if r[1] == "check.result"]


def test_the_view_holds_the_block_the_program_writes(recorded):
    a, b = blocks(recorded)
    assert a == b  # two checks of one model: the counts are its constants
    final = next(e for e in recorded["jobs"][0]["events"]
                 if e["event"] == "final")
    # the span's attributes are `final`'s fields of the same names
    assert {k: final[k] for k in a} == a
    assert (final["generated"], final["distinct"],
            final["constraint_discarded"]) == (41533, 10209, 10042)
    # the block's identities, on the recorded numbers
    assert a["commit_valid"] == 41533 - 1 - 10042
    assert a["commit_new"] == 10209 - 1
    assert a["commit_claimed"] + a["commit_stragglers"] == 10208
    assert sum(a["commit_compact_rung"]) == a["commit_bodies"] == sum(
        a["commit_enqueue_rung"])
    assert a["commit_width"] == 2048 * 27
    assert a["commit_compact_ladder"] == [16384, 32768, 55296]
    assert a["commit_enqueue_ladder"] == [4096, 16384, 32768, 55296]


def test_each_reader_gives_the_number_worked_out_by_hand(recorded):
    a = blocks(recorded)[0]
    # 31 bodies; every compaction at the 16,384 rung, every enqueue
    # order at the probe width
    assert (a["commit_bodies"], a["commit_compact_rung"],
            a["commit_enqueue_rung"]) == (31, [31, 0, 0], [31, 0, 0, 0])
    assert read("compact_sort_fill_pct", recorded) == pytest.approx(
        100.0 * 31490 / (31 * 16384))
    assert read("enqueue_sort_fill_pct", recorded) == pytest.approx(
        100.0 * 10931 / (31 * 4096))
    assert read("sort_whole_width_pct", recorded) == 0.0
    # 30 probe segments and 30 checker trips of 4,096 rows; 39 blocks of
    # 512 rows scattered for 10,208 claims; no claim walked
    assert (a["commit_probe_segments"], a["commit_checker_trips"],
            a["commit_claim_blocks"], a["commit_claim_block"]) == (
        30, 30, 39, 512)
    assert read("probe_fill_pct", recorded) == pytest.approx(
        100.0 * 10931 / (30 * 4096))
    assert read("claim_fill_pct", recorded) == pytest.approx(
        100.0 * 10208 / (39 * 512))
    assert read("straggler_rounds_per_body", recorded) == 0.0
    assert read("checker_fresh_pct", recorded) == pytest.approx(
        100.0 * 10208 / (30 * 4096))


def edited(run, **attrs):
    """The view with every `check.result` row's attributes updated."""
    out = copy.deepcopy(run)
    for r in out["spans"]:
        if r[1] == "check.result":
            r[7].update(attrs)
    return out


def without(run, *keys):
    """The view with `keys` taken off every `check.result` row."""
    out = copy.deepcopy(run)
    for r in out["spans"]:
        if r[1] == "check.result":
            for k in keys:
                r[7].pop(k, None)
    return out


def test_rungs_and_rounds_enter_as_counted(recorded):
    """Other histograms, by hand: 20 bodies compact at 16,384, 8 at
    32,768 and 3 at the whole 55,296; 3 enqueue orders at the whole
    array; 62 walk rounds over 31 bodies."""
    run = edited(recorded, commit_compact_rung=[20, 8, 3],
                 commit_enqueue_rung=[25, 3, 0, 3], commit_walk_rounds=62)
    assert read("compact_sort_fill_pct", run) == pytest.approx(
        100.0 * 31490 / (20 * 16384 + 8 * 32768 + 3 * 55296))
    assert read("enqueue_sort_fill_pct", run) == pytest.approx(
        100.0 * 10931 / (25 * 4096 + 3 * 16384 + 3 * 55296))
    assert read("sort_whole_width_pct", run) == pytest.approx(
        100.0 * (3 + 3) / (31 + 31))
    assert read("straggler_rounds_per_body", run) == 2.0


@pytest.mark.parametrize("name", NAMES)
def test_a_view_without_the_attrs_gives_none(recorded, name):
    """The parent's line: a `check.result` span with no block (a commit
    before PR 50), and a view with no such span at all."""
    bare = copy.deepcopy(recorded)
    for r in bare["spans"]:
        if r[1] == "check.result":
            r[7] = {}
    assert read(name, bare) is None
    bare["spans"] = [r for r in bare["spans"] if r[1] != "check.result"]
    assert read(name, bare) is None


def test_what_a_geometry_does_not_define_is_left_out(recorded):
    """A ladder of one rung is no ladder (the recheck cell's
    compaction: 32,768 lanes or fewer), the mesh's enqueue has none at
    all, and an immediate checker makes no trips: None, and the line
    leaves the metric out; the others still read."""
    one = edited(recorded, commit_compact_ladder=[55296],
                 commit_compact_rung=[31])
    # the enqueue's ladder still has rungs (the recheck cell's two): it
    # alone is judged
    assert read("sort_whole_width_pct", one) == 0.0
    assert read("compact_sort_fill_pct", one) == pytest.approx(
        100.0 * 31490 / (31 * 55296))
    mesh = without(one, "commit_enqueue_ladder", "commit_enqueue_rung")
    assert read("sort_whole_width_pct", mesh) is None
    assert read("enqueue_sort_fill_pct", mesh) is None
    assert read("compact_sort_fill_pct", mesh) is not None
    immediate = edited(recorded, commit_checker_trips=0)
    assert read("checker_fresh_pct", immediate) is None
    assert read("probe_fill_pct", immediate) is not None


@pytest.mark.parametrize("name", NAMES)
def test_counts_without_their_widths_are_no_metric(recorded, name):
    """A result whose caller named no geometry carries the counts
    alone (jaxtlc.engine.bfs.result_from_carry without `commit`): no
    ratio can be taken, and none is."""
    bare = without(recorded, "commit_width", "commit_probe_width",
                   "commit_claim_block", "commit_compact_ladder",
                   "commit_enqueue_ladder")
    assert read(name, bare) is None


def test_benchmark_json_lists_the_readers_by_membership():
    b = load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NAMES:
        m = by_name[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "states_per_s"
        assert m["layer"] == ("engine step" if name == "checker_fresh_pct"
                              else "fingerprint set")
        assert m["unit"] == ("rounds" if name.endswith("_body") else "%")
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
        for cell in (WIDE, CHANNELS, REFINE):
            assert cell in m["workloads"]
            assert name in [x["name"] for x in metrics_of(
                b, "per_layer", cell)]
    # the cells whose geometry leaves a reader nothing to read are not
    # listed for it: a listed cell's traced line has to carry the metric
    assert RECHECK in by_name["sort_whole_width_pct"]["workloads"]
    assert RECHECK not in by_name["checker_fresh_pct"]["workloads"]
    assert MESH not in by_name["enqueue_sort_fill_pct"]["workloads"]
    assert MESH not in by_name["sort_whole_width_pct"]["workloads"]
    for name in ("compact_sort_fill_pct", "probe_fill_pct",
                 "claim_fill_pct", "straggler_rounds_per_body"):
        assert RECHECK in by_name[name]["workloads"]
        assert MESH in by_name[name]["workloads"]
    assert MESH in by_name["checker_fresh_pct"]["workloads"]
    assert by_name["sort_whole_width_pct"]["better"] == "lower"
    assert by_name["straggler_rounds_per_body"]["better"] == "lower"
