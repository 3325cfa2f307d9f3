"""benchmark/trace_reduce.py: the arithmetic on planes made by hand, and
the whole reduction on the small TPU trace kept in
benchmark/testdata/ (recorded by testdata/record.py on a v5e)."""

from __future__ import annotations

import os
import sys

import pytest
from conftest import BENCH

sys.path.insert(0, BENCH)
import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "kubeapi-ff-2checks.xplane.pb.gz")


def planes(dev_events, host_events=(), line="XLA Ops"):
    return [
        dict(name="/device:TPU:0",
             lines=[dict(name=line, events=list(dev_events)),
                    dict(name="XLA Modules",
                         events=[(0.0, 10e9, "jit_whole_program")])]),
        dict(name="/host:CPU", lines=[dict(name="python",
                                           events=list(host_events))]),
    ]


def test_busy_is_a_union_and_idle_its_complement():
    r = trace_reduce.reduce_planes(planes(
        [(1e9, 3e9, "fusion.1"), (2e9, 4e9, "fusion.2"),  # overlap: 3 s
         (6e9, 7e9, "copy.3")],
        [(0.0, 10e9, "bench:trace_slice")]))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["idle_pct"] == pytest.approx(60.0)


def test_nested_ops_count_their_own_time_once():
    r = trace_reduce.reduce_planes(planes(
        [(0.0, 8e9, "while.7"), (1e9, 3e9, "fusion.1"),
         (3e9, 6e9, "fusion.2"), (3.5e9, 4e9, "fusion.1")],
        [(0.0, 8e9, "bench:trace_slice")]))
    ops = dict(r["device_ops"])
    assert r["busy_s"] == pytest.approx(8.0) and r["idle_pct"] == 0.0
    assert ops["fusion.1"] == pytest.approx(2.5)
    assert ops["fusion.2"] == pytest.approx(2.5)
    assert ops["while.7"] == pytest.approx(3.0)  # 8 less its children
    assert [k for k, _ in r["device_ops"]][0] == "while.7"


def test_events_are_clipped_to_the_slice_and_gaps_named_by_host_span():
    r = trace_reduce.reduce_planes(planes(
        [(0.0, 2e9, "fusion.1"), (5e9, 6e9, "fusion.2"),
         (9e9, 12e9, "fusion.3")],
        [(1e9, 10e9, "bench:trace_slice"), (1.5e9, 5.2e9, "bench:wait"),
         (5.9e9, 9.5e9, "bench:submit"), (0.0, 20e9, "not_ours")]))
    assert r["window_s"] == pytest.approx(9.0)
    assert r["busy_s"] == pytest.approx(1.0 + 1.0 + 1.0)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench:wait", pytest.approx(3.0)]
    assert gaps[1] == ["bench:submit", pytest.approx(3.0)]
    assert dict(r["idle_by_host_span"]) == {
        "bench:wait": pytest.approx(3.0), "bench:submit": pytest.approx(3.0)}


def test_no_device_plane_is_no_idle_share():
    r = trace_reduce.reduce_planes(
        [dict(name="/host:CPU", lines=[dict(name="t", events=[
            (0.0, 1e9, "bench:trace_slice")])])])
    assert r["n_devices"] == 0 and r["idle_pct"] is None
    assert r["busy_s"] == 0.0


def test_falls_back_to_other_lines_where_no_xla_ops_line():
    r = trace_reduce.reduce_planes(planes(
        [(0.0, 1e9, "op")], [(0.0, 4e9, "bench:trace_slice")],
        line="Ops"))
    assert r["busy_s"] == pytest.approx(1.0)  # "XLA Modules" is skipped


@pytest.mark.skipif(not os.path.exists(TRACE),
                    reason="no recorded trace in benchmark/testdata")
def test_recorded_tpu_trace():
    import json

    with open(os.path.join(BENCH, "testdata", "kubeapi-ff-2checks.expect.json")) as f:
        want = json.load(f)
    r = trace_reduce.reduce_file(TRACE)
    assert r["n_devices"] == want["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["idle_pct"] == pytest.approx(want["idle_pct"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert [k for k, _ in r["device_ops"]] == [
        k for k, _ in want["device_ops"]]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # two checks, each inside the harness's own span (recorded before
    # the program had spans of its own)
    assert {k for k, _ in r["idle_gaps"]} <= {
        "bench:check_with_checkpoints", trace_reduce.NO_SPAN}


@pytest.mark.skipif(not os.path.exists(TRACE),
                    reason="no recorded trace in benchmark/testdata")
def test_recorded_trace_gaps_take_the_programs_innermost_span():
    """The program's closed spans, as the harness feeds them from the
    recorder on the host clock: the recorded trace's two long gaps
    (0.47-1.94 s and 2.56-4.20 s of the slice, each a check's host part)
    are named by the innermost `jaxtlc:` span over their midpoints, and
    a gap no program span covers keeps the harness's."""
    t = 5000.0  # the slice's start on the host clock
    spans = [("bench:check_with_checkpoints", t + 0.0, t + 1.96),
             ("jaxtlc:check", t + 0.01, t + 1.95),
             ("jaxtlc:build", t + 0.40, t + 1.93),
             ("jaxtlc:build.lower", t + 0.90, t + 1.50),  # midpoint 1.20
             ("jaxtlc:build", t + 2.50, t + 4.19),
             ("jaxtlc:build.trace", t + 2.60, t + 3.00),  # midpoint 3.38:
             ("jaxtlc:build.compile", t + 3.10, t + 4.15)]  # this one
    r = trace_reduce.reduce_file(TRACE, host_spans=spans, slice_t0=t)
    plain = trace_reduce.reduce_file(TRACE)
    assert [g[1] for g in r["idle_gaps"]] == [
        g[1] for g in plain["idle_gaps"]]  # the same gaps, renamed
    assert r["idle_gaps"][0] == ["jaxtlc:build.compile",
                                 pytest.approx(1.644034023)]
    assert r["idle_gaps"][1] == ["jaxtlc:build.lower",
                                 pytest.approx(1.470546383)]
    # 0.17-0.36 s: inside `check`, before `build`
    assert r["idle_gaps"][2] == ["jaxtlc:check", pytest.approx(0.190951246)]
    # 2.17-2.32 s: the second check, which the fed spans do not cover
    assert r["idle_gaps"][3] == ["bench:check_with_checkpoints",
                                 pytest.approx(0.152391067)]
    assert (r["busy_s"], r["window_s"]) == (plain["busy_s"],
                                            plain["window_s"])


def test_innermost_span_wins_and_the_trace_holds_both_kinds():
    r = trace_reduce.reduce_planes(planes(
        [(0.0, 1e9, "fusion.1"), (9e9, 10e9, "fusion.2")],
        [(0.0, 10e9, "bench:trace_slice"), (0.5e9, 9.5e9, "bench:run_check"),
         (0.6e9, 9.4e9, "jaxtlc:check"), (2e9, 8e9, "jaxtlc:build"),
         (4e9, 6e9, "jaxtlc:build.lower"), (0.0, 20e9, "other:span")]))
    assert r["idle_gaps"] == [["jaxtlc:build.lower", pytest.approx(8.0)]]


def test_a_span_that_began_before_the_slice_still_names_the_gap():
    # the harness's own log, on the host clock: the check began 20 s
    # before the profiler did, so the trace itself does not hold its span
    r = trace_reduce.reduce_planes(
        planes([(2e9, 3e9, "fusion.1")], [(1e9, 5e9, "bench:trace_slice")]),
        host_spans=[("bench:check_with_checkpoints", 980.0, 1040.0),
                    ("bench:run_check", 900.0, 950.0)],
        slice_t0=1000.0)
    assert r["window_s"] == pytest.approx(4.0)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench:check_with_checkpoints"] * 2
    assert dict(r["idle_by_host_span"]) == {
        "bench:check_with_checkpoints": pytest.approx(3.0)}
