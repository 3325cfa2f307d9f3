"""run.Tracer with the profiler and the program's spans faked: which job
a slice goes into, on which thread it starts and stops, and that the
served cell's profiler stops only after the last verdict."""

from __future__ import annotations

import sys
import threading
import time

import pytest
from conftest import BENCH

sys.path.insert(0, BENCH)
import run  # noqa: E402
import span_read  # noqa: E402


class FakeProfiler:
    def __init__(self, monkeypatch):
        import jax

        self.calls = []  # (what, thread, seconds since construction)
        self.t0 = time.time()
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **kw: self._note("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: self._note("stop"))

    def _note(self, what):
        self.calls.append((what, threading.get_ident(),
                           time.time() - self.t0))

    def whats(self):
        return [c[0] for c in self.calls]


def tracer(tmp_path, mix, seconds, warm=None, log=None):
    lines = [] if log is None else log
    return run.Tracer(str(tmp_path), mix, seconds, lines.append,
                      warm), lines


def shapes(monkeypatch, by_start):
    """span_read.job_shape answers from a table keyed by the job's
    start."""
    monkeypatch.setattr(span_read, "job_shape",
                        lambda a, b: by_start.get(round(a, 3)))


CLOSED = dict(loop="closed", trace=dict(busy_budget_s=0.2, loop_share=0.3,
                                        start_share=0.5, slice_s=0.1))


def test_whole_jobs_are_traced_on_the_callers_thread(tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    # the warm job is ten times a timed one (first-use loads): L <= budget
    shapes(monkeypatch, {100.0: (5.0, 0.08), 200.0: (0.5, 0.08)})
    monkeypatch.setattr(span_read, "loop_started",
                        lambda since: since + 0.01)
    tr, log = tracer(tmp_path, CLOSED, 3.0, warm=(100.0, 106.0))
    assert tr._shape == (5.0, 0.08, pytest.approx(6.0))
    tr.window_opens()
    # by the warm job's 6 s every job is the last: job 0 is watched, not
    # believed, and 0 + 2 x (0.01 + 0.08 + 0.92) x 1.15 < 3.0 is not it
    tr.before_job(0)
    time.sleep(0.15)
    assert prof.whats() == [] and tr.placed is None
    tr.after_job(0, 200.0, 200.6)
    assert tr._shape == (0.5, 0.08, pytest.approx(0.6)) and tr._timed
    tr._t0 -= 1.0
    tr.before_job(1)  # 1.0 + 3 x 0.6 x 1.15 > 3.0: taken for the last
    assert prof.whats() == ["start"]
    tr.after_job(1, 300.0, 300.6)
    assert prof.whats() == ["start"]  # two jobs fit the budget
    tr.before_job(2)
    tr.after_job(2, 300.6, 301.2)
    assert prof.whats() == ["start", "stop"]
    assert {c[1] for c in prof.calls} == {threading.get_ident()}
    tr.before_job(3)  # one slice a run
    assert prof.whats() == ["start", "stop"]
    assert tr.placed["mode"] == "whole" and tr.placed["jobs"] == 2
    assert (tr.placed["job"], tr.placed["h"], tr.placed["L"]) == (
        1, 0.5, 0.08)
    tr.finish(timeout=2.0)
    assert "no .xplane.pb" in tr.error  # the fake wrote none
    assert any("job 1" in ln and "h=0.500 L=0.080" in ln for ln in log)


def test_a_window_that_ends_inside_the_whole_jobs_still_stops(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    shapes(monkeypatch, {100.0: (0.5, 0.08), 200.0: (0.5, 0.08)})
    monkeypatch.setattr(span_read, "loop_started", lambda since: None)
    tr, _ = tracer(tmp_path, CLOSED, 1.5, warm=(100.0, 100.6))
    tr.window_opens()
    tr.before_job(0)
    tr.after_job(0, 200.0, 200.6)
    tr._t0 -= 0.6
    tr.before_job(1)  # 0.6 + 3 x 0.6 x 1.15 > 1.5
    tr.after_job(1, 300.0, 300.6)
    tr.finish(timeout=2.0)  # no third job came
    assert prof.whats() == ["start", "stop"]


def test_the_only_job_of_a_window_gives_its_loop_where_whole_jobs_were_wanted(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    shapes(monkeypatch, {100.0: (5.0, 0.1)})  # the warm job: L <= budget
    monkeypatch.setattr(
        span_read, "loop_started",
        lambda since: since + 0.3 if time.time() >= since + 0.3 else None)
    tr, log = tracer(tmp_path, CLOSED, 0.5, warm=(100.0, 106.0))
    tr.window_opens()
    t_job = time.time()
    tr.before_job(0)  # seen at 0.3 s: 0 + 2 x (0.3 + 0.1 + 0.9) x 1.15 > 0.5
    time.sleep(0.6)
    assert prof.whats() == ["start", "stop"]
    assert tr.placed["mode"] == "loop" and tr.placed["length_s"] == 0.1
    assert tr.slice_t0 - t_job == pytest.approx(0.3, abs=0.08)
    assert any("the window's only one" in ln for ln in log)
    tr.finish(timeout=2.0)


def test_a_slice_inside_the_loop_waits_for_the_last_job_and_its_loop(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    now = time.time()
    # every finished job: 0.05 s of host work, a 0.5 s loop
    monkeypatch.setattr(span_read, "job_shape", lambda a, b: (0.05, 0.5))
    # a running job's loop shows in the recorder 0.2 s after its start (a
    # build four times the last job's), once it has begun
    monkeypatch.setattr(
        span_read, "loop_started",
        lambda since: since + 0.2 if time.time() >= since + 0.2 else None)
    mix = dict(CLOSED, trace=dict(CLOSED["trace"], busy_budget_s=0.1))
    tr, log = tracer(tmp_path, mix, 2.0, warm=(now - 1.0, now - 0.45))
    tr.window_opens()
    tr.before_job(0)  # watched; decided when its loop begins
    time.sleep(0.3)  # 0 + 2 x (0.2 + 0.5) x 1.15 < 2.0: not the last
    assert tr.placed is None and prof.whats() == []
    assert any("job 0 began its loop 0.2" in ln and "not the last" in ln
               for ln in log)
    tr.after_job(0, now, now + 0.55)
    tr._t0 -= 1.0  # a second later
    t_job = time.time()
    tr.before_job(1)
    assert tr.placed is None  # not before its loop has begun
    time.sleep(0.3)  # 1.0 + 2 x 0.7 x 1.15 > 2.0: the last
    assert tr.placed["mode"] == "loop" and tr.placed["job"] == 1
    assert tr.placed["start_s"] == pytest.approx(0.05 + 0.3 * 0.5)
    assert tr.placed["length_s"] == 0.1 and tr.placed["sure"]
    time.sleep(0.4)
    assert prof.whats() == ["start", "stop"]
    assert {c[1] for c in prof.calls} != {threading.get_ident()}
    # 0.3 x 0.5 s into the loop as SEEN (0.2 s), not as estimated (0.05)
    assert tr.placed["loop_seen_s"] == pytest.approx(0.2, abs=0.06)
    assert tr.slice_t0 - t_job == pytest.approx(0.35, abs=0.08)
    assert prof.calls[1][2] - prof.calls[0][2] == pytest.approx(0.1,
                                                                 abs=0.05)
    monkeypatch.setattr(span_read, "job_shape", lambda a, b: (0.2, 0.5))
    tr.after_job(1, t_job, t_job + 0.7)
    assert tr.placed["inside"] is True
    assert any("lay inside the loop" in ln for ln in log)
    tr.finish(timeout=2.0)
    assert prof.whats() == ["start", "stop"]


def test_a_loop_that_never_shows_is_placed_by_the_estimate_and_reported(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    answers = iter([(0.05, 0.5), (0.40, 0.2)])  # warm; then the traced job
    monkeypatch.setattr(span_read, "job_shape",
                        lambda a, b: next(answers))
    monkeypatch.setattr(span_read, "loop_started", lambda since: None)
    monkeypatch.setattr(run.Tracer, "GIVE_UP_S", 0.0)
    mix = dict(CLOSED, trace=dict(CLOSED["trace"], busy_budget_s=0.1))
    tr, log = tracer(tmp_path, mix, 1.0, warm=(0.0, 0.55))
    tr.window_opens()
    t_job = time.time()
    tr.before_job(0)  # at 0.1 s: 0 + 2 x 0.55 x 1.15 > 1.0, by the estimate
    time.sleep(0.45)
    assert prof.whats() == ["start", "stop"]
    assert tr.placed["loop_seen_s"] is None
    assert any("did not show: by the estimate" in ln for ln in log)
    assert tr.slice_t0 - t_job == pytest.approx(0.2, abs=0.06)
    tr.after_job(0, t_job, t_job + 0.6)  # its loop ran 0.40 - 0.60 s
    assert tr.placed["inside"] is False
    assert any("DID NOT lie inside" in ln for ln in log)
    tr.finish(timeout=2.0)


def test_a_job_that_ends_without_a_loop_stands_its_watcher_down(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    monkeypatch.setattr(span_read, "job_shape", lambda a, b: (0.05, 0.5))
    monkeypatch.setattr(span_read, "loop_started", lambda since: None)
    mix = dict(CLOSED, trace=dict(CLOSED["trace"], busy_budget_s=0.1))
    tr, _ = tracer(tmp_path, mix, 1.0, warm=(0.0, 0.55))
    tr.window_opens()
    tr.before_job(0)
    tr.after_job(0, 10.0, 10.6)
    tr._threads[-1].join(1.0)
    assert not tr._threads[-1].is_alive()
    tr.finish(timeout=2.0)
    assert prof.whats() == [] and "no job was taken" in tr.error


def test_without_an_estimate_the_clock_places_and_with_one_it_does_not(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    shapes(monkeypatch, {})  # no recorder: no job says anything
    tr, _ = tracer(tmp_path, CLOSED, 1.0, warm=(0.0, 1.0))
    tr.window_opens()
    tr.before_job(0)
    assert prof.whats() == []
    time.sleep(0.5 + 0.1 + 0.2)  # start_share x 1.0, slice_s
    assert prof.whats() == ["start", "stop"]
    assert prof.calls[0][2] == pytest.approx(0.5, abs=0.1)
    tr.finish(timeout=2.0)
    # an estimate by then: the clock's thread stands down, and a window
    # in which no job was taken for the last says so
    prof2 = FakeProfiler(monkeypatch)
    monkeypatch.setattr(span_read, "job_shape", lambda a, b: (0.05, 0.5))
    tr, _ = tracer(tmp_path, CLOSED, 10.0)
    tr.window_opens()
    tr.before_job(0)  # no estimate yet: nothing to place by
    tr.after_job(0, 0.0, 0.55)
    tr.before_job(1)  # watched: its loop never shows
    tr.after_job(1, 0.55, 1.1)
    tr._threads[0].join(6.0)
    tr.finish(timeout=2.0)
    assert prof2.whats() == []
    assert "no job was taken for the last" in tr.error
    assert "0.050, 0.500, 0.550" in tr.error


def test_served_traffic_the_profiler_stops_after_the_last_verdict(
        tmp_path, monkeypatch):
    prof = FakeProfiler(monkeypatch)
    mix = dict(loop="open", trace=dict(slice_s=0.2))
    tr, log = tracer(tmp_path, mix, 0.6)
    tr.window_opens()
    tr.before_job(0)  # open loop: the hooks are not wired, and harmless
    time.sleep(0.6 + 0.3)  # the window and 0.3 s of drain
    assert prof.whats() == ["start"]  # the slice is over, not the trace
    assert prof.calls[0][2] == pytest.approx(0.4, abs=0.1)
    assert tr.slice_t1 - tr.slice_t0 == pytest.approx(0.2, abs=0.05)
    tr.finish(timeout=2.0)  # the last verdict is in hand
    assert prof.whats() == ["start", "stop"]
    assert prof.calls[1][2] >= 0.9
    assert tr.placed["mode"] == "end of the window"


def test_span_reads_doors_on_the_live_recorder():
    """span_read.job_shape, loop_started and rows_between against the
    program's own recorder: a job's loop is seen as soon as its first
    `loop.dispatch` has closed, its shape only once the `loop` has."""
    from conftest import REPO

    sys.path.insert(0, REPO)
    from jaxtlc.obs import spans

    t0 = time.time()
    with spans.span("check"):
        with spans.span("build"):
            time.sleep(0.05)
        with spans.span("loop"):
            assert span_read.loop_started(t0) is None
            with spans.span("loop.dispatch"):
                pass
            seen = span_read.loop_started(t0)
            assert t0 + 0.05 <= seen <= time.time()
            assert span_read.job_shape(t0, time.time()) is None
            time.sleep(0.05)
    t1 = time.time()
    h, loop_s = span_read.job_shape(t0, t1)
    assert 0.05 <= h < 0.15 and 0.05 <= loop_s < 0.15
    assert seen == pytest.approx(t0 + h, abs=0.01)
    assert span_read.loop_started(t1) is None  # the next job's: not yet
    assert [r["name"] for r in span_read.rows_between(t0, t1)] == [
        "build", "loop.dispatch", "loop", "check"]
