"""Host spans (jaxtlc/obs/spans.py, ISSUE 24): the recorder's own
arithmetic, the spans a `check_with_checkpoints` call leaves, and the
device scopes (jax.named_scope) on the engine's stages.

The supervised check's spans are pinned in tests/test_obs.py (on the
golden `obs_run` fixture) and the pooled job's in tests/test_service.py
(on the shared CheckServer) - each where its compile is already paid.
"""

import contextlib
import re
import threading
import time
from collections import deque

import jax
import pytest

from jaxtlc.config import ModelConfig
from jaxtlc.obs import spans
from jaxtlc.obs.spans import span

FF = ModelConfig(False, False)
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)
SCOPES = ("jaxtlc.expand", "jaxtlc.pack_fp", "jaxtlc.dedup",
          "jaxtlc.fpset", "jaxtlc.enqueue", "jaxtlc.level")
BUILD = ("build.engine", "build.init", "build.trace", "build.lower",
         "build.compile")
# a segment's spans (ISSUE 37: `loop.readback` over its two halves);
# `check_with_checkpoints` emits in `loop.overlap` and has no `.emit`
SEGMENT = ("loop.dispatch", "loop.overlap", "loop.wait", "loop.readback",
           "loop.readback.get")
SUPERVISED = SEGMENT + ("loop.readback.emit",)


def assert_tree(rows, root_name, min_cover=0.95):
    """One root, one job identifier, every child inside its parent, and
    the root's direct children covering >= min_cover of it."""
    by_id = {r.id: r for r in rows}
    roots = [r for r in rows if r.name == root_name]
    assert len(roots) == 1, [r.name for r in rows]
    root = roots[0]
    assert len({r.job for r in rows}) == 1
    for r in rows:
        assert r.t1 >= r.t0
        if r is not root:
            p = by_id[r.parent]  # KeyError: an orphan
            assert p.t0 <= r.t0 and r.t1 <= p.t1, (r.name, p.name)
    covered = sum(r.t1 - r.t0 for r in rows if r.parent == root.id)
    assert covered >= min_cover * (root.t1 - root.t0), (
        covered, root.t1 - root.t0)
    return root


# ---- the recorder -------------------------------------------------------


def test_nesting_parent_and_self_time():
    t = time.time()
    with span("a", k=1) as a:
        with span("a.b"):
            time.sleep(0.01)
        with span("a.c") as c:
            with span("a.c.d"):
                time.sleep(0.01)
            c.attrs["late"] = True
    rows = {r.name: r for r in spans.snapshot(since=t)}
    assert set(rows) == {"a", "a.b", "a.c", "a.c.d"}
    assert rows["a"].parent == 0 and rows["a"].attrs == {"k": 1}
    assert rows["a.b"].parent == rows["a.c"].parent == rows["a"].id
    assert rows["a.c.d"].parent == rows["a.c"].id
    assert rows["a.c"].attrs == {"late": True}
    assert a.seconds == rows["a"].t1 - rows["a"].t0 >= 0.02
    # a parent spans its children, which do not overlap
    dur = {n: r.t1 - r.t0 for n, r in rows.items()}
    assert dur["a"] >= dur["a.b"] + dur["a.c"] and dur["a.c"] >= dur["a.c.d"]
    assert rows["a.b"].t1 <= rows["a.c"].t0
    # rows land in closing order; `since` cuts on the closing time
    assert [r.name for r in spans.snapshot(since=t)][-1] == "a"
    assert spans.snapshot(since=time.time() + 1) == []


def test_the_bound_and_dropped(monkeypatch):
    monkeypatch.setattr(spans, "_rows", deque(maxlen=4))
    monkeypatch.setattr(spans, "MAX_ROWS", 4)
    monkeypatch.setattr(spans, "dropped", 0)
    for i in range(7):
        with span(f"s{i}"):
            pass
    assert [r.name for r in spans.snapshot()] == ["s3", "s4", "s5", "s6"]
    assert spans.dropped == 3


def test_an_exception_still_closes_the_span_and_restores_the_parent():
    t = time.time()
    with span("outer") as outer:
        with pytest.raises(ZeroDivisionError):
            with span("boom"):
                1 / 0
        with span("after"):
            pass
    rows = {r.name: r for r in spans.snapshot(since=t)}
    assert rows["boom"].parent == rows["after"].parent == outer.id


def test_two_threads_and_the_job_identifier():
    """Each thread has its own parent chain; a job set on the scheduler
    thread names every span opened there and no span elsewhere."""
    t = time.time()
    seen = {}

    def scheduler():
        with spans.job("job-1") as ctx:
            with span("sched.run"):
                with span("pool.run"):
                    time.sleep(0.01)
            seen["rows"] = spans.journal_event()["rows"]
            seen["kept"] = [r.name for r in ctx.rows]

    th = threading.Thread(target=scheduler)
    with span("elsewhere"):
        th.start()
        th.join()
    rows = {r.name: r for r in spans.snapshot(since=t)}
    assert rows["sched.run"].parent == 0  # not under the other thread's
    assert rows["pool.run"].parent == rows["sched.run"].id
    assert rows["sched.run"].thread == rows["pool.run"].thread
    assert rows["elsewhere"].thread != rows["sched.run"].thread
    assert rows["elsewhere"].job is None
    assert rows["sched.run"].job == rows["pool.run"].job == "job-1"
    assert seen["kept"] == ["pool.run", "sched.run"]  # not the other's
    assert [(r[0], r[3]) for r in seen["rows"]] == [
        ("pool.run", 1), ("sched.run", -1)]
    assert spans.journal_event() == dict(rows=[], attrs={})  # no job here


def test_many_threads_lose_no_row(monkeypatch):
    """The recorder is shared by every thread of the process: with more
    writers than cores and a short switch interval, rows kept plus rows
    dropped is rows written, and every id is distinct."""
    import sys

    monkeypatch.setattr(spans, "_rows", deque(maxlen=5000))
    monkeypatch.setattr(spans, "MAX_ROWS", 5000)
    monkeypatch.setattr(spans, "dropped", 0)
    n_threads, per = 32, 400

    def writer():
        for _ in range(per // 2):
            with span("outer"):
                with span("inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer)
                   for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    rows = spans.snapshot()
    assert len(rows) == 5000
    assert len(rows) + spans.dropped == n_threads * per
    assert len({r.id for r in rows}) == len(rows)
    by_id = {r.id: r for r in rows}
    for r in rows:  # a parent is the same thread's, never a neighbour's
        if r.name == "inner" and r.parent in by_id:
            assert by_id[r.parent].thread == r.thread


def test_check_names_the_job_by_ordinal_and_nests_once():
    t = time.time()

    @spans.in_check
    def inner():
        with span("loop"):
            pass

    @spans.in_check
    def entry():
        with span("build"):
            pass
        inner()

    entry()
    entry()
    with spans.job("served-7"):
        entry()
    rows = spans.snapshot(since=t)
    checks = [r for r in rows if r.name == "check"]
    assert len(checks) == 3  # the inner entry shared the outer's
    a, b = (int(c.job.split("-")[1]) for c in checks[:2])
    assert b == a + 1 and checks[2].job == "served-7"
    for c in checks:
        mine = [r for r in rows if r.job == c.job]
        assert sorted(r.name for r in mine) == ["build", "check", "loop"]
        assert all(r.parent == c.id for r in mine if r is not c)


def test_the_journal_counts_its_cost_and_the_closing_span_carries_it(
        tmp_path):
    from jaxtlc.obs.journal import RunJournal

    t = time.time()
    with span("check.journal_close") as closing:
        j = RunJournal(str(tmp_path / "j.jsonl"))
        j.event("progress", depth=1, generated=2, distinct=2, queue=0)
        j.event("progress", depth=2, generated=3, distinct=3, queue=0)
        j.close()
        closing.attrs.update(j.cost())
    (row,) = spans.snapshot(since=t)
    assert row.attrs["events"] == 2 and row.attrs["fsyncs"] == 2
    assert 0 < row.attrs["seconds"] <= row.t1 - row.t0
    assert j.seconds == pytest.approx(row.attrs["seconds"], abs=1e-6)


def test_a_span_costs_microseconds():
    """The budget's arithmetic: 16 spans a pooled job must stay far
    under 50 us each, or 'always on' is not honest."""
    with span("warm"):
        pass
    n = 2000
    t = time.perf_counter()
    for _ in range(n):
        with span("x"):
            pass
    per = (time.perf_counter() - t) / n
    assert per < 50e-6, per


# ---- a check_with_checkpoints call --------------------------------------


@pytest.fixture(scope="module")
def ckpt_calls():
    """Two calls of the driver (the second is the warm one) and, with
    the scopes patched out, a third: (rows, result, compile requests)
    of each."""
    from jaxtlc.engine.checkpoint import check_with_checkpoints
    from jaxtlc.runtime import CompileMeter

    meter = CompileMeter.instance()

    def call():
        t, n = time.time(), meter.count
        r = check_with_checkpoints(FF, ckpt_every=16, max_segments=3,
                                   **KW)
        return spans.snapshot(since=t), r, meter.count - n

    first, warm = call(), call()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        bare = call()
    return first, warm, bare


def test_check_with_checkpoints_leaves_every_span(ckpt_calls):
    rows, r, _ = ckpt_calls[1]
    root = assert_tree(rows, "check")
    names = [x.name for x in rows]
    for n in ("build", "loop", "check.result", *BUILD):
        assert names.count(n) == 1, n
    for n in SEGMENT:
        assert names.count(n) == r.iterations == 3, n
    assert len(rows) <= 13 + 5 * r.iterations  # the budget
    assert "loop.readback.emit" not in names
    by_name = {x.name: x for x in rows}
    by_id = {x.id: x for x in rows}
    for n in BUILD:
        assert by_name[n].parent == by_name["build"].id
    for x in rows:
        if x.name == "loop.readback.get":
            assert by_id[x.parent].name == "loop.readback"
        elif x.name in SEGMENT:
            assert x.parent == by_name["loop"].id
    assert by_name["build"].parent == by_name["loop"].parent == root.id
    c = by_name["build.compile"].attrs
    assert c["requests"] >= 1 and c["backend_s"] >= 0
    assert set(c) == {"requests", "cache_hits", "backend_s",
                      "retrieval_s"}


def test_scopes_change_no_count_and_no_compile_request(ckpt_calls):
    (_, r1, _), (_, r2, n_warm), (_, r3, n_bare) = ckpt_calls
    sig = [(r.generated, r.distinct, r.depth, r.queue_left, r.violation,
            tuple(sorted(r.action_generated.items())))
           for r in (r1, r2, r3)]
    assert sig[0] == sig[1] == sig[2]
    assert n_warm == n_bare  # a warm call's compile requests


# ---- the device scopes --------------------------------------------------


def lowered_segment():
    from jaxtlc.engine.bfs import make_engine

    init_fn, _, step_fn = make_engine(FF, donate=False, **KW)
    return step_fn.segment(4).trace(jax.eval_shape(init_fn)).lower()


def test_lowered_segment_holds_the_six_scopes_and_the_same_program(
        monkeypatch):
    scoped = lowered_segment()
    text = scoped.as_text(debug_info=True)
    for name in SCOPES:
        assert name in text, name
    # the innermost scope is what a trace attributes an op to: the
    # probe/claim sits inside dedup, pack_fp inside expand
    assert re.search(r"jaxtlc\.dedup/(\S*/)?jaxtlc\.fpset/", text)
    assert re.search(r"jaxtlc\.expand/(\S*/)?jaxtlc\.pack_fp/", text)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lowered_segment()
    assert "jaxtlc." not in bare.as_text(debug_info=True)
    # scopes are metadata: without locations the programs are one text
    assert scoped.as_text() == bare.as_text()
