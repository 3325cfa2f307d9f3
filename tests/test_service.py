"""Checking-as-a-service tests (ISSUE 9).

Budget discipline (tier-1 runs ~800 s of its 870 s ceiling): ONE
module-scoped CheckServer owns the only sweep-class compile; the
bit-for-bit parity test reuses that warm engine (shared fixture, no
extra engine compiles beyond the one sweep compile + its sequential
AOT twin), and the independent baked-constant baseline runs the same
TwoPhaseB geometry so the struct-cache memo shares what it can.

Pinned here:

* server e2e: POST /jobs -> FIFO schedule -> sweep batch -> job-scoped
  SSE stream -> verdict -> /runs registry (the acceptance flow);
* warm resubmit of an already-compiled (digest, constants-class,
  geometry) performs ZERO fresh XLA compiles (CompileMeter delta == 0);
* vmapped K-config sweep verdicts/counters bit-for-bit against K
  sequential runs of the same compiled step - final carries compared
  leaf-by-leaf, fpset TABLE words included - and counter-equal to K
  independent `api.run_check` calls on baked-constant TwoPhase
  variants;
* struct.cache LRU cap + hit/miss stats; EnginePool LRU eviction;
* obs.journal batched-fsync mode semantics.
"""

import io
import json
import os
import time
import urllib.error

import pytest

from jaxtlc.serve import client
from jaxtlc.serve.server import start_server

_TPB = """---- MODULE TwoPhaseB ----
EXTENDS Naturals, FiniteSets, TLC

CONSTANTS RM, MAXR

VARIABLES rmState, tmState, tmPrepared, msgs, reneged

vars == <<rmState, tmState, tmPrepared, msgs, reneged>>

Init == /\\ rmState = [r \\in RM |-> "working"]
        /\\ tmState = "running"
        /\\ tmPrepared = {}
        /\\ msgs = {}
        /\\ reneged = 0

Vote(r) == /\\ rmState[r] = "working"
           /\\ rmState' = [rmState EXCEPT ![r] = "prepared"]
           /\\ msgs' = msgs \\cup {[kind |-> "vote", from |-> r]}
           /\\ UNCHANGED <<tmState, tmPrepared, reneged>>

Renege(r) == /\\ rmState[r] = "working"
             /\\ reneged < MAXR
             /\\ reneged' = reneged + 1
             /\\ rmState' = [rmState EXCEPT ![r] = "aborted"]
             /\\ UNCHANGED <<tmState, tmPrepared, msgs>>

Collect(r) == /\\ tmState = "running"
              /\\ [kind |-> "vote", from |-> r] \\in msgs
              /\\ tmPrepared' = tmPrepared \\cup {r}
              /\\ UNCHANGED <<rmState, tmState, msgs, reneged>>

Decide == /\\ tmState = "running"
          /\\ tmPrepared = RM
          /\\ tmState' = "committed"
          /\\ msgs' = msgs \\cup {[kind |-> "commit"]}
          /\\ UNCHANGED <<rmState, tmPrepared, reneged>>

CallOff == /\\ tmState = "running"
           /\\ tmState' = "aborted"
           /\\ msgs' = msgs \\cup {[kind |-> "stop"]}
           /\\ UNCHANGED <<rmState, tmPrepared, reneged>>

ObeyCommit(r) == /\\ [kind |-> "commit"] \\in msgs
                 /\\ rmState[r] = "prepared"
                 /\\ rmState' = [rmState EXCEPT ![r] = "committed"]
                 /\\ UNCHANGED <<tmState, tmPrepared, msgs, reneged>>

ObeyAbort(r) == /\\ [kind |-> "stop"] \\in msgs
                /\\ rmState[r] # "committed"
                /\\ rmState[r] # "aborted"
                /\\ rmState' = [rmState EXCEPT ![r] = "aborted"]
                /\\ UNCHANGED <<tmState, tmPrepared, msgs, reneged>>

Next == \\/ Decide
        \\/ CallOff
        \\/ \\E r \\in RM : \\/ Vote(r)
                         \\/ Renege(r)
                         \\/ Collect(r)
                         \\/ ObeyCommit(r)
                         \\/ ObeyAbort(r)

Spec == /\\ Init
        /\\ [][Next]_vars

Agreement == \\A r1, r2 \\in RM : ~(/\\ rmState[r1] = "aborted"
                                  /\\ rmState[r2] = "committed")

CommitVoted == tmState = "committed" => tmPrepared = RM
====
"""


def _cfg(maxr: int) -> str:
    return (f"CONSTANT RM = {{r1, r2}}\nCONSTANT MAXR = {maxr}\n"
            "SPECIFICATION\nSpec\nINVARIANT\nAgreement\nCommitVoted\n")


_SWEEP = {"const": "MAXR", "lo": 0, "hi": 2}
_OPTS = dict(chunk=64, qcap=1 << 10, fpcap=1 << 12, nodeadlock=True)
# (generated, distinct, depth, Renege fires) per MAXR - the bounded
# 2PC family genuinely differs per config (MAXR=0 disables Renege)
_EXPECT = {0: (81, 49, 8, 0), 1: (119, 66, 8, 18), 2: (124, 68, 8, 22)}


@pytest.fixture(scope="module")
def server():
    srv = start_server(sweep_width=3)
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def sweep_jobs(server):
    """Three compatible sweep submits - the scheduler folds them into
    batched dispatches through ONE constants-class compile."""
    ids = {
        v: client.submit(server.url, _TPB, _cfg(2), name=f"tpb-max{v}",
                         constants={"MAXR": v}, sweep=_SWEEP,
                         options=_OPTS)
        for v in (0, 1, 2)
    }
    return {v: client.wait(server.url, i, timeout=600)
            for v, i in ids.items()}


# ---------------------------------------------------------------------------
# server e2e: submit -> schedule -> sweep -> SSE -> verdict -> registry
# ---------------------------------------------------------------------------


def test_server_sweep_e2e(server, sweep_jobs):
    for v, st in sweep_jobs.items():
        assert st["state"] == "done", st
        r = st["result"]
        gen, dist, depth, renege = _EXPECT[v]
        assert r["engine"] == "sweep"
        assert r["verdict"] == "ok"
        assert (r["generated"], r["distinct"], r["depth"]) == \
            (gen, dist, depth)
        assert r["action_generated"].get("Renege", 0) == renege
    stats = client.pool_stats(server.url)
    # one constants-class entry served all three configs
    assert stats["pool"]["misses"] >= 1
    assert stats["scheduler"]["batched_jobs"] == 3
    assert stats["scheduler"]["batches_run"] < 3  # folding happened


def test_job_scoped_sse_stream_and_registry(server, sweep_jobs):
    """/events?run=<job id> is the job's own SSE feed (the obs.serve
    machinery over the scheduler's per-job journal); /runs lists every
    job journal."""
    job_id = sweep_jobs[1]["id"]
    events = list(client.stream(server.url, job_id))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "final"
    assert events[0]["engine"] == "sweep"
    assert events[-1]["verdict"] == "ok"
    assert events[-1]["distinct"] == _EXPECT[1][1]
    runs = client._get(server.url + "/runs")["runs"]
    names = {r["run"] for r in runs}
    assert {st["id"] for st in sweep_jobs.values()} <= names
    by = {r["run"]: r for r in runs}
    assert by[job_id]["verdict"] == "ok"


def test_server_rejects_malformed_jobs(server):
    import urllib.error
    import urllib.request

    def post(payload):
        req = urllib.request.Request(
            server.url + "/jobs", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        return urllib.request.urlopen(req, timeout=10)

    for bad in (
        {},  # no spec/cfg
        {"spec": "not a module", "cfg": _cfg(1)},  # no MODULE header
        # sweep job without its swept constant pinned
        {"spec": _TPB, "cfg": _cfg(1), "sweep": _SWEEP},
        # sweep descriptor missing its 'hi' domain bound: a 400, not a
        # KeyError-turned-500
        {"spec": _TPB, "cfg": _cfg(1), "constants": {"MAXR": 1},
         "sweep": {"const": "MAXR", "lo": 0}},
    ):
        with pytest.raises(urllib.error.HTTPError) as e:
            post(bad)
        assert e.value.code == 400


# ---------------------------------------------------------------------------
# warm-path contract: zero fresh XLA compiles (the acceptance pin)
# ---------------------------------------------------------------------------


def test_warm_resubmit_zero_fresh_xla_compiles(server, sweep_jobs):
    """Resubmitting an already-compiled (digest, constants-class,
    geometry) must be pure warm execution: pool hit, CompileMeter
    delta exactly zero.  Covers BOTH pool paths - the plain engine and
    the batched sweep."""
    from jaxtlc.serve.pool import xla_compiles

    # plain engine: first submit builds (cold), second is warm
    cold = client.check(server.url, _TPB, _cfg(2), name="plain-cold",
                        options=_OPTS)
    assert cold["result"]["engine"] == "pool"
    assert cold["result"]["verdict"] == "ok"
    pre = xla_compiles()
    warm = client.check(server.url, _TPB, _cfg(2), name="plain-warm",
                        options=_OPTS)
    assert warm["result"]["pool_hit"] is True
    assert xla_compiles() - pre == 0, "warm plain submit recompiled"
    assert warm["result"]["generated"] == cold["result"]["generated"]

    # sweep engine: the class is warm from the fixture batch
    pre = xla_compiles()
    st = client.check(server.url, _TPB, _cfg(2), name="sweep-warm",
                      constants={"MAXR": 1}, sweep=_SWEEP,
                      options=_OPTS)
    assert st["result"]["pool_hit"] is True
    assert xla_compiles() - pre == 0, "warm sweep submit recompiled"
    assert st["result"]["distinct"] == _EXPECT[1][1]


def test_warm_job_verdict_in_one_get_without_a_pause(
        server, sweep_jobs, monkeypatch):
    """ISSUE 32: a pooled job (warm from the test above: no compile)
    answered through client.wait costs exactly ONE GET, which the
    server holds until the verdict exists; the client never pauses.
    No assertion on milliseconds: six workers share this CPU."""
    from test_overload import _GetCounter, _no_sleep

    jid = client.submit(server.url, _TPB, _cfg(2), name="plain-wait",
                        options=_OPTS)
    gets = _GetCounter(monkeypatch)
    _no_sleep(monkeypatch)
    st = client.wait(server.url, jid, timeout=600)
    assert gets.urls == [
        f"{server.url}/jobs/{jid}?wait={client._WAIT_ASK_S}"]
    assert st["state"] == "done" and st["finished_t"], st
    assert st["result"]["engine"] == "pool"
    assert st["result"]["distinct"] == _EXPECT[2][1]
    # the record a waiter receives is the one a plain GET returns
    monkeypatch.undo()
    assert client.status(server.url, jid) == st


def test_job_whose_options_still_name_the_slab_runs(server, sweep_jobs):
    """ISSUE 44: `sortfree` is no job option any more.  A client that
    still sends it is answered as it always was for an option the
    scheduler does not know - ignored - and the job ends on the pinned
    verdict through the pool."""
    jid = client.submit(server.url, _TPB, _cfg(2), name="old-client",
                        options=dict(_OPTS, sortfree=True))
    st = client.wait(server.url, jid, timeout=600)
    assert st["state"] == "done", st
    assert st["result"]["engine"] == "pool"
    assert (st["result"]["generated"], st["result"]["distinct"],
            st["result"]["depth"]) == _EXPECT[2][:3]
    assert st["options"]["sortfree"] is True  # kept as sent, not read


def test_pooled_job_leaves_every_span(server, sweep_jobs):
    """ISSUE 24: a pooled job's host spans - the scheduler thread's
    eleven (since PR 31: `build.struct.load` inside `sched.load`)
    under one `sched.run`, all carrying the served job's id, children
    inside parents, top-level children covering the dispatch - the
    HTTP handlers' threads adding nothing to the recorder, and the job
    journal's one `spans` event."""
    from test_spans import assert_tree

    from jaxtlc.obs import journal as jr
    from jaxtlc.obs import spans

    t = time.time()
    st = client.check(server.url, _TPB, _cfg(2), name="plain-spans",
                      options=_OPTS)
    assert st["result"]["engine"] == "pool"
    # a client's requests (any id, any rate) cost the recorder no row:
    # every row since the submit is the scheduler thread's, of this job
    with pytest.raises(urllib.error.HTTPError):
        client.status(server.url, "no-such-job")
    # (the waiter is woken once sched.run has closed: ISSUE 32)
    sched = spans.snapshot(since=t)
    assert {r.job for r in sched} == {st["id"]}
    root = assert_tree(sched, "sched.run")
    assert len({r.thread for r in sched}) == 1
    assert sorted(r.name for r in sched) == sorted([
        "sched.run", "sched.jobdir", "sched.load", "build.struct.load",
        "build.struct.fairness", "build.struct.seqcap", "sched.cache_lookup",
        "pool.get", "pool.carry", "pool.run",
        "pool.readback", "sched.journal", "sched.finish"])
    assert len(sched) <= 16  # the budget
    by_name = {r.name: r for r in sched}
    for n in ("pool.carry", "pool.run", "pool.readback"):
        assert by_name[n].parent == by_name["sched.journal"].id
    # the engine's wall IS the pool.run span: one clock, one pair
    assert st["result"]["wall_s"] == pytest.approx(
        by_name["pool.run"].t1 - by_name["pool.run"].t0, abs=1e-5)
    events = jr.read(os.path.join(server.root,
                                  f"{st['id']}.journal.jsonl"))
    kinds = [e["event"] for e in events]
    assert kinds.count("spans") == 1 and kinds[-2:] == ["spans", "final"]
    assert [row[0] for row in events[-2]["rows"]] == [
        r.name for r in sched if r.t1 <= events[-2]["t"]] == [
        "sched.jobdir", "build.struct.fairness", "build.struct.seqcap",
        "build.struct.load", "sched.load", "sched.cache_lookup", "pool.get", "pool.carry", "pool.run",
        "pool.readback"]
    # the journal reports what it cost itself on its closing span
    closing = by_name["sched.journal"].attrs
    assert closing["events"] == len(events) and closing["fsyncs"] >= 1
    assert 0 < closing["seconds"] < root.t1 - root.t0


def test_pooled_jobs_of_one_spec_load_it_once(server, sweep_jobs):
    """ISSUE 46: every job's spec is written into a job directory of
    its own, and the loaded model is kept by the bytes read, not by
    where they lie: the second job of a spec says `hit` on its
    `sched.load` (and on the `build.struct.load` inside it), `/pool`
    counts the `model` memo's hit, and a third job with one byte of
    the spec changed misses."""
    from jaxtlc.obs import spans

    def job(spec, name):
        before = client.pool_stats(server.url)["pool"]["memo"]["model"]
        t = time.time()
        st = client.check(server.url, spec, _cfg(2), name=name,
                          options=_OPTS)
        assert st["result"]["engine"] == "pool"
        assert (st["result"]["generated"], st["result"]["distinct"],
                st["result"]["depth"]) == _EXPECT[2][:3]
        after = client.pool_stats(server.url)["pool"]["memo"]["model"]
        memo = {r.name: r.attrs.get("memo")
                for r in spans.snapshot(since=t) if r.job == st["id"]
                and r.name in ("sched.load", "build.struct.load",
                               "build.struct.fairness")}
        return st["id"], memo, (after["hits"] - before["hits"],
                                after["misses"] - before["misses"])

    first, _, _ = job(_TPB, "memo-1")
    second, memo, counted = job(_TPB, "memo-2")
    cfgs = [os.path.join(server.root, "jobs", j, "TwoPhaseB.cfg")
            for j in (first, second)]
    assert cfgs[0] != cfgs[1] and all(map(os.path.exists, cfgs))
    assert memo == {"sched.load": "hit", "build.struct.load": "hit",
                    "build.struct.fairness": "hit"}
    assert counted == (1, 0)
    # one byte more, after the module's end: another text, another model
    _, memo, counted = job(_TPB + " ", "memo-3")
    assert memo == {"sched.load": "miss", "build.struct.load": "miss",
                    "build.struct.fairness": "miss"}
    assert counted == (0, 1)


# ---------------------------------------------------------------------------
# smoke job class: sim submits fold, reuse the warm engine (ISSUE 14)
# ---------------------------------------------------------------------------


_SIM_OPTS = dict(simulate=True, walkers=8, depth=12, fpcap=1 << 10,
                 nodeadlock=True)


def test_smoke_job_class_e2e(server, sweep_jobs):
    """The simulation job class end to end on the SHARED CheckServer:
    two smoke submits with different seeds fold into one vmapped
    dispatch through one warm sim engine (the seed is a batch lane,
    not key material), journal schema-v1 `sim` events, and a warm
    resubmit performs ZERO fresh XLA compiles."""
    from jaxtlc.serve.pool import xla_compiles

    pre_batches = client.pool_stats(server.url)["scheduler"]
    ids = {s: client.submit(server.url, _TPB, _cfg(2),
                            name=f"smoke-{s}",
                            options=dict(_SIM_OPTS, simseed=s))
           for s in (1, 2)}
    sts = {s: client.wait(server.url, i, timeout=600)
           for s, i in ids.items()}
    for s, st in sts.items():
        assert st["state"] == "done", st
        r = st["result"]
        assert r["engine"] == "sim" and r["verdict"] == "ok", r
        assert r["sim"]["seed"] == s
        assert r["sim"]["walkers"] == 8
        assert r["sim"]["transitions"] > 0
    # different seeds diverge (the TwoPhaseB walk space branches)
    assert (sts[1]["result"]["action_generated"]
            != sts[2]["result"]["action_generated"]
            or sts[1]["result"]["sim"]["distinct_est"]
            != sts[2]["result"]["sim"]["distinct_est"])
    post = client.pool_stats(server.url)["scheduler"]
    assert post["batched_jobs"] - pre_batches["batched_jobs"] == 2

    # the journal is a complete schema-valid run with a sim summary
    events = list(client.stream(server.url, sts[1]["id"]))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "final"
    assert events[0]["engine"] == "sim"
    sim_evs = [e for e in events if e["event"] == "sim"]
    assert sim_evs and sim_evs[-1]["phase"] == "summary"
    assert events[-1]["verdict"] == "ok"

    # warm resubmit of a THIRD seed: pool hit, zero fresh XLA compiles
    pre = xla_compiles()
    st = client.check(server.url, _TPB, _cfg(2), name="smoke-warm",
                      options=dict(_SIM_OPTS, simseed=3))
    assert st["result"]["engine"] == "sim"
    assert st["result"]["pool_hit"] is True
    assert xla_compiles() - pre == 0, "warm smoke submit recompiled"


# ---------------------------------------------------------------------------
# sweep parity: vmapped == sequential, bit for bit
# ---------------------------------------------------------------------------


def _sweep_engine(server):
    entries = [e for e in server.pool._entries.values()
               if e.kind == "sweep"]
    assert len(entries) == 1, "expected exactly one sweep-class entry"
    return entries[0].runner


def test_sweep_parity_bit_for_bit(server, sweep_jobs):
    """The vmapped batch and K sequential runs of the SAME compiled
    step agree on the full final carry - every pytree leaf, fpset
    TABLE words included (vmap's batched while_loop freezes each lane
    at its own fixpoint; this pins that nothing leaks across lanes)."""
    import jax
    import numpy as np

    eng = _sweep_engine(server)
    configs = [{"MAXR": v} for v in (0, 1, 2)]
    batch = eng.run(configs)
    seq = eng.run_sequential(configs)
    for b, s in zip(batch, seq):
        assert (b.generated, b.distinct, b.depth, b.violation,
                b.queue_left, b.outdegree) == \
            (s.generated, s.distinct, s.depth, s.violation,
             s.queue_left, s.outdegree)
        assert b.action_generated == s.action_generated
        assert b.action_distinct == s.action_distinct
    # leaf-level: stacked batch carry row k == config k's solo carry
    stacked_out = eng._aot(eng._stack(configs))
    for k, values in enumerate(configs):
        solo_out = eng._aot_seq(eng.carry_for(values))
        for a, b in zip(
            jax.tree_util.tree_leaves(
                jax.tree.map(lambda x: x[k], stacked_out)),
            jax.tree_util.tree_leaves(solo_out),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sweep_program_holds_the_sorts_it_held(server, sweep_jobs):
    """Under `vmap(run_fn)` a switch on a batched count runs every
    branch and selects, so a ladder of sort widths (ISSUE 49) would
    multiply the vmapped program's sorts: at the sweep's geometry (768
    candidate lanes) `fpset.sort_ladder` gives the compaction ONE rung
    and the enqueue its probe width and the whole, and the lowered
    program holds the six sorts it held before there was a ladder -
    grouping, compaction, the enqueue's two, the probe block's two
    (the round-0 claimers' and the straggler walk's compactions)."""
    import re

    from jaxtlc.engine.fpset import sort_ladder

    eng = _sweep_engine(server)
    ncand = _OPTS["chunk"] * eng.backend.n_lanes
    assert sort_ladder(ncand) == (ncand,)
    assert sort_ladder(ncand, 2 * _OPTS["chunk"]) == (
        2 * _OPTS["chunk"], ncand)
    text = eng._vrun.lower(
        eng._stack([{"MAXR": v} for v in (0, 1, 2)])).as_text()
    assert len(re.findall(r"stablehlo\.sort", text)) == 6


def test_sweep_matches_baked_constant_run_check(tmp_path, sweep_jobs):
    """Independent baseline: K `api.run_check` calls on TwoPhaseB
    variants with MAXR BAKED into the cfg (the pre-sweep path - its
    own compiled step per config) report the same verdict and the same
    generated/distinct/depth/per-action counters as the sweep lanes.
    The swept-field encoding changes fingerprints, never counts: the
    per-config state graphs are isomorphic."""
    from jaxtlc.api import CheckRequest, run_check

    for v, (gen, dist, depth, renege) in _EXPECT.items():
        d = tmp_path / f"V{v}"
        d.mkdir()
        (d / "TwoPhaseB.tla").write_text(_TPB)
        (d / "TwoPhaseB.cfg").write_text(_cfg(v))
        out = io.StringIO()
        oc = run_check(CheckRequest(
            config=str(d / "TwoPhaseB.cfg"), workers="cpu",
            frontend="struct", chunk=64, qcap=1 << 10, fpcap=1 << 12,
            nodeadlock=True, obs=False, autogrow=False, noTool=True,
            out=out,
        ))
        assert oc.exit_code == 0 and oc.verdict == "ok"
        r = oc.result
        assert (r.generated, r.distinct, r.depth) == (gen, dist, depth)
        assert r.action_generated.get("Renege", 0) == renege
        sl = sweep_jobs[v]["result"]
        assert (sl["generated"], sl["distinct"], sl["depth"]) == \
            (r.generated, r.distinct, r.depth)
        assert sl["action_generated"] == {
            k: int(n) for k, n in r.action_generated.items()
        }
        # the library surface: transcript captured, not printed
        assert "TwoPhaseB" in out.getvalue()
        assert "states generated" in out.getvalue()


# ---------------------------------------------------------------------------
# constant overrides reach every route (supervised + sweep anchor)
# ---------------------------------------------------------------------------


def _write_model(tmp_path, maxr: int = 2) -> str:
    d = tmp_path / "model"
    d.mkdir()
    (d / "TwoPhaseB.tla").write_text(_TPB)
    (d / "TwoPhaseB.cfg").write_text(_cfg(maxr))
    return str(d / "TwoPhaseB.cfg")


def test_check_request_constants_reach_the_frontend(tmp_path):
    """CheckRequest.constants threads MC.cfg-style overrides through
    frontend.resolve into the loaded model - the supervised server
    path: a job's constants must shape the checked configuration, not
    be silently dropped in favor of the cfg's baked values."""
    from jaxtlc.frontend.model import resolve

    cfg = _write_model(tmp_path, maxr=2)
    spec = resolve(cfg, frontend="struct", const_overrides={"MAXR": 0})
    assert spec.structmodel.constants["MAXR"] == 0
    baked = resolve(cfg, frontend="struct")
    assert baked.structmodel.constants["MAXR"] == 2
    # overrides are digest material: a -recover / cache key can never
    # confuse the two configurations
    assert (spec.structmodel.source_digest
            != baked.structmodel.source_digest)


def test_sweep_anchor_honors_fixed_overrides(tmp_path):
    """load_anchored bakes a job's FIXED (non-swept) constants into the
    anchor model: config_inits' fallback values and the constants-CLASS
    pool key both reflect them, so two sweep batches differing only in
    a fixed override cannot share one warm engine."""
    from jaxtlc.serve import sweep as sw

    cfg = _write_model(tmp_path, maxr=2)
    params = {"MAXR": (0, 2)}
    base = sw.load_anchored(cfg, params)
    ov = sw.load_anchored(cfg, params,
                          const_overrides={"RM": frozenset({"r1"})})
    assert base.constants["RM"] == frozenset({"r1", "r2"})
    assert ov.constants["RM"] == frozenset({"r1"})
    # the anchor still pins swept constants at their domain max, even
    # when the job's dict carries a swept value too
    both = sw.load_anchored(cfg, params,
                            const_overrides={"MAXR": 0,
                                             "RM": frozenset({"r1"})})
    assert both.constants["MAXR"] == 2
    assert sw.class_key(ov, params) != sw.class_key(base, params)
    assert sw.class_key(both, params) == sw.class_key(ov, params)


def test_job_constants_json_sets_normalize():
    """JSON has no set type: a list value in a job's constants is the
    JSON spelling of an MC.cfg set literal and becomes the loaders'
    frozenset representation on every route."""
    from jaxtlc.serve.scheduler import _loader_constants

    assert _loader_constants({"RM": ["r1", "r2"], "MAXR": 1}) == \
        {"RM": frozenset({"r1", "r2"}), "MAXR": 1}


def test_failed_runner_finalizes_job_journals(tmp_path):
    """A runner that explodes after the per-job journals opened must
    not leak handles or hang SSE followers: every affected job's
    journal still ends with a final error event, and the job records
    the error.  Covers both scheduler-owned paths (sweep + pool)."""
    from types import SimpleNamespace

    from jaxtlc.obs import journal as jrn
    from jaxtlc.serve.scheduler import Scheduler

    def _boom(*_a, **_k):
        raise RuntimeError("boom")

    class _BoomPool:
        sweep_width = 4
        hits = 0

        def get_sweep(self, model, params, **geo):
            return SimpleNamespace(runner=SimpleNamespace(run=_boom))

        def get_single(self, model, **geo):
            return SimpleNamespace(runner=SimpleNamespace(run=_boom))

    sched = Scheduler(str(tmp_path), pool=_BoomPool())
    try:
        jobs = [
            sched.submit(_TPB, _cfg(2), name=f"boom-sweep{v}",
                         constants={"MAXR": v}, sweep=_SWEEP,
                         options=_OPTS)
            for v in (0, 1)
        ]
        jobs.append(sched.submit(_TPB, _cfg(2), name="boom-plain",
                                 options=_OPTS))
        assert sched.drain(timeout=60)
    finally:
        sched.shutdown()
    for job in jobs:
        assert job.state == "error" and "boom" in job.error
        events = jrn.read(
            os.path.join(str(tmp_path), f"{job.id}.journal.jsonl")
        )
        assert events[0]["event"] == "run_start"
        assert events[-1]["event"] == "final"
        assert events[-1]["verdict"] == "error"
        assert events[-1]["interrupted"] is True


# ---------------------------------------------------------------------------
# satellites: memo cap + stats, pool LRU, batched fsync
# ---------------------------------------------------------------------------


def test_struct_cache_lru_cap_and_stats():
    from jaxtlc.struct.cache import _LRUMemo, stats

    m = _LRUMemo(2)
    assert m.get("a") is None  # miss
    m.put("a", 1)
    m.put("b", 2)
    assert m.get("a") == 1  # hit; "a" becomes MRU
    m.put("c", 3)  # evicts "b" (LRU)
    assert m.get("b") is None
    assert m.get("a") == 1 and m.get("c") == 3
    s = m.stats()
    assert (s["hits"], s["misses"], s["size"], s["evictions"]) == \
        (3, 2, 2, 1)
    top = stats()
    for memo in ("backend", "engine"):
        for k in ("hits", "misses", "size", "cap", "evictions"):
            assert k in top[memo]
        assert top[memo]["cap"] >= 1


def test_engine_pool_lru_eviction_and_stats():
    from jaxtlc.serve.pool import EnginePool

    pool = EnginePool(capacity=2)
    built = []

    def make(tag):
        def build():
            built.append(tag)
            return tag
        return build

    for tag in ("a", "b"):
        pool._get_or_build((tag,), make(tag), "single", {})
    assert pool._get_or_build(("a",), make("a2"), "single", {}).runner \
        == "a"  # hit, no rebuild
    pool._get_or_build(("c",), make("c"), "single", {})  # evicts "b"
    assert built == ["a", "b", "c"]
    pool._get_or_build(("b",), make("b2"), "single", {})  # miss again
    s = pool.stats()
    assert (s["hits"], s["misses"], s["evictions"], s["size"]) == \
        (1, 4, 2, 2)
    assert s["compiles"] == 4
    assert "xla_compiles" in s and "memo" in s
    # the zero-compile contract has its ground truth: listener
    # registration raises rather than leave a meter that counts nothing
    assert s["xla_meter"] == "ok"


def test_journal_batched_fsync(tmp_path, monkeypatch):
    """fsync_every=N: every event still lands as a complete flushed
    line (the reader sees it immediately); the fsync barrier fires once
    per N events and on close/sync."""
    from jaxtlc.obs import journal as jr

    syncs = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (syncs.append(fd), real_fsync(fd)))
    path = str(tmp_path / "batched.journal.jsonl")
    j = jr.RunJournal(path, fsync_every=3)
    for d in (1, 2):
        j.event("progress", depth=d, generated=d, distinct=d, queue=0)
    assert syncs == []  # below the batch threshold: no barrier yet
    assert len(jr.read(path, validate=False)) == 2  # but lines landed
    j.event("progress", depth=3, generated=3, distinct=3, queue=0)
    assert len(syncs) == 1  # third event hit the threshold
    j.event("progress", depth=4, generated=4, distinct=4, queue=0)
    j.sync()
    assert len(syncs) == 2  # explicit barrier flushes the remainder
    j.sync()
    assert len(syncs) == 2  # idempotent when nothing is pending
    j.event("progress", depth=5, generated=5, distinct=5, queue=0)
    j.close()
    assert len(syncs) == 3  # close never leaves unsynced lines
    events = jr.read(path)
    assert [e["depth"] for e in events] == [1, 2, 3, 4, 5]

    # default remains per-event fsync (checkpointed-run durability)
    syncs.clear()
    with jr.RunJournal(str(tmp_path / "d.journal.jsonl")) as j2:
        j2.event("progress", depth=1, generated=1, distinct=1, queue=0)
        j2.event("progress", depth=2, generated=2, distinct=2, queue=0)
    assert len(syncs) == 2


# ---------------------------------------------------------------------------
# overload control plane on the REAL supervised path (ISSUE 17): the
# policy-speed scheduler tests live in tests/test_overload.py against a
# stub pool; these three pin the parts only a real engine can prove -
# drain-at-segment-fence preemption with bit-for-bit resume parity,
# running-deadline expiry, and running cancel.  The LoadChain spec and
# heavy geometry are byte-identical to tools/loadgen.py so struct.cache
# memoizes ONE supervised compile across the whole pytest process.
# ---------------------------------------------------------------------------

_CHAIN_SPEC = """---- MODULE LoadChain ----
EXTENDS Naturals
CONSTANTS MAX
VARIABLES x

Init == x = 0

Up == /\\ x < MAX
      /\\ x' = x + 1

Next == Up

Spec == Init /\\ [][Next]_x

InRange == x <= MAX
====
"""

_CHAIN_CFG = """CONSTANT MAX = 600
SPECIFICATION
Spec
INVARIANT
InRange
"""

# the `checkpoint` option alone routes the job supervised (it is a
# _HEAVY_OPTIONS member) while the tiny fpcap keeps checkpoints ~KB;
# checkpointevery=8 puts a drain fence every 8 of the 600 levels
_HEAVY = dict(chunk=16, qcap=256, fpcap=1024, nodeadlock=True,
              checkpointevery=8, noartifactcache=True)


def _wait_running(url, jid, timeout=30.0):
    deadline = time.time() + timeout
    while True:
        st = client.status(url, jid)
        if st["state"] == "running":
            return st
        assert st["state"] == "queued", st
        assert time.time() < deadline, f"{jid} never started running"
        time.sleep(0.005)


def test_priority_preemption_resume_bit_for_bit(server, tmp_path):
    """A high-priority arrival drains the running checkpointed job at
    the next segment fence (checkpoint + exit 75); the preempted job
    requeues as a -recover resume and its final counters match an
    uninterrupted run of the same spec EXACTLY (the PR 2/7 resume
    contract, now exercised by the scheduler itself)."""
    url = server.url
    ref = client.check(
        url, _CHAIN_SPEC, _CHAIN_CFG, name="preempt-ref",
        options=dict(_HEAVY, checkpoint=str(tmp_path / "ref.npz")),
        timeout=600,
    )
    assert ref["state"] == "done", ref
    assert ref["result"]["verdict"] == "ok"

    low = {}
    for attempt in range(3):  # preemption needs the low job mid-run
        lo_id = client.submit(
            url, _CHAIN_SPEC, _CHAIN_CFG, name=f"preempt-lo{attempt}",
            options=dict(_HEAVY, priority=0,
                         checkpoint=str(tmp_path / f"lo{attempt}.npz")),
        )
        _wait_running(url, lo_id)
        hi_id = client.submit(
            url, _CHAIN_SPEC, _CHAIN_CFG, name=f"preempt-hi{attempt}",
            options=dict(_HEAVY, priority=10,
                         checkpoint=str(tmp_path / f"hi{attempt}.npz")),
        )
        low = client.wait(url, lo_id, timeout=600)
        hi = client.wait(url, hi_id, timeout=600)
        assert hi["state"] == "done", hi
        if low.get("requeues", 0) >= 1:
            break
    assert low["state"] == "done", low
    assert low["requeues"] >= 1, "high-priority arrival never preempted"
    assert low["options"]["recover"] is True  # resumed as -recover
    for k in ("generated", "distinct", "depth", "violation",
              "action_generated"):
        assert low["result"][k] == ref["result"][k], (
            k, low["result"], ref["result"])
    # the scheduler journaled the preempt -> requeue pair
    from jaxtlc.obs import journal as obs_journal
    sched = [e for e in obs_journal.read(
        os.path.join(server.root, "sched.journal.jsonl"))
        if e["event"] == "sched" and e.get("job") == low["id"]]
    assert any(e["action"] == "preempt" and e["reason"] == "priority"
               for e in sched)
    assert any(e["action"] == "requeue" and e["requeues"] == 1
               for e in sched)


def test_running_deadline_drains_to_expired(server, tmp_path):
    """Deadline hits while the job is RUNNING: the reaper sets its
    drain Event, the supervisor checkpoints at the next fence and
    exits 75, and the job lands `expired` with its partial progress
    attached - not killed mid-step, not left running past its
    deadline."""
    st = client.check(
        server.url, _CHAIN_SPEC, _CHAIN_CFG, name="deadline-run",
        options=dict(_HEAVY, deadline_s=0.3,
                     checkpoint=str(tmp_path / "dl.npz")),
        timeout=600,
    )
    assert st["state"] == "expired", st
    assert "deadline expired while running" in st["error"]
    assert st["result"]["exit_code"] == 75
    assert 0 < st["result"]["depth"] < 600  # partial progress attached


def test_cancel_running_job_drains_to_canceled(server, tmp_path):
    """DELETE /jobs/<id> on a RUNNING checkpointed job rides the same
    drain path: checkpoint at the next fence, exit 75, terminal
    `canceled`."""
    jid = client.submit(
        server.url, _CHAIN_SPEC, _CHAIN_CFG, name="cancel-run",
        options=dict(_HEAVY, checkpoint=str(tmp_path / "cx.npz")),
    )
    _wait_running(server.url, jid)
    client.cancel(server.url, jid)
    st = client.wait(server.url, jid, timeout=600)
    assert st["state"] == "canceled", st
    assert "canceled by client" in st["error"]
    assert st["result"]["exit_code"] == 75
