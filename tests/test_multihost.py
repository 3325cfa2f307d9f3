"""Multi-host pod driver (jaxtlc.dist, ISSUE 19): elastic membership
(SIGTERM -> per-host snapshot -> resume parity; wrong-width resume
failing loudly; reshard-on-recover), and the over-capacity space that
completes ONLY through the spill lifeboat.

Everything below the slow marker runs IN PROCESS on the conftest 8-way
virtual-device mesh via run_pod's `devices=` truncation knob - the pod
driver's whole control surface (segment loop, consensus vote, per-host
checkpoint format, reshard migration) is exercised without forking a
real jax.distributed pod.  Every run_pod call AOT-compiles a sharded
engine, so the tests are folded to the minimum compile count (three
tests, six engine builds); width parity itself rides along as the
resume-completion assertions.  The real 2-process gloo pod
(subprocess, ~30s) is slow-marked."""

import os
import signal

import numpy as np
import pytest

from jaxtlc.dist import run_pod
from jaxtlc.engine.bfs import VIOL_FPSET_FULL

TINY = (31, 31, 4)  # generated, distinct, depth of the 3-lane counter
# fp_capacity must clear the engine's in-flight insert margin D*B
# (route buckets, ~64 at these widths) or the highwater fence trips
GEO = dict(chunk=8, queue_capacity=64, fp_capacity=256, ckpt_every=1)


class _TinyCdc:
    """One int16 field: pack/unpack are casts (W = 1)."""

    n_fields = 1
    nbits = 16

    def pack(self, flat):
        import jax.numpy as jnp

        return flat.astype(jnp.uint32)

    def unpack(self, block):
        import jax.numpy as jnp

        return block.astype(jnp.int32)


def _tiny_plane():
    """4-site coverage plane for the 3-lane counter: the per-action
    prefix (whose counts must equal the engine's own generated
    counters) plus one guard site shadowing lane a - the same
    prefix-view contract as the KubeAPI device table (ISSUE 11)."""
    import jax.numpy as jnp

    from jaxtlc.obs.coverage import (
        CoveragePlane, Site, action_site_table,
    )

    sites = tuple(action_site_table("Tiny", ("a", "b", "c"))
                  + [Site(key="a.g0", kind="guard", action="a")])

    def count(batch, mask, valid):
        v = valid & mask[:, None]
        per_lane = v.sum(0).astype(jnp.uint32)
        return jnp.concatenate([per_lane, per_lane[:1]])

    return CoveragePlane(sites=sites, count=count, module="Tiny")


def _tiny_backend(viol_at: int = 1 << 20, coverage: bool = False):
    """3-lane counter spec: x -> {3x+1, 3x+2, 3x+3} while 3x+3 <= 30
    (31 states, depth 4); invariant bit 0 = (x < viol_at), so the
    default never violates.  Same fixture family as test_deferred."""
    import jax.numpy as jnp

    from jaxtlc.engine.backend import SpecBackend
    from jaxtlc.engine.bfs import VIOL_TYPEOK

    def step(vec):
        x = vec[0]
        succs = (3 * x + jnp.arange(1, 4, dtype=jnp.int32))[:, None]
        valid = succs[:, 0] <= 30
        action = jnp.arange(3, dtype=jnp.int32)
        afail = jnp.zeros(3, bool)
        ovf = jnp.zeros(3, bool)
        return succs, valid, action, afail, ovf

    def inv_check(vec):
        return (vec[0] < viol_at).astype(jnp.int32)

    return SpecBackend(
        cdc=_TinyCdc(),
        step=step,
        n_lanes=3,
        inv_check=inv_check,
        inv_codes=(VIOL_TYPEOK,),
        initial_vectors=lambda: np.zeros((1, 1), np.int32),
        labels=("a", "b", "c"),
        viol_names={},
        check_deadlock=False,
        coverage=_tiny_plane() if coverage else None,
    )


def _counts(pr):
    r = pr.result
    return (r.generated, r.distinct, r.depth)


def test_pod_sigterm_checkpoints_and_resumes(tmp_path):
    """Elastic membership: SIGTERM mid-run flips the cooperative flag,
    the next segment fence votes, EVERY shard checkpoints, and the
    driver returns the preemption exit code (75).  Plain resume at the
    same width completes to the exact counts - no state generated
    before the signal is lost - and the per-host journal is one
    schema-valid continuous stream ending in the ok verdict."""
    from jaxtlc.obs import journal as jr

    base = str(tmp_path / "pod.ckpt")
    fired = []

    def kill_once(kind, info):
        if kind == "progress" and not fired:
            fired.append(1)
            os.kill(os.getpid(), signal.SIGTERM)

    pr = run_pod(backend=_tiny_backend(), devices=2, ckpt_path=base,
                 on_event=kill_once, **GEO)
    assert pr.exit_code == 75 and fired
    assert os.path.exists(base + ".h0")
    assert _counts(pr) != TINY  # it really stopped early
    pr2 = run_pod(backend=_tiny_backend(), devices=2, ckpt_path=base,
                  resume=True, **GEO)
    assert _counts(pr2) == TINY and pr2.exit_code == 0
    assert pr2.resumed and not pr2.resharded
    events = jr.read(base + ".h0.journal.jsonl")  # validate=True
    kinds = [e["event"] for e in events]
    assert kinds.count("run_start") == 1 and kinds.count("run_resume") == 1
    assert "pod" in kinds and "interrupted" in kinds
    assert kinds[-1] == "final" and events[-1]["verdict"] == "ok"


def test_pod_wrong_width_refused_then_reshard_resumes(tmp_path):
    """A pod snapshot resumes only at the width that cut it: a plain
    resume at another width must refuse with the reshard hint (not
    silently mis-shard the fingerprint space), and `reshard=True` at
    the surviving width re-partitions the saved tables and frontier to
    the exact counts (a lost host's capacity re-owned exactly)."""
    base = str(tmp_path / "pod.ckpt")
    pr = run_pod(backend=_tiny_backend(), devices=4, ckpt_path=base,
                 max_segments=2, **GEO)
    assert pr.exit_code == 0 and _counts(pr) != TINY
    with pytest.raises(ValueError, match="--reshard"):
        run_pod(backend=_tiny_backend(), devices=2, ckpt_path=base,
                resume=True, **GEO)
    pr2 = run_pod(backend=_tiny_backend(), devices=2, ckpt_path=base,
                  resume=True, reshard=True, **GEO)
    assert _counts(pr2) == TINY and pr2.exit_code == 0
    assert pr2.resumed and pr2.resharded


def test_pod_over_capacity_needs_spill():
    """A space the per-device tables cannot hold (31 distinct vs a
    64-slot table whose highwater fence reserves the D*B in-flight
    margin) halts loudly with VIOL_FPSET_FULL without the lifeboat,
    and completes exactly with spill='on' - capacity beyond device
    memory is the pod+spill claim, demonstrated at tiny scale."""
    geo = dict(GEO, fp_capacity=64)
    pr = run_pod(backend=_tiny_backend(), devices=2, **geo)
    assert pr.exit_code == 12
    assert pr.result.violation == VIOL_FPSET_FULL
    pr2 = run_pod(backend=_tiny_backend(), devices=2, spill="on",
                  spill_capacity=1 << 10, **geo)
    assert _counts(pr2) == TINY and pr2.exit_code == 0
    assert pr2.spilled > 0 and pr2.spill_flushes > 0


@pytest.fixture(scope="module")
def pod_obs_run(tmp_path_factory):
    """ONE interrupt+resume pod run with the obs ring + coverage plane
    on, shared by the parity and SSE-merge tests below (engine builds
    are the tier-1 budget: two run_pod compiles here serve both)."""
    tmp = tmp_path_factory.mktemp("podobs")
    base = str(tmp / "pod.ckpt")
    fired = []

    def kill_once(kind, info):
        if kind == "progress" and not fired:
            fired.append(1)
            os.kill(os.getpid(), signal.SIGTERM)

    pr = run_pod(backend=_tiny_backend(coverage=True), devices=2,
                 obs_slots=16, ckpt_path=base, on_event=kill_once,
                 **GEO)
    pr2 = run_pod(backend=_tiny_backend(coverage=True), devices=2,
                  obs_slots=16, ckpt_path=base, resume=True, **GEO)
    return dict(base=base, pr=pr, pr2=pr2)


def test_pod_obs_coverage_parity(pod_obs_run):
    """Pod obs parity (ISSUE 20): the per-fence ring decode + coverage
    deltas a pod host journals, folded back through the merge tier,
    reproduce the engine's own counters EXACTLY across a SIGTERM +
    resume - level rows are exactly-once (the resume cursors seed from
    the restored carry), the folded final row carries the oracle
    totals, and the summed site table equals the run's own
    site_coverage with the action-prefix sites matching the per-action
    generated counters (the PR 11 one-accounting contract)."""
    from jaxtlc.obs import journal as jr
    from jaxtlc.obs.coverage import coverage_from_events
    from jaxtlc.obs.views import fold_pod_levels

    pr, pr2 = pod_obs_run["pr"], pod_obs_run["pr2"]
    assert pr.exit_code == 75 and _counts(pr) != TINY
    assert _counts(pr2) == TINY and pr2.exit_code == 0
    events = jr.read(pod_obs_run["base"] + ".h0.journal.jsonl")
    raw = [e for e in events if e["event"] == "level"]
    assert [e["level"] for e in raw] == [1, 2, 3, 4]  # exactly-once
    assert all(e["host"] == 0 for e in raw)
    levels = [e for e in fold_pod_levels(events)
              if e.get("event") == "level"]
    assert levels[-1]["generated"] == TINY[0]
    assert levels[-1]["distinct"] == TINY[1]
    assert levels[-1]["queue"] == 0
    cov = coverage_from_events(events)
    assert cov["sites"] == pr2.result.site_coverage
    for name, g in pr2.result.action_generated.items():
        assert cov["sites"][name] == g
    assert cov["sites"]["a.g0"] == cov["sites"]["a"]


def test_pod_sse_merged_tail(pod_obs_run):
    """The serving merge tier: the interrupted+resumed pod run streams
    over /events as ONE time-ordered sequence (resume APPENDS to the
    same per-host journal), k-way merged with a second host's journal;
    no level row is duplicated or dropped, the pod /runs row groups
    the hosts (with the coverage fields), and /coverage answers the
    merged summed site table."""
    import json as _json

    from jaxtlc.obs import journal as jr
    from jaxtlc.obs.serve import _http_get, start_server

    base = pod_obs_run["base"]
    h0 = jr.read(base + ".h0.journal.jsonl")
    # synthesize host 1's journal: zero-count partial level rows
    # interleaved just after host 0's (a 2-host loopback pod's other
    # member, without paying a second jax.distributed process)
    h0_levels = [e for e in h0 if e["event"] == "level"]
    with open(base + ".h1.journal.jsonl", "w") as f:
        for lv in h0_levels:
            f.write(_json.dumps({
                "event": "level", "t": lv["t"] + 1e-4, "host": 1,
                "level": lv["level"], "generated": 0, "distinct": 0,
                "queue": 0, "bodies": 0, "expanded": 0,
            }) + "\n")
        f.write(_json.dumps({
            "event": "final", "t": h0[-1]["t"] + 1e-4,
            "verdict": "ok", "generated": 0, "distinct": 0,
            "depth": 4, "queue": 0, "wall_s": 0.0,
        }) + "\n")
    srv = start_server(os.path.dirname(base))
    try:
        runs = _json.loads(_http_get(srv.url + "/runs"))["runs"]
        pod = next(r for r in runs if r["run"] == "pod.ckpt")
        assert pod["pod_hosts"] == 2 and pod["resumes"] == 1
        assert pod["verdict"] == "ok"
        assert pod["coverage"] and not pod["coverage_saturated"]
        sse = _http_get(srv.url + "/events?once=1&run=pod.ckpt")
        evs = [_json.loads(ln[len("data: "):])
               for ln in sse.splitlines() if ln.startswith("data: ")]
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts)  # ONE time-ordered stream
        kinds = [e["event"] for e in evs]
        assert "interrupted" in kinds and "run_resume" in kinds
        for host, want in ((0, [1, 2, 3, 4]), (1, [1, 2, 3, 4])):
            got = [e["level"] for e in evs
                   if e["event"] == "level" and e.get("host") == host]
            assert got == want, (host, got)
        cov = _json.loads(_http_get(srv.url + "/coverage?run=pod.ckpt"))
        assert cov["sites"] == pod_obs_run["pr2"].result.site_coverage
        metrics = _http_get(srv.url + "/metrics?run=pod.ckpt")
        assert "jaxtlc_coverage_site_total{site=" in metrics
        assert 'jaxtlc_host_states_per_second{host="0"}' in metrics
    finally:
        srv.shutdown()


@pytest.mark.slow
def test_pod_two_process_gloo_exact(tmp_path):
    """The real thing: a 2-process localhost jax.distributed pod (gloo
    collectives) over KubeAPI FF, with the counter ring + coverage
    plane ON, reproduces the oracle counts through python -m
    jaxtlc.dist --spawn - and the two hosts' journals fold back to the
    exact global per-level counters and per-action site table."""
    import json
    import subprocess
    import sys

    base = str(tmp_path / "gloo.ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "jaxtlc.dist", "--spawn", "2",
         "--devices-per-host", "2", "--ff", "--chunk", "128",
         "--queue-capacity", "4096", "--fp-capacity", "16384",
         "--obs-slots", "128", "--coverage", "--ckpt", base],
        env=env, timeout=560, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("POD_RESULT "))
    out = json.loads(line[len("POD_RESULT "):])
    assert (out["generated"], out["distinct"], out["depth"]) == \
        (17020, 8203, 109)
    assert out["hosts"] == 2 and out["rc"] == 0
    from jaxtlc.obs import journal as jr
    from jaxtlc.obs.coverage import coverage_from_events
    from jaxtlc.obs.views import fold_pod_levels, merge_journals

    events = merge_journals(*(
        jr.read(f"{base}.h{h}.journal.jsonl", validate=False)
        for h in range(2)))
    levels = [e for e in fold_pod_levels(events)
              if e.get("event") == "level"]
    assert len(levels) == 109
    assert (levels[-1]["generated"], levels[-1]["distinct"]) == \
        (17020, 8203)
    cov = coverage_from_events(events)
    for name, g in out["action_generated"].items():
        assert cov["sites"].get(name, 0) == g
