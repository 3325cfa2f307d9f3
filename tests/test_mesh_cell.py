"""The four-chip cell's program side (ISSUE 27), on the virtual CPU mesh:
the hand frontend's process set as two integer constants, api.run_check
-sharded 4 on a scaled rung against the benchmark's plain reference, the
shards of the fingerprint space, and the owner-routing counters."""

import hashlib
import io
import json
import os
import sys

import jax
import numpy as np
import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.config import ModelConfig, make_scaled
from jaxtlc.engine.sharded import (
    make_sharded_engine,
    result_from_shard_carry,
    route_bucket_width,
    route_geometry,
)
from jaxtlc.frontend.model import resolve
from jaxtlc.runtime import fp_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MC_CFG = os.path.join(REPO, "specs", "KubeAPI.toolbox", "Model_1", "MC.cfg")
FF = ModelConfig(False, False)
# one tiny geometry for every engine of this file (per device)
GEOM = dict(chunk=128, queue_capacity=1 << 11, fp_capacity=1 << 13)


# -- (a) the process set as constants ------------------------------------


def test_no_constants_is_model_1():
    assert resolve(MC_CFG, frontend="hand").model == ModelConfig(True, True)


@pytest.mark.parametrize("n,m", [(2, 1), (1, 2), (1, 1)])
def test_request_constants_resolve_to_make_scaled(n, m):
    spec = resolve(MC_CFG, frontend="hand", const_overrides={
        "N_RECONCILERS": n, "N_BINDERS": m,
        "REQUESTS_CAN_FAIL": False, "REQUESTS_CAN_TIMEOUT": True})
    assert spec.model == make_scaled(n, m, False, True)


def test_cfg_constant_line_resolves_to_make_scaled(tmp_path):
    with open(MC_CFG) as f:
        text = f.read()
    (tmp_path / "MC.cfg").write_text(
        text + "CONSTANT N_RECONCILERS = 2\nCONSTANT\nN_BINDERS = 1\n")
    with open(os.path.join(os.path.dirname(MC_CFG), "MC.tla")) as f:
        (tmp_path / "MC.tla").write_text(f.read())
    spec = resolve(str(tmp_path / "MC.cfg"), frontend="hand")
    # the fault constants still come from MC.tla (both TRUE)
    assert spec.model == make_scaled(2, 1, True, True)
    # a count left out is 1
    one = resolve(MC_CFG, frontend="hand",
                  const_overrides={"N_BINDERS": 2})
    assert one.model == make_scaled(1, 2, True, True)


@pytest.mark.parametrize("bad", [0, -1, 1.5, True, "two", '"2"'])
def test_bad_process_count_is_an_error(bad):
    with pytest.raises(ValueError, match="integer >= 1"):
        resolve(MC_CFG, frontend="hand",
                const_overrides={"N_RECONCILERS": bad})
    out = io.StringIO()
    got = run_check(CheckRequest(
        config=MC_CFG, frontend="hand", workers="cpu", out=out, err=out,
        constants={"N_BINDERS": bad}))
    assert got.exit_code == 1 and got.result is None
    assert "N_BINDERS must be an integer >= 1" in out.getvalue()


# -- (b) run_check -sharded 4 against the plain reference -----------------


@pytest.fixture(scope="module")
def reference_1x1():
    sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))
    try:
        import kubeapi
    finally:
        sys.path.pop(0)
    return kubeapi.bfs(kubeapi.scaled(1, 1, False, False))


@pytest.fixture(scope="module")
def checked_1x1(tmp_path_factory):
    journal = str(tmp_path_factory.mktemp("mesh") / "check.jsonl")
    out = io.StringIO()
    got = run_check(CheckRequest(
        config=MC_CFG, frontend="hand", workers="cpu", noTool=True,
        sharded=4, chunk=GEOM["chunk"], qcap=GEOM["queue_capacity"],
        fpcap=GEOM["fp_capacity"], journal=journal, out=out, err=out,
        constants={"N_RECONCILERS": 1, "N_BINDERS": 1,
                   "REQUESTS_CAN_FAIL": False,
                   "REQUESTS_CAN_TIMEOUT": False}))
    with open(journal) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return got, events, out.getvalue()


def test_run_check_sharded_equals_the_reference(checked_1x1, reference_1x1):
    got, events, text = checked_1x1
    ref = reference_1x1
    assert got.exit_code == 0 and got.verdict == "ok", text[-400:]
    r = got.result
    assert (r.generated, r.distinct, r.depth) == (
        ref.generated, ref.distinct, ref.depth) == (17020, 8203, 109)
    assert r.queue_left == 0
    assert {k: v for k, v in r.action_generated.items() if v} == dict(
        ref.action_generated)


def test_journal_records_the_process_set_and_the_counts(
        checked_1x1, reference_1x1):
    got, events, _ = checked_1x1
    start = next(e for e in events if e["event"] == "run_start")
    assert start["engine"] == "sharded"
    assert start["params"]["sharded"] == 4
    assert start["params"]["model"] == dict(
        n_reconcilers=1, n_binders=1, requests_can_fail=False,
        requests_can_timeout=False, clients=["Client0", "PVCCtl0"])
    final = next(e for e in events if e["event"] == "final")
    ref = reference_1x1
    assert (final["verdict"], final["generated"], final["distinct"],
            final["depth"], final["queue"]) == (
        "ok", ref.generated, ref.distinct, ref.depth, 0)
    assert not [e for e in events
                if e["event"] in ("regrow", "retry", "degrade", "spill")]
    # the mesh counters ride the final event, and agree with the result
    r = got.result
    assert final["shard_distinct"] == list(r.shard_distinct)
    assert final["shard_generated"] == list(r.shard_generated)
    assert sum(final["shard_generated"]) == ref.generated
    assert (final["route_max_fill"], final["route_bucket"],
            final["route_bytes"]) == (
        r.route_max_fill, r.route_bucket, r.route_bytes)


# -- (c) the shares add up ------------------------------------------------


def _raw_fps(table) -> set:
    """The fingerprints a table (or shard) holds, unmixed."""
    from jaxtlc.engine.fpset import unmix_host

    t = np.asarray(table).reshape(-1, 16)
    lo, hi = t[:, 0::2].reshape(-1), t[:, 1::2].reshape(-1)
    occ = (lo != 0) | (hi != 0)
    raw_lo, raw_hi = unmix_host(lo[occ], hi[occ])
    return set(zip(raw_lo.tolist(), raw_hi.tolist()))


@pytest.fixture(scope="module")
def mesh_run():
    """The FF corner through the mesh engine's segment program on four
    devices, to the end: (final carry, bodies a segment could hold)."""
    init_fn, seg_fn = make_sharded_engine(FF, fp_mesh(4), segment=16,
                                          **GEOM)
    carry, segments = init_fn(), 0
    while bool(np.asarray(carry.cont).any()):
        carry = jax.block_until_ready(seg_fn(carry))
        segments += 1
    return carry, segments


def test_shards_partition_the_one_chip_table(mesh_run):
    from jaxtlc.engine.bfs import make_engine

    carry, _ = mesh_run
    shards = [_raw_fps(carry.table[d]) for d in range(4)]
    assert [len(s) for s in shards] == [
        int(v) for v in np.asarray(carry.distinct)]
    assert sum(len(s) for s in shards) == 8203
    union = set().union(*shards)
    assert len(union) == 8203  # disjoint
    for d, s in enumerate(shards):  # each fingerprint at its owner
        assert all(hi & 3 == d for _, hi in s)
    init_fn, run_fn, _ = make_engine(FF, chunk=128,
                                     queue_capacity=1 << 13,
                                     fp_capacity=1 << 15)
    single = jax.block_until_ready(run_fn(init_fn()))
    assert union == _raw_fps(single.fps.table)


# -- (d) the routing counters ---------------------------------------------


def carry_digest(c, qcap: int) -> str:
    """What a check leaves behind, less the dump rows and bins that a
    body writes whether or not it pops (tests/test_mesh_cell.py pins the
    parent engine's digest with it)."""
    h = hashlib.sha256()
    for name in ("table", "generated", "distinct", "depth", "level",
                 "qhead", "qtail", "viol"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(c, name))).tobytes())
    for name in ("act_gen", "act_dist", "outdeg_hist"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(c, name))[:, :-1]).tobytes())
    h.update(np.ascontiguousarray(np.asarray(c.queue)[:, :qcap]).tobytes())
    return h.hexdigest()


# sha256 of the same run's final carry on the parent commit (258ba50,
# before the counters, the scopes and the `while` segment), by
# carry_digest above: fused loop and 16-step segments alike
PARENT_DIGEST = (
    "4142a18eacd18e5acd01b68649707a98b517141b0dbb981f5ce3758e37573e7a")


def test_results_with_counters_are_the_parents_bit_for_bit(mesh_run):
    carry, segments = mesh_run
    assert carry_digest(carry, GEOM["queue_capacity"]) == PARENT_DIGEST
    # a finished check leaves its last segment: 109 levels of at most
    # one body per level here, so fewer than 16 x segments bodies
    bodies = int(np.asarray(carry.route_stat)[:, 1].max())
    assert 16 * (segments - 1) < bodies < 16 * segments


def test_wide_chunk_paths_are_the_parents_bit_for_bit():
    """The paths a 16384-wide chunk takes on the chip (sort-free slab,
    owner-side deferred invariants, the enqueue of the compacted
    claimants alone), forced here at chunk 128."""
    init_fn, run_fn = make_sharded_engine(
        FF, fp_mesh(4), sort_free=True, deferred=True, **GEOM)
    carry = jax.block_until_ready(run_fn(init_fn()))
    assert carry_digest(carry, GEOM["queue_capacity"]) == PARENT_DIGEST


def test_route_counters_against_the_hand_count(mesh_run):
    carry, _ = mesh_run
    from jaxtlc.engine.backend import kubeapi_backend

    be = kubeapi_backend(FF)
    L, F = be.n_lanes, be.cdc.n_fields
    # by hand: 128 x L candidates a body over 4 owners at factor 2.0
    B = int(2.0 * 128 * L / 4) + 8
    assert route_bucket_width(128, L, 4, 2.0) == B
    step_bytes = 4 * B * (F + 3) * 4 + 4 * B
    geo = route_geometry(be, 128, 4, 2.0)
    assert geo == dict(bucket=B, step_bytes=step_bytes)
    r = result_from_shard_carry(carry, 1.0, route=geo)
    stat = np.asarray(carry.route_stat)
    assert (stat[:, 1] == stat[0, 1]).all()  # every device, every body
    assert r.route_bytes == int(stat[0, 1]) * step_bytes
    assert 0 < r.route_max_fill <= r.route_bucket == B
    assert sum(r.shard_generated) == r.generated == 17020
    assert sum(r.shard_distinct) == r.distinct == 8203
    # without the geometry the result carries no routing counters
    bare = result_from_shard_carry(carry, 1.0)
    assert bare.route_bytes is None and bare.route_max_fill is None
