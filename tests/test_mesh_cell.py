"""The four-chip cell's program side (ISSUE 27), on the virtual CPU mesh:
the hand frontend's process set as two integer constants, api.run_check
-sharded 4 on a scaled rung against the benchmark's plain reference, the
shards of the fingerprint space, and the owner-routing counters."""

import contextlib
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
from jaxtlc.engine.backend import kubeapi_backend
from jaxtlc.engine import fpset, sharded
from jaxtlc.engine.sharded import (
    commit_width,
    compact_lanes,
    compact_rows,
    enqueue_new_rows,
    make_sharded_engine,
    masked_hist,
    owner_counts,
    result_from_shard_carry,
    route_bucket_width,
    route_geometry,
    sorted_route,
    sorted_verdicts,
)
from jaxtlc.frontend.model import resolve
from jaxtlc.runtime import fp_mesh
from jaxpr_walk import scoped_eqns

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
    # the owner-side insert's segments: a chunk of rows each, as many a
    # body as what a device received needs (none, one, at most two at
    # this rung's ~2 candidates a popped state)
    assert final["commit_rows"] == r.commit_rows == GEOM["chunk"]
    assert final["commit_segments"] == list(r.commit_segments)
    assert final["enqueue_segments"] == list(r.enqueue_segments)
    bodies = r.route_bytes // route_geometry(
        kubeapi_backend(FF), GEOM["chunk"], 4, 2.0)["step_bytes"]
    assert all(0 < s <= 2 * bodies for s in r.commit_segments)
    # the enqueue's blocks follow what is NEW: under 128 rows a body
    # here, so one block in every body that found a new state
    assert all(0 < s <= bodies for s in r.enqueue_segments)


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
    # 96 rows an insert segment, not the geometry's 128: under the
    # fingerprints since PR 45 no owner of this rung receives more than
    # 128 rows a body (one did before), and some bodies are to take two
    with segments_of(96):
        init_fn, seg_fn = make_sharded_engine(FF, fp_mesh(4), segment=16,
                                              **GEOM)
    carry, segments = init_fn(), 0
    while bool(np.asarray(carry.cont).any()):
        carry = jax.block_until_ready(seg_fn(carry))
        segments += 1
    return carry, segments


@pytest.fixture(scope="module")
def one_chip_fps():
    """The fingerprints the one-chip engine's table holds after the
    same check."""
    from jaxtlc.engine.bfs import make_engine

    init_fn, run_fn, _ = make_engine(FF, chunk=128,
                                     queue_capacity=1 << 13,
                                     fp_capacity=1 << 15)
    single = jax.block_until_ready(run_fn(init_fn()))
    return _raw_fps(single.fps.table)


def test_shards_partition_the_one_chip_table(mesh_run, one_chip_fps):
    carry, _ = mesh_run
    shards = [_raw_fps(carry.table[d]) for d in range(4)]
    assert [len(s) for s in shards] == [
        int(v) for v in np.asarray(carry.distinct)]
    assert sum(len(s) for s in shards) == 8203
    union = set().union(*shards)
    assert len(union) == 8203  # disjoint
    for d, s in enumerate(shards):  # each fingerprint at its owner
        assert all(hi & 3 == d for _, hi in s)
    assert union == one_chip_fps


# -- (d) the routing counters ---------------------------------------------


def segments(carry):
    """[D, 2]: the segments a device's owner-side insert has run and the
    blocks its enqueue has written, off the carry's `commit_stat`."""
    stat = np.asarray(carry.commit_stat)
    return stat[:, [fpset.COMMIT_COUNTS.index("probe_segments"), -1]]


def carry_digest(c, qcap: int, table: bool = True) -> str:
    """What a check leaves behind, less the dump rows and bins that a
    body writes whether or not it pops (tests/test_mesh_cell.py pins the
    parent engine's digest with it); `table=False` leaves the table's
    words out."""
    h = hashlib.sha256()
    for name in (("table",) if table else ()) + (
            "generated", "distinct", "depth", "level", "qhead", "qtail",
            "viol"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(c, name))).tobytes())
    for name in ("act_gen", "act_dist", "outdeg_hist"):
        h.update(np.ascontiguousarray(
            np.asarray(getattr(c, name))[:, :-1]).tobytes())
    h.update(np.ascontiguousarray(np.asarray(c.queue)[:, :qcap]).tobytes())
    return h.hexdigest()


# sha256 of the same run's final carry on the parent commit (258ba50,
# before the counters, the scopes and the `while` segment), by
# carry_digest above: fused loop and 16-step segments alike.  Pinned
# again in PR 45, whose dense polynomial table changes every fingerprint
# (the table's words, the owner of a state, the queues' order): the
# values are PR 44's engine with the new table alone
PARENT_DIGEST = (
    "835822bce992e15ac00906e3d1a9232c353e0a31ae473bc55bf42afd0b51d424")
# the same less the table's words (9bca0db, PR 27's engine: one insert
# over all D x B received lanes); sorted and wide paths alike
PARENT_DIGEST_LESS_TABLE = (
    "1131d3733bc69dc30f3fdb8609c027d9df54ce4a0c312eb62c3f912dcf07ebde")
# the owner-side deferred invariants (a 16384-wide chunk's auto),
# forced at chunk 128
WIDE = dict(deferred=True)


@contextlib.contextmanager
def segments_of(width: int):
    """Engines built inside take `width` rows an insert segment: the
    width is a function of the geometry and not an option, so the test
    swaps the function while the engine is built."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded, "commit_width", lambda chunk, D, B: width)
        yield


def engine_at_width(width: int, **kw):
    with segments_of(width):
        return make_sharded_engine(FF, fp_mesh(4), **GEOM, **kw)


def test_results_with_counters_are_the_parents_bit_for_bit(mesh_run):
    """Counts, queue rows and their order, per-action and outdegree
    statistics: the parent's, bit for bit, though some bodies of this
    run take two insert segments (the table's fingerprints, slot order
    aside: test_shards_partition_the_one_chip_table)."""
    carry, n_segments = mesh_run
    assert carry_digest(carry, GEOM["queue_capacity"], table=False
                        ) == PARENT_DIGEST_LESS_TABLE
    # a finished check leaves its last segment: 109 levels of at most
    # one body per level here, so fewer than 16 x segments bodies
    stat = np.asarray(carry.route_stat)
    assert 16 * (n_segments - 1) < stat[0, 1] < 16 * n_segments
    assert (segments(carry)[:, 0] > stat[:, 1]).any()


def test_wide_chunk_paths_are_the_parents_bit_for_bit():
    init_fn, run_fn = make_sharded_engine(FF, fp_mesh(4), **WIDE, **GEOM)
    carry = jax.block_until_ready(run_fn(init_fn()))
    assert carry_digest(carry, GEOM["queue_capacity"], table=False
                        ) == PARENT_DIGEST_LESS_TABLE


@pytest.mark.parametrize("kw", [{}, WIDE], ids=["sorted", "wide"])
def test_one_segment_a_body_leaves_the_parents_table_too(kw):
    """Where a body's candidates fit one segment (512 rows hold any of
    this run's), compaction keeps lane order and the insert is the
    parent's one insert exactly: the table's words as well."""
    init_fn, run_fn = engine_at_width(512, **kw)
    carry = jax.block_until_ready(run_fn(init_fn()))
    assert carry_digest(carry, GEOM["queue_capacity"]) == PARENT_DIGEST
    stat = np.asarray(carry.route_stat)
    assert (segments(carry)[:, 0] <= stat[:, 1]).all()


def test_route_counters_against_the_hand_count(mesh_run):
    carry, _ = mesh_run
    be = kubeapi_backend(FF)
    L, F = be.n_lanes, be.cdc.n_fields
    # by hand: 128 x L candidates a body over 4 owners at factor 2.0
    B = int(2.0 * 128 * L / 4) + 8
    assert route_bucket_width(128, L, 4, 2.0) == B
    step_bytes = 4 * B * (F + 3) * 4 + 4 * B
    geo = route_geometry(be, 128, 4, 2.0)
    # one insert segment: a chunk of compacted candidates
    assert geo == dict(bucket=B, step_bytes=step_bytes, commit_rows=128)
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


def test_the_commits_own_counts_on_the_mesh(mesh_run, narrow_run):
    """ISSUE 50's block on four devices: one accumulator a device, its
    `probe_segments` named `commit_segments` and its `enqueue_trips`
    `enqueue_segments` as before there was a block, every other count
    summed over the devices; every device counts every body; the valid
    lanes are what the owners received (generated less the initial
    states); the compaction sorted once a segment and the enqueue has
    no ladder.  Several segments a body (NARROW rows each) change the
    segments and nothing the insert counts about states."""
    carry, _ = mesh_run
    with segments_of(96):  # the geometry the fixture's engine was built at
        geo = route_geometry(kubeapi_backend(FF), 128, 4, 2.0)
    r = result_from_shard_carry(carry, 1.0, route=geo)
    n_init = len(kubeapi_backend(FF).initial_vectors())
    stat = np.asarray(carry.commit_stat)
    assert stat.shape == (4, fpset.COMMIT_STAT_COLS + len(
        sharded.MESH_COUNTS))
    assert r.commit_segments == tuple(segments(carry)[:, 0].tolist())
    assert r.enqueue_segments == tuple(segments(carry)[:, 1].tolist())
    assert r.commit_probe_segments == sum(r.commit_segments)
    bodies = int(np.asarray(carry.route_stat)[0, 1])
    per_device = [fpset.commit_stat_fields(
        row[fpset.COMMIT_STAT_COLS:], sharded.MESH_COUNTS, None)
        for row in stat]
    assert [d["bodies"] for d in per_device] == [bodies] * 4
    assert r.commit_bodies == 4 * bodies
    assert r.commit_valid == r.generated - n_init
    new = r.distinct - n_init
    assert r.commit_new == new
    assert r.commit_claimed <= new <= (
        r.commit_claimed + r.commit_stragglers) <= r.commit_reps
    assert sum(r.commit_compact_rung) == r.commit_probe_segments
    assert r.commit_enqueue_rung is None and r.commit_enqueue_ladder is None
    # the statics: the test's 96-row segments, probed whole; chunk 128
    # is under the deferred mode's threshold
    assert (r.commit_width, r.commit_probe_width, r.commit_claim_block,
            r.commit_checker_trips) == (96, 96, 96, 0)
    assert r.commit_compact_ladder == (96,)
    # without the geometry the counts are still there
    bare = result_from_shard_carry(carry, 1.0)
    assert bare.commit_rows is None and bare.commit_width is None
    assert bare.commit_segments == r.commit_segments
    assert bare.commit_probe_segments == r.commit_probe_segments
    many = result_from_shard_carry(narrow_run, 1.0)
    assert many.commit_probe_segments > r.commit_probe_segments
    assert (many.commit_valid, many.commit_bodies) == (
        r.commit_valid, r.commit_bodies)


# -- (e) the owner-side insert, a segment of compacted candidates at a time


def hand_lanes(cnt, bucket):
    """The received lanes that hold a candidate, in lane order: bucket
    d's first cnt[d] slots."""
    return [d * bucket + p for d, n in enumerate(cnt) for p in range(n)]


# (live rows a bucket, bucket width, segment width)
COMPACT_CASES = [
    ([3, 5, 2, 4], 8, 4),   # prefixes of every length, 14 = 3.5 widths
    ([0, 6, 0, 1], 8, 4),   # empty buckets, the first among them
    ([2, 8, 8, 0], 8, 6),   # buckets filled to the brim; 18 = 3 widths
    ([0, 0, 0, 0], 8, 4),   # nothing received
    ([4, 4, 4, 4], 4, 16),  # everything received, one segment, exactly
    ([7], 9, 4),            # one device: one bucket
]


@pytest.mark.parametrize("cnt,bucket,width", COMPACT_CASES)
def test_compact_lanes_against_the_hand_count(cnt, bucket, width):
    want = hand_lanes(cnt, bucket)
    dead = len(cnt) * bucket
    trips = -(-len(want) // width)
    got = []
    for k in range(trips + 1):  # one segment past the last: all dead
        j = k * width + np.arange(width, dtype=np.int32)
        got += np.asarray(compact_lanes(
            np.asarray(cnt, np.int32), j, bucket)).tolist()
    assert got == want + [dead] * (len(got) - len(want))
    # any shape of positions, as the insert maps its claimants back
    j2 = np.arange(2 * width, dtype=np.int32).reshape(2, width)
    assert np.asarray(compact_lanes(
        np.asarray(cnt, np.int32), j2, bucket)).reshape(-1).tolist() == (
        (want + [dead] * 2 * width)[:2 * width])


def test_commit_width_is_a_chunk_or_all_that_arrives():
    assert commit_width(16384, 4, 98312) == 16384  # the four-chip cell
    assert commit_width(1024, 4, 6152) == 1024     # chip_smoke's leg
    assert commit_width(128, 1, 100) == 100        # never past D x B


@pytest.mark.parametrize("cnt,bucket,width", COMPACT_CASES)
def test_compact_rows_are_the_gather_at_compact_lanes(cnt, bucket, width):
    """The segment's words by slices and selects against the plain
    gather, zeros past the total; every segment and one beyond."""
    D = len(cnt)
    arr = np.arange(1, D * bucket + 1, dtype=np.uint32)
    padded = np.concatenate([arr, np.zeros(width, np.uint32)])
    c = np.asarray(cnt, np.int32)
    for k in range(-(-sum(cnt) // width) + 1):
        j = k * width + np.arange(width, dtype=np.int32)
        lane = np.asarray(compact_lanes(c, j, bucket))
        want = np.where(lane < D * bucket,
                        arr[np.minimum(lane, D * bucket - 1)], 0)
        got = np.asarray(compact_rows(
            padded, c, np.int32(k * width), width))
        assert got.tolist() == want.tolist()


NARROW = 16  # rows a segment, where a body of this rung receives ~40


@pytest.fixture(scope="module", params=[{}, WIDE], ids=["sorted", "wide"])
def narrow_run(request):
    init_fn, run_fn = engine_at_width(NARROW, **request.param)
    return jax.block_until_ready(run_fn(init_fn()))


def test_many_segments_a_body_give_the_same_check(
        narrow_run, mesh_run, one_chip_fps, reference_1x1):
    carry, ref = narrow_run, reference_1x1
    r = result_from_shard_carry(
        carry, 1.0, labels=kubeapi_backend(FF).labels)
    assert (r.generated, r.distinct, r.depth, r.queue_left, r.violation
            ) == (ref.generated, ref.distinct, ref.depth, 0, 0)
    assert {k: v for k, v in r.action_generated.items() if v} == dict(
        ref.action_generated)
    # the table holds the one-chip engine's fingerprints, each at its
    # owner and each counted new once
    shards = [_raw_fps(carry.table[d]) for d in range(4)]
    assert [len(s) for s in shards] == list(r.shard_distinct)
    assert set().union(*shards) == one_chip_fps
    assert sum(len(s) for s in shards) == len(one_chip_fps) == 8203
    # the highest segment goes first, so each new fingerprint is still
    # claimed by its highest received lane: queue rows and order, the
    # per-action distinct counts and the outdegree histogram are the
    # one-segment run's bit for bit; only slots inside a table bucket
    # may be taken in another order
    assert carry_digest(carry, GEOM["queue_capacity"], table=False
                        ) == PARENT_DIGEST_LESS_TABLE
    stat = np.asarray(carry.route_stat)
    bodies = int(stat[0, 1])
    assert bodies == int(np.asarray(mesh_run[0].route_stat)[0, 1])
    ran = segments(carry)[:, 0]
    assert (ran > bodies).all()  # several segments a body
    # and never more than the received candidates need
    assert (ran <= (ref.generated + 3 * bodies) // NARROW + bodies).all()


def test_spill_veto_goes_through_the_segments():
    """The halves as the spill runtime runs them, the host's veto
    between them: a veto of everything commits nothing, whatever the
    segment; a veto of nothing is the fused body's step."""
    from jaxtlc.engine.sharded import ShardedSpillRuntime

    with segments_of(NARROW):
        rt = ShardedSpillRuntime(FF, fp_mesh(4), **GEOM)
    init_fn, run_fn = engine_at_width(NARROW, segment=1)
    carry = rt.init_fn()
    for _ in range(40):  # to a level that fills several segments
        carry = rt.audit_step_fn(carry)
    fused = init_fn()
    for _ in range(40):
        fused = run_fn(fused)
    qcap = GEOM["queue_capacity"]
    assert carry_digest(carry, qcap) == carry_digest(fused, qcap)
    ex = rt._expand_fn(carry)
    received = np.asarray(ex.r_valid).sum(axis=1)
    assert (received > NARROW).any()
    before = segments(carry)[:, 0]
    all_veto = rt._commit_fn(carry, ex, np.ones((4, rt._DB), bool))
    assert (np.asarray(all_veto.distinct)
            == np.asarray(carry.distinct)).all()
    assert (np.asarray(all_veto.qtail) == np.asarray(carry.qtail)).all()
    assert (np.asarray(all_veto.table) == np.asarray(carry.table)).all()
    assert int(np.asarray(all_veto.spill_hits).sum()) == received.sum()
    # the segments still ran: they cover what arrived, vetoed or not
    assert (segments(all_veto)[:, 0] - before
            == -(-received // NARROW)).all()
    # half the lanes vetoed: exactly the rest can be new
    ex_lo = np.asarray(ex.r_lo)
    veto = (ex_lo & 1).astype(bool)
    some = rt._commit_fn(carry, ex, veto)
    none = rt._commit_fn(carry, ex, np.zeros((4, rt._DB), bool))
    kept = [_raw_fps(some.table[d]) - _raw_fps(carry.table[d])
            for d in range(4)]
    full = [_raw_fps(none.table[d]) - _raw_fps(carry.table[d])
            for d in range(4)]
    for d in range(4):
        ok = {(lo, hi) for lo, hi in zip(
            ex_lo[d][np.asarray(ex.r_valid)[d] & ~veto[d]].tolist(),
            np.asarray(ex.r_hi)[d][
                np.asarray(ex.r_valid)[d] & ~veto[d]].tolist())}
        assert kept[d] == full[d] & ok and len(full[d]) > len(kept[d]) > 0


def test_pipeline_goes_through_the_segments():
    init_fn, run_fn = engine_at_width(NARROW, pipeline=True)
    carry = jax.block_until_ready(run_fn(init_fn()))
    # the deferred verdict fold lands the same adds one body later
    assert carry_digest(carry, GEOM["queue_capacity"], table=False
                        ) == PARENT_DIGEST_LESS_TABLE
    stat = np.asarray(carry.route_stat)
    assert (segments(carry)[:, 0] > stat[:, 1]).all()


def test_commit_counters_cross_a_regrow_and_a_reshard(mesh_run):
    from jaxtlc.dist.pod import reshard_carry
    from jaxtlc.resil.regrow import migrate_shard_carry

    carry, _ = mesh_run
    stat, ran = np.asarray(carry.route_stat), segments(carry)
    assert stat.shape == (4, 2) and (ran > 0).all()
    old = dict(queue_capacity=GEOM["queue_capacity"],
               fp_capacity=GEOM["fp_capacity"], route_factor=2.0)
    grown = migrate_shard_carry(carry, old, dict(
        old, fp_capacity=2 * GEOM["fp_capacity"], route_factor=4.0))
    assert (np.asarray(grown.route_stat) == stat).all()
    assert (np.asarray(grown.commit_stat)
            == np.asarray(carry.commit_stat)).all()
    r = result_from_shard_carry(
        grown, 1.0, route=route_geometry(kubeapi_backend(FF), 128, 4, 4.0))
    assert r.commit_segments == tuple(ran[:, 0].tolist())
    assert r.enqueue_segments == tuple(ran[:, 1].tolist())
    assert r.commit_rows == 128
    halved = reshard_carry(
        jax.tree.map(np.asarray, carry), kubeapi_backend(FF), 2)
    # a pod's new rows all start from the old pod's maxima; the
    # commit's counts are partial sums, and go on from row 0
    assert (np.asarray(halved.route_stat) == stat.max(axis=0)).all()
    assert np.asarray(halved.route_stat).shape == (2, 2)
    assert (np.asarray(halved.commit_stat)[0]
            == np.asarray(carry.commit_stat).sum(axis=0)).all()
    assert not np.asarray(halved.commit_stat)[1].any()


# -- (g) the enqueue: a compaction and contiguous writes onto the ring ----

# the block alone: W rows a trip onto a ring of QR rows (+ the dump
# row), D x B = 64 received lanes of 3 words
EQ_W, EQ_QCAP, EQ_DB, EQ_F = 8, 40, 64, 3


def scatter_replay(queue, r_flat, is_new, qtail, go):
    """The parent's rule in numpy: lane l, where new, goes to row
    (qtail + cumsum(is_new)[l] - 1) % qcap; a halting body writes
    nothing."""
    q = np.array(queue)
    qcap = q.shape[0] - 1
    if go:
        pos = qtail + np.cumsum(is_new.astype(np.int32)) - 1
        q[pos[is_new] % qcap] = r_flat[is_new]
    return q


enqueue_block = jax.jit(enqueue_new_rows, static_argnames="width")


# (new rows, tail before the body): the ring's last row is 39
ENQUEUE_CASES = {
    "nothing-new": (0, 5),
    "one": (1, 5),
    "a-row-short-of-a-block": (EQ_W - 1, 5),
    "a-block": (EQ_W, 5),
    "a-block-and-a-row": (EQ_W + 1, 5),
    "several-trips": (3 * EQ_W + 3, 2),
    "ends-on-the-last-row": (EQ_W, EQ_QCAP - EQ_W),
    "wraps-in-the-first-row": (EQ_W + 2, EQ_QCAP - 1),
    "wraps-in-a-middle-row": (EQ_W + 2, EQ_QCAP - 3),
    "wraps-in-the-last-row": (EQ_W, EQ_QCAP - EQ_W + 1),
    "wraps-in-the-second-trip": (2 * EQ_W + 5, EQ_QCAP - EQ_W - 4),
    "fills-the-ring": (EQ_QCAP, 17),
    "a-tail-many-laps-on": (EQ_W + 3, 7 * EQ_QCAP + EQ_QCAP - 2),
    "every-lane-new": (EQ_DB, 0),  # past the ring: only with go False
}


@pytest.mark.parametrize("n_new,qtail,go", [
    pytest.param(n, t, go, id=f"{name}-{'go' if go else 'queue-full'}")
    for name, (n, t) in ENQUEUE_CASES.items() for go in (True, False)
    if n <= EQ_QCAP or not go])
def test_enqueue_block_is_the_scatter_it_replaces(n_new, qtail, go):
    """Rows [0, qcap) after the block equal the parent's per-lane
    scatter, wherever the block lies on the ring; a body that halts on
    a full queue leaves them all; the trips follow the new rows."""
    rng = np.random.default_rng(n_new * 1000 + qtail)
    is_new = np.zeros(EQ_DB, bool)
    is_new[rng.choice(EQ_DB, n_new, replace=False)] = True
    r_flat = rng.integers(1, 1 << 20, (EQ_DB, EQ_F)).astype(np.int32)
    queue = -rng.integers(1, 1 << 20, (EQ_QCAP + 1, EQ_F)).astype(np.int32)
    got, trips = enqueue_block(
        queue, r_flat, is_new, np.int32(qtail),
        np.int32(n_new if go else 0), width=EQ_W)
    want = scatter_replay(queue, r_flat, is_new, qtail, go)
    assert (np.asarray(got)[:EQ_QCAP] == want[:EQ_QCAP]).all()
    # the dump row has fallen idle
    assert (np.asarray(got)[EQ_QCAP] == queue[EQ_QCAP]).all()
    assert int(trips) == (-(-n_new // EQ_W) if go else 0)


def test_enqueue_block_wider_than_the_ring():
    """A ring shorter than a segment (tiny geometries) takes blocks of
    its own length."""
    rng = np.random.default_rng(7)
    qcap, n_new, qtail = 6, 5, 4
    is_new = np.zeros(EQ_DB, bool)
    is_new[rng.choice(EQ_DB, n_new, replace=False)] = True
    r_flat = rng.integers(1, 99, (EQ_DB, EQ_F)).astype(np.int32)
    queue = np.zeros((qcap + 1, EQ_F), np.int32)
    got, trips = enqueue_block(
        queue, r_flat, is_new, np.int32(qtail), np.int32(n_new),
        width=EQ_W)
    assert (np.asarray(got) == scatter_replay(
        queue, r_flat, is_new, qtail, True)).all()
    assert int(trips) == 1


# a ring short enough to turn over six to fifteen times in the check, and
# NARROW rows a block, so that bodies take several
RING = 192


@pytest.fixture(scope="module")
def ring_engine():
    with segments_of(NARROW):
        return make_sharded_engine(
            FF, fp_mesh(4), segment=1, **dict(GEOM, queue_capacity=RING))


@pytest.fixture(scope="module")
def ring_run(ring_engine):
    """The check a body at a time: every carry but the queue's and the
    table's own words (tails and counters a body), and the last."""
    init_fn, step_fn = ring_engine
    carry = init_fn()
    tails, stats, mid = [np.asarray(carry.qtail)], [], None
    while bool(np.asarray(carry.cont).any()):
        carry = jax.block_until_ready(step_fn(carry))
        tails.append(np.asarray(carry.qtail))
        stats.append(segments(carry))
        if len(stats) == 60:
            mid = carry
    return carry, np.stack(tails), np.stack(stats), mid


def test_a_ring_that_wraps_gives_the_same_check(
        ring_run, mesh_run, reference_1x1):
    carry, tails, _, _ = ring_run
    ref, wide = reference_1x1, mesh_run[0]
    r = result_from_shard_carry(
        carry, 1.0, labels=kubeapi_backend(FF).labels)
    assert (r.generated, r.distinct, r.depth, r.queue_left, r.violation
            ) == (ref.generated, ref.distinct, ref.depth, 0, 0)
    # the ring turned over many times on every device ...
    assert (tails[-1] > 6 * RING).all()
    # ... and the statistics are the long queue's (the parent's, by
    # its digest above) bit for bit: same rows in the same order
    for name in ("act_gen", "act_dist", "outdeg_hist", "qtail", "qhead",
                 "distinct", "generated"):
        assert (np.asarray(getattr(carry, name))[..., :-1]
                == np.asarray(getattr(wide, name))[..., :-1]).all(), name
    # what the ring still holds is what the long queue holds at the
    # same positions (it turned over once itself, by a few rows)
    long_q, ring_q = np.asarray(wide.queue), np.asarray(carry.queue)
    for d in range(4):
        pos = np.arange(tails[-1][d] - RING, tails[-1][d])
        assert (ring_q[d][pos % RING]
                == long_q[d][pos % GEOM["queue_capacity"]]).all()


def test_enqueue_segments_against_the_hand_count(ring_run):
    """Blocks of NARROW rows, as many a body as its NEW rows need:
    the tails say how many those were."""
    _, tails, stats, _ = ring_run
    new = np.diff(tails, axis=0)  # [bodies, 4]
    assert (new > 2 * NARROW).any()  # bodies of three blocks and more
    want = np.cumsum(-(-new // NARROW), axis=0)
    assert (stats[:, :, 1] == want).all()
    # the insert's segments follow what arrived, so they are more
    assert (stats[-1, :, 0] > stats[-1, :, 1]).all()


def test_a_full_queue_halts_and_leaves_the_ring(ring_engine, ring_run):
    """The body that finds no room writes no row of [0, qcap), moves no
    tail and halts the run by name."""
    from jaxtlc.engine.bfs import VIOL_QUEUE_FULL

    _, step_fn = ring_engine
    mid = ring_run[3]
    # stale rows counted in behind the tail, up to a full ring: any
    # new row is one too many, and the pop still takes the frontier
    full = mid._replace(qtail=mid.qhead + RING)
    halted = jax.block_until_ready(step_fn(full))
    assert (np.asarray(halted.viol) == VIOL_QUEUE_FULL).all()
    assert not np.asarray(halted.cont).any()
    assert (np.asarray(halted.queue)[:, :RING]
            == np.asarray(full.queue)[:, :RING]).all()
    assert (np.asarray(halted.qtail) == np.asarray(full.qtail)).all()
    assert (segments(halted)[:, 1] == segments(full)[:, 1]).all()
    # the same body with room enqueues
    went = jax.block_until_ready(step_fn(mid))
    assert (np.asarray(went.qtail) > np.asarray(mid.qtail)).any()


def test_a_reshard_reads_the_ring_where_it_has_wrapped(ring_run):
    """A pod's reshard takes the live window off the ring, not off rows
    [qhead, qtail) of a queue that long."""
    from jaxtlc.dist.pod import reshard_carry

    mid = jax.tree.map(np.asarray, ring_run[3])
    assert (mid.qtail > RING).all()  # every ring has turned over
    want = np.concatenate([
        mid.queue[d][np.arange(mid.qhead[d], mid.qtail[d]) % RING]
        for d in range(4)])
    halved = reshard_carry(mid, kubeapi_backend(FF), 2)
    got = np.concatenate([
        np.asarray(halved.queue)[d][:int(np.asarray(halved.qtail)[d])]
        for d in range(2)])
    assert len(got) == len(want) > 0
    assert sorted(map(tuple, got)) == sorted(map(tuple, want))


def test_no_scatter_holds_the_queue():
    """The lowered body writes the queue by dynamic_update_slice alone:
    no scatter's operand has the queue's shape, on any composition of
    commit_half, and jaxtlc.enqueue holds none at all."""
    F = kubeapi_backend(FF).cdc.n_fields
    qshape = (GEOM["queue_capacity"] + 1, F)

    def scatters(fn, *args):
        traced = jax.make_jaxpr(fn)(*args)
        out = []
        for stack, eqn in scoped_eqns(traced.jaxpr):
            if eqn.primitive.name.startswith("scatter"):
                out.append((stack, eqn.invars[0].aval.shape))
        return out

    found = []
    # fused, pipelined, the deferred path, and the one-device mesh,
    # where B is all of ncand
    for D, kw in ((4, {}), (4, dict(pipeline=True)), (4, WIDE), (1, {})):
        init_fn, seg_fn = make_sharded_engine(
            FF, fp_mesh(D), segment=16, **GEOM, **kw)
        found += scatters(seg_fn, init_fn())
    from jaxtlc.engine.sharded import ShardedSpillRuntime

    rt = ShardedSpillRuntime(FF, fp_mesh(4), **GEOM)
    carry = rt.init_fn()
    ex = rt._expand_fn(carry)
    found += scatters(rt._commit_fn, carry, ex,
                      np.zeros((4, rt._DB), bool))
    assert found  # the table's and is_new's are there
    assert not [f for f in found if f[1][-2:] == qshape], found
    assert not [f for f in found if "jaxtlc.enqueue" in f[0]], found


def test_a_snapshot_from_before_the_column_is_refused_by_name(
        mesh_run, tmp_path):
    """route_stat lost its two segment counts to the commit's block
    (ISSUE 50): a checkpoint cut by an engine before (three columns, or
    four) does not resume, and the refusal names the leaf - whole-carry
    snapshots and a pod's reshard alike."""
    from jaxtlc.dist.pod import reshard_carry
    from jaxtlc.engine.checkpoint import load_checkpoint, save_checkpoint

    carry = jax.tree.map(np.asarray, mesh_run[0])
    old = carry._replace(route_stat=np.pad(carry.route_stat,
                                           ((0, 0), (0, 1))))
    path = str(tmp_path / "old.npz")
    save_checkpoint(path, old, {})
    with pytest.raises(ValueError, match=r"\.route_stat shape \(4, 3\)"):
        load_checkpoint(path, carry)
    with pytest.raises(ValueError, match="'route_stat' has 3 columns"):
        reshard_carry(old, kubeapi_backend(FF), 2)
    save_checkpoint(path, carry, {})
    _, back = load_checkpoint(path, carry)
    assert (np.asarray(back.route_stat) == carry.route_stat).all()
    # the commit's own counts (ISSUE 50) are a leaf of the layout too:
    # a snapshot cut before them is refused by the leaf's name, not
    # resumed with a zeroed block
    older = carry._replace(commit_stat=None)
    save_checkpoint(path, older, {})
    with pytest.raises(ValueError, match=r"no leaf \.commit_stat"):
        load_checkpoint(path, carry)
    with pytest.raises(ValueError, match="lacks leaf 'commit_stat'"):
        reshard_carry(older, kubeapi_backend(FF), 2)


# -- (f) the source side without per-element indexing at candidate width --


def sort_and_gather(own, valid, D):
    """The forms sorted_route replaces, in numpy: the stable sort by
    owner key, the key and the validity gathered through it, the
    bucket starts searched in the sorted key."""
    own, valid = np.asarray(own, np.int32), np.asarray(valid, bool)
    key = np.where(valid, own, D).astype(np.int32)
    order = np.argsort(key, kind="stable")
    s_own, s_valid = key[order], valid[order]
    starts = np.searchsorted(s_own, np.arange(D + 1), side="left")
    pos = np.arange(len(key)) - starts[np.clip(s_own, 0, D)]
    return (key, s_own, pos.astype(np.int32), s_valid,
            starts[1:] - starts[:-1])


# (owner a lane, valid a lane, D, bucket width B, a bucket overflows)
ROUTE_CASES = {
    "mixed": ([2, 0, 3, 0, 1, 2, 0, 3, 1, 0, 2, 2],
              [1, 1, 0, 1, 1, 1, 0, 1, 1, 1, 0, 1], 4, 5, False),
    "empty-owners": ([3, 1, 1, 3, 3, 1, 1, 3], [1] * 8, 4, 4, False),
    "nothing-valid": ([0, 1, 2, 3, 0, 1], [0] * 6, 4, 3, False),
    "everything-valid": ([1, 0, 3, 2, 2, 3, 0, 1], [1] * 8, 4, 4, False),
    # owner 0 holds 5 > B = 3: route_ovf, and positions at and past B
    "overflow": ([0, 0, 1, 0, 0, 2, 0, 3], [1, 1, 1, 1, 1, 0, 1, 1], 4, 3,
                 True),
    "one-device": ([0] * 7, [1, 0, 1, 1, 0, 1, 1], 1, 7, False),
}


@pytest.mark.parametrize("own,valid,D,B,ovf", ROUTE_CASES.values(),
                         ids=ROUTE_CASES.keys())
def test_sorted_route_is_the_sort_and_gather_form(own, valid, D, B, ovf):
    key, s_own, pos, s_valid, cnt = sort_and_gather(own, valid, D)
    counts = np.asarray(owner_counts(key, D))
    assert counts.tolist() == cnt.tolist() == [
        sum(1 for o, v in zip(own, valid) if v and o == d)
        for d in range(D)]
    got = sorted_route(counts, len(key))
    for g, want in zip(got, (s_own, pos, s_valid)):
        assert np.asarray(g).dtype == want.dtype
        assert np.asarray(g).tolist() == want.tolist()
    # the overflow flag as the counts give it
    assert bool((counts > B).any()) == bool(
        (s_valid & (pos >= B)).any()) == ovf
    # the sorted owners give the counts back (the pipeline's stash)
    assert np.asarray(owner_counts(got[0], D)).tolist() == cnt.tolist()


# counts a bucket and its width: in the second and third the earlier
# buckets hold more than d * B, so bucket d's slice starts in front of
# the verdicts; in the last nothing was sent
VERDICT_CASES = [([3, 2, 4, 1], 5), ([5, 2, 0, 1], 3), ([0, 7, 1, 2], 3),
                 ([6], 8), ([0, 0, 0, 0], 4)]


@pytest.mark.parametrize("cnt,B", VERDICT_CASES)
def test_sorted_verdicts_are_the_gather_at_owner_and_position(cnt, B):
    D, ncand = len(cnt), sum(cnt) + 3  # an invalid tail of 3
    s_own, pos, s_valid = (np.asarray(a) for a in sorted_route(
        np.asarray(cnt, np.int32), ncand))
    rng = np.random.default_rng(sum(cnt) + B)
    verd = rng.integers(0, 2, (D, B)).astype(np.uint8)
    verd[:, 0] = 1  # a wrong row shows
    gate = s_valid & (pos < B)
    want = (verd[np.clip(s_own, 0, D - 1), np.clip(pos, 0, B - 1)] == 1
            ) & gate
    got = np.asarray(sorted_verdicts(verd, s_own))
    assert got.dtype == verd.dtype and got.shape == (ncand,)
    assert ((got == 1) & gate).tolist() == want.tolist()
    assert (got[s_own == D] == 0).all()


@pytest.mark.parametrize("n_bins,n,live", [(23, 64, 40), (5, 16, 0),
                                           (3, 9, 9), (14, 1, 1)])
def test_masked_hist_is_the_scatter_add_less_its_dump_bin(n_bins, n, live):
    rng = np.random.default_rng(n_bins * n)
    ids = rng.integers(0, n_bins, n).astype(np.int32)
    mask = np.arange(n) < live  # a masked-out tail
    want = np.zeros(n_bins + 1, np.uint32)
    np.add.at(want, np.where(mask, ids, n_bins), 1)
    got = np.asarray(masked_hist(ids, mask, n_bins))
    assert got.dtype == np.uint32
    assert got.tolist() == want[:n_bins].tolist()
    assert int(got.sum()) == live


def test_source_side_indexes_no_element_at_candidate_width():
    """Inside jaxtlc.route, jaxtlc.verdict_return and jaxtlc.level
    nothing gathers or scatters single elements at the ncand lanes of
    a body: the one row gather by the owner sort's order and
    is_new_local's scatter are all that index there (immediate and
    pipelined bodies alike)."""
    ncand = GEOM["chunk"] * kubeapi_backend(FF).n_lanes
    for kw in ({}, dict(pipeline=True)):
        init_fn, seg_fn = make_sharded_engine(FF, fp_mesh(4), segment=16,
                                              **GEOM, **kw)
        traced = jax.make_jaxpr(seg_fn)(init_fn())
        found = []
        for stack, eqn in scoped_eqns(traced.jaxpr):
            if not any(sc in stack for sc in (
                    "jaxtlc.route", "jaxtlc.verdict_return",
                    "jaxtlc.level")):
                continue
            if not eqn.primitive.name.startswith(("gather", "scatter")):
                continue
            shapes_ = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            if any(sh and sh[0] == ncand for sh in shapes_):
                found.append((eqn.primitive.name,
                              eqn.invars[0].aval.shape))
        rows = kubeapi_backend(FF).cdc.n_fields + 3
        # the payload's row gather by `order`, and undoing the
        # permutation (in the pipeline's prologue when the verdicts are
        # deferred)
        assert sorted(found) == [("gather", (ncand, rows)),
                                 ("scatter", (ncand,))], found
