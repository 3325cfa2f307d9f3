"""The commit's in-batch dedup (ISSUE 38, ISSUE 44): ONE ordering, two
stable sorts at candidate width (fpset.fpset_insert_sorted), on every
route that reaches the expand/commit seam - no mode, no flag, no key.
Pinned here: the ordering against a reference that shares no code with
it, past one probe block; that no route indexes an element at candidate
width under `jaxtlc.dedup`; the segment program's shape; the two-tier
step nest; and that a snapshot whose meta still names the deleted
hash-slab mode (`sort_free: true`, every run at chunk >= 2,048 before
ISSUE 38 wrote one) resumes exact, the key ignored.

Compile budget: ONE module-scoped fixture owns the FF engine compile;
the segment case, the three resume cases and the both-tiers case each
pay their own small FF compile; the route pins trace and do not
compile; everything else is fpset-level or host-only.
"""

import inspect
import json
import os

import numpy as np
import pytest

from jaxtlc.config import ModelConfig
from jaxtlc.engine import checkpoint as ck
from jaxtlc.engine.backend import kubeapi_backend
from jaxtlc.engine.bfs import (
    commit_geometry,
    make_engine,
    result_from_carry,
)
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised

FF = ModelConfig(False, False)
EXPECT_FF = (17020, 8203, 109)
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)
TWOPHASE_CFG = os.path.join(
    os.path.dirname(__file__), os.pardir, "specs", "TwoPhase.toolbox",
    "Model_1", "MC.cfg")


def signature(r):
    """Full exactness signature of a CheckResult."""
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())),
            r.outdegree)


def _same_leaves(a, b) -> bool:
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def ff_run():
    """The module's ONLY run_fn compile: the FF corner to the end, the
    final carry kept (carry, CheckResult)."""
    import jax

    init_fn, run_fn, _ = make_engine(FF, **KW, donate=False)
    carry = jax.block_until_ready(run_fn(init_fn()))
    r = result_from_carry(carry, 0.0, commit=commit_geometry(
        kubeapi_backend(FF).n_lanes, KW["chunk"]))
    assert (r.generated, r.distinct, r.depth) == EXPECT_FF
    return carry, r


# ---------------------------------------------------------------------------
# the ordering against a reference that shares no code with it
# ---------------------------------------------------------------------------


def _batch(kind: str, seed: int, n: int):
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, 2 ** 32, size=n, dtype=np.uint32)
    hi = rng.integers(0, 2 ** 32, size=n, dtype=np.uint32)
    if kind == "mixed":  # in-batch duplicates, some lanes masked
        pick = rng.integers(0, (2 * n) // 3, size=n)
        lo, hi = lo[pick], hi[pick]
        mask = rng.random(n) < 0.85
    elif kind == "distinct":  # a burst: every lane its own class
        mask = np.ones(n, bool)
    else:  # "masked": nothing to insert
        mask = np.zeros(n, bool)
    return lo, hi, mask


@pytest.mark.parametrize("n, probe_width, kind", [
    (1024, 256, "mixed"), (4096, 512, "mixed"), (1024, 128, "distinct"),
    (512, 128, "masked"), (800, 256, "mixed")],
    ids=["1024x256", "4096x512", "distinct-burst", "all-masked",
         "ragged-last-block"])
def test_sorted_insert_past_one_probe_block_matches_host_replay(
        n, probe_width, kind):
    """`fpset_insert_sorted` with more representatives than one probe
    block holds (the block loop of `_probe_segments`, its padded last
    block included), against a Python set and `fpset.host_insert`'s
    one-at-a-time walk: three batches into one table, each repeating
    some of the one before - the verdicts name the HIGHEST lane of
    every fresh fingerprint and no other, the representatives stand
    compacted in ascending stored order, and the table holds exactly
    the host replay's words."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine import fpset

    cap = 1 << 15
    insert = jax.jit(lambda s, lo, hi, mask: fpset.fpset_insert_sorted(
        s, lo, hi, mask, probe_width=probe_width,
        claim_width=probe_width))
    s = fpset.fpset_new(cap)
    ref = np.zeros_like(np.asarray(s.table))
    seen, blocks, prev = set(), 0, None
    for step in range(3):
        lo, hi, mask = _batch(kind, 40 + step, n)
        if prev is not None:  # a third of the lanes were seen before
            lo[::3], hi[::3] = prev[0][::3], prev[1][::3]
        prev = (lo.copy(), hi.copy())
        s, is_new_c, c_idx, nreps, _ = insert(
            s, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(mask))
        is_new_c, c_idx, nreps = (
            np.asarray(is_new_c), np.asarray(c_idx), int(nreps))
        last = {}  # class -> its highest masked lane
        for lane in np.flatnonzero(mask):
            last[(int(lo[lane]), int(hi[lane]))] = int(lane)
        assert nreps == len(last)
        assert sorted(c_idx.tolist()) == list(range(n))  # a permutation
        assert sorted(c_idx[:nreps].tolist()) == sorted(last.values())
        assert not is_new_c[nreps:].any()
        fresh = {k: v for k, v in last.items() if k not in seen}
        assert sorted(c_idx[is_new_c].tolist()) == sorted(fresh.values())
        # fp-ascending: the stored (mixed, remapped) words of the
        # representatives, (hi, lo) major to minor
        mlo, mhi = fpset._remap(*fpset._mix(
            jnp.asarray(lo[c_idx[:nreps]]), jnp.asarray(hi[c_idx[:nreps]])))
        keys = (np.asarray(mhi).astype(np.uint64) << np.uint64(32)
                ) | np.asarray(mlo).astype(np.uint64)
        assert (keys[1:] > keys[:-1]).all()
        for key in fresh:
            assert fpset.host_insert(ref, *key)
        seen |= set(fresh)
        blocks = max(blocks, -(-nreps // probe_width))

    def words(t):
        pairs = t.reshape(-1, 2)
        return sorted(map(tuple, pairs[pairs.any(axis=1)].tolist()))

    table = np.asarray(s.table).copy()
    assert words(table) == words(ref) and len(words(table)) == len(seen)
    assert not any(fpset.host_insert(table, *key) for key in seen)
    # the width was real: some batch took more than one probe block
    assert blocks >= (0 if kind == "masked" else 2)


# ---------------------------------------------------------------------------
# the commit's own counts (ISSUE 50): the seam's fifth value against a
# numpy recount, and a whole check's block against its identities
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n, probe_width", [
    (4096, 256), (4096, 1024), (40_000, 8192)],
    ids=["4096x256", "4096x1024-blocked-write", "40000-three-rungs"])
def test_the_inserts_own_counts_match_a_numpy_recount(n, probe_width):
    """`fpset_insert_sorted`'s fifth value over three batches into one
    table, the valid share falling from batch to batch (at 40,000 lanes
    across the three rungs of the compaction's ladder): the lanes the
    mask let through, their classes, those the table held, those
    written in round 0 or left to the walk, the probe's segments, the
    round-0 write's blocks and the rung the compaction sorted at - all
    recounted in numpy from the batch and the set of what went
    before."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine import fpset

    insert = jax.jit(lambda s, lo, hi, mask: fpset.fpset_insert_sorted(
        s, lo, hi, mask, probe_width=probe_width,
        claim_width=probe_width)[::4])
    ladder = fpset.sort_ladder(n)
    s, seen, rungs, prev = fpset.fpset_new(1 << 18), set(), [], None
    most = written = 0  # a batch's segments; writes that stopped early
    for step, fill in enumerate((0.95, 0.6, 0.2)):
        lo, hi, _ = _batch("mixed", 60 + step, n)
        if prev is not None:  # a third of the lanes were seen before
            lo[::3], hi[::3] = prev[0][::3], prev[1][::3]
        prev = (lo.copy(), hi.copy())
        mask = np.random.default_rng(step).random(n) < fill
        s, stat = insert(s, jnp.asarray(lo), jnp.asarray(hi),
                         jnp.asarray(mask))
        did = fpset.commit_stat_fields(stat)
        classes = {(int(a), int(b))
                   for a, b in zip(lo[mask], hi[mask])}
        fresh = classes - seen
        seen |= classes
        assert did["valid"] == mask.sum()
        assert did["reps"] == len(classes)
        # a representative its home bucket does not hold writes a slot
        # in round 0 or is left to the walk (a class of an earlier
        # batch that lives past a full home bucket walks to be found)
        assert did["claimed"] <= len(fresh) <= (
            did["claimed"] + did["stragglers"]) <= len(classes)
        assert (did["walk_rounds"] > 0) == (did["stragglers"] > 0)
        segments = -(-len(classes) // probe_width)
        assert did["probe_segments"] == segments >= 1
        block = probe_width // fpset.WRITE_BLOCKS
        if fpset._blocked(probe_width):  # as far as its claimers go
            assert -(-did["claimed"] // block) <= did["claim_blocks"] <= (
                segments * fpset.WRITE_BLOCKS)
            written += did["claim_blocks"] < segments * fpset.WRITE_BLOCKS
        else:
            assert did["claim_blocks"] == segments
        most = max(most, segments)
        at = next(i for i, w in enumerate(ladder) if w >= mask.sum())
        assert did["compact_rung"] == tuple(
            int(i == at) for i in range(fpset.LADDER_RUNGS))
        rungs.append(at)
        # the seam's own counts and no more: a loop's are its engine's
        assert stat.shape == (fpset.COMMIT_STAT_COLS,)
    assert len(set(rungs)) == len(ladder)  # every rung was taken
    assert most > 1 and written == (3 if fpset._blocked(probe_width) else 0)


def _block_identities(r, n_init: int, masked: int = 0):
    """What holds of a whole check's block whatever the model: `r` a
    CheckResult of a finished run from `n_init` initial states, on a
    model whose insert mask lets every kept successor through
    (`masked`: the successors its constraint discarded)."""
    new = r.distinct - n_init
    assert r.commit_bodies > 0 and r.commit_new == new
    assert sum(r.commit_compact_rung) == r.commit_probe_segments or (
        sum(r.commit_compact_rung) == r.commit_bodies)
    assert r.commit_valid == r.generated - n_init - masked
    assert r.commit_claimed <= new <= (
        r.commit_claimed + r.commit_stragglers) <= r.commit_reps
    assert (r.commit_walk_rounds > 0) == (r.commit_stragglers > 0)
    assert len(r.commit_compact_rung) == len(r.commit_compact_ladder)
    assert r.commit_compact_ladder[-1] == r.commit_width
    assert r.commit_probe_width <= r.commit_width


def test_a_whole_checks_block_keeps_its_identities(ff_run):
    """The hand path's block over the FF corner (the module's engine):
    both rung histograms sum to the bodies, the claims and the walk's
    new rows are the distinct states less the initial ones, the valid
    lanes are what was generated less the initial states, and the
    statics beside them are the geometry's - absent where the caller
    names none."""
    from jaxtlc.engine.bfs import commit_counters

    carry, r = ff_run
    L = kubeapi_backend(FF).n_lanes
    n_init = len(kubeapi_backend(FF).initial_vectors())
    _block_identities(r, n_init)
    assert sum(r.commit_compact_rung) == r.commit_bodies
    assert sum(r.commit_enqueue_rung) == r.commit_bodies
    # chunk 128 is under the deferred mode's threshold: no checker
    assert r.commit_checker_trips == 0
    assert (r.commit_width, r.commit_probe_width, r.commit_claim_block
            ) == (128 * L, 256, 256)
    assert r.commit_compact_ladder == (128 * L,)
    assert r.commit_enqueue_ladder == (256, 128 * L)
    # a body probes in one segment unless nothing was valid
    assert r.commit_probe_segments <= r.commit_bodies
    # the table ends half full: some claims met a full home bucket
    assert 0 < r.commit_stragglers < r.commit_claimed
    named = commit_counters(r)
    assert set(named) == {
        f for f in r._fields if f.startswith("commit_")} - {
        "commit_segments", "commit_rows"}
    assert json.loads(json.dumps(named)) == named  # journal-ready
    bare = commit_counters(result_from_carry(carry, 0.0))
    assert bare["commit_valid"] == r.commit_valid
    assert not [k for k in bare if "ladder" in k or "width" in k]


@pytest.mark.parametrize("entry", ["ckpt", "supervised"])
def test_the_block_rides_the_check_result_span(tmp_path, ff_run, entry):
    """Both entries close a `check.result` span around the one read of
    the carry, and the block is its attributes: the supervisor's, which
    also writes it on `final`, and `check_with_checkpoints`, which
    writes no journal.  The counts are the module engine's - a run cut
    into segments commits the same bodies."""
    import time

    from jaxtlc.engine.bfs import commit_counters
    from jaxtlc.obs import spans

    t0 = time.time()
    if entry == "ckpt":
        r = ck.check_with_checkpoints(FF, **KW, ckpt_every=64)
    else:
        events = []
        r = check_supervised(FF, **KW, opts=SupervisorOptions(
            ckpt_every=64,
            on_event=lambda kind, info: events.append((kind, info)),
        )).result
        (final,) = [info for kind, info in events if kind == "final"]
        assert {k: final[k] for k in commit_counters(r)} == (
            commit_counters(r))
    assert signature(r) == signature(ff_run[1])
    (read,) = [row for row in spans.snapshot(since=t0)
               if row.name == "check.result"]
    assert read.attrs == commit_counters(r) == commit_counters(ff_run[1])


def test_a_snapshot_without_the_block_is_refused_by_name(ff_run, tmp_path):
    """The block is a leaf of the carry's layout: a snapshot cut before
    it does not resume - the refusal names the leaf, nothing pads it -
    and one of another shape is refused by the same name."""
    from jaxtlc.engine.checkpoint import load_checkpoint, save_checkpoint

    carry = ff_run[0]
    path = str(tmp_path / "old.npz")
    save_checkpoint(path, carry._replace(commit_stat=None), {})
    with pytest.raises(ValueError, match=r"no leaf \.commit_stat"):
        load_checkpoint(path, carry)
    save_checkpoint(path, carry._replace(
        commit_stat=carry.commit_stat[:-1]), {})
    with pytest.raises(ValueError, match=r"\.commit_stat shape"):
        load_checkpoint(path, carry)
    save_checkpoint(path, carry, {})
    _, back = load_checkpoint(path, carry)
    assert _same_leaves(back, carry)


# ---------------------------------------------------------------------------
# the ladder (ISSUE 49): the second and third sorts at the width of what
# is live, against host references that share no code with them
# ---------------------------------------------------------------------------

LADDER_N = 70_000  # sort_ladder: 16,384 / 32,768 / 65,536 / whole
LADDER_R = 8_192  # the enqueue's: 8,192 / 16,384 / 32,768 / whole


def test_the_ladder_is_a_function_of_the_static_widths():
    """`fpset.sort_ladder`: ascending, at most four rungs, each a power
    of two of at least 16,384 lanes but the enqueue's first (its probe
    width) and the last, which is the whole array; 32,768 lanes or
    fewer get no power of two - the compaction ONE rung (the parent's
    program: recheck, the pool, serve/sweep's vmap), the enqueue its
    probe width and the whole (the parent's two-way conditional)."""
    from jaxtlc.engine.fpset import sort_ladder

    assert sort_ladder(LADDER_N) == (16384, 32768, 65536, LADDER_N)
    assert sort_ladder(LADDER_N, LADDER_R) == (
        8192, 16384, 32768, LADDER_N)
    # the cells' own widths (PERF.md, PR 49 Step 0)
    assert sort_ladder(200704) == (32768, 65536, 131072, 200704)
    assert sort_ladder(110592) == (16384, 32768, 65536, 110592)
    assert sort_ladder(356352) == (65536, 131072, 262144, 356352)
    assert sort_ladder(196608, 32768) == (32768, 65536, 131072, 196608)
    for n in (1, 1280, 20480, 32768):
        assert sort_ladder(n) == (n,)
        assert sort_ladder(n, n) == (n,)
    assert sort_ladder(1280, 256) == (256, 1280)
    assert sort_ladder(32768, 8192) == (8192, 32768)
    for n in (32769, 65536, 69632, 1 << 20):
        for first in (0, 4096, 8192):
            rungs = sort_ladder(n, first)
            assert rungs[-1] == n and len(rungs) <= 4
            assert list(rungs) == sorted(set(rungs))
            for w in rungs[bool(first):-1]:
                assert w >= 16384 and w & (w - 1) == 0, rungs


@pytest.fixture(scope="module")
def ladder_order():
    """ONE compile of `_sorted_order` at LADDER_N lanes for every fill
    share below."""
    import jax

    from jaxtlc.engine import fpset

    return jax.jit(fpset._sorted_order)


def _fill(kind: str, nv: int, n: int):
    """Stored-form words (what `_sorted_order` is handed: mask-zeroed,
    never (0, 0) on a valid lane) with `nv` valid lanes scattered over
    the n, duplicates among them."""
    rng = np.random.default_rng(nv + 1)
    lo = np.zeros(n, np.uint32)
    hi = np.zeros(n, np.uint32)
    at = rng.permutation(n)[:nv]
    if kind == "dups":  # one class: a single representative
        lo[at], hi[at] = 7, 9
    else:  # about two lanes a class, (hi, lo) both in play
        lo[at] = rng.integers(1, max(2, nv // 4), nv, dtype=np.uint32)
        hi[at] = rng.integers(0, 2, nv, dtype=np.uint32)
    return lo, hi


@pytest.mark.parametrize("kind, nv", [
    ("mixed", 0), ("mixed", 5), ("mixed", 16384), ("mixed", 16385),
    ("mixed", 32768), ("mixed", 32769), ("mixed", 65536),
    ("mixed", 65537), ("mixed", LADDER_N), ("dups", 16385),
    ("dups", LADDER_N)],
    ids=["no-valid-lane", "five", "rung0-full", "rung0-plus-1",
         "rung1-full", "rung1-plus-1", "rung2-full", "rung2-plus-1",
         "every-lane-valid", "all-duplicates", "all-duplicates-whole"])
def test_ordering_on_every_rung_matches_the_host_reference(
        ladder_order, kind, nv):
    """`_sorted_order` at a width whose ladder has four rungs, the
    valid lanes filling exactly a rung and one past it: rows [0, nreps)
    are the highest lane of every class in ascending (hi, lo) - a host
    reference in numpy -, nreps the class count, and no row past nreps
    names a representative: past the rung every c_idx is the
    out-of-range lane n (what `fpset_insert`'s and the mesh's drop
    leave out)."""
    import jax.numpy as jnp

    from jaxtlc.engine.fpset import sort_ladder

    n = LADDER_N
    lo, hi = _fill(kind, nv, n)
    c_lo, c_hi, c_idx, nreps, nvalid, at = (
        np.asarray(x) for x in ladder_order(jnp.asarray(lo),
                                            jnp.asarray(hi)))
    last = {}  # class -> its highest valid lane
    for lane in np.flatnonzero(lo | hi):
        last[(int(hi[lane]), int(lo[lane]))] = int(lane)
    want = sorted(last.items())
    nreps = int(nreps)
    assert nreps == len(want)
    assert c_idx[:nreps].tolist() == [lane for _, lane in want]
    assert list(zip(c_hi[:nreps].tolist(), c_lo[:nreps].tolist())) == [
        key for key, _ in want]
    rung = next(w for w in sort_ladder(n) if w >= nv)
    # what the commit's block counts: the valid lanes, and the rung
    assert int(nvalid) == nv and sort_ladder(n)[int(at)] == rung
    reps = set(last.values())
    assert not reps & set(c_idx[nreps:].tolist())
    assert (c_idx[rung:] == n).all()
    assert not c_lo[rung:].any() and not c_hi[rung:].any()
    assert ((c_idx[nreps:rung] >= 0) & (c_idx[nreps:rung] < n)).all()


@pytest.fixture(scope="module")
def ladder_enqueue():
    """ONE compile of the enqueue's order (`fpset.enqueue_order`, what
    `bfs.make_stage_pair` calls) at LADDER_N lanes, R = LADDER_R."""
    import jax

    from jaxtlc.engine.fpset import enqueue_order

    def order(is_new_c, c_idx, nreps):
        return enqueue_order(is_new_c, c_idx, nreps, LADDER_R)

    return jax.jit(order)


@pytest.mark.parametrize("nreps", [
    0, 1, 8192, 8193, 16384, 16385, 32768, 32769, LADDER_N],
    ids=["none", "one", "R", "R-plus-1", "rung1-full", "rung1-plus-1",
         "rung2-full", "rung2-plus-1", "all-distinct-burst"])
def test_enqueue_order_on_both_sides_of_each_rung(ladder_enqueue, nreps):
    """The enqueue's order with nreps on both sides of every rung: the
    new rows' lanes come out ascending in the first n_new entries,
    whatever the rows past nreps hold (the compaction's pad lane n
    among them)."""
    import jax.numpy as jnp

    from jaxtlc.engine.fpset import sort_ladder

    n = LADDER_N
    rng = np.random.default_rng(nreps + 3)
    c_idx = np.full(n, n, np.int32)  # past nreps: nobody's lane
    c_idx[:nreps] = rng.permutation(n)[:nreps]
    is_new_c = np.zeros(n, bool)
    is_new_c[:nreps] = rng.random(nreps) < 0.6
    e_idx, at = (np.asarray(x) for x in ladder_enqueue(
        jnp.asarray(is_new_c), jnp.asarray(c_idx), jnp.int32(nreps)))
    n_new = int(is_new_c.sum())
    ladder = sort_ladder(n, LADDER_R)
    assert ladder[int(at)] == next(w for w in ladder if w >= nreps)
    assert e_idx.shape == (n,)
    assert e_idx[:n_new].tolist() == sorted(c_idx[is_new_c].tolist())


def _rungs_taken(monkeypatch, log):
    """Wrap `fpset.sort_live` (test side) so a run leaves, per call
    site, the rung every step took."""
    import jax

    from jaxtlc.engine import fpset

    real = fpset.sort_live

    def spy(operands, num_keys, n_live, widths, fill, tail=False):
        if len(widths) > 1:
            jax.debug.callback(
                lambda v, site=(tail, widths): log.append(
                    (site, next(w for w in site[1] if w >= int(v)))),
                n_live)
        return real(operands, num_keys, n_live, widths, fill, tail)

    monkeypatch.setattr(fpset, "sort_live", spy)


def _commit_counts(carry) -> dict:
    """The carry's `commit_stat` by name, less what a patched ladder
    changes: the two rung histograms."""
    from jaxtlc.engine.bfs import commit_result_fields

    return {k: v for k, v in commit_result_fields(
        carry.commit_stat).items() if "rung" not in k}


def _live_queue(carry):
    """The rows of the queue a run can still read: what is left of the
    level being popped, then the level being built, in order."""
    q = np.asarray(carry.queue)
    par = int(carry.parity)
    return np.concatenate([
        q[par, int(carry.qhead):int(carry.level_n)],
        q[1 - par, :int(carry.next_n)]])


def test_engine_across_rungs_is_the_whole_width_engine(
        monkeypatch, ff_run):
    """An engine whose steps cross at least three rungs of each sort -
    the ladder shrunk by a test-side patch of `sort_ladder` (powers of
    two from 32 lanes for both sorts at chunk 128: the FF corner's
    steps hold 0 to a few hundred valid lanes) - against the same engine with the ladder patched
    to its last rung alone, every sort at the whole width: the same
    queue rows in the same order after each of the first 120 steps, and
    at the end the same table words, counters and outdegree histogram - which
    are also the module engine's, whose ladder is the shipped one."""
    import jax

    from jaxtlc.engine import fpset

    def fine(n, first=0):
        return tuple(w for w in (32, 64, 128, 256, 512) if w < n) + (n,)

    def build(ladder):
        """The live queue after each of the first 120 steps, and the
        final carry."""
        monkeypatch.setattr(fpset, "sort_ladder", ladder)
        init_fn, run_fn, step_fn = make_engine(FF, **KW, donate=False)
        carry, step, queues = init_fn(), step_fn.segment(1), []
        for _ in range(120):
            carry = step(carry)
            queues.append(_live_queue(carry))
        return queues, jax.block_until_ready(run_fn(carry))

    log = []
    _rungs_taken(monkeypatch, log)
    queues_l, end_l = build(fine)
    jax.effects_barrier()
    taken = {}
    for site, rung in log:
        taken.setdefault(site, set()).add(rung)
    assert len(taken) == 2  # the compaction (tail) and the enqueue
    for site, rungs in taken.items():
        assert len(rungs) >= 3, (site, rungs)
    queues_w, end_w = build(lambda n, first=0: (n,))

    # mid-level steps among them: a level being popped and one built
    assert sum(len(q) > 128 for q in queues_l) > 10
    for a, b in zip(queues_l, queues_w):
        assert a.shape == b.shape and (a == b).all()
    for b in (end_w, ff_run[0]):
        for leaf in end_l._fields:
            # rows nobody reads differ by rung; so do the rungs counted
            if leaf not in ("queue", "commit_stat"):
                assert _same_leaves(
                    getattr(end_l, leaf), getattr(b, leaf)), leaf
        # ... and nothing else the commit counts about itself
        assert _commit_counts(end_l) == _commit_counts(b)
    assert signature(result_from_carry(end_l, 0.0)) == signature(ff_run[1])


def test_mesh_insert_meets_the_pad_lane(monkeypatch):
    """`sharded.insert_compacted` with a segment wide enough for a
    ladder (40,000 rows) and mostly invalid lanes: the compaction runs
    on its narrowest rung, the rows past it carry the lane `width`, and
    the `idx_k < width` guard drops them - is_new, the claimants'
    lanes and verdicts up to `c_rows`, and the table are what the
    whole-width ordering returns."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine import fpset, sharded

    D, bucket, width = 2, 30_000, 40_000
    rng = np.random.default_rng(5)
    cnt = np.array([2_000, 1_500], np.int32)
    lo = rng.integers(1, 600, D * bucket, dtype=np.uint32)
    hi = rng.integers(0, 3, D * bucket, dtype=np.uint32)
    mask = np.zeros(D * bucket, bool)
    for d in range(D):  # each bucket's live rows are a prefix
        mask[d * bucket: d * bucket + cnt[d]] = True
    mask &= rng.random(D * bucket) < 0.9
    assert fpset.sort_ladder(width)[0] >= mask.sum()

    def run():
        out = jax.jit(lambda t, *a: sharded.insert_compacted(
            t, *a, width=width))(
            fpset.fpset_new(1 << 14).table, jnp.asarray(lo),
            jnp.asarray(hi), jnp.asarray(mask), jnp.asarray(cnt))
        return [np.asarray(x) for x in out]

    table, is_new, c_lane, c_new, c_rows, trips, stat = run()
    # the segment's block: the mask's lanes, on the narrowest rung
    did = fpset.commit_stat_fields(stat)
    assert did["valid"] == mask.sum() and did["probe_segments"] == 1
    assert did["claimed"] + did["stragglers"] >= is_new.sum() > 0
    assert did["compact_rung"][0] == 1
    monkeypatch.setattr(fpset, "sort_ladder", lambda n, first=0: (n,))
    table_w, is_new_w, c_lane_w, c_new_w, c_rows_w, trips_w, _ = run()
    assert int(trips) == int(trips_w) == 1 and is_new.sum() > 500
    assert (is_new == is_new_w).all() and (table == table_w).all()
    assert int(c_rows) == int(c_rows_w) > 0
    assert (c_lane[:c_rows] == c_lane_w[:c_rows]).all()
    assert (c_new == c_new_w).all()
    # the pad lane came through as the out-of-range received lane
    assert (c_lane[fpset.sort_ladder(width)[0]:width] == D * bucket).all()


# ---------------------------------------------------------------------------
# the segment program (ISSUE 26): loops only, writes in place
# ---------------------------------------------------------------------------


def test_segment_that_ends_with_the_check_exact(ff_run):
    """`step_fn.segment(n)` leaves its loop when the check is done: a
    run cut into 64-step segments, the last of which exhausts the
    queue part-way, ends on run_fn's very carry - counters, queue and
    fingerprint-table words - and a finished carry passes through."""
    from jaxtlc.engine.bfs import carry_done

    init_fn, _, step_fn = make_engine(FF, **KW, donate=False)
    segment = step_fn.segment(64)
    carry, calls = init_fn(), 0
    while not carry_done(carry):
        carry = segment(carry)
        calls += 1
    assert calls >= 2  # whole segments, then one that ends early
    assert _same_leaves(carry, ff_run[0])
    assert _same_leaves(segment(carry), ff_run[0])  # nothing left


def test_segment_program_crosses_no_conditional_with_its_buffers():
    """What the chip's HLO showed (PERF.md PR 26, Step 0), pinned on the
    lowered text: no conditional returns a table- or queue-shaped
    tensor (a whole-buffer copy a step where one did), and every write
    into the table is ONE scatter of whole bucket rows (an element
    scatter is flattened by XLA at two table relayouts a call)."""
    import re

    import jax

    init_fn, _, step_fn = make_engine(FF, **KW, donate=False)
    shapes = jax.eval_shape(init_fn)
    text = step_fn.segment(8).lower(shapes).as_text()
    dims = lambda x: "x".join(map(str, x.shape)) + "xui32"  # noqa: E731
    table, queue = dims(shapes.fps.table), dims(shapes.queue)
    cases = re.findall(r"^\s*\}\) : \(tensor<i32>\) -> (.*)$", text, re.M)
    assert cases  # the enqueue's order is a conditional
    for results in cases:
        assert table not in results and queue not in results, results
    writes = re.findall(
        rf"^\s*\}}\) : \(tensor<{table}>, (tensor<\S+>), (tensor<\S+>)\)"
        rf" -> tensor<{table}>$", text, re.M)
    # round 0 and the straggler walk: one scatter each, [n, 2B] rows
    assert len(writes) == 2, writes
    for idx, upd in writes:
        assert re.fullmatch(r"tensor<\d+x1xi32>", idx), idx
        assert re.fullmatch(r"tensor<\d+x16xui32>", upd), upd
    assert f"tensor<{table}>" in text and f"tensor<{queue}>" in text


# ---------------------------------------------------------------------------
# no route indexes an element at candidate width (ISSUE 38), traced
# ---------------------------------------------------------------------------

WIDE_KW = dict(chunk=2048, queue_capacity=1 << 13, fp_capacity=1 << 16)


def _route(name):
    """(traceable program, its argument, candidate width, probe bound)
    of one route to the seam at chunk 2,048, every option on auto."""
    import jax

    from jaxtlc.engine.backend import kubeapi_backend

    chunk = WIDE_KW["chunk"]
    if name in ("one-chip", "pipeline"):
        init_fn, _, step_fn = make_engine(
            FF, **WIDE_KW, donate=False, pipeline=name == "pipeline")
        return (step_fn.segment(8), jax.eval_shape(init_fn),
                chunk * kubeapi_backend(FF).n_lanes, 2 * chunk)
    if name == "spill-pair":
        from jaxtlc.engine.spill import SpillRuntime

        backend = kubeapi_backend(FF)
        rt = SpillRuntime(backend, chunk, WIDE_KW["queue_capacity"],
                          WIDE_KW["fp_capacity"])
        return (rt.audit_step_fn, jax.eval_shape(rt.init_fn),
                chunk * backend.n_lanes, 2 * chunk)
    if name == "struct":
        from jaxtlc.struct.cache import get_backend, get_engine
        from jaxtlc.struct.loader import load

        model = load(TWOPHASE_CFG)
        init_fn, _, step_fn = get_engine(
            model, **WIDE_KW, fp_index=0, seed=0, fp_highwater=0.85,
            check_deadlock=False)
        return (step_fn.segment(8), jax.eval_shape(init_fn),
                chunk * get_backend(model, False).n_lanes, 2 * chunk)
    assert name == "mesh-2dev"
    from jaxtlc.engine import sharded
    from jaxtlc.runtime import fp_mesh

    backend = kubeapi_backend(FF)
    init_fn, seg = sharded.make_sharded_engine(
        FF, fp_mesh(2), **WIDE_KW, segment=16)
    bucket = sharded.route_bucket_width(chunk, backend.n_lanes, 2, 2.0)
    width = sharded.commit_width(chunk, 2, bucket)
    assert width < 2 * bucket  # a segment is narrower than what arrives
    # the owner's insert orders one compacted segment, not the 2 x
    # bucket received lanes: its "candidate width" is the segment's
    return seg, init_fn(), width, width


@pytest.mark.parametrize(
    "route", ["one-chip", "pipeline", "spill-pair", "struct", "mesh-2dev"])
def test_dedup_indexes_no_element_at_candidate_width(route):
    """What ISSUE 38 took out of the step, pinned on every route's
    traced program at chunk 2,048 on auto: under `jaxtlc.dedup` the
    only instructions as wide as the candidates are the ordering's two
    sorts; everything that gathers or scatters there is the probe's,
    under `jaxtlc.fpset`, at probe width.  (The deleted hash slab held
    five element gathers and scatters at candidate width.)"""
    import jax
    from jaxpr_walk import scoped_eqns

    program, arg, ncand, probe = _route(route)
    traced = jax.make_jaxpr(program)(arg)
    sorts, indexed = [], []
    for stack, eqn in scoped_eqns(traced.jaxpr):
        if "jaxtlc.dedup" not in stack:
            continue
        name = eqn.primitive.name
        if name == "sort" and "jaxtlc.fpset" not in stack:
            sorts.append(eqn.invars[0].aval.shape[0])
        elif name.startswith(("gather", "scatter")):
            indexed.append((name, eqn.invars[1].aval.shape[0], stack))
    assert sorts == [ncand, ncand]
    assert indexed  # the scope reaches the program
    for name, rows, stack in indexed:
        assert "jaxtlc.fpset" in stack, (name, rows, stack)
        assert rows <= probe and (rows < ncand or route == "mesh-2dev"), (
            name, rows)


# the two-tier nest on stub bodies (the tier threshold is chunk / 2 =
# 8,192 states of one level at the only width that has a small tier; a
# real engine reaches it in test_real_engine_takes_both_tiers below)
_TIER_WIDTHS = (3, 40, 100, 17, 9, 64, 8, 7)  # level widths
_TIER_CHUNK, _TIER_SMALL = 16, 4


def _tier_reference():
    """The tier taken and the states popped, step by step, by a choice
    made before every step: 1000 * tier + pop."""
    lvl, qh, log = 0, 0, []
    while lvl < len(_TIER_WIDTHS):
        avail = _TIER_WIDTHS[lvl] - qh
        tier, width = ((1, _TIER_CHUNK) if avail >= _TIER_CHUNK // 2
                       else (2, _TIER_SMALL))
        pop = min(width, avail)
        qh += pop
        log.append(1000 * tier + pop)
        if qh >= _TIER_WIDTHS[lvl]:
            lvl, qh = lvl + 1, 0
    return log


@pytest.mark.parametrize("steps", [None, 0, 1, 6, 10, 11, 13, 20, 21, 25])
def test_two_tier_nest_is_the_step_by_step_choice(steps):
    """`bfs.run_steps` with a small body: two inner loops under one
    step counter take the bodies a per-step choice would, in its order,
    and `steps=n` stops after exactly n of them - between the tiers,
    inside a run of either, at the end and past it."""
    import jax
    import jax.numpy as jnp

    from jaxtlc.engine.bfs import run_steps

    ref = _tier_reference()
    assert ref[:2] == [2003, 1016] and ref[-2:] == [2004, 2003]
    widths = jnp.asarray(_TIER_WIDTHS + (0,), jnp.int32)

    def cond(c):
        return c[0] < len(_TIER_WIDTHS)

    def big(c):
        return widths[c[0]] - c[1] >= _TIER_CHUNK // 2

    def tier_body(tier, width):
        def body(c):
            lvl, qh, log, n = c
            pop = jnp.minimum(width, widths[lvl] - qh)
            done = qh + pop >= widths[lvl]
            return (jnp.where(done, lvl + 1, lvl),
                    jnp.where(done, 0, qh + pop),
                    log.at[n].set(1000 * tier + pop), n + 1)
        return body

    start = (jnp.int32(0), jnp.int32(0),
             jnp.zeros(len(ref) + 4, jnp.int32), jnp.int32(0))
    lvl, qh, log, n = jax.jit(lambda c: run_steps(
        cond, tier_body(1, _TIER_CHUNK), c, steps,
        tier_body(2, _TIER_SMALL), big))(start)
    want = ref if steps is None else ref[:steps]
    assert int(n) == len(want)
    assert np.asarray(log)[:int(n)].tolist() == want
    assert not np.asarray(log)[int(n):].any()
    # where the carry stands is where the reference stands after n steps
    popped = sum(w % 1000 for w in want)
    at = 0
    while at < len(_TIER_WIDTHS) and popped >= _TIER_WIDTHS[at]:
        popped -= _TIER_WIDTHS[at]
        at += 1
    assert (int(lvl), int(qh)) == (at, popped)


def test_real_engine_takes_both_tiers():
    """A real engine at chunk 2^14, the one width with a small tier
    (PERF.md 7-8e): the 1x2 FF rung passes 8,192 states a level at
    level 38, so by level 44 the engine has stepped in both tiers.
    Level by level it has taken the bodies the step-by-step rule takes
    (the big body while at least chunk / 2 of the level is left, the
    chunk / 16 body otherwise), and what it generated, found distinct,
    popped and queued is the chunk-1024 engine's, which has one tier.
    (The full signature is not compared: within a batch the highest
    lane claims a duplicate, so per-action attribution follows the
    batch boundaries.)"""
    from jaxtlc.config import make_scaled
    from jaxtlc.engine.bfs import obs_rows

    cfg, upto, chunk = make_scaled(1, 2, False, False), 44, 1 << 14

    def levels(ck_):
        init_fn, _run, step_fn = make_engine(
            cfg, chunk=ck_, queue_capacity=1 << 16, fp_capacity=1 << 20,
            obs_slots=64)
        carry, seg = init_fn(), step_fn.segment(8)
        while int(carry.level) <= upto:
            carry = seg(carry)
        assert int(carry.viol) == 0
        blocks.append((result_from_carry(carry, 0.0, commit=commit_geometry(
            kubeapi_backend(cfg).n_lanes, ck_)), int(carry.obs_bodies)))
        return [r for r in obs_rows(carry)[0] if r["level"] <= upto]

    blocks = []
    two_tier, one_tier = levels(chunk), levels(1024)
    # the commit's own counts (ISSUE 50) are the chunk-wide bodies',
    # read against one set of static widths: the small body adds nothing
    (both, stepped), (one, one_stepped) = blocks
    L = one.commit_width // 1024
    assert both.commit_width == chunk * L
    assert both.commit_probe_width == 2 * chunk
    assert both.commit_claim_block == 2 * chunk // 8
    assert 0 < both.commit_bodies < stepped
    assert one.commit_bodies == one_stepped
    assert sum(both.commit_compact_rung) == both.commit_bodies == sum(
        both.commit_enqueue_rung)
    n_init = one.generated - one.commit_valid
    assert 0 < both.commit_valid < both.generated - n_init
    assert 0 < both.commit_new < both.distinct - n_init
    # chunk 2^14 defers its invariants: a trip a probe segment
    assert both.commit_checker_trips == both.commit_probe_segments > 0
    assert one.commit_checker_trips == 0
    assert len(two_tier) == len(one_tier) == upto
    counted = ("level", "generated", "distinct", "queue", "expanded")
    for a, b in zip(two_tier, one_tier):
        assert [a[k] for k in counted] == [b[k] for k in counted]
    # level 1 is the Init states: what its row says was popped
    width, bodies, tiers = two_tier[0]["expanded"], 0, set()
    for row in two_tier:
        left = width
        while left > 0:
            big = left >= chunk // 2
            left -= min(chunk if big else chunk // 16, left)
            bodies += 1
            tiers.add(big)
        assert row["bodies"] == bodies, row
        width = row["queue"]
    assert tiers == {True, False}


# ---------------------------------------------------------------------------
# old snapshots: a meta that names the deleted mode resumes, exact
# ---------------------------------------------------------------------------


def _snapshots(p):
    """Every snapshot file of the family `p` (the supervisor writes
    generations beside the plain path)."""
    return ([p] if os.path.exists(p) else []) + [
        path for _, path in ck.list_generations(p)]


def _record_mode(path, value):
    """Rewrite a snapshot's meta as a run before ISSUE 44 wrote it:
    with a `sort_free` key."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
        meta = json.loads(str(z["__meta__"]))
    meta["sort_free"] = value
    with open(path, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta), **arrays)


@pytest.mark.parametrize("entry", ["ckpt", "supervised", "sharded-ckpt"])
def test_snapshot_that_records_the_slab_mode_resumes_exact(
        tmp_path, ff_run, entry):
    """A checkpoint whose meta says `sort_free: true` - what every run
    at chunk >= 2,048 wrote before ISSUE 38, and `false` every run
    since - resumes and ends on the pinned signature: the key is not
    compared (the slab was a per-commit temporary, the carry is the
    sorted ordering's bit for bit) and is not written again."""
    p = str(tmp_path / "ck.npz")
    if entry == "supervised":
        def run(resume, cut):
            return check_supervised(
                FF, **KW,
                opts=SupervisorOptions(
                    ckpt_path=p, ckpt_every=16, resume=resume,
                    faults=FaultPlan.parse("sigterm@2") if cut else None,
                )).result
    elif entry == "ckpt":
        def run(resume, cut):
            return ck.check_with_checkpoints(
                FF, **KW, ckpt_path=p, ckpt_every=16, resume=resume,
                max_segments=2 if cut else None)
    else:
        from jaxtlc.engine.sharded import check_sharded_with_checkpoints
        from jaxtlc.runtime import fp_mesh

        def run(resume, cut):
            return check_sharded_with_checkpoints(
                FF, fp_mesh(2), **KW, ckpt_path=p, ckpt_every=16,
                resume=resume, max_segments=2 if cut else None)

    cut = run(False, cut=True)
    assert cut.queue_left > 0 and _snapshots(p)
    # no engine writes the key any more
    assert "sort_free" not in ck.read_checkpoint_meta(_snapshots(p)[-1])
    for path in _snapshots(p):
        _record_mode(path, True)
    assert ck.read_checkpoint_meta(_snapshots(p)[-1])["sort_free"] is True
    r = run(True, cut=False)
    assert (r.generated, r.distinct, r.depth) == EXPECT_FF
    assert r.violation == 0 and r.queue_left == 0
    if entry != "sharded-ckpt":  # in-batch attribution follows the mesh
        assert signature(r) == signature(ff_run[1])
    assert "sort_free" not in ck.read_checkpoint_meta(_snapshots(p)[-1])


def test_pod_snapshot_that_records_the_mode_passes_the_meta_gate():
    """`dist.pod._validate_pod_meta` (host-only): a pod snapshot whose
    meta carries `sort_free`, either value, passes against what this
    tree writes; a key that shapes the carry still refuses."""
    from jaxtlc.dist.pod import _validate_pod_meta

    want = ck._meta(
        FF, queue_capacity=1 << 12, fp_capacity=1 << 14, devices=2,
        pipeline=False, obs_slots=0, deferred=False, symmetry=False,
        por=False, spill=False, num_hosts=1)
    for recorded in (True, False):
        for reshard in (False, True):
            _validate_pod_meta(dict(want, sort_free=recorded), want,
                               reshard=reshard)
    with pytest.raises(ValueError, match="checkpoint deferred mismatch"):
        _validate_pod_meta(dict(want, sort_free=True, deferred=True),
                           want, reshard=False)


@pytest.mark.parametrize("entry", ["ckpt", "sharded-ckpt"])
def test_resume_with_no_file_raises_before_any_build(
        tmp_path, monkeypatch, entry):
    """`resume=True` at a path that holds nothing (or no path at all)
    is FileNotFoundError before the engine is built."""
    from jaxtlc import runtime

    def no_build(*a, **k):
        raise AssertionError("built an engine for a resume with no file")

    monkeypatch.setattr(runtime, "aot_build", no_build)
    if entry == "ckpt":
        run = lambda path: ck.check_with_checkpoints(  # noqa: E731
            FF, **KW, ckpt_path=path, resume=True)
    else:
        from jaxtlc.engine.sharded import check_sharded_with_checkpoints
        from jaxtlc.runtime import fp_mesh

        run = lambda path: check_sharded_with_checkpoints(  # noqa: E731
            FF, fp_mesh(2), **KW, ckpt_path=path, resume=True)
    for path in (str(tmp_path / "nothing.npz"), None):
        with pytest.raises(FileNotFoundError, match="no checkpoint at"):
            run(path)


# ---------------------------------------------------------------------------
# the surface: no factory, flag or option names a dedup mode
# ---------------------------------------------------------------------------


def test_no_factory_takes_a_dedup_mode():
    """Every entry that built an engine with a `sort_free` parameter
    has none (a stale caller fails loudly, not silently sorted), and
    the modules of the seam define nothing of the slab."""
    from jaxtlc import api
    from jaxtlc.dist import pod
    from jaxtlc.engine import bfs, fpset, sharded, spill
    from jaxtlc.resil import supervisor
    from jaxtlc.serve import pool, sweep
    from jaxtlc.struct import cache
    from jaxtlc.struct import engine as struct_engine

    entries = [
        bfs.make_engine, bfs.make_backend_engine, bfs.make_stage_pair,
        sharded.make_sharded_engine, sharded.insert_compacted,
        sharded.check_sharded, sharded.check_sharded_with_checkpoints,
        sharded.ShardedSpillRuntime.__init__, spill.SpillRuntime.__init__,
        ck.check_with_checkpoints, supervisor.check_supervised,
        supervisor.check_sharded_supervised,
        supervisor.SingleDeviceAdapter.__init__,
        supervisor.ShardedAdapter.__init__, cache.engine_key,
        cache.get_engine, struct_engine.check_struct,
        struct_engine.check_struct_sharded, pod.run_pod,
        pool.EnginePool.get_single, pool.EnginePool.get_sweep,
        sweep.SweepEngine.__init__, fpset.fpset_insert,
    ]
    for fn in entries:
        assert "sort_free" not in inspect.signature(fn).parameters, fn
    with pytest.raises(TypeError, match="sort_free"):
        make_engine(FF, **KW, sort_free=False)
    assert not [n for n in dir(fpset) if "slab" in n.lower()]
    assert not hasattr(bfs, "resolve_sort_free")
    assert "sortfree" not in {
        f.name for f in api.CheckRequest.__dataclass_fields__.values()}


@pytest.mark.parametrize("flag", ["-sort-free", "-no-sort-free"])
def test_the_parser_refuses_the_flag(flag, capsys):
    """No compatibility alias: the command line that named the slab is
    a usage error (argparse's exit 2), before any spec is read."""
    from jaxtlc.cli import main

    with pytest.raises(SystemExit) as e:
        main(["check", TWOPHASE_CFG, flag])
    assert e.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err
