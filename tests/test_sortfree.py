"""Sort-free commit tests (ISSUE 12): the hash-slab dedup path is
BIT-FOR-BIT the sorted path - full signature plus fpset TABLE words -
at the one seam every engine shares, and the mode flag rides engine
memos / checkpoint meta so a resume can never silently cross modes.

Compile budget (tier-1 runs ~800 s of its 870 s hard timeout): ONE
module-scoped fixture owns the two FF engine compiles (sorted +
sort-free); the supervised-interrupt and sharded tests each pay their
own small FF compile because their jit closures differ by
construction, and everything else is fpset-level (tiny shapes) or
host-only.  Model_1 parity is slow-marked.
"""

import os

import numpy as np
import pytest

from jaxtlc.config import MODEL_1, ModelConfig
from jaxtlc.engine import checkpoint as ck
from jaxtlc.engine.bfs import (
    make_engine,
    resolve_sort_free,
    result_from_carry,
)
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised

FF = ModelConfig(False, False)
EXPECT_FF = (17020, 8203, 109)
EXPECT_M1 = (577736, 163408, 124)  # MC.out:1098,1101
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)


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
def ab_runs():
    """The module's ONLY full engine compiles: the FF corner run
    through the sorted and the sort-free engines, final carries kept
    for TABLE-word comparison."""
    import jax

    out = {}
    for sf in (False, True):
        init_fn, run_fn, _ = make_engine(
            FF, **KW, donate=False, sort_free=sf,
        )
        carry = jax.block_until_ready(run_fn(init_fn()))
        out[sf] = (carry, result_from_carry(carry, 0.0))
    return out


# ---------------------------------------------------------------------------
# the exactness contract
# ---------------------------------------------------------------------------


def test_ff_bit_for_bit(ab_runs):
    """-sort-free FF == sorted FF on the full signature AND the final
    fingerprint-table words (the ISSUE 12 non-negotiable)."""
    carry_s, r_s = ab_runs[False]
    carry_f, r_f = ab_runs[True]
    assert (r_s.generated, r_s.distinct, r_s.depth) == EXPECT_FF
    assert signature(r_s) == signature(r_f)
    assert (np.asarray(carry_s.fps.table)
            == np.asarray(carry_f.fps.table)).all()


def _lane_verdicts(is_new_c, c_idx, n):
    """Engine-facing view of an insert result: per-lane is_new (the
    slab layout interleaves rep rows with duplicate/padding rows, so
    positional comparison is meaningless - lane verdicts are the
    contract)."""
    out = np.zeros(n, bool)
    ci = np.asarray(c_idx)
    keep = ci < n
    out[ci[keep]] = np.asarray(is_new_c)[keep]
    return out


def test_slab_forced_collisions_residue_exact():
    """An 8-cell slab (slab_bits=3) collides nearly every class: the
    collision-spill lane (unresolved lanes riding into the ordering
    sort, last-of-group rep) must still reproduce the sorted path's
    per-lane verdicts and TABLE words exactly."""
    import jax.numpy as jnp

    from jaxtlc.engine.fpset import (
        fpset_insert_slab,
        fpset_insert_sorted,
        fpset_new,
    )

    rng = np.random.default_rng(11)
    n, R = 384, 384
    s_a, s_b = fpset_new(1 << 12), fpset_new(1 << 12)
    for step in range(3):
        base = rng.integers(0, 2 ** 32, size=(n // 2, 2),
                            dtype=np.uint32)
        pick = rng.integers(0, n // 2, size=n)  # in-batch duplicates
        lo = jnp.asarray(base[pick, 0])
        hi = jnp.asarray(base[pick, 1])
        mask = jnp.asarray(rng.random(n) < 0.8)
        s_a, na, ca, ra = fpset_insert_sorted(
            s_a, lo, hi, mask, probe_width=R, claim_width=R,
        )
        s_b, nb, cb, rb = fpset_insert_slab(
            s_b, lo, hi, mask, probe_width=R, claim_width=R,
            slab_bits=3,
        )
        assert int(ra) == int(rb)  # same distinct-rep count
        assert (_lane_verdicts(na, ca, n)
                == _lane_verdicts(nb, cb, n)).all()
        assert (np.asarray(s_a.table) == np.asarray(s_b.table)).all()


def test_slab_overflow_takes_sorted_fallback_exact():
    """Claimants wider than the probe width (all-distinct burst x tiny
    slab) must take the wholesale sorted fallback - bit-identical by
    definition, including the full [N] compacted order the fallback
    returns."""
    import jax.numpy as jnp

    from jaxtlc.engine.fpset import (
        fpset_insert_slab,
        fpset_insert_sorted,
        fpset_new,
    )

    rng = np.random.default_rng(5)
    n, R = 512, 64  # all-distinct batch: claimants >> R
    lo = jnp.asarray(rng.integers(0, 2 ** 32, size=n, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2 ** 32, size=n, dtype=np.uint32))
    mask = jnp.ones(n, bool)
    s_a, na, ca, ra = fpset_insert_sorted(
        fpset_new(1 << 11), lo, hi, mask, probe_width=R, claim_width=R,
    )
    s_b, nb, cb, rb = fpset_insert_slab(
        fpset_new(1 << 11), lo, hi, mask, probe_width=R, claim_width=R,
        slab_bits=3,
    )
    # the fallback returns the sorted path's FULL arrays: everything
    # matches positionally, not just the lane view
    assert int(ra) == int(rb)
    assert (np.asarray(na) == np.asarray(nb)).all()
    assert (np.asarray(ca) == np.asarray(cb)).all()
    assert (np.asarray(s_a.table) == np.asarray(s_b.table)).all()


# ---------------------------------------------------------------------------
# mode resolution + memo identity (host-only)
# ---------------------------------------------------------------------------


def test_auto_resolution_and_memo_key():
    """Auto is the sorted ordering at every chunk (ISSUE 38): at chunk
    64 and at the wide cell's 16,384 an auto caller shares the explicit
    `False` caller's entry in every key the resolved mode is material
    of, and `True` differs in every one."""
    for chunk in (64, 2048, 16384, 1 << 20):
        assert resolve_sort_free(None, chunk) is False
    assert resolve_sort_free(True, 64) is True
    assert resolve_sort_free(False, 1 << 20) is False
    # a resume: auto takes the checkpoint's recorded mode, a snapshot
    # from before the flag existed reads as sorted, an explicit flag is
    # itself (and the caller's mismatch where it contradicts)
    assert resolve_sort_free(None, 16384, {"sort_free": True}) is True
    assert resolve_sort_free(None, 16384, {}) is False
    assert resolve_sort_free(False, 16384, {"sort_free": True}) is False
    assert resolve_sort_free(True, 64, {"sort_free": False}) is True

    from jaxtlc.resil.supervisor import SingleDeviceAdapter
    from jaxtlc.runtime import engine_key as kept_key
    from jaxtlc.struct.cache import engine_key
    from jaxtlc.struct.loader import load

    model = load(os.path.join(
        os.path.dirname(__file__), os.pardir, "specs",
        "TwoPhase.toolbox", "Model_1", "MC.cfg",
    ))
    for chunk in (64, 16384):
        geo = dict(queue_capacity=1 << 10, fp_capacity=1 << 12)
        base = dict(chunk=chunk, fp_index=0, seed=0, fp_highwater=0.85,
                    **geo)
        # struct engine memo
        k_auto, k_off, k_on = (
            engine_key(model, **base, sort_free=sf)
            for sf in (None, False, True))
        assert k_auto == k_off and k_on != k_off
        # checkpoint meta, and the kept engines' key built from it
        metas = [SingleDeviceAdapter(FF, chunk=chunk, sort_free=sf
                                     ).meta(geo)
                 for sf in (None, False, True)]
        assert metas[0] == metas[1] and metas[0]["sort_free"] is False
        assert metas[2]["sort_free"] is True
        keys = [kept_key("single", FF, m, True, 8) for m in metas]
        assert keys[0] == keys[1] and keys[2] != keys[1]


# ---------------------------------------------------------------------------
# the segment program (ISSUE 26): loops only, writes in place.  The
# exactness cases pay one FF segment compile a mode (a segment is a
# program of its own); the structural pin only lowers.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sort_free", [False, True],
                         ids=["sorted", "sort-free"])
def test_segment_that_ends_with_the_check_exact(ab_runs, sort_free):
    """`step_fn.segment(n)` leaves its loop when the check is done: a
    run cut into 64-step segments, the last of which exhausts the
    queue part-way, ends on run_fn's very carry - counters, queue and
    fingerprint-table words - and a finished carry passes through."""
    import jax

    from jaxtlc.engine.bfs import carry_done

    init_fn, _, step_fn = make_engine(
        FF, **KW, donate=False, sort_free=sort_free,
    )
    segment = step_fn.segment(64)
    carry, calls = init_fn(), 0
    while not carry_done(carry):
        carry = segment(carry)
        calls += 1
    assert calls >= 2  # whole segments, then one that ends early
    ref = ab_runs[sort_free][0]
    assert _same_leaves(carry, ref)
    assert _same_leaves(segment(carry), ref)  # nothing left: unchanged


def test_segment_program_crosses_no_conditional_with_its_buffers():
    """What the chip's HLO showed (PERF.md PR 26, Step 0), pinned on the
    lowered text: no conditional returns a table- or queue-shaped
    tensor (a whole-buffer copy a step where one did), and every write
    into the table is ONE scatter of whole bucket rows (an element
    scatter is flattened by XLA at two table relayouts a call)."""
    import re

    import jax

    init_fn, _, step_fn = make_engine(
        FF, **KW, donate=False, sort_free=True,
    )
    shapes = jax.eval_shape(init_fn)
    text = step_fn.segment(8).lower(shapes).as_text()
    dims = lambda x: "x".join(map(str, x.shape)) + "xui32"  # noqa: E731
    table, queue = dims(shapes.fps.table), dims(shapes.queue)
    cases = re.findall(r"^\s*\}\) : \(tensor<i32>\) -> (.*)$", text, re.M)
    assert cases  # the slab fallback and the enqueue order are conditionals
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


def test_auto_dedup_indexes_no_element_at_candidate_width():
    """What ISSUE 38 took out of the step, pinned on the traced segment
    program at chunk 2,048 on auto: under `jaxtlc.dedup` nothing
    gathers or scatters with chunk * L indices (the slab's scatter-max,
    its three element gathers and the claimant scatter did); what
    indexes there is the probe's, under `jaxtlc.fpset`, at probe width.
    The explicit slab still holds its five."""
    import jax
    from jaxpr_walk import scoped_eqns

    from jaxtlc.engine.backend import kubeapi_backend

    chunk = 2048
    ncand = chunk * kubeapi_backend(FF).n_lanes
    wide = {}
    for sf in (None, True):
        init_fn, _, step_fn = make_engine(
            FF, chunk=chunk, queue_capacity=1 << 13, fp_capacity=1 << 16,
            donate=False, sort_free=sf,
        )
        traced = jax.make_jaxpr(step_fn.segment(8))(
            jax.eval_shape(init_fn))
        # (primitive, index rows, name stack) of what indexes in the dedup
        ops = [(eqn.primitive.name, eqn.invars[1].aval.shape[0], stack)
               for stack, eqn in scoped_eqns(traced.jaxpr)
               if "jaxtlc.dedup" in stack
               and eqn.primitive.name.startswith(("gather", "scatter"))]
        assert ops  # the scope reaches the program
        wide[sf] = sorted(op for op, rows, _ in ops if rows == ncand)
        if sf is None:
            for op, rows, stack in ops:
                assert "jaxtlc.fpset" in stack, (op, rows, stack)
                assert rows <= 2 * chunk, (op, rows)
    assert wide[None] == []
    assert wide[True] == ["gather"] * 3 + ["scatter", "scatter-max"]


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
        return [r for r in obs_rows(carry)[0] if r["level"] <= upto]

    two_tier, one_tier = levels(chunk), levels(1024)
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
# checkpoint mode continuity (supervised FF, ONE segment compile +
# the resume rebuild; wrong-mode rejection happens BEFORE any build)
# ---------------------------------------------------------------------------


def test_sigterm_recover_mode_continuity(tmp_path, ab_runs):
    p = str(tmp_path / "ck.npz")
    events = []
    sr = check_supervised(
        FF, sort_free=True,
        opts=SupervisorOptions(
            ckpt_path=p, ckpt_every=8,
            faults=FaultPlan.parse("sigterm@2"),
            on_event=lambda k, i: events.append(k),
        ),
        **KW,
    )
    assert sr.interrupted and "interrupted" in events
    gens = ck.list_generations(p)
    assert gens
    meta = ck.read_checkpoint_meta(gens[-1][1])
    assert meta["sort_free"] is True  # the mode travels in the meta

    # wrong-mode recover is LOUD - and rejected before any engine
    # build (the meta check runs first), so this costs no compile
    with pytest.raises(ValueError, match="sort_free mismatch"):
        check_supervised(
            FF, sort_free=False,
            opts=SupervisorOptions(ckpt_path=p, resume=True),
            **KW,
        )
    # (a caller on auto takes the checkpoint's mode since ISSUE 38:
    # test_recorded_mode_resumes_under_auto)

    # same mode resumes to the exact clean-run statistics
    sr2 = check_supervised(
        FF, sort_free=True,
        opts=SupervisorOptions(ckpt_path=p, ckpt_every=64, resume=True),
        **KW,
    )
    assert not sr2.interrupted
    assert signature(sr2.result) == signature(ab_runs[False][1])


def _live(c):
    """A one-chip carry with its queue cut to the rows in use: what is
    left of the level being popped, and what the next level holds so
    far, in order (the enqueue writes whole segments, so rows past
    `next_n` hold whatever the ordering left behind its new states)."""
    q = np.asarray(c.queue)
    par, qh = int(c.parity), int(c.qhead)
    return c._replace(queue=(q[par, qh:int(c.level_n)],
                             q[1 - par, :int(c.next_n)]))


@pytest.mark.parametrize("route", ["one-chip", "mesh-2dev"])
def test_auto_at_wide_chunk_is_the_slab_run_bit_for_bit(route):
    """Auto at chunk 2,048 (the sorted ordering since ISSUE 38, the
    slab before it) against the explicit slab at the same geometry, at
    every boundary of 16-step segments to the end of the check:
    counters, the queue's rows in their order, the fingerprint table's
    words - one-chip and through the owner-side insert of a 2-device
    mesh."""
    from jaxtlc.engine.bfs import carry_done

    kw = dict(chunk=2048, queue_capacity=1 << 13, fp_capacity=1 << 16)
    carry, seg = {}, {}
    for sf in (None, True):
        if route == "one-chip":
            init_fn, _, step_fn = make_engine(FF, **kw, donate=False,
                                              sort_free=sf)
            seg[sf] = step_fn.segment(16)
        else:
            from jaxtlc.engine.sharded import make_sharded_engine
            from jaxtlc.runtime import fp_mesh

            init_fn, seg[sf] = make_sharded_engine(
                FF, fp_mesh(2), **kw, segment=16, sort_free=sf)
        carry[sf] = init_fn()
    if route == "one-chip":
        done, view = carry_done, _live
    else:
        def done(c):
            return not bool(np.asarray(c.cont).any())

        def view(c):  # less the queue's dump row, written pop or not
            return c._replace(queue=c.queue[:, :kw["queue_capacity"]])
    boundaries = 0
    while not done(carry[None]):
        for sf in (None, True):
            carry[sf] = seg[sf](carry[sf])
        assert _same_leaves(view(carry[None]), view(carry[True]))
        boundaries += 1
    assert boundaries >= 4 and done(carry[True])
    assert int(np.asarray(carry[None].distinct).sum()) == EXPECT_FF[1]


@pytest.mark.parametrize("entry", ["ckpt", "supervised", "sharded-ckpt"])
def test_recorded_mode_resumes_under_auto(tmp_path, ab_runs, entry):
    """A checkpoint whose meta says `sort_free: true` - what every run
    at chunk >= 2,048 wrote before ISSUE 38 - resumes under a caller
    that leaves the flag on auto, in the recorded mode, and ends exact;
    an explicit `sort_free=False` against it stays the loud mismatch."""
    p = str(tmp_path / "ck.npz")
    if entry == "supervised":
        def run(resume, sort_free, cut):
            return check_supervised(
                FF, sort_free=sort_free, **KW,
                opts=SupervisorOptions(
                    ckpt_path=p, ckpt_every=16, resume=resume,
                    faults=FaultPlan.parse("sigterm@2") if cut else None,
                )).result
    elif entry == "ckpt":
        def run(resume, sort_free, cut):
            return ck.check_with_checkpoints(
                FF, **KW, ckpt_path=p, ckpt_every=16, resume=resume,
                max_segments=2 if cut else None, sort_free=sort_free)
    else:
        import jax
        from jax.sharding import Mesh

        from jaxtlc.engine.sharded import check_sharded_with_checkpoints

        mesh = Mesh(np.array(jax.devices()[:2]), ("fp",))

        def run(resume, sort_free, cut):
            return check_sharded_with_checkpoints(
                FF, mesh, **KW, ckpt_path=p, ckpt_every=16, resume=resume,
                max_segments=2 if cut else None, sort_free=sort_free)

    def recorded():  # the newest snapshot's mode (the supervisor
        # writes generations beside the plain path)
        path = p if os.path.exists(p) else ck.list_generations(p)[-1][1]
        return ck.read_checkpoint_meta(path)["sort_free"]

    run(False, True, cut=True)
    assert recorded() is True
    with pytest.raises(ValueError, match="sort_free mismatch"):
        run(True, False, cut=False)
    r = run(True, None, cut=False)
    assert (r.generated, r.distinct, r.depth) == EXPECT_FF
    assert r.violation == 0 and r.queue_left == 0
    if entry != "sharded-ckpt":  # in-batch attribution follows the mesh
        assert signature(r) == signature(ab_runs[False][1])
    # and the run went on in the checkpoint's mode, not the rule's
    assert recorded() is True


def test_twophase_struct_bit_for_bit():
    """The struct path inherits the mode through get_engine: TwoPhase
    sorted vs sort-free, full signature + TABLE words (two tiny struct
    compiles; the backend lane-compile is shared via the cache memo
    with the selfcheck suite)."""
    import jax

    from jaxtlc.struct.cache import get_engine
    from jaxtlc.struct.loader import load

    model = load(os.path.join(
        os.path.dirname(__file__), os.pardir, "specs",
        "TwoPhase.toolbox", "Model_1", "MC.cfg",
    ))
    geo = dict(chunk=64, queue_capacity=1 << 10, fp_capacity=1 << 12,
               fp_index=0, seed=0, fp_highwater=0.85)
    finals = {}
    for sf in (False, True):
        # TwoPhase has intended terminal states: deadlock checking off
        init_fn, run_fn, _ = get_engine(model, **geo,
                                        check_deadlock=False,
                                        sort_free=sf)
        finals[sf] = jax.block_until_ready(run_fn(init_fn()))
    r_s = result_from_carry(finals[False], 0.0)
    r_f = result_from_carry(finals[True], 0.0)
    assert r_s.violation == 0 and r_s.queue_left == 0
    assert signature(r_s) == signature(r_f)
    assert (np.asarray(finals[False].fps.table)
            == np.asarray(finals[True].fps.table)).all()


# ---------------------------------------------------------------------------
# sharded inheritance (one 2-dev compile)
# ---------------------------------------------------------------------------


def test_sharded_2dev_parity(ab_runs):
    import jax
    from jax.sharding import Mesh

    from jaxtlc.engine.sharded import check_sharded

    mesh = Mesh(np.array(jax.devices()[:2]), ("fp",))
    r = check_sharded(FF, mesh, sort_free=True, **KW)
    ref = ab_runs[False][1]
    assert (r.generated, r.distinct, r.depth) == EXPECT_FF
    assert r.violation == 0 and r.queue_left == 0
    # sharded-vs-single parity semantics per test_sharded.py: generated
    # attribution is exact; in-batch DISTINCT attribution (and the
    # outdegree max) legitimately differ when the frontier is split
    # across devices, so those compare as sums / (avg, min, p95).
    # Cross-MODE equality on the mesh engine (sorted sharded ==
    # sort-free sharded, leaf for leaf) follows transitively from
    # test_sharded pinning the sorted mesh engine to the same stats.
    assert r.action_generated == ref.action_generated
    assert sum(r.action_distinct.values()) == sum(
        ref.action_distinct.values()
    )
    a, lo_, _, p95 = r.outdegree
    sa, slo, _, sp95 = ref.outdegree
    assert (a, lo_, p95) == (sa, slo, sp95)


# ---------------------------------------------------------------------------
# Model_1 (slow): the chunk-2048 regime the auto rule targets
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_model1_parity_bit_for_bit():
    """Model_1 at chunk 2048 (auto -> sort-free): full signature +
    TABLE words vs the forced-sorted engine."""
    import jax

    kw = dict(chunk=2048, queue_capacity=1 << 15, fp_capacity=1 << 20)
    finals = {}
    for sf in (False, True):
        init_fn, run_fn, _ = make_engine(
            MODEL_1, **kw, donate=False, sort_free=sf,
        )
        finals[sf] = jax.block_until_ready(run_fn(init_fn()))
    r_s = result_from_carry(finals[False], 0.0)
    r_f = result_from_carry(finals[True], 0.0)
    assert (r_s.generated, r_s.distinct, r_s.depth) == EXPECT_M1
    assert signature(r_s) == signature(r_f)
    assert (np.asarray(finals[False].fps.table)
            == np.asarray(finals[True].fps.table)).all()
