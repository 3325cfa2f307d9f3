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
from jaxtlc.engine.bfs import make_engine, result_from_carry
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
    r = result_from_carry(carry, 0.0)
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
        s, is_new_c, c_idx, nreps = insert(
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
