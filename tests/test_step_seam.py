"""The seam between a struct-compiled step and the engine's expand stage
(ISSUE 47).  The stage's contract is the candidate array in candidate
order - candidate `c` is lane `c % L` of state `c // L` - and everything
it derives from it: the constraint judged on the rows at batch width,
and a lane's assert or overflow reporting its SOURCE state,
`batch[c // L]`, read off the block by index.  The reference form lives
here alone: `jax.vmap` of the per-row step, then `reshape`, the block
repeated `L` times, then the stage's arithmetic written out plainly.

One parametrised test, a case a bundled struct model at its tier-1
constants.
"""

import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.engine.backend import make_expand_stage
from jaxtlc.engine.bfs import (
    OK,
    VIOL_ASSERT,
    VIOL_DEADLOCK,
    VIOL_SLOT_OVERFLOW,
)
from jaxtlc.engine.fingerprint import (
    DEFAULT_FP_INDEX,
    DEFAULT_SEED,
    fp64_words_mxu,
)
from jaxtlc.struct.cache import get_backend, wants_symmetry
from jaxtlc.struct.loader import load

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 48


def cfg_of(box, model="Model_1"):
    return os.path.join(REPO, "specs", box + ".toolbox", model, "MC.cfg")


# name -> (cfg, constant overrides, check_deadlock, static lanes, slots)
MODELS = {
    "lamportmutex": (cfg_of("LamportMutex"), {"maxClock": 3}, True, 27, 27),
    "paxos-compacted": (cfg_of("Paxos"),
                        {"Ballot": frozenset({0, 1})}, False, 80, 32),
    "paxos-symmetry": (cfg_of("Paxos", "Model_sym"),
                       {"Ballot": frozenset({0, 1})}, False, 80, 32),
    "ewd998-constraint": (cfg_of("EWD998"), {"N": 2}, True, None, None),
    "ewd840": (cfg_of("EWD840"), {"N": 4}, True, None, None),
    "raftreplication": (cfg_of("RaftReplication"), None, True, 21, 21),
}


def backend_of(name):
    cfg, consts, deadlock, _, _ = MODELS[name]
    m = load(cfg, const_overrides=consts) if consts else load(cfg)
    return get_backend(m, deadlock, symmetry=wants_symmetry(m))


def reachable(backend, want=600):
    """Some hundreds of states reachable through kept states, in a
    fixed order, by the per-row view of the step."""
    step = jax.jit(jax.vmap(backend.step))
    con = backend.constraint
    keep = jax.jit(con) if con is not None else None
    seen = np.unique(np.asarray(backend.initial_vectors(), np.int32),
                     axis=0)
    front = seen
    while len(seen) < want and len(front):
        succs, valid, *_ = step(jnp.asarray(front[:256]))
        s = np.asarray(succs).reshape(-1, succs.shape[-1])[
            np.asarray(valid).reshape(-1)]
        if keep is not None and len(s):
            s = s[np.asarray(keep(jnp.asarray(s)))]
        mark = {r.tobytes() for r in seen}
        seen = np.unique(np.concatenate([seen, s]), axis=0)
        front = np.asarray([r for r in seen if r.tobytes() not in mark],
                           np.int32).reshape(-1, seen.shape[1])
    return seen


def flagged(backend, lane, which):
    """`backend` with `afail` or `ovf` raised on every firing instance
    of static position `lane` of a state's fan, so the stage's
    violation reduce has something to find."""
    def step(vec):
        succs, valid, action, afail, ovf = backend.step(vec)
        hit = valid & (jnp.arange(valid.shape[-1]) == lane)
        if which == "afail":
            return succs, valid, action, afail | hit, ovf
        return succs, valid, action, afail, ovf | hit

    return backend._replace(step=step)


def reference(backend, batch, mask, check_deadlock):
    """What the stage must give, from the per-row step: `jax.vmap`,
    `reshape`, and the stage's rules in numpy."""
    cdc = backend.cdc
    L, F = backend.n_lanes, cdc.n_fields
    succs, valid, action, afail, ovf = jax.vmap(backend.step)(batch)
    assert succs.shape == (len(batch), L, F)
    flat = succs.reshape(-1, F)
    valid = np.asarray(valid) & np.asarray(mask)[:, None]
    afail = np.asarray(afail) & valid
    ovf = np.asarray(ovf) & valid
    dead = (np.asarray(mask) & ~valid.any(axis=1) if check_deadlock
            else np.zeros(len(batch), bool))
    counted = valid
    con_stat = None
    if backend.constraint is not None:
        keep = np.asarray(backend.constraint(flat)).reshape(valid.shape)
        con_stat = [int(valid.sum()), int((valid & ~keep).sum())]
        valid, ovf = valid & keep, ovf & keep
    plan = backend.reduce.plan if backend.reduce is not None else None
    if plan is not None:
        flat = plan.canon(flat)
    inv = np.asarray(jax.vmap(backend.inv_check)(flat))
    fvalid = valid.reshape(-1)
    faction = np.asarray(action).reshape(-1)
    packed = cdc.pack(flat)
    lo, hi = fp64_words_mxu(packed, cdc.nbits, DEFAULT_FP_INDEX,
                            DEFAULT_SEED)
    gen = np.bincount(faction[counted.reshape(-1)],
                      minlength=len(backend.labels))
    flat = np.asarray(flat)
    src = np.repeat(np.asarray(batch), L, axis=0)
    viol = (OK, np.zeros(F, np.int32), -1)
    for code, vmask, states, acts in (
        *((c, fvalid & ((inv & (1 << k)) == 0), flat, faction)
          for k, c in enumerate(backend.inv_codes)),
        (VIOL_ASSERT, afail.reshape(-1), src, faction),
        (VIOL_DEADLOCK, dead, np.asarray(batch),
         np.full(len(batch), -1)),
        (VIOL_SLOT_OVERFLOW, ovf.reshape(-1), src, faction),
    ):
        if vmask.any() and viol[0] == OK:
            at = int(np.argmax(vmask))
            viol = (code, states[at], int(acts[at]))
    return dict(flat=flat, valid=fvalid, action=faction,
                packed=np.asarray(packed), lo=np.asarray(lo),
                hi=np.asarray(hi), gen=gen, con_stat=con_stat,
                viol=viol[0], viol_state=viol[1], viol_action=viol[2])


@pytest.mark.parametrize("name", list(MODELS))
def test_the_stage_takes_the_steps_rows_in_candidate_order(name):
    _, _, deadlock, static, slots = MODELS[name]
    backend = backend_of(name)
    L = backend.n_lanes
    if static is not None:
        assert (backend.cdc.static_lanes, L) == (static, slots)
    pool = reachable(backend)
    assert len(pool) >= CHUNK
    fired = np.asarray(jax.jit(jax.vmap(backend.step))(
        jnp.asarray(pool))[1])
    rng = np.random.default_rng(47)
    # the state that fires the most lanes, among others
    pick = np.concatenate([
        [int(np.argmax(fired.sum(axis=1)))],
        rng.choice(len(pool), CHUNK - 1, replace=False)])
    busiest = pool[pick]
    # a block popped short of the chunk: masked rows
    short = pool[rng.choice(len(pool), CHUNK, replace=False)]
    lane = int(np.argmax(fired[pick].sum(axis=0)))
    stages = {}

    def stage(b, deferred):
        # one compile a (backend, mode): the blocks share it
        if (id(b), deferred) not in stages:
            stages[id(b), deferred] = jax.jit(make_expand_stage(
                b, CHUNK, deadlock, DEFAULT_FP_INDEX, DEFAULT_SEED,
                deferred=deferred))
        return stages[id(b), deferred]

    asserting = flagged(backend, lane, "afail")
    cases = [
        (backend, busiest, np.ones(CHUNK, bool), (False, True)),
        (backend, short, rng.random(CHUNK) < 0.6, (False, True)),
        (asserting, busiest, np.ones(CHUNK, bool), (False,)),
        (flagged(backend, lane, "ovf"), busiest,
         rng.random(CHUNK) < 0.8, (True,)),
    ]
    for b, batch, mask, modes in cases:
        batch, mask = jnp.asarray(batch), jnp.asarray(mask)
        want = reference(b, batch, mask, deadlock)
        for deferred in modes:
            got = stage(b, deferred)(batch, mask)
            assert got.valid.shape == (CHUNK * L,)
            for k in ("valid", "action", "packed", "lo", "hi", "gen"):
                # a lane that did not fire promises nothing but `valid`
                g, w = np.asarray(getattr(got, k)), want[k]
                if k in ("packed", "lo", "hi"):
                    g, w = g[want["valid"]], w[want["valid"]]
                assert np.array_equal(g, w), (name, k, deferred)
            if deferred:
                assert np.array_equal(
                    np.asarray(got.flat)[want["valid"]],
                    want["flat"][want["valid"]])
            if want["con_stat"] is not None:
                assert [int(v) for v in got.con_stat] == want["con_stat"]
            if not deferred or want["viol"] not in b.inv_codes:
                assert int(got.viol) == want["viol"], (name, deferred)
                assert np.array_equal(np.asarray(got.viol_state),
                                      want["viol_state"])
                assert int(got.viol_action) == want["viol_action"]
    # the flags were found, and report the SOURCE state of the first
    # flagged candidate
    assert reference(asserting, jnp.asarray(busiest),
                     jnp.ones(CHUNK, bool), deadlock)["viol"] in (
        VIOL_ASSERT, *backend.inv_codes)


def test_a_run_through_the_seam_keeps_its_counts_and_its_deadlock(tmp_path):
    """The oracle's counts and `lane_live_pct`'s inputs through the
    stage; a state with no successor is still a deadlock."""
    from test_lamportmutex import C3, engine, five

    r = engine()
    assert five(r) == C3
    assert (r.step_lanes, r.step_slots) == (27, 27)
    # lane_live_pct's inputs: lanes that fired over lanes evaluated
    assert r.lane_fires == r.generated - 1
    assert r.states_expanded == r.distinct

    d = tmp_path / "dead"
    d.mkdir()
    (d / "Stop.tla").write_text(
        "---- MODULE Stop ----\nEXTENDS Naturals\nVARIABLES x\n"
        "Init == x = 0\nNext == x < 3 /\\ x' = x + 1\n"
        "====\n")
    (d / "Stop.cfg").write_text("INIT Init\nNEXT Next\n")
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=str(d / "Stop.cfg"), frontend="struct", workers="cpu",
        noTool=True, out=out, err=out, chunk=64, qcap=1024, fpcap=4096))
    assert o.result.violation == VIOL_DEADLOCK, out.getvalue()[-400:]


def test_a_compacted_step_that_overflows_still_halts(monkeypatch):
    """Slot 0 of a state carries the compaction's overflow: a state
    with more live lanes than slots raises VIOL_SLOT_OVERFLOW and is
    the state reported, its row read off the block by `at // L`."""
    import jaxtlc.struct.backend as sb

    cfg, consts, _, _, _ = MODELS["paxos-compacted"]
    full = backend_of("paxos-compacted")
    monkeypatch.setattr(sb, "compact_width", lambda n: 1)
    tight = sb.struct_backend(load(cfg, const_overrides=consts),
                              check_deadlock=False)
    width = tight.n_lanes
    assert width < 8 and tight.lane_action is None
    pool = reachable(full)
    fires = np.asarray(jax.jit(jax.vmap(full.step))(
        jnp.asarray(pool))[1]).sum(axis=1)
    assert fires.max() > width
    fits = pool[fires <= width]
    batch = np.concatenate([
        np.tile(fits, (-(-CHUNK // len(fits)), 1))[:CHUNK - 1],
        pool[[int(np.argmax(fires))]]])
    got = jax.jit(make_expand_stage(
        tight, CHUNK, False, DEFAULT_FP_INDEX, DEFAULT_SEED,
        deferred=True))(jnp.asarray(batch), jnp.ones(CHUNK, bool))
    assert int(got.viol) == VIOL_SLOT_OVERFLOW
    assert np.array_equal(np.asarray(got.viol_state), batch[-1])
    # the slots that fit are kept, in lane order
    assert int(got.valid.reshape(CHUNK, width)[-1].sum()) == width
    ovf = np.asarray(tight.step(jnp.asarray(batch[-1]))[4])
    assert ovf[0] and not ovf[1:].any()


def test_the_mesh_engine_reports_the_source_state_of_a_lanes_assert():
    """The mesh engine's own violation reduce reads the source row the
    same way (`batch[at // L]`): a lane that asserts halts the run on a
    state that fires that lane, on two devices."""
    from jax.sharding import Mesh

    from jaxtlc.config import ModelConfig
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.sharded import check_sharded

    cfg = ModelConfig(False, False)
    backend = kubeapi_backend(cfg)
    step = jax.jit(jax.vmap(backend.step))
    pool = reachable(backend, want=300)
    # a lane that fires on some reachable state and on no initial one:
    # the first flagged candidate is then not candidate 0 of its block
    fires = np.asarray(step(jnp.asarray(pool))[1]).any(axis=0) & ~np.asarray(
        step(jnp.asarray(backend.initial_vectors()))[1]).any(axis=0)
    lane = int(np.argmax(fires))
    assert fires[lane]
    r = check_sharded(
        cfg, Mesh(np.array(jax.devices()[:2]), ("fp",)), chunk=128,
        queue_capacity=1 << 12, fp_capacity=1 << 14,
        backend=flagged(backend, lane, "afail"))
    assert r.violation == VIOL_ASSERT
    assert bool(backend.step(jnp.asarray(r.violation_state))[1][lane])
