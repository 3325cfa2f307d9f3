"""Distinct-first deferred invariant/cert evaluation tests (ISSUE 15):
moving invariant + certificate evaluation from the chunk*L expand sweep
to the commit stage's fresh-insert claimants is BIT-FOR-BIT on verdict,
full signature, fpset TABLE words and rendered exit-12 traces; only the
violation-LANE attribution changes, to the pinned highest-lane rule.
The tri-state flag rides engine memos / checkpoint meta so a resume can
never silently cross modes, and the sim tier ignores it entirely.

Compile budget (tier-1 runs ~800 s of its 870 s hard timeout): ONE
module-scoped fixture owns the two FF engine compiles (immediate and
deferred).  The attribution / exit-12 / cert-lie tests run tiny
synthetic or struct engines (seconds); the supervised-interrupt and
sharded tests each pay their own small FF compile like
tests/test_commit_dedup.py does; the dense claim-walk parity test is
fpset-level (no engine)."""

import dataclasses
import io
import os

import numpy as np
import pytest

from jaxtlc.config import ModelConfig
from jaxtlc.engine import checkpoint as ck
from jaxtlc.engine.bfs import (
    DEFERRED_AUTO_CHUNK,
    make_engine,
    resolve_deferred,
    result_from_carry,
)
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised

FF = ModelConfig(False, False)
EXPECT_FF = (17020, 8203, 109)
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)

SPECS = os.path.join(os.path.dirname(__file__), os.pardir, "specs")


def signature(r):
    """Full exactness signature of a CheckResult."""
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())),
            r.outdegree)


@pytest.fixture(scope="module")
def ab_runs():
    """The module's ONLY full engine compiles: the FF corner through
    the immediate engine and the deferred engine, final carries kept
    for TABLE-word comparison."""
    import jax

    out = {}
    for df in (False, True):
        init_fn, run_fn, _ = make_engine(
            FF, **KW, donate=False, deferred=df,
        )
        carry = jax.block_until_ready(run_fn(init_fn()))
        out[df] = (carry, result_from_carry(carry, 0.0))
    return out


# ---------------------------------------------------------------------------
# the exactness contract
# ---------------------------------------------------------------------------


def test_ff_bit_for_bit(ab_runs):
    """-deferred-inv FF == immediate FF on the full signature AND the
    final fingerprint-table words (the ISSUE 15 non-negotiable)."""
    carry_i, r_i = ab_runs[False]
    carry_d, r_d = ab_runs[True]
    assert (r_i.generated, r_i.distinct, r_i.depth) == EXPECT_FF
    assert signature(r_i) == signature(r_d)
    assert (np.asarray(carry_i.fps.table)
            == np.asarray(carry_d.fps.table)).all()


# ---------------------------------------------------------------------------
# mode resolution + memo identity (host-only)
# ---------------------------------------------------------------------------


def test_auto_resolution_and_memo_key():
    assert resolve_deferred(None, DEFERRED_AUTO_CHUNK) is True
    assert resolve_deferred(None, DEFERRED_AUTO_CHUNK // 2) is False
    assert resolve_deferred(True, 64) is True
    assert resolve_deferred(False, 1 << 20) is False

    from jaxtlc.struct.cache import engine_key
    from jaxtlc.struct.loader import load

    model = load(os.path.join(SPECS, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    base = dict(chunk=64, queue_capacity=1 << 10, fp_capacity=1 << 12,
                fp_index=0, seed=0, fp_highwater=0.85)
    k_auto = engine_key(model, **base, deferred=None)
    k_off = engine_key(model, **base, deferred=False)
    k_on = engine_key(model, **base, deferred=True)
    assert k_auto == k_off  # chunk 64 < auto threshold
    assert k_on != k_off


# ---------------------------------------------------------------------------
# the pinned violation-lane attribution rule
# ---------------------------------------------------------------------------


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


def _tiny_backend(viol_at: int):
    """3-lane counter spec: x -> {3x+1, 3x+2, 3x+3} while 3x+3 <= 30;
    invariant bit 0 = (x < viol_at).  From Init x=0 the first block
    generates 1, 2, 3 - all distinct fresh inserts - so a viol_at of 2
    makes candidates lane 1 (x=2) and lane 2 (x=3) violate at once:
    the immediate path reports the FIRST (x=2), the deferred path must
    report the pinned HIGHEST-lane fresh rep (x=3)."""
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
    )


def test_attribution_rule_pinned():
    """Both modes report the same VERDICT; the reported lane follows
    first-candidate (immediate) vs the pinned highest-lane fresh rep
    (deferred) - deterministic, layout-independent (defined on original
    candidate lanes, the PR 12 rep convention)."""
    import jax

    from jaxtlc.engine.bfs import VIOL_TYPEOK, make_backend_engine

    geo = dict(chunk=8, queue_capacity=1 << 8, fp_capacity=1 << 10)
    finals = {}
    for df in (False, True):
        init_fn, run_fn, _ = make_backend_engine(
            _tiny_backend(2), donate=False, deferred=df, **geo,
        )
        finals[df] = jax.block_until_ready(run_fn(init_fn()))
    for df in (False, True):
        assert int(finals[df].viol) == VIOL_TYPEOK
    # immediate: first violating candidate (lane 1 -> state 2)
    assert int(finals[False].viol_state[0]) == 2
    assert int(finals[False].viol_action) == 1
    # deferred: highest-lane violating fresh rep (lane 2 -> state 3)
    assert int(finals[True].viol_state[0]) == 3
    assert int(finals[True].viol_action) == 2


# ---------------------------------------------------------------------------
# exit-12 trace identity through the full front door
# ---------------------------------------------------------------------------


_DEFV = """---- MODULE DefV ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Up == /\\ x < 5
      /\\ x' = x + 1
Next == Up
Small == x < 3
====
"""
_DEFV_CFG = "INVARIANT\nSmall\n"


def test_exit12_trace_identical(tmp_path):
    """A seeded invariant violation renders the IDENTICAL exit-12
    transcript in both modes: the counterexample trace is reconstructed
    by the host re-walk from the spec, and the deferred attribution
    rule changes only which device lane carried the report - never the
    rendered trace or the verdict."""
    from jaxtlc.api import CheckRequest, run_check

    (tmp_path / "DefV.tla").write_text(_DEFV)
    cfg = tmp_path / "DefV.cfg"
    cfg.write_text(_DEFV_CFG)

    transcripts = {}
    for df in (False, True):
        out = io.StringIO()
        outcome = run_check(CheckRequest(
            config=str(cfg), workers="cpu", frontend="struct",
            noTool=True, autogrow=False, obs=False,
            chunk=64, qcap=1 << 10, fpcap=1 << 12,
            deferredinv=df, out=out, err=out,
        ))
        assert outcome.exit_code == 12, out.getvalue()
        transcripts[df] = out.getvalue()
    assert "Small is violated" in transcripts[False]

    def normalize(t):
        # wall-clock noise only: timestamps and elapsed-seconds vary
        # between the two runs, nothing else may
        import re

        t = re.sub(r"\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}", "<ts>", t)
        return re.sub(r"\d+m?s", "<n>s", t)

    assert normalize(transcripts[False]) == normalize(transcripts[True])


# ---------------------------------------------------------------------------
# the cert lie still trips from the deferred site
# ---------------------------------------------------------------------------


_SLOTC = """---- MODULE SlotC ----
EXTENDS Naturals, FiniteSets
CONSTANTS RM
VARIABLES msgs, n
Init == /\\ msgs = {} /\\ n = 0
Send == /\\ n < 2
        /\\ \\E r \\in RM : msgs' = msgs \\cup {[kind |-> "a", from |-> r]}
        /\\ n' = n + 1
Drop == /\\ \\E m \\in msgs : msgs' = msgs \\ {m}
        /\\ UNCHANGED n
Next == Send \\/ Drop
TypeOK == /\\ \\A m \\in msgs : m.from \\in RM /\\ n \\in 0..5
====
"""
_SLOTC_CFG = ("CONSTANT RM = {r1, r2, r3, r4, r5, r6, r7, r8, r9, "
              "ra, rb, rc, rd}\nINVARIANT\nTypeOK\n")


def test_cert_lie_trips_from_deferred_site(tmp_path):
    """The cardinality lie (the one narrowing with NO codec trap -
    analysis.absint) must still trip the sticky COL_CERT flag when the
    certificate runs at the DEFERRED site: the first escaping states
    are fresh-insert claimants, so the commit-side checker sees their
    raw pre-pack fields and latches the flag (the same spec/lie as
    tests/test_absint's immediate-mode pin)."""
    from jaxtlc.analysis.absint import analyze_bounds
    from jaxtlc.struct.engine import check_struct
    from jaxtlc.struct.loader import load

    (tmp_path / "SlotC.tla").write_text(_SLOTC)
    cfg = tmp_path / "SlotC.cfg"
    cfg.write_text(_SLOTC_CFG)
    model = load(str(cfg))
    honest = analyze_bounds(model)
    assert honest.certified
    lie = dataclasses.replace(
        honest, card_bounds={**honest.card_bounds, "msgs": 1}
    )
    r = check_struct(model, check_deadlock=False, obs_slots=8,
                     bounds=lie, deferred=True,
                     chunk=64, queue_capacity=1024, fp_capacity=8192)
    assert r.cert_violated is True


# ---------------------------------------------------------------------------
# checkpoint mode continuity (supervised FF, ONE segment compile +
# the resume rebuild; wrong-mode rejection happens BEFORE any build)
# ---------------------------------------------------------------------------


def test_sigterm_recover_mode_continuity(tmp_path, ab_runs):
    p = str(tmp_path / "ck.npz")
    events = []
    sr = check_supervised(
        FF, deferred=True,
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
    assert meta["deferred"] is True  # the mode travels in the meta

    # wrong-mode recover is LOUD - and rejected before any engine
    # build (the meta check runs first), so this costs no compile
    with pytest.raises(ValueError, match="deferred mismatch"):
        check_supervised(
            FF, deferred=False,
            opts=SupervisorOptions(ckpt_path=p, resume=True),
            **KW,
        )
    # auto at chunk 128 resolves to immediate - also a loud mismatch,
    # not a silent mode flip
    with pytest.raises(ValueError, match="deferred mismatch"):
        check_supervised(
            FF,
            opts=SupervisorOptions(ckpt_path=p, resume=True),
            **KW,
        )

    # same mode resumes to the exact clean-run statistics
    sr2 = check_supervised(
        FF, deferred=True,
        opts=SupervisorOptions(ckpt_path=p, ckpt_every=64, resume=True),
        **KW,
    )
    assert not sr2.interrupted
    assert signature(sr2.result) == signature(ab_runs[False][1])


# ---------------------------------------------------------------------------
# sharded inheritance: owner-side post-routing (one 2-dev compile)
# ---------------------------------------------------------------------------


def test_sharded_2dev_parity(ab_runs):
    import jax
    from jax.sharding import Mesh

    from jaxtlc.engine.sharded import check_sharded

    mesh = Mesh(np.array(jax.devices()[:2]), ("fp",))
    r = check_sharded(FF, mesh, deferred=True, **KW)
    ref = ab_runs[False][1]
    assert (r.generated, r.distinct, r.depth) == EXPECT_FF
    assert r.violation == 0 and r.queue_left == 0
    # sharded-vs-single parity semantics per test_sharded.py: generated
    # attribution is exact; in-batch DISTINCT attribution legitimately
    # differs when the frontier splits across devices
    assert r.action_generated == ref.action_generated
    assert sum(r.action_distinct.values()) == sum(
        ref.action_distinct.values()
    )
    a, lo_, _, p95 = r.outdegree
    sa, slo, _, sp95 = ref.outdegree
    assert (a, lo_, p95) == (sa, slo, sp95)


# ---------------------------------------------------------------------------
# dense claim walk (the BLEST membership-probe half, fpset-level)
# ---------------------------------------------------------------------------


def _hot_bucket_batch(seed: int, n: int):
    """Random fingerprints whose STORED (mixed) form falls into 32 hot
    buckets of a 256-bucket table (the table hashes by the top bits of
    the mixed `hi`, so the batch is drawn in mixed space and unmixed):
    round-0 claims overflow into the straggler walk, and a hot bucket's
    ~30 words fill the buckets behind it."""
    import jax.numpy as jnp

    from jaxtlc.engine.fpset import _unmix

    rng = np.random.default_rng(seed)
    mlo = rng.integers(1, 2 ** 32, size=n, dtype=np.uint32)
    mhi = (rng.integers(0, 2 ** 5, size=n, dtype=np.uint32) << 27) | (
        rng.integers(0, 2 ** 24, size=n, dtype=np.uint32))
    lo, hi = _unmix(jnp.asarray(mlo), jnp.asarray(mhi))
    mask = rng.random(n) < 0.9
    return np.array(lo), np.array(hi), mask


def test_dense_walk_matches_host_replay():
    """The straggler walk's own pin, against a reference that shares no
    code with it (a Python set and fpset.host_insert's one-at-a-time
    linear walk): under hot-bucket pressure with a narrow round-0 claim
    width the verdicts name the highest lane of every fresh
    fingerprint, the table holds each of them exactly once (the host
    replay's words, wherever in a bucket they sit), and every stored
    word is where a lookup's walk finds it."""
    import jax.numpy as jnp

    from jaxtlc.engine import fpset

    n, cap = 384, 1 << 11
    s = fpset.fpset_new(cap)
    ref = np.zeros_like(np.asarray(s.table))
    seen = set()
    for step in range(3):
        lo, hi, mask = _hot_bucket_batch(100 + step, n)
        lo[::7], hi[::7] = lo[1::7][:len(lo[::7])], hi[1::7][:len(hi[::7])]
        s, is_new_c, c_idx, _, stat = fpset.fpset_insert_sorted(
            s, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(mask),
            probe_width=n, claim_width=64,
        )
        fresh = {}
        for lane in np.flatnonzero(mask):
            key = (int(lo[lane]), int(hi[lane]))
            if key not in seen:
                fresh[key] = int(lane)  # the highest lane wins
        new_lanes = np.asarray(c_idx)[np.asarray(is_new_c)]
        assert sorted(new_lanes.tolist()) == sorted(fresh.values())
        # the call's counts say the walk was driven: at most 64 claims
        # written in round 0, every other fresh class a straggler
        did = fpset.commit_stat_fields(stat)
        assert did["claimed"] <= 64
        assert did["stragglers"] >= len(fresh) - did["claimed"] > 0
        assert did["walk_rounds"] >= 2  # a compaction and a bucket step
        for key in fresh:
            assert fpset.host_insert(ref, *key)
        seen |= set(fresh)

    def words(t):
        pairs = t.reshape(-1, 2)
        return sorted(map(tuple, pairs[pairs.any(axis=1)].tolist()))

    table = np.asarray(s.table).copy()
    assert len(words(table)) == len(seen)  # the distinct count
    assert words(table) == words(ref)
    # the lookup invariant: a host walk finds every stored word
    assert not any(fpset.host_insert(table, *key) for key in seen)
    # and the pressure was real: some words walked past a full bucket
    pairs = table.reshape(-1, 2)
    home = np.array([fpset.bucket_of_host(int(h), cap // fpset.BUCKET)
                     for h in pairs[:, 1]])
    at = np.arange(len(pairs)) // fpset.BUCKET
    assert (pairs.any(axis=1) & (home != at)).sum() > 10


# ---------------------------------------------------------------------------
# the sim tier is untouched by the flag
# ---------------------------------------------------------------------------


def test_sim_tier_ignores_deferred():
    """Every walker state in the sim tier is by definition "fresh", so
    the deferred flag must not reach it: the sim engine factory has no
    deferred parameter, and the api's -simulate dispatch never threads
    deferredinv (the flag is consumed only by the BFS engine
    factories)."""
    import inspect

    from jaxtlc import api
    from jaxtlc.sim.engine import make_sim_engine

    assert "deferred" not in inspect.signature(
        make_sim_engine
    ).parameters
    assert "deferredinv" not in inspect.getsource(api._run_sim_struct)
