"""Device fingerprint-set tests (E4): exactness vs a python set under
in-batch duplicates, masking, and load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxtlc.engine.fpset import fpset_count, fpset_insert, fpset_new


def test_matches_python_set_with_duplicates():
    rng = np.random.default_rng(1)
    s = fpset_new(1 << 12)
    ins = jax.jit(fpset_insert)
    seen = set()
    total_new = 0
    for _ in range(20):
        vals = rng.integers(0, 400, size=256)
        lo = jnp.asarray(vals.astype(np.uint32))
        hi = jnp.asarray((vals * 7 + 3).astype(np.uint32))
        mask = rng.random(256) < 0.9
        s, is_new = ins(s, lo, hi, jnp.asarray(mask))
        is_new = np.asarray(is_new)
        assert not is_new[~mask].any()
        total_new += int(is_new.sum())
        seen.update(int(v) for v, m in zip(vals, mask) if m)
    assert int(fpset_count(s)) == len(seen) == total_new


def test_in_batch_duplicates_yield_single_new():
    s = fpset_new(1 << 8)
    lo = jnp.asarray(np.array([5, 5, 5, 9], dtype=np.uint32))
    hi = jnp.asarray(np.array([1, 1, 1, 2], dtype=np.uint32))
    s, new = fpset_insert(s, lo, hi, jnp.ones(4, bool))
    assert int(np.asarray(new).sum()) == 2
    s, new = fpset_insert(s, lo, hi, jnp.ones(4, bool))
    assert int(np.asarray(new).sum()) == 0


def test_zero_fingerprint_is_representable():
    # fp == (0, 0) must work: it is remapped to (1, 0) behind the scenes
    # (the (0,0) row means empty), so insert-then-find still holds
    s = fpset_new(1 << 8)
    z = jnp.zeros(1, jnp.uint32)
    s, new = fpset_insert(s, z, z, jnp.ones(1, bool))
    assert bool(np.asarray(new)[0])
    s, new = fpset_insert(s, z, z, jnp.ones(1, bool))
    assert not bool(np.asarray(new)[0])


def test_all_ones_fingerprint_with_masked_lanes():
    # regression: a valid fp of all-ones must not be conflated with
    # masked-out lanes (the old sort keyed invalid lanes to 0xFFFFFFFF)
    s = fpset_new(1 << 8)
    ones = jnp.full(3, 0xFFFFFFFF, jnp.uint32)
    mask = jnp.asarray([True, False, False])
    s, new = fpset_insert(s, ones, ones, mask)
    assert list(np.asarray(new)) == [True, False, False]
    s, new = fpset_insert(s, ones, ones, jnp.ones(3, bool))
    assert not np.asarray(new).any()
    assert int(fpset_count(s)) == 1


def test_segmented_probe_partial_final_segment():
    # regression: probe_width not dividing the batch must not clamp the
    # final partial segment (dynamic_slice clamps OOB starts; the unpadded
    # version re-probed earlier entries and never probed the tail)
    from jaxtlc.engine.fpset import commit_stat_fields, fpset_insert_sorted

    s = fpset_new(1 << 8)
    vals = np.arange(10, dtype=np.uint32)
    s, is_new_c, c_idx, nreps, stat = fpset_insert_sorted(
        s, jnp.asarray(vals), jnp.asarray(vals ^ 0xABCD), jnp.ones(10, bool),
        probe_width=4,
    )
    assert int(nreps) == 10
    # the call's own counts: three segments of four rows for ten
    did = commit_stat_fields(stat)
    assert (did["valid"], did["reps"], did["probe_segments"]) == (10, 10, 3)
    assert did["claimed"] + did["stragglers"] == 10
    assert int(np.asarray(is_new_c).sum()) == 10
    assert int(fpset_count(s)) == 10
    # idempotence: nothing is new the second time
    s, is_new_c, _, _, stat = fpset_insert_sorted(
        s, jnp.asarray(vals), jnp.asarray(vals ^ 0xABCD), jnp.ones(10, bool),
        probe_width=4,
    )
    assert not np.asarray(is_new_c).any()
    did = commit_stat_fields(stat)
    assert (did["reps"], did["claimed"], did["stragglers"]) == (10, 0, 0)


def test_mix_unmix_roundtrip_and_actual_collision():
    from jaxtlc.engine.fpset import (
        _mix,
        _unmix,
        fpset_actual_collision,
        mix_host,
    )

    rng = np.random.default_rng(3)
    lo = jnp.asarray(rng.integers(0, 1 << 32, 500, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 1 << 32, 500, dtype=np.uint32))
    ml, mh = _mix(lo, hi)
    ul, uh = _unmix(ml, mh)
    assert (np.asarray(ul) == np.asarray(lo)).all()
    assert (np.asarray(uh) == np.asarray(hi)).all()
    hl, hh = mix_host(int(lo[0]), int(hi[0]))
    assert (hl, hh) == (int(ml[0]), int(mh[0]))

    s = fpset_new(1 << 12)
    s, _ = fpset_insert(s, lo, hi, jnp.ones(500, bool))
    p = float(fpset_actual_collision(s))
    assert 0 < p < 1  # a positive probability-scale estimate


def test_high_load():
    s = fpset_new(1 << 10)
    vals = np.arange(700, dtype=np.uint32)
    s, new = fpset_insert(
        s, jnp.asarray(vals), jnp.asarray(vals ^ 0xFFFF), jnp.ones(700, bool)
    )
    assert int(np.asarray(new).sum()) == 700


def test_blocked_write_bit_for_bit(monkeypatch):
    """A wide write goes block by block behind a trip count (ISSUE 26:
    `_blocked`, round 0 over its compacted claimers, the straggler walk
    over its live prefix): the table words and verdicts are those of
    the one whole-width scatter, with and without straggler pressure,
    and the distinct count is the set's."""
    from jaxtlc.engine import fpset

    insert = fpset.fpset_insert_sorted
    n = 1024
    assert fpset._blocked(n)
    got = {}
    for blocked in (True, False):
        if not blocked:
            monkeypatch.setattr(fpset, "_blocked", lambda n: False)
        s, seen, verdicts = fpset_new(1 << 13), set(), []
        for step in range(3):
            rng = np.random.default_rng(7 + step)
            lo = rng.integers(0, 2 ** 32, size=n, dtype=np.uint32)
            hi = rng.integers(0, 2 ** 6, size=n, dtype=np.uint32) << 26
            lo[::5] = lo[1::5][: len(lo[::5])]  # in-batch duplicates
            hi[::5] = hi[1::5][: len(hi[::5])]
            mask = rng.random(n) < 0.9
            # step 0 claims in round 0 (several blocks); a narrow claim
            # width then sends most claimers to the straggler walk
            s, is_new_c, c_idx, nreps, stat = insert(
                s, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(mask),
                claim_width=128 if step else 0,
            )
            # the blocks the round-0 write scattered follow its
            # claimers: 128 rows a block here, one where it is not cut
            did = fpset.commit_stat_fields(stat)
            assert did["claim_blocks"] == (
                -(-did["claimed"] // (n // fpset.WRITE_BLOCKS))
                if blocked else 1)
            assert did["claimed"] <= (128 if step else n)
            fresh = {(int(a), int(b))
                     for a, b, m in zip(lo, hi, mask) if m} - seen
            assert int(np.asarray(is_new_c).sum()) == len(fresh)
            seen |= fresh
            verdicts.append((np.asarray(is_new_c), np.asarray(c_idx)))
        got[blocked] = (np.asarray(s.table), verdicts)
    assert (got[True][0] == got[False][0]).all()
    for (n0, c0), (n1, c1) in zip(got[True][1], got[False][1]):
        assert (n0 == n1).all() and (c0 == c1).all()
