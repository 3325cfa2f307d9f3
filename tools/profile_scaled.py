"""On-chip phase profiler for the scaled workload's loop body.

A dispatch has a fixed host-side cost that swamps naive per-op timing;
every phase here runs K times inside ONE fused ``lax.fori_loop``
dispatch and the report subtracts the measured dispatch floor.  Perf work then attacks the measured bottleneck instead of a
guessed one (VERDICT round-3 item 1).

Usage: python tools/profile_scaled.py [--chunk N] [--fpcap LOG2] [--load F]
"""

import argparse
import os
import sys
import time

# so the tool runs from any cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jaxtlc.config import scaled_config
from jaxtlc.engine.fingerprint import fp64_words
from jaxtlc.engine.fpset import (
    BUCKET,
    _bucket_of,
    _mix,
    _remap,
    fpset_insert,
    fpset_new,
)
from jaxtlc.spec.codec import get_codec
from jaxtlc.spec.invariants import make_invariant_kernel
from jaxtlc.spec.kernel import initial_vectors, make_kernel

K = 32  # inner repetitions fused into one dispatch


def fused_time(name, body, carry, floor_s=0.0, reps=3):
    """body: carry -> carry. Times lax.fori_loop(0, K, body) per iteration."""

    @jax.jit
    def loop(c):
        return lax.fori_loop(0, K, lambda _, cc: body(cc), c)

    out = jax.block_until_ready(loop(carry))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(loop(carry))
        best = min(best, time.perf_counter() - t0)
    per = (best - floor_s) / K
    if name:
        print(f"{name:36s} {per * 1e3:9.3f} ms/iter")
    return out, per


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--fpcap", type=int, default=26, help="log2 fp capacity")
    ap.add_argument("--load", type=float, default=0.29)
    args = ap.parse_args()

    cfg, _ = scaled_config()
    cdc = get_codec(cfg)
    step = make_kernel(cfg)
    L = step.n_lanes
    F = cdc.n_fields
    inv_check = make_invariant_kernel(cfg)
    chunk = args.chunk
    cap = 1 << args.fpcap
    n = chunk * L
    print(f"chunk={chunk} L={L} F={F} nbits={cdc.nbits} cand/iter={n} "
          f"fpcap=2^{args.fpcap} load={args.load} dev={jax.devices()[0]}")

    # dispatch floor: trivial fused loop
    _, floor_per = fused_time("", lambda c: c + 1, jnp.int32(0))
    floor_s = floor_per * K
    print(f"{'dispatch floor (whole loop)':36s} {floor_s * 1e3:9.3f} ms")

    # representative batch: random walk from init to get real states
    rng = np.random.default_rng(0)
    inits = jnp.asarray(initial_vectors(cfg))
    batch = jnp.tile(inits, (chunk // inits.shape[0] + 1, 1))[:chunk]
    vstep = jax.jit(jax.vmap(step))
    for _ in range(30):  # random successor walk to diversify
        succs, valid, *_ = jax.block_until_ready(vstep(batch))
        succs = np.asarray(succs)
        valid_np = np.asarray(valid)
        pick = []
        for i in range(chunk):
            idx = np.flatnonzero(valid_np[i])
            pick.append(succs[i, rng.choice(idx)] if idx.size else np.asarray(batch)[i])
        batch = jnp.asarray(np.stack(pick))

    succs0, valid0, *_ = jax.block_until_ready(vstep(batch))
    flat = jnp.reshape(succs0, (n, F))
    fvalid = jnp.reshape(valid0, (-1,))
    print(f"  valid lanes: {int(fvalid.sum())}/{n}")

    # 1. kernel expansion (carry the batch through so it isn't DCE'd)
    def b_kernel(c):
        s, v, a, af, ov = jax.vmap(step)(c)
        return c ^ s[:, 0, :1]  # cheap dependency

    _, t_kernel = fused_time("vmap(step) expansion", b_kernel, batch, floor_s)

    # 2. invariants
    def b_inv(c):
        inv = jax.vmap(inv_check)(c)
        return c ^ inv[:, None].astype(jnp.int32)

    _, t_inv = fused_time("invariant kernel", b_inv, flat, floor_s)

    # 3. pack + fingerprint
    def b_fp(c):
        packed = cdc.pack(c)
        lo, hi = fp64_words(packed, cdc.nbits)
        return c ^ lo[:, None].astype(jnp.int32)

    _, t_fp = fused_time("pack + fp64 fingerprint", b_fp, flat, floor_s)

    packed = cdc.pack(flat)
    lo, hi = fp64_words(packed, cdc.nbits)

    # table at target load with random fingerprints
    n_fill = int(cap * args.load)
    fill_lo = rng.integers(1, 1 << 32, n_fill, dtype=np.uint32)
    fill_hi = rng.integers(0, 1 << 32, n_fill, dtype=np.uint32)
    fps = fpset_new(cap)
    ins = jax.jit(fpset_insert)
    CH = 1 << 20
    for i in range(0, n_fill, CH):
        fps, _ = jax.block_until_ready(
            ins(fps, jnp.asarray(fill_lo[i:i + CH]), jnp.asarray(fill_hi[i:i + CH]),
                jnp.ones(len(fill_lo[i:i + CH]), bool)))
    print(f"  table filled to {n_fill}/{cap}")

    # 4. full fpset_insert (vary fp per rep so probes don't trivialize;
    #    table grows by ~#new per rep: negligible load change over K reps)
    def b_insert(c):
        fps_c, xlo = c
        xl = xlo ^ lo
        f2, is_new = fpset_insert(fps_c, xl, hi, fvalid)
        return (f2, xlo + jnp.uint32(1))

    _, t_ins = fused_time("fpset_insert (sort+probe)", b_insert,
                          (fps, jnp.uint32(1)), floor_s)

    # 4a. sort-dedup prefix alone
    def b_sort(c):
        xlo = c ^ lo
        inval = (~fvalid).astype(jnp.uint32)
        idx = jnp.arange(n, dtype=jnp.int32)
        s_inv, s_hi, s_lo, s_idx = lax.sort((inval, hi, xlo, idx), num_keys=3,
                                            is_stable=True)
        last = jnp.concatenate([
            (s_inv[1:] != s_inv[:-1]) | (s_hi[1:] != s_hi[:-1])
            | (s_lo[1:] != s_lo[:-1]), jnp.ones(1, bool)])
        rep_sorted = fvalid[s_idx] & last
        rep = jnp.zeros(n, bool).at[s_idx].set(rep_sorted)
        return c + rep[0].astype(jnp.uint32)

    _, t_sort = fused_time("  sort-dedup prefix", b_sort, jnp.uint32(1), floor_s)

    # 4b. one v4 bucket-probe pass (bucket gather + membership test)
    rep = fvalid

    def b_round(c):
        table, xlo = c
        l2, h2 = _mix(xlo ^ lo, hi)
        l2, h2 = _remap(l2, h2)
        bid = _bucket_of(h2, cap // BUCKET)
        bk = table[bid]  # [R, 2B] interleaved bucket rows
        hit = (bk[:, 0::2] == l2[:, None]) & (bk[:, 1::2] == h2[:, None])
        found = rep & hit.any(axis=1)
        return (table, xlo + jnp.uint32(1) + found[0].astype(jnp.uint32))

    _, t_round = fused_time("  one bucket-probe pass (gather)", b_round,
                            (fps.table, jnp.uint32(1)), floor_s)

    # 5. queue append scatter
    qcap = 1 << 21
    queue = jnp.zeros((qcap + 1, F), jnp.int32)
    is_new = fvalid

    def b_q(c):
        q, off = c
        pos = jnp.cumsum(is_new.astype(jnp.int32)) - 1 + off
        tgt = jnp.where(is_new, pos % qcap, qcap)
        return (q.at[tgt].set(flat), off + jnp.int32(7919))

    _, t_q = fused_time("queue append scatter", b_q, (queue, jnp.int32(0)), floor_s)

    total = t_kernel + t_inv + t_fp + t_ins + t_q
    print(f"{'SUM of phases':36s} {total * 1e3:9.3f} ms/iter")
    print(f"  -> at ~{chunk} distinct/iter: {chunk / total / 1e3:.1f}k distinct/s ceiling")


if __name__ == "__main__":
    main()
