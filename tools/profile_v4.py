"""Phase profiler for the v4 engine loop on the scaled workload.

Unlike tools/profile_scaled.py (whose host-side random-walk setup is
unusably slow at 128k chunks), this drives the REAL engine to a mid-run
carry (realistic frontier block + realistic table load), then times each
phase of the engine step in a fused ``lax.fori_loop`` so the measured
per-dispatch floor is amortized and subtracted.

Round 7 additions: per-stage wall attribution for the pipelined engine
(expand stage measured directly through the backend seam, commit stage
by subtraction from the real fused step) and an overlap-efficiency line
(wall saved by the pipelined step over min(expand, commit), the
theoretical two-stage overlap ceiling).

Usage: python tools/profile_v4.py [--chunk N] [--fpcap LOG2] [--steps K]
       python tools/profile_v4.py --tiny   # FF corner smoke (tier-1)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from jaxtlc.config import scaled_config
from jaxtlc.engine.bfs import make_engine
from jaxtlc.engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED, fp64_words_mxu
from jaxtlc.engine.fpset import fpset_insert_sorted
from jaxtlc.spec.codec import get_codec
from jaxtlc.spec.invariants import make_invariant_kernel
from jaxtlc.spec.kernel import make_kernel

K = 16


def fused_time(name, body, carry, floor_s=0.0, reps=3):
    @jax.jit
    def loop(c):
        return lax.fori_loop(0, K, lambda _, cc: body(cc), c)

    out = jax.block_until_ready(loop(carry))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(loop(carry))
        best = min(best, time.perf_counter() - t0)
    per = (best - floor_s) / K
    if name:
        print(f"{name:40s} {per * 1e3:9.3f} ms/iter")
    return out, per


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=131072)
    ap.add_argument("--fpcap", type=int, default=26)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--tiny", action="store_true",
                    help="FF-corner smoke sizing (chunk 256, fp 2^15, "
                         "8 warm steps) so the tier-1 suite can run the "
                         "whole profiler without a TPU")
    args = ap.parse_args(argv)

    if args.tiny:
        from jaxtlc.config import ModelConfig

        cfg = ModelConfig(False, False)
        if args.chunk == 131072:
            args.chunk = 256
        if args.fpcap == 26:
            args.fpcap = 15
        if args.steps == 60:
            args.steps = 8
        qcap = 1 << 13
    else:
        cfg, _ = scaled_config()
        qcap = 1 << 21
    cdc = get_codec(cfg)
    F = cdc.n_fields
    W = (cdc.nbits + 31) // 32
    step = make_kernel(cfg)
    L = step.n_lanes
    inv_check = make_invariant_kernel(cfg)
    chunk = args.chunk
    ncand = chunk * L
    print(f"chunk={chunk} L={L} F={F} W={W} nbits={cdc.nbits} "
          f"ncand={ncand} dev={jax.devices()[0]}")

    # drive the real engine to a mid-run carry (donate=False: the same
    # warmed carry seeds every timing closure below, repeatedly)
    init_fn, _, step_fn = make_engine(
        cfg, chunk=chunk, queue_capacity=qcap,
        fp_capacity=1 << args.fpcap, donate=False,
    )
    carry = init_fn()
    t0 = time.time()
    for _ in range(args.steps):
        carry = step_fn(carry)
    carry = jax.block_until_ready(carry)
    print(f"  warmed {args.steps} steps in {time.time() - t0:.1f}s: "
          f"distinct={int(carry.distinct)} level={int(carry.level)} "
          f"level_n={int(carry.level_n)} qhead={int(carry.qhead)}")

    block = lax.dynamic_slice(
        carry.queue, (carry.parity, jnp.int32(0), jnp.int32(0)),
        (1, chunk, W))[0]
    batch = cdc.unpack(block)
    fps = carry.fps

    _, floor_per = fused_time("", lambda c: c + 1, jnp.int32(0))
    floor_s = floor_per * K
    print(f"{'dispatch floor (whole fused loop)':40s} {floor_s * 1e3:9.3f} ms")

    # 0. whole step body, for reference
    body_full = None  # step_fn is cond-wrapped; time via engine below

    # 1. unpack
    def b_unpack(c):
        b = cdc.unpack(block ^ c[None, :])
        return c ^ b[0, :1].astype(jnp.uint32)

    _, t_unpack = fused_time("unpack", b_unpack,
                             jnp.zeros(W, jnp.uint32), floor_s)

    # 2. kernel expansion
    def b_kernel(c):
        s, v, a, af, ov = jax.vmap(step)(c)
        return c ^ s[:, 0, :1]

    _, t_kernel = fused_time("vmap(step) expansion", b_kernel, batch, floor_s)

    succs, valid, action, afail, ovf = jax.vmap(step)(batch)
    flat = succs.reshape(ncand, F)
    fvalid = valid.reshape(-1)
    print(f"  valid: {int(fvalid.sum())}/{ncand}")

    # 3. invariants
    def b_inv(c):
        inv = jax.vmap(inv_check)(c)
        return c ^ inv[:, None].astype(jnp.int32)

    _, t_inv = fused_time("invariant kernel", b_inv, flat, floor_s)

    # 4. pack
    def b_pack(c):
        p = cdc.pack(c)
        return c ^ p[:, :1].astype(jnp.int32)

    _, t_pack = fused_time("pack", b_pack, flat, floor_s)

    packed = cdc.pack(flat)

    # 5. fingerprint (MXU)
    def b_fp(c):
        lo, hi = fp64_words_mxu(c, cdc.nbits, DEFAULT_FP_INDEX, DEFAULT_SEED)
        return c ^ lo[:, None]

    _, t_fp = fused_time("fp64 fingerprint (MXU)", b_fp, packed, floor_s)

    lo, hi = fp64_words_mxu(packed, cdc.nbits, DEFAULT_FP_INDEX, DEFAULT_SEED)
    R = min(2 * chunk, ncand)

    # 6. fpset_insert_sorted at real load (vary lo so probes are honest;
    # table occupancy grows negligibly over K reps)
    def b_ins(c):
        fps_c, x = c
        f2, is_new_c, c_idx, nreps = fpset_insert_sorted(
            fps_c, lo ^ x, hi, fvalid, probe_width=R, claim_width=R)
        return (f2, x + jnp.uint32(1))

    _, t_ins = fused_time("fpset_insert_sorted (2 sorts + probe)", b_ins,
                          (fps, jnp.uint32(1)), floor_s)

    # 6a. sort 1 alone (group duplicates): 4 arrays, 3 keys
    idx = jnp.arange(ncand, dtype=jnp.uint32)

    def b_sort1(c):
        inval = (~fvalid).astype(jnp.uint32)
        s_inv, s_hi, s_lo, s_idx = lax.sort(
            (inval, hi, lo ^ c, idx), num_keys=3, is_stable=True)
        return c + s_lo[0]

    _, t_sort1 = fused_time("  sort1 (4 arrays, 3 keys)", b_sort1,
                            jnp.uint32(1), floor_s)

    # 6b. sort 2 alone (compact reps): 4 arrays, 1 key
    rep = fvalid

    def b_sort2(c):
        nonrep = (~rep).astype(jnp.uint32)
        _, c_lo, c_hi, c_idx = lax.sort(
            (nonrep, lo ^ c, hi, idx), num_keys=1, is_stable=True)
        return c + c_lo[0]

    _, t_sort2 = fused_time("  sort2 (4 arrays, 1 key)", b_sort2,
                            jnp.uint32(1), floor_s)

    # 6c. probe block alone at R rows
    from jaxtlc.engine.fpset import _probe_block, _mix, _remap
    mlo, mhi = _mix(lo[:R], hi[:R])
    mlo, mhi = _remap(mlo, mhi)
    s_hi2, s_lo2 = lax.sort((mhi, mlo), num_keys=2)

    def b_probe(c):
        tbl, x = c
        t2, isn = _probe_block(tbl, s_lo2 ^ x, s_hi2, fvalid[:R], R)
        return (t2, x + jnp.uint32(1))

    _, t_probe = fused_time("  probe block (R rows)", b_probe,
                            (fps.table, jnp.uint32(1)), floor_s)

    # 7. enqueue sort + gather + contiguous write
    A = min(2 * chunk, ncand)
    is_new_c = fvalid  # worst-ish case

    def b_enq(c):
        q, x = c
        _, e_idx = lax.sort(
            ((~is_new_c).astype(jnp.uint32), (idx + x)), num_keys=2,
            is_stable=True)
        rows_a = packed[e_idx[:A].astype(jnp.int32)]
        q = lax.dynamic_update_slice(q, rows_a[None], (0, 0, jnp.int32(0)))
        return (q, x + jnp.uint32(1))

    _, t_enq = fused_time("enqueue (sort + A-gather + write)", b_enq,
                          (carry.queue, jnp.uint32(1)), floor_s)

    # 8. per-action stats
    from jaxtlc.spec.labels import LABELS
    from jaxtlc.spec.kernel import lane_layout
    CL, _ = lane_layout(cfg)
    nc = cdc.nc
    n_labels = len(LABELS)
    pc_off = cdc.offsets["pc"]
    label_ids = jnp.arange(n_labels, dtype=jnp.int32)

    def b_stats(c):
        gen_counts = jnp.zeros(n_labels, jnp.uint32)
        for ci in range(nc):
            vc = valid[:, ci * CL:(ci + 1) * CL].sum(axis=1)
            pcs = batch[:, pc_off + ci] + c
            gen_counts = gen_counts + (
                (pcs[:, None] == label_ids[None, :]) * vc[:, None]
            ).sum(axis=0).astype(jnp.uint32)
        return c + gen_counts[0].astype(jnp.int32)

    _, t_stats = fused_time("per-action gen counters", b_stats,
                            jnp.int32(0), floor_s)

    total = (t_unpack + t_kernel + t_inv + t_pack + t_fp + t_ins + t_enq
             + t_stats)
    print(f"{'SUM of phases':40s} {total * 1e3:9.3f} ms/iter")
    print(f"  -> at ~{chunk} distinct/iter ceiling: "
          f"{chunk / total / 1e3:.0f}k distinct/s")

    # whole real step via the engine's own jitted step_fn (one dispatch
    # per step; subtract the measured dispatch floor per call)
    out = jax.block_until_ready(step_fn(carry))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        c2 = carry
        for _ in range(K):
            c2 = step_fn(c2)
        jax.block_until_ready(c2)
        best = min(best, time.perf_counter() - t0)
    # each step_fn call is its own dispatch, so subtract the whole
    # dispatch floor per call (floor_s = one fused-loop dispatch's cost)
    per = best / K - floor_s
    print(f"{'REAL step_fn (x16, floor-adjusted)':40s} {per * 1e3:9.3f} ms/iter")

    # --- round 7: expand/commit stage attribution + overlap efficiency ---
    # expand measured directly through the backend seam (the SAME
    # function the pipelined body runs); commit attributed by
    # subtraction from the real fused step so the two columns add up to
    # what the engine actually pays
    from jaxtlc.engine.backend import kubeapi_backend, make_expand_stage

    backend = kubeapi_backend(cfg)
    expand_fn = make_expand_stage(
        backend, chunk, True, DEFAULT_FP_INDEX, DEFAULT_SEED
    )
    mask_all = jnp.ones(chunk, bool)

    def b_expand(c):
        ex = expand_fn(c, mask_all)
        return c ^ ex.lo[:chunk, None].astype(jnp.int32)

    _, t_expand = fused_time("expand stage (seam)", b_expand, batch,
                             floor_s)
    t_commit = max(per - t_expand, 0.0)
    print(f"{'commit stage (real step - expand)':40s} "
          f"{t_commit * 1e3:9.3f} ms/iter")

    # pipelined engine at the same geometry, warmed identically: the
    # per-step delta over the fused engine is the realized overlap;
    # min(expand, commit) is the two-stage ceiling
    pinit, _, pstep = make_engine(
        cfg, chunk=chunk, queue_capacity=qcap,
        fp_capacity=1 << args.fpcap, pipeline=True, donate=False,
    )
    pcarry = pinit()
    for _ in range(args.steps):
        pcarry = pstep(pcarry)
    pcarry = jax.block_until_ready(pcarry)
    jax.block_until_ready(pstep(pcarry))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        c2 = pcarry
        for _ in range(K):
            c2 = pstep(c2)
        jax.block_until_ready(c2)
        best = min(best, time.perf_counter() - t0)
    per_pipe = best / K - floor_s
    print(f"{'PIPELINED step_fn (x16, floor-adjusted)':40s} "
          f"{per_pipe * 1e3:9.3f} ms/iter")
    ceiling = min(t_expand, t_commit)
    saved = per - per_pipe
    eff = saved / ceiling if ceiling > 0 else 0.0
    print(f"overlap efficiency: {eff:6.2f} "
          f"(saved {saved * 1e3:.3f} ms of {ceiling * 1e3:.3f} ms "
          f"overlappable per step)")


if __name__ == "__main__":
    main()
