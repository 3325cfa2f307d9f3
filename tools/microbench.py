"""Primitive-cost microbench for the accelerator (design inputs for the
fpset v4 / engine restructure).  Everything runs K times inside one fused
dispatch (see profile_scaled.py for why)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

K = 32


def fused_time(name, body, carry, reps=3):
    @jax.jit
    def loop(c):
        return lax.fori_loop(0, K, lambda _, cc: body(cc), c)

    out = jax.block_until_ready(loop(carry))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(loop(carry))
        best = min(best, time.perf_counter() - t0)
    print(f"{name:44s} {best / K * 1e3:9.3f} ms")
    return out


def main():
    rng = np.random.default_rng(0)
    print(f"dev={jax.devices()[0]}")
    n = 245760  # chunk 16384 x 15 lanes
    R = 32768
    cap = 1 << 26

    lo = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    idx = jnp.arange(n, dtype=jnp.int32).astype(jnp.uint32)
    flag = jnp.asarray(rng.integers(0, 2, n, dtype=np.uint32))

    # sorts
    def s3(c):
        a, b, d = lax.sort((hi ^ c, lo, idx), num_keys=2, is_stable=True)
        return c + a[0]

    fused_time(f"sort {n} 3-lane (2 keys)", s3, jnp.uint32(1))

    def s4(c):
        a, b, d, e = lax.sort((flag ^ (c & 1), hi, lo, idx), num_keys=3,
                              is_stable=True)
        return c + a[0]

    fused_time(f"sort {n} 4-lane (3 keys)", s4, jnp.uint32(1))

    def s1p3(c):
        a, b, d, e = lax.sort((flag ^ (c & 1), hi, lo, idx), num_keys=1,
                              is_stable=True)
        return c + a[0]

    fused_time(f"sort {n} 4-lane (1 key, stable)", s1p3, jnp.uint32(1))

    # gathers from a big table
    table2 = jnp.zeros((cap, 2), jnp.uint32)
    slots = jnp.asarray(rng.integers(0, cap, R, dtype=np.int32))

    def g_row(c):
        t, x = c
        r = t[(slots + x) & (cap - 1)]
        return (t, x + r[0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} rows [2]u32 of 2^26-row table", g_row,
               (table2, jnp.int32(0)))

    tb8 = jnp.zeros((cap // 8, 8, 2), jnp.uint32)

    def g_b8(c):
        t, x = c
        r = t[(slots + x) & (cap // 8 - 1)]
        return (t, x + r[0, 0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} buckets [8,2]u32", g_b8, (tb8, jnp.int32(0)))

    tb16 = jnp.zeros((cap // 16, 16, 2), jnp.uint32)

    def g_b16(c):
        t, x = c
        r = t[(slots + x) & (cap // 16 - 1)]
        return (t, x + r[0, 0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} buckets [16,2]u32", g_b16, (tb16, jnp.int32(0)))

    # scatters
    rows2 = jnp.asarray(rng.integers(0, 1 << 32, (R, 2), dtype=np.uint32))

    def sc_row(c):
        t, x = c
        t = t.at[(slots + x) & (cap - 1)].set(rows2, mode="drop")
        return (t, x + 1)

    fused_time(f"scatter {R} rows [2]u32 into 2^26-row", sc_row,
               (table2, jnp.int32(0)))

    rows7 = jnp.asarray(rng.integers(0, 1 << 32, (R, 7), dtype=np.uint32))
    q7 = jnp.zeros((1 << 21, 7), jnp.uint32)

    def sc_q7(c):
        t, x = c
        t = t.at[(slots + x) & ((1 << 21) - 1)].set(rows7, mode="drop")
        return (t, x + 1)

    fused_time(f"scatter {R} rows [7]u32 into 2^21-row queue", sc_q7,
               (q7, jnp.int32(0)))

    rows34 = jnp.asarray(rng.integers(0, 1 << 31, (R, 34), dtype=np.int32))
    q34 = jnp.zeros((1 << 21, 34), jnp.int32)

    def sc_q34(c):
        t, x = c
        t = t.at[(slots + x) & ((1 << 21) - 1)].set(rows34, mode="drop")
        return (t, x + 1)

    fused_time(f"scatter {R} rows [34]i32 into 2^21-row queue", sc_q34,
               (q34, jnp.int32(0)))

    def g_q7(c):
        t, x = c
        r = t[(slots + x) & ((1 << 21) - 1)]
        return (t, x + r[0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} rows [7]u32 from 2^21-row queue", g_q7,
               (q7, jnp.int32(0)))

    # monotonic (compaction-style) scatter: targets sorted ascending
    mono = jnp.sort(slots) % (1 << 21)

    def sc_mono(c):
        t, x = c
        t = t.at[jnp.minimum(mono + x, (1 << 21) - 1)].set(rows7, mode="drop")
        return (t, x + 1)

    fused_time(f"scatter {R} rows [7]u32 monotonic tgts", sc_mono,
               (q7, jnp.int32(0)))

    # dynamic_slice-based contiguous write (append simulation)
    def ds_app(c):
        t, x = c
        t = lax.dynamic_update_slice(t, rows7, (x & ((1 << 20)), 0))
        return (t, x + 1)

    fused_time(f"dyn_update_slice {R}x7 contiguous append", ds_app,
               (q7, jnp.int32(0)))

    # MXU parity fingerprint: bits [n, 224] x basis_bits [224, 64]
    nbits = 224
    bits = jnp.asarray(rng.integers(0, 2, (n, nbits), dtype=np.int8))
    basis = jnp.asarray(rng.integers(0, 2, (nbits, 64), dtype=np.int8))

    def mxu_fp(c):
        b = (bits ^ (c & 1)).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(b, basis.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        par = acc.astype(jnp.int32) & 1
        w = jnp.arange(32, dtype=jnp.uint32)
        lo32 = (par[:, :32].astype(jnp.uint32) << w).sum(axis=1)
        hi32 = (par[:, 32:].astype(jnp.uint32) << w).sum(axis=1)
        return c + lo32[0] + hi32[0]

    fused_time(f"MXU parity fp {n}x{nbits}->64", mxu_fp, jnp.uint32(1))

    # current XOR-tree fp for comparison
    basis32 = jnp.asarray(rng.integers(0, 1 << 32, (nbits,), dtype=np.uint32))

    def xor_fp(c):
        mask = (bits ^ (c & 1)).astype(jnp.uint32)
        x = mask * basis32
        m = x.shape[-1]
        while m > 1:
            half = m // 2
            x = x[..., :half] ^ x[..., half:2 * half] if m % 2 == 0 else jnp.concatenate(
                [x[..., :half] ^ x[..., half:2 * half], x[..., 2 * half:]], axis=-1)
            m = x.shape[-1]
        return c + x[0, 0]

    fused_time(f"XOR-tree fp {n}x{nbits}->32 (one half)", xor_fp, jnp.uint32(1))

    # scatter-add counters (current) vs compare-reduce
    act = jnp.asarray(rng.integers(0, 30, n, dtype=np.int32))
    cnt = jnp.zeros(31, jnp.uint32)

    def sc_add(c):
        t, x = c
        t = t.at[jnp.minimum(act + (x & 1), 30)].add(1)
        return (t, x + 1)

    fused_time(f"scatter-add {n} into 31 bins", sc_add, (cnt, jnp.int32(0)))

    def cmp_red(c):
        t, x = c
        oh = (act[:, None] == jnp.arange(31)[None, :] - (x & 1)).astype(jnp.uint32)
        return (t + oh.sum(0), x + 1)

    fused_time(f"compare-reduce {n} into 31 bins", cmp_red, (cnt, jnp.int32(0)))


def bench_bucket_row_layout():
    """[nb, 16] u32 interleaved bucket rows (lo0,hi0,...,lo7,hi7) vs the
    materialized reshape of a flat [cap, 2] table."""
    import numpy as np
    rng = np.random.default_rng(0)
    cap = 1 << 26
    nb = cap // 8
    R = 262144
    bid = jnp.asarray(rng.integers(0, nb, R, dtype=np.int32))
    t16 = jnp.zeros((nb, 16), jnp.uint32)
    t2 = jnp.zeros((cap, 2), jnp.uint32)

    def g16(c):
        t, x = c
        r = t[(bid + x) & (nb - 1)]
        return (t, x + r[0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} rows [16]u32 of [nb,16]", g16, (t16, jnp.int32(0)))

    def g_reshape(c):
        t, x = c
        r = t.reshape(nb, 8, 2)[(bid + x) & (nb - 1)]
        return (t, x + r[0, 0, 0].astype(jnp.int32) + 1)

    fused_time(f"gather {R} via reshape of flat [cap,2]", g_reshape,
               (t2, jnp.int32(0)))

    # claim scatter: two element scatters (lo col, hi col) into [nb, 16]
    C = 262144
    cb = jnp.asarray(rng.integers(0, nb, C, dtype=np.int32))
    cs = jnp.asarray(rng.integers(0, 8, C, dtype=np.int32))
    vlo = jnp.asarray(rng.integers(0, 1 << 32, C, dtype=np.uint32))
    vhi = jnp.asarray(rng.integers(0, 1 << 32, C, dtype=np.uint32))

    def sc16(c):
        t, x = c
        b = (cb + x) & (nb - 1)
        t = t.at[b, 2 * cs].set(vlo)
        t = t.at[b, 2 * cs + 1].set(vhi)
        return (t, x + 1)

    fused_time(f"2x element scatter {C} into [nb,16]", sc16, (t16, jnp.int32(0)))

    rows2 = jnp.stack([vlo, vhi], 1)

    def sc2(c):
        t, x = c
        t = t.at[((cb + x) & (nb - 1)) * 8 + cs].set(rows2)
        return (t, x + 1)

    fused_time(f"row scatter {C} into flat [cap,2]", sc2, (t2, jnp.int32(0)))


def bench_windowed_scatter():
    """lax.scatter of [C,2] windows into [nb,16] at (b, 2s) vs 2x element."""
    rng = np.random.default_rng(0)
    nb = (1 << 26) // 8
    C = 131072
    cb = jnp.asarray(rng.integers(0, nb, C, dtype=np.int32))
    cs = jnp.asarray(rng.integers(0, 8, C, dtype=np.int32))
    vlo = jnp.asarray(rng.integers(0, 1 << 32, C, dtype=np.uint32))
    vhi = jnp.asarray(rng.integers(0, 1 << 32, C, dtype=np.uint32))
    t16 = jnp.zeros((nb, 16), jnp.uint32)
    rows = jnp.stack([vlo, vhi], 1)  # [C, 2]
    dn = lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1))

    def scw(c):
        t, x = c
        idx = jnp.stack([(cb + x) & (nb - 1), 2 * cs], 1)  # [C, 2]
        t = lax.scatter(t, idx, rows, dn,
                        mode=lax.GatherScatterMode.FILL_OR_DROP)
        return (t, x + 1)

    fused_time(f"windowed scatter {C}x[2] into [nb,16]", scw, (t16, jnp.int32(0)))

    def sc2e(c):
        t, x = c
        b = (cb + x) & (nb - 1)
        t = t.at[b, 2 * cs].set(vlo)
        t = t.at[b, 2 * cs + 1].set(vhi)
        return (t, x + 1)

    fused_time(f"2x element scatter {C} into [nb,16]", sc2e, (t16, jnp.int32(0)))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("bench", nargs="?", default="main",
                    choices=["main", "bucket-layout", "wscatter"])
    which = ap.parse_args().bench
    {"main": main, "bucket-layout": bench_bucket_row_layout,
     "wscatter": bench_windowed_scatter}[which]()
