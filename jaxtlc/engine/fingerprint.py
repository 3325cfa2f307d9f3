"""FP64: 64-bit GF(2) Rabin fingerprints, TPU-native.

TLC fingerprints every state with a 64-bit Rabin fingerprint selected from a
table of irreducible degree-64 polynomials via the launch-file knob
``fpIndex`` (fpIndex=51 in the reference run,
/root/reference/KubeAPI.toolbox/KubeAPI___Model_1.launch:8; banner "fp 51 and
seed ..." in MC.out:5), and dedups *by fingerprint* - the reported distinct
count is the count of distinct fingerprints, with the collision probability
reported at the end (MC.out:39-42).  This module reproduces that capability:

* an irreducible-polynomial table (our own, generated by ``find_polys`` below
  - TLC's exact table is not vendored; SURVEY.md §7 "hard parts" requires
  duplicating TLC's *verdicts*, not its fingerprint values);
* a host-side exact bitwise implementation over Python ints (the
  property-test reference);
* a TPU kernel that is gather-free: because the Rabin fingerprint of a
  fixed-length bit string is an *affine* GF(2) map of its bits, we precompute
  ``basis[i] = fp(e_i) ^ fp(0)`` once on the host and evaluate
  ``fp(M) = fp(0) ^ XOR_{i in M} basis[i]`` on device as a masked XOR
  reduction in 2 x uint32 lanes (TPUs have no native int64).  TLC's serial
  byte-table loop is a CPU idiom; the linear form is the TPU idiom.

Collision-probability reporting (the MC.out:39-42 analog) lives in
``collision_probability``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "POLYS",
    "DEFAULT_FP_INDEX",
    "DEFAULT_SEED",
    "fp64_host",
    "fp64_words",
    "affine_basis",
    "collision_probability",
    "find_polys",
    "is_irreducible",
    "POLY_SEED",
]

MASK64 = (1 << 64) - 1

# Table of c such that x^64 + p(c) is irreducible over GF(2), where p(c) is
# the polynomial with coefficient bits c (bit i = coefficient of x^i).  131
# entries, matching the size/indexing convention of TLC's polynomial table so
# .launch fpIndex values (0..130) stay meaningful.  DENSE (ROADMAP M10): 24 to
# 40 of the low 64 coefficients are set.  The fingerprint is GF(2)-affine in
# the state's bits, so two states whose XOR is a multiple of the polynomial
# of degree under the state's width collide; a polynomial that is x^64 plus
# a dozen low terms (the table until PR 45: index 51 was 0xc8b) has such
# multiples that are shifted copies of itself, a pattern of a few bits that
# a dense product space past 64 bits is full of (528 of 400,000 states at an
# 87-bit EWD998 layout).  A dense polynomial's low multiples are dense too.
# At <= 64 message bits the map is injective for ANY irreducible entry.
# Generated by find_polys(); `python -m jaxtlc.engine.fingerprint` verifies
# the table against it.
POLYS = [
    0x5f4e14147961f2e9, 0x52aead5ef08ac45f, 0x2f597787e8c75c47,
    0x6a06d8f31cc8127b, 0xe1460d5af30b3df5, 0x12d82d1c233b6a89,
    0x56c2dbc758d198b1, 0x9a4311b95a6a991d, 0x1b4ecbbf8e54cf3d,
    0x613143aef10f037f, 0xd29b84ddd8e3c053, 0x8006bdf6cb4518d9,
    0x870de46cf6010981, 0xb6aec7073042e657, 0x45a234c595d28e3b,
    0xb651de1c0f036271, 0x1e44bd3adc1f31cb, 0x74150821214d6d19,
    0xae66d9c9ed35cb75, 0x17b4506d91c11f09, 0x1ef381471882459d,
    0x15d296b65b10ed59, 0xca4f5ea6aea466e7, 0x0a13c4dcdb40f663,
    0x457f2a8688d8b03b, 0x9a2c36ee9be78d1d, 0xd312bc02cc6c8311,
    0x9d47f3d9610d4d55, 0x96ec43c2680631b3, 0xd81c8b9f164d0bd9,
    0x9f6715a3c8797a7f, 0x9b18920312fc39bb, 0xeb96c94bb566f4b7,
    0x7744e0eb26de95dd, 0xd1dbb2a1f21a4bdf, 0x8d5812b02211f6cf,
    0x48a566055b4ca627, 0xd7a4ae4f2042b923, 0x26cda44e28ab3873,
    0x02a67819614e6aab, 0x9a24ec9e6a65dc0b, 0xdb609cdb0440b5b3,
    0xa9a927d2616dfce1, 0xc533150e98913695, 0xfb1278a4836cc403,
    0xf761229f95978179, 0x44381783ce9490ff, 0xa736d79354a2bb4f,
    0xd06ecbf50de0c833, 0x9a3f24f1bbac10e7, 0xae148dd2b296009b,
    0x755d64d039122153, 0x86e9d9ed6fe2382b, 0x8ceb9ec0da9b9e83,
    0x9191be792822db5d, 0x913bf9c7a38d2339, 0x1519e40836464fb9,
    0x40697b0f17d1da69, 0xc7d58eeaeb7bc66d, 0x52b52ec56083f5b3,
    0x9f0290337ec9a4db, 0x548f60d5ed2622b1, 0xcd64276b582d9c95,
    0x40e27de20c22ae0f, 0x10a0cd5b85de16e9, 0xa36a2df71c8a638f,
    0x60fa6a135941a397, 0xaa4abc9a190c0fad, 0xd5ed0c2d8aaf1d4b,
    0xb8941c6e22c6a4f5, 0x3ff97507f50ee223, 0x60b0597084c5e961,
    0x8b923a29839a0379, 0xeddc7a61479c9bbd, 0x041ea91d649ec6f7,
    0xb2d901c3f669bd7d, 0xabd2db8dce369e8f, 0xee858fc07cd3d079,
    0x3501b062bcd9e891, 0x33fb44206361f895, 0xcb3f628790cf7c97,
    0xb99b8676e5f22391, 0x0dbd28a6a022f39b, 0xfadb9ae2725fc5bb,
    0x70d41a17a3eccdbb, 0x073b443b11625fa5, 0xfcaa7ef6c41050d7,
    0x43cc66143e0bffa7, 0x262e76295c8c7623, 0x78069b83a33661e3,
    0x2e95c5a9567f7dad, 0xc8301bd3ae65a1c3, 0xd605731c2e8c3dc1,
    0xba5f85573269cad1, 0x001a663d8482c9cf, 0xca131e77f78f3fa7,
    0x3c5862bace0b027b, 0x9e69aad8bffd3545, 0xedf1f872f3c682dd,
    0x1f7a3dbaaf3efa07, 0x3ba562aed4b91319, 0x02c7c3a90ce855ed,
    0x9a55dec108781d59, 0x4d31be0a4b72578b, 0x4c3651a335502ad3,
    0x90f92c1d524e30cf, 0xb305f96e1fd294bb, 0x9944ea6da4307de5,
    0x469bf393fbe21077, 0x7ec103a2519f4289, 0xa2024a00b98d5ee9,
    0x71dc47e1f2cce4bd, 0x829a3f5058f3fddd, 0x6b423b9c1e9f1bed,
    0x20127cea37e1e313, 0xb5d3fc89a3938e57, 0x8fef5e57a2430dd9,
    0x91d63bc8a9609a93, 0x4ab98e96e1eec03f, 0x1aa3f67e6e4dd6e9,
    0x0d5a61c0df35dceb, 0xd0c94613968c7f43, 0x67e88320c93f656b,
    0x7397f8389fc5683d, 0x7b1f9a5db0ceb1a5, 0xf1ada11335ea49d7,
    0xa6032841a7a1f963, 0x62f3549f54ade86f, 0x9d69155abcbd61a3,
    0xcef53f1120df9b8d, 0xf6d3447fc352e45b,
]

DEFAULT_FP_INDEX = 51  # the reference run's fpIndex (launch:8)
DEFAULT_SEED = 0x9E3779B97F4A7C15  # fixed engine seed (TLC randomizes; we pin)


# ---------------------------------------------------------------------------
# Polynomial generation (provenance for POLYS; not on any hot path)
# ---------------------------------------------------------------------------


def _pmulmod(a: int, b: int, p: int) -> int:
    r = 0
    while b:
        if b & 1:
            r ^= a
        b >>= 1
        a <<= 1
        if a >> 64:
            a ^= p
    return r


def _pgcd(a: int, b: int) -> int:
    while b:
        while a and a.bit_length() >= b.bit_length():
            a ^= b << (a.bit_length() - b.bit_length())
        a, b = b, a
    return a


def is_irreducible(p: int) -> bool:
    """Rabin irreducibility test for a degree-64 polynomial over GF(2)."""
    assert p >> 64 == 1
    t = 2  # the polynomial 'x'
    for _ in range(32):
        t = _pmulmod(t, t, p)
    if _pgcd(t ^ 2, p) != 1:
        return False
    for _ in range(32):
        t = _pmulmod(t, t, p)
    return t == 2


POLY_SEED = 0x6A09E667F3BCC908  # the fraction of sqrt(2): no choice in it


def find_polys(n: int = 131, seed: int = POLY_SEED):
    """The first `n` irreducible x^64 + p(c) of a seeded stream of dense
    candidates: c the splitmix64 sequence from `seed`, made odd (a zero
    constant term is a factor x), kept where it has an even number of
    terms (an odd one with x^64 is a multiple of x + 1) between 24 and
    40: about half of the low 64 coefficients."""
    out, x = [], seed
    while len(out) < n:
        x = (x + 0x9E3779B97F4A7C15) & MASK64
        z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
        c = (z ^ (z >> 31)) | 1
        terms = bin(c).count("1")
        if terms % 2 or not 24 <= terms <= 40 or c in out:
            continue
        if is_irreducible((1 << 64) | c):
            out.append(c)
    return out


# ---------------------------------------------------------------------------
# Host-side exact fingerprint (property-test reference; int64 semantics)
# ---------------------------------------------------------------------------


def fp64_host(bits: int, nbits: int, fp_index: int = DEFAULT_FP_INDEX,
              seed: int = DEFAULT_SEED) -> int:
    """Rabin fingerprint of an nbits-long bit string (bit i of `bits` is
    message bit i), processed LSB-first starting from `seed`.

    Definition: state s (64 bits); per message bit b:
        s = (s >> 1) ^ ((s ^ b) & 1 ? R : 0)
    where R is the degree-64 polynomial in reflected (bit-reversed) form.
    This is the standard reflected LFSR realization of division by the
    irreducible polynomial - the same algebra TLC's FP64 byte tables unroll.
    """
    poly = (1 << 64) | POLYS[fp_index]
    refl = _reflect64(poly & MASK64)  # reflected low-64 coefficients
    s = seed & MASK64
    for i in range(nbits):
        b = (bits >> i) & 1
        low = (s ^ b) & 1
        s >>= 1
        if low:
            s ^= refl
    return s


def _reflect64(v: int) -> int:
    r = 0
    for i in range(64):
        if (v >> i) & 1:
            r |= 1 << (63 - i)
    return r


# ---------------------------------------------------------------------------
# Affine basis for the TPU kernel
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def affine_basis(nbits: int, fp_index: int = DEFAULT_FP_INDEX,
                 seed: int = DEFAULT_SEED):
    """(const, basis): fp(M) = const ^ XOR_{i: bit i of M set} basis[i].

    Valid because fp64_host is GF(2)-affine in the message bits at fixed
    length.  Returned as numpy uint32 arrays: const (2,), basis (nbits, 2),
    column 0 = low 32 bits, column 1 = high 32 bits.
    """
    c = fp64_host(0, nbits, fp_index, seed)
    basis = np.zeros((nbits, 2), dtype=np.uint32)
    for i in range(nbits):
        f = fp64_host(1 << i, nbits, fp_index, seed) ^ c
        basis[i, 0] = f & 0xFFFFFFFF
        basis[i, 1] = f >> 32
    const = np.array([c & 0xFFFFFFFF, c >> 32], dtype=np.uint32)
    return const, basis


def fp64_words(words, nbits: int, fp_index: int = DEFAULT_FP_INDEX,
               seed: int = DEFAULT_SEED):
    """Device fingerprint of packed states.

    words: [..., W] uint32, little-endian bit order (bit i of the message is
    bit i%32 of word i//32); nbits <= 32*W message bits are fingerprinted.
    Returns (lo, hi): each [...] uint32.  Gather-free: bits -> masked XOR
    reduction against the affine basis.
    """
    const, basis = affine_basis(nbits, fp_index, seed)
    w = words.astype(jnp.uint32)
    nwords = (nbits + 31) // 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    # [..., W, 32] bit expansion, flattened to [..., 32*W], cropped to nbits
    bits = (w[..., :nwords, None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*w.shape[:-1], nwords * 32)[..., :nbits]
    mask = bits.astype(jnp.uint32)
    blo = jnp.asarray(basis[:, 0])
    bhi = jnp.asarray(basis[:, 1])
    # masked XOR-reduce along the bit axis (VPU-friendly; no gathers)
    lo = _xor_reduce(mask * blo)
    hi = _xor_reduce(mask * bhi)
    return lo ^ jnp.uint32(const[0]), hi ^ jnp.uint32(const[1])


def _xor_reduce(x):
    """XOR-reduce the last axis (log-depth tree keeps XLA happy)."""
    n = x.shape[-1]
    while n > 1:
        half = n // 2
        x = x[..., :half] ^ x[..., half : 2 * half] if n % 2 == 0 else jnp.concatenate(
            [x[..., :half] ^ x[..., half : 2 * half], x[..., 2 * half :]], axis=-1
        )
        n = x.shape[-1]
    return x[..., 0]


@functools.lru_cache(maxsize=None)
def _basis_bits(nbits: int, fp_index: int = DEFAULT_FP_INDEX,
                seed: int = DEFAULT_SEED):
    """Bit-plane view of the affine basis: [nbits, 64] int8, column j = bit
    j of the 64-bit basis fingerprint (lo bits 0-31, hi bits 32-63)."""
    _, basis = affine_basis(nbits, fp_index, seed)
    bb = np.zeros((nbits, 64), dtype=np.int8)
    for j in range(32):
        bb[:, j] = (basis[:, 0] >> j) & 1
        bb[:, 32 + j] = (basis[:, 1] >> j) & 1
    return bb


def fp64_words_mxu(words, nbits: int, fp_index: int = DEFAULT_FP_INDEX,
                   seed: int = DEFAULT_SEED):
    """fp64_words computed as a GF(2) matmul on the MXU.

    Identical values to fp64_words (property-tested): the masked-XOR
    reduction of an affine GF(2) map is a parity per output bit, and
    parity = popcount & 1, so ``bits [n, nbits] @ basis_bits [nbits, 64]``
    accumulated in f32 (exact: counts <= nbits << 2^24) followed by ``& 1``
    gives the fingerprint bits.  The reduction rides the systolic array
    (tests/test_fingerprint.py::test_mxu_path_matches_xor_tree_and_host
    pins the values; the two were not timed against each other on the
    present chip).
    """
    bb = jnp.asarray(_basis_bits(nbits, fp_index, seed), dtype=jnp.bfloat16)
    const, _ = affine_basis(nbits, fp_index, seed)
    w = words.astype(jnp.uint32)
    nwords = (nbits + 31) // 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (w[..., :nwords, None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*w.shape[:-1], nwords * 32)[..., :nbits]
    acc = jax.lax.dot_general(
        bits.astype(jnp.bfloat16), bb,
        (((bits.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    par = acc.astype(jnp.int32).astype(jnp.uint32) & jnp.uint32(1)
    w32 = jnp.arange(32, dtype=jnp.uint32)
    lo = (par[..., :32] << w32).sum(axis=-1, dtype=jnp.uint32)
    hi = (par[..., 32:] << w32).sum(axis=-1, dtype=jnp.uint32)
    return lo ^ jnp.uint32(const[0]), hi ^ jnp.uint32(const[1])


def collision_probability(generated: int, distinct: int) -> float:
    """TLC's "calculated (optimistic)" fingerprint-collision estimate.

    distinct * (generated - distinct) / 2^64: every of the d stored
    fingerprints tested against each of the g-d dedup hits, uniform over
    the 64-bit space.  Reproduces MC.out:41 exactly
    (163408 * 414328 / 2^64 = 3.7E-9)."""
    return float(distinct) * float(generated - distinct) / float(1 << 64)


if __name__ == "__main__":
    found = find_polys()
    assert found == POLYS, "embedded table out of date"
    print(f"verified {len(POLYS)} irreducible polynomials; "
          f"fpIndex {DEFAULT_FP_INDEX} -> x^64 + 0x{POLYS[DEFAULT_FP_INDEX]:x}")
