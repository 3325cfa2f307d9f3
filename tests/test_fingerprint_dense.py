"""ROADMAP M10's guard (ISSUE 45): the fingerprint polynomials are DENSE.

FP64 is GF(2)-affine in a state's bits, so two states whose XOR is a
multiple of the polynomial (of degree under the state's width) share a
fingerprint.  The table until PR 45 held x^64 plus a dozen low terms
(index 51: 0xc8b), whose low multiples are shifted copies of itself - a
pattern of seven bits that a dense product space past 64 bits is full of:
a run ended `ok` short of states.  Held here: the table's provenance and
density; on two dense state sets past 64 bits under their real codecs -
LamportMutex's 150-bit rows and EWD998 laid out at 87 bits - as many
`fp64_host` values as states, where the old entry loses some of the
second; at <= 64 bits the map is injective for any entry; the device
matmul equals the host on the dense rows.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from jaxtlc.engine import fingerprint as fp
from jaxtlc.struct.codec import StructCodec
from jaxtlc.struct.loader import load
from jaxtlc.struct.shapes import constraint_bounds, infer_shapes, typeok_hints

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))

OLD_ENTRY_51 = 0xc8b  # the table's default entry until PR 45
EWD998_SAMPLE = 150_000


def codec_of(model, **kw):
    s = model.system
    kept = constraint_bounds(s.ev, model.constraints, s.variables)
    shapes = infer_shapes(
        s.ev, s.variables, s.init_ast, s.next_ast,
        hints=typeok_hints(s.ev, model.invariants, s.variables), kept=kept,
        seq_caps=list(model.seq_caps), **kw)
    return shapes, kept


def host_fps(fields, widths, fp_index=fp.DEFAULT_FP_INDEX):
    """fp64_host of every row of [n, F] field codes, through its affine
    form (basis read off fp64_host bit by bit; a sample of rows checked
    against the bit loop itself)."""
    bits = np.stack([(fields[:, j] >> b) & 1
                     for j, w in enumerate(widths) for b in range(w)],
                    axis=1).astype(np.uint64)
    n, nbits = bits.shape
    const = fp.fp64_host(0, nbits, fp_index)
    acc = np.full(n, const, np.uint64)
    for i in range(nbits):
        acc ^= bits[:, i] * np.uint64(
            fp.fp64_host(1 << i, nbits, fp_index) ^ const)
    for r in np.random.default_rng(1).integers(0, n, 8):
        m = sum(int(b) << i for i, b in enumerate(bits[r]))
        assert fp.fp64_host(m, nbits, fp_index) == int(acc[r])
    return acc


@pytest.fixture(scope="module")
def lamport_rows():
    """The 70,472 kept states of LamportMutex at N = 3, maxClock = 4
    (the plain reference's search) as field codes of the model's own
    codec: 150 bits, five words."""
    import lamportmutex as ref

    model = load(os.path.join(REPO, "specs", "LamportMutex.toolbox",
                              "Model_1", "MC.cfg"), {"maxClock": 4})
    shapes, kept = codec_of(model)
    cdc = StructCodec(model.system.variables, shapes,
                      structural=frozenset(b.var for b in kept))
    _, seen, _ = ref.search(3, 4)

    def conv(s):
        clock, req, ack, net, crit = s
        return (clock, req, tuple(frozenset(x + 1 for x in a) for a in ack),
                tuple(tuple(tuple((("clock", c), ("type", k))
                                  for k, c in ch) for ch in row)
                      for row in net),
                frozenset(x + 1 for x in crit))

    return cdc, np.stack([cdc.encode(conv(s)) for s in seen])


@pytest.fixture(scope="module")
def ewd998_rows():
    """The first EWD998_SAMPLE kept states of EWD998 at N = 3 in
    breadth-first order, laid out at 87 bits (the open side of every
    constrained leaf capped far out: the layout of PERF.md section 7-17a,
    where a run lost states under the old table)."""
    import ewd998 as ref

    model = load(os.path.join(REPO, "specs", "EWD998.toolbox", "Model_1",
                              "MC.cfg"))
    shapes, _ = codec_of(model, open_side_factor=1 << 16)
    cdc = StructCodec(model.system.variables, shapes)
    assert cdc.nbits == 87
    bounds = dict(counter=3, pending=3, token_q=9)
    inits = ref.initial_states(3)
    seen, order, frontier = set(inits), list(inits), list(inits)
    while frontier and len(order) < EWD998_SAMPLE:
        nxt = []
        for s in frontier:
            for _, t in ref.successors(s, 3):
                if t not in seen and ref.in_constraint(t, bounds):
                    seen.add(t)
                    order.append(t)
                    nxt.append(t)
        frontier = nxt

    def pairs(t):
        return tuple(enumerate(t))

    def conv(s):
        active, color, counter, pending, pos, q, tcolor = s
        return (pairs(active),
                pairs(tuple("black" if c else "white" for c in color)),
                pairs(counter), pairs(pending),
                (("color", "black" if tcolor else "white"), ("pos", pos),
                 ("q", q)))

    return cdc, np.stack([cdc.encode(conv(s))
                          for s in order[:EWD998_SAMPLE]])


def test_the_table_is_what_the_seeded_search_gives_and_is_dense():
    assert len(fp.POLYS) == len(set(fp.POLYS)) == 131
    assert fp.find_polys(4) == fp.POLYS[:4]
    for c in fp.POLYS:
        assert c & 1 and c <= fp.MASK64
        assert 24 <= bin(c).count("1") <= 40
    for idx in (0, 1, 50, fp.DEFAULT_FP_INDEX, 52, 129, 130):
        assert fp.is_irreducible((1 << 64) | fp.POLYS[idx])
    # a sparse entry passes the same test of irreducibility: density is
    # the table's own property, and the old default was irreducible too
    assert fp.is_irreducible((1 << 64) | OLD_ENTRY_51)


def test_lamportmutex_rows_have_as_many_fingerprints_as_states(
        lamport_rows):
    cdc, fields = lamport_rows
    assert (cdc.nbits, cdc.n_words, len(fields)) == (150, 5, 70472)
    assert len(np.unique(fields, axis=0)) == len(fields)
    assert len(np.unique(host_fps(fields, cdc.widths))) == len(fields)


def test_ewd998_at_87_bits_loses_no_state_where_the_old_entry_did(
        ewd998_rows, monkeypatch):
    cdc, fields = ewd998_rows
    assert len(np.unique(fields, axis=0)) == len(fields) == EWD998_SAMPLE
    assert len(np.unique(host_fps(fields, cdc.widths))) == len(fields)
    # the witness: the same rows under the entry the table had
    old = list(fp.POLYS)
    old[fp.DEFAULT_FP_INDEX] = OLD_ENTRY_51
    monkeypatch.setattr(fp, "POLYS", old)
    assert len(np.unique(host_fps(fields, cdc.widths))) < len(fields)


@pytest.mark.parametrize("nbits", [64, 50, 33])
@pytest.mark.parametrize("fp_index", [0, 17, fp.DEFAULT_FP_INDEX, 130])
def test_at_most_64_bits_the_map_is_injective(fp_index, nbits):
    """The basis vectors of the affine map are linearly independent over
    GF(2): no two messages of `nbits` <= 64 bits share a fingerprint,
    whatever irreducible entry divides."""
    const = fp.fp64_host(0, nbits, fp_index)
    pivots = {}  # leading bit -> reduced vector
    for i in range(nbits):
        v = fp.fp64_host(1 << i, nbits, fp_index) ^ const
        while v and v.bit_length() in pivots:
            v ^= pivots[v.bit_length()]
        assert v, f"basis vector {i} depends on the ones before it"
        pivots[v.bit_length()] = v


def test_device_matmul_equals_host_on_the_dense_rows(lamport_rows):
    cdc, fields = lamport_rows
    take = np.random.default_rng(3).choice(len(fields), 4096, replace=False)
    words = cdc.pack(jnp.asarray(fields[take]))
    lo, hi = fp.fp64_words_mxu(words, cdc.nbits)
    got = np.asarray(lo).astype(np.uint64) | (
        np.asarray(hi).astype(np.uint64) << np.uint64(32))
    assert np.array_equal(got, host_fps(fields[take], cdc.widths))
