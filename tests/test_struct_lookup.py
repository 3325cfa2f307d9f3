"""A field of an enum-coded value is read by arithmetic on its code
(ISSUE 43): `LaneCompiler.look_up` classifies its host table by ALL of
its values (`compile.table_form`) and emits a literal, the code's
mixed-radix digit, or - only where the table is neither - the gather.

One module fixture builds the five bundled struct models' backends as
shipped (no engine, no XLA compile of a step: the lane walk and three
jaxprs a model) with a spy on `look_up`, so the tests below see every
table the compiled step, invariants and constraint of each model read."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from jaxtlc.struct import compile as C
from jaxtlc.struct.backend import struct_backend
from jaxtlc.struct.codec import MaskLeaf
from jaxtlc.struct.compile import LE, LaneCompiler, table_form
from jaxtlc.struct.loader import load

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = {
    "ewd840": ("EWD840.toolbox/Model_1", {}),
    "ewd998": ("EWD998.toolbox/Model_1", {}),
    "paxos": ("Paxos.toolbox/Model_1", {}),
    "paxos-sym": ("Paxos.toolbox/Model_sym", {"symmetry": True}),
    "raftrepl": ("RaftReplication.toolbox/Model_1", {}),
}
# (tables looked up, of them digits, of them constants): ISSUE 43's table
TABLES = {"ewd840": (33, 16, 17), "ewd998": (13, 6, 7),
          "paxos": (13, 6, 7), "paxos-sym": (13, 6, 7),
          "raftrepl": (19, 9, 10)}


def bare_compiler():
    """A LaneCompiler for direct `look_up` calls: it reads none of the
    spec's objects, and its tallies are its own."""
    return LaneCompiler(None, (), {}, None)


def the_gather(table, codes):
    """What `look_up` emitted for every table until ISSUE 43."""
    return jnp.asarray(table)[jnp.maximum(codes, 0)]


def gather_operands(closed):
    """The constant operand (None where it is not one) of every gather
    of a traced program, its calls' and loops' bodies included."""
    out = []

    def walk(jaxpr, consts):
        known = dict(zip(jaxpr.constvars, consts))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                op = eqn.invars[0]
                out.append(op.val if isinstance(op, jcore.Literal)
                           else known.get(op))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr, sub.consts)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub, ())

    walk(closed.jaxpr, closed.consts)
    return out


def reads_a_table(operands, tables):
    return [op for op in operands if op is not None and any(
        np.shape(op) == t.shape and np.array_equal(op, t) for t in tables)]


@pytest.fixture(scope="module")
def built():
    """name -> (backend, [(table, leaf)] distinct tables looked up at a
    value that is no lifted binder, {"inv" | "step": jaxpr}, model)."""
    seen = []
    orig = LaneCompiler.look_up

    def spy(self, table, le):
        if not le.universe:
            seen.append((table, le.leaf))
        return orig(self, table, le)

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(LaneCompiler, "look_up", spy)
        for name, (path, kw) in MODELS.items():
            del seen[:]
            model = load(os.path.join(REPO, "specs", path, "MC.cfg"))
            backend = struct_backend(model, check_deadlock=False, **kw)
            rows = jnp.zeros((8, backend.cdc.n_fields), jnp.int32)
            traced = {"inv": jax.make_jaxpr(jax.vmap(backend.inv_check))(
                rows), "step": jax.make_jaxpr(jax.vmap(backend.step))(rows)}
            if backend.constraint is not None:
                traced["constraint"] = jax.make_jaxpr(
                    backend.constraint)(rows)
            tables = list({id(t): (t, leaf) for t, leaf in seen}.values())
            out[name] = (backend, tables, traced, model)
    return out


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_table_a_bundled_model_looks_up_reads_as_the_gather_did(
        built, name):
    _, tables, _, _ = built[name]
    forms = [table_form(t)[0] for t, _ in tables]
    assert (len(tables), forms.count("arith"), forms.count("const"),
            forms.count("gather")) == TABLES[name] + (0,)
    compiler = bare_compiler()
    for table, leaf in tables:
        assert len(table) == len(leaf.values)
        # every code the packed field can hold, and the absent code
        codes = jnp.arange(-1, 1 << leaf.widths[0], dtype=jnp.int32)
        got = compiler.look_up(table, LE(codes, leaf))
        want = the_gather(table, codes)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), (name, table_form(table))
    assert compiler.lookup_counts()["gather"] == 0


@pytest.mark.parametrize("name", ["ewd840", "ewd998", "paxos"])
def test_compiled_functions_gather_from_no_lookup_table(built, name):
    """At the seed the vmapped `inv_check` of the three held 66, 26 and
    21 gathers, every one from a look-up table."""
    backend, tables, traced, _ = built[name]
    tabs = [t for t, _ in tables]
    assert gather_operands(traced["inv"]) == []
    for jaxpr in traced.values():
        assert reads_a_table(gather_operands(jaxpr), tabs) == []
    counts = backend.cdc.lookup_counts()
    assert counts["gather"] == 0 and counts["arith"] > 0
    assert counts["const"] > 0


def test_a_table_that_is_no_digit_still_gathers_and_is_counted(built):
    """Paxos's declared Message universe (216 records of four kinds) is
    no product of its fields': `mbal` of a message is a real table of a
    real leaf that only a gather reads.  (The CHOOSE ranks of the
    bundled leaves are the identity: their universes are sorted.)"""
    backend = built["paxos"][0]
    msgs = next(lay for lay in backend.cdc.layouts
                if isinstance(lay, MaskLeaf)).elem
    compiler = bare_compiler()
    mbal = compiler.field_table(
        msgs, "mbal", compiler._leaf_of_shape(
            dict((f, s) for f, s, _ in compiler._rec_fields(msgs.shape))[
                "mbal"]))
    perm = np.asarray([2, 0, 3, 1], np.int32)
    rank = compiler.choose_rank_table(msgs)
    assert table_form(mbal) == table_form(perm) == ("gather",)
    assert table_form(rank) == ("arith", 1, len(msgs.values))
    codes = jnp.arange(-1, 256, dtype=jnp.int32)

    def read(c):
        return (compiler.look_up(mbal, LE(c, msgs)),
                compiler.look_up(perm, LE(c + 0, msgs)))

    traced = jax.make_jaxpr(read)(codes)
    assert len(reads_a_table(gather_operands(traced), [mbal, perm])) == 2
    for got, table in zip(read(codes), (mbal, perm)):
        assert np.array_equal(got, the_gather(table, codes))
    # the traced pair and the eager pair: four distinct (table, value)s
    assert compiler.lookup_counts() == {"const": 0, "arith": 0, "gather": 4}


U = np.arange


@pytest.mark.parametrize("table,form", [
    # non-power-of-two radices, s > 1, the leading digit (no `% r`)
    ((U(125) // 5) % 5, ("arith", 5, 5)),
    ((U(125) // 25) % 5, ("arith", 25, 5)),
    ((U(27) // 3) % 3, ("arith", 3, 3)),
    ((U(27) // 9) % 3, ("arith", 9, 3)),
    (U(27) % 3, ("arith", 1, 3)),
    # powers of two: shift and mask; a bool table stays bool
    ((U(256) // 16) % 2 == 1, ("arith", 16, 2)),
    ((U(64) // 4) % 4, ("arith", 4, 4)),
    # a universe that ends inside a digit's period
    ((U(7) // 2) % 3, ("arith", 2, 3)),
    # the identity
    (U(5), ("arith", 1, 5)),
    # constants: presence, an absent field, one code
    (np.ones(256, bool), ("const",)),
    (np.full(8, -1), ("const",)),
    (np.zeros(1, np.int32), ("const",)),
    # near misses
    ((U(125) // 5) % 5 + 1, ("gather",)),
    (np.where(U(27) == 26, 0, (U(27) // 3) % 3), ("gather",)),
    # a predicate over at most 32 codes that is no digit: a bit test
    # on a constant (ISSUE 45: `m \in Message` for a channel's slot)
    ((U(8) // 2) % 2 == 0, ("bits", 0b00110011)),
    (np.isin(U(24), [0, 1, 5, 8, 11, 23]),
     ("bits", sum(1 << i for i in (0, 1, 5, 8, 11, 23)))),
    (np.isin(U(33), [0, 1, 5, 32]), ("gather",)),
    (np.asarray([0, -1, 0, -1]), ("gather",)),
    (np.asarray([0, 2, 4, 6]), ("gather",)),
    (np.asarray([0, 0, 0, 5]), ("gather",)),
])
def test_forms_of_synthetic_tables_and_their_reads(table, form):
    table = np.asarray(table)
    if table.dtype != bool:
        table = table.astype(np.int32)
    assert table_form(table) == form
    compiler = bare_compiler()
    # absent (-1), every code, and codes past the universe
    codes = jnp.arange(-1, 2 * len(table) + 3, dtype=jnp.int32)
    got = compiler.look_up(table, LE(codes, None))
    want = the_gather(table, codes)
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    counts = compiler.lookup_counts()
    # a bit test is arithmetic on the code, and counted as such
    tallied = "arith" if form[0] == "bits" else form[0]
    assert counts[tallied] == 1 and sum(counts.values()) == 1
    # a second read of the same value is the memo's: not counted again
    assert compiler.look_up(table, LE(codes, None)) is got
    assert compiler.lookup_counts() == counts


def test_a_retrace_restarts_its_functions_tally(built):
    """The counters are of each built function's LAST trace: a second
    trace of a predicate adds nothing to them."""
    backend, _, _, model = built["ewd998"]
    before = backend.cdc.lookup_counts()
    fn = backend.cdc.compile_predicate(model.invariants["Inv"])
    once = []
    for rows in (4, 8):
        jax.eval_shape(fn, jax.ShapeDtypeStruct(
            (rows, backend.cdc.n_fields), jnp.int32))
        once.append(backend.cdc.lookup_counts())
    assert once[0] == once[1] and tuple(once[0]) == C.LOOKUP_FORMS
    assert once[0]["arith"] > before["arith"]
    assert once[0]["gather"] == 0
