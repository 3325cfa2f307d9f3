"""Lamport's distributed mutual exclusion as `tlaplus/Examples` publishes
it (ISSUE 45): specs/LamportMutex.toolbox/Model_1, the first model whose
state is per-pair FIFO channels of records - a function of functions of
sequences of records - through the struct frontend on the normal path,
bounded by its cfg's `CONSTRAINT ClockConstraint`.  Three
implementations agree - the device engine, the structural interpreter
(struct/oracle.py) and the plain reference
(benchmark/reference/lamportmutex.py) - at maxClock = 3 on the CPU (the
published maxClock = 6, 724,274 kept states, is the benchmark cell's);
the parser's four forms load; a sequence's capacity is what the spec
declares or a first guess, trapped and widened by a rung; an Append on a
full channel is a trap, never a shorter sequence.
"""

import io
import json
import os
import shutil
import sys

import numpy as np
import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.struct.loader import load
from jaxtlc.struct.oracle import bfs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = os.path.join(REPO, "specs", "LamportMutex.toolbox", "Model_1")
CFG = os.path.join(MODEL, "MC.cfg")
sys.path.insert(0, os.path.join(REPO, "benchmark", "reference"))

C3 = dict(generated=41533, distinct=10209, depth=31, discarded=10042,
          action_generated={"Request": 6275, "ReceiveRequest": 16793,
                            "ReceiveAck": 9267, "Enter": 1416,
                            "Exit": 708, "ReceiveRelease": 7073})
GEOMETRY = dict(chunk=256, qcap=16384, fpcap=65536)


def engine(cfg=CFG, journal=None, **kw):
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=cfg, frontend="struct", workers="cpu", noTool=True,
        out=out, err=out, journal=journal,
        **{**dict(constants={"maxClock": 3}), **GEOMETRY, **kw}))
    assert o.verdict == "ok", out.getvalue()[-600:]
    return o.result


def five(r):
    return dict(generated=r.generated, distinct=r.distinct, depth=r.depth,
                discarded=r.constraint_discarded,
                action_generated=dict(r.action_generated))


@pytest.fixture(scope="module")
def unbounded_cfg(tmp_path_factory):
    """The model with BoundedNetwork taken off the cfg's INVARIANTS:
    nothing declares the channels' capacity."""
    d = tmp_path_factory.mktemp("lm-unbounded")
    for name in ("LamportMutex.tla", "MC.tla"):
        shutil.copy(os.path.join(MODEL, name), d / name)
    with open(CFG) as f:
        lines = [ln for ln in f if "BoundedNetwork" not in ln]
    (d / "MC.cfg").write_text("".join(lines))
    return str(d / "MC.cfg")


def test_the_shipped_files_are_the_sources_model():
    m = load(CFG)
    assert list(m.constraints) == ["ClockConstraint"]
    assert list(m.invariants) == ["TypeOK", "BoundedNetwork", "Mutex"]
    assert (m.constants["N"], m.constants["maxClock"]) == (3, 6)
    # the one override, which the module's own comment asks for
    assert m.constants["Clock"] == frozenset(range(1, 8))
    assert m.root_name == "LamportMutex"
    assert m.system.variables == ("clock", "req", "ack", "network", "crit")
    assert len(m.system.initial_states()) == 1
    # the capacity the spec declares, channel by channel
    assert sorted((b.var, b.path, b.hi) for b in m.seq_caps) == [
        ("network", (p, q), 3) for p in (1, 2, 3) for q in (1, 2, 3)]
    with open(os.path.join(MODEL, "LamportMutex.tla")) as f:
        text = f.read()
    for form in (r"\union", "SUBSET Proc", "[Proc -> [Proc -> Nat]]",
                 "Seq(Message)"):
        assert form in text


@pytest.mark.parametrize("src,env,want", [
    (r"{1, 2} \union {3}", {}, frozenset({1, 2, 3})),
    (r"{1, 2} \union {2, 3} \cup {4}", {}, frozenset({1, 2, 3, 4})),
    (r"{1} \in SUBSET {1, 2}", {}, True),
    (r"{3} \in SUBSET {1, 2}", {}, False),
    (r"f \in [{1, 2} -> [{1, 2} -> Nat]]", {"f": ((0, 1), (2, 3))}, True),
    (r"f \in [{1, 2} -> [{1, 2} -> Nat]]", {"f": ((0, 1), (2,))}, False),
    (r"s \in Seq({1, 2})", {"s": (1, 2, 2)}, True),
    (r"s \in Seq({1, 2})", {"s": (1, 3)}, False),
    (r"1 \in Nat \ {0}", {}, True),
    (r"0 \in Nat \ {0}", {}, False),
])
def test_the_parsers_four_forms_load_and_mean_what_tla_says(src, env, want):
    from jaxtlc.struct.eval import Evaluator
    from jaxtlc.struct.parser import parse_expression

    assert Evaluator({}, {}).eval(parse_expression(src), env) == want


def test_engine_interpreter_and_reference_agree_at_maxclock_3(tmp_path):
    """All five numbers, three ways: generated, distinct, depth, the six
    per-action totals and the discards."""
    import lamportmutex

    with open(os.path.join(REPO, "benchmark", "configs",
                           "lamportmutex-mc.json")) as f:
        want = lamportmutex.pins_for(json.load(f), max_clock=3)
    assert {k: want[k] for k in C3} == C3
    assert want["longest_channel"] == 3
    m = load(CFG, const_overrides={"maxClock": 3})
    host = bfs(m.system, m.invariants, check_deadlock=True,
               constraints=m.constraints)
    assert host.violations == []
    assert dict(generated=host.generated, distinct=host.distinct,
                depth=host.depth, discarded=host.discarded,
                action_generated=host.action_generated) == C3
    journal = str(tmp_path / "check.jsonl")
    r = engine(journal=journal)
    assert five(r) == C3
    assert r.constraint_rows == C3["generated"] - 1
    assert r.constraint_names == ("ClockConstraint",)
    # 27 lanes, not compacted; no trap; no table gather
    assert (r.step_lanes, r.step_slots, r.struct_traps) == (27, 27, 0)
    assert r.lookup_gather == 0
    # 6 channels of 3 slots, their capacity declared; no rung taken
    assert (r.seq_slots, r.seq_cap_from, r.seq_widen) == (18, "declared", 0)
    assert r.state_bits > 64 and r.state_words == -(-r.state_bits // 32)
    with open(journal) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    final = next(e for e in events if e["event"] == "final")
    assert (final["state_bits"], final["state_words"], final["seq_slots"],
            final["seq_cap_from"], final["seq_widen"]) == (
        r.state_bits, r.state_words, 18, "declared", 0)
    assert not [e for e in events if e["event"] == "degrade"]


def test_the_published_sizes_layout_is_dense_and_five_words():
    """No engine: the shapes and the codec of maxClock = 6.  The
    diagonal channels, which no action appends to, cost no bit; a
    message is 5 bits (its two fields' product), a channel 2 + 3 x 5."""
    from jaxtlc.struct.codec import SeqNode, StructCodec, TupNode
    from jaxtlc.struct.shapes import (
        SSeq, STup, constraint_bounds, infer_shapes, seq_summary,
        typeok_hints)

    m = load(CFG)
    s = m.system
    kept = constraint_bounds(s.ev, m.constraints, s.variables)
    shapes = infer_shapes(
        s.ev, s.variables, s.init_ast, s.next_ast,
        hints=typeok_hints(s.ev, m.invariants, s.variables), kept=kept,
        seq_caps=list(m.seq_caps))
    net = shapes["network"]
    assert isinstance(net, STup) and len(net.items) == 3
    for p, row in enumerate(net.items):
        for q, ch in enumerate(row.items):
            assert isinstance(ch, SSeq) and ch.cap == (0 if p == q else 3)
    assert seq_summary(shapes, list(m.seq_caps)) == (18, "declared", 3)
    cdc = StructCodec(s.variables, shapes,
                      structural=frozenset(b.var for b in kept))
    assert (cdc.nbits, cdc.n_words) == (150, 5)
    lay = cdc.layouts[s.variables.index("network")]
    assert isinstance(lay, TupNode)
    chans = [c for row in lay.children for c in row.children]
    assert all(isinstance(c, SeqNode) for c in chans)
    assert sorted(sum(c.widths) for c in chans) == [0] * 3 + [17] * 6
    init = s.initial_states()[0]
    assert cdc.decode(cdc.encode(init)) == init


def test_an_undeclared_capacity_is_a_guess_a_trap_and_a_rung(
        unbounded_cfg, tmp_path):
    """Nothing declares the channels' capacity: the first guess (2)
    traps at the first third message, the rung reaches 3, and the check
    that starts again counts what the declared one counts."""
    from jaxtlc.struct import cache

    m = load(unbounded_cfg, const_overrides={"maxClock": 3})
    assert m.seq_caps == () and list(m.invariants) == ["TypeOK", "Mutex"]
    journal = str(tmp_path / "check.jsonl")
    r = engine(cfg=unbounded_cfg, journal=journal)
    assert five(r) == C3
    assert (r.seq_slots, r.seq_cap_from, r.seq_widen) == (18, "guess", 1)
    assert r.struct_traps == 0  # of the check that gave the verdict
    with open(journal) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    rungs = [e for e in events if e["event"] == "degrade"]
    assert [(e["rung"], e["resource"], e["action"]) for e in rungs] == [
        ("widen", "seq_cap", "2->3")]
    assert cache._floor(cache.model_key(m), "seq_widen") == 1


def test_an_append_on_a_full_channel_is_a_trap_never_a_shorter_sequence(
        unbounded_cfg):
    """The step compiled at the first guess (capacity 2), handed a state
    whose channel 1 -> 2 is full, in which process 1 exits the critical
    section and broadcasts `rel`: the lane that appends is valid and
    flags the trap; the host encode of that successor raises."""
    from jaxtlc.struct.backend import struct_backend
    from jaxtlc.struct.codec import SeqCapError

    m = load(unbounded_cfg, const_overrides={"maxClock": 3})
    backend = struct_backend(m)
    assert backend.cdc.seq_cap_from == "guess"
    ack, req2 = (("clock", 0), ("type", "ack")), (("clock", 2), ("type",
                                                                 "req"))
    state = ((3, 3, 3), ((1, 0, 0), (0, 0, 0), (0, 0, 0)),
             (frozenset(),) * 3,
             (((), (ack, req2), ()), ((), (), ()), ((), (), ())),
             frozenset({1}))
    succs, valid, action, _, ovf = backend.step(backend.cdc.encode(state))
    fired = [backend.labels[int(a)] for a, v in zip(action, valid) if v]
    assert "Exit" in fired
    trapped = np.asarray(valid) & np.asarray(ovf)
    assert [backend.labels[int(a)]
            for a, t in zip(action, trapped) if t] == ["Exit"]
    # the lanes that do not append decode to what the interpreter gives
    host = dict(m.system.successors(state))
    for row, a, v, t in zip(np.asarray(succs), action, valid, trapped):
        if v and not t:
            assert backend.cdc.decode(row) in [
                s for lab, s in m.system.successors(state)
                if lab == backend.labels[int(a)]]
    with pytest.raises(SeqCapError):
        backend.cdc.encode(host["Exit"])


def test_preflight_names_the_constraint_of_the_unmodified_cfg():
    from jaxtlc.analysis.preflight import preflight_struct

    rep = preflight_struct(load(CFG), fp_capacity=1 << 21, chunk=4096,
                           queue_capacity=1 << 17)
    text = "\n".join(rep.constraint_lines)
    assert "CONSTRAINT ClockConstraint" in text and not rep.errors
    for p in (1, 2, 3):
        assert f"clock[{p}]: <= 6 by ClockConstraint" in text


@pytest.mark.parametrize("flags,word", [
    (dict(sharded=2), "-sharded"),
    (dict(simulate=True), "-simulate"),
])
def test_the_other_routes_go_on_refusing_the_model_by_name(flags, word):
    out = io.StringIO()
    o = run_check(CheckRequest(
        config=CFG, frontend="struct", workers="cpu", noTool=True,
        out=out, err=out, constants={"maxClock": 3}, **GEOMETRY, **flags))
    assert o.exit_code == 1 and o.result is None
    assert "ClockConstraint" in out.getvalue() and word in out.getvalue()


@pytest.mark.slow
def test_the_benchmark_cells_rung_matches_its_pins():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lamportmutex-mc.json")) as f:
        config = json.load(f)
    r = engine(constants={}, chunk=2048, qcap=131072, fpcap=2097152)
    pins = config["pins"]
    assert (r.generated, r.distinct, r.depth) == (
        pins["generated"], pins["distinct"], pins["depth"])
    assert dict(r.action_generated) == pins["action_generated"]
    assert r.constraint_discarded == 232728
