"""Device scopes made readable (jaxtlc/obs/scopes.py, ISSUE 37): the
`op_name` -> scope chain parser, a real table from each route's small
engine (every device scope PERF.md section 3 lists must be in its
route's table: the test that fails when a scope is dropped), the
reduction on synthetic planes, laziness, and `-xprof` end to end.

Each route's check runs once (module fixtures) with the engine kept, as
a process outside the test suite keeps it: the tables are those of the
executables `runtime.aot_build` registered.
"""

import io
import json
import os

import pytest

from jaxtlc import runtime
from jaxtlc.api import CheckRequest, run_check
from jaxtlc.obs import journal as jr
from jaxtlc.obs import scopes
from jaxtlc.obs.schema import validate_event

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KUBEAPI = os.path.join(REPO, "specs", "KubeAPI.toolbox", "Model_1", "MC.cfg")
TWOPHASE = os.path.join(REPO, "specs", "TwoPhase.toolbox")
FF = {"REQUESTS_CAN_FAIL": False, "REQUESTS_CAN_TIMEOUT": False}

COMMIT = ("jaxtlc.expand", "jaxtlc.pack_fp", "jaxtlc.dedup",
          "jaxtlc.fpset", "jaxtlc.enqueue", "jaxtlc.level")
# route -> (its request, the scopes PERF.md section 3 lists for it)
ROUTES = {
    "hand": (dict(config=KUBEAPI, frontend="hand", chunk=128,
                  qcap=1 << 12, fpcap=1 << 14, constants=FF), COMMIT),
    "struct": (dict(config=os.path.join(TWOPHASE, "Model_1", "MC.cfg"),
                    frontend="struct", chunk=256, nodeadlock=True),
               COMMIT + ("jaxtlc.step.struct",)),
    "reduced": (dict(config=os.path.join(TWOPHASE, "Model_sym", "MC.cfg"),
                     frontend="struct", chunk=256, nodeadlock=True,
                     symmetry=True),
                COMMIT + ("jaxtlc.step.struct", "jaxtlc.canon")),
    "constrained": (dict(config=os.path.join(
        REPO, "specs", "EWD998.toolbox", "Model_1", "MC.cfg"),
        frontend="struct", chunk=256, qcap=1 << 13, fpcap=1 << 15,
        constants={"N": 2}),
        COMMIT + ("jaxtlc.step.struct", "jaxtlc.constraint")),
    # a cfg whose PROPERTY is an action property (ISSUE 48): judged on
    # every generated edge inside the expand stage
    "refinement": (dict(config=os.path.join(
        REPO, "specs", "PaxosCommit.toolbox", "Model_1", "MC.cfg"),
        frontend="struct", chunk=256, qcap=1 << 12, fpcap=1 << 14,
        constants={"RM": frozenset({"r1"})}),
        COMMIT + ("jaxtlc.step.struct", "jaxtlc.actionprop")),
    "mesh": (dict(config=KUBEAPI, frontend="hand", sharded=4, chunk=128,
                  qcap=1 << 11, fpcap=1 << 13,
                  constants=dict(FF, N_RECONCILERS=1, N_BINDERS=1)),
             COMMIT + ("jaxtlc.route", "jaxtlc.verdict_return",
                       "jaxtlc.fence", "jaxtlc.compact")),
}


def check(**kw):
    out = io.StringIO()
    got = run_check(CheckRequest(workers="cpu", noTool=True, out=out,
                                 err=out, **kw))
    assert got.verdict == "ok", out.getvalue()
    return got, out.getvalue()


@pytest.fixture(scope="module")
def kept():
    """Engines kept, as outside the test suite (tests/conftest.py sets
    the debug variable under which nothing is)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("JAXTLC_DEBUG_DONATION", raising=False)
        runtime.clear_engine_cache()
        yield
        runtime.clear_engine_cache()


# -- the chain parser ------------------------------------------------------


@pytest.mark.parametrize("op_name,chain", [
    ("jit(f)/jaxtlc.expand/jaxtlc.dedup/jit(sort)/sort",
     ("jaxtlc.expand", "jaxtlc.dedup")),
    ("jit(<lambda>)/while/body/jaxtlc.dedup/while/body/jaxtlc.fpset/and",
     ("jaxtlc.dedup", "jaxtlc.fpset")),
    ("jit(f)/jit(main)/jaxtlc.expand/jaxtlc.step.struct/jit(step)/mul",
     ("jaxtlc.expand", "jaxtlc.step.struct")),
    ("jit(f)/while/body/vmap(jaxtlc.level)/jit(inv)/gather",
     ("jaxtlc.level",)),
    ("jit(f)/jaxtlc.expand/jaxtlc.canon/jit(g)/jaxtlc.expand/jaxtlc.canon/"
     "mul", ("jaxtlc.expand", "jaxtlc.canon")),
    ("jit(f)/jaxtlc.expand/jaxtlc.dedup/jaxtlc.expand/add",
     ("jaxtlc.dedup", "jaxtlc.expand")),
    ("jit(f)/while/body/closed_call/add", ()),
    ("reduce_window_sum", ()),
    ("", ()),
])
def test_chain_of(op_name, chain):
    assert scopes.chain_of(op_name) == chain


HLO = '''HloModule jit_seg, is_scheduled=true

%fused_computation.1 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %add.1 = u32[8]{0} add(%p, %p), metadata={op_name="jit(seg)/while/body/jaxtlc.expand/jaxtlc.pack_fp/add"}
}

%fused_computation.2 (q: u32[8]) -> (u32[8], u32[8]) {
  %q = u32[8]{0} parameter(0)
  %not.2 = u32[8]{0} not(%q), metadata={op_name="jit(seg)/while/body/jaxtlc.dedup/jaxtlc.fpset/not"}
  ROOT %tuple.2 = (u32[8]{0}, u32[8]{0}) tuple(%not.2, %q)
}

%fused_computation.6 (r: u32[8]) -> (u32[8], u32[8]) {
  %r = u32[8]{0} parameter(0)
  %not.6 = u32[8]{0} not(%r), metadata={op_name="jit(seg)/while/body/jaxtlc.dedup/jaxtlc.fpset/not"}
  %neg.6 = u32[8]{0} negate(%r), metadata={op_name="jit(seg)/while/body/jaxtlc.dedup/neg"}
  ROOT %tuple.6 = (u32[8]{0}, u32[8]{0}) tuple(%not.6, %neg.6)
}

%fused_computation.7 (s: u32[8]) -> (u32[8], u32[8]) {
  %s = u32[8]{0} parameter(0)
  %not.7 = u32[8]{0} not(%s), metadata={op_name="jit(seg)/while/body/jaxtlc.level/not"}
  %neg.7 = u32[8]{0} negate(%s), metadata={op_name="jit(seg)/while/body/neg"}
  ROOT %tuple.7 = (u32[8]{0}, u32[8]{0}) tuple(%not.7, %neg.7)
}

%body (c: (u32[8])) -> (u32[8]) {
  %c = (u32[8]{0}) parameter(0)
  %gte = u32[8]{0:T(128)S(1)} get-tuple-element(%c), index=0
  %fusion.1 = u32[8]{0:T(128)S(1)} fusion(%gte), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = (u32[8]{0}, u32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.6 = (u32[8]{0}, u32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.6
  %fusion.7 = (u32[8]{0}, u32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.7
  %copy.3 = u32[8]{0} copy(%fusion.1)
  %sort.4 = u32[8]{0} sort(%copy.3), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(seg)/while/body/jaxtlc.dedup/jit(sort)/sort"}
  ROOT %tuple.5 = (u32[8]{0}) tuple(%sort.4)
}

ENTRY %main (a: u32[8]) -> (u32[8]) {
  %a = u32[8]{0} parameter(0)
  %t = (u32[8]{0}) tuple(%a)
  ROOT %while.9 = (u32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(seg)/while"}
}
'''


def test_parse_hlo_text_fusions_take_their_fused_root():
    t = scopes.parse_hlo_text(HLO)
    assert t["module"] == "jit_seg"
    row = {k: (tuple(t["chains"][v[0]]), v[1], v[3])
           for k, v in t["instructions"].items()}
    agreed = {k for k, v in t["instructions"].items() if v[4]}
    # no metadata of its own: the fused root's
    assert row["fusion.1"] == (("jaxtlc.expand", "jaxtlc.pack_fp"),
                               "fusion", 1)
    # a bare tuple for a root: the one chain its named instructions
    # stand under, and the row says it was placed that way
    assert row["fusion.2"] == (("jaxtlc.dedup", "jaxtlc.fpset"),
                               "fusion", 1)
    assert agreed == {"fusion.2"}
    # two scopes in one fusion, or a scope beside none: unscoped, never
    # a guess
    assert row["fusion.6"] == ((), "fusion", 1)
    assert row["fusion.7"] == ((), "fusion", 1)
    assert row["sort.4"] == (("jaxtlc.dedup",), "sort", 1)
    assert row["copy.3"] == ((), "copy", 1)
    assert row["while.9"] == ((), "while", 1)
    assert row["add.1"][2] == 0 and row["not.2"][2] == 0  # fused
    assert t["instructions"]["fusion.1"][2] == "u32[8]{0:T(128)S(1)}"
    # of what runs on its own (fusion x4, copy, sort, while): three
    assert scopes.cover(t) == (3, 7)


# -- a real table from each route's engine ---------------------------------


@pytest.fixture(scope="module", params=list(ROUTES))
def route_tables(request, kept):
    """One small check of the route; the tables of what it built."""
    runtime.clear_engine_cache()
    req, want = ROUTES[request.param]
    check(**req)
    return request.param, want, scopes.tables()


def test_every_scope_of_the_route_is_in_its_table(route_tables):
    route, want, tables = route_tables
    assert tables, "aot_build registered nothing"
    table = max(tables, key=lambda t: len(t["instructions"]))
    have = {s for chain in table["chains"] for s in chain}
    assert set(want) <= have, (route, sorted(set(want) - have))
    # the innermost scope is what time is attributed to
    chains = {tuple(c) for c in table["chains"]}
    assert ("jaxtlc.dedup", "jaxtlc.fpset") in chains
    assert ("jaxtlc.expand", "jaxtlc.pack_fp") in chains
    scoped, total = scopes.cover(table)
    assert 0 < scoped <= total
    # every row: a chain index, an opcode, a bounded shape, runs or
    # not, placed by its fused computation's agreement or not
    for chain, opcode, shape, runs, agreed in \
            table["instructions"].values():
        assert 0 <= chain < len(table["chains"]) and runs in (0, 1)
        assert agreed in (0, 1) and (not agreed or opcode == "fusion")
        assert len(shape) <= scopes.SHAPE_CHARS and isinstance(opcode, str)
    whiles = [r for r in table["instructions"].values() if r[1] == "while"]
    assert whiles and all(r[3] == 1 for r in whiles)


def test_the_liveness_programs_bring_their_scopes(kept):
    """A struct check with a PROPERTY (ISSUE 41) keeps four programs:
    the segment engine and the liveness route's three, each under a
    device scope of its own - `-xprof` lists them like the others."""
    runtime.clear_engine_cache()
    check(config=os.path.join(REPO, "specs", "EWD840.toolbox", "Model_1",
                              "MC.cfg"),
          frontend="struct", chunk=256, qcap=1 << 12, fpcap=1 << 14,
          constants={"N": 3})
    assert runtime.engine_cache_stats()["misses"] == 4
    tables = scopes.tables()
    outer = {chain[0] for t in tables for chain in t["chains"] if chain}
    assert {"jaxtlc.live.enumerate", "jaxtlc.live.capture",
            "jaxtlc.live.fixpoint", "jaxtlc.expand"} <= outer
    # the capture runs the backend's own step, inside its scope
    chains = {tuple(c) for t in tables for c in t["chains"]}
    assert ("jaxtlc.live.capture", "jaxtlc.step.struct") in chains
    # a second check of the process builds nothing
    check(config=os.path.join(REPO, "specs", "EWD840.toolbox", "Model_1",
                              "MC.cfg"),
          frontend="struct", chunk=256, qcap=1 << 12, fpcap=1 << 14,
          constants={"N": 3})
    stats = runtime.engine_cache_stats()
    assert (stats["hits"], stats["misses"], stats["evictions"]) == (4, 4, 0)


# -- the reduction on synthetic planes --------------------------------------


def table(module, **rows):
    chains = [[]]
    out = {}
    for name, (chain, opcode, *agreed) in rows.items():
        if list(chain) not in chains:
            chains.append(list(chain))
        out[name] = [chains.index(list(chain)), opcode, "u32[8]", 1,
                     int(bool(agreed))]
    return dict(module=module, chains=chains, instructions=out)


SEG = table("jit_seg", **{
    "while.9": ((), "while"),
    "fusion.1": (("jaxtlc.expand", "jaxtlc.pack_fp"), "fusion"),
    "fusion.2": (("jaxtlc.dedup", "jaxtlc.fpset"), "fusion", "agreed"),
    "sort.4": (("jaxtlc.dedup",), "sort"),
    "copy.3": ((), "copy")})
S = 1e9  # a second in the trace's ns


def plane(name, ops, modules=()):
    return dict(name=name, lines=[
        dict(name="XLA Modules", events=list(modules)),
        dict(name="XLA Ops", events=list(ops)),
        dict(name="Async XLA Ops", events=[(0.0, 9 * S, "%copy-start.1")])])


def body(t0):
    """One `while` of 4 s and its body, from t0 seconds."""
    return [(t0 * S, (t0 + 4) * S, "%while.9 = (u32[8]) while(...)"),
            (t0 * S, (t0 + 1) * S, "%fusion.1 = u32[8] fusion(...)"),
            ((t0 + 1) * S, (t0 + 2.5) * S, "%fusion.2 fusion"),
            ((t0 + 2.5) * S, (t0 + 3) * S, "%sort.4 sort"),
            ((t0 + 3) * S, (t0 + 3.5) * S, "%copy.3 copy")]


def rows_of(reduced):
    return {r["scope"]: r for r in reduced["scopes"]}


def test_reduce_a_while_and_its_body():
    mods = [(0.0, 4 * S, "jit_seg(123)")]
    r = scopes.reduce_planes([plane("/device:TPU:0", body(0), mods),
                              plane("/host:CPU", body(0))], [SEG])
    assert (r["n_devices"], r["busy_s"], r["window_s"]) == (1, 4.0, 4.0)
    rows = rows_of(r)
    assert rows["jaxtlc.pack_fp"]["own_s"] == 1.0
    assert rows["jaxtlc.fpset"]["own_s"] == 1.5
    assert rows["jaxtlc.dedup"]["own_s"] == 0.5
    # the while's own time is what its body leaves: 4 - 3.5, unscoped
    # beside the copy's 0.5
    assert rows["unscoped"]["own_s"] == r["unscoped_s"] == 1.0
    assert r["unmatched_s"] == 0.0 and "unmatched" not in rows
    assert sum(x["own_s"] for x in r["scopes"]) == r["busy_s"]
    # fusion.2 was placed by its fused instructions' agreement: stated
    assert r["fallback_s"] == 1.5
    assert rows["jaxtlc.fpset"]["pct_of_busy"] == 37.5
    # inclusive: dedup holds fpset; by chain, outermost first
    assert rows["jaxtlc.dedup"]["incl_s"] == 2.0
    assert r["chains"] == {"jaxtlc.dedup": 2.0,
                           "jaxtlc.dedup/jaxtlc.fpset": 1.5,
                           "jaxtlc.expand": 1.0,
                           "jaxtlc.expand/jaxtlc.pack_fp": 1.0}
    assert [x["scope"] for x in r["scopes"]][0] == "jaxtlc.fpset"
    text = "\n".join(scopes.render(r))
    assert "agreeing instructions 1.5000 s" in text
    assert ("inclusive s of the nested chains: jaxtlc.dedup/jaxtlc.fpset "
            "1.5000; jaxtlc.expand/jaxtlc.pack_fp 1.0000") in text
    assert sorted(x[:2] for x in rows["unscoped"]["top"]) == [
        ["copy.3", "copy"], ["while.9", "while"]]
    assert rows["jaxtlc.fpset"]["events"] == 1


def test_reduce_clips_to_the_window():
    r = scopes.reduce_planes([plane("/device:TPU:0", body(0))], [SEG],
                             window=(0.5 * S, 2 * S))
    rows = rows_of(r)
    assert (r["window_s"], r["busy_s"]) == (1.5, 1.5)
    assert rows["jaxtlc.pack_fp"]["own_s"] == 0.5
    assert rows["jaxtlc.fpset"]["own_s"] == 1.0
    assert "jaxtlc.dedup" not in rows and r["unscoped_s"] == 0.0


def test_reduce_two_devices_mean_and_each_device():
    twice = body(0) + body(4)
    r = scopes.reduce_planes([plane("/device:TPU:0", body(0)),
                              plane("/device:TPU:1", twice)], [SEG],
                             window=(0.0, 8 * S))
    assert r["n_devices"] == 2 and r["busy_s"] == 6.0
    rows = rows_of(r)
    assert rows["jaxtlc.fpset"]["own_s"] == 2.25  # (1.5 + 3.0) / 2
    assert rows["jaxtlc.fpset"]["events"] == 3 and r["fallback_s"] == 2.25
    assert [d["device"] for d in r["devices"]] == ["/device:TPU:0",
                                                   "/device:TPU:1"]
    assert [d["busy_s"] for d in r["devices"]] == [4.0, 8.0]
    assert r["devices"][1]["own_s"]["jaxtlc.fpset"] == 3.0


def test_reduce_one_name_in_two_modules_and_an_unmatched_event():
    other = table("jit_init", **{"fusion.1": (("jaxtlc.level",), "fusion")})
    mods = [(0.0, 4 * S, "jit_seg(1)"), (4 * S, 6 * S, "jit_init(2)")]
    ops = body(0) + [(4 * S, 5 * S, "%fusion.1 fusion"),
                     (5 * S, 6 * S, "%eager.7 fusion")]
    r = scopes.reduce_planes([plane("/device:TPU:0", ops, mods)],
                             [SEG, other])
    rows = rows_of(r)
    # the module that ran it decides; no table knows eager.7
    assert rows["jaxtlc.pack_fp"]["own_s"] == 1.0
    assert rows["jaxtlc.level"]["own_s"] == 1.0
    assert rows["unmatched"]["own_s"] == r["unmatched_s"] == 1.0
    assert rows["unmatched"]["top"][0][0] == "eager.7"
    # without the modules line the two tables disagree on fusion.1:
    # unmatched, never a guess; what one table alone knows still matches
    r = scopes.reduce_planes([plane("/device:TPU:0", ops)], [SEG, other])
    rows = rows_of(r)
    assert "jaxtlc.pack_fp" not in rows and "jaxtlc.level" not in rows
    assert rows["unmatched"]["own_s"] == 3.0
    assert rows["jaxtlc.fpset"]["own_s"] == 1.5


def test_reduce_nothing():
    r = scopes.reduce_planes([plane("/host:CPU", body(0))], [SEG])
    assert (r["n_devices"], r["busy_s"], r["scopes"]) == (0, 0.0, [])
    assert r["fallback_s"] == 0.0
    assert "no device operation" in "\n".join(scopes.render(r))


# -- laziness, the operator's door -------------------------------------------


def test_an_unprofiled_check_parses_nothing(kept, monkeypatch):
    calls = []
    real = scopes.table_of
    monkeypatch.setattr(scopes, "table_of",
                        lambda c: calls.append(c) or real(c))
    runtime.clear_engine_cache()
    check(**ROUTES["struct"][0])
    check(**ROUTES["struct"][0])  # a kept engine answers
    assert runtime.engine_cache_stats()["hits"] >= 1
    assert calls == []
    first = scopes.tables()
    assert len(calls) >= 1 and first
    n = len(calls)
    assert scopes.tables() == first and len(calls) == n  # kept


def test_xprof_ends_in_a_table(kept, tmp_path):
    """`-xprof DIR` through api.run_check on the struct route: the
    sidecar, ONE schema-valid `device_scopes` event, the table under the
    verdict, and the same table again from DIR alone."""
    runtime.clear_engine_cache()
    req = ROUTES["struct"][0]
    check(**req)  # build outside the profile: a profiled build is slow
    trace_dir, journal = str(tmp_path / "xp"), str(tmp_path / "j.jsonl")
    got, text = check(xprof=trace_dir, journal=journal, **req)
    events = jr.read(journal)  # validates every line
    mine = [e for e in events if e["event"] == "device_scopes"]
    assert len(mine) == 1
    ev = validate_event(mine[0])
    assert ev["t0"] < ev["t1"] <= ev["t"]
    assert ev["sidecar"] == os.path.join(trace_dir, scopes.SIDECAR)
    with open(ev["sidecar"]) as f:
        side = json.load(f)
    have = {s for t in side["tables"] for c in t["chains"] for s in c}
    assert set(ROUTES["struct"][1]) <= have
    assert scopes.find_xplane(trace_dir) is not None
    assert "Device time by scope:" in text
    assert text.index("Device time by scope:") > text.index(
        "Model checking completed")
    # after the fact, from DIR alone (no engine in this call): the event
    again = scopes.reduce_dir(trace_dir)
    assert {k: again[k] for k in again} == {k: ev[k] for k in again}
    assert scopes.main([trace_dir]) == 0


def test_a_table_that_cannot_be_written_does_not_cost_the_verdict(
        kept, tmp_path):
    """DIR's sidecar cannot be written (a directory stands where its
    temporary file goes; a full or read-only disk behaves the same):
    the check still ends in its verdict and its `final` event, with a
    warning in place of the table."""
    req = ROUTES["struct"][0]
    check(**req)
    trace_dir, journal = tmp_path / "xp", str(tmp_path / "j.jsonl")
    (trace_dir / (scopes.SIDECAR + ".tmp")).mkdir(parents=True)
    got, text = check(xprof=str(trace_dir), journal=journal, **req)
    assert "Model checking completed" in text
    assert "Warning: no device scope table" in text
    assert "Device time by scope:" not in text
    kinds = [e["event"] for e in jr.read(journal)]
    assert "device_scopes" not in kinds and kinds[-1] == "final"
