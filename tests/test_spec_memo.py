"""What is a pure function of a spec's text is computed once a process
(ISSUE 46): `struct.loader.load` keeps the loaded model under the digest
of every text it reads plus the overrides, `api._struct_preflight` keeps
the lite preflight report under the model's key and the request integers
it reads; both live in struct.cache (`text`, `model`, `preflight`).

Held here: the keys are content (a changed byte anywhere in the closure
or another override misses, the same bytes under another directory hit,
a file rewritten in place misses), a hit runs no parser and no
evaluator, an error is never kept, the spans stay and say which it was,
and a check of a kept text journals what a check of a fresh process
does - the `analysis` events field for field, the same `final` event,
the same exit code on an error-severity finding.

Host only: a four-text model (cfg, MC.tla, the module it extends, the
module that one extends) of eight kept states; the three checks that run
an engine share one tiny geometry.
"""

import io
import json
import shutil
import time

import pytest

from jaxtlc.api import CheckRequest, run_check
from jaxtlc.obs import spans
from jaxtlc.struct import cache, loader
from jaxtlc.struct.loader import StructLoadError, load
from jaxtlc.struct.parser import StructParseError

GEO = dict(chunk=16, qcap=128, fpcap=512)

TEXTS = {
    "MC.cfg": ("CONSTANT K = 3\nINIT Init\nNEXT Next\nCONSTRAINT Bound\n"
               "INVARIANT Small Vacuous\n"),
    "MC.tla": "---- MODULE MC ----\nEXTENDS Walk, TLC\n====\n",
    "Walk.tla": """---- MODULE Walk ----
EXTENDS Naturals, Lim
CONSTANT K
VARIABLES x, y
Init == x = 0 /\\ y = 0
Up == x' = x + 1 /\\ y' = y
Side == y' = 1 - y /\\ x' = x
Next == Up \\/ Side
Bound == x <= K
AlsoY == y <= 1
Small == x <= K + Slack
Vacuous == Slack = 0
====
""",
    "Lim.tla": "---- MODULE Lim ----\nEXTENDS Naturals\nSlack == 0\n====\n",
}


def model_dir(path, edits=None):
    """TEXTS written under `path` (`edits`: file name -> its text, None
    to leave the file out)."""
    path.mkdir(parents=True, exist_ok=True)
    for name, text in {**TEXTS, **(edits or {})}.items():
        if text is None:
            (path / name).unlink(missing_ok=True)
        else:
            (path / name).write_text(text)
    return str(path / "MC.cfg")


def counts(memo):
    s = cache.stats()[memo]
    return s["hits"], s["misses"], s["size"]


@pytest.fixture
def fresh():
    """No test of this file sees what another kept.  Yields
    since(memo) -> (hits, misses) of this test alone and the memo's
    size: clear() drops the entries, the counters are the process's."""
    cache.clear()
    base = {m: counts(m) for m in ("text", "model", "preflight")}

    def since(memo):
        h, m, size = counts(memo)
        return h - base[memo][0], m - base[memo][1], size

    yield since
    cache.clear()


def load_spans(since):
    """(name, attrs) of the loader's spans closed since `since`."""
    return [(r.name, dict(r.attrs)) for r in spans.snapshot(since=since)
            if r.name.startswith("build.struct.")]


def test_a_second_load_returns_the_kept_model_and_parses_nothing(
        tmp_path, fresh, monkeypatch):
    cfg = model_dir(tmp_path / "a")
    first = load(cfg)
    calls = []
    from jaxtlc.struct.eval import Evaluator

    for name in ("parse_module", "parse_cfg", "declared_constraints",
                 "declared_fairness"):
        monkeypatch.setattr(loader, name, lambda *a, _n=name, **k:
                            calls.append(_n))
    monkeypatch.setattr(Evaluator, "eval",
                        lambda *a, **k: calls.append("eval"))
    assert load(cfg) is first and load(cfg, const_overrides={}) is first
    assert calls == []
    assert first.source_digest and first.constants["K"] == 3
    assert first.seq_caps == () and list(first.constraints) == ["Bound"]


@pytest.mark.parametrize("where", ["MC.cfg", "MC.tla", "Walk.tla", "Lim.tla",
                                   "override"])
def test_a_changed_byte_anywhere_misses(tmp_path, fresh, where):
    """The cfg, the root module, a module it extends, a module THAT one
    extends, an override: each is part of the key.  The file is
    rewritten in place (same path, same length where it can be): the key
    is the content, not the path or the mtime."""
    cfg = model_dir(tmp_path / "a")
    first = load(cfg)
    if where == "override":
        other = load(cfg, const_overrides={"K": 2})
        assert other.constants["K"] == 2
        assert load(cfg, const_overrides={"K": 2}) is other
        assert load(cfg, const_overrides={"K": 3}) is not first
    else:
        path = tmp_path / "a" / where
        text = path.read_text()
        # one byte: the 3 of the cfg, the 0 of Lim, a blank after ====
        edited = (text.replace("K = 3", "K = 2") if where == "MC.cfg"
                  else text.replace("Slack == 0", "Slack == 1")
                  if where == "Lim.tla" else text.replace("====", "===="
                                                          " ", 1))
        assert edited != text
        path.write_text(edited)
        t = time.time()
        other = load(cfg)
        assert load_spans(t)[-1] == ("build.struct.load", {"memo": "miss"})
        path.write_text(text)
    assert other is not first
    assert other.source_digest != first.source_digest
    assert cache.model_key(other) != cache.model_key(first)
    # the first text again, from the same path: the first model again
    assert load(cfg) is first


def test_the_same_text_under_another_directory_hits(tmp_path, fresh):
    """The served path writes every job's spec into a fresh job
    directory: the second job's load has to hit."""
    first = load(model_dir(tmp_path / "job-1"))
    shutil.copytree(tmp_path / "job-1", tmp_path / "deeper" / "job-2")
    assert load(str(tmp_path / "deeper" / "job-2" / "MC.cfg")) is first
    # the bare layout (no MC.tla; the cfg's own basename names the root)
    # reads other texts: another key
    bare = tmp_path / "bare"
    bare.mkdir()
    for name in ("Walk.tla", "Lim.tla"):
        shutil.copy(tmp_path / "job-1" / name, bare / name)
    (bare / "Walk.cfg").write_text(TEXTS["MC.cfg"])
    other = load(str(bare / "Walk.cfg"))
    assert other is not first and other.root_name == first.root_name
    assert fresh("model") == (1, 2, 2)
    # every text was parsed once, whichever closure read it
    assert fresh("text")[1:] == (4, 4)


def test_a_hit_records_the_spans_the_miss_recorded(tmp_path, fresh):
    cfg = model_dir(tmp_path / "a")
    t = time.time()
    load(cfg)
    miss = load_spans(t)
    t = time.time()
    with spans.span("sched.load") as outer:
        load(cfg)
    hit = load_spans(t)
    assert [n for n, _ in miss] == [n for n, _ in hit] == [
        "build.struct.constraint", "build.struct.fairness",
        "build.struct.seqcap", "build.struct.load"]
    assert {a["memo"] for _, a in miss} == {"miss"}
    assert {a["memo"] for _, a in hit} == {"hit"}
    # a child keeps what it said of the model on the miss
    strip = [{k: v for k, v in a.items() if k != "memo"} for _, a in miss]
    assert strip == [{k: v for k, v in a.items() if k != "memo"}
                     for _, a in hit]
    assert strip[0] == {"names": "Bound"} and strip[2] == {"declared": 0}
    # ... and the span around the load is told which it was
    assert outer.attrs == {"memo": "hit"}
    rows = {r.name: r for r in spans.snapshot(since=t)}
    assert rows["build.struct.seqcap"].parent == rows["build.struct.load"].id
    assert rows["build.struct.load"].parent == rows["sched.load"].id


@pytest.mark.parametrize("name,text,error", [
    ("MC.cfg", TEXTS["MC.cfg"].replace("Small", "NoSuchInvariant"),
     StructLoadError),
    ("MC.cfg", TEXTS["MC.cfg"] + "VIEW x\n", StructLoadError),
    ("Walk.tla", TEXTS["Walk.tla"].replace("Bound == x <= K",
                                           "Bound == x <= ("),
     StructParseError),
    ("Lim.tla", None, StructLoadError),  # Walk extends a module not there
], ids=["no-such-invariant", "refused-cfg-keyword", "parse-error",
        "missing-module"])
def test_a_load_error_is_raised_again_not_kept(tmp_path, fresh, name, text,
                                               error):
    cfg = model_dir(tmp_path / "a", {name: text})
    seen = []
    for _ in range(2):
        with pytest.raises(error) as e:
            load(cfg)
        seen.append(str(e.value))
    assert seen[0] == seen[1]
    hits, _, size = fresh("model")
    assert (hits, size) == (0, 0)
    # mended in place, the same path loads
    model_dir(tmp_path / "a")
    assert load(cfg).root_name == "Walk"


# -- the preflight report, through api.run_check ---------------------------


def check(cfg, tmp_path, **kw):
    """One api.run_check: (outcome, transcript, journal events, the
    spans closed inside it)."""
    out = io.StringIO()
    journal = str(tmp_path / "run.journal.jsonl")
    t = time.time()
    o = run_check(CheckRequest(config=cfg, frontend="struct", workers="cpu",
                               noTool=True, out=out, err=out,
                               journal=journal, **{**GEO, **kw}))
    with open(journal) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return o, out.getvalue(), events, spans.snapshot(since=t)


def untimed(event):
    return {k: v for k, v in event.items() if k not in ("t", "wall_s")}


def analysis_of(events):
    return [untimed(e) for e in events
            if e["event"] in ("analysis", "analysis_summary")]


def memo_of(rows, name):
    return [r.attrs.get("memo") for r in rows if r.name == name]


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """Three checks of one cfg - a first, a second of the kept text, a
    third after cache.clear() - and what each left."""
    cache.clear()
    tmp = tmp_path_factory.mktemp("checked")
    cfg = model_dir(tmp / "m")
    memos = ("text", "model", "preflight")
    base = {m: counts(m) for m in memos}
    first = check(cfg, tmp)
    after_first = {m: counts(m) for m in memos}
    second = check(cfg, tmp)
    after_second = {m: counts(m) for m in memos}
    cache.clear()
    third = check(cfg, tmp)
    return dict(cfg=cfg, tmp=tmp, first=first, second=second, third=third,
                counts=(base, after_first, after_second))


def test_a_check_of_a_kept_text_journals_what_a_fresh_one_does(checked):
    (o1, text1, ev1, rows1), (o2, text2, ev2, rows2), (o3, _, ev3, rows3) = (
        checked[k] for k in ("first", "second", "third"))
    assert o1.verdict == o2.verdict == o3.verdict == "ok"
    assert (o2.result.generated, o2.result.distinct, o2.result.depth) == (
        17, 8, 5)
    # the lite report has a finding to carry (Vacuous reads no variable)
    found = analysis_of(ev1)
    assert [e["check"] for e in found if e["event"] == "analysis"] == [
        "invariant-vacuity"]
    assert found[-1]["findings"] == 1 and found[-1]["warnings"] == 1
    # ... and the kept one is journalled into THIS check's journal, field
    # for field but the times, and rendered as the same banner
    assert analysis_of(ev2) == found == analysis_of(ev3)
    assert [ln for ln in text1.splitlines() if "Preflight" in ln] == [
        ln for ln in text2.splitlines() if "Preflight" in ln] != []
    assert memo_of(rows1, "check.preflight") == ["miss"]
    assert memo_of(rows2, "check.preflight") == ["hit"]
    assert memo_of(rows3, "check.preflight") == ["miss"]
    for rows, memo in ((rows1, "miss"), (rows2, "hit"), (rows3, "miss")):
        assert memo_of(rows, "build.struct.load") == [memo]
        assert memo_of(rows, "check.resolve") == [memo]
    # nothing a run produced is kept: every check expands every state
    # and writes its own journal, and the verdict is the fresh one's
    final = [next(e for e in ev if e["event"] == "final")
             for ev in (ev1, ev2, ev3)]
    assert untimed(final[1]) == untimed(final[2]) == untimed(final[0])
    assert final[1]["states_expanded"] == 8
    for ev in (ev1, ev2, ev3):
        kinds = [e["event"] for e in ev]
        assert kinds.count("level") == 5 and kinds.count("run_start") == 1


def test_the_memos_are_counted_and_clear_drops_them(checked):
    base, first, second = checked["counts"]
    for memo, texts in (("text", 4), ("model", 1), ("preflight", 1)):
        (h0, m0, _), (h1, m1, size1), (h2, m2, size2) = (
            base[memo], first[memo], second[memo])
        # the first check builds each entry once, the second only reads
        assert (h1 - h0, m1 - m0, size1) == (0, texts, texts)
        assert (h2 - h1, m2 - m1, size2) == (texts, 0, texts)
    assert {"text", "model", "preflight", "backend", "engine",
            "bounds"} <= set(cache.stats())
    kept = load(checked["cfg"])
    cache.clear()
    cleared = cache.stats()
    assert [cleared[m]["size"] for m in ("text", "model", "preflight")] == [
        0, 0, 0]
    assert load(checked["cfg"]) is not kept


@pytest.mark.parametrize("change,memo", [
    (dict(), "hit"),
    (dict(fpcap=1024), "miss"),
    (dict(qcap=256), "miss"),
    (dict(chunk=32), "miss"),
    (dict(nodeadlock=True), "miss"),
    (dict(narrow=True), "miss"),
])
def test_the_report_is_kept_by_every_integer_it_reads(tmp_path, fresh,
                                                      monkeypatch, change,
                                                      memo):
    """No engine: the gate is driven as `_run_check_struct` drives it,
    on the request a check would carry."""
    import argparse

    from jaxtlc import api
    from jaxtlc.io.tlc_log import TLCLog

    sm = load(model_dir(tmp_path / "a"))
    built = []
    from jaxtlc.analysis import preflight as pf

    real = pf.preflight_struct
    monkeypatch.setattr(pf, "preflight_struct", lambda *a, **k: (
        built.append(k), real(*a, **k))[1])

    def gate(**kw):
        req = {**GEO, "nodeadlock": False, "narrow": False, **kw}
        args = argparse.Namespace(
            preflight=True, analyze=False, symmetry=None,
            narrow=req["narrow"], fpcap=req["fpcap"], chunk=req["chunk"],
            qcap=req["qcap"])
        spec = argparse.Namespace(check_deadlock=not req["nodeadlock"])
        t = time.time()
        rc = api._preflight_gate(
            args, TLCLog(out=io.StringIO(), tool_mode=False),
            lambda deep: api._struct_preflight(args, spec, sm, deep))
        assert rc is None
        return memo_of(spans.snapshot(since=t), "check.preflight")

    assert gate() == ["miss"]
    assert gate(**change) == [memo]
    assert len(built) == (1 if memo == "hit" else 2)
    assert built[-1]["fp_capacity"] == change.get("fpcap", GEO["fpcap"])
    # the report handed out is a copy: writing to it leaves the kept one
    spec = argparse.Namespace(check_deadlock=True)
    args = argparse.Namespace(symmetry=None, narrow=False, fpcap=512,
                              chunk=16, qcap=128)
    one = api._struct_preflight(args, spec, sm, False)
    one.findings.clear()
    one.constraint_lines.append("scribble")
    two = api._struct_preflight(args, spec, sm, False)
    assert [f.check for f in two.findings] == ["invariant-vacuity"]
    assert "scribble" not in two.constraint_lines and two.constraint_lines


def test_analyze_always_builds(checked):
    """-analyze traces the engine: never kept, never answered from the
    kept lite report."""
    before = cache.stats()["preflight"]
    o, text, events, rows = check(checked["cfg"], checked["tmp"],
                                  analyze=True)
    assert o.verdict == "ok"
    after = cache.stats()["preflight"]
    assert (after["hits"], after["misses"], after["size"]) == (
        before["hits"], before["misses"], before["size"])
    assert memo_of(rows, "check.preflight") == [None]
    assert analysis_of(events)[-1]["findings"] >= 1


def test_an_error_finding_ends_the_check_the_same_on_a_hit(tmp_path, fresh):
    """`AlsoY` bounds y alone: x is bounded neither by the constraint
    nor by inference, the preflight refuses the run by the leaf's name -
    on the kept report as on the built one, before any engine."""
    cfg = model_dir(tmp_path / "a", {"MC.cfg": TEXTS["MC.cfg"].replace(
        "CONSTRAINT Bound", "CONSTRAINT AlsoY")})
    runs = [check(cfg, tmp_path) for _ in range(2)]
    (o1, text1, ev1, rows1), (o2, text2, ev2, rows2) = runs
    assert o1.result is None and o2.result is None
    assert o1.exit_code == o2.exit_code != 0
    assert "integer leaf x is bounded neither by CONSTRAINT AlsoY" in text2
    assert text1 == text2
    assert analysis_of(ev1) == analysis_of(ev2)
    assert [untimed(e) for e in ev2 if e["event"] == "final"] == [
        untimed(e) for e in ev1 if e["event"] == "final"]
    assert ev2[-1]["verdict"] == "error"
    assert memo_of(rows1, "check.preflight") == ["miss"]
    assert memo_of(rows2, "check.preflight") == ["hit"]
    assert not [r for r in rows2 if r.name == "build"]
