"""Tooling smoke: the instruments must not silently rot (ISSUEs 4, 5).

tools/tlcstat.py, tools/covdiff.py and the Chrome-trace exporter are the
observability plane's operator surface; tools/loadgen.py and
tools/chaos.py drive the service.  A broken import or a drifted engine
signature must show up in tier-1, not on the next TPU session.  Each
tool's --tiny runs its WHOLE pipeline in-process.
"""

import ast
import functools
import importlib.util
import json
import os
import re
import tokenize

import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), os.pardir))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_covdiff_tiny_smoke(capsys):
    """tools/covdiff.py --tiny: regression detection + JSON-artifact
    round-trip + {base}.hN pod-journal merge on synthetic coverage
    tables (no engine run)."""
    mod = _load_tool("covdiff")
    assert mod.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    assert ("covdiff tiny OK: regression detection + artifact "
            "round-trip + pod-journal merge") in out


def test_tlcstat_tiny_smoke(capsys):
    """tlcstat --tiny renders a full dashboard frame from a synthetic
    journal (rates, occupancy, ETA, verdict) - the whole read/render
    pipeline, no engine run."""
    mod = _load_tool("tlcstat")
    assert mod.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    # the tiny journal exercises the spill tier too, so the occupancy
    # line renders in its spilling form plus the spill-tier line
    for needle in ("ds/min", "fp space", "(spilling)", "spill tier:",
                   "ETA", "VERDICT:", "tlcstat tiny OK"):
        assert needle in out, f"tlcstat output lost {needle!r}:\n{out}"


def test_loadgen_tiny_smoke(capsys):
    """tools/loadgen.py --tiny: start a real checking service, submit
    4 plain + 4 sweep jobs through the HTTP surface, assert pool reuse
    and ZERO fresh XLA compiles on the warm path, and report the
    p50/p95 warm latency (ISSUE 9 CI wiring; spec is tiny - one small
    engine + one sweep-class compile total)."""
    mod = _load_tool("loadgen")
    assert mod.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    assert "loadgen OK" in out, out
    report = json.loads(out[: out.index("loadgen OK")])
    assert report["warm_fresh_xla_compiles"] == 0
    assert report["pool"]["hits"] >= report["jobs"] - 1
    assert report["warm_p50_s"] <= report["warm_p95_s"]
    assert report["scheduler"]["batched_jobs"] == report["sweep_jobs"]


def test_loadgen_sim_tiny_smoke(capsys):
    """tools/loadgen.py --sim --tiny: the smoke job class under load -
    1 cold + 3 warm sim submits (different seeds, ONE warm engine,
    zero fresh XLA compiles asserted) plus a folded seed-batch burst
    (ISSUE 14 CI wiring; the sim engine is tiny)."""
    mod = _load_tool("loadgen")
    assert mod.main(["--sim", "--tiny"]) == 0
    out = capsys.readouterr().out
    assert "loadgen OK" in out, out
    report = json.loads(out[: out.index("loadgen OK")])
    assert report["sim_fresh_xla_compiles"] == 0
    assert report["pool"]["hits"] >= report["jobs"] - 1
    assert report["sim_p50_s"] <= report["sim_p95_s"]
    assert report["transitions"] > 0


def test_loadgen_infer_tiny_smoke(capsys):
    """tools/loadgen.py --infer --tiny: the inference job class under
    load - 1 cold + 3 warm infer submits (different evidence seeds,
    ONE warm engine, zero fresh XLA compiles asserted), with the
    candidate funnel reported (ISSUE 16 CI wiring)."""
    mod = _load_tool("loadgen")
    assert mod.main(["--infer", "--tiny"]) == 0
    out = capsys.readouterr().out
    assert "loadgen OK" in out, out
    report = json.loads(out[: out.index("loadgen OK")])
    assert report["infer_fresh_xla_compiles"] == 0
    assert report["pool"]["hits"] >= report["jobs"] - 1
    assert report["infer_p50_s"] <= report["infer_p95_s"]
    assert report["candidates"] > 0
    assert report["certified"] > 0


def test_cachectl_tiny_smoke(capsys):
    """tools/cachectl.py --tiny: synthetic artifact store -> ls ->
    verify (clean + after a deliberate corruption) -> gc to a byte
    budget (ISSUE 13 CI tooling; engine-free, jax-free)."""
    mod = _load_tool("cachectl")
    assert mod.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    for needle in ("CORRUPT", "gc: kept 2", "cachectl tiny OK"):
        assert needle in out, f"cachectl output lost {needle!r}:\n{out}"


def test_loadgen_cache_tiny_smoke(capsys):
    """tools/loadgen.py --cache --tiny: 4 identical submits through a
    real checking service against a self-contained artifact store -
    1 cold population run, 3 verdict-tier hits asserted to perform
    ZERO fresh XLA compiles and ZERO engine dispatches, hit p50/p95
    reported (the ISSUE 13 acceptance instrument)."""
    mod = _load_tool("loadgen")
    assert mod.main(["--cache", "--tiny"]) == 0
    out = capsys.readouterr().out
    assert "loadgen OK" in out, out
    report = json.loads(out[: out.index("loadgen OK")])
    assert report["hit_fresh_xla_compiles"] == 0
    assert report["hit_engine_dispatches"] == 0
    assert report["scheduler_cache_hits"] == report["jobs"] - 1
    assert report["store"]["verdict_hits"] == report["jobs"] - 1
    assert report["hit_p50_s"] <= report["hit_p95_s"]


def test_trace_exporter_tiny_smoke(capsys):
    """The Chrome-trace exporter's --tiny: synthesize a journal, export
    it, and assert the bare-segment rendering landed in the JSON."""
    from jaxtlc.obs import trace as obs_trace

    assert obs_trace.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    assert "trace-export tiny OK" in out


def test_loadgen_overload_tiny_smoke(capsys):
    """tools/loadgen.py --overload --tiny: a real checking service
    with a small admission bound under deliberate overload - warm
    latency gate (zero fresh compiles), a supervised heavy job
    preempted by a priority arrival and resumed bit-for-bit, a burst
    past the queue bound rejected 429 + Retry-After with the client
    backoff landing the resubmit, one deadline expiry + one cancel
    (the ISSUE 17 acceptance instrument)."""
    mod = _load_tool("loadgen")
    assert mod.main(["--overload", "--tiny"]) == 0
    out = capsys.readouterr().out
    assert "loadgen OK" in out, out
    report = json.loads(out[: out.index("loadgen OK")])
    assert report["warm_fresh_xla_compiles"] == 0
    assert report["burst"]["rejected"] >= 1
    assert report["burst"]["retry_after_s"][0] >= 1  # [min, max] hints
    assert report["burst"]["accepted"] + report["burst"]["rejected"] \
        == report["burst"]["submitted"]
    assert report["preempt"]["requeues"] >= 1
    assert report["preempt"]["parity"] is True
    assert report["expired"] == 1 and report["canceled"] == 1
    assert report["counters"]["rejected"] >= 1
    assert report["warm_p50_s"] <= report["warm_p95_s"]


def test_chaos_serve_tiny_smoke(capsys):
    """tools/chaos.py --serve --tiny: the scheduler chaos matrix on a
    stub pool - runner_die absorbed by retry, slow_dispatch creating
    the overload window for 429 / deadline expiry / cancel, a poison
    spec tripping the breaker into quarantine, SSE followers
    terminating on every outcome, queue drained clean (engine-free,
    policy-speed)."""
    mod = _load_tool("chaos")
    assert mod.main(["--serve", "--tiny"]) == 0
    out = capsys.readouterr().out
    assert "chaos serve OK" in out, out


# ---- reference guard: what the documents name exists ---------------------


def _py_files(*dirs):
    for d in dirs:
        for base, _dirs, names in os.walk(os.path.join(ROOT, d)):
            for n in sorted(names):
                if n.endswith(".py"):
                    yield os.path.join(base, n)


@functools.lru_cache(maxsize=None)
def _prose(source):
    """The text a reader of `source` sees: a markdown file whole, or the
    comments and string literals (docstrings, help strings) of every
    module under jaxtlc/."""
    if source.endswith(".md"):
        with open(os.path.join(ROOT, source), encoding="utf-8") as f:
            return f.read()
    out = []
    for path in _py_files("jaxtlc"):
        with open(path, "rb") as f:
            out += [t.string for t in tokenize.tokenize(f.readline)
                    if t.type in (tokenize.COMMENT, tokenize.STRING)]
    return "\n".join(out)


DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md", "jaxtlc"]


@pytest.mark.parametrize("source", DOCUMENTS)
def test_named_files_exist(source):
    """Every tools/*.py, every path under the repo's own directories,
    every bare *.py and every top-level record (*.json in capitals)
    that a document names is on disk: a sentence about a deleted
    instrument fails here, not in a reader's shell."""
    text = _prose(source)
    basenames = {os.path.basename(p) for p in _py_files(
        "jaxtlc", "tools", "tests", "benchmark")}
    basenames |= {n for n in os.listdir(ROOT) if n.endswith(".py")}
    missing = set()
    for m in re.finditer(
            r"(?<![\w/.-])((?:jaxtlc|tools|tests|benchmark|specs)/"
            r"[\w./-]*\w\.(?:py|json|md|cfg|tla))\b", text):
        if not os.path.exists(os.path.join(ROOT, m.group(1))):
            missing.add(m.group(1))
    for m in re.finditer(r"(?<![\w/.*<{-])([A-Za-z_]\w*\.py)\b", text):
        if m.group(1) not in basenames:
            missing.add(m.group(1))
    for m in re.finditer(r"(?<![\w/.*<{-])([A-Z][A-Z0-9_]*(?:_r\d+)?"
                         r"\.jsonl?)\b", text):
        if not os.path.exists(os.path.join(ROOT, m.group(1))):
            missing.add(m.group(1))
    assert not missing, f"{source} names files that do not exist: " \
                        f"{sorted(missing)}"


def _parser_flags():
    """Every single-dash option some argparse parser of the repo
    defines (AST scan of add_argument calls: no import, no compile)."""
    flags = set()
    for path in _py_files("jaxtlc", "tools"):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"):
                flags |= {a.value for a in node.args
                          if isinstance(a, ast.Constant)
                          and isinstance(a.value, str)
                          and re.fullmatch(r"-[A-Za-z][\w-]+", a.value)}
    return flags


# single-dash options of other programs that the documents quote:
# TLC's own command line, and the C compiler's
FOREIGN_FLAGS = {"-config", "-deadlock", "-fPIC", "-shared", "-std"}


@pytest.mark.parametrize("source", DOCUMENTS)
def test_named_flags_exist(source):
    """Every `-flag` a document names is an option of one of the
    repo's parsers (cli.py's above all): a removed flag cannot stay
    in a synopsis, a help string or a comment."""
    known = _parser_flags()
    assert "-sharded" in known and "-checkpoint" in known
    named = set(re.findall(
        r"(?:(?<=[\s/|,(\[])|^)[`\"']?(-[A-Za-z][A-Za-z-]*[A-Za-z])\b",
        _prose(source)))
    unknown = named - known - FOREIGN_FLAGS
    assert not unknown, f"{source} names flags no parser defines: " \
                        f"{sorted(unknown)}"
