"""BENCHMARK.json against the limits of the benchmark's contract that can
be checked without a run: exact key sets, names, units, lengths, bounds,
that every file a cell names lies under `paths`, and that every cell
reports `setup_s`, another end-to-end metric and a per-layer metric."""

from __future__ import annotations

import json
import os
import re

from conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_meets_the_contract():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p
        for p in b["paths"])
    assert len(b["command"]) <= 32 and all(one_line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # the whole check has to fit with the full 24 cells
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    under = tuple(p.rstrip("/") + "/" for p in b["paths"])
    assert 1 <= len(b["configs"]) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(under) and PATH.match(c["file"])
        assert os.path.exists(os.path.join(REPO, c["file"]))
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    cnames = [c["name"] for c in b["configs"]]
    assert len(set(cnames)) == len(cnames)

    assert 1 <= len(b["workloads"]) <= 24
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cnames and w["chips"] in (1, 4)
        assert one_line(w["why"])
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert set(cnames) == {w["config"] for w in b["workloads"]}
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)

    assert 1 <= len(b["end_to_end"]) <= 16
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)

    assert 1 <= len(b["per_layer"]) <= 128
    layer_cells = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and one_line(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        mine = set(m.get("workloads", e2e[m["moves"]]))
        assert mine <= e2e[m["moves"]], m["name"]
        layer_cells |= mine
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for cell in cells:
        assert any(cell in ws for k, ws in e2e.items() if k != "setup_s")
        assert cell in layer_cells

    # every file under paths is named from the characters of a name and /
    for root in b["paths"]:
        for d, dirs, fs in os.walk(os.path.join(REPO, root)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PATH.match(rel), rel
