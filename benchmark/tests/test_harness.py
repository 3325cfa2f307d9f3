"""The harness's own tests: python -m pytest benchmark/tests

On the CPU at tiny sizes, with the platform gate stubbed in conftest.py.
They hold: the generator is seed-exact and the seed never changes the
work; the percentile and window rules; the gate (a wrong count, a ladder
event, a job without a verdict, a compile in the window each give
`correct: false`); that a cell, a configuration, a traffic mix and a
layer metric added as NEW files are found by name; the refusal of a
device that is not a known TPU; the plain references against their pins;
and the controls - a relaxed guarantee or a broken timed path comes out
as not correct through the whole of run.py.
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest
from conftest import BENCH, FF_PINS, REPO, tiny_ff_config, write_json

sys.path.insert(0, BENCH)
import control  # noqa: E402
import gate  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402

sys.path.insert(0, os.path.join(BENCH, "reference"))
import kubeapi  # noqa: E402
import pin  # noqa: E402
import raftrepl  # noqa: E402


def load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


# -- the generator -----------------------------------------------------------

SERVED = load("traffic/served.json")
# a two-class variant of the served mix (the class of jobs above
# large_fpcap is a row of PERF.md's Open questions): the generator must
# already read it
SMALL = SERVED["classes"][0]["options"]
MIXED = dict(SERVED, classes=[
    dict(name="small", weight=0.85, options=SMALL),
    dict(name="large", weight=0.15, options=dict(SMALL, fpcap=131072))])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3000000019])
def test_schedule_is_seed_exact(seed):
    a = loadgen.schedule(SERVED, seed, 20.0)
    b = loadgen.schedule(SERVED, seed, 20.0)
    assert a == b
    assert all(0 <= d.due_s < 20.0 for d in a)
    assert [d.due_s for d in a] == sorted(d.due_s for d in a)


def test_seed_changes_the_order_not_the_work():
    runs = [loadgen.schedule(MIXED, s, 20.0) for s in (1, 2, 2**31 + 5)]
    n = round(MIXED["arrivals"]["rate_per_s"] * 20.0)

    def gaps(draws, sort=True):
        ts = [0.0] + [d.due_s for d in draws]
        g = [round(b - a, 9) for a, b in zip(ts, ts[1:])]
        return sorted(g) if sort else g

    def counts(draws, key):
        out = {}
        for d in draws:
            out[getattr(d, key)] = out.get(getattr(d, key), 0) + 1
        return out

    assert {len(r) for r in runs} == {n}
    assert gaps(runs[0]) == gaps(runs[1]) == gaps(runs[2])
    # the order of the gaps is work in front of a queue: a seed rotates
    # the one cyclic sequence and never reorders it
    g0, g1 = gaps(runs[0], sort=False), gaps(runs[1], sort=False)
    k = g1.index(g0[0])
    assert g1[k:] + g1[:k] == g0
    assert counts(runs[0], "klass") == counts(runs[1], "klass")
    assert counts(runs[0], "tenant") == counts(runs[2], "tenant")
    assert counts(runs[0], "klass") == {"small": round(0.85 * n),
                                        "large": n - round(0.85 * n)}
    assert [d.klass for d in runs[0]] != [d.klass for d in runs[1]]
    assert [d.due_s for d in runs[0]] != [d.due_s for d in runs[1]]
    # every job carries its class's options as the mix states them
    assert all(d.options == SMALL for d in runs[0] if d.klass == "small")
    assert all(d.options["fpcap"] == 131072 for d in runs[0]
               if d.klass == "large")
    tenants = counts(runs[0], "tenant")
    assert tenants == {"ci": round(0.8 * n), "dev": n - round(0.8 * n)}


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("durations, seconds, started", [
    ([50.0], 10.0, 1),  # the first check always starts, and runs out
    ([4.0, 4.0, 4.0], 10.0, 2),  # 2 left < 4 needed: no third start
    ([3.0, 3.0, 3.0, 3.0], 12.0, 4),  # exactly fits
    ([1.0, 8.0, 1.0, 1.0], 10.0, 2),  # what is left < the LAST one's
    ([11.0, 1.0], 10.0, 1),  # no start past the window
])
def test_window_rule(durations, seconds, started):
    clock = FakeClock()
    it = iter(durations)

    def job(draw):
        clock.t += next(it)
        return dict(ok=True)

    mix = load("traffic/exhaustive.json")
    recs = loadgen.drive_closed(job, loadgen.schedule(mix, 1, seconds),
                                seconds, clock=clock)
    assert len(recs) == started
    assert all(r["start_t"] - 1000.0 < seconds for r in recs[1:])


def test_open_loop_times_from_the_due_instant_and_fails_the_unfinished():
    mix = dict(SERVED, arrivals=dict(SERVED["arrivals"], rate_per_s=50.0),
               client_threads=2, drain_s=0.3)
    import threading
    import time

    hang = threading.Event()

    def job(d):
        if d.index == 3:
            hang.wait(2.0)  # no verdict by the end of the drain
        else:
            time.sleep(0.01)
        return dict(ok=True)

    recs = loadgen.drive(job, mix, 9, 0.2)
    hang.set()
    assert len(recs) == 10
    assert [r["index"] for r in recs] == list(range(10))
    late = [r for r in recs if not r.get("ok", True)]
    assert [r["index"] for r in late] == [3] and "drain" in late[0]["why"]
    good = [r for r in recs if r.get("ok")]
    assert all(r["done_t"] >= r["start_t"] >= r["due_t"] - 1e-3
               for r in good)


# -- percentiles -------------------------------------------------------------

def test_percentiles_are_nearest_rank_as_tools_loadgen_has_them():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 0.5) == 50 or stats.percentile(xs, 0.5) == 51
    assert stats.percentile(xs, 0.95) == 95
    assert stats.samples_beyond(xs, 0.95) == 5
    assert stats.samples_beyond(list(range(400)), 0.95) >= 10
    assert stats.percentile([], 0.5) is None
    assert stats.percentile([7.0], 0.95) == 7.0
    import importlib.util
    import random

    spec = importlib.util.spec_from_file_location(
        "tools_loadgen", os.path.join(REPO, "tools", "loadgen.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    rng = random.Random(3)
    ys = [rng.random() for _ in range(137)]
    for q in (0.5, 0.9, 0.95, 0.99):
        assert stats.percentile(ys, q) == theirs._pct(ys, q)


def test_spread_rule_is_the_drivers():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics

    q = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == (q[2] - q[0]) / statistics.median(xs)


# -- the gate ----------------------------------------------------------------

RAFT = load("configs/raftrepl-model1.json")


def sound_record(pins=None, engine="pool"):
    pins = pins or RAFT["pins"]
    counts = {k: pins[k] for k in gate.COUNT_KEYS}
    return dict(
        ok=True, index=0, klass="small",
        result=dict(verdict="ok", queue=0, engine=engine,
                    action_generated=dict(pins["action_generated"]),
                    **counts),
        events=[dict(event="run_start", device="TPU_0(process=0)",
                     engine=engine),
                dict(event="final", verdict="ok", queue=0, wall_s=0.02,
                     **counts)])


def test_gate_passes_a_sound_window():
    v = gate.judge([sound_record(), sound_record()], RAFT, 0)
    assert v["correct"] and v["attempted"] == 2 and v["failed"] == 0
    assert any("limit 0" in ln for ln in v["lines"])


def _break(rec, what):
    if what == "distinct":
        rec["result"]["distinct"] -= 1
    elif what == "journal-count":
        rec["events"][1]["generated"] += 1
    elif what == "depth":
        rec["result"]["depth"] += 1
    elif what == "action":
        rec["result"]["action_generated"]["Elect"] += 1
    elif what == "verdict":
        rec["result"]["verdict"] = "violation"
    elif what == "queue":
        rec["result"]["queue"] = 629
    elif what == "device":
        rec["events"][0]["device"] = "TFRT_CPU_0"
    elif what == "cache-engine":
        rec["result"]["engine"] = "cache"
    elif what == "supervised-engine":  # the served cell is the pool route
        rec["result"]["engine"] = "supervised"
    elif what == "no-journal":
        rec["events"] = None
    elif what == "no-verdict":
        rec.update(ok=False, why="state expired")
    else:  # a ladder event
        rec["events"].insert(1, dict(event=what))
    return rec


@pytest.mark.parametrize("what", [
    "distinct", "journal-count", "depth", "action", "verdict", "queue",
    "device", "cache-engine", "supervised-engine", "no-journal",
    "no-verdict",
    "regrow", "retry", "degrade", "spill"])
def test_gate_fails_one_broken_job_among_sound_ones(what):
    recs = [sound_record(), _break(sound_record(), what), sound_record()]
    v = gate.judge(recs, RAFT, 0)
    assert v["correct"] is False
    assert (v["attempted"], v["failed"]) == (3, 1)
    assert recs[1]["findings"] and not recs[0]["findings"]


def test_gate_fails_a_compile_in_the_window_and_an_empty_window():
    assert gate.judge([sound_record()], RAFT, 1)["correct"] is False
    assert gate.judge([], RAFT, 0)["correct"] is False


# -- the device gate and the peaks -------------------------------------------

def fake_jax(monkeypatch, platform, kind, n=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


@pytest.mark.parametrize("platform, kind, n, chips, word", [
    ("cpu", "cpu", 1, 1, "no CPU mode"),
    ("tpu", "TPU v5 lite", 1, 4, "asks for 4 chips"),
    ("tpu", "TPU v9 mega", 1, 1, "not in benchmark/peaks.json"),
])
def test_device_gate_refuses(monkeypatch, checkout, platform, kind, n,
                             chips, word):
    run = control.load_run(checkout.root, "gate")
    fake_jax(monkeypatch, platform, kind, n)
    with pytest.raises(run.Refused, match=word):
        run.device_gate(chips, load("peaks.json"))


def test_device_gate_takes_a_known_tpu(monkeypatch, checkout):
    run = control.load_run(checkout.root, "gate2")
    fake_jax(monkeypatch, "tpu", "TPU v5 lite", 4)
    assert len(run.device_gate(4, load("peaks.json"))) == 4
    v5e = load("peaks.json")["devices"]["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)


def test_run_refuses_without_a_tpu_and_prints_no_result(checkout, capsys):
    # the real gate, not the stub: this sandbox has no TPU
    run = control.load_run(checkout.root, "nogate")
    rc = run.main(["--workload", "kubeapi-model1.recheck", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and "{" not in out.out
    assert "refusing to start" in out.err


def test_run_refuses_where_only_the_benchmark_is(tmp_path, capsys):
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    run = control.load_run(str(tmp_path), "bare")
    run.device_gate = lambda chips, peaks: pytest.fail("gate reached")
    rc = run.main(["--workload", "kubeapi-model1.recheck", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2 and "{" not in capsys.readouterr().out


# -- the plain references and the pins ---------------------------------------

def test_raft_reference_gives_the_configs_pins():
    got = pin.reference_pins(RAFT)
    assert got == RAFT["pins"]


def test_kubeapi_reference_ff_corner_and_scaling():
    r = kubeapi.bfs(kubeapi.model_1(False, False))
    assert (r.generated, r.distinct, r.depth) == (17020, 8203, 109)
    assert not r.violations
    # the scaled rule at 1 x 1 is the same machine under other names
    s = kubeapi.bfs(kubeapi.scaled(1, 1, False, False))
    assert (s.generated, s.distinct, s.depth) == (17020, 8203, 109)


@pytest.mark.slow
def test_kubeapi_reference_gives_model_1_pins_and_tlcs():
    cfg = load("configs/kubeapi-model1.json")
    got = pin.reference_pins(cfg)  # ~15 s of pure Python
    assert got == cfg["pins"]
    assert (got["generated"], got["distinct"], got["depth"]) == (
        577736, 163408, 124)  # MC.out:1098,1101


@pytest.mark.parametrize("bits, salt", [(12, 1), (12, 2), (12, 3)])
def test_reference_control_narrow_fingerprints_change_the_counts(bits, salt):
    r = raftrepl.bfs(fp_bits=bits, fp_salt=salt)
    assert r.distinct < RAFT["pins"]["distinct"]
    k = kubeapi.bfs(kubeapi.model_1(False, False), fp_bits=bits,
                    fp_salt=salt)
    assert k.distinct < 8203


def test_every_config_states_its_guarantees_and_pins():
    bench = load("../BENCHMARK.json")
    for c in bench["configs"]:
        cfg = load(os.path.relpath(os.path.join(REPO, c["file"]), BENCH))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for key in ("source", "assumed", "guarantees", "entry", "request",
                    "reference", "deployment"):
            assert key in cfg, (c["name"], key)
        assert set(gate.COUNT_KEYS) <= set(cfg["pins"])
        assert os.path.exists(os.path.join(BENCH, "entries",
                                           cfg["entry"] + ".py"))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           m["name"] + ".py"))
        e2e = next(e for e in bench["end_to_end"]
                   if e["name"] == m["moves"])
        # every cell of a per-layer metric reports what it moves
        assert set(m["workloads"]) <= set(
            e2e.get("workloads", [w["name"] for w in bench["workloads"]]))


# -- through the whole of run.py, on the CPU ----------------------------------

@pytest.fixture
def tiny(checkout):
    """Two tiny cells added as NEW files and entries: the FF corner
    through each batch entry."""
    checkout.add_config("tiny-cwc", tiny_ff_config(
        "check_with_checkpoints", checkout.root))
    checkout.add_config("tiny-rc", tiny_ff_config("run_check",
                                                  checkout.root))
    checkout.add_cell("tiny-cwc.exhaustive", "tiny-cwc", "exhaustive",
                      ["states_per_s"],
                      ["step_ms", "level_ms", "fp_load_pct"])
    checkout.add_cell("tiny-rc.recheck", "tiny-rc", "recheck",
                      ["states_per_s", "verdict_ms.p50"],
                      ["level_ms", "fp_load_pct", "host_overhead_ms",
                       "verdict_ms.p95"])
    return checkout


def test_new_cell_config_traffic_and_layer_are_found_by_name(tiny):
    co = tiny
    # a new traffic mix and a new layer metric, as files of their own
    write_json(co.path("traffic", "twice.json"),
               dict(loop="closed"))
    with open(co.path("layers", "jobs_done.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(len(run['jobs']))\n")
    co.bench["per_layer"].append(dict(
        name="jobs_done", unit="jobs", better="higher",
        source="program_counter", layer="engine step",
        moves="states_per_s", workloads=["tiny-cwc.twice"]))
    co.add_cell("tiny-cwc.twice", "tiny-cwc", "twice", ["states_per_s"],
                ["level_ms"])
    rc, line, text = co.run("tiny-cwc.twice", seconds=1.0)
    assert rc == 0 and line["correct"] is True, text[-2000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"states_per_s", "setup_s"}
    assert line["metrics"]["states_per_s"]["unit"] == "states/s"
    assert line["device"]["platform"] == "cpu"  # named as JAX has it
    assert "compiles in the window: 0, limit 0" in text
    # the traced run reports the cell's per-layer metrics; on a CPU no
    # device operation exists, so it may not call itself correct
    rc, line, text = co.run("tiny-cwc.twice", seconds=1.0, trace=1)
    assert rc == 0 and line["correct"] is False
    assert "no operation ran on the device" in text
    assert line["metrics"]["jobs_done"] == dict(
        value=float(line["attempted"]), unit="jobs")
    assert "level_ms" in line["metrics"]
    assert "states_per_s" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_run_check_cell_end_to_end_and_its_seed_is_inert(tiny):
    rc, a, text = tiny.run("tiny-rc.recheck", seed=5, seconds=1.0)
    assert rc == 0 and a["correct"] is True, text[-2000:]
    assert set(a["metrics"]) == {"states_per_s", "verdict_ms.p50",
                                 "setup_s"}
    assert "FEWER THAN TEN" in text  # the p95 says what it is
    rc, b, _ = tiny.run("tiny-rc.recheck", seed=2**31 + 7, seconds=1.0,
                        trace=1)
    assert b["attempted"] >= 1 and b["failed"] == 0
    assert {"level_ms", "fp_load_pct", "host_overhead_ms"} <= set(
        b["metrics"])
    # a reader that finds no tail to read returns nothing
    assert "verdict_ms.p95" not in b["metrics"]


@pytest.mark.parametrize("cell, edit, word", [
    # a relaxed guarantee, by the program's own path (controls.json)
    ("tiny-cwc.exhaustive", {"request.max_segments": 2}, "queue "),
    ("tiny-rc.recheck", {"request.faults": "transient@0"}, "retry"),
    # a wrong pin stands for a wrong count
    ("tiny-rc.recheck", {"pins.distinct": FF_PINS["distinct"] + 1},
     "distinct"),
])
def test_relaxed_guarantee_comes_out_not_correct(tiny, cell, edit, word):
    conf = f"benchmark/configs/{cell.split('.')[0]}.json"
    control.edit_config(tiny.root, conf, edit)
    rc, line, text = tiny.run(cell, seconds=1.0)
    assert rc == 0 and line["correct"] is False, text[-2000:]
    assert line["failed"] == line["attempted"] >= 1
    assert word in text
    assert "states_per_s" not in line["metrics"]  # no rate without a
    # correct job


def test_broken_timed_path_comes_out_not_correct(tiny, monkeypatch):
    """The rest of a run driven with the timed path broken underneath:
    the answer is altered where the engine produces it."""
    sys.path.insert(0, REPO)
    from jaxtlc.engine import checkpoint

    real = checkpoint.result_from_carry

    def one_state_short(*a, **kw):
        r = real(*a, **kw)
        return r._replace(distinct=r.distinct - 1)

    monkeypatch.setattr(checkpoint, "result_from_carry", one_state_short)
    rc, line, text = tiny.run("tiny-cwc.exhaustive", seconds=1.0)
    assert rc == 0 and line["correct"] is False
    assert "worst difference 1, limit 0" in text


def test_narrowed_fingerprints_underneath_the_engine_drop_states(tiny):
    """controls.json `fp32`, at a width a tiny state space can see: the
    engine's fingerprint keeps 12 bits, states collide and are lost."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from jaxtlc.engine import backend

    orig = backend.fp64_words_mxu

    def narrowed(*a, **kw):
        lo, _ = orig(*a, **kw)
        lo = lo & jnp.uint32(0xFFF)
        return lo, lo ^ jnp.uint32(0x9E3779B9)

    backend.fp64_words_mxu = narrowed
    try:
        rc, line, text = tiny.run("tiny-cwc.exhaustive", seconds=1.0)
    finally:
        backend.fp64_words_mxu = orig
    assert rc == 0 and line["correct"] is False, text[-1500:]
    assert "compare distinct" in text and "worst difference 0" not in (
        next(ln for ln in text.splitlines() if "compare distinct" in ln))


def test_generators_only_control_keeps_more_than_one_state_an_orbit():
    """controls.json `generators-only` at a size a test can hold
    (Ballot == 0..1): the program with the patch finds more "orbits" (460;
    the plain reference's --generators-only, in its own order of
    comparison, 457) than the 443 the full group leaves, and the orbit
    certificate, which samples the kept programs, does not trip: only
    the pins catch it."""
    import io

    sys.path.insert(0, REPO)
    from jaxtlc.api import CheckRequest, run_check

    undo = control.PATCHES["generators-only"]()
    try:
        o = run_check(CheckRequest(
            config=os.path.join(REPO, "specs", "Paxos.toolbox", "Model_sym",
                                "MC.cfg"),
            constants={"Ballot": frozenset({0, 1})}, frontend="struct",
            workers="cpu", noTool=True, chunk=256, qcap=4096, fpcap=16384,
            out=io.StringIO()))
    finally:
        undo()
    assert o.verdict == "ok" and o.result.sym_cert_trips == 0
    assert o.result.distinct == 460 > 443 and o.result.depth == 17


def test_controls_file_names_real_cells_and_edits():
    bench = load("../BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    controls = load("controls.json")["controls"]
    assert {c for v in controls.values() for c in v["cells"]} == cells
    for name, c in controls.items():
        assert c.get("edit") or c.get("patch") in control.PATCHES, name


def test_served_cell_and_its_verdict_cache_control(checkout, tmp_path):
    """The served entry end to end over HTTP (a slow mix of the cell's own
    shape, as a NEW traffic file), then controls.json `verdict-cache`: with
    a store switched on the scheduler answers from the verdict tier, no
    device is named, and the run is not correct."""
    co = checkout
    mix = load("traffic/served.json")
    mix["arrivals"]["rate_per_s"] = 2.0
    mix["drain_s"] = 60.0
    write_json(co.path("traffic", "served-slow.json"), mix)
    co.bench["per_layer"].append(dict(
        name="jobs_p95", unit="ms", better="lower", source="host_clock",
        layer="entry, supervisor, served path", moves="verdict_ms.p50",
        workloads=["raft.slow"]))
    os.link(co.path("layers", "verdict_ms.p95.py"),
            co.path("layers", "jobs_p95.py"))
    co.add_cell("raft.slow", "raftrepl-model1", "served-slow",
                ["verdict_ms.p50", "verdict_ms.mean"],
                ["host_overhead_ms", "queue_wait_ms"])
    rc, line, text = co.run("raft.slow", seed=2**31 + 99, seconds=3.0)
    assert rc == 0 and line["correct"] is True, text[-2500:]
    assert (line["attempted"], line["failed"]) == (6, 0)
    assert set(line["metrics"]) == {"verdict_ms.p50", "verdict_ms.mean",
                                    "setup_s"}
    assert "generator lateness" in text
    assert "compare action_generated: 6 jobs, 5 actions each, 0 jobs" in text
    rc, line, _ = co.run("raft.slow", seed=7, seconds=3.0, trace=1)
    assert {"host_overhead_ms", "queue_wait_ms"} <= set(line["metrics"])
    assert "jobs_p95" not in line["metrics"]  # 6 jobs have no tail
    control.edit_config(co.root, "benchmark/configs/raftrepl-model1.json",
                        {"env.JAXTLC_ARTIFACT_CACHE": str(tmp_path / "store")})
    try:
        rc, line, text = co.run("raft.slow", seed=7, seconds=3.0)
    finally:
        os.environ["JAXTLC_ARTIFACT_CACHE"] = "off"
    assert rc == 0 and line["correct"] is False, text[-2500:]
    assert line["failed"] >= 1 and "engine 'cache'" in text
    assert "does not name a cpu" in text
