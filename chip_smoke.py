#!/usr/bin/env python3
"""Chip smoke: the quickest proof that jaxtlc still starts on the TPU.

Drives the main path once, in ONE process, through the entry points a
user calls, and gates every leg on exact state counts:

  L1  jaxtlc.cli check (hand frontend, default geometry, supervisor
      route, -analyze) on KubeAPI Model_1      577,736 / 163,408 / 124
  L3  api.run_check -frontend struct on RaftReplication Model_1 (a toy:
      it proves the lane-compiled step compiles and is exact on the
      chip, nothing about speed)                 17,431 /   7,279 /  14
  L4  in-process jaxtlc.serve + serve.client over HTTP: cold submit
      (pool), the same again (pool hit, zero compiles), one job above
      large_fpcap (supervised route)            L3's counts each
  L5  jaxtlc.cli check -sharded D over every local device (D >= 2), or
      "not run: 1 device" in so many words      L1's counts, all tables
                                                occupied
  L2  engine.checkpoint.check_with_checkpoints on scaled 2x1FF at its
      full geometry (config.scaled_config; fp table 2^26 slots,
      ~104k-state levels)               62,014,325 / 19,359,985 / 186

L2 runs last so that the peak-memory reading after each smaller leg is
still that leg's own.  Supervised legs also fail on any regrow, retry,
shrink or spill: the degradation ladder is a product feature, but a
smoke that finished from host RAM did not run on the chip it names.

It refuses to start unless jax.devices()[0].platform == "tpu" (exit 2,
nothing on stdout), and only imports the jaxtlc that sits next to this
file.  On success the LAST stdout line is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}};
any failed leg makes the exit code 1 and that line is not printed.  The
per-leg report (compile s, run s, peak bytes, compile-cache hits) goes
to stdout as `LEG {json}` lines and to chiprun_out/chip_smoke.json.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
KUBE_CFG = os.path.join(HERE, "specs", "KubeAPI.toolbox", "Model_1",
                        "MC.cfg")
RAFT_DIR = os.path.join(HERE, "specs", "RaftReplication.toolbox",
                        "Model_1")
RAFT_CFG = os.path.join(RAFT_DIR, "MC.cfg")

MODEL_1 = (577736, 163408, 124)  # the reference TLC run (MC.out)
RAFT = (17431, 7279, 14)  # host-oracle pin (tests/test_raft_replication)
SCALED = (62014325, 19359985, 186)  # SCALED_VALIDATION.json pins

# journal events that mean the supervisor left the plain device path
LADDER_EVENTS = ("regrow", "retry", "degrade", "spill")


class LegFailed(AssertionError):
    pass


def _require(cond, msg):
    if not cond:
        raise LegFailed(msg)


def _read_journal(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _journal_facts(events, expect, what):
    """Gate a run journal: TPU named in run_start, exact final counts,
    no degradation-ladder event."""
    start = next(e for e in events if e["event"] == "run_start")
    final = next(e for e in events if e["event"] == "final")
    _require("tpu" in start["device"].lower(),
             f"{what}: run_start device is {start['device']!r}")
    got = (final["generated"], final["distinct"], final["depth"])
    _require(final["verdict"] == "ok" and got == expect
             and final["queue"] == 0,
             f"{what}: final {final['verdict']} {got}, want ok {expect}")
    ladder = [e["event"] for e in events if e["event"] in LADDER_EVENTS]
    _require(not ladder, f"{what}: left the device path: {ladder}")
    facts = dict(device=start["device"], counts=list(got),
                 engine=start["engine"], run_s=final["wall_s"],
                 params=start["params"])
    if "shard_distinct" in final:  # mesh runs: per-device occupancy
        facts["shard_distinct"] = final["shard_distinct"]
    return facts


def _require_preflight_ran(transcript, what):
    # lint WARNINGS are the preflight working; a skipped or crashed
    # audit is the guard silently not running
    for bad in ("Preflight analysis skipped", "crashed"):
        _require(bad not in transcript,
                 f"{what}: {bad!r} in transcript:\n{transcript[:2000]}")


def _cli_check(argv, expect, what):
    """One `jaxtlc.cli check` run with its transcript and journal."""
    from jaxtlc.cli import main as cli_main

    with tempfile.TemporaryDirectory() as d:
        journal = os.path.join(d, "run.journal.jsonl")
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main(["check", *argv, "-noTool", "-journal",
                           journal])
        transcript = out.getvalue()
        _require(rc == 0, f"{what}: exit {rc}\n{transcript[-2000:]}")
        facts = _journal_facts(_read_journal(journal), expect, what)
    _require_preflight_ran(transcript, what)
    _require(f"{expect[0]} states generated, {expect[1]} distinct"
             in transcript, f"{what}: counts missing from transcript")
    return facts


def leg_l1():
    return _cli_check([KUBE_CFG, "-frontend", "hand", "-analyze"],
                      MODEL_1, "L1")


def leg_l2():
    from jaxtlc.config import scaled_config
    from jaxtlc.engine.bfs import resolve_deferred
    from jaxtlc.engine.checkpoint import check_with_checkpoints

    cfg, kw = scaled_config()
    r = check_with_checkpoints(cfg, ckpt_every=64, **kw)
    got = (r.generated, r.distinct, r.depth)
    _require(r.violation == 0 and r.queue_left == 0 and got == SCALED,
             f"L2: {r.violation_name if r.violation else 'ok'} {got}, "
             f"want {SCALED}")
    return dict(
        counts=list(got), run_s=round(r.wall_s, 3),
        segments=r.iterations, geometry=kw,
        deferred_inv=resolve_deferred(None, kw["chunk"]),
    )


def leg_l3():
    from jaxtlc.api import CheckRequest, run_check

    with tempfile.TemporaryDirectory() as d:
        journal = os.path.join(d, "run.journal.jsonl")
        out = io.StringIO()
        outcome = run_check(CheckRequest(
            config=RAFT_CFG, frontend="struct", nodeadlock=True,
            noTool=True, journal=journal, out=out, err=out,
        ))
        _require(outcome.exit_code == 0,
                 f"L3: exit {outcome.exit_code}\n{out.getvalue()[-2000:]}")
        facts = _journal_facts(_read_journal(journal), RAFT, "L3")
    _require_preflight_ran(out.getvalue(), "L3")
    facts["note"] = "toy spec (7,279 distinct): exactness only"
    return facts


def leg_l4():
    from jaxtlc.serve import CompileMeter, client, start_server
    from jaxtlc.serve.scheduler import DEFAULT_LARGE_FPCAP

    with open(os.path.join(RAFT_DIR, "RaftReplication.tla")) as f:
        spec = f.read()
    with open(RAFT_CFG) as f:
        cfg = f.read()
    meter = CompileMeter.instance()
    _require(meter.available, "L4: compile meter not listening")
    small = dict(chunk=1024, qcap=1 << 15, fpcap=DEFAULT_LARGE_FPCAP,
                 nodeadlock=True)
    # the CLI's default geometry, i.e. L3's engine: above large_fpcap,
    # and its compile is already in this process's cache
    large = dict(small, fpcap=1 << 20)
    _require(large["fpcap"] > DEFAULT_LARGE_FPCAP, "L4: large is small")
    jobs = {}
    with tempfile.TemporaryDirectory() as root:
        srv = start_server(root)
        try:
            def submit(name, options):
                st = client.check(srv.url, spec, cfg, name=name,
                                  options=options, timeout=900.0)
                res = st.get("result") or {}
                got = (res.get("generated"), res.get("distinct"),
                       res.get("depth"))
                _require(st["state"] == "done"
                         and res.get("verdict") == "ok" and got == RAFT,
                         f"L4 {name}: {st['state']} {res.get('verdict')} "
                         f"{got}, want ok {RAFT}: {st.get('error')}")
                events = list(client.stream(srv.url, st["id"]))
                jobs[name] = _journal_facts(events, RAFT, f"L4 {name}")
                del jobs[name]["params"]  # same geometry dicts as above
                return res

            cold = submit("cold", small)
            _require(cold["engine"] == "pool", f"L4 cold: {cold}")
            before = meter.count
            warm = submit("warm", small)
            warm_compiles = meter.count - before
            _require(warm["engine"] == "pool" and warm["pool_hit"] is True
                     and warm_compiles == 0,
                     f"L4 warm: pool_hit={warm.get('pool_hit')} "
                     f"compiles={warm_compiles}")
            big = submit("large", large)
            _require(big["engine"] == "supervised", f"L4 large: {big}")
        finally:
            srv.shutdown()
    return dict(jobs=jobs, warm_compiles=warm_compiles,
                large_fpcap=DEFAULT_LARGE_FPCAP)


def leg_l5(n_devices):
    facts = _cli_check(
        [KUBE_CFG, "-frontend", "hand", "-sharded", str(n_devices)],
        MODEL_1, "L5")
    shards = facts.get("shard_distinct")
    _require(shards is not None and len(shards) == n_devices
             and all(v > 0 for v in shards)
             and sum(shards) == MODEL_1[1],
             f"L5: per-device table occupancy {shards}")
    return facts


def run_leg(name, fn, devices):
    """Run one leg; never raises.  Returns its report dict with the
    compile/run split and the device-memory peak so far."""
    from jaxtlc.serve import CompileMeter

    gc.collect()
    meter = CompileMeter.instance()
    c0, h0, w0 = meter.count, meter.cache_hits, meter.wall_s
    t0 = time.time()
    try:
        report = dict(leg=name, ok=True, **fn())
    except Exception as e:
        report = dict(leg=name, ok=False,
                      error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc())
    requests, hits = meter.count - c0, meter.cache_hits - h0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    report.update(
        wall_s=round(time.time() - t0, 3),
        compile_s=round(meter.wall_s - w0, 3),
        compile_requests=requests, cache_hits=hits,
        backend_compiles=requests - hits,
        peak_bytes_in_use=max((p for p in peaks if p is not None),
                              default=None),
    )
    print("LEG " + json.dumps(report, default=str), flush=True)
    return report


def run_legs(devices):
    """All legs, in order; a failed leg does not stop the next (each is
    gated on its own), and any failure fails the smoke."""
    n = len(devices)
    legs = [("L1", leg_l1), ("L3", leg_l3), ("L4", leg_l4)]
    if n >= 2:
        legs.append(("L5", lambda: leg_l5(n)))
    legs.append(("L2", leg_l2))
    reports = [run_leg(name, fn, devices) for name, fn in legs]
    if n < 2:
        skipped = dict(leg="L5", ok=True, sharded="not run: 1 device")
        print("LEG " + json.dumps(skipped), flush=True)
        reports.append(skipped)
    return reports


def main() -> int:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices))
    banner = (f"chip_smoke: platform={d0.platform} "
              f"device_kind={d0.device_kind} "
              f"device_count={len(devices)} jax={jax.__version__}")
    if d0.platform != "tpu":
        print(f"{banner}\nchip_smoke: refusing to start: "
              f"jax.devices()[0] is {d0!r}, not a TPU - this script "
              "has no CPU mode", file=sys.stderr)
        return 2
    # a verdict-cache hit would answer a repeated spec with no engine
    # and no device: exactly what a smoke must not accept
    os.environ["JAXTLC_ARTIFACT_CACHE"] = "off"
    sys.path.insert(0, HERE)
    import jaxtlc

    if os.path.dirname(os.path.abspath(jaxtlc.__file__)) != os.path.join(
            HERE, "jaxtlc"):
        print(f"chip_smoke: imported jaxtlc from {jaxtlc.__file__}, not "
              f"from the checkout at {HERE}", file=sys.stderr)
        return 2
    from jaxtlc.runtime import enable_compile_cache

    print(banner, flush=True)
    cache_dir = enable_compile_cache()
    t0 = time.time()
    reports = run_legs(devices)
    failed = [r["leg"] for r in reports if not r["ok"]]
    summary = dict(ok=not failed, failed=failed, device=device,
                   jax=jax.__version__, compile_cache=cache_dir,
                   wall_s=round(time.time() - t0, 3), legs=reports)
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if failed:
        for r in reports:
            if not r["ok"]:
                print(f"chip_smoke: {r['leg']} FAILED: {r['error']}\n"
                      f"{r['traceback']}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
