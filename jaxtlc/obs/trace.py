"""Chrome-trace (Perfetto) export of a run journal: the timeline tier.

Renders the journal's host-observed intervals as a `chrome://tracing` /
https://ui.perfetto.dev JSON file (`-trace-out run.trace.json`):

* pid "device": one slice per supervised segment (dispatch -> fence).
  The segment slice is all the journal knows about the device: nothing
  is drawn inside it (the per-level counters feed the counter tracks,
  at the fence they were read back at).  Ground-truth device timelines
  come from `-xprof DIR` (jax.profiler); the engine's stages, the
  `jaxtlc.*` named scopes, are not in that trace on a TPU: the check
  joins them to it (obs.scopes) and says where the device's time went
  in one `device_scopes` journal event, with the instruction -> scope
  tables beside the trace in `DIR/jaxtlc_scopes.json`.
* pid "host": the check's host spans (the `spans` event, obs.spans:
  `build` with its trace / lower / compile children, `loop` with its
  per-segment dispatch / overlap / wait / readback) as nested slices on
  one thread, at the times the recorder measured; checkpoint-write and
  regrow-migration slices, plus instant markers for retries, faults,
  interruption, recovery and the final verdict - so "why was this
  segment slow" is one glance (the TensorFlow timeline discipline,
  arXiv:1605.08695 §5).
* counter tracks: distinct states, queue depth and fingerprint-table
  load per level, which Perfetto renders as rate/occupancy graphs.

The export is a pure function of the journal events (obs.journal), so
it can be produced live (`-trace-out`), after the fact from any
journal file (`python -m jaxtlc.obs.trace run.journal.jsonl`), or
across an interruption - a SIGTERM'd + `-recover`ed run's single
continuous journal renders as one timeline with the gap visible.

Pod runs (ISSUE 20): a merged ``{base}.hN`` sibling stream renders as
ONE trace with a process-row PAIR per host (device lanes + host lanes,
keyed by the events' ``host`` field).  Every host's segment slices
share the same time origin, so cross-host skew is the horizontal
offset between the rows' fence edges, and the all_to_all fence wait is
the gap a fast host's segment end leaves before the slow host's - the
distributed-timeline reading the TensorFlow timeline discipline
(arXiv:1605.08695 §5) is built for.  Spill flushes carry their
measured wall (the highwater-triggered sweep) and render as duration
slices on their host's row.
"""

from __future__ import annotations

import json
import os
from typing import List

PID_DEVICE = 1
PID_HOST = 2
POD_PID_BASE = 10  # host h -> pids (BASE + 2h, BASE + 2h + 1)
TID_SEGMENT = 1
TID_CKPT = 1
TID_REGROW = 2
TID_SPANS = 3


def _meta(pid: int, name: str) -> dict:
    return {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": name}}


def _thread(pid: int, tid: int, name: str) -> dict:
    return {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name}}


def chrome_trace_events(events: List[dict]) -> List[dict]:
    """The journal -> traceEvents transform (timestamps in us, relative
    to the first journal event)."""
    if not events:
        return []
    # origin: the first journal event, or the earliest host span where
    # one began before it (check.resolve precedes run_start)
    t0 = min([events[0]["t"]] + [row[1] for ev in events
                                 if ev["event"] == "spans"
                                 for row in ev["rows"]])
    us = lambda t: (t - t0) * 1e6  # noqa: E731

    out = []
    known: set = set()

    def pid_device(h):
        return PID_DEVICE if h is None else POD_PID_BASE + 2 * h

    def pid_host(h):
        return PID_HOST if h is None else POD_PID_BASE + 2 * h + 1

    def ensure(h):
        """Emit the process/thread metadata rows for host key `h` once
        (None = the single-process row pair; pod hosts each get their
        own pair, so the merged journal renders one process row per
        host with identical lane structure)."""
        if h in known:
            return
        known.add(h)
        tag = "" if h is None else f" host {h}"
        out.extend([
            _meta(pid_device(h), f"device engine{tag}"),
            _meta(pid_host(h), f"host (checkpoint/regrow){tag}"),
            _thread(pid_device(h), TID_SEGMENT, "segments"),
            _thread(pid_host(h), TID_CKPT, "checkpoint writes"),
            _thread(pid_host(h), TID_REGROW, "regrow migrations"),
            _thread(pid_host(h), TID_SPANS, "host spans (obs.spans)"),
        ])

    ensure(None)

    def instant(ev, name, args=None, h=None):
        ensure(h)
        out.append({"name": name, "ph": "i", "s": "g",
                    "ts": us(ev["t"]), "pid": pid_host(h),
                    "tid": TID_CKPT, "args": args or {}})

    # a fence's level rows journal BEFORE its `segment` event, which
    # closes the fence: walk in order, buffering levels until their
    # segment arrives - PER HOST KEY, so a merged pod stream's
    # interleaved hosts never cross-attribute.  A journal from before
    # ISSUE 37 wrote the segment FIRST, then its `phase` rows (no
    # writer emits them since), then its levels: there the levels
    # belong to the segment before them
    pending_levels: dict = {}  # host key -> [level rows]
    last_segment: dict = {}  # host key -> segment event
    segment_first: dict = {}  # host key -> the last segment had phase rows
    prev_level: dict = {}  # host key -> last level event

    def flush_levels(h, seg):
        """Emit host `h`'s buffered levels' counter tracks at the fence
        of their segment: the journal does not know where in the
        segment a level ran, so no slice is drawn for it."""
        if seg is None:
            return
        levels = pending_levels.pop(h, [])
        pid = pid_device(h)
        end = us(seg["t_dispatch"]) + max(seg["wall_s"] * 1e6, 1.0)
        for lv in levels:
            out.append({"name": "states", "ph": "C", "ts": end,
                        "pid": pid, "tid": 0,
                        "args": {"distinct": lv["distinct"],
                                 "queue": lv["queue"]}})
            if "fp_load" in lv:
                out.append({"name": "fp_load", "ph": "C", "ts": end,
                            "pid": pid, "tid": 0,
                            "args": {"load": lv["fp_load"]}})

    for ev in events:
        kind = ev["event"]
        h = ev.get("host") if kind in (
            "segment", "level", "phase", "checkpoint", "spill") else None
        if kind == "segment":
            ensure(h)
            flush_levels(h, last_segment.get(h)
                         if segment_first.pop(h, False) else ev)
            last_segment[h] = ev
            out.append({
                "name": f"segment {ev['index']}", "ph": "X",
                "ts": us(ev["t_dispatch"]),
                "dur": max(ev["wall_s"] * 1e6, 1.0),
                "pid": pid_device(h), "tid": TID_SEGMENT,
                "args": {"index": ev["index"],
                         "wall_s": ev["wall_s"]},
            })
            if "readback_s" in ev:
                # the host readback wall behind the fence
                out.append({
                    "name": "readback", "ph": "X",
                    "ts": us(ev["t_fence"]),
                    "dur": max(ev["readback_s"] * 1e6, 1.0),
                    "pid": pid_host(h), "tid": TID_CKPT,
                    "args": {"segment": ev["index"]},
                })
        elif kind == "phase":
            if ev.get("scope") == "segment":
                segment_first[h] = True
        elif kind == "level":
            prev = prev_level.get(h)
            if prev is not None and prev["level"] == ev["level"]:
                # empty-queue trailing flips re-record the final
                # level's (identical, cumulative) row each no-op step
                continue
            prev_level[h] = ev
            pending_levels.setdefault(h, []).append(ev)
        elif kind == "spans":
            # the recorder's host spans, at the times it measured
            # (nested complete events on one thread: Perfetto stacks a
            # child under its parent by containment)
            for name, t_start, dur_s, _parent in ev["rows"]:
                out.append({
                    "name": name, "ph": "X", "ts": us(t_start),
                    "dur": max(dur_s * 1e6, 1.0), "pid": PID_HOST,
                    "tid": TID_SPANS, "args": {"wall_s": dur_s},
                })
        elif kind == "checkpoint":
            ensure(h)
            out.append({
                "name": f"checkpoint ({ev['label']})", "ph": "X",
                "ts": us(ev["t"] - ev["seconds"]),
                "dur": max(ev["seconds"] * 1e6, 1.0),
                "pid": pid_host(h), "tid": TID_CKPT,
                "args": {"path": ev["path"]},
            })
        elif kind == "regrow":
            out.append({
                "name": f"regrow {ev['resource']}", "ph": "X",
                "ts": us(ev["t"] - ev["seconds"]),
                "dur": max(ev["seconds"] * 1e6, 1.0),
                "pid": PID_HOST, "tid": TID_REGROW,
                "args": {"old": ev["old"], "new": ev["new"],
                         "violation": ev["violation"]},
            })
        elif kind == "retry":
            instant(ev, f"retry #{ev['attempt']}",
                    {"error": ev["error"]})
        elif kind == "fault":
            instant(ev, f"fault {ev['kind']}@{ev['at']}")
        elif kind == "spill":
            # the host tier's lifecycle rides the regrow thread (both
            # are host-side capacity work); also a counter track so
            # Perfetto graphs the cold-tier growth.  Highwater flushes
            # carry their measured wall (ISSUE 20) and render as
            # DURATION slices, so the timeline shows what the sweep
            # cost at the fence that paid it
            ensure(h)
            if ev.get("phase") == "flush" and ev.get("wall_s"):
                out.append({
                    "name": "spill flush", "ph": "X",
                    "ts": us(ev["t"] - ev["wall_s"]),
                    "dur": max(ev["wall_s"] * 1e6, 1.0),
                    "pid": pid_host(h), "tid": TID_REGROW,
                    "args": {"spilled": ev["spilled"],
                             "flushed_tables": ev.get("flushed_tables"),
                             "wall_s": ev["wall_s"]},
                })
            else:
                instant(ev, f"spill {ev['phase']}",
                        {"spilled": ev["spilled"],
                         "hits": ev.get("hits"),
                         "probes": ev.get("probes")}, h=h)
            out.append({"name": "spilled_fps", "ph": "C",
                        "ts": us(ev["t"]), "pid": pid_host(h), "tid": 0,
                        "args": {"spilled": ev["spilled"]}})
        elif kind == "degrade":
            instant(ev, f"degrade [{ev['rung']}] {ev['resource']}",
                    {"action": ev["action"], "reason": ev["reason"]})
        elif kind == "exhausted":
            instant(ev, f"exhausted ({ev['resource']})",
                    {"checkpoint": ev["path"],
                     "distinct": ev["distinct"]})
        elif kind == "interrupted":
            instant(ev, f"interrupted (signal {ev['signum']})",
                    {"checkpoint": ev["path"]})
        elif kind in ("recovery", "run_resume"):
            instant(ev, kind, {"path": ev["path"]})
        elif kind == "final":
            instant(ev, f"final: {ev['verdict']}",
                    {"generated": ev["generated"],
                     "distinct": ev["distinct"],
                     "wall_s": ev["wall_s"]})
    for h in list(pending_levels):
        flush_levels(h, last_segment.get(h))
    return out


def export_chrome_trace(events: List[dict], path: str) -> int:
    """Write the Perfetto-loadable JSON for `events` to `path` (fsync +
    rename, the checkpoint durability discipline).  Returns the number
    of trace events written."""
    from ..engine.checkpoint import fsync_replace

    trace = chrome_trace_events(events)
    doc = {"traceEvents": trace, "displayTimeUnit": "ms",
           "otherData": {"producer": "jaxtlc obs.trace"}}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
        fsync_replace(tmp, path, f=f)
    return len(trace)


def _tiny_journal(path: str) -> None:
    """A synthetic but schema-valid journal exercising every event kind
    the exporter renders (the --tiny smoke's input)."""
    from .journal import RunJournal

    with RunJournal(path) as j:
        base = j.event("run_start", version="tiny", workload="FF",
                       engine="single", device="cpu",
                       params={"pipeline": True, "chunk": 128})["t"]
        for s in range(2):
            td = base + 0.1 * s
            for i in range(2):
                lvl = 2 * s + i + 1
                j.event("level", level=lvl, generated=100 * lvl,
                        distinct=60 * lvl, queue=30, bodies=4 * lvl,
                        expanded=50 * lvl, fp_load=0.01 * lvl)
            j.event("progress", depth=2 * s + 2, generated=200 * (s + 1),
                    distinct=120 * (s + 1), queue=30)
            j.event("segment", index=s, t_dispatch=td,
                    t_fence=td + 0.09, wall_s=0.09, readback_s=0.002)
        j.event("checkpoint", path="ck.g000001.npz", seconds=0.004,
                label="periodic")
        j.event("spans", rows=[
            ["build.compile", base - 0.4, 0.3, 1],
            ["build", base - 0.5, 0.45, -1],
            ["loop.wait", base + 0.1, 0.09, 3],
            ["loop", base, 0.2, -1],
        ])
        j.event("device_scopes", t0=base, t1=base + 0.2, window_s=0.18,
                busy_s=0.17, n_devices=1, unscoped_s=0.01,
                unmatched_s=0.0, fallback_s=0.0,
                chains={"jaxtlc.dedup": 0.16},
                scopes=[dict(scope="jaxtlc.dedup", own_s=0.16,
                             pct_of_busy=94.12, incl_s=0.16, events=4,
                             top=[["fusion.1", "fusion", "u32[8]", 0.1]]),
                        dict(scope="unscoped", own_s=0.01,
                             pct_of_busy=5.88, incl_s=0.01, events=2,
                             top=[["copy.2", "copy", "u32[8]", 0.01]])])
        j.event("regrow", resource="fp_capacity", old=1 << 11,
                new=1 << 12, violation="fpset full", seconds=0.01)
        j.event("degrade", rung="regrow", resource="fp_capacity",
                action="denied", reason="RESOURCE_EXHAUSTED (tiny)")
        j.event("spill", phase="activate", resident=240, spilled=0,
                capacity=1 << 12, hits=0, probes=0)
        j.event("spill", phase="flush", resident=0, spilled=240,
                capacity=1 << 12, hits=12, probes=60, wall_s=0.003,
                flushed_tables=1)
        j.event("retry", attempt=1, delay_s=0.01, error="injected")
        j.event("interrupted", signum=15, path=None, generated=400,
                distinct=240, queue=30, wall_s=0.2)
        j.event("final", verdict="interrupted", generated=400,
                distinct=240, depth=4, queue=30, wall_s=0.2,
                interrupted=True)


def main(argv=None) -> int:
    """CLI: `python -m jaxtlc.obs.trace JOURNAL [-o OUT]` exports a
    journal file; `--tiny` self-tests the whole pipeline on a synthetic
    journal (wired into tier-1: tests/test_tools.py)."""
    import argparse
    import sys
    import tempfile

    from . import journal as jr

    p = argparse.ArgumentParser(prog="jaxtlc.obs.trace")
    p.add_argument("journal", nargs="?", help="run journal (JSONL)")
    p.add_argument("-o", "--out", default="", help="trace output path "
                   "(default: <journal>.trace.json)")
    p.add_argument("--tiny", action="store_true",
                   help="smoke: synthesize a journal, export it, "
                        "validate the result")
    args = p.parse_args(argv)
    if args.tiny:
        with tempfile.TemporaryDirectory() as d:
            jpath = os.path.join(d, "tiny.journal.jsonl")
            _tiny_journal(jpath)
            events = jr.read(jpath)
            out = args.out or os.path.join(d, "tiny.trace.json")
            n = export_chrome_trace(events, out)
            with open(out) as f:
                doc = json.load(f)
            assert doc["traceEvents"] and n == len(doc["traceEvents"])
            names = {e.get("name", "") for e in doc["traceEvents"]}
            # bare segments: two slices, nothing drawn inside them,
            # each level's counters at its segment's fence
            assert {"segment 0", "segment 1", "readback"} <= names
            assert not any(s.startswith(("expand L", "commit L"))
                           for s in names)
            fences = {e["ts"] + e["dur"] for e in doc["traceEvents"]
                      if e.get("name", "").startswith("segment ")}
            states = [e["ts"] for e in doc["traceEvents"]
                      if e.get("name") == "states"]
            assert len(states) == 4 and set(states) == fences
            assert {"build", "build.compile", "loop.wait"} <= names
            assert min(e["ts"] for e in doc["traceEvents"]
                       if "ts" in e) >= 0
        print(f"trace-export tiny OK: {n} trace events "
              f"({len(events)} journal events)")
        return 0
    if not args.journal:
        p.error("journal path required (or --tiny)")
    events = jr.read(args.journal, validate=False)
    out = args.out or args.journal + ".trace.json"
    n = export_chrome_trace(events, out)
    print(f"wrote {n} trace events from {len(events)} journal events "
          f"to {out}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys

    sys.exit(main())
