"""From a profiler trace (.xplane.pb) to device busy time, idle share,
the device operations that took most time and the longest idle gaps by
what the host was doing.

Reads the file with jax.profiler.ProfileData and nothing else.  Needs no
scope names: a device plane is one whose name starts with "/device:",
and its operations are the events of its "XLA Ops" line (where a plane
has no such line, of all its lines but "Steps" and "XLA Modules", whose
events span whole programs).  Busy is the union of those events'
intervals, clipped to the traced window; operations nest (a `while`
holds its body), so an operation's own time is its duration less its
children's.  An idle gap is named by the innermost host span that covers
its midpoint: the program's own (`jaxtlc:*`, jaxtlc/obs/spans.py:
`jaxtlc:build.lower`, `jaxtlc:loop.wait`, ...) inside the harness's
(`bench:*`, put around submit, wait and the call into the program).  Both
kinds are taken from the trace, and from what the harness and the
program's recorder kept on the host clock itself (`host_spans`, mapped
onto the trace's clock at the slice's start), because a span that began
before the profiler did is not in the trace.

The window is the harness's own `bench:trace_slice` annotation, which
it opens right after the profiler starts and closes right before it
stops; device events are clipped to it.  A trace without one is taken
from its first to its last event.
"""

from __future__ import annotations

import bisect
import functools
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")
HARNESS_PREFIX = ("bench:", "jaxtlc:")  # the harness's, the program's
SLICE_SPAN = "bench:trace_slice"
NO_SPAN = "no harness span (no job in flight)"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@functools.lru_cache(maxsize=None)
def short_name(name: str) -> str:
    """XLA names a device event by its whole HLO instruction; keep the
    instruction's name and its opcode ("%fusion.11 fusion")."""
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(rest)
    return f"{lhs} {m.group(1)}" if m else lhs[:120]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Own time by name for nested events of one line (ns)."""
    own: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, duration, children]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            e = stack.pop()
            own[e[1]] = own.get(e[1], 0.0) + max(0.0, e[2] - e[3])
        if stack:
            stack[-1][3] += end - start
        stack.append([end, name, end - start, 0.0])
    for e in stack:
        own[e[1]] = own.get(e[1], 0.0) + max(0.0, e[2] - e[3])
    return own


def _innermost(spans: List[Tuple[float, float, str]]):
    """(a, b) -> the name of the shortest span that covers the midpoint
    of [a, b], NO_SPAN where none does.  The spans' edges cut the clock
    into pieces with one answer each, found once; a look-up is a
    bisection (a slice of served traffic holds thousands of gaps and
    hundreds of spans)."""
    edges = sorted({x for s in spans for x in s[:2]})
    names = []
    by_length = sorted(spans, key=lambda s: s[1] - s[0])
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        names.append(next((s[2] for s in by_length
                           if s[0] <= mid <= s[1]), NO_SPAN))

    def host_doing(a: float, b: float) -> str:
        i = bisect.bisect_right(edges, (a + b) / 2) - 1
        return names[i] if 0 <= i < len(names) else NO_SPAN

    return host_doing


def reduce_planes(planes: List[dict], top: int = 10,
                  host_spans: Optional[List[Tuple[str, float, float]]] = None,
                  slice_t0: Optional[float] = None) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(start_ns,
    end_ns, name)]}]}].  Pure arithmetic, so it can be tested without a
    trace file."""
    device = [p for p in planes if p["name"].startswith("/device:")
              and any(ln["events"] for ln in p["lines"])]
    every = [e for p in planes for ln in p["lines"] for e in ln["events"]]
    if not every:
        return dict(n_devices=0, busy_s=0.0, window_s=0.0, idle_pct=None,
                    device_ops=[], idle_gaps=[])
    slices = [e for e in every if e[2] == SLICE_SPAN]
    if slices:
        w0, w1 = slices[0][0], slices[0][1]
    else:
        w0 = min(e[0] for e in every)
        w1 = max(e[1] for e in every)
    window = (w1 - w0) / 1e9
    spans_ns = [e for p in planes if not p["name"].startswith("/device:")
                for ln in p["lines"] for e in ln["events"]
                if e[2].startswith(HARNESS_PREFIX) and e[2] != SLICE_SPAN]
    if host_spans and slices and slice_t0 is not None:
        # the harness's own log, host seconds -> the trace's ns
        spans_ns += [(w0 + (a - slice_t0) * 1e9, w0 + (b - slice_t0) * 1e9,
                      name) for name, a, b in host_spans]
    host_doing = _innermost([h for h in spans_ns if h[1] > w0 and h[0] < w1])
    busy_each: List[float] = []
    own: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for p in device:
        lines = [ln for ln in p["lines"] if ln["name"] == OPS_LINE] or [
            ln for ln in p["lines"] if ln["name"] not in SKIP_LINES]
        spans = _union([(max(e[0], w0), min(e[1], w1)) for ln in lines
                        for e in ln["events"] if e[1] > w0 and e[0] < w1])
        busy_each.append(sum(b - a for a, b in spans) / 1e9)
        for ln in lines:
            for k, v in _self_times(ln["events"]).items():
                own[k] = own.get(k, 0.0) + v
        edges = [w0] + [x for s in spans for x in s] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(device)
    busy = sum(busy_each) / n if n else 0.0

    by_what: Dict[str, float] = {}
    for a, b in gaps:
        k = host_doing(a, b)
        by_what[k] = by_what.get(k, 0.0) + (b - a) / 1e9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return dict(
        n_devices=n, busy_s=busy, window_s=window,
        idle_pct=(100.0 * (1.0 - busy / window)) if n and window else None,
        device_ops=[[k, v / 1e9 / n] for k, v in sorted(
            own.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[host_doing(a, b), (b - a) / 1e9] for a, b in longest],
        idle_by_host_span=sorted(by_what.items(), key=lambda kv: -kv[1]),
    )


def load_planes(path: str) -> List[dict]:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        keep_all = p.name.startswith("/device:")
        lines = []
        for ln in p.lines:
            evs = []
            for e in ln.events:
                name = e.name
                if not keep_all and not name.startswith(HARNESS_PREFIX):
                    continue
                s = float(e.start_ns)
                evs.append((s, s + float(e.duration_ns),
                            short_name(name) if keep_all else name))
            lines.append(dict(name=ln.name, events=evs))
        planes.append(dict(name=p.name, lines=lines))
    return planes


def reduce_file(path: str, top: int = 10, host_spans=None,
                slice_t0: Optional[float] = None) -> dict:
    return reduce_planes(load_planes(path), top, host_spans, slice_t0)
