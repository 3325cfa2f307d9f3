"""Device scopes made readable: instruction -> `jaxtlc.*` scope, and a
profiler trace reduced by scope.

The engines name their device stages with `jax.named_scope`
(`jaxtlc.expand`, `jaxtlc.dedup`, `jaxtlc.fpset`, ...: PERF.md section 3
lists them).  On a TPU those names are neither a line nor an event of
the trace: they are the `op_name` metadata of each HLO instruction of
the executable, and only the process that holds the LOADED executable
can read them (metadata is not in the compile cache's key, so a cached
executable carries the names of whatever source compiled it first, and
a lowering's text is not the chip's).  So the program exports the map
itself:

* `table_of(compiled)`: {instruction name: (scope chain, opcode, shape,
  ...)} of every instruction of the executable's own HLO modules.
  `runtime.aot_build` registers each executable it builds or keeps
  (`register`: one weak reference, no parse); `tables()` parses on
  first demand and keeps the table as long as the executable lives.  A
  check that nobody profiles parses nothing.
* `reduce_planes(planes, tables, window)`: own time of each `XLA Ops`
  event (a `while` less its body) attributed to the innermost scope of
  its instruction.  Matched-but-unscoped and unmatched time are rows
  like any other, so coverage is stated, not assumed.
* `write_sidecar(DIR)` puts the tables beside a trace
  (`DIR/jaxtlc_scopes.json`) so the join survives the process;
  `python -m jaxtlc.obs.scopes DIR` reduces and prints after the fact.

`-xprof DIR` (api._xprof) does all three when the profiler stops; a
caller who profiles a slice of a long loop himself calls
`write_sidecar(DIR)` before the process ends.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import threading
import weakref
from typing import Dict, Iterable, List, Optional, Tuple

UNSCOPED = "unscoped"  # a table knows the instruction, no scope on it
UNMATCHED = "unmatched"  # no table knows it (eager programs, init_fn)
SIDECAR = "jaxtlc_scopes.json"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# opcodes that never run on their own: plumbing of the HLO graph
PLUMBING = frozenset(("parameter", "constant", "tuple",
                      "get-tuple-element", "bitcast"))
SHAPE_CHARS = 96  # of an instruction's shape kept (a while's is its carry)
TOP = 3  # of a scope's longest instructions kept

_SCOPE = re.compile(r"jaxtlc\.[A-Za-z0-9_.]+")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s+\(.*\{\s*$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INNER = re.compile(r"\b(?:calls|to_apply)=%?([^\s,}]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def chain_of(op_name: str) -> Tuple[str, ...]:
    """The `jaxtlc.*` components of an `op_name`, outermost first:
    `jit(f)/jaxtlc.expand/jaxtlc.dedup/jit(sort)/sort` ->
    ("jaxtlc.expand", "jaxtlc.dedup").  A transform's wrapper
    (`vmap(jaxtlc.level)`) still names its scope.  Each scope once (an
    inner jit's `op_name` repeats its caller's prefix:
    `jaxtlc.expand/jaxtlc.canon/.../jaxtlc.expand/jaxtlc.canon`), the
    innermost kept last: it is what time is attributed to."""
    found = _SCOPE.findall(op_name)
    if not found:
        return ()
    once = dict.fromkeys(found)
    once.pop(found[-1])
    return (*once, found[-1])


def parse_hlo_text(text: str, module: str = "") -> dict:
    """One module's table from its HLO text: {"module", "chains":
    [chain, ...], "instructions": {name: [chain index, opcode, shape,
    runs, agreed]}}.  `runs` is 1 where the instruction stands in a
    computation the device walks (entry, a while's body or condition, a
    branch, a call) and 0 inside a fused computation or a reducer.  A
    fusion with no `op_name` of its own takes its fused computation's
    root's.  Where the root has none either (a multi-output fusion's
    root is a bare tuple) and every named instruction of the fused
    computation stands under ONE chain, the fusion takes that chain and
    `agreed` is 1, so that the reduction can say how much time it
    placed that way (`fallback_s`); where they disagree the fusion is
    `unscoped`: never a guess."""
    chains: Dict[Tuple[str, ...], int] = {(): 0}
    instructions: Dict[str, list] = {}
    roots: Dict[str, tuple] = {}  # computation -> its named root's chain
    under: Dict[str, set] = {}  # computation -> its named ones' chains
    inner = set()  # computations a fusion or a reducer holds
    bare = []  # (fusion name, called computation): no op_name of its own
    where: Dict[str, str] = {}  # instruction -> its computation
    computation = ""
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            elif not module:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        root, name, rest = m.groups()
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        shape = rest[:op.start()].strip() if op else ""
        named = _OP_NAME.search(rest)
        op_name = named.group(1) if named else None
        chain = chain_of(op_name) if op_name else ()
        if op_name:
            under.setdefault(computation, set()).add(chain)
            if root:
                roots[computation] = chain
        inner.update(_INNER.findall(rest))
        if opcode == "fusion" and op_name is None:
            called = _INNER.search(rest)  # a fusion's one: `calls=`
            if called:
                bare.append((name, called.group(1)))
        instructions[name] = [chains.setdefault(chain, len(chains)),
                              opcode, shape[:SHAPE_CHARS], 1, 0]
        where[name] = computation
    for name, called in bare:
        if called in roots:
            chain = roots[called]
        else:
            one = under.get(called, ())
            chain = next(iter(one)) if len(one) == 1 else ()
            instructions[name][4] = int(bool(chain))
        instructions[name][0] = chains.setdefault(chain, len(chains))
    for name, computation in where.items():
        if computation in inner:
            instructions[name][3] = 0
    return dict(module=module, chains=[list(c) for c in chains],
                instructions=instructions)


def table_of(compiled) -> List[dict]:
    """The tables of an executable's own HLO modules (one a module;
    `as_text()` where the runtime gives no modules)."""
    try:
        modules = compiled.runtime_executable().hlo_modules()
    except (AttributeError, NotImplementedError, RuntimeError):
        return [parse_hlo_text(compiled.as_text())]
    return [parse_hlo_text(m.to_string(), m.name) for m in modules]


def cover(table: dict) -> Tuple[int, int]:
    """(instructions under a `jaxtlc.*` scope, all that can run on their
    own) of one table: a count, not time - the guard that the scopes
    still reach the executable's HLO."""
    scoped = total = 0
    chains = table["chains"]
    for chain, opcode, _shape, runs, _agreed in \
            table["instructions"].values():
        if not runs or opcode in PLUMBING:
            continue
        total += 1
        scoped += bool(chains[chain])
    return scoped, total


# -- the registry: executables this process holds, tables on demand --------

_lock = threading.Lock()
# executable -> its tables (None until somebody asks); weak, so a table
# lives exactly as long as the executable runtime.aot_build keeps
_registered: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def register(compiled) -> None:
    """Note an executable whose table may be asked for.  No parse."""
    with _lock:
        _registered.setdefault(compiled, None)


def tables() -> List[dict]:
    """The tables of every registered executable still alive, parsed
    now where nobody asked before."""
    with _lock:
        live = list(_registered.items())
    out = []
    for compiled, known in live:
        if known is None:
            known = table_of(compiled)
            with _lock:
                _registered[compiled] = known
        out.extend(known)
    return out


def write_sidecar(trace_dir: str) -> str:
    """Write this process's tables to `trace_dir/jaxtlc_scopes.json`
    (whoever profiles this process calls it before the process ends);
    returns the path."""
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, SIDECAR)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(dict(v=1, tables=tables()), f, separators=(",", ":"))
    os.replace(tmp, path)
    return path


def read_sidecar(trace_dir: str) -> List[dict]:
    with open(os.path.join(trace_dir, SIDECAR), encoding="utf-8") as f:
        return json.load(f)["tables"]


# -- the reduction ---------------------------------------------------------


def instruction_of(event_name: str) -> str:
    """An `XLA Ops` event is named by its HLO instruction, whole
    (`%fusion.11 = u32[8]{0} fusion(...)`) or cut short by a loader
    (`%fusion.11 fusion`): the instruction's name either way."""
    return event_name.lstrip("%").split(" ", 1)[0]


def _own_times(events: Iterable[tuple], w0: float, w1: float) -> List[tuple]:
    """[(start, name, own ns)] of nested events of one line, clipped to
    [w0, w1]: an event's duration less its direct children's."""
    out = []
    stack: List[list] = []  # [end, start, name, duration, children]

    def close():
        end, start, name, duration, children = stack.pop()
        out.append((start, name, max(0.0, duration - children)))

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        a, b = max(start, w0), min(end, w1)
        if b <= a:
            continue
        while stack and a >= stack[-1][0]:
            close()
        if stack:
            stack[-1][4] += b - a
        stack.append([b, a, name, b - a, 0.0])
    while stack:
        close()
    return out


def _union_ns(events: Iterable[tuple], w0: float, w1: float) -> float:
    busy, reach = 0.0, w0
    for start, end, _ in sorted(events):
        a, b = max(start, reach), min(end, w1)
        if b > a:
            busy += b - a
            reach = b
    return busy


class _Lookup:
    """instruction (and the module that ran it) -> (chain, opcode,
    shape, agreed), or None where no table knows it or two disagree: the tables
    of the module's name first, then every table."""

    def __init__(self, tables: List[dict]):
        self.tables = tables
        self.by_module: Dict[str, List[dict]] = {}
        for t in tables:
            self.by_module.setdefault(t["module"], []).append(t)
        self.known: Dict[tuple, Optional[tuple]] = {}

    def __call__(self, name: str, module: Optional[str]):
        key = (name, module)
        if key not in self.known:
            self.known[key] = self._find(name, module)
        return self.known[key]

    def _find(self, name, module):
        for among in (self.by_module.get(module, ()), self.tables):
            rows = [(t, t["instructions"].get(name)) for t in among]
            hits = [(t["chains"][row[0]], row[1], row[2], row[4])
                    for t, row in rows if row]
            if hits:
                chain = hits[0][0]
                return hits[0] if all(h[0] == chain for h in hits) else None
        return None


def reduce_planes(planes: List[dict], tables: List[dict],
                  window: Optional[Tuple[float, float]] = None) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(start_ns,
    end_ns, name)]}]}] (what benchmark/trace_reduce.load_planes gives);
    `window`: (start_ns, end_ns) on the trace's clock, else from the
    first device operation to the last.  Pure arithmetic.

    Out: `window_s`, `busy_s` (mean over devices of the union of their
    operations), `n_devices`, `scopes`: one row a scope, longest first
    - `scope`, `own_s` (mean over devices), `pct_of_busy`, `incl_s` (own
    time of everything under the scope, at any depth), `events`, `top`
    ([instruction, opcode, shape, own_s] of its longest instructions) -
    among them the rows `unscoped` and `unmatched`, whose seconds are
    also `unscoped_s` / `unmatched_s`; `fallback_s`: of the scoped
    seconds, those of fusions placed by their fused computation's
    agreeing instructions (parse_hlo_text); `chains`: inclusive seconds
    by chain ("jaxtlc.expand/jaxtlc.dedup"); on a mesh `devices`: each
    device's busy seconds and own seconds by scope."""
    device = [p for p in planes if p["name"].startswith("/device:")
              and any(ln["name"] == OPS_LINE and ln["events"]
                      for ln in p["lines"])]
    ops = [[e for ln in p["lines"] if ln["name"] == OPS_LINE
            for e in ln["events"]] for p in device]
    if window is None and device:
        window = (min(e[0] for evs in ops for e in evs),
                  max(e[1] for evs in ops for e in evs))
    n = len(device)
    out = dict(window_s=0.0, busy_s=0.0, n_devices=n, scopes=[],
               unscoped_s=0.0, unmatched_s=0.0, fallback_s=0.0, chains={})
    if not n:
        return out
    w0, w1 = window
    lookup = _Lookup(tables)
    own: Dict[str, float] = {}
    incl: Dict[str, float] = {}
    count: Dict[str, int] = {}
    chains: Dict[str, float] = {}
    instr: Dict[str, Dict[str, list]] = {}
    each = []
    busy = fallback = 0.0
    for p, evs in zip(device, ops):
        ran = sorted((e for ln in p["lines"] if ln["name"] == MODULES_LINE
                      for e in ln["events"]), key=lambda e: e[0])
        starts = [e[0] for e in ran]
        mine: Dict[str, float] = {}
        for start, event, ns in _own_times(evs, w0, w1):
            i = bisect.bisect_right(starts, start) - 1
            module = (ran[i][2].split("(", 1)[0]
                      if i >= 0 and start < ran[i][1] else None)
            name = instruction_of(event)
            hit = lookup(name, module)
            s = ns / 1e9
            if hit is None:
                chain, opcode, shape = (), "", ""
                scope = UNMATCHED
            else:
                chain, opcode, shape, agreed = hit
                scope = chain[-1] if chain else UNSCOPED
                fallback += s * agreed
            own[scope] = own.get(scope, 0.0) + s
            mine[scope] = mine.get(scope, 0.0) + s
            count[scope] = count.get(scope, 0) + 1
            for k in set(chain):
                incl[k] = incl.get(k, 0.0) + s
            for k in range(1, len(chain) + 1):
                key = "/".join(chain[:k])
                chains[key] = chains.get(key, 0.0) + s
            row = instr.setdefault(scope, {}).setdefault(
                name, [name, opcode, shape, 0.0])
            row[3] += s
        b = _union_ns(evs, w0, w1) / 1e9
        busy += b
        each.append(dict(device=p["name"], busy_s=b, own_s=mine))
    busy /= n
    rows = [dict(
        scope=k, own_s=v / n,
        pct_of_busy=100.0 * v / n / busy if busy else 0.0,
        incl_s=incl.get(k, v) / n, events=count[k],
        top=[[i, o, sh, s / n] for i, o, sh, s in sorted(
            instr[k].values(), key=lambda r: -r[3])[:TOP]])
        for k, v in sorted(own.items(), key=lambda kv: -kv[1])]
    out.update(window_s=(w1 - w0) / 1e9, busy_s=busy, scopes=rows,
               unscoped_s=own.get(UNSCOPED, 0.0) / n,
               unmatched_s=own.get(UNMATCHED, 0.0) / n,
               fallback_s=fallback / n,
               chains={k: v / n for k, v in sorted(chains.items())})
    if n > 1:
        out["devices"] = each
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_planes(path: str) -> List[dict]:
    """The device planes of an .xplane.pb in reduce_planes' shape, read
    with jax.profiler.ProfileData and nothing else."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/device:"):
            continue
        lines = []
        for ln in p.lines:
            if ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for e in ln.events:
                s = float(e.start_ns)
                # an operation's name is its whole instruction: keep
                # the instruction's own (a trace holds 10^5-10^6 events)
                evs.append((s, s + float(e.duration_ns),
                            instruction_of(e.name)
                            if ln.name == OPS_LINE else e.name))
            lines.append(dict(name=ln.name, events=evs))
        planes.append(dict(name=p.name, lines=lines))
    return planes


def reduce_dir(trace_dir: str, tables: Optional[List[dict]] = None) -> dict:
    """The newest trace under `trace_dir` reduced with `tables` (default:
    the sidecar beside it).  No trace there: the empty reduction."""
    if tables is None:
        tables = read_sidecar(trace_dir)
    xplane = find_xplane(trace_dir)
    return reduce_planes(load_planes(xplane) if xplane else [], tables)


def render(reduced: dict) -> List[str]:
    """The table as text lines (`cli check -xprof`, tlcstat, `python -m
    jaxtlc.obs.scopes`)."""
    busy = reduced["busy_s"]
    lines = [
        f"Device time by scope: busy {busy:.4f} s of "
        f"{reduced['window_s']:.4f} s on {reduced['n_devices']} "
        f"device(s); unscoped {reduced['unscoped_s']:.4f} s, unmatched "
        f"{reduced['unmatched_s']:.4f} s; scoped through a fusion's "
        f"agreeing instructions {reduced['fallback_s']:.4f} s"]
    if not reduced["scopes"]:
        return lines + ["  (no device operation in the trace)"]
    lines.append(f"  {'scope':<24}{'own s':>10}{'% busy':>8}"
                 f"{'incl s':>10}{'events':>9}  longest instructions")
    for r in reduced["scopes"]:
        longest = "; ".join(f"{i} {o} {sh} {s:.4f}"
                            for i, o, sh, s in r["top"])
        lines.append(f"  {r['scope']:<24}{r['own_s']:>10.4f}"
                     f"{r['pct_of_busy']:>8.2f}{r['incl_s']:>10.4f}"
                     f"{r['events']:>9}  {longest}")
    nested = [f"{k} {v:.4f}" for k, v in reduced["chains"].items()
              if "/" in k]
    if nested:
        lines.append("  inclusive s of the nested chains: "
                     + "; ".join(nested))
    for d in reduced.get("devices", ()):
        shares = ", ".join(f"{k} {v:.4f}" for k, v in sorted(
            d["own_s"].items(), key=lambda kv: -kv[1]))
        lines.append(f"  {d['device']}: busy {d['busy_s']:.4f} s; {shares}")
    return lines


def main(argv=None) -> int:
    """`python -m jaxtlc.obs.scopes DIR`: reduce the trace under DIR
    with the sidecar written beside it, print the table."""
    import argparse

    p = argparse.ArgumentParser(prog="jaxtlc.obs.scopes")
    p.add_argument("dir", help="a profiler trace directory holding "
                   + SIDECAR + " (`cli check -xprof DIR`)")
    print("\n".join(render(reduce_dir(p.parse_args(argv).dir))))
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys

    sys.exit(main())
