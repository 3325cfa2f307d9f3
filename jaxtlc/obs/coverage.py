"""Device-resident live coverage plane (ISSUE 11 tentpole).

TLC's headline observability product is its per-expression coverage
dump (reference MC.out:44-1092); until this round we reproduced it only
by host-side instrumented RE-WALKS of the whole state space
(spec/coverage.py for the KubeAPI family, gen/coverage.py for the gen
subset) - a third exploration, after the run finished.  This module is
the shared vocabulary of the device-native replacement: coverage
counters live IN the computation, the way large-scale ML systems carry
telemetry - a cumulative ``[n_sites]`` uint32 tensor riding the engine
carry exactly like the PR 5 obs ring (optional None-default leaf, pure
telemetry, bit-for-bit gated), incremented by the compiled step itself
and read back only at the segment fences the supervisor already pays.

* ``Site`` / ``CoveragePlane`` - what a SpecBackend exposes: an ordered
  site table plus a ``count(batch, mask, valid) -> [n_sites] uint32``
  device hook the expand stage folds into every block.  The FIRST
  ``len(plane.actions)`` sites are always the per-action sites (kind
  "action"), so the PR 3 per-action coverage lines are a PREFIX VIEW of
  per-site coverage - one accounting, two renderings, no drift.
* site-table builders (``action_site_table``) shared by the struct lane
  compiler (struct/compile.py assigns the fine-grained sites), the
  KubeAPI hand-kernel table (spec/coverage_device.py, pinned
  site-for-site against the host coverage walker) and gen/coverage.py.
* journal/views plumbing: ``coverage`` journal events carry per-segment
  DELTAS; ``coverage_from_events`` folds them back into cumulative
  totals for obs.serve ``GET /coverage``, the Prometheus
  ``coverage_site_total`` counters, tlcstat's coverage line and
  tools/covdiff.py.
* ``render_site_dump`` - the end-of-run dump in MC.out's exact message
  framing (2201 banner, 2772 action headers, 2221 span lines), with
  the span table's source locations when the spec has one
  (coverage_spans) and the stable site keys otherwise.
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np


class Site(NamedTuple):
    """One coverage site: a stable key, its kind, and the action it
    belongs to.  `loc` is a source span when the frontend knows one
    (the KubeAPI span table); the key renders in its place otherwise.

    Kinds: "action" (per-action header site, distinct:generated prefix
    view), "guard" (guard conjunct), "branch" (IF/CASE arm), "quant"
    (quantifier/binder body), "effect" (update conjunct / UNCHANGED),
    "init" (Init conjunct), "inv" (invariant span)."""

    key: str
    kind: str
    action: str
    loc: str = ""


class CoveragePlane(NamedTuple):
    """The backend -> engine coverage seam (SpecBackend.coverage).

    ``count(batch [ck,F] int32, mask [ck] bool, valid [ck,L] bool) ->
    [n_sites] uint32`` runs inside the expand stage and returns this
    block's visit increments; the commit stage accumulates them into
    the carry's cumulative ``cov_counts`` leaf.  ``init_count`` is a
    HOST function charging the Init-site visits for the seed states
    (None = all-zero seed).  Pure telemetry: neither feeds control
    flow, so coverage-on results are bit-for-bit coverage-off results
    (tests/test_coverage_device.py::
    test_struct_coverage_deterministic_and_pure pins it)."""

    sites: tuple  # tuple[Site]
    count: object  # device fn(batch, mask, valid) -> [n_sites] uint32
    init_count: object = None  # host fn(inits [n0,F] np) -> [n_sites]
    module: str = ""  # module name for the MC.out-format dump

    @property
    def n_sites(self) -> int:
        return len(self.sites)

    def seed(self, inits) -> np.ndarray:
        """[n_sites] uint32 Init-visit seed for `inits` (host-side)."""
        if self.init_count is None:
            return np.zeros(self.n_sites, np.uint32)
        out = np.asarray(self.init_count(np.asarray(inits)), np.uint32)
        assert out.shape == (self.n_sites,)
        return out


def action_site_table(module: str, actions: Sequence[str],
                      locs: Optional[Dict[str, str]] = None
                      ) -> List[Site]:
    """The per-action PREFIX of every site table: one "action" site per
    action, in rendering order.  gen/coverage.py, the struct compiler
    and the KubeAPI device table all open with exactly this prefix, so
    the per-action coverage lines (PR 3 CLI path) are site table rows
    0..n_actions-1 - one accounting, no drift between renderers."""
    locs = locs or {}
    return [Site(key=a, kind="action", action=a, loc=locs.get(a, ""))
            for a in actions]


def site_totals_dict(sites: Sequence[Site], counts) -> Dict[str, int]:
    """{site key: cumulative count} from a device counts vector."""
    counts = np.asarray(counts)
    return {s.key: int(c) for s, c in zip(sites, counts)}


# ---------------------------------------------------------------------------
# Journal plumbing: per-segment deltas -> cumulative views
# ---------------------------------------------------------------------------


def coverage_delta_event(sites: Sequence[Site], totals: np.ndarray,
                         seen: Optional[np.ndarray]) -> Optional[dict]:
    """The `coverage` journal-event payload for one segment fence:
    nonzero per-site DELTAS since `seen` plus the visited/total header.
    None when nothing moved (no event is journaled)."""
    totals = np.asarray(totals, np.int64)
    prev = (np.zeros_like(totals) if seen is None
            else np.asarray(seen, np.int64))
    delta = totals - prev
    if not (delta != 0).any():
        return None
    return {
        "visited": int((totals > 0).sum()),
        "sites": len(sites),
        "delta": {s.key: int(d) for s, d in zip(sites, delta) if d},
    }


def coverage_from_events(events) -> Optional[dict]:
    """Fold a journal's `coverage` delta events back into cumulative
    totals - the derived view obs.serve's ``GET /coverage``, the
    Prometheus ``coverage_site_total`` counters, tlcstat and covdiff
    all render.  None when the run carried no coverage plane.

    Pod-aware (ISSUE 20): merged ``{base}.hN`` sibling journals carry
    per-host PARTIAL deltas (disjoint fingerprint shards, so the sum
    of partials IS the global total) whose `visited` headers describe
    only that host's rows - so `visited` recomputes from the folded
    totals instead of trusting any single header, and the pod counts
    as saturated only when EVERY host that emitted coverage carried
    its once-per-run saturation event (the level reported is the max)."""
    totals: Dict[str, int] = {}
    n_sites = 0
    sat: Dict = {}  # host key (None = single journal) -> sat level
    covered = set()
    for ev in events:
        if ev.get("event") != "coverage":
            continue
        hk = ev.get("host")
        covered.add(hk)
        for k, d in ev.get("delta", {}).items():
            totals[k] = totals.get(k, 0) + int(d)
        n_sites = ev.get("sites", n_sites)
        if ev.get("saturated"):
            sat[hk] = ev.get("level")
    if not totals and n_sites == 0:
        return None
    saturated_at = None
    if covered and covered <= set(sat):
        levels = [v for v in sat.values() if v is not None]
        saturated_at = max(levels) if levels else None
    return {
        "sites": totals,
        "visited": sum(1 for v in totals.values() if v),
        "n_sites": n_sites or len(totals),
        "saturated_at_level": saturated_at,
    }


# ---------------------------------------------------------------------------
# MC.out-format rendering
# ---------------------------------------------------------------------------


def render_site_dump(sites: Sequence[Site], counts,
                     module: str, stamp: str,
                     init_count: int = 0,
                     act_gen: Optional[Dict[str, int]] = None,
                     act_dist: Optional[Dict[str, int]] = None,
                     order: Optional[Sequence[str]] = None,
                     ) -> List[str]:
    """The end-of-run device coverage dump in MC.out's format/order:
    the 2201 banner text, one 2772-style action header per action (its
    prefix "action" site carries the generated count; `act_dist` fills
    TLC's distinct:generated pair), and one indented span line per
    fine-grained site under its action, rendered with the site's source
    loc when the table has one and the stable key otherwise.  Message
    framing (STARTMSG/ENDMSG) is added by TLCLog.coverage_site_dump."""
    counts = np.asarray(counts)
    act_gen = act_gen or {}
    act_dist = act_dist or {}
    by_action: Dict[str, List] = {}
    # header order: the caller's (module-definition / MC.out) order
    # when given, the site table's otherwise; actions the order list
    # does not know render after it
    order = list(order) if order is not None else []
    for s, c in zip(sites, counts):
        if s.kind == "action":
            if s.action not in order:
                order.append(s.action)
            continue
        by_action.setdefault(s.action, []).append((s, int(c)))
    for s in sites:  # actions that only have fine-grained sites
        if s.kind != "action" and s.action not in order:
            order.append(s.action)
    lines = [f"The coverage statistics at {stamp}"]
    lines.append(f"<Init of module {module}>: {init_count}:{init_count}")
    idx = {s.key: i for i, s in enumerate(sites)}
    for a in order:
        g = act_gen.get(a)
        if g is None:
            i = idx.get(a)
            g = int(counts[i]) if i is not None else 0
        d = act_dist.get(a, 0)
        lines.append(f"<{a} of module {module}>: {d}:{g}")
        for s, c in by_action.get(a, []):
            where = s.loc or s.key
            lines.append(f"  |{where} of module {module}: {c}")
    return lines


def zero_sites(sites: Sequence[Site], counts) -> List[Site]:
    """Sites with zero cumulative visits (the dead-site lint's input);
    action-prefix sites included."""
    counts = np.asarray(counts)
    return [s for s, c in zip(sites, counts) if int(c) == 0]
