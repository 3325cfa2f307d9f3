"""Device liveness orchestration: capture -> fixpoint -> validated lasso.

Two frontend entry points, both producing the SAME result types their
host-path counterparts produce, so the CLI rendering is path-agnostic:

* check_properties_device(cfg, props)  - the KubeAPI family
  (engine.liveness.LivenessResult with encoded field-vector states);
* check_leads_to_device(genspec, p, q) - generic-frontend specs
  (gen.oracle.LivenessResult with decoded state tuples).

Semantics are the host path's WF_vars(Next) reduction exactly
(engine.liveness module docstring); `wf_process` stays host-only - the
CLI routes it there.  Every violation is oracle-replayed before being
returned (live.lasso.replay_lasso); the differential tests additionally
pin whole-verdict and state-set equality against the host engines.

The device path is picked automatically above HOST_PATH_MAX distinct
states (where the host path's per-state Python dict becomes the
bottleneck); `-liveness-host` forces the old path.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

from ..engine.liveness import LivenessResult as KubeLivenessResult
from .capture import CapturedGraph, capture_edges, eval_state_masks
from .fixpoint import has_nonself, surviving_set
from .lasso import LassoError, build_lasso, replay_lasso

# above this many distinct states the host liveness graph (one Python
# dict entry + adjacency list per state) stops being viable; the device
# path has no per-state host objects at all
HOST_PATH_MAX = 1_000_000


def use_device_path(distinct: int, fairness: str = "wf_next",
                    force_host: bool = False) -> bool:
    """CLI dispatch rule: device path automatically above the host-path
    size threshold; wf_process and -liveness-host stay on the host path."""
    return (not force_host) and fairness == "wf_next" \
        and distinct > HOST_PATH_MAX


def _violation(graph, alive, in_h, trigger, name, labels,
               decode, is_initial, is_transition, equal=None):
    prefix_ids, cycle_ids, pre_act, cyc_act = build_lasso(
        graph, alive, in_h, trigger
    )
    prefix = [decode(i) for i in prefix_ids]
    cycle = [decode(i) for i in cycle_ids]
    replay_lasso(prefix, cycle, is_initial, is_transition, equal=equal)
    names = [None if a is None else labels[a] for a in pre_act]
    cnames = [None if a is None else labels[a] for a in cyc_act]
    return prefix_ids, cycle_ids, prefix, cycle, names, cnames


# ---------------------------------------------------------------------------
# KubeAPI family
# ---------------------------------------------------------------------------


def capture_kube_graph(cfg, chunk: int = 1024,
                       state_capacity: int = 1 << 20,
                       fp_capacity: int = 1 << 20,
                       spill_path: Optional[str] = None) -> CapturedGraph:
    from ..engine.sharded import kubeapi_backend

    return capture_edges(
        kubeapi_backend(cfg), chunk=chunk, state_capacity=state_capacity,
        fp_capacity=fp_capacity, spill_path=spill_path,
    )


def check_properties_device(
    cfg,
    properties: List[str],
    chunk: int = 1024,
    state_capacity: int = 1 << 20,
    fp_capacity: int = 1 << 20,
    mesh=None,
    graph: Optional[CapturedGraph] = None,
    spill_path: Optional[str] = None,
) -> List[KubeLivenessResult]:
    """Device-path analog of engine.liveness.check_properties (wf_next)."""
    import jax.numpy as jnp

    from ..spec import oracle
    from ..spec.codec import get_codec
    from ..spec.labels import LABELS

    cdc = get_codec(cfg)
    if graph is None:
        graph = capture_kube_graph(
            cfg, chunk=chunk, state_capacity=state_capacity,
            fp_capacity=fp_capacity, spill_path=spill_path,
        )
    nonself = has_nonself(graph)
    sr_off = cdc.offsets["sr"]
    api_sl = cdc.sl("api")

    def sr_fn(ri):
        return lambda f: f[:, sr_off + ri] == 1

    def secret_fn(ci):
        si, _ = cfg.targets[ci]

        def fn(f):
            api = f[:, api_sl]
            pres = (api >> cdc.o_present) & 1
            ident = (api >> cdc.o_ident) & ((1 << cdc.ib) - 1)
            return ((pres == 1) & (ident == si)).any(axis=1)

        return fn

    def decode_fields(i):
        row = jnp.asarray(graph.states[i][None])
        return np.asarray(cdc.unpack(row))[0].astype(np.int32)

    inits = set(oracle.initial_states(cfg))

    def is_initial(enc):
        return cdc.decode(np.asarray(enc)) in inits

    def is_transition(ea, eb):
        sa = cdc.decode(np.asarray(ea))
        sb = cdc.decode(np.asarray(eb))
        return sb in {x.state for x in oracle.successors(sa, cfg)}

    out: List[KubeLivenessResult] = []
    for name in properties:
        if cfg.n_reconcilers == 0:
            out.append(KubeLivenessResult(name, True, None, None))
            continue
        if name == "ReconcileCompletes":
            zones = [(sr_fn(ri), None) for ri in range(cfg.n_reconcilers)]
        elif name == "CleansUpProperly":
            zones = [
                (sr_fn(k), secret_fn(ci))
                for k, ci in enumerate(cfg.reconciler_indices)
            ]
        else:
            raise ValueError(f"unknown temporal property {name!r}")
        res = None
        for sr, secret in zones:
            if secret is None:
                # sr[c] ~> ~sr[c]: H = trigger = {sr[c]}
                (mask,) = eval_state_masks(graph, cdc, [sr])
                in_h = trigger = mask
            else:
                # []~sr[c] ~> absent: H = trigger = {~sr[c] /\ present}
                srm, pm = eval_state_masks(graph, cdc, [sr, secret])
                in_h = trigger = ~srm & pm
            alive, _ = surviving_set(graph, in_h, mesh=mesh,
                                     nonself=nonself)
            bad = trigger & alive
            if not bad.any():
                res = KubeLivenessResult(name, True, None, None)
                continue
            _, _, prefix, cycle, pnames, cnames = _violation(
                graph, alive, in_h, bad, name, LABELS,
                decode_fields, is_initial, is_transition,
                equal=np.array_equal,
            )
            res = KubeLivenessResult(name, False, prefix, cycle,
                                     pnames, cnames)
            break
        out.append(res)
    return out


# ---------------------------------------------------------------------------
# Generic frontend
# ---------------------------------------------------------------------------


def check_leads_to_device(
    spec,
    p_ast,
    q_ast,
    name: str = "",
    chunk: int = 1024,
    state_capacity: int = 1 << 20,
    fp_capacity: int = 1 << 20,
    mesh=None,
    graph: Optional[CapturedGraph] = None,
    spill_path: Optional[str] = None,
):
    """Device-path analog of gen.oracle.check_leads_to (wf_next)."""
    import jax

    from ..gen import oracle as go
    from ..gen.kernel import _Ctx, compile_expr
    from ..engine.sharded import gen_backend

    backend = gen_backend(spec)
    cdc = backend.cdc
    if graph is None:
        graph = capture_edges(
            backend, chunk=chunk, state_capacity=state_capacity,
            fp_capacity=fp_capacity, spill_path=spill_path,
        )
    ctx = _Ctx(codec=cdc, consts=dict(spec.constants), binding={}, at=None)
    masks = []
    for ast in (p_ast, q_ast):
        kind, fn = compile_expr(ast, ctx)
        if kind != "bool":
            raise ValueError(f"property operand is not BOOLEAN: {ast!r}")
        masks.append(jax.vmap(fn))
    p_mask, q_mask = eval_state_masks(graph, cdc, masks)
    in_h = ~q_mask
    alive, _ = surviving_set(graph, in_h, mesh=mesh)
    bad = p_mask & alive
    if not bad.any():
        return go.LivenessResult(name, True, None, None)

    init = go.initial_state(spec)

    def decode(i):
        import jax.numpy as jnp

        row = jnp.asarray(graph.states[i][None])
        return cdc.decode(np.asarray(cdc.unpack(row))[0])

    def is_transition(sa, sb):
        return any(
            nxt == sb and changed
            for _, nxt, changed in go.successors(spec, sa)
        )

    _, _, prefix, cycle, _, _ = _violation(
        graph, alive, in_h, bad, name, backend.labels,
        decode, lambda s: s == init, is_transition,
    )
    return go.LivenessResult(name, False, prefix, cycle)


# ---------------------------------------------------------------------------
# Structural frontend: the relation stays on the device (ISSUE 41)
# ---------------------------------------------------------------------------

# the counters of one property's analysis, as the `liveness` journal
# event, CheckResult and the `final` event carry them (`live_` + name)
LIVE_COUNTERS = ("states", "edges", "changed_edges", "fair_edges",
                 "h_states", "p_states", "survivors", "outer", "sweeps",
                 "swept_rows", "edge_bytes", "host_bytes")


class StructLiveResult(NamedTuple):
    """One property's verdict on the device route.  The lasso (decoded
    state tuples) only where it is violated; `counters` by LIVE_COUNTERS;
    `alive` (the fixpoint's set as a host mask) only where asked for."""

    name: str
    holds: bool
    lasso_prefix: Optional[list]
    lasso_cycle: Optional[list]
    counters: dict
    fairness: tuple
    alive: Optional[np.ndarray] = None


class LiveTooLarge(RuntimeError):
    """The relation does not fit the device: the caller's cue for the
    host tier."""


def device_budget_bytes() -> Optional[int]:
    """What the first device can still hold, or None where it does not
    say (the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def check_struct_properties(
    model,
    backend,
    properties,
    n_states: int,
    n_edges: int,
    chunk: int = 1024,
    fp_capacity: int = 1 << 20,
    fp_index: Optional[int] = None,
    seed: Optional[int] = None,
    keep_alive: bool = False,
) -> List[StructLiveResult]:
    """`P ~> Q` for each of `properties` ((name, p_ast, q_ast), ...) of a
    struct model, under the fairness its SPECIFICATION formula states
    (`model.fairness`: WF_vars(A_1) /\\ ... /\\ WF_vars(A_K), resolved
    to action labels by the loader), on the device.

    `n_states` and `n_edges` are what the safety run has just counted
    (distinct; generated less the initial states): the state array and
    the edge store are sized from them and never regrow.  Three kept
    programs (`runtime.aot_build`, kinds `live-enum`, `live-capture`,
    `live-fixpoint`: a second check of the process builds nothing):
    the enumerator leaves the states on the device in id order, the
    capture their changed successor rows as a CSR (live.capture), the
    fixpoint Emerson and Lei's fair set (live.fixpoint).  P and Q are
    the struct compiler's predicates, as the invariants are compiled,
    evaluated over the state array on the device.  What comes to the
    host is one stats vector a property and three scalars of the
    capture; only a violation brings states and rows (the lasso, which
    is replayed through the evaluator and whose cycle is held to the
    fairness rule before it is returned).

    Host spans `live.enumerate`, `live.capture`, and per property
    `live.masks`, `live.fixpoint`, `live.verdict`; the caller opens
    `live` around the call.  Raises LiveTooLarge where the relation
    cannot fit the device."""
    import jax
    import jax.numpy as jnp

    from ..engine.bfs import OK, VIOLATION_NAMES
    from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
    from ..obs.spans import span
    from ..runtime import aot_build, engine_key
    from .capture import make_device_capture, make_scoped_enumerator
    from .fixpoint import SWEEP_BLOCK, fair_stats, make_fair_fixpoint

    fp_index = DEFAULT_FP_INDEX if fp_index is None else fp_index
    seed = DEFAULT_SEED if seed is None else seed
    cdc = backend.cdc
    V, E = int(n_states), int(n_edges)
    W = (cdc.nbits + 31) // 32
    e_cap = E + chunk * backend.n_lanes
    K = len(model.fairness)
    # dst + act of the store, the fixpoint's cut of them, and a sweep's
    # few vectors at row width; the state array and its masks; a
    # fairness group's compacted destinations and row bounds, and their
    # sort's operands once
    need = (e_cap * 5 * 2 + E * 14 + (V + 2 * chunk) * (4 * W + 16)
            + K * (4 * E + 4 * (V + 1)) + 16 * E * bool(K))
    budget = device_budget_bytes()
    if budget is not None and need > 0.8 * budget:
        raise LiveTooLarge(
            f"the liveness relation ({E} successor rows, {V} states: "
            f"~{need / 1e9:.2f} GB) does not fit the device "
            f"({budget / 1e9:.2f} GB free)")
    geometry = dict(chunk=int(chunk), fp_capacity=int(fp_capacity),
                    fp_index=int(fp_index), seed=int(seed), states=V,
                    edges=E)

    with span("live.enumerate") as sp:
        template, enum = aot_build(
            lambda: make_scoped_enumerator(backend, chunk, V, fp_capacity,
                                           fp_index, seed),
            key=engine_key("live-enum", backend, geometry))
        carry = enum(template)
        code, tail = (int(x) for x in jax.device_get(
            (carry.viol, carry.tail)))
        sp.attrs["states"] = tail
    if code != OK or tail != V:
        raise RuntimeError(
            "liveness enumeration disagrees with the safety run: "
            + (VIOLATION_NAMES[code] if code != OK else
               f"{tail} states enumerated, {V} distinct counted"))
    states = carry.states
    del carry

    with span("live.capture") as sp:
        _, capture = aot_build(
            lambda: make_device_capture(backend, chunk, V, E, fp_index,
                                        seed),
            key=engine_key("live-capture", backend, geometry))
        graph = capture(states)
        n_rows, n_changed, missing = (int(x) for x in jax.device_get(
            (graph.n_rows, graph.n_changed, graph.missing)))
        sp.attrs.update(edges=n_rows, changed=n_changed,
                        sweeps=-(-V // chunk))
    if missing or n_rows != E:
        raise RuntimeError(
            "liveness capture disagrees with the safety run: "
            + ("a successor outside the enumerated set" if missing else
               f"{n_rows} successor rows walked, {E} counted"))
    # the analysis reads the changed rows alone: its programs are built
    # for their count (a constant of the model), in whole sweep blocks
    e_rows = max(1, -(-n_changed // SWEEP_BLOCK)) * SWEEP_BLOCK
    dst, act = graph.dst[:e_rows], graph.act[:e_rows]
    if e_rows > e_cap:  # a store shorter than its last block
        dst, act = (jnp.pad(x, (0, e_rows - e_cap)) for x in (dst, act))
    row_start = graph.row_start
    del graph
    fairness = tuple(model.fairness)
    groups = tuple(tuple(backend.labels.index(lab) for lab in labels
                         if lab in backend.labels)
                   for _, labels in fairness)
    n_rows_states = int(states.shape[0])
    edge_bytes = e_rows * 5 + (V + 1) * 4

    out: List[StructLiveResult] = []
    for name, p_ast, q_ast in properties:
        with span("live.masks", property=name):
            p, h = _struct_masks(backend, name, p_ast, q_ast, V)(states)
        with span("live.fixpoint", property=name) as sp:
            _, fix = aot_build(
                lambda: make_fair_fixpoint(V, n_rows_states, e_rows, groups),
                key=engine_key(
                    "live-fixpoint", backend,
                    dict(states=V, rows=n_rows_states, e_rows=e_rows,
                         groups=[list(g) for g in groups])))
            z, stats = fix((dst, act, row_start, jnp.int32(n_changed),
                            p, h))
            stats = fair_stats(stats)
            sp.attrs.update(outer=stats["outer"], sweeps=stats["sweeps"],
                            swept_rows=stats["swept_rows"])
        with span("live.verdict", property=name):
            counters = dict(
                states=V, edges=n_rows, changed_edges=n_changed,
                fair_edges=stats["fair_edges"], h_states=stats["h_states"],
                p_states=stats["p_states"], survivors=stats["survivors"],
                outer=stats["outer"], sweeps=stats["sweeps"],
                swept_rows=stats["swept_rows"], edge_bytes=edge_bytes,
                host_bytes=0)
            alive = np.asarray(z) if keep_alive else None
            if stats["survivors"] == 0:
                out.append(StructLiveResult(name, True, None, None,
                                            counters, fairness, alive))
                continue
            prefix, cycle, counters["host_bytes"] = _struct_lasso(
                model, backend, states, dst, act, row_start, n_changed,
                z, p, V, groups)
            out.append(StructLiveResult(name, False, prefix, cycle,
                                        counters, fairness, alive))
    return out


def _struct_masks(backend, name: str, p_ast, q_ast, n_states: int):
    """The jitted (states -> P, H = ~Q over the enumerator's rows, the
    rows past the states False) of one property, kept on the backend
    (the struct memo keeps that): a second check compiles nothing."""
    import jax
    import jax.numpy as jnp

    kept = backend.cdc.__dict__.setdefault("_live_masks", {})
    key = (name, repr(p_ast), repr(q_ast), n_states)
    if key not in kept:
        cdc = backend.cdc
        p_fn = cdc.compile_predicate(p_ast)
        q_fn = cdc.compile_predicate(q_ast)

        @jax.jit
        def masks(states):
            fields = cdc.unpack(states)
            real = jnp.arange(states.shape[0]) < n_states
            return p_fn(fields) & real, ~q_fn(fields) & real

        kept[key] = masks
    return kept[key]


def _struct_lasso(model, backend, states, dst, act, row_start, n_changed,
                  z, p, V, groups):
    """A violation's lasso, on the host: the one case that reads states
    and rows back.  (prefix, cycle as decoded state tuples, bytes
    read.)"""
    import jax.numpy as jnp

    from .lasso import cycle_is_fair, fair_lasso

    system = model.system
    cdc = backend.cdc
    dst_h = np.asarray(dst)[:n_changed]
    act_h = np.asarray(act)[:n_changed].astype(np.int32)
    rs = np.asarray(row_start)
    src_h = np.repeat(np.arange(V, dtype=np.int32), np.diff(rs))
    z_h, p_h = np.asarray(z), np.asarray(p)[:V]
    n_init = system.initial_count()
    prefix_ids, cycle_ids, _, _ = fair_lasso(
        V, n_init, src_h, dst_h, act_h, z_h, p_h, groups)
    if not cycle_is_fair(cycle_ids, src_h, dst_h, act_h, groups):
        raise LassoError("the reconstructed cycle is not fair")
    ids = prefix_ids + cycle_ids
    rows = np.asarray(cdc.unpack(states[jnp.asarray(ids)]))
    decoded = [cdc.decode(r) for r in rows]
    prefix, cycle = decoded[:len(prefix_ids)], decoded[len(prefix_ids):]
    inits = None

    def is_initial(st):
        nonlocal inits
        if inits is None:
            inits = set(system.initial_states())
        return st in inits

    def is_transition(sa, sb):
        return any(nxt == sb for _, nxt in system.successors(sa))

    replay_lasso(prefix, cycle, is_initial, is_transition)
    read = (dst_h.nbytes + n_changed + rs.nbytes + z_h.nbytes
            + p_h.nbytes + rows.nbytes)
    return prefix, cycle, int(read)
