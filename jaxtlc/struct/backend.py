"""Struct-compiled specs as a SpecBackend (the engine seam).

The LaneCompiler step (struct.compile) becomes a pluggable kernel for
the production engines: the fused single-device loop
(engine.bfs.make_backend_engine), the mesh-sharded loop
(engine.sharded.make_sharded_engine) and the resil supervisor's
segmented drivers all consume this backend, so struct specs get
segmented execution, fingerprint-space mesh sharding, checkpoints,
auto-regrow and two-tier adaptive stepping through the exact code paths
the hand kernel uses - no private BFS loop (the round-6 tentpole; the
old struct/engine.py loop is retired).

The compiler emits a batch step ([B, L, F] directly); the engines
expect a per-row kernel they vmap themselves, so the step here is a
B=1 wrapper - under vmap the batch dimension is re-introduced by
tracing, producing the same fused XLA as the native batch compile.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.backend import ActionPropSeam, SpecBackend
from ..engine.bfs import VIOL_ASSERT
from .codec import StructCodec
from .compile import (
    LaneCompiler,
    TrapPolicy,
    compact_lanes,
    compact_width,
    state_vars_read,
)
from .loader import StructModel
from .shapes import (
    OPEN_SIDE_FACTOR,
    constraint_bounds,
    infer_shapes,
    seq_summary,
    typeok_hints,
)

VIOL_INVARIANT_BASE = 100
# an action property `I /\\ [][A]_v` (model.action_props, in the cfg's
# order): 200 + 2k where I fails on an initial state, 201 + 2k where an
# edge fails `[A]_v`
VIOL_ACTION_PROP_BASE = 200


def struct_viol_names(model: StructModel) -> Dict[int, str]:
    """Violation-code name overrides for a struct model (invariants by
    cfg order + the PlusCal assertion channel)."""
    names = {VIOL_ASSERT: "Failure of PlusCal assertion"}
    for k, name in enumerate(model.invariants):
        names[VIOL_INVARIANT_BASE + k] = f"Invariant {name} is violated"
    for k, prop in enumerate(model.action_props.values()):
        names[VIOL_ACTION_PROP_BASE + 2 * k] = (
            f"Property {prop.name} is violated: an initial state does "
            f"not satisfy {prop.init_name}")
        names[VIOL_ACTION_PROP_BASE + 2 * k + 1] = (
            f"Property {prop.name} is violated: a step is neither a "
            f"{prop.action_name} step nor leaves {prop.sub_text} "
            f"unchanged (action property [][{prop.action_name}]_"
            f"{prop.sub_text})")
    return names


def make_cert_check(cdc: StructCodec, card_specs=()):
    """The on-device runtime certificate check for a (narrowed) codec:
    every VALID generated successor's raw int32 fields must hold a
    legal code of its universe claim (0 <= field <= max_code - checked
    PRE-packing, so escapes that would wrap into a legal-looking word
    are still caught), and every cardinality-bounded mask variable's
    popcount must fit its certified bound.  Returns a scalar bool:
    "some reachable state violated a claimed bound" - the signal the
    engines latch into the sticky certificate column."""
    from jax import lax

    max_code = jnp.asarray(np.asarray(cdc.max_codes(), np.int32))
    specs = tuple((int(off), int(nf), int(bound))
                  for off, nf, bound in card_specs)

    def cert_check(flat, valid):
        bad = (flat < 0) | (flat > max_code[None, :])
        viol = bad.any(axis=1)
        for off, nf, bound in specs:
            pc = lax.population_count(
                flat[:, off:off + nf].astype(jnp.uint32)
            )
            viol = viol | (pc.sum(axis=1).astype(jnp.int32) > bound)
        return (viol & valid).any()

    return cert_check


def _card_specs(cdc: StructCodec, variables, card_bounds) -> list:
    """(field offset, field count, bound) triples for the mask-layout
    variables whose certified cardinality bound actually constrains."""
    from .codec import MaskLeaf

    out = []
    for v, lay in zip(variables, cdc.layouts):
        bound = (card_bounds or {}).get(v)
        if bound is None or not isinstance(lay, MaskLeaf):
            continue
        if bound < lay.n_bits:
            out.append((cdc.offsets[v], lay.n_fields, bound))
    return out


def struct_backend(model: StructModel,
                   check_deadlock: bool = True,
                   bounds=None,
                   elide: bool = True,
                   coverage: bool = False,
                   symmetry: bool = False,
                   por: bool = False,
                   slots: int = 0,
                   open_side_factor: int = OPEN_SIDE_FACTOR,
                   seq_cap_floor: int = 0) -> SpecBackend:
    """Compile `model` into a SpecBackend: parse -> shape-infer ->
    lane-compile, the pipeline struct.cache memoizes in-process.

    `bounds` (a CERTIFIED analysis.absint.BoundReport) swaps the
    widened inferred shapes for the certified reachable bounds: the
    codec's enum universes, mask bit counts and sequence caps shrink
    to the certified ranges (fewer packed uint32 words through the
    fingerprint/sort/probe path) and, with `elide` (default), the
    compiler drops the range traps the bounds prove safe while the backend carries the on-device certificate check
    that re-verifies every claimed bound on every generated state -
    so an unsound bound turns the verdict loud instead of silently
    narrowing real states away.  `elide=False` narrows the codec but
    keeps every trap and carries no certificate (the mesh-sharded
    engines, which have no certificate column: the encode traps stay
    the soundness story there).

    `coverage` compiles the device coverage plane in (ISSUE 11): the
    lane walker assigns a stable site id to every guard conjunct,
    branch arm, action-position binder body and update conjunct, and
    the backend exposes an obs.coverage.CoveragePlane whose count hook
    the engines fold into the cumulative per-site counter leaf.  The
    site table opens with one "action" site per action (the PR 3
    per-action coverage lines are a prefix view of per-site coverage).
    Pure telemetry: coverage-on results are bit-for-bit coverage-off
    results.

    `symmetry` / `por` (RESOLVED bools; the tri-state flags resolve via
    engine.bfs.resolve_symmetry / resolve_por) attach the state-space
    reduction capability (engine.reduce.ReduceOps, ISSUE 18):
    symmetry canonicalizes every successor to its orbit representative
    over the statically-verified symmetric constant sets
    (analysis.symfind) before fingerprinting, POR prunes commutative
    interleavings through singleton ample sets.  Verdicts, invariant
    outcomes and rendered traces are preserved; DISTINCT/GENERATED
    counts legitimately shrink, which is why both default off.

    `slots` is the least a compacted step keeps a state (0: what
    compile.compact_width gives): struct.cache.widen_slots raises it
    after a run met a state with more live lanes.

    A model whose cfg declares CONSTRAINT (model.constraints, ISSUE 39)
    gets the conjunction compiled once, here, as a predicate on raw
    successor fields (SpecBackend.constraint; host span
    `build.struct.constraint`), which the engine's expand stage
    applies: no flag switches it on or off, the cfg does.  Its leaf
    bounds (shapes.constraint_bounds) are the upper side of the shape
    inference - successors are read off kept states alone, so the
    codec holds a kept state and one step outside it, which is what
    the predicate has to be able to judge - and the certified
    narrowing is not taken: it knows nothing of the constraint.
    `open_side_factor` is how far out the
    side of a leaf that the constraint leaves open is capped:
    struct.cache.widen_open_sides raises it after a range trap.

    A sequence that grows has a capacity (ISSUE 45): what an invariant
    or the CONSTRAINT declares for it (`Len(network[p][q]) <= 3`:
    model.seq_caps, the loader's `build.struct.seqcap`), else a first
    guess; `seq_cap_floor` is the least capacity of every sequence once
    an Append met a full one and the run started again
    (struct.cache.widen_seq_caps)."""
    from ..obs.spans import span

    system = model.system
    trap_policy = None
    cert = False
    kept = constraint_bounds(system.ev, model.constraints,
                             system.variables)
    if model.constraints:
        bounds = None
    seq_caps = list(model.seq_caps)
    with span("build.struct.shapes"):
        if bounds is not None and getattr(bounds, "certified", False):
            var_shapes = {v: bounds.bounds[v] for v in system.variables}
            if elide:
                trap_policy = TrapPolicy(elide_range=True)
                cert = True
        else:
            bounds = None
            hints = typeok_hints(system.ev, model.invariants,
                                 system.variables)
            var_shapes = infer_shapes(system.ev, system.variables,
                                      system.init_ast, system.next_ast,
                                      hints=hints, kept=kept,
                                      open_side_factor=open_side_factor,
                                      seq_caps=seq_caps,
                                      seq_cap_floor=seq_cap_floor)
        cdc = StructCodec(system.variables, var_shapes,
                          structural=frozenset(b.var for b in kept))
    compiler = LaneCompiler(system.ev, system.variables, var_shapes,
                            cdc, trap_policy=trap_policy)
    # jitted at the [1, F] shape the per-row seam calls them with: the
    # lane walk (seconds of Python for a wide fan: Paxos's 256 lanes are
    # 52k equations) then runs once per backend, and every engine trace
    # after it - each tier of each check's per-call build - replays the
    # cached jaxpr instead of walking the spec again
    batch_step = jax.jit(compiler.build_step(system.next_ast))
    inv_fns = [
        jax.jit(compiler.build_invariant(ast))
        for ast in model.invariants.values()
    ]
    F = cdc.n_fields

    # discover the lane structure (labels) with a shape-only trace:
    # the lane walk itself (host span `build.struct.lanes`)
    with span("build.struct.lanes"):
        jax.eval_shape(batch_step,
                       jax.ShapeDtypeStruct((1, F), jnp.int32))
    labels: List[str] = list(compiler.labels)
    action_names: Tuple[str, ...] = tuple(sorted(set(labels)))
    lane_action = jnp.asarray(
        [action_names.index(x) for x in labels], jnp.int32
    )
    # a wide static fan (universe lanes: Paxos's 256, ~8 live a state)
    # leaves the step compacted to `width` slots a state, so the
    # engine's candidate width follows the live lanes.  The coverage
    # plane and POR read static lanes, so they keep the full fan
    guess = compact_width(len(labels))
    if guess < len(labels) and not (coverage or por):
        # a first guess an initial state already refutes is not built:
        # the widest fan among a few initial states that span Init (the
        # host evaluator's successor rows are the lanes that fire),
        # taken up the same doubling ladder an overflow would take
        # (struct.cache.widen_slots) - EWD840's all-active initial
        # state fires 67 of 87 lanes at N = 8 against a guess of 32
        fan = max((len(system.successors(st))
                   for st in system.initial_corners()), default=0)
        while guess < min(len(labels), fan):
            guess = min(len(labels), 2 * guess)
    width = len(labels) if coverage or por else min(
        len(labels), max(guess, slots))
    compacted = width < len(labels)
    trap_stats = (compiler.trap_sites + int(compacted),
                  compiler.elided_traps)

    def step(vec):
        # device scope of the compiled successor function (inside the
        # engine's `jaxtlc.expand`): the lanes, and their compaction
        with jax.named_scope("jaxtlc.step.struct"):
            succs, valid, ovf, afail = batch_step(vec[None])
            if compacted:
                return compact_lanes(succs[0], valid[0], lane_action,
                                     afail[0], ovf[0], width)
            return succs[0], valid[0], lane_action, afail[0], ovf[0]

    def inv_check(vec):
        bits = jnp.int32(0)
        for k, fn in enumerate(inv_fns):
            bits = bits | (fn(vec[None])[0].astype(jnp.int32) << k)
        return bits

    def initial_vectors():
        doms = system.init_product()
        if doms is None:
            inits = system.initial_states()
            return np.stack([cdc.encode(st) for st in inits])
        # Init is a product of per-variable domains (EWD840: 2^(2N) N
        # states): each variable's values are encoded once, and the
        # rows are their product in initial_states' order
        names = [v for v, _ in doms]
        at = np.indices([len(vals) for _, vals in doms]).reshape(
            len(doms), -1)
        parts = []
        for v, lay in zip(system.variables, cdc.layouts):
            k = names.index(v)
            codes = []
            for val in doms[k][1]:
                out: List[int] = []
                lay.encode(val, out)
                codes.append(out)
            parts.append(np.asarray(codes, np.int32)[at[k]])
        return np.concatenate(parts, axis=1)

    constraint = None
    if model.constraints:
        # the cfg's CONSTRAINT: one predicate over a successor's raw
        # fields, walked once here (like the lanes) and replayed by
        # every engine trace
        with span("build.struct.constraint") as sp:
            sp.attrs["names"] = " ".join(model.constraints)
            constraint = jax.jit(compiler.build_invariant(
                ("and", list(model.constraints.values()))))
            jax.eval_shape(constraint,
                           jax.ShapeDtypeStruct((1, F), jnp.int32))

    action_prop = None
    if model.action_props:
        # the cfg's action properties `I /\\ [][A]_v`: each `[A]_v` one
        # two-state predicate over (the source columns it reads, the
        # successor row) and each I one state predicate, walked once
        # here (like the lanes) and replayed by every engine trace
        with span("build.struct.actionprop") as sp:
            props = list(model.action_props.values())
            sp.attrs["names"] = " ".join(p.name for p in props)
            read = state_vars_read(
                [p.action for p in props] + [list(p.sub) for p in props],
                system.ev.defs, system.variables)
            src_cols = tuple(j for v in read for j in cdc.columns(v))
            sp.attrs["src_cols"] = len(src_cols)
            steps = [jax.jit(compiler.build_two_state(
                p.action, p.sub, src_cols)) for p in props]
            inits_ok = [jax.jit(compiler.build_invariant(p.init))
                        for p in props]
            for fn in steps:
                jax.eval_shape(
                    fn, jax.ShapeDtypeStruct((1, len(src_cols)), jnp.int32),
                    jax.ShapeDtypeStruct((1, F), jnp.int32))
            for fn in inits_ok:
                jax.eval_shape(fn, jax.ShapeDtypeStruct((1, F), jnp.int32))

        def ap_step(src, succ):
            both = [fn(src, succ) for fn in steps]
            return (jnp.stack([ok for ok, _ in both]),
                    jnp.stack([moved for _, moved in both]))

        action_prop = ActionPropSeam(
            names=tuple(p.name for p in props),
            step=ap_step,
            init=lambda rows: jnp.stack([fn(rows) for fn in inits_ok]),
            src_cols=src_cols,
            init_codes=tuple(VIOL_ACTION_PROP_BASE + 2 * k
                             for k in range(len(props))),
            step_codes=tuple(VIOL_ACTION_PROP_BASE + 2 * k + 1
                             for k in range(len(props))),
        )

    cert_check = None
    if cert:
        cert_check = make_cert_check(
            cdc, _card_specs(cdc, system.variables, bounds.card_bounds)
        )

    plane = None
    if coverage:
        from ..obs.coverage import (
            CoveragePlane,
            Site,
            action_site_table,
        )

        cov_fn = compiler.build_cov(system.next_ast)
        # discover the site table with a shape-only trace (the same
        # discipline as the label discovery above)
        jax.eval_shape(
            cov_fn,
            jax.ShapeDtypeStruct((1, F), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.bool_),
            jax.ShapeDtypeStruct((1, len(labels)), jnp.bool_),
        )
        fine_sites = tuple(
            Site(key=k, kind=kind, action=a, loc=desc)
            for k, kind, a, desc in compiler.cov.sites
        )
        sites = tuple(action_site_table(model.root_name, action_names)
                      ) + fine_sites
        label_ids = jnp.arange(len(action_names), dtype=jnp.int32)

        def cov_count(batch, mask, valid):
            # action-prefix sites = per-action generated counts, the
            # same [L, n_actions] fold the engine's gen counters use -
            # one accounting, two renderings
            lane_counts = valid.sum(axis=0).astype(jnp.uint32)
            act = (
                (lane_action[:, None] == label_ids[None, :])
                * lane_counts[:, None]
            ).sum(axis=0).astype(jnp.uint32)
            return jnp.concatenate([act, cov_fn(batch, mask, valid)])

        plane = CoveragePlane(sites=sites, count=cov_count,
                              module=model.root_name)

    reduce_ops = None
    if symmetry or por:
        from ..analysis.speclint import analyze_spec
        from ..analysis.symfind import analyze_reduction, require_declared
        from ..engine.reduce import ReduceOps, build_plan

        # host span `build.struct.symmetry` (with the loader's one of
        # the same name, which evaluates a cfg's SYMMETRY): the static
        # verification of the sets and the plan's build; a set the cfg
        # declares that either rejects is an error, never a silently
        # unreduced run
        with span("build.struct.symmetry") as sp:
            rep = analyze_reduction(
                model, analyze_spec(model, var_shapes=var_shapes)
            )
            plan, dropped = (None, {})
            if symmetry:
                plan, dropped = build_plan(cdc, rep.symmetric_sets)
                require_declared(model, {**rep.rejected_sets, **dropped})
            sp.attrs["perms"] = plan.n_perms if plan is not None else 1
        safe_ids: Tuple[int, ...] = ()
        if por:
            safe_ids = tuple(
                action_names.index(a) for a in rep.safe_actions
                if a in action_names
            )
        reduce_ops = ReduceOps(
            plan=plan,
            safe_ids=safe_ids,
            por=bool(por),
            sym_sets=tuple(sorted(plan.sym_sets.items()))
            if plan is not None else (),
            dropped_sets=tuple(sorted(
                {**rep.rejected_sets, **dropped}.items()
            )),
        )

    viol_names = struct_viol_names(model)
    if bounds is not None:
        from ..engine.bfs import VIOL_SLOT_OVERFLOW

        viol_names[VIOL_SLOT_OVERFLOW] = (
            "Codec slot overflow / certified-bound escape (narrowed "
            "codec: a value left the certified reachable range - "
            "re-run with -no-narrow; if that passes, report the spec: "
            "the bound certification is unsound)"
        )
    backend = SpecBackend(
        cdc=cdc,
        step=step,
        n_lanes=width,
        inv_check=inv_check,
        inv_codes=tuple(
            VIOL_INVARIANT_BASE + k for k in range(len(model.invariants))
        ),
        initial_vectors=initial_vectors,
        labels=action_names,
        viol_names=viol_names,
        lane_action=None if compacted else lane_action,
        check_deadlock=check_deadlock,
        cert_check=cert_check,
        coverage=plane,
        reduce=reduce_ops,
        constraint=constraint,
        constraint_names=tuple(model.constraints),
        action_prop=action_prop,
    )
    # trap-audit surface (preflight renders which traps remain and why)
    backend.cdc.trap_stats = trap_stats
    # the static fan before compaction (CheckResult.step_lanes)
    backend.cdc.static_lanes = len(labels)
    # the sequences' static slots, where their capacities came from
    # (CheckResult.seq_slots / seq_cap_from) and the largest (the rung's
    # start); None under certified bounds, whose capacities are the
    # report's
    (backend.cdc.seq_slots, backend.cdc.seq_cap_from,
     backend.cdc.seq_cap_max) = seq_summary(
        var_shapes, seq_caps) if bounds is None else (0, None, 0)
    # the forms its field reads took (CheckResult.lookup_*)
    backend.cdc.lookup_counts = compiler.lookup_counts
    # a state predicate compiled as the invariants are ([B, F] -> bool
    # [B]): the liveness route's P and Q (live.check)
    backend.cdc.compile_predicate = compiler.build_invariant
    return backend


def canonical_constants(model: StructModel) -> dict:
    """JSON-stable rendering of the model's resolved constants (the
    checkpoint-meta / cache-key form; frozensets sort, everything else
    goes through repr so model values and numbers stay distinct)."""
    out = {}
    for k in sorted(model.constants):
        v = model.constants[k]
        out[k] = (sorted(map(repr, v)) if isinstance(v, frozenset)
                  else repr(v))
    return out


def struct_meta_config(model: StructModel, bounds=None) -> dict:
    """The checkpoint `config` stanza for struct runs: digest +
    canonical constants + invariant list - everything that shapes the
    compiled step, so a -recover against a different spec text or
    overrides is a loud mismatch, never a silent misrun.  A narrowed
    run additionally records its bound digest: a narrowed checkpoint
    resumed without -narrow (or with re-derived different bounds) is a
    different carry layout and must mismatch loudly."""
    out = {
        "frontend": "struct",
        "root": model.root_name,
        "digest": model.source_digest,
        "constants": canonical_constants(model),
        "invariants": list(model.invariants),
    }
    if bounds is not None:
        out["bound_digest"] = bounds.digest()
    return out
