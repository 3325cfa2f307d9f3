"""Engine-layer audits over jaxprs traced from the engine factories.

Three hazards the device engines can carry silently on CPU and pay for
on TPU or at scale; each is checkable by tracing (never compiling) the
factory's run/step functions:

* **Donation safety**: `make_backend_engine(donate=True)` marks the
  carry donated so XLA aliases the ping-pong buffers.  Feeding the SAME
  carry twice (the supervisor retry loop, profilers, A/B harnesses) is
  then a use-after-donate - invisible on CPU where XLA has no donation,
  a garbage run on TPU.  The factories tag their functions with
  `donate_requested` / `donates_carry`; the audit cross-checks the tag
  against the driver's declared reuse.  (`JAXTLC_DEBUG_DONATION=1`
  additionally poisons donated carries at runtime so reuse fails fast
  on CPU too - analysis.donation.)
* **Hot-body purity**: a `pure_callback` / `io_callback` /
  `debug_callback` inside a `lax.while_loop` engine body syncs the
  device to the host EVERY iteration - the exact round-trip the fused
  engines exist to avoid.  The audit walks the traced jaxpr (through
  pjit / while / cond / scan sub-jaxprs) and flags any callback
  primitive.
* **Counter width**: the obs ring and per-action counters are
  cumulative uint32 (obs/counters.py).  `generated` grows up to
  n_lanes candidates per expanded state, so a run bounded by
  fp_capacity distinct states can generate up to fp_capacity * n_lanes
  - past 2^32 the columns silently wrap.  The audit flags the
  configuration up front; the ring's sticky overflow column
  (COL_OVERFLOW) catches the residual risk at runtime.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Set

from . import SEV_ERROR, SEV_WARNING, Finding

U32_MAX = 1 << 32

# host-callback primitives that have no place in a fused engine body
# (jax.debug.print traces to its own `debug_print` primitive)
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    import jax.extend.core as jc

    for v in params.values():
        if isinstance(v, jc.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jc.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jc.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jc.Jaxpr):
                    yield x


def jaxpr_primitives(jaxpr) -> Set[str]:
    """All primitive names in `jaxpr`, recursing through pjit bodies,
    while/cond/scan sub-jaxprs and custom-call wrappers."""
    prims: Set[str] = set()
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            prims.add(eqn.primitive.name)
            stack.extend(_sub_jaxprs(eqn.params))
    return prims


def trace_engine_fn(fn, carry) -> Set[str]:
    """Primitive-name set of `fn(carry)` - tracing only, no XLA compile
    (the preflight contract: no extra engine compiles)."""
    import jax

    return jaxpr_primitives(jax.make_jaxpr(fn)(carry).jaxpr)


def carry_shapes(init_fn):
    """Abstract carry for tracing: `jax.eval_shape` when the init is
    traceable (single-device engines), the tiny concrete carry
    otherwise (the sharded init stages numpy through device_put)."""
    import jax

    try:
        return jax.eval_shape(init_fn)
    except Exception:
        return init_fn()


# ---------------------------------------------------------------------------
# audits
# ---------------------------------------------------------------------------


def audit_purity(name: str, fn, carry) -> List[Finding]:
    """Flag host-callback primitives inside an engine function body."""
    prims = trace_engine_fn(fn, carry)
    bad = sorted(prims & CALLBACK_PRIMS)
    if not bad:
        return []
    return [Finding(
        layer="engine", check="hot-body-purity", severity=SEV_ERROR,
        subject=name,
        detail=(f"{name} traces host callback primitive(s) "
                f"{', '.join(bad)} inside its device body; every loop "
                "iteration would sync to the host"),
    )]


def audit_donation(name: str, fn, reuses_carry: bool) -> List[Finding]:
    """Cross-check a factory function's donation tag against the
    driver's carry-reuse behavior.  `donate_requested` is the factory
    intent; on CPU XLA ignores donation (`donates_carry` False), which
    is exactly why the hazard must be flagged statically - the failure
    only reproduces on device."""
    requested = bool(getattr(fn, "donate_requested", False))
    if requested and reuses_carry:
        return [Finding(
            layer="engine", check="donation-reuse", severity=SEV_ERROR,
            subject=name,
            detail=(f"{name} donates its carry but the driver feeds the "
                    "same carry twice (retry/profiler reuse); on TPU "
                    "this is a use-after-donate - build the engine with "
                    "donate=False or stop reusing the carry"),
        )]
    return []


def audit_counter_width(subject: str, fp_capacity: int, n_lanes: int,
                        dtype_bits: int = 32) -> List[Finding]:
    """Static saturation bound for the cumulative uint32 counters: a
    run can expand up to fp_capacity distinct states, each generating
    up to n_lanes candidates, so cumulative `generated` (and the
    per-action columns summing to it) is bounded by fp_capacity *
    n_lanes.  Past 2^32 the uint32 columns wrap silently - exactly
    where ROADMAP #3's billion-state runs are headed.

    Note the bound assumes fp_capacity caps the distinct-state count.
    Once the HOST SPILL TIER activates (engine.spill - the recovery
    story for fpset saturation), distinct states are bounded by host
    RAM instead, so a spilling run can saturate these counters at ANY
    fp_capacity; the ring's sticky overflow column is then the only
    guard."""
    bound = int(fp_capacity) * max(int(n_lanes), 1)
    if bound < (1 << dtype_bits):
        return []
    return [Finding(
        layer="engine", check="counter-width", severity=SEV_WARNING,
        subject=subject,
        detail=(f"cumulative uint32 counters can saturate: fp_capacity "
                f"{fp_capacity} x {n_lanes} lanes bounds `generated` at "
                f"{bound} >= 2^{dtype_bits} (and the host spill tier, "
                "once active, lifts the fp_capacity bound entirely); "
                "the obs ring's sticky overflow column will flag it at "
                "runtime, but totals will be wrong - shard the fp "
                "space or lower fp_capacity"),
    )]


def audit_engine(
    name: str,
    init_fn=None,
    run_fn=None,
    step_fn=None,
    *,
    reuses_carry: bool = False,
    fp_capacity: Optional[int] = None,
    n_lanes: Optional[int] = None,
    trace: bool = True,
    carry=None,
) -> List[Finding]:
    """The full engine-layer suite over one factory's functions.
    `trace=False` skips the jaxpr purity pass (the CLI's lite preflight;
    `-analyze` and the self-check run it)."""
    findings: List[Finding] = []
    fns = [("run_fn", run_fn), ("step_fn", step_fn)]
    for label, fn in fns:
        if fn is None:
            continue
        findings.extend(audit_donation(f"{name}.{label}", fn,
                                       reuses_carry))
    if trace and init_fn is not None:
        if carry is None:
            carry = carry_shapes(init_fn)
        for label, fn in fns:
            if fn is None:
                continue
            findings.extend(audit_purity(f"{name}.{label}", fn, carry))
    if fp_capacity is not None and n_lanes is not None:
        findings.extend(audit_counter_width(name, fp_capacity, n_lanes))
    return findings


def describe_engine(name: str, fn, carry,
                    extras: Iterable[str] = ()) -> str:
    """One stable report line per audited engine function: primitive
    count + the capability-relevant primitive classes present (used by
    the golden engine-layer reports; primitive NAMES vary with jax
    versions less than their classes do)."""
    prims = trace_engine_fn(fn, carry)
    classes = []
    for label, members in (
        ("while", {"while"}),
        ("cond", {"cond"}),
        ("sort", {"sort"}),
        ("gather", {"gather", "dynamic_slice"}),
        # ragged_all_to_all / reduce_scatter are how newer jax lowers
        # the cross-host (DCN) exchange of a multi-process pod mesh
        # (jaxtlc.dist); they must classify as collective, not fall
        # through as unknown primitives, or the census would report a
        # pod engine as collective-free
        ("collective", {"all_to_all", "psum", "pmax", "pmin",
                        "all_gather", "ppermute", "ragged_all_to_all",
                        "reduce_scatter"}),
        ("callback", CALLBACK_PRIMS),
    ):
        if prims & members:
            classes.append(label)
    parts = [f"{name}: {'+'.join(classes)}"]
    parts.extend(extras)
    return "  ".join(parts)
