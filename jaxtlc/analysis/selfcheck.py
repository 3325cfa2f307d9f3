"""Audit every shipped engine factory (the preflight's own CI).

`python -m jaxtlc.analysis --self-check --tiny` builds each production
engine factory at tiny geometry, traces its run/step jaxprs and runs
the engine-layer audit suite (purity, donation tags, counter widths).
The registry below IS the definition of "shipped": a new engine path
added without a registry entry fails the tier-1 smoke test
(tests/test_analysis.py pins the factory list), so no engine can ship
unaudited.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from . import AnalysisReport, Finding
from .engine_audit import audit_engine, carry_shapes

# tiny self-check geometry: enough rows for the FF inits, nothing more
_TINY = dict(chunk=16, queue_capacity=1 << 8, fp_capacity=1 << 10)


def _ff_backend():
    from ..config import ModelConfig
    from ..engine.backend import kubeapi_backend

    return kubeapi_backend(ModelConfig(False, False))


def _build_fused():
    from ..engine.bfs import make_backend_engine

    init_fn, run_fn, step_fn = make_backend_engine(
        _ff_backend(), donate=False, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=_ff_backend().n_lanes,
                fp_capacity=_TINY["fp_capacity"])


def _build_pipelined():
    from ..engine.bfs import make_backend_engine

    init_fn, run_fn, step_fn = make_backend_engine(
        _ff_backend(), donate=False, pipeline=True, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=_ff_backend().n_lanes,
                fp_capacity=_TINY["fp_capacity"])


def _build_sharded():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..engine.sharded import make_sharded_engine

    mesh = Mesh(np.array(jax.devices()[:1]), ("fp",))
    init_fn, run_fn = make_sharded_engine(
        None, mesh, backend=_ff_backend(), **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn,
                n_lanes=_ff_backend().n_lanes,
                fp_capacity=_TINY["fp_capacity"])


def _specs_dir() -> Optional[str]:
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cand = os.path.join(os.path.dirname(here), "specs")
    return cand if os.path.isdir(cand) else None


def _build_struct():
    import os

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    b = get_backend(model, True)
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_narrowed():
    # the certified-bound narrowed struct engine (ISSUE 10): the same
    # TwoPhase model as "struct" but compiled against the certified
    # reachable bounds with the runtime certificate check on - the
    # narrowed codec + cert column path cannot ship unaudited
    import os

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend, get_bounds
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    bounds = get_bounds(model)
    b = get_backend(model, True, bounds=bounds)
    assert b.cert_check is not None, "narrowed factory must carry cert"
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, obs_slots=8, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_covered():
    # the device coverage plane engine (ISSUE 11): the same TwoPhase
    # model as "struct" but compiled with the per-site coverage
    # counters + the obs ring - the covered carry layout (cov_counts
    # leaf) cannot ship unaudited
    import os

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    b = get_backend(model, True, coverage=True)
    assert b.coverage is not None, "covered factory must carry a plane"
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, obs_slots=8, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_covsharded():
    # the pod obs MESH engine (ISSUE 20): the sharded owner-commit
    # engine with the counter ring + coverage plane riding its carry -
    # the per-shard cov_counts leaf and ring rows the pod driver
    # checkpoints, reads at fences and migrates on --reshard cannot
    # ship unaudited
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..config import ModelConfig
    from ..engine.backend import kubeapi_backend
    from ..engine.sharded import make_sharded_engine

    b = kubeapi_backend(ModelConfig(False, False), coverage=True)
    assert b.coverage is not None, "covsharded factory needs a plane"
    mesh = Mesh(np.array(jax.devices()[:1]), ("fp",))
    init_fn, run_fn = make_sharded_engine(
        None, mesh, backend=b, obs_slots=8, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_deferred():
    # the distinct-first deferred-evaluation engine (ISSUE 15): the
    # same TwoPhase model as "struct" but with invariant + certificate
    # evaluation moved to the commit stage (fresh-insert claimants
    # only), the obs ring riding along - the commit-site checker's
    # gather/while_loop path cannot ship unaudited
    import os

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    b = get_backend(model, True)
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, obs_slots=8, deferred=True, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_sim():
    # the random-walk simulation engine (jaxtlc.sim, ISSUE 14): the
    # same TwoPhase model as "struct", walked with the counter-based
    # RNG and the fp sampling filter - the chosen-successor gather,
    # threefry draw and saturating filter path cannot ship unaudited
    import os

    from ..sim.engine import make_sim_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    b = get_backend(model, True)
    init_fn, run_fn, step_fn = make_sim_engine(
        b, walkers=8, depth=8, fp_capacity=1 << 10,
    )
    return dict(init_fn=lambda: init_fn(0), run_fn=run_fn,
                step_fn=step_fn, n_lanes=b.n_lanes,
                fp_capacity=1 << 10)


def _build_infer():
    # the inference filter/certify kernels (jaxtlc.infer, ISSUE 16):
    # the same TwoPhase model as "struct", its conjectured candidate
    # pool compiled into the [P, S] filter dispatch (run_fn) and the
    # one-step closure certify dispatch (step_fn) - the vmapped
    # stacked-predicate path cannot ship unaudited
    import os

    from ..infer.candidates import conjecture
    from ..infer.certify import make_certify_fn
    from ..infer.filter import (
        compile_predicates,
        make_filter_fn,
        predicate_compiler,
    )
    from ..struct.cache import get_backend, get_bounds
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_1",
                              "MC.cfg"))
    b = get_backend(model, True)
    cands, _ = conjecture(model, bounds=get_bounds(model), budget=16)
    fns, _ = compile_predicates(predicate_compiler(model, b), cands)

    def init_fn():
        import jax.numpy as jnp

        return jnp.zeros((16, b.cdc.n_fields), jnp.int32)

    return dict(init_fn=init_fn, run_fn=make_filter_fn(fns),
                step_fn=make_certify_fn(b, fns), n_lanes=b.n_lanes,
                fp_capacity=_TINY["fp_capacity"])


def _build_symmetry():
    # the symmetry-reduced engine (engine.reduce, ISSUE 18): the
    # TwoPhase model with a 3-element symmetric RM set, compiled with
    # the on-device orbit canonicalization + the sticky COL_SYM orbit
    # certificate - the permutation-program tournament and the ring's
    # tenth column cannot ship unaudited
    import os

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = _specs_dir()
    if d is None:
        raise FileNotFoundError("specs/ directory not found")
    model = load(os.path.join(d, "TwoPhase.toolbox", "Model_sym",
                              "MC.cfg"))
    b = get_backend(model, False, symmetry=True)
    assert b.reduce is not None and b.reduce.plan is not None, \
        "symmetry factory must carry an orbit plan"
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, obs_slots=8, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


_POR_SPEC = """---- MODULE PorAudit ----
EXTENDS Naturals
VARIABLES x, y

Init == x = 0 /\\ y = 0

IncX == /\\ x < 4
        /\\ x' = x + 1
        /\\ UNCHANGED <<y>>

IncY == /\\ y < 4
        /\\ y' = y + 1
        /\\ UNCHANGED <<x>>

Next == IncX \\/ IncY

Spec == Init /\\ [][Next]_<<x, y>>

InRange == x <= 4
====
"""

_POR_CFG = """SPECIFICATION
Spec
INVARIANT
InRange
"""


def _build_por():
    # the partial-order-pruned engine (engine.reduce, ISSUE 18):
    # audited over a synthetic two-counter module whose IncY is a POR-
    # safe action (independent, invisible to the invariant, monotone;
    # frame conjuncts MUST be UNCHANGED or speclint counts them as
    # writes) - the singleton-ample lane-mask path cannot ship
    # unaudited
    import os
    import tempfile

    from ..engine.bfs import make_backend_engine
    from ..struct.cache import get_backend
    from ..struct.loader import load

    d = tempfile.mkdtemp(prefix="jaxtlc-por-audit-")
    with open(os.path.join(d, "PorAudit.tla"), "w") as f:
        f.write(_POR_SPEC)
    cfg = os.path.join(d, "PorAudit.cfg")
    with open(cfg, "w") as f:
        f.write(_POR_CFG)
    model = load(cfg)
    b = get_backend(model, False, por=True)
    assert b.reduce is not None and b.reduce.safe_ids, \
        "por factory must carry safe action ids"
    init_fn, run_fn, step_fn = make_backend_engine(
        b, donate=False, obs_slots=8, **_TINY
    )
    return dict(init_fn=init_fn, run_fn=run_fn, step_fn=step_fn,
                n_lanes=b.n_lanes, fp_capacity=_TINY["fp_capacity"])


def _build_enumerator():
    from ..engine.bfs import make_enumerator

    init_fn, run_fn = make_enumerator(
        _ff_backend(), chunk=16, state_capacity=1 << 10,
        fp_capacity=1 << 10,
    )
    return dict(init_fn=init_fn, run_fn=run_fn,
                n_lanes=_ff_backend().n_lanes, fp_capacity=1 << 10)


def _build_spill():
    # the spill-capable engine: the DEVICE composition (expand +
    # fpset_member filter + veto commit) is traced as one step; the
    # host probe sits between the two jits in production, outside any
    # device body, which is exactly what the purity audit verifies
    from ..engine.spill import SpillRuntime, SpillStore

    rt = SpillRuntime(
        _ff_backend(), chunk=_TINY["chunk"],
        queue_capacity=_TINY["queue_capacity"],
        fp_capacity=_TINY["fp_capacity"],
        store=SpillStore(1 << 10),
    )
    return dict(init_fn=rt.init_fn, step_fn=rt.audit_step_fn,
                n_lanes=_ff_backend().n_lanes,
                fp_capacity=_TINY["fp_capacity"])


def _build_shardspill():
    # the spill-capable MESH engine (ISSUE 19): the audited step is the
    # expand half (candidate-routing all_to_all + owner fpset_member
    # filter) composed with the veto commit half; the host SpillStore
    # probe sits between the two shard_map dispatches in production,
    # outside any device body - exactly what the purity audit verifies
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..engine.sharded import ShardedSpillRuntime
    from ..engine.spill import SpillStore

    mesh = Mesh(np.array(jax.devices()[:1]), ("fp",))
    rt = ShardedSpillRuntime(
        None, mesh, _TINY["chunk"], _TINY["queue_capacity"],
        _TINY["fp_capacity"], backend=_ff_backend(),
        store=SpillStore(1 << 10),
    )
    return dict(init_fn=rt.init_fn, step_fn=rt.audit_step_fn,
                n_lanes=_ff_backend().n_lanes,
                fp_capacity=_TINY["fp_capacity"])


_SWEEP_SPEC = """---- MODULE SweepAudit ----
EXTENDS Naturals
CONSTANTS MAX
VARIABLES x

Init == x = 0

Up == /\\ x < MAX
      /\\ x' = x + 1

Next == Up

Spec == Init /\\ [][Next]_x

InRange == x <= MAX
====
"""

_SWEEP_CFG = """CONSTANT MAX = 3
SPECIFICATION
Spec
INVARIANT
InRange
"""


def _build_sweep():
    # the constants-class sweep engine (jaxtlc.serve.sweep): audited
    # over a synthetic one-constant module so the registry never
    # depends on serve-side fixtures; init_fn presents the stacked
    # width-2 batch carry the vmapped run_fn consumes
    import os
    import tempfile

    from ..serve.sweep import SweepEngine, load_anchored

    d = tempfile.mkdtemp(prefix="jaxtlc-sweep-audit-")
    with open(os.path.join(d, "SweepAudit.tla"), "w") as f:
        f.write(_SWEEP_SPEC)
    cfg = os.path.join(d, "SweepAudit.cfg")
    with open(cfg, "w") as f:
        f.write(_SWEEP_CFG)
    params = {"MAX": (1, 3)}
    model = load_anchored(cfg, params)
    eng = SweepEngine(
        model, params, chunk=_TINY["chunk"],
        queue_capacity=_TINY["queue_capacity"],
        fp_capacity=_TINY["fp_capacity"], check_deadlock=False,
        width=2,
    )

    def init_fn():
        return eng._stack([{"MAX": 1}, {"MAX": 3}])

    return dict(init_fn=init_fn, run_fn=eng._vrun,
                n_lanes=eng.backend.n_lanes,
                fp_capacity=_TINY["fp_capacity"])


# every shipped engine factory; audited by the self-check and pinned
# by tier-1 so a new engine path cannot ship unaudited
FACTORIES: Dict[str, Callable[[], dict]] = {
    "covered": _build_covered,
    "covsharded": _build_covsharded,
    "deferred": _build_deferred,
    "fused": _build_fused,
    "infer": _build_infer,
    "narrowed": _build_narrowed,
    "pipelined": _build_pipelined,
    "por": _build_por,
    "sharded": _build_sharded,
    "shardspill": _build_shardspill,
    "sim": _build_sim,
    "spill": _build_spill,
    "struct": _build_struct,
    "sweep": _build_sweep,
    "symmetry": _build_symmetry,
    "enumerator": _build_enumerator,
}


def self_check(tiny: bool = True, out=None) -> AnalysisReport:
    """Build + audit every registered factory.  `tiny` is accepted for
    CLI symmetry; the registry always builds tiny geometries (the audit
    is geometry-independent - jaxprs, not runs)."""
    import sys
    import time

    out = out or sys.stdout
    t0 = time.time()
    report = AnalysisReport(name="self-check")
    for name in sorted(FACTORIES):
        try:
            built = FACTORIES[name]()
        except FileNotFoundError as e:
            out.write(f"audit {name}: SKIPPED ({e})\n")
            continue
        carry = carry_shapes(built["init_fn"])
        findings: List[Finding] = audit_engine(
            name,
            built["init_fn"],
            built.get("run_fn"),
            built.get("step_fn"),
            reuses_carry=built.get("reuses_carry", False),
            fp_capacity=built.get("fp_capacity"),
            n_lanes=built.get("n_lanes"),
            trace=True,
            carry=carry,
        )
        report.extend(findings)
        status = "ok" if not findings else (
            f"{len(findings)} finding(s)"
        )
        out.write(f"audit {name}: {status}\n")
        report.engine_lines.append(f"{name}: {status}")
    report.wall_s = time.time() - t0
    out.write(
        f"self-check: {len(FACTORIES)} factories, "
        f"{len(report.findings)} finding(s), "
        f"{report.wall_s:.2f}s\n"
    )
    return report
