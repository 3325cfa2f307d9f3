"""Device checking of struct-compiled specs (E1) on the production
engines.

The private struct BFS loop is retired (round-6 tentpole): the
LaneCompiler step plugs into the same fused v4 engine the hand kernel
uses (engine.bfs.make_backend_engine via struct.backend.struct_backend),
so struct specs get the bucketized sort-compacted dedup, MXU
fingerprints, contiguous enqueue, two-tier adaptive stepping, segmented
execution (the resil supervisor's unit of work), TLC outdegree stats
and the assertion-failure channel from one code path.  Mesh sharding
routes through engine.sharded with the same backend.

Engine builds are memoized and XLA compiles persist across processes
(struct.cache): repeated runs of the same model skip the minutes-long
compile (tests/test_artifacts.py::
test_cached_verdict_matches_fresh_with_zero_compiles pins the hit).
"""

from __future__ import annotations

import time

import jax

from ..engine.bfs import (
    CheckResult,
    VIOLATION_NAMES,
    commit_geometry,
    result_from_carry,
    with_step_counters,
)
from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from .backend import (  # noqa: F401 - VIOL_INVARIANT_BASE is API here
    VIOL_INVARIANT_BASE,
    struct_backend,
    struct_viol_names,
)
from .cache import get_backend, get_engine
from .loader import StructModel


def violation_name(model: StructModel, code: int) -> str:
    return struct_viol_names(model).get(code) or VIOLATION_NAMES.get(
        code, f"violation {code}"
    )


def check_struct(
    model: StructModel,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    check_deadlock: bool = True,
    fp_highwater: float = 0.85,
    pipeline: bool = False,
    obs_slots: int = 0,
    bounds=None,
    coverage: bool = False,
    deferred: bool = None,
    symmetry: bool = None,
    por: bool = None,
    capture_fps: bool = False,
) -> CheckResult:
    """Exhaustive device check of a struct-compiled spec (single device,
    fused loop; AOT-compiled before timing like bfs.check).  `bounds`
    (a certified analysis.absint.BoundReport) runs the NARROWED engine
    with the runtime certificate check on; `coverage` the covered
    engine (device per-site coverage on CheckResult.site_coverage);
    `symmetry`/`por` the state-space-reduced engine (orbit
    canonicalization with the runtime orbit certificate + ample-set
    pruning - same verdict, legitimately fewer states, ISSUE 18);
    `capture_fps` reads the final fingerprint table back to host on a
    clean verdict (CheckResult.fp_table - the artifact cache's
    reachable-set source, struct.artifacts)."""
    from ..engine.bfs import resolve_por
    from .cache import wants_symmetry

    init_fn, run_fn, _ = get_engine(
        model, chunk, queue_capacity, fp_capacity, fp_index, seed,
        fp_highwater, check_deadlock=check_deadlock, pipeline=pipeline,
        obs_slots=obs_slots, bounds=bounds, coverage=coverage,
        deferred=deferred, symmetry=symmetry, por=por,
    )
    backend = get_backend(model, check_deadlock, bounds=bounds,
                          coverage=coverage,
                          symmetry=wants_symmetry(model, symmetry, chunk),
                          por=resolve_por(por, chunk))
    carry = init_fn()
    compiled = run_fn.lower(carry).compile()
    t0 = time.time()
    out = jax.block_until_ready(compiled(carry))
    wall = time.time() - t0
    result = with_step_counters(result_from_carry(
        out, wall, fp_capacity=fp_capacity, labels=backend.labels,
        viol_names=backend.viol_names,
        sites=backend.coverage.sites if backend.coverage else None,
        commit=commit_geometry(backend.n_lanes, chunk),
    ), backend)
    if capture_fps and result.violation == 0:
        import numpy as np

        result = result._replace(
            fp_table=np.asarray(jax.device_get(out.fps.table))
        )
    return result


def check_struct_sharded(
    model: StructModel,
    mesh,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    route_factor: float = 2.0,
    check_deadlock: bool = True,
    pipeline: bool = False,
    obs_slots: int = 0,
    bounds=None,
    coverage: bool = False,
    deferred: bool = None,
    symmetry: bool = None,
    por: bool = None,
) -> CheckResult:
    """Exhaustive mesh-sharded check of a struct-compiled spec
    (capacities PER DEVICE; fingerprint-space all_to_all partitioning,
    psum-reduced counters - engine.sharded, same backend seam).
    `bounds` narrows the codec; the mesh engine has no certificate
    column yet, so every trap stays compiled in (elide=False) and the
    encode traps carry the soundness story there.  `coverage` carries
    the per-device coverage partials, summed at readback.
    `symmetry`/`por` reduce the state space before routing: orbit
    canonicalization runs pre-fingerprint so representatives shard
    consistently (the fingerprint is a pure function of the canonical
    packed words on every device)."""
    from ..engine.bfs import resolve_por
    from .cache import wants_symmetry
    from ..engine.sharded import check_sharded

    backend = get_backend(model, check_deadlock, bounds=bounds,
                          elide=False, coverage=coverage,
                          symmetry=wants_symmetry(model, symmetry, chunk),
                          por=resolve_por(por, chunk))
    return check_sharded(
        None, mesh, chunk=chunk, queue_capacity=queue_capacity,
        fp_capacity=fp_capacity, route_factor=route_factor,
        backend=backend, pipeline=pipeline, obs_slots=obs_slots,
        deferred=deferred,
    )
