"""Warm AOT engine pool: the serving tier's compile amortizer.

A checking service lives or dies on cold-start amortization (the
TensorFlow-serving lesson in PAPERS.md: compile the graph once, serve
it forever).  The pool holds FULLY COMPILED engines - the AOT
executable, not just the jit closures - keyed by the struct-cache memo
key for plain engines (`struct.cache.engine_key`: spec digest x
canonical constants x geometry x pipeline/obs flags) and by the
constants-CLASS key for sweep engines (`sweep.class_key`: the swept
values drop out, which is what lets one entry serve a whole config
portfolio).  LRU eviction bounds a long-lived process; hit/miss/
eviction/compile counters make the warm-path contract assertable.

The contract - **warm submit performs ZERO fresh XLA compiles** - is
pinned by `CompileMeter`, which counts jax's own
`/jax/core/compile/backend_compile_duration` monitoring events: every
real backend compile fires one, a warm AOT call fires none, so a test
(and `tools/loadgen.py --tiny`) can assert the meter's delta across a
resubmit is exactly zero.  Our own `compiles` counter says when the
POOL built; the meter says what XLA actually did - the two together
catch both a broken pool key and a silently-recompiling executable.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from ..obs.spans import span
from ..runtime import CompileMeter, aot_build  # noqa: F401 - re-export


def xla_compiles() -> int:
    """Monotonic count of real XLA compiles this process performed."""
    return CompileMeter.instance().count


class PoolEntry:
    """One warm engine: the AOT executable plus everything needed to
    run a job against it without touching the compiler."""

    def __init__(self, key, kind: str, runner, meta: dict):
        self.key = key
        self.kind = kind  # "single" | "sweep"
        self.runner = runner  # _SingleRunner | sweep.SweepEngine
        self.meta = meta
        self.built_t = time.time()
        self.last_used = self.built_t
        self.uses = 0


class _SingleRunner:
    """AOT wrapper for one plain struct engine (one model, one config):
    compile once at build, fresh carry + warm executable per job."""

    def __init__(self, model, chunk, queue_capacity, fp_capacity,
                 fp_index, seed, check_deadlock, pipeline, obs_slots,
                 deferred=None):
        import jax

        from ..engine.bfs import DEFAULT_FP_HIGHWATER
        from ..struct.cache import get_backend, get_engine

        self.model = model
        self.fp_capacity = fp_capacity
        self.backend = get_backend(model, check_deadlock)

        def make():
            init_fn, run_fn, _ = get_engine(
                model, chunk, queue_capacity, fp_capacity, fp_index,
                seed, DEFAULT_FP_HIGHWATER, check_deadlock=check_deadlock,
                pipeline=pipeline, obs_slots=obs_slots,
                deferred=deferred,
            )
            self._mk_carry = jax.jit(lambda: init_fn())
            return self._mk_carry, run_fn

        # the engine memo shares jit closures; the POOL owns the AOT
        # executables so a warm submit never re-lowers or re-traces
        # (lower().compile() bypasses the jit call cache, and an EAGER
        # init_fn re-compiles its fpset while_loop per call - both
        # would make every submit of a memo-hit engine pay fresh XLA
        # compiles; the zero-compile warm contract pins this).  No key:
        # this whole-run program takes a fresh carry a job and the pool's
        # own table already keeps the executable (folding that table
        # under runtime's kept engines is ROADMAP C7)
        _, self._aot = aot_build(make)

    def run(self, capture_fps: bool = False):
        import jax

        from ..engine.bfs import result_from_carry
        from ..struct.backend import struct_viol_names

        with span("pool.carry"):
            carry = self._mk_carry()
        with span("pool.run") as ran:
            out = jax.block_until_ready(self._aot(carry))
        with span("pool.readback"):
            result = result_from_carry(
                out, ran.seconds, fp_capacity=self.fp_capacity,
                labels=self.backend.labels,
                viol_names=struct_viol_names(self.model),
            )
            if capture_fps and result.violation == 0:
                # the artifact cache's reachable-set source (ISSUE 13):
                # one host copy of the final table, clean verdicts only
                import numpy as np

                result = result._replace(
                    fp_table=np.asarray(jax.device_get(out.fps.table))
                )
        return result


class EnginePool:
    """LRU pool of warm AOT engines (thread-safe: the HTTP handlers
    read stats while the scheduler thread builds/runs)."""

    def __init__(self, capacity: int = 8,
                 sweep_width: int = None):
        from .sweep import DEFAULT_WIDTH

        self.capacity = max(1, int(capacity))
        self.sweep_width = sweep_width or DEFAULT_WIDTH
        self._entries: "OrderedDict[tuple, PoolEntry]" = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0  # pool-level builds (one per miss)
        self.compile_wall_s = 0.0
        # --prewarm accounting (ISSUE 13 satellite): engines compiled
        # ahead of traffic so the FIRST submit rides the warm path
        self.prewarmed = 0
        self.prewarm_errors = 0
        self.prewarm_wall_s = 0.0
        CompileMeter.instance()  # start metering before the first build

    # -- lookup ------------------------------------------------------------

    def _get_or_build(self, key, build, kind: str, meta: dict):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self.hits += 1
                hit.uses += 1
                hit.last_used = time.time()
                self._entries.move_to_end(key)
                return hit
            self.misses += 1
        # build OUTSIDE the lock: compiles are seconds-to-minutes and
        # stats reads must not block behind them
        t0 = time.time()
        runner = build()
        wall = time.time() - t0
        entry = PoolEntry(key, kind, runner, meta)
        with self._lock:
            self.compiles += 1
            self.compile_wall_s += wall
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
        return entry

    def get_single(
        self,
        model,
        chunk: int = 64,
        queue_capacity: int = 1 << 10,
        fp_capacity: int = 1 << 12,
        fp_index: int = DEFAULT_FP_INDEX,
        seed: int = DEFAULT_SEED,
        check_deadlock: bool = True,
        pipeline: bool = False,
        obs_slots: int = 0,
        deferred: bool = None,
    ) -> PoolEntry:
        """Warm plain engine for (model meaning, geometry) - keyed on
        the struct-cache memo key, so pool identity == memo identity."""
        from ..engine.bfs import DEFAULT_FP_HIGHWATER
        from ..struct.cache import engine_key

        key = engine_key(
            model, chunk, queue_capacity, fp_capacity, fp_index, seed,
            DEFAULT_FP_HIGHWATER, check_deadlock=check_deadlock,
            pipeline=pipeline, obs_slots=obs_slots, deferred=deferred,
        )
        return self._get_or_build(
            key,
            lambda: _SingleRunner(
                model, chunk, queue_capacity, fp_capacity, fp_index,
                seed, check_deadlock, pipeline, obs_slots,
                deferred=deferred,
            ),
            "single",
            dict(workload=model.root_name, chunk=chunk,
                 fp_capacity=fp_capacity),
        )

    def get_sweep(
        self,
        model,
        params: Dict[str, Tuple[int, int]],
        chunk: int = 64,
        queue_capacity: int = 1 << 10,
        fp_capacity: int = 1 << 12,
        fp_index: int = DEFAULT_FP_INDEX,
        seed: int = DEFAULT_SEED,
        check_deadlock: bool = True,
        deferred: bool = None,
    ) -> PoolEntry:
        """Warm constants-class sweep engine: one entry per CLASS (the
        swept values are runtime data, not key material)."""
        from ..engine.bfs import resolve_deferred
        from .sweep import SweepEngine, class_key

        key = ("sweep", class_key(model, params), chunk, queue_capacity,
               fp_capacity, fp_index, seed, bool(check_deadlock),
               int(self.sweep_width), resolve_deferred(deferred, chunk))
        return self._get_or_build(
            key,
            lambda: SweepEngine(
                model, params, chunk=chunk,
                queue_capacity=queue_capacity, fp_capacity=fp_capacity,
                fp_index=fp_index, seed=seed,
                check_deadlock=check_deadlock, width=self.sweep_width,
                deferred=deferred,
            ),
            "sweep",
            dict(workload=model.root_name, chunk=chunk,
                 fp_capacity=fp_capacity,
                 params={c: list(d) for c, d in sorted(params.items())}),
        )

    def get_sim(
        self,
        model,
        params: Optional[Dict[str, Tuple[int, int]]] = None,
        walkers: int = 64,
        depth: int = 64,
        fp_capacity: int = 0,
        check_deadlock: bool = True,
    ) -> PoolEntry:
        """Warm random-walk engine for the smoke job class (jaxtlc.sim,
        ISSUE 14), keyed like the sweep classes: the SEED is run data
        (a vmapped batch lane), so one entry serves every per-commit
        smoke submit of a spec, and `params` (swept constant domains)
        keys a seeds-x-configs class exactly as sweep.class_key does."""
        from ..sim.engine import SimEngine, sim_engine_key
        from .sweep import class_key

        if params:
            key = ("sim-sweep", class_key(model, params), int(walkers),
                   int(depth), int(fp_capacity), bool(check_deadlock),
                   int(self.sweep_width))
        else:
            key = sim_engine_key(
                model, walkers, depth, fp_capacity, check_deadlock
            ) + (int(self.sweep_width),)
        return self._get_or_build(
            key,
            lambda: SimEngine(
                model, params=params, walkers=walkers, depth=depth,
                fp_capacity=fp_capacity, check_deadlock=check_deadlock,
                width=self.sweep_width,
            ),
            "sim",
            dict(workload=model.root_name, walkers=int(walkers),
                 depth=int(depth), fp_capacity=int(fp_capacity)),
        )

    def get_infer(
        self,
        model,
        budget: int = 64,
        walkers: int = 64,
        depth: int = 64,
        check_deadlock: bool = True,
        max_host_states: int = None,
    ) -> PoolEntry:
        """Warm inference engine for the infer job class (jaxtlc.infer,
        ISSUE 16).  Like sim, the SEED is run data - candidate pool,
        filter/certify kernels (AOT against their fixed block shapes)
        and exact evidence all build once per (model, budget, walk
        geometry) class, so a warm resubmit is pure dispatch."""
        from ..infer.driver import InferEngine
        from ..infer.filter import DEFAULT_MAX_HOST_STATES
        from ..struct.cache import model_key

        if max_host_states is None:
            max_host_states = DEFAULT_MAX_HOST_STATES
        key = ("infer", model_key(model), int(budget), int(walkers),
               int(depth), bool(check_deadlock), int(max_host_states))
        return self._get_or_build(
            key,
            lambda: InferEngine(
                model, budget=budget, walkers=walkers, depth=depth,
                check_deadlock=check_deadlock,
                max_host_states=max_host_states,
            ),
            "infer",
            dict(workload=model.root_name, budget=int(budget),
                 walkers=int(walkers), depth=int(depth)),
        )

    # -- prewarm (ISSUE 13 satellite) --------------------------------------

    def prewarm(self, specs, chunk: int = None, queue_capacity: int = None,
                fp_capacity: int = None) -> dict:
        """Compile the listed models into the pool ahead of traffic.

        `specs` is a list of ``CFG`` paths (or ``SPEC:CFG`` pairs - the
        spec half is informational; the loader reads the sibling .tla
        from the cfg's directory anyway).  Geometry defaults to the
        scheduler's pooled-path defaults, so a prewarmed engine and a
        default submit land on the SAME pool key: the first submit of a
        prewarmed spec rides the disk-warm/AOT path (0.77 s class)
        instead of the true-cold path (4.8 s class, PERF.md round 12).
        Errors are counted, never fatal - a bad prewarm entry must not
        stop the server."""
        from ..struct.loader import load
        from .scheduler import DEFAULT_CHUNK, DEFAULT_FPCAP, DEFAULT_QCAP

        chunk = chunk or DEFAULT_CHUNK
        queue_capacity = queue_capacity or DEFAULT_QCAP
        fp_capacity = fp_capacity or DEFAULT_FPCAP
        report = {"ok": [], "errors": []}
        for item in specs:
            cfg = item.split(":", 1)[1] if ":" in item else item
            t0 = time.time()
            try:
                model = load(cfg)
                self.get_single(model, chunk=chunk,
                                queue_capacity=queue_capacity,
                                fp_capacity=fp_capacity)
            except Exception as e:  # noqa: BLE001 - count, don't die
                with self._lock:
                    self.prewarm_errors += 1
                report["errors"].append(f"{cfg}: {e}")
                continue
            wall = time.time() - t0
            with self._lock:
                self.prewarmed += 1
                self.prewarm_wall_s += wall
            report["ok"].append(dict(cfg=cfg, workload=model.root_name,
                                     wall_s=round(wall, 3)))
        return report

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """Pool + memo + kept-engine + compile-meter counters (the
        /pool endpoint)."""
        from ..runtime import engine_cache_stats
        from ..struct import cache as struct_cache

        meter = CompileMeter.instance()
        with self._lock:
            entries = [
                dict(kind=e.kind, uses=e.uses,
                     built_t=round(e.built_t, 3),
                     last_used=round(e.last_used, 3), **e.meta)
                for e in self._entries.values()
            ]
            return dict(
                capacity=self.capacity,
                size=len(self._entries),
                hits=self.hits,
                misses=self.misses,
                evictions=self.evictions,
                compiles=self.compiles,
                compile_wall_s=round(self.compile_wall_s, 6),
                prewarmed=self.prewarmed,
                prewarm_errors=self.prewarm_errors,
                prewarm_wall_s=round(self.prewarm_wall_s, 6),
                xla_compiles=meter.count,
                xla_compile_wall_s=round(meter.wall_s, 6),
                xla_meter="ok" if meter.available else "unavailable",
                sweep_width=self.sweep_width,
                memo=struct_cache.stats(),
                engines=engine_cache_stats(),
                entries=entries,
            )

    def keys(self) -> List[tuple]:
        with self._lock:
            return list(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
