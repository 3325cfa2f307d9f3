"""Benchmark entry point (driver contract).

Runs an exhaustive state-space check on the platform JAX resolves - there is
no fallback: without a chip a mode fails unless CPU was asked for with
JAX_PLATFORMS=cpu, and the `device` field then plainly names a CPU - and
prints ONE machine-parseable JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Baseline: the committed single-host TLC run checked 163,408 distinct states
in 9.875 s => 16,547 distinct states/s
(/root/reference/KubeAPI.toolbox/Model_1/MC.out:1098,1107; BASELINE.md).

Correctness is a gate, not an assumption: the run must reproduce the exact
expected state counts (TLC's for Model_1; oracle-pinned for the scaled
workload) or this script reports failure instead of a throughput number.

The fused engine loop is AOT-compiled before the timed run (compile time is
excluded, matching how TLC's figure excludes JVM/startup costs).

Usage:
    python bench.py            # scaled workload (the workload the 50x
                               # target is defined on; ~19.4M states)
    python bench.py --model1   # Model_1 exhaustive (the TLC-comparable
                               # workload)
    python bench.py --struct   # struct-compiled workload: cold + warm
                               # (persistent compile cache) runs; emits
                               # distinct_states_per_s + struct_warm_start_s
    python bench.py --pipeline-ab  # Model_1 with -pipeline and
                               # -no-pipeline in one invocation: both
                               # rates + a step_overlap_ms metric line,
                               # full-signature bit-equality gated
    python bench.py --obs-ab   # Model_1 with the observability counter
                               # ring on vs off: obs_overhead_pct metric
                               # line, full-signature bit-equality gated
                               # (the <= 2% acceptance gate of ISSUE 5)
    python bench.py --cov-ab   # Model_1 with the device coverage plane
                               # on vs off (obs ring on both sides):
                               # coverage_overhead_pct metric line,
                               # full-signature bit-equality gated
                               # (the <= 0.5% acceptance gate of
                               # ISSUE 11)
    python bench.py --commit-ab  # Model_1 at chunk 2048 with
                               # -sort-free vs -no-sort-free, AOT
                               # compiles shared, timed runs
                               # interleaved best-of-5: sort_ms_saved
                               # metric line + both rates, full
                               # signature AND fpset TABLE words
                               # bit-equality gated (the ISSUE 12
                               # exactness contract)
    python bench.py --expand-ab  # Model_1 at chunk 2048 (sort-free
                               # on both sides) with -deferred-inv vs
                               # -no-deferred-inv, AOT compiles
                               # shared, timed runs interleaved
                               # best-of-5: inv_ms_saved metric line +
                               # both rates, full signature AND fpset
                               # TABLE words bit-equality gated (the
                               # ISSUE 15 exactness contract)
    python bench.py --infer    # inference tier (ISSUE 16): the dense
                               # [P, S] predicates x states filter
                               # kernel over RaftElection evidence
                               # tiled to a fixed state count, AOT
                               # once, best-of-5; emits
                               # predicate_evals_per_s with
                               # vs_baseline = device rate over the
                               # host ev.eval oracle rate
    python bench.py --reduce-ab  # TwoPhase Model_sym (3-element
                               # symmetric RM set) full vs symmetry-
                               # reduced, AOT compiles shared, timed
                               # runs interleaved best-of-5:
                               # distinct_reduction_x metric line
                               # with states_per_s_delta_pct,
                               # identical-verdict gated and orbit-
                               # certificate gated (the ISSUE 18
                               # soundness contract)
    python bench.py --multihost-ab  # localhost jax.distributed pod
                               # scaling (ISSUE 19): 1x8 / 2x4 / 4x2
                               # processes x devices over KubeAPI FF,
                               # exact-count gated per row, plus the
                               # over-capacity leg that completes ONLY
                               # with the per-host spill lifeboat;
                               # emits multihost_scaling_x and writes
                               # MULTICHIP_r06.json
    python bench.py --sim      # simulation tier (ISSUE 14): Model_1
                               # random walks vs the chunk-matched BFS
                               # engine, both AOT once, interleaved
                               # best-of-5; emits walks_per_s
                               # (transitions/s) with vs_baseline =
                               # sim rate over BFS distinct/s
"""

import json
import sys
import time
import traceback

TLC_DISTINCT_PER_S = 163408 / 9.875  # = 16547/s, MC.out:1098,1107
EXPECT = {
    # workload -> (generated, distinct, depth)
    "Model_1": (577736, 163408, 124),  # MC.out:1098,1101
    # validated by independent engine geometries + platforms agreeing
    # exactly (SCALED_VALIDATION.json; tools/validate_scaled.py re-derives)
    "scaled": (62014325, 19359985, 186),
}


def _emit(payload: dict) -> None:
    """The contract: exactly one JSON line on stdout, on EVERY exit path.

    Every payload records the engine pipeline setting (ISSUE 4: the A/B
    harness and history need to know which step schedule produced a
    number); modes that run both put their setting in explicitly.

    Payload assembly is a derived view of the run journal (ISSUE 5):
    obs.views.bench_payload stamps every line through an in-memory
    journal as a schema-validated `bench_metric` event, so the required
    metric/unit/vs_baseline fields are enforced at emit time - a drifted
    payload is a crash here, not a hole in BENCH history."""
    from jaxtlc.obs.views import bench_payload

    print(json.dumps(bench_payload(payload, journal=_JOURNAL)),
          flush=True)


# the bench process's in-memory journal: every emitted payload is also a
# validated bench_metric event (tests read _JOURNAL.events)
from jaxtlc.obs.journal import RunJournal  # noqa: E402

_JOURNAL = RunJournal()


def bench_liveness() -> int:
    """--liveness: benchmark the device-resident liveness subsystem.

    Captures the edge relation on device, runs the tensorized survive-set
    fixpoint for both reference temporal properties, cross-checks the
    verdicts (both are genuinely VIOLATED - a wrong verdict reports
    failure, not a rate), and emits edges-captured/s as the metric line.
    Model_1 on an accelerator; the FF fault-injection corner on CPU
    (Model_1 liveness takes minutes on one CPU core)."""
    import jax

    from jaxtlc.config import MATRIX, MODEL_1
    from jaxtlc.live.check import capture_kube_graph, check_properties_device

    on_cpu = jax.devices()[0].platform == "cpu"
    cfg = MATRIX[(False, False)] if on_cpu else MODEL_1
    workload = "Model_1_FF" if on_cpu else "Model_1"
    sizing = dict(chunk=256 if on_cpu else 1024,
                  state_capacity=1 << 14 if on_cpu else 1 << 18,
                  fp_capacity=1 << 14 if on_cpu else 1 << 18)
    t0 = time.time()
    graph = capture_kube_graph(cfg, **sizing)
    capture_wall = time.time() - t0
    results = check_properties_device(
        cfg, ["ReconcileCompletes", "CleansUpProperly"],
        graph=graph, **sizing,
    )
    wall = time.time() - t0
    if any(r.holds for r in results):
        _emit({"error": "liveness verdict mismatch (both properties are "
                        "violated)", "workload": workload})
        return 1
    rate = len(graph.src) / capture_wall
    _emit(
        {
            "metric": "liveness_edges_per_s",
            "value": round(rate, 1),
            "unit": "edges/s",
            "workload": workload,
            "states": graph.n_states,
            "edges": int(len(graph.src)),
            "wall_s": round(wall, 3),
            "device": str(jax.devices()[0]),
        }
    )
    return 0


def bench_resil() -> int:
    """--resil: measure the perf cost of robustness.

    Runs a supervised checkpointed run (measuring mean checkpoint-write
    seconds) and a deliberately undersized run (measuring regrow-migration
    seconds), gating both on exact expected counts, and emits ONE metric
    line so BENCH_*.json tracks the overhead of the resil tier."""
    import tempfile

    import jax

    from jaxtlc.config import MATRIX, MODEL_1
    from jaxtlc.resil import SupervisorOptions, check_supervised

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        cfg, expect = MATRIX[(False, False)], (17020, 8203, 109)
        kw = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)
        small = dict(chunk=128, queue_capacity=1 << 12,
                     fp_capacity=1 << 11)
        workload = "Model_1_FF"
    else:
        cfg, expect = MODEL_1, EXPECT["Model_1"]
        kw = dict(chunk=1024, queue_capacity=1 << 15, fp_capacity=1 << 20)
        small = dict(chunk=1024, queue_capacity=1 << 15,
                     fp_capacity=1 << 17)
        workload = "Model_1"
    with tempfile.TemporaryDirectory() as d:
        sr = check_supervised(
            cfg, opts=SupervisorOptions(ckpt_path=f"{d}/b.npz",
                                        ckpt_every=32), **kw,
        )
        grown = check_supervised(
            cfg, opts=SupervisorOptions(ckpt_every=32), **small
        )
    for name, run in (("checkpointed", sr), ("regrown", grown)):
        r = run.result
        if r.violation or (r.generated, r.distinct, r.depth) != expect:
            _emit({"error": f"{name} count mismatch: "
                            f"{(r.generated, r.distinct, r.depth)}",
                   "workload": workload})
            return 1
    if grown.regrows == 0:
        _emit({"error": "regrow scenario did not regrow",
               "workload": workload})
        return 1
    ckpt_ms = 1000 * sr.ckpt_write_s / max(sr.ckpt_writes, 1)
    _emit(
        {
            "metric": "ckpt_write_ms",
            "value": round(ckpt_ms, 2),
            "unit": "ms/checkpoint",
            "workload": workload,
            "ckpt_writes": sr.ckpt_writes,
            "ckpt_write_s_total": round(sr.ckpt_write_s, 3),
            "regrow_events": grown.regrows,
            "regrow_migrate_ms": round(1000 * grown.regrow_s, 1),
            "run_wall_s": round(sr.result.wall_s, 3),
            "device": str(jax.devices()[0]),
        }
    )
    return 0


def bench_struct() -> int:
    """--struct: throughput + warm-start wall time of the struct path.

    Runs the struct-compiled workload TWICE in fresh subprocesses
    sharing one persistent compile-cache directory: the first (cold)
    pays the full parse -> lane-compile -> XLA compile pipeline, the
    second (warm) hits the on-disk XLA cache - the honest cross-process
    warm-start figure.  Counts are gated both times; emits a
    `struct_warm_start_s` line and the `distinct_states_per_s` line,
    each naming the device the children ran on."""
    import json as _json
    import os
    import subprocess
    import tempfile

    # the parent stays off jax (a chip belongs to one process, and the
    # children need it), so an asked-for CPU run is read from the
    # environment the children inherit
    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    ref = "/root/reference/KubeAPI.toolbox/Model_1/MC.cfg"
    if os.path.exists(ref) and not on_cpu:
        workload, expect = "Model_1_struct", EXPECT["Model_1"]
        plan = dict(cfg=ref, overrides=None, chunk=1024, qcap=1 << 15,
                    fpcap=1 << 20, nodeadlock=False)
    elif os.path.exists(ref):
        # JAX_PLATFORMS=cpu with the reference mounted: the FF corner
        # (full Model_1 takes ~10 CPU-minutes per run)
        workload, expect = "Model_1_FF_struct", (17020, 8203, 109)
        plan = dict(cfg=ref, chunk=512, qcap=1 << 14, fpcap=1 << 17,
                    nodeadlock=False,
                    overrides={"REQUESTS_CAN_FAIL": False,
                               "REQUESTS_CAN_TIMEOUT": False})
    else:
        # reference not mounted: the bundled struct-frontend family
        workload, expect = "TwoPhase_struct", (114, 56, 8)
        plan = dict(cfg="specs/TwoPhase.toolbox/Model_1/MC.cfg",
                    overrides=None, chunk=64, qcap=1 << 10,
                    fpcap=1 << 12, nodeadlock=True)

    child = (
        "import json, os, time\n"
        "t0 = time.time()\n"
        "import jax\n"
        "from jaxtlc.runtime import enable_compile_cache\n"
        "from jaxtlc.runtime import require_platform\n"
        "enable_compile_cache()\n"
        "require_platform()\n"
        "from jaxtlc.struct.loader import load\n"
        "from jaxtlc.struct.engine import check_struct\n"
        "p = json.loads(os.environ['BENCH_STRUCT'])\n"
        "m = load(p['cfg'], const_overrides=p.get('overrides'))\n"
        "r = check_struct(m, chunk=p['chunk'],\n"
        "                 queue_capacity=p['qcap'],\n"
        "                 fp_capacity=p['fpcap'],\n"
        "                 check_deadlock=not p['nodeadlock'])\n"
        "print(json.dumps({'generated': r.generated,\n"
        "                  'distinct': r.distinct, 'depth': r.depth,\n"
        "                  'violation': r.violation,\n"
        "                  'wall_s': r.wall_s,\n"
        "                  'total_s': time.time() - t0,\n"
        "                  'device': str(jax.devices()[0])}))\n"
    )
    runs = []
    with tempfile.TemporaryDirectory() as cache_dir:
        env = dict(os.environ, BENCH_STRUCT=_json.dumps(plan),
                   JAX_COMPILATION_CACHE_DIR=cache_dir)
        for label in ("cold", "warm"):
            try:
                proc = subprocess.run(
                    [sys.executable, "-c", child], env=env, timeout=1800,
                    capture_output=True, text=True,
                )
            except subprocess.TimeoutExpired:
                _emit({"error": f"{label} struct run timed out",
                       "workload": workload})
                return 1
            if proc.returncode != 0:
                _emit({"error": f"{label} struct run failed: "
                                f"{proc.stderr.strip().splitlines()[-1:]}",
                       "workload": workload})
                return 1
            out = _json.loads(proc.stdout.strip().splitlines()[-1])
            if out["violation"] or (
                out["generated"], out["distinct"], out["depth"]
            ) != expect:
                _emit({"error": f"{label} count mismatch: "
                                f"{(out['generated'], out['distinct'], out['depth'])}"
                                f" != {expect}",
                       "workload": workload})
                return 1
            runs.append(out)
    cold, warm = runs
    device = warm["device"]
    _emit(
        {
            "metric": "struct_warm_start_s",
            "value": round(warm["total_s"], 3),
            "unit": "s",
            "cold_start_s": round(cold["total_s"], 3),
            "warm_over_cold": round(warm["total_s"] / cold["total_s"], 3),
            "workload": workload,
            "device": device,
        }
    )
    rate = warm["distinct"] / warm["wall_s"]
    _emit(
        {
            "value": round(rate, 1),
            "vs_baseline": (round(rate / TLC_DISTINCT_PER_S, 2)
                            if workload == "Model_1_struct" else 0),
            "workload": workload,
            "generated": warm["generated"],
            "distinct": warm["distinct"],
            "depth": warm["depth"],
            "wall_s": round(warm["wall_s"], 3),
            "device": device,
        }
    )
    return 0


def bench_pipeline_ab() -> int:
    """--pipeline-ab: A/B the pipelined step schedule against the fused
    one, in one invocation.

    Runs Model_1 (the TLC-comparable workload) twice through the AOT
    engine - `-no-pipeline` then `-pipeline` at the same chunk, where
    the pipelined run is contractually BIT-FOR-BIT identical (full
    signature gate below, not just counts) - and emits a
    `step_overlap_ms` line (per-level wall saved by overlap; negative
    means the pipeline lost) plus the rate line carrying both rates.
    Best-of-2 walls per mode damp timer noise."""
    import jax

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.bfs import check

    workload = "Model_1"
    kw = dict(chunk=1024, queue_capacity=1 << 15, fp_capacity=1 << 20)
    runs = {}
    for pipelined in (False, True):
        best = None
        for _ in range(2):
            r = check(MODEL_1, pipeline=pipelined, **kw)
            if r.violation or (
                r.generated, r.distinct, r.depth
            ) != EXPECT[workload]:
                _emit({"error": f"pipeline={pipelined} count mismatch: "
                                f"{(r.generated, r.distinct, r.depth)}",
                       "workload": workload, "pipeline": pipelined})
                return 1
            if best is None or r.wall_s < best.wall_s:
                best = r
        runs[pipelined] = best

    def signature(r):
        return (r.generated, r.distinct, r.depth, r.violation,
                tuple(sorted(r.action_generated.items())),
                tuple(sorted(r.action_distinct.items())),
                r.outdegree, r.fp_occupancy)

    if signature(runs[False]) != signature(runs[True]):
        _emit({"error": "pipelined run is not bit-identical to the "
                        "unpipelined engine", "workload": workload})
        return 1

    wall_np, wall_p = runs[False].wall_s, runs[True].wall_s
    depth = runs[False].depth
    overlap_ms = 1000.0 * (wall_np - wall_p) / depth
    device = str(jax.devices()[0])
    _emit(
        {
            "metric": "step_overlap_ms",
            "value": round(overlap_ms, 3),
            "unit": "ms/level-step",
            "workload": workload,
            "wall_s_no_pipeline": round(wall_np, 3),
            "wall_s_pipeline": round(wall_p, 3),
            "levels": depth,
            "pipeline": True,
            "device": device,
        }
    )
    rate_p = runs[True].distinct / wall_p
    rate_np = runs[False].distinct / wall_np
    _emit(
        {
            "value": round(rate_p, 1),
            "vs_baseline": round(rate_p / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "rate_pipeline": round(rate_p, 1),
            "rate_no_pipeline": round(rate_np, 1),
            "generated": runs[True].generated,
            "distinct": runs[True].distinct,
            "depth": runs[True].depth,
            "wall_s": round(wall_p, 3),
            "pipeline": True,
            "device": device,
        }
    )
    return 0


def bench_obs_ab() -> int:
    """--obs-ab: measure the cost of the observability plane.

    Runs the full-signature-gated workload twice through the AOT engine
    - the device counter ring ON (CLI default: 256 slots) and OFF
    (Model_1 on an accelerator; the FF corner on CPU).  The obs-on run
    must be
    BIT-FOR-BIT identical to obs-off (the ring feeds no control flow);
    emits an `obs_overhead_pct` metric line (acceptance: <= 2% on the
    CPU benchmark) plus the standard rate line for the obs-on engine.
    Both engines are AOT-compiled ONCE and the timed runs interleave
    (off/on per repeat, best-of-5): single-digit-percent CPU timer
    drift otherwise dominates the effect being measured.

    ISSUE 8 extension: a second interleaved best-of-5 A/B over the SAME
    compiled segment stepper measures the fence-mode phase-timing tier
    (obs.phases.segment_phases -> fsync'd `phase` journal events) WITH
    a live obs.serve monitor + /events SSE subscriber attached, vs the
    bare stepped loop.  Gate: bit-for-bit finals again;
    `phase_overhead_pct` (acceptance: <= 0.5%) rides the obs payload."""
    import jax

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.bfs import make_engine, result_from_carry

    workload = "Model_1"
    kw = dict(chunk=1024, queue_capacity=1 << 15, fp_capacity=1 << 20)
    compiled = {}
    for slots in (0, 256):
        init_fn, run_fn, _ = make_engine(
            MODEL_1, **kw, obs_slots=slots, donate=False,
        )
        carry0 = init_fn()
        compiled[slots] = (run_fn.lower(carry0).compile(), carry0)

    walls = {0: [], 256: []}
    finals = {}
    for _ in range(5):
        for slots in (0, 256):
            fn, carry0 = compiled[slots]
            t0 = time.time()
            out = jax.block_until_ready(fn(carry0))
            walls[slots].append(time.time() - t0)
            finals[slots] = out

    import numpy as np

    results = {}
    for slots, out in finals.items():
        r = result_from_carry(out, min(walls[slots]),
                              fp_capacity=kw["fp_capacity"])
        if r.violation or (
            r.generated, r.distinct, r.depth
        ) != EXPECT[workload]:
            _emit({"error": f"obs_slots={slots} count mismatch: "
                            f"{(r.generated, r.distinct, r.depth)}",
                   "workload": workload})
            return 1
        results[slots] = r

    def signature(r):
        return (r.generated, r.distinct, r.depth, r.violation,
                tuple(sorted(r.action_generated.items())),
                tuple(sorted(r.action_distinct.items())),
                r.outdegree, r.fp_occupancy)

    # the full signature AND the fingerprint-table words must match:
    # the ring is telemetry, not a participant
    if signature(results[0]) != signature(results[256]) or not (
        np.asarray(finals[0].fps.table)
        == np.asarray(finals[256].fps.table)
    ).all():
        _emit({"error": "obs-on run is not bit-identical to the obs-off "
                        "engine", "workload": workload})
        return 1

    # ---- phase-timing + live-subscriber A/B (ISSUE 8) -----------------
    # Same compiled engine, driven in fixed segments: loop A is the bare
    # stepper, loop B adds exactly what a monitored run adds - fence
    # timestamps, schema-validated fsync'd `phase`/`segment` journal
    # events, a live obs.serve server and an SSE /events subscriber.
    import tempfile
    import threading
    import urllib.request

    from jaxtlc.engine.bfs import carry_done, make_engine as _mk
    from jaxtlc.obs.journal import RunJournal
    from jaxtlc.obs.phases import segment_phases
    from jaxtlc.obs.serve import start_server

    init_fn, _, step_fn = _mk(MODEL_1, **kw, obs_slots=256,
                              donate=False)
    from jax import lax

    @jax.jit
    def seg_fn(c):
        return lax.fori_loop(0, 64, lambda _, cc: step_fn(cc), c)

    carry0 = init_fn()
    seg_c = seg_fn.lower(carry0).compile()

    tmpdir = tempfile.mkdtemp(prefix="obs-ab-")
    jpath = f"{tmpdir}/ab.journal.jsonl"
    journal = RunJournal(jpath)
    journal.event("run_start", version="bench", workload=workload,
                  engine="single", device=str(jax.devices()[0]),
                  params=dict(kw))
    server = start_server(tmpdir)
    sse_seen = [0]

    def subscribe():
        try:
            with urllib.request.urlopen(server.url + "/events",
                                        timeout=60) as r:
                while True:
                    line = r.readline()
                    if not line:
                        return
                    if line.startswith(b"data: "):
                        sse_seen[0] += 1
        except OSError:
            pass

    sub = threading.Thread(target=subscribe, daemon=True)
    sub.start()

    def run_plain():
        c = carry0
        t0 = time.time()
        while True:
            c = jax.block_until_ready(seg_c(c))
            if carry_done(c):
                break
        return time.time() - t0, c

    def run_phased():
        c = carry0
        seg_i = 0
        t0 = time.time()
        while True:
            t_d = time.time()
            c = jax.block_until_ready(seg_c(c))
            t_f = time.time()
            journal.event("segment", index=seg_i, t_dispatch=t_d,
                          t_fence=t_f, wall_s=round(t_f - t_d, 6))
            for row in segment_phases(seg_i, t_f - t_d):
                journal.event("phase", **row)
            seg_i += 1
            if carry_done(c):
                break
        return time.time() - t0, c

    ab_walls = {"plain": [], "phased": []}
    ab_finals = {}
    for _ in range(5):
        for name, fn in (("plain", run_plain), ("phased", run_phased)):
            w, out = fn()
            ab_walls[name].append(w)
            ab_finals[name] = out
    time.sleep(0.5)  # let the subscriber drain the tail
    server.shutdown()
    journal.close()

    ok_phase = signature(
        result_from_carry(ab_finals["plain"], 0.0,
                          fp_capacity=kw["fp_capacity"])
    ) == signature(
        result_from_carry(ab_finals["phased"], 0.0,
                          fp_capacity=kw["fp_capacity"])
    ) and (
        np.asarray(ab_finals["plain"].fps.table)
        == np.asarray(ab_finals["phased"].fps.table)
    ).all()
    if not ok_phase:
        _emit({"error": "phase-timed run is not bit-identical to the "
                        "bare stepped engine", "workload": workload})
        return 1
    phase_overhead_pct = 100.0 * (
        min(ab_walls["phased"]) - min(ab_walls["plain"])
    ) / min(ab_walls["plain"])

    wall_off, wall_on = min(walls[0]), min(walls[256])
    overhead_pct = 100.0 * (wall_on - wall_off) / wall_off
    device = str(jax.devices()[0])
    _emit(
        {
            "metric": "obs_overhead_pct",
            "value": round(overhead_pct, 3),
            "unit": "%",
            "workload": workload,
            "obs_slots": 256,
            "wall_s_obs": round(wall_on, 3),
            "wall_s_no_obs": round(wall_off, 3),
            "rate_obs": round(results[256].distinct / wall_on, 1),
            "rate_no_obs": round(results[0].distinct / wall_off, 1),
            "phase_overhead_pct": round(phase_overhead_pct, 3),
            "wall_s_phase": round(min(ab_walls["phased"]), 3),
            "wall_s_no_phase": round(min(ab_walls["plain"]), 3),
            "sse_events_seen": sse_seen[0],
            "repeats": 5,
            "device": device,
        }
    )
    rate = results[256].distinct / wall_on
    _emit(
        {
            "value": round(rate, 1),
            "vs_baseline": round(rate / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "generated": results[256].generated,
            "distinct": results[256].distinct,
            "depth": results[256].depth,
            "wall_s": round(wall_on, 3),
            "obs_slots": 256,
            "device": device,
        }
    )
    return 0


def bench_commit_ab() -> int:
    """--commit-ab: A/B the sort-free hash-slab commit against the
    sorted dedup path (the ISSUE 12 acceptance harness).

    Runs Model_1 at chunk 2048 (the regime where the fitted cost model
    puts the two dedup sorts at 89% of commit, COSTMODEL.json) through
    BOTH engines - `-no-sort-free` and `-sort-free` - AOT-compiled once
    each, with the timed runs INTERLEAVED (sorted/slab per repeat,
    best-of-5): sequential best-of-2 on this CPU shows +-3% phantom
    effects (PERF.md round 8 methodology note).  Gate: the sort-free
    run must be BIT-FOR-BIT the sorted run - full signature AND the
    final fpset TABLE words - or the harness reports failure instead of
    a number.  Emits a `sort_ms_saved` line (per-step dedup-stage wall
    saved, from the differential sub-phase profiler at the same chunk)
    and the rate line carrying both rates.  A CPU wall delta is
    REPORT-ONLY: the acceptance rate gate ("no worse than sorted") is
    an on-chip gate, not measured on the chip yet; the committed
    COSTMODEL.json carries the CPU sort-ms reduction."""
    import jax
    import numpy as np

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.bfs import make_engine, result_from_carry
    from jaxtlc.obs.phases import subphase_walls

    workload = "Model_1"
    kw = dict(chunk=2048, queue_capacity=1 << 15, fp_capacity=1 << 20)
    compiled = {}
    for sf in (False, True):
        init_fn, run_fn, _ = make_engine(
            MODEL_1, **kw, donate=False, sort_free=sf,
        )
        carry0 = init_fn()
        compiled[sf] = (run_fn.lower(carry0).compile(), carry0)

    walls = {False: [], True: []}
    finals = {}
    for _ in range(5):
        for sf in (False, True):
            fn, carry0 = compiled[sf]
            t0 = time.time()
            out = jax.block_until_ready(fn(carry0))
            walls[sf].append(time.time() - t0)
            finals[sf] = out

    results = {}
    for sf, out in finals.items():
        r = result_from_carry(out, min(walls[sf]),
                              fp_capacity=kw["fp_capacity"])
        if r.violation or (
            r.generated, r.distinct, r.depth
        ) != EXPECT[workload]:
            _emit({"error": f"sort_free={sf} count mismatch: "
                            f"{(r.generated, r.distinct, r.depth)}",
                   "workload": workload, "sort_free": sf})
            return 1
        results[sf] = r

    def signature(r):
        return (r.generated, r.distinct, r.depth, r.violation,
                tuple(sorted(r.action_generated.items())),
                tuple(sorted(r.action_distinct.items())),
                r.outdegree, r.fp_occupancy)

    # exactness is the contract, not a sampling property: the full
    # signature AND the fingerprint-table words must match
    if signature(results[False]) != signature(results[True]) or not (
        np.asarray(finals[False].fps.table)
        == np.asarray(finals[True].fps.table)
    ).all():
        _emit({"error": "sort-free run is not bit-identical to the "
                        "sorted engine", "workload": workload,
               "sort_free": True})
        return 1

    # dedup-stage attribution at the same chunk: the differential
    # sub-phase profiler's "sort" column in both modes
    backend = kubeapi_backend(MODEL_1)
    sort_ms = {}
    for sf in (False, True):
        w = subphase_walls(backend, kw["chunk"], kw["queue_capacity"],
                           kw["fp_capacity"], sort_free=sf)
        sort_ms[sf] = 1e3 * w["sort"]

    wall_sorted, wall_free = min(walls[False]), min(walls[True])
    rate_free = results[True].distinct / wall_free
    rate_sorted = results[False].distinct / wall_sorted
    device = str(jax.devices()[0])
    _emit(
        {
            "metric": "sort_ms_saved",
            "value": round(sort_ms[False] - sort_ms[True], 3),
            "unit": "ms/step",
            "workload": workload,
            "chunk": kw["chunk"],
            "sort_ms_sorted": round(sort_ms[False], 3),
            "sort_ms_sort_free": round(sort_ms[True], 3),
            "wall_s_sorted": round(wall_sorted, 3),
            "wall_s_sort_free": round(wall_free, 3),
            "states_per_s_delta_pct": round(
                100.0 * (rate_free - rate_sorted) / rate_sorted, 3
            ),
            "repeats": 5,
            "sort_free": True,
            "device": device,
        }
    )
    _emit(
        {
            "value": round(rate_free, 1),
            "vs_baseline": round(rate_free / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "rate_sort_free": round(rate_free, 1),
            "rate_sorted": round(rate_sorted, 1),
            "generated": results[True].generated,
            "distinct": results[True].distinct,
            "depth": results[True].depth,
            "wall_s": round(wall_free, 3),
            "sort_free": True,
            "device": device,
        }
    )
    return 0


def bench_expand_ab() -> int:
    """--expand-ab: A/B the distinct-first deferred invariant/cert
    evaluation against the immediate per-candidate expand (the ISSUE
    15 acceptance harness).

    Runs Model_1 at chunk 2048 (the regime where the fitted cost model
    puts the invariant sweep at the top of the step - COSTMODEL.json
    v3 splits the old inv_fp wall to show it) through BOTH engines -
    `-no-deferred-inv` and `-deferred-inv`, sort-free commit on both
    sides (the chunk-2048 auto default) - AOT-compiled once each, with
    the timed runs INTERLEAVED (immediate/deferred per repeat,
    best-of-5): sequential best-of-2 on this CPU shows +-3% phantom
    effects (PERF.md round 8 methodology note).  Gate: the deferred
    run must be BIT-FOR-BIT the immediate run - verdict, full
    signature AND the final fpset TABLE words - or the harness reports
    failure instead of a number.  Emits an `inv_ms_saved` line (the
    per-step invariant-evaluation wall saved, from the v3 differential
    sub-phase profiler at the same chunk) and the rate line carrying
    both rates plus `states_per_s_delta_pct`.  Not measured on the
    chip yet: a CPU wall is report-only; the committed COSTMODEL.json
    v3 carries the CPU inv-ms reduction."""
    import jax
    import numpy as np

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.bfs import make_engine, result_from_carry
    from jaxtlc.obs.phases import subphase_walls

    workload = "Model_1"
    kw = dict(chunk=2048, queue_capacity=1 << 15, fp_capacity=1 << 20)
    compiled = {}
    for df in (False, True):
        init_fn, run_fn, _ = make_engine(
            MODEL_1, **kw, donate=False, sort_free=True, deferred=df,
        )
        carry0 = init_fn()
        compiled[df] = (run_fn.lower(carry0).compile(), carry0)

    walls = {False: [], True: []}
    finals = {}
    for _ in range(5):
        for df in (False, True):
            fn, carry0 = compiled[df]
            t0 = time.time()
            out = jax.block_until_ready(fn(carry0))
            walls[df].append(time.time() - t0)
            finals[df] = out

    results = {}
    for df, out in finals.items():
        r = result_from_carry(out, min(walls[df]),
                              fp_capacity=kw["fp_capacity"])
        if r.violation or (
            r.generated, r.distinct, r.depth
        ) != EXPECT[workload]:
            _emit({"error": f"deferred={df} count mismatch: "
                            f"{(r.generated, r.distinct, r.depth)}",
                   "workload": workload, "deferred": df})
            return 1
        results[df] = r

    def signature(r):
        return (r.generated, r.distinct, r.depth, r.violation,
                tuple(sorted(r.action_generated.items())),
                tuple(sorted(r.action_distinct.items())),
                r.outdegree, r.fp_occupancy)

    # exactness is the contract: verdict + full signature + TABLE words
    if signature(results[False]) != signature(results[True]) or not (
        np.asarray(finals[False].fps.table)
        == np.asarray(finals[True].fps.table)
    ).all():
        _emit({"error": "deferred run is not bit-identical to the "
                        "immediate engine", "workload": workload,
               "deferred": True})
        return 1

    # invariant-evaluation attribution at the same chunk: the v3
    # differential sub-phase profiler's "inv" column in both modes
    backend = kubeapi_backend(MODEL_1)
    inv_ms = {}
    for df in (False, True):
        w = subphase_walls(backend, kw["chunk"], kw["queue_capacity"],
                           kw["fp_capacity"], sort_free=True,
                           deferred=df)
        inv_ms[df] = 1e3 * w["inv"]

    wall_imm, wall_def = min(walls[False]), min(walls[True])
    rate_def = results[True].distinct / wall_def
    rate_imm = results[False].distinct / wall_imm
    device = str(jax.devices()[0])
    _emit(
        {
            "metric": "inv_ms_saved",
            "value": round(inv_ms[False] - inv_ms[True], 3),
            "unit": "ms/step",
            "workload": workload,
            "chunk": kw["chunk"],
            "inv_ms_immediate": round(inv_ms[False], 3),
            "inv_ms_deferred": round(inv_ms[True], 3),
            "wall_s_immediate": round(wall_imm, 3),
            "wall_s_deferred": round(wall_def, 3),
            "states_per_s_delta_pct": round(
                100.0 * (rate_def - rate_imm) / rate_imm, 3
            ),
            "repeats": 5,
            "sort_free": True,
            "deferred": True,
            "device": device,
        }
    )
    _emit(
        {
            "value": round(rate_def, 1),
            "vs_baseline": round(rate_def / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "rate_deferred": round(rate_def, 1),
            "rate_immediate": round(rate_imm, 1),
            "generated": results[True].generated,
            "distinct": results[True].distinct,
            "depth": results[True].depth,
            "wall_s": round(wall_def, 3),
            "sort_free": True,
            "deferred": True,
            "device": device,
        }
    )
    return 0


def bench_reduce_ab() -> int:
    """--reduce-ab: A/B the device-resident symmetry reduction against
    the full state space (the ISSUE 18 acceptance harness).

    Runs the bundled TwoPhase Model_sym (RM = {r1, r2, r3}, a
    3-element SYMMETRY-eligible set - 6 orbit permutations) through
    BOTH struct engines - the full space and the orbit-canonicalizing
    reduced one - AOT-compiled once each, timed runs INTERLEAVED
    best-of-5 (round-8 methodology).  Gate: identical verdict AND
    identical depth on both sides, a >= 2x distinct reduction (the
    acceptance floor), and the reduced run's sticky orbit certificate
    clean - a tripped COL_SYM means the canonicalization lied and the
    harness reports failure instead of a number.  Emits a
    `distinct_reduction_x` line carrying both distinct counts, both
    best walls and `states_per_s_delta_pct` (generated-states
    throughput delta; the reduced engine pays the canon kernel per
    candidate and earns it back in states it never expands).  Not
    measured on the chip yet: a CPU wall is report-only."""
    import jax

    from jaxtlc.engine.bfs import make_backend_engine, result_from_carry
    from jaxtlc.struct.cache import get_backend
    from jaxtlc.struct.loader import load

    workload = "TwoPhase_sym"
    model = load("specs/TwoPhase.toolbox/Model_sym/MC.cfg")
    kw = dict(chunk=256, queue_capacity=1 << 12, fp_capacity=1 << 14)
    compiled = {}
    orbit_factor = 1
    for sym in (False, True):
        # TwoPhase terminates: deadlock-as-violation off on both sides
        b = get_backend(model, False, symmetry=sym)
        if sym:
            orbit_factor = int(b.reduce.orbit_factor)
        init_fn, run_fn, _ = make_backend_engine(
            b, **kw, donate=False, obs_slots=8,
        )
        carry0 = init_fn()
        compiled[sym] = (run_fn.lower(carry0).compile(), carry0)

    walls = {False: [], True: []}
    finals = {}
    for _ in range(5):
        for sym in (False, True):
            fn, carry0 = compiled[sym]
            t0 = time.time()
            out = jax.block_until_ready(fn(carry0))
            walls[sym].append(time.time() - t0)
            finals[sym] = out

    results = {
        sym: result_from_carry(out, min(walls[sym]),
                               fp_capacity=kw["fp_capacity"])
        for sym, out in finals.items()
    }
    full, red = results[False], results[True]
    # soundness gates: same verdict + depth, certificate clean, and
    # the acceptance floor on the reduction itself
    if (red.violation, red.depth) != (full.violation, full.depth):
        _emit({"error": "reduced verdict/depth diverged: "
                        f"{(red.violation, red.depth)} != "
                        f"{(full.violation, full.depth)}",
               "workload": workload, "symmetry": True})
        return 1
    if getattr(red, "sym_violated", False):
        _emit({"error": "orbit certificate tripped: the symmetry "
                        "canonicalization is not constant on a "
                        "reachable orbit", "workload": workload,
               "symmetry": True})
        return 1
    if red.distinct * 2 > full.distinct:
        _emit({"error": f"reduction below the 2x floor: "
                        f"{full.distinct} -> {red.distinct}",
               "workload": workload, "symmetry": True})
        return 1

    wall_full, wall_red = min(walls[False]), min(walls[True])
    rate_full = full.generated / wall_full
    rate_red = red.generated / wall_red
    device = str(jax.devices()[0])
    _emit(
        {
            "metric": "distinct_reduction_x",
            "value": round(full.distinct / red.distinct, 3),
            "unit": "x",
            "workload": workload,
            "distinct_full": full.distinct,
            "distinct_reduced": red.distinct,
            "generated_full": full.generated,
            "generated_reduced": red.generated,
            "depth": red.depth,
            "orbit_factor": orbit_factor,
            "wall_s_full": round(wall_full, 3),
            "wall_s_reduced": round(wall_red, 3),
            "states_per_s_delta_pct": round(
                100.0 * (rate_red - rate_full) / rate_full, 3
            ),
            "repeats": 5,
            "symmetry": True,
            "por": False,
            "device": device,
        }
    )
    return 0


def bench_cov_ab() -> int:
    """--cov-ab: measure the cost of the device coverage plane.

    The ISSUE 11 acceptance A/B, run with the round-8/11 methodology:
    both engines (the 311-site KubeAPI coverage plane ON vs OFF, obs
    ring 256 on both sides so only the coverage tensor differs) are
    AOT-compiled once and the timed runs INTERLEAVE best-of-5.  The
    coverage-on run must be bit-for-bit the coverage-off run (full
    signature + fpset TABLE word equality - the plane is telemetry,
    not a participant), its tracked per-action sites must equal the
    engine's own generated counters, and the emitted
    `coverage_overhead_pct` gates at <= 0.5%."""
    import jax
    import numpy as np

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.bfs import make_backend_engine, result_from_carry

    workload = "Model_1"
    kw = dict(chunk=1024, queue_capacity=1 << 15, fp_capacity=1 << 20)
    compiled = {}
    planes = {}
    for cov in (False, True):
        backend = kubeapi_backend(MODEL_1, coverage=cov)
        planes[cov] = backend.coverage
        init_fn, run_fn, _ = make_backend_engine(
            backend, **kw, obs_slots=256, donate=False,
        )
        carry0 = init_fn()
        compiled[cov] = (run_fn.lower(carry0).compile(), carry0)

    walls = {False: [], True: []}
    finals = {}
    for _ in range(5):
        for cov in (False, True):
            fn, carry0 = compiled[cov]
            t0 = time.time()
            out = jax.block_until_ready(fn(carry0))
            walls[cov].append(time.time() - t0)
            finals[cov] = out

    results = {}
    for cov, out in finals.items():
        r = result_from_carry(
            out, min(walls[cov]), fp_capacity=kw["fp_capacity"],
            sites=planes[cov].sites if planes[cov] else None,
        )
        if r.violation or (
            r.generated, r.distinct, r.depth
        ) != EXPECT[workload]:
            _emit({"error": f"coverage={cov} count mismatch: "
                            f"{(r.generated, r.distinct, r.depth)}",
                   "workload": workload})
            return 1
        results[cov] = r

    def signature(r):
        return (r.generated, r.distinct, r.depth, r.violation,
                tuple(sorted(r.action_generated.items())),
                tuple(sorted(r.action_distinct.items())),
                r.outdegree, r.fp_occupancy)

    if signature(results[False]) != signature(results[True]) or not (
        np.asarray(finals[False].fps.table)
        == np.asarray(finals[True].fps.table)
    ).all():
        _emit({"error": "coverage-on run is not bit-identical to the "
                        "coverage-off engine", "workload": workload})
        return 1
    # the action-prefix sites are the engine's own generated counters
    cov_tab = results[True].site_coverage
    for name, g in results[True].action_generated.items():
        if cov_tab.get(name, 0) != g:
            _emit({"error": f"coverage action site {name} "
                            f"{cov_tab.get(name, 0)} != generated {g}",
                   "workload": workload})
            return 1

    wall_off, wall_on = min(walls[False]), min(walls[True])
    overhead_pct = round((wall_on - wall_off) / wall_off * 100, 3)
    device = str(jax.devices()[0])
    on_cpu = jax.devices()[0].platform == "cpu"
    rate = results[True].distinct / wall_on
    visited = sum(1 for v in cov_tab.values() if v)
    # the 0.5% wall gate is an ON-CHIP acceptance: XLA's CPU backend
    # pays per-op dispatch for the ~1.4k-op site hook (~1 ms/block
    # against a ~3.5 ms CPU step - PERF.md round 14), a floor that
    # fusion should remove on the TPU (not measured on the chip).  On
    # CPU the number is reported and only the bit-equality gates are
    # fatal; on-chip the wall gate enforces.
    gate_ok = bool(overhead_pct <= 0.5)
    _emit(
        {
            "metric": "coverage_overhead_pct",
            "value": overhead_pct,
            "unit": "%",
            "vs_baseline": 0,
            "workload": workload,
            "wall_coverage_off_s": round(wall_off, 3),
            "wall_coverage_on_s": round(wall_on, 3),
            "sites": len(cov_tab),
            "sites_visited": visited,
            "gate": "<=0.5% on-chip (on CPU: report-only, "
                    "per-op dispatch floor - PERF round 14)",
            "gate_ok": gate_ok,
            "device": device,
        }
    )
    _emit(
        {
            "metric": "distinct_states_per_s",
            "value": round(rate),
            "unit": "states/s",
            "vs_baseline": round(rate / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "generated": results[True].generated,
            "distinct": results[True].distinct,
            "depth": results[True].depth,
            "wall_s": round(wall_on, 3),
            "coverage": True,
            "device": device,
        }
    )
    return 0 if (gate_ok or on_cpu) else 1


def bench_sim() -> int:
    """--sim: the simulation tier's throughput (ISSUE 14).

    Walks Model_1 with the random-walk engine and runs the chunk-
    matched exhaustive BFS engine beside it, both AOT-compiled once,
    timed runs INTERLEAVED best-of-5 (the round-8 methodology): the
    emitted `walks_per_s` line carries transitions/s (the
    states-visited rate comparable to states/s) with vs_baseline =
    sim transitions/s over BFS distinct states/s.  The two tiers
    answer different questions - BFS proves, simulation samples - so
    this is a price sheet, not a race."""
    import jax

    import numpy as np

    from jaxtlc.config import MODEL_1
    from jaxtlc.engine.backend import kubeapi_backend
    from jaxtlc.engine.bfs import make_backend_engine
    from jaxtlc.sim.engine import make_sim_engine, result_from_sim_carry

    on_cpu = jax.devices()[0].platform == "cpu"
    walkers, depth = (512, 128) if on_cpu else (4096, 256)
    backend = kubeapi_backend(MODEL_1)
    s_init, s_run, _ = make_sim_engine(
        backend, walkers=walkers, depth=depth, fp_capacity=1 << 20,
    )
    b_init, b_run, _ = make_backend_engine(
        backend, chunk=1024, queue_capacity=1 << 15,
        fp_capacity=1 << 20, donate=False,
    )
    sim_c0 = jax.jit(s_init)(0)
    sim_aot = s_run.lower(sim_c0).compile()
    bfs_c0 = b_init()
    bfs_aot = b_run.lower(bfs_c0).compile()

    sim_walls, bfs_walls = [], []
    sim_out = bfs_out = None
    for _ in range(5):  # interleaved best-of-5, shared AOT (round 8)
        t0 = time.time()
        sim_out = jax.block_until_ready(sim_aot(jax.jit(s_init)(0)))
        sim_walls.append(time.time() - t0)
        t0 = time.time()
        bfs_out = jax.block_until_ready(bfs_aot(b_init()))
        bfs_walls.append(time.time() - t0)
    sim_wall, bfs_wall = min(sim_walls), min(bfs_walls)
    r = result_from_sim_carry(sim_out, sim_wall, backend, walkers,
                              depth, 0)
    if r.violation or int(bfs_out.viol):
        _emit({"error": f"unexpected violation (sim {r.violation}, "
                        f"bfs {int(bfs_out.viol)})", "sim": True})
        return 1
    bfs_distinct_per_s = int(bfs_out.distinct) / bfs_wall
    trans_per_s = r.transitions / sim_wall
    _emit({
        "metric": "walks_per_s",
        "value": round(trans_per_s, 1),
        "unit": "transitions/s",
        "vs_baseline": round(trans_per_s / bfs_distinct_per_s, 3),
        "sim": True,
        "workload": "Model_1",
        "walkers": walkers,
        "depth": depth,
        "walks_completed_per_s": round(walkers / sim_wall, 1),
        "transitions": r.transitions,
        "distinct_sampled": r.distinct,
        "sim_wall_s": round(sim_wall, 3),
        "bfs_distinct_per_s": round(bfs_distinct_per_s, 1),
        "bfs_wall_s": round(bfs_wall, 3),
        "device": str(jax.devices()[0]),
    })
    return 0


def bench_infer() -> int:
    """--infer: the inference tier's filter throughput (ISSUE 16).

    Builds the RaftElection inference engine once (candidate pool +
    [P, S] filter kernel AOT-compiled against the fixed block shape),
    tiles the exact reachable evidence to a fixed state count, and
    times the dense predicates x states filter best-of-5: the emitted
    `predicate_evals_per_s` line carries P*S/wall with vs_baseline =
    device rate over the host `ev.eval` oracle rate (measured on a
    sample - the same per-eval work, minus vmap).  One full inference
    run beside it reports the funnel (candidates -> survivors ->
    certified) and the certify wall so the end-to-end price is on the
    line too."""
    import os

    import jax

    import numpy as np

    from jaxtlc.infer.driver import InferEngine
    from jaxtlc.infer.filter import filter_matrix, host_filter
    from jaxtlc.struct.loader import load

    specs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "specs")
    model = load(os.path.join(specs, "RaftElection.toolbox", "Model_1",
                              "MC.cfg"))
    eng = InferEngine(model, budget=64)
    if eng.exact_fields is None:
        _emit({"error": "RaftElection evidence is not exact (expected "
                        "artifact or host-BFS reachable set)",
               "infer": True})
        return 1
    rep = eng.run(seed=0)
    P = len(eng.candidates)

    # tile the evidence up so the timed region is kernel-bound, not
    # pad-bound (the reachable set is small; the kernel does not care
    # whether rows repeat)
    reps = max(1, 200_000 // eng.exact_fields.shape[0])
    fields = np.tile(eng.exact_fields, (reps, 1))
    S = fields.shape[0]
    filter_matrix(eng.filter_fn, fields)  # warm the dispatch path
    walls = []
    for _ in range(5):
        t0 = time.time()
        filter_matrix(eng.filter_fn, fields)
        walls.append(time.time() - t0)
    wall = min(walls)
    evals_per_s = (P * S) / wall

    # host oracle rate on a sample: the same P predicates through
    # ev.eval, the reference the device matrix is pinned against
    sample = [eng.backend.cdc.decode(v)
              for v in eng.exact_fields[:256]]
    t0 = time.time()
    host_filter(model.system, eng.candidates, sample)
    host_wall = time.time() - t0
    host_evals_per_s = (P * len(sample)) / host_wall

    _emit({
        "metric": "predicate_evals_per_s",
        "value": round(evals_per_s, 1),
        "unit": "predicate-evals/s",
        "vs_baseline": round(evals_per_s / host_evals_per_s, 1),
        "infer": True,
        "workload": "RaftElection",
        "predicates": P,
        "states": S,
        "filter_wall_s": round(wall, 4),
        "host_evals_per_s": round(host_evals_per_s, 1),
        "evidence": rep.evidence,
        "evidence_states": rep.n_states,
        "survivors": len(rep.survivors),
        "certified": len(rep.certified),
        "certify_wall_s": round(rep.certify_wall_s, 4),
        "device": str(jax.devices()[0]),
    })
    return 0


def bench_multihost_ab() -> int:
    """--multihost-ab: localhost jax.distributed pod scaling A/B.

    Spawns N coordinator+worker pods on loopback (python -m jaxtlc.dist
    --spawn N, gloo collectives) over the KubeAPI FF workload at a
    CONSTANT total device count - 1x8, 2x4, 4x2 processes x devices -
    so the delta between rows is pure multi-process overhead (the
    level-fence all_to_all crossing process boundaries).  Every row is
    gated on the exact oracle counts; peak per-host shard occupancy is
    read back from the per-host journals (obs.views.pod_host_gauges).

    Then the over-capacity demonstration: a pod whose per-host tables
    are too small for the state space (4 x 1024 slots < 8,203 distinct)
    must FAIL without the spill lifeboat and complete EXACTLY with
    --spill on - capacity beyond one host's memory is the point of the
    pod + spill combination, and this leg commits the evidence.

    Emits a `multihost_scaling_x` metric line and writes the full
    table to MULTICHIP_r06.json at the repo root."""
    import json as _json
    import os
    import subprocess
    import tempfile

    expect = (17020, 8203, 109)  # KubeAPI FF oracle (BASELINE.md)
    art_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MULTICHIP_r06.json")
    art = {"mode": "multihost_ab", "workload": "kubeapi_ff",
           "expect": list(expect), "table": [], "overcap": {},
           "ok": False}

    def _commit_art() -> None:
        with open(art_path, "w") as f:
            _json.dump(art, f, indent=2)
            f.write("\n")

    def _pod(procs: int, dph: int, fpcap: int, spill: bool,
             ckpt: str, timeout_s: int) -> dict:
        """One localhost pod run -> parsed POD_RESULT (+ peak per-host
        shard occupancy from the journals) or an error dict."""
        cmd = [sys.executable, "-m", "jaxtlc.dist",
               "--spawn", str(procs), "--devices-per-host", str(dph),
               "--ff", "--chunk", "128", "--queue-capacity", "4096",
               "--fp-capacity", str(fpcap), "--ckpt", ckpt]
        if spill:
            cmd += ["--spill", "on", "--spill-capacity", str(1 << 15)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # workers size their own virtual-device mesh from
        # --devices-per-host; an inherited count would override it
        env.pop("XLA_FLAGS", None)
        try:
            proc = subprocess.run(cmd, env=env, timeout=timeout_s,
                                  capture_output=True, text=True,
                                  cwd=os.path.dirname(art_path))
        except subprocess.TimeoutExpired:
            return {"error": f"pod timed out > {timeout_s}s"}
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("POD_RESULT ")), None)
        if proc.returncode != 0 or line is None:
            tail = (proc.stdout + proc.stderr).strip().splitlines()[-3:]
            return {"error": f"rc={proc.returncode} {tail}"}
        out = _json.loads(line[len("POD_RESULT "):])
        peak = 0.0
        for h in range(procs):
            jp = f"{ckpt}.h{h}.journal.jsonl"
            if os.path.exists(jp):
                from jaxtlc.obs import journal as _jr
                from jaxtlc.obs.views import pod_host_gauges

                g = pod_host_gauges(_jr.read(jp, validate=False))
                if g:
                    peak = max(peak, *(
                        v["shard_occupancy"] for v in g.values()))
        out["peak_shard_occupancy"] = round(peak, 4)
        return out

    rows = []
    with tempfile.TemporaryDirectory() as d:
        for procs, dph in ((1, 8), (2, 4), (4, 2)):
            r = _pod(procs, dph, fpcap=16384, spill=False,
                     ckpt=os.path.join(d, f"ab{procs}.ckpt"),
                     timeout_s=600)
            row = {"procs": procs, "devices_per_host": dph, **{
                k: r.get(k) for k in
                ("generated", "distinct", "depth", "wall_s",
                 "peak_shard_occupancy", "error")
                if k in r or k != "error"}}
            counts = (r.get("generated"), r.get("distinct"),
                      r.get("depth"))
            row["ok"] = "error" not in r and counts == expect \
                and r.get("rc") == 0
            if row["ok"]:
                row["states_per_s"] = round(r["distinct"] / r["wall_s"],
                                            1)
            rows.append(row)
            art["table"] = rows
            _commit_art()
            if not row["ok"]:
                _emit({"error": f"{procs}-process pod failed: "
                                f"{r.get('error', counts)}",
                       "workload": "kubeapi_ff_pod"})
                return 1

        # over-capacity: 4 x 1024 table slots < 8,203 distinct states.
        # Without spill the pod MUST fail (table overflow is detected,
        # not silently wrong); with the per-host spill lifeboat it must
        # complete bit-exactly.
        nosp = _pod(2, 2, fpcap=1024, spill=False,
                    ckpt=os.path.join(d, "oc_nospill.ckpt"),
                    timeout_s=300)
        nosp_completed = ("error" not in nosp and nosp.get("rc") == 0
                          and (nosp.get("generated"),
                               nosp.get("distinct"),
                               nosp.get("depth")) == expect)
        sp = _pod(2, 2, fpcap=1024, spill=True,
                  ckpt=os.path.join(d, "oc_spill.ckpt"), timeout_s=600)
        sp_ok = ("error" not in sp and sp.get("rc") == 0
                 and (sp.get("generated"), sp.get("distinct"),
                      sp.get("depth")) == expect)
        art["overcap"] = {
            "fp_capacity_total": 4 * 1024,
            "no_spill": {"completed": nosp_completed,
                         "detail": nosp.get("error",
                                            f"rc={nosp.get('rc')}")},
            "spill": {k: sp.get(k) for k in
                      ("generated", "distinct", "depth", "wall_s",
                       "spilled", "spill_flushes")} | {"ok": sp_ok},
        }
        _commit_art()
        if nosp_completed:
            _emit({"error": "over-capacity pod completed WITHOUT "
                            "spill - the table-overflow gate is gone",
                   "workload": "kubeapi_ff_pod"})
            return 1
        if not sp_ok:
            _emit({"error": f"over-capacity spill pod failed: "
                            f"{sp.get('error', sp)}",
                   "workload": "kubeapi_ff_pod"})
            return 1

    r1, r2, r4 = (row["states_per_s"] for row in rows)
    art["ok"] = True
    _commit_art()
    _emit({
        "metric": "multihost_scaling_x",
        "value": round(r4 / r1, 3),
        "unit": "x",
        "vs_baseline": round(r4 / r1, 3),
        "workload": "kubeapi_ff_pod",
        "states_per_s_1x8": r1,
        "states_per_s_2x4": r2,
        "states_per_s_4x2": r4,
        "overcap_spilled": art["overcap"]["spill"]["spilled"],
        "artifact": "MULTICHIP_r06.json",
        "device": "cpu pod (gloo loopback)",
    })
    return 0


def bench_pod_obs_ab() -> int:
    """--pod-obs-ab: the obs plane must be free ON A POD, bit-for-bit.

    Runs the same 2-process x 2-device loopback pod (gloo collectives,
    KubeAPI FF workload) twice - obs OFF vs obs ON (counter ring 256 +
    the workload CoveragePlane, per-host journals) - and gates the ON
    run bit-for-bit against OFF: the full result signature (counts,
    per-action counters, outdegree, occupancy from POD_RESULT) AND the
    fpset TABLE words of every host's final shard checkpoint - the
    PR 5/11 telemetry-not-a-participant gate, now across process
    boundaries.  The merged {base}.hN sibling journals must also fold
    back to the engine's own totals: the last pod-global level row
    carries the exact generated/distinct counts and the summed site
    table reproduces every action's generated counter site-for-site.
    Emits `pod_obs_overhead_pct`; like --cov-ab, the wall number is
    reported honestly but only gates on-chip (the CPU backend pays
    per-op dispatch for the site hook - the standing PERF.md caveat)."""
    import json as _json
    import os
    import subprocess
    import tempfile

    import numpy as np

    expect = (17020, 8203, 109)  # KubeAPI FF oracle (BASELINE.md)
    procs, dph = 2, 2

    def _pod(obs: bool, ckpt: str, timeout_s: int = 600) -> dict:
        cmd = [sys.executable, "-m", "jaxtlc.dist",
               "--spawn", str(procs), "--devices-per-host", str(dph),
               "--ff", "--chunk", "128", "--queue-capacity", "4096",
               "--fp-capacity", "16384", "--ckpt", ckpt]
        if obs:
            cmd += ["--obs-slots", "256", "--coverage"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        try:
            proc = subprocess.run(
                cmd, env=env, timeout=timeout_s, capture_output=True,
                text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)))
        except subprocess.TimeoutExpired:
            return {"error": f"pod timed out > {timeout_s}s"}
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("POD_RESULT ")), None)
        if proc.returncode != 0 or line is None:
            tail = (proc.stdout + proc.stderr).strip().splitlines()[-3:]
            return {"error": f"rc={proc.returncode} {tail}"}
        return _json.loads(line[len("POD_RESULT "):])

    from jaxtlc.dist.pod import (
        _load_host_payload, host_checkpoint_path, host_journal_path,
    )

    runs = {}
    tables = {}
    jpaths = []
    with tempfile.TemporaryDirectory() as d:
        for obs in (False, True):
            ck = os.path.join(d, f"obs_{'on' if obs else 'off'}.ckpt")
            r = _pod(obs, ck)
            counts = (r.get("generated"), r.get("distinct"),
                      r.get("depth"))
            if "error" in r or r.get("rc") != 0 or counts != expect:
                _emit({"error": f"obs={obs} pod failed: "
                                f"{r.get('error', counts)}",
                       "workload": "kubeapi_ff_pod"})
                return 1
            runs[obs] = r
            # final per-host shard checkpoints hold the end-of-run
            # carry (save_all runs at the last fence) - the TABLE words
            tables[obs] = []
            for h in range(procs):
                _, payload = _load_host_payload(
                    host_checkpoint_path(ck, h))
                tables[obs].append(payload["table"])
            if obs:
                jpaths = [host_journal_path(ck, h)
                          for h in range(procs)]

        def signature(r):
            return (r["generated"], r["distinct"], r["depth"],
                    r["violation"],
                    tuple(sorted(r["action_generated"].items())),
                    tuple(sorted(r["action_distinct"].items())),
                    r["outdegree"], r["fp_occupancy"])

        if signature(runs[False]) != signature(runs[True]):
            _emit({"error": "obs-on pod result signature differs "
                            "from obs-off",
                   "workload": "kubeapi_ff_pod"})
            return 1
        for h, (off, on) in enumerate(zip(tables[False],
                                          tables[True])):
            if not np.array_equal(off, on):
                _emit({"error": f"host {h} fpset TABLE words differ "
                                "between obs-on and obs-off pods",
                       "workload": "kubeapi_ff_pod"})
                return 1

        # the merge tier: sibling journals -> ONE pod-global timeline
        from jaxtlc.obs import journal as _jr
        from jaxtlc.obs.coverage import coverage_from_events
        from jaxtlc.obs.views import fold_pod_levels, merge_journals

        events = merge_journals(*(
            _jr.read(p, validate=False) for p in jpaths))
        levels = [e for e in fold_pod_levels(events)
                  if e.get("event") == "level"]
        cov = coverage_from_events(events)
        if not levels or cov is None:
            _emit({"error": "obs-on pod journals carry no level / "
                            "coverage events",
                   "workload": "kubeapi_ff_pod"})
            return 1
        last = levels[-1]
        if (last["generated"], last["distinct"],
                last["level"]) != expect:
            _emit({"error": "folded pod level rows do not reach the "
                            f"engine totals: {last}",
                   "workload": "kubeapi_ff_pod"})
            return 1
        for name, g in runs[True]["action_generated"].items():
            if cov["sites"].get(name, 0) != g:
                _emit({"error": f"merged pod coverage site {name} "
                                f"{cov['sites'].get(name, 0)} != "
                                f"generated {g}",
                       "workload": "kubeapi_ff_pod"})
                return 1

    wall_off, wall_on = runs[False]["wall_s"], runs[True]["wall_s"]
    overhead_pct = round((wall_on - wall_off) / wall_off * 100, 3)
    _emit({
        "metric": "pod_obs_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "workload": "kubeapi_ff_pod",
        "procs": procs,
        "devices_per_host": dph,
        "wall_s_off": wall_off,
        "wall_s_on": wall_on,
        "pod_levels": len(levels),
        "pod_sites_visited": cov["visited"],
        "bit_identical": True,
        "device": "cpu pod (gloo loopback)",
    })
    return 0


def main() -> int:
    from jaxtlc.runtime import enable_compile_cache, require_platform

    enable_compile_cache()  # config only: the backend stays untouched
    # child-process modes first: their parent must stay off the chip
    if "--pod-obs-ab" in sys.argv:
        return bench_pod_obs_ab()
    if "--multihost-ab" in sys.argv:
        return bench_multihost_ab()
    if "--struct" in sys.argv:
        return bench_struct()
    require_platform()  # no chip and no JAX_PLATFORMS=cpu: fail here
    if "--infer" in sys.argv:
        return bench_infer()
    if "--sim" in sys.argv:
        return bench_sim()
    if "--commit-ab" in sys.argv:
        return bench_commit_ab()
    if "--expand-ab" in sys.argv:
        return bench_expand_ab()
    if "--reduce-ab" in sys.argv:
        return bench_reduce_ab()
    if "--cov-ab" in sys.argv:
        return bench_cov_ab()
    if "--obs-ab" in sys.argv:
        return bench_obs_ab()
    if "--pipeline-ab" in sys.argv:
        return bench_pipeline_ab()
    if "--liveness" in sys.argv:
        return bench_liveness()
    if "--resil" in sys.argv:
        return bench_resil()
    # the scaled workload (the 50x target's definition, BASELINE.json)
    # unless Model_1 is asked for; it runs on whatever platform jax
    # resolves and the `device` field names it
    scaled = "--model1" not in sys.argv
    workload = "scaled" if scaled else "Model_1"
    import jax

    from jaxtlc.config import MODEL_1, scaled_config
    from jaxtlc.engine.bfs import check

    if scaled:
        # segmented execution (one fused 64-chunk dispatch per host sync):
        # multi-minute single dispatches can hit device-runtime limits
        from jaxtlc.engine.checkpoint import check_with_checkpoints

        cfg, kwargs = scaled_config()
        r = check_with_checkpoints(cfg, ckpt_every=64, **kwargs)
    else:
        cfg, kwargs = MODEL_1, dict(
            chunk=1024, queue_capacity=1 << 15, fp_capacity=1 << 20
        )
        r = check(cfg, **kwargs)
    fail = None
    if r.violation:
        fail = r.violation_name
    elif (r.generated, r.distinct, r.depth) != EXPECT[workload]:
        fail = (
            f"count mismatch: {(r.generated, r.distinct, r.depth)}"
            f" != {EXPECT[workload]}"
        )
    if fail:
        _emit({"error": fail, "workload": workload})
        return 1

    rate = r.distinct / r.wall_s
    _emit(
        {
            "value": round(rate, 1),
            "vs_baseline": round(rate / TLC_DISTINCT_PER_S, 2),
            "workload": workload,
            "generated": r.generated,
            "distinct": r.distinct,
            "depth": r.depth,
            "wall_s": round(r.wall_s, 3),
            "device": str(jax.devices()[0]),
        }
    )
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException as e:  # noqa: BLE001 - contract: always emit JSON
        if isinstance(e, KeyboardInterrupt):
            raise
        traceback.print_exc(file=sys.stderr)
        _emit({"error": f"{type(e).__name__}: {e}"})
        rc = 1
    sys.exit(rc)
