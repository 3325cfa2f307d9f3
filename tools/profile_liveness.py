"""Device-liveness microbench (ISSUE 1 satellite): one JSON line in the
bench.py style, covering the three phases of the jaxtlc.live pipeline -

    enumerate  - fused append-only distinct-state enumeration
    capture    - edge-relation emission (re-expand + batched id search)
    fixpoint   - tensorized survive-set sweeps for ReconcileCompletes

The metric line reports edges captured per second (the capture pass
dominates at scale and is the subsystem's throughput unit), plus the
fixpoint sweep count and per-phase walls, so perf work attacks the
measured phase instead of a guessed one.

Correctness is a gate, as in bench.py: the fixpoint verdict must be the
known one (ReconcileCompletes is violated in every KubeAPI fault
corner) or the tool reports failure instead of a rate.

Usage:
    python tools/profile_liveness.py                 # FF corner (fast)
    python tools/profile_liveness.py --workload model1
    python tools/profile_liveness.py --workload scaled3x0tt
    python tools/profile_liveness.py --mesh 8        # shard the fixpoint
"""

import argparse
import json
import os
import sys
import time

# so the tool runs from any cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKLOADS = {
    # name -> (cfg factory, sizing, pinned distinct states)
    "ff": (lambda: __import__("jaxtlc.config", fromlist=["MATRIX"])
           .MATRIX[(False, False)],
           dict(chunk=256, state_capacity=1 << 14, fp_capacity=1 << 14),
           8203),
    "model1": (lambda: __import__("jaxtlc.config", fromlist=["MODEL_1"])
               .MODEL_1,
               dict(chunk=4096, state_capacity=1 << 18,
                    fp_capacity=1 << 19), 163408),
    "scaled3x0tt": (lambda: __import__(
        "jaxtlc.config", fromlist=["make_scaled"]).make_scaled(3, 0, True,
                                                               True),
        dict(chunk=16384, state_capacity=1 << 24, fp_capacity=1 << 25),
        8869743),
}


def _emit(payload: dict) -> None:
    """The bench.py contract: exactly one JSON line, on every exit path."""
    base = {
        "metric": "liveness_edges_per_s",
        "value": 0,
        "unit": "edges/s",
    }
    base.update(payload)
    print(json.dumps(base), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ff", choices=sorted(WORKLOADS))
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the fixpoint over this many devices")
    args = ap.parse_args()

    try:
        import jax

        from jaxtlc.live.capture import capture_edges
        from jaxtlc.live.check import capture_kube_graph
        from jaxtlc.live.fixpoint import has_nonself, surviving_set
        from jaxtlc.spec.codec import get_codec

        cfg_fn, sizing, expect = WORKLOADS[args.workload]
        cfg = cfg_fn()

        t0 = time.time()
        graph = capture_kube_graph(cfg, **sizing)
        capture_wall = time.time() - t0
        if graph.n_states != expect:
            _emit({"error": f"state count {graph.n_states} != pinned "
                            f"{expect}", "workload": args.workload})
            return 1

        mesh = None
        if args.mesh:
            from jaxtlc.runtime import fp_mesh

            mesh = fp_mesh(args.mesh)

        cdc = get_codec(cfg)
        nonself = has_nonself(graph)
        t1 = time.time()
        # ReconcileCompletes zone for reconciler 0: H = {sr[0]}
        fields_off = cdc.offsets["sr"]
        from jaxtlc.live.capture import eval_state_masks

        (in_h,) = eval_state_masks(
            graph, cdc, [lambda f: f[:, fields_off] == 1]
        )
        alive, sweeps = surviving_set(graph, in_h, mesh=mesh,
                                      nonself=nonself)
        fix_wall = time.time() - t1
        if not (in_h & alive).any():
            _emit({"error": "fixpoint verdict flipped (ReconcileCompletes "
                            "is violated in every fault corner)",
                   "workload": args.workload})
            return 1

        wall = time.time() - t0
        _emit({
            "value": round(len(graph.src) / capture_wall, 1),
            "workload": args.workload,
            "states": graph.n_states,
            "edges": int(len(graph.src)),
            "fixpoint_sweeps": int(sweeps),
            "capture_wall_s": round(capture_wall, 3),
            "fixpoint_wall_s": round(fix_wall, 3),
            "wall_s": round(wall, 3),
            "device": str(jax.devices()[0]),
            "mesh": args.mesh or 1,
        })
        return 0
    except Exception as e:  # noqa: BLE001 - the contract is one JSON line
        _emit({"error": f"{type(e).__name__}: {e}"})
        return 1


if __name__ == "__main__":
    sys.exit(main())
