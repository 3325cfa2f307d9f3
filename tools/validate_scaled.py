"""Scaled-workload pin validation (VERDICT r3 item 5: de-circularize).

Re-derives the scaled-config expected counts by running INDEPENDENT
engine configurations and recording their agreement in
SCALED_VALIDATION.json - the artifact tests/test_scaled.py and the
benchmark's kubeapi-2x1ff pins cite.  Independence axes:

* engine geometry: different chunk sizes and fingerprint-table
  capacities execute different instruction schedules, candidate
  groupings and probe patterns - identical counts across them rule out
  geometry-dependent dedup/enqueue bugs;
* platform: the TPU path (MXU fingerprints, real HBM layouts) vs the
  forced-CPU path (totally different XLA backend lowering);
* engine variant: the hybrid (host-tier dedup) engine shares no
  fingerprint-set or queue code with the device engine.

Usage:
    python tools/validate_scaled.py [--workload 2x1|1x2] [--quick]
        [--engine device|hybrid] [--chunk N] [--fpcap LOG2]

Each invocation appends one validated run to the artifact (exact-count
agreement with the recorded pins is asserted; a mismatch aborts loudly
WITHOUT touching the file).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "SCALED_VALIDATION.json",
)

PINS = {
    "2x1FF": (62014325, 19359985, 186),  # the flagship (benchmark cell kubeapi-2x1ff.sharded4)
    "1x2FF": (30582846, 9942722, 160),  # tests/test_scaled.py slow pin
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["2x1", "1x2"], default="2x1")
    ap.add_argument("--engine", choices=["device", "hybrid"],
                    default="device")
    ap.add_argument("--chunk", type=int, default=16384)
    ap.add_argument("--fpcap", type=int, default=25, help="log2")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from jaxtlc.config import make_scaled

    key = f"{args.workload}FF"
    cfg = (make_scaled(2, 1, False, False) if args.workload == "2x1"
           else make_scaled(1, 2, False, False))
    t0 = time.time()
    if args.engine == "device":
        from jaxtlc.engine.bfs import check

        r = check(cfg, chunk=args.chunk, queue_capacity=1 << 21,
                  fp_capacity=1 << args.fpcap)
    else:
        from jaxtlc.engine.hybrid import check_hybrid

        r = check_hybrid(cfg, chunk=args.chunk)
    got = (r.generated, r.distinct, r.depth)
    print(f"{key} {args.engine} chunk={args.chunk}: {got} "
          f"in {time.time() - t0:.1f}s on {jax.devices()[0]}")
    if got != PINS[key]:
        print(f"MISMATCH: expected {PINS[key]}", file=sys.stderr)
        return 1

    entry = {
        "workload": key,
        "engine": args.engine,
        "platform": str(jax.devices()[0]),
        "chunk": args.chunk,
        "fp_capacity_log2": args.fpcap if args.engine == "device" else None,
        "generated": r.generated,
        "distinct": r.distinct,
        "depth": r.depth,
        "wall_s": round(r.wall_s, 2),
        "date": time.strftime("%Y-%m-%d"),
    }
    doc = {"pins": {k: list(v) for k, v in PINS.items()}, "runs": []}
    if os.path.exists(ARTIFACT):
        with open(ARTIFACT) as f:
            doc = json.load(f)
    doc["runs"].append(entry)
    tmp = ARTIFACT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, ARTIFACT)
    print(f"recorded in {ARTIFACT} ({len(doc['runs'])} runs)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
