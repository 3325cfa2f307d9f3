"""Compute a configuration's pins with its plain reference.

    python benchmark/reference/pin.py <config name> [--fp-bits N --salt S]

Reads benchmark/configs/<name>.json, runs the reference that the file
names (`reference`) on the deployment it describes (`deployment`), and
prints {"generated", "distinct", "depth", "action_generated"} as one
JSON line.  With --fp-bits it runs the control instead (dedup by a
truncated hash).  It imports nothing of the program and needs no JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def reference_pins(config: dict, fp_bits: int = 0, fp_salt: int = 0) -> dict:
    dep = config["deployment"]
    if config["reference"] == "kubeapi":
        import kubeapi

        if dep.get("scaling") is None:
            model = kubeapi.model_1(dep["REQUESTS_CAN_FAIL"],
                                    dep["REQUESTS_CAN_TIMEOUT"])
        else:
            model = kubeapi.scaled(
                dep["scaling"]["n_reconcilers"],
                dep["scaling"]["n_binders"],
                dep["REQUESTS_CAN_FAIL"], dep["REQUESTS_CAN_TIMEOUT"])
        r = kubeapi.bfs(model, fp_bits=fp_bits, fp_salt=fp_salt)
    elif config["reference"] == "raftrepl":
        import raftrepl

        r = raftrepl.bfs(len(dep["Nodes"]), dep["MaxLog"], dep["MaxTerm"],
                         fp_bits=fp_bits, fp_salt=fp_salt)
    else:
        raise SystemExit(f"unknown reference {config['reference']!r}")
    if r.violations:
        raise SystemExit(f"reference found violations: {r.violations[:3]}")
    return dict(generated=r.generated, distinct=r.distinct, depth=r.depth,
                action_generated=dict(sorted(r.action_generated.items())))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("config")
    p.add_argument("--fp-bits", type=int, default=0)
    p.add_argument("--salt", type=int, default=0)
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    t0 = time.time()
    pins = reference_pins(config, args.fp_bits, args.salt)
    pins["reference_s"] = round(time.time() - t0, 1)
    print(json.dumps(pins))
    return 0


if __name__ == "__main__":
    sys.exit(main())
