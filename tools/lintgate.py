#!/usr/bin/env python
"""Engine-free lint gate over the specs tree (CI entry point).

    python tools/lintgate.py [SPECS_DIR]

Runs speclint + the certified abstract interpretation over every
MC.cfg under SPECS_DIR (default: the repo's specs/), printing one line
per spec plus its findings, and exits nonzero on any error-severity
finding.  Milliseconds per spec - no jax import, no engine build - so
it belongs in front of every commit touching specs/.  The same pass
runs as ``python -m jaxtlc.analysis --gate`` and as a tier-1 test
(tests/test_absint.py), so the committed tree can never drift into an
error-class lint silently.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# every engine factory CI expects audited (mirrors the tier-1 pin in
# tests/test_analysis.py::test_selfcheck_registry_pinned); importing
# the registry is jax-free, so this stays an engine-free gate
REQUIRED_FACTORIES = (
    "covered", "covsharded", "deferred", "enumerator", "fused",
    "infer", "narrowed", "pipelined", "por", "sharded", "shardspill",
    "sim", "spill", "struct", "sweep", "symmetry",
)


def check_factories() -> int:
    """Engine-free registry pin: every REQUIRED factory (the
    deferred-evaluation engine, ISSUE 15, included) must be registered
    for the `python -m jaxtlc.analysis --self-check` audit - a commit
    that drops one fails here before any engine builds."""
    from jaxtlc.analysis.selfcheck import FACTORIES

    missing = sorted(set(REQUIRED_FACTORIES) - set(FACTORIES))
    if missing:
        print(f"lintgate: selfcheck registry is missing {missing} - "
              "the factory would ship unaudited", file=sys.stderr)
        return 1
    print(f"lintgate: selfcheck registry covers "
          f"{len(REQUIRED_FACTORIES)} factories"
          " (run `python -m jaxtlc.analysis --self-check --tiny` for "
          "the full audit)")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "specs",
    )
    from jaxtlc.analysis.gate import run_gate

    rc = run_gate(root)
    return rc or check_factories()


if __name__ == "__main__":
    sys.exit(main())
