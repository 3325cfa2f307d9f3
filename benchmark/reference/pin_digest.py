"""Compute a KubeAPI configuration's pins where pin.py cannot: the same
plain reference (kubeapi.py's `successors`, `initial_states`, `type_ok`,
`only_one_version`, unedited) under the same level-synchronous BFS with
TLC's accounting, but a state is remembered by a 16-byte digest of its
canonical form instead of by itself, and the frontier is expanded by a
few worker processes.

    python benchmark/reference/pin_digest.py <config name> [--workers N]

Why it exists: kubeapi.bfs stops at 10,000,000 distinct states (its
`max_states`, which pin.py does not pass), and keeps every state as
nested tuples; kubeapi-2x1ff has 19,359,985.  Prints the same JSON line
as pin.py.  It imports nothing of the program and needs no JAX.

A digest is blake2b-128 over a canonical serialisation (frozensets
sorted by kubeapi._ckey, so it does not depend on a process's hash
seed).  With 2*10^7 states the chance that two share a digest is about
10^-24; a collision would lose a state and the counts would then differ
from SCALED_VALIDATION.json's cross-engine pin.

The parent keeps the set of digests and decides, first come, which
successor is new; a new state stays in the worker that generated it and
is expanded there at the next level; states cross a pipe only to even
out the workers' frontiers when one holds a fifth more than its share.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import multiprocessing as mp
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import kubeapi  # noqa: E402

DIGEST = 16


def build_model(config: dict):
    dep = config["deployment"]
    if config["reference"] != "kubeapi":
        raise SystemExit("pin_digest.py serves the kubeapi reference only")
    if dep.get("scaling") is None:
        return kubeapi.model_1(dep["REQUESTS_CAN_FAIL"],
                               dep["REQUESTS_CAN_TIMEOUT"])
    return kubeapi.scaled(dep["scaling"]["n_reconcilers"],
                          dep["scaling"]["n_binders"],
                          dep["REQUESTS_CAN_FAIL"],
                          dep["REQUESTS_CAN_TIMEOUT"])


def _canon(v):
    """A value with every frozenset replaced by a sorted tuple."""
    if isinstance(v, frozenset):
        return ("{}",) + tuple(_canon(x) for x in sorted(v, key=kubeapi._ckey))
    if isinstance(v, tuple):
        return tuple(_canon(x) for x in v)
    return v


class Digester:
    """digest(state): per-field canonical bytes, memoised by field value
    (a field takes far fewer values than the state does)."""

    def __init__(self):
        self.memo = [dict() for _ in kubeapi.State._fields]

    def __call__(self, st) -> bytes:
        h = hashlib.blake2b(digest_size=DIGEST)
        for memo, v in zip(self.memo, st):
            b = memo.get(v)
            if b is None:
                b = memo[v] = repr(_canon(v)).encode() + b"\x00"
            h.update(b)
        return h.digest()


def _worker(conn, cfg):
    digest = Digester()
    frontier, pending = [], []
    while True:
        msg = conn.recv()
        if msg[0] == "init":
            # the parent hands out the initial states it found new
            frontier = list(msg[1])
        elif msg[0] == "expand":
            # expand this worker's frontier; answer the digests of the
            # successors, each once, in generation order
            generated, by_action, violations = 0, {}, []
            max_out, min_out = 0, 1 << 30
            local, pending = {}, []
            for s in frontier:
                succs = kubeapi.successors(s, cfg)
                generated += len(succs)
                outdeg = len({x.state for x in succs})
                max_out, min_out = max(max_out, outdeg), min(min_out, outdeg)
                if outdeg == 0:
                    violations.append("deadlock")
                for x in succs:
                    by_action[x.label] = by_action.get(x.label, 0) + 1
                    if x.violation:
                        violations.append(x.violation)
                    d = digest(x.state)
                    if d not in local:
                        local[d] = None
                        pending.append(x.state)
            conn.send((generated, by_action, violations[:3], max_out, min_out,
                       b"".join(local)))
        elif msg[0] == "keep":
            # msg[1]: one byte per pending successor, 1 = new to the parent
            frontier = [s for s, k in zip(pending, msg[1]) if k]
            pending = []
            bad = [name for s in frontier
                   for name, ok in (("TypeOK", kubeapi.type_ok(s)),
                                    ("OnlyOneVersion",
                                     kubeapi.only_one_version(s))) if not ok]
            conn.send(bad[:3])
        elif msg[0] == "give":
            conn.send(frontier[len(frontier) - msg[1]:])
            del frontier[len(frontier) - msg[1]:]
        elif msg[0] == "take":
            frontier += msg[1]
        else:
            return


def _rebalance(pipes, held) -> None:
    """Move states from the fullest workers to the emptiest until none
    holds more than a fifth over its share."""
    share = -(-sum(held) // len(held))
    while max(held) > share + share // 5 + 1:
        src, dst = held.index(max(held)), held.index(min(held))
        k = min(held[src] - share, share - held[dst])
        pipes[src].send(("give", k))
        pipes[dst].send(("take", pipes[src].recv()))
        held[src] -= k
        held[dst] += k


def bfs(cfg, workers: int) -> dict:
    ctx = mp.get_context("fork")
    pipes, procs = [], []
    for _ in range(workers):
        a, b = ctx.Pipe()
        p = ctx.Process(target=_worker, args=(b, cfg), daemon=True)
        p.start()
        pipes.append(a)
        procs.append(p)
    digest = Digester()
    seen = set()
    generated, by_action, violations = 0, {}, []
    max_out, min_out = 0, 1 << 30
    first = [[] for _ in range(workers)]
    n_new = 0
    for s in kubeapi.initial_states(cfg):
        generated += 1
        d = digest(s)
        if d not in seen:
            seen.add(d)
            first[n_new % workers].append(s)
            n_new += 1
    for conn, states in zip(pipes, first):
        conn.send(("init", states))
    depth, levels = 1, [n_new]
    while n_new:
        for conn in pipes:
            conn.send(("expand",))
        n_new = 0
        held = [0] * workers
        # first come is by reading order: rotate it, so no worker always wins
        order = [(depth + i) % workers for i in range(workers)]
        for w in order:
            conn = pipes[w]
            g, acts, viol, mx, mn, blob = conn.recv()
            generated += g
            for k, v in acts.items():
                by_action[k] = by_action.get(k, 0) + v
            violations += viol
            max_out, min_out = max(max_out, mx), min(min_out, mn)
            keep = bytearray(len(blob) // DIGEST)
            for i in range(len(keep)):
                d = blob[i * DIGEST:(i + 1) * DIGEST]
                if d not in seen:
                    seen.add(d)
                    keep[i] = 1
            held[w] = sum(keep)
            n_new += held[w]
            conn.send(("keep", bytes(keep)))
        for conn in pipes:
            violations += conn.recv()
        _rebalance(pipes, held)
        if n_new:
            depth += 1
            levels.append(n_new)
            if depth % 10 == 0:
                print(f"level {depth}: {n_new} new, {len(seen)} distinct, "
                      f"{generated} generated", file=sys.stderr, flush=True)
    for conn in pipes:
        conn.send(("stop",))
    for p in procs:
        p.join()
    if violations:
        raise SystemExit(f"reference found violations: {violations[:3]}")
    return dict(generated=generated, distinct=len(seen), depth=depth,
                action_generated=dict(sorted(by_action.items())))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("config")
    p.add_argument("--workers", type=int, default=4)
    args = p.parse_args(argv)
    path = os.path.join(os.path.dirname(HERE), "configs",
                        args.config + ".json")
    with open(path) as f:
        config = json.load(f)
    t0 = time.time()
    pins = bfs(build_model(config), args.workers)
    pins["reference_s"] = round(time.time() - t0, 1)
    print(json.dumps(pins))
    return 0


if __name__ == "__main__":
    sys.exit(main())
