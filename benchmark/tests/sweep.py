"""The rate sweep that placed the served cell's rate (run once, on the
chip, when the cell was defined; the benchmark itself never searches).

    python3 benchmark/tests/sweep.py --workload raftrepl-model1.served \
        --rates 4,8,12,16,20,24,32 --seconds 15

One process, one set-up; then the cell's own mix at each rate in turn
through the benchmark's own generator and entry.  For each rate it prints
offered and completed jobs per second, the latency percentiles from the
due instant, the scheduler's queue wait in the first and the second half
of the window (a queue that grows through the window is past the knee),
and the failures.  The knee is the highest rate whose completed rate
keeps up and whose queue wait does not grow.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seed", type=int, default=2147483693)
    args = p.parse_args(argv)

    import gate
    import loadgen
    import run
    import stats

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic = run.find_cell(bench, args.workload)
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = v
    devices = run.device_gate(
        int(cell["chips"]), run.load_json(os.path.join(BENCH, "peaks.json")))
    sys.path.insert(0, run.ROOT)
    from jaxtlc.runtime import enable_compile_cache

    enable_compile_cache()
    entry = run.load_module("entries", config["entry"])
    workdir = tempfile.mkdtemp(prefix="jaxtlc-sweep-")
    try:
        handle = entry.setup(dict(config=config, traffic=traffic,
                                  root=run.ROOT, workdir=workdir,
                                  annotate=run.annotate))
        try:
            for rate in [float(r) for r in args.rates.split(",")]:
                mix = dict(traffic, arrivals=dict(traffic["arrivals"],
                                                  rate_per_s=rate))
                recs = loadgen.drive(
                    lambda d: entry.run_job(handle, d, run.annotate),
                    mix, args.seed, args.seconds)
                entry.collect(handle, recs)
                v = gate.judge(recs, config, 0, devices[0].platform)
                good = [r for r in recs if r.get("ok")
                        and not r.get("findings")]
                lat = [1e3 * (r["done_t"] - r["due_t"]) for r in good]
                t0 = min(r["due_t"] for r in recs)
                span = max([r["done_t"] for r in good] or [t0]) - t0
                half = t0 + args.seconds / 2
                qw = [[], []]
                for r in good:
                    s = r.get("sched") or {}
                    if "admit" in s and "dispatch" in s:
                        qw[r["due_t"] >= half].append(
                            1e3 * (s["dispatch"] - s["admit"]))
                late = [1e3 * (r["start_t"] - r["due_t"]) for r in recs
                        if r.get("start_t")]
                print(json.dumps(dict(
                    offered_per_s=rate, jobs=len(recs),
                    failed=v["failed"],
                    completed_per_s=len(good) / span if span else None,
                    drain_past_window_s=span - args.seconds,
                    p50_ms=stats.percentile(lat, 0.5),
                    p95_ms=stats.percentile(lat, 0.95),
                    p99_ms=stats.percentile(lat, 0.99),
                    queue_wait_ms_first_half=stats.median(qw[0]),
                    queue_wait_ms_second_half=stats.median(qw[1]),
                    queue_wait_ms_p95=stats.percentile(qw[0] + qw[1], 0.95),
                    generator_late_ms_max=max(late) if late else None,
                )), flush=True)
        finally:
            entry.close(handle)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
