#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  It finds the cell in BENCHMARK.json, and by the
names there its configuration (benchmark/configs/<config>.json), its
traffic mix (benchmark/traffic/<traffic>.json), the entry the
configuration names (benchmark/entries/<entry>.py) and each per-layer
metric's reader (benchmark/layers/<metric>.py): adding a cell, a
configuration, a mix, an entry or a metric adds files and entries and
edits none.

It refuses to start (exit 2, no result line) unless
jax.devices()[0].platform is "tpu" with at least the cell's chips and a
device_kind that benchmark/peaks.json knows; there is no CPU mode.
Set-up (imports, the one compile cache, server start, a warm job through
the cell's own shapes) is timed as `setup_s` from process start; the
window then runs for --seconds under the mix's loop; XLA compilations in
the window are counted and any makes the run not correct.  The last
stdout line is the result object of the contract.
"""

from __future__ import annotations

import time

_T0 = time.time()  # process start, as near as Python can say

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import gate  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402

EXIT_REFUSED = 2


class Refused(RuntimeError):
    """The run may not start: no result line, exit 2."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name (a name may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise Refused(f"no {kind}/{name}.py under {HERE}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {[w['name'] for w in bench['workloads']]})")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(bench: dict, group: str, workload: str):
    """The cell's metrics of one group: an entry without `workloads` is
    every cell's."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def device_gate(chips: int, peaks: dict):
    """The platform gate: a TPU, enough chips, a known device_kind."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise Refused(f"jax.devices()[0] is {d0!r} on platform "
                      f"{d0.platform!r}: this benchmark has no CPU mode")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX reports "
                      f"{len(devices)}")
    if d0.device_kind not in peaks["devices"]:
        raise Refused(f"device_kind {d0.device_kind!r} is not in "
                      "benchmark/peaks.json")
    return devices


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


SPANS = []  # (name, start, end) on time.time(), of every annotate()


@contextlib.contextmanager
def annotate(name: str):
    """A host span around a call into the program: written into the
    profiler's own trace (free when no trace runs) and kept in SPANS on
    the host clock, so that a span that began before a trace slice can
    still name the idle gaps inside it."""
    import jax

    t = time.time()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        SPANS.append((name, t, time.time()))


class SliceTracer:
    """Traces `slice_s` seconds of the window, starting `start_s` in,
    from a thread of its own: the caller may be blocked in one long
    check the whole time."""

    def __init__(self, workdir: str, start_s: float, slice_s: float):
        self.dir = os.path.join(workdir, "trace")
        self.start_s, self.slice_s = start_s, slice_s
        self.error = None
        self.slice_t0 = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.start_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the harness's own spans only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench:trace_slice"):
                    self.slice_t0 = time.time()
                    time.sleep(self.slice_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported; the run is then not traced
            self.error = f"{type(e).__name__}: {e}"

    def finish(self, timeout: float = 300.0):
        self._thread.join(timeout)
        if self._thread.is_alive():
            self.error = "the profiler did not stop"
            return None
        if self.error:
            return None
        import trace_reduce

        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            self.error = "the profiler wrote no .xplane.pb"
            return None
        return trace_reduce.reduce_file(
            path, host_spans=list(SPANS), slice_t0=self.slice_t0)


def end_to_end(records, setup_s: float, log):
    """Every end-to-end metric this harness knows, from all the jobs and
    all the time of the window."""
    done = [r for r in records if r.get("ok") and not r.get("findings")]
    out = {"setup_s": setup_s}
    if done:
        t_first = min(r["due_t"] for r in records)
        t_last = max(r["done_t"] for r in done)
        states = sum(r["result"]["distinct"] for r in done)
        out["states_per_s"] = states / (t_last - t_first)
        log(f"states_per_s: {states} distinct states of {len(done)} "
            f"correct jobs over {t_last - t_first:.3f} s of the window")
        lat = [1e3 * (r["done_t"] - r["due_t"]) for r in done]
        out["verdict_ms.p50"] = stats.percentile(lat, 0.50)
        out["verdict_ms.mean"] = sum(lat) / len(lat)
        beyond = stats.samples_beyond(lat, 0.95)
        log(f"verdict_ms: {len(lat)} samples, mean "
            f"{out['verdict_ms.mean']:.3f}, p50 "
            f"{out['verdict_ms.p50']:.3f}, p90 "
            f"{stats.percentile(lat, 0.90):.3f}, p95 "
            f"{stats.percentile(lat, 0.95):.3f} with {beyond} beyond it"
            + ("" if beyond >= 10 else " (FEWER THAN TEN: a high sample, "
               "not a tail)")
            + f", p99 {stats.percentile(lat, 0.99):.3f}, max "
            f"{max(lat):.3f}")
    return out


def run(args, log) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, args.workload)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if not os.path.isdir(os.path.join(ROOT, "jaxtlc")):
        raise Refused(f"no jaxtlc package beside {HERE}: nothing to "
                      "measure")
    for k, v in (config.get("env") or {}).items():
        os.environ[k] = v
    devices = device_gate(int(cell["chips"]), peaks)
    d0 = devices[0]
    sys.path.insert(0, ROOT)
    import jaxtlc

    if os.path.dirname(os.path.realpath(jaxtlc.__file__)) != (
            os.path.realpath(os.path.join(ROOT, "jaxtlc"))):
        raise Refused(f"imported jaxtlc from {jaxtlc.__file__}, not from "
                      f"the checkout at {ROOT}")
    from jaxtlc.runtime import enable_compile_cache
    from jaxtlc.serve.pool import CompileMeter

    cache_dir = enable_compile_cache()
    meter = CompileMeter.instance()
    log(f"device: platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")
    entry = load_module("entries", config["entry"])
    workdir = tempfile.mkdtemp(prefix="jaxtlc-bench-")
    try:
        ctx = dict(config=config, traffic=traffic, root=ROOT,
                   workdir=workdir, annotate=annotate)
        handle = entry.setup(ctx)
        try:
            setup_s = time.time() - _T0
            req0, hit0 = meter.count, meter.cache_hits
            log(f"set-up {setup_s:.3f} s: {req0} compile requests, "
                f"{hit0} from the persistent cache, "
                f"{req0 - hit0} backend compiles")
            tracer = None
            if args.trace:
                tr = traffic.get("trace") or {}
                tracer = SliceTracer(
                    workdir,
                    float(tr.get("start_share", 0.25)) * args.seconds,
                    min(float(tr.get("slice_s", 2.0)), args.seconds / 2))
                log(f"trace: a slice of {tracer.slice_s:.1f} s from "
                    f"{tracer.start_s:.1f} s into the window")
                tracer.start()
            records = loadgen.drive(
                lambda d: entry.run_job(handle, d, annotate),
                traffic, args.seed, args.seconds)
            window_compiles = ((meter.count - req0)
                               - (meter.cache_hits - hit0))
            log(f"window: {len(records)} jobs, "
                f"{meter.count - req0} compile requests, "
                f"{window_compiles} backend compiles")
            trace = tracer.finish() if tracer else None
            if tracer and tracer.error:
                log(f"trace: FAILED: {tracer.error}")
            entry.collect(handle, records)
        finally:
            entry.close(handle)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    late = [1e3 * (r["start_t"] - r["due_t"]) for r in records
            if r.get("start_t") is not None]
    if traffic["loop"] == "open" and late:
        log(f"generator lateness: median {stats.median(late):.3f} ms, "
            f"max {max(late):.3f} ms over {len(late)} arrivals")
    verdict = gate.judge(records, config, window_compiles, d0.platform)
    for line in verdict["lines"]:
        log(line)

    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices),
                  memory_peak_bytes=memory_peak(devices))
    run_view = dict(jobs=records, trace=trace, device=device, cell=cell,
                    config=config, traffic=traffic, setup_s=setup_s)
    units = {}
    if args.trace:
        values = {}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            v = load_module("layers", m["name"]).read(run_view)
            if v is not None:
                values[m["name"]] = v
                units[m["name"]] = m["unit"]
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            if trace["n_devices"] == 0 or trace["busy_s"] <= 0:
                verdict["correct"] = False
                log("trace: no operation ran on the device in the slice")
        else:
            verdict["correct"] = False
    else:
        have = end_to_end(records, setup_s, log)
        values = {}
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if have.get(m["name"]) is not None:
                values[m["name"]] = have[m["name"]]
                units[m["name"]] = m["unit"]
    line = dict(
        correct=bool(verdict["correct"]), attempted=verdict["attempted"],
        failed=verdict["failed"],
        metrics={k: dict(value=v, unit=units[k])
                 for k, v in values.items()},
        device=device, workload=cell["name"], seed=args.seed,
        seconds=args.seconds)
    if args.trace and trace is not None:
        line["breakdown"] = dict(device_ops=trace["device_ops"],
                                 idle_gaps=trace["idle_gaps"])
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    def log(msg: str):
        print(f"bench: {msg}", flush=True)

    try:
        # the program prints to stdout at will; the result line is ours
        line = run(args, log)
    except Refused as e:
        print(f"benchmark/run.py: refusing to start: {e}", file=sys.stderr)
        return EXIT_REFUSED
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
