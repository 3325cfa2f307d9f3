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
stdout line is the result object of the contract; its last key,
`compared`, holds each number compared beside its limit, and the same
lines are the last on standard error.

A traced run (`--trace 1`) takes ONE slice of the window under the
profiler.  Where it lies follows the run and not the clock (class
Tracer; the arithmetic is benchmark/placement.py, the policy numbers
are the traffic mix's `trace` block, what a traced second costs is in
benchmark/README.md).  An untraced run builds no Tracer.
"""

from __future__ import annotations

import time

_T0 = time.time()  # process start, as near as Python can say

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import gate  # noqa: E402
import loadgen  # noqa: E402
import placement  # noqa: E402
import span_read  # noqa: E402
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


class Tracer:
    """One slice of the window under the profiler, placed by what the
    run is doing.

    Closed loop (one caller, whole checks back to back): `before_job`
    and `after_job` are called around every job on the caller's thread.
    After each finished job - the warm one of set-up first - the
    program's own spans give the host seconds before that job's loop
    (`h`) and the loop's length (`L`), and benchmark/placement.py turns
    them and the mix's `busy_budget_s` / `loop_share` into a plan: whole
    jobs, traced from a job's start to its end on the caller's thread,
    or a slice inside one job's loop, traced from a thread of its own
    (the caller is blocked in the check).  The slice goes into the last
    job(s) the window will start, so the profiler's stop falls behind
    the last verdict; for a slice inside a loop both where the loop
    begins and whether the job is the last are settled when the job's
    loop is SEEN to begin, not from the last job's host seconds, and no
    job is placed by the warm job's seconds: a warm job's host part is
    many times a timed one's.

    Open loop (served traffic): the slice is the last `slice_s` seconds
    of the window, and the profiler stops only when `finish` says the
    last verdict is in hand, so no job runs beside a stopping profiler.

    By the clock, `slice_s` from `start_share` of the window, only where
    no finished job has said by then where its loop lies (a program
    without the span recorder; a first check that is still running): the
    fallback and nothing else."""

    GIVE_UP_S = 10.0  # past the estimated start of a loop that never shows

    def __init__(self, workdir: str, traffic: dict, seconds: float, log,
                 warm=None):
        tr = traffic.get("trace") or {}
        self.dir = os.path.join(workdir, "trace")
        self.seconds, self.log = seconds, log
        self.open_loop = traffic["loop"] == "open"
        self.slice_s = min(float(tr.get("slice_s", 2.0)), seconds / 2)
        self.start_s = float(tr.get("start_share", 0.25)) * seconds
        self.budget_s = min(float(tr.get("busy_budget_s", self.slice_s)),
                            seconds / 2)
        self.loop_share = float(tr.get("loop_share", 0.3))
        self.error = None
        self.slice_t0 = self.slice_t1 = None
        self.placed = None  # the numbers that placed the slice
        self.cost = {}
        self._state = "idle"  # -> "tracing" -> "done"
        self._lock = threading.Lock()
        self._threads = []
        self._slice = None  # the open bench:trace_slice annotation
        self._jobs_left = 0
        self._job_over = threading.Event()  # the running job's watcher
        self._verdicts_in = threading.Event()
        self._t0 = None
        self._shape = None  # (h, L, seconds at the caller) of the last job
        self._timed = False  # ... and that job was a timed one, not the warm
        if warm is not None:
            self._learn(*warm)
            if self._shape:
                log("trace: the warm job spent h={:.3f} s before a loop of "
                    "L={:.3f} s ({:.3f} s in all)".format(*self._shape))

    # -- the profiler, once a run ------------------------------------------

    def _claim(self) -> bool:
        with self._lock:
            if self._state != "idle":
                return False
            self._state = "tracing"
            return True

    def _start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # annotations: ours and the program's
        t = time.time()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._slice = jax.profiler.TraceAnnotation("bench:trace_slice")
        self._slice.__enter__()
        self.slice_t0 = time.time()
        self.cost["start_s"] = self.slice_t0 - t

    def _stop(self, hold: bool = False):
        import jax

        self.slice_t1 = time.time()
        self._slice.__exit__(None, None, None)
        if hold:  # the profiler runs on, outside the slice
            self._verdicts_in.wait(self.seconds + 120.0)
        t = time.time()
        jax.profiler.stop_trace()
        self.cost["stop_s"] = time.time() - t
        self._state = "done"

    def _in_thread(self, wait, hold: bool = False):
        """From a thread of its own: `wait()` blocks until the slice is
        to start and gives its length in seconds, or None where none is
        to be taken after all."""
        def body():
            try:
                length_s = wait()
                if length_s is not None and self._claim():
                    self._start()
                    time.sleep(length_s)
                    self._stop(hold)
            except Exception as e:  # reported; the run is then not traced
                self.error = f"{type(e).__name__}: {e}"

        t = threading.Thread(target=body, daemon=True)
        self._threads.append(t)
        t.start()

    def _wait_for_loop(self, index: int, plan, shape, now_s: float, over):
        """From a thread of its own, as job `index` starts - for a slice
        inside a loop, and for the first job where whole jobs are wanted
        but only the warm job has said anything: blocks until the job's
        loop has begun, decides THEN whether this job is the last the
        window will start, and if so until `loop_share` of the way into
        the loop.  Where the loop begins is not taken from the estimate
        `h` but seen: the job's first `loop.dispatch` span closes as its
        loop starts, so a build that is gone (a kept engine), shorter or
        longer than the last job's - the warm job's above all, whose
        first-use loads make it many times a timed one - moves the slice
        and the decision with it.  Only where no such span shows (up to
        twice the estimate, or GIVE_UP_S past it) does the estimate
        stand in."""
        h, loop_s, job_s = shape
        t_job = time.time()
        give_up = t_job + max(2 * h, h + self.GIVE_UP_S)
        seen = None
        while seen is None and not over.is_set() and time.time() < give_up:
            seen = span_read.loop_started(t_job)
            if seen is None:
                time.sleep(0.05)
        if seen is None and over.is_set():
            return None  # the job ended and showed no loop
        h_now = h if seen is None else seen - t_job
        est = h_now + loop_s + max(0.0, job_s - h - loop_s)
        if not placement.is_last(now_s, est, 1, self.seconds):
            self.log(f"trace: job {index} began its loop {h_now:.3f} s "
                     f"after its start ({now_s:.3f} s into the window): a "
                     f"job of ~{est:.3f} s is not the last, no slice in it")
            return None
        if plan.mode == "whole":
            # whole jobs were wanted, but this job is the window's only
            # one and its start has passed: its loop, which fits the
            # budget, without the host seconds before it
            plan = plan._replace(mode="loop", start_s=h, length_s=loop_s,
                                 jobs=1, busy_s=loop_s)
            self.log(f"trace: job {index} is the window's only one and its "
                     "host part has passed: the slice holds its loop, not "
                     "its duty cycle")
        self._take(index, plan, shape, now_s, loop_seen_s=None
                   if seen is None else h_now)
        start = (t_job + plan.start_s if seen is None
                 else seen + plan.start_s - h)
        time.sleep(max(0.0, start - time.time()))
        return plan.length_s

    # -- placement -----------------------------------------------------------

    def window_opens(self):
        self._t0 = time.time()
        if self.open_loop:
            at = self.seconds - self.slice_s
            self.placed = dict(mode="end of the window", start_s=at,
                               length_s=self.slice_s)
            self.log(f"trace: the window's last {self.slice_s:.2f} s; the "
                     "profiler stops after the last verdict")
            self._in_thread(lambda: time.sleep(at) or self.slice_s, hold=True)
            return
        self.log(f"trace: placed by job and loop, busy budget "
                 f"{self.budget_s:.2f} s, loop share {self.loop_share:.2f}; "
                 f"by the clock ({self.slice_s:.1f} s from "
                 f"{self.start_s:.1f} s) only if no finished job has said "
                 "where its loop lies by then")
        self._in_thread(lambda: time.sleep(self.start_s) or (
            self.slice_s if self._shape is None else None))

    def _learn(self, start_t: float, done_t: float):
        shape = span_read.job_shape(start_t, done_t)
        if shape is not None:
            self._shape = shape + (done_t - start_t,)
        return shape

    def _take(self, index: int, plan, shape, now_s: float, **seen):
        h, loop_s, job_s = shape
        self.placed = dict(plan._asdict(), job=index, h=h, L=loop_s,
                           job_s=job_s, at_s=now_s, **seen)
        self.log(f"trace: job {index}, {now_s:.3f} s into the window, is "
                 f"taken for the last; h={h:.3f} L={loop_s:.3f} -> "
                 + (f"{plan.jobs} whole job(s) from its start"
                    if plan.mode == "whole" else
                    f"{plan.length_s:.3f} s from {plan.start_s - h:.3f} s "
                    "into its loop, " + (
                        "seen {loop_seen_s:.3f} s after the job's start"
                        .format(**seen) if seen.get("loop_seen_s")
                        is not None else "which did not show: by the "
                        f"estimate, {plan.start_s:.3f} s after the job's "
                        "start"))
                 + f", {plan.busy_s:.3f} busy seconds expected")

    def before_job(self, index: int):
        """On the caller's thread, right before job `index` starts."""
        if self.placed or self._state != "idle" or self._shape is None:
            return
        shape = self._shape
        plan = placement.place(*shape[:2], self.budget_s, self.loop_share)
        now = time.time() - self._t0
        if plan is None:
            return
        if plan.mode == "whole" and self._timed:
            # settled here, since the slice starts with the job: by the
            # last timed job's seconds at the caller
            if placement.is_last(now, shape[2], plan.jobs,
                                 self.seconds) and self._claim():
                self._take(index, plan, shape, now)
                self._jobs_left = plan.jobs
                self._start()
            return
        # a slice inside a loop; or whole jobs with only the warm job to
        # go by, whose seconds say nothing of a timed one's: watched
        self._job_over = over = threading.Event()
        self._in_thread(lambda: self._wait_for_loop(index, plan, shape, now,
                                                    over))

    def after_job(self, index: int, start_t: float, done_t: float):
        """On the caller's thread, right after job `index` ended."""
        self._job_over.set()
        if self._jobs_left:
            self._jobs_left -= 1
            if not self._jobs_left:
                self._stop()
        if self.placed is None:
            self._timed = self._learn(start_t, done_t) is not None
        elif self.placed.get("job") == index and self.placed[
                "mode"] == "loop":
            shape = self._learn(start_t, done_t)
            if shape and self.slice_t0:
                a = self.slice_t0 - start_t
                b = (self.slice_t1 or done_t + 1.0) - start_t
                ok = placement.inside(a, b - a, *shape)
                self.placed.update(job_h=shape[0], job_L=shape[1],
                                   inside=ok)
                self.log(f"trace: job {index} ran its loop from "
                         f"{shape[0]:.3f} to {sum(shape):.3f} s after its "
                         f"start, the slice from {a:.3f} to {b:.3f} s: it "
                         + ("lay inside the loop" if ok else "DID NOT lie "
                            "inside the loop (the job did not behave as "
                            "the last one)"))

    # -- after the window ----------------------------------------------------

    def finish(self, timeout: float = 280.0):
        """The window is closed and every verdict in hand: stop what
        still runs, then reduce the trace."""
        self._verdicts_in.set()
        if self._jobs_left:  # the window ended inside the whole jobs
            self._jobs_left = 0
            self._stop()
        with self._lock:
            if self._state == "idle":
                self._state = "done"
                self.error = "no slice was taken: " + (
                    "job {job} was taken but the window closed before its "
                    "slice began".format(**self.placed) if self.placed else
                    "no job was taken for the last; the last estimate was "
                    "h, L, job = {:.3f}, {:.3f}, {:.3f} s".format(
                        *self._shape) if self._shape else
                    "no finished job said where its loop lies, and the "
                    "window closed before the clock's slice")
        end = time.time() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.time()))
        if any(t.is_alive() for t in self._threads) and not self.error:
            self.error = "the profiler did not stop"
        if self.error:
            return None
        import trace_reduce

        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            self.error = "the profiler wrote no .xplane.pb"
            return None
        program = span_read.rows_between(self.slice_t0, self.slice_t1) or []
        t = time.time()
        out = trace_reduce.reduce_file(
            path, slice_t0=self.slice_t0,
            host_spans=list(SPANS) + [("jaxtlc:" + r["name"], r["t0"],
                                       r["t1"]) for r in program])
        self.cost.update(reduce_s=time.time() - t,
                         xplane_bytes=os.path.getsize(path))
        return out

    def report(self, trace):
        """The numbers that placed the slice and what it cost: a
        `correct: false` then says why."""
        def show(d):
            return ", ".join(f"{k}={v:.3f}" if isinstance(v, float)
                             else f"{k}={v}" for k, v in d.items())

        p = self.placed or dict(mode="the clock", start_s=self.start_s,
                                length_s=self.slice_s)
        at = (f"{self.slice_t0 - self._t0:.3f} s into the window, "
              f"{self.slice_t1 - self.slice_t0:.3f} s long"
              if self.slice_t1 else "not taken")
        self.log(f"trace: slice placed by {show(p)}; {at}")
        if trace is not None:
            self.log(f"trace: busy {trace['busy_s']:.4f} s of "
                     f"{trace['window_s']:.4f} s on {trace['n_devices']} "
                     f"device(s); cost {show(self.cost)}")


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


def log_jobs(records, open_loop: bool, log):
    """One line a job, after the window, in every run: where a job's
    seconds went (at the caller, the engine's own wall, and from the
    program's spans the host seconds before the loop, the build and the
    loop).  Open loop: the ten slowest jobs, with the server's stamps."""
    done = [r for r in records if r.get("done_t") is not None]
    if not done:
        return
    t0 = min(r["due_t"] for r in records)
    if open_loop:
        for r in sorted(done, key=lambda r: r["due_t"] - r["done_t"])[:10]:
            sv = r.get("server") or {}
            log(f"job {r['index']}: due {r['due_t'] - t0:.3f} s, "
                f"{1e3 * (r['done_t'] - r['due_t']):.1f} ms to its verdict"
                + "".join(f", {k[:-2]} +{1e3 * (sv[k] - r['due_t']):.1f}"
                          for k in ("submitted_t", "started_t", "finished_t")
                          if sv.get(k)) + " ms")
        return
    rows = span_read.rows_between(t0, max(r["done_t"] for r in done)) or []
    for r in done:
        mine = [x for x in rows if x["t0"] >= r["start_t"]
                and x["t1"] <= r["done_t"]]
        shape = span_read.job_shape(r["start_t"], r["done_t"], mine)
        build = span_read.seconds(mine, "build")
        wall = r.get("engine_wall_s")
        log(f"job {r['index']}: start {r['start_t'] - t0:.3f} s, "
            f"{r['done_t'] - r['start_t']:.3f} s at the caller, engine wall "
            + (f"{wall:.3f} s" if wall is not None else "unknown")
            + (f", h {shape[0]:.3f} s, build "
               + (f"{build:.3f}" if build is not None else "none")
               + f" s, loop {shape[1]:.3f} s" if shape else
               ", no spans"))


_COMPARED = re.compile(r"compare ([^:]+):.*?(\d+)(?: jobs(?: differ)?)?, "
                       r"(?:limit (\d+)|at least (\d+))$")


def compared_of(lines) -> dict:
    """gate.judge's `compare ...` lines as {name: {value, limit}}: each
    number compared beside its limit, for the result line."""
    out = {}
    for ln in lines:
        m = _COMPARED.match(ln)
        if m:
            name, value, limit, least = m.groups()
            out[name.replace(" ", "_")] = dict(
                value=int(value),
                limit=int(limit) if limit is not None
                else f"at least {least}")
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
            tracer = hooks = None
            if args.trace:
                warm = SPANS[-1][1:] if SPANS and getattr(
                    entry, "WARM_JOB_IS_WHOLE", True) else None
                tracer = Tracer(workdir, traffic, args.seconds, log, warm)
                hooks = (tracer.before_job, tracer.after_job)
                tracer.window_opens()
            records = loadgen.drive(
                lambda d: entry.run_job(handle, d, annotate),
                traffic, args.seed, args.seconds, hooks)
            window_compiles = ((meter.count - req0)
                               - (meter.cache_hits - hit0))
            log(f"window: {len(records)} jobs, "
                f"{meter.count - req0} compile requests, "
                f"{window_compiles} backend compiles")
            trace = tracer.finish() if tracer else None
            if tracer:
                tracer.report(trace)
                if tracer.error:
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
    log_jobs(records, traffic["loop"] == "open", log)
    compared = compared_of(verdict["lines"])

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
        busy_s = 0.0
        if trace is not None:
            device["busy_s"] = busy_s = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        compared["device_busy_s_in_the_slice"] = dict(
            value=busy_s, limit="more than 0")
        verdict["lines"].append(
            f"compare device busy seconds in the traced slice: {busy_s}, "
            "more than 0")
        if trace is None or trace["n_devices"] == 0 or busy_s <= 0:
            verdict["correct"] = False
            log("trace: no operation ran on the device in the slice")
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
    line["compared"] = compared  # last: each number beside its limit
    for ln in verdict["lines"]:
        log(ln)
    print("\n".join(f"bench: {ln}" for ln in verdict["lines"]),
          file=sys.stderr, flush=True)
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
