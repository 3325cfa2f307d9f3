"""Process-entry rules (jaxtlc.runtime, ISSUE 21): where the compile
cache lives, that CPU is used only when asked for, that chip_smoke.py
refuses to start off-TPU, and that the localhost pod drill cannot fight
over a chip.  No engine is built for those.

The kept engines (ISSUE 36, second half of the file): `aot_build(make,
key)` keeps what it built, process-wide.  The mechanism's own cases run
on a stub builder and compile nothing; the four keyed routes each build
one tiny engine at a geometry another test module already uses
(tests/test_resil.py, test_struct_resil.py, test_mesh_cell.py), so the
persistent compile cache answers them where those ran first.
conftest.py sets JAXTLC_DEBUG_DONATION=1, under which nothing is kept:
the `kept` fixture takes it away for the tests that want the cache."""

import importlib.util
import os
import threading
import time
import types

import jax
import numpy as np
import pytest

from jaxtlc import runtime
from jaxtlc.config import ModelConfig
from jaxtlc.obs import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path,
                                           cache_dir_restored):
    """JAX_COMPILATION_CACHE_DIR set: jax already has the directory and
    jaxtlc makes no jax_compilation_cache_dir update at all."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real(name, val))[1],
    )
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == before
    assert jax.config.jax_compilation_cache_dir == before
    assert "jax_compilation_cache_dir" not in updates
    # the zeroed persistence thresholds apply either way
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch,
                                                cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache") == \
        runtime.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.isdir(path) and "/.cache/" not in path
    assert runtime.enable_compile_cache() == path  # idempotent


def test_default_workers_without_accelerator_exits_1(monkeypatch, capsys):
    """JAX drops to CPU without a word when it finds no chip; unless CPU
    was asked for, that is exit 1 naming the two ways to ask."""
    from jaxtlc.cli import main

    # as if neither JAX_PLATFORMS=cpu nor jax.config asked for cpu
    monkeypatch.setattr(runtime, "cpu_requested",
                        lambda workers="": workers == "cpu")
    rc = main(["check", "specs/TwoPhase.toolbox/Model_1/MC.cfg"])
    cap = capsys.readouterr()
    assert rc == 1 and cap.out == ""
    assert "-workers cpu" in cap.err and "JAX_PLATFORMS=cpu" in cap.err
    assert runtime.require_platform("cpu") == "cpu"  # asked for: fine
    with pytest.raises(runtime.PlatformError):
        runtime.require_platform("tpu")


def test_fp_mesh_never_shrinks():
    assert runtime.fp_mesh(2).size == 2
    assert runtime.fp_mesh().size == len(jax.devices())
    with pytest.raises(runtime.PlatformError, match="9 devices requested"):
        runtime.fp_mesh(9)


def test_chip_smoke_refuses_cpu_before_any_leg(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def no_legs(devices):
        raise AssertionError("a leg ran on a non-TPU platform")

    monkeypatch.setattr(mod, "run_legs", no_legs)
    assert mod.main() == 2
    cap = capsys.readouterr()
    assert cap.out == ""  # no result line
    assert "platform=cpu" in cap.err and "not a TPU" in cap.err


def test_dist_spawn_forces_cpu_workers(monkeypatch, capsys):
    """--spawn is a localhost CPU drill: its workers get
    JAX_PLATFORMS=cpu whatever the launcher inherited (N processes
    cannot share one chip), and the output says "cpu pod"."""
    from jaxtlc.dist import __main__ as dist_main

    envs = []

    class FakeProc:
        returncode = 0

        def __init__(self, argv, env=None, **kw):
            envs.append(env)

        def communicate(self):
            return "POD_RESULT {}\n", None

        def poll(self):
            return 0

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(dist_main.subprocess, "Popen", FakeProc)
    assert dist_main.main(["--spawn", "3", "--ff"]) == 0
    assert len(envs) == 3
    assert all(e["JAX_PLATFORMS"] == "cpu" for e in envs)
    assert "cpu pod" in capsys.readouterr().out


# ---- the kept engines (ISSUE 36) -----------------------------------------

FF = ModelConfig(False, False)
# tests/test_resil.py's geometry, tests/test_mesh_cell.py's (per device),
# tests/test_struct_resil.py's
KW = dict(chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 14)
GEOM = dict(chunk=128, queue_capacity=1 << 11, fp_capacity=1 << 13)
STRUCT_CFG = "specs/TwoPhase.toolbox/Model_1/MC.cfg"
STRUCT_KW = dict(chunk=16, queue_capacity=1 << 8, fp_capacity=1 << 10)


def signature(r):
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())), r.outdegree)


@pytest.fixture()
def kept(monkeypatch):
    """The cache as a process outside the test suite has it: neither
    debug variable set, empty before and after."""
    monkeypatch.delenv("JAXTLC_DEBUG_DONATION", raising=False)
    monkeypatch.delenv("JAXTLC_DEBUG_SYM_LIE", raising=False)
    runtime.clear_engine_cache()
    yield runtime
    runtime.clear_engine_cache()


class _Compiled(str):
    """A stand-in executable: equal to its name, and (unlike a plain
    str) weakly referenceable, as obs.scopes registers what is built."""


class _FakeProgram:
    """What `aot_build` asks of a jitted program, compiling nothing."""

    def __init__(self, n):
        self.n = n

    def trace(self, template):
        return self

    def lower(self):
        return self

    def compile(self):
        return _Compiled("compiled-%d" % self.n)


@pytest.fixture()
def fake():
    """A `make` for `aot_build` that builds nothing: the pair it leads
    to names the build's ordinal; `fake.built` counts the calls."""
    def make():
        make.built += 1
        n = make.built
        return (lambda: "template-%d" % n), _FakeProgram(n)

    make.built = 0
    return make


@pytest.fixture(scope="module")
def mesh4():
    return runtime.fp_mesh(4)


@pytest.fixture(scope="module")
def struct_model():
    from jaxtlc.struct.loader import load

    return load(STRUCT_CFG)


def _stats():
    s = runtime.engine_cache_stats()
    return s["hits"], s["misses"], s["size"]


def _hand():
    from jaxtlc.resil import SupervisorOptions, check_supervised

    r = check_supervised(FF, opts=SupervisorOptions(
        ckpt_every=8, capture_fps=True), **KW).result
    return signature(r), [r.fp_table]


def _struct(model):
    from jaxtlc.resil import SupervisorOptions, check_supervised
    from jaxtlc.struct import cache
    from jaxtlc.struct.backend import struct_meta_config

    r = check_supervised(
        None, backend=cache.get_backend(model, check_deadlock=False),
        meta_config=struct_meta_config(model), check_deadlock=False,
        opts=SupervisorOptions(ckpt_every=2, capture_fps=True),
        **STRUCT_KW).result
    return signature(r), [r.fp_table]


def _sharded(mesh):
    from jaxtlc.resil import SupervisorOptions, check_sharded_supervised

    r = check_sharded_supervised(
        FF, mesh, opts=SupervisorOptions(ckpt_every=16), **GEOM).result
    return signature(r), [np.asarray(r.shard_distinct),
                          np.asarray(r.shard_generated)]


def _ckpt(tmp_path, n):
    """check_with_checkpoints; the table (and every other leaf of the
    final carry) is read back from the checkpoint it leaves."""
    from jaxtlc.engine.checkpoint import check_with_checkpoints

    path = str(tmp_path / f"ck{n}.npz")
    r = check_with_checkpoints(FF, ckpt_every=8, ckpt_path=path, **KW)
    with np.load(path) as z:
        return signature(r), [z[k] for k in sorted(z.files)
                              if k.startswith("leaf_")]


@pytest.mark.parametrize("route", ["hand", "struct", "sharded4", "ckpt"])
def test_second_check_of_a_key_is_a_hit_and_the_same_check(
        route, kept, tmp_path, request):
    def once(n):
        if route == "hand":
            return _hand()
        if route == "ckpt":
            return _ckpt(tmp_path, n)
        if route == "struct":
            return _struct(request.getfixturevalue("struct_model"))
        return _sharded(request.getfixturevalue("mesh4"))

    t = time.time()
    sig_miss, arrays_miss = once(0)
    assert _stats() == (0, 1, 1)
    sig_hit, arrays_hit = once(1)
    assert _stats() == (1, 1, 1)
    assert sig_hit == sig_miss and sig_miss[1] > 0
    assert len(arrays_hit) == len(arrays_miss) > 0
    for a, b in zip(arrays_miss, arrays_hit):
        np.testing.assert_array_equal(a, b)
    # the two checks' `build` spans say which was which
    said = [r.attrs["engine_cache"] for r in spans.snapshot(since=t)
            if r.name == "build"]
    assert said == ["miss", "hit"]


def _single(**over):
    from jaxtlc.resil.supervisor import SingleDeviceAdapter

    kw = dict(chunk=128, fp_index=3, seed=7, check_deadlock=True,
              obs_slots=0, deferred=False)
    kw.update(over)
    return SingleDeviceAdapter(kw.pop("cfg", FF), **kw)


def _fake_backend():
    return types.SimpleNamespace(coverage=None, reduce=None)


BASE = dict(queue_capacity=1 << 12, fp_capacity=1 << 14)
# a case a key field: (the adapter, params, ckpt_every) that differ from
# `_single()`, BASE, 8 in that one field
SINGLE_CASES = {
    "chunk": lambda: (_single(chunk=256), BASE, 8),
    "queue_capacity": lambda: (
        _single(), dict(BASE, queue_capacity=1 << 13), 8),
    "fp_capacity": lambda: (_single(), dict(BASE, fp_capacity=1 << 15), 8),
    "seed": lambda: (_single(seed=8), BASE, 8),
    "fp_index": lambda: (_single(fp_index=4), BASE, 8),
    "fp_highwater": lambda: (_single(fp_highwater=0.5), BASE, 8),
    "pipeline": lambda: (_single(pipeline=True), BASE, 8),
    "ckpt_every": lambda: (_single(), BASE, 16),
    "check_deadlock": lambda: (_single(check_deadlock=False), BASE, 8),
    "obs_slots": lambda: (_single(obs_slots=64), BASE, 8),
    "deferred": lambda: (_single(deferred=True), BASE, 8),
    "config": lambda: (_single(cfg=ModelConfig(True, False)), BASE, 8),
}


def _with(adapter, **attrs):
    for k, v in attrs.items():
        setattr(adapter, k, v)
    return adapter


@pytest.mark.parametrize("field", sorted(SINGLE_CASES) + [
    "coverage", "symmetry", "por", "another-backend", "another-mesh",
    "route_factor", "route-kind"])
def test_changing_one_key_field_misses(field, kept, fake, mesh4,
                                       monkeypatch):
    """Through the adapters' own `build`, which states the key; what it
    would build is swapped for the fake: the base is a miss, the base
    again a hit, the changed field a miss."""
    from jaxtlc.resil import supervisor
    from jaxtlc.resil.supervisor import ShardedAdapter

    monkeypatch.setattr(
        supervisor, "aot_build",
        lambda make, key=None: runtime.aot_build(fake, key=key))

    backend = _fake_backend()
    if field in SINGLE_CASES:
        base = (_single(), BASE, 8)
        other = SINGLE_CASES[field]()
    elif field in ("coverage", "symmetry", "por"):
        # the flags an adapter derives from its backend, one at a time
        # on the SAME backend object
        base = (_single(backend=backend), BASE, 8)
        other = (_with(_single(backend=backend), **{field: True}), BASE, 8)
    elif field == "another-backend":
        base = (_single(backend=backend), BASE, 8)
        other = (_single(backend=_fake_backend()), BASE, 8)
    else:
        mesh_params = dict(BASE, route_factor=2.0)
        base = (ShardedAdapter(FF, mesh4, backend=backend), mesh_params, 8)
        other = {
            "another-mesh": lambda: (ShardedAdapter(
                FF, runtime.fp_mesh(2), backend=backend), mesh_params, 8),
            "route_factor": lambda: (
                ShardedAdapter(FF, mesh4, backend=backend),
                dict(mesh_params, route_factor=3.0), 8),
            # one backend, one geometry, the other adapter
            "route-kind": lambda: (_single(
                backend=backend, chunk=512, deferred=False), BASE, 8),
        }[field]()
    for n, (adapter, params, every) in enumerate([base, base, other]):
        adapter.build(dict(params), every)
        assert fake.built == (1, 1, 2)[n], field
    assert _stats() == (1, 2, 2)


def test_regrow_builds_the_new_geometry_and_leaves_the_old(kept):
    """fp 2^13 cannot take the FF corner's 8,203 states under the
    highwater mark: one regrow to tests/test_resil.py's 2^14.  Both
    geometries are kept, and the same check again builds neither."""
    from jaxtlc.resil import SupervisorOptions, check_supervised

    def once():
        return check_supervised(
            FF, chunk=128, queue_capacity=1 << 12, fp_capacity=1 << 13,
            opts=SupervisorOptions(ckpt_every=8))

    first = once()
    assert first.regrows == 1 and first.params["fp_capacity"] == 1 << 14
    assert _stats() == (0, 2, 2)
    again = once()
    assert again.regrows == 1
    assert _stats() == (2, 2, 2)
    assert signature(again.result) == signature(first.result)
    assert (first.result.generated, first.result.distinct,
            first.result.depth) == (17020, 8203, 109)


@pytest.mark.parametrize("var, value", [("JAXTLC_DEBUG_SYM_LIE", "1"),
                                        ("JAXTLC_DEBUG_DONATION", "1")])
def test_a_build_under_a_debug_variable_is_not_kept(var, value, kept,
                                                    fake,
                                                    monkeypatch):
    monkeypatch.setenv(var, value)
    t = time.time()
    for _ in range(2):
        runtime.aot_build(fake, key=("k",))
    assert fake.built == 2 and _stats() == (0, 0, 0)
    assert [r.attrs for r in spans.snapshot(since=t)
            if r.name == "build"] == [{"engine_cache": "off"}] * 2


def test_unkeyed_build_is_as_before(kept, fake):
    assert runtime.aot_build(fake) == ("template-1", "compiled-1")
    assert runtime.aot_build(fake) == ("template-2", "compiled-2")
    assert _stats() == (0, 0, 0)


def test_least_recently_used_goes_at_the_cap(kept, fake):
    cap = runtime.engine_cache_stats()["cap"]
    assert cap == runtime.ENGINE_CACHE_CAP >= 2
    pairs = [runtime.aot_build(fake, key=(i,)) for i in range(cap)]
    assert runtime.aot_build(fake, key=(0,)) is pairs[0]  # touched
    runtime.aot_build(fake, key=("new",))  # pushes (1,) out
    s = runtime.engine_cache_stats()
    assert (s["size"], s["evictions"]) == (cap, 1)
    n = fake.built
    assert runtime.aot_build(fake, key=(0,)) is pairs[0]
    assert fake.built == n  # (0,) was kept
    runtime.aot_build(fake, key=(1,))
    assert fake.built == n + 1  # (1,) was not


def test_two_threads_asking_one_key_build_once(kept, fake):
    started, release, got = threading.Event(), threading.Event(), []

    def slow_make():
        started.set()
        assert release.wait(10.0)
        return fake()

    threads = [threading.Thread(target=lambda: got.append(
        runtime.aot_build(slow_make, key=("one",)))) for _ in range(4)]
    threads[0].start()
    assert started.wait(10.0)
    for t in threads[1:]:
        t.start()
    time.sleep(0.05)  # the three wait for the first, none builds
    assert fake.built == 0 and _stats() == (0, 1, 0)
    release.set()
    for t in threads:
        t.join(10.0)
        assert not t.is_alive()
    assert fake.built == 1 and len(got) == 4
    assert all(pair is got[0] for pair in got)
    assert _stats() == (3, 1, 1)


def test_a_build_that_raises_keeps_nothing(kept, fake):
    def broken():
        raise RuntimeError("no such lane")

    with pytest.raises(RuntimeError, match="no such lane"):
        runtime.aot_build(broken, key=("k",))
    assert _stats() == (0, 1, 0)
    # and leaves the key free for the next builder
    assert runtime.aot_build(fake, key=("k",)) == ("template-1",
                                                   "compiled-1")
    assert _stats() == (0, 2, 1)


def test_a_hit_still_closes_the_build_spans(kept, fake):
    runtime.aot_build(fake, key=("k",))
    t = time.time()
    with spans.job("hit-job") as job:
        runtime.aot_build(fake, key=("k",))
        event = spans.journal_event()
    rows = {r.name: r for r in job.rows}
    assert sorted(rows) == ["build", "build.compile", "build.lower",
                            "build.trace"]
    build = rows["build"]
    assert build.attrs == {"engine_cache": "hit"}
    assert rows["build.compile"].attrs["requests"] == 0
    for name in ("build.trace", "build.lower", "build.compile"):
        assert rows[name].parent == build.id
        assert build.t0 <= rows[name].t0 <= rows[name].t1 <= build.t1
    assert build.t1 - build.t0 < 0.05 and build.t0 >= t
    # the attr reaches the journal with the job's `spans` event
    names = [row[0] for row in event["rows"]]
    assert event["attrs"][str(names.index("build"))] == {
        "engine_cache": "hit"}
    assert event["attrs"][str(names.index("build.compile"))][
        "requests"] == 0


@pytest.mark.parametrize("where", ["segment", "regrow-probe"])
def test_oom_drops_the_other_kept_engines_before_a_rung(where, kept,
                                                        fake):
    """A device out-of-memory while engines are kept - a segment that
    dies of RESOURCE_EXHAUSTED, or a regrow's allocation probe denied:
    all but the running engine go and the step is tried again, before
    the ladder takes a rung."""
    from jaxtlc.engine import checkpoint as ck
    from jaxtlc.engine.bfs import VIOL_FPSET_FULL, CheckResult
    from jaxtlc.resil import FaultPlan, SupervisorOptions, supervise
    from jaxtlc.resil.faults import AllocDeniedFault

    class Adapter:
        kind = "stub"
        GEOM_KEYS = ("fp_capacity",)
        FIXED_KEYS = ("format",)
        attempts = 0

        def build(self, params, ckpt_every):
            def seg(carry):
                self.attempts += 1
                if where == "segment" and self.attempts == 1:
                    raise AllocDeniedFault("segment arena exhausted")
                return dict(carry, cap=params["fp_capacity"])

            # kept, as a real adapter's: the pair's executable is `seg`
            program = _FakeProgram(0)
            program.compile = lambda: seg
            return runtime.aot_build(
                lambda: ((lambda: {"x": np.zeros(2), "cap": 0}), program),
                key=("running", params["fp_capacity"]))

        def meta(self, params):
            return {"format": ck.FORMAT_VERSION, **params}

        def viol(self, carry):
            # the table of 8 slots is "full": one regrow
            return VIOL_FPSET_FULL if (
                where == "regrow-probe" and carry["cap"] == 8) else 0

        def done(self, carry):
            return self.attempts >= 2

        def progress(self, carry):
            return (0, 0, 0, 0)

        def migrate(self, carry, old, new):
            return carry

        def result(self, carry, wall, segments, params):
            return CheckResult(0, 0, 0, 0, 0, "none", np.zeros(1), -1, {},
                               {}, wall, segments)

    runtime.aot_build(fake, key=("other", 1))
    runtime.aot_build(fake, key=("other", 2))
    adapter, events = Adapter(), []
    sr = supervise(adapter, {"fp_capacity": 8}, SupervisorOptions(
        faults=FaultPlan.parse("alloc_fail@1"), spill="off",
        on_event=lambda k, i: events.append((k, i))))
    assert adapter.attempts == 2 and not sr.exhausted and sr.shrinks == 0
    s = runtime.engine_cache_stats()
    degrades = [(i["rung"], i["action"]) for k, i in events
                if k == "degrade"]
    if where == "segment":
        assert degrades == [("oom", "drop-kept-engines")]
        assert (sr.regrows, s["size"], s["evictions"]) == (0, 1, 2)
    else:
        # the second probe was granted: the regrow went ahead, and the
        # engine that was running stays beside the regrown one
        assert degrades == [] and sr.params["fp_capacity"] == 16
        assert (sr.regrows, s["size"], s["evictions"]) == (1, 2, 2)
    assert [i for k, i in events if k == "final"][-1]["verdict"] == "ok"


def test_pool_runner_builds_unkeyed(monkeypatch, struct_model):
    """serve/pool.py's whole-run program takes a fresh carry a job and
    the pool's own table keeps the executable: no key."""
    from jaxtlc.serve import pool

    asked = []

    def record(make, key=None):
        asked.append(key)
        return None, None

    monkeypatch.setattr(pool, "aot_build", record)
    pool._SingleRunner(struct_model, 16, 1 << 8, 1 << 10, 0, 0, False,
                       False, 0)
    assert asked == [None]


def test_stats_are_republished_on_the_pool_endpoint(kept, fake):
    from jaxtlc.serve.pool import EnginePool

    runtime.aot_build(fake, key=("k",))
    runtime.aot_build(fake, key=("k",))
    got = EnginePool(capacity=1).stats()["engines"]
    assert got == runtime.engine_cache_stats() == dict(
        hits=1, misses=1, evictions=0, size=1,
        cap=runtime.ENGINE_CACHE_CAP)
