"""Process-entry rules (jaxtlc.runtime, ISSUE 21): where the compile
cache lives, that CPU is used only when asked for, that chip_smoke.py
refuses to start off-TPU, and that the localhost pod drill cannot fight
over a chip.  No engine is built here."""

import importlib.util
import os

import jax
import pytest

from jaxtlc import runtime

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
