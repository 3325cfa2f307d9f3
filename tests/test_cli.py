"""End-to-end CLI tests (E14): TLC invocation contract, structured log
protocol, exit codes, counterexample trace printing, checkpoint flags."""

import os

import pytest

from jaxtlc.cli import main

MC_TLA = """---- MODULE MC ----
EXTENDS KubeAPI, TLC

\\* CONSTANT definitions @modelParameterConstants:1REQUESTS_CAN_FAIL
const_fail ==
FALSE

\\* CONSTANT definitions @modelParameterConstants:2REQUESTS_CAN_TIMEOUT
const_to ==
FALSE
====
"""

MC_CFG = """CONSTANT defaultInitValue = defaultInitValue
CONSTANT REQUESTS_CAN_FAIL <- const_fail
CONSTANT REQUESTS_CAN_TIMEOUT <- const_to
SPECIFICATION Spec
INVARIANT TypeOK
INVARIANT OnlyOneVersion
"""


@pytest.fixture()
def model_dir(tmp_path):
    d = tmp_path / "Model_FF"
    d.mkdir()
    (d / "MC.tla").write_text(MC_TLA)
    (d / "MC.cfg").write_text(MC_CFG)
    return d


SMALL = ["-chunk", "128", "-qcap", "4096", "-fpcap", "16384"]


def test_cli_clean_run_exit0_and_counts(model_dir, capsys):
    import jax

    from jaxtlc.runtime import DEFAULT_CACHE_DIR

    rc = main(["check", str(model_dir / "MC.cfg"), "-noTool"] + SMALL)
    out = capsys.readouterr().out
    assert rc == 0
    assert "17020" in out and "8203" in out  # FF corner final counts
    assert "Model checking completed. No error has been found" in out
    # the banner names the platform jax resolved, not the -workers text
    assert "with cpu workers on" in out
    # the HAND path persists its compiles too (run_check switches the
    # compile cache on for every engine): where the caller put it, else
    # the fixed in-checkout directory
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == want
    assert any(os.scandir(want)), "no persisted XLA cache entries"


def test_cli_sharded_beyond_device_count_exit1(model_dir, capsys):
    """-sharded N with fewer than N devices is an error, never a
    smaller mesh (conftest provides 8 virtual devices)."""
    rc = main(["check", str(model_dir / "MC.cfg"), "-noTool",
               "-sharded", "16"] + SMALL)
    cap = capsys.readouterr()
    assert rc == 1
    assert "16 devices requested but JAX reports 8" in cap.err
    assert "states generated" not in cap.out  # no engine ran


def test_cli_crashing_analyze_audit_exit1(model_dir, capsys, monkeypatch):
    """A crash inside the deep audit that -analyze asked for by name
    fails the run (exit 1, -no-preflight named as the override); it is
    not reported as "skipped" with exit 0."""
    import jaxtlc.analysis.preflight as pf

    def boom(*a, **kw):
        raise RuntimeError("audit blew up")

    monkeypatch.setattr(pf, "preflight_kubeapi", boom)
    rc = main(["check", str(model_dir / "MC.cfg"), "-noTool",
               "-analyze"] + SMALL)
    out = capsys.readouterr().out
    assert rc == 1
    assert "crashed: RuntimeError: audit blew up" in out
    assert "-no-preflight" in out
    assert "skipped" not in out and "states generated" not in out


def test_cli_tool_mode_framing(model_dir, capsys):
    rc = main(["check", str(model_dir / "MC.cfg")] + SMALL)
    out = capsys.readouterr().out
    assert rc == 0
    assert "@!@!@STARTMSG 2193" in out  # success + collision estimate
    assert "@!@!@STARTMSG 2199" in out  # final counts
    assert "@!@!@ENDMSG" in out


def test_cli_violation_exit12_and_trace(model_dir, capsys):
    rc = main(
        ["check", str(model_dir / "MC.cfg"), "-noTool", "-mutation",
         "delete_noop"] + SMALL
    )
    out = capsys.readouterr().out
    assert rc == 12
    assert "assert" in out.lower()
    # a trace of TLA-syntax states with PlusCal action labels
    assert "/\\ apiState" in out
    assert "State 1" in out


def test_cli_disk_fpset_engine(model_dir, capsys):
    rc = main(
        ["check", str(model_dir / "MC.cfg"), "-noTool", "-fpset",
         "DiskFPSet", "-chunk", "256"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "17020" in out and "8203" in out


def test_cli_liveness_exit13_and_lasso(model_dir, capsys):
    rc = main(
        ["check", str(model_dir / "MC.cfg"), "-noTool", "-liveness"] + SMALL
    )
    out = capsys.readouterr().out
    assert rc == 13  # TLC liveness-violation exit convention
    assert "Temporal properties were violated" in out
    assert "form a cycle" in out
    assert "/\\ apiState" in out
    # a liveness-violating run must not also claim success
    assert "No error has been found" not in out


def test_cli_checkpoint_and_recover(model_dir, tmp_path, capsys):
    ck = str(tmp_path / "run.ckpt.npz")
    rc = main(
        ["check", str(model_dir / "MC.cfg"), "-noTool", "-checkpoint", ck,
         "-checkpointevery", "16"] + SMALL
    )
    capsys.readouterr()
    assert rc == 0
    assert os.path.exists(ck)
    # recover from the final checkpoint: immediately complete, same verdict
    rc = main(
        ["check", str(model_dir / "MC.cfg"), "-noTool", "-checkpoint", ck,
         "-recover"] + SMALL
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "17020" in out and "8203" in out
