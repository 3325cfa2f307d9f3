"""Test environment: force CPU with 8 virtual devices.

Tests never grab a chip and always see an 8-device mesh so multi-chip
sharding paths are exercised exactly as the driver's dryrun does.  CPU is
ASKED for here (JAX_PLATFORMS=cpu, set before jax is imported): the entry
points refuse to drop to CPU unasked (jaxtlc.runtime.require_platform).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# make use-after-donate loud on CPU: engines built with donate=True
# poison their input carry after every run/step call, so feeding the
# same carry twice fails HERE instead of corrupting a TPU run
# (jaxtlc.analysis.donation; ISSUE 6 satellite)
os.environ.setdefault("JAXTLC_DEBUG_DONATION", "1")

# incremental re-checking stays OFF by default under test: a shared
# ~/.cache store would let one test's verdict artifact short-circuit
# another's engine run (the warm-pool and parity pins depend on the
# engines actually executing).  tests/test_artifacts.py and the tool
# tinies opt IN against tmp-dir stores via struct.artifacts.configure
os.environ.setdefault("JAXTLC_ARTIFACT_CACHE", "off")

os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-state-space runs (minutes on 1 CPU core)"
    )


# -- collection errors are fatal, never silently-green (ISSUE 3) -----------
#
# Tier-1 runs with --continue-on-collection-errors so one broken module
# doesn't hide every other module's results, but an ImportError must
# still sink the run LOUDLY: a module that fails to collect contributes
# zero failing tests, and a green-looking run with a quietly-skipped
# module shipped a never-executed exit-criterion test once already
# (test_struct_engine's package-relative import).  Collect every failed
# collection report and abort the session after collection finishes.

_COLLECT_ERRORS = []


def pytest_collectreport(report):
    if report.failed:
        _COLLECT_ERRORS.append(str(report.nodeid or report.fspath))


def pytest_collection_finish(session):
    if _COLLECT_ERRORS:
        raise pytest.UsageError(
            "test collection failed (a broken import must never ship as "
            "silently-skipped green): " + ", ".join(_COLLECT_ERRORS)
        )
