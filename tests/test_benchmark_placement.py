"""benchmark/tests/test_placement.py's cases, run by tier-1 (ISSUE 34's
left-over, ISSUE 36): the rule that places a traced run's slice
(benchmark/placement.py) is what every traced run of the check rests
on, and tier-1 does not collect benchmark/tests.  Imports only: the
cases stay where they are and nothing under benchmark/ knows of this
file.

That module says `from conftest import BENCH`, meaning
benchmark/tests/conftest.py; under tier-1 the name `conftest` is
tests/conftest.py, so it is loaded here with a stand-in of that one
name around the import, and `sys.path` is left as it was found."""

import importlib.util
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load():
    stand_in = types.ModuleType("conftest")
    stand_in.BENCH = BENCH
    ours, path = sys.modules.get("conftest"), list(sys.path)
    sys.modules["conftest"] = stand_in
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_tests_test_placement",
            os.path.join(BENCH, "tests", "test_placement.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path[:] = path
        if ours is not None:
            sys.modules["conftest"] = ours
        else:
            del sys.modules["conftest"]
    return module


_cases = _load()
globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})


def test_every_case_of_the_harness_file_is_collected_here():
    theirs = {n for n in vars(_cases) if n.startswith("test_")}
    assert theirs and theirs <= set(globals())
