"""Fixtures of the harness's own tests (run: python -m pytest benchmark/tests).

They run on the CPU at tiny sizes.  The platform gate is stubbed HERE, in
the test: run.py has no CPU mode and gets none.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAXTLC_ARTIFACT_CACHE"] = "off"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import control  # noqa: E402  (also points the compile cache at the repo's)

BENCH, REPO = control.BENCH, control.REPO

FF_PINS = dict(generated=17020, distinct=8203, depth=109)


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


class Checkout:
    """A temp copy of what a run needs: benchmark/ (copied, so a test may
    add files to it), BENCHMARK.json, and links to the program."""

    def __init__(self, root):
        self.root = control.make_checkout(str(root))
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self._n = 0

    def path(self, *parts):
        return os.path.join(self.root, "benchmark", *parts)

    def save(self):
        write_json(os.path.join(self.root, "BENCHMARK.json"), self.bench)

    def add_config(self, name, config):
        config = dict(config, name=name)
        write_json(self.path("configs", name + ".json"), config)
        self.bench["configs"].append(dict(
            name=name, source="test", reduced=[], why="test",
            file=f"benchmark/configs/{name}.json"))

    def add_cell(self, name, config, traffic, e2e, layers=()):
        self.bench["workloads"].append(dict(
            name=name, config=config, traffic=traffic, chips=1,
            why="test"))
        for group, names in (("end_to_end", e2e), ("per_layer", layers)):
            for m in self.bench[group]:
                if m["name"] in names and "workloads" in m:
                    m["workloads"].append(name)
        self.save()

    def load_run(self):
        """This checkout's own run.py, with the platform gate stubbed:
        any device JAX has counts, and its kind is put in the peaks."""
        self._n += 1
        run = control.load_run(self.root, str(self._n))

        def any_device(chips, peaks):
            import jax

            return jax.devices()

        run.device_gate = any_device
        return run

    def run(self, workload, seed=2147483659, seconds=2.0, trace=0,
            run=None):
        return control.run_once(run or self.load_run(), workload, seed,
                                seconds, trace)


@pytest.fixture
def checkout(tmp_path):
    co = Checkout(tmp_path / "co")
    yield co
    sys.path[:] = [p for p in sys.path if not p.startswith(co.root)]
    for mod in ("gate", "loadgen", "stats", "trace_reduce"):
        sys.modules.pop(mod, None)


def ff_model_dir(root):
    """KubeAPI Model_1 with both fault constants FALSE (8,203 states):
    the hand frontend's fast corner."""
    d = os.path.join(root, "specs_ff")
    os.makedirs(d, exist_ok=True)
    src = os.path.join(REPO, "specs", "KubeAPI.toolbox", "Model_1")
    shutil.copy(os.path.join(src, "MC.cfg"), d)
    with open(os.path.join(src, "MC.tla")) as f:
        tla = f.read()
    with open(os.path.join(d, "MC.tla"), "w") as f:
        f.write(tla.replace("\nTRUE\n", "\nFALSE\n"))
    return d


def tiny_ff_config(entry, root):
    base = dict(
        source="test", reduced=[], assumed={}, guarantees={},
        env={"JAXTLC_ARTIFACT_CACHE": "off"}, reference="kubeapi",
        deployment=dict(spec="KubeAPI", scaling=None,
                        REQUESTS_CAN_FAIL=False,
                        REQUESTS_CAN_TIMEOUT=False),
        pins=dict(FF_PINS), engines=["single"])
    if entry == "check_with_checkpoints":
        return dict(base, entry=entry, journal=False, request=dict(
            make_scaled=dict(n_reconcilers=1, n_binders=1,
                             requests_can_fail=False,
                             requests_can_timeout=False),
            chunk=256, queue_capacity=1 << 12, fp_capacity=1 << 15,
            ckpt_every=16))
    return dict(base, entry="run_check", journal=True, request=dict(
        config=os.path.join(ff_model_dir(root), "MC.cfg"),
        frontend="hand", noTool=True, chunk=256, qcap=1 << 12,
        fpcap=1 << 15))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: pure-Python reference runs of tens of seconds")
