"""Run a control of benchmark/controls.json: one cell with one guarantee
relaxed, through the benchmark's own run.py, and see `correct` come out
false.

    python3 benchmark/tests/control.py --workload <cell> --control <name> \
        --seeds 11,12,13 --seconds 10 [--sound]

It copies the benchmark into a temp checkout beside links to the
program, applies the control (a data edit of the configuration's copy,
or the `fp32` patch underneath the engine), and calls that copy's
run.main once per seed in this one process, so the compiles are shared.
With --sound it applies nothing: the same path must then say `correct:
true`.  On the chip this is how the controls were read at the cells' own
sizes; benchmark/tests/test_harness.py drives the same functions on the
CPU at tiny sizes.  Exit code 0 = every run came out as expected.
"""

from __future__ import annotations

import argparse
import importlib.util
import io
import json
import os
import shutil
import sys
import tempfile
from contextlib import redirect_stdout

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
# the temp checkout's own .jax_cache would start cold every time (the
# directory is part of the key): share the real checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(REPO, ".jax_cache"))


def make_checkout(root: str) -> str:
    """benchmark/ copied (so a control may edit its copy), BENCHMARK.json
    copied, the program linked."""
    os.makedirs(root, exist_ok=True)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name in ("jaxtlc", "specs"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    return root


def edit_config(root: str, config_file: str, edits: dict) -> None:
    """Apply {"a.b": value} edits (None deletes) to a config's copy."""
    path = os.path.join(root, config_file)
    with open(path) as f:
        config = json.load(f)
    for dotted, value in edits.items():
        node = config
        *parents, leaf = dotted.split(".")
        for k in parents:
            node = node.setdefault(k, {})
        if value is None:
            node.pop(leaf, None)
            os.environ.pop(leaf, None)
        else:
            node[leaf] = value
    with open(path, "w") as f:
        json.dump(config, f, indent=1)


def patch_fp32():
    """Narrow every fingerprint to 32 bits underneath the engine: the
    high word becomes a function (murmur3's 32-bit finalizer) of the low
    one, so two states are told apart by the low word alone.  On the chip
    this lost nearly everything (6,111 of 9,942,722 states), and
    `hi = lo ^ constant` lost exactly the same: the loss is the low
    word's own.  A Rabin fingerprint is GF(2)-linear in the state's bits,
    so structured states that differ by a vector in the 32-bit map's
    kernel collide wholesale.  Returns the undo."""
    import jax.numpy as jnp

    from jaxtlc.engine import backend

    orig = backend.fp64_words_mxu

    def narrowed(*a, **kw):
        lo, _ = orig(*a, **kw)
        h = lo ^ (lo >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        return lo, h ^ (h >> 16)

    backend.fp64_words_mxu = narrowed

    def undo():
        backend.fp64_words_mxu = orig

    return undo


def patch_generators_only():
    """The orbit tournament over the permutations the cfg's SYMMETRY set
    LISTS (those that move one of its constant sets and fix the others)
    and not over the group they generate: engine.reduce.ReducePlan keeps
    only those programs.  A state whose least image needs a product of
    two listed permutations then keeps two representatives: more
    "orbits" than the pin.  Returns the undo."""
    import itertools

    from jaxtlc.engine import reduce

    orig = reduce.ReducePlan.__init__

    def init(self, cdc, sym_sets, lie=None):
        orig(self, cdc, sym_sets, lie)
        bases = [tuple(sorted(a)) for a in self.sym_sets.values()]
        combos = list(itertools.product(
            *[list(itertools.permutations(b)) for b in bases]))[1:]
        keep = [k for k, combo in enumerate(combos)
                if sum(p != b for p, b in zip(combo, bases)) == 1]
        self.programs = [self.programs[k] for k in keep]
        self.form = reduce._array_form(self.programs, cdc.n_fields)

    reduce.ReducePlan.__init__ = init

    def undo():
        reduce.ReducePlan.__init__ = orig

    return undo


PATCHES = {"fp32": patch_fp32, "generators-only": patch_generators_only}


def load_run(root: str, tag: str = "control"):
    for mod in ("gate", "loadgen", "stats", "trace_reduce"):
        sys.modules.pop(mod, None)
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{tag}", os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def run_once(run, workload: str, seed: int, seconds: float, trace: int = 0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    text = out.getvalue()
    line = json.loads(text.strip().splitlines()[-1]) if rc == 0 else None
    return rc, line, text


def apply_control(root: str, workload: str, name: str):
    """Returns the patch's undo callable, or None for a data edit."""
    with open(os.path.join(root, "benchmark", "controls.json")) as f:
        control = json.load(f)["controls"][name]
    if workload not in control["cells"]:
        raise SystemExit(f"control {name!r} is not for {workload!r}")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    if control.get("edit"):
        edit_config(root, conf["file"], control["edit"])
    if control.get("patch"):
        sys.path.insert(0, root)
        return PATCHES[control["patch"]]()
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--control", default="")
    p.add_argument("--sound", action="store_true")
    p.add_argument("--seeds", default="11,12,13")
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    want = bool(args.sound)
    tmp = tempfile.mkdtemp(prefix="jaxtlc-control-")
    undo = None
    ok = True
    try:
        root = make_checkout(os.path.join(tmp, "co"))
        if not args.sound:
            undo = apply_control(root, args.workload, args.control)
        run = load_run(root)
        for seed in [int(s) for s in args.seeds.split(",")]:
            rc, line, text = run_once(run, args.workload, seed,
                                      args.seconds)
            got = None if line is None else line["correct"]
            why = [ln for ln in text.splitlines()
                   if ln.startswith("bench: compare")
                   and " 0 jobs, limit 0" not in ln
                   and "worst difference 0," not in ln
                   and "0 jobs differ" not in ln][:8]
            why += [ln[:400] for ln in text.splitlines()
                    if ln.startswith("bench: job ")][:2]
            print(json.dumps(dict(
                workload=args.workload,
                control=args.control or "sound", seed=seed, rc=rc,
                correct=got, attempted=line and line["attempted"],
                failed=line and line["failed"], expected=want,
                readings=why)), flush=True)
            ok = ok and rc == 0 and got is want
    finally:
        if undo is not None:
            undo()
        shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
