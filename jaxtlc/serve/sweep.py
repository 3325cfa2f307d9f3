"""Vmapped constant-config sweeps: one dispatch checks K models.

Real verification practice runs a PORTFOLIO of small models per spec -
the same module under many MC.cfg constant overrides (PAPER.md §L4's
configuration layer; the TLA+ Trifecta workflow in PAPERS.md runs
dozens per proof effort).  Checking them one at a time wastes both the
compile (each override bakes new literals into the step) and the
device (a tiny model leaves the chip idle).  This module batches the
override layer itself:

* **Swept constants become state fields.**  `sweep_backend` compiles
  the module ONCE with each swept CONSTANT promoted to a read-only
  codec field (LaneCompiler `sweep_vars`): expressions read the value
  from the state vector at runtime, every lane passes it through
  verbatim, and each configuration's Init seeds the field with its
  value.  Within one run the field never changes, so a config's state
  graph is isomorphic to the baked-constant run's - verdict, depth and
  every generated/distinct/per-action counter are IDENTICAL numbers
  (fingerprints differ: the encoding carries the extra field).

* **The config axis vmaps.**  K per-config carries (one `init_fn`
  seeding each, through the production packing/fpset/init-invariant
  path) stack into one batched carry and `vmap(run_fn)` drives all K
  BFS loops in a single device dispatch.  jax's batched while_loop
  freezes each lane at its own fixpoint, so every lane's final carry
  is bit-for-bit what a sequential run of the same compiled engine
  produces (`run_sequential` is that baseline; tests pin the equality
  down to the fpset table words).

Supported sweep class: integer scalar CONSTANTs used as VALUES (guards,
arithmetic, comparisons).  A constant that determines shapes - set
universes, quantifier domains, sequence caps - cannot ride a state
field; the compiler then needs a static value and raises CompileError,
loudly, at class-build time (never a silent misrun).  Load the anchor
model with each swept constant at its domain MAX (`load_anchored`) so
the inferred integer ranges cover the whole class; a config whose
values escape the anchored ranges halts with the codec range trap.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.backend import SpecBackend
from ..engine.bfs import (
    CheckResult,
    make_backend_engine,
    result_from_carry,
)
from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
from ..struct.backend import struct_viol_names
from ..struct.codec import StructCodec
from ..struct.compile import LaneCompiler
from ..struct.loader import StructModel, load
from ..struct.shapes import SInt, infer_shapes, typeok_hints

DEFAULT_WIDTH = 4  # configs per batched dispatch (pad-to-width)


class SweepError(ValueError):
    pass


def load_anchored(cfg_path: str,
                  params: Dict[str, Tuple[int, int]],
                  const_overrides: Optional[Dict[str, object]] = None,
                  ) -> StructModel:
    """Load the model with every swept constant at its domain MAX (the
    shape anchor: inferred integer ranges must cover the class).

    const_overrides carries a job's FIXED (non-swept) constants: they
    bake into the anchor like any cfg value, so the model's digest,
    canonical constants - and therefore `class_key` and every
    `config_inits` fallback - all reflect them.  Swept names in the
    dict are ignored (the anchor pins those to the domain max)."""
    overrides = {k: v for k, v in (const_overrides or {}).items()
                 if k not in params}
    overrides.update({c: int(hi) for c, (_lo, hi) in params.items()})
    return load(cfg_path, const_overrides=overrides)


def class_key(model: StructModel,
              params: Dict[str, Tuple[int, int]]) -> tuple:
    """The constants-CLASS cache key: spec digest + canonical constants
    WITHOUT the swept names + their domains.  Every configuration of
    the class maps to the same key, which is the whole point - the
    EnginePool holds one warm engine per class, not per config."""
    from ..struct.backend import canonical_constants

    consts = canonical_constants(model)
    for c in params:
        consts.pop(c, None)
    return (
        model.source_digest,
        tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in consts.items()
        )),
        tuple(model.invariants),
        tuple((c, int(lo), int(hi))
              for c, (lo, hi) in sorted(params.items())),
    )


def sweep_backend(model: StructModel,
                  params: Dict[str, Tuple[int, int]],
                  check_deadlock: bool = True) -> SpecBackend:
    """Compile `model` with the swept constants as runtime state fields
    - the constants-class step every configuration shares."""
    system = model.system
    names = tuple(sorted(params))
    for c in names:
        if c not in model.constants:
            raise SweepError(f"swept name {c!r} is not a CONSTANT")
        if not isinstance(model.constants[c], int) or isinstance(
            model.constants[c], bool
        ):
            raise SweepError(
                f"swept constant {c!r} must be an integer scalar, "
                f"got {model.constants[c]!r}"
            )
        lo, hi = params[c]
        if not (lo <= model.constants[c] <= hi):
            raise SweepError(
                f"anchor value {model.constants[c]} of {c!r} outside "
                f"its domain [{lo}, {hi}] (load the anchor model at "
                "the domain max: load_anchored)"
            )
    hints = typeok_hints(system.ev, model.invariants, system.variables)
    var_shapes = infer_shapes(system.ev, system.variables,
                              system.init_ast, system.next_ast,
                              hints=hints)
    for c in names:
        lo, hi = params[c]
        var_shapes[c] = SInt(int(lo), int(hi))
    ext_vars = tuple(system.variables) + names
    cdc = StructCodec(ext_vars, var_shapes)
    compiler = LaneCompiler(system.ev, ext_vars, var_shapes, cdc,
                            sweep_vars=frozenset(names))
    batch_step = compiler.build_step(system.next_ast)
    inv_fns = [
        compiler.build_invariant(ast) for ast in model.invariants.values()
    ]
    F = cdc.n_fields

    jax.eval_shape(batch_step, jax.ShapeDtypeStruct((1, F), jnp.int32))
    labels: List[str] = list(compiler.labels)
    action_names: Tuple[str, ...] = tuple(sorted(set(labels)))
    lane_action = jnp.asarray(
        [action_names.index(x) for x in labels], jnp.int32
    )

    def step(vec):
        succs, valid, ovf, afail = batch_step(vec[None])
        return succs[0], valid[0], lane_action, afail[0], ovf[0]

    def inv_check(vec):
        bits = jnp.int32(0)
        for k, fn in enumerate(inv_fns):
            bits = bits | (fn(vec[None])[0].astype(jnp.int32) << k)
        return bits

    def initial_vectors():
        # the anchor configuration's Init set (engine geometry probe +
        # AOT compile input; per-config seeds come from config_inits)
        return config_inits(
            model, params, {c: model.constants[c] for c in names}, cdc
        )

    from ..struct.backend import VIOL_INVARIANT_BASE

    return SpecBackend(
        cdc=cdc,
        step=step,
        n_lanes=len(labels),
        inv_check=inv_check,
        inv_codes=tuple(
            VIOL_INVARIANT_BASE + k for k in range(len(model.invariants))
        ),
        initial_vectors=initial_vectors,
        labels=action_names,
        viol_names=struct_viol_names(model),
        lane_action=lane_action,
        check_deadlock=check_deadlock,
    )


def config_inits(model: StructModel,
                 params: Dict[str, Tuple[int, int]],
                 values: Dict[str, int],
                 cdc: StructCodec) -> np.ndarray:
    """One configuration's Init set as [n0, F] field vectors of the
    class codec: enumerate Init host-side under the config's CONSTANT
    values, then append the swept fields."""
    names = tuple(sorted(params))
    missing = [c for c in names if c not in values]
    if missing:
        raise SweepError(f"config misses swept constants {missing}")
    consts = dict(model.constants)
    consts.update({c: int(values[c]) for c in names})
    sysk = model.system.with_constants(consts)
    tail = tuple(int(values[c]) for c in names)
    rows = [cdc.encode(st + tail) for st in sysk.initial_states()]
    if not rows:
        raise SweepError(f"config {values!r} has an empty Init set")
    return np.stack(rows)


class SweepEngine:
    """A warm constants-class engine: one compiled step + one batched
    AOT executable that checks up to `width` configurations per device
    dispatch.  Build once per class (the expensive part), `run` per
    submitted batch (the cheap part) - the EnginePool holds these."""

    def __init__(
        self,
        model: StructModel,
        params: Dict[str, Tuple[int, int]],
        chunk: int = 64,
        queue_capacity: int = 1 << 10,
        fp_capacity: int = 1 << 12,
        fp_index: int = DEFAULT_FP_INDEX,
        seed: int = DEFAULT_SEED,
        check_deadlock: bool = True,
        width: int = DEFAULT_WIDTH,
        deferred: bool = None,
    ):
        self.model = model
        self.params = {c: (int(lo), int(hi))
                       for c, (lo, hi) in params.items()}
        self.width = max(1, int(width))
        self.fp_capacity = fp_capacity
        self.backend = sweep_backend(model, self.params, check_deadlock)
        # donate=False: the vmap traces THROUGH run_fn (donation would
        # alias a carry the sequential parity baseline reuses), and the
        # JAXTLC_DEBUG_DONATION poisoner must not wrap a vmapped callee
        init_fn, run_fn, _ = make_backend_engine(
            self.backend, chunk, queue_capacity, fp_capacity,
            fp_index, seed, check_deadlock=check_deadlock, donate=False,
            deferred=deferred,
        )
        # jitted seeding: an eager init_fn recompiles its fpset
        # while_loop per call; under jit the (per-Init-set-shape)
        # compile happens once and warm batches run compile-free
        self._init_jit = jax.jit(init_fn)
        self._run_fn = run_fn
        self._vrun = jax.jit(jax.vmap(run_fn))
        self._aot = None
        self._aot_seq = None

    # -- carries -----------------------------------------------------------

    def carry_for(self, values: Dict[str, int]):
        """A fresh engine carry seeded with one configuration's Init."""
        return self._init_jit(
            config_inits(self.model, self.params, values,
                         self.backend.cdc)
        )

    def _stack(self, configs: List[Dict[str, int]]):
        if not configs:
            raise SweepError("empty config batch")
        if len(configs) > self.width:
            raise SweepError(
                f"{len(configs)} configs > sweep width {self.width} "
                "(the scheduler slices batches to width)"
            )
        # pad to the compiled width by repeating the last config: the
        # pad lanes are pure discarded compute, so the AOT executable
        # is one shape per class, not one per batch size
        pad = configs + [configs[-1]] * (self.width - len(configs))
        carries = [self.carry_for(v) for v in pad]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *carries)

    def _result(self, carry, wall_s: float) -> CheckResult:
        return result_from_carry(
            carry, wall_s, fp_capacity=self.fp_capacity,
            labels=self.backend.labels,
            viol_names=struct_viol_names(self.model),
        )

    # -- execution ---------------------------------------------------------

    def run(self, configs: List[Dict[str, int]]) -> List[CheckResult]:
        """Check up to `width` configurations in ONE device dispatch;
        per-config results in submission order.  wall_s on every result
        is the whole batch's dispatch wall (one dispatch = one wall)."""
        stacked = self._stack(configs)
        if self._aot is None:
            self._aot = self._vrun.lower(stacked).compile()
        t0 = time.time()
        out = jax.block_until_ready(self._aot(stacked))
        wall = time.time() - t0
        return [
            self._result(jax.tree.map(lambda x: x[k], out), wall)
            for k in range(len(configs))
        ]

    def run_sequential(self,
                       configs: List[Dict[str, int]]) -> List[CheckResult]:
        """The parity baseline: the SAME compiled step, one config at a
        time (K dispatches).  tests pin run() bit-for-bit against this,
        fpset table words included."""
        results = []
        for values in configs:
            carry = self.carry_for(values)
            if self._aot_seq is None:
                self._aot_seq = self._run_fn.lower(carry).compile()
            t0 = time.time()
            out = jax.block_until_ready(self._aot_seq(carry))
            results.append(self._result(out, time.time() - t0))
        return results
