"""In-process step-compile cache for struct specs.

Compiling a struct spec is the expensive part of running one: the
parse -> shape-infer -> lane-compile pipeline is seconds of Python and
the XLA compile of the fused engine loop is the dominant cold-start
cost (minutes for Model_1-class modules).  Both are pure functions of
(module text, constant overrides, engine geometry), so both cache:

* **In-process memo**: backends are keyed on (source digest, canonical
  constants, invariant list); built engines additionally on the full
  geometry (chunk, queue/fp capacities, fp polynomial + seed,
  highwater, deadlock switch, engine kind, mesh devices).  Repeated
  runs of the same model in one process skip straight to execution -
  and jax's jit cache keeps the compiled executable alive because the
  memo returns the SAME engine closures.

Compiled executables persist ACROSS processes through jax's own
compilation cache, which every process entry point switches on for all
engines (`jaxtlc.runtime.enable_compile_cache`); nothing here touches
it.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Tuple


class _LRUMemo:
    """Bounded in-process memo (ISSUE 9 satellite): a long-lived
    serving process runs an unbounded stream of distinct models, so the
    memo that used to be a plain dict now evicts least-recently-used
    entries at a size cap and exposes hit/miss/size stats (the
    serve-side EnginePool builds on these counters for its own
    warm/cold accounting).  Eviction only drops OUR reference: callers
    holding an evicted backend/engine keep it alive (and jax keeps its
    compiled executable alive through their closures)."""

    def __init__(self, cap: int):
        self.cap = cap
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        hit = self._d.get(key)
        if hit is None:
            self.misses += 1
            return None
        self.hits += 1
        self._d.move_to_end(key)
        return hit

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)
            self.evictions += 1

    def stats(self) -> dict:
        return dict(hits=self.hits, misses=self.misses,
                    size=len(self._d), cap=self.cap,
                    evictions=self.evictions)

    def clear(self) -> None:
        self._d.clear()


def _env_cap(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, "")))
    except ValueError:
        return default


# backends are cheap-ish Python (parse + shape-infer + closures); built
# engines pin compiled-executable references, so their cap is tighter
_BACKEND_MEMO = _LRUMemo(_env_cap("JAXTLC_BACKEND_MEMO_CAP", 64))
_ENGINE_MEMO = _LRUMemo(_env_cap("JAXTLC_ENGINE_MEMO_CAP", 32))


def stats() -> dict:
    """Hit/miss/size/eviction counters for the memos (cumulative per
    process; the serve /pool endpoint republishes them)."""
    with _SPEC_LOCK:
        spec = {name: memo.stats() for name, memo in _SPEC_MEMOS.items()}
    return {"backend": _BACKEND_MEMO.stats(),
            "engine": _ENGINE_MEMO.stats(),
            "bounds": _BOUNDS_MEMO.stats(), **spec}


def set_caps(backend: int = None, engine: int = None) -> None:
    """Resize the memo caps (tests + server sizing; shrinking evicts
    LRU entries immediately)."""
    for memo, cap in ((_BACKEND_MEMO, backend), (_ENGINE_MEMO, engine)):
        if cap is None:
            continue
        memo.cap = max(1, int(cap))
        while len(memo._d) > memo.cap:
            memo._d.popitem(last=False)
            memo.evictions += 1


def model_key(model) -> tuple:
    """The spec-meaning component of every cache key."""
    from .backend import canonical_constants

    consts = tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in canonical_constants(model).items()
    )
    return (
        model.source_digest or repr(id(model)),
        consts,
        tuple(model.invariants),
    )


# certified bound reports are pure functions of the spec meaning
# (digest + constants + invariants); milliseconds of host Python, but
# the memo keeps the narrowed-engine key stable within a process
_BOUNDS_MEMO = _LRUMemo(_env_cap("JAXTLC_BOUNDS_MEMO_CAP", 64))


def get_bounds(model):
    """Memoized certified bound report (analysis.absint) for a struct
    model - every consumer of the narrowed codec (backend memo, engine
    memo, checkpoint meta) derives its key from this one report."""
    from ..analysis.absint import analyze_bounds

    key = model_key(model)
    hit = _BOUNDS_MEMO.get(key)
    if hit is None:
        hit = analyze_bounds(model)
        _BOUNDS_MEMO.put(key, hit)
    return hit


# What is a pure function of a spec's text, in front of `model_key`
# (ISSUE 46): a check of a text this process has seen goes from the
# bytes it reads to its kept backend and engine.  Keys are content,
# never a path or an mtime (the served path writes every job's spec
# into a fresh job directory); no verdict, count or journal row is ever
# kept.  Fixed caps; `api.run_check` is a public entry, so these three
# are read and written under one lock (`spec_kept` / `spec_keep`).
#   text       struct.loader: a parsed cfg or module, by (parser, sha256
#              of its own text) - the closure is only known by parsing
#   model      struct.loader: (the StructModel, the child spans its load
#              recorded), by (source_digest, layout): every text `load`
#              reads plus the overrides
#   preflight  api._struct_preflight: the lite AnalysisReport, by
#              (model_key, every request integer preflight_struct reads)
_SPEC_MEMOS = {"text": _LRUMemo(256), "model": _LRUMemo(64),
               "preflight": _LRUMemo(128)}
_SPEC_LOCK = threading.Lock()


def spec_kept(memo: str, key):
    """The entry of the `text` / `model` / `preflight` memo under `key`,
    or None (counted as that memo's hit or miss)."""
    with _SPEC_LOCK:
        return _SPEC_MEMOS[memo].get(key)


def spec_keep(memo: str, key, value) -> None:
    """Keep `value` under `key`.  The builder of a value that raised
    never gets here: an error is built again by the next call."""
    with _SPEC_LOCK:
        _SPEC_MEMOS[memo].put(key, value)


def _bounds_key(bounds) -> str:
    """The bound-digest component of narrowed cache keys ("" = the
    un-narrowed baseline layout)."""
    if bounds is None:
        return ""
    return bounds.digest()


# (model key, resource) -> what a rung raised the resource to, for
# every backend of the model built from now on (this process); part of
# every backend and engine key.  The resources (a `degrade` event's
# `resource`):
#   step_slots        the slots a state of the compacted step keeps
#   open_side_factor  the factor of shapes.cap_open_sides
#   seq_cap           the least capacity of every sequence
#   seq_widen         the capacity rungs taken (CheckResult.seq_widen)
_FLOORS: dict = {}
OPEN_SIDE_LIMIT = 1 << 16
SEQ_CAP_RUNG_LIMIT = 1 << 10


def _floor(spec, resource: str) -> int:
    """The floor of `resource` for the model keyed `spec`: 0 where no
    rung was taken (the open side's factor: shapes.OPEN_SIDE_FACTOR)."""
    from .shapes import OPEN_SIDE_FACTOR

    return _FLOORS.get((spec, resource), OPEN_SIDE_FACTOR
                       if resource == "open_side_factor" else 0)


def widen_slots(model, backend):
    """The rung a compaction overflow takes: twice the slots `backend`
    kept a state, at most its static fan.  Returns (old, new), or None
    where `backend` is not compacted: its overflow is a trap of the
    codec, which no width cures."""
    static = getattr(backend.cdc, "static_lanes", backend.n_lanes)
    if backend.n_lanes >= static:
        return None
    new = min(static, 2 * backend.n_lanes)
    _FLOORS[model_key(model), "step_slots"] = new
    return backend.n_lanes, new


def widen_open_sides(model, backend):
    """The rung a range trap of a CONSTRAINED model's step takes: the
    sides of its integer leaves that the constraint leaves open are
    capped 16 times further out (shapes.cap_open_sides).  Returns
    (old, new) factors, or None where the model has no constraint or
    the cap has passed the widening's own last threshold: the trap is
    then the codec's, which no factor cures."""
    if getattr(backend, "constraint", None) is None:
        return None
    key = model_key(model)
    old = _floor(key, "open_side_factor")
    if old >= OPEN_SIDE_LIMIT:
        return None
    _FLOORS[key, "open_side_factor"] = old * 16
    return old, old * 16


def widen_seq_caps(model, backend):
    """The rung an Append on a full sequence takes: every sequence of
    the model at least half as large again as the largest the layout
    holds (a declared capacity that did not hold is overridden: its
    invariant then fails as an invariant).  Returns (old, new)
    capacities, or None where the layout's capacities are not this
    ladder's (no growing sequence; a certified-bound layout)."""
    if getattr(backend.cdc, "seq_cap_from", None) is None:
        return None
    old = backend.cdc.seq_cap_max
    if old >= SEQ_CAP_RUNG_LIMIT:
        return None
    key = model_key(model)
    new = old + max(1, old // 2)
    _FLOORS[key, "seq_cap"] = new
    _FLOORS[key, "seq_widen"] = _floor(key, "seq_widen") + 1
    return old, new


def _append_trapped(model, backend, vec) -> bool:
    """Whether the trap that halted a run at the state `vec` (its [F]
    field vector) is an Append on a full sequence: some successor of
    the state, by the host evaluator, holds a sequence longer than the
    layout's capacity.  A constrained model's trap fires on kept
    successors alone, as the engine's does.  (The step folds its two
    flags, an Append's overflow and a value out of range, into one
    violation code: PERF.md section 7-21e.)"""
    from .codec import SeqCapError

    if vec is None or getattr(backend.cdc, "seq_cap_from", None) is None:
        return False  # no state named; no sequence this ladder widens
    system = model.system
    try:
        for _, succ in system.successors(backend.cdc.decode(vec)):
            env = dict(system.ev.constants)
            env.update(zip(system.variables, succ))
            if not all(system.ev.eval(ast, env) is True
                       for ast in model.constraints.values()):
                continue
            try:
                backend.cdc.encode(succ)
            except SeqCapError:
                return True
            except ValueError:
                pass  # a successor out of a leaf's range: another rung's
    except (ValueError, IndexError):
        # the host cannot take the step again (the evaluator or the
        # decode refuses the state): not this rung's to judge
        pass
    return False


def widen(model, backend, state=None):
    """The rung a run of `model` takes that halted on a trap
    (VIOL_SLOT_OVERFLOW) expanding `state`, in the ladder's order: an
    Append on a full sequence -> longer sequences; a compacted step ->
    more slots; a constrained model -> its open sides further out.
    Returns (resource, (old, new), reason) - the check then starts
    again from its initial states - or None where no rung cures the
    trap: it is the codec's."""
    step = _append_trapped(model, backend, state) and widen_seq_caps(
        model, backend)
    if step:
        return "seq_cap", step, (
            "an Append met a full sequence; the check starts again")
    step = widen_slots(model, backend)
    if step:
        return "step_slots", step, (
            "a state fired more lanes than the compacted step keeps; "
            "the check starts again")
    step = widen_open_sides(model, backend)
    if step:
        return "open_side_factor", step, (
            "a kept state left the range guessed for a leaf the "
            "CONSTRAINT bounds on one side; the check starts again")
    return None


def wants_symmetry(model, symmetry=None, chunk: int = 0) -> bool:
    """The RESOLVED symmetry mode of a check of `model`: on where the
    model's cfg declares SYMMETRY (the second way in beside the
    tri-state `-symmetry` flag, resolved to the same bool every memo
    key holds), else the flag's (engine.bfs.resolve_symmetry).  A cfg
    that says SYMMETRY and a result that ignores it would be a wrong
    distinct count by the cfg's accounting, so `-no-symmetry` against
    such a cfg is an error, not a way round it."""
    from ..analysis.symfind import SymmetryError
    from ..engine.bfs import resolve_symmetry

    if getattr(model, "symmetry", ()):
        if symmetry is False:
            raise SymmetryError(
                "the cfg declares SYMMETRY; -no-symmetry would report "
                "counts the cfg does not ask for (take the line out of "
                "the cfg instead)")
        return True
    return resolve_symmetry(symmetry, chunk)


def get_backend(model, check_deadlock: bool = True, bounds=None,
                elide: bool = True, coverage: bool = False,
                symmetry: bool = False, por: bool = False):
    """Memoized struct_backend (the parse -> shape-infer -> lane-compile
    pipeline runs once per spec meaning per process).  `bounds` (a
    certified analysis.absint.BoundReport) selects the NARROWED
    compile - a distinct memo entry keyed on the bound digest;
    `elide=False` keeps every trap (the sharded engines' narrowed
    form, which has no certificate column).  `coverage` compiles the
    device coverage plane in (a distinct memo entry: the backend
    carries the site table + count hook).  `symmetry`/`por` (resolved
    bools) attach the state-space reduction ops - distinct memo
    entries because the reduced engine has a different carry layout
    (COL_SYM ring column, prune counters) and different step XLA."""
    from ..obs.spans import span
    from .backend import struct_backend

    spec = model_key(model)
    slots, open_side, seq_floor = (_floor(spec, r) for r in (
        "step_slots", "open_side_factor", "seq_cap"))
    key = (spec, bool(check_deadlock), _bounds_key(bounds),
           bool(elide), bool(coverage), bool(symmetry), bool(por), slots,
           open_side, seq_floor)
    # host span `build.struct`: the memo's look-up and, on a miss, the
    # shape inference and the lane walk inside it (`build.struct.shapes`,
    # `build.struct.lanes`) - the struct path's own part of a build
    with span("build.struct") as sp:
        hit = _BACKEND_MEMO.get(key)
        sp.attrs["memo"] = "hit" if hit is not None else "miss"
        if hit is None:
            hit = struct_backend(model, check_deadlock=check_deadlock,
                                 bounds=bounds, elide=elide,
                                 coverage=coverage, symmetry=symmetry,
                                 por=por, slots=slots,
                                 open_side_factor=open_side,
                                 seq_cap_floor=seq_floor)
            hit.cdc.seq_widen = _floor(spec, "seq_widen")
            _BACKEND_MEMO.put(key, hit)
    return hit


def engine_key(
    model,
    chunk: int,
    queue_capacity: int,
    fp_capacity: int,
    fp_index: int,
    seed: int,
    fp_highwater: float,
    check_deadlock: bool = True,
    pipeline: bool = False,
    obs_slots: int = 0,
    bounds=None,
    coverage: bool = False,
    deferred: bool = None,
    symmetry: bool = None,
    por: bool = None,
) -> tuple:
    """The full engine-memo key: spec meaning (digest + canonical
    constants + invariants) x engine geometry x pipeline/obs/coverage
    flags x the certified-bound digest (a narrowed engine is a
    DIFFERENT compile - its codec, lanes and traps all change with the
    bounds; a covered engine carries the coverage leaves; a deferred
    engine moves invariant/cert evaluation to the commit stage, ISSUE
    15; a symmetry/POR-reduced engine canonicalizes and prunes in the
    expand stage, ISSUE 18).  The serve EnginePool keys its warm AOT
    entries on exactly this tuple so pool identity and memo identity
    cannot drift.  `deferred`/`symmetry`/`por` are resolved (tri-state
    auto -> bool) against the chunk so the key never depends on who
    asked."""
    from ..engine.bfs import resolve_deferred, resolve_por

    spec = model_key(model)
    return (
        spec, "single", chunk, queue_capacity, fp_capacity,
        fp_index, seed, fp_highwater, bool(check_deadlock),
        bool(pipeline), int(obs_slots), _bounds_key(bounds),
        bool(coverage), resolve_deferred(deferred, chunk),
        wants_symmetry(model, symmetry, chunk), resolve_por(por, chunk),
        _floor(spec, "step_slots"), _floor(spec, "open_side_factor"),
        _floor(spec, "seq_cap"),
    )


def get_engine(
    model,
    chunk: int,
    queue_capacity: int,
    fp_capacity: int,
    fp_index: int,
    seed: int,
    fp_highwater: float,
    check_deadlock: bool = True,
    pipeline: bool = False,
    obs_slots: int = 0,
    bounds=None,
    coverage: bool = False,
    deferred: bool = None,
    symmetry: bool = None,
    por: bool = None,
) -> Tuple:
    """Memoized single-device engine triple (init_fn, run_fn, step_fn)
    for a struct model.  obs_slots is
    part of the key: the ring changes the carry pytree, so an obs-on
    engine is a different compile than an obs-off one.  `bounds`
    selects the narrowed engine (certificate check on, keyed on the
    bound digest); `coverage` the covered engine (per-site counter
    leaves on the carry); `symmetry`/`por` the reduced engine (orbit
    canonicalization + ample-set pruning, ISSUE 18)."""
    from ..engine.bfs import (
        make_backend_engine,
        resolve_por,
    )

    key = engine_key(
        model, chunk, queue_capacity, fp_capacity, fp_index, seed,
        fp_highwater, check_deadlock=check_deadlock, pipeline=pipeline,
        obs_slots=obs_slots, bounds=bounds, coverage=coverage,
        deferred=deferred, symmetry=symmetry, por=por,
    )
    hit = _ENGINE_MEMO.get(key)
    if hit is None:
        backend = get_backend(model, check_deadlock, bounds=bounds,
                              coverage=coverage,
                              symmetry=wants_symmetry(model, symmetry,
                                                      chunk),
                              por=resolve_por(por, chunk))
        hit = make_backend_engine(
            backend, chunk, queue_capacity, fp_capacity, fp_index, seed,
            fp_highwater=fp_highwater, pipeline=pipeline,
            obs_slots=obs_slots, deferred=deferred,
        )
        _ENGINE_MEMO.put(key, hit)
    return hit


def clear() -> None:
    """Drop the in-process memos (tests)."""
    _BACKEND_MEMO.clear()
    _ENGINE_MEMO.clear()
    _BOUNDS_MEMO.clear()
    _FLOORS.clear()
    with _SPEC_LOCK:
        for memo in _SPEC_MEMOS.values():
            memo.clear()
