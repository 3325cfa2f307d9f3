"""Finite-shape inference for structural specs (E1 device compilation).

TLC executes unbounded TLA+ values on a JVM heap; a tensor kernel needs
every variable laid out in fixed integer lanes.  This pass infers, by
abstract interpretation of Init and every action's primed updates, a
finite *shape* per variable - the TPU-first replacement for TLC's
dynamic value representations:

  SBool | SInt(lo,hi) | SAtoms(strings/model values) |
  SRec(field -> (shape, optional)) | SSet(elem) |
  SFun(keys, val, partial) | SSeq(elem, cap) | STup(items) |
  SUnion(alts) | SEnum(values)

Records with optional fields become presence-tagged products; sets of
records become bitmasks over the record universe (KubeAPI's apiState,
/root/reference/KubeAPI.tla:14); partial functions (requests :16) get
per-key presence bits; procedure frames/stacks (:466) become bounded
sequences.  The abstract domains over-approximate reachable values -
over-approximation costs lanes, never soundness, because the codec can
then represent every reachable value.  Fixpoint iteration with range
hulls for ints.  A function over 1..n (`[p \\in Proc |-> ...]` with
`Proc == 1 .. N`; in TLA+ a tuple) is STup: exactly n components, each
with its own shape, so that `network[p][p]`, which no action appends
to, costs nothing.  A sequence that grows (`Append`) has a CAPACITY,
and the abstract step cannot find one (every pass appends once more):
it is read off the spec where an invariant or the cfg's CONSTRAINT
declares it (`Len(network[p][q]) <= 3`: seq_cap_bounds), and is a first
guess (SEQ_CAP_GUESS) where nothing does.  Either way it is guarded:
an `Append` on a full sequence is a trap that halts the run (never a
shorter sequence), and the run starts again with the capacity a rung
higher (struct.cache.widen_seq_caps) - a declared bound that does not
hold then fails as the invariant it is.
"""

from __future__ import annotations

import dataclasses
from itertools import product as _product
from typing import Dict, FrozenSet, List, NamedTuple, Optional, Tuple

from ..spec.labels import DEFAULT_INIT
from .eval import BUILTIN_SETS, Evaluator, LazySet, is_fn, is_int
from .parser import Definition


class ShapeError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Shape classes (immutable, hashable)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shape:
    pass


@dataclasses.dataclass(frozen=True)
class SBool(Shape):
    pass


@dataclasses.dataclass(frozen=True)
class SInt(Shape):
    lo: int
    hi: int


@dataclasses.dataclass(frozen=True)
class SAtoms(Shape):
    atoms: FrozenSet[str]


@dataclasses.dataclass(frozen=True)
class SRec(Shape):
    # (field, shape, optional) triples, field-sorted
    fields: Tuple[Tuple[str, Shape, bool], ...]

    def field(self, name: str) -> Optional[Tuple[Shape, bool]]:
        for f, s, o in self.fields:
            if f == name:
                return s, o
        return None


@dataclasses.dataclass(frozen=True)
class SSet(Shape):
    elem: Optional[Shape]  # None = always-empty set


@dataclasses.dataclass(frozen=True)
class SFun(Shape):
    keys: Tuple[str, ...]
    val: Optional[Shape]  # None = always-empty function
    partial: bool


@dataclasses.dataclass(frozen=True)
class SSeq(Shape):
    elem: Optional[Shape]
    cap: int


@dataclasses.dataclass(frozen=True)
class STup(Shape):
    """A function over 1..n, which TLA+ and the value model keep as a
    tuple: exactly len(items) components, each with its own shape (an
    item None: no value seen there yet)."""
    items: Tuple[Optional[Shape], ...]


@dataclasses.dataclass(frozen=True)
class SUnion(Shape):
    alts: Tuple[Shape, ...]  # at most one alt per shape class


@dataclasses.dataclass(frozen=True)
class SEnum(Shape):
    """An explicit finite universe: exactly these canonical values, in
    this order.  Where a spec DECLARES a set variable's type as a union
    of record sets (`msgs \\subseteq Message`, typeok_hints), the
    declared set is the universe - the product of every field's values
    that joining the record shapes would give is many times larger
    (Paxos at Ballot == 0..1: 1,536 against Message's 72)."""
    values: tuple


def enum_field(sh: SEnum, fname: str) -> Optional[Tuple[Shape, bool]]:
    """(shape, optional) of field `fname` over the record values of an
    explicit universe; None where no value has it."""
    out, have, n = None, 0, 0
    for v in sh.values:
        if isinstance(v, tuple) and v and is_fn(v):
            n += 1
            d = dict(v)
            if fname in d:
                have += 1
                out = join(out, shape_of_value(d[fname]))
    return None if not have else (out, have < n)


def enum_fields(sh: SEnum) -> List[str]:
    names = set()
    for v in sh.values:
        if isinstance(v, tuple) and v and is_fn(v):
            names |= {k for k, _ in v}
    return sorted(names)


# a growing sequence's capacity where the spec declares none: a first
# guess, guarded by the Append trap and raised by a rung
SEQ_CAP_GUESS = 2
# `join`, `Append` and `\\o` give a capacity as they find it; whoever
# drives abstract passes (ShapeInference.run, analysis.absint) settles
# the capacities after each pass (cap_sequences)


def _as_seq(sh: "STup") -> "SSeq":
    """A tuple read as a sequence (it met one of another length)."""
    elem = None
    for x in sh.items:
        elem = join(elem, x)
    return SSeq(elem, len(sh.items))


# ---------------------------------------------------------------------------
# Join
# ---------------------------------------------------------------------------


def join(a: Optional[Shape], b: Optional[Shape]) -> Optional[Shape]:
    if a is None:
        return b
    if b is None:
        return a
    # the empty tuple value is both the empty function and the empty
    # sequence (eval._pairs_to_fn); its shape SSeq(None, 0) coerces to
    # whatever container it joins with
    if isinstance(a, STup) and isinstance(b, STup) \
            and len(a.items) == len(b.items):
        return STup(tuple(join(x, y) for x, y in zip(a.items, b.items)))
    if isinstance(a, STup) and isinstance(b, (STup, SSeq)):
        a = _as_seq(a)
    if isinstance(b, STup) and isinstance(a, SSeq):
        b = _as_seq(b)
    if a == SSeq(None, 0) and not isinstance(b, SSeq):
        a = _empty_as(b)
    if b == SSeq(None, 0) and not isinstance(a, SSeq):
        b = _empty_as(a)
    if isinstance(a, SUnion) or isinstance(b, SUnion):
        alts = list(a.alts if isinstance(a, SUnion) else (a,))
        for x in (b.alts if isinstance(b, SUnion) else (b,)):
            alts = _merge_alt(alts, x)
        return alts[0] if len(alts) == 1 else SUnion(tuple(alts))
    if type(a) is not type(b):
        return SUnion(tuple(_merge_alt([a], b)))
    if isinstance(a, SBool):
        return a
    if isinstance(a, SInt):
        return SInt(min(a.lo, b.lo), max(a.hi, b.hi))
    if isinstance(a, SAtoms):
        return SAtoms(a.atoms | b.atoms)
    if isinstance(a, SEnum):
        have = set(a.values)
        extra = tuple(v for v in b.values if v not in have)
        return SEnum(a.values + extra) if extra else a
    if isinstance(a, SRec):
        names = sorted({f for f, _, _ in a.fields}
                       | {f for f, _, _ in b.fields})
        out = []
        for n in names:
            fa, fb = a.field(n), b.field(n)
            if fa is None:
                out.append((n, fb[0], True))
            elif fb is None:
                out.append((n, fa[0], True))
            else:
                out.append((n, join(fa[0], fb[0]), fa[1] or fb[1]))
        return SRec(tuple(out))
    if isinstance(a, SSet):
        return SSet(join(a.elem, b.elem))
    if isinstance(a, SFun):
        keys = tuple(sorted(set(a.keys) | set(b.keys)))
        partial = a.partial or b.partial or set(a.keys) != set(b.keys)
        return SFun(keys, join(a.val, b.val), partial)
    if isinstance(a, SSeq):
        return SSeq(join(a.elem, b.elem), max(a.cap, b.cap))
    raise ShapeError(f"cannot join {a} and {b}")


def _empty_as(like: Shape) -> Shape:
    """The empty-container shape coerced to `like`'s container class."""
    if isinstance(like, SFun):
        return SFun((), None, True)
    if isinstance(like, SRec):
        return SRec(())
    if isinstance(like, SUnion):
        for alt in like.alts:
            if isinstance(alt, (SFun, SRec)):
                return _empty_as(alt)
    return SSeq(None, 0)


def _merge_alt(alts: List[Shape], x: Shape) -> List[Shape]:
    out = []
    merged = False
    seqs = (SSeq, STup)
    for alt in alts:
        if type(alt) is type(x) or (isinstance(alt, seqs)
                                    and isinstance(x, seqs)):
            out.append(join(alt, x))
            merged = True
        else:
            out.append(alt)
    if not merged:
        out.append(x)
    return sorted(out, key=lambda s: type(s).__name__)


# ---------------------------------------------------------------------------
# Shape of a concrete value
# ---------------------------------------------------------------------------


def shape_of_value(v) -> Shape:
    if isinstance(v, bool):
        return SBool()
    if isinstance(v, int):
        return SInt(v, v)
    if isinstance(v, str):
        return SAtoms(frozenset({v}))
    if isinstance(v, frozenset):
        elem = None
        for x in v:
            elem = join(elem, shape_of_value(x))
        return SSet(elem)
    if isinstance(v, tuple):
        if v and is_fn(v):
            # records AND string-keyed functions both become SRec: per-key
            # field shapes with presence bits (partial functions get
            # optional fields); one shape class covers TLA's record/
            # function unification
            return SRec(tuple(
                (k, shape_of_value(x), False) for k, x in v
            ))
        if v:
            return STup(tuple(shape_of_value(x) for x in v))
        return SSeq(None, 0)
    raise ShapeError(f"cannot shape value {v!r}")


# ---------------------------------------------------------------------------
# Universe enumeration
# ---------------------------------------------------------------------------

ENUM_LIMIT = 1 << 21
# a binder over a constant set of at most this many integers is bound
# one value at a time in the abstract pass (ShapeInference._const)
CONST_BINDER_LIMIT = 64


def universe(shape: Optional[Shape], limit: int = ENUM_LIMIT) -> List:
    """All canonical values of `shape`, deterministic order.  Raises
    ShapeError when the universe exceeds `limit` (caller then decomposes
    the shape structurally instead of enumerating it)."""
    if shape is None:
        return []
    if isinstance(shape, SBool):
        return [False, True]
    if isinstance(shape, SInt):
        n = shape.hi - shape.lo + 1
        if n > limit:
            raise ShapeError(f"int range too large: {shape}")
        return list(range(shape.lo, shape.hi + 1))
    if isinstance(shape, SAtoms):
        return sorted(shape.atoms)
    if isinstance(shape, SEnum):
        if len(shape.values) > limit:
            raise ShapeError("explicit universe too large")
        return list(shape.values)
    if isinstance(shape, SRec):
        per_field = []
        total = 1
        for f, s, opt in shape.fields:
            u = universe(s, limit)
            opts = ([None] if opt else []) + u
            total *= max(len(opts), 1)
            if total > limit:
                raise ShapeError(f"record universe too large at {f}")
            per_field.append((f, opts))
        out = []
        for combo in _product(*(opts for _, opts in per_field)):
            out.append(tuple(
                (f, v) for (f, _), v in zip(per_field, combo)
                if v is not None
            ))
        return out
    if isinstance(shape, SSet):
        eu = universe(shape.elem, 20)  # subsets only of tiny universes
        if len(eu) > 20:
            raise ShapeError("set universe too large to enumerate")
        out = []
        for bits in range(1 << len(eu)):
            out.append(frozenset(
                eu[i] for i in range(len(eu)) if bits >> i & 1
            ))
        return out
    if isinstance(shape, SSeq):
        eu = universe(shape.elem, limit)
        total = sum(len(eu) ** k for k in range(shape.cap + 1))
        if total > limit:
            raise ShapeError("sequence universe too large")
        out = [()]
        layer = [()]
        for _ in range(shape.cap):
            layer = [t + (e,) for t in layer for e in eu]
            out.extend(layer)
        return out
    if isinstance(shape, STup):
        per_item = []
        total = 1
        for x in shape.items:
            per_item.append(universe(x, limit))
            total *= max(len(per_item[-1]), 1)
            if total > limit:
                raise ShapeError("tuple universe too large")
        return list(_product(*per_item))
    if isinstance(shape, SFun):
        per_key = []
        total = 1
        for k in shape.keys:
            u = universe(shape.val, limit)
            opts = ([None] if shape.partial else []) + u
            total *= max(len(opts), 1)
            if total > limit:
                raise ShapeError("function universe too large")
            per_key.append((k, opts))
        out = []
        for combo in _product(*(opts for _, opts in per_key)):
            out.append(tuple(
                (k, v) for (k, _), v in zip(per_key, combo)
                if v is not None
            ))
        return out
    if isinstance(shape, SUnion):
        out = []
        for alt in shape.alts:
            out.extend(universe(alt, limit - len(out)))
        return out
    raise ShapeError(f"cannot enumerate {shape}")


def enumerable(shape: Optional[Shape], limit: int = ENUM_LIMIT) -> bool:
    try:
        universe(shape, limit)
        return True
    except ShapeError:
        return False


# ---------------------------------------------------------------------------
# Abstract interpretation of expressions
# ---------------------------------------------------------------------------


_NOVAL = object()  # "no host value": _const, constraint_bounds


class ShapeInference:
    """Infers per-variable shapes from Init + all primed updates."""

    # abstract values for CONSTANT names, consulted before the concrete
    # ev.constants: the sweep-class audit (jaxtlc.analysis) widens a
    # swept constant to its whole lo..hi interval here, so one abstract
    # pass covers every configuration of the class
    const_hints: Dict[str, Shape] = {}
    # a cfg's CONSTRAINT (infer_shapes' `kept`): {var: [(path, lo, hi)]},
    # the leaf bounds every kept state lies inside, and how far out the
    # side of a leaf they leave open is capped (cap_open_sides)
    kept_bounds: Dict[str, list] = {}
    open_side_factor: int = 8  # OPEN_SIDE_FACTOR, below
    # the capacities the spec declares for its sequences (infer_shapes'
    # `seq_caps`): {var: [(path, cap)]}, and the least capacity a rung
    # has raised every sequence to (0: none taken)
    seq_caps: Dict[str, list] = {}
    seq_cap_floor: int = 0

    def __init__(self, ev: Evaluator, variables: Tuple[str, ...],
                 init_ast, next_ast):
        self.ev = ev
        self.variables = variables
        self.init_ast = init_ast
        self.next_ast = next_ast
        self.var_shapes: Dict[str, Optional[Shape]] = {
            v: None for v in variables
        }

    # -- fixpoint ----------------------------------------------------------

    def run(self, max_iters: int = 30) -> Dict[str, Shape]:
        # seed from concrete initial states (uses the exact evaluator)
        from .actions import ActionSystem

        system = ActionSystem.__new__(ActionSystem)
        system.ev = self.ev
        system.variables = self.variables
        system.init_ast = self.init_ast
        system.next_ast = self.next_ast
        system._mentions_cache = {}
        system._init_product = False
        # where Init is a product of per-variable domains, a variable's
        # shape is the join over its own values: not one join a state
        doms = system.init_product()
        seeds = doms if doms is not None else [
            (v, [st[k] for st in system.initial_states()])
            for k, v in enumerate(self.variables)]
        for v, vals in seeds:
            for val in vals:
                self.var_shapes[v] = join(
                    self.var_shapes[v], shape_of_value(val)
                )
        hints = getattr(self, "hints", {})
        for it in range(max_iters):
            before = dict(self.var_shapes)
            self._pass_next()
            for v in self.variables:
                self.var_shapes[v] = cap_sequences(
                    self.var_shapes[v], self.seq_caps.get(v, ()),
                    self.seq_cap_floor)
            if it >= 2:
                # widen growing int ranges up a threshold ladder so
                # counter-style specs (x' = x + 1 under a guard the
                # abstract pass cannot see) converge; the kernel traps
                # at runtime if a real value escapes the widened range
                for v in self.variables:
                    self.var_shapes[v] = _widen(before.get(v),
                                                self.var_shapes[v])
            for v, hint in hints.items():
                # TypeOK-declared bounds keep universes tight (one value
                # of slack, see typeok_hints); clamping LAST keeps the
                # widen/clamp pair convergent
                self.var_shapes[v] = _clamp(self.var_shapes[v], hint)
            for v, bs in self.kept_bounds.items():
                self.var_shapes[v] = cap_open_sides(
                    self.var_shapes[v], bs, self.open_side_factor)
            if self.var_shapes == before:
                return {v: s for v, s in self.var_shapes.items()}
        raise ShapeError("shape inference did not converge")

    def _pass_next(self):
        # under a cfg's CONSTRAINT only kept states are expanded: the
        # read side is the shapes met with the constraint's leaf
        # bounds, the written side (var_shapes) what one step from a
        # kept state can hold - a successor outside the constraint has
        # to be representable as raw fields long enough to be judged
        kept = self.kept_bounds
        env = {v: apply_leaf_bounds(s, kept[v]) if v in kept else s
               for v, s in self.var_shapes.items()}
        self._walk_action(self.next_ast, dict(env))

    # -- what is a host constant under the binders so far ------------------

    def _const(self, ast, env):
        """The value of `ast` where it reads nothing but constants and
        binders whose value the walk knows (a binder over a constant
        set of integers is walked one value at a time, as the lane
        compiler binds it: the `p` of `Proc \\ {p}`, of `s = r`, of
        `![p][q]`); _NOVAL where it reads anything else.  The binder's
        SHAPE stays its whole domain (arithmetic on it is as wide as it
        was): the value is kept beside it, under ("#", name)."""
        cenv = {k[1]: v for k, v in env.items()
                if isinstance(k, tuple) and k[0] == "#"}
        cenv.update((k, v) for k, v in env.items()
                    if isinstance(v, Definition))
        try:
            return self.ev.eval(ast, cenv)
        except Exception:
            return _NOVAL

    @staticmethod
    def _bind(env, name, shape, value=_NOVAL):
        """`name` bound to `shape` in `env`, and to `value` where the
        walk knows it (a name bound again loses the old value)."""
        env[name] = shape
        if value is _NOVAL:
            env.pop(("#", name), None)
        else:
            env[("#", name)] = value

    def _int_set(self, ast, env):
        """The constant, non-empty, small set of integers `ast` is
        under `env`, sorted; else None."""
        dom = self._const(ast, env)
        if isinstance(dom, frozenset) and 0 < len(dom) <= CONST_BINDER_LIMIT \
                and all(is_int(x) for x in dom):
            return sorted(dom)
        return None

    # -- action walk: collect var' = rhs joins -----------------------------

    def _walk_action(self, ast, env):
        op = ast[0]
        if op in ("and", "or"):
            for x in ast[1]:
                self._walk_action(x, env)
            return
        if op == "exists":
            _, names, dom_ast, body = ast
            ints = self._int_set(dom_ast, env)
            dom_sh = self._abstract(dom_ast, env)
            elem = self._elem_shape(dom_sh)
            if ints is not None:
                inner = ("exists", names[1:], dom_ast, body) \
                    if len(names) > 1 else body
                for x in ints:
                    env2 = dict(env)
                    self._bind(env2, names[0], elem, x)
                    self._walk_action(inner, env2)
                return
            env2 = dict(env)
            for nm in names:
                self._bind(env2, nm, elem)
            self._walk_action(body, env2)
            return
        if op == "if":
            cond = self._const(ast[1], env)
            if cond is not False:
                self._walk_action(ast[2], env)
            if cond is not True:
                self._walk_action(ast[3], env)
            return
        if op == "let":
            env2 = dict(env)
            for name, params, body in ast[1]:
                if params:
                    self._bind(env2, name, Definition(name, params, body))
                else:
                    self._bind(env2, name, self._abstract(body, env2),
                               self._const(body, env2))
            self._walk_action(ast[2], env2)
            return
        if op in ("call", "name"):
            dname = ast[1]
            d = env.get(dname)
            if not isinstance(d, Definition):
                d = self.ev.defs.get(dname)
            if isinstance(d, Definition) and _mentions_prime_static(
                d.body, self.ev.defs
            ):
                args = ast[2] if op == "call" else []
                env2 = dict(env)
                for p, a in zip(d.params, args):
                    self._bind(env2, p, self._abstract(a, env),
                               self._const(a, env))
                self._walk_action(d.body, env2)
            return
        if op == "cmp" and ast[1] in ("=", r"\in") and ast[2][0] == "prime":
            name = ast[2][1]
            rhs = self._abstract(ast[3], env)
            if ast[1] == r"\in":
                rhs = self._elem_shape(rhs)
            self._record_write(name, rhs)
            return
        # guards / UNCHANGED contribute nothing

    def _record_write(self, name: str, sh: Optional[Shape]) -> None:
        """One primed assignment observed; the abstract-interpretation
        subclass (analysis.absint) collects writes separately to run
        descending (narrowing) iterations."""
        self.var_shapes[name] = join(self.var_shapes[name], sh)

    # -- abstract expression evaluation ------------------------------------

    def _elem_shape(self, sh: Optional[Shape]) -> Optional[Shape]:
        if isinstance(sh, SSet):
            return sh.elem
        if isinstance(sh, SUnion):
            out = None
            for a in sh.alts:
                if isinstance(a, SSet):
                    out = join(out, a.elem)
            return out
        return None

    def _abstract(self, ast, env) -> Optional[Shape]:
        op = ast[0]
        if op == "bool":
            return SBool()
        if op == "num":
            return SInt(ast[1], ast[1])
        if op == "str":
            return SAtoms(frozenset({ast[1]}))
        if op == "name":
            nm = ast[1]
            if nm in env and not isinstance(env[nm], Definition):
                return env[nm]
            if nm in self.const_hints:
                return self.const_hints[nm]
            if nm in self.ev.constants:
                return shape_of_value(self.ev.constants[nm])
            if nm in BUILTIN_SETS:
                v = BUILTIN_SETS[nm]
                if isinstance(v, frozenset):
                    return shape_of_value(v)
                raise ShapeError(f"cannot shape builtin set {nm}")
            d = self.ev.defs.get(nm)
            if d is not None and not d.params:
                return self._abstract(d.body, env)
            raise ShapeError(f"unknown name {nm!r} in shape inference")
        if op == "prime":
            return self.var_shapes[ast[1]]
        if op == "setlit":
            elem = None
            for x in ast[1]:
                elem = join(elem, self._abstract(x, env))
            return SSet(elem)
        if op == "tuple":
            elem = None
            for x in ast[1]:
                elem = join(elem, self._abstract(x, env))
            return SSeq(elem, len(ast[1]))
        if op == "record":
            return SRec(tuple(sorted(
                (f, self._abstract(x, env), False) for f, x in ast[1]
            )))
        if op == "recset":
            return SSet(SRec(tuple(sorted(
                (f, self._elem_shape(self._abstract(x, env)), False)
                for f, x in ast[1]
            ))))
        if op == "apply":
            base = self._abstract(ast[1], env)
            arg_ast = ast[2]
            return self._apply_shape(base, arg_ast, env)
        if op == "domain":
            base = self._abstract(ast[1], env)
            keys = self._domain_atoms(base)
            if keys is not None:
                return SSet(SAtoms(frozenset(keys)))
            n = len(base.items) if isinstance(base, STup) else \
                base.cap if isinstance(base, SSeq) else SEQ_CAP_GUESS
            return SSet(SInt(1, max(n, 1)))
        if op == "subset":
            return SSet(self._abstract(ast[1], env))
        if op in ("not", "and", "or", "implies", "forall", "exists"):
            return SBool()
        if op == "cmp":
            return SBool()
        if op == "binop":
            return self._binop_shape(ast, env)
        if op == "if":
            cond = self._const(ast[1], env)
            if isinstance(cond, bool):
                return self._abstract(ast[2 if cond else 3], env)
            return join(self._abstract(ast[2], env),
                        self._abstract(ast[3], env))
        if op == "case":
            out = None
            for _, e in ast[1]:
                out = join(out, self._abstract(e, env))
            if ast[2] is not None:
                out = join(out, self._abstract(ast[2], env))
            return out
        if op == "let":
            env2 = dict(env)
            for name, params, body in ast[1]:
                if params:
                    self._bind(env2, name, Definition(name, params, body))
                else:
                    self._bind(env2, name, self._abstract(body, env2),
                               self._const(body, env2))
            return self._abstract(ast[2], env2)
        if op == "choose":
            _, var, dom_ast, _ = ast
            return self._elem_shape(self._abstract(dom_ast, env))
        if op == "setfilter":
            _, var, dom_ast, _ = ast
            dom = self._abstract(dom_ast, env)
            if isinstance(dom, SSet):
                return dom
            return SSet(self._elem_shape(dom))
        if op == "setmap":
            _, expr, var, dom_ast = ast
            dom = self._abstract(dom_ast, env)
            env2 = dict(env)
            self._bind(env2, var, self._elem_shape(dom))
            return SSet(self._abstract(expr, env2))
        if op == "fnlit":
            _, var, dom_ast, body = ast
            ints = self._int_set(dom_ast, env)
            if ints is not None and ints == list(
                    range(ints[0], ints[-1] + 1)):
                # over an integer interval: key by key, each body under
                # its own key
                vals = []
                for k in ints:
                    env2 = dict(env)
                    self._bind(env2, var, SInt(k, k), k)
                    vals.append(self._abstract(body, env2))
                if ints[0] == 1:
                    return STup(tuple(vals))
                return SRec(tuple(
                    (k, v, False) for k, v in zip(ints, vals)))
            dom = self._abstract(dom_ast, env)
            elem = self._elem_shape(dom)
            env2 = dict(env)
            self._bind(env2, var, elem)
            val = self._abstract(body, env2)
            keys = self._atoms_of(elem)
            if keys is None:
                if elem is None:
                    return SRec(())
                raise ShapeError("fnlit over non-atom domain")
            return SRec(tuple(
                (k, val, False) for k in sorted(keys)
            ))
        if op == "funcset":
            dom = self._abstract(ast[1], env)
            rng = self._elem_shape(self._abstract(ast[2], env))
            keys = self._atoms_of(self._elem_shape(dom))
            if keys is None:
                raise ShapeError("function set over non-atom domain")
            return SSet(SRec(tuple(
                (k, rng, False) for k in sorted(keys)
            )))
        if op == "except":
            base = self._abstract(ast[1], env)
            for path_asts, val_ast in ast[2]:
                base = self._except_shape(base, path_asts, val_ast, env)
            return base
        if op == "atref":
            if "@" not in env:
                raise ShapeError("@ outside EXCEPT in shape inference")
            return env["@"]  # may be None (bottom) early in the fixpoint
        if op == "call":
            return self._call_shape(ast, env)
        if op == "unchanged":
            return SBool()
        if op == "recfn":
            # a LET's `f[x \\in S] == e` (PaxosCommit's Max): the shape
            # of its values, e's own with f's applications inside it
            # read at the join so far, to the fixpoint
            _, name, var, dom_ast, body = ast
            elem = self._elem_shape(self._abstract(dom_ast, env))
            val = None
            for _ in range(8):
                env2 = dict(env)
                self._bind(env2, var, elem)
                self._bind(env2, name, SFun((), val, False))
                new = join(val, self._abstract(body, env2))
                if new == val:
                    break
                val = new
            return SFun((), val, False)
        raise ShapeError(f"cannot abstract {op!r}")

    def _atoms_of(self, sh) -> Optional[FrozenSet[str]]:
        if isinstance(sh, SAtoms):
            return sh.atoms
        if isinstance(sh, SUnion):
            out = frozenset()
            for a in sh.alts:
                if isinstance(a, SAtoms):
                    out |= a.atoms
                else:
                    return None
            return out
        return None

    def _domain_atoms(self, sh) -> Optional[FrozenSet[str]]:
        if isinstance(sh, SFun):
            return frozenset(sh.keys)
        if isinstance(sh, SRec):
            return frozenset(f for f, _, _ in sh.fields)
        if isinstance(sh, SEnum):
            return frozenset(enum_fields(sh))
        if sh is None or sh == SSeq(None, 0):
            return frozenset()  # DOMAIN of the empty function is {}
        if isinstance(sh, SUnion):
            # alternatives with no DOMAIN (atoms flowing through guards)
            # are runtime-unreachable in DOMAIN position - skip them
            out = frozenset()
            any_dom = False
            for a in sh.alts:
                d = self._domain_atoms(a)
                if d is not None:
                    any_dom = True
                    out |= d
            return out if any_dom else None
        return None

    def _apply_shape(self, base, arg_ast, env) -> Optional[Shape]:
        shapes = base.alts if isinstance(base, SUnion) else (base,)
        out = None
        for sh in shapes:
            if isinstance(sh, STup):
                k = self._const(arg_ast, env)
                if is_int(k) and 1 <= k <= len(sh.items):
                    out = join(out, sh.items[k - 1])
                elif k is _NOVAL:
                    for x in sh.items:
                        out = join(out, x)
            elif isinstance(sh, SRec):
                if arg_ast[0] == "str":
                    f = sh.field(arg_ast[1])
                    if f is not None:
                        out = join(out, f[0])
                else:
                    for _, s, _ in sh.fields:
                        out = join(out, s)
            elif isinstance(sh, SFun):
                out = join(out, sh.val)
            elif isinstance(sh, SSeq):
                out = join(out, sh.elem)
            elif isinstance(sh, SEnum):
                names = [arg_ast[1]] if arg_ast[0] == "str" \
                    else enum_fields(sh)
                for f in names:
                    got = enum_field(sh, f)
                    if got is not None:
                        out = join(out, got[0])
        return out

    def _binop_shape(self, ast, env) -> Optional[Shape]:
        _, sym, la, ra = ast
        a = self._abstract(la, env)
        b = self._abstract(ra, env)
        if sym in (r"\cup", r"\cap", "\\"):
            ea = self._elem_shape(a)
            eb = self._elem_shape(b)
            if sym == r"\cup":
                return SSet(join(ea, eb))
            return SSet(ea)
        if sym in ("+", "-", "*"):
            if a is None or b is None:
                return None  # an operand no pass has given a value yet
            if isinstance(a, SInt) and isinstance(b, SInt):
                if sym == "+":
                    return SInt(a.lo + b.lo, a.hi + b.hi)
                if sym == "-":
                    return SInt(a.lo - b.hi, a.hi - b.lo)
                corners = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo,
                           a.hi * b.hi]
                return SInt(min(corners), max(corners))
            return SInt(-(1 << 30), 1 << 30)
        if sym == "..":
            if isinstance(a, SInt) and isinstance(b, SInt):
                return SSet(SInt(a.lo, b.hi))
            raise ShapeError(".. over non-ints")
        if sym == r"\o":
            sa, sb = (_as_seq(x) if isinstance(x, STup) else x
                      if isinstance(x, SSeq) else SSeq(None, 0)
                      for x in (a, b))
            return SSeq(join(sa.elem, sb.elem), sa.cap + sb.cap)
        if sym == ":>":
            keys = self._atoms_of(a)
            if keys is None:
                raise ShapeError(":> with non-atom key")
            # single-key function; with several possible keys each is
            # optional (exactly one will be present at runtime)
            opt = len(keys) > 1
            return SRec(tuple(
                (k, b, opt) for k in sorted(keys)
            ))
        if sym == "@@":
            return self._merge_fun_shapes(a, b)
        raise ShapeError(f"cannot abstract binop {sym}")

    def _merge_fun_shapes(self, a, b) -> Shape:
        def as_fun(sh):
            """Function-like view of sh, or None.  Non-function
            alternatives (e.g. the defaultInitValue atom flowing through
            Write's argument) are guard-unreachable at runtime - TLC
            would error on them too - so they contribute nothing."""
            if isinstance(sh, SRec):
                return sh
            if isinstance(sh, SFun):
                return SRec(tuple(
                    (k, sh.val, sh.partial) for k in sh.keys
                ))
            if sh == SSeq(None, 0):
                return SRec(())
            if isinstance(sh, SUnion):
                out = None
                for alt in sh.alts:
                    f = as_fun(alt)
                    if f is not None:
                        out = join(out, f)
                return out
            return None

        fa, fb = as_fun(a), as_fun(b)
        if fa is None and fb is None:
            raise ShapeError(f"@@ over {a} and {b}")
        if fa is None:
            return fb
        if fb is None:
            return fa
        if isinstance(fa, SRec) or isinstance(fb, SRec):
            # record-style merge: union fields; a's fields win (present),
            # b-only fields keep b's optionality
            fields: Dict[str, Tuple[Shape, bool]] = {}
            if isinstance(fb, SRec):
                for f, s, o in fb.fields:
                    fields[f] = (s, o)
            else:
                for k in fb.keys:
                    fields[k] = (fb.val, fb.partial)
            if isinstance(fa, SRec):
                for f, s, o in fa.fields:
                    if f in fields:
                        fields[f] = (join(fields[f][0], s),
                                     fields[f][1] and o)
                    else:
                        fields[f] = (s, o)
            else:
                for k in fa.keys:
                    old = fields.get(k)
                    if old:
                        fields[k] = (join(old[0], fa.val),
                                     old[1] and fa.partial)
                    else:
                        fields[k] = (fa.val, fa.partial)
            return SRec(tuple(sorted(
                (f, s, o) for f, (s, o) in fields.items()
            )))
        keys = tuple(sorted(set(fa.keys) | set(fb.keys)))
        partial = fa.partial and fb.partial
        return SFun(keys, join(fa.val, fb.val), partial)

    def _except_shape(self, base, path_asts, val_ast, env):
        shapes = base.alts if isinstance(base, SUnion) else (base,)
        out = None
        for sh in shapes:
            out = join(out, self._except_one(sh, path_asts, val_ast, env))
        return out

    def _except_one(self, sh, path_asts, val_ast, env):
        idx_ast = path_asts[0]
        if sh is None or sh == SSeq(None, 0):
            # bottom / empty container: early fixpoint iterations see
            # EXCEPT before any assignment populated the base shape
            if idx_ast[0] == "str":
                sh = SRec(((idx_ast[1], None, True),))
            else:
                return None
        if isinstance(sh, SRec) and idx_ast[0] != "str":
            # dynamic index ![self]: the update may land on any key -
            # join the new value into every field (sound over-approx)
            fields = []
            for fn, s, o in sh.fields:
                if len(path_asts) > 1:
                    new = self._except_one(s, path_asts[1:], val_ast, env)
                else:
                    env2 = dict(env)
                    env2["@"] = s
                    new = self._abstract(val_ast, env2)
                fields.append((fn, join(s, new), o))
            return SRec(tuple(fields))
        if isinstance(sh, SRec) and idx_ast[0] == "str":
            f = sh.field(idx_ast[1])
            old = f[0] if f else None
            if len(path_asts) > 1:
                new = self._except_one(old, path_asts[1:], val_ast, env)
            else:
                env2 = dict(env)
                env2["@"] = old
                new = self._abstract(val_ast, env2)
            fields = []
            seen = False
            for fn, s, o in sh.fields:
                if fn == idx_ast[1]:
                    fields.append((fn, join(s, new), o))
                    seen = True
                else:
                    fields.append((fn, s, o))
            if not seen:
                fields.append((idx_ast[1], new, True))
            return SRec(tuple(sorted(fields)))
        if isinstance(sh, STup):
            k = self._const(idx_ast, env)
            items = []
            for i, old in enumerate(sh.items, start=1):
                if k is not _NOVAL and k != i:
                    items.append(old)
                    continue
                if len(path_asts) > 1:
                    new = self._except_one(old, path_asts[1:], val_ast,
                                           env)
                else:
                    env2 = dict(env)
                    env2["@"] = old
                    new = self._abstract(val_ast, env2)
                # the one component a constant index names is replaced;
                # any component an unknown index may name is joined
                items.append(new if k is not _NOVAL else join(old, new))
            return STup(tuple(items))
        if isinstance(sh, SFun):
            old = sh.val
            if len(path_asts) > 1:
                new = self._except_one(old, path_asts[1:], val_ast, env)
            else:
                env2 = dict(env)
                env2["@"] = old
                new = self._abstract(val_ast, env2)
            return SFun(sh.keys, join(sh.val, new), sh.partial)
        if isinstance(sh, SSeq):
            old = sh.elem
            if len(path_asts) > 1:
                new = self._except_one(old, path_asts[1:], val_ast, env)
            else:
                env2 = dict(env)
                env2["@"] = old
                new = self._abstract(val_ast, env2)
            return SSeq(join(sh.elem, new), sh.cap)
        raise ShapeError(f"EXCEPT on shape {sh}")

    def _call_shape(self, ast, env) -> Optional[Shape]:
        _, name, args = ast
        d = env.get(name)
        if not isinstance(d, Definition):
            d = self.ev.defs.get(name)
        if isinstance(d, Definition):
            env2 = dict(env)
            for p, a in zip(d.params, args):
                self._bind(env2, p, self._abstract(a, env),
                           self._const(a, env))
            return self._abstract(d.body, env2)
        if name in ("FoldFunctionOnSet", "FoldFunction"):
            # + or * over a function of integers (eval.fold_args): the
            # hull over any subset of the keys
            f = self._abstract(args[2], env)
            base = self._abstract(args[1], env)
            vals = [s for _, s, _ in f.fields] if isinstance(f, SRec) \
                else []
            if args[0] == ("opsym", "+") and isinstance(base, SInt) \
                    and vals and all(isinstance(s, SInt) for s in vals):
                return SInt(base.lo + sum(min(s.lo, 0) for s in vals),
                            base.hi + sum(max(s.hi, 0) for s in vals))
            return SInt(-(1 << 30), 1 << 30)
        if name in ("Cardinality", "Len"):
            return SInt(0, 64)
        if name in ("Head", "Tail", "Append"):
            sh = self._abstract(args[0], env)
            if isinstance(sh, STup):
                sh = _as_seq(sh)
        if name == "Head":
            if isinstance(sh, SSeq):
                return sh.elem
            return None
        if name == "Tail":
            if isinstance(sh, SSeq):
                return SSeq(sh.elem, max(sh.cap - 1, 0))
            return sh
        if name == "Append":
            el = self._abstract(args[1], env)
            cap = sh.cap if isinstance(sh, SSeq) else 0
            elem = sh.elem if isinstance(sh, SSeq) else None
            return SSeq(join(elem, el), cap + 1)
        if name == "Assert":
            return SBool()
        raise ShapeError(f"cannot abstract call {name}")


# a hint's "no bound on this side" (Nat's upper side)
UNBOUNDED = 1 << 30

_INT_THRESHOLDS = (1, 3, 7, 15, 31, 63, 127, 255, 511, 1023, 4095,
                   16383, 65535)


def _widen(old: Optional[Shape], new: Optional[Shape]) -> Optional[Shape]:
    """Accelerate int-range growth to the next threshold (sticky at the
    top) so the fixpoint terminates; recurses through containers."""
    if new is None or old is None or old == new:
        return new
    if isinstance(new, SInt) and isinstance(old, SInt):
        hi = new.hi
        if hi > old.hi:
            hi = next((t for t in _INT_THRESHOLDS if t >= hi),
                      _INT_THRESHOLDS[-1])
        lo = new.lo
        if lo < old.lo:
            lo = -next((t for t in _INT_THRESHOLDS if t >= -lo),
                       _INT_THRESHOLDS[-1]) - 1
        return SInt(min(lo, hi), hi)
    if isinstance(new, SRec) and isinstance(old, SRec):
        return SRec(tuple(
            (f, _widen(old.field(f)[0] if old.field(f) else None, s), o)
            for f, s, o in new.fields
        ))
    if isinstance(new, SSet) and isinstance(old, SSet):
        return SSet(_widen(old.elem, new.elem))
    if isinstance(new, SSeq) and isinstance(old, SSeq):
        return SSeq(_widen(old.elem, new.elem), new.cap)
    if isinstance(new, STup) and isinstance(old, STup) \
            and len(new.items) == len(old.items):
        return STup(tuple(_widen(o, n)
                          for o, n in zip(old.items, new.items)))
    if isinstance(new, SUnion) and isinstance(old, SUnion):
        olds = {type(a): a for a in old.alts}
        return SUnion(tuple(
            _widen(olds.get(type(a)), a) for a in new.alts
        ))
    return new


def _mentions_prime_static(ast, defs, _seen=None) -> bool:
    if _seen is None:
        _seen = set()
    stack = [ast]
    while stack:
        node = stack.pop()
        if isinstance(node, tuple):
            if node and node[0] in ("prime", "unchanged"):
                return True
            if node and node[0] in ("call", "name"):
                d = defs.get(node[1])
                if d is not None and node[1] not in _seen:
                    _seen.add(node[1])
                    stack.append(d.body)
            stack.extend(x for x in node if isinstance(x, (tuple, list)))
        elif isinstance(node, list):
            stack.extend(x for x in node if isinstance(x, (tuple, list)))
    return False


def typeok_hints(ev: Evaluator, invariants: Dict[str, tuple],
                 variables) -> Dict[str, Shape]:
    """Extract per-variable bounds from TypeOK-style conjuncts: the same
    place TLC users document type bounds (`x \\in 0..N`,
    `f \\in [S -> D]`).  Ints get one value of slack beyond the declared
    bound so an off-by-one violation still encodes faithfully and is
    reported as the invariant violation it is (values beyond the slack
    hit the runtime range trap instead)."""
    hints: Dict[str, Shape] = {}

    def set_shape(v) -> Optional[Shape]:
        """ELEMENT shape of a constant set value, with int slack.  Nat
        bounds from below alone, Int and STRING say nothing; a record
        or function set with such a part (eval.LazySet) is taken field
        by field, a part that says nothing left out."""
        if isinstance(v, frozenset):
            sh = None
            for x in v:
                sh = join(sh, shape_of_value(x))
            return _slack(sh)
        if v is BUILTIN_SETS["Nat"]:
            return SInt(-1, UNBOUNDED)
        if isinstance(v, LazySet):
            if v.kind == "seq":
                return SSeq(set_shape(v.parts), 0)
            if v.kind == "subset":
                return SSet(set_shape(v.parts))
            if v.kind == "diff":
                return set_shape(v.parts[0])
            fields = [(f, set_shape(d)) for f, d in v.fields()]
            if not all(isinstance(f, str) or is_int(f) for f, _ in fields):
                return None
            if [f for f, _ in fields] == list(range(1, len(fields) + 1)):
                return STup(tuple(sh for _, sh in fields))
            return SRec(tuple(
                (f, sh, False) for f, sh in fields if sh is not None))
        return None

    def dom_shape(ast) -> Optional[Shape]:
        try:
            return set_shape(ev.eval(ast, {}))
        except Exception:
            return None

    def visit(ast):
        if not isinstance(ast, tuple):
            return
        if ast[0] == "and":
            for x in ast[1]:
                visit(x)
            return
        if ast[0] == "cmp" and ast[1] == r"\in" and ast[2][0] == "name" \
                and ast[2][1] in variables:
            var = ast[2][1]
            rhs = ast[3]
            if rhs[0] == "funcset":
                # key by key, without enumerating the function space
                try:
                    keys = ev.eval(rhs[1], {})
                except Exception:
                    keys = None
                val_sh = dom_shape(rhs[2])
                if val_sh is not None and isinstance(keys, frozenset) \
                        and keys and (
                            all(isinstance(k, str) for k in keys)
                            or all(is_int(k) for k in keys)):
                    hints[var] = STup((val_sh,) * len(keys)) \
                        if sorted(keys) == list(range(1, len(keys) + 1)) \
                        else SRec(tuple(
                            (k, val_sh, False) for k in sorted(keys)))
            else:
                sh = dom_shape(rhs)
                if sh is not None:
                    hints[var] = sh
        if ast[0] == "cmp" and ast[1] == r"\subseteq" \
                and ast[2][0] == "name" and ast[2][1] in variables:
            # `msgs \subseteq Message`: the declared set IS the element
            # universe, value for value (no slack: a value outside it
            # has no bit, and the compiled union traps on it)
            try:
                v = ev.eval(ast[3], {})
            except Exception:
                return
            if isinstance(v, frozenset) and 0 < len(v) <= ENUM_LIMIT:
                hints[ast[2][1]] = SSet(SEnum(tuple(sorted(v, key=repr))))

    for ast in invariants.values():
        visit(ast)
    return hints


class LeafBound(NamedTuple):
    """One integer leaf a cfg's CONSTRAINT bounds: the variable, the
    path of keys / fields down to the leaf, the bound on each side
    (None: that side is not bounded) and the constraint that gives it."""

    var: str
    path: tuple
    lo: Optional[int]
    hi: Optional[int]
    by: str


def constraint_bounds(ev: Evaluator, constraints: Dict[str, tuple],
                      variables, of_len: bool = False) -> List[LeafBound]:
    """What the constraints' conjunction says, leaf by leaf, about
    integer leaves: conjuncts `leaf <= c`, `<`, `>=`, `>`, `=` with a
    constant side, under `\\A x \\in S` over constant sets, where `leaf`
    is a variable or a path of constant keys and fields into one
    (`counter[i]`, `token.q`).  Anything else in a constraint (a
    disjunction, a sum) bounds no leaf by itself and is left to the
    predicate.  Every KEPT state lies inside these bounds: the shape
    inference reads successors' shapes off kept states alone
    (ShapeInference.run), and the preflight report shows them.
    `of_len`: the same walk for what the predicates say about the
    LENGTH of a sequence leaf (`Len(network[p][q]) <= 3`) and nothing
    else: seq_cap_bounds."""
    found: Dict[tuple, list] = {}

    def const(ast, env):
        try:
            return ev.eval(ast, env)
        except Exception:
            return _NOVAL

    def leaf_of(ast, env):
        path = []
        if (ast[0] == "call" and ast[1] == "Len"
                and len(ast[2]) == 1) != of_len:
            return None
        if of_len:
            ast = ast[2][0]
        while ast[0] == "apply":
            k = const(ast[2], env)
            if not (isinstance(k, str) or is_int(k)):
                return None
            path.append(k)
            ast = ast[1]
        if ast[0] == "name" and ast[1] in variables and ast[1] not in env:
            return ast[1], tuple(reversed(path))
        return None

    flip = {"<": ">", ">": "<", "<=": ">=", ">=": "<=", "=": "="}

    def visit(ast, env, by):
        if not isinstance(ast, tuple) or not ast:
            return
        if ast[0] == "and":
            for x in ast[1]:
                visit(x, env, by)
        elif ast[0] == "forall":
            _, names, dom_ast, body = ast
            dom = const(dom_ast, env)
            if isinstance(dom, frozenset) and len(dom) ** len(names) <= 4096:
                for combo in _product(sorted(dom, key=repr),
                                      repeat=len(names)):
                    visit(body, {**env, **dict(zip(names, combo))}, by)
        elif ast[0] in ("name", "call"):
            d = ev.defs.get(ast[1])
            if d is not None and len(d.params) == len(
                    ast[2] if ast[0] == "call" else ()):
                args = [const(a, env) for a in (
                    ast[2] if ast[0] == "call" else ())]
                if all(a is not _NOVAL for a in args):
                    visit(d.body, {**env, **dict(zip(d.params, args))}, by)
        elif ast[0] == "cmp" and ast[1] in flip:
            for lhs, rhs, sym in ((ast[2], ast[3], ast[1]),
                                  (ast[3], ast[2], flip[ast[1]])):
                leaf, c = leaf_of(lhs, env), const(rhs, env)
                if leaf is None or not is_int(c):
                    continue
                lo, hi = {"<": (None, c - 1), "<=": (None, c),
                          ">": (c + 1, None), ">=": (c, None),
                          "=": (c, c)}[sym]
                cur = found.setdefault(leaf, [None, None, by])
                if lo is not None:
                    cur[0] = lo if cur[0] is None else max(cur[0], lo)
                if hi is not None:
                    cur[1] = hi if cur[1] is None else min(cur[1], hi)
                break

    for name, ast in constraints.items():
        visit(ast, {}, name)
    return [LeafBound(v, path, lo, hi, by)
            for (v, path), (lo, hi, by) in sorted(
                found.items(), key=lambda kv: (kv[0][0], repr(kv[0][1])))]


def _over_components(sh: Optional[Shape], fn, path):
    """`sh` rebuilt with `fn(component, its path)` in place of every
    component of a tuple (the keys 1..n) or a record (its fields); `sh`
    itself where it is neither."""
    if isinstance(sh, SRec):
        return SRec(tuple((f, fn(s, path + (f,)), o)
                          for f, s, o in sh.fields))
    if isinstance(sh, STup):
        return STup(tuple(fn(s, path + (k,))
                          for k, s in enumerate(sh.items, start=1)))
    return sh


def seq_cap_bounds(ev: Evaluator, predicates: Dict[str, tuple],
                   variables) -> List[LeafBound]:
    """The capacities the spec DECLARES for its sequences: conjuncts
    `Len(leaf) <= c` (`<`, `=`) of the invariants and the cfg's
    CONSTRAINT, under `\\A` over constant sets, `leaf` a variable or a
    path of constant keys into one (`BoundedNetwork == \\A p, q \\in
    Proc : Len(network[p][q]) <= 3`).  An invariant is a claim, not a
    fact: the capacity read off it is guarded by the Append trap like a
    guessed one, and a run that trips it starts again a rung higher -
    where the invariant then fails as the invariant it is."""
    return [b for b in constraint_bounds(ev, predicates, variables,
                                         of_len=True)
            if b.hi is not None and b.hi >= 0]


def caps_by_var(seq_caps) -> Dict[str, list]:
    """seq_cap_bounds' LeafBounds as cap_sequences takes them: {var:
    [(path, cap)]}."""
    out: Dict[str, list] = {}
    for b in seq_caps or ():
        out.setdefault(b.var, []).append((b.path, b.hi))
    return out


def cap_sequences(sh: Optional[Shape], caps, floor: int = 0, path=()):
    """`sh` with every growing sequence held to its capacity: what the
    spec declares at its path (`caps`: (path, cap) pairs), else the
    first guess, and at least `floor` (the rung).  A tuple's components
    are its paths' next keys, a record's its fields."""
    if isinstance(sh, SSeq):
        declared = [c for p, c in caps if p == path]
        cap = max(min(declared) if declared else SEQ_CAP_GUESS, floor)
        return sh if sh.cap <= cap else SSeq(sh.elem, cap)
    if isinstance(sh, SUnion):
        return SUnion(tuple(cap_sequences(a, caps, floor, path)
                            for a in sh.alts))
    return _over_components(
        sh, lambda s, p: cap_sequences(s, caps, floor, p), path)


def seq_summary(var_shapes: Dict[str, Shape], caps: List[LeafBound]):
    """(slots, from, largest) of the sequences of a model's layout: the
    static slots of all of them; where their capacities came from -
    "declared" where the spec declares one for every sequence that can
    hold an element, "guess" where some capacity is the first guess or a
    rung's, None where the model has no such sequence; and the largest
    capacity (what a rung widens from: struct.cache.widen_seq_caps)."""
    declared = {(b.var, b.path): b.hi for b in caps}
    slots, guessed = [], []

    def visit(var, sh, path):
        if isinstance(sh, SSeq):
            if sh.cap:
                slots.append(sh.cap)
                guessed.append(declared.get((var, path), -1) < sh.cap)
        elif isinstance(sh, STup):
            for k, x in enumerate(sh.items, start=1):
                visit(var, x, path + (k,))
        elif isinstance(sh, SRec):
            for f, x, _ in sh.fields:
                visit(var, x, path + (f,))
        elif isinstance(sh, SUnion):
            for a in sh.alts:
                visit(var, a, path)

    for v, sh in var_shapes.items():
        visit(v, sh, ())
    return sum(slots), (None if not slots else "guess" if any(guessed)
                        else "declared"), max(slots, default=0)


def apply_leaf_bounds(sh: Optional[Shape], bounds, path=()):
    """`sh` met with the LeafBounds of one variable (`bounds`: (path,
    lo, hi) triples): the shape of the variable over KEPT states."""
    if isinstance(sh, SInt):
        lo, hi = sh.lo, sh.hi
        for p, blo, bhi in bounds:
            if p == path:
                lo = lo if blo is None else max(lo, blo)
                hi = hi if bhi is None else min(hi, bhi)
        # a leaf the constraint empties here keeps one value: the
        # abstract pass has then not reached a kept state yet
        return SInt(min(lo, hi), hi) if lo <= hi else SInt(hi, hi)
    return _over_components(
        sh, lambda s, p: apply_leaf_bounds(s, bounds, p), path)


OPEN_SIDE_FACTOR = 8


def cap_open_sides(sh: Optional[Shape], bounds, factor: int, path=()):
    """An integer leaf a cfg's CONSTRAINT bounds on ONE side is, in the
    abstract, unbounded on the other (`counter[i] <= 3` with a
    decrement no guard of which an interval sees): left alone the
    widening runs that side to its last threshold, 17 bits a leaf.  It
    is capped instead at the first threshold at least `factor` times
    the bounded side's magnitude - a guess, not a proof: the range trap
    guards it, and a run that trips the trap starts again with the
    factor 16 times as large (struct.cache.widen_open_sides)."""
    if isinstance(sh, SInt):
        lo, hi = sh.lo, sh.hi
        for p, blo, bhi in bounds:
            if p != path or (blo is None) == (bhi is None):
                continue
            if blo is None:
                t = factor * max(1, abs(hi))
                cap = -next((x for x in _INT_THRESHOLDS if x >= t),
                            _INT_THRESHOLDS[-1]) - 1
                lo = max(lo, min(cap, hi))
            else:
                t = factor * max(1, abs(lo))
                cap = next((x for x in _INT_THRESHOLDS if x >= t),
                           _INT_THRESHOLDS[-1])
                hi = min(hi, max(cap, lo))
        return SInt(lo, hi)
    return _over_components(
        sh, lambda s, p: cap_open_sides(s, bounds, factor, p), path)


def _slack(sh: Optional[Shape]) -> Optional[Shape]:
    if isinstance(sh, SInt):
        return SInt(sh.lo - 1, sh.hi + 1)
    return sh


def _clamp(sh: Optional[Shape], hint: Optional[Shape]) -> Optional[Shape]:
    """Meet `sh` with a TypeOK hint (ints narrowed; containers
    recursed); anything the hint does not constrain stays as inferred."""
    if sh is None or hint is None:
        return sh
    if isinstance(sh, SInt) and isinstance(hint, SInt):
        lo = max(sh.lo, hint.lo)
        hi = min(sh.hi, hint.hi)
        return SInt(lo, max(lo, hi))
    if isinstance(sh, SRec) and isinstance(hint, SRec):
        return SRec(tuple(
            (f, _clamp(s, hint.field(f)[0] if hint.field(f) else None),
             o)
            for f, s, o in sh.fields
        ))
    if isinstance(sh, SSet) and isinstance(hint, SSet):
        if isinstance(hint.elem, SEnum):
            # a declared universe replaces the inferred one where it is
            # the smaller of the two
            if isinstance(sh.elem, SEnum):
                return sh
            try:
                n = len(universe(sh.elem, len(hint.elem.values)))
            except ShapeError:
                n = None
            return sh if n is not None and n <= len(hint.elem.values) \
                else hint
        return SSet(_clamp(sh.elem, hint.elem))
    if isinstance(sh, STup) and isinstance(hint, STup) \
            and len(sh.items) == len(hint.items):
        return STup(tuple(_clamp(s, h)
                          for s, h in zip(sh.items, hint.items)))
    if isinstance(sh, SSeq):
        elem_hint = hint.elem if isinstance(hint, SSeq) else (
            hint if isinstance(hint, SInt) else None)
        return SSeq(_clamp(sh.elem, elem_hint), sh.cap)
    if isinstance(sh, SUnion):
        return SUnion(tuple(_clamp(a, hint) if isinstance(a, type(hint))
                            else a for a in sh.alts))
    return sh


def shape_leq(a: Optional[Shape], b: Optional[Shape]) -> bool:
    """Abstract-domain containment: every concrete value of `a` is a
    value of `b`.  Conservative (False on anything unproven) - this is
    the check that CERTIFIES a narrowed bound environment as a
    post-fixpoint (analysis.absint), so an unprovable containment must
    fail closed."""
    if a is None:
        return True  # bottom
    if b is None:
        return False
    if a == b:
        return True
    # the empty container coerces across container classes (see join)
    if a == SSeq(None, 0) and isinstance(b, (SFun, SRec, SSeq)):
        return True
    if isinstance(b, SUnion):
        alts = a.alts if isinstance(a, SUnion) else (a,)
        return all(any(shape_leq(x, alt) for alt in b.alts)
                   for x in alts)
    if isinstance(a, SUnion):
        return all(shape_leq(x, b) for x in a.alts)
    if type(a) is not type(b):
        return False
    if isinstance(a, SBool):
        return True
    if isinstance(a, SInt):
        return b.lo <= a.lo and a.hi <= b.hi
    if isinstance(a, SAtoms):
        return a.atoms <= b.atoms
    if isinstance(a, SEnum):
        return set(a.values) <= set(b.values)
    if isinstance(a, SRec):
        bf = {f: (s, o) for f, s, o in b.fields}
        for f, s, o in a.fields:
            if f not in bf:
                return False
            bs, bo = bf[f]
            if o and not bo:
                return False  # a may omit the field; b cannot
            if not shape_leq(s, bs):
                return False
        # fields of b absent from a must be omittable in b
        anames = {f for f, _, _ in a.fields}
        return all(o for f, _, o in b.fields if f not in anames)
    if isinstance(a, SSet):
        return shape_leq(a.elem, b.elem)
    if isinstance(a, SSeq):
        return a.cap <= b.cap and shape_leq(a.elem, b.elem)
    if isinstance(a, STup):
        return len(a.items) == len(b.items) and all(
            shape_leq(x, y) for x, y in zip(a.items, b.items))
    if isinstance(a, SFun):
        if not set(a.keys) <= set(b.keys):
            return False
        if not b.partial and (a.partial or set(a.keys) != set(b.keys)):
            return False
        return shape_leq(a.val, b.val)
    return False


def infer_shapes(ev: Evaluator, variables, init_ast, next_ast,
                 hints: Optional[Dict[str, Shape]] = None,
                 const_hints: Optional[Dict[str, Shape]] = None,
                 kept: Optional[List[LeafBound]] = None,
                 open_side_factor: int = OPEN_SIDE_FACTOR,
                 seq_caps: Optional[List[LeafBound]] = None,
                 seq_cap_floor: int = 0) -> Dict[str, Shape]:
    """`kept` (constraint_bounds of a cfg's CONSTRAINT): successors are
    only ever taken of states inside these leaf bounds, and a leaf they
    bound on one side is capped on the other (cap_open_sides).
    `seq_caps` (seq_cap_bounds): the capacities the spec declares for
    its sequences; `seq_cap_floor`: the least capacity of every
    sequence once a rung was taken (cap_sequences)."""
    inf = ShapeInference(ev, variables, init_ast, next_ast)
    inf.seq_cap_floor = seq_cap_floor
    inf.seq_caps = caps_by_var(seq_caps)
    inf.hints = hints or {}
    inf.open_side_factor = open_side_factor
    inf.kept_bounds = {}
    for b in kept or ():
        inf.kept_bounds.setdefault(b.var, []).append((b.path, b.lo, b.hi))
    if const_hints:
        inf.const_hints = dict(const_hints)
    return inf.run()
