"""TLA+ value semantics for the structural frontend (E1).

Evaluates the parser's ASTs over the oracle's canonical value model
(spec.oracle State docstring): sets are frozensets, records/functions
are key-sorted tuples of (key, value) pairs, sequences are tuples -
so states produced here compare equal to hand-oracle states directly.

Covers the full expression language of the reference's committed
translation (/root/reference/KubeAPI.tla:373-768) plus its invariants
and define-block operators (:376-446,776-789): DOMAIN, :> and @@, IF /
CASE / LET / CHOOSE, set filter/map, sequence ops (Head/Tail/Append/
\\o/Len), function sets [S -> T], EXCEPT paths, user operator
application, Assert.  CHOOSE picks the canonically-least witness
(deterministic; TLC's pick is also deterministic but order-internal -
for specs whose CHOOSE is semantically unique, e.g. KubeAPI's Get
:311 under the OnlyOneVersion invariant, the values agree).

Original implementation; TLC's evaluator is Java and none of it is
translated here.
"""

from __future__ import annotations

from itertools import permutations as _permutations
from itertools import product as _product
from typing import Dict, Optional

from ..spec.labels import DEFAULT_INIT
from .parser import Definition

_SORT_KEY = repr  # deterministic iteration order over set elements


class StructEvalError(ValueError):
    pass


class TlaAssertionError(ValueError):
    """A TLA+ Assert(...) fired during action evaluation."""

    def __init__(self, msg: str):
        super().__init__(msg)
        self.tla_msg = msg


class UnboundPrime(StructEvalError):
    """A primed variable was read before the action assigned it."""


class _Sentinel:
    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return self.name


STRING = _Sentinel("STRING")
NAT = _Sentinel("Nat")
INT = _Sentinel("Int")

class LazySet:
    """A set that is only ever asked for membership, because a part of
    it is infinite or its enumeration is beside the point: `[Node ->
    Int]`, `[pos : Node, q : Int]` (the type invariants of specs over
    unbounded integers), `Seq(Message)`, `Nat \\ {0}`, the powerset of
    a large set.  `kind` is "funcset" (parts: domain, range), "recset"
    (parts: ((field, set), ...)), "seq" (parts: the element set),
    "subset" (parts: the base set) or "diff" (parts: left, right)."""

    def __init__(self, kind, parts):
        self.kind = kind
        self.parts = parts

    def fields(self):
        """[(key or field, the set its value lies in), ...] in key
        order: what a member is checked against, part by part."""
        if self.kind == "recset":
            return sorted(self.parts, key=lambda p: p[0])
        dom, rng = self.parts
        return [(k, rng) for k in sorted(dom, key=_SORT_KEY)]

    def __repr__(self):
        return f"<{self.kind} with an infinite part>"


# SUBSET S is enumerated up to this many elements of S, and is a set
# asked for membership alone (LazySet) beyond
SUBSET_ENUM_LIMIT = 12

BUILTIN_SETS = {
    "STRING": STRING,
    "Nat": NAT,
    "Int": INT,
    "BOOLEAN": frozenset({False, True}),
}


def canon(v):
    """Canonicalize nested containers to the oracle value model.

    A tuple of (string, value) pairs reads as a string-keyed function -
    the only tuple shape the model cannot disambiguate from a sequence
    of string-first 2-tuples.  Genuine functions are always constructed
    key-sorted with distinct keys (record literal, _pairs_to_fn, EXCEPT,
    @@), so a duplicate or out-of-order key proves the value is really a
    SEQUENCE about to be silently reordered/misrouted: raise loudly
    instead (ADVICE.md eval.py:75)."""
    if isinstance(v, tuple) and v and all(
        isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)
        for x in v
    ):
        keys = [k for k, _ in v]
        if len(set(keys)) != len(keys) or keys != sorted(keys):
            raise StructEvalError(
                "ambiguous value: a tuple of (string, value) pairs with "
                "duplicate or unsorted keys is a sequence that would be "
                f"misread as a string-keyed function: {v!r}"
            )
        return tuple(sorted((k, canon(x)) for k, x in v))
    if isinstance(v, tuple):
        return tuple(canon(x) for x in v)
    if isinstance(v, frozenset):
        return frozenset(canon(x) for x in v)
    return v


def permute_value(v, pmap):
    """Apply an atom permutation (`pmap`: atom -> atom, identity where
    absent) to a value under this module's conventions: atoms are
    strings, records/functions key-sorted tuples of (str, value) pairs,
    sets frozensets, sequences plain tuples."""
    if isinstance(v, str):
        return pmap.get(v, v)
    if isinstance(v, frozenset):
        return frozenset(permute_value(x, pmap) for x in v)
    if isinstance(v, tuple):
        if v and is_fn(v):
            return tuple(sorted(
                (permute_value(k, pmap), permute_value(x, pmap))
                for k, x in v
            ))
        return tuple(permute_value(x, pmap) for x in v)
    return v


def is_int(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool)


def is_fn(v) -> bool:
    """Function/record: non-empty tuple of (key, value) pairs whose keys
    are all strings (records, functions over model values), or all
    integers making one interval lo..hi in order that does not start at
    1 (`[0 .. N-1 -> ...]`: a function over 1..n IS a sequence, and is
    kept as one).  The empty tuple is both the empty function and the
    empty sequence - all its uses below are consistent for either
    reading.  A sequence of pairs whose first components happen to be
    such an interval reads as a function: the one shape the value model
    cannot tell apart, as with string-first pairs (`canon`)."""
    if not isinstance(v, tuple) or not all(
            isinstance(x, tuple) and len(x) == 2 for x in v):
        return False
    if all(isinstance(k, str) for k, _ in v):
        return True
    return bool(v) and all(is_int(k) for k, _ in v) and v[0][0] != 1 \
        and all(k == v[0][0] + i for i, (k, _) in enumerate(v))


class RecFn:
    """The value of a LET's `f[x \\in S] == e`, a function given by
    recursion on its argument (PaxosCommit's `Max[T \\in SUBSET S]`,
    which calls itself on `T \\ {n}`): applied by evaluating `e` with x
    bound to the argument and f to this value, one evaluation an
    argument.  Its domain is not enumerated - it may be a powerset -
    so an argument is checked for membership, and the function is
    never compared, stored in a state or asked for its DOMAIN."""

    def __init__(self, ev, name, var, dom, body, env, primed):
        self.ev, self.name, self.var = ev, name, var
        self.dom, self.body = dom, body
        self.env, self.primed = env, primed
        self._memo: dict = {}

    def __call__(self, arg):
        if arg not in self._memo:
            if not Evaluator._member(arg, self.dom):
                raise StructEvalError(
                    f"{arg!r} not in the domain of {self.name}")
            env = dict(self.env)
            env[self.var] = arg
            env[self.name] = self
            self._memo[arg] = self.ev.eval(self.body, env, self.primed)
        return self._memo[arg]


def fn_apply(f, arg):
    if isinstance(f, RecFn):
        return f(arg)
    if isinstance(f, tuple):
        if f and is_fn(f):
            for k, v in f:
                if k == arg:
                    return v
            raise StructEvalError(f"{arg!r} not in DOMAIN")
        if isinstance(arg, int) and 1 <= arg <= len(f):
            return f[arg - 1]
        raise StructEvalError(f"index {arg!r} outside sequence/function")
    raise StructEvalError(f"cannot apply non-function {f!r}")


def fn_domain(f):
    if isinstance(f, tuple):
        if f and is_fn(f):
            return frozenset(k for k, _ in f)
        return frozenset(range(1, len(f) + 1))
    raise StructEvalError(f"DOMAIN of non-function {f!r}")


def fn_merge(left, right):
    """left @@ right: domain union, left-biased (TLC's TLC.tla @@)."""
    if not (is_fn(left) and is_fn(right)):
        raise StructEvalError("@@ expects functions")
    d = dict(right)
    d.update(dict(left))
    return tuple(sorted(d.items()))


class Evaluator:
    """Expression evaluator over a module's definitions + constants."""

    def __init__(self, defs: Dict[str, Definition],
                 constants: Dict[str, object]):
        self.defs = defs
        self.constants = constants

    # -- name resolution ---------------------------------------------------

    def _resolve_name(self, name: str, env: dict, primed: Optional[dict]):
        if env is not None and name in env:
            v = env[name]
            if isinstance(v, Definition):
                if v.params:
                    raise StructEvalError(
                        f"operator {name} needs {len(v.params)} arguments"
                    )
                return self.eval(v.body, env, primed)
            return v
        if name in self.constants:
            return self.constants[name]
        if name in BUILTIN_SETS:
            return BUILTIN_SETS[name]
        d = self.defs.get(name)
        if d is not None:
            if d.params:
                raise StructEvalError(
                    f"operator {name} needs {len(d.params)} arguments"
                )
            return self.eval(d.body, env, primed)
        raise StructEvalError(f"unknown name {name!r}")

    # -- evaluation --------------------------------------------------------

    def eval(self, ast, env: dict, primed: Optional[dict] = None):
        op = ast[0]
        if op in ("num", "str", "bool"):
            return ast[1]
        if op == "name":
            return self._resolve_name(ast[1], env, primed)
        if op == "prime":
            if primed is None or ast[1] not in primed:
                raise UnboundPrime(f"{ast[1]}' read before assignment")
            return primed[ast[1]]
        if op == "setlit":
            return frozenset(self.eval(x, env, primed) for x in ast[1])
        if op == "tuple":
            return tuple(self.eval(x, env, primed) for x in ast[1])
        if op == "record":
            return tuple(sorted(
                (k, self.eval(x, env, primed)) for k, x in ast[1]
            ))
        if op == "apply":
            return fn_apply(
                self.eval(ast[1], env, primed), self.eval(ast[2], env, primed)
            )
        if op == "domain":
            return fn_domain(self.eval(ast[1], env, primed))
        if op == "subset":
            base = self.eval(ast[1], env, primed)
            if not isinstance(base, frozenset) \
                    or len(base) > SUBSET_ENUM_LIMIT:
                return LazySet("subset", base)
            elems = sorted(base, key=_SORT_KEY)
            return frozenset(
                frozenset(x for i, x in enumerate(elems) if bits >> i & 1)
                for bits in range(1 << len(elems)))
        if op == "not":
            return not self._bool(ast[1], env, primed)
        if op == "and":
            return all(self._bool(x, env, primed) for x in ast[1])
        if op == "or":
            return any(self._bool(x, env, primed) for x in ast[1])
        if op == "implies":
            return (not self._bool(ast[1], env, primed)) or self._bool(
                ast[2], env, primed
            )
        if op == "cmp":
            return self._cmp(ast, env, primed)
        if op == "binop":
            return self._binop(ast, env, primed)
        if op == "if":
            c = self._bool(ast[1], env, primed)
            return self.eval(ast[2] if c else ast[3], env, primed)
        if op == "case":
            for g, e in ast[1]:
                if self._bool(g, env, primed):
                    return self.eval(e, env, primed)
            if ast[2] is not None:
                return self.eval(ast[2], env, primed)
            raise StructEvalError("CASE: no arm matched and no OTHER")
        if op == "let":
            env2 = dict(env)
            for name, params, body in ast[1]:
                if params:
                    env2[name] = Definition(name, params, body)
                else:
                    # non-parameterized LET bindings are evaluated eagerly
                    # (their value cannot depend on later bindings)
                    env2[name] = self.eval(body, env2, primed)
            return self.eval(ast[2], env2, primed)
        if op == "recset":
            names = [f for f, _ in ast[1]]
            parts = [self.eval(x, env, primed) for _, x in ast[1]]
            if any(not isinstance(d, frozenset) for d in parts):
                return LazySet("recset", tuple(zip(names, parts)))
            doms = [sorted(self._set(x, env, primed), key=_SORT_KEY)
                    for _, x in ast[1]]
            return frozenset(
                tuple(sorted(zip(names, combo)))
                for combo in _product(*doms)
            )
        if op == "choose":
            _, var, dom_ast, pred = ast
            if dom_ast is None:
                raise StructEvalError(
                    f"unbounded CHOOSE {var} cannot be evaluated (TLC "
                    "cannot either): override the definition in the "
                    "model's cfg"
                )
            dom = self._set(dom_ast, env, primed)
            for x in sorted(dom, key=_SORT_KEY):
                env2 = dict(env)
                env2[var] = x
                if self._bool(pred, env2, primed):
                    return x
            raise StructEvalError("CHOOSE: no witness")
        if op in ("forall", "exists"):
            _, names, dom_ast, body = ast
            dom = sorted(self._set(dom_ast, env, primed), key=_SORT_KEY)

            def results():
                # short-circuit like TLC: a witness/falsifier stops
                # enumeration before later combos can raise
                for combo in _product(dom, repeat=len(names)):
                    env2 = dict(env)
                    env2.update(zip(names, combo))
                    yield self._bool(body, env2, primed)

            return all(results()) if op == "forall" else any(results())
        if op == "setfilter":
            _, var, dom_ast, pred = ast
            dom = self._set(dom_ast, env, primed)
            out = []
            for x in sorted(dom, key=_SORT_KEY):
                env2 = dict(env)
                env2[var] = x
                if self._bool(pred, env2, primed):
                    out.append(x)
            return frozenset(out)
        if op == "setmap":
            _, expr, var, dom_ast = ast
            dom = self._set(dom_ast, env, primed)
            out = []
            for x in sorted(dom, key=_SORT_KEY):
                env2 = dict(env)
                env2[var] = x
                out.append(self.eval(expr, env2, primed))
            return frozenset(out)
        if op == "fnlit":
            _, var, dom_ast, body = ast
            dom = self._set(dom_ast, env, primed)
            pairs = []
            for x in sorted(dom, key=_SORT_KEY):
                env2 = dict(env)
                env2[var] = x
                pairs.append((x, self.eval(body, env2, primed)))
            return _pairs_to_fn(pairs)
        if op == "funcset":
            rng = self.eval(ast[2], env, primed)
            if not isinstance(rng, frozenset):
                return LazySet("funcset",
                               (self._set(ast[1], env, primed), rng))
            dom = sorted(self._set(ast[1], env, primed), key=_SORT_KEY)
            rng = sorted(self._set(ast[2], env, primed), key=_SORT_KEY)
            fns = []
            for values in _product(rng, repeat=len(dom)):
                fns.append(_pairs_to_fn(list(zip(dom, values))))
            return frozenset(fns)
        if op == "except":
            f = self.eval(ast[1], env, primed)
            for path_asts, val_ast in ast[2]:
                path = [self.eval(p, env, primed) for p in path_asts]
                f = self._except(f, path, val_ast, env, primed)
            return f
        if op == "atref":
            if "@" not in env:
                raise StructEvalError("@ outside EXCEPT")
            return env["@"]
        if op == "call":
            return self._call(ast, env, primed)
        if op == "recfn":
            _, name, var, dom_ast, body = ast
            return RecFn(self, name, var, self.eval(dom_ast, env, primed),
                         body, env, primed)
        if op == "unchanged":
            if primed is None:
                raise StructEvalError(
                    "UNCHANGED outside an action conjunction"
                )
            # an action read as a predicate on a pair of states (an
            # action property's `[A]_v`): v' = v, variable by variable
            from .actions import expand_unchanged

            return all(
                self.eval(("prime", v), env, primed)
                == self._resolve_name(v, env, primed)
                for v in expand_unchanged(ast[1], self.defs, set(primed)))
        if op in ("box", "leadsto", "spec"):
            raise StructEvalError(
                f"temporal operator {op} has no state-level value"
            )
        raise StructEvalError(f"unhandled AST node {op!r}")

    # -- helpers -----------------------------------------------------------

    def _bool(self, ast, env, primed) -> bool:
        v = self.eval(ast, env, primed)
        if not isinstance(v, bool):
            raise StructEvalError(f"expected BOOLEAN, got {v!r}")
        return v

    def _set(self, ast, env, primed) -> frozenset:
        v = self.eval(ast, env, primed)
        if not isinstance(v, frozenset):
            raise StructEvalError(f"expected a set, got {v!r}")
        return v

    def _in_funcset(self, a, ra, env, primed) -> bool:
        """a \\in [S -> T] without the function space (18^3 functions
        of PaxosCommit's aState a state, were it enumerated): the
        domain is S and every value lies in T, T itself maybe one."""
        dom = self._set(ra[1], env, primed)
        if not isinstance(a, tuple):
            return False
        pairs = a if a and is_fn(a) else tuple(enumerate(a, 1))
        if len(pairs) != len(dom) or {k for k, _ in pairs} != dom:
            return False
        if ra[2][0] == "funcset":
            return all(self._in_funcset(v, ra[2], env, primed)
                       for _, v in pairs)
        rng = self.eval(ra[2], env, primed)
        return all(self._member(v, rng) for _, v in pairs)

    def _cmp(self, ast, env, primed):
        _, sym, la, ra = ast
        a = self.eval(la, env, primed)
        if sym in (r"\in", r"\notin") and ra[0] == "funcset":
            inn = self._in_funcset(a, ra, env, primed)
            return inn if sym == r"\in" else not inn
        b = self.eval(ra, env, primed)
        if sym == "=":
            return a == b
        if sym == "#":
            return a != b
        if sym in (r"\in", r"\notin"):
            inn = self._member(a, b)
            return inn if sym == r"\in" else not inn
        if sym == r"\subseteq":
            if not (isinstance(a, frozenset) and isinstance(b, frozenset)):
                raise StructEvalError("\\subseteq expects sets")
            return a <= b
        try:
            return {"<": a < b, ">": a > b, "<=": a <= b, ">=": a >= b}[sym]
        except TypeError:
            raise StructEvalError(f"cannot order {a!r} {sym} {b!r}")

    @staticmethod
    def _member(a, b) -> bool:
        if isinstance(b, frozenset):
            return a in b
        if b is STRING:
            # model values (defaultInitValue) are not strings in TLC
            return isinstance(a, str) and a != DEFAULT_INIT
        if b is NAT:
            return isinstance(a, int) and not isinstance(a, bool) and a >= 0
        if b is INT:
            return isinstance(a, int) and not isinstance(a, bool)
        if isinstance(b, LazySet):
            if b.kind == "seq":
                return isinstance(a, tuple) and not (a and is_fn(a)) \
                    and all(Evaluator._member(x, b.parts) for x in a)
            if b.kind == "subset":
                return isinstance(a, frozenset) and all(
                    Evaluator._member(x, b.parts) for x in a)
            if b.kind == "diff":
                return Evaluator._member(a, b.parts[0]) \
                    and not Evaluator._member(a, b.parts[1])
            if not isinstance(a, tuple):
                return False
            # a function over 1..n is kept as a sequence
            pairs = a if a and is_fn(a) else tuple(enumerate(a, 1))
            want = b.fields()
            return [k for k, _ in pairs] == [f for f, _ in want] and all(
                Evaluator._member(x, dom)
                for (_, x), (_, dom) in zip(pairs, want))
        raise StructEvalError(f"\\in over non-set {b!r}")

    def _binop(self, ast, env, primed):
        _, sym, la, ra = ast
        a = self.eval(la, env, primed)
        b = self.eval(ra, env, primed)
        if sym in (r"\cup", r"\cap", "\\"):
            if sym == "\\" and isinstance(b, frozenset) and (
                    a in (NAT, INT, STRING) or isinstance(a, LazySet)):
                return LazySet("diff", (a, b))  # `Nat \ {0}`
            if not (isinstance(a, frozenset) and isinstance(b, frozenset)):
                raise StructEvalError(f"{sym} expects sets")
            return {r"\cup": a | b, r"\cap": a & b, "\\": a - b}[sym]
        if sym in ("+", "-", "*"):
            if not (isinstance(a, int) and isinstance(b, int)):
                raise StructEvalError(f"{sym} expects integers")
            return {"+": a + b, "-": a - b, "*": a * b}[sym]
        if sym == "..":
            return frozenset(range(a, b + 1))
        if sym == r"\o":
            if not (isinstance(a, tuple) and isinstance(b, tuple)):
                raise StructEvalError("\\o expects sequences")
            return a + b
        if sym == "@@":
            return fn_merge(a, b)
        if sym == ":>":
            if not isinstance(a, str):
                raise StructEvalError(":> key must be a string here")
            return ((a, b),)
        raise StructEvalError(f"unhandled binop {sym!r}")

    def _except(self, f, path, val_ast, env, primed):
        idx = path[0]
        old = fn_apply(f, idx)
        if len(path) > 1:
            val = self._except(old, path[1:], val_ast, env, primed)
        else:
            env2 = dict(env)
            env2["@"] = old
            val = self.eval(val_ast, env2, primed)
        if isinstance(f, tuple) and f and is_fn(f):
            return tuple(sorted(
                (k, val if k == idx else v) for k, v in f
            ))
        if isinstance(f, tuple) and isinstance(idx, int):
            return f[: idx - 1] + (val,) + f[idx:]
        raise StructEvalError("EXCEPT on a non-function")

    def _fold(self, name, args, env, primed):
        sym, base_ast, f_ast, set_ast = fold_args(name, args)
        acc = self.eval(base_ast, env, primed)
        f = self.eval(f_ast, env, primed)
        keys = fn_domain(f) if set_ast is None else self._set(
            set_ast, env, primed)
        for k in sorted(keys, key=_SORT_KEY):
            acc = FOLD_OPS[sym](acc, fn_apply(f, k))
        return acc

    def _call(self, ast, env, primed):
        _, name, args = ast
        target = None
        if env is not None and isinstance(env.get(name), Definition):
            target = env[name]
        elif name in self.defs:
            target = self.defs[name]
        if target is not None:
            if len(target.params) != len(args):
                raise StructEvalError(
                    f"{name} expects {len(target.params)} args, "
                    f"got {len(args)}"
                )
            env2 = dict(env)
            for p, a in zip(target.params, args):
                env2[p] = self.eval(a, env, primed)
            return self.eval(target.body, env2, primed)
        if name in ("FoldFunctionOnSet", "FoldFunction"):
            return self._fold(name, args, env, primed)
        vals = [self.eval(a, env, primed) for a in args]
        if name == "Cardinality":
            (s,) = vals
            if not isinstance(s, frozenset):
                raise StructEvalError("Cardinality expects a set")
            return len(s)
        if name == "Len":
            (s,) = vals
            if not isinstance(s, tuple) or is_fn(s) and s:
                raise StructEvalError("Len expects a sequence")
            return len(s)
        if name == "Head":
            (s,) = vals
            if not isinstance(s, tuple) or not s:
                raise StructEvalError("Head of empty/non-sequence")
            return s[0]
        if name == "Tail":
            (s,) = vals
            if not isinstance(s, tuple) or not s:
                raise StructEvalError("Tail of empty/non-sequence")
            return s[1:]
        if name == "Append":
            s, e = vals
            if not isinstance(s, tuple):
                raise StructEvalError("Append expects a sequence")
            return s + (e,)
        if name == "Seq":
            # the finite sequences over a set: membership only
            (dom,) = vals
            return LazySet("seq", dom)
        if name == "Permutations":
            # TLC module: the set of all bijections of a finite set onto
            # itself (what a cfg's SYMMETRY definition is built from)
            (dom,) = vals
            if not isinstance(dom, frozenset):
                raise StructEvalError("Permutations expects a set")
            base = sorted(dom, key=repr)
            return frozenset(
                _pairs_to_fn(list(zip(base, perm)))
                for perm in _permutations(base)
            )
        if name == "Assert":
            cond, msg = vals
            if cond is not True:
                raise TlaAssertionError(str(msg))
            return True
        raise StructEvalError(f"unknown operator {name!r}")


FOLD_OPS = {"+": lambda a, b: a + b, "*": lambda a, b: a * b}


def fold_args(name, args):
    """(operator symbol, base AST, function AST, set AST or None) of a
    call of the community module Functions' folds:
    `FoldFunctionOnSet(op, base, f, S)` folds f's values over the
    elements of S, `FoldFunction(op, base, f)` over DOMAIN f.  `op` is
    written as the bare infix symbol (`+`, `*`): commutative and
    associative, so the order TLC's CHOOSE would pick does not show."""
    want = 4 if name == "FoldFunctionOnSet" else 3
    if len(args) != want:
        raise StructEvalError(f"{name} expects {want} arguments")
    if args[0][0] != "opsym" or args[0][1] not in FOLD_OPS:
        raise StructEvalError(
            f"{name}: the folded operator has to be + or * here")
    return (args[0][1], args[1], args[2],
            args[3] if want == 4 else None)


def _pairs_to_fn(pairs):
    """Key-typed function literal: string keys -> sorted pairs; 1..n ->
    sequence; empty -> () (empty function == empty sequence)."""
    if not pairs:
        return ()
    if all(isinstance(k, str) for k, _ in pairs):
        return tuple(sorted(pairs))
    keys = {k for k, _ in pairs}
    if keys == set(range(1, len(pairs) + 1)):
        return tuple(v for _, v in sorted(pairs))
    if all(is_int(k) for k in keys) and len(keys) == len(pairs) \
            and keys == set(range(min(keys), max(keys) + 1)):
        # an integer interval that is not 1..n (`Node == 0 .. N-1`):
        # key-sorted pairs, like a record
        return tuple(sorted(pairs))
    raise StructEvalError(
        "function domains must be strings, 1..n or an integer "
        "interval here"
    )
