"""Load a TLC model directory through the structural frontend (E1).

Reads the unmodified reference artifacts the way TLC does
(MC.out:8-24's SANY pass): MC.cfg for CONSTANT/SPECIFICATION/INVARIANT/
PROPERTY, MC.tla for the generated constant-override definitions, and
the EXTENDS closure of real module files next to the config (Model_1
carries its own KubeAPI.tla copy) - falling back to the toolbox parent
directory for the root spec.  Standard modules (Naturals, FiniteSets,
Sequences, TLC; of the community modules `Functions`, whose folds the
evaluator knows) are built into the evaluator.  A cfg's `SYMMETRY <def>`
is evaluated here (`declared_symmetry`) to the constant sets whose full
permutation groups it is the union of; a cfg's `CONSTRAINT <defs>` is
resolved here (`declared_constraints`) to the state predicates whose
conjunction every kept state satisfies.  `N == INSTANCE M` (no WITH:
M's constants and variables are the instancing module's of the same
name) defines `N!Op` for every definition of M (`_instantiate`), M found
on the same search path and hashed into the model's digest.  A cfg
PROPERTY that unfolds to `I /\\ [][A]_v` - another specification, the
module's refinement theorem - is an action property
(`declared_action_properties`): I on the initial states, `A \\/ v' = v`
on every edge the search generates.
"""

from __future__ import annotations

import hashlib
import math
import os
from typing import Dict, NamedTuple, Optional, Tuple

from ..frontend.mc_cfg import CfgError, parse_cfg
from ..obs.spans import annotate, span
from ..spec.labels import DEFAULT_INIT
from . import cache
from .actions import ActionSystem
from .eval import Evaluator
from .parser import Definition, Module, StructParseError, parse_module

_BUILTIN_MODULES = {
    "TLC", "Naturals", "Integers", "Reals", "Sequences", "FiniteSets",
    "Bags", "TLAPS", "Toolbox", "Functions",
}


class StructModel(NamedTuple):
    system: ActionSystem
    invariants: Dict[str, tuple]  # name -> AST
    properties: Dict[str, tuple]  # name -> AST (leadsto shapes)
    constants: Dict[str, object]
    module: Module
    # the fairness conjuncts of the SPECIFICATION formula, resolved:
    # ((A, the action labels A is a disjunction of), ...), one entry a
    # `WF_<vars>(A)`; () where the formula states none.  Read by the
    # liveness route alone (declared_fairness): a conjunct it cannot
    # honour is a load error whenever the cfg has a PROPERTY
    fairness: Tuple[Tuple[str, Tuple[str, ...]], ...]
    root_name: str
    # sha256 over every source text this model was loaded from (cfg +
    # module closure) plus the constant overrides - the step-compile
    # cache key component that changes iff the spec's meaning can
    # (struct.cache keys its memo and the checkpoint meta on it)
    source_digest: str = ""
    # the cfg's SYMMETRY declaration, resolved: ((constant name, its
    # sorted atoms), ...) for the sets whose full permutation groups the
    # named definition is the union of; () where the cfg has no such
    # line.  A model that declares one is only ever checked reduced
    # (struct.cache.wants_symmetry); analysis.symfind verifies the sets
    symmetry: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()
    # the cfg's CONSTRAINT definitions, resolved: name -> AST, in the
    # cfg's order; {} where the cfg has no such line.  A model that
    # declares one is only ever checked constrained: the backend
    # carries the predicate (struct.backend), the engines that share
    # the expand stage apply it, and every other route refuses the
    # model by name (engine.backend.require_unconstrained)
    constraints: Dict[str, tuple] = {}
    # the capacities the spec declares for its sequences
    # (shapes.seq_cap_bounds over the cfg's invariants and CONSTRAINT:
    # `Len(network[p][q]) <= 3`), resolved once a load; () where it
    # declares none.  A growing sequence without one takes a first
    # guess; both are guarded by the Append trap (struct.backend)
    seq_caps: tuple = ()
    # the cfg's PROPERTY lines of the shape `I /\\ [][A]_v` (a
    # specification as a property: the refinement a module's closing
    # theorem states), resolved: name -> ActionProperty, in the cfg's
    # order; {} where the cfg has none.  `properties` holds the cfg's
    # other PROPERTY lines alone.  A model that declares one is only
    # ever checked with it: the backend carries the two-state predicate
    # (struct.backend), the single-device expand stage judges it on
    # every generated edge, and every other route refuses the model by
    # name (engine.backend.require_unconstrained)
    action_props: Dict[str, "ActionProperty"] = {}


class ActionProperty(NamedTuple):
    """A PROPERTY `I /\\ [][A]_v`, resolved."""

    name: str  # the cfg's name for it
    init: tuple  # I, a state predicate's AST
    action: tuple  # A, an action's AST: primed variables are READ
    sub: Tuple[str, ...]  # the variables of the subscript v
    # the three as the formula names them (`TC!TCInit`, `TC!TCNext`,
    # `rmState` or `<<x,y>>`), for the journal and the messages
    init_name: str
    action_name: str
    sub_text: str

    @property
    def text(self) -> str:
        return (f"{self.init_name} /\\ [][{self.action_name}]_"
                f"{self.sub_text}")


class StructLoadError(ValueError):
    pass


def _parse_const_literal(text: str):
    t = text.strip()
    if t == "TRUE":
        return True
    if t == "FALSE":
        return False
    if t.startswith('"') and t.endswith('"'):
        return t[1:-1]
    if t.lstrip("-").isdigit():
        return int(t)
    if t.startswith("{") and t.endswith("}"):
        # model-value set: CONSTANT RM = {r1, r2}, or a set of such sets
        # (`Majority = {{a1, a2}, {a1, a3}, {a2, a3}}`): split at the
        # commas of this pair of braces.  A quoted comma would split
        # wrong, so a string inside is a loud error, not a garbage
        # constant.
        inner = t[1:-1].strip()
        if not inner:
            return frozenset()
        if '"' in inner:
            raise StructLoadError(
                f"unsupported constant set literal {t!r} (sets of "
                "model values, numbers and such sets only)"
            )
        parts, depth, start = [], 0, 0
        for k, ch in enumerate(inner):
            depth += (ch == "{") - (ch == "}")
            if depth < 0:
                break
            if ch == "," and depth == 0:
                parts.append(inner[start:k])
                start = k + 1
        if depth != 0:
            raise StructLoadError(
                f"unbalanced braces in constant set literal {t!r}")
        parts.append(inner[start:])
        return frozenset(_parse_const_literal(x) for x in parts)
    if t == "defaultInitValue":
        return DEFAULT_INIT
    # TLC model value: an atom equal only to itself; the hand oracle
    # uses the same string-atom convention (spec/labels.py DEFAULT_INIT)
    return t


def declared_symmetry(defname: str, module: Module,
                      constants: Dict[str, object]
                      ) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
    """The constant sets a cfg's `SYMMETRY defname` declares symmetric:
    the definition has to evaluate to a set of functions that is the
    union, over some constant sets of model values, of ALL permutations
    of each.  Anything else is an error that says what it met: the
    orbit search takes the least image under the whole group, so a
    proper subset of a set's permutations has no meaning here."""
    from .eval import StructEvalError, is_fn

    d = module.defs.get(defname)
    if d is None:
        raise StructLoadError(f"SYMMETRY {defname}: no such definition")
    try:
        val = Evaluator(module.defs, constants).eval(d.body, {})
    except StructEvalError as e:
        raise StructLoadError(f"SYMMETRY {defname}: {e}")
    if not isinstance(val, frozenset) or not all(
            f and is_fn(f) for f in val):
        raise StructLoadError(
            f"SYMMETRY {defname}: not a set of functions over model "
            f"values (got {val!r:.80})")
    by_dom: Dict[frozenset, list] = {}
    for f in val:
        by_dom.setdefault(frozenset(k for k, _ in f), []).append(f)
    out = []
    for dom, fns in sorted(by_dom.items(), key=lambda kv: sorted(kv[0])):
        shown = "{" + ", ".join(sorted(dom)) + "}"
        if any(frozenset(v for _, v in f) != dom for f in fns):
            raise StructLoadError(
                f"SYMMETRY {defname}: a function over {shown} is not a "
                "permutation of it")
        if len(fns) != math.factorial(len(dom)):
            raise StructLoadError(
                f"SYMMETRY {defname}: {len(fns)} of the "
                f"{math.factorial(len(dom))} permutations of {shown}: a "
                "proper subset of a set's permutations is not supported "
                "(declare Permutations of the whole set)")
        names = sorted(n for n, v in constants.items() if v == dom)
        if not names:
            raise StructLoadError(
                f"SYMMETRY {defname}: {shown} is not the value of a "
                "CONSTANT set of model values")
        if len(dom) >= 2:  # a one-element set has only the identity
            out.append((names[0], tuple(sorted(dom))))
    return tuple(out)


def declared_constraints(names, module: Module) -> Dict[str, tuple]:
    """The state predicates a cfg's `CONSTRAINT names` declares: each
    has to be a defined operator without parameters whose body reads
    the current state alone.  An undefined name, an operator with
    parameters or a primed variable is a load error that names it."""
    from .shapes import _mentions_prime_static

    out: Dict[str, tuple] = {}
    for n in names:
        d = module.defs.get(n)
        if d is None:
            raise StructLoadError(f"CONSTRAINT {n}: no such definition")
        if d.params:
            raise StructLoadError(
                f"CONSTRAINT {n}: an operator with parameters "
                f"({', '.join(d.params)}) is not a state predicate")
        if _mentions_prime_static(d.body, module.defs):
            raise StructLoadError(
                f"CONSTRAINT {n}: mentions a primed variable or "
                "UNCHANGED (a state constraint reads one state; "
                "ACTION_CONSTRAINT is not supported)")
        out[n] = d.body
    return out


def declared_action_properties(names, module: Module) -> Dict[
        str, ActionProperty]:
    """The cfg PROPERTY lines that state a specification, `I /\\
    [][A]_v` with no further conjunct (through the names that only
    stand for it: `TCSpec == TC!TCSpec`): name -> ActionProperty, in
    the cfg's order.  I and A have to be defined without parameters and
    v a variable, a tuple of variables or a definition of one.  A
    specification with a fairness conjunct is no safety property and is
    not taken here (the liveness route reads `P ~> Q` alone and says
    what it skips)."""
    from .actions import expand_unchanged
    from .parser import parse_expression

    out: Dict[str, ActionProperty] = {}
    defs = module.defs
    for n in names:
        d = defs.get(n)
        seen = set()
        while d is not None and not d.params and d.body[0] == "name" \
                and d.body[1] in defs and d.body[1] not in seen:
            seen.add(d.body[1])
            d = defs[d.body[1]]
        if d is None or d.params or d.body[0] != "spec" or d.body[4]:
            continue
        _, init, next_, sub, _ = d.body

        def refuse(why):
            raise StructLoadError(f"PROPERTY {n} ({d.name}): {why}")

        for part in (init, next_):
            if part is None or part not in defs or defs[part].params:
                refuse(f"`{part}` is not a definition without "
                       "parameters")
        try:
            sub_ast = parse_expression(sub)
        except StructParseError as e:
            refuse(f"cannot read the subscript `{sub}`: {e}")
        items = sub_ast[1] if sub_ast[0] == "tuple" else [sub_ast]
        if not all(x[0] == "name" for x in items):
            refuse(f"the subscript `{sub}` is not a tuple of variables")
        subs = tuple(expand_unchanged([x[1] for x in items], defs,
                                      module.variables))
        stray = [v for v in subs if v not in module.variables]
        if stray or not subs:
            refuse(f"the subscript `{sub}` names {stray or 'nothing'}, "
                   "no variable of the module")
        out[n] = ActionProperty(
            name=n, init=defs[init].body, action=defs[next_].body,
            sub=subs, init_name=init, action_name=next_, sub_text=sub)
    return out


def action_property_names(cfg_path: str) -> Tuple[str, ...]:
    """The names of the cfg's PROPERTY lines that are action properties
    (`I /\\ [][A]_v`), read off the parsed texts alone - for a frontend
    that cannot judge one and has to say so by name
    (frontend.model.resolve); () where there is none, or where the cfg
    or a module does not load here (that frontend's own error stands)."""
    try:
        with open(cfg_path, encoding="utf-8") as f:
            cfg = _parsed("cfg", f.read())
        if not cfg.properties:
            return ()
        model_dir = os.path.dirname(os.path.abspath(cfg_path))
        dirs = (model_dir, os.path.dirname(os.path.dirname(model_dir)))
        root = os.path.join(model_dir, "MC.tla")
        if not os.path.exists(root):
            root = os.path.join(
                model_dir,
                os.path.splitext(os.path.basename(cfg_path))[0] + ".tla")
        module = _load_module_closure(root, dirs)
        return tuple(declared_action_properties(cfg.properties, module))
    except (OSError, CfgError, StructLoadError, StructParseError):
        return ()


def _action_labels(ast, defs, stop: Optional[str] = None):
    """The action labels under `ast`, an action on the way down from
    Next: through `\\/`, bounded `\\E` and definitions that only split
    further, to the definitions that name the fired action
    (actions.names_action: the labels the engines count by).  Returns
    (labels, definitions passed on the way), or None where a disjunct
    is no named action of the module.  `stop`: a definition whose
    subtree is left out."""
    from .actions import names_action
    from .shapes import _mentions_prime_static

    labels, passed = set(), set()
    stack = [ast]
    while stack:
        node = stack.pop()
        if node[0] == "or":
            stack.extend(node[1])
        elif node[0] == "exists":
            stack.append(node[3])
        elif node[0] in ("call", "name") and node[1] in defs \
                and _mentions_prime_static(defs[node[1]].body, defs):
            if node[1] == stop:
                continue
            passed.add(node[1])
            if names_action(defs[node[1]].body):
                labels.add(node[1])
            else:
                stack.append(defs[node[1]].body)
        else:
            return None
    return labels, passed


def declared_fairness(conjuncts, next_name: str, subscript: str,
                      module: Module
                      ) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
    """The fairness a SPECIFICATION formula states, as data: every
    conjunct after Init and [][Next]_sub has to be `WF_sub(A)` with the
    subscript of the [][Next]_ and `A` either Next or a definition met
    on the way down from Next, a disjunction of the spec's actions; it
    resolves to (A, the labels of the actions under it).  Anything else
    - `SF_`, another subscript, a body that is no such definition, an
    action that also fires outside `A` (the labels could not tell its
    steps apart), any other conjunct - is an error that names the
    conjunct: a property is never judged under a fairness the spec did
    not state."""
    defs = module.defs
    out = []
    whole = _action_labels(("name", next_name), defs)
    for kind, sub, body, text in conjuncts:
        def refuse(why):
            raise StructLoadError(
                f"SPECIFICATION conjunct `{text}`: {why}")

        if kind == "other":
            refuse("not a fairness condition this checker knows "
                   "(WF_vars(A) for a named action A)")
        if kind != "WF":
            refuse("strong fairness (SF_) is not supported")
        if sub != subscript:
            refuse(f"its subscript is not the [][{next_name}]_"
                   f"{subscript} one")
        if body not in defs or defs[body].params:
            refuse(f"`{body}` is not a defined action without "
                   "parameters")
        mine = _action_labels(("name", body), defs)
        if mine is None or not mine[0]:
            refuse(f"`{body}` is not a disjunction of the spec's "
                   "actions")
        if whole is None or body not in whole[1]:
            refuse(f"`{body}` is not {next_name} or a disjunct of it")
        rest = _action_labels(("name", next_name), defs, stop=body)
        both = sorted(mine[0] & rest[0]) if body != next_name else []
        if both:
            refuse(f"the action {both[0]} also fires outside `{body}`: "
                   "its steps cannot be told apart by label")
        out.append((body, tuple(sorted(mine[0]))))
    return tuple(out)


def _parsed(kind: str, src: str):
    """The text of a `cfg` or a `module`, parsed; kept by the text's own
    digest (struct.cache's `text` memo): a text this process has parsed
    is not parsed again, whatever file it is read from.  A text that
    does not parse raises again every time.  What is kept is shared:
    nothing writes to a parsed cfg or Module."""
    key = (kind, hashlib.sha256(src.encode()).hexdigest())
    hit = cache.spec_kept("text", key)
    if hit is None:
        hit = parse_cfg(src) if kind == "cfg" else parse_module(src)
        cache.spec_keep("text", key, hit)
    return hit


def _find_module(name: str, search_dirs, why: str) -> str:
    for d in search_dirs:
        cand = os.path.join(d, f"{name}.tla")
        if os.path.exists(cand):
            return cand
    raise StructLoadError(
        f"{why} {name}: no {name}.tla in {list(search_dirs)}")


def _prefixed(ast, names, prefix: str):
    """`ast` of an instanced module with every reference to one of the
    module's own definitions (`names`) renamed `prefix + name`: the
    name and call nodes, UNCHANGED's list, and the names a
    specification's normal form holds.  Nothing else is a reference: a
    string, a record's field, a bound variable stay (TLA+ lets no
    bound variable shadow a definition)."""
    def ref(x):
        return prefix + x if x in names else x

    if isinstance(ast, list):
        return [_prefixed(x, names, prefix) for x in ast]
    if not isinstance(ast, tuple) or not ast:
        return ast
    op = ast[0]
    if op in ("name", "call") and len(ast) >= 2 and isinstance(ast[1], str):
        return (op, ref(ast[1])) + tuple(
            _prefixed(x, names, prefix) for x in ast[2:])
    if op == "unchanged" and len(ast) == 2 and isinstance(ast[1], list):
        return (op, [ref(x) for x in ast[1]])
    if op == "spec" and len(ast) == 5:
        _, init, next_, sub, conjuncts = ast
        return (op, ref(init), ref(next_), ref(sub), tuple(
            (kind, csub if csub is None else ref(csub),
             body if body is None else ref(body), text)
            for kind, csub, body, text in conjuncts))
    return tuple(_prefixed(x, names, prefix)
                 if isinstance(x, (tuple, list)) else x for x in ast)


def _instantiate(inst: Module, prefix: str, into: str, declared) -> list:
    """The definitions `N == INSTANCE M` (prefix `N!`; a bare INSTANCE
    has none) adds to the instancing module `into`: M's, each under its
    prefixed name with its references to M's own definitions prefixed
    alike.  Without a WITH clause M's constants and variables are the
    instancing module's of the same name, so each has to be `declared`
    there (a constant, a variable or a definition of that name)."""
    missing = [x for x in inst.constants + inst.variables
               if x not in declared]
    if missing:
        raise StructLoadError(
            f"INSTANCE {inst.name} in {into}: {', '.join(missing)} of "
            f"{inst.name} has no constant, variable or definition of "
            f"that name in {into} (a WITH clause is not supported)")
    names = set(inst.defs)
    return [Definition(prefix + d, inst.defs[d].params,
                       _prefixed(inst.defs[d].body, names, prefix))
            for d in inst.def_order]


def _require_instances(module: Module) -> None:
    """Every `N!Op` a definition names has to be a definition an
    INSTANCE made: an unknown N, or an Op the instanced module lacks, is
    a load error that says so - not an unknown name the day a state
    reaches it."""
    known = {n for n, _ in module.instances if n}
    stack = [(d.name, d.body) for d in module.defs.values()]
    while stack:
        where, node = stack.pop()
        if isinstance(node, tuple) and len(node) >= 2 \
                and node[0] in ("name", "call") \
                and isinstance(node[1], str) and "!" in node[1] \
                and node[1] not in module.defs:
            n = node[1].split("!")[0]
            raise StructLoadError(
                f"{where}: {node[1]}: " + (
                    f"the module {n} instances has no such definition"
                    if n in known else
                    f"no `{n} == INSTANCE ...` in the module (it has "
                    f"{sorted(known) or 'none'})"))
        if isinstance(node, (tuple, list)):
            stack.extend((where, x) for x in node
                         if isinstance(x, (tuple, list)))


def _load_module_closure(path: str, search_dirs, texts=None) -> Module:
    """Parse `path` and fold in its non-builtin EXTENDS (depth-first,
    extended defs first so the extender can override) and the modules
    it instances (`_instantiate`; host span `build.struct.instance`, its
    `memo` says whether this process had the substitution already).
    `texts`, when given, collects every (path, source) read - the
    digest input, instanced modules included."""
    with open(path) as f:
        src = f.read()
    if texts is not None:
        texts.append((path, src))
    root = _parsed("module", src)
    defs: Dict[str, Definition] = {}
    def_order = []
    variables = []
    constants = []
    instances = list(root.instances)

    def fold(mod: Module):
        instances.extend(i for i in mod.instances if i not in instances)
        for d in mod.def_order:
            if d not in defs:
                def_order.append(d)
            defs[d] = mod.defs[d]
        for v in mod.variables:
            if v not in variables:
                variables.append(v)
        for c in mod.constants:
            if c not in constants:
                constants.append(c)

    for ext in root.extends:
        if ext in _BUILTIN_MODULES:
            continue
        fold(_load_module_closure(
            _find_module(ext, search_dirs, "EXTENDS"), search_dirs, texts))
    fold(root)
    for as_name, modname in root.instances:
        if modname in _BUILTIN_MODULES:
            continue
        with span("build.struct.instance") as sp:
            sp.attrs["module"] = modname
            mine: list = []
            inst = _load_module_closure(
                _find_module(modname, search_dirs, "INSTANCE"),
                search_dirs, mine)
            if texts is not None:
                texts.extend(mine)
            prefix = f"{as_name}!" if as_name else ""
            declared = set(constants) | set(variables) | set(defs)
            digest = hashlib.sha256()
            for _, text in mine:
                digest.update(text.encode() + b"\x00")
            key = ("instance", digest.hexdigest(), prefix, root.name,
                   tuple(sorted(declared & set(
                       inst.constants + inst.variables))))
            added = cache.spec_kept("text", key)
            sp.attrs["memo"] = "miss" if added is None else "hit"
            if added is None:
                added = _instantiate(inst, prefix, root.name, declared)
                cache.spec_keep("text", key, added)
        for d in added:
            if d.name not in defs:
                def_order.append(d.name)
            defs[d.name] = d
    return Module(
        name=root.name,
        extends=root.extends,
        constants=tuple(constants),
        variables=tuple(variables),
        defs=defs,
        def_order=tuple(def_order),
        instances=tuple(instances),
    )


def load(cfg_path: str,
         const_overrides: Optional[Dict[str, object]] = None) -> StructModel:
    """The model of a cfg and its module closure, its constants
    resolved.  A pure function of the texts it reads and the overrides,
    so it is computed once a process (struct.cache's `text` and `model`
    memos): the first load of a text parses the cfg and every module,
    evaluates the constants and resolves SYMMETRY, CONSTRAINT, the
    fairness and the sequence capacities; a later load, from whatever
    directory, reads and hashes the same files in the same search order
    and returns the model kept under their digest - no parser, no
    evaluator.  A changed byte anywhere, or another override, is another
    key; an error is raised again by every call.  The kept model is
    shared: a check reads it and writes nothing to it (what a check
    learns lives in struct.cache under `model_key`).

    Host span `build.struct.load`, `memo` = hit | miss, which the span
    open around the call (`sched.load`, `check.resolve`) is told too;
    on a hit the children the model's load recorded
    (`build.struct.symmetry` / `.constraint` / `.fairness` / `.seqcap`)
    are opened again around no work, `memo` = hit."""
    with span("build.struct.load") as sp:
        model, memo = _load(cfg_path, const_overrides)
        sp.attrs["memo"] = memo
    annotate(memo=memo)
    return model


def _load(cfg_path: str, const_overrides: Optional[Dict[str, object]]
          ) -> Tuple[StructModel, str]:
    with open(cfg_path, encoding="utf-8") as f:
        cfg_text = f.read()
    try:
        cfg = _parsed("cfg", cfg_text)
    except CfgError as e:
        raise StructLoadError(str(e))
    model_dir = os.path.dirname(os.path.abspath(cfg_path))
    toolbox_parent = os.path.dirname(os.path.dirname(model_dir))
    search_dirs = (model_dir, toolbox_parent)
    texts = [(cfg_path, cfg_text)]

    mc_path = os.path.join(model_dir, "MC.tla")
    mc_layout = os.path.exists(mc_path)
    if mc_layout:
        module = _load_module_closure(mc_path, search_dirs, texts)
        root_name = next(
            (e for e in module.extends if e not in _BUILTIN_MODULES), "MC"
        )
    else:
        # bare layout: the cfg's own basename names the root module
        base = os.path.splitext(os.path.basename(cfg_path))[0]
        cand = os.path.join(model_dir, f"{base}.tla")
        if not os.path.exists(cand):
            tlas = [f for f in sorted(os.listdir(model_dir))
                    if f.endswith(".tla")]
            if len(tlas) != 1:
                raise StructLoadError(
                    f"no MC.tla and no {base}.tla next to {cfg_path}"
                )
            cand = os.path.join(model_dir, tlas[0])
        module = _load_module_closure(cand, search_dirs, texts)
        root_name = module.name

    digest = hashlib.sha256()
    for _, src in texts:
        digest.update(src.encode())
        digest.update(b"\x00")
    if const_overrides:
        for k in sorted(const_overrides):
            digest.update(f"{k}={const_overrides[k]!r};".encode())
    # everything below reads the texts hashed above and the overrides
    # alone (root_name besides, which of the two layouts named it)
    key = (digest.hexdigest(), mc_layout)
    kept = cache.spec_kept("model", key)
    if kept is not None:
        for name, attrs in kept[1]:
            with span(name, **attrs, memo="hit"):
                pass
        return kept[0], "hit"
    children = []

    def child(name: str) -> span:
        children.append(span(name, memo="miss"))
        return children[-1]

    _require_instances(module)
    constants: Dict[str, object] = {}
    for name, val in cfg.constants.items():
        constants[name] = _parse_const_literal(val)
    if const_overrides:
        constants.update(const_overrides)
    # every declared constant needs a value (defaultInitValue is a model
    # value equal only to itself when left unassigned)
    for c in module.constants:
        if c not in constants and c not in cfg.substitutions:
            constants[c] = DEFAULT_INIT if c == "defaultInitValue" else c
    # `Quorum <- MCQuorum`: the replacing definition may name the
    # model's assigned constants and model values (MC.tla's
    # `CONSTANTS a1, a2, a3`) and an earlier replacement, so it is
    # evaluated over them, in the cfg's order
    for name, defname in cfg.substitutions.items():
        if const_overrides and name in const_overrides:
            continue
        d = module.defs.get(defname)
        if d is None:
            raise StructLoadError(
                f"CONSTANT {name} <- {defname}: no such definition"
            )
        constants[name] = Evaluator(module.defs, constants).eval(d.body, {})

    ev = Evaluator(module.defs, constants)
    symmetry = ()
    if cfg.symmetry:
        with child("build.struct.symmetry") as sp:
            symmetry = declared_symmetry(cfg.symmetry, module, constants)
            sp.attrs["perms"] = math.prod(
                math.factorial(len(atoms)) for _, atoms in symmetry)

    constraints: Dict[str, tuple] = {}
    if cfg.constraints:
        # host span `build.struct.constraint` (with the backend's one of
        # the same name, which compiles the predicate where the memo
        # misses): the resolution, paid where the model is not kept
        with child("build.struct.constraint") as sp:
            sp.attrs["names"] = " ".join(cfg.constraints)
            constraints = declared_constraints(cfg.constraints, module)

    spec_name = cfg.specification or "Spec"
    spec_def = module.defs.get(spec_name)
    conjuncts, subscript = (), None
    if spec_def is not None and spec_def.body[0] == "spec":
        _, init_name, next_name, subscript, conjuncts = spec_def.body
    else:
        init_name, next_name = "Init", "Next"
    if init_name not in module.defs or next_name not in module.defs:
        raise StructLoadError(
            f"cannot resolve Init/Next ({init_name}/{next_name})"
        )
    action_props = declared_action_properties(cfg.properties, module)
    # host span `build.struct.fairness`: the formula's fairness
    # conjuncts resolved to action labels.  Only a temporal PROPERTY
    # reads them: a safety-only check - an action property is one -
    # loads whatever the formula says
    with child("build.struct.fairness") as sp:
        try:
            fairness = declared_fairness(conjuncts, next_name, subscript,
                                         module)
        except StructLoadError:
            if any(p not in action_props for p in cfg.properties):
                raise
            fairness = ()
        sp.attrs["names"] = " ".join(a for a, _ in fairness)

    def _named_defs(names):
        out = {}
        for n in names:
            d = module.defs.get(n)
            if d is None:
                raise StructLoadError(f"no definition for {n!r}")
            out[n] = d.body
        return out

    invariants = _named_defs(cfg.invariants)
    # host span `build.struct.seqcap`: the sequence capacities the
    # invariants and the constraint declare, settled here so that the
    # shape inference (on a backend-memo miss) only reads them; the
    # walk is paid where the model is not kept, like the constraint's
    with child("build.struct.seqcap") as sp:
        from .shapes import seq_cap_bounds

        seq_caps = tuple(seq_cap_bounds(
            ev, {**invariants, **constraints}, module.variables))
        sp.attrs["declared"] = len(seq_caps)

    model = StructModel(
        system=ActionSystem(ev, module.variables, init_name, next_name),
        invariants=invariants,
        properties=_named_defs(
            p for p in cfg.properties if p not in action_props),
        action_props=action_props,
        constants=constants,
        module=module,
        fairness=fairness,
        root_name=root_name,
        source_digest=key[0],
        symmetry=symmetry,
        constraints=constraints,
        seq_caps=seq_caps,
    )
    cache.spec_keep("model", key, (model, tuple(
        (c.name, {k: v for k, v in c.attrs.items() if k != "memo"})
        for c in children)))
    return model, "miss"
