"""Full-module TLA+ parser for the structural frontend (E1).

Parses real TLA+ modules - the reference's own committed translation
included (/root/reference/KubeAPI.tla:373-768) - into expression ASTs:

* junction lists by column alignment (the PlusCal translator's bullet
  style; TLA+'s /\\ and \\/ list grammar),
* IF/THEN/ELSE, CASE [] arms, LET..IN, CHOOSE,
* EXCEPT with multi-update paths (![c].status = ...),
* set literals / filters {x \\in S : P} / maps {e : x \\in S},
* sequences <<...>>, \\o, Head/Tail/Append/Len,
* records [f |-> e], singleton functions k :> v, left-biased merge @@,
* DOMAIN, function sets [S -> T], function literals [x \\in S |-> e],
* quantifiers with multiple binders (\\A o1, o2 \\in S : P),
* temporal property shapes: P ~> Q and []P ~> Q (MC.out's checked
  property forms), WF_vars(Next)-style Spec conjunctions.

The parse obligations mirror what SANY reports for the reference model
(MC.out:8-24).  Original hand-rolled design - no code from TLC/SANY
(which are Java) is or could be reused.

AST nodes are plain tuples (texpr-compatible where the form overlaps):
  ("num", n) ("str", s) ("bool", b) ("name", x) ("prime", x)
  ("and", [..]) ("or", [..]) ("not", e) ("implies", a, b)
  ("box", e) ("leadsto", a, b)
  ("cmp", op, a, b)            op in = # < > <= >= \\in \\notin \\subseteq
  ("binop", op, a, b)          op in \\cup \\cap \\ + - .. \\o @@ :>
  ("apply", f, arg)            f[arg] and r.field (field as ("str", f))
  ("call", name, [args])       operator application Foo(a, b); a bare
                               infix operator argument is ("opsym", "+")
  ("setlit", [..]) ("setfilter", var, dom, pred) ("setmap", e, var, dom)
  ("tuple", [..]) ("record", [(f, e), ..]) ("recset", [(f, S), ..])
  ("fnlit", var, dom, body) ("funcset", dom, rng)
  ("except", f, [([path..], val), ..])   path elements are value ASTs
  ("if", c, t, e) ("case", [(g, e), ..], other|None)
  ("let", [(name, params, body), ..], e)
  ("choose", var, dom|None, pred)
  ("forall", [vars], dom, body) ("exists", [vars], dom, body)
  ("unchanged", [names]) ("domain", e) ("subset", e) ("atref",)
  ("recfn", f, var, dom, body) a LET's `f[var \\in dom] == body`, which
                               may apply f to smaller arguments
  ("spec", init, next, subscript, conjuncts)  `Init /\\ [][Next]_sub ...`
A name of an instanced module, `TC!TCSpec`, is one name: ("name",
"TC!TCSpec") / ("call", "TC!Op", [args]); the loader defines it
(loader._instantiate).
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple


class StructParseError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Comment stripping (position-preserving) and module header handling
# ---------------------------------------------------------------------------


def strip_comments(src: str) -> str:
    """Blank out (* .. *) blocks (nested), \\* line comments, module
    header/separator lines - preserving every character position."""
    out = list(src)
    i, n = 0, len(src)
    depth = 0
    in_str = False
    while i < n:
        c = src[i]
        if depth == 0 and not in_str and c == '"':
            in_str = True
            i += 1
            continue
        if in_str:
            if c == '"':
                in_str = False
            i += 1
            continue
        if src.startswith("(*", i):
            depth += 1
            out[i] = out[i + 1] = " "
            i += 2
            continue
        if depth > 0:
            if src.startswith("*)", i):
                depth -= 1
                out[i] = out[i + 1] = " "
                i += 2
                continue
            if c != "\n":
                out[i] = " "
            i += 1
            continue
        if src.startswith("\\*", i):
            j = src.find("\n", i)
            j = n if j < 0 else j
            for k in range(i, j):
                out[k] = " "
            i = j
            continue
        i += 1
    text = "".join(out)
    # blank module header / separator / terminator lines
    lines = text.split("\n")
    for li, ln in enumerate(lines):
        if re.match(r"^\s*----+\s*MODULE\s+\w+\s*----+\s*$", ln):
            lines[li] = " " * len(ln)
        elif re.match(r"^\s*(----+|====+)\s*$", ln):
            lines[li] = " " * len(ln)
    return "\n".join(lines)


def module_name(src: str) -> Optional[str]:
    m = re.search(r"^\s*----+\s*MODULE\s+(\w+)\s*----+\s*$", src, re.M)
    return m.group(1) if m else None


# ---------------------------------------------------------------------------
# Tokenizer (line/column aware)
# ---------------------------------------------------------------------------


class Tok(NamedTuple):
    kind: str
    val: str
    line: int
    col: int


_TOKEN_RE = re.compile(
    r"""
    (?P<ws>[ \t\r\f]+)
  | (?P<land>/\\)
  | (?P<lor>\\/)
  | (?P<forall>\\A\b)
  | (?P<exists>\\E\b)
  | (?P<ge>\\geq\b)
  | (?P<le>\\leq\b|=<)
  | (?P<op>\\(?:in|notin|subseteq|cup|cap|union|o)\b)
  | (?P<setminus>\\)
  | (?P<leadsto>~>)
  | (?P<implies>=>)
  | (?P<mapsto>\|->)
  | (?P<arrow>->)
  | (?P<defeq>==)
  | (?P<range>\.\.)
  | (?P<le_><=)
  | (?P<ge_>>=)
  | (?P<ltup><<)
  | (?P<rtup>>>)
  | (?P<box>\[\])
  | (?P<colongt>:>)
  | (?P<atat>@@)
  | (?P<eq>=)
  | (?P<ne>\#|/=)
  | (?P<lt><)
  | (?P<gt>>)
  | (?P<num>\d+)
  | (?P<str>"[^"]*")
  | (?P<name>[A-Za-z_][A-Za-z0-9_]*)
  | (?P<sym>[()\[\]{},.~'+\-!@:*])
    """,
    re.VERBOSE,
)


# the ASCII spellings TLA+ gives one operator: one token value each
_OP_ALIASES = {r"\union": r"\cup"}


def tokenize(text: str) -> List[Tok]:
    toks: List[Tok] = []
    for line_no, line in enumerate(text.split("\n"), start=1):
        pos = 0
        while pos < len(line):
            m = _TOKEN_RE.match(line, pos)
            if not m:
                raise StructParseError(
                    f"line {line_no}: cannot tokenize {line[pos:pos+20]!r}"
                )
            if m.lastgroup != "ws":
                toks.append(Tok(m.lastgroup,
                                _OP_ALIASES.get(m.group(), m.group()),
                                line_no, pos))
            pos = m.end()
    return toks


# ---------------------------------------------------------------------------
# Module structure
# ---------------------------------------------------------------------------


class Definition(NamedTuple):
    name: str
    params: Tuple[str, ...]
    body: tuple  # AST


class Module(NamedTuple):
    name: str
    extends: Tuple[str, ...]
    constants: Tuple[str, ...]
    variables: Tuple[str, ...]  # declaration order
    defs: Dict[str, Definition]
    def_order: Tuple[str, ...]
    # `N == INSTANCE M` as (N, M), a bare `INSTANCE M` as (None, M), in
    # the module's order; the loader finds M and defines N!Op
    instances: Tuple[Tuple[Optional[str], str], ...] = ()


_DECL_KEYWORDS = {
    "CONSTANT", "CONSTANTS", "VARIABLE", "VARIABLES", "EXTENDS",
    "ASSUME", "ASSUMPTION", "THEOREM", "LOCAL", "INSTANCE",
}


def parse_module(src: str) -> Module:
    name = module_name(src) or ""
    toks = tokenize(strip_comments(src))
    extends: List[str] = []
    constants: List[str] = []
    variables: List[str] = []
    defs: Dict[str, Definition] = {}
    def_order: List[str] = []
    instances: List[Tuple[Optional[str], str]] = []

    i, n = 0, len(toks)

    def instance_of(ts: List[Tok], who: str) -> str:
        """The module of `INSTANCE M` (ts starts after the keyword)."""
        if not ts or ts[0].kind != "name":
            raise StructParseError(f"{who}: INSTANCE names no module")
        if len(ts) > 1:
            raise StructParseError(
                f"{who}: INSTANCE {ts[0].val} {ts[1].val} ...: a WITH "
                "clause is not supported (constants and variables of "
                "the same name are the only substitution)")
        return ts[0].val

    def is_def_start(j: int) -> bool:
        """name at column 0 followed by `==` or `(p, ..) ==`."""
        if toks[j].kind != "name" or toks[j].col != 0:
            return False
        if toks[j].val in _DECL_KEYWORDS:
            return False
        k = j + 1
        if k < n and toks[k].kind == "sym" and toks[k].val == "(":
            depth = 0
            while k < n:
                t = toks[k]
                if t.kind == "sym" and t.val == "(":
                    depth += 1
                elif t.kind == "sym" and t.val == ")":
                    depth -= 1
                    if depth == 0:
                        k += 1
                        break
                k += 1
        return k < n and toks[k].kind == "defeq"

    def unit_end(j: int) -> int:
        """First index >= j that starts a new top-level unit."""
        while j < n:
            t = toks[j]
            if t.col == 0 and t.kind == "name" and (
                t.val in _DECL_KEYWORDS or is_def_start(j)
            ):
                return j
            j += 1
        return n

    while i < n:
        t = toks[i]
        if t.kind == "name" and t.val == "EXTENDS" and t.col == 0:
            i += 1
            while i < n and toks[i].kind == "name":
                extends.append(toks[i].val)
                i += 1
                if i < n and toks[i].kind == "sym" and toks[i].val == ",":
                    i += 1
                else:
                    break
        elif t.kind == "name" and t.val in ("CONSTANT", "CONSTANTS") \
                and t.col == 0:
            i += 1
            while i < n and toks[i].kind == "name" \
                    and not (toks[i].col == 0 and (
                        toks[i].val in _DECL_KEYWORDS or is_def_start(i))):
                constants.append(toks[i].val)
                i += 1
                if i < n and toks[i].kind == "sym" and toks[i].val == ",":
                    i += 1
                else:
                    break
        elif t.kind == "name" and t.val in ("VARIABLE", "VARIABLES") \
                and t.col == 0:
            i += 1
            while i < n and toks[i].kind == "name" \
                    and not (toks[i].col == 0 and (
                        toks[i].val in _DECL_KEYWORDS or is_def_start(i))):
                variables.append(toks[i].val)
                i += 1
                if i < n and toks[i].kind == "sym" and toks[i].val == ",":
                    i += 1
                else:
                    break
        elif t.kind == "name" and t.val in ("ASSUME", "ASSUMPTION",
                                            "THEOREM") and t.col == 0:
            # assumptions are not checked here, theorems not proved
            i = unit_end(i + 1)
        elif t.kind == "name" and t.val == "INSTANCE" and t.col == 0:
            end = unit_end(i + 1)
            instances.append((None, instance_of(toks[i + 1:end],
                                                "INSTANCE")))
            i = end
        elif is_def_start(i):
            dname = t.val
            j = i + 1
            params: List[str] = []
            if toks[j].kind == "sym" and toks[j].val == "(":
                j += 1
                while toks[j].kind == "name":
                    params.append(toks[j].val)
                    j += 1
                    if toks[j].kind == "sym" and toks[j].val == ",":
                        j += 1
                if not (toks[j].kind == "sym" and toks[j].val == ")"):
                    raise StructParseError(
                        f"{dname}: malformed parameter list"
                    )
                j += 1
            assert toks[j].kind == "defeq"
            j += 1
            end = unit_end(j)
            body_toks = toks[j:end]
            if body_toks and body_toks[0].kind == "name" \
                    and body_toks[0].val == "INSTANCE" and not params:
                instances.append((dname, instance_of(body_toks[1:],
                                                     dname)))
                i = end
                continue
            if dname == "Spec" or _spec_shaped(body_toks):
                body = _parse_spec_body(body_toks)
            else:
                body = _ExprParser(body_toks).parse_full()
            if dname not in defs:
                def_order.append(dname)
            defs[dname] = Definition(dname, tuple(params), body)
            i = end
        else:
            raise StructParseError(
                f"unexpected top-level token {t.val!r} at line {t.line}"
            )

    return Module(
        name=name,
        extends=tuple(extends),
        constants=tuple(constants),
        variables=tuple(variables),
        defs=defs,
        def_order=tuple(def_order),
        instances=tuple(instances),
    )


def _qualified(toks: List[Tok]) -> List[Tok]:
    """`toks` with each `N ! Op` run of an instanced module's name as
    one name token, `N!Op`."""
    out: List[Tok] = []
    for t in toks:
        if len(out) >= 2 and t.kind == "name" and out[-1].val == "!" \
                and out[-1].kind == "sym" and out[-2].kind == "name":
            out[-2:] = [out[-2]._replace(val=out[-2].val + "!" + t.val)]
        else:
            out.append(t)
    return out


def _spec_shaped(toks: List[Tok]) -> bool:
    """Does a definition's body hold `[][A]_sub` - is it a
    specification, whatever its name (PCSpec, TCSpec)?"""
    toks = _qualified(toks)
    return any(
        t.kind == "box" and k + 4 < len(toks)
        and toks[k + 1].val == "[" and toks[k + 2].kind == "name"
        and toks[k + 3].val == "]" and toks[k + 4].kind == "name"
        and toks[k + 4].val.startswith("_")
        for k, t in enumerate(toks))


def _parse_spec_body(toks: List[Tok]) -> tuple:
    """Spec == /\\ Init /\\ [][Next]_vars /\\ WF_vars(A) ...: the
    temporal normal form, structurally: ("spec", init, next, subscript,
    conjuncts).  `conjuncts` is EVERY conjunct after Init and
    [][Next]_sub, none dropped, each as (kind, subscript, body, text):
    kind "WF" / "SF" with the formula's subscript and the text of its
    body (`WF_vars(System)` -> ("WF", "vars", "System", ...)), or
    "other" for anything else; `text` is the conjunct as written.  What
    each means is the loader's to say (loader.declared_fairness)."""
    parts: List[List[Tok]] = [[]]
    depth = 0
    for t in _qualified(toks):
        if t.kind == "land" and depth == 0:
            parts.append([])
            continue
        if t.kind == "ltup" or (t.kind == "sym" and t.val in "([{"):
            depth += 1
        elif t.kind == "rtup" or (t.kind == "sym" and t.val in ")]}"):
            depth -= 1
        parts[-1].append(t)

    def text(ts) -> str:
        return " ".join(t.val for t in ts)

    init = next_ = sub = None
    conjuncts = []
    for ts in parts:
        if not ts:
            continue  # the leading bullet
        if next_ is None and ts[0].kind == "box" and len(ts) >= 5 \
                and ts[1].val == "[" and ts[3].val == "]" \
                and ts[4].val.startswith("_"):
            next_ = ts[2].val
            sub = (ts[4].val[1:] + text(ts[5:])).replace(" ", "")
            continue
        if init is None and len(ts) == 1 and ts[0].kind == "name":
            init = ts[0].val
            continue
        head = ts[0].val
        opens = next((k for k, t in enumerate(ts) if t.val == "("), None)
        if ts[0].kind == "name" and head[:3] in ("WF_", "SF_") \
                and opens is not None and ts[-1].val == ")":
            conjuncts.append((
                head[:2],
                (head[3:] + text(ts[1:opens])).replace(" ", ""),
                text(ts[opens + 1:-1]), text(ts)))
        else:
            conjuncts.append(("other", None, None, text(ts)))
    return ("spec", init, next_, sub, tuple(conjuncts))


# ---------------------------------------------------------------------------
# Expression parser (precedence climbing + junction-boundary stack)
# ---------------------------------------------------------------------------

_KEYWORDS_STOP = {"THEN", "ELSE", "IN", "OTHER", "EXCEPT", "LET", "CASE",
                  "IF", "CHOOSE", "UNCHANGED", "DOMAIN", "SUBSET", "UNION"}

_EOF = Tok("eof", "", 1 << 30, -1)


class _ExprParser:
    def __init__(self, toks: List[Tok]):
        self.toks = toks
        self.i = 0
        # junction boundaries: (line, col) of the current bullet; tokens
        # at line > bullet line with col <= bullet col end the item
        self.bounds: List[Tuple[int, int]] = []

    # -- token access ------------------------------------------------------

    def _blocked(self, t: Tok) -> bool:
        if not self.bounds:
            return False
        bl, bc = self.bounds[-1]
        return t.line > bl and t.col <= bc

    def peek(self) -> Tok:
        if self.i >= len(self.toks):
            return _EOF
        t = self.toks[self.i]
        return _EOF if self._blocked(t) else t

    def peek_raw(self) -> Tok:
        return self.toks[self.i] if self.i < len(self.toks) else _EOF

    def next(self) -> Tok:
        t = self.peek()
        if t.kind != "eof":
            self.i += 1
        return t

    def expect(self, kind: str, what: str = "") -> Tok:
        t = self.next()
        if t.kind != kind and t.val != kind:
            raise StructParseError(
                f"expected {what or kind}, got {t.val!r} (line {t.line})"
            )
        return t

    def expect_kw(self, kw: str):
        t = self.next()
        if t.kind != "name" or t.val != kw:
            raise StructParseError(
                f"expected {kw}, got {t.val!r} (line {t.line})"
            )

    # -- entry points ------------------------------------------------------

    def parse_full(self) -> tuple:
        e = self.parse_expr()
        t = self.peek()
        if t.kind != "eof":
            raise StructParseError(
                f"trailing input {t.val!r} at line {t.line}"
            )
        return e

    def parse_expr(self) -> tuple:
        return self.parse_leadsto()

    # -- precedence levels -------------------------------------------------

    def parse_leadsto(self) -> tuple:
        left = self.parse_implies()
        if self.peek().kind == "leadsto":
            self.next()
            return ("leadsto", left, self.parse_leadsto())
        return left

    def parse_implies(self) -> tuple:
        left = self.parse_or()
        if self.peek().kind == "implies":
            self.next()
            return ("implies", left, self.parse_implies())
        return left

    def parse_or(self) -> tuple:
        left = self.parse_and()
        items = [left]
        while self.peek().kind == "lor":
            self.next()
            items.append(self.parse_and())
        return items[0] if len(items) == 1 else ("or", items)

    def parse_and(self) -> tuple:
        left = self.parse_not()
        items = [left]
        while self.peek().kind == "land":
            self.next()
            items.append(self.parse_not())
        return items[0] if len(items) == 1 else ("and", items)

    def parse_not(self) -> tuple:
        t = self.peek()
        if t.kind == "sym" and t.val == "~":
            self.next()
            return ("not", self.parse_not())
        if t.kind == "box":
            self.next()
            return ("box", self.parse_not())
        if t.kind in ("land", "lor"):
            return self.parse_junction(t)
        if t.kind in ("forall", "exists"):
            return self.parse_quantifier(t)
        return self.parse_cmp()

    def parse_junction(self, bullet: Tok) -> tuple:
        kind = bullet.kind
        col = bullet.col
        items: List[tuple] = []
        while True:
            t = self.peek()
            if t.kind != kind or t.col != col:
                break
            self.next()
            self.bounds.append((t.line, col))
            try:
                items.append(self.parse_expr())
            finally:
                self.bounds.pop()
        if not items:
            raise StructParseError(
                f"empty junction list at line {bullet.line}"
            )
        node = "and" if kind == "land" else "or"
        return items[0] if len(items) == 1 else (node, items)

    def parse_quantifier(self, t: Tok) -> tuple:
        self.next()
        # \A b1, b2 \in S, v \in T : P - one (names, domain) group per
        # \in, nested left to right
        groups = []
        while True:
            names = [self.expect("name").val]
            while self.peek().kind == "sym" and self.peek().val == ",":
                self.next()
                names.append(self.expect("name").val)
            op = self.next()
            if (op.kind, op.val) != ("op", r"\in"):
                raise StructParseError(
                    f"expected \\in in quantifier (line {t.line})"
                )
            groups.append((names, self.parse_cmp_operand()))
            if self.peek().kind == "sym" and self.peek().val == ",":
                self.next()
                continue
            break
        self.expect(":", "':' in quantifier")
        body = self.parse_expr()
        node = "forall" if t.kind == "forall" else "exists"
        for names, dom in reversed(groups):
            body = (node, names, dom, body)
        return body

    _CMP_KINDS = {"eq": "=", "ne": "#", "lt": "<", "gt": ">", "le": "<=",
                  "ge": ">=", "le_": "<=", "ge_": ">="}

    def parse_cmp(self) -> tuple:
        left = self.parse_cmp_operand()
        t = self.peek()
        if t.kind in self._CMP_KINDS:
            self.next()
            return ("cmp", self._CMP_KINDS[t.kind], left,
                    self.parse_cmp_operand())
        if t.kind == "op" and t.val in (r"\in", r"\notin", r"\subseteq"):
            self.next()
            return ("cmp", t.val, left, self.parse_cmp_operand())
        return left

    def parse_cmp_operand(self) -> tuple:
        return self.parse_setop()

    def parse_setop(self) -> tuple:
        # @@ (left, loosest here) < \cup/\cap/\ < :> ; then .. + - \o
        left = self.parse_setop2()
        while self.peek().kind == "atat":
            self.next()
            left = ("binop", "@@", left, self.parse_setop2())
        return left

    def parse_setop2(self) -> tuple:
        left = self.parse_colongt()
        while True:
            t = self.peek()
            if t.kind == "op" and t.val in (r"\cup", r"\cap"):
                self.next()
                left = ("binop", t.val, left, self.parse_colongt())
            elif t.kind == "setminus":
                self.next()
                left = ("binop", "\\", left, self.parse_colongt())
            else:
                return left

    def parse_colongt(self) -> tuple:
        left = self.parse_range()
        if self.peek().kind == "colongt":
            self.next()
            return ("binop", ":>", left, self.parse_range())
        return left

    def parse_range(self) -> tuple:
        left = self.parse_add()
        if self.peek().kind == "range":
            self.next()
            return ("binop", "..", left, self.parse_add())
        return left

    def parse_add(self) -> tuple:
        left = self.parse_mul()
        while True:
            t = self.peek()
            if t.kind == "sym" and t.val in ("+", "-"):
                self.next()
                left = ("binop", t.val, left, self.parse_mul())
            else:
                return left

    def parse_mul(self) -> tuple:
        left = self.parse_concat()
        while True:
            t = self.peek()
            if t.kind == "sym" and t.val == "*":
                self.next()
                left = ("binop", "*", left, self.parse_concat())
            else:
                return left

    def parse_concat(self) -> tuple:
        left = self.parse_postfix()
        while self.peek().kind == "op" and self.peek().val == r"\o":
            self.next()
            left = ("binop", r"\o", left, self.parse_postfix())
        return left

    def parse_postfix(self) -> tuple:
        e = self.parse_atom()
        while True:
            t = self.peek()
            if t.kind == "sym" and t.val == "[":
                self.next()
                arg = self.parse_expr()
                args = [arg]
                while self.peek().kind == "sym" and self.peek().val == ",":
                    self.next()
                    args.append(self.parse_expr())
                self.expect("]")
                for a in args:
                    e = ("apply", e, a)
            elif t.kind == "sym" and t.val == ".":
                # field access - but only when followed by a name (guards
                # against tokenizer surprises)
                nxt = self.toks[self.i + 1] if self.i + 1 < len(self.toks) \
                    else _EOF
                if nxt.kind != "name":
                    return e
                self.next()
                f = self.next()
                e = ("apply", e, ("str", f.val))
            elif t.kind == "sym" and t.val == "'":
                self.next()
                if e[0] != "name":
                    raise StructParseError(
                        f"prime on non-variable (line {t.line})"
                    )
                e = ("prime", e[1])
            else:
                return e

    # -- atoms -------------------------------------------------------------

    def parse_atom(self) -> tuple:
        t = self.next()
        if t.kind == "num":
            return ("num", int(t.val))
        if t.kind == "str":
            return ("str", t.val[1:-1])
        if t.kind == "name":
            return self.parse_name_atom(t)
        if t.kind == "sym" and t.val == "(":
            e = self.parse_expr()
            self.expect(")")
            return e
        if t.kind == "sym" and t.val == "{":
            return self.parse_braces()
        if t.kind == "ltup":
            items = []
            if self.peek().kind != "rtup":
                items.append(self.parse_expr())
                while self.peek().kind == "sym" and self.peek().val == ",":
                    self.next()
                    items.append(self.parse_expr())
            self.expect("rtup", ">>")
            return ("tuple", items)
        if t.kind == "sym" and t.val == "[":
            return self.parse_brackets()
        if t.kind == "sym" and t.val == "@":
            return ("atref",)
        if t.kind == "sym" and t.val == "-":
            inner = self.parse_postfix()
            return ("binop", "-", ("num", 0), inner)
        raise StructParseError(
            f"unexpected token {t.val!r} (line {t.line})"
        )

    def parse_name_atom(self, t: Tok) -> tuple:
        v = t.val
        if v == "TRUE":
            return ("bool", True)
        if v == "FALSE":
            return ("bool", False)
        if v == "IF":
            c = self.parse_expr()
            self.expect_kw("THEN")
            a = self.parse_expr()
            self.expect_kw("ELSE")
            b = self.parse_expr()
            return ("if", c, a, b)
        if v == "CASE":
            arms = []
            other = None
            while True:
                if self.peek().kind == "name" and self.peek().val == "OTHER":
                    self.next()
                    self.expect("arrow", "->")
                    other = self.parse_expr()
                else:
                    g = self.parse_expr()
                    self.expect("arrow", "->")
                    arms.append((g, self.parse_expr()))
                if self.peek().kind == "box":
                    self.next()
                    continue
                break
            return ("case", arms, other)
        if v == "LET":
            binds = []
            while True:
                dname = self.expect("name").val
                params: List[str] = []
                fn_of = None
                if self.peek().kind == "sym" and self.peek().val == "(":
                    self.next()
                    while self.peek().kind == "name":
                        params.append(self.next().val)
                        if self.peek().kind == "sym" \
                                and self.peek().val == ",":
                            self.next()
                    self.expect(")")
                elif self.peek().kind == "sym" and self.peek().val == "[":
                    # `Max[T \in SUBSET S] == ...`: a function defined
                    # by recursion on its argument
                    self.next()
                    var = self.expect("name").val
                    op = self.next()
                    if (op.kind, op.val) != ("op", r"\in"):
                        raise StructParseError(
                            f"expected \\in in the function definition "
                            f"{dname}[...] (line {op.line})")
                    fn_of = (var, self.parse_expr())
                    self.expect("]")
                self.expect("defeq", "==")
                body = self.parse_expr()
                if fn_of is not None:
                    body = ("recfn", dname, fn_of[0], fn_of[1], body)
                binds.append((dname, tuple(params), body))
                nt = self.peek()
                if nt.kind == "name" and nt.val == "IN":
                    self.next()
                    break
                if nt.kind == "name" and nt.val not in _KEYWORDS_STOP \
                        and self._looks_like_let_def():
                    continue
                self.expect_kw("IN")
            return ("let", binds, self.parse_expr())
        if v == "CHOOSE":
            var = self.expect("name").val
            dom = None
            if self.peek().kind == "op" and self.peek().val == r"\in":
                self.next()
                dom = self.parse_cmp_operand()
            # `CHOOSE v : P` (unbounded) parses; like TLC the evaluator
            # refuses to evaluate it, so a model overrides the
            # definition (Paxos's None)
            self.expect(":", "':' in CHOOSE")
            pred = self.parse_expr()
            return ("choose", var, dom, pred)
        if v == "UNCHANGED":
            t2 = self.peek()
            if t2.kind == "ltup":
                self.next()
                names = [self.expect("name").val]
                while self.peek().kind == "sym" and self.peek().val == ",":
                    self.next()
                    names.append(self.expect("name").val)
                self.expect("rtup", ">>")
                return ("unchanged", names)
            return ("unchanged", [self.expect("name").val])
        if v == "DOMAIN":
            return ("domain", self.parse_postfix())
        if v == "SUBSET":
            # the powerset, a prefix operator that binds like DOMAIN
            return ("subset", self.parse_postfix())
        while self.peek().kind == "sym" and self.peek().val == "!":
            # `TC!TCSpec`: a definition of an instanced module, one name
            # (an EXCEPT's `!` follows EXCEPT or a comma, never a name)
            nxt = self.toks[self.i + 1] if self.i + 1 < len(self.toks) \
                else _EOF
            if nxt.kind != "name":
                break
            self.next()
            v = v + "!" + self.next().val
        if self.peek().kind == "sym" and self.peek().val == "(":
            self.next()
            args = [self._parse_arg()]
            while self.peek().kind == "sym" and self.peek().val == ",":
                self.next()
                args.append(self._parse_arg())
            self.expect(")")
            return ("call", v, args)
        return ("name", v)

    def _parse_arg(self) -> tuple:
        """One argument of an operator application: an expression, or a
        bare infix operator handed to a higher-order operator
        (`FoldFunctionOnSet(+, 0, f, S)`): ("opsym", "+")."""
        t = self.peek()
        nxt = self.toks[self.i + 1] if self.i + 1 < len(self.toks) \
            else _EOF
        if t.kind == "sym" and t.val in ("+", "*", "-") \
                and nxt.kind == "sym" and nxt.val in (",", ")"):
            self.next()
            return ("opsym", t.val)
        return self.parse_expr()

    def _looks_like_let_def(self) -> bool:
        """After one LET binding, is the next token run another
        `name [(params)] ==` binding?"""
        j = self.i
        toks = self.toks
        if j >= len(toks) or toks[j].kind != "name":
            return False
        j += 1
        if j < len(toks) and toks[j].kind == "sym" \
                and toks[j].val in ("(", "["):
            opens = toks[j].val
            closes = ")" if opens == "(" else "]"
            depth = 0
            while j < len(toks):
                if toks[j].val == opens:
                    depth += 1
                elif toks[j].val == closes:
                    depth -= 1
                    if depth == 0:
                        j += 1
                        break
                j += 1
        return j < len(toks) and toks[j].kind == "defeq"

    def parse_braces(self) -> tuple:
        """{ } | {a, b} | {x \\in S : P} | {e : x \\in S}"""
        if self.peek().kind == "sym" and self.peek().val == "}":
            self.next()
            return ("setlit", [])
        save = self.i
        t = self.peek()
        if t.kind == "name":
            self.next()
            t2 = self.peek()
            if t2.kind == "op" and t2.val == r"\in":
                self.next()
                dom = self.parse_cmp_operand()
                t3 = self.peek()
                if t3.kind == "sym" and t3.val == ":":
                    self.next()
                    pred = self.parse_expr()
                    self.expect("}")
                    return ("setfilter", t.val, dom, pred)
            self.i = save
        first = self.parse_expr()
        t2 = self.peek()
        if t2.kind == "sym" and t2.val == ":":
            self.next()
            var = self.expect("name").val
            op = self.next()
            if (op.kind, op.val) != ("op", r"\in"):
                raise StructParseError("expected \\in in set map")
            dom = self.parse_cmp_operand()
            self.expect("}")
            return ("setmap", first, var, dom)
        items = [first]
        while self.peek().kind == "sym" and self.peek().val == ",":
            self.next()
            items.append(self.parse_expr())
        self.expect("}")
        return ("setlit", items)

    def parse_brackets(self) -> tuple:
        """[f |-> e, ..] | [x \\in S |-> e] | [f EXCEPT !..] | [S -> T]"""
        save = self.i
        t = self.peek()
        if t.kind == "name":
            self.next()
            t2 = self.peek()
            if t2.kind == "mapsto":
                self.i = save
                return self.parse_record_literal()
            if t2.kind == "sym" and t2.val == ":":
                self.i = save
                return self.parse_record_set()
            if t2.kind == "op" and t2.val == r"\in":
                self.next()
                dom = self.parse_expr()
                self.expect("mapsto", "|->")
                body = self.parse_expr()
                self.expect("]")
                return ("fnlit", t.val, dom, body)
            self.i = save
        fexpr = self.parse_expr()
        t2 = self.peek()
        if t2.kind == "name" and t2.val == "EXCEPT":
            self.next()
            updates = []
            while True:
                self.expect("!", "'!' in EXCEPT")
                path = []
                while True:
                    t3 = self.peek()
                    if t3.kind == "sym" and t3.val == "[":
                        self.next()
                        path.append(self.parse_expr())
                        self.expect("]")
                    elif t3.kind == "sym" and t3.val == ".":
                        self.next()
                        path.append(("str", self.expect("name").val))
                    else:
                        break
                if not path:
                    raise StructParseError("empty EXCEPT path")
                self.expect("eq", "=")
                val = self.parse_expr()
                updates.append((path, val))
                t3 = self.next()
                if t3.kind == "sym" and t3.val == "]":
                    break
                if not (t3.kind == "sym" and t3.val == ","):
                    raise StructParseError(
                        f"expected , or ] in EXCEPT (line {t3.line})"
                    )
            return ("except", fexpr, updates)
        if t2.kind == "arrow":
            self.next()
            rng = self.parse_expr()
            self.expect("]")
            return ("funcset", fexpr, rng)
        raise StructParseError(
            f"unsupported bracket expression (line {t.line})"
        )

    def parse_record_set(self) -> tuple:
        """[f : S, g : T] - the set of records with f in S and g in T."""
        fields = []
        while True:
            f = self.expect("name").val
            self.expect(":", "':' in record set")
            fields.append((f, self.parse_expr()))
            t = self.next()
            if t.kind == "sym" and t.val == "]":
                break
            if not (t.kind == "sym" and t.val == ","):
                raise StructParseError("expected , or ] in record set")
        return ("recset", fields)

    def parse_record_literal(self) -> tuple:
        fields = []
        while True:
            f = self.expect("name").val
            self.expect("mapsto", "|->")
            fields.append((f, self.parse_expr()))
            t = self.next()
            if t.kind == "sym" and t.val == "]":
                break
            if not (t.kind == "sym" and t.val == ","):
                raise StructParseError("expected , or ] in record literal")
        return ("record", fields)


def parse_expression(src: str) -> tuple:
    """Parse a standalone expression (tests / trace expressions)."""
    return _ExprParser(tokenize(strip_comments(src))).parse_full()
