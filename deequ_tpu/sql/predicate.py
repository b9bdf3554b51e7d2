"""A small SQL-expression compiler for predicates over device columns.

The reference's ``Compliance`` analyzer and ``.where(...)`` filters take
arbitrary Spark SQL expression strings (reference:
``src/main/scala/com/amazon/deequ/analyzers/Compliance.scala``,
``checks/Check.scala``; SURVEY.md §2.2). deequ_tpu keeps that surface but
compiles the expression to pure JAX ops at plan time:

- numeric columns evaluate on their device ``values``;
- string comparisons become *dictionary-code* operations — equality/IN
  become host-side dictionary lookups producing code sets, LIKE/RLIKE
  become a host-side regex sweep over the (small) dictionary producing a
  device bool lookup table gathered by code. Strings never reach the TPU
  (SURVEY.md §7 hard part #3).

Three-valued logic follows SQL: comparisons involving NULL are NULL; a
row "complies" iff the predicate is TRUE (not NULL, not FALSE).

Supported grammar (r4 extends toward the reference's Spark SQL surface;
SURVEY.md §2.2 Compliance = "arbitrary SQL predicate"):

| form | notes |
|---|---|
| OR / AND / NOT | SQL three-valued logic |
| = == != <> < <= > >= | string orderings via shared lexicographic ranks |
| + - * / % , unary - | / and % by zero -> NULL |
| IS [NOT] NULL | |
| [NOT] IN (...) | string or numeric item lists |
| BETWEEN x AND y | |
| [NOT] LIKE 'pat%' / RLIKE 're' | host regex over the dictionary |
| CASE WHEN c THEN v ... [ELSE v] END | numeric/bool OR string branch values (homogeneous) |
| COALESCE(a, b, ...) | numeric/bool OR string arguments (homogeneous) |
| ABS(x) | |
| LENGTH(s) | also over TRIM/UPPER/... and CASE/CONCAT results |
| TRIM/LTRIM/RTRIM(s) | host transform over the dictionary |
| UPPER(s) / LOWER(s) | compose freely, e.g. UPPER(TRIM(s)) |
| SUBSTR/SUBSTRING(s, pos[, len]) | Spark 1-based semantics |
| CONCAT(...) | any mix of string columns/expressions and literals (cross-dictionary product bounded by a 65536-entry plan budget) |
| CAST(x AS INT/BIGINT/DOUBLE/...) | string operands parse per dictionary entry, unparseable -> NULL; timestamp columns -> epoch SECONDS (floor for integral targets) |
| CAST(x AS STRING) | string operands (identity) and boolean columns ('true'/'false') |
| ts_col <op> 'YYYY-MM-DD[ HH:MM:SS]' | date literal in the column's unit |
| DATE_ADD(ts_col, n) / DATE_SUB | shifts by whole days in the column's unit |
| DATEDIFF(a, b) | UTC-day difference; timestamp columns and/or date literals |
| literals | numbers, 'strings', TRUE/FALSE/NULL |

String functions never reach the device: they evaluate host-side over
the (small) column dictionary, composing into per-code lookup tables —
string-valued CASE/COALESCE, multi-column CONCAT and CAST(bool AS
STRING) build SYNTHETIC dictionaries (union / cross-product /
'true'-'false') whose codes the device selects with the same gathers
(SURVEY.md §7 hard part #3). Unsupported syntax fails at PLANNING time
(PredicateParseError), which the runner degrades to that analyzer's
failure metric — never a crash mid-scan.

Known not-yet-implemented vs full Spark SQL (documented, degrade
cleanly): timezone-aware date semantics (DATEDIFF counts UTC days),
and CAST of numeric/timestamp VALUES to STRING (Java number/timestamp
formatting; compare numerically instead).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from deequ_tpu.data.table import ColumnRequest, Dataset, Kind

# --------------------------------------------------------------------------
# Tokenizer
# --------------------------------------------------------------------------

_TOKEN_RE = re.compile(
    r"""
    (?P<ws>\s+)
  | (?P<number>\d+\.\d*(?:[eE][+-]?\d+)?|\.\d+(?:[eE][+-]?\d+)?|\d+(?:[eE][+-]?\d+)?)
  | (?P<string>'(?:[^'\\]|\\.)*'|"(?:[^"\\]|\\.)*")
  | (?P<bq_ident>`[^`]+`)
  | (?P<op><=|>=|!=|<>|==|=|<|>|\+|-|\*|/|%|\(|\)|,)
  | (?P<ident>[A-Za-z_][A-Za-z_0-9.]*)
    """,
    re.VERBOSE,
)

_KEYWORDS = {
    "AND", "OR", "NOT", "IS", "NULL", "IN", "BETWEEN", "LIKE", "RLIKE",
    "TRUE", "FALSE", "CASE", "WHEN", "THEN", "ELSE", "END", "CAST", "AS",
}


@dataclass(frozen=True)
class Token:
    kind: str  # 'number' | 'string' | 'ident' | 'op' | 'kw'
    text: str


def tokenize(expression: str) -> List[Token]:
    tokens: List[Token] = []
    pos = 0
    while pos < len(expression):
        m = _TOKEN_RE.match(expression, pos)
        if not m:
            raise PredicateParseError(
                f"cannot tokenize {expression[pos:pos + 20]!r} in predicate"
            )
        pos = m.end()
        if m.lastgroup == "ws":
            continue
        text = m.group()
        kind = m.lastgroup
        if kind == "bq_ident":
            tokens.append(Token("ident", text[1:-1]))
        elif kind == "ident" and text.upper() in _KEYWORDS:
            tokens.append(Token("kw", text.upper()))
        else:
            tokens.append(Token(kind, text))
    return tokens


class PredicateParseError(ValueError):
    pass


# --------------------------------------------------------------------------
# AST
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Node:
    pass


@dataclass(frozen=True)
class ColumnRef(Node):
    name: str


@dataclass(frozen=True)
class NumberLit(Node):
    value: float


@dataclass(frozen=True)
class StringLit(Node):
    value: str


@dataclass(frozen=True)
class BoolLit(Node):
    value: bool


@dataclass(frozen=True)
class NullLit(Node):
    pass


@dataclass(frozen=True)
class UnaryOp(Node):
    op: str  # 'NOT' | 'NEG'
    operand: Node


@dataclass(frozen=True)
class BinOp(Node):
    op: str  # 'AND','OR','=','!=','<','<=','>','>=','+','-','*','/','%'
    left: Node
    right: Node


@dataclass(frozen=True)
class IsNull(Node):
    operand: Node
    negate: bool


@dataclass(frozen=True)
class InList(Node):
    operand: Node
    items: Tuple[Node, ...]
    negate: bool


@dataclass(frozen=True)
class Between(Node):
    operand: Node
    low: Node
    high: Node


@dataclass(frozen=True)
class Like(Node):
    operand: Node
    pattern: str
    regex: bool
    negate: bool


@dataclass(frozen=True)
class CaseWhen(Node):
    """CASE WHEN c1 THEN v1 [WHEN c2 THEN v2 ...] [ELSE v] END."""

    whens: Tuple[Tuple[Node, Node], ...]
    else_: Optional[Node]


@dataclass(frozen=True)
class Cast(Node):
    """CAST(expr AS type); numeric targets only (INT truncates toward
    zero; string operands parse per dictionary entry, unparseable ->
    NULL, Spark's cast semantics)."""

    operand: Node
    type_name: str  # 'INT' | 'BIGINT' | 'LONG' | 'FLOAT' | 'DOUBLE'


@dataclass(frozen=True)
class StarLit(Node):
    """The `*` inside COUNT(*) (aggregate expressions only)."""


@dataclass(frozen=True)
class FuncCall(Node):
    name: str
    args: Tuple[Node, ...]


class _Parser:
    def __init__(self, tokens: List[Token]):
        self.tokens = tokens
        self.pos = 0

    def peek(self) -> Optional[Token]:
        return self.tokens[self.pos] if self.pos < len(self.tokens) else None

    def next(self) -> Token:
        tok = self.peek()
        if tok is None:
            raise PredicateParseError("unexpected end of predicate")
        self.pos += 1
        return tok

    def accept(self, kind: str, text: Optional[str] = None) -> Optional[Token]:
        tok = self.peek()
        if tok and tok.kind == kind and (text is None or tok.text == text):
            return self.next()
        return None

    def expect(self, kind: str, text: Optional[str] = None) -> Token:
        tok = self.accept(kind, text)
        if tok is None:
            got = self.peek()
            raise PredicateParseError(
                f"expected {text or kind}, got {got.text if got else 'EOF'!r}"
            )
        return tok

    def parse(self) -> Node:
        node = self.or_expr()
        if self.peek() is not None:
            raise PredicateParseError(
                f"trailing tokens starting at {self.peek().text!r}"
            )
        return node

    def or_expr(self) -> Node:
        node = self.and_expr()
        while self.accept("kw", "OR"):
            node = BinOp("OR", node, self.and_expr())
        return node

    def and_expr(self) -> Node:
        node = self.not_expr()
        while self.accept("kw", "AND"):
            node = BinOp("AND", node, self.not_expr())
        return node

    def not_expr(self) -> Node:
        if self.accept("kw", "NOT"):
            return UnaryOp("NOT", self.not_expr())
        return self.comparison()

    def comparison(self) -> Node:
        node = self.additive()
        tok = self.peek()
        if tok is None:
            return node
        if tok.kind == "op" and tok.text in ("=", "==", "!=", "<>", "<", "<=", ">", ">="):
            self.next()
            op = {"==": "=", "<>": "!="}.get(tok.text, tok.text)
            return BinOp(op, node, self.additive())
        if tok.kind == "kw" and tok.text == "IS":
            self.next()
            negate = self.accept("kw", "NOT") is not None
            self.expect("kw", "NULL")
            return IsNull(node, negate)
        negate = False
        if tok.kind == "kw" and tok.text == "NOT":
            nxt = (
                self.tokens[self.pos + 1]
                if self.pos + 1 < len(self.tokens)
                else None
            )
            if nxt and nxt.kind == "kw" and nxt.text in ("IN", "LIKE", "RLIKE"):
                self.next()
                negate = True
                tok = self.peek()
        if tok and tok.kind == "kw" and tok.text == "IN":
            self.next()
            self.expect("op", "(")
            items = [self.additive()]
            while self.accept("op", ","):
                items.append(self.additive())
            self.expect("op", ")")
            return InList(node, tuple(items), negate)
        if tok and tok.kind == "kw" and tok.text == "BETWEEN":
            self.next()
            low = self.additive()
            self.expect("kw", "AND")
            high = self.additive()
            return Between(node, low, high)
        if tok and tok.kind == "kw" and tok.text in ("LIKE", "RLIKE"):
            self.next()
            pat = self.next()
            if pat.kind != "string":
                raise PredicateParseError(
                    f"{tok.text} expects a string pattern"
                )
            return Like(
                node,
                _unquote(pat.text),
                regex=tok.text == "RLIKE",
                negate=negate,
            )
        return node

    def additive(self) -> Node:
        node = self.multiplicative()
        while True:
            tok = self.peek()
            if tok and tok.kind == "op" and tok.text in ("+", "-"):
                self.next()
                node = BinOp(tok.text, node, self.multiplicative())
            else:
                return node

    def multiplicative(self) -> Node:
        node = self.unary()
        while True:
            tok = self.peek()
            if tok and tok.kind == "op" and tok.text in ("*", "/", "%"):
                self.next()
                node = BinOp(tok.text, node, self.unary())
            else:
                return node

    def unary(self) -> Node:
        if self.accept("op", "-"):
            return UnaryOp("NEG", self.unary())
        return self.primary()

    def primary(self) -> Node:
        tok = self.next()
        if tok.kind == "kw" and tok.text == "CAST":
            self.expect("op", "(")
            operand = self.or_expr()
            self.expect("kw", "AS")
            type_tok = self.next()
            if type_tok.kind != "ident":
                raise PredicateParseError(
                    f"CAST expects a type name, got {type_tok.text!r}"
                )
            self.expect("op", ")")
            return Cast(operand, type_tok.text.upper())
        if tok.kind == "kw" and tok.text == "CASE":
            whens: List[Tuple[Node, Node]] = []
            while self.accept("kw", "WHEN"):
                cond = self.or_expr()
                self.expect("kw", "THEN")
                whens.append((cond, self.or_expr()))
            if not whens:
                raise PredicateParseError(
                    "CASE requires at least one WHEN ... THEN branch"
                )
            else_ = self.or_expr() if self.accept("kw", "ELSE") else None
            self.expect("kw", "END")
            return CaseWhen(tuple(whens), else_)
        if tok.kind == "number":
            return NumberLit(float(tok.text))
        if tok.kind == "string":
            return StringLit(_unquote(tok.text))
        if tok.kind == "kw" and tok.text == "TRUE":
            return BoolLit(True)
        if tok.kind == "kw" and tok.text == "FALSE":
            return BoolLit(False)
        if tok.kind == "kw" and tok.text == "NULL":
            return NullLit()
        if tok.kind == "op" and tok.text == "(":
            node = self.or_expr()
            self.expect("op", ")")
            return node
        if tok.kind == "ident":
            if self.accept("op", "("):
                args: List[Node] = []
                if tok.text.upper() == "COUNT" and self.accept("op", "*"):
                    args.append(StarLit())  # COUNT(*) only
                    self.expect("op", ")")
                elif not self.accept("op", ")"):
                    args.append(self.or_expr())
                    while self.accept("op", ","):
                        args.append(self.or_expr())
                    self.expect("op", ")")
                return FuncCall(tok.text.upper(), tuple(args))
            return ColumnRef(tok.text)
        raise PredicateParseError(f"unexpected token {tok.text!r}")


def _unquote(s: str) -> str:
    body = s[1:-1]
    return re.sub(r"\\(.)", r"\1", body)


def parse_predicate(expression: str) -> Node:
    return _Parser(tokenize(expression)).parse()


def _validate_date_literal(text: str) -> None:
    """The ONE date-literal validation (plan time); comparison and
    DATEDIFF literals must accept/reject identically."""
    import datetime as _dt

    try:
        _dt.datetime.fromisoformat(text)
    except ValueError as exc:
        raise PredicateParseError(
            f"{text!r} is not a date/timestamp literal "
            "(YYYY-MM-DD[ HH:MM:SS])"
        ) from exc


_REGEX_META = frozenset("\\.^$|?*+()[]{}")


def _sql_like_to_regex(pattern: str) -> str:
    """SQL LIKE as an anchored regex in the syntax Python's ``re`` and
    RE2 share: only regex metacharacters are escaped, so
    data/strings.regex_plane can hand it to RE2."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        elif ch in _REGEX_META:
            out.append("\\" + ch)
        else:
            out.append(ch)
    return "^" + "".join(out) + "$"


# --------------------------------------------------------------------------
# Compiler: AST -> (requests, traced eval over batch)
# --------------------------------------------------------------------------

# An evaluated expression: (values, valid) with SQL null semantics, or for
# booleans (truth, valid). `values` may be numeric or int32 codes tagged
# with the column whose dictionary they index.


@dataclass
class _Val:
    values: jnp.ndarray
    valid: jnp.ndarray
    is_bool: bool = False
    codes_of: Optional[str] = None  # column name whose dictionary applies
    # host-side string transform composed over the dictionary (TRIM/
    # UPPER/LOWER/SUBSTR chains): consumers build per-code LUTs from
    # transform(dict[i]) instead of dict[i]; None = raw values
    transform: Optional[Callable[[str], str]] = None
    # SYNTHETIC string lane (string-valued CASE/COALESCE, multi-column
    # CONCAT, CAST(bool AS STRING)): ``values`` are codes into this
    # tuple instead of a column dictionary; entries may be None for
    # never-selected slots (row validity governs). codes_of stays None.
    entries: Optional[Tuple[Optional[str], ...]] = None
    # timestamp/date lane: ``ts_per_day`` = how many epoch units make
    # one UTC day (set for TIMESTAMP/date columns and DATE_ADD results;
    # 1 = day-valued). Comparisons convert string literals into this
    # unit, and mixed-unit lanes normalize to the finer unit.
    # ``ts_col`` names the source column when the values are its RAW
    # storage epochs (literal conversion then goes through the exact
    # Arrow cast); None for derived day-valued lanes.
    ts_col: Optional[str] = None
    ts_per_day: Optional[int] = None

    def view(self, value: str) -> str:
        return self.transform(value) if self.transform else value


class _PredicateData:
    """What predicate evaluation may touch: the schema (strong) and the
    dictionaries (weak — only string predicates dereference them, and
    only at trace time while the owning run holds the dataset)."""

    __slots__ = ("schema", "_ref", "lanes")

    def __init__(self, schema, ref, lanes=frozenset()):
        self.schema = schema
        self._ref = ref
        # hashed-string lane columns (data/strings.py): read through
        # their per-row planes, never a dictionary
        self.lanes = lanes

    def dictionary(self, column: str):
        dataset = self._ref()
        if dataset is None:  # pragma: no cover — contract violation
            raise RuntimeError(
                "string predicate outlived its dataset; string "
                "predicates are only traced while the owning run holds "
                "the data"
            )
        return dataset.dictionary(column)

    def arrow_type(self, column: str):
        """Storage type (timestamp predicates need the epoch unit)."""
        dataset = self._ref()
        if dataset is None:  # pragma: no cover — contract violation
            raise RuntimeError(
                "timestamp predicate outlived its dataset; it is only "
                "traced while the owning run holds the data"
            )
        return dataset._column_arrow_type(column)


class CompiledPredicate:
    """A predicate compiled against a dataset's schema + dictionaries.

    ``requests`` lists the device columns needed; ``evaluate(batch)`` is
    traceable and returns (truth: bool array, valid: bool array). A row
    complies iff truth & valid.
    """

    def __init__(
        self,
        node: Node,
        dataset: Dataset,
        columns_used: Sequence[str],
        requests: Sequence[ColumnRequest],
        lanes: frozenset = frozenset(),
    ):
        import weakref

        self._node = node
        self._lanes = lanes
        # WEAK reference: compiled predicates end up inside jitted
        # closures that the cross-run plan cache retains — a strong ref
        # would pin the whole Arrow table for the cache's lifetime. The
        # dataset is only dereferenced at TRACE time (schema lookups,
        # dictionary lookups for string predicates), which happens while
        # the owning run still holds the dataset.
        self._dataset_ref = weakref.ref(dataset)
        self._schema = dataset.schema
        self.columns_used = tuple(columns_used)
        self.requests = tuple(requests)
        # a predicate touching NO string and NO timestamp column
        # evaluates identically on any dataset with the same schema
        # kinds (no dictionary-derived constants and no unit-dependent
        # epoch literals get baked into its closure) — the engine's
        # plan cache may reuse compiled scans across datasets only then
        self.dataset_independent = all(
            dataset.schema.kind_of(c) not in (Kind.STRING, Kind.TIMESTAMP)
            for c in self.columns_used
        )

    @property
    def _dataset(self) -> "_PredicateData":
        # shim: schema strongly held (all a NUMERIC predicate touches,
        # incl. on re-trace after the origin dataset is gone);
        # dictionaries resolve through the weakref (string predicates
        # only — those are never in cached cross-dataset plans)
        return _PredicateData(self._schema, self._dataset_ref, self._lanes)

    def evaluate(self, batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        val = _eval(self._node, batch, self._dataset)
        truth, valid = _as_bool(val)
        return truth, valid

    def complies(self, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        truth, valid = self.evaluate(batch)
        return truth & valid


def compile_predicate(expression: str, dataset: Dataset) -> CompiledPredicate:
    # per-dataset compile cache: device_requests() and make_ops() both
    # compile the same expressions during planning
    cache = getattr(dataset, "_predicate_cache", None)
    if cache is None:
        cache = {}
        setattr(dataset, "_predicate_cache", cache)
    if expression in cache:
        return cache[expression]
    node = parse_predicate(expression)
    cols = sorted(_columns_of(node))
    schema = dataset.schema
    requests: List[ColumnRequest] = []
    for c in cols:
        if not schema.has_column(c):
            raise KeyError(f"predicate references unknown column '{c}'")
    lanes = {
        c
        for c in cols
        if schema.kind_of(c) == Kind.STRING and dataset.string_lane(c)
    }
    # a lane column read other than through its per-row planes (an
    # ordering, a string function, a comparison with another column)
    # takes dictionary codes, as every string column did before lanes
    lanes -= _dictionary_reads(node, lanes)
    for c in cols:
        kind = schema.kind_of(c)
        if c in lanes:
            pass
        elif kind == Kind.STRING:
            requests.append(ColumnRequest(c, "codes"))
        else:
            requests.append(ColumnRequest(c, "values"))
        requests.append(ColumnRequest(c, "mask"))
    for col in _length_columns_of(node):
        requests.append(ColumnRequest(col, "lengths"))
    if lanes:
        requests += [
            ColumnRequest(c, r) for c, r in sorted(_lane_planes(node, lanes))
        ]
    # static type check NOW (make_ops/planning time) so a bad predicate
    # degrades to THAT analyzer's failure metric — a raise later, inside
    # the shared fused-scan trace, would poison every co-scheduled
    # analyzer in the pass
    _check_types(node, schema)
    _check_plan_budgets(node, dataset)
    compiled = CompiledPredicate(
        node, dataset, cols, requests, frozenset(lanes)
    )
    cache[expression] = compiled
    return compiled


def _check_types(node: Node, schema) -> str:
    """Static kind inference: returns 'string' | 'stringlit' | 'value' |
    'null'; raises PredicateParseError on string/numeric mixes that the
    runtime would otherwise hit mid-trace."""

    def kind_of(n: Node) -> str:
        if isinstance(n, ColumnRef):
            k = schema.kind_of(n.name)
            if k == Kind.STRING:
                return "string"
            if k == Kind.TIMESTAMP:
                return "timestamp"
            return "value"
        if isinstance(n, StringLit):
            return "stringlit"
        if isinstance(n, NullLit):
            return "null"
        if isinstance(n, (NumberLit, BoolLit)):
            return "value"
        if isinstance(n, UnaryOp):
            k = kind_of(n.operand)
            if k in ("string", "stringlit"):
                raise PredicateParseError(
                    f"{'negation' if n.op == 'NEG' else 'NOT'} is "
                    "undefined for string operands"
                )
            return "value"
        if isinstance(n, IsNull):
            kind_of(n.operand)
            return "value"
        if isinstance(n, Between):
            check_cmp(n.operand, n.low)
            check_cmp(n.operand, n.high)
            return "value"
        if isinstance(n, CaseWhen):
            results = [r for _, r in n.whens]
            if n.else_ is not None:
                results.append(n.else_)
            for cond, _ in n.whens:
                if kind_of(cond) in ("string", "stringlit"):
                    raise PredicateParseError(
                        "a CASE condition must be boolean, not a bare "
                        "string operand"
                    )
            return _homogeneous_branches(
                [kind_of(r) for r in results], "CASE"
            )
        if isinstance(n, InList):
            base = kind_of(n.operand)
            for item in n.items:
                if isinstance(item, NullLit):
                    continue
                item_kind = kind_of(item)
                if base == "string" and item_kind != "stringlit":
                    raise PredicateParseError(
                        "IN on a string column requires string literals"
                    )
                if base != "string" and item_kind == "stringlit":
                    raise PredicateParseError(
                        "IN with string literals requires a string column"
                    )
            return "value"
        if isinstance(n, Like):
            if kind_of(n.operand) != "string":
                raise PredicateParseError("LIKE requires a string column")
            return "value"
        if isinstance(n, Cast):
            if (
                n.type_name not in _CAST_TYPES
                and n.type_name not in _STRING_CASTS
            ):
                raise PredicateParseError(
                    f"CAST to {n.type_name} is not supported "
                    "(numeric or STRING targets)"
                )
            k = kind_of(n.operand)
            if k == "stringlit":
                raise PredicateParseError(
                    "CAST of a string literal is constant"
                )
            if n.type_name in _STRING_CASTS:
                if k == "string":
                    return "string"
                if (
                    isinstance(n.operand, ColumnRef)
                    and schema.kind_of(n.operand.name) == Kind.BOOLEAN
                ):
                    return "string"
                raise PredicateParseError(
                    "CAST to STRING supports string and boolean "
                    "operands only (numeric/timestamp formatting is "
                    "not supported)"
                )
            if k == "timestamp" and not isinstance(n.operand, ColumnRef):
                # day-valued DATE_ADD/DATE_SUB results are DATEs;
                # Spark refuses date -> numeric casts
                raise PredicateParseError(
                    "CAST of a date value to a number is not "
                    "supported (Spark refuses date -> numeric)"
                )
            # timestamp COLUMNS cast to epoch seconds (Spark); the
            # date-typed-column refusal needs the arrow type and lives
            # in _check_plan_budgets
            return "value"
        if isinstance(n, FuncCall):
            # the predicate evaluator supports only these functions;
            # aggregates (SUM/COUNT/...) belong to CustomSql expressions
            # and must fail HERE (planning time), not mid-trace where
            # they would poison every co-scheduled analyzer
            if n.name not in (
                "ABS", "LENGTH", "COALESCE", "CONCAT",
                "DATE_ADD", "DATE_SUB", "DATEDIFF",
            ) + _STRING_FNS:
                raise PredicateParseError(
                    f"unsupported function {n.name} in a predicate"
                )
            if n.name in ("DATE_ADD", "DATE_SUB"):
                if len(n.args) != 2:
                    raise PredicateParseError(
                        f"{n.name} takes (timestamp column, days)"
                    )
                if kind_of(n.args[0]) != "timestamp":
                    raise PredicateParseError(
                        f"{n.name} requires a timestamp/date column"
                    )
                _static_int(n.args[1], f"{n.name} day count")
                return "timestamp"
            if n.name == "DATEDIFF":
                if len(n.args) != 2:
                    raise PredicateParseError(
                        "DATEDIFF takes (end, start)"
                    )
                kinds_ = []
                for a in n.args:
                    k = kind_of(a)
                    if k == "stringlit":
                        assert isinstance(a, StringLit)
                        _validate_date_literal(a.value)
                    elif k != "timestamp":
                        raise PredicateParseError(
                            "DATEDIFF arguments must be timestamp "
                            "columns or date literals"
                        )
                    kinds_.append(k)
                if all(k == "stringlit" for k in kinds_):
                    raise PredicateParseError(
                        "DATEDIFF of two literals is constant"
                    )
                return "value"
            if n.name == "CONCAT":
                if not n.args:
                    raise PredicateParseError("CONCAT needs arguments")
                col_args = 0
                for a in n.args:
                    k = kind_of(a)
                    if k == "string":
                        col_args += 1
                    elif k != "stringlit":
                        raise PredicateParseError(
                            "CONCAT arguments must be strings"
                        )
                if col_args == 0:
                    raise PredicateParseError(
                        "CONCAT of only literals is constant"
                    )
                # multi-column CONCAT builds a cross-product synthetic
                # dictionary; its SIZE is validated against the plan
                # budget in _check_plan_budgets (needs dictionaries)
                return "string"
            for a in n.args:
                if isinstance(a, StarLit):
                    raise PredicateParseError(
                        f"* is not a valid argument to {n.name}"
                    )
            if n.name in _STRING_FNS:
                # FULL static validation here: a raise later, inside
                # the shared fused-scan trace, would poison every
                # co-scheduled analyzer (this module's core invariant)
                if n.name in ("SUBSTR", "SUBSTRING"):
                    if len(n.args) not in (2, 3):
                        raise PredicateParseError(
                            f"{n.name} takes (string, pos[, length])"
                        )
                    _static_int(n.args[1], f"{n.name} position")
                    if len(n.args) == 3:
                        _static_int(n.args[2], f"{n.name} length")
                elif len(n.args) != 1:
                    raise PredicateParseError(
                        f"{n.name} takes exactly one argument"
                    )
                if kind_of(n.args[0]) != "string":
                    raise PredicateParseError(
                        f"{n.name} requires a string column operand"
                    )
                return "string"
            if n.name == "COALESCE":
                if not n.args:
                    raise PredicateParseError(
                        "COALESCE needs arguments"
                    )
                return _homogeneous_branches(
                    [kind_of(a) for a in n.args], "COALESCE"
                )
            if n.name == "LENGTH":
                for a in n.args:
                    kind_of(a)
                return "value"
            for a in n.args:
                kind_of(a)
            return "value"
        if isinstance(n, BinOp):
            if n.op in ("AND", "OR"):
                for side in (n.left, n.right):
                    if kind_of(side) in ("string", "stringlit"):
                        raise PredicateParseError(
                            "a bare string operand is not a boolean "
                            f"(in {n.op})"
                        )
                return "value"
            lk, rk = kind_of(n.left), kind_of(n.right)
            if n.op in _CMP:
                check_kinds(lk, rk, n.op)
                check_ts_literal(n.left, lk, n.right, rk)
                return "value"
            # arithmetic
            for k in (lk, rk):
                if k in ("string", "stringlit"):
                    raise PredicateParseError(
                        f"arithmetic {n.op!r} is undefined for string "
                        "operands"
                    )
            return "value"
        return "value"

    def check_kinds(lk: str, rk: str, op: str) -> None:
        stringish = ("string", "stringlit")
        if "null" in (lk, rk):
            return
        # timestamp vs string literal: the literal is a date — valid
        if {"timestamp", "stringlit"} == {lk, rk}:
            return
        if lk == "timestamp":
            lk = "value"
        if rk == "timestamp":
            rk = "value"
        if (lk in stringish) != (rk in stringish):
            raise PredicateParseError(
                "cannot compare a string operand with a non-string "
                "operand (dictionary codes are not values)"
            )
        if lk == "stringlit" and rk == "stringlit":
            raise PredicateParseError(
                f"comparison {op!r} of two string literals is constant"
            )

    def check_ts_literal(a: Node, ak: str, b: Node, bk: str) -> None:
        """A timestamp-vs-string-literal compare carries a STATIC date
        literal — validate it NOW (plan time), not mid-trace."""
        import datetime as _dt

        for node_, kind_, other in ((a, ak, bk), (b, bk, ak)):
            if kind_ == "stringlit" and other == "timestamp":
                assert isinstance(node_, StringLit)
                _validate_date_literal(node_.value)

    def check_cmp(a: Node, b: Node) -> None:
        check_kinds(kind_of(a), kind_of(b), "BETWEEN")
        check_ts_literal(a, kind_of(a), b, kind_of(b))

    return kind_of(node)


def _homogeneous_branches(kinds: List[str], what: str) -> str:
    """CASE/COALESCE result branches must all be stringish or all
    value-ish (NULLs are neutral); returns the result kind."""
    stringish = [k for k in kinds if k in ("string", "stringlit")]
    valueish = [k for k in kinds if k in ("value", "timestamp")]
    if stringish and valueish:
        raise PredicateParseError(
            f"{what} branches mix string and non-string results"
        )
    return "string" if stringish else "value"


def _estimated_entries(node: Node, dataset: Dataset) -> int:
    """Upper bound on a string expression's dictionary size (plan
    time): column lanes count their dictionary, CONCAT multiplies,
    CASE/COALESCE unions sum, literals are 1."""
    if isinstance(node, StringLit):
        return 1
    if isinstance(node, ColumnRef):
        return len(dataset.dictionary(node.name))
    if isinstance(node, FuncCall):
        if node.name == "CONCAT":
            total = 1
            for a in node.args:
                e = _estimated_entries(a, dataset)
                if e > 1:  # literals fold into neighbors
                    total *= e
            return total
        if node.name == "COALESCE":
            return sum(
                _estimated_entries(a, dataset) for a in node.args
            )
        if node.name in _STRING_FNS:
            return _estimated_entries(node.args[0], dataset)
    if isinstance(node, CaseWhen):
        total = sum(
            _estimated_entries(r, dataset) for _, r in node.whens
        )
        if node.else_ is not None:
            total += _estimated_entries(node.else_, dataset)
        return total
    if isinstance(node, Cast):  # CAST(s AS STRING) is identity
        return _estimated_entries(node.operand, dataset)
    return 2  # bool lanes etc.


def _check_plan_budgets(node: Node, dataset: Dataset) -> None:
    """Dictionary-dependent plan-time validation (runs after the
    static type check, with the dataset in hand): CONCAT cross-product
    budgets and the date-typed-column CAST refusal."""
    if isinstance(node, FuncCall) and node.name == "CONCAT":
        est = _estimated_entries(node, dataset)
        if est > _CONCAT_DICT_BUDGET:
            raise PredicateParseError(
                f"CONCAT cross-dictionary size ~{est} exceeds the "
                f"{_CONCAT_DICT_BUDGET}-entry plan budget"
            )
    if (
        isinstance(node, Cast)
        and node.type_name in _CAST_TYPES
        and isinstance(node.operand, ColumnRef)
        and dataset.schema.kind_of(node.operand.name) == Kind.TIMESTAMP
    ):
        import pyarrow as pa

        if pa.types.is_date(dataset._column_arrow_type(node.operand.name)):
            raise PredicateParseError(
                "CAST of a DATE column to a number is not supported "
                "(Spark refuses date -> numeric)"
            )
    for child in _children_of(node):
        _check_plan_budgets(child, dataset)


def _children_of(node: Node):
    """Every child Node, uniformly across node shapes (incl. CASE)."""
    for attr in ("operand", "left", "right", "low", "high", "else_"):
        child = getattr(node, attr, None)
        if isinstance(child, Node):
            yield child
    for attr in ("items", "args"):
        for child in getattr(node, attr, ()):
            if isinstance(child, Node):
                yield child
    for pair in getattr(node, "whens", ()):
        yield pair[0]
        yield pair[1]


def _lane_operand(node: Node, lanes) -> Optional[str]:
    """The lane column a lane-readable node applies to, else None: IN
    over string literals, ``=``/``!=`` against a string literal, LIKE
    and RLIKE, each straight on the column."""
    if isinstance(node, (InList, Like)):
        operand = node.operand
        if isinstance(node, InList) and not all(
            isinstance(i, (StringLit, NullLit)) for i in node.items
        ):
            return None
    elif isinstance(node, BinOp) and node.op in ("=", "!="):
        if isinstance(node.right, StringLit):
            operand = node.left
        elif isinstance(node.left, StringLit):
            operand = node.right
        else:
            return None
    else:
        return None
    if isinstance(operand, ColumnRef) and operand.name in lanes:
        return operand.name
    return None


def _lane_plane(node: Node) -> str:
    """The bool plane repr (data/strings.py) a lane-readable node
    reads."""
    from deequ_tpu.data import strings

    if isinstance(node, Like):
        return strings.match_repr(
            node.pattern if node.regex else _sql_like_to_regex(node.pattern)
        )
    if isinstance(node, InList):
        return strings.in_repr(
            [i.value for i in node.items if isinstance(i, StringLit)]
        )
    literal = node.right if isinstance(node.right, StringLit) else node.left
    return strings.in_repr([literal.value])


def _dictionary_reads(node: Node, lanes) -> set:
    """The lane columns ``node`` reads other than through a bool plane,
    the mask (IS NULL) or the lengths (LENGTH): they need codes."""
    if _lane_operand(node, lanes) is not None:
        return set()
    if isinstance(node, IsNull) and isinstance(node.operand, ColumnRef):
        return set()
    if (
        isinstance(node, FuncCall)
        and node.name == "LENGTH"
        and all(isinstance(a, ColumnRef) for a in node.args)
    ):
        return set()
    if isinstance(node, ColumnRef):
        return {node.name} & set(lanes)
    out: set = set()
    for child in _children_of(node):
        out |= _dictionary_reads(child, lanes)
    return out


def _lane_planes(node: Node, lanes) -> set:
    """(column, plane repr) for every lane-readable node over a column
    in ``lanes``."""
    column = _lane_operand(node, lanes)
    if column is not None:
        if isinstance(node, Like) and node.regex:
            re.compile(node.pattern)  # a bad regex fails here, not mid-scan
        return {(column, _lane_plane(node))}
    out: set = set()
    for child in _children_of(node):
        out |= _lane_planes(child, lanes)
    return out


def _length_columns_of(node: Node) -> set:
    """Columns appearing as LENGTH(col) — they need the 'lengths' repr."""
    out: set = set()
    if isinstance(node, FuncCall) and node.name == "LENGTH":
        for arg in node.args:
            if isinstance(arg, ColumnRef):
                out.add(arg.name)
    for child in _children_of(node):
        out |= _length_columns_of(child)
    return out


def _columns_of(node: Node) -> set:
    if isinstance(node, ColumnRef):
        return {node.name}
    out: set = set()
    for child in _children_of(node):
        out |= _columns_of(child)
    return out


def _as_bool(v: _Val) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if v.is_bool:
        return v.values.astype(bool), v.valid
    return v.values != 0, v.valid


_CMP = ("=", "!=", "<", "<=", ">", ">=")
_CMP_FNS = {
    "=": jnp.equal,
    "!=": jnp.not_equal,
    "<": jnp.less,
    "<=": jnp.less_equal,
    ">": jnp.greater,
    ">=": jnp.greater_equal,
}


def _is_string_lane(v: "_Val") -> bool:
    """Column-backed (codes_of) OR synthetic (entries) string lane."""
    return v.codes_of is not None or v.entries is not None


def _lane_entries(ds, v: "_Val") -> "list[Optional[str]]":
    """The lane's dictionary as the EXPRESSION sees it: synthetic
    entries verbatim (transforms were folded in at construction);
    column-backed entries through the composed view."""
    if v.entries is not None:
        return list(v.entries)
    return [
        None if x is None else v.view(str(x))
        for x in ds.dictionary(v.codes_of)
    ]


def _dict_lookup(dataset: Dataset, column: str, value: str) -> int:
    dictionary = dataset.dictionary(column)
    matches = np.nonzero(dictionary == value)[0]
    return int(matches[0]) if len(matches) else -2  # -2: matches nothing


def _string_eq_lut(ds: Dataset, base: "_Val", literal: str) -> jnp.ndarray:
    """Per-code bool LUT for ``view(entry[i]) == literal`` — required
    when a transform applies (several raw entries may map to the same
    transformed value, so a single-code lookup can't represent it) and
    for synthetic lanes."""
    view = _lane_entries(ds, base)
    table = np.zeros(len(view) + 1, dtype=bool)
    for i, s in enumerate(view):
        if s is not None and s == literal:
            table[i] = True
    lut = jnp.asarray(table)
    idx = jnp.where(base.values < 0, len(view), base.values)
    return lut[jnp.clip(idx, 0, len(view))]


def _rank_table(
    views: "list[list[str]]", extra: "list[str]"
) -> "dict[str, int]":
    """Lexicographic rank of every distinct string across the given
    (already-transformed) dictionary views (+ literals): the shared
    value domain that makes codes from unrelated dictionaries — or
    transformed views of them — comparable."""
    values = set(extra)
    for view in views:
        values.update(v for v in view if v is not None)
    return {v: i for i, v in enumerate(sorted(values))}


def _dict_view(ds: Dataset, val: "_Val") -> "list[Optional[str]]":
    """The dictionary as the expression sees it: transform applied
    (synthetic lanes included)."""
    return _lane_entries(ds, val)


def _ranks_for(
    view: "list[Optional[str]]", rank: "dict[str, int]"
) -> np.ndarray:
    """int32 LUT code -> shared rank; one trailing slot (-1) for null
    codes so a single clipped gather covers every code."""
    out = np.full(len(view) + 1, -1, dtype=np.int32)
    for i, v in enumerate(view):
        if v is not None:
            out[i] = rank[v]
    return out


def _gather_ranks(lut: np.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    table = jnp.asarray(lut)
    idx = jnp.where(codes < 0, table.shape[0] - 1, codes)
    return table[jnp.clip(idx, 0, table.shape[0] - 1)]


def _shared_rank_luts(dataset: Dataset, a: "_Val", b: "_Val"):
    va, vb = _dict_view(dataset, a), _dict_view(dataset, b)
    rank = _rank_table(
        [[x for x in va if x is not None], [x for x in vb if x is not None]],
        [],
    )
    return _ranks_for(va, rank), _ranks_for(vb, rank)


def _rank_lut_with_literal(dataset: Dataset, base: "_Val", literal: str):
    view = _dict_view(dataset, base)
    rank = _rank_table([[x for x in view if x is not None]], [literal])
    return _ranks_for(view, rank), rank[literal]


_STRING_FNS = ("TRIM", "LTRIM", "RTRIM", "UPPER", "LOWER", "SUBSTR",
               "SUBSTRING")
_CAST_TYPES = (
    "INT", "INTEGER", "BIGINT", "LONG", "SMALLINT", "TINYINT",
    "FLOAT", "DOUBLE", "REAL",
)
_INT_CASTS = ("INT", "INTEGER", "BIGINT", "LONG", "SMALLINT", "TINYINT")
_STRING_CASTS = ("STRING", "VARCHAR", "TEXT")
# cap on a synthetic cross-product dictionary (multi-column CONCAT):
# host-side string materialization + per-code LUT sizes stay bounded
_CONCAT_DICT_BUDGET = 1 << 16
# JVM d2i-style saturation bounds per integral target (f64 lane: the
# i64 bounds round to the nearest representable double)
_INT_CAST_BOUNDS = {
    "INT": (-2147483648.0, 2147483647.0),
    "INTEGER": (-2147483648.0, 2147483647.0),
    "BIGINT": (-9.223372036854776e18, 9.223372036854776e18),
    "LONG": (-9.223372036854776e18, 9.223372036854776e18),
    "SMALLINT": (-32768.0, 32767.0),
    "TINYINT": (-128.0, 127.0),
}


def _static_int(node: Node, what: str) -> int:
    """A SUBSTR position/length argument must be a static integer."""
    if isinstance(node, UnaryOp) and node.op == "NEG":
        return -_static_int(node.operand, what)
    if isinstance(node, NumberLit) and float(node.value).is_integer():
        return int(node.value)
    raise PredicateParseError(f"{what} must be an integer literal")


def _substr(s: str, pos: int, length: Optional[int]) -> str:
    """Spark substring semantics: 1-based; pos 0 behaves like 1;
    negative pos counts from the end; negative length -> empty."""
    if pos > 0:
        start = pos - 1
    elif pos < 0:
        start = max(len(s) + pos, 0)
    else:
        start = 0
    if length is None:
        return s[start:]
    if length <= 0:
        return ""
    return s[start:start + length]


def _eval_string_fn(
    node: "FuncCall", batch: Dict[str, jnp.ndarray], ds: Dataset
) -> "_Val":
    """TRIM/LTRIM/RTRIM/UPPER/LOWER/SUBSTR compose a host-side
    transform over the operand's dictionary view; codes/validity pass
    through untouched (the device never sees strings)."""
    if node.name in ("SUBSTR", "SUBSTRING"):
        if len(node.args) not in (2, 3):
            raise PredicateParseError(
                f"{node.name} takes (string, pos[, length])"
            )
        base = _eval(node.args[0], batch, ds)
        pos = _static_int(node.args[1], f"{node.name} position")
        length = (
            _static_int(node.args[2], f"{node.name} length")
            if len(node.args) == 3
            else None
        )
        inner = base.view

        def transform(s: str, _pos=pos, _len=length, _inner=inner):
            return _substr(_inner(s), _pos, _len)

    else:
        if len(node.args) != 1:
            raise PredicateParseError(
                f"{node.name} takes exactly one argument"
            )
        base = _eval(node.args[0], batch, ds)
        inner = base.view
        fn = {
            "TRIM": str.strip,
            "LTRIM": str.lstrip,
            "RTRIM": str.rstrip,
            "UPPER": str.upper,
            "LOWER": str.lower,
        }[node.name]

        def transform(s: str, _fn=fn, _inner=inner):
            return _fn(_inner(s))

    if base.entries is not None:
        # synthetic lane: entries are final strings — apply the
        # function eagerly instead of composing a lazy transform
        return _Val(
            base.values,
            base.valid,
            entries=tuple(
                None if e is None else transform(e)
                for e in base.entries
            ),
        )
    if base.codes_of is None:
        raise PredicateParseError(
            f"{node.name} requires a string column operand"
        )
    return _Val(
        base.values, base.valid, codes_of=base.codes_of,
        transform=transform,
    )


def _units_per_day(arrow_type) -> int:
    """How many of the column's int64 epoch units make one UTC day
    (mirrors the values-repr cast in data.table.convert_basic_repr)."""
    import pyarrow as pa

    if pa.types.is_date32(arrow_type):
        return 1
    if pa.types.is_date64(arrow_type):
        return 86_400_000
    unit = getattr(arrow_type, "unit", "us")
    return 86_400 * {
        "s": 1, "ms": 1_000, "us": 1_000_000, "ns": 1_000_000_000
    }[unit]


def _epoch_days_of_literal(literal: str) -> int:
    import datetime as _dt

    d = _dt.datetime.fromisoformat(literal).date()
    return (d - _dt.date(1970, 1, 1)).days


def _date_literal_epoch(ds, column: str, literal: str) -> int:
    """'YYYY-MM-DD[ HH:MM:SS[.ffffff]]' -> the column's int64 epoch
    value (same cast the values repr uses: pc.cast(col, int64) keeps
    the storage unit, so converting the LITERAL through the same arrow
    type makes the numeric compare exact)."""
    import datetime as _dt

    import pyarrow as pa
    import pyarrow.compute as pc

    try:
        dt = _dt.datetime.fromisoformat(literal)
    except ValueError as exc:
        raise PredicateParseError(
            f"{literal!r} is not a date/timestamp literal "
            "(YYYY-MM-DD[ HH:MM:SS])"
        ) from exc
    arrow_type = ds.arrow_type(column)
    value = dt.date() if pa.types.is_date(arrow_type) else dt
    arr = pa.array([value], type=arrow_type)
    if pa.types.is_date32(arrow_type):
        # Arrow has no date32->int64 kernel; hop through int32 — the
        # SAME two-step the values repr uses (convert_basic_repr), so
        # literal and column land in identical units (days)
        arr = pc.cast(arr, pa.int32())
    return int(pc.cast(arr, pa.int64())[0].as_py())


def _eval_lane(node: Node, column: str, batch) -> _Val:
    """A lane-readable node over its column's bool plane, with the
    same SQL null semantics as the dictionary path."""
    truth = batch[f"{column}::{_lane_plane(node)}"]
    valid = batch[f"{column}::mask"]
    if isinstance(node, InList) and any(
        isinstance(i, NullLit) for i in node.items
    ):
        valid = valid & truth  # x IN (..., NULL): non-matches are NULL
    negate = node.op == "!=" if isinstance(node, BinOp) else node.negate
    if negate:
        truth = ~truth
    return _Val(truth, valid, is_bool=True)


def _eval_stringish(node: Node, batch, ds):
    """Branch evaluation for CASE/COALESCE, where a bare string
    literal (or NULL) is a legal RESULT: literals become ('lit', s)
    markers instead of erroring, everything else evaluates."""
    if isinstance(node, StringLit):
        return ("lit", node.value)
    if isinstance(node, NullLit):
        return ("null",)
    return _eval(node, batch, ds)


def _any_stringish(branches) -> bool:
    return any(
        (isinstance(b, tuple) and b[0] == "lit")
        or (isinstance(b, _Val) and _is_string_lane(b))
        for b in branches
    )


def _string_union(ds, branches):
    """Union synthetic dictionary over string branches + each branch
    as (union codes, valid). Branches: ('lit', s) | ('null',) | string
    _Val lanes (homogeneity is enforced at plan time; a numeric _Val
    here means the checker missed a case — refuse loudly)."""
    values: set = set()
    views: List[Optional[List[Optional[str]]]] = []
    for b in branches:
        if isinstance(b, tuple):
            views.append(None)
            if b[0] == "lit":
                values.add(b[1])
        elif _is_string_lane(b):
            view = _lane_entries(ds, b)
            views.append(view)
            values.update(v for v in view if v is not None)
        else:
            raise PredicateParseError(
                "CASE/COALESCE branches mix string and non-string "
                "results"
            )
    union = sorted(values)
    index = {v: i for i, v in enumerate(union)}
    out = []
    for b, view in zip(branches, views):
        if isinstance(b, tuple):
            if b[0] == "lit":
                out.append(
                    (jnp.int32(index[b[1]]), jnp.asarray(True))
                )
            else:
                out.append((jnp.int32(0), jnp.asarray(False)))
        else:
            lut = np.zeros(len(view) + 1, dtype=np.int32)
            for i, v in enumerate(view):
                if v is not None:
                    lut[i] = index[v]
            table = jnp.asarray(lut)
            idx = jnp.clip(
                jnp.where(b.values < 0, len(view), b.values),
                0,
                len(view),
            )
            out.append((table[idx], b.valid))
    return union, out


def _eval(node: Node, batch: Dict[str, jnp.ndarray], ds: Dataset) -> _Val:
    lanes = getattr(ds, "lanes", ())
    if lanes:
        column = _lane_operand(node, lanes)
        if column is not None:
            return _eval_lane(node, column, batch)
        if isinstance(node, ColumnRef) and node.name in lanes:
            # only IS NULL and LENGTH reach here (_dictionary_reads)
            mask = batch[f"{node.name}::mask"]
            return _Val(jnp.zeros(mask.shape, jnp.int32), mask)
    if isinstance(node, ColumnRef):
        kind = ds.schema.kind_of(node.name)
        mask = batch[f"{node.name}::mask"]
        if kind == Kind.STRING:
            return _Val(batch[f"{node.name}::codes"], mask, codes_of=node.name)
        vals = batch[f"{node.name}::values"]
        is_ts = kind == Kind.TIMESTAMP
        return _Val(
            vals,
            mask,
            is_bool=kind == Kind.BOOLEAN,
            ts_col=node.name if is_ts else None,
            ts_per_day=(
                _units_per_day(ds.arrow_type(node.name)) if is_ts else None
            ),
        )
    if isinstance(node, NumberLit):
        return _Val(jnp.asarray(node.value), jnp.asarray(True))
    if isinstance(node, BoolLit):
        return _Val(jnp.asarray(node.value), jnp.asarray(True), is_bool=True)
    if isinstance(node, NullLit):
        return _Val(jnp.asarray(0.0), jnp.asarray(False))
    if isinstance(node, StringLit):
        # bare string literal only makes sense inside comparisons, which
        # special-case it; standing alone it is an error
        raise PredicateParseError(
            f"string literal {node.value!r} outside comparison"
        )
    if isinstance(node, UnaryOp):
        if node.op == "NEG":
            v = _eval(node.operand, batch, ds)
            return _Val(-v.values, v.valid)
        truth, valid = _as_bool(_eval(node.operand, batch, ds))
        return _Val(~truth, valid, is_bool=True)
    if isinstance(node, IsNull):
        v = _eval(node.operand, batch, ds)
        res = v.valid if node.negate else ~v.valid
        return _Val(res, jnp.ones_like(res, dtype=bool), is_bool=True)
    if isinstance(node, Between):
        return _eval(
            BinOp(
                "AND",
                BinOp(">=", node.operand, node.low),
                BinOp("<=", node.operand, node.high),
            ),
            batch,
            ds,
        )
    if isinstance(node, Cast):
        v = _eval(node.operand, batch, ds)
        if node.type_name in _STRING_CASTS:
            if _is_string_lane(v):
                return v  # identity (transform/entries preserved)
            if v.is_bool:
                # Spark: cast(true AS STRING) = 'true'
                return _Val(
                    v.values.astype(jnp.int32),
                    v.valid,
                    entries=("false", "true"),
                )
            raise PredicateParseError(
                "CAST to STRING supports string and boolean operands "
                "only (numeric/timestamp formatting is not supported)"
            )
        integral = node.type_name in _INT_CASTS
        if v.ts_per_day is not None:
            # Spark: cast(timestamp AS BIGINT/DOUBLE) = epoch SECONDS
            # (floor for integral targets, then the same saturation
            # bounds every integral cast applies); date operands are
            # refused at plan time like Spark's analyzer does
            upd = v.ts_per_day // 86_400  # units per second
            raw = v.values.astype(jnp.int64)
            if integral:
                lo, hi = _INT_CAST_BOUNDS[node.type_name]
                vals = jnp.clip(
                    jnp.floor_divide(raw, jnp.int64(upd)).astype(
                        jnp.float64
                    ),
                    lo,
                    hi,
                )
            else:
                vals = raw.astype(jnp.float64) / float(upd)
            return _Val(vals, v.valid)
        if _is_string_lane(v):
            # string lane: parse each dictionary entry ONCE
            # (Spark cast semantics: unparseable -> NULL). Validity
            # lives in its OWN table — overloading NaN as the invalid
            # sentinel would misreport an entry 'NaN' (which Spark
            # casts to the VALUE NaN) as NULL (r4 advisory).
            view = _lane_entries(ds, v)
            table = np.zeros(len(view) + 1)
            ok = np.zeros(len(view) + 1, dtype=bool)
            for i, s in enumerate(view):
                if s is not None:
                    text = s.strip()
                    if "_" in text:  # Python-only numeric syntax
                        continue  # ('1_0'); Spark casts it to NULL
                    try:
                        table[i] = float(text)
                        ok[i] = True
                    except ValueError:
                        pass
            lut = jnp.asarray(table)
            ok_lut = jnp.asarray(ok)
            idx = jnp.clip(
                jnp.where(v.values < 0, len(view), v.values),
                0,
                len(view),
            )
            vals = lut[idx]
            valid = v.valid & ok_lut[idx]
            vals = jnp.where(valid, vals, 0.0)
            if integral:
                # a string with no finite numeric value has no
                # integral parse -> NULL (Spark's string-to-int cast
                # rejects 'NaN'/'Infinity'; review finding on the r4
                # validity-table fix); finite parses saturate at the
                # target bounds like the numeric-source path — one
                # consistent JVM-d2i cast model (review finding r5)
                finite = jnp.isfinite(vals)
                valid = valid & finite
                lo, hi = _INT_CAST_BOUNDS[node.type_name]
                vals = jnp.clip(
                    jnp.trunc(jnp.where(finite, vals, 0.0)), lo, hi
                )
            return _Val(vals, valid)
        vals = v.values.astype(jnp.float64)
        valid = v.valid
        if integral:
            # numeric source follows JVM double-to-int conversion like
            # non-ANSI Spark: truncate toward zero, SATURATE at the
            # target bounds, NaN -> 0 (NOT NULL — review finding)
            lo, hi = _INT_CAST_BOUNDS[node.type_name]
            vals = jnp.clip(jnp.trunc(vals), lo, hi)
            vals = jnp.where(jnp.isnan(vals), 0.0, vals)
        return _Val(vals, valid)
    if isinstance(node, CaseWhen):
        # SQL: first branch whose condition is TRUE wins (NULL
        # conditions skip); no match and no ELSE -> NULL. Folded in
        # reverse so earlier branches override later ones. String-
        # valued results (homogeneous, enforced at plan time) fold the
        # same way over codes into a UNION synthetic dictionary.
        branches = [
            (cond, _eval_stringish(r, batch, ds))
            for cond, r in node.whens
        ]
        else_b = (
            _eval_stringish(node.else_, batch, ds)
            if node.else_ is not None
            else ("null",)
        )
        if _any_stringish([b for _, b in branches] + [else_b]):
            union, codes_of_branch = _string_union(
                ds, [b for _, b in branches] + [else_b]
            )
            vals, valid = codes_of_branch[-1]
            for (cond, _), (bc, bv) in zip(
                reversed(branches), reversed(codes_of_branch[:-1])
            ):
                ct, cv = _as_bool(_eval(cond, batch, ds))
                hit = ct & cv
                vals = jnp.where(hit, bc, vals)
                valid = jnp.where(hit, bv, valid)
            return _Val(vals, valid, entries=tuple(union))

        # numeric fold, REUSING the already-evaluated branches (a
        # ('null',) marker is an invalid slot); branch values coerce
        # to f64 (SQL promotes mixed numeric/bool CASE branches)
        def as_num(b):
            if isinstance(b, tuple):  # ('null',)
                return jnp.asarray(0.0), jnp.asarray(False)
            return jnp.asarray(b.values, dtype=jnp.float64), b.valid

        vals, valid = as_num(else_b)
        for (cond, _), b in zip(reversed(node.whens), reversed(branches)):
            ct, cv = _as_bool(_eval(cond, batch, ds))
            hit = ct & cv
            bv, bok = as_num(b[1])
            vals = jnp.where(hit, bv, vals)
            valid = jnp.where(hit, bok, valid)
        return _Val(vals, valid)
    if isinstance(node, InList):
        base = _eval(node.operand, batch, ds)
        truth = jnp.zeros_like(base.values, dtype=bool)
        has_null_item = False
        for item in node.items:
            if isinstance(item, NullLit):
                # SQL: x IN (..., NULL) is TRUE on a match, else NULL
                has_null_item = True
            elif isinstance(item, StringLit):
                if not _is_string_lane(base):
                    raise PredicateParseError(
                        "IN with string literals requires a string column"
                    )
                if base.transform is not None or base.entries is not None:
                    truth = truth | _string_eq_lut(ds, base, item.value)
                else:
                    code = _dict_lookup(ds, base.codes_of, item.value)
                    truth = truth | (base.values == code)
            else:
                rhs = _eval(item, batch, ds)
                truth = truth | ((base.values == rhs.values) & rhs.valid)
        valid = base.valid
        if has_null_item:
            valid = valid & truth  # non-matches become NULL
        if node.negate:
            truth = ~truth
        return _Val(truth, valid, is_bool=True)
    if isinstance(node, Like):
        base = _eval(node.operand, batch, ds)
        if not _is_string_lane(base):
            raise PredicateParseError("LIKE requires a string column")
        view = _lane_entries(ds, base)
        pattern = (
            node.pattern if node.regex else _sql_like_to_regex(node.pattern)
        )
        re.compile(pattern)  # Python's verdict on a bad pattern
        import pyarrow as pa

        from deequ_tpu.data.strings import regex_plane

        table = np.zeros(len(view) + 1, dtype=bool)
        if view:
            table[: len(view)] = regex_plane(
                pa.array(view, type=pa.large_string()), pattern
            )
        lut = jnp.asarray(table)
        truth = lut[jnp.clip(base.values, -1, len(view) - 1)]
        truth = jnp.where(base.values < 0, False, truth)
        if node.negate:
            truth = ~truth
        return _Val(truth, base.valid, is_bool=True)
    if isinstance(node, FuncCall):
        if node.name == "ABS" and len(node.args) == 1:
            v = _eval(node.args[0], batch, ds)
            return _Val(jnp.abs(v.values), v.valid)
        if node.name == "COALESCE":
            if not node.args:
                raise PredicateParseError("COALESCE needs arguments")
            branches = [
                _eval_stringish(a, batch, ds) for a in node.args
            ]
            if _any_stringish(branches):
                union, pairs = _string_union(ds, branches)
                vals, valid = pairs[0]
                for code, ok in pairs[1:]:
                    vals = jnp.where(valid, vals, code)
                    valid = valid | ok
                return _Val(vals, valid, entries=tuple(union))
            parts = [
                b if isinstance(b, _Val)
                else _Val(jnp.asarray(0.0), jnp.asarray(False))
                for b in branches
            ]
            vals = parts[0].values
            valid = parts[0].valid
            for p in parts[1:]:
                vals = jnp.where(valid, vals, p.values)
                valid = valid | p.valid
            return _Val(
                vals, valid, is_bool=all(p.is_bool for p in parts)
            )
        if node.name == "LENGTH" and len(node.args) == 1:
            arg = node.args[0]
            if isinstance(arg, ColumnRef):
                mask = batch[f"{arg.name}::mask"]
                return _Val(batch[f"{arg.name}::lengths"], mask)
            # LENGTH over a transformed string expression: per-code
            # i32 LUT of len(view(dict[i])), gathered by code
            v = _eval(arg, batch, ds)
            if not _is_string_lane(v):
                raise PredicateParseError(
                    "LENGTH expects a string column or string function"
                )
            view = _lane_entries(ds, v)
            table = np.zeros(len(view) + 1, dtype=np.int32)
            for i, s in enumerate(view):
                if s is not None:
                    table[i] = len(s)
            lut = jnp.asarray(table)
            idx = jnp.where(v.values < 0, len(view), v.values)
            return _Val(
                lut[jnp.clip(idx, 0, len(view))], v.valid
            )
        if node.name in ("DATE_ADD", "DATE_SUB"):
            v = _eval(node.args[0], batch, ds)
            if v.ts_per_day is None:
                raise PredicateParseError(
                    f"{node.name} requires a timestamp/date column"
                )
            n_days = _static_int(node.args[1], f"{node.name} day count")
            if node.name == "DATE_SUB":
                n_days = -n_days
            # Spark's date_add casts to DATE first: the result is
            # DAY-valued (time-of-day truncates), so equality against
            # date literals behaves like Spark's
            days = jnp.floor_divide(
                v.values.astype(jnp.int64), jnp.int64(v.ts_per_day)
            )
            return _Val(
                days + jnp.int64(n_days), v.valid, ts_per_day=1
            )
        if node.name == "DATEDIFF":
            def days_of(arg):
                if isinstance(arg, StringLit):
                    return (
                        jnp.int64(_epoch_days_of_literal(arg.value)),
                        jnp.asarray(True),
                    )
                v = _eval(arg, batch, ds)
                if v.ts_per_day is None:
                    raise PredicateParseError(
                        "DATEDIFF arguments must be timestamp columns "
                        "or date literals"
                    )
                return (
                    jnp.floor_divide(
                        v.values.astype(jnp.int64),
                        jnp.int64(v.ts_per_day),
                    ),
                    v.valid,
                )

            end_days, end_valid = days_of(node.args[0])
            start_days, start_valid = days_of(node.args[1])
            return _Val(end_days - start_days, end_valid & start_valid)
        if node.name == "CONCAT":
            lanes: List[Tuple[str, object]] = []
            for a in node.args:
                if isinstance(a, StringLit):
                    lanes.append(("lit", a.value))
                else:
                    v = _eval(a, batch, ds)
                    if not _is_string_lane(v):
                        raise PredicateParseError(
                            "CONCAT arguments must be strings"
                        )
                    lanes.append(("lane", v))
            n_lanes = sum(1 for k, _ in lanes if k == "lane")
            if n_lanes == 0:
                raise PredicateParseError(
                    "CONCAT of only literals is constant"
                )
            if n_lanes == 1 and all(
                k == "lit" or v.codes_of is not None for k, v in lanes
            ):
                # one COLUMN-BACKED lane: literals fold into its lazy
                # transform — no synthetic dictionary needed
                col_val = next(v for k, v in lanes if k == "lane")
                inner = col_val.view
                parts = tuple(
                    v if k == "lit" else None for k, v in lanes
                )

                def transform(s, _parts=parts, _inner=inner):
                    return "".join(
                        _inner(s) if p is None else p for p in _parts
                    )

                return _Val(
                    col_val.values,
                    col_val.valid,
                    codes_of=col_val.codes_of,
                    transform=transform,
                )
            # MULTI-column (or synthetic-lane) CONCAT: fold lanes into
            # a cross-product synthetic dictionary (size bounded at
            # plan time by _check_plan_budgets); row code = left_code
            # * |right| + right_code; NULL if ANY operand is null
            # (Spark's concat)
            acc_entries: Optional[List[Optional[str]]] = None
            acc_codes = None
            acc_valid = None
            pending = ""
            for k, v in lanes:
                if k == "lit":
                    if acc_entries is None:
                        pending += v
                    else:
                        acc_entries = [
                            None if e is None else e + v
                            for e in acc_entries
                        ]
                    continue
                view = _lane_entries(ds, v)
                L = len(view)
                codes = jnp.clip(
                    jnp.where(v.values < 0, 0, v.values), 0, L - 1
                ).astype(jnp.int32)
                if acc_entries is None:
                    acc_entries = [
                        None if e is None else pending + e
                        for e in view
                    ]
                    pending = ""
                    acc_codes = codes
                    acc_valid = v.valid
                else:
                    acc_entries = [
                        (
                            None
                            if ea is None or eb is None
                            else ea + eb
                        )
                        for ea in acc_entries
                        for eb in view
                    ]
                    acc_codes = acc_codes * jnp.int32(L) + codes
                    acc_valid = acc_valid & v.valid
            return _Val(
                acc_codes, acc_valid, entries=tuple(acc_entries)
            )
        if node.name in _STRING_FNS:
            return _eval_string_fn(node, batch, ds)
        raise PredicateParseError(f"unsupported function {node.name}")
    if isinstance(node, BinOp):
        if node.op in ("AND", "OR"):
            lt, lv = _as_bool(_eval(node.left, batch, ds))
            rt, rv = _as_bool(_eval(node.right, batch, ds))
            if node.op == "AND":
                truth = lt & rt
                # SQL 3VL: FALSE AND NULL = FALSE (valid)
                valid = (lv & rv) | (lv & ~lt) | (rv & ~rt)
            else:
                truth = lt | rt
                # TRUE OR NULL = TRUE (valid)
                valid = (lv & rv) | (lv & lt) | (rv & rt)
            return _Val(truth, valid, is_bool=True)
        # comparisons involving string literals: =/!= compare raw codes
        # (one O(n) dictionary lookup, scalar compare); orderings need
        # lexicographic ranks — codes are in order of appearance
        if node.op in _CMP and (
            isinstance(node.left, StringLit) or isinstance(node.right, StringLit)
        ):
            lit_on_right = isinstance(node.right, StringLit)
            col_node, lit = (
                (node.left, node.right)
                if lit_on_right
                else (node.right, node.left)
            )
            base = _eval(col_node, batch, ds)
            if base.ts_per_day is not None:
                # timestamp/date lane vs date literal: the literal
                # converts to the lane's epoch unit at trace time (via
                # the exact Arrow cast for raw columns; as UTC days
                # for day-valued DATE_ADD results); the device compare
                # stays numeric
                if base.ts_col is not None:
                    epoch = _date_literal_epoch(
                        ds, base.ts_col, lit.value
                    )
                else:
                    epoch = _epoch_days_of_literal(lit.value)
                lv, rv = (
                    (base.values, epoch)
                    if lit_on_right
                    else (epoch, base.values)
                )
                return _Val(
                    _CMP_FNS[node.op](lv, rv), base.valid, is_bool=True
                )
            if not _is_string_lane(base):
                raise PredicateParseError(
                    "string comparison requires a string column"
                )
            if node.op in ("=", "!="):
                if base.transform is not None or base.entries is not None:
                    truth = _string_eq_lut(ds, base, lit.value)
                else:
                    code = _dict_lookup(ds, base.codes_of, lit.value)
                    truth = base.values == code
                if node.op == "!=":
                    truth = ~truth
                return _Val(truth, base.valid, is_bool=True)
            ranks, lit_rank = _rank_lut_with_literal(
                ds, base, lit.value
            )
            col_ranks = _gather_ranks(ranks, base.values)
            lv, rv = (
                (col_ranks, lit_rank) if lit_on_right else (lit_rank, col_ranks)
            )
            return _Val(_CMP_FNS[node.op](lv, rv), base.valid, is_bool=True)
        lhs = _eval(node.left, batch, ds)
        rhs = _eval(node.right, batch, ds)
        valid = lhs.valid & rhs.valid
        lv, rv = lhs.values, rhs.values
        if (
            node.op in _CMP
            and lhs.ts_per_day is not None
            and rhs.ts_per_day is not None
            and lhs.ts_per_day != rhs.ts_per_day
        ):
            # mixed-unit timestamp lanes (timestamp[us] vs date32, or
            # a day-valued DATE_ADD vs a raw column): scale the coarser
            # side up to the finer unit so epochs compare as instants
            # (comparing raw epochs across units would be silently
            # wrong — r4 review finding)
            if lhs.ts_per_day < rhs.ts_per_day:
                lv = lv.astype(jnp.int64) * jnp.int64(
                    rhs.ts_per_day // lhs.ts_per_day
                )
            else:
                rv = rv.astype(jnp.int64) * jnp.int64(
                    lhs.ts_per_day // rhs.ts_per_day
                )
        if node.op in _CMP:
            if _is_string_lane(lhs) and _is_string_lane(rhs):
                # two string columns: dictionary codes come from
                # UNRELATED dictionaries (and even one dictionary is in
                # order of appearance, not sorted) — remap both sides to
                # ranks in a shared sorted value domain so =/!= and
                # lexicographic ordering are exact
                lut_l, lut_r = _shared_rank_luts(ds, lhs, rhs)
                lv = _gather_ranks(lut_l, lv)
                rv = _gather_ranks(lut_r, rv)
            elif _is_string_lane(lhs) != _is_string_lane(rhs):
                raise PredicateParseError(
                    "cannot compare a string column with a non-string "
                    "operand (dictionary codes are not values)"
                )
            return _Val(_CMP_FNS[node.op](lv, rv), valid, is_bool=True)
        if _is_string_lane(lhs) or _is_string_lane(rhs):
            raise PredicateParseError(
                f"arithmetic {node.op!r} is undefined for string columns"
            )
        if node.op == "+":
            return _Val(lv + rv, valid)
        if node.op == "-":
            return _Val(lv - rv, valid)
        if node.op == "*":
            return _Val(lv * rv, valid)
        if node.op == "/":
            denom_ok = rv != 0
            safe = jnp.where(denom_ok, rv, 1)
            return _Val(lv / safe, valid & denom_ok)
        if node.op == "%":
            denom_ok = rv != 0
            safe = jnp.where(denom_ok, rv, 1)
            return _Val(lv % safe, valid & denom_ok)
    raise PredicateParseError(f"cannot evaluate node {node!r}")
