"""Trace-hazard analyzer: host semantics inside traced scopes.

Code that runs under a ``jax.jit`` / ``shard_map`` / ``lax.scan``
trace must stay in graph land: a ``float()``/``int()``/``bool()``/
``.item()`` coercion forces a device sync (ConcretizationTypeError at
best, a silent per-batch device round trip at worst), an ``np.*`` call
on a traced value falls out of the graph, and a Python ``if``/
``while`` on a traced operand raises at trace time. This analyzer
infers the traced-function set per module and flags those constructs
inside it.

Traced-set inference (flow-insensitive, same-module):

1. seeds — functions decorated with / passed to a trace entry point
   (``jit``, ``shard_map``, ``pallas_call``, ``vmap``, ``pmap``,
   ``lax.scan``/``fori_loop``/``while_loop``/``cond``/``switch``,
   ``custom_vjp``/``custom_jvp``);
2. nesting — a ``def`` inside a traced function is traced;
3. closure — a function a traced function calls by bare name (or
   ``self.<method>``) in the same module is traced;
4. usage heuristic — a function whose body calls ``jnp.*``/``lax.*``/
   ``pl.*`` is treated as traced even when the trace entry point is a
   dynamic dispatch the call graph can't see (the op-protocol
   ``apply_update`` methods jitted via the fused-scan step builder).

The heuristic deliberately over-approximates: host-side glue that
builds arrays with ``jnp`` gets marked, and its deliberate syncs take
a ``# lint-ok: trace-hazard`` waiver saying WHY the value is host-side
there (post-``device_get`` fold, one-time probe, metadata-only).
``np.*`` metadata accessors (dtype/shape arithmetic) are allowed.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.staticcheck.core import (
    Analyzer,
    Finding,
    SourceFile,
    dotted_name,
    register,
)

SCOPE_PREFIXES = ("deequ_tpu/engine/", "deequ_tpu/sketches/")

# call targets whose function arguments (or decorated function) run
# under a trace
TRACE_ENTRY_TAILS = frozenset(
    {
        "jit",
        "shard_map",
        "pallas_call",
        "vmap",
        "pmap",
        "scan",
        "fori_loop",
        "while_loop",
        "cond",
        "switch",
        "custom_vjp",
        "custom_jvp",
        "checkpoint",
        "remat",
    }
)

TRACED_MODULE_HEADS = frozenset({"jnp", "lax", "pl", "pltpu"})

# np.* attributes that are metadata/static-shape arithmetic, legal in
# a traced function (they never touch traced values)
NP_ALLOWED = frozenset(
    {
        "dtype",
        "iinfo",
        "finfo",
        "float16",
        "float32",
        "float64",
        "int8",
        "int16",
        "int32",
        "int64",
        "uint8",
        "uint16",
        "uint32",
        "uint64",
        "bool_",
        "ceil",
        "floor",
        "log2",
        "log",
        "sqrt",
        "prod",
        "ndarray",
        "generic",
        "pi",
        "inf",
        "nan",
        "e",
        "errstate",
    }
)

COERCIONS = frozenset({"float", "int", "bool", "complex"})


def _func_key(stack: Tuple[str, ...]) -> str:
    return ".".join(stack)


class _FunctionIndex(ast.NodeVisitor):
    """Qualified-name index of every function in a module, plus the
    raw data the traced-set inference needs: decorators, call edges,
    and whether the body touches jnp/lax/pl."""

    def __init__(self) -> None:
        self.functions: Dict[str, ast.AST] = {}
        self.class_of: Dict[str, Optional[str]] = {}
        self.decorators: Dict[str, List[str]] = {}
        self.calls: Dict[str, Set[str]] = {}
        self.uses_traced_module: Dict[str, bool] = {}
        self._stack: List[str] = []
        self._class_stack: List[str] = []

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()
        self._class_stack.pop()

    def _visit_function(self, node) -> None:
        self._stack.append(node.name)
        key = _func_key(tuple(self._stack))
        self.functions[key] = node
        self.class_of[key] = (
            self._class_stack[-1] if self._class_stack else None
        )
        self.decorators[key] = [
            d for d in (
                dotted_name(dec.func if isinstance(dec, ast.Call) else dec)
                for dec in node.decorator_list
            ) if d
        ]
        self.calls.setdefault(key, set())
        self.uses_traced_module.setdefault(key, False)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_Call(self, node: ast.Call) -> None:
        if self._stack:
            key = _func_key(tuple(self._stack))
            if key in self.calls:
                name = dotted_name(node.func)
                if name:
                    self.calls[key].add(name)
                    head = name.split(".")[0]
                    if head in TRACED_MODULE_HEADS:
                        self.uses_traced_module[key] = True
        self.generic_visit(node)


def _entry_point_args(tree: ast.AST) -> Set[str]:
    """Bare function names passed to a trace entry point anywhere in
    the module (``lax.scan(step, ...)`` marks ``step``)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if not name or name.split(".")[-1] not in TRACE_ENTRY_TAILS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                out.add(arg.id)
    return out


def _traced_functions(index: _FunctionIndex, tree: ast.AST) -> Set[str]:
    traced: Set[str] = set()
    entry_args = _entry_point_args(tree)
    for key, _node in index.functions.items():
        short = key.split(".")[-1]
        if short in entry_args:
            traced.add(key)
        if any(
            d.split(".")[-1] in TRACE_ENTRY_TAILS
            for d in index.decorators[key]
        ):
            traced.add(key)
        if index.uses_traced_module[key]:
            traced.add(key)
    # nesting: inner defs of traced functions are traced
    for key in list(index.functions):
        for t in list(traced):
            if key.startswith(t + ".") :
                traced.add(key)
    # closure: propagate through same-module calls until fixed point
    short_to_keys: Dict[str, List[str]] = {}
    for key in index.functions:
        short_to_keys.setdefault(key.split(".")[-1], []).append(key)
    changed = True
    while changed:
        changed = False
        for key in traced.copy():
            for callee in index.calls.get(key, ()):
                tail = callee.split(".")[-1]
                head = callee.split(".")[0]
                if head not in ("self", "cls") and "." in callee:
                    continue  # external module call
                for ckey in short_to_keys.get(tail, ()):
                    # self.<m> resolves only within the same class
                    if head in ("self", "cls") and index.class_of[
                        ckey
                    ] != index.class_of.get(key):
                        continue
                    if ckey not in traced:
                        traced.add(ckey)
                        changed = True
    return traced


#: jnp/lax functions that compute dtype METADATA, static under
#: tracing — a Python `if` on them is the sanctioned way to dispatch
#: (``if jnp.issubdtype(x.dtype, jnp.floating):``)
STATIC_JNP_TAILS = frozenset(
    {"issubdtype", "isdtype", "result_type", "promote_types", "dtype"}
)


def _test_is_traced_operand(test: ast.AST) -> bool:
    """Heuristic: the if/while test itself manufactures or reduces a
    traced value (jnp call, .any()/.all()/.item() reduction)."""
    for node in ast.walk(test):
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if (
                name
                and name.split(".")[0] in TRACED_MODULE_HEADS
                and name.split(".")[-1] not in STATIC_JNP_TAILS
            ):
                return True
            if isinstance(node.func, ast.Attribute) and node.func.attr in (
                "any",
                "all",
                "item",
            ):
                return True
    return False


#: calls whose results are host-side static values even in a trace
STATIC_CALLS = frozenset({"len", "range", "min", "max", "abs", "round"})
#: metadata attributes that are static under tracing
STATIC_ATTRS = frozenset({"shape", "ndim", "size", "dtype", "itemsize"})


def _is_static_arg(node: ast.AST) -> bool:
    """True when a coercion argument is demonstrably static under
    tracing: built from literals, bare names (could be Python scalars
    — the analyzer gives the benefit of the doubt ONLY when no array
    operation appears), shape/dtype metadata, and len()/math.* calls.
    Any jnp/lax call, ``.sum()``-style reduction, or subscript of a
    call result makes it non-static."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            name = dotted_name(sub.func)
            if name is None:
                return False
            tail = name.split(".")[-1]
            head = name.split(".")[0]
            if name in STATIC_CALLS or head == "math":
                continue
            if isinstance(sub.func, ast.Attribute) and tail in STATIC_ATTRS:
                continue
            return False
        if isinstance(sub, ast.Attribute):
            continue
        if isinstance(
            sub,
            (
                ast.Constant, ast.Name, ast.BinOp, ast.UnaryOp, ast.Compare,
                ast.BoolOp, ast.IfExp, ast.Subscript, ast.Tuple, ast.List,
                ast.Load, ast.operator, ast.unaryop, ast.cmpop, ast.boolop,
                ast.expr_context, ast.Slice, ast.keyword, ast.Starred,
            ),
        ):
            continue
        return False
    return True


def _walk_skipping_nested_defs(func: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested ``def``s —
    those are traced entries of their own and report separately."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


class TraceHazardAnalyzer(Analyzer):
    name = "trace"
    rules = ("trace-hazard",)
    description = (
        "host-sync coercions, np.* calls, and Python control flow on "
        "traced values inside jit/shard_map/scan scopes"
    )

    def analyze(
        self, files: Sequence[SourceFile], root: str
    ) -> Iterable[Finding]:
        for sf in files:
            if sf.tree is None or not any(
                sf.rel.startswith(p) for p in SCOPE_PREFIXES
            ):
                continue
            index = _FunctionIndex()
            index.visit(sf.tree)
            traced = _traced_functions(index, sf.tree)
            for key in sorted(traced):
                yield from self._hazards_in(sf, key, index.functions[key])

    def _hazards_in(
        self, sf: SourceFile, key: str, func: ast.AST
    ) -> Iterable[Finding]:
        short = key.split(".")[-1]
        for node in _walk_skipping_nested_defs(func):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if (
                    name in COERCIONS
                    and node.args
                    and not all(
                        isinstance(a, ast.Constant) for a in node.args
                    )
                    and not all(_is_static_arg(a) for a in node.args)
                ):
                    yield Finding(
                        rule="trace-hazard",
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            f"host coercion {name}(...) inside traced "
                            f"scope '{short}' forces a device sync"
                        ),
                        symbol=name,
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    yield Finding(
                        rule="trace-hazard",
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            f".item() inside traced scope '{short}' "
                            "forces a device sync"
                        ),
                        symbol="item",
                    )
                elif (
                    name
                    and name.startswith("np.")
                    and name.split(".")[1] not in NP_ALLOWED
                ):
                    yield Finding(
                        rule="trace-hazard",
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            f"{name}(...) inside traced scope '{short}' "
                            "falls out of the graph (use jnp)"
                        ),
                        symbol=name,
                    )
            elif isinstance(node, (ast.If, ast.While)):
                if _test_is_traced_operand(node.test):
                    kw = "if" if isinstance(node, ast.If) else "while"
                    yield Finding(
                        rule="trace-hazard",
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            f"Python '{kw}' on a traced operand inside "
                            f"'{short}' — use lax.cond/jnp.where"
                        ),
                        symbol=kw,
                    )


register(TraceHazardAnalyzer())
