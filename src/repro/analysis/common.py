"""Shared AST infrastructure for the ``ndpplint`` rules.

Every rule operates on a :class:`Module` — a parsed source file plus the
derived facts most rules need:

  * an *alias table* mapping local names to fully-qualified dotted paths
    (``jnp`` → ``jax.numpy``, ``split`` → ``jax.random.split``), so rules
    match semantics (``jax.numpy.arange``) rather than spelling;
  * a *parent map* (AST child → parent), for context checks like "is this
    name only used through ``.shape``";
  * the set of *traced regions*: function/lambda nodes whose bodies run
    under a JAX trace (``@jax.jit``-decorated, wrapped by ``jax.jit(f)``,
    or passed to ``lax.scan`` / ``while_loop`` / ``shard_map`` /
    ``pallas_call`` / ... — including every ``def`` nested inside one).

The ``Module.kind`` classification drives rule scoping: ``"test"`` files
are exempt from most rules, ``"fixture"`` files (``tests/lint_fixtures/``)
are in scope for *every* rule so the analyzer's own test corpus works.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

# Wrappers whose function-valued argument executes under a JAX trace.
TRACING_WRAPPERS = {
    "jax.jit",
    "jax.pmap",
    "jax.vmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.custom_jvp",
    "jax.custom_vjp",
    "jax.lax.scan",
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.map",
    "jax.lax.associative_scan",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
}

# Attribute accesses through which a traced value yields *static* (Python)
# information — branching on these never leaks a tracer.
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "weak_type"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str          # posix relpath as given to the runner
    line: int
    col: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclasses.dataclass
class TracedDef:
    """A function/lambda node whose body runs under a JAX trace."""

    node: ast.AST                      # FunctionDef | AsyncFunctionDef | Lambda
    static_params: Set[str]            # params known static (static_argnames /
    #                                    keyword-bound pallas kernel params)


class Module:
    """A parsed source file plus derived lookup tables (see module doc)."""

    def __init__(self, path: Path, rel: str, text: str, kind: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.kind = kind
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        self.aliases = _build_aliases(self.tree)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.traced: List[TracedDef] = _find_traced(self)
        self._traced_nodes = {t.node for t in self.traced}

    # ------------------------------------------------------------- helpers
    def dotted(self, node: ast.AST) -> Optional[str]:
        """Fully-qualified dotted path of a Name/Attribute expression, or
        None when the base name is not an import-derived alias."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        base = self.aliases.get(cur.id)
        if base is None:
            return None
        parts.append(base)
        return ".".join(reversed(parts))

    def call_dotted(self, call: ast.Call) -> Optional[str]:
        return self.dotted(call.func)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def in_traced(self, node: ast.AST) -> bool:
        """Is ``node`` lexically inside a traced region?"""
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in self._traced_nodes:
                return True
            cur = self.parents.get(cur)
        return False

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self.parents.get(cur)
        return None


def classify(rel: str) -> str:
    """Path kind: fixture | test | script | src."""
    parts = Path(rel).parts
    if "lint_fixtures" in parts:
        return "fixture"
    if "tests" in parts or Path(rel).name.startswith("test_"):
        return "test"
    if parts and parts[0] in ("benchmarks", "examples", "tools"):
        return "script"
    return "src"


def load_module(path: Path, rel: Optional[str] = None) -> Module:
    rel = rel if rel is not None else path.as_posix()
    text = path.read_text()
    return Module(path, rel, text, classify(rel))


# --------------------------------------------------------------- aliases
def _build_aliases(tree: ast.Module) -> Dict[str, str]:
    al: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    al[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    al[head] = head
        elif isinstance(node, ast.ImportFrom):
            mod = ("." * node.level) + (node.module or "")
            for a in node.names:
                if a.name == "*":
                    continue
                al[a.asname or a.name] = f"{mod}.{a.name}" if mod else a.name
    return al


# --------------------------------------------------------- traced regions
def _resolves_to_jit(mod: Module, node: ast.AST) -> bool:
    """Does ``node`` denote ``jax.jit`` — directly or via
    ``functools.partial(jax.jit, ...)``?"""
    d = mod.dotted(node)
    if d == "jax.jit":
        return True
    if isinstance(node, ast.Call):
        fd = mod.call_dotted(node)
        if fd == "functools.partial" and node.args:
            return _resolves_to_jit(mod, node.args[0])
        if fd == "jax.jit":      # jax.jit(static_argnames=...) factory style
            return True
    return False


def _static_names_from_call(call: ast.Call) -> Set[str]:
    """static_argnames=("a", "b") → {"a", "b"} (constants only)."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and isinstance(el.value, str):
                        out.add(el.value)
    return out


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _find_traced(mod: Module) -> List[TracedDef]:
    traced: List[TracedDef] = []
    defs_by_name: Dict[str, ast.AST] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)

    def add(fn: ast.AST, static: Set[str]):
        traced.append(TracedDef(node=fn, static_params=static))

    for node in ast.walk(mod.tree):
        # 1. decorated defs
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _resolves_to_jit(mod, dec) or mod.dotted(dec) in TRACING_WRAPPERS:
                    static: Set[str] = set()
                    if isinstance(dec, ast.Call):
                        static = _static_names_from_call(dec)
                    add(node, static)
                    break
        # 2./3. functions or lambdas handed to a tracing wrapper
        elif isinstance(node, ast.Call):
            fd = mod.call_dotted(node)
            wraps = fd in TRACING_WRAPPERS or _resolves_to_jit(mod, node.func)
            if not wraps:
                continue
            static = _static_names_from_call(node)
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                target, bound_static = arg, set(static)
                # pallas_call(functools.partial(kernel, scale=...), ...):
                # keyword-bound kernel params are Python values, not tracers
                if (isinstance(arg, ast.Call)
                        and mod.call_dotted(arg) == "functools.partial"
                        and arg.args):
                    bound_static |= {kw.arg for kw in arg.keywords if kw.arg}
                    target = arg.args[0]
                if isinstance(target, ast.Lambda):
                    add(target, bound_static)
                elif isinstance(target, ast.Name) and target.id in defs_by_name:
                    add(defs_by_name[target.id], bound_static)
    # dedupe, keeping the union of static params per node
    by_node: Dict[ast.AST, Set[str]] = {}
    for t in traced:
        by_node.setdefault(t.node, set()).update(t.static_params)
    return [TracedDef(node=n, static_params=s) for n, s in by_node.items()]


# ------------------------------------------------------------- misc utils
def walk_skipping_defs(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk over ``node``'s subtree, not descending into nested
    function/class definitions (the node itself is yielded even if it is
    a def)."""
    stack = [node]
    first = True
    while stack:
        cur = stack.pop()
        if not first and isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                      ast.ClassDef)):
            continue
        first = False
        yield cur
        stack.extend(ast.iter_child_nodes(cur))


def names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def assigned_names(target: ast.AST) -> Set[str]:
    """Names bound by an assignment target (handles tuple unpacking)."""
    out: Set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            out.add(n.id)
    return out


def loop_ancestors(mod: Module, node: ast.AST) -> List[ast.AST]:
    """Enclosing Python ``for``/``while`` statements, innermost first,
    stopping at the nearest function boundary."""
    out = []
    cur = mod.parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.While)):
            out.append(cur)
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            break
        cur = mod.parents.get(cur)
    return out
