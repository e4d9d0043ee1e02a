"""Jit-region inference: which functions run under a JAX trace.

The analyzer's precision lives here.  A rule like "no ``assert`` on a
traced value" is only useful if (a) it fires inside ``while_loop`` bodies
three calls away from the ``@jax.jit`` decorator, and (b) it stays quiet
about host-side code and about *static* values inside traced code (shape
asserts in the Pallas kernels are load-bearing and legal).

Three passes over the parsed project:

1. **Indexing** — every module's functions (nested defs and methods
   included), import aliases, and ``from``-imports.
2. **Trace roots** — functions made traced directly: decorated with
   ``jax.jit`` / ``functools.partial(jax.jit, …)`` /
   :func:`repro.knobs.solver_jit`, or passed as a function argument to a
   tracing entry point (``jax.jit(f)``, ``lax.while_loop(cond, body, …)``,
   ``lax.scan`` / ``fori_loop`` / ``cond`` / ``switch``, ``jax.vmap``,
   ``jax.shard_map``, ``pl.pallas_call``, ``jax.checkpoint``).  Roots
   carry their declared ``static_argnames`` (derived from the knob
   declaration for ``solver_jit``).
3. **Closure + staticness fixpoint** — tracedness propagates through the
   project-internal call graph and into nested defs; parameter staticness
   propagates from root declarations through call sites (a parameter of a
   non-root traced function is static iff *every* traced call site passes
   a static expression).  The fixpoint is optimistic (params start
   static, downgrade monotonically), so cycles converge.

Expression staticness (:func:`is_static`) is the shared oracle: constants,
static parameters, ``x is None``, closure variables from host scope, and
shape-like attributes (``.shape`` / ``.ndim`` / ``.dtype`` / graph counts
``.n`` / ``.nb`` / ``.nf`` / ``.num_edges``) are static; everything that
could be a tracer — positional array params, ``jnp.*`` results, unknown
calls — is not.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.findings import norm_path

# ---------------------------------------------------------------------------
# tracing entry points
# ---------------------------------------------------------------------------

# callee last-segment -> positions of function-valued arguments that will
# be traced when the callee runs.  "rest" = every argument from the given
# index on (lax.switch's branch list).
TRACE_ARG_CALLS: Dict[str, object] = {
    "jit": (0,),
    "vmap": (0,),
    "pmap": (0,),
    "grad": (0,),
    "value_and_grad": (0,),
    "checkpoint": (0,),
    "remat": (0,),
    "custom_jvp": (0,),
    "custom_vjp": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "scan": (0,),
    "associative_scan": (0,),
    "cond": (1, 2),
    "switch": ("rest", 1),
    "shard_map": (0,),
    "pallas_call": (0,),
    "solver_jit": (0,),
}

# decorator last segments that make the decorated function a trace root
TRACING_DECORATORS = frozenset(
    {"jit", "vmap", "pmap", "solver_jit", "checkpoint", "remat",
     "custom_jvp", "custom_vjp", "pallas_call"}
)

# attribute names that are Python scalars / aux metadata even on traced
# containers — ``g.n`` is a host int carried on the jitted EllGraph pytree
# (hashable aux data), ``x.shape`` is always static under jit
STATIC_ATTRS = frozenset(
    {"shape", "ndim", "dtype", "size", "n", "nb", "nf", "num_edges",
     "width", "rows", "n_local", "n_pad"}
)

# builtins whose result is static when every argument is static
_STATIC_BUILTINS = frozenset(
    {"len", "min", "max", "abs", "sum", "range", "int", "float", "bool",
     "str", "round", "divmod", "sorted", "tuple", "list", "dict", "set",
     "frozenset", "enumerate", "zip", "all", "any", "isinstance", "type",
     "getattr", "hasattr", "repr", "format", "id", "print"}
)

def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chain as a string; None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last_segment(dotted: Optional[str]) -> Optional[str]:
    return dotted.rsplit(".", 1)[-1] if dotted else None


def _unwrap_partial(
    call: ast.Call,
) -> Tuple[ast.AST, List[ast.keyword], List[ast.AST]]:
    """``functools.partial(jax.jit, static_argnames=…)`` → the effective
    (callee, keywords, positional args).

    For ``partial(f, a, b)`` the callee is ``f`` and the effective
    positional args are ``[a, b]`` — position 0 of the *wrapped* call.
    Non-partial calls pass through as (func, keywords, args)."""
    if (
        _last_segment(_dotted(call.func)) == "partial"
        and call.args
    ):
        inner = call.args[0]
        kws = list(call.keywords)
        if isinstance(inner, ast.Call):  # partial(jit(...)) — unusual
            kws += list(inner.keywords)
            inner = inner.func
        return inner, kws, list(call.args[1:])
    return call.func, list(call.keywords), list(call.args)


def _literal_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """A literal ``("a", "b")`` / ``["a"]`` / ``"a"`` as a tuple of str."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, str)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


# ---------------------------------------------------------------------------
# project model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)  # identity hash: used as env-cache key
class FunctionInfo:
    """One function (or method, or nested def) in the project."""

    qualname: str  # dotted within the module, e.g. "EllPatcher.apply"
    module: "ModuleInfo"
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    parent: Optional["FunctionInfo"]
    # trace state (filled by Project.resolve)
    traced: bool = False
    trace_reason: str = ""
    is_root: bool = False
    # declared static params of a root (decorator / jit-call declaration)
    root_static: Set[str] = dataclasses.field(default_factory=set)
    # the literal static_argnames tuple, if the root declared one (TS06)
    declared_static: Optional[Tuple[str, ...]] = None
    decl_node: Optional[ast.AST] = None
    derived: bool = False  # statics derived via solver_jit, not literal
    # per-parameter staticness under trace (optimistic fixpoint result)
    param_static: Dict[str, bool] = dataclasses.field(default_factory=dict)

    @property
    def params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        if a.vararg:
            names.append(a.vararg.arg)
        names += [p.arg for p in a.kwonlyargs]
        if a.kwarg:
            names.append(a.kwarg.arg)
        return names

    @property
    def positional(self) -> List[str]:
        a = self.node.args
        return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]

    @property
    def kwonly(self) -> List[str]:
        return [p.arg for p in self.node.args.kwonlyargs]

    def display(self) -> str:
        return f"{self.module.name}.{self.qualname}"


@dataclasses.dataclass
class ModuleInfo:
    path: str
    name: str  # dotted module name, e.g. "repro.core.voronoi"
    tree: ast.Module
    lines: List[str]
    # local alias -> dotted module ("np" -> "numpy", "pl" -> "jax.experimental.pallas")
    import_aliases: Dict[str, str] = dataclasses.field(default_factory=dict)
    # local name -> (source module, original name)
    from_imports: Dict[str, Tuple[str, str]] = dataclasses.field(default_factory=dict)
    functions: Dict[str, FunctionInfo] = dataclasses.field(default_factory=dict)
    top_level: Dict[str, FunctionInfo] = dataclasses.field(default_factory=dict)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def resolve_dotted(self, node: ast.AST) -> Optional[str]:
        """Dotted name of an expression with the leading alias expanded.

        ``pl.pallas_call`` → "jax.experimental.pallas.pallas_call";
        ``jit`` (from ``from jax import jit``) → "jax.jit"."""
        d = _dotted(node)
        if d is None:
            return None
        head, _, rest = d.partition(".")
        if head in self.from_imports:
            src, orig = self.from_imports[head]
            base = f"{src}.{orig}"
        elif head in self.import_aliases:
            base = self.import_aliases[head]
        else:
            base = head
        return f"{base}.{rest}" if rest else base


class _ModuleIndexer(ast.NodeVisitor):
    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.stack: List[FunctionInfo] = []

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = alias.asname or alias.name.partition(".")[0]
            self.mod.import_aliases[local] = alias.name if alias.asname else alias.name.partition(".")[0]

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.level:  # relative import — resolve against this module
            pkg = self.mod.name.split(".")
            pkg = pkg[: len(pkg) - node.level]
            src = ".".join(pkg + ([node.module] if node.module else []))
        else:
            src = node.module or ""
        for alias in node.names:
            local = alias.asname or alias.name
            self.mod.from_imports[local] = (src, alias.name)

    def _add_function(self, node) -> None:
        parent = self.stack[-1] if self.stack else None
        prefix = f"{parent.qualname}." if parent else self._class_prefix(node)
        info = FunctionInfo(
            qualname=f"{prefix}{node.name}",
            module=self.mod,
            node=node,
            parent=parent,
        )
        self.mod.functions[info.qualname] = info
        if parent is None and not prefix:
            self.mod.top_level[node.name] = info
        self.stack.append(info)
        for child in node.body:
            self.visit(child)
        self.stack.pop()

    def _class_prefix(self, node) -> str:
        # class methods get "Class." prefixes via the _classes stack
        return getattr(node, "_repro_class_prefix", "")

    def visit_FunctionDef(self, node) -> None:
        self._add_function(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        for child in node.body:
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child._repro_class_prefix = f"{node.name}."
            self.visit(child)


class Project:
    """All indexed modules + the resolved trace map."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}
        self.by_path: Dict[str, ModuleInfo] = {}

    # -- loading -----------------------------------------------------------

    @staticmethod
    def module_name_for(path: str) -> str:
        parts = [p for p in norm_path(path).split("/") if p]
        if parts[-1].endswith(".py"):
            parts[-1] = parts[-1][:-3]
        if "src" in parts:
            parts = parts[parts.index("src") + 1:]
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts) or "<root>"

    def add_file(self, path: str) -> Optional[ModuleInfo]:
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=path)
        except (OSError, SyntaxError):
            return None
        mod = ModuleInfo(
            path=norm_path(path),
            name=self.module_name_for(path),
            tree=tree,
            lines=source.splitlines(),
        )
        _ModuleIndexer(mod).visit(tree)
        self.modules[mod.name] = mod
        self.by_path[mod.path] = mod
        return mod

    @classmethod
    def load(cls, paths) -> "Project":
        proj = cls()
        for p in paths:
            if os.path.isdir(p):
                for root, dirs, files in os.walk(p):
                    dirs[:] = sorted(
                        d for d in dirs
                        if d not in {"__pycache__", ".git", ".venv", "node_modules"}
                    )
                    for f in sorted(files):
                        if f.endswith(".py"):
                            proj.add_file(os.path.join(root, f))
            elif p.endswith(".py"):
                proj.add_file(p)
        proj.resolve()
        return proj

    # -- name resolution ---------------------------------------------------

    def lookup_function(
        self, expr: ast.AST, mod: ModuleInfo, scope: Optional[FunctionInfo]
    ) -> Optional[FunctionInfo]:
        """Resolve an expression naming a function to its FunctionInfo."""
        if isinstance(expr, ast.Call):  # partial(f, …) as a loop body
            callee, _, _eff = _unwrap_partial(expr)
            if callee is not expr.func:
                return self.lookup_function(callee, mod, scope)
            return None
        if isinstance(expr, ast.Name):
            name = expr.id
            s = scope
            while s is not None:  # nested defs visible in enclosing scopes
                cand = mod.functions.get(f"{s.qualname}.{name}")
                if cand is not None:
                    return cand
                s = s.parent
            if name in mod.top_level:
                return mod.top_level[name]
            if name in mod.from_imports:
                src, orig = mod.from_imports[name]
                target = self.modules.get(src)
                if target is not None:
                    return target.top_level.get(orig)
            return None
        if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
            alias = expr.value.id
            src = None
            if alias in mod.import_aliases:
                src = mod.import_aliases[alias]
            elif alias in mod.from_imports:  # "from repro.core import voronoi"
                m, orig = mod.from_imports[alias]
                src = f"{m}.{orig}" if m else orig
            if src is not None and src in self.modules:
                return self.modules[src].top_level.get(expr.attr)
        return None

    def lookup_candidates(
        self, expr: ast.AST, mod: ModuleInfo, scope: Optional[FunctionInfo]
    ) -> List[FunctionInfo]:
        """Every function ``expr`` may name — the direct resolution plus,
        for a bare name, functions rebound onto it in an enclosing scope
        (``body = frontier_body`` before ``shard_map(body, …)``)."""
        out: List[FunctionInfo] = []
        direct = self.lookup_function(expr, mod, scope)
        if direct is not None:
            out.append(direct)
        if isinstance(expr, ast.Name):
            s = scope
            while s is not None:
                for node in ast.walk(s.node):
                    if not isinstance(node, ast.Assign):
                        continue
                    for t in node.targets:
                        if isinstance(t, ast.Name) and t.id == expr.id:
                            cand = self.lookup_function(node.value, mod, s)
                            if cand is not None and cand not in out:
                                out.append(cand)
                s = s.parent
        return out

    # -- root detection ----------------------------------------------------

    def _root_from_jit_decl(
        self,
        fn: FunctionInfo,
        callee_dotted: Optional[str],
        keywords: List[ast.keyword],
        decl_node: ast.AST,
        reason: str,
    ) -> None:
        fn.is_root = True
        fn.traced = True
        fn.trace_reason = reason
        fn.decl_node = decl_node
        last = _last_segment(callee_dotted)
        if last == "solver_jit":
            from repro import knobs

            fn.derived = True
            statics = tuple(p for p in fn.kwonly if knobs.classify(p) == "static")
            fn.declared_static = statics
            fn.root_static |= set(statics)
            return
        declared: Tuple[str, ...] = ()
        for kw in keywords:
            if kw.arg == "static_argnames":
                lit = _literal_str_tuple(kw.value)
                if lit is not None:
                    declared += lit
            elif kw.arg == "static_argnums":
                nums = None
                if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, int):
                    nums = (kw.value.value,)
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    vals = [e.value for e in kw.value.elts
                            if isinstance(e, ast.Constant) and isinstance(e.value, int)]
                    nums = tuple(vals)
                if nums:
                    pos = fn.positional
                    declared += tuple(pos[i] for i in nums if i < len(pos))
        if last in ("jit", "solver_jit", "pjit"):
            fn.declared_static = declared
        fn.root_static |= set(declared)

    def _detect_roots(self) -> None:
        for mod in self.modules.values():
            # decorators
            for fn in mod.functions.values():
                node = fn.node
                for dec in getattr(node, "decorator_list", []):
                    if isinstance(dec, ast.Call):
                        callee, kws, _ = _unwrap_partial(dec)
                    else:
                        callee, kws = dec, []
                    dotted = mod.resolve_dotted(callee)
                    if _last_segment(dotted) in TRACING_DECORATORS:
                        self._root_from_jit_decl(
                            fn, dotted, kws, dec,
                            f"decorated with {_dotted(callee) or '?'}",
                        )
            # call-argument roots: jit(f), while_loop(cond, body, …), …
            for fn_scope, call in self._iter_calls(mod):
                callee, kws, eff_args = _unwrap_partial(call)
                last = _last_segment(_dotted(callee))
                spec = TRACE_ARG_CALLS.get(last or "")
                if spec is None:
                    continue
                if isinstance(spec, tuple) and spec and spec[0] == "rest":
                    positions = range(spec[1], len(eff_args))
                else:
                    positions = spec  # type: ignore[assignment]
                for i in positions:
                    if i >= len(eff_args):
                        continue
                    targets = self.lookup_candidates(eff_args[i], mod, fn_scope)
                    for target in targets:
                        if target.is_root:
                            continue
                        target.traced = True
                        if not target.trace_reason:
                            target.trace_reason = f"passed to {last}"
                        if last in ("jit", "solver_jit"):
                            self._root_from_jit_decl(
                                target, mod.resolve_dotted(callee), kws, call,
                                f"passed to {last}",
                            )

    def _iter_calls(self, mod: ModuleInfo):
        """(enclosing FunctionInfo or None, Call node) for a module."""

        out: List[Tuple[Optional[FunctionInfo], ast.Call]] = []

        def walk(node: ast.AST, scope: Optional[FunctionInfo]) -> None:
            for child in ast.iter_child_nodes(node):
                child_scope = scope
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{scope.qualname}.{child.name}" if scope else child.name
                    child_scope = mod.functions.get(q, scope)
                    if child_scope is scope:  # method — find via class prefix
                        for cand in mod.functions.values():
                            if cand.node is child:
                                child_scope = cand
                                break
                if isinstance(child, ast.Call):
                    out.append((scope, child))
                walk(child, child_scope)

        walk(mod.tree, None)
        return out

    # -- closure + staticness fixpoint ------------------------------------

    def resolve(self) -> None:
        self._detect_roots()
        # nested defs inside traced functions are traced (loop bodies,
        # shard_map closures) — iterate to closure
        changed = True
        while changed:
            changed = False
            for mod in self.modules.values():
                for fn in mod.functions.values():
                    if fn.traced:
                        continue
                    if fn.parent is not None and fn.parent.traced:
                        fn.traced = True
                        fn.trace_reason = f"defined inside traced {fn.parent.qualname}"
                        changed = True
            # call-graph closure: traced caller -> project-internal callee
            for mod in self.modules.values():
                for scope, call in self._iter_calls(mod):
                    if scope is None or not scope.traced:
                        continue
                    target = self.lookup_function(call.func, mod, scope)
                    if target is not None and not target.traced:
                        target.traced = True
                        target.trace_reason = f"called from traced {scope.display()}"
                        changed = True
        self._resolve_param_staticness()

    def traced_functions(self) -> List[FunctionInfo]:
        return [
            fn
            for mod in self.modules.values()
            for fn in mod.functions.values()
            if fn.traced
        ]

    def _resolve_param_staticness(self) -> None:
        from repro.analysis.rules import is_static  # shared oracle

        for fn in self.traced_functions():
            if fn.is_root:
                fn.param_static = {p: p in fn.root_static for p in fn.params}
            else:
                # optimistic init: static until a traced call site says no
                fn.param_static = {p: True for p in fn.params}
                # …except functions handed to while_loop/scan/shard_map
                # and nested defs: their params are carries/operands
                if fn.trace_reason.startswith(("passed to", "defined inside")):
                    fn.param_static = {p: False for p in fn.params}
        for _ in range(8):  # small project: fixpoint in a few passes
            changed = False
            self._env_cache = {}  # envs depend on param_static — rebuild
            for mod in self.modules.values():
                for scope, call in self._iter_calls(mod):
                    if scope is None or not scope.traced:
                        continue
                    target = self.lookup_function(call.func, mod, scope)
                    if target is None or not target.traced or target.is_root:
                        continue
                    if target.trace_reason.startswith(("passed to", "defined inside")):
                        continue
                    pos = target.positional
                    for i, arg in enumerate(call.args):
                        if isinstance(arg, ast.Starred) or i >= len(pos):
                            continue
                        name = pos[i]
                        if target.param_static.get(name) and not is_static(
                            arg, self, scope
                        ):
                            target.param_static[name] = False
                            changed = True
                    for kw in call.keywords:
                        if kw.arg is None:  # **kwargs forwarding — opaque
                            continue
                        if target.param_static.get(kw.arg) and not is_static(
                            kw.value, self, scope
                        ):
                            target.param_static[kw.arg] = False
                            changed = True
            if not changed:
                break
        self._env_cache = {}  # rules re-derive envs from the final fixpoint
