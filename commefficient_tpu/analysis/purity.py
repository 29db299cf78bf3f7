"""traced-purity: functions reachable from jit/shard_map/pallas_call
roots must stay host-pure.

The whole system rests on the compiled round being a pure function of
its arguments: bit-exact replay after rollback (resilience/), bit-exact
resume from checkpoint, the retrace sentinel's zero-retrace contract
(telemetry/), and asyncfed's K = W, C = 1 == synchronous pin all
assume that tracing the same program twice yields the same program. One
``time.time()`` or ``np.random.<draw>`` inside traced code bakes a
different constant into every trace; one ``float(x)`` on a tracer is a
``ConcretizationTypeError`` at best and a silent trace-time
constant-fold at worst.

Mechanically: the analyzer builds a package-local call graph —

  * **roots**: functions decorated with / passed to ``jit`` / ``pjit`` /
    ``shard_map`` / ``pallas_call`` (final-name match, so
    ``jax.shard_map`` and ``pl.pallas_call`` both count), including ``functools.partial(...)``-wrapped and lambda
    arguments;
  * **edges**: a function *referencing* another package function (call,
    argument, closure) links to it — reference, not just call, so
    ``jax.vmap(per_client)`` and higher-order plumbing like
    ``comp.client_grad(grad_one, ...)`` are followed. Aliases through
    builder returns are tracked one hop (``grad_one = make_grad_one(...)``
    links to the inner def that ``make_grad_one`` returns), and
    attribute calls (``comp.device_encode(...)``) resolve by method name
    across the package's classes, minus a blocklist of builtin
    collection/str method names that would otherwise tie every
    ``list.append`` to an unrelated host class.

Every function reachable from a root is then scanned for host impurity:

  * wall-clock / host entropy / IO: any call into ``time``,
    ``datetime``, stdlib ``random``, or ``numpy.random``; the builtins
    ``print`` / ``input`` / ``breakpoint`` / ``open``;
  * tracer coercion: ``.item()``, and ``float()`` / ``int()`` /
    ``bool()`` applied directly to a function parameter (a parameter is
    exactly what holds a tracer; coercions of locally computed static
    values stay legal).

Deterministic trace-time host work (e.g. CountSketch's seed-derived
hash-coefficient tables) is exempted per line with
``# lint: allow[traced-purity] <reason>`` — the reason is mandatory, so
every exemption documents why it cannot break replay.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from commefficient_tpu.analysis.core import (
    Finding,
    PackageIndex,
    dotted_path as _core_dotted_path,
    final_name as _final_name,
)

RULE = "traced-purity"
DESCRIPTION = (
    "no wall-clock/host-rng/print/IO or tracer coercion in code "
    "reachable from jit/shard_map/pallas_call roots"
)

# final-name match: covers jax.jit, jax.experimental.pjit.pjit,
# jax.shard_map and pl.pallas_call alike
TRACER_NAMES = frozenset({"jit", "pjit", "shard_map", "pallas_call"})

# builtin collection/str/array method names excluded from the
# method-name edge rule — linking every traced `candidates.append(...)`
# to some host class's `append` would poison the graph with false paths
GENERIC_METHODS = frozenset({
    "append", "extend", "insert", "remove", "pop", "clear", "copy",
    "count", "index", "sort", "reverse", "get", "items", "keys",
    "values", "setdefault", "update", "add", "discard", "union",
    "join", "split", "rsplit", "strip", "lstrip", "rstrip", "format",
    "startswith", "endswith", "replace", "lower", "upper", "read",
    "write", "close", "flush", "open", "item", "tolist", "astype",
    "reshape", "mean", "sum", "max", "min", "all", "any",
    # flax's model.apply is ubiquitous in traced code; linking it to
    # unrelated package methods named `apply` (resilience policies)
    # would fuse the traced and host worlds into one component
    "apply",
})

BANNED_BUILTINS = frozenset({"print", "input", "breakpoint", "open"})
COERCIONS = frozenset({"float", "int", "bool"})


def _banned_module(dotted: str) -> Optional[str]:
    """The impurity family a resolved dotted call path belongs to, or
    None. ``random`` means the stdlib module — ``jax.random`` resolves
    to a ``jax.``-rooted path and never matches."""
    top = dotted.split(".", 1)[0]
    if top in ("time", "datetime"):
        return top
    if dotted == "random" or dotted.startswith("random."):
        return "random"
    if dotted == "numpy.random" or dotted.startswith("numpy.random."):
        return "numpy.random"
    return None


@dataclass
class FuncNode:
    """One function (or rooted lambda) in the call graph."""

    qualname: str  # module-rel path + dotted nesting, for messages
    file_rel: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    parent: Optional["FuncNode"]
    local_defs: Dict[str, "FuncNode"] = field(default_factory=dict)
    aliases: Dict[str, "FuncNode"] = field(default_factory=dict)
    params: frozenset = frozenset()
    returns_def: Optional["FuncNode"] = None


@dataclass
class ModuleInfo:
    rel: str
    modname: str  # importable dotted name (root package name + path)
    imports: Dict[str, str] = field(default_factory=dict)  # name -> dotted
    defs: Dict[str, FuncNode] = field(default_factory=dict)  # module level
    aliases: Dict[str, FuncNode] = field(default_factory=dict)
    nodes: List[FuncNode] = field(default_factory=list)
    # (call node, enclosing FuncNode or None) for every tracer-wrapper call
    tracer_calls: List[Tuple[ast.Call, Optional[FuncNode]]] = field(
        default_factory=list
    )


def _params_of(node: ast.AST) -> frozenset:
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = node.args
        names = [x.arg for x in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return frozenset(names)
    return frozenset()


def _body_walk(node: ast.AST):
    """Walk a function body WITHOUT descending into nested function
    defs (each is its own graph node); lambdas stay inline — their
    bodies execute in this function's dynamic extent when traced."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))


class CallGraph:
    """Package-local reference graph + traced-root reachability."""

    def __init__(self, index: PackageIndex):
        self.index = index
        self.pkg_name = index.root.name
        self.modules: Dict[str, ModuleInfo] = {}
        self.global_defs: Dict[str, FuncNode] = {}  # dotted name -> node
        self.method_map: Dict[str, List[FuncNode]] = {}
        self.node_module: Dict[int, ModuleInfo] = {}  # id(FuncNode) -> mod
        for sf in index.trees():
            self._build_module(sf)
        for mod in self.modules.values():
            self._resolve_aliases(mod)
        self.roots: List[Tuple[FuncNode, str]] = []
        self._collect_roots()

    # ---- construction -------------------------------------------------

    def _modname_for(self, rel: str) -> str:
        parts = rel[:-3].split("/")  # strip .py
        if parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join([self.pkg_name] + parts) if parts else self.pkg_name

    def _build_module(self, sf) -> None:
        mod = ModuleInfo(rel=sf.rel, modname=self._modname_for(sf.rel))
        self.modules[sf.rel] = mod
        # relative-import anchoring differs for packages: in a MODULE,
        # level 1 names its containing package (one climb from modname);
        # in an __init__.py, modname already IS the package, so level 1
        # names modname itself and only extra levels climb
        is_pkg = sf.rel.rsplit("/", 1)[-1] == "__init__.py"

        def visit(node, parent_func, in_class):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Import):
                    for a in child.names:
                        if a.asname:
                            mod.imports[a.asname] = a.name
                        else:
                            mod.imports[a.name.split(".")[0]] = \
                                a.name.split(".")[0]
                elif isinstance(child, ast.ImportFrom):
                    base = child.module or ""
                    if child.level:
                        anchor = mod.modname.split(".")
                        climb = child.level - 1 if is_pkg else child.level
                        if climb:
                            anchor = anchor[:-climb]
                        base = ".".join(anchor + ([base] if base else []))
                    for a in child.names:
                        if a.name == "*":
                            continue
                        mod.imports[a.asname or a.name] = (
                            f"{base}.{a.name}" if base else a.name
                        )
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = child.name if parent_func is None else \
                        f"{parent_func.qualname.split(':', 1)[1]}.{child.name}"
                    fn = FuncNode(
                        qualname=f"{sf.rel}:{qual}",
                        file_rel=sf.rel, node=child, parent=parent_func,
                        params=_params_of(child),
                    )
                    mod.nodes.append(fn)
                    self.node_module[id(fn)] = mod
                    if parent_func is not None:
                        parent_func.local_defs[child.name] = fn
                    elif not in_class:
                        mod.defs[child.name] = fn
                        self.global_defs[f"{mod.modname}.{child.name}"] = fn
                    if in_class:
                        self.method_map.setdefault(child.name, []).append(fn)
                    visit(child, fn, False)
                elif isinstance(child, ast.ClassDef):
                    # methods keep the enclosing *function* scope chain
                    # (class bodies are not a lookup scope for names)
                    visit(child, parent_func, True)
                elif isinstance(child, (ast.If, ast.Try, ast.With,
                                        ast.For, ast.While, ast.AsyncWith,
                                        ast.AsyncFor, ast.ExceptHandler)):
                    # defs nested under control flow register in the SAME
                    # scope — recurse with unchanged context
                    visit(child, parent_func, in_class)
                else:
                    # tracer-wrapper calls can appear anywhere (module
                    # level, class level, expression statements)
                    for sub in ast.walk(child):
                        if isinstance(sub, ast.Call) and \
                                _final_name(sub.func) in TRACER_NAMES:
                            mod.tracer_calls.append((sub, parent_func))
                    continue
                # calls inside defs/classes: collected when visiting the
                # def's own statements above — also sweep decorators etc.
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    for dec in getattr(child, "decorator_list", []):
                        for sub in ast.walk(dec):
                            if isinstance(sub, ast.Call) and \
                                    _final_name(sub.func) in TRACER_NAMES:
                                mod.tracer_calls.append((sub, parent_func))

        visit(sf.tree, None, False)

        # returns_def: `def maker(): ... def inner(): ...; return inner`
        for fn in mod.nodes:
            for sub in _body_walk(fn.node):
                if isinstance(sub, ast.Return) and \
                        isinstance(sub.value, ast.Name) and \
                        sub.value.id in fn.local_defs:
                    fn.returns_def = fn.local_defs[sub.value.id]
                    break

    def _resolve_aliases(self, mod: ModuleInfo) -> None:
        """One-hop builder aliasing: ``v = maker(...)`` binds ``v`` to
        the inner def ``maker`` returns, so closures over built
        functions (round.py's ``grad_one = make_grad_one(...)``) stay
        connected."""

        def bind(scope_assigns, resolver):
            for target_name, call in scope_assigns:
                callee = resolver(call.func)
                if callee is not None and callee.returns_def is not None:
                    yield target_name, callee.returns_def

        def assigns_in(body_owner):
            # _body_walk skips nested defs in BOTH cases: a function's
            # local assigns must not leak into module scope and vice versa
            walker = _body_walk(
                body_owner.node if isinstance(body_owner, FuncNode)
                else self._module_tree(mod)
            )
            for sub in walker:
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name) \
                        and isinstance(sub.value, ast.Call):
                    yield sub.targets[0].id, sub.value

        mod.aliases.update(bind(
            ((n, c) for n, c in assigns_in(mod)),
            lambda f: self.resolve_func_expr(f, None, mod),
        ))
        for fn in mod.nodes:
            fn.aliases.update(bind(
                assigns_in(fn),
                lambda f, fn=fn: self.resolve_func_expr(f, fn, mod),
            ))

    def _module_tree(self, mod: ModuleInfo):
        return self.index.files[mod.rel].tree

    # ---- resolution ---------------------------------------------------

    def resolve_name(self, name: str, func: Optional[FuncNode],
                     mod: ModuleInfo) -> Optional[FuncNode]:
        n = func
        while n is not None:
            if name in n.local_defs:
                return n.local_defs[name]
            if name in n.aliases:
                return n.aliases[name]
            if name in n.params:
                return None  # parameter shadows everything outward
            n = n.parent
        if name in mod.defs:
            return mod.defs[name]
        if name in mod.aliases:
            return mod.aliases[name]
        dotted = mod.imports.get(name)
        if dotted is not None:
            return self.global_defs.get(dotted)
        return None

    def resolve_func_expr(self, expr: ast.AST, func: Optional[FuncNode],
                          mod: ModuleInfo) -> Optional[FuncNode]:
        if isinstance(expr, ast.Name):
            return self.resolve_name(expr.id, func, mod)
        if isinstance(expr, ast.Attribute):
            dotted = self.dotted_path(expr, mod)
            if dotted is not None:
                return self.global_defs.get(dotted)
        return None

    def dotted_path(self, expr: ast.AST, mod: ModuleInfo) -> Optional[str]:
        """``np.random.default_rng`` -> ``numpy.random.default_rng`` via
        the module's import table (core.dotted_path over mod.imports,
        which — unlike the line-level analyzers' tables — also carries
        package-anchored relative imports)."""
        return _core_dotted_path(expr, mod.imports)

    # ---- roots --------------------------------------------------------

    def _root_candidates(self, call: ast.Call) -> List[ast.AST]:
        """Function-valued expressions possibly traced by this wrapper
        call: the first positional arg, unwrapped through ``partial(f,
        ...)`` AND arbitrary wrapper calls — ``jit(sentinel.wrap(f,
        tag))`` traces ``f`` just as surely, so each Call layer
        contributes both itself (a builder whose RETURN may be the
        traced fn) and its own first argument (the wrapped fn)."""
        out: List[ast.AST] = []
        arg = call.args[0] if call.args else None
        for _ in range(5):  # bounded unwrap; real nesting is 1-2 deep
            if arg is None:
                break
            if isinstance(arg, ast.Call):
                out.append(arg)
                arg = arg.args[0] if arg.args else None
                continue
            out.append(arg)
            break
        return out

    def _collect_roots(self) -> None:
        seen = set()

        def add(fn: FuncNode, why: str):
            if fn is not None and id(fn) not in seen:
                seen.add(id(fn))
                self.roots.append((fn, why))

        for mod in self.modules.values():
            for fn in mod.nodes:
                for dec in getattr(fn.node, "decorator_list", []):
                    d = dec
                    if isinstance(d, ast.Call):
                        if _final_name(d.func) == "partial" and d.args:
                            d = d.args[0]
                        elif _final_name(d.func) in TRACER_NAMES:
                            add(fn, f"@{_final_name(d.func)}")
                            continue
                    if _final_name(d) in TRACER_NAMES:
                        add(fn, f"@{_final_name(d)}")
            for call, enclosing in mod.tracer_calls:
                wrapper = _final_name(call.func)
                for arg in self._root_candidates(call):
                    if isinstance(arg, ast.Lambda):
                        fn = FuncNode(
                            qualname=f"{mod.rel}:<lambda@L{arg.lineno}>",
                            file_rel=mod.rel, node=arg, parent=enclosing,
                            params=_params_of(arg),
                        )
                        self.node_module[id(fn)] = mod
                        add(fn, wrapper)
                        continue
                    if isinstance(arg, ast.Call):
                        # builder/wrapper call: whatever nested def its
                        # callee returns is (part of) the traced program
                        callees = []
                        t = self.resolve_func_expr(arg.func, enclosing, mod)
                        if t is not None:
                            callees.append(t)
                        elif isinstance(arg.func, ast.Attribute) and \
                                arg.func.attr not in GENERIC_METHODS:
                            callees.extend(
                                self.method_map.get(arg.func.attr, ())
                            )
                        for c in callees:
                            if c.returns_def is not None:
                                add(c.returns_def, wrapper)
                        continue
                    target = self.resolve_func_expr(arg, enclosing, mod)
                    if target is not None:
                        add(target, wrapper)

    # ---- edges + reachability -----------------------------------------

    def edges_from(self, fn: FuncNode) -> List[FuncNode]:
        mod = self.node_module[id(fn)]
        out, seen = [], set()

        def add(t: Optional[FuncNode]):
            if t is not None and id(t) not in seen and t is not fn:
                seen.add(id(t))
                out.append(t)

        for sub in _body_walk(fn.node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                add(self.resolve_name(sub.id, fn, mod))
            elif isinstance(sub, ast.Attribute) and \
                    isinstance(sub.ctx, ast.Load):
                # a bare attribute LOAD only links through a resolvable
                # module path (`mod.helper` passed as a value); method-name
                # matching is reserved for CALL positions below — linking
                # every `state.step` field access to methods named `step`
                # would fuse the traced and host worlds
                dotted = self.dotted_path(sub, mod)
                if dotted is not None:
                    add(self.global_defs.get(dotted))
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    self.dotted_path(sub.func, mod) is None and \
                    sub.func.attr in self.method_map and \
                    sub.func.attr not in GENERIC_METHODS:
                for m in self.method_map[sub.func.attr]:
                    add(m)
        return out

    def reachable(self) -> Dict[int, Tuple[FuncNode, str]]:
        """{id(node): (node, provenance)} for every function reachable
        from a traced root; provenance names the root for messages."""
        out: Dict[int, Tuple[FuncNode, str]] = {}
        work = []
        for fn, why in self.roots:
            prov = f"{fn.qualname} [{why}]"
            if id(fn) not in out:
                out[id(fn)] = (fn, prov)
                work.append((fn, prov))
        while work:
            fn, prov = work.pop()
            for nxt in self.edges_from(fn):
                if id(nxt) not in out:
                    out[id(nxt)] = (nxt, prov)
                    work.append((nxt, prov))
        return out


def _scan_reached(graph: CallGraph, fn: FuncNode, prov: str,
                  index: PackageIndex) -> List[Finding]:
    mod = graph.node_module[id(fn)]
    sf = index.files[fn.file_rel]
    out = []

    def hit(node, what):
        out.append(sf.finding(
            RULE, node.lineno,
            f"{what} in traced code ({fn.qualname}, reachable from "
            f"traced root {prov})",
        ))

    param_scope = set()
    n: Optional[FuncNode] = fn
    while n is not None:
        param_scope |= n.params
        n = n.parent

    for sub in _body_walk(fn.node):
        if not isinstance(sub, ast.Call):
            continue
        func = sub.func
        if isinstance(func, ast.Attribute):
            if func.attr == "item" and not sub.args and not sub.keywords:
                hit(sub, "tracer coercion .item()")
                continue
            dotted = graph.dotted_path(func, mod)
            if dotted is not None:
                fam = _banned_module(dotted)
                if fam is not None:
                    hit(sub, f"host-impure call {dotted} ({fam})")
            continue
        if not isinstance(func, ast.Name):
            continue
        name = func.id
        # an explicitly imported banned name (`from time import
        # perf_counter`) resolves through the import table
        dotted = mod.imports.get(name)
        if dotted is not None:
            fam = _banned_module(dotted)
            if fam is not None:
                hit(sub, f"host-impure call {dotted} ({fam})")
            continue
        if graph.resolve_name(name, fn, mod) is not None:
            continue  # package-local call; its body is scanned directly
        if name in BANNED_BUILTINS:
            hit(sub, f"host-impure builtin {name}()")
        elif name in COERCIONS and len(sub.args) == 1 and not sub.keywords \
                and isinstance(sub.args[0], ast.Name) \
                and sub.args[0].id in param_scope:
            hit(sub, f"tracer coercion {name}({sub.args[0].id}) on a "
                     "function parameter")
    return out


def analyze(index: PackageIndex) -> List[Finding]:
    graph = CallGraph(index)
    findings: List[Finding] = []
    for fn, prov in graph.reachable().values():
        findings.extend(_scan_reached(graph, fn, prov, index))
    return findings
