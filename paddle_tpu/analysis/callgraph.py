"""Lightweight package call graph for ptlint.

Pure-AST, name-based and deliberately conservative (an over-
approximation: unresolvable calls match every same-named definition in
the project) — the rules that consume it (PT001 host-sync scope, PT003
traced-side-effect scope) want "could this run inside a traced program /
the dispatch loop", and a false edge only widens the lint scope, never
hides a finding.

Three things are computed in one pass per file:

- every function/lambda definition with its enclosing-scope qualname,
- the called names inside each definition (terminal name only:
  ``self._pump()`` records ``_pump``),
- **jit roots**: functions handed to ``jax.jit`` / ``jit`` / ``pjit`` /
  ``shard_map`` (call-site args, decorators, ``partial(jax.jit, ...)``
  decorators, and one level of wrapper nesting like
  ``jax.jit(checkify.checkify(fn))``).

Bare-name uses are first run through local reference aliases
(``step = self._traced`` makes a later ``jit(step)`` resolve to
``_traced``, NOT to every function named ``step``) — the one spot where
precision beats over-approximation, because a false jit root drags a
host-only method into trace scope and produces false PT001/PT003
findings on it. For the same reason a call through a deeper chain
(``self.kind.step(...)``) never resolves to a method of the caller's
own class: it is the member's method that runs.

Reachability (`reachable`) walks call edges plus the
parent→nested-function edge: a ``def one(carry, _)`` defined inside a
jitted body executes at trace time even though it is only ever *passed*
to ``lax.scan``.
"""

import ast
from typing import Dict, Iterable, List, Optional, Set

# call-ee names that wrap a function for tracing. Terminal-name match:
# jax.jit, framework.jit, pjit, jax.shard_map ... all end in one of these.
JIT_WRAPPER_NAMES = {"jit", "pjit", "shard_map", "checkify", "named_call",
                     "vmap", "pmap", "grad", "value_and_grad", "scan",
                     "while_loop", "fori_loop", "cond", "remat",
                     "checkpoint", "custom_vjp", "custom_jvp"}
# Of those, the ones whose wrapped function really enters a NEW trace
# context on its own (scan/cond bodies only trace when already inside
# one, but marking them roots is harmless over-approximation kept OFF
# to avoid noise):
JIT_ROOT_NAMES = {"jit", "pjit", "shard_map", "checkify", "pmap"}


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute chains, 'jit' for a Name, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def terminal_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def iter_own_nodes(func_node: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body WITHOUT descending into nested function /
    class definitions (those are separate FunctionInfos)."""
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class FunctionInfo:
    __slots__ = ("ctx", "node", "name", "qual", "cls", "parent",
                 "children", "calls", "aliases", "lineno")

    def __init__(self, ctx, node, name, qual, cls, parent):
        self.ctx = ctx            # FileContext
        self.node = node
        self.name = name          # terminal name ('<lambda>' for Lambda)
        self.qual = qual          # relpath::Class.meth.<locals>.inner
        self.cls = cls            # enclosing class name or ""
        self.parent = parent      # enclosing FunctionInfo or None
        self.children: List["FunctionInfo"] = []
        # typed call edges: (base, name) — base '' for bare names,
        # 'self'/'cls', a module alias, or '<expr>' (see resolve_edge)
        self.calls: Set[tuple] = set()
        # local reference aliases: ``step = self._traced`` records
        # {'step': ('self', '_traced')} so later uses of the bare name
        # resolve to the real target, not every same-named definition
        self.aliases: Dict[str, tuple] = {}
        self.lineno = getattr(node, "lineno", 1)

    def __repr__(self):
        return f"FunctionInfo({self.qual})"


class _FileVisitor(ast.NodeVisitor):
    def __init__(self, ctx, graph):
        self.ctx = ctx
        self.graph = graph
        self.fn_stack: List[FunctionInfo] = []
        self.cls_stack: List[str] = []
        self.scope_names: List[str] = []   # for quals

    # -- scopes -------------------------------------------------------------
    def _add_function(self, node, name):
        qual = self.ctx.relpath + "::" + ".".join(
            self.scope_names + [name])
        parent = self.fn_stack[-1] if self.fn_stack else None
        cls = self.cls_stack[-1] if self.cls_stack else ""
        info = FunctionInfo(self.ctx, node, name, qual, cls, parent)
        if parent is not None:
            parent.children.append(info)
        self.graph._register(info)
        return info

    def visit_ClassDef(self, node):
        for dec in node.decorator_list:
            self.visit(dec)
        self.cls_stack.append(node.name)
        self.scope_names.append(node.name)
        for child in node.body:
            self.visit(child)
        self.scope_names.pop()
        self.cls_stack.pop()

    def _visit_funcdef(self, node):
        for dec in node.decorator_list:
            self.visit(dec)
            self._check_jit_decorator(dec, node)
        info = self._add_function(node, node.name)
        self.fn_stack.append(info)
        self.scope_names.extend([node.name, "<locals>"])
        for child in node.body:
            self.visit(child)
        for default in (node.args.defaults + node.args.kw_defaults):
            if default is not None:
                self.visit(default)
        self.scope_names.pop()
        self.scope_names.pop()
        self.fn_stack.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def visit_Lambda(self, node):
        info = self._add_function(node, "<lambda>")
        self.fn_stack.append(info)
        self.scope_names.extend(["<lambda>", "<locals>"])
        self.visit(node.body)
        self.scope_names.pop()
        self.scope_names.pop()
        self.fn_stack.pop()

    # -- imports ------------------------------------------------------------
    def visit_Import(self, node):
        imports = self.graph.imports.setdefault(self.ctx.relpath, {})
        for alias in node.names:
            imports[alias.asname or alias.name.split(".")[0]] = alias.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        imports = self.graph.imports.setdefault(self.ctx.relpath, {})
        mod = node.module or ""
        for alias in node.names:
            imports[alias.asname or alias.name] = (
                f"{mod}.{alias.name}" if mod else alias.name)
        self.generic_visit(node)

    # -- aliases ------------------------------------------------------------
    def visit_Assign(self, node):
        """Record ``name = self.method`` / ``name = module.fn`` /
        ``name = other_name`` reference aliases so a later bare-name
        use (a call, or being handed to jax.jit) resolves to the REAL
        target instead of smearing over every same-named definition —
        the ``step = self._traced; jit(step)`` pattern must not mark an
        unrelated host-side ``step`` method as a jit root."""
        if (len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, (ast.Name, ast.Attribute))):
            edge = self._call_edge(node.value)
            if edge is not None and edge != ("", node.targets[0].id):
                if self.fn_stack:
                    self.fn_stack[-1].aliases[
                        node.targets[0].id] = edge
                else:
                    self.graph.module_aliases.setdefault(
                        self.ctx.relpath, {})[
                        node.targets[0].id] = edge
        self.generic_visit(node)

    def _translate(self, edge, depth: int = 0):
        """Follow bare-name aliases (innermost scope first, then module
        level) to the edge they actually reference; depth-capped for
        alias chains."""
        base, name = edge
        if base != "" or depth > 4:
            return edge
        for fn in reversed(self.fn_stack):
            tgt = fn.aliases.get(name)
            if tgt is not None:
                return self._translate(tgt, depth + 1)
        tgt = self.graph.module_aliases.get(
            self.ctx.relpath, {}).get(name)
        if tgt is not None:
            return self._translate(tgt, depth + 1)
        return edge

    # -- calls --------------------------------------------------------------
    @staticmethod
    def _call_edge(func):
        """(base, name) for a call: base '' for bare names, the base
        identifier for one-level attribute calls ('self', a module
        alias, a local object), '<expr>' for deeper chains."""
        if isinstance(func, ast.Name):
            return ("", func.id)
        if isinstance(func, ast.Attribute):
            v = func.value
            if isinstance(v, ast.Name):
                return (v.id, func.attr)
            return ("<expr>", func.attr)
        return None

    def visit_Call(self, node):
        edge = self._call_edge(node.func)
        if edge:
            edge = self._translate(edge)
        if edge and self.fn_stack:
            self.fn_stack[-1].calls.add(edge)
        if edge and edge[1] in JIT_ROOT_NAMES:
            self._mark_roots_from_call(node)
        self.generic_visit(node)

    def _mark_roots_from_call(self, call: ast.Call):
        if not call.args:
            return
        self._mark_root_expr(call.args[0])

    def _mark_root_expr(self, expr, depth: int = 0):
        if depth > 2:
            return
        if isinstance(expr, ast.Lambda):
            self.graph._pending_lambda_roots.append(expr)
        elif isinstance(expr, (ast.Name, ast.Attribute)):
            edge = self._call_edge(expr)
            if edge:
                self.graph._pending_name_roots.append(
                    (self.ctx.relpath,) + self._translate(edge))
        elif isinstance(expr, ast.Call):
            # jax.jit(checkify.checkify(fn)) — descend one wrapper level
            for a in expr.args:
                self._mark_root_expr(a, depth + 1)

    def _check_jit_decorator(self, dec, funcdef):
        name = terminal_name(dec.func if isinstance(dec, ast.Call)
                             else dec)
        if name in JIT_ROOT_NAMES:
            self.graph._pending_name_roots.append(
                (self.ctx.relpath, "", funcdef.name))
        elif (isinstance(dec, ast.Call) and name == "partial"
                and dec.args
                and terminal_name(dec.args[0]) in JIT_ROOT_NAMES):
            self.graph._pending_name_roots.append(
                (self.ctx.relpath, "", funcdef.name))


class CallGraph:
    def __init__(self, files):
        self.functions: List[FunctionInfo] = []
        self.by_name: Dict[str, List[FunctionInfo]] = {}
        self.by_file: Dict[str, List[FunctionInfo]] = {}
        self.by_node: Dict[ast.AST, FunctionInfo] = {}
        self.imports: Dict[str, Dict[str, str]] = {}
        # module-level reference aliases per file (see visit_Assign)
        self.module_aliases: Dict[str, Dict[str, tuple]] = {}
        self._pending_name_roots = []
        self._pending_lambda_roots = []
        for ctx in files:
            _FileVisitor(ctx, self).visit(ctx.tree)
        self._module_index = self._build_module_index(files)
        self._import_closure: Dict[str, Set[str]] = {}
        self.jit_roots: Set[FunctionInfo] = set()
        for relpath, base, name in self._pending_name_roots:
            self.jit_roots.update(self.resolve_edge(base, name, relpath))
        for lam in self._pending_lambda_roots:
            info = self.by_node.get(lam)
            if info is not None:
                self.jit_roots.add(info)
        self._jit_scope: Optional[Set[FunctionInfo]] = None

    def _register(self, info: FunctionInfo):
        self.functions.append(info)
        self.by_name.setdefault(info.name, []).append(info)
        self.by_file.setdefault(info.ctx.relpath, []).append(info)
        self.by_node[info.node] = info

    @staticmethod
    def _build_module_index(files) -> Dict[str, str]:
        """dotted module name -> relpath for every linted file."""
        out: Dict[str, str] = {}
        for ctx in files:
            mod = ctx.relpath[:-3] if ctx.relpath.endswith(".py") \
                else ctx.relpath
            mod = mod.replace("/", ".")
            if mod.endswith(".__init__"):
                mod = mod[:-len(".__init__")]
            out[mod] = ctx.relpath
        return out

    def _imported_files(self, relpath: str) -> Set[str]:
        """Relpaths of project modules this file imports (any depth in
        the file — function-level imports count)."""
        cached = self._import_closure.get(relpath)
        if cached is not None:
            return cached
        out: Set[str] = set()
        for target in self.imports.get(relpath, {}).values():
            # target may be a module or module.symbol — try both
            for cand in (target, target.rpartition(".")[0]):
                hit = self._module_index.get(cand)
                if hit is not None:
                    out.add(hit)
                    break
        self._import_closure[relpath] = out
        return out

    def resolve_edge(self, base: str, name: str,
                     from_file: str) -> List[FunctionInfo]:
        """Definitions a call ``base.name(...)`` (base '' = bare name)
        may refer to. Resolution is deliberately narrow — a global
        name fallback smears scopes across the package via generic
        method names like ``run``/``update``:

        - same file always wins;
        - bare names may follow a ``from x import name`` alias;
        - ``self.``/``cls.`` methods may live in an imported base-class
          file (PagedDecodeEngine calling ResilientScheduler._pump);
        - ``alias.name`` where alias imports a project MODULE resolves
          inside that module only (``gpt_lib._sample_token``);
        - any other base (an arbitrary object) stays same-file.
        """
        cands = self.by_name.get(name, [])
        local = [c for c in cands if c.ctx.relpath == from_file]
        if local:
            return local
        imports = self.imports.get(from_file, {})
        if base == "":
            target = imports.get(name)
            if target:
                for cand in (target, target.rpartition(".")[0]):
                    hit = self._module_index.get(cand)
                    if hit:
                        return [c for c in cands
                                if c.ctx.relpath == hit]
            return []
        if base in ("self", "cls"):
            imported = self._imported_files(from_file)
            return [c for c in cands if c.ctx.relpath in imported]
        target = imports.get(base)
        if target:
            hit = self._module_index.get(target)
            if hit:
                return [c for c in cands if c.ctx.relpath == hit]
        return []

    def reachable(self,
                  roots: Iterable[FunctionInfo]) -> Set[FunctionInfo]:
        """BFS over call edges + nested definitions."""
        seen: Set[FunctionInfo] = set()
        frontier = [r for r in roots]
        while frontier:
            fn = frontier.pop()
            if fn in seen:
                continue
            seen.add(fn)
            frontier.extend(fn.children)
            for base, name in fn.calls:
                targets = self.resolve_edge(base, name, fn.ctx.relpath)
                if base == "<expr>" and fn.cls:
                    # ``self.kind.step(...)`` calls the MEMBER's step,
                    # never the caller's own class's: that false edge
                    # drags a host scheduler into trace scope
                    targets = [t for t in targets if t.cls != fn.cls]
                frontier.extend(targets)
        return seen

    def jit_scope(self) -> Set[FunctionInfo]:
        """Every function that may execute at trace time."""
        if self._jit_scope is None:
            self._jit_scope = self.reachable(self.jit_roots)
        return self._jit_scope

    def functions_matching(self, pred) -> List[FunctionInfo]:
        return [f for f in self.functions if pred(f)]
