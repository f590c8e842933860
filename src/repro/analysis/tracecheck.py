"""AST-based JAX trace-safety linter over ``src/repro``.

The jitted engine hot paths live or die by staying traceable: a Python
``if`` on a traced value raises ``TracerBoolConversionError`` only on
the code path that reaches it, a stray ``np.*`` on a traced array
silently falls back to host round-trips, and a list captured into a
``RunCache``-keyed predicate breaks compile-cache keying.  This pass
finds those *statically*, before a run trips over them.

Rules
-----
* ``TS101`` — Python ``if``/``while`` on a traced value inside a traced
  context (scan body, jitted function, or anything they call).
  Hashability tests the tracer allows — ``x is None``, ``isinstance``,
  ``len(...)`` (shape-only) — are exempt.
* ``TS102`` — host coercion of a traced value (``.item()``, ``int()``,
  ``float()``, ``bool()``) inside a traced context.
* ``TS103`` — ``np.*`` call on a traced value inside a traced context
  (silent device->host fallback).
* ``TS104`` — non-hashable closure capture (list/dict/set) in a
  callable passed to a cache-keyed sink (``extra_predicates``): the
  engine's ``RunCache`` freezes callables by closure contents, and
  mutable captures either fail to hash or alias stale state.
* ``TS105`` — ``jax.numpy`` import in a module outside the allowlisted
  hot-path set: keeps accidental device code out of host-side layers
  (artifacts, CLIs, docs tooling) as the codebase grows.

Traced contexts are discovered, not annotated: direct functional
operands of ``lax.scan`` / ``cond`` / ``while_loop`` / ``fori_loop`` /
``switch`` and of ``jit`` / ``vmap`` / ``pmap`` / ``shard_map``
(decorator or call form), one level of higher-order propagation (a
function whose *parameter* is scanned marks its callers' arguments,
resolving ``partial``), then transitive closure over same-project
callees via import-alias resolution.  Within a context, traced values
propagate forward from ``jnp.``/``lax.``/``jax.`` producers (and, for
direct scan bodies, from the function's own parameters) through
assignments.

Suppression: append ``# lint: ignore[ts101]`` (comma-separate several
ids) to the offending line, or put ``# lint: skip-file`` near the top
of a file.  Suppressions are per-rule by design — a bare ``ignore``
does not parse.
"""
from __future__ import annotations

import ast
import os
import re

from repro.analysis.report import ERROR, WARN, Finding, LintReport

#: modules (dotted, relative to the lint root package) allowed to import
#: jax.numpy — the compiled hot paths and their direct model/kernel
#: dependencies.  Everything else is host-side by policy (TS105).
JNP_ALLOWLIST = frozenset({
    "repro.core.controller", "repro.core.device", "repro.core.engine",
    "repro.core.frontend",
    "repro.data.pipeline",
    "repro.dse.executor",
    "repro.kernels.flash_attention", "repro.kernels.ops",
    "repro.kernels.ref", "repro.kernels.timing_check",
    "repro.launch.serve", "repro.launch.specs",
    "repro.models.blocks", "repro.models.layers", "repro.models.model",
    "repro.optim.adamw",
    "repro.runtime.compress",
    "repro.serve.step",
    "repro.train.step",
    "repro.verify.explore",
})

#: jax transforms whose functional operand becomes a traced context.
#: value: True when the operand's *parameters* are traced values
#: (loop/branch bodies); False when only jnp-derived locals are (jit &co
#: trace whatever arrays flow in, which we can't see statically).
_TRACERS = {
    "scan": True, "cond": True, "while_loop": True, "fori_loop": True,
    "switch": True, "checkpoint": False, "remat": False,
    "jit": False, "vmap": False, "pmap": False, "shard_map": False,
}

#: sinks whose callable arguments are frozen into cache keys (TS104)
_CACHE_KEYED_KWARGS = frozenset({"extra_predicates"})

_IGNORE_RE = re.compile(r"#\s*lint:\s*ignore\[([a-zA-Z0-9_,\s-]+)\]")
_SKIP_RE = re.compile(r"#\s*lint:\s*skip-file")


# ---------------------------------------------------------------------------
# per-module model
# ---------------------------------------------------------------------------

class Module:
    """One parsed source file: AST + import aliases + function index."""

    def __init__(self, path: str, name: str, tree: ast.Module,
                 source: str):
        self.path = path
        self.name = name                    # dotted module name
        self.tree = tree
        self.lines = source.splitlines()
        self.aliases: dict = {}             # local alias -> dotted module
        self.from_imports: dict = {}        # local name -> (module, attr)
        self.functions: dict = {}           # qualname -> FunctionDef
        self._index()

    def _index(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    full = f"{node.module}.{a.name}"
                    self.from_imports[a.asname or a.name] = (node.module,
                                                             a.name)
                    # `from jax import numpy as jnp` is a module alias too
                    self.aliases.setdefault(a.asname or a.name, full)

        def visit(node, prefix=""):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    self.functions[q] = child
                    visit(child, prefix=f"{q}.")
                elif isinstance(child, ast.ClassDef):
                    visit(child, prefix=f"{prefix}{child.name}.")
                else:
                    visit(child, prefix=prefix)
        visit(self.tree)

    def imports_jnp(self) -> bool:
        for alias, target in self.aliases.items():
            if target in ("jax.numpy", "jax.experimental.pallas"):
                return True
        return any(m == "jax" and a == "numpy"
                   for m, a in self.from_imports.values())

    def jaxish_roots(self) -> set:
        """Local names that are jax-module aliases (jnp, lax, jax, ...)."""
        roots = set()
        for alias, target in self.aliases.items():
            if target == "jax" or target.startswith("jax."):
                roots.add(alias)
        for alias, (mod, attr) in self.from_imports.items():
            if mod == "jax" or mod.startswith("jax."):
                roots.add(alias)
        return roots

    def numpy_roots(self) -> set:
        roots = set()
        for alias, target in self.aliases.items():
            if target == "numpy":
                roots.add(alias)
        return roots

    def suppressed(self, line: int) -> set:
        """Rule ids suppressed on a 1-indexed source line."""
        if 1 <= line <= len(self.lines):
            m = _IGNORE_RE.search(self.lines[line - 1])
            if m:
                return {t.strip().lower() for t in m.group(1).split(",")}
        return set()

    def skip_file(self) -> bool:
        return any(_SKIP_RE.search(ln) for ln in self.lines[:5])


def _module_name(path: str, root: str) -> str:
    rel = os.path.relpath(path, root)
    parts = rel[:-3].split(os.sep)          # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def load_modules(paths, root: str | None = None) -> dict:
    """Parse every ``.py`` under ``paths`` into {dotted name: Module}.

    ``root`` is the directory whose children are top-level packages
    (defaults to the common parent of ``paths`` that makes the first
    path's package importable — for this repo, ``src/``)."""
    files = []
    dir_roots = []
    for p in paths:
        if os.path.isdir(p):
            # the scanned directory IS a package (possibly a namespace
            # package without __init__.py): its parent is the root
            dir_roots.append(os.path.dirname(os.path.abspath(p)))
            for dirpath, _dirs, names in os.walk(p):
                files += [os.path.join(dirpath, n) for n in names
                          if n.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
    files = sorted(set(files))
    if root is None:
        root = dir_roots[0] if dir_roots else _guess_root(files)
    out = {}
    for path in files:
        with open(path) as fh:
            src = fh.read()
        tree = ast.parse(src, filename=path)
        name = _module_name(path, root)
        out[name] = Module(path, name, tree, src)
    return out


def _guess_root(files) -> str:
    """Find the ancestor directory that makes files importable packages
    (walk up while __init__.py is present)."""
    if not files:
        return "."
    d = os.path.dirname(os.path.abspath(files[0]))
    while os.path.exists(os.path.join(d, "__init__.py")):
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return d


# ---------------------------------------------------------------------------
# traced-context discovery
# ---------------------------------------------------------------------------

def _func_operand(node):
    """Resolve a call argument to the *name* of the function it denotes:
    plain name, ``mod.attr``, or ``partial(f, ...)`` -> f."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = node.value
        if isinstance(base, ast.Name):
            return f"{base.id}.{node.attr}"
        return None
    if isinstance(node, ast.Call):
        fn = node.func
        fname = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None)
        if fname == "partial" and node.args:
            return _func_operand(node.args[0])
    return None


def _own_nodes(fn):
    """Walk a function's own body without descending into nested defs
    (nested functions are their own scopes/contexts)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(node))


def _scoped_calls(mod: Module):
    """Yield (scope function or None, Call node) with innermost scopes."""
    for fn in mod.functions.values():
        for node in _own_nodes(fn):
            if isinstance(node, ast.Call):
                yield fn, node
    stack = list(ast.iter_child_nodes(mod.tree))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            yield None, node
        stack.extend(ast.iter_child_nodes(node))


def _tracer_name(call: ast.Call):
    """If ``call`` invokes a jax transform from ``_TRACERS``, return its
    short name, else None."""
    fn = call.func
    name = None
    if isinstance(fn, ast.Attribute):
        name = fn.attr
    elif isinstance(fn, ast.Name):
        name = fn.id
    return name if name in _TRACERS else None


class ContextIndex:
    """Project-wide set of traced-context functions.

    Keys are ``(module name, function qualname)``; the value records
    whether the function's own parameters count as traced (scan/cond
    bodies) or only jnp-derived locals do (jit/vmap operands and
    transitive callees).
    """

    def __init__(self, modules: dict):
        self.modules = modules
        self.contexts: dict = {}            # (mod, qual) -> params_traced
        self._discover_direct()
        self._discover_higher_order()
        self._close_over_callees()

    # -- resolution helpers -------------------------------------------------
    def _resolve_operand(self, mod: Module, node, scope=None, depth=0):
        """Resolve a call-argument AST node to (module, qualname),
        chasing local aliases like ``body = partial(cycle, ...)`` inside
        the enclosing ``scope`` function."""
        name = _func_operand(node)
        key = self._resolve(mod, name) if name else None
        if key is not None or depth > 4:
            return key
        if isinstance(node, ast.Name) and scope is not None:
            for n in _own_nodes(scope):
                if isinstance(n, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == node.id
                        for t in n.targets):
                    return self._resolve_operand(mod, n.value, scope,
                                                 depth + 1)
        return None

    def _resolve(self, mod: Module, name: str):
        """Resolve a (possibly dotted) local name to (module, qualname)."""
        if name is None:
            return None
        if "." in name:
            base, attr = name.split(".", 1)
            target = mod.aliases.get(base)
            if target in self.modules and attr in self.modules[target] \
                    .functions:
                return (target, attr)
            return None
        if name in mod.functions:
            return (mod.name, name)
        # nested qualnames: prefer the innermost match
        for q in mod.functions:
            if q.endswith(f".{name}"):
                return (mod.name, q)
        if name in mod.from_imports:
            m, attr = mod.from_imports[name]
            if m in self.modules and attr in self.modules[m].functions:
                return (m, attr)
        return None

    def _mark(self, key, params_traced: bool):
        if key is None:
            return
        if key not in self.contexts or (params_traced
                                        and not self.contexts[key]):
            self.contexts[key] = params_traced

    # -- passes -------------------------------------------------------------
    def _discover_direct(self):
        for mod in self.modules.values():
            for scope, node in _scoped_calls(mod):
                t = _tracer_name(node)
                if t is None:
                    continue
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    key = self._resolve_operand(mod, arg, scope)
                    if key:
                        self._mark(key, _TRACERS[t])
            for node in ast.walk(mod.tree):
                # decorator form: @jax.jit / @partial(jax.jit, ...)
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        d = dec.func if isinstance(dec, ast.Call) else dec
                        inner = None
                        if isinstance(dec, ast.Call) \
                                and _func_operand(dec.func) == "partial" \
                                and dec.args:
                            d = dec.args[0]
                        t = None
                        if isinstance(d, ast.Attribute):
                            t = d.attr if d.attr in _TRACERS else None
                        elif isinstance(d, ast.Name):
                            t = d.id if d.id in _TRACERS else None
                        if t:
                            key = self._resolve(mod, node.name)
                            self._mark(key, _TRACERS[t])
                        del inner

    def _discover_higher_order(self):
        """One level: a function that scans one of its own parameters is
        a sink — function-valued arguments at its call sites become
        traced contexts (with traced params)."""
        sinks: dict = {}                    # (mod, qual) -> {param index}
        for mod in self.modules.values():
            for qual, fn in mod.functions.items():
                params = [a.arg for a in fn.args.args]
                scanned = set()
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    if _tracer_name(node) is None:
                        continue
                    for arg in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        nm = _func_operand(arg)
                        if nm in params:
                            scanned.add(params.index(nm))
                if scanned:
                    sinks[(mod.name, qual)] = scanned
        for mod in self.modules.values():
            for scope, node in _scoped_calls(mod):
                key = self._resolve_operand(mod, node.func, scope)
                if key not in sinks:
                    continue
                for idx in sinks[key]:
                    if idx < len(node.args):
                        fk = self._resolve_operand(mod, node.args[idx],
                                                   scope)
                        self._mark(fk, True)

    def _close_over_callees(self):
        """Transitive closure: everything a traced context calls (same
        project) is traced too — jit/scan trace through plain calls."""
        work = list(self.contexts)
        seen = set(work)
        while work:
            mname, qual = work.pop()
            mod = self.modules.get(mname)
            fn = mod.functions.get(qual) if mod else None
            if fn is None:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                key = self._resolve_operand(mod, node.func, fn)
                if key and key not in seen:
                    seen.add(key)
                    self.contexts[key] = False   # params not traced
                    work.append(key)


# ---------------------------------------------------------------------------
# in-context dataflow + rule checks
# ---------------------------------------------------------------------------

def _names_in(node) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class _FunctionLint(ast.NodeVisitor):
    """Forward traced-value propagation + rule checks for one function."""

    def __init__(self, mod: Module, fn, params_traced: bool,
                 jax_roots: set, np_roots: set):
        self.mod = mod
        self.fn = fn
        self.jax_roots = jax_roots
        self.np_roots = np_roots
        self.traced: set = set()
        if params_traced:
            self.traced |= {a.arg for a in fn.args.args
                            if a.arg not in ("self", "cls")}
        self.findings: list = []

    # -- traced-expression predicate ---------------------------------------
    def is_traced(self, node) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in self.traced:
                return True
            if isinstance(n, ast.Call):
                root = _root_name(n.func)
                if root in self.jax_roots:
                    return True
        return False

    def _exempt_test(self, test) -> bool:
        """Tracer-tolerated tests: identity vs None, isinstance, len()
        (shape is static under trace), attribute flags (self.x)."""
        if isinstance(test, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops):
            return True
        for n in ast.walk(test):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id in ("isinstance", "len", "hasattr",
                                      "getattr", "callable"):
                return True
        return False

    def emit(self, rule, node, msg, severity=ERROR):
        line = getattr(node, "lineno", 0)
        if rule.lower() in self.mod.suppressed(line):
            return
        self.findings.append(Finding(
            rule=rule, severity=severity, message=msg,
            target=self.mod.name, path=self.mod.path, line=line))

    # -- statement flow -----------------------------------------------------
    def _assign_targets(self, target):
        if isinstance(target, ast.Name):
            yield target.id
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                yield from self._assign_targets(el)
        elif isinstance(target, ast.Starred):
            yield from self._assign_targets(target.value)

    def visit_Assign(self, node):
        self.generic_visit(node)
        if self.is_traced(node.value):
            for t in node.targets:
                self.traced.update(self._assign_targets(t))

    def visit_AugAssign(self, node):
        self.generic_visit(node)
        if self.is_traced(node.value) and isinstance(node.target, ast.Name):
            self.traced.add(node.target.id)

    def visit_AnnAssign(self, node):
        self.generic_visit(node)
        if node.value is not None and self.is_traced(node.value) \
                and isinstance(node.target, ast.Name):
            self.traced.add(node.target.id)

    def visit_For(self, node):
        # iterating a traced array is itself suspect, but the common
        # legitimate pattern is `for i in range(static)`; only propagate
        if self.is_traced(node.iter):
            self.traced.update(self._assign_targets(node.target))
        self.generic_visit(node)

    # -- rules --------------------------------------------------------------
    def visit_If(self, node):
        if self.is_traced(node.test) and not self._exempt_test(node.test):
            self.emit("TS101", node,
                      "Python `if` on a traced value inside a traced "
                      "context — use jnp.where / lax.cond (or hoist the "
                      "decision out of the jitted region)")
        self.generic_visit(node)

    def visit_While(self, node):
        if self.is_traced(node.test) and not self._exempt_test(node.test):
            self.emit("TS101", node,
                      "Python `while` on a traced value inside a traced "
                      "context — use lax.while_loop")
        self.generic_visit(node)

    def visit_Call(self, node):
        fn = node.func
        # TS102: int()/float()/bool() on a traced expression
        if isinstance(fn, ast.Name) and fn.id in ("int", "float", "bool") \
                and node.args and self.is_traced(node.args[0]):
            self.emit("TS102", node,
                      f"`{fn.id}()` coerces a traced value to host — "
                      "fails under jit; keep it as a jnp array or "
                      "compute it outside the traced region")
        # TS102: .item()
        if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                and self.is_traced(fn.value):
            self.emit("TS102", node,
                      "`.item()` on a traced value — host sync; fails "
                      "under jit")
        # TS103: np.* on traced args
        root = _root_name(fn)
        if root in self.np_roots and (
                any(self.is_traced(a) for a in node.args)
                or any(self.is_traced(kw.value) for kw in node.keywords)):
            self.emit("TS103", node,
                      "`np.*` call on a traced value — silently leaves "
                      "the device (or fails under jit); use jnp")
        self.generic_visit(node)

    # do not descend into nested defs: they are linted as their own
    # contexts if reachable
    def visit_FunctionDef(self, node):
        if node is self.fn:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def run(self) -> list:
        self.visit_FunctionDef(self.fn)
        return self.findings


def _root_name(node):
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


# ---------------------------------------------------------------------------
# TS104: non-hashable captures in cache-keyed callables
# ---------------------------------------------------------------------------

_MUTABLE_NODES = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                  ast.SetComp)


def _check_cache_keyed(mod: Module) -> list:
    findings = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg not in _CACHE_KEYED_KWARGS:
                continue
            for f in _callables_in(kw.value, mod):
                findings += _mutable_captures(mod, f, kw.arg)
    return findings


def _callables_in(node, mod: Module):
    """Lambdas / resolvable function defs inside a sink argument."""
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Lambda):
            out.append(n)
        elif isinstance(n, ast.Name) and n.id in mod.functions:
            out.append(mod.functions[n.id])
    return out


def _mutable_captures(mod: Module, fn, sink: str) -> list:
    """Flag free variables of ``fn`` bound to list/dict/set literals in
    an enclosing scope, and mutable default arguments."""
    findings = []
    args = fn.args
    params = {a.arg for a in list(args.args) + list(args.kwonlyargs)}
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    local = set(params)
    for n in ast.walk(fn):
        if isinstance(n, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            tgt = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in tgt:
                for nm in ast.walk(t):
                    if isinstance(nm, ast.Name):
                        local.add(nm.id)
    free = set()
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id not in local:
                free.add(n.id)
    # mutable defaults are captured into the callable's identity too
    for d in list(args.defaults) + [d for d in args.kw_defaults if d]:
        if isinstance(d, _MUTABLE_NODES):
            findings.append(Finding(
                rule="TS104", severity=ERROR, target=mod.name,
                path=mod.path, line=d.lineno,
                message=f"mutable default argument in a callable passed "
                        f"to cache-keyed sink `{sink}` — unhashable / "
                        "aliases state across cached runs"))
    # free names assigned mutable literals anywhere in the module
    mutable_names = set()
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.Assign) and isinstance(n.value,
                                                    _MUTABLE_NODES):
            for t in n.targets:
                if isinstance(t, ast.Name):
                    mutable_names.add(t.id)
    for nm in sorted(free & mutable_names):
        line = getattr(fn, "lineno", 0)
        if "ts104" in mod.suppressed(line):
            continue
        findings.append(Finding(
            rule="TS104", severity=ERROR, target=mod.name, path=mod.path,
            line=line,
            message=f"callable passed to cache-keyed sink `{sink}` "
                    f"captures `{nm}`, which is bound to a mutable "
                    "list/dict/set — RunCache freezes closures by value "
                    "and mutables are unhashable"))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_paths(paths, root: str | None = None,
               allowlist=JNP_ALLOWLIST) -> LintReport:
    """Run the trace-safety pass over files/directories."""
    modules = load_modules(paths, root=root)
    report = LintReport(target="trace-safety", meta={
        "modules": len(modules),
        "paths": [str(p) for p in paths]})
    index = ContextIndex(modules)
    report.meta["traced_contexts"] = sorted(
        f"{m}:{q}" for (m, q) in index.contexts)

    for mod in modules.values():
        if mod.skip_file():
            continue
        # TS105: jnp import policy
        if mod.imports_jnp() and mod.name not in allowlist \
                and not any(mod.name.startswith(a + ".")
                            for a in allowlist):
            if "ts105" not in mod.suppressed(1):
                report.add(Finding(
                    rule="TS105", severity=WARN, target=mod.name,
                    path=mod.path, line=1,
                    message="module imports jax.numpy but is not in the "
                            "hot-path allowlist (repro.analysis."
                            "tracecheck.JNP_ALLOWLIST) — host-side "
                            "layers should stay numpy-only"))
        report.extend(_check_cache_keyed(mod))

    jax_roots = {}
    for (mname, qual), params_traced in sorted(index.contexts.items()):
        mod = modules.get(mname)
        fn = mod.functions.get(qual) if mod else None
        if fn is None or mod.skip_file():
            continue
        if mname not in jax_roots:
            jax_roots[mname] = (mod.jaxish_roots(), mod.numpy_roots())
        jx, npx = jax_roots[mname]
        lint = _FunctionLint(mod, fn, params_traced, jx, npx)
        report.extend(lint.run())
    return report
