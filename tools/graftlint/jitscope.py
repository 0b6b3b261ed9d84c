"""Shared jit-context resolution for GL001/GL002/GL003.

Answers, per module: WHICH function bodies are traced (decorated with
or passed to ``jax.jit`` / ``pmap`` / ``shard_map`` / ``lax.scan`` and
friends, resolved through ``functools.partial`` and local name
aliases), and WHERE the jit wrap sites are (with their
``static_argnums`` / ``static_argnames`` / ``donate_argnums`` and the
local name the jitted callable is bound to).

Resolution is purely lexical — no imports are executed. Attribute
targets (``self._step``) are not resolved across methods; the rules
built on this are precise within a scope and silent across ones,
which is the right polarity for a CI gate.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

# canonical dotted names that WRAP a callable for device execution
JIT_WRAPPERS = {
    "jax.jit", "jit", "jax.pmap", "pmap",
    "jax.experimental.pjit.pjit", "pjit",
}
# canonical dotted names whose FIRST argument is a traced body
BODY_TAKERS = {
    "jax.shard_map", "shard_map",
    "jax.lax.scan", "lax.scan",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.cond", "lax.cond",
    "jax.checkpoint", "jax.remat",
}
PARTIAL_NAMES = {"functools.partial", "partial"}
# transforms that preserve "the first argument's body is traced"
TRANSPARENT_TRANSFORMS = {
    "jax.grad", "jax.value_and_grad", "jax.vmap",
    "grad", "value_and_grad", "vmap",
}

FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef)


def dotted_name(node: ast.AST) -> str:
    """'jax.jit' for Attribute/Name chains; '' when not a plain
    dotted path (calls, subscripts...)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _import_aliases(tree: ast.Module) -> Dict[str, str]:
    """local name -> canonical dotted prefix, from module imports."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


@dataclasses.dataclass
class JitSite:
    """One jit wrap: ``@jax.jit``-style decorator or ``jax.jit(f)``
    call."""
    node: ast.AST                      # the Call or decorator expr
    line: int
    target: Optional[ast.AST]          # resolved FunctionDef / Lambda
    bound_name: str                    # local name the wrap binds
    scope: ast.AST                     # scope the binding lives in
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    wrapper: str = "jax.jit"


class ModuleJitInfo:
    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.aliases = _import_aliases(tree)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # name -> def/lambda per lexical scope (Module / FunctionDef)
        self.scope_defs: Dict[ast.AST, Dict[str, ast.AST]] = {}
        # name -> aliased-to name per scope (x = y)
        self.scope_aliases: Dict[ast.AST, Dict[str, str]] = {}
        # name -> underlying callable name per scope, through
        # functools.partial (x = partial(f, ...))
        self.scope_partials: Dict[ast.AST, Dict[str, str]] = {}
        self._index_scopes()
        self.sites: List[JitSite] = []
        self.contexts: Set[ast.AST] = set()
        self._find_sites()
        self._close_over_calls()

    # -- scope bookkeeping -------------------------------------------------
    def canon(self, node: ast.AST) -> str:
        """Canonical dotted name with import aliases applied."""
        name = dotted_name(node)
        if not name:
            return ""
        head, _, rest = name.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head

    def enclosing_scope(self, node: ast.AST) -> ast.AST:
        cur = self.parents.get(node)
        while cur is not None and not isinstance(
                cur, FunctionNode + (ast.Module, ast.Lambda)):
            cur = self.parents.get(cur)
        return cur if cur is not None else self.tree

    def enclosing_function(self, node: ast.AST
                           ) -> Optional[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, FunctionNode + (ast.Lambda,)):
                return cur
            cur = self.parents.get(cur)
        return None

    def _index_scopes(self) -> None:
        for node in ast.walk(self.tree):
            # methods and class attributes are NOT bare-name
            # resolvable — indexing them into the enclosing scope
            # would let `foo()` resolve to some class's method `foo`
            if isinstance(self.parents.get(node), ast.ClassDef):
                continue
            if isinstance(node, FunctionNode):
                scope = self.enclosing_scope(node)
                self.scope_defs.setdefault(scope, {})[node.name] = node
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                scope = self.enclosing_scope(node)
                tgt = node.targets[0].id
                val = node.value
                if isinstance(val, ast.Name):
                    self.scope_aliases.setdefault(scope, {})[tgt] = \
                        val.id
                elif isinstance(val, ast.Lambda):
                    self.scope_defs.setdefault(scope, {})[tgt] = val
                elif isinstance(val, ast.Call) and \
                        self.canon(val.func) in PARTIAL_NAMES \
                        and val.args:
                    inner = dotted_name(val.args[0])
                    if inner:
                        self.scope_partials.setdefault(
                            scope, {})[tgt] = inner

    def resolve_callable(self, scope: ast.AST, name: str,
                         depth: int = 0) -> Optional[ast.AST]:
        """Find the def/lambda a bare name refers to, walking alias
        and partial chains and enclosing scopes."""
        if depth > 8 or "." in name:
            return None
        cur: Optional[ast.AST] = scope
        while cur is not None:
            defs = self.scope_defs.get(cur, {})
            if name in defs:
                return defs[name]
            part = self.scope_partials.get(cur, {})
            if name in part:
                return self.resolve_callable(cur, part[name],
                                             depth + 1)
            ali = self.scope_aliases.get(cur, {})
            if name in ali:
                return self.resolve_callable(cur, ali[name],
                                             depth + 1)
            cur = None if cur is self.tree else \
                self.enclosing_scope(cur)
        return None

    # -- site discovery ----------------------------------------------------
    @staticmethod
    def _literal_ints(node: Optional[ast.AST]) -> Tuple[int, ...]:
        if node is None:
            return ()
        if isinstance(node, ast.Constant) and isinstance(
                node.value, int) and not isinstance(node.value, bool):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List)):
            out = []
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(
                        e.value, int):
                    out.append(e.value)
            return tuple(out)
        return ()

    @staticmethod
    def _literal_strs(node: Optional[ast.AST]) -> Tuple[str, ...]:
        if node is None:
            return ()
        if isinstance(node, ast.Constant) and isinstance(
                node.value, str):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(e.value for e in node.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
        return ()

    def _jit_kwargs(self, call: ast.Call) -> dict:
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        return {
            "static_argnums": self._literal_ints(
                kw.get("static_argnums")),
            "static_argnames": self._literal_strs(
                kw.get("static_argnames")),
            "donate_argnums": self._literal_ints(
                kw.get("donate_argnums")),
        }

    def _unwrap_partial(self, node: ast.AST) -> Optional[ast.AST]:
        """partial(f, ...) / bare name / lambda -> resolved callable
        node (for names, via the lexical scope of *node*)."""
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, FunctionNode):
            return node
        if isinstance(node, ast.Call) and node.args and \
                self.canon(node.func) in (
                    PARTIAL_NAMES | TRANSPARENT_TRANSFORMS):
            return self._unwrap_partial(node.args[0])
        name = dotted_name(node)
        if name and "." not in name:
            return self.resolve_callable(
                self.enclosing_scope(node), name)
        return None

    def _decorator_jit(self, dec: ast.AST) -> Optional[dict]:
        """None, or the jit kwargs dict when this decorator jits the
        function (``@jax.jit``, ``@jax.jit(...)``,
        ``@functools.partial(jax.jit, ...)``)."""
        if self.canon(dec) in JIT_WRAPPERS:
            return {"static_argnums": (), "static_argnames": (),
                    "donate_argnums": (), "wrapper": self.canon(dec)}
        if isinstance(dec, ast.Call):
            fn = self.canon(dec.func)
            if fn in JIT_WRAPPERS:
                d = self._jit_kwargs(dec)
                d["wrapper"] = fn
                return d
            if fn in PARTIAL_NAMES and dec.args and \
                    self.canon(dec.args[0]) in JIT_WRAPPERS:
                d = self._jit_kwargs(dec)
                d["wrapper"] = self.canon(dec.args[0])
                return d
        return None

    def _bound_name_of(self, call: ast.Call) -> Tuple[str, ast.AST]:
        """Name an ``x = jax.jit(f)`` assignment binds, and its
        scope."""
        parent = self.parents.get(call)
        if isinstance(parent, ast.Assign) and \
                len(parent.targets) == 1 and \
                isinstance(parent.targets[0], ast.Name):
            return parent.targets[0].id, self.enclosing_scope(parent)
        return "", self.enclosing_scope(call)

    def _find_sites(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, FunctionNode):
                for dec in node.decorator_list:
                    d = self._decorator_jit(dec)
                    if d is not None:
                        self.sites.append(JitSite(
                            node=dec, line=dec.lineno, target=node,
                            bound_name=node.name,
                            scope=self.enclosing_scope(node), **d))
                        self.contexts.add(node)
            elif isinstance(node, ast.Call):
                fn = self.canon(node.func)
                if fn in JIT_WRAPPERS and node.args:
                    target = self._unwrap_partial(node.args[0])
                    d = self._jit_kwargs(node)
                    name, scope = self._bound_name_of(node)
                    self.sites.append(JitSite(
                        node=node, line=node.lineno, target=target,
                        bound_name=name, scope=scope,
                        wrapper=fn, **d))
                    if target is not None:
                        self.contexts.add(target)
                elif fn in BODY_TAKERS and node.args:
                    target = self._unwrap_partial(node.args[0])
                    if target is not None:
                        self.contexts.add(target)
                    # while_loop/fori/cond trace every fn arg
                    for extra in node.args[1:]:
                        t = self._unwrap_partial(extra)
                        if t is not None and isinstance(
                                t, FunctionNode + (ast.Lambda,)):
                            if isinstance(extra, (ast.Name, ast.Lambda,
                                                  ast.Call)):
                                self.contexts.add(t)

    def _close_over_calls(self) -> None:
        """Fixpoint: a local function CALLED from a traced body is
        itself traced (one lexical hop at a time)."""
        for _ in range(10):
            grew = False
            for ctx in list(self.contexts):
                for node in ast.walk(ctx):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Name)):
                        continue
                    tgt = self.resolve_callable(
                        self.enclosing_scope(node), node.func.id)
                    if tgt is not None and tgt not in self.contexts:
                        self.contexts.add(tgt)
                        grew = True
            if not grew:
                return

    # -- queries -----------------------------------------------------------
    def in_context(self, node: ast.AST) -> Optional[ast.AST]:
        """Innermost traced function this node sits inside, if any.
        Walks lexical parents; returns the context function node."""
        cur = node
        while cur is not None:
            if cur in self.contexts:
                return cur
            cur = self.parents.get(cur)
        return None

    def context_params(self, fn: ast.AST,
                       static_names: Sequence[str] = (),
                       static_nums: Sequence[int] = ()) -> Set[str]:
        """Parameter names of a traced function that carry TRACED
        values (static args excluded)."""
        if isinstance(fn, ast.Lambda):
            args = fn.args
        elif isinstance(fn, FunctionNode):
            args = fn.args
        else:
            return set()
        names = [a.arg for a in args.posonlyargs + args.args]
        traced = set(names)
        traced -= set(static_names)
        for i in static_nums:
            if 0 <= i < len(names):
                traced.discard(names[i])
        traced.discard("self")
        traced.discard("cls")
        return traced
