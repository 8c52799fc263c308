"""jit-purity — Python side effects inside functions staged through
``jax.jit`` / ``shard_map`` / the replay fold builders.

A staged function's Python body runs ONCE at trace time: a ``print`` fires
once then never again, a wall-clock read bakes a constant timestamp into the
compiled program, and mutation of closed-over host state (``stats.append``,
``cache[k] = …``) happens at trace time only — silently wrong on every
subsequent cached-compilation call. The replay engine's fold builders
(``fold_resident_slab``, ``_make_tile``, the ``replay_*`` programs) are
all built this way, so the ROADMAP item-3 push of the hot path off the GIL
multiplies the blast radius of one impure fold.
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional, Set

from surge_tpu.analysis.core import Finding, ModuleContext, Rule, register

_STAGERS = frozenset({"jax.jit", "jit", "shard_map", "jax.shard_map",
                      "pjit", "jax.pjit"})
_CLOCK_CALLS = frozenset({"time.time", "time.perf_counter", "time.monotonic",
                          "time.time_ns", "time.perf_counter_ns",
                          "datetime.now", "datetime.datetime.now",
                          "datetime.utcnow", "datetime.datetime.utcnow"})
_MUTATING_METHODS = frozenset({"append", "extend", "insert", "update",
                               "setdefault", "add", "discard", "remove",
                               "pop", "popitem", "clear"})


@register
class JitPurity(Rule):
    id = "jit-purity"
    summary = "Python side effect (print/clock/closed-over mutation) in a staged fn"

    def check_module(self, ctx: ModuleContext) -> Iterator[Finding]:
        module_names = self._module_level_names(ctx)
        # decorator-staged functions
        for fn in ctx.functions():
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                dotted = ctx.dotted(target)
                if dotted in _STAGERS or (
                        isinstance(dec, ast.Call) and dec.args
                        and ctx.dotted(dec.args[0]) in _STAGERS):
                    yield from self._check_staged(ctx, fn, module_names)
                    break
        # call-staged functions: jit(f) / shard_map(f, ...) where f is a
        # def in the same lexical body
        for scope in self._scopes(ctx):
            local_defs = {n.name: n for n in ctx.walk_scope(scope)
                          if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
            for node in ast.walk(scope):
                if not (isinstance(node, ast.Call)
                        and ctx.dotted(node.func) in _STAGERS and node.args):
                    continue
                staged = node.args[0]
                fn = None
                if isinstance(staged, ast.Name):
                    fn = local_defs.get(staged.id)
                if fn is not None:
                    yield from self._check_staged(ctx, fn, module_names)
                elif isinstance(staged, ast.Lambda):
                    yield from self._check_staged(ctx, staged, module_names)

    def _scopes(self, ctx: ModuleContext):
        yield ctx.tree
        yield from ctx.functions()

    def _check_staged(self, ctx: ModuleContext, fn: ast.AST,
                      module_names: Set[str]) -> Iterator[Finding]:
        local = self._local_names(fn)
        name = getattr(fn, "name", "<lambda>")
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                dotted = ctx.dotted(node.func)
                if isinstance(node.func, ast.Name) and node.func.id == "print":
                    yield self.finding(
                        ctx, node,
                        f"`print` inside staged fn `{name}` fires at trace "
                        "time only — use jax.debug.print if it must survive "
                        "compilation")
                elif dotted in _CLOCK_CALLS:
                    yield self.finding(
                        ctx, node,
                        f"wall-clock read inside staged fn `{name}` bakes a "
                        "trace-time constant into the compiled program — pass "
                        "timestamps in as arguments")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _MUTATING_METHODS):
                    base = self._base_name(node.func.value)
                    if base and base not in local and base not in module_names:
                        yield self.finding(
                            ctx, node,
                            f"`{base}.{node.func.attr}(...)` mutates "
                            f"closed-over host state inside staged fn "
                            f"`{name}` — it runs at trace time only (cached "
                            "calls skip it); return the value instead")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript):
                        base = self._base_name(t.value)
                        if base and base not in local and base not in module_names:
                            yield self.finding(
                                ctx, node,
                                f"subscript assignment into closed-over "
                                f"`{base}` inside staged fn `{name}` happens "
                                "at trace time only — cached compilations "
                                "skip it")

    @staticmethod
    def _base_name(node: ast.AST) -> Optional[str]:
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        return node.id if isinstance(node, ast.Name) else None

    @staticmethod
    def _local_names(fn: ast.AST) -> Set[str]:
        """Params + names assigned anywhere inside the staged fn (its own
        state is fair game — purity is about what it closes over)."""
        local: Set[str] = set()
        args = getattr(fn, "args", None)
        if args is not None:
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                local.add(a.arg)
            if args.vararg:
                local.add(args.vararg.arg)
            if args.kwarg:
                local.add(args.kwarg.arg)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    _collect_target_names(t, local)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                _collect_target_names(node.target, local)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                _collect_target_names(node.target, local)
            elif isinstance(node, ast.comprehension):
                _collect_target_names(node.target, local)
            elif isinstance(node, ast.withitem) and node.optional_vars:
                _collect_target_names(node.optional_vars, local)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local.add(node.name)
        return local

    @staticmethod
    def _module_level_names(ctx: ModuleContext) -> Set[str]:
        """Imported module aliases (jnp, np, jax, …): `jnp.add(...)` is not a
        closed-over mutation however suspicious the method name."""
        names: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    names.add(alias.asname or alias.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    names.add(alias.asname or alias.name)
        return names


def _collect_target_names(t: ast.AST, out: Set[str]) -> None:
    if isinstance(t, ast.Name):
        out.add(t.id)
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            _collect_target_names(e, out)
    elif isinstance(t, ast.Starred):
        _collect_target_names(t.value, out)
    # Attribute/Subscript targets mutate existing objects — handled above
