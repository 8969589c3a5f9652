"""Tracer-safety rules (PUR001–PUR004).

Inside a traced context — a function decorated with ``jax.jit`` (directly or
via ``functools.partial(jax.jit, static_argnames=...)``), a body passed to
``jax.lax.map``/``scan``/``fori_loop``/``while_loop``/``cond``/``switch``,
``shard_map``, ``vmap``/``pmap``, or a Pallas kernel — Python-level control
flow and host casts silently see tracers, not values.

PUR001  Python ``if``/``while`` on a traced value (use ``jnp.where`` /
        ``lax.cond`` / ``pl.when``)
PUR002  host cast of a traced value: ``float()``/``int()``/``bool()``/
        ``np.*`` / ``.item()`` / ``.tolist()``
PUR003  Python randomness or wall-clock time inside traced code
        (``random.*``, ``np.random.*``, ``time.*``) — traces once, then
        is frozen into the compiled program
PUR004  ``assert`` on a traced value

Staticness is tracked per function: parameters are traced except those
named in ``static_argnames`` or bound by ``functools.partial``; shape
metadata (``.shape``/``.ndim``/``.dtype``/``.size``, ``len()``) is static;
taint propagates through assignments.  ``pl.program_id``/``num_programs``
produce traced values.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set

from repro.analysis.core import Finding, Module, dotted_name

STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize", "sharding"}
STATIC_CALLS = {"len", "isinstance", "hasattr", "getattr", "range", "min",
                "max", "abs", "sum", "tuple", "list", "sorted", "enumerate",
                "zip", "math.sqrt", "math.ceil", "math.floor", "math.log",
                "math.log2", "cdiv", "pl.cdiv"}
HOST_CASTS = {"float", "int", "bool", "complex"}
HOST_CAST_METHODS = {"item", "tolist", "numpy"}
IMPURE_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.",
                   "datetime.")
TRACED_PRODUCERS = {"pl.program_id", "pl.num_programs", "pltpu.prng_seed"}
LAX_HOF = {"jax.lax.map", "lax.map", "jax.lax.scan", "lax.scan",
           "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.while_loop",
           "lax.while_loop", "jax.lax.cond", "lax.cond", "jax.lax.switch",
           "lax.switch", "jax.lax.associative_scan", "lax.associative_scan"}
VMAPPERS = {"jax.vmap", "vmap", "jax.pmap", "pmap", "shard_map",
            "jax.shard_map"}


def _jit_static_argnames(deco: ast.expr) -> Optional[Set[str]]:
    """Static argnames if this decorator makes the function jitted."""
    d = dotted_name(deco)
    if d in ("jax.jit", "jit"):
        return set()
    if isinstance(deco, ast.Call):
        fn = dotted_name(deco.func)
        if fn in ("jax.jit", "jit"):
            return _static_names_from_kw(deco.keywords)
        if fn in ("functools.partial", "partial") and deco.args:
            inner = dotted_name(deco.args[0])
            if inner in ("jax.jit", "jit"):
                return _static_names_from_kw(deco.keywords)
    return None


def _static_names_from_kw(keywords: Sequence[ast.keyword]) -> Set[str]:
    out: Set[str] = set()
    for kw in keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                    out.add(sub.value)
    return out


def _partial_bindings(mod: Module, fn_name: str) -> Optional[Set[str]]:
    """Names statically bound when fn is only invoked via functools.partial.

    Returns None if the function is never partial-bound.  Positional
    partial args bind the first k parameters; keyword args bind by name.
    """
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if dotted_name(node.func) not in ("functools.partial", "partial"):
            continue
        if not node.args or dotted_name(node.args[0]) != fn_name:
            continue
        bound_kw = {kw.arg for kw in node.keywords if kw.arg}
        return {"__npos__%d" % (len(node.args) - 1)} | bound_kw
    return None


class _FnCheck(ast.NodeVisitor):
    def __init__(self, mod: Module, fn: ast.FunctionDef,
                 static_params: Set[str], is_kernel: bool):
        self.mod = mod
        self.fn = fn
        self.is_kernel = is_kernel
        self.findings: List[Finding] = []
        args = fn.args
        all_params = [a.arg for a in args.posonlyargs + args.args
                      + args.kwonlyargs]
        self.traced: Set[str] = {p for p in all_params
                                 if p not in static_params
                                 and p not in ("self", "cls")}

    # -- staticness -------------------------------------------------------

    def is_static(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id not in self.traced
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return True
            return self.is_static(node.value)
        if isinstance(node, ast.Subscript):
            # shape[0] is static; x[0] of a traced x is traced
            return self.is_static(node.value)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return all(self.is_static(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return all(self.is_static(v) for v in
                       list(node.keys or []) + list(node.values or [])
                       if v is not None)
        if isinstance(node, ast.BinOp):
            return self.is_static(node.left) and self.is_static(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_static(node.operand)
        if isinstance(node, ast.BoolOp):
            return all(self.is_static(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a static structural check
            # even on a traced name (tracers are never None)
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops) \
                    and all(isinstance(c, ast.Constant) and c.value is None
                            for c in node.comparators):
                return True
            return self.is_static(node.left) and all(
                self.is_static(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return all(self.is_static(n) for n in
                       (node.test, node.body, node.orelse))
        if isinstance(node, ast.Call):
            fn = dotted_name(node.func) or ""
            if fn in TRACED_PRODUCERS or fn.endswith(".program_id") \
                    or fn.endswith(".num_programs"):
                return False
            # a method call on a traced receiver (x.sum(), q.astype(...))
            # produces a traced value
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr not in STATIC_ATTRS \
                    and not self.is_static(node.func.value):
                return False
            if fn == "len" or fn in STATIC_CALLS:
                return all(self.is_static(a) for a in node.args)
            return all(self.is_static(a) for a in node.args) and all(
                self.is_static(kw.value) for kw in node.keywords)
        if isinstance(node, ast.JoinedStr):
            return True
        return True  # unknown constructs: assume static (precision first)

    # -- taint propagation + checks ---------------------------------------

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        tainted = not self.is_static(node.value)
        for t in node.targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    if tainted:
                        self.traced.add(sub.id)
                    else:
                        self.traced.discard(sub.id)

    def visit_If(self, node: ast.If) -> None:
        if not self.is_static(node.test):
            self._flag("PUR001", node.test.lineno,
                       "Python `if` on a traced value inside traced code",
                       "use jnp.where / lax.cond"
                       + (" / pl.when" if self.is_kernel else ""))
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if not self.is_static(node.test):
            self._flag("PUR001", node.test.lineno,
                       "Python `while` on a traced value inside traced code",
                       "use lax.while_loop")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        if not self.is_static(node.test):
            self._flag("PUR004", node.test.lineno,
                       "`assert` on a traced value inside traced code",
                       "assert on static shapes/dtypes only, or use "
                       "checkify/debug.check")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = dotted_name(node.func)
        if fn:
            if fn in HOST_CASTS and node.args \
                    and not self.is_static(node.args[0]):
                self._flag("PUR002", node.lineno,
                           f"host-side `{fn}()` cast of a traced value",
                           "keep it on-device (jnp) or hoist out of the "
                           "traced region")
            elif (fn.startswith(("np.", "numpy."))
                  and not fn.startswith(IMPURE_PREFIXES)
                  and any(not self.is_static(a) for a in node.args)):
                self._flag("PUR002", node.lineno,
                           f"`{fn}` applied to a traced value forces a "
                           "host transfer",
                           "use the jnp equivalent inside traced code")
            if fn.startswith(IMPURE_PREFIXES):
                self._flag("PUR003", node.lineno,
                           f"impure host call `{fn}` inside traced code is "
                           "frozen at trace time",
                           "pass PRNG keys / timestamps in as arguments")
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in HOST_CAST_METHODS \
                and not self.is_static(node.func.value):
            self._flag("PUR002", node.lineno,
                       f"`.{node.func.attr}()` on a traced value",
                       "hoist host materialization out of the traced region")
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if node is not self.fn:
            return  # nested defs get their own context if traced
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def _flag(self, rule: str, line: int, msg: str, hint: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.mod.path, line=line,
            message=f"{msg} (in `{self.fn.name}`)", hint=hint))


def _traced_functions(mod: Module):
    """Yield (FunctionDef, static_param_names, is_kernel) for traced defs."""
    by_name = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, node)

    seen: Set[str] = set()

    # 1) jit-decorated functions
    for node in by_name.values():
        for deco in node.decorator_list:
            statics = _jit_static_argnames(deco)
            if statics is not None:
                seen.add(node.name)
                yield node, _expand_static(node, statics), False
                break

    # 2) bodies handed to lax HOFs / vmap / shard_map, and Pallas kernels
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = dotted_name(node.func) or ""
        is_pallas = fn.endswith("pallas_call")
        is_jit_call = fn in ("jax.jit", "jit")
        if not (fn in LAX_HOF or fn in VMAPPERS or is_pallas
                or is_jit_call):
            continue
        for arg in node.args[:1] if (is_pallas or is_jit_call) else node.args:
            target, bound = _resolve_fn_arg(arg)
            if target is None or target not in by_name:
                continue
            if target in seen:
                continue
            seen.add(target)
            fndef = by_name[target]
            statics = set(bound)
            if is_jit_call:
                statics |= _static_names_from_kw(node.keywords)
            pb = _partial_bindings(mod, target)
            if pb:
                statics |= _positional_expand(fndef, pb)
            if is_pallas:
                # keyword-only params of a kernel are always static config
                statics |= {a.arg for a in fndef.args.kwonlyargs}
            yield fndef, _expand_static(fndef, statics), is_pallas


def _resolve_fn_arg(arg: ast.expr):
    """(function_name, statically_bound_param_markers) for a callable arg."""
    if isinstance(arg, ast.Name):
        return arg.id, set()
    if isinstance(arg, ast.Call) \
            and dotted_name(arg.func) in ("functools.partial", "partial") \
            and arg.args and isinstance(arg.args[0], ast.Name):
        bound = {"__npos__%d" % (len(arg.args) - 1)}
        bound |= {kw.arg for kw in arg.keywords if kw.arg}
        return arg.args[0].id, bound
    return None, set()


def _positional_expand(fndef: ast.FunctionDef, markers: Set[str]) -> Set[str]:
    out = {m for m in markers if not m.startswith("__npos__")}
    npos = max((int(m[len("__npos__"):]) for m in markers
                if m.startswith("__npos__")), default=0)
    params = [a.arg for a in fndef.args.posonlyargs + fndef.args.args]
    out.update(params[:npos])
    return out


def _expand_static(fndef: ast.FunctionDef, statics: Set[str]) -> Set[str]:
    statics = _positional_expand(fndef, statics)
    # static_argnums indices arrive as strings of digits from kw parsing;
    # map any pure-digit entries onto parameter names
    params = [a.arg for a in fndef.args.posonlyargs + fndef.args.args]
    for s in list(statics):
        if s.isdigit() and int(s) < len(params):
            statics.add(params[int(s)])
    return statics


def check(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    for fndef, statics, is_kernel in _traced_functions(mod):
        chk = _FnCheck(mod, fndef, statics, is_kernel)
        for stmt in fndef.body:
            chk.visit(stmt)
        findings.extend(chk.findings)
    return findings
