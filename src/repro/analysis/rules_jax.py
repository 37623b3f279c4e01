"""JX001–JX003: JAX tracing contracts on the jit-reachable set.

* **JX001 tracer-leak** — ``.item()`` / ``.tolist()``, ``bool()/int()/
  float()`` on traced values, and ``if``/``while`` branching on array
  expressions: all raise ``ConcretizationTypeError`` (or silently constant-
  fold) under ``jax.jit``.
* **JX002 host-numpy-in-jit** — ``np.*`` calls fed traced data inside jitted
  code pull the value to host per call (or fail to trace); use ``jnp``.
* **JX003 impure-jit** — side effects in a jitted python body run once per
  *compile*, not per call: printing, wall-clock reads, host RNG, host
  profiler spans (``repro.obs.metrics.span``,
  ``jax.profiler.TraceAnnotation``), ``global`` / ``self`` mutation and
  module-global mutation are almost always bugs (the deliberate
  compile-counter exception carries a waiver).

Whether a value is "traced" is approximated by taint: function parameters
(minus every name seen in a ``static_argnames``) and anything assigned from
them or from a ``jax.*`` call.  Host-side numpy on *constants* at trace time
is idiomatic constant folding and stays clean.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set

from .findings import Finding
from .project import ModuleInfo, dotted_name
from .reachability import ReachableSet, Unit

_HOST_CASTS = ("bool", "int", "float")
_SHAPE_SAFE_ATTRS = {"shape", "ndim", "dtype", "size"}
_LEAK_METHODS = {"item", "tolist"}
_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.process_time", "time.sleep", "time.monotonic_ns",
               "time.perf_counter_ns"}
_SPAN_CALLS = {"repro.obs.metrics.span", "jax.profiler.TraceAnnotation"}
_GLOBAL_MUTATORS = {"inc", "dec", "append", "add", "update", "extend",
                    "insert", "remove", "clear", "setdefault", "pop",
                    "reset"}


#: parameter annotations that mark a *host* value even inside jitted code:
#: python scalars are static under jit, and the repo's ``*Config`` /
#: ``*Spec`` dataclasses carry static hyperparameters (their pytree
#: registrations put every field in ``meta_fields``)
_STATIC_ANNOTATIONS = {"int", "bool", "str"}


def _params(node: ast.AST) -> List[str]:
    a = node.args
    names = []
    for p in (a.posonlyargs + a.args + a.kwonlyargs):
        ann = getattr(p, "annotation", None)
        if ann is not None:
            src = ast.unparse(ann)
            if src in _STATIC_ANNOTATIONS or src.endswith("Config") \
                    or src.endswith("Spec"):
                continue
        names.append(p.arg)
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _taint(unit: Unit, static_names: frozenset) -> Set[str]:
    """Names plausibly bound to traced arrays inside the unit's subtree."""
    tainted: Set[str] = set()
    for node in ast.walk(unit.node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            tainted.update(p for p in _params(node)
                           if p not in static_names)

    def refs_taint(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
            if isinstance(n, ast.Call):
                d = dotted_name(n.func, unit.mod) or ""
                if d.startswith("jax."):
                    return True
        return False

    stmts = [n for n in ast.walk(unit.node)
             if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                               ast.For, ast.withitem))]
    stmts.sort(key=lambda n: getattr(n, "lineno", 0))
    for _ in range(2):                       # cheap fixpoint, 2 passes
        for st in stmts:
            if isinstance(st, ast.For):
                src, dsts = st.iter, [st.target]
            elif isinstance(st, ast.withitem):
                src = st.context_expr
                dsts = [st.optional_vars] if st.optional_vars else []
            else:
                src = st.value
                dsts = st.targets if isinstance(st, ast.Assign) \
                    else [st.target]
            if src is None or not refs_taint(src):
                continue
            for d in dsts:
                for n in ast.walk(d):
                    if isinstance(n, ast.Name):
                        tainted.add(n.id)
    return tainted


def _is_tainted(expr: ast.AST, tainted: Set[str], mod: ModuleInfo) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
        if isinstance(n, ast.Call):
            d = dotted_name(n.func, mod) or ""
            if d.startswith("jax."):
                return True
    return False


def _shape_safe(expr: ast.AST) -> bool:
    """True when the expression reads static metadata (shape/ndim/len)."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr in _SHAPE_SAFE_ATTRS:
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id == "len":
            return True
    return False


def _jax_call_in(expr: ast.AST, mod: ModuleInfo) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            d = dotted_name(n.func, mod) or ""
            if d.startswith("jax."):
                return True
    return False


def check_jax_rules(reachable: ReachableSet,
                    rules: Iterable[str]) -> List[Finding]:
    rules = set(rules)
    raw: List[Finding] = []
    for unit in reachable:
        tainted = _taint(unit, reachable.static_param_names)
        raw.extend(_check_unit(unit, tainted, rules))
    # nested defs can appear both inside a parent unit and as their own
    # root — report each site once
    seen, out = set(), []
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.col, f.code)):
        key = (f.code, f.path, f.line, f.col)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def _check_unit(unit: Unit, tainted: Set[str],
                rules: Set[str]) -> List[Finding]:
    mod, out = unit.mod, []

    def emit(code: str, node: ast.AST, msg: str) -> None:
        if code in rules:
            out.append(Finding(code=code, path=mod.path, line=node.lineno,
                               col=node.col_offset,
                               message=f"{msg} [jit-reachable via "
                                       f"`{unit.name}`]"))

    for node in ast.walk(unit.node):
        # -- JX001: tracer leaks ------------------------------------------
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _LEAK_METHODS \
                    and _is_tainted(node.func.value, tainted, mod):
                emit("JX001", node,
                     f"`.{node.func.attr}()` on a traced value pulls it to "
                     f"host (ConcretizationTypeError under jit)")
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in _HOST_CASTS and node.args \
                    and _is_tainted(node.args[0], tainted, mod) \
                    and not _shape_safe(node.args[0]):
                emit("JX001", node,
                     f"`{node.func.id}()` on a traced value concretizes it; "
                     f"keep it an array (jnp) or hoist out of the jit")
        if isinstance(node, (ast.If, ast.While)) \
                and _jax_call_in(node.test, mod):
            kw = "if" if isinstance(node, ast.If) else "while"
            emit("JX001", node,
                 f"`{kw}` on an array expression branches on a traced "
                 f"value; use jnp.where / lax.cond / lax.while_loop")

        # -- JX002: host numpy on traced data -----------------------------
        if isinstance(node, ast.Call):
            d = dotted_name(node.func, mod) or ""
            if d.startswith("numpy.") and not d.startswith("numpy.random.") \
                    and any(_is_tainted(a, tainted, mod)
                            for a in list(node.args)
                            + [k.value for k in node.keywords]):
                emit("JX002", node,
                     f"host numpy call `{d}` on traced data inside jitted "
                     f"code; use the jnp equivalent")

        # -- JX003: impurity ----------------------------------------------
        if isinstance(node, ast.Call):
            d = dotted_name(node.func, mod) or ""
            if isinstance(node.func, ast.Name) and node.func.id == "print":
                emit("JX003", node,
                     "`print` in a jitted body runs only on trace; use "
                     "jax.debug.print for per-call output")
            elif d in _TIME_CALLS:
                emit("JX003", node,
                     f"wall-clock read `{d}` inside jitted code executes "
                     f"once per compile, not per call")
            elif d in _SPAN_CALLS:
                emit("JX003", node,
                     f"host span `{d}` inside jitted code records once per "
                     f"compile, not per call; put it around the jitted "
                     f"call on the host")
            elif d.startswith("numpy.random.") or d.startswith("random."):
                emit("JX003", node,
                     f"host RNG `{d}` inside jitted code is baked in at "
                     f"trace time; thread a jax.random key instead")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _GLOBAL_MUTATORS \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in mod.global_names:
                emit("JX003", node,
                     f"mutation of module global "
                     f"`{node.func.value.id}.{node.func.attr}()` inside "
                     f"jitted code happens per compile, not per call")
        if isinstance(node, ast.Global):
            emit("JX003", node,
                 f"`global {', '.join(node.names)}` inside jitted code: "
                 f"writes happen per compile, not per call")
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    emit("JX003", node,
                         f"`self.{t.attr} = …` inside jitted code mutates "
                         f"state per compile, not per call")
                elif isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id in mod.global_names:
                    emit("JX003", node,
                         f"subscript write to module global "
                         f"`{t.value.id}[…]` inside jitted code happens "
                         f"per compile, not per call")
    return out
