"""JAX tracing-hygiene passes (DESIGN.md §12.3b).

A *traced function* is one that runs under ``jax.jit``: decorated with
``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)``, or wrapped at module
level (``fn = jax.jit(g, static_argnums=...)``). Inside one, Python-level
control flow runs at *trace time* against abstract tracers, so:

* ``jit-assert`` — a bare ``assert`` on traced values either always passes
  (trace-time truthiness of an abstract value raises) or silently
  disappears under ``-O``; invariants on device values belong in
  ``checkify`` or host-side wrappers. Any ``assert`` in a traced function
  is flagged.
* ``jit-python-branch`` — ``if``/``while`` on a traced value raises
  ``TracerBoolConversionError`` at trace time — but only sometimes (dead
  branches under concrete shapes hide it). Branching on *static metadata*
  is fine and idiomatic: attributes named in :data:`STATIC_ATTRS`
  (``DeviceIndex.num_nodes`` and friends are aux_data of a registered
  pytree, Python ints at trace time) are allowed; direct branches on array
  parameters are flagged.
* ``jit-host-sync`` — ``.item()`` / ``np.asarray`` / ``jax.device_get`` /
  ``block_until_ready`` inside a traced function forces a trace-time
  round-trip (or fails outright); host materialization belongs in the
  host wrapper.
* ``jit-unhashable-static`` — at a call site of a jitted function with
  ``static_argnums``, passing a list/dict/set/``np.array(...)`` in a
  static position recompiles per call (or raises on unhashable); static
  args must be hashable scalars/tuples.
* ``jit-mutable-closure`` — a traced function reading a module-level
  mutable (list/dict/set) global: the value is baked in at trace time,
  later mutation silently diverges from the compiled program.
* ``hot-path-transfer`` — host<->device transfer calls
  (``jax.device_get`` / ``jax.device_put`` / ``.item()`` /
  ``block_until_ready``) in modules on the configured hot-path list
  (executor/planner/batch_query): every transfer there is either a
  deliberate, measured sync point (suppress it inline with a reason) or a
  latency bug.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .core import (AnalysisConfig, Finding, Module, iter_symbols,
                   make_finding)

#: Attribute names that are static (Python-int) metadata at trace time —
#: aux_data of registered pytrees (DeviceIndex & co), safe to branch on.
STATIC_ATTRS = frozenset({
    "num_nodes", "n", "t_max", "max_node_entries", "max_vert_entries",
    "num_versions", "max_stratum_nodes", "max_live_nodes", "ndim", "dtype",
    "shape",
})

_HOST_SYNC_DOTTED = {
    "jax.device_get": "jax.device_get",
    "jax.device_put": "jax.device_put",
    "np.asarray": "np.asarray",
    "np.array": "np.array",
    "numpy.asarray": "numpy.asarray",
    "numpy.array": "numpy.array",
}

_TRANSFER_DOTTED = {"jax.device_get", "jax.device_put"}
_TRANSFER_ATTRS = {"item", "block_until_ready"}


def _dotted(node: ast.AST) -> str | None:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` /
    ``functools.partial(jax.jit, ...)``."""
    d = _dotted(node)
    if d in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        f = _dotted(node.func)
        if f in ("partial", "functools.partial") and node.args:
            return _is_jit_expr(node.args[0])
        # jax.jit(g, ...) used as a decorator factory result
        if f in ("jax.jit", "jit"):
            return True
    return False


def _jit_static_argnums(call: ast.Call) -> tuple[int, ...] | None:
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for el in v.elts:
                    if (isinstance(el, ast.Constant)
                            and isinstance(el.value, int)):
                        out.append(el.value)
                return tuple(out)
    return None


def collect_traced(module: Module) -> dict[str, ast.FunctionDef]:
    """Functions that run under jit in this module: decorated defs, plus
    defs wrapped by a module-level ``name = jax.jit(def_name, ...)``."""
    by_name: dict[str, ast.FunctionDef] = {}
    traced: dict[str, ast.FunctionDef] = {}
    for symbol, node in iter_symbols(module.tree):
        if isinstance(node, ast.FunctionDef):
            by_name[node.name] = node
            if any(_is_jit_expr(d) for d in node.decorator_list):
                traced[symbol] = node
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func) in ("jax.jit", "jit") and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Name) and arg.id in by_name:
                traced.setdefault(arg.id, by_name[arg.id])
    return traced


def _param_names(fn: ast.FunctionDef) -> set[str]:
    a = fn.args
    names = [p.arg for p in
             (a.posonlyargs + a.args + a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def pass_jax_hygiene(module: Module,
                     config: AnalysisConfig) -> Iterable[Finding]:
    findings: list[Finding] = []
    traced = collect_traced(module)
    hot = any(module.dotted == m or module.dotted.startswith(m + ".")
              for m in config.hot_path_modules)

    # -- per traced function ---------------------------------------------
    for symbol, fn in traced.items():
        params = _param_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assert):
                findings.append(make_finding(
                    module, "jit-assert", node,
                    f"bare assert inside traced function {fn.name!r}: "
                    "on tracers it raises at trace time (or vanishes "
                    "under -O); validate in the host wrapper or use "
                    "checkify", symbol=symbol))
            elif isinstance(node, (ast.If, ast.While)):
                off = _offending_branch_expr(node.test, params)
                if off is not None:
                    findings.append(make_finding(
                        module, "jit-python-branch", node,
                        f"Python branch on {off!r} inside traced function "
                        f"{fn.name!r}: traced values need lax.cond/"
                        "lax.select; branching is only safe on static "
                        f"metadata attrs {sorted(STATIC_ATTRS)[:4]}...",
                        symbol=symbol))
            elif isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in _HOST_SYNC_DOTTED:
                    findings.append(make_finding(
                        module, "jit-host-sync", node,
                        f"{_HOST_SYNC_DOTTED[d]} inside traced function "
                        f"{fn.name!r} forces host materialization at "
                        "trace time; hoist it into the host wrapper",
                        symbol=symbol))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _TRANSFER_ATTRS):
                    findings.append(make_finding(
                        module, "jit-host-sync", node,
                        f".{node.func.attr}() inside traced function "
                        f"{fn.name!r} is a device sync; traced code "
                        "must stay on device", symbol=symbol))

        # mutable-closure: reads of module-level mutable globals
        mutable_globals = _module_mutable_globals(module)
        local_names = params | _assigned_names(fn)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in mutable_globals
                    and node.id not in local_names):
                findings.append(make_finding(
                    module, "jit-mutable-closure", node,
                    f"traced function {fn.name!r} reads module-level "
                    f"mutable {node.id!r}; its value is baked in at "
                    "trace time — later mutation silently diverges "
                    "from the compiled program", symbol=symbol))

    # -- unhashable static args at call sites ----------------------------
    jitted_with_static = _jitted_bindings_with_static(module)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _dotted(node.func)
        if fname not in jitted_with_static:
            continue
        for idx in jitted_with_static[fname]:
            if idx < len(node.args):
                arg = node.args[idx]
                if _is_unhashable_expr(arg):
                    findings.append(make_finding(
                        module, "jit-unhashable-static", arg,
                        f"static arg {idx} of {fname!r} is a mutable/"
                        "array-valued expression; static args must be "
                        "hashable (ints, strings, tuples) or every call "
                        "recompiles"))

    # -- hot-path transfers ----------------------------------------------
    if hot:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            label = None
            if d in _TRANSFER_DOTTED:
                label = d
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _TRANSFER_ATTRS):
                label = f".{node.func.attr}()"
            if label is not None:
                findings.append(make_finding(
                    module, "hot-path-transfer", node,
                    f"{label} in hot-path module {module.dotted}: every "
                    "host<->device transfer here is either a deliberate "
                    "measured sync point (suppress inline with a reason) "
                    "or a latency bug"))
    return findings


def _offending_branch_expr(test: ast.AST, params: set[str]) -> str | None:
    """A parameter read in ``test`` that is not a static-attr access."""
    attr_bases = {id(n.value) for n in ast.walk(test)
                  if isinstance(n, ast.Attribute)}
    for node in ast.walk(test):
        if isinstance(node, ast.Attribute):
            base = node.value
            if (isinstance(base, ast.Name) and base.id in params
                    and node.attr not in STATIC_ATTRS):
                return f"{base.id}.{node.attr}"
        elif (isinstance(node, ast.Name) and node.id in params
              and id(node) not in attr_bases):
            return node.id
    return None


def _module_mutable_globals(module: Module) -> set[str]:
    out: set[str] = set()
    for stmt in module.tree.body:
        targets: list[ast.AST] = []
        value: ast.AST | None = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        if value is None:
            continue
        if isinstance(value, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(value, ast.Call)
                and _dotted(value.func) in ("list", "dict", "set",
                                            "collections.defaultdict",
                                            "defaultdict")):
            for t in targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _assigned_names(fn: ast.FunctionDef) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            out.add(node.id)
    return out


def _jitted_bindings_with_static(module: Module) -> dict[str, tuple[int, ...]]:
    """``fn = jax.jit(g, static_argnums=(3,))`` -> {"fn": (3,)}; also
    decorated defs with partial(jax.jit, static_argnums=...)."""
    out: dict[str, tuple[int, ...]] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if _dotted(call.func) in ("jax.jit", "jit"):
                nums = _jit_static_argnums(call)
                if nums:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out[t.id] = nums
        elif isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _is_jit_expr(dec):
                    nums = _jit_static_argnums(dec)
                    if nums:
                        out[node.name] = nums
    return out


def _is_unhashable_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        return _dotted(node.func) in (
            "list", "dict", "set", "np.array", "np.asarray",
            "numpy.array", "numpy.asarray", "jnp.array", "jnp.asarray")
    return False
