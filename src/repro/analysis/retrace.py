"""DNVM002 — jax.jit retrace/trace-time discipline.

The engines trace their kernels a fixed number of times (PR 7 pins
``node_retraces == 0``) and run everything float64 inside the
``jax.enable_x64(True)`` context.  Three trace-time hazards break those
contracts silently:

- **varying-global capture**: a jitted body reads a module-level name
  that is reassigned/mutated elsewhere — the value at *trace* time is
  baked into the compiled executable, so later mutations are ignored
  (or worse, keyed off ``id()`` and retraced unpredictably);
- **traced-argument branching**: a Python ``if``/``while``/``not`` on a
  jitted parameter that is not in ``static_argnames`` — either a
  ``TracerBoolConversionError`` at runtime or, if the arg is a weak
  Python scalar, one silent retrace per distinct value (the
  ``anchor_peri`` static flag in ``core/engine.py`` is the corrected
  form);
- **dtype narrowing**: ``float32``/``float16``/``bfloat16``
  constructions inside a jitted body of an ``enable_x64`` module — one
  narrowed intermediate is enough to lose the ≤1-ulp scalar parity the
  anchor tests pin.

Jitted regions are found through ``@jax.jit`` / ``@functools.partial(
jax.jit, ...)`` decorators and ``name = jax.jit(fn, ...)`` /
``jax.jit(shard_map(fn, ...))`` wrapping of a resolvable local
function.
"""

from __future__ import annotations

import ast
import dataclasses

from repro.analysis.common import (
    Finding,
    ModuleInfo,
    decorator_name,
    dotted,
    func_params,
    iter_functions,
    loads_in,
    local_bindings,
)

RULE = "DNVM002"

_JIT_NAMES = frozenset({"jax.jit", "jit"})
_PARTIAL_NAMES = frozenset({"functools.partial", "partial"})
_NARROW_DTYPES = frozenset({"float32", "float16", "bfloat16"})


@dataclasses.dataclass
class JitSite:
    fn: ast.FunctionDef | ast.AsyncFunctionDef
    static: set[str]


def check(mod: ModuleInfo) -> list[Finding]:
    sites = _jit_sites(mod)
    if not sites:
        return []
    x64_module = "enable_x64" in mod.source
    findings: list[Finding] = []
    for site in sites:
        findings += _check_captures(mod, site)
        findings += _check_static_branches(mod, site)
        if x64_module:
            findings += _check_dtypes(mod, site)
    return findings


# ---------------------------------------------------------------------------
# jit site discovery


def _jit_sites(mod: ModuleInfo) -> list[JitSite]:
    by_name = {fn.name: fn for fn in iter_functions(mod.tree)}
    sites: dict[ast.AST, JitSite] = {}

    for fn in iter_functions(mod.tree):
        static = _static_from_decorators(fn)
        if static is not None:
            sites[fn] = JitSite(fn, static)

    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and dotted(node.func) in _JIT_NAMES and node.args):
            continue
        target: ast.expr = node.args[0]
        # unwrap one transform layer: jax.jit(shard_map(body, ...))
        if isinstance(target, ast.Call) and target.args:
            target = target.args[0]
        if isinstance(target, ast.Name) and target.id in by_name:
            fn = by_name[target.id]
            static = _static_names(node, fn)
            if fn in sites:
                sites[fn].static |= static
            else:
                sites[fn] = JitSite(fn, static)
    return list(sites.values())


def _static_from_decorators(
        fn: ast.FunctionDef | ast.AsyncFunctionDef) -> set[str] | None:
    for dec in fn.decorator_list:
        name = decorator_name(dec)
        if name in _JIT_NAMES:
            return _static_names(dec, fn) if isinstance(dec, ast.Call) \
                else set()
        if (name in _PARTIAL_NAMES and isinstance(dec, ast.Call)
                and dec.args and dotted(dec.args[0]) in _JIT_NAMES):
            return _static_names(dec, fn)
    return None


def _static_names(call: ast.Call,
                  fn: ast.FunctionDef | ast.AsyncFunctionDef) -> set[str]:
    params = func_params(fn)
    out: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            out |= set(_str_values(kw.value))
        elif kw.arg == "static_argnums":
            for i in _int_values(kw.value):
                if 0 <= i < len(params):
                    out.add(params[i])
    return out


def _str_values(node: ast.expr) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _int_values(node: ast.expr) -> list[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


# ---------------------------------------------------------------------------
# checks


def _check_captures(mod: ModuleInfo, site: JitSite) -> list[Finding]:
    out = []
    local = local_bindings(site.fn)
    seen: set[str] = set()
    for name in loads_in(site.fn):
        if name.id in local or name.id in seen:
            continue
        if name.id in mod.varying_globals:
            seen.add(name.id)
            out.append(Finding(
                mod.path, name.lineno, RULE,
                f"jitted '{site.fn.name}' captures mutable module state "
                f"'{name.id}' — baked in at trace time",
                mod.scope_of(name)))
    return out


def _check_static_branches(mod: ModuleInfo, site: JitSite) -> list[Finding]:
    traced = set(func_params(site.fn)) - site.static - {"self", "cls"}
    out = []
    flagged: set[str] = set()
    for node in ast.walk(site.fn):
        if isinstance(node, (ast.If, ast.While)):
            test = node.test
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            test = node.operand
        elif isinstance(node, ast.IfExp):
            test = node.test
        else:
            continue
        for used in _bare_param_uses(test, traced):
            if used.id in flagged:
                continue
            flagged.add(used.id)
            out.append(Finding(
                mod.path, used.lineno, RULE,
                f"jitted '{site.fn.name}' branches on traced argument "
                f"'{used.id}' — add it to static_argnames",
                mod.scope_of(used)))
    return out


def _bare_param_uses(test: ast.expr, params: set[str]) -> list[ast.Name]:
    """Bare Name uses of a traced param in a branch test.  Attribute
    access (``x.ndim``) and ``len(x)``/``isinstance(x, ...)`` are
    shape/type queries — static under tracing — and stay silent."""
    out = []
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
                and node.id in params):
            continue
        parent = getattr(node, "_dnvm_parent", None)
        if isinstance(parent, ast.Attribute) and parent.value is node:
            continue
        if (isinstance(parent, ast.Call) and node in parent.args
                and dotted(parent.func) in ("len", "isinstance", "type")):
            continue
        out.append(node)
    return out


def _check_dtypes(mod: ModuleInfo, site: JitSite) -> list[Finding]:
    out = []
    for node in ast.walk(site.fn):
        token = _narrow_token(node)
        if token is not None:
            out.append(Finding(
                mod.path, node.lineno, RULE,
                f"jitted '{site.fn.name}' uses {token} — narrows the "
                "enable_x64 float64 contract",
                mod.scope_of(node)))
    return out


def _narrow_token(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute) and node.attr in _NARROW_DTYPES:
        base = dotted(node.value)
        if base in ("jnp", "np", "jax.numpy", "numpy", "jax"):
            return f"{base}.{node.attr}"
    if (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value in _NARROW_DTYPES):
        return f"dtype string '{node.value}'"
    return None
