"""GT003 recompile hazard: jit call-site discipline, checked ahead of
deploy.

PR 3's compile ledger can *count* serve-time recompiles after they
already stalled traffic; this rule catches the classic causes at review
time. Ahead-of-time shape/staticness discipline is what makes TPU
compilation viable at all (PAPERS.md: Julia→TPU full compilation; TPU
exploration survey).

Checks:

- **jit-per-call** (``hazard=fresh-jit``): ``jax.jit(f)(x)`` inside a
  function body builds a *new* wrapper — and a new compile cache entry —
  on every invocation. Cache the jitted callable (module level, a
  factory-held dict like ``GenerationEngine._tick_fns``, or a closure
  built once).
- **unhashable static** (``hazard=unhashable-static``): a list/dict/set
  literal passed at a ``static_argnums`` position of a known-jitted
  callable raises at call time or, with tuple-coercing wrappers,
  recompiles per call.
- **shape-derived argument** (``hazard=shape-arg``): ``len(x)`` /
  ``x.shape[i]`` passed to a known-jitted callable at a *non-static*
  position. As a traced value it cannot affect shapes (so it is almost
  always intended static), and once declared static every distinct
  length compiles a fresh executable — round it to a declared bucket
  rung first (the ladder idiom in ``gofr_tpu/tpu/executor.py``).
- **raw-len device shape** (``hazard=raw-shape``): ``jnp.zeros``-family
  constructors whose shape contains a bare ``len(...)`` — an unbucketed
  dimension mints one executable per distinct request size.
- **live-count slice width** (``hazard=page-width``): a device upload
  (``jnp.asarray``/``jnp.array``/``jax.device_put``) or a known-jitted
  call whose argument is sliced to a ``len(...)``/``.shape``-derived
  bound (``table[:, :len(pages)]``). The slice width becomes an array
  dimension, so a *live count* — pages held, slots active — mints one
  executable per distinct value. Upload the whole table instead, one
  static width cached against the table's version (the idiom of
  ``GenerationEngine._table_dev``), or slice to a declared ladder rung.

Known-jitted callables are resolved module-locally: names bound to
``jax.jit(...)`` and functions decorated with ``@jax.jit`` /
``@partial(jax.jit, ...)``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from gofr_tpu.analysis.engine import Finding, ModuleInfo, Rule

_ARRAY_CTORS = {"zeros", "ones", "full", "empty", "arange"}


def _is_jit(module: ModuleInfo, node: ast.AST) -> Optional[ast.Call]:
    """Return the ``jax.jit(...)`` Call if ``node`` is one (including
    ``partial(jax.jit, ...)``), else None."""
    if not isinstance(node, ast.Call):
        return None
    dotted = module.dotted(node.func)
    if dotted in ("jax.jit", "jax.api.jit"):
        return node
    if dotted in ("functools.partial", "partial") and node.args:
        inner = module.dotted(node.args[0])
        if inner in ("jax.jit", "jax.api.jit"):
            return node
    return None


def _static_positions(jit_call: ast.Call) -> Tuple[Set[int], Set[str]]:
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, int):
                    nums.add(el.value)
        elif kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
    return nums, names


def _shape_derived(node: ast.AST) -> Optional[str]:
    """'len(...)' / '.shape[...]' expressions, including simple arithmetic
    on them (``len(x) + 1``)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                and sub.func.id == "len":
            return "len(...)"
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim",
                                                           "size"):
            return f".{sub.attr}"
    return None


def _sliced_by_len(node: ast.AST) -> Optional[str]:
    """A Subscript anywhere in ``node`` whose slice *bounds* are
    len()/.shape-derived — ``x[:, :len(pages)]`` — i.e. a live count
    becoming an array dimension."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Subscript):
            continue
        parts = sub.slice.elts if isinstance(sub.slice, ast.Tuple) \
            else [sub.slice]
        for part in parts:
            if not isinstance(part, ast.Slice):
                continue
            for bound in (part.lower, part.upper, part.step):
                if bound is None:
                    continue
                src = _shape_derived(bound)
                if src is not None:
                    return src
    return None


class RecompileHazardRule(Rule):
    rule_id = "GT003"
    title = "recompile-hazard"
    severity = "error"

    def check_module(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        jitted: Dict[str, Tuple[Set[int], Set[str]]] = {}

        # pass 1: collect known-jitted names (module level and class body)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                jit_call = _is_jit(module, node.value)
                if jit_call is not None and \
                        module.enclosing_function(node) is None:
                    jitted[node.targets[0].id] = _static_positions(jit_call)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    dotted = module.dotted(deco)
                    if dotted in ("jax.jit", "jax.api.jit"):
                        jitted[node.name] = (set(), set())
                    else:
                        jit_call = _is_jit(module, deco)
                        if jit_call is not None:
                            jitted[node.name] = _static_positions(jit_call)

        # pass 2: call-site checks
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            findings.extend(self._fresh_jit(module, node))
            findings.extend(self._jitted_call(module, node, jitted))
            findings.extend(self._raw_shape(module, node))
            findings.extend(self._page_width(module, node))
        return findings

    def _fresh_jit(self, module: ModuleInfo,
                   call: ast.Call) -> Iterable[Finding]:
        """jax.jit(f)(x): the outer call's func is itself a jit call."""
        jit_call = _is_jit(module, call.func)
        if jit_call is None:
            return ()
        fn = module.enclosing_function(call)
        if fn is None:
            return ()  # module-scope immediate invoke runs once at import
        return (Finding(
            rule=self.rule_id,
            path=module.relpath,
            line=call.lineno,
            message=(
                f"recompile hazard [fresh-jit]: jax.jit(...)(...) inside "
                f"'{fn.name}' builds a new wrapper (and compile-cache "
                f"entry) every call — jit once and cache the callable"),
            severity=self.severity,
            key=f"fresh-jit in {fn.name}",
        ),)

    def _jitted_call(self, module: ModuleInfo, call: ast.Call,
                     jitted: Dict[str, Tuple[Set[int], Set[str]]]
                     ) -> Iterable[Finding]:
        if not isinstance(call.func, ast.Name) or \
                call.func.id not in jitted:
            return ()
        name = call.func.id
        static_nums, static_names = jitted[name]
        findings: List[Finding] = []
        for index, arg in enumerate(call.args):
            is_static = index in static_nums
            if is_static and isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                findings.append(Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=arg.lineno,
                    message=(
                        f"recompile hazard [unhashable-static]: argument "
                        f"{index} of jitted '{name}' is declared static "
                        f"but passed an unhashable "
                        f"{type(arg).__name__.lower()} literal — static "
                        f"args must hash (use a tuple)"),
                    severity=self.severity,
                    key=f"unhashable-static arg{index} of {name}",
                ))
            width_src = None if is_static else _sliced_by_len(arg)
            if width_src is not None:
                findings.append(Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=arg.lineno,
                    message=(
                        f"recompile hazard [page-width]: argument {index} "
                        f"of jitted '{name}' is sliced to a "
                        f"{width_src}-derived width — the live count "
                        f"becomes an array dimension, one executable per "
                        f"distinct value; slice to a declared ladder "
                        f"rung instead"),
                    severity=self.severity,
                    key=f"page-width arg{index} of {name}",
                ))
                continue   # the precise finding; skip the generic one
            shape_src = None if is_static else _shape_derived(arg)
            if shape_src is not None:
                findings.append(Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=arg.lineno,
                    message=(
                        f"recompile hazard [shape-arg]: {shape_src} flows "
                        f"into non-static argument {index} of jitted "
                        f"'{name}' — declare it in static_argnums and "
                        f"round to a bucket rung, or it silently becomes "
                        f"a traced scalar that cannot shape anything"),
                    severity="warning",
                    key=f"shape-arg arg{index} of {name}",
                ))
        for kw in call.keywords:
            if kw.arg in static_names and \
                    isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                findings.append(Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=kw.value.lineno,
                    message=(
                        f"recompile hazard [unhashable-static]: static "
                        f"argname '{kw.arg}' of jitted '{name}' is passed "
                        f"an unhashable literal"),
                    severity=self.severity,
                    key=f"unhashable-static {kw.arg} of {name}",
                ))
        return findings

    def _raw_shape(self, module: ModuleInfo,
                   call: ast.Call) -> Iterable[Finding]:
        dotted = module.dotted(call.func)
        if dotted is None:
            return ()
        root, _, ctor = dotted.rpartition(".")
        if ctor not in _ARRAY_CTORS or root not in (
                "jax.numpy", "jnp", "numpy.jnp"):
            return ()
        if not call.args:
            return ()
        shape = call.args[0]
        elements = shape.elts if isinstance(shape, (ast.Tuple, ast.List)) \
            else [shape]
        for element in elements:
            if isinstance(element, ast.Call) and \
                    isinstance(element.func, ast.Name) and \
                    element.func.id == "len":
                fn = module.enclosing_function(call)
                where = fn.name if fn is not None else "<module>"
                return (Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=call.lineno,
                    message=(
                        f"recompile hazard [raw-shape]: device buffer in "
                        f"'{where}' is shaped by a bare len(...) — every "
                        f"distinct length mints one executable; round up "
                        f"to a declared bucket rung first"),
                    severity=self.severity,
                    key=f"raw-shape in {where}",
                ),)
        return ()

    def _page_width(self, module: ModuleInfo,
                    call: ast.Call) -> Iterable[Finding]:
        """Device uploads sliced to a live-count width: the host->device
        copy's shape tracks ``len(pages)``-style state, so every distinct
        count both re-uploads and re-specializes whatever consumes it."""
        dotted = module.dotted(call.func)
        if dotted not in ("jnp.asarray", "jax.numpy.asarray", "jnp.array",
                          "jax.numpy.array", "jax.device_put"):
            return ()
        for arg in call.args:
            src = _sliced_by_len(arg)
            if src is None:
                continue
            fn = module.enclosing_function(call)
            where = fn.name if fn is not None else "<module>"
            return (Finding(
                rule=self.rule_id,
                path=module.relpath,
                line=call.lineno,
                message=(
                    f"recompile hazard [page-width]: device upload in "
                    f"'{where}' is sliced to a {src}-derived width — a "
                    f"live page/item count becomes an array dimension, "
                    f"minting one executable per distinct value; slice "
                    f"to a declared ladder rung instead"),
                severity=self.severity,
                key=f"page-width in {where}",
            ),)
        return ()
