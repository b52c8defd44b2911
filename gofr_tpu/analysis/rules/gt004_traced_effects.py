"""GT004 traced side effects: host-world calls inside jit-traced bodies.

A ``print`` / logger / metrics call inside a jit-traced function body
runs **once, at trace time**, then never again — the dashboard metric
you think is per-step is per-compile, and the log line prints a tracer.
A Python ``if`` on a traced value is worse: ``ConcretizationTypeError``
at trace time, or — when callers happen to pass Python scalars — a
hidden static argument that recompiles per distinct value.

Traced bodies are resolved module-locally: functions decorated with
``@jax.jit`` / ``@partial(jax.jit, ...)``, functions wrapped by a
``jax.jit(fn)`` call in the same scope, and every ``def`` nested inside
a traced body (``lax.scan`` step functions — see
``GenerationEngine._tick_fn``'s ``one``).

Flags inside a traced body:

- calls to ``print`` and to logger-shaped receivers
  (``logger.info/debug/warning/error/...``) — use ``jax.debug.print`` /
  ``jax.debug.callback`` when you really need trace-time output;
- Manager metric observations (``increment_counter`` etc.) — record
  metrics at the dispatch site, outside the traced body;
- ``if``/ternary on a bare parameter of the traced function. Structure
  checks stay exempt: ``x is None``, ``isinstance(...)``,
  ``x.shape/ndim/dtype/size``, ``len(x)`` are resolved at trace time
  and legitimately steer tracing.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from gofr_tpu.analysis.engine import Finding, ModuleInfo, Rule
from gofr_tpu.analysis.rules.gt003_recompile import (
    _is_jit,
    _static_positions,
)

_LOGGER_METHODS = {"debug", "info", "warn", "warning", "error", "exception",
                   "critical", "fatal"}
_METRIC_METHODS = {"increment_counter", "delta_updown_counter",
                   "record_histogram", "set_gauge"}
_DEBUG_OK = {"jax.debug.print", "jax.debug.callback",
             "jax.experimental.io_callback", "io_callback"}


def _traced_defs(module: ModuleInfo) -> List[ast.AST]:
    """Function defs whose bodies jit traces, with their static argnames
    attached as ``_graftcheck_static``."""
    by_name = {}
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    traced: List[ast.AST] = []

    def mark(defn: ast.AST, static_nums: Set[int],
             static_names: Set[str]) -> None:
        params = [a.arg for a in defn.args.args]
        static = set(static_names)
        static.update(params[i] for i in static_nums if i < len(params))
        defn._graftcheck_static = static
        traced.append(defn)

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if module.dotted(deco) in ("jax.jit", "jax.api.jit"):
                    mark(node, set(), set())
                else:
                    jit_call = _is_jit(module, deco)
                    if jit_call is not None:
                        nums, names = _static_positions(jit_call)
                        mark(node, nums, names)
        jit_call = _is_jit(module, node) if isinstance(node, ast.Call) \
            else None
        if jit_call is not None and jit_call.args:
            target = jit_call.args[0]
            if isinstance(target, ast.Name) and target.id in by_name:
                nums, names = _static_positions(jit_call)
                for defn in by_name[target.id]:
                    if not hasattr(defn, "_graftcheck_static"):
                        mark(defn, nums, names)
    return traced


class TracedSideEffectsRule(Rule):
    rule_id = "GT004"
    title = "traced-side-effects"
    severity = "error"

    def check_module(self, module: ModuleInfo) -> Iterable[Finding]:
        findings: List[Finding] = []
        for defn in _traced_defs(module):
            static = getattr(defn, "_graftcheck_static", set())
            params = {a.arg for a in defn.args.args}
            # nested defs (lax.scan step fns) trace too — their params
            # carry tracers from the enclosing trace
            for node in ast.walk(defn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and node is not defn:
                    params.update(a.arg for a in node.args.args)
            params -= static
            for node in ast.walk(defn):
                findings.extend(
                    self._check_node(module, defn, node, params))
        # dedupe: nested traced defs are walked once via their parent and
        # once if independently marked
        unique = {}
        for finding in findings:
            unique[(finding.path, finding.line, finding.key)] = finding
        return list(unique.values())

    def _check_node(self, module: ModuleInfo, defn: ast.AST, node: ast.AST,
                    params: Set[str]) -> Iterable[Finding]:
        if isinstance(node, ast.Call):
            label = self._effect_label(module, node)
            if label is not None:
                return (Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=node.lineno,
                    message=(
                        f"traced side effect: {label} inside jit-traced "
                        f"'{defn.name}' runs once at trace time, not per "
                        f"step — hoist it to the dispatch site or use "
                        f"jax.debug.print/callback"),
                    severity=self.severity,
                    key=f"{label} in {defn.name}",
                ),)
        if isinstance(node, (ast.If, ast.IfExp)):
            name = self._tracer_test(module, node.test, params)
            if name is not None:
                return (Finding(
                    rule=self.rule_id,
                    path=module.relpath,
                    line=node.lineno,
                    message=(
                        f"traced side effect: Python 'if' on traced "
                        f"parameter '{name}' of jit-traced '{defn.name}' "
                        f"— concretization error at trace time (or a "
                        f"hidden per-value recompile); use jnp.where/"
                        f"lax.cond, or declare the arg static"),
                    severity=self.severity,
                    key=f"if {name} in {defn.name}",
                ),)
        return ()

    def _effect_label(self, module: ModuleInfo,
                      call: ast.Call) -> Optional[str]:
        dotted = module.dotted(call.func)
        if dotted in _DEBUG_OK:
            return None
        if isinstance(call.func, ast.Name) and call.func.id == "print":
            return "print(...)"
        if isinstance(call.func, ast.Attribute):
            attr = call.func.attr
            receiver = call.func.value
            receiver_name = ""
            if isinstance(receiver, ast.Name):
                receiver_name = receiver.id
            elif isinstance(receiver, ast.Attribute):
                receiver_name = receiver.attr
            if attr in _LOGGER_METHODS and "log" in receiver_name.lower():
                return f"{receiver_name}.{attr}(...)"
            if attr in _METRIC_METHODS:
                return f".{attr}(...)"
        return None

    def _tracer_test(self, module: ModuleInfo, test: ast.AST,
                     params: Set[str]) -> Optional[str]:
        """Name of a traced param the test branches on, or None if the
        test only inspects static structure."""

        def walk_skipping_is(node):
            # `x is None` / `x is not None` compares pytree structure,
            # resolved at trace time — never a tracer branch
            if isinstance(node, ast.Compare) and \
                    any(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops):
                return
            yield node
            for child in ast.iter_child_nodes(node):
                yield from walk_skipping_is(child)

        for node in walk_skipping_is(test):
            if not (isinstance(node, ast.Name) and node.id in params):
                continue
            parent = module.parents.get(node)
            if isinstance(parent, ast.Attribute):
                continue  # x.shape / x.dtype / cfg.flag — static lookups
            if isinstance(parent, ast.Call) and node in parent.args and \
                    isinstance(parent.func, ast.Name) and \
                    parent.func.id in ("len", "isinstance", "getattr",
                                       "hasattr", "type"):
                continue
            return node.id
        return None
