"""GT015 use-after-donate: reading an array after donating its buffer.

``jax.jit(..., donate_argnums=(i, ...))`` is the zero-copy backbone of
the decode loop: the KV pool's leaves are donated into every step so
XLA writes the new cache in place instead of doubling HBM. The contract
is brutal and unchecked at the Python layer — after the call, the
donated ``jax.Array`` is *deleted*; touching it again raises (best
case) or silently reads garbage through a stale NumPy view (worst
case, and only on real TPUs, which is why it never shows up under
``JAX_PLATFORMS=cpu`` tests).

Detection — three steps, per function body, using the project symbol
table plus the intraprocedural value-flow pass (``dataflow.py``):

1. **Find donating callables.** ``jax.jit(fn, donate_argnums=...)``
   results are tracked wherever the repo puts them: a local (``step =
   jax.jit(...)``), an instance attribute (``self._step_fn = ...``),
   a cache table (``self._tick_fns[key] = jax.jit(...)`` — every
   subscript of that table donates), and factory functions that
   ``return jax.jit(...)`` (or build it into a local and return that),
   resolved cross-module through the project graph. Attribute and
   table paths are shared module-wide; bare locals stay scoped to
   their own function (two functions reusing the name ``fn`` must not
   contaminate each other).
2. **Find dispatches.** Every call whose callee is a donating callable
   marks its donated *positional* arguments (keyword args cannot map to
   ``donate_argnums`` positions; ``*args`` splats are skipped —
   documented blind spot).
3. **Find stale reads.** For each donated argument with a stable dotted
   path (``buf``, ``self._pool.leaves``), flag any later load of that
   path — or an extension of it — with no rebind in between; and, when
   the dispatch sits in a loop, flag a missing rebind inside the loop
   body (the next iteration re-reads, and re-donates, a deleted array).

The rebind check means the sanctioned idiom passes untouched::

    leaves, ... = fn(self._pool.leaves, ...)   # donate
    self._pool.leaves = leaves                 # rebind — all clear

Suppress a deliberate re-read (e.g. donation disabled on CPU backends)
with ``# graftcheck: ignore[GT015]`` plus a justification.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from gofr_tpu.analysis.dataflow import ValueFlow, dotted_path
from gofr_tpu.analysis.engine import Finding, Rule

_JIT_NAMES = {"jax.jit", "jax.api.jit", "jax.pjit", "jax.experimental.pjit"}


def _donate_positions(module, call: ast.Call) -> Optional[Set[int]]:
    """``jax.jit(..., donate_argnums=...)`` → the donated positions,
    None when this is not a donating jit call."""
    dotted = module.dotted(call.func)
    if dotted not in _JIT_NAMES:
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        value = kw.value
        if isinstance(value, ast.Constant) and isinstance(value.value, int):
            return {value.value}
        if isinstance(value, (ast.Tuple, ast.List)):
            out = set()
            for elt in value.elts:
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, int):
                    out.add(elt.value)
            return out or None
    return None


class DonateUseRule(Rule):
    rule_id = "GT015"
    title = "use-after-donate"
    severity = "error"

    def check_project(self, project) -> Iterable[Finding]:
        # module-wide donating paths: attribute targets ("self._fn")
        # and table containers ("self._fns[]"); factory FuncRefs
        attr_paths: Dict[Tuple[str, str], Set[int]] = {}
        factories: Dict[Tuple, Set[int]] = {}
        flows: Dict[Tuple, ValueFlow] = {}
        for ref, fn in project.functions.items():
            flows[ref] = flow = ValueFlow(fn.node)
            self._collect_donators(
                project, ref, flow, attr_paths, factories)
        findings: List[Finding] = []
        for ref in sorted(project.functions):
            findings.extend(self._check_function(
                project, ref, flows[ref], attr_paths, factories))
        return findings

    # -- step 1: where do donating callables live? --------------------------
    def _collect_donators(self, project, ref, flow: ValueFlow,
                          attr_paths, factories) -> None:
        rel = ref[0]
        module = project.module_of(ref)
        returned_locals: Set[str] = set()
        for _idx, value in flow.returns:
            if isinstance(value, ast.Call):
                positions = _donate_positions(module, value)
                if positions:
                    factories.setdefault(ref, set()).update(positions)
            path = dotted_path(value) if value is not None else None
            if path is not None:
                returned_locals.add(path)
        for fact in flow.assigns_in_order:
            if not isinstance(fact.value, ast.Call):
                continue
            positions = _donate_positions(module, fact.value)
            if not positions:
                continue
            if "." in fact.path:
                # instance/module attribute: visible module-wide
                attr_paths.setdefault(
                    (rel, fact.path), set()).update(positions)
            if fact.path in returned_locals:
                # ``fn = jax.jit(...); return fn`` factory shape
                factories.setdefault(ref, set()).update(positions)
        # table entries: self._fns[key] = jax.jit(...) — the kill pass
        # skips Subscript targets, so scan raw assigns
        for node in project.body_nodes(ref):
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Call):
                continue
            positions = _donate_positions(module, node.value)
            if not positions:
                continue
            for target in node.targets:
                if isinstance(target, ast.Subscript):
                    container = dotted_path(target.value)
                    if container is not None:
                        attr_paths.setdefault(
                            (rel, container + "[]"),
                            set()).update(positions)

    # -- steps 2+3: dispatches and stale reads ------------------------------
    def _check_function(self, project, ref, flow: ValueFlow,
                        attr_paths, factories) -> Iterable[Finding]:
        rel, qualname = ref
        module = project.module_of(ref)
        fn = project.functions[ref]
        edges = {id(site): callee for callee, site in project.calls(ref)}

        # function-scoped donating locals: ``step = jax.jit(...)`` or
        # ``step = make_step(...)`` where make_step is a factory
        local_paths: Dict[str, Set[int]] = {}
        for fact in flow.assigns_in_order:
            if "." in fact.path or not isinstance(fact.value, ast.Call):
                continue
            positions = _donate_positions(module, fact.value)
            if positions is None:
                callee = edges.get(id(fact.value))
                positions = factories.get(callee) if callee else None
            if positions:
                local_paths[fact.path] = set(positions)

        findings: List[Finding] = []
        for node in project.body_nodes(ref):
            if not isinstance(node, ast.Call):
                continue
            positions = self._positions_for_call(
                rel, node, edges, attr_paths, factories, local_paths)
            if not positions:
                continue
            stmt = flow.stmt_index(node)
            if stmt is None:
                continue
            for index in sorted(positions):
                if index >= len(node.args):
                    continue
                arg = node.args[index]
                if isinstance(arg, ast.Starred):
                    continue
                path = dotted_path(arg)
                if path is None or path in ("self", "cls"):
                    continue
                reads = flow.loads_after(path, stmt)
                if reads:
                    lineno = reads[0][0]
                    findings.append(Finding(
                        rule=self.rule_id, path=module.relpath,
                        line=lineno,
                        message=(
                            f"use-after-donate: '{path}' is donated at "
                            f"line {node.lineno} (donate_argnums "
                            f"position {index}) and read again here — "
                            f"the buffer is deleted after dispatch; "
                            f"rebind '{path}' to the call's result "
                            f"before any further use"),
                        severity=self.severity,
                        key=f"use-after-donate {path} in {qualname}",
                    ))
                loop = self._enclosing_loop(module, node, fn.node)
                if loop is not None and \
                        not flow.kills_inside(path, loop):
                    findings.append(Finding(
                        rule=self.rule_id, path=module.relpath,
                        line=node.lineno,
                        message=(
                            f"use-after-donate: '{path}' is donated "
                            f"inside a loop with no rebind in the loop "
                            f"body — the next iteration dispatches a "
                            f"deleted buffer; assign the call's result "
                            f"back to '{path}'"),
                        severity=self.severity,
                        key=(f"loop-carried donate {path} "
                             f"in {qualname}"),
                    ))
        return findings

    @staticmethod
    def _positions_for_call(rel, call, edges, attr_paths, factories,
                            local_paths) -> Optional[Set[int]]:
        func = call.func
        # a cached table dispatch: self._fns[key](...)
        if isinstance(func, ast.Subscript):
            container = dotted_path(func.value)
            if container is not None:
                return attr_paths.get((rel, container + "[]"))
            return None
        path = dotted_path(func)
        if path is None:
            return None
        if "." in path:
            hit = attr_paths.get((rel, path))
            if hit:
                return hit
        else:
            hit = local_paths.get(path)
            if hit:
                return hit
        callee = edges.get(id(call))
        if callee is not None:
            return factories.get(callee)
        return None

    @staticmethod
    def _enclosing_loop(module, node, fn_node):
        cursor = module.parents.get(node)
        while cursor is not None and cursor is not fn_node:
            if isinstance(cursor, (ast.For, ast.AsyncFor, ast.While)):
                return cursor
            if isinstance(cursor, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.Lambda)):
                return None
            cursor = module.parents.get(cursor)
        return None
