"""R2 ``hot-path-host-sync``: device→host synchronization inside the hot
dispatch/consume paths.

PR 4's contract — ONE ragged dispatch per scheduler iteration — dies by a
thousand ``.item()`` calls: any host materialization of a device value inside the
dispatch path serializes the pipeline (the host blocks until the device
catches up) and reintroduces the per-round sync PR 1/PR 4 removed. The
blessed pattern is batching every host fetch into the single
``await asyncio.to_thread(...)`` consume seam.

Hot scopes (the ISSUE 8 set):

- every function in ``finchat_tpu/ops/`` (kernel wrappers),
- ``finchat_tpu/engine/engine.py`` except construction/teardown
  (``__init__`` / ``create_state`` / ``warmup`` / ``rebuild_device_state``
  — warmup *exists* to pay syncs up front),
- the scheduler's dispatch/consume path functions (by name),
- any function whose ``def`` line carries ``# finchat-lint: hot``.

Flagged inside a hot scope (off-loop lambdas handed to ``to_thread`` /
``submit`` are exempt — that's the blessed seam):

- ``.item()`` — always a device sync,
- ``np.asarray`` / ``np.array`` / ``jax.device_get`` on a device-tainted
  value (D2H transfer),
- ``float()`` / ``int()`` / ``bool()`` on a device-tainted value,
- ``.block_until_ready()``,
- an ``if`` / ``while`` / ``assert`` test on a device-tainted value —
  the implicit ``__bool__`` is a hidden blocking transfer.

"Device-tainted" is a per-function dataflow approximation: ``jnp.*`` /
``lax.*`` call results seed it; assignments, arithmetic, subscripts,
and method calls on tainted values propagate it; array METADATA
(``x.shape``, ``jnp.ndim(x)``) and identity tests (``x is None``) are
host-side and never taint. Cross-function: a resolved call taints only
when the callee itself "returns device" — inferred by checking whether
its own ``return`` expressions are tainted (fixpoint over the call
graph), so host helpers living in hot modules (backend-name lookups,
shape math) correctly taint nothing. Function parameters are untainted
by default (the consume seam hands *host* arrays around).
"""

from __future__ import annotations

import ast

from finchat_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    ProjectIndex,
    Rule,
    dotted_name,
)

_OFF_LOOP_TAILS = ("to_thread", "run_in_executor", "submit")

SCHEDULER_HOT = {
    "_dispatch_decode",
    "_ragged_round",
    "_prefill_round",
    "_run_spec_step",
    "_consume_step",
    "_drain_inflight",
    "_deliver",
    "_pack_prefill_rows",
}

ENGINE_COLD = {"__init__", "create_state", "warmup", "rebuild_device_state"}

_TAINT_ROOTS = {"jnp", "lax"}
_D2H_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get"}
_CAST_BUILTINS = {"float", "int", "bool"}


def is_hot(fn: FunctionInfo) -> bool:
    rel = fn.module.relpath
    if fn.node.lineno in fn.module.hot_marks:
        return True
    if "/ops/" in f"/{rel}":
        return True
    if rel.endswith("engine/engine.py"):
        return fn.name not in ENGINE_COLD
    if rel.endswith("engine/scheduler.py"):
        return fn.name in SCHEDULER_HOT
    return False


def _is_hot_module(relpath: str) -> bool:
    return "/ops/" in f"/{relpath}" or relpath.endswith("engine/engine.py")


class HotPathHostSyncRule(Rule):
    name = "hot-path-host-sync"
    code = "R2"
    description = (
        "host sync (.item()/np.asarray/float()/implicit __bool__/"
        "block_until_ready) on device values inside hot dispatch paths"
    )

    def run(self, project: ProjectIndex) -> list[Finding]:
        self._returns_device = _infer_returns_device(project)
        findings: list[Finding] = []
        for fn in project.all_functions():
            if is_hot(fn):
                findings.extend(self._check(fn, project))
        return findings

    def _check(self, fn: FunctionInfo, project: ProjectIndex) -> list[Finding]:
        tainted = self._taint(fn, project)
        findings: list[Finding] = []

        def hit(node: ast.AST, msg: str) -> None:
            findings.append(
                Finding(
                    self.name,
                    fn.module.relpath,
                    node.lineno,
                    fn.qualname,
                    f"{msg} in hot path (one-dispatch-per-iteration "
                    "contract); batch it into the off-loop consume seam "
                    "or suppress with a justification",
                )
            )

        returns_device = self._returns_device

        def is_tainted(expr: ast.AST) -> bool:
            return _expr_tainted(expr, tainted, fn, project, returns_device)

        class V(ast.NodeVisitor):
            def __init__(self) -> None:
                self._top = True

            def visit_FunctionDef(self, node):  # nested defs scanned on their own
                if self._top:
                    self._top = False
                    self.generic_visit(node)

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Call(self, node: ast.Call) -> None:
                d = dotted_name(node.func)
                tail = d.rsplit(".", 1)[-1] if d else (
                    node.func.attr if isinstance(node.func, ast.Attribute) else None
                )
                if isinstance(node.func, ast.Attribute):
                    if node.func.attr == "item":
                        hit(node, "`.item()` device sync")
                    elif node.func.attr == "block_until_ready":
                        hit(node, "`.block_until_ready()` device sync")
                if d and node.args:
                    ext = _external(d, fn)
                    if ext in _D2H_CALLS and is_tainted(node.args[0]):
                        hit(node, f"`{d}` D2H transfer of a device value")
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in _CAST_BUILTINS
                    and node.args
                    and is_tainted(node.args[0])
                ):
                    hit(node, f"`{node.func.id}()` on a device value")
                # recurse, skipping off-loop lambda bodies
                off = tail in _OFF_LOOP_TAILS
                for child in list(node.args) + [kw.value for kw in node.keywords]:
                    if off and isinstance(child, ast.Lambda):
                        continue
                    self.visit(child)
                if not isinstance(node.func, ast.Name):
                    self.visit(node.func)

            def visit_If(self, node: ast.If) -> None:
                if is_tainted(node.test):
                    hit(node, "implicit `__bool__` (if) on a device value")
                self.generic_visit(node)

            def visit_While(self, node: ast.While) -> None:
                if is_tainted(node.test):
                    hit(node, "implicit `__bool__` (while) on a device value")
                self.generic_visit(node)

            def visit_Assert(self, node: ast.Assert) -> None:
                if is_tainted(node.test):
                    hit(node, "implicit `__bool__` (assert) on a device value")
                self.generic_visit(node)

        V().visit(fn.node)
        return findings

    def _check_taint(self, fn, project):
        return _local_taint(fn, project, self._returns_device)

    def _taint(self, fn: FunctionInfo, project: ProjectIndex) -> set[str]:
        return _local_taint(fn, project, self._returns_device)


def _taint_target(tgt: ast.AST, tainted: set[str]) -> None:
    if isinstance(tgt, ast.Name):
        tainted.add(tgt.id)
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for elt in tgt.elts:
            _taint_target(elt, tainted)
    elif isinstance(tgt, ast.Starred):
        _taint_target(tgt.value, tainted)


def _external(dotted: str, fn: FunctionInfo) -> str:
    parts = dotted.split(".")
    imp = fn.module.imports.get(parts[0])
    return ".".join([imp] + parts[1:]) if imp else dotted


# array metadata accessors return HOST values (ints/tuples/dtypes), not
# device buffers — both as attributes (``x.shape``) and as jnp/np helper
# calls (``jnp.ndim(x)``)
_HOST_META_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "itemsize"}
_HOST_META_CALLS = {"ndim", "shape", "size", "result_type", "iinfo", "finfo"}


def _local_taint(fn, project, returns_device) -> set[str]:
    """Fixpoint over assignments: names bound (directly or through
    arithmetic/subscripts) to jnp/lax call results or to calls of
    functions inferred to return device values."""
    tainted: set[str] = set()
    for _ in range(2):
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Assign):
                if _expr_tainted(node.value, tainted, fn, project, returns_device):
                    for tgt in node.targets:
                        _taint_target(tgt, tainted)
            elif isinstance(node, ast.AugAssign):
                if _expr_tainted(node.value, tainted, fn, project, returns_device):
                    _taint_target(node.target, tainted)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if _expr_tainted(node.value, tainted, fn, project, returns_device):
                    _taint_target(node.target, tainted)
    return tainted


def _infer_returns_device(project: ProjectIndex) -> dict:
    """One-level interprocedural inference: a function "returns device"
    when any of its ``return`` expressions is device-tainted under its own
    local taint. Host helpers living in hot modules (backend-name lookups,
    shape math) correctly come out False — calling them taints nothing."""
    returns_device: dict = {}
    fns = list(project.all_functions())
    for _ in range(3):  # fixpoint across call chains
        changed = False
        for fn in fns:
            tainted = _local_taint(fn, project, returns_device)
            val = False
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Return) and node.value is not None:
                    if _expr_tainted(node.value, tainted, fn, project, returns_device):
                        val = True
                        break
            if returns_device.get(fn) != val:
                returns_device[fn] = val
                changed = True
        if not changed:
            break
    return returns_device


def _expr_tainted(
    expr: ast.AST,
    tainted: set[str],
    fn: FunctionInfo,
    project: ProjectIndex,
    returns_device: dict,
) -> bool:
    def rec(e: ast.AST) -> bool:
        return _expr_tainted(e, tainted, fn, project, returns_device)

    if isinstance(expr, ast.Name):
        return expr.id in tainted
    if isinstance(expr, ast.Attribute):
        if expr.attr in _HOST_META_ATTRS:
            return False
        return rec(expr.value)
    if isinstance(expr, ast.Subscript):
        return rec(expr.value)
    if isinstance(expr, ast.Call):
        # a method call on a tainted value stays device-side
        # (logits.argmax(), x.astype(...), x.reshape(...))
        if isinstance(expr.func, ast.Attribute) and rec(expr.func.value):
            return expr.func.attr not in _HOST_META_CALLS
        d = dotted_name(expr.func)
        if d:
            parts = d.split(".")
            if parts[0] in _TAINT_ROOTS:
                return parts[-1] not in _HOST_META_CALLS
            for target in project.resolve_call(
                # a lightweight CallSite stand-in: resolve_call only reads
                # .dotted
                type("S", (), {"dotted": d, "node": expr, "off_loop_wrapper": False})(),
                fn,
            ):
                if returns_device.get(target):
                    return True
        return False
    if isinstance(expr, ast.BinOp):
        return rec(expr.left) or rec(expr.right)
    if isinstance(expr, ast.UnaryOp):
        return rec(expr.operand)
    if isinstance(expr, ast.Compare):
        # identity tests never touch __bool__ on the array
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
            return False
        return rec(expr.left) or any(rec(c) for c in expr.comparators)
    if isinstance(expr, ast.BoolOp):
        return any(rec(v) for v in expr.values)
    if isinstance(expr, (ast.Tuple, ast.List)):
        return any(rec(e) for e in expr.elts)
    if isinstance(expr, ast.IfExp):
        return rec(expr.body) or rec(expr.orelse)
    if isinstance(expr, ast.Starred):
        return rec(expr.value)
    return False
