"""R5 ``metrics-discipline``: metric naming, the labeled-vs-unlabeled
family convention, and span/trace-event name discipline.

The Prometheus surface is the product's north star (utils/metrics.py);
PR 6 established the convention this rule enforces mechanically:

- every series is ``finchat_*``,
- counters (``inc``) end ``_total``; histograms (``observe`` / ``Timer``)
  end ``_seconds``; gauges (``set_gauge``) end in neither,
- per-engine families are emitted through the replica's ``LabeledMetrics``
  view (``self.metrics`` — the ``replica`` label rides implicitly), while
  **fleet-level** series (``finchat_fleet_*``) are emitted UNLABELED on
  the global ``METRICS`` registry — one reader sees the whole family. A
  fleet counter emitted through a labeled view was exactly the PR 6
  review catch (per-replica ``finchat_fleet_drain_failures_total`` series
  that no dashboard summed),
- one series name must not mix explicit-``labels`` and label-free call
  sites (the render groups by base name; a mixed family splits).

Span discipline (ISSUE 12): every ``span.mark("...")`` literal must come
from ``utils/tracing.py``'s ``SPAN_MARKS``, every ``TRACER.event("...")``
literal from the full ``TRACE_EVENT_NAMES`` registry, and every
``TRACER.anomaly("...")`` literal from ``ANOMALY_KINDS`` — a typo'd name
otherwise just silently vanishes from every timeline and flight dump.
The same holds for the names a profile is reduced by (ISSUE 24): a
``named_scope("...")`` literal must come from ``DEVICE_SCOPES``, a
``TRACER.phase("...")`` literal from ``ROUND_PHASES``, a
``TRACER.startup("...")`` / ``startup_phase("...")`` literal from
``STARTUP_PHASES``, and a ``span.finish(reason="...")`` literal — or the
one a ``_close_span(handle, "...")`` helper forwards — from
``FINISH_REASONS`` (each checked only where the analyzed tracing module
declares that registry).
Literal names are checked wherever they appear, INCLUDING through the
repo's forwarding helpers (a call to a ``_trace``-named helper whose
literal string argument carries the event name); a forwarding helper's
own non-literal pass-through is exempt by construction, because its call
sites carry the literals. The registries are read from the analyzed
set's ``utils/tracing.py`` (fixtures supply a miniature one); with no
tracing module in scope, the span checks are skipped.

Emission sites are found by shape, not receiver type: a call to
``inc`` / ``set_gauge`` / ``observe`` whose first argument is a string
literal (or a conditional between string literals), or a ``Timer(...,
"name")`` construction. Sites outside ``finchat_tpu/`` (tests,
fixtures) are ignored.
"""

from __future__ import annotations

import ast

from finchat_tpu.analysis.core import Finding, ProjectIndex, Rule, dotted_name

_EMITTERS = {"inc", "set_gauge", "observe"}
# the tracing-registry names read out of utils/tracing.py
_REGISTRY_VARS = ("SPAN_MARKS", "TRACE_EVENTS", "ANOMALY_KINDS",
                  "ROUND_PHASES", "DEVICE_SCOPES", "FINISH_REASONS",
                  "STARTUP_PHASES")


class MetricsDisciplineRule(Rule):
    name = "metrics-discipline"
    code = "R5"
    description = (
        "finchat_* naming, _total/_seconds suffix conventions, and the "
        "fleet-family unlabeled-emission convention"
    )

    def run(self, project: ProjectIndex) -> list[Finding]:
        findings: list[Finding] = []
        # name -> list of (has_explicit_labels, Finding-location tuple)
        sites: dict[str, list[tuple[bool, str, int, str]]] = {}

        for mod in project.modules.values():
            if not mod.modname.startswith("finchat_tpu."):
                continue
            if mod.relpath.endswith("utils/metrics.py"):
                continue  # the registry's own internals
            for fn in mod.functions.values():
                labeled_view = _class_uses_labeled_view(fn)
                for site in fn.calls:
                    for kind, name, node in _emissions(site.node):
                        receiver = (site.dotted or "").rsplit(".", 1)[0]
                        has_labels = any(kw.arg == "labels" for kw in node.keywords)
                        site_labeled = labeled_view and receiver.endswith("metrics")
                        if ".labeled(" in ast.unparse(node.func):
                            site_labeled = True
                        if name is None:
                            continue
                        sites.setdefault(name, []).append(
                            (has_labels, mod.relpath, node.lineno, fn.qualname)
                        )
                        findings.extend(
                            self._check_one(
                                kind, name, receiver, has_labels, site_labeled,
                                mod.relpath, node.lineno, fn.qualname,
                            )
                        )

        findings.extend(self._span_discipline(project))

        # mixed labeled/unlabeled families
        for name, occurrences in sorted(sites.items()):
            kinds = {has for has, *_ in occurrences}
            if len(kinds) == 2:
                for has, relpath, line, qual in occurrences:
                    if not has:
                        findings.append(
                            Finding(
                                self.name,
                                relpath,
                                line,
                                qual,
                                f"`{name}` is emitted both with and "
                                "without explicit labels across the "
                                "package; a mixed family splits the "
                                "Prometheus series grouping",
                            )
                        )
        return findings

    def _check_one(
        self,
        kind: str,
        name: str,
        receiver: str,
        has_labels: bool,
        labeled_view: bool,
        relpath: str,
        line: int,
        qual: str,
    ) -> list[Finding]:
        out: list[Finding] = []

        def bad(msg: str) -> None:
            out.append(Finding(self.name, relpath, line, qual, msg))

        if not name.startswith("finchat_"):
            bad(f"metric `{name}` must be namespaced `finchat_*`")
        if kind == "inc" and not name.endswith("_total"):
            bad(f"counter `{name}` must end `_total`")
        if kind in ("observe", "timer") and not name.endswith("_seconds"):
            bad(f"histogram `{name}` must end `_seconds`")
        if kind == "set_gauge" and (
            name.endswith("_total") or name.endswith("_seconds")
        ):
            bad(
                f"gauge `{name}` must not use a counter/histogram suffix "
                "(_total/_seconds)"
            )
        if name.startswith("finchat_fleet_"):
            # PR 6 convention: fleet-level series are unlabeled — never
            # through a replica's LabeledMetrics view and never with
            # explicit labels. A plain registry receiver (METRICS itself,
            # or a self.metrics that is never built from `.labeled(...)`)
            # is fine.
            if has_labels or labeled_view:
                bad(
                    f"fleet-family series `{name}` must be emitted "
                    "unlabeled on the plain METRICS registry (a labeled "
                    "view would split it into per-replica series no "
                    "dashboard sums — the PR 6 convention)"
                )
        return out


    # --- span/trace-event name discipline (ISSUE 12) --------------------
    def _span_discipline(self, project: ProjectIndex) -> list[Finding]:
        registries = _tracing_registries(project)
        if registries is None:
            return []  # no tracing module in the analyzed set
        span_marks, trace_events, anomaly_kinds = (
            registries[v] for v in _REGISTRY_VARS[:3])
        all_names = span_marks | trace_events | anomaly_kinds
        findings: list[Finding] = []

        def bad(mod, node, fn, msg: str) -> None:
            findings.append(Finding(self.name, mod.relpath, node.lineno,
                                    fn.qualname, msg))

        for mod in project.modules.values():
            if not mod.modname.startswith("finchat_tpu."):
                continue
            if mod.relpath.endswith("utils/tracing.py"):
                continue  # the registry's own internals
            for fn in mod.functions.values():
                for site in fn.calls:
                    node = site.node
                    func = node.func
                    if not isinstance(func, ast.Attribute):
                        continue
                    receiver = (dotted_name(func.value) or "")
                    head = receiver.split(".")[-1]
                    declared = _registry_literals(func, head, node)
                    if declared is not None:
                        var, what, names = declared
                        for name in names:
                            # a tracing module without that registry (a
                            # fixture's miniature one) leaves the check out
                            if var in registries and name not in registries[var]:
                                bad(mod, node, fn,
                                    f"{what} `{name}` is not declared in {var} "
                                    "(utils/tracing.py) — nothing that reads a "
                                    "profile or a timeline would find it")
                    elif func.attr == "mark" and head == "span":
                        for name in _name_literals(node):
                            if name not in span_marks:
                                bad(mod, node, fn,
                                    f"span mark `{name}` is not declared in "
                                    "SPAN_MARKS (utils/tracing.py) — a typo'd "
                                    "mark silently vanishes from every timeline")
                    elif func.attr == "event" and head.lower().endswith("tracer"):
                        for name in _name_literals(node):
                            if name not in all_names:
                                bad(mod, node, fn,
                                    f"trace event `{name}` is not declared in "
                                    "the tracing registries (utils/tracing.py)")
                    elif func.attr == "anomaly" and head.lower().endswith("tracer"):
                        for name in _name_literals(node):
                            if name not in anomaly_kinds:
                                bad(mod, node, fn,
                                    f"anomaly kind `{name}` is not declared in "
                                    "ANOMALY_KINDS (utils/tracing.py)")
                    elif func.attr == "_trace":
                        # forwarding-helper convention: the literal event
                        # name rides the helper call (the helper's own
                        # pass-through to TRACER.event is non-literal and
                        # exempt — the literals are checked HERE)
                        for name in _name_literals(node, anywhere=True):
                            if name in all_names:
                                break
                            bad(mod, node, fn,
                                f"trace name `{name}` forwarded through a "
                                "_trace helper is not declared in the tracing "
                                "registries (utils/tracing.py)")
                            break
        return findings


def _registry_literals(func: ast.Attribute, head: str, node: ast.Call):
    """(registry, what it names, the call's literals) for the calls whose
    name argument must come from one of the ISSUE 24 registries — scopes,
    round phases, start-up phases, finish reasons (also through the
    scheduler's ``_close_span(handle, "...")`` forwarding helper) — or
    None for any other call."""
    tracer = head.lower().endswith("tracer")
    if func.attr == "named_scope":
        return "DEVICE_SCOPES", "named scope", _name_literals(node)
    if func.attr == "phase" and tracer:
        return "ROUND_PHASES", "round phase", _name_literals(node)
    if func.attr in ("startup", "startup_phase") and tracer:
        return "STARTUP_PHASES", "start-up phase", _name_literals(node)
    if func.attr == "finish" and head == "span":
        return "FINISH_REASONS", "finish reason", [
            name for kw in node.keywords if kw.arg == "reason"
            for name in _const_strings(kw.value)]
    if func.attr == "_close_span":
        return "FINISH_REASONS", "finish reason", _name_literals(node, anywhere=True)
    return None


def _name_literals(node: ast.Call, anywhere: bool = False) -> list[str]:
    """The event-name string literal(s) of a tracing call: the first
    positional arg (or ``name=`` keyword); with ``anywhere``, the first
    string-literal positional at any position (forwarding helpers take
    ``(state, "name")``-style signatures)."""
    exprs: list[ast.AST] = []
    if anywhere:
        for arg in node.args:
            if _const_strings(arg):
                exprs.append(arg)
                break
    else:
        if node.args:
            exprs.append(node.args[0])
        for kw in node.keywords:
            if kw.arg == "name":
                exprs.append(kw.value)
    out: list[str] = []
    for e in exprs:
        out.extend(_const_strings(e))
    return out


def _tracing_registries(project: ProjectIndex):
    """{registry name: its string set} from the analyzed set's
    ``utils/tracing.py`` (the three event registries always, the others
    where it assigns them), or None when there is no such module."""
    mod = next(
        (m for m in project.modules.values()
         if m.relpath.endswith("utils/tracing.py")),
        None,
    )
    if mod is None:
        return None
    sets: dict[str, set[str]] = {name: set() for name in _REGISTRY_VARS[:3]}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and tgt.id in _REGISTRY_VARS:
                for inner in ast.walk(node.value):
                    if isinstance(inner, ast.Constant) and isinstance(inner.value, str):
                        sets.setdefault(tgt.id, set()).add(inner.value)
    return sets


def _class_uses_labeled_view(fn) -> bool:
    """True when the function's enclosing class ever builds its
    ``self.metrics`` from a ``.labeled(...)`` view — i.e. instances emit
    per-replica series implicitly (the scheduler/session-cache pattern)."""
    cls = fn.cls
    if cls is None:
        return False
    for meth in cls.methods.values():
        for node in ast.walk(meth.node):
            if not isinstance(node, ast.Assign):
                continue
            tgt_hit = any(
                isinstance(t, ast.Attribute)
                and t.attr == "metrics"
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
                for t in node.targets
            )
            if not tgt_hit:
                continue
            for inner in ast.walk(node.value):
                if (
                    isinstance(inner, ast.Call)
                    and isinstance(inner.func, ast.Attribute)
                    and inner.func.attr == "labeled"
                ):
                    return True
    return False


def _emissions(node: ast.Call):
    """Yield (kind, metric_name, call_node) for emission-shaped calls.
    Conditional names (``inc("a" if x else "b")``) yield once per arm."""
    func = node.func
    # Timer(registry, "name")
    if isinstance(func, ast.Name) and func.id == "Timer" and len(node.args) >= 2:
        for name in _const_strings(node.args[1]):
            yield "timer", name, node
        return
    if not isinstance(func, ast.Attribute) or func.attr not in _EMITTERS:
        return
    if not node.args:
        return
    names = _const_strings(node.args[0])
    receiver = dotted_name(func.value) or ""
    for name in names:
        # only metric-shaped literals (avoids unrelated .observe/.inc APIs)
        if name.startswith("finchat_") or "metrics" in receiver.lower() or receiver == "METRICS":
            yield func.attr, name, node


def _const_strings(expr: ast.AST) -> list[str]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return [expr.value]
    if isinstance(expr, ast.IfExp):
        return _const_strings(expr.body) + _const_strings(expr.orelse)
    return []
