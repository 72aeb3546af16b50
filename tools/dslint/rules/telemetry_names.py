"""unregistered-telemetry-name: every span opened and every metric
instrument created anywhere in the tree must carry a name registered in
the telemetry single-source registries —
``deepspeed_tpu/telemetry/spans.py::SpanName`` for ``.span(...)`` sites,
``deepspeed_tpu/telemetry/metrics.py::MetricName`` for
``.counter/.gauge/.histogram(...)`` sites.  The same machinery as
``unregistered-journal-kind``: an ad-hoc string at an emit site is a name
the docs tables (``docs/telemetry.md``), the span-inventory gate
(``BENCH_TELEMETRY.json``), and the offline report can't account for.

Checked call shapes: ``<obj>.span(<name>, ...)``,
``<obj>.record(<name>, ...)`` (a finished span noted earlier) and
``<obj>.counter/gauge/histogram(<name>, ...)``, where ``<name>`` is a
string literal (must be a registered value) or a ``SpanName.X`` /
``MetricName.X`` attribute (``X`` must be a registered name).
Dynamically-computed names pass through uninspected.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..core import FileContext, Finding, Rule

SPAN_METHODS = {"span", "record"}
METRIC_METHODS = {"counter", "gauge", "histogram"}


class UnregisteredTelemetryName(Rule):
    id = "unregistered-telemetry-name"
    description = ("span/metric names must be registered in "
                   "telemetry/spans.py::SpanName and "
                   "telemetry/metrics.py::MetricName")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith(("deepspeed_tpu/", "scripts/")) \
            and not relpath.endswith(("telemetry/spans.py",
                                      "telemetry/metrics.py"))

    def check(self, tree: ast.Module,
              ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.args):
                continue
            method = node.func.attr
            if method in SPAN_METHODS:
                registry, values, names = ("SpanName",
                                           ctx.project.span_names,
                                           set(ctx.project.span_name_map))
            elif method in METRIC_METHODS:
                registry, values, names = ("MetricName",
                                           ctx.project.metric_names,
                                           set(ctx.project.metric_name_map))
            else:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if arg.value not in values:
                    yield ctx.finding(
                        self.id, node,
                        f"telemetry name '{arg.value}' at a .{method}() "
                        f"site is not registered in {registry} — register "
                        "it (and its docs/telemetry.md row) first")
            elif isinstance(arg, ast.Attribute) \
                    and isinstance(arg.value, ast.Name) \
                    and arg.value.id == registry:
                if arg.attr not in names:
                    yield ctx.finding(
                        self.id, node,
                        f"{registry}.{arg.attr} is not defined in the "
                        f"telemetry {registry} registry")
