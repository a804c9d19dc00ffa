"""Pass ``trace-sites``: span names form a closed registry.

Every span literal started anywhere — ``trace_begin``/``trace_span``/
``record_span`` helpers, ``tracer.start_request``/
``tracer.start_background`` roots, the HTTP router's
``_trace_request`` wrapper, and a stage histogram fed under a literal
name (``observe_stage``: ``query.respond``, which ends after its
root has finished) — must resolve to
:data:`opentsdb_tpu.obs.trace.KNOWN_SPANS` (the ``faults.KNOWN_SITES``
idiom): a typo'd stage would otherwise record an orphan stage nothing
dashboards or the shape-log miner ever look for. The reverse is
checked too: a REGISTERED name never started anywhere in the package
or tests is reported stale (only when the scan includes the registry's
defining module, so fixture runs over single files don't false-flag
the whole registry).
"""

from __future__ import annotations

import ast

from opentsdb_tpu.tools.tsdlint.base import Finding, dotted_name

PASS_ID = "trace-sites"

# unique helper names: the first str constant among the leading args
# is the span name (record_span takes (ctx, name, ...))
_FUNCS = {"trace_begin", "trace_span", "record_span",
          "_trace_request", "observe_stage"}
# root starters: only on tracer-ish receivers (other classes may
# legitimately own a start_background)
_METHODS = {"start_request", "start_background"}

_REGISTRY_REL = "opentsdb_tpu/obs/trace.py"


def _span_names_in(src) -> list[tuple[str, int]]:
    out: list[tuple[str, int]] = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        term = func.attr if isinstance(func, ast.Attribute) else \
            func.id if isinstance(func, ast.Name) else ""
        if term in _METHODS:
            recv = dotted_name(func.value).rsplit(".", 1)[-1] \
                if isinstance(func, ast.Attribute) else ""
            if "tracer" not in recv:
                continue
        elif term not in _FUNCS:
            continue
        for arg in node.args[:2]:
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str):
                out.append((arg.value, node.lineno))
                break
    return out


def run(package_sources, test_sources, ctx) -> list[Finding]:
    from opentsdb_tpu.obs.trace import KNOWN_SPANS
    findings: list[Finding] = []
    used: set[str] = set()
    registry_src = None
    for src in list(package_sources) + list(test_sources):
        if src.rel.endswith(_REGISTRY_REL):
            registry_src = src
        for name, line in _span_names_in(src):
            used.add(name)
            if name in KNOWN_SPANS or src.allowed(PASS_ID, line):
                continue
            findings.append(Finding(
                PASS_ID, src.path, src.rel, line,
                f"span name {name!r} is not registered in "
                f"obs/trace.py KNOWN_SPANS — starting it raises at "
                f"runtime",
                detail=name))
    if registry_src is not None:
        # stale check only on scans that include the registry: a
        # single-fixture run must not flag every registered name
        for name in sorted(KNOWN_SPANS - used):
            line = 0
            needle = f'"{name}"'
            for i, text in enumerate(registry_src.text.splitlines(),
                                     1):
                if needle in text:
                    line = i
                    break
            if registry_src.allowed(PASS_ID, line):
                continue
            findings.append(Finding(
                PASS_ID, registry_src.path, registry_src.rel, line,
                f"span name {name!r} is registered in KNOWN_SPANS "
                f"but never started anywhere — stale entry",
                detail=f"stale:{name}"))
    return findings
