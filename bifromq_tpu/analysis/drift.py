"""R5 — trace-span and metric-registry drift.

Observability names are stringly-typed: a typo'd span or stage name
silently creates a new series nobody dashboards, and a README table row
for a deleted span misleads the operator reading a live trace. There is
ONE registry of boundary names, the table in ``trace/names.py``; the
README's span table is generated from it. Checks:

- **R5/span**: every span or counter name opened in code
  (``trace.span(...)``, ``trace.record_finished(...)``,
  ``trace.count(...)``) must have a row in the registry, and every
  ``span`` / ``counter`` row must still be opened somewhere in code.
- **R5/span-doc**: the README's span table must hold exactly the
  registry's names (regenerate it: ``python -m bifromq_tpu.trace
  --write``).
- **R5/stage**: every literal stage fed to the always-on stage
  histograms by hand (``STAGES.record``, ``Batcher(stage=...)``,
  ``OBS.record_latency``) must be a stage some row names, and every
  such stage must be emitted somewhere: by a literal, or by a span whose
  row feeds it (dead registry entries fail too).
- **R5/cache-field**: literal fields passed to ``MATCH_CACHE.inc`` must
  be declared in ``MatchCacheMetrics._FIELDS``.

The registries are parsed from the analyzed tree's ``trace/names.py``
and ``utils/metrics.py``; when the root has none (fixture runs), the
installed package's are used so fixture snippets still check.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Context, Finding, ParsedFile, Rule, dotted_name

_SPAN_OPENERS = {"span", "record_finished", "count"}
_SPAN_NAME_RE = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
_BACKTICK_RE = re.compile(r"`([^`]+)`")


def _collect_spans(ctx: Context) -> Dict[str, List[Tuple[str, int, str]]]:
    spans: Dict[str, List[Tuple[str, int, str]]] = {}
    for pf in ctx.files:
        for node in ast.walk(pf.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            callee = dotted_name(node.func).rsplit(".", 1)[-1]
            if callee not in _SPAN_OPENERS:
                continue
            a0 = node.args[0]
            if isinstance(a0, ast.Constant) and isinstance(a0.value, str) \
                    and _SPAN_NAME_RE.match(a0.value):
                spans.setdefault(a0.value, []).append(
                    (pf.path, node.lineno, pf.scope_of(node)))
    return spans


def _readme_span_table(readme: str) -> Set[str]:
    """Span names from the first cell of every row of the table whose
    header starts ``| span |``."""
    out: Set[str] = set()
    in_table = False
    for line in readme.splitlines():
        stripped = line.strip()
        if stripped.startswith("| span |"):
            in_table = True
            continue
        if in_table:
            if not stripped.startswith("|"):
                in_table = False
                continue
            first_cell = stripped.split("|")[1]
            for name in _BACKTICK_RE.findall(first_cell):
                if _SPAN_NAME_RE.match(name):
                    out.add(name)
    return out


def _find(ctx: Context, suffix: str) -> Optional[ParsedFile]:
    for pf in ctx.files:
        if pf.path.replace("\\", "/").endswith(suffix):
            return pf
    return None


def _parse_boundaries(pf: Optional[ParsedFile]) -> Dict[str, dict]:
    """``{name: {kind, stage, window, by_hand}}`` from the ``_row(...)``
    calls of a ``trace/names.py`` AST; the installed package's table when
    the analyzed root has none."""
    if pf is None:
        from ..trace.names import BOUNDARIES
        return {b.name: {"kind": b.kind, "stage": b.stage,
                         "window": b.window, "by_hand": b.by_hand}
                for b in BOUNDARIES.values()}
    rows: Dict[str, dict] = {}
    for node in ast.walk(pf.tree):
        if not (isinstance(node, ast.Call)
                and dotted_name(node.func) == "_row"
                and len(node.args) >= 2
                and all(isinstance(a, ast.Constant) for a in node.args[:2])):
            continue
        row = {"kind": node.args[1].value, "stage": None, "window": None,
               "by_hand": False}
        for kw in node.keywords:
            if kw.arg in row and isinstance(kw.value, ast.Constant):
                row[kw.arg] = kw.value.value
        rows[node.args[0].value] = row
    return rows


def _parse_cache_fields(pf: Optional[ParsedFile]) -> Set[str]:
    """``MatchCacheMetrics._FIELDS`` from a metrics module's AST; the
    installed package's when the analyzed root has no utils/metrics.py."""
    if pf is None:
        from ..utils.metrics import MatchCacheMetrics
        return set(MatchCacheMetrics._FIELDS)
    for node in ast.walk(pf.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and t.id == "_FIELDS":
                return {n.value for n in ast.walk(node.value)
                        if isinstance(n, ast.Constant)
                        and isinstance(n.value, str)}
    return set()


class RegistryDriftRule(Rule):
    rule_id = "R5"
    title = "trace/metric registry drift"

    def run(self, ctx: Context) -> List[Finding]:
        out: List[Finding] = []
        names_pf = _find(ctx, "trace/names.py")
        rows = _parse_boundaries(names_pf)
        cache_fields = _parse_cache_fields(_find(ctx, "utils/metrics.py"))
        known_stages = {s for r in rows.values()
                        for s in (r["stage"], r["window"]) if s}
        spans = _collect_spans(ctx)

        # -- span <-> registry <-> README ------------------------------------
        for name, sites in sorted(spans.items()):
            if name not in rows:
                path, line, scope = sites[0]
                out.append(Finding(
                    rule=self.rule_id, path=path, line=line,
                    scope=scope, symbol=name,
                    message=(f"boundary `{name}` is opened in code but "
                             f"has no row in trace/names.py")))
        if names_pf is not None:
            for name, row in sorted(rows.items()):
                if row["kind"] != "stage" and name not in spans:
                    out.append(Finding(
                        rule=self.rule_id, path=names_pf.path, line=0,
                        scope="<BOUNDARIES>", symbol=name,
                        message=(f"registry row `{name}` is opened "
                                 f"nowhere in code — dead entry")))
            if ctx.readme_text is not None:
                documented = _readme_span_table(ctx.readme_text)
                for name in sorted(set(rows) ^ documented):
                    out.append(Finding(
                        rule=self.rule_id, path="README.md", line=0,
                        scope="<span-table>", symbol=name,
                        message=(f"README span table and trace/names.py "
                                 f"disagree on `{name}` — regenerate the "
                                 f"table (python -m bifromq_tpu.trace "
                                 f"--write)")))

        # -- stage registry --------------------------------------------------
        emitted: Dict[str, List[Tuple[str, int, str]]] = {}
        for pf in ctx.files:
            for node in ast.walk(pf.tree):
                if not isinstance(node, ast.Call):
                    continue
                stage = self._stage_literal(node)
                if stage is not None:
                    emitted.setdefault(stage, []).append(
                        (pf.path, node.lineno, pf.scope_of(node)))
                self._check_cache_field(pf, node, cache_fields, out)
        if known_stages:
            for stage, sites in sorted(emitted.items()):
                if stage not in known_stages:
                    path, line, scope = sites[0]
                    out.append(Finding(
                        rule=self.rule_id, path=path, line=line,
                        scope=scope, symbol=stage,
                        message=(f"stage `{stage}` recorded but no row of "
                                 f"trace/names.py names it — typo'd "
                                 f"stage names create silent orphan "
                                 f"histograms")))
            if names_pf is not None:
                # a stage is alive when a literal feeds it, or a span
                # whose row feeds it (not by hand) is opened in code
                fed = set(emitted)
                for name, row in rows.items():
                    if name in spans and not row["by_hand"]:
                        fed.update(s for s in (row["stage"], row["window"])
                                   if s)
                for stage in sorted(known_stages - fed):
                    out.append(Finding(
                        rule=self.rule_id, path=names_pf.path, line=0,
                        scope="<KNOWN_STAGES>", symbol=stage,
                        message=(f"registered stage `{stage}` is "
                                 f"emitted nowhere — dead registry "
                                 f"entry")))
        return out

    @staticmethod
    def _stage_literal(node: ast.Call) -> Optional[str]:
        callee = dotted_name(node.func)
        short = callee.rsplit(".", 1)[-1]
        # STAGES.record("stage", secs) / STAGES.hist("stage")
        if short in ("record", "hist") and "STAGES" in callee \
                and node.args:
            a0 = node.args[0]
            if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                return a0.value
        # OBS.record_latency(tenant, "stage", secs)
        if short == "record_latency" and len(node.args) >= 2:
            a1 = node.args[1]
            if isinstance(a1, ast.Constant) and isinstance(a1.value, str):
                return a1.value
        # Batcher(..., stage="x") / BatchCallScheduler(..., stage="x")
        if short in ("Batcher", "BatchCallScheduler"):
            for kw in node.keywords:
                if kw.arg == "stage" \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    return kw.value.value
        return None

    def _check_cache_field(self, pf: ParsedFile, node: ast.Call,
                           fields: Set[str], out: List[Finding]) -> None:
        callee = dotted_name(node.func)
        if not (callee.endswith(".inc") and "MATCH_CACHE" in callee
                and len(node.args) >= 2):
            return
        a1 = node.args[1]
        if isinstance(a1, ast.Constant) and isinstance(a1.value, str) \
                and fields and a1.value not in fields:
            out.append(Finding(
                rule=self.rule_id, path=pf.path, line=node.lineno,
                scope=pf.scope_of(node), symbol=a1.value,
                message=(f"MATCH_CACHE field `{a1.value}` not declared "
                         f"in MatchCacheMetrics._FIELDS")))
