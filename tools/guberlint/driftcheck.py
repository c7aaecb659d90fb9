"""Pass 7 — knob/metric/doc drift across the whole surface.

The operator contract, enforced (STATIC_ANALYSIS.md):

- ``drift-knob-no-config-home`` — a GUBER_* env var is read somewhere
  (Python call-site string literal under config.KNOB_SCAN_ROOTS, or a
  getenv in the native sources) but config.py — the canonical
  env-surface index — never mentions it.  Daemon knobs load there;
  debug/infra knobs read elsewhere are indexed by the KNOWN_ENV_KNOBS
  registry.
- ``drift-knob-undocumented`` — a knob is read but has no row in the
  README's configuration table (config.KNOB_DOC_FILE).
- ``drift-knob-stale`` — the README documents a GUBER_* knob nothing
  reads any more: the row promises a lever that no longer exists.
- ``drift-metric-undocumented`` — a metric registered in
  utils/metrics.py appears in none of config.METRIC_DOC_FILES (README/
  PERF/RESILIENCE/STATIC_ANALYSIS or the bench-trend columns).
- ``drift-metric-stale`` — a doc names a ``gubernator_*`` metric the
  registry no longer exports.
- ``drift-span-name-style`` / ``drift-span-name-duplicate`` — the
  trace sub-rule: every literal ``span("name", ...)`` site must be
  dot-separated snake_case (span names are an operator-facing query
  surface: /debug/trace, the OTel backend, OBSERVABILITY.md's
  catalog), and each name must identify ONE site — two sites sharing
  a name make "where did this span come from" unanswerable.
  Deliberate twins (the sharded engine mirrors engine.py's stages
  under the same names so the tests/oracles stay backend-agnostic)
  carry reasoned suppressions at the twin site.  A literal
  ``stage("name", ...)`` / ``self._stage("name")`` site
  (utils/metrics.stage: histogram, span and profiler annotation under
  one name) is held to the same style; a stage is a PHASE, so one
  module may time it at several sites, but a second module that
  times the same stage is a twin and says so, once, at its first site.
- ``drift-slo-metric-unregistered`` / ``drift-slo-no-metric`` — the
  slo sub-rule: every ``SLI(...)`` declaration in config.SLO_REGISTRY
  (obs/slo.py) must carry a literal ``metric=`` naming a series
  utils/metrics.py actually registers.  An SLI is an operator promise
  ("this burn rate watches that metric"); one over a dropped or
  mistyped series would silently evaluate nothing.

Knob reads are collected from the AST (string literals used as call
arguments), so prose/docstrings never count as reads; metric
registrations are the first-argument literals of ``*MetricFamily``
constructors; span sites are calls to a function named ``span``
(``stage`` / ``_stage`` for stage sites) with a literal first argument.  Suppression uses the normal grammar at the
read / registration / span site.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Set, Tuple

from tools.guberlint.common import Finding, SourceFile, iter_py_files
from tools.guberlint.config import (
    EXCLUDE,
    KNOB_DOC_FILE,
    KNOB_HOME,
    KNOB_SCAN_ROOTS,
    METRIC_DOC_FILES,
    METRIC_REGISTRY,
    SLO_REGISTRY,
)
from tools.guberlint.csource import CSourceFile

PASS = "drift"

_KNOB_RE = re.compile(r"^GUBER_[A-Z0-9_]+$")
_DOC_KNOB_RE = re.compile(r"\bGUBER_[A-Z0-9_]+\b")
_DOC_METRIC_RE = re.compile(r"\bgubernator_[a-z0-9_]+\b")
# Tokens the metric regex matches that are not metrics.
_METRIC_TOKEN_EXCLUDE = {"gubernator_tpu", "gubernator_pb2", "gubernator_pool"}


def check(repo_root: Path, csrcs: List[CSourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    reads = _knob_reads(repo_root, csrcs)
    _check_knobs(repo_root, reads, findings)
    _check_metrics(repo_root, findings)
    _check_spans(repo_root, findings)
    _check_slo(repo_root, findings)
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings


# -- knob surface ------------------------------------------------------


def _knob_reads(
    repo_root: Path, csrcs: List[CSourceFile]
) -> Dict[str, List[Tuple[SourceFile, int]]]:
    """knob -> [(source, line)] read sites.  A 'read' is a GUBER_*
    string literal appearing as a call argument (env lookups), never a
    docstring/prose mention."""
    reads: Dict[str, List[Tuple[object, int]]] = {}
    roots = [repo_root / r for r in KNOB_SCAN_ROOTS]
    for src in iter_py_files(roots, repo_root, exclude=EXCLUDE):
        if src.tree is None:
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and _KNOB_RE.match(arg.value)
                ):
                    reads.setdefault(arg.value, []).append(
                        (src, arg.lineno)
                    )
    for csrc in csrcs:
        for lineno, value in csrc.strings:
            if _KNOB_RE.match(value):
                line = csrc.lines[lineno - 1] if lineno <= len(csrc.lines) else ""
                prev = csrc.lines[lineno - 2] if lineno >= 2 else ""
                if "getenv" in line or "getenv" in prev:
                    reads.setdefault(value, []).append((csrc, lineno))
    return reads


def _check_knobs(
    repo_root: Path,
    reads: Dict[str, List[Tuple[object, int]]],
    findings: List[Finding],
) -> None:
    home_path = repo_root / KNOB_HOME
    home_text = home_path.read_text() if home_path.exists() else ""
    doc_path = repo_root / KNOB_DOC_FILE
    doc_text = doc_path.read_text() if doc_path.exists() else ""
    for knob in sorted(reads):
        src, lineno = reads[knob][0]
        rel = getattr(src, "rel", "")
        # C getenv reads: the config-home side is the CONTRACT pass's
        # rule (contract/knob-homeless) — reporting it here too would
        # double-bill one defect.  The README-row check below still
        # applies to C-read knobs.
        is_c_read = rel.endswith((".cpp", ".cc", ".c", ".h", ".hpp"))
        if rel != KNOB_HOME and not is_c_read and knob not in home_text:
            if not src.suppressed(lineno, PASS):
                findings.append(
                    Finding(
                        PASS, "knob-no-config-home", src.rel, lineno,
                        "<module>", knob,
                        f"{knob} is read here but config.py (the "
                        "canonical GUBER_* index) never mentions it — "
                        "add it to the daemon config or the "
                        "KNOWN_ENV_KNOBS registry",
                    )
                )
        if knob not in doc_text:
            if not src.suppressed(lineno, PASS):
                findings.append(
                    Finding(
                        PASS, "knob-undocumented", src.rel, lineno,
                        "<module>", knob,
                        f"{knob} is read here but {KNOB_DOC_FILE}'s "
                        "configuration table has no row for it",
                    )
                )
    # Reverse: documented knobs nothing reads.
    for m in _DOC_KNOB_RE.finditer(doc_text):
        knob = m.group(0)
        if knob in reads:
            continue
        # Prefix rows like GUBER_TLS_CLIENT_AUTH cover their family.
        if any(r.startswith(knob) for r in reads):
            continue
        lineno = doc_text[: m.start()].count("\n") + 1
        findings.append(
            Finding(
                PASS, "knob-stale", KNOB_DOC_FILE, lineno, "<module>",
                knob,
                f"{KNOB_DOC_FILE} documents {knob} but nothing reads "
                "it — drop the row or re-wire the knob",
            )
        )


# -- span-site surface (the trace sub-rule) ----------------------------

# Dot-separated snake_case: "global.hits_window", "engine.batch".
_SPAN_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)*$")


_SPAN_CALLS = {"span": "span", "stage": "stage", "_stage": "stage"}


def _span_sites(
    repo_root: Path,
) -> List[Tuple[str, SourceFile, int, str]]:
    """(name, source, line, kind) for every literal span("name", ...)
    or stage("name", ...) call under KNOB_SCAN_ROOTS.  Helper-routed
    spans (a variable name argument) are invisible here by design —
    the rule governs the literal catalog OBSERVABILITY.md indexes."""
    out: List[Tuple[str, SourceFile, int, str]] = []
    roots = [repo_root / r for r in KNOB_SCAN_ROOTS]
    for src in iter_py_files(roots, repo_root, exclude=EXCLUDE):
        if src.tree is None:
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else ""
            )
            kind = _SPAN_CALLS.get(name)
            if kind is None or not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.append((arg.value, src, node.lineno, kind))
    out.sort(key=lambda site: (site[1].rel, site[2]))
    return out


def _check_spans(repo_root: Path, findings: List[Finding]) -> None:
    sites = _span_sites(repo_root)
    by_name: Dict[str, List[Tuple[SourceFile, int]]] = {}
    for name, src, line, kind in sites:
        if not _SPAN_NAME_RE.match(name):
            if not src.suppressed(line, PASS):
                findings.append(
                    Finding(
                        PASS, "span-name-style", src.rel, line,
                        "<module>", name,
                        f"span name {name!r} is not dot-separated "
                        "snake_case — span names are the /debug/trace "
                        "+ OTel query surface (OBSERVABILITY.md)",
                    )
                )
        where = by_name.setdefault(name, [])
        # A stage is a phase of its module's path: its later sites in
        # a module that already has one are the same phase, not twins.
        if kind == "stage" and any(s.rel == src.rel for s, _l in where):
            continue
        where.append((src, line))
    for name, where in sorted(by_name.items()):
        if len(where) < 2:
            continue
        first_src, first_line = where[0]
        for src, line in where[1:]:
            if src.suppressed(line, PASS):
                continue
            findings.append(
                Finding(
                    PASS, "span-name-duplicate", src.rel, line,
                    "<module>", name,
                    f"span name {name!r} is also emitted at "
                    f"{first_src.rel}:{first_line} — a span name must "
                    "identify one site; rename, or suppress the "
                    "deliberate twin with its reason",
                )
            )


# -- SLI surface (the slo sub-rule) ------------------------------------


def _check_slo(repo_root: Path, findings: List[Finding]) -> None:
    """Every SLI(...) declaration in config.SLO_REGISTRY must name a
    registered metric via a literal ``metric=`` kwarg."""
    path = repo_root / SLO_REGISTRY
    if not path.exists():
        return
    src = SourceFile(path, SLO_REGISTRY)
    if src.tree is None:
        return
    registered = {name for name, _src, _line in _registered_metrics(repo_root)}
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = (
            func.attr if isinstance(func, ast.Attribute)
            else func.id if isinstance(func, ast.Name) else ""
        )
        if name != "SLI":
            continue
        metric = None
        for kw in node.keywords:
            if kw.arg == "metric" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                metric = kw.value.value
        if metric is None:
            if not src.suppressed(node.lineno, PASS):
                findings.append(
                    Finding(
                        PASS, "slo-no-metric", src.rel, node.lineno,
                        "<module>", f"SLI@{node.lineno}",
                        "SLI declaration without a literal metric= — "
                        "every declared SLI must name the documented "
                        "metric backing it (the drift slo sub-rule "
                        "cannot verify a computed name)",
                    )
                )
            continue
        if metric in registered:
            continue
        if src.suppressed(node.lineno, PASS):
            continue
        findings.append(
            Finding(
                PASS, "slo-metric-unregistered", src.rel, node.lineno,
                "<module>", metric,
                f"SLI declares metric {metric} but "
                f"{METRIC_REGISTRY} never registers it — the burn "
                "rate would watch a series that does not exist",
            )
        )


# -- metric surface ----------------------------------------------------


def _registered_metrics(repo_root: Path) -> List[Tuple[str, SourceFile, int]]:
    path = repo_root / METRIC_REGISTRY
    if not path.exists():
        return []
    src = SourceFile(path, METRIC_REGISTRY)
    out: List[Tuple[str, SourceFile, int]] = []
    if src.tree is None:
        return out
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = (
            func.attr if isinstance(func, ast.Attribute)
            else func.id if isinstance(func, ast.Name) else ""
        )
        if not name.endswith("MetricFamily"):
            continue
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            out.append((node.args[0].value, src, node.args[0].lineno))
    return out


def _check_metrics(repo_root: Path, findings: List[Finding]) -> None:
    registered = _registered_metrics(repo_root)
    doc_texts = {
        rel: (repo_root / rel).read_text()
        for rel in METRIC_DOC_FILES
        if (repo_root / rel).exists()
    }
    names: Set[str] = set()
    for metric, src, lineno in registered:
        names.add(metric)
        if any(metric in text for text in doc_texts.values()):
            continue
        if src.suppressed(lineno, PASS):
            continue
        findings.append(
            Finding(
                PASS, "metric-undocumented", METRIC_REGISTRY, lineno,
                "<module>", metric,
                f"metric {metric} is registered but appears in none "
                f"of {', '.join(METRIC_DOC_FILES)} — document what it "
                "means or it is noise on the scrape",
            )
        )
    # Reverse: docs promising metrics the registry no longer exports.
    # Hierarchical names are fine: a doc token that is a PREFIX of a
    # registered metric (or vice versa) still refers to a live series.
    for rel, text in doc_texts.items():
        seen: Set[str] = set()
        for m in _DOC_METRIC_RE.finditer(text):
            token = m.group(0)
            if token in seen or token in _METRIC_TOKEN_EXCLUDE:
                continue
            seen.add(token)
            if any(
                token == n or token.startswith(n) or n.startswith(token)
                for n in names
            ):
                continue
            lineno = text[: m.start()].count("\n") + 1
            findings.append(
                Finding(
                    PASS, "metric-stale", rel, lineno, "<module>",
                    token,
                    f"{rel} names metric {token} but utils/metrics.py "
                    "never registers it — stale doc or a dropped "
                    "series",
                )
            )
