"""Static-analysis layer: jaxpr/HLO hazard audits + package AST lint.

Three tiers, one verdict (``lint_report.json``, gated in CI):

- **IR tier** (:mod:`pystella_tpu.lint.graph` +
  :mod:`pystella_tpu.lint.targets`): trace and lower the real step
  functions and audit the lowered StableHLO / compiled HLO for
  donation misses (wasted HBM bytes), dtype-policy violations (silent
  f64), unallowlisted collectives (an accidental all-gather from a bad
  sharding constraint), host interaction (infeed/outfeed/callbacks on
  the step path), and sentinel fusion (the PR-4 health reductions must
  live INSIDE the step module).
- **Dataflow tier** (:mod:`pystella_tpu.lint.dataflow`): def-use
  analysis over the SAME cached artifacts — precision-flow role
  propagation enforcing ``POLICY_BF16_ACC32`` as a flow property
  (bf16 never on an accumulation chain, downcasts only at registered
  carry points), and a static communication model (per-collective
  bytes by class, field-sized replication detection) whose
  ``static_comm`` blocks the perf ledger joins against measured
  traffic.
- **Source tier** (:mod:`pystella_tpu.lint.source`): AST lint over the
  package — host-sync calls in traced hot paths, ``os.environ`` reads
  outside the central registry (:mod:`pystella_tpu.config`),
  unregistered trace-scope literals, and env-var doc coverage.

CLI::

    python -m pystella_tpu.lint [--out DIR] [--targets a,b]
                                [--no-graph] [--no-source]
                                [--no-dataflow]

writes ``lint_report.json`` and exits nonzero on violations. The
:class:`~pystella_tpu.obs.ledger.PerfLedger` folds a ``lint`` run event
into the perf report's ``lint`` section and
:mod:`pystella_tpu.obs.gate` refuses evidence whose lint failed.

See ``doc/static_analysis.md``.
"""

from __future__ import annotations

import os

from pystella_tpu.lint.report import (LINT_SCHEMA_VERSION, LintReport,
                                      Violation)
from pystella_tpu.lint import dataflow, graph, source
from pystella_tpu.lint.dataflow import (audit_dataflow_artifacts,
                                        audit_dataflow_targets)
from pystella_tpu.lint.graph import (ArtifactCache, GraphTarget,
                                     POLICY_BF16_ACC32,
                                     POLICY_F32, POLICY_F64,
                                     POLICY_SPECTRAL_F32,
                                     audit_artifacts, audit_target,
                                     audit_targets, lower_and_compile)
from pystella_tpu.lint.source import HOT_MODULES, check_package

__all__ = [
    "LINT_SCHEMA_VERSION", "LintReport", "Violation",
    "ArtifactCache", "GraphTarget",
    "POLICY_F32", "POLICY_F64", "POLICY_BF16_ACC32",
    "POLICY_SPECTRAL_F32",
    "audit_artifacts", "audit_target", "audit_targets",
    "audit_dataflow_artifacts", "audit_dataflow_targets",
    "lower_and_compile", "HOT_MODULES", "check_package",
    "run_lint", "package_dir", "doc_path",
    "SOURCE_CHECKS", "DOC_CHECK", "GRAPH_CHECKS", "DATAFLOW_CHECKS",
]

#: the canonical checker names per tier — run_lint() and the smoke
#: run's in-run lint both derive their `checks` lists from these, so a
#: new checker cannot silently vanish from one consumer's coverage
SOURCE_CHECKS = ("host-sync", "env-registry", "scope-registry",
                 "event-registry")
#: the doc-coverage check: only meaningful (and only recorded) when a
#: doc file actually exists to check against
DOC_CHECK = "env-doc"
GRAPH_CHECKS = ("donation", "dtype", "collectives", "host", "fusion")
#: the dataflow tier (pystella_tpu.lint.dataflow): precision-flow
#: role propagation + the static communication model
DATAFLOW_CHECKS = dataflow.DATAFLOW_CHECKS


def package_dir():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def doc_path():
    """``doc/observability.md`` of an in-repo checkout (``None`` for an
    installed package without the doc tree)."""
    path = os.path.join(os.path.dirname(package_dir()), "doc",
                        "observability.md")
    return path if os.path.exists(path) else None


def run_lint(pkg_dir=None, targets=None, run_source=True, run_graph=True,
             run_dataflow=None, doc=None, checks=None):
    """Run the requested tiers; returns a
    :class:`~pystella_tpu.lint.report.LintReport`.

    :arg pkg_dir: package directory for the source tier (default: this
        installed ``pystella_tpu``).
    :arg targets: :class:`GraphTarget` list for the IR tier (default:
        :func:`pystella_tpu.lint.targets.default_targets`).
    :arg run_dataflow: run the dataflow tier (precision-flow + static
        comm model) over the same lowered artifacts. Default
        (``None``): follows ``run_graph`` — drivers that skip the IR
        tier and audit their own artifacts skip it here too.
    :arg doc: path for the env-var doc-coverage check (default: the
        in-repo ``doc/observability.md`` when linting the real
        package).
    """
    import time as _time
    if run_dataflow is None:
        run_dataflow = run_graph
    rep = LintReport()
    if run_source:
        if pkg_dir is None:
            pkg_dir = package_dir()
            if doc is None:
                doc = doc_path()
        violations, stats = source.check_package(
            pkg_dir, doc_path=doc, checks=checks)
        rep.extend(violations)
        rep.source = {"package": stats["package"],
                      "files_scanned": stats["files_scanned"]}
        ran = list(SOURCE_CHECKS)
        if doc and os.path.exists(doc):
            ran.append(DOC_CHECK)  # doc coverage only ran with a doc
        for name in ran:
            if checks is None or name in checks:
                rep.add_check(name)
    if run_graph or run_dataflow:
        if targets is None:
            from pystella_tpu.lint.targets import default_targets
            targets = default_targets()
        # one build/lower/compile per target per RUN: the IR-tier
        # audits and the dataflow tier share the same cached artifacts
        cache = graph.ArtifactCache()
        t0 = _time.perf_counter()
        if run_graph:
            violations, graph_stats, donation = graph.audit_targets(
                targets, cache=cache)
            rep.extend(violations)
            rep.graph = graph_stats
            rep.donation = donation
            for name in GRAPH_CHECKS:
                rep.add_check(name)
        if run_dataflow:
            violations, df_stats = dataflow.audit_dataflow_targets(
                targets, cache=cache)
            rep.extend(violations)
            for tname, stats in df_stats.items():
                g = rep.graph.setdefault(tname, {})
                audits = stats.pop("timing_audits", None)
                g.update(stats)
                if audits:
                    tm = g.setdefault("timing",
                                      {"audits": {}, "total_s": 0.0})
                    tm.setdefault("audits", {}).update(audits)
                    tm["total_s"] = round(
                        tm.get("total_s", 0.0)
                        + sum(audits.values()), 4)
            for name in DATAFLOW_CHECKS:
                rep.add_check(name)
        rep.timing = {
            "targets": {
                tname: (stats.get("timing") or {}).get("total_s")
                for tname, stats in rep.graph.items()},
            "total_s": round(_time.perf_counter() - t0, 4),
            "cache": cache.stats()}
    return rep
