"""Profiler capture and Perfetto-trace analysis for the PR-1 scope names.

PR 1 threaded :func:`pystella_tpu.obs.scope.trace_scope` names through
every hot path (RK stages, halo exchange, Pallas stencils, multigrid
smoothers); this module closes the loop by turning a captured trace back
into *numbers* — per-scope durations the perf ledger can cite, instead
of a screenshot of a timeline.

Two halves:

- :class:`capture` — a context manager around ``jax.profiler``
  start/stop that, on exit, locates the emitted Perfetto
  ``*.trace.json.gz``, parses it, and emits one ``trace_summary`` run
  event carrying the per-scope duration table. Degrades gracefully: a
  backend that produces no trace file (some CPU/interpret setups) emits
  a ``trace_missing`` event and ``summary`` stays ``None`` — the
  instrumented run never dies for lack of a profile.
- the parser (:func:`find_trace_file`, :func:`parse_trace_file`,
  :func:`scope_durations`) — stdlib-only (``gzip`` + ``json``), so the
  jax-free supervisor and offline analysis scripts can digest a
  trace captured elsewhere.

Matching semantics: a trace event belongs to the *longest* known scope
name that appears in the event name at a token boundary (so host-side
``TraceAnnotation`` spans named ``halo_exchange`` match exactly;
device-op rows named ``jit(step)/fused_rk_stage_pair/fusion.3`` match
``fused_rk_stage_pair`` and NOT its prefix ``fused_rk_stage``; the
generic stepper's ``rk_stage0`` ... ``rk_stage4`` all fold into
``rk_stage``). Nested scopes each keep their own wall time — per-scope
totals may overlap and are reported as independent rows, not a
partition of the window.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

from pystella_tpu.obs import events as _events
from pystella_tpu.obs import scope as _scope
from pystella_tpu.obs.scope import RAW_OP_ALIASES as _ALIASES
from pystella_tpu.obs.scope import registered_scopes as _registered

__all__ = ["KNOWN_SCOPES", "capture", "find_trace_file",
           "parse_trace_file", "scope_durations", "summarize_trace",
           "format_host_spans"]

# The instrumentation vocabulary (doc/observability.md "Trace
# scopes") is the central registry in :mod:`pystella_tpu.obs.scope`:
# ``KNOWN_SCOPES`` (served via module ``__getattr__`` below) and every
# ``scopes=None`` default in this module resolve the registry AT CALL
# TIME, so ``register_scope()`` after import is sufficient for traces
# and ledger tables to pick a scope up (and an unregistered literal
# fails ``tests/test_scope_registry.py``). Notable members:
# ``halo_overlap*`` are the overlapped-halo-path phases (whole
# overlapped update / interior-while-collectives-fly / shell
# stitching); ``collective-permute`` matches the RAW XLA ppermute op
# rows (spelled ``ppermute.N`` by jax 0.9 — ``scope.RAW_OP_ALIASES``),
# which appear in device traces without any named-scope path — the
# comm-time denominator for the ledger's exposed-vs-hidden breakdown.


def __getattr__(name):
    if name == "KNOWN_SCOPES":
        return tuple(sorted(_registered()))
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _scope_matchers(scopes):
    """Longest-first ``(scope, compiled_regex)`` pairs. The boundary
    rule: the scope name must not be preceded by an identifier char and
    must not be followed by a lowercase letter or underscore — digits
    ARE allowed after (``rk_stage0`` is an ``rk_stage`` span) but
    ``fused_rk_stage_pair`` is not a ``fused_rk_stage`` span. A raw-op
    alias (``scope.RAW_OP_ALIASES``) matches under the same rule and
    counts toward the scope it stands for."""
    names = {s: s for s in scopes}
    names.update({a: s for a, s in _ALIASES.items() if s in names})
    out = []
    for n in sorted(names, key=len, reverse=True):
        out.append((names[n], re.compile(
            r"(?<![A-Za-z0-9_])" + re.escape(n) + r"(?![a-z_])")))
    return out


def find_trace_file(logdir):
    """Newest ``*.trace.json(.gz)`` under ``logdir`` (jax writes
    ``<logdir>/plugins/profile/<run>/<host>.trace.json.gz``), or ``None``
    when the capture produced nothing."""
    hits = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        hits += glob.glob(os.path.join(logdir, "**", pat), recursive=True)
    if not hits:
        return None
    return max(hits, key=os.path.getmtime)


def parse_trace_file(path):
    """The Perfetto/Chrome ``traceEvents`` list from a ``.json`` or
    ``.json.gz`` trace file. Returns ``[]`` for unreadable or
    schema-less files rather than raising — trace analysis is evidence
    collection, not a correctness gate."""
    try:
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "rt") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return []
    evs = data.get("traceEvents") if isinstance(data, dict) else None
    return evs if isinstance(evs, list) else []


def scope_durations(trace_events, scopes=None):
    """Fold complete-span events (``ph == "X"``, microsecond ``dur``)
    into ``{scope: {"count", "total_ms", "mean_ms", "min_ms",
    "max_ms"}}`` for every known scope that appears (default: the live
    scope registry). Each event counts toward the longest matching
    scope only."""
    matchers = _scope_matchers(_registered() if scopes is None
                               else scopes)
    acc = {}
    for ev in trace_events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        name = ev.get("name")
        dur = ev.get("dur")
        if not isinstance(name, str) or not isinstance(dur, (int, float)):
            continue
        for scope, rx in matchers:
            if rx.search(name):
                ms = dur / 1e3
                a = acc.setdefault(scope, [0, 0.0, ms, ms])
                a[0] += 1
                a[1] += ms
                a[2] = min(a[2], ms)
                a[3] = max(a[3], ms)
                break
    return {scope: {"count": n, "total_ms": tot, "mean_ms": tot / n,
                    "min_ms": lo, "max_ms": hi}
            for scope, (n, tot, lo, hi) in sorted(acc.items())}


def summarize_trace(logdir, scopes=None, label="", step=None,
                    log=None, host_spans=None):
    """Parse the newest trace under ``logdir`` into a per-scope duration
    table and emit it as one ``kind="trace_summary"`` run event
    (``kind="trace_missing"`` when no trace file appeared — CPU or
    interpret-mode captures sometimes produce none). ``host_spans`` (a
    :func:`pystella_tpu.obs.scope.span_table`) rides on the event as it
    is. Returns the summary dict, or ``None`` when there was nothing to
    parse."""
    sink = log if log is not None else _events.get_log()
    path = find_trace_file(logdir)
    if path is None:
        sink.emit("trace_missing", step=step, logdir=str(logdir),
                  label=label)
        return None
    table = scope_durations(parse_trace_file(path), scopes)
    summary = {"trace_file": path, "label": label, "scopes": table}
    if host_spans is not None:
        summary["host_spans"] = host_spans
    sink.emit("trace_summary", step=step, **summary)
    return summary


def format_host_spans(table):
    """The lines ``--profile`` prints for a ``host_spans`` table."""
    steps = table.get("steps")
    head = f"host spans over {steps} steps" if steps else "host spans"
    if "host_syncs_per_step" in table:
        head += (f": {table['fetches']} fetches, "
                 f"{table['host_syncs_per_step']:.3g} host syncs per step")
    lines = [head, f"  {'span':<20}{'count':>7}{'total ms':>12}"
                   f"{'self ms':>12}" + (f"{'ms/step':>10}" if steps else "")]
    for name, row in sorted(table["spans"].items(),
                            key=lambda kv: -kv[1]["total_ms"]):
        lines.append(
            f"  {name:<20}{row['count']:>7}{row['total_ms']:>12.3f}"
            f"{row['self_ms']:>12.3f}"
            + (f"{row['ms_per_step']:>10.3f}" if steps else ""))
    return lines


class capture:
    """``jax.profiler`` capture around a step window, with automatic
    post-capture analysis.

    Usage (the bench/example drivers' ``--profile`` flag)::

        with obs.trace.capture(logdir, label="preheat-256^3") as cap:
            for _ in range(profile_steps):
                state = step(state)
            jax.block_until_ready(state)
        cap.summary      # per-scope table, or None if no trace appeared

    The underlying Perfetto file stays in ``logdir`` for interactive
    inspection (``ui.perfetto.dev``); the extracted per-scope durations
    additionally land in the run-event log, where
    :class:`pystella_tpu.obs.ledger.PerfLedger` picks them up.

    The capture also records the program's host spans
    (:func:`pystella_tpu.obs.scope.recording`) for the window and puts
    their table into the summary as ``host_spans``: per span the count,
    total, self and (with ``steps``, the steps the window advances)
    per-step milliseconds, and ``host_syncs_per_step``. There is one
    recorder at a time: a capture inside someone else's ``recording()``
    raises on entry.
    """

    def __init__(self, logdir, scopes=None, label="", step=None,
                 log=None, steps=None):
        self.logdir = str(logdir)
        self.scopes = scopes
        self.label = label
        self.step = step
        self.log = log
        self.steps = steps
        self.summary = None
        self._recording = _scope.recording()
        self._rows = None

    def __enter__(self):
        import jax
        os.makedirs(self.logdir, exist_ok=True)
        self._rows = self._recording.__enter__()
        try:
            jax.profiler.start_trace(self.logdir)
        except BaseException:
            self._recording.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
        self._recording.__exit__(None, None, None)
        try:
            jax.profiler.stop_trace()
        except Exception:
            # a failed stop must not mask the body's exception (or kill
            # a healthy run); there is simply no trace to analyze
            return False
        if exc_type is None:
            self.summary = summarize_trace(
                self.logdir, self.scopes, label=self.label,
                step=self.step, log=self.log,
                host_spans=_scope.span_table(self._rows, self.steps))
        return False
