"""Unified telemetry: run events, metrics, trace scopes, memory reports.

One subsystem behind the pieces that grew up scattered (``utils/monitor``,
``utils/profiling``, hand-rolled prints):

- :mod:`pystella_tpu.obs.events` — a structured JSONL run-event log
  (wall + monotonic timestamps, host id, step, event kind, payload) that
  drivers, :class:`~pystella_tpu.HealthMonitor`, checkpointing and the
  multigrid driver all emit through. Outage and
  contamination forensics become ``grep``s over one file instead of
  archaeology on interleaved stderr.
- :mod:`pystella_tpu.obs.metrics` — a lightweight registry of counters /
  gauges / timers (steps taken, halo exchanges, V-cycles, compile
  events, ms/step EMA, site-updates/s) with a multihost-aware
  :meth:`~pystella_tpu.obs.metrics.MetricsRegistry.aggregate` so host 0
  reports fleet-wide numbers.
- :mod:`pystella_tpu.obs.scope` — names for traces: ``trace_scope``
  (``jax.named_scope``, plus a host annotation when eager) threaded
  through the hot paths so device rows say which layer they are (RK
  stages, halo exchanges, stencil kernels by kind, multigrid
  smoothers), and ``host_span`` at every dispatch and fetch of the main
  path, recorded while ``recording()`` is active.
- :mod:`pystella_tpu.obs.memory` — compile-time and HBM
  instrumentation: per-computation compile seconds and
  ``memory_analysis()`` byte counts recorded into the event log, plus
  live device-memory reports (the evidence that catches an HBM
  overshoot *before* Mosaic or the allocator rejects it).

The PERF EVIDENCE PIPELINE (PR 2) sits on top — emission above, analysis
below, so a throughput claim is a distribution with provenance instead
of one wall-clock number:

- :mod:`pystella_tpu.obs.trace` — ``jax.profiler`` capture around a
  step window plus a stdlib Perfetto-trace parser that recovers
  per-scope durations for the names ``obs.scope`` threaded through the
  hot paths, emitted as ``trace_summary`` events.
- :mod:`pystella_tpu.obs.ledger` — :class:`~pystella_tpu.obs.ledger.
  PerfLedger` ingests the event log + metrics registry into
  ``bench_results/perf_report.json`` / ``.md``: step-time percentiles
  and MAD, per-scope breakdown, site-updates/s, roofline fraction, and
  an environment fingerprint.
- :mod:`pystella_tpu.obs.gate` — the noise-aware regression gate CLI
  (``python -m pystella_tpu.obs.gate``): ``median +- k*MAD`` comparison
  plus a contamination detector; exits nonzero on regression or invalid
  evidence so CI can consume it.

The NUMERICS OBSERVABILITY layer (PR 4) makes the *physics* of a run as
observable as its performance — always-on, with no host sync on the
step critical path:

- :mod:`pystella_tpu.obs.sentinel` — a compact per-step health vector
  (per-field finite/max-abs/rms plus model invariants: energy
  components, Friedmann-constraint residual) computed *inside* the
  compiled step, consumed asynchronously by a
  :class:`~pystella_tpu.obs.sentinel.SentinelMonitor` that only ever
  blocks on vectors already ``every`` steps behind the driver.
- :mod:`pystella_tpu.obs.forensics` — on a tripped sentinel, a
  forensic bundle: last-K health vectors, per-field blowup curves, the
  event-log tail, config/env fingerprint, and the last-good-checkpoint
  pointer.
- the ledger gains a ``numerics`` report section (invariant drift
  slopes, sentinel overhead) and the gate fails CI on a
  constraint-drift regression exactly like a step-time regression.

See ``doc/observability.md`` for the event schema and driver recipes.
"""

from pystella_tpu.obs.events import (
    EventLog, configure, emit, get_log, read_events, register_event_kind,
    registered_event_kinds)
from pystella_tpu.obs.metrics import (
    Counter, Gauge, MetricsRegistry, Timer, counter, gauge, registry, timer)
from pystella_tpu.obs.scope import (
    has_scope, host_span, lowered_scopes, recording, register_scope,
    registered_scopes, trace_scope)
from pystella_tpu.obs.memory import (
    CompileRecord, compile_totals,
    compile_watch, compile_with_report, device_memory_report,
    device_memory_stats, ensure_compilation_cache,
    environment_fingerprint, instrument_jit, program_fingerprint,
    runtime_versions, signature_fingerprint)
# obs.gate and obs.warmstart are deliberately NOT imported here: their
# primary entry points are ``python -m pystella_tpu.obs.gate`` /
# ``... .obs.warmstart``, and runpy warns when the module is already in
# sys.modules at -m execution time. Import them explicitly (``from
# pystella_tpu.obs import gate, warmstart``) for programmatic use.
from pystella_tpu.obs import forensics, ledger, sentinel, trace
from pystella_tpu.obs.ledger import PerfLedger
from pystella_tpu.obs.trace import scope_durations, summarize_trace
from pystella_tpu.obs.sentinel import (
    Sentinel, SentinelMonitor, SimulationDiverged)
from pystella_tpu.obs.forensics import ForensicSink, load_bundle, write_bundle

__all__ = [
    "EventLog", "configure", "emit", "get_log", "read_events",
    "register_event_kind", "registered_event_kinds",
    "Counter", "Gauge", "Timer", "MetricsRegistry",
    "counter", "gauge", "timer", "registry",
    "trace_scope", "host_span", "recording", "lowered_scopes",
    "has_scope",
    "register_scope", "registered_scopes",
    "CompileRecord", "compile_with_report", "compile_watch",
    "compile_totals", "instrument_jit", "ensure_compilation_cache",
    "program_fingerprint", "signature_fingerprint", "runtime_versions",
    "device_memory_report", "device_memory_stats",
    "trace", "ledger", "sentinel", "forensics",
    "PerfLedger", "environment_fingerprint",
    "scope_durations", "summarize_trace",
    "Sentinel", "SentinelMonitor", "SimulationDiverged",
    "ForensicSink", "load_bundle", "write_bundle",
]
