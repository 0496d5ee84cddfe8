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

The REQUEST TRACING layer (PR 13) makes the scenario service's latency
causal, not just measured: event schema v2 carries
``trace``/``span``/``parent`` fields through an ambient
:func:`~pystella_tpu.obs.events.tracing` context, and
:mod:`pystella_tpu.obs.spans` (``python -m pystella_tpu.obs.spans``)
reassembles them into per-request span trees — critical-path phase
decomposition, the deadline-miss ledger, and a Perfetto-loadable
service timeline sharing the hardware traces' scope vocabulary. The
ledger's ``latency`` section and the gate's deadline-miss SLO consume
it; :func:`~pystella_tpu.obs.events.registered_event_kinds` is the
central emit vocabulary the source lint audits.

The LIVE OPERATIONS PLANE (PR 14) is the other half of the
production-telemetry split — everything above is post-hoc, while a
persistent service needs scrape-time truth:

- :mod:`pystella_tpu.obs.live` — an opt-in stdlib ``http.server``
  endpoint on a daemon thread (``PYSTELLA_LIVE_PORT``, 0 = off):
  ``/metrics`` Prometheus exposition of the metrics registry plus the
  scenario service's live gauges (queue depth per class/tenant, active
  leases, warm-pool fingerprint health, last-chunk member-steps/s),
  ``/healthz`` liveness+readiness from the serve loop and supervisor
  state, ``/slo`` the current burn-rate state.
- :mod:`pystella_tpu.obs.slo` — a rolling-window SLO monitor fed by the
  :meth:`EventLog.subscribe <pystella_tpu.obs.events.EventLog.
  subscribe>` in-process push hook (not log tailing): queue-p95, warm
  TTFS, deadline-miss rate, and incident rate as fast/slow multi-window
  burn rates against the SAME factor+floor bars the gate uses, emitting
  ``slo_alert``/``slo_resolved`` events so live alerts become
  gate-visible evidence — the ledger's ``alerts`` section counts them
  and the gate refuses an unresolved burn alert beside a green post-hoc
  SLO section.

The CONTINUOUS-PERFORMANCE PLANE (PR 17) watches for the regression
nobody pages on — performance *drift*:

- :mod:`pystella_tpu.obs.perf` — per-program-signature rolling
  step-time quantile digests (p50/p95/p99, count-vector mergeable
  across hosts) fed by every :class:`~pystella_tpu.utils.profiling.
  StepTimer` tick and the scenario service's dispatch loop; a robust
  CUSUM change-point detector emitting ``perf_anomaly`` /
  ``perf_recovered`` (routed into the SLO monitor's
  ``perf_regression`` burn leg); and an anomaly-triggered, rate-limited
  ``jax.profiler`` flight recorder whose Perfetto artifacts land as
  ``perf_capture`` events — the evidence is captured while the
  regression is live, not after an operator notices.
- :mod:`pystella_tpu.obs.stragglers` — cross-host step-time skew
  attribution naming the slowest host in every anomaly payload.
- the ledger gains a ``perf`` report section (anomaly rollup, digest
  summaries, linked captures) and the gate refuses a report whose
  unresolved ``perf_anomaly`` sits beside a green step-time verdict.

See ``doc/observability.md`` for the event schema and driver recipes.
"""

from pystella_tpu.obs.events import (
    EventLog, configure, current_trace, emit, get_log, new_span_id,
    new_trace_id, read_events, register_event_kind,
    registered_event_kinds, tracing)
from pystella_tpu.obs.metrics import (
    Counter, Gauge, MetricsRegistry, Timer, counter, gauge, registry, timer)
from pystella_tpu.obs.scope import (
    has_scope, host_span, lowered_scopes, recording, register_scope,
    registered_scopes, trace_scope)
from pystella_tpu.obs.memory import (
    CompileRecord, compile_totals,
    compile_watch, compile_with_report, device_memory_report,
    device_memory_stats, ensure_compilation_cache, instrument_jit,
    program_fingerprint, runtime_versions,
    signature_fingerprint)
# obs.gate, obs.warmstart, and obs.spans are deliberately NOT imported
# here: their primary entry points are ``python -m pystella_tpu.obs.gate``
# / ``... .obs.warmstart`` / ``... .obs.spans``, and runpy warns when
# the module is already in sys.modules at -m execution time. Import
# them explicitly (``from pystella_tpu.obs import gate, spans,
# warmstart``) for programmatic use.
from pystella_tpu.obs import forensics, ledger, perf, sentinel, stragglers, trace
from pystella_tpu.obs.ledger import PerfLedger, environment_fingerprint
from pystella_tpu.obs.perf import (
    CusumDetector, Digest, FlightRecorder, PerfMonitor)
from pystella_tpu.obs.trace import scope_durations, summarize_trace
from pystella_tpu.obs.sentinel import (
    Sentinel, SentinelMonitor, SimulationDiverged)
from pystella_tpu.obs.forensics import ForensicSink, load_bundle, write_bundle

__all__ = [
    "EventLog", "configure", "current_trace", "emit", "get_log",
    "new_span_id", "new_trace_id", "read_events",
    "register_event_kind", "registered_event_kinds", "tracing",
    "Counter", "Gauge", "Timer", "MetricsRegistry",
    "counter", "gauge", "timer", "registry",
    "trace_scope", "host_span", "recording", "lowered_scopes",
    "has_scope",
    "register_scope", "registered_scopes",
    "CompileRecord", "compile_with_report", "compile_watch",
    "compile_totals", "instrument_jit", "ensure_compilation_cache",
    "program_fingerprint", "signature_fingerprint", "runtime_versions",
    "device_memory_report", "device_memory_stats",
    "trace", "ledger", "sentinel", "forensics", "perf", "stragglers",
    "PerfLedger", "environment_fingerprint",
    "CusumDetector", "Digest", "FlightRecorder", "PerfMonitor",
    "scope_durations", "summarize_trace",
    "Sentinel", "SentinelMonitor", "SimulationDiverged",
    "ForensicSink", "load_bundle", "write_bundle",
]
