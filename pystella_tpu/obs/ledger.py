"""PerfLedger: run events + metrics -> a defensible perf report.

Round 5's verdict was that the headline throughput claim rested on
"zero valid measurements": single wall-clock numbers, no noise model,
no environment provenance, one contaminated run flagged by hand. The
ledger is the analysis layer that turns the PR-1 telemetry (the JSONL
run-event log plus the metrics registry) into evidence the way the
stencil-compiler literature justifies results — distributions and
roofline fractions, not a lone number:

- **step-time distribution** — per-step wall-time samples (from
  ``step_time`` events, falling back to ``step_timer`` window reports)
  summarized as percentiles, mean, and MAD (median absolute deviation —
  the robust noise scale the regression gate's ``median +- k*MAD``
  comparison needs);
- **per-scope breakdown** — the latest ``trace_summary`` event's
  per-scope duration table (:mod:`pystella_tpu.obs.trace`);
- **derived throughput** — site-updates/s from the lattice volume in
  the run-metadata event and the median step time;
- **roofline fraction** — bytes moved per step from the step
  executable's ``compile`` event (XLA ``memory_analysis()`` argument +
  output bytes, a traffic lower bound) over the step time, against the
  device's peak HBM bandwidth;
- **environment fingerprint** — jax/jaxlib versions, device kind and
  count, process count, mesh shape, hostname: the provenance that makes
  two reports comparable at all.

``PerfLedger.write(dir)`` produces ``perf_report.json`` (schema below,
consumed by :mod:`pystella_tpu.obs.gate`) and a human ``perf_report.md``.
The module body never requires jax at runtime — versions come from
package metadata and device fields degrade to ``None`` when no jax is
loaded (importing it as ``pystella_tpu.obs.ledger`` still pulls jax via
the package ``__init__``; a jax-free supervisor should load it by
file).
"""

from __future__ import annotations

import json
import os
import platform as _platform
import socket
import sys
import time

from pystella_tpu.obs import events as _events

__all__ = ["REPORT_SCHEMA_VERSION", "PerfLedger", "environment_fingerprint",
           "mad", "percentile", "step_stats"]

REPORT_SCHEMA_VERSION = 1

#: peak HBM bandwidth per device generation, GB/s (vendor figures; keys
#: are matched as substrings of ``device_kind``, longest first). Used
#: for the roofline denominator; unknown kinds (CPU included) yield a
#: ``None`` fraction rather than a made-up one.
HBM_PEAK_GBPS = {
    "TPU v2": 700.0,
    "TPU v3": 900.0,
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}

#: cap on raw samples persisted into the report: enough for the gate's
#: contamination detector to see bursts, small enough to keep reports
#: reviewable in a diff
MAX_SAMPLES = 4096


def _version_of(dist):
    try:
        from importlib.metadata import version
        return version(dist)
    except Exception:
        return None


def runtime_versions():
    """The jax/jaxlib/libtpu version triple — the compiler stack that
    keys both perf-report comparability (this module's environment
    fingerprint) and cached/AOT program staleness
    (``obs.memory`` bakes it into every program fingerprint, via this
    one definition so the two can never diverge). Stdlib-only:
    resolved from installed-distribution metadata, no jax import."""
    return {
        "jax": _version_of("jax"),
        "jaxlib": _version_of("jaxlib"),
        # a libtpu bump changes the generated code: cached/AOT programs
        # keyed without it would silently serve stale executables
        "libtpu": _version_of("libtpu") or _version_of("libtpu-nightly"),
    }


#: env-var name substrings that make an XLA/libtpu flag relevant to the
#: fingerprint: async-collective and latency-hiding-scheduler toggles
#: change what a step-time comparison means (the overlapped halo path
#: depends on them to pay off). Kept in sync with
#: ``pystella_tpu.parallel.overlap`` — duplicated here because this
#: module must stay loadable BY FILE in a jax-free supervisor, where
#: the package import (and thus jax) is unavailable.
_FLAG_MARKERS = ("async_collective", "async_all_gather",
                 "latency_hiding", "scheduler")


def xla_flag_fingerprint():
    """The scheduler-relevant flags in this process's environment
    (``XLA_FLAGS`` + ``LIBTPU_INIT_ARGS``), as ``{name: value}``, plus
    the ``PYSTELLA_HALO_OVERLAP`` policy setting when present —
    stdlib-only, embedded in every report's environment fingerprint so
    the gate can warn when two reports differ only in flags."""
    flags = {}
    for var in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"):
        # direct reads: this module stays loadable by file, jax- and
        # package-free  # env-registry: XLA_FLAGS, LIBTPU_INIT_ARGS
        for tok in os.environ.get(var, "").split():
            name, _, value = tok.lstrip("-").partition("=")
            if any(m in name for m in _FLAG_MARKERS):
                flags[name] = value if value else "true"
    setting = os.environ.get(
        "PYSTELLA_HALO_OVERLAP")  # env-registry: PYSTELLA_HALO_OVERLAP
    if setting is not None:
        flags["PYSTELLA_HALO_OVERLAP"] = setting
    return flags


def environment_fingerprint():
    """Everything needed to decide whether two perf reports are
    comparable. Resolved from an already-imported jax only (the module
    must stay importable in a jax-free supervisor); device fields
    are ``None`` when jax is not loaded."""
    env = {
        "python": _platform.python_version(),
        **runtime_versions(),
        "hostname": socket.gethostname(),
        "platform": None,
        "device_kind": None,
        "num_devices": None,
        "num_processes": None,
        "xla_flags": xla_flag_fingerprint(),
    }
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            devs = jax.devices()
            env["platform"] = devs[0].platform
            env["device_kind"] = devs[0].device_kind
            env["num_devices"] = len(devs)
            env["num_processes"] = int(jax.process_count())
        except Exception:
            pass
    return env


def percentile(sorted_xs, q):
    """Linear-interpolation percentile of an already-sorted list
    (``q`` in [0, 100])."""
    if not sorted_xs:
        return None
    if len(sorted_xs) == 1:
        return float(sorted_xs[0])
    pos = q / 100.0 * (len(sorted_xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_xs) - 1)
    frac = pos - lo
    return float(sorted_xs[lo] * (1 - frac) + sorted_xs[hi] * frac)


def mad(xs):
    """Median absolute deviation — the robust noise scale. (Multiply by
    1.4826 for a Gaussian-consistent sigma; the gate does.)"""
    if not xs:
        return None
    s = sorted(xs)
    med = percentile(s, 50)
    return percentile(sorted(abs(x - med) for x in s), 50)


def step_stats(samples_ms):
    """Distribution summary of per-step wall times (ms)."""
    if not samples_ms:
        return {"count": 0}
    s = sorted(samples_ms)
    return {
        "count": len(s),
        "mean_ms": sum(s) / len(s),
        "min_ms": s[0],
        "max_ms": s[-1],
        "p10_ms": percentile(s, 10),
        "p50_ms": percentile(s, 50),
        "p90_ms": percentile(s, 90),
        "p99_ms": percentile(s, 99),
        "mad_ms": mad(s),
    }


def _peak_gbps(device_kind):
    if not device_kind:
        return None
    for key in sorted(HBM_PEAK_GBPS, key=len, reverse=True):
        if key in device_kind:
            return HBM_PEAK_GBPS[key]
    return None


class PerfLedger:
    """Aggregates one run's telemetry into a perf report.

    Build with :meth:`from_events` (the normal path: ingest a
    ``run_events.jsonl`` plus the live metrics registry), or construct
    directly and feed :meth:`add_step_ms` / attributes for synthetic
    ledgers in tests.
    """

    def __init__(self, label="", sites=None, env=None):
        self.label = label
        self.sites = sites              # lattice sites updated per step
        self.env = env or environment_fingerprint()
        self.samples_ms = []            # per-step wall times
        self.scopes = {}                # trace-derived per-scope table
        self.trace_file = None
        self.bytes_per_step = None      # HBM traffic lower bound
        self.halo_bytes_per_step = None  # ICI bytes per overlapped call
        self.compile_records = []       # compile-event payloads
        self.metrics = {}               # registry snapshot
        self.meta = {}                  # run-metadata event payload
        self.health_series = {}         # invariant name -> [(step, value)]
        self.health_events = 0          # health events ingested
        self.diverged = []              # sentinel trips (step, fields)
        self.forensic_bundles = []      # bundle paths written this run
        self.lint = None                # lint-event summary (see lint())
        self.donated_bytes = None       # aliased bytes in the step compile
        self.kernel_tiers = []          # kernel_tier payloads (dispatch
        #                                 record: which fused tier ran)
        self.block_choices = []         # block_choice payloads
        self.cold_start_meta = {}       # cold_start-event payload
        self.cache_info = {}            # compile_cache-event payload
        self.warmstart_loads = []       # warmstart_load payloads
        self.warmstart_mismatches = []  # warmstart_mismatch payloads
        self.ensemble_runs = []         # ensemble_done payloads
        self.ensemble_chunks_ms = []    # per-dispatch ms (ensemble_chunk)
        self.ensemble_evictions = []    # member_evicted payloads
        self.faults_injected = 0        # fault_injected events (harness)
        self.faults_detected = []       # fault_detected payloads
        self.recovery_attempts = 0      # recovery_attempt events
        self.recovery_failures = []     # recovery_failed payloads
        self.resumes = []               # run_resumed payloads
        self.degraded_events = []       # run_degraded payloads
        self.preempted_events = []      # run_preempted payloads
        self.checkpoint_counts = {}     # checkpoint_* event kind -> count
        self.durable_steps = []         # checkpoint_durable steps, in order
        self.checkpoint_barrier_s = 0.0  # summed durability-barrier waits
        self.supervisor_runs = []       # supervisor_done payloads, in order
        self.remesh_plans = []          # remesh_plan payloads, in order
        self._post_remesh_start = None  # samples_ms index at last remesh
        self.fft_runs = []              # fft_spectra payloads (driver legs)
        self.spectra_ms = []            # per-call spectra wall times
        #                                 (spectra_time events — drivers
        #                                 emit one per spectra output)
        self.service_dispatches = []    # service_dispatch payloads
        self.service_leases = []        # service_lease payloads
        self.service_admits = []        # service_admit payloads
        self.service_rejects = []       # service_reject payloads
        self.service_preemptions = 0    # service_preempted events
        self.service_results = []       # member_result payloads
        self.service_done = {}          # last service_done payload
        self.service_loadgen = {}       # last service_loadgen payload
        self.service_lease_failures = 0  # service_lease_failed events
        self.span_records = []          # raw trace/span-carrying events
        #                                 (obs schema v2) — the latency
        #                                 section's SpanAssembler input
        self.deadline_miss_events = 0   # deadline_missed events
        self.slo_events = []            # ("alert"|"resolved", ts, data)
        #                                 from the live burn-rate
        #                                 monitor (obs.slo) -> alerts()
        self.fleet_scrapes = []         # fleet_scrape payloads, in order
        self.fleet_lost = []            # fleet_replica_lost payloads
        self.fleet_slo_events = []      # ("alert"|"resolved", ts, data)
        #                                 from the fleet aggregator
        self.fleet_announces = []       # fleet_announce payloads
        self.fleet_withdraws = []       # fleet_withdraw payloads
        self.perf_events = []           # ("anomaly"|"recovered", ts,
        #                                 data) from the continuous-
        #                                 performance detector
        #                                 (obs.perf) -> perf()
        self.perf_captures = []         # perf_capture payloads (the
        #                                 flight-recorder artifacts)
        self.perf_digests = []          # perf_digest window reports
        self.capacity_footprints = []   # capacity_footprint payloads
        self.capacity_watermarks = []   # capacity_watermark samples
        self.capacity_rejects = []      # capacity_reject payloads
        self.capacity_evictions = []    # capacity_evict payloads
        self.capacity_oom = []          # capacity_oom payloads (the
        #                                 OOM forensic-bundle pointers)
        self.capacity_accounts = []     # capacity_account payloads
        self.capacity_usage = {}        # last capacity_usage payload

    # -- ingestion ---------------------------------------------------------

    def add_step_ms(self, ms):
        self.samples_ms.append(float(ms))

    @classmethod
    def from_events(cls, events_path, registry=None, label="",
                    sites=None, step_label=None):
        """Ingest a run-event JSONL file (and optionally the live
        metrics registry).

        - per-step samples: ``step_time`` events (``data.ms``); when a
          run only kept ``step_timer`` window reports, those window
          averages stand in (coarser, still gateable);
        - lattice sites: explicit ``sites`` arg, else the grid shape in
          the latest ``run_start`` event;
        - scope table: the latest ``trace_summary`` event;
        - bytes/step: the ``compile`` event labeled ``step_label`` (or
          the largest-argument one), argument + output bytes.

        :class:`~pystella_tpu.obs.events.EventLog` appends, so a reused
        log file holds several runs; ingestion is scoped to the LATEST
        run — everything from the last ``run_start`` event on — so a
        report never averages two runs' step times together (a
        regression between them would vanish into the mix).
        A log with no run-metadata event is ingested whole.
        """
        led = cls(label=label, sites=sites)
        window_ms = []
        # include_rotated: a size-rotated long-lived log (the scenario
        # service's rotate_bytes=) ingests as one continuous stream —
        # the latest-run scoping below then applies across the family
        all_events = _events.read_events(events_path,
                                         include_rotated=True)
        starts = [i for i, ev in enumerate(all_events)
                  if ev.get("kind") == "run_start"]
        if starts:
            all_events = all_events[starts[-1]:]
        for ev in all_events:
            kind = ev.get("kind")
            data = ev.get("data") or {}
            # the span stream: every record carrying schema-v2 trace
            # context feeds the latency section's SpanAssembler (raw,
            # not just data — the assembler needs ts/trace/span/parent)
            if ev.get("trace") is not None or ev.get("span") is not None:
                led.span_records.append(ev)
            if kind == "deadline_missed":
                led.deadline_miss_events += 1
            if kind == "step_time" and isinstance(
                    data.get("ms"), (int, float)):
                led.samples_ms.append(float(data["ms"]))
            elif kind == "step_timer" and isinstance(
                    data.get("ms_per_step"), (int, float)):
                window_ms.append(float(data["ms_per_step"]))
            elif kind == "trace_summary":
                led.scopes = data.get("scopes") or {}
                led.trace_file = data.get("trace_file")
            elif kind == "halo_traffic" and isinstance(
                    data.get("bytes_per_step"), (int, float)):
                # per-device ICI bytes one overlapped halo update moves
                # (drivers compute it from decomp.traced_halo_bytes())
                led.halo_bytes_per_step = float(data["bytes_per_step"])
            elif kind == "compile":
                led.compile_records.append(data)
            elif kind == "kernel_tier":
                led.kernel_tiers.append(data)
            elif kind == "block_choice":
                led.block_choices.append(data)
            elif kind == "health":
                # sentinel health vectors (obs.sentinel): the invariant
                # scalars become the numerics section's drift series
                led.health_events += 1
                for name, val in (data.get("invariants") or {}).items():
                    if isinstance(val, (int, float)):
                        led.health_series.setdefault(name, []).append(
                            (ev.get("step"), float(val)))
            elif kind == "diverged":
                led.diverged.append({"step": ev.get("step"),
                                     "fields": data.get("fields"),
                                     "offending_invariant":
                                         data.get("offending_invariant")})
            elif kind == "forensic_bundle":
                led.forensic_bundles.append(data.get("path"))
            elif kind == "lint":
                # the static-analysis verdict (pystella_tpu.lint): the
                # report's `lint` section, and the gate's refusal
                # trigger when the run's lint failed
                led.lint = data
            elif kind == "cold_start":
                # driver-emitted time-to-first-step breakdown (import /
                # build / trace / compile / first dispatch)
                led.cold_start_meta = data
            elif kind == "compile_cache":
                # persistent-compilation-cache wiring
                # (obs.memory.ensure_compilation_cache)
                led.cache_info = data
            elif kind == "warmstart_load":
                led.warmstart_loads.append(data)
            elif kind == "warmstart_mismatch":
                led.warmstart_mismatches.append(data)
            elif kind == "ensemble_done":
                # the ensemble driver's batch totals (member-steps/s,
                # occupancy, evictions) -> the `ensemble` report section
                led.ensemble_runs.append(data)
            elif kind == "ensemble_chunk" and isinstance(
                    data.get("ms"), (int, float)):
                led.ensemble_chunks_ms.append(float(data["ms"]))
            elif kind == "member_evicted":
                led.ensemble_evictions.append(
                    {"member": data.get("member"),
                     "step": ev.get("step"),
                     "scenario": data.get("scenario"),
                     "fields": data.get("fields"),
                     "params": data.get("params")})
            elif kind == "fault_injected":
                led.faults_injected += 1
            elif kind == "fault_detected":
                led.faults_detected.append(
                    {"step": ev.get("step"),
                     "kind": data.get("fault_kind"),
                     "error": data.get("error"),
                     "action": data.get("action")})
            elif kind == "recovery_attempt":
                led.recovery_attempts += 1
            elif kind == "recovery_failed":
                led.recovery_failures.append(data)
            elif kind == "run_resumed":
                led.resumes.append({"step": ev.get("step"), **data})
            elif kind == "run_degraded":
                led.degraded_events.append(
                    {"step": ev.get("step"), **data})
            elif kind == "remesh_plan":
                # the re-mesh library's decision record (resilience.
                # remesh): old/new mesh, survivors, rejected
                # candidates. Only a plan that actually CHANGED the
                # mesh marks degradation (a transport-blip recovery
                # emits changed=False and leaves the program alone);
                # steps ingested after a changed plan are the degraded
                # mesh's — the `degraded` block normalizes throughput
                # per SURVIVING chip from them.
                led.remesh_plans.append({"step": ev.get("step"), **data})
                if data.get("changed") and data.get("feasible"):
                    led._post_remesh_start = len(led.samples_ms)
            elif kind == "run_preempted":
                led.preempted_events.append(
                    {"step": ev.get("step"), **data})
            elif kind in ("checkpoint_save", "checkpoint_durable",
                          "checkpoint_fallback", "checkpoint_restore"):
                led.checkpoint_counts[kind] = \
                    led.checkpoint_counts.get(kind, 0) + 1
                if kind == "checkpoint_durable":
                    if isinstance(ev.get("step"), (int, float)):
                        led.durable_steps.append(int(ev["step"]))
                    if isinstance(data.get("wait_s"), (int, float)):
                        led.checkpoint_barrier_s += float(data["wait_s"])
            elif kind == "supervisor_done":
                led.supervisor_runs.append(data)
            elif kind == "fft_spectra":
                # a driver's sharded-spectra leg totals (scheme, grid,
                # field count, per-call ms) -> the `fft` report section
                led.fft_runs.append(data)
            elif kind == "spectra_time" and isinstance(
                    data.get("ms"), (int, float)):
                # one spectra output's wall time — emitted per output
                # step by the preheating driver (--spectra-cadence), so
                # spectra cost is a ledger-visible series, not a one-off
                # microbenchmark
                led.spectra_ms.append(float(data["ms"]))
            elif kind == "service_dispatch":
                # the scenario service's per-request dispatch record
                # (queue latency, priority class, warm/cold tag) — the
                # `service` section's queue-latency percentiles come
                # from these
                led.service_dispatches.append(data)
            elif kind == "service_lease":
                led.service_leases.append(data)
            elif kind == "service_admit":
                led.service_admits.append(data)
            elif kind == "service_reject":
                led.service_rejects.append(data)
            elif kind == "service_preempted":
                led.service_preemptions += 1
            elif kind == "service_lease_failed":
                led.service_lease_failures += 1
            elif kind == "member_result":
                led.service_results.append(data)
            elif kind == "service_done":
                led.service_done = data
            elif kind == "service_loadgen":
                led.service_loadgen = data
            elif kind == "slo_alert":
                led.slo_events.append(("alert", ev.get("ts"), data))
            elif kind == "slo_resolved":
                led.slo_events.append(("resolved", ev.get("ts"), data))
            elif kind == "fleet_scrape":
                led.fleet_scrapes.append(data)
            elif kind == "fleet_replica_lost":
                led.fleet_lost.append(data)
            elif kind == "fleet_alert":
                led.fleet_slo_events.append(("alert", ev.get("ts"),
                                             data))
            elif kind == "fleet_resolved":
                led.fleet_slo_events.append(("resolved", ev.get("ts"),
                                             data))
            elif kind == "fleet_announce":
                led.fleet_announces.append(data)
            elif kind == "fleet_withdraw":
                led.fleet_withdraws.append(data)
            elif kind == "perf_anomaly":
                led.perf_events.append(("anomaly", ev.get("ts"), data))
            elif kind == "perf_recovered":
                led.perf_events.append(("recovered", ev.get("ts"),
                                        data))
            elif kind == "perf_capture":
                led.perf_captures.append(data)
            elif kind == "perf_digest":
                led.perf_digests.append(data)
            elif kind == "capacity_footprint":
                led.capacity_footprints.append(data)
            elif kind == "capacity_watermark":
                led.capacity_watermarks.append(data)
            elif kind == "capacity_reject":
                led.capacity_rejects.append(data)
            elif kind == "capacity_evict":
                led.capacity_evictions.append(data)
            elif kind == "capacity_oom":
                led.capacity_oom.append(data)
            elif kind == "capacity_account":
                led.capacity_accounts.append(data)
            elif kind == "capacity_usage":
                led.capacity_usage = data
            elif kind == "run_start":
                led.meta = data
        if not led.samples_ms and window_ms:
            led.samples_ms = window_ms
            # window averages cannot be attributed before/after a
            # remesh (the index marker was taken against the empty
            # per-step list): drop the post-remesh split rather than
            # blending full-mesh windows into the degraded stats
            led._post_remesh_start = None
        if led.sites is None:
            shape = led.meta.get("grid_shape")
            if isinstance(shape, (list, tuple)) and shape:
                sites = 1
                for d in shape:
                    sites *= int(d)
                led.sites = sites
        led._pick_step_compile(step_label)
        if registry is not None:
            try:
                led.metrics = registry.snapshot()
            except Exception:
                led.metrics = {}
        return led

    def _pick_step_compile(self, step_label=None):
        """Bytes moved per step from the step executable's compile
        record: arguments read + outputs written is the floor on HBM
        traffic for one call. Prefers the record labeled ``step_label``;
        otherwise the one with the largest argument footprint (the step
        computation dominates any helper compiles)."""
        recs = [r for r in self.compile_records
                if isinstance(r.get("argument_bytes"), (int, float))]
        if not recs:
            return
        if step_label is not None:
            labeled = [r for r in recs if r.get("label") == step_label]
            recs = labeled or recs
        rec = max(recs, key=lambda r: r["argument_bytes"])
        out = rec.get("output_bytes")
        self.bytes_per_step = int(rec["argument_bytes"]) + int(out or 0)
        alias = rec.get("alias_bytes")
        if isinstance(alias, (int, float)):
            # donated (input->output aliased) bytes the step does NOT
            # hold twice — the realized HBM saving buffer donation buys
            # (0 on backends that drop donation, e.g. CPU)
            self.donated_bytes = int(alias)

    # -- derived quantities ------------------------------------------------

    def stats(self):
        return step_stats(self.samples_ms)

    def site_updates_per_s(self):
        st = self.stats()
        if not self.sites or not st.get("p50_ms"):
            return None
        return float(self.sites) * 1e3 / st["p50_ms"]

    def roofline(self):
        """Achieved HBM bandwidth (bytes/step over median step time)
        and its fraction of the device peak; fields are ``None`` when
        the inputs (compile bytes, step times, a known device kind) are
        missing."""
        st = self.stats()
        achieved = None
        if self.bytes_per_step and st.get("p50_ms"):
            achieved = self.bytes_per_step / (st["p50_ms"] / 1e3) / 1e9
        peak = _peak_gbps(self.env.get("device_kind"))
        frac = achieved / peak if achieved and peak else None
        return {"bytes_per_step": self.bytes_per_step,
                "achieved_gbps": achieved,
                "peak_gbps": peak,
                "fraction_of_peak": frac,
                "donated_bytes": self.donated_bytes,
                "kernel_tiers": self.kernel_tier_summary()}

    def kernel_tier_summary(self):
        """The roofline's dispatch record: which fused kernel tier each
        stepper ACTUALLY ran (``kernel_tier`` events: resident-chunk /
        streaming-chunk / pair / single / xla, with the modeled
        per-step lattice traffic — exact for the Pallas tiers, whose
        kernels read every input and write every output once), the
        chunk-vs-pair per-step HBM-traffic reduction when both tiers
        ran in the window, and where the block choices came from
        (``block_choice`` sources: explicit pins or the heuristic).
        ``None`` when the run carried no tier telemetry."""
        if not (self.kernel_tiers or self.block_choices):
            return None
        rows = {}
        for kt in self.kernel_tiers:
            key = (kt.get("label"), kt.get("entrypoint"),
                   kt.get("tier"))
            rows[key] = kt  # last emission wins per dispatch site
        tiers = [
            {k: r.get(k) for k in (
                "label", "entrypoint", "tier", "chunk_depth",
                "bytes_per_step", "kernels_per_2_steps", "local_shape")}
            for r in rows.values()]
        # measured per-step traffic reduction: the chunked stepper's
        # bytes/step against the pair-tier stepper of the same system
        # and local shape in the same window
        reduction = None
        chunk = next((r for r in tiers
                      if "chunk" in (r.get("tier") or "")), None)
        if chunk is not None:
            pair = next(
                (r for r in tiers if r.get("tier") == "pair"
                 and r.get("local_shape") == chunk.get("local_shape")
                 and r.get("label") == chunk.get("label")), None)
            cb = chunk.get("bytes_per_step")
            pb = (pair or {}).get("bytes_per_step")
            if (isinstance(cb, (int, float))
                    and isinstance(pb, (int, float)) and pb):
                reduction = {
                    "chunk_bytes_per_step": int(cb),
                    "pair_bytes_per_step": int(pb),
                    "traffic_reduction": 1.0 - cb / pb}
        sources = {}
        for bc in self.block_choices:
            src = bc.get("source") or "?"
            sources[src] = sources.get(src, 0) + 1
        return {
            "dispatched": tiers,
            "chunk_vs_pair": reduction,
            "block_choice_sources": sources,
        }

    def overlap_summary(self):
        """Exposed-vs-hidden communication time of the overlapped halo
        path, from the trace scope table: the comm denominator is the
        raw ``collective-permute`` op rows (present in device traces
        with no named-scope path; falls back to the ``halo_exchange``
        scope), the hidden share is bounded by the
        ``halo_overlap_interior`` compute that ran concurrently, and
        ``halo_overlap`` host spans count the overlapped calls in the
        window. With a ``halo_traffic`` event (per-device ICI bytes per
        overlapped call) an achieved-ICI-bandwidth estimate is derived.
        ``None`` when the trace shows no halo activity at all.

        Device rows appear once PER DEVICE in a trace, so the raw scope
        totals are fleet sums; ``comm_ms``/``interior_ms`` here are
        normalized to per-device wall time (divided by
        ``env.num_devices``), which is what the exposed-vs-hidden split
        and the per-device ICI bandwidth are about. ``halo_overlap``
        host spans are emitted once per call and are not scaled."""
        scopes = self.scopes or {}
        comm_scope = next((s for s in ("collective-permute",
                                       "halo_exchange") if s in scopes),
                          None)
        calls = scopes.get("halo_overlap")
        if comm_scope is None and calls is None:
            return None
        ndev = self.env.get("num_devices") or 1
        comm = scopes.get(comm_scope) or {}
        comm_ms = comm.get("total_ms")
        if isinstance(comm_ms, (int, float)):
            comm_ms /= ndev
        interior = scopes.get("halo_overlap_interior")
        interior_ms = interior.get("total_ms") if interior else None
        if isinstance(interior_ms, (int, float)):
            interior_ms /= ndev
        hidden = exposed = None
        if isinstance(comm_ms, (int, float)):
            # the interior compute is the only work the scheduler can
            # hide the collectives behind; without device rows for it
            # (host-span-only CPU traces) nothing is provably hidden
            hidden = min(comm_ms, interior_ms or 0.0)
            exposed = comm_ms - hidden
        n_calls = calls.get("count") if calls else None
        ici = None
        if (self.halo_bytes_per_step and n_calls
                and isinstance(comm_ms, (int, float)) and comm_ms > 0):
            ici = (self.halo_bytes_per_step * n_calls
                   / (comm_ms / 1e3) / 1e9)
        return {
            "comm_scope": comm_scope,
            "comm_ms": comm_ms,
            "interior_ms": interior_ms,
            "hidden_ms": hidden,
            "exposed_ms": exposed,
            "num_devices": ndev,
            "overlapped_calls": n_calls,
            "halo_bytes_per_step": self.halo_bytes_per_step,
            "achieved_ici_gbps": ici,
        }

    def comm(self):
        """Modeled-vs-measured communication: joins the lint event's
        static comm model (``static_comm`` — per-target per-invocation
        collective bytes the dataflow lint tier classified as halo /
        transpose / scalar from the compiled HLO) against the traffic
        the run actually measured. The halo leg pairs the
        ``smoke_overlap`` model with the ``halo_traffic`` event
        (``decomp.traced_halo_bytes()`` — the per-device ICI bytes one
        overlapped call moves, the same per-invocation unit the model
        counts); targets the run has no byte counter for stay
        model-only rows. ``covered`` is True only when at least one
        leg has BOTH sides — the gate refuses a report that claims
        coverage without a model. ``None`` when the run carried
        neither a model nor a measured counter."""
        model = (self.lint or {}).get("static_comm") or {}
        calls = (self.scopes or {}).get("halo_overlap") or {}
        measured = {}
        if self.halo_bytes_per_step:
            measured["smoke_overlap"] = {
                "bytes": float(self.halo_bytes_per_step),
                "class": "halo",
                "source": "halo_traffic",
                "calls": calls.get("count"),
            }
        if not model and not measured:
            return None
        legs = []
        for target in sorted(set(model) | set(measured)):
            block = model.get(target) or {}
            per_inv = block.get("per_invocation_bytes") or {}
            total = (block.get("total_bytes")
                     if block.get("modeled") else None)
            meas = measured.get(target)
            cls = meas["class"] if meas else (
                max(per_inv, key=per_inv.get) if per_inv else None)
            # compare like against like: a measured halo counter joins
            # the model's halo class, not the program's total (which
            # may also carry scalar all-reduces)
            modeled = per_inv.get(cls, total) if cls else total
            leg = {
                "target": target,
                "class": cls,
                "modeled_bytes": modeled,
                "modeled_total_bytes": total,
                "modeled_classes": per_inv or None,
                "measured_bytes": meas["bytes"] if meas else None,
                "measured_source": meas["source"] if meas else None,
                "calls": meas["calls"] if meas else None,
                "excess_pct": None,
                "within": None,
            }
            if meas and modeled:
                leg["excess_pct"] = round(
                    (meas["bytes"] / modeled - 1.0) * 100.0, 2)
                # 25% is the gate's default excess threshold
                # (PYSTELLA_GATE_COMM_EXCESS_PCT); recorded here so
                # the markdown can flag a leg without re-deriving it
                leg["within"] = leg["excess_pct"] <= 25.0
            legs.append(leg)
        return {
            "covered": any(leg["modeled_bytes"] and leg["measured_bytes"]
                           for leg in legs),
            "legs": legs,
            "halo_bytes_exchanged":
                self.metrics.get("halo_bytes_exchanged"),
        }

    def cold_start(self):
        """The cold-start summary: time-to-first-step breakdown (from
        the driver's ``cold_start`` event), the per-program compile
        table (from ``compile`` events — trace vs backend-compile
        seconds, fingerprint, persistent-cache attribution), cache
        wiring and hit rate, and the warm-start story (artifacts
        loaded, fingerprint mismatches). ``None`` when the run carried
        no compile telemetry at all.

        Nested instrumented dispatches each report their own row, so
        the table's per-row seconds may overlap (an outer chunk's row
        includes its inner kernels'); the headline phase numbers come
        from the driver's own breakdown, not a sum of rows."""
        if not (self.cold_start_meta or self.compile_records
                or self.cache_info or self.warmstart_loads
                or self.warmstart_mismatches):
            return None
        compiles = []
        hits = misses = 0
        for r in self.compile_records:
            h = int(r.get("cache_hits") or 0)
            m = int(r.get("cache_misses") or 0)
            hits += h
            misses += m
            compiles.append({
                "label": r.get("label"),
                "fingerprint": r.get("fingerprint"),
                "fingerprint_kind": r.get("fingerprint_kind"),
                "trace_s": float(r.get("trace_seconds") or 0.0),
                "compile_s": float(r.get("compile_seconds") or 0.0),
                "cache_hit": r.get("cache_hit"),
                "source": r.get("source"),
            })
        compiles.sort(key=lambda c: -(c["trace_s"] + c["compile_s"]))
        cache = dict(self.cold_start_meta.get("cache") or {})
        cache.setdefault("dir", self.cache_info.get("dir"))
        cache.setdefault("hits", hits)
        cache.setdefault("misses", misses)
        tot = (cache.get("hits") or 0) + (cache.get("misses") or 0)
        cache["hit_rate"] = (cache.get("hits", 0) / tot) if tot else None
        warm = self.cold_start_meta.get("warmstart") or {}
        artifacts = list(warm.get("artifacts") or [])
        seen = {(a.get("label"), a.get("fingerprint"))
                for a in artifacts}
        for w in self.warmstart_loads:
            key = (w.get("label"), w.get("fingerprint"))
            if key not in seen:
                seen.add(key)
                artifacts.append({"label": w.get("label"),
                                  "fingerprint": w.get("fingerprint"),
                                  "match": True})
        # a warmstart_mismatch event means the store REFUSED an
        # artifact and the driver took the cold jit path — an honest
        # fallback, not a warm-path claim, so it must not land in
        # `artifacts` where the gate would refuse the run as invalid
        # evidence; only driver-declared artifacts and actual loads
        # belong there
        fallbacks = [{"label": w.get("label"),
                      "fingerprint": w.get("fingerprint"),
                      "reason": w.get("reason")}
                     for w in self.warmstart_mismatches]
        warmstart = {
            "claimed": bool(warm.get("claimed",
                                     bool(self.warmstart_loads))),
            "artifacts": artifacts,
            "fallbacks": fallbacks,
        }
        return {
            "time_to_first_step_s":
                self.cold_start_meta.get("time_to_first_step_s"),
            "phases": self.cold_start_meta.get("phases") or {},
            "compiles": compiles[:64],
            "n_compile_events": len(compiles),
            "cache": cache,
            "warmstart": warmstart,
        }

    def numerics(self):
        """The numerics-observability summary (sentinel health): per
        invariant the first/last values and the least-squares
        **drift slope per step** (the quantity the gate compares — a
        silent physics regression shows up as a steeper slope), plus
        health-event counts, the sentinel's host-side overhead as a
        percentage of step time (from the ``sentinel`` and ``step``
        metrics timers), any sentinel trips, and forensic-bundle
        pointers. ``None`` when the run carried no numerics telemetry
        at all."""
        invariants = {}
        for name, series in self.health_series.items():
            vals = [v for _, v in series]
            steps = [s if isinstance(s, (int, float)) else i
                     for i, (s, _) in enumerate(series)]
            invariants[name] = {
                "n": len(vals),
                "first": vals[0],
                "last": vals[-1],
                "min": min(vals),
                "max": max(vals),
                "drift_per_step": _slope(steps, vals),
            }
        overhead = None
        step_s = self.metrics.get("step.total_s")
        sent_s = self.metrics.get("sentinel.total_s")
        if isinstance(step_s, (int, float)) and step_s > 0 \
                and isinstance(sent_s, (int, float)):
            overhead = 100.0 * sent_s / step_s
        checks = self.metrics.get("health_checks")
        if not (invariants or self.health_events or self.diverged
                or checks):
            return None
        return {
            "invariants": invariants,
            "health_events": self.health_events,
            "health_checks": checks,
            "sentinel_overhead_pct": overhead,
            "diverged": self.diverged,
            "forensic_bundles": self.forensic_bundles,
        }

    def ensemble(self):
        """The ensemble-throughput summary (:mod:`pystella_tpu.
        ensemble`): the driver's batch totals from ``ensemble_done``
        events (member-steps/s, mean batch occupancy, members
        completed), per-member throughput normalized per device
        (``member_steps_per_s_per_device`` — the packed-small-lattice
        figure of merit the TPU-window validation compares against the
        single-run headline), a chunk-dispatch time distribution from
        the ``ensemble_chunk`` events, and the eviction record (count +
        the ``member_evicted`` events naming each member, its scenario,
        and its parameter draw). ``None`` when the run carried no
        ensemble telemetry at all. Several ``ensemble_done`` events
        (one driver run per scenario group) are summed into the
        totals."""
        if not (self.ensemble_runs or self.ensemble_chunks_ms
                or self.ensemble_evictions):
            return None
        member_steps = sum(int(r.get("member_steps") or 0)
                           for r in self.ensemble_runs)
        wall_s = sum(float(r.get("wall_s") or 0.0)
                     for r in self.ensemble_runs)
        completed = sum(int(r.get("members_completed") or 0)
                        for r in self.ensemble_runs)
        rate = member_steps / wall_s if wall_s > 0 else None
        # the driver names each eviction in a member_evicted event AND
        # counts them in the ensemble_done totals; trust whichever
        # survived into the log (an event-window truncation must not
        # understate the count)
        evict_total = max(len(self.ensemble_evictions),
                          sum(int(r.get("evictions") or 0)
                              for r in self.ensemble_runs))
        ndev = self.env.get("num_devices")
        occs = [r.get("occupancy_mean") for r in self.ensemble_runs
                if isinstance(r.get("occupancy_mean"), (int, float))]
        return {
            "runs": len(self.ensemble_runs),
            "size": (self.ensemble_runs[-1].get("size")
                     if self.ensemble_runs else None),
            "member_steps": member_steps,
            "wall_s": wall_s,
            "member_steps_per_s": rate,
            "member_steps_per_s_per_device":
                (rate / ndev if rate and ndev else None),
            "occupancy_mean": (sum(occs) / len(occs) if occs else None),
            "members_completed": completed,
            "evictions": evict_total,
            "eviction_records": self.ensemble_evictions[:64],
            "chunks": step_stats(self.ensemble_chunks_ms),
        }

    def resilience(self):
        """The elastic-runtime summary (:mod:`pystella_tpu.resilience`):
        the incident table (one row per recovered fault, from
        ``run_resumed`` events with ``incident=True`` — kind, detect
        step, MTTR, steps replayed, attempts), detected-vs-claimed
        consistency against the supervisor's own ``supervisor_done``
        totals, recovery-attempt and give-up counts, the checkpoint
        record (saves scheduled vs durable, restore fallbacks, cadence
        between durable steps, summed durability-barrier seconds and
        their share of the supervised wall time), preemption/degrade
        flags, and the fault-injection count (a drill's harness
        activity is part of its evidence). ``None`` when the run
        carried no resilience telemetry at all.

        ``consistent`` is the gate's refusal trigger: a report whose
        supervisors CLAIM fewer incidents than the event log's
        RESOLVED (``run_resumed``) count is hiding a degraded fleet
        behind a clean headline. Detected-but-unresolved incidents (a
        run that died mid-recovery never wrote a ``supervisor_done``
        and could not claim its fault) land in ``unresolved`` instead
        — the gate warns on those, honestly."""
        # checkpoint events alone do NOT make a resilience section: any
        # plain Checkpointer-using driver emits them, and a section for
        # every such run would make the gate's lost-resilience-coverage
        # warning fire on runs that were never supervised — noise that
        # trains operators to ignore the real warning. The section
        # requires actual supervisor/fault telemetry; the checkpoint
        # record then rides inside it.
        if not (self.faults_detected or self.faults_injected
                or self.resumes or self.recovery_failures
                or self.preempted_events or self.supervisor_runs
                or self.remesh_plans):
            return None
        incidents = [
            {"kind": r.get("fault_kind"),
             "detected_at_step": r.get("from_step"),
             "restored_step": r.get("step"),
             "mttr_s": r.get("mttr_s"),
             "steps_replayed": r.get("steps_replayed"),
             "attempts": r.get("attempts")}
            for r in self.resumes if r.get("incident")]
        detected = len([f for f in self.faults_detected
                        if f.get("action") != "reraise"])
        mttrs = [i["mttr_s"] for i in incidents
                 if isinstance(i.get("mttr_s"), (int, float))]
        replayed = sum(int(i.get("steps_replayed") or 0)
                       for i in incidents)
        # several supervised runs can share one ingestion window (a
        # preempted run + its resumed successor, an ensemble beside a
        # main run): the CLAIM the gate audits is their SUM — keeping
        # only the last run's count would flag an honest multi-run log
        # as inconsistent
        claims = [r.get("incidents") for r in self.supervisor_runs
                  if isinstance(r.get("incidents"), int)]
        claimed = sum(claims) if claims else None
        cadence = None
        if len(self.durable_steps) >= 2:
            deltas = [b - a for a, b in zip(self.durable_steps,
                                            self.durable_steps[1:])
                      if b > a]
            if deltas:
                cadence = percentile(sorted(deltas), 50)
        walls = [r.get("wall_s") for r in self.supervisor_runs
                 if isinstance(r.get("wall_s"), (int, float))]
        wall_s = sum(walls) if walls else None
        overhead_pct = None
        if isinstance(wall_s, (int, float)) and wall_s > 0:
            overhead_pct = 100.0 * self.checkpoint_barrier_s / wall_s
        return {
            "incidents": incidents,
            "n_incidents": detected,
            "resolved": len(incidents),
            "unresolved": max(0, detected - len(incidents)),
            "claimed_incidents": claimed,
            # the claim is audited against RESOLVED incidents (each
            # run_resumed row), not raw detections: a run that died
            # mid-recovery never wrote a supervisor_done and could not
            # claim its fault — that is the honest `unresolved` path
            # (the gate warns), not a lie about recovered ones
            "consistent": (claimed is None
                           or int(claimed) >= len(incidents)),
            # completed = every supervised run in the window either
            # finished or handed off cleanly (a preemption drain is a
            # clean hand-off, not a death mid-recovery)
            "completed": (all(r.get("completed") or r.get("preempted")
                              for r in self.supervisor_runs)
                          if self.supervisor_runs else None),
            "mttr_s_mean": (sum(mttrs) / len(mttrs) if mttrs else None),
            "mttr_s_max": (max(mttrs) if mttrs else None),
            "steps_replayed": replayed,
            "recovery_attempts": self.recovery_attempts,
            "recovery_failures": self.recovery_failures[:8],
            "faults_injected": self.faults_injected,
            "preempted": bool(self.preempted_events),
            "degraded": self.degraded_block(),
            "checkpoints": {
                "saved": self.checkpoint_counts.get(
                    "checkpoint_save", 0),
                "durable": self.checkpoint_counts.get(
                    "checkpoint_durable", 0),
                "fallbacks": self.checkpoint_counts.get(
                    "checkpoint_fallback", 0),
                "restores": self.checkpoint_counts.get(
                    "checkpoint_restore", 0),
                "cadence_steps": cadence,
                "barrier_s": self.checkpoint_barrier_s,
                "barrier_pct_of_wall": overhead_pct,
            },
        }

    def degraded_block(self):
        """The degraded-mode accounting inside the ``resilience``
        section (``None`` when the run never degraded): the
        ``run_degraded`` notes, the ``remesh_plan`` decision records
        (:mod:`pystella_tpu.resilience.remesh` — old -> new mesh,
        survivors, rejected candidates), and the post-remesh
        throughput normalized per **surviving** chip — the only honest
        per-chip figure for a window that finished on fewer devices
        than it started with. The gate refuses a degraded report whose
        throughput section still normalizes by the full pre-loss mesh
        (:func:`pystella_tpu.obs.gate.compare_reports`)."""
        plan = self._degrading_plan()
        if not (self.degraded_events or plan is not None):
            # blip-only remesh_plan records (changed=False: every old
            # device survived, nothing was swapped) do NOT make a
            # degraded block — the window never degraded
            return None
        block = {"events": self.degraded_events[:8],
                 "remesh_plans": self.remesh_plans[:4]}
        if plan is not None:
            used = plan.get("devices") or plan.get("survivors") or []
            block.update({
                "old_mesh": plan.get("old_proc_shape"),
                "new_mesh": plan.get("new_proc_shape"),
                "surviving_devices": (len(plan.get("survivors"))
                                      if isinstance(plan.get("survivors"),
                                                    list) else None),
                "devices_used": len(used) if isinstance(used, list)
                else None,
                "lost_devices": (len(plan.get("lost"))
                                 if isinstance(plan.get("lost"), list)
                                 else None),
            })
            post = (self.samples_ms[self._post_remesh_start:]
                    if self._post_remesh_start is not None else [])
            post_block = None
            if post:
                stats = step_stats(post)
                per_chip = None
                if self.sites and stats.get("p50_ms") and used:
                    per_chip = (float(self.sites) * 1e3
                                / stats["p50_ms"] / len(used))
                post_block = {
                    "samples": len(post),
                    "p50_ms": stats.get("p50_ms"),
                    "site_updates_per_s_per_surviving_chip": per_chip,
                }
            block["post_remesh"] = post_block
        return block

    def fft(self):
        """The distributed-spectral-tier summary
        (:mod:`pystella_tpu.fourier.pencil`): per-call spectra wall
        times (``spectra_time`` events — the preheating driver emits
        one per spectra output, a bench leg several per run) folded
        with the driver's ``fft_spectra`` leg metadata (scheme, grid,
        field count); a ``5 N log₂ N``-per-field flops model over the
        median call time (achieved GFLOP/s, and — since distributed
        FFTs are HBM-bandwidth bound — a traffic model of the three
        local stages against the device's peak HBM bandwidth, the
        roofline fraction); and the per-stage scope rows
        (``fft_stage`` / ``fft_transpose``) with the transposes'
        exposed-vs-hidden split, derived exactly like the halo
        overlap's (hidden is bounded by the stage compute available to
        run concurrently; device rows are fleet sums, normalized
        per-device). ``None`` when the run carried no spectral
        telemetry at all."""
        scopes = self.scopes or {}
        # prefer the named-scope rows (TPU device traces carry the
        # scope path); fall back to the raw op rows (`fft.N` /
        # `all-to-all.N`), which CPU device traces carry instead
        stage = scopes.get("fft_stage") or scopes.get("fft")
        transpose = (scopes.get("fft_transpose")
                     or scopes.get("all-to-all"))
        samples = list(self.spectra_ms)
        if not samples:
            samples = [float(r["ms_per_call"]) for r in self.fft_runs
                       if isinstance(r.get("ms_per_call"), (int, float))]
        if not (self.fft_runs or samples or stage or transpose):
            return None
        meta = self.fft_runs[-1] if self.fft_runs else {}
        stats = step_stats(samples)

        model = None
        shape = meta.get("grid_shape")
        if isinstance(shape, (list, tuple)) and shape:
            import math
            ntot = 1
            for d in shape:
                ntot *= int(d)
            nfields = int(meta.get("nfields") or 1)
            # r2c forward per field: the standard 5 N log2 N real-FFT
            # flops model (the roofline numerator the ISSUE pins)
            flops = nfields * 5 * ntot * math.log2(max(ntot, 2))
            # traffic floor: each of the 3 local FFT stages reads and
            # writes the complex field once per field (transposes move
            # the same bytes again over the interconnect, not HBM).
            # The complex array is the r2c HALF spectrum — sizing the
            # full grid would overstate the roofline fraction ~2x, the
            # same accounting error the DFT replicate limit fixed
            kelems = ntot
            if meta.get("real", True) and len(shape) == 3:
                kelems = (int(shape[0]) * int(shape[1])
                          * (int(shape[2]) // 2 + 1))
            itemsize = int(meta.get("complex_itemsize") or 8)
            traffic = nfields * 3 * 2 * kelems * itemsize
            model = {"grid_shape": list(shape), "nfields": nfields,
                     "model_flops": flops,
                     "model_bytes": traffic,
                     "achieved_gflops": None,
                     "achieved_gbps": None,
                     "peak_gbps": _peak_gbps(self.env.get("device_kind")),
                     "fraction_of_peak": None}
            p50 = stats.get("p50_ms")
            if isinstance(p50, (int, float)) and p50 > 0:
                model["achieved_gflops"] = flops / (p50 / 1e3) / 1e9
                model["achieved_gbps"] = traffic / (p50 / 1e3) / 1e9
                if model["peak_gbps"]:
                    model["fraction_of_peak"] = (
                        model["achieved_gbps"] / model["peak_gbps"])

        ndev = self.env.get("num_devices") or 1

        def _row(scope_row):
            if not scope_row:
                return None
            out = dict(scope_row)
            if isinstance(out.get("total_ms"), (int, float)):
                out["total_ms_per_device"] = out["total_ms"] / ndev
            return out

        stage_row = _row(stage)
        transpose_row = _row(transpose)
        hidden = exposed = None
        if transpose_row and isinstance(
                transpose_row.get("total_ms_per_device"), (int, float)):
            t_ms = transpose_row["total_ms_per_device"]
            s_ms = (stage_row or {}).get("total_ms_per_device") or 0.0
            hidden = min(t_ms, s_ms)
            exposed = t_ms - hidden
        return {
            "scheme": meta.get("scheme"),
            "calls": len(samples) or None,
            "ms": stats,
            "runs": self.fft_runs[:16],
            "model": model,
            "stages": {"fft_stage": stage_row,
                       "fft_transpose": transpose_row},
            "transpose_hidden_ms": hidden,
            "transpose_exposed_ms": exposed,
            "num_devices": ndev,
        }

    def service(self):
        """The scenario-service summary (:mod:`pystella_tpu.service`):
        queue-latency percentiles per priority class (from the
        per-request ``service_dispatch`` records), time-to-first-step
        split warm/cold (from the lease records — the cold side pays
        the build+compile, the warm side must stay pure dispatch),
        tenant occupancy shares, preemption counts plus
        work-lost-to-replay, rejection/eviction accounting, and the
        warm-admission evidence the gate audits: every warm admission's
        fingerprint status and the warm leases' backend-compile count
        from the compile ledger (a warm lease that compiled broke the
        dispatch-never-compile contract). ``None`` when the run carried
        no service telemetry at all."""
        if not (self.service_dispatches or self.service_leases
                or self.service_admits or self.service_rejects
                or self.service_results or self.service_done):
            return None
        by_class = {}
        qlats = []
        for d in self.service_dispatches:
            q = d.get("queue_latency_s")
            if not isinstance(q, (int, float)):
                continue
            qlats.append(float(q))
            by_class.setdefault(str(d.get("priority")), []).append(
                float(q))
        ttfs = {"warm": [], "cold": []}
        for rec in self.service_leases:
            t = rec.get("ttfs_s")
            if isinstance(t, (int, float)):
                ttfs["warm" if rec.get("warm") else "cold"].append(
                    float(t))
        warm_admissions = [
            {"id": a.get("id"), "fingerprint": a.get("fingerprint"),
             "fingerprint_ok": a.get("fingerprint_ok")}
            for a in self.service_admits if a.get("warm")]
        warm_leases = [r for r in self.service_leases if r.get("warm")]
        warm_compiles = sum(int(r.get("backend_compiles") or 0)
                            for r in warm_leases)
        rejects = {}
        for r in self.service_rejects:
            reason = str(r.get("reason"))
            rejects[reason] = rejects.get(reason, 0) + 1
        statuses = {}
        for r in self.service_results:
            s = str(r.get("status"))
            statuses[s] = statuses.get(s, 0) + 1
        tenant_steps = dict(self.service_done.get("tenant_steps") or {})
        if not tenant_steps:
            for rec in self.service_leases:
                for tenant, steps in (rec.get("tenant_steps")
                                      or {}).items():
                    tenant_steps[tenant] = (tenant_steps.get(tenant, 0)
                                            + int(steps))
        total_steps = sum(tenant_steps.values())
        replayed = self.service_done.get("replayed_member_steps")
        if replayed is None:
            replayed = sum(int(r.get("replayed_member_steps") or 0)
                           for r in self.service_leases)
        out = {
            "requests": len({d.get("id")
                             for d in self.service_dispatches}),
            "admitted": len(self.service_admits),
            "results": statuses,
            "completed": statuses.get("completed", 0),
            "diverged": statuses.get("diverged", 0),
            "rejected": rejects,
            "queue_latency_s": {
                "overall": _lat_stats(qlats),
                "by_priority": {cls: _lat_stats(v)
                                for cls, v in sorted(by_class.items())},
            },
            "ttfs_s": {"warm": _lat_stats(ttfs["warm"]),
                       "cold": _lat_stats(ttfs["cold"])},
            "warm_claimed": bool(warm_admissions),
            "warm_admissions": warm_admissions[:64],
            "warm_leases": len(warm_leases),
            "warm_lease_backend_compiles": warm_compiles,
            "leases": len(self.service_leases),
            "lease_failures": self.service_lease_failures,
            "preemptions": self.service_preemptions,
            "work_lost_to_replay_member_steps": int(replayed or 0),
            "tenant_member_steps": tenant_steps,
            "tenant_share": ({t: s / total_steps
                              for t, s in tenant_steps.items()}
                             if total_steps else {}),
        }
        if self.service_loadgen:
            out["loadgen"] = {
                k: self.service_loadgen.get(k)
                for k in ("seed", "requests", "warm_admissions",
                          "cold_admissions", "preempted_requests",
                          "preempt_bitexact")}
        return out

    def alerts(self):
        """The live-alert summary (:mod:`pystella_tpu.obs.slo` burn-rate
        monitor): per-leg alert/resolve counts, flaps (re-fires after a
        resolve), total and max alert durations, and — the field the
        gate audits — ``unresolved``: alerts still burning when the run
        record ends. An unresolved burn alert beside a post-hoc SLO
        section that claims green is the live/post-hoc contradiction
        the gate refuses as invalid evidence (exit 2). ``None`` when
        the run carried no live SLO telemetry at all (monitor not
        attached — coverage the gate warns about when the baseline had
        it)."""
        if not self.slo_events:
            return None
        return _alert_rollup(self.slo_events)

    def fleet(self):
        """The fleet federation summary (:mod:`pystella_tpu.obs.fleet`
        aggregator over the replica registry): the replica table as of
        the last scrape (each row annotated with heartbeat age and
        per-replica scrape outcomes), the aggregated fleet SLO legs,
        lost replicas, the scrape-success rate, skew/divergence
        findings, and the fleet alert rollup (same shape as
        :meth:`alerts`, built from ``fleet_alert``/``fleet_resolved``).
        The ``coverage`` block is the gate's honesty anchor: a fleet
        claim over a run with lost replicas or failed scrapes is a
        claim over PARTIAL evidence, and ``complete`` says which kind
        this run's record is. ``None`` when the run carried no fleet
        telemetry at all."""
        if not (self.fleet_scrapes or self.fleet_lost
                or self.fleet_slo_events):
            return None
        replicas = {}
        for sc in self.fleet_scrapes:
            for row in sc.get("replicas") or []:
                rid = row.get("replica")
                if rid:
                    replicas[rid] = dict(row)
        lost_rows = []
        for data in self.fleet_lost:
            rid = data.get("replica")
            lost_rows.append({"replica": rid,
                              "reason": data.get("reason"),
                              "age_s": data.get("age_s")})
            if rid:
                replicas.setdefault(rid, {"replica": rid})
                replicas[rid]["status"] = "lost"
                replicas[rid]["lost_reason"] = data.get("reason")
        last = self.fleet_scrapes[-1] if self.fleet_scrapes else {}
        ok = sum(int(sc.get("ok") or 0) for sc in self.fleet_scrapes)
        failed = sum(int(sc.get("failed") or 0)
                     for sc in self.fleet_scrapes)
        attempts = ok + failed
        lost_ids = sorted({r["replica"] for r in lost_rows
                           if r.get("replica")})
        return {
            "replicas": [replicas[rid] for rid in sorted(replicas)],
            "scrapes": len(self.fleet_scrapes),
            "endpoint_ok": ok,
            "endpoint_failed": failed,
            "scrape_success_rate": (ok / attempts if attempts
                                    else None),
            "replicas_lost": lost_rows,
            "dead": last.get("dead"),
            "legs": last.get("legs"),
            "alerts": (_alert_rollup(self.fleet_slo_events)
                       if self.fleet_slo_events else None),
            "skew": {
                "skewed": any(sc.get("skewed")
                              for sc in self.fleet_scrapes),
                "stacks": last.get("stacks"),
            },
            "divergence": sorted({sig for sc in self.fleet_scrapes
                                  for sig in (sc.get("divergent")
                                              or [])}),
            "announces": len(self.fleet_announces),
            "withdraws": len(self.fleet_withdraws),
            "coverage": {
                "replicas": len(replicas),
                "lost": len(lost_ids),
                "endpoint_failed": failed,
                "complete": not lost_ids and failed == 0,
            },
        }

    def perf(self):
        """The continuous-performance summary (:mod:`pystella_tpu.obs.
        perf` detector + flight recorder): the anomaly rollup per
        program signature (same shape as :meth:`alerts` — the field
        the gate audits is ``anomalies.unresolved``, anomalies still
        open when the run record ends), the latest digest window per
        signature (p50/p95/p99 ms), the flight-recorder captures with
        their Perfetto artifact paths (the ledger link the gate checks
        when anomalies fired), and the straggler attribution from the
        last anomaly that carried one. ``None`` when the run carried
        no continuous-performance telemetry at all (``PYSTELLA_PERF=0``
        or a pre-PR-17 log — coverage the gate warns about when the
        baseline had it)."""
        if not (self.perf_events or self.perf_captures
                or self.perf_digests):
            return None
        # reuse the alert rollup: an anomaly is a fired alert on the
        # leg named by its signature, recovery resolves it
        anomalies = _alert_rollup([
            (("alert" if kind == "anomaly" else "resolved"), ts,
             {**data, "leg": data.get("signature", "step"),
              "value": data.get("ms"),
              "bar": data.get("baseline_ms")})
            for kind, ts, data in self.perf_events])
        digests = {}
        for data in self.perf_digests:
            sig = data.get("signature", "step")
            digests[sig] = {k: data.get(k) for k in
                            ("count", "mean_ms", "p50_ms", "p95_ms",
                             "p99_ms")}
        straggler = None
        for kind, _, data in reversed(self.perf_events):
            if kind == "anomaly" and data.get("straggler"):
                straggler = data["straggler"]
                break
        captures = [{k: data.get(k) for k in
                     ("signature", "reason", "artifact", "logdir",
                      "steps", "suppressed", "error") if k in data}
                    for data in self.perf_captures]
        return {
            "anomalies": anomalies,
            "digests": digests or None,
            "captures": captures,
            "captures_suppressed": max(
                [int(c.get("suppressed") or 0) for c in captures],
                default=0),
            "straggler": straggler,
        }

    def capacity(self):
        """The capacity & goodput summary (:mod:`pystella_tpu.obs.
        capacity`): the per-program footprint table (predicted bytes +
        prediction source) against the observed live watermarks, the
        predicted-vs-peak reconciliation, the headroom series summary,
        memory-aware admission rejections/evictions, OOM forensic
        bundles, and the retire-time chargeback — per-tenant
        chip-second/goodput table plus the overall
        ``goodput = committed member-steps / total chip-seconds``. The
        ``coverage`` block is the gate's honesty anchor: a capacity
        claim over leases with NO watermark samples cannot read as
        ``complete`` (CPU runs degrade to ``predicted_only``). ``None``
        when the run carried no capacity telemetry at all (pre-PR-19
        logs, or the plane disabled)."""
        if not (self.capacity_footprints or self.capacity_watermarks
                or self.capacity_accounts or self.capacity_usage
                or self.capacity_rejects or self.capacity_oom):
            return None
        usage = self.capacity_usage or {}
        footprints = {}
        for data in self.capacity_footprints:
            key = (data.get("label"), data.get("fingerprint"))
            footprints[key] = {
                k: data.get(k) for k in
                ("label", "fingerprint", "predicted_bytes", "source")}
        peaks = [w.get("peak_bytes_in_use")
                 for w in self.capacity_watermarks
                 if isinstance(w.get("peak_bytes_in_use"),
                               (int, float))]
        in_use = [w.get("bytes_in_use") for w in self.capacity_watermarks
                  if isinstance(w.get("bytes_in_use"), (int, float))]
        headroom = [w.get("headroom_frac")
                    for w in self.capacity_watermarks
                    if isinstance(w.get("headroom_frac"), (int, float))]
        coverage = usage.get("coverage") or {
            "leases": None,
            "leases_sampled": None,
            "watermark_samples": len(self.capacity_watermarks),
            "predicted_only": not self.capacity_watermarks,
            "complete": False,
        }
        rejects = {
            "count": len(self.capacity_rejects),
            "signatures": sorted({r.get("signature")
                                  for r in self.capacity_rejects
                                  if r.get("signature")}),
            "last": (self.capacity_rejects[-1]
                     if self.capacity_rejects else None),
        }
        return {
            "footprints": [footprints[k] for k in sorted(
                footprints, key=lambda k: (str(k[0]), str(k[1])))],
            "watermarks": {
                "samples": len(self.capacity_watermarks),
                "peak_bytes_in_use": max(peaks) if peaks else None,
                "max_bytes_in_use": max(in_use) if in_use else None,
                "headroom_frac_max": (max(headroom) if headroom
                                      else None),
            },
            "reconciliation": usage.get("reconciliation"),
            "rejections": rejects,
            "evictions": len(self.capacity_evictions),
            "oom_bundles": [d.get("path") for d in self.capacity_oom],
            "tenants": usage.get("tenants"),
            "goodput": usage.get("goodput"),
            "total_chip_s": usage.get("total_chip_s"),
            "committed_steps": usage.get("committed_steps"),
            "waste_chip_s": usage.get("waste_chip_s"),
            "capacity_bytes": usage.get("capacity_bytes"),
            "headroom": usage.get("headroom"),
            "resident_predicted_bytes":
                usage.get("resident_predicted_bytes"),
            "accounts": self.capacity_accounts[-64:],
            "coverage": coverage,
        }

    def latency(self):
        """Request-scoped critical-path latency attribution
        (:mod:`pystella_tpu.obs.spans` over the schema-v2 trace
        stream): per-request phase decomposition percentiles (queue
        wait / admission / compile / chunk compute / checkpoint
        barrier / recovery replay / preempt drain), the dominant-phase
        histogram, the partition audit (phases must sum to the
        measured submit→retire wall), the deadline ledger (miss rate
        per priority class + margin distribution — the gate's
        deadline-miss SLO), and the coverage split (``unassembled``
        names traced requests whose span tree failed to close — the
        gate's coverage-loss warning). ``None`` when the run carried
        no traced request at all (v1 logs, or
        ``PYSTELLA_TRACE_SERVICE=0``)."""
        if not self.span_records:
            return None
        # deferred import: obs.spans has a ``python -m`` entry point,
        # and a module-level import here would put it in sys.modules
        # before runpy executes it (same reason obs/__init__ leaves
        # gate and warmstart out)
        from pystella_tpu.obs import spans as _spans
        summary = _spans.SpanAssembler.from_records(
            self.span_records).summary()
        if summary is not None:
            summary["deadline"]["miss_events"] = \
                self.deadline_miss_events
        return summary

    def _degrading_plan(self):
        """The last remesh_plan that actually changed the mesh
        (``changed`` and ``feasible``), or ``None`` — transport-blip
        recoveries emit ``changed=False`` plans that must not make a
        window read as degraded."""
        for plan in reversed(self.remesh_plans):
            if plan.get("changed") and plan.get("feasible"):
                return plan
        return None

    def _per_chip_throughput(self):
        """The per-chip normalization of the headline throughput —
        and the honesty marker the gate audits: a window that
        re-meshed finished on the SURVIVORS, so its per-chip figure
        uses the POST-remesh step times divided by the degraded
        mesh's device count (``basis: "surviving"``) — never the
        full-mesh-dominated whole-window median over the survivors,
        which would overstate the degraded throughput ~(lost/survived)
        fold. ``None`` rate when no post-remesh samples exist (e.g. a
        drill whose timed loop ran before the remesh); ``None``
        entirely when no device count is known."""
        plan = self._degrading_plan()
        if plan is not None:
            used = plan.get("devices") or plan.get("survivors") or []
            chips = len(used) if isinstance(used, list) else None
            post = (self.samples_ms[self._post_remesh_start:]
                    if self._post_remesh_start is not None else [])
            rate = None
            if post and self.sites:
                p50 = step_stats(post).get("p50_ms")
                if p50:
                    rate = float(self.sites) * 1e3 / p50
            basis = "surviving"
        else:
            rate = self.site_updates_per_s()
            chips = self.env.get("num_devices")
            basis = "all"
        if not chips:
            return None
        return {"chips": int(chips), "basis": basis,
                "site_updates_per_s_per_chip": (rate / chips
                                                if rate else None)}

    # -- report ------------------------------------------------------------

    def report(self):
        """The JSON-safe report dict (``perf_report.json`` schema v1;
        doc/observability.md documents every field)."""
        return {
            "schema": REPORT_SCHEMA_VERSION,
            "generated_ts": time.time(),
            "label": self.label,
            "env": self.env,
            "run": self.meta,
            "steps": self.stats(),
            "samples_ms": [round(x, 6)
                           for x in self.samples_ms[-MAX_SAMPLES:]],
            "throughput": {
                "sites": self.sites,
                "site_updates_per_s": self.site_updates_per_s(),
                "per_chip": self._per_chip_throughput(),
            },
            "roofline": self.roofline(),
            "overlap": self.overlap_summary(),
            "comm": self.comm(),
            "cold_start": self.cold_start(),
            "numerics": self.numerics(),
            "ensemble": self.ensemble(),
            "resilience": self.resilience(),
            "fft": self.fft(),
            "service": self.service(),
            "latency": self.latency(),
            "alerts": self.alerts(),
            "fleet": self.fleet(),
            "perf": self.perf(),
            "capacity": self.capacity(),
            "lint": self.lint,
            "scopes": self.scopes,
            "trace_file": self.trace_file,
            "metrics": self.metrics,
        }

    def write(self, out_dir, stem="perf_report"):
        """Write ``<stem>.json`` + ``<stem>.md`` under ``out_dir``;
        returns the JSON path. Also emits a ``perf_report`` run event
        pointing at it, so the event log records which report a run
        produced."""
        os.makedirs(out_dir, exist_ok=True)
        rep = self.report()
        json_path = os.path.join(out_dir, stem + ".json")
        with open(json_path, "w") as f:
            json.dump(rep, f, indent=1, sort_keys=True)
            f.write("\n")
        with open(os.path.join(out_dir, stem + ".md"), "w") as f:
            f.write(render_markdown(rep))
        _events.emit("perf_report", path=json_path, label=self.label)
        return json_path


def _alert_rollup(events):
    """Per-leg fire/resolve bookkeeping over ``[("alert"|"resolved",
    ts, data), ...]`` — one definition for both the live
    (``slo_alert``) and fleet (``fleet_alert``) vocabularies, so their
    report shapes cannot diverge."""
    by_leg = {}

    def row(leg):
        return by_leg.setdefault(str(leg), {
            "alerts": 0, "resolved": 0, "flaps": 0,
            "total_alert_s": 0.0, "max_alert_s": None,
            "open": None})

    for kind, ts, data in events:
        r = row(data.get("leg"))
        if kind == "alert":
            r["alerts"] += 1
            r["flaps"] = max(0, r["alerts"] - 1)
            r["open"] = {"since_ts": ts,
                         "value": data.get("value"),
                         "bar": data.get("bar"),
                         "burn_fast": data.get("burn_fast"),
                         "burn_slow": data.get("burn_slow")}
        else:
            r["resolved"] += 1
            d = data.get("duration_s")
            if d is None and r["open"] is not None \
                    and isinstance(ts, (int, float)) \
                    and isinstance(r["open"].get("since_ts"),
                                   (int, float)):
                d = ts - r["open"]["since_ts"]
            if isinstance(d, (int, float)):
                r["total_alert_s"] += float(d)
                r["max_alert_s"] = (float(d)
                                    if r["max_alert_s"] is None
                                    else max(r["max_alert_s"],
                                             float(d)))
            r["open"] = None
    unresolved = [{"leg": leg, **r["open"]}
                  for leg, r in sorted(by_leg.items())
                  if r["open"] is not None]
    return {
        "alerts": sum(r["alerts"] for r in by_leg.values()),
        "resolved": sum(r["resolved"] for r in by_leg.values()),
        "flaps": sum(r["flaps"] for r in by_leg.values()),
        "unresolved": unresolved,
        "by_leg": {leg: {k: v for k, v in r.items() if k != "open"}
                   for leg, r in sorted(by_leg.items())},
    }


def _lat_stats(samples_s):
    """Latency-distribution summary in SECONDS (the service section's
    queue-latency / TTFS fields; ``step_stats`` stays the millisecond
    step-time shape): count, mean, p50/p90/p95, max."""
    if not samples_s:
        return {"count": 0}
    s = sorted(float(x) for x in samples_s)
    return {
        "count": len(s),
        "mean_s": sum(s) / len(s),
        "p50_s": percentile(s, 50),
        "p90_s": percentile(s, 90),
        "p95_s": percentile(s, 95),
        "max_s": s[-1],
    }


def _slope(xs, ys):
    """Least-squares slope of ``ys`` against ``xs`` (0.0 for degenerate
    inputs) — the invariant-drift-per-step statistic."""
    n = len(xs)
    if n < 2:
        return 0.0
    mx = sum(xs) / n
    my = sum(ys) / n
    var = sum((x - mx) ** 2 for x in xs)
    if var == 0:
        return 0.0
    return sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var


def _fmt(x, spec=".4g", none="—"):
    return format(x, spec) if isinstance(x, (int, float)) else none


def render_markdown(rep):
    """Human rendering of a report dict (the ``perf_report.md`` body)."""
    env = rep.get("env", {})
    st = rep.get("steps", {})
    tp = rep.get("throughput", {})
    rf = rep.get("roofline", {})
    lines = [
        f"# Perf report — {rep.get('label') or 'unlabeled run'}",
        "",
        "Generated "
        + time.strftime("%Y-%m-%d %H:%M:%S UTC",
                        time.gmtime(rep.get("generated_ts", 0)))
        + f" · schema v{rep.get('schema')}",
        "",
        "## Environment",
        "",
        f"- jax {env.get('jax')} / jaxlib {env.get('jaxlib')}"
        + (f" / libtpu {env['libtpu']}" if env.get("libtpu") else "")
        + f", python {env.get('python')}",
        f"- platform `{env.get('platform')}`, device kind "
        f"`{env.get('device_kind')}`, {env.get('num_devices')} device(s), "
        f"{env.get('num_processes')} process(es), "
        f"host `{env.get('hostname')}`",
        "",
        "## Step-time distribution",
        "",
        f"{st.get('count', 0)} steps: "
        f"p50 {_fmt(st.get('p50_ms'))} ms, p90 {_fmt(st.get('p90_ms'))} ms, "
        f"p99 {_fmt(st.get('p99_ms'))} ms, MAD {_fmt(st.get('mad_ms'))} ms "
        f"(mean {_fmt(st.get('mean_ms'))}, min {_fmt(st.get('min_ms'))}, "
        f"max {_fmt(st.get('max_ms'))})",
        "",
        "## Throughput",
        "",
        f"- sites/step: {_fmt(tp.get('sites'), ',.0f')}",
        f"- site-updates/s (median step): "
        f"{_fmt(tp.get('site_updates_per_s'), '.4e')}",
        "",
        "## Roofline",
        "",
        f"- bytes/step (XLA arg+out floor): "
        f"{_fmt(rf.get('bytes_per_step'), ',.0f')}",
        f"- achieved {_fmt(rf.get('achieved_gbps'))} GB/s of "
        f"{_fmt(rf.get('peak_gbps'))} GB/s peak -> "
        f"{_fmt(rf.get('fraction_of_peak'), '.1%')} of roofline",
        f"- donated (input->output aliased) bytes: "
        f"{_fmt(rf.get('donated_bytes'), ',.0f')} — HBM the step does "
        "not hold twice (from the step compile's alias analysis)",
        "",
    ]
    kt = rf.get("kernel_tiers")
    if kt:
        lines += ["### Kernel tiers dispatched", ""]
        for row in kt.get("dispatched") or []:
            extra = ""
            if row.get("chunk_depth"):
                extra = f", depth {row['chunk_depth']}"
            if isinstance(row.get("bytes_per_step"), (int, float)):
                extra += (f", {row['bytes_per_step']:,.0f} lattice "
                          "bytes/step")
            lines.append(f"- {row.get('label')}.{row.get('entrypoint')}"
                         f": **{row.get('tier')}**{extra}")
        cvp = kt.get("chunk_vs_pair")
        if cvp:
            lines.append(
                f"- chunk vs pair: "
                f"{cvp['chunk_bytes_per_step']:,} vs "
                f"{cvp['pair_bytes_per_step']:,} bytes/step -> "
                f"{cvp['traffic_reduction']:.1%} less HBM traffic")
        lines.append("")
    lint = rep.get("lint")
    if lint:
        lines += ["## Lint", ""]
        lines.append(
            f"- static analysis {'PASSED' if lint.get('ok') else '**FAILED**'}"
            f": {_fmt(lint.get('errors'), '.0f', '0')} error(s), "
            f"{_fmt(lint.get('warnings'), '.0f', '0')} warning(s) "
            f"({', '.join(lint.get('checks') or []) or 'no checks'})")
        don = lint.get("donation") or {}
        if don:
            lines.append(
                f"- donation coverage {_fmt(don.get('coverage_pct'), '.1f')}%"
                f" ({_fmt(don.get('aliased_bytes'), ',.0f')} of "
                f"{_fmt(don.get('donatable_bytes'), ',.0f')} donatable "
                f"step-state bytes aliased; "
                f"{_fmt(don.get('wasted_bytes'), ',.0f')} B wasted)")
        for reason in (lint.get("first_errors") or [])[:5]:
            lines.append(f"- {reason}")
        lines.append("")
    ov = rep.get("overlap")
    if ov:
        lines += ["## Communication overlap", ""]
        lines.append(
            f"- halo comm (`{ov.get('comm_scope')}` rows, per-device): "
            f"{_fmt(ov.get('comm_ms'))} ms in the traced window — "
            f"hidden behind interior compute {_fmt(ov.get('hidden_ms'))}"
            f" ms, exposed {_fmt(ov.get('exposed_ms'))} ms")
        if ov.get("interior_ms") is None:
            lines.append(
                "- *(no `halo_overlap_interior` device rows in this "
                "trace — host-span-only captures cannot attribute "
                "hiding, so all comm time counts as exposed)*")
        if ov.get("halo_bytes_per_step"):
            lines.append(
                f"- halo traffic {_fmt(ov['halo_bytes_per_step'], ',.0f')}"
                f" B/call x {_fmt(ov.get('overlapped_calls'), '.0f')} "
                f"overlapped call(s) -> achieved "
                f"~{_fmt(ov.get('achieved_ici_gbps'))} GB/s ICI "
                "(per-device estimate)")
        lines.append("")
    cm = rep.get("comm")
    if cm:
        lines += ["## Modeled vs measured communication", ""]
        for leg in cm.get("legs") or []:
            row = (f"- {leg.get('target')} ({leg.get('class') or '—'}): "
                   f"modeled {_fmt(leg.get('modeled_bytes'), ',.0f')} B")
            if leg.get("measured_bytes") is not None:
                row += (f", measured "
                        f"{_fmt(leg.get('measured_bytes'), ',.0f')} B "
                        f"({leg.get('measured_source')}) -> "
                        f"{_fmt(leg.get('excess_pct'), '+.1f')}% vs "
                        f"model"
                        + ("" if leg.get("within") in (None, True)
                           else " **EXCESS**"))
            else:
                row += " (model-only: no measured counter this run)"
            lines.append(row)
        if not cm.get("covered"):
            lines.append("- *(no leg carries both a model and a "
                         "measured counter — comm not covered)*")
        lines.append("")
    cs = rep.get("cold_start")
    if cs:
        lines += ["## Cold start", ""]
        ph = cs.get("phases") or {}
        # drivers report different phase sets (import/build, dial,
        # the examples' setup) — render whatever
        # this run measured, in pipeline order, instead of a fixed
        # key list that dashes out the dial/setup share
        order = ("import_s", "dial_s", "setup_s", "build_s", "trace_s",
                 "compile_s", "first_dispatch_s")
        keys = ([k for k in order if k in ph]
                + sorted(k for k in ph if k not in order))
        parts = ", ".join(
            f"{k[:-2].replace('_', ' ') if k.endswith('_s') else k} "
            f"{_fmt(ph.get(k))}" for k in keys)
        lines.append(
            f"- time to first step: "
            f"{_fmt(cs.get('time_to_first_step_s'))} s"
            + (f" ({parts} s)" if parts else ""))
        ca = cs.get("cache") or {}
        lines.append(
            f"- compilation cache: "
            + (f"`{ca.get('dir')}` — {_fmt(ca.get('hits'), '.0f', '0')} "
               f"hit(s) / {_fmt(ca.get('misses'), '.0f', '0')} miss(es)"
               f" (hit rate {_fmt(ca.get('hit_rate'), '.1%')})"
               if ca.get("dir") else "not wired "
               "(obs.ensure_compilation_cache was not called)"))
        ws = cs.get("warmstart") or {}
        if ws.get("claimed"):
            arts = ws.get("artifacts") or []
            ok = sum(1 for a in arts if a.get("match"))
            bad = [a for a in arts if a.get("match") is False]
            lines.append(
                f"- warm start: {ok} AOT artifact(s) loaded"
                + (f", **{len(bad)} fingerprint mismatch(es)**"
                   if bad else ""))
            for a in bad[:5]:
                lines.append(f"  - `{a.get('label')}`: "
                             f"{a.get('reason') or 'mismatch'}")
        falls = ws.get("fallbacks") or []
        if falls:
            lines.append(
                f"- {len(falls)} stale artifact(s) refused (honest "
                "cold fallback)")
            for a in falls[:5]:
                lines.append(f"  - `{a.get('label')}`: "
                             f"{a.get('reason') or 'mismatch'}")
        compiles = cs.get("compiles") or []
        if compiles:
            lines += ["", "| program | trace s | compile s | cache |",
                      "|---|---|---|---|"]
            for c in compiles[:12]:
                hit = c.get("cache_hit")
                tag = "hit" if hit else ("miss" if hit is False else "—")
                lines.append(
                    f"| `{c.get('label')}` | {_fmt(c.get('trace_s'))} "
                    f"| {_fmt(c.get('compile_s'))} | {tag} |")
            if len(compiles) > 12:
                lines.append(f"| … {len(compiles) - 12} more | | | |")
        lines.append("")
    nm = rep.get("numerics")
    if nm:
        lines += ["## Numerics health", ""]
        for name, row in sorted((nm.get("invariants") or {}).items()):
            lines.append(
                f"- invariant `{name}`: {_fmt(row.get('first'), '.6g')} "
                f"-> {_fmt(row.get('last'), '.6g')} over "
                f"{row.get('n')} sample(s), drift "
                f"{_fmt(row.get('drift_per_step'), '.3e')}/step")
        lines.append(
            f"- {_fmt(nm.get('health_checks'), '.0f', '0')} health "
            f"check(s), sentinel overhead "
            f"{_fmt(nm.get('sentinel_overhead_pct'), '.2f')}% of step "
            "time (host-side; the in-graph reductions are inside the "
            "step samples themselves)")
        for d in nm.get("diverged") or []:
            lines.append(
                f"- **DIVERGED** at step {d.get('step')}: "
                f"{d.get('fields')}"
                + (f" (invariant `{d['offending_invariant']}`)"
                   if d.get("offending_invariant") else ""))
        for b in nm.get("forensic_bundles") or []:
            lines.append(f"- forensic bundle: `{b}`")
        lines.append("")
    en = rep.get("ensemble")
    if en:
        lines += ["## Ensemble", ""]
        lines.append(
            f"- {_fmt(en.get('member_steps'), ',.0f')} member-steps in "
            f"{_fmt(en.get('wall_s'))} s -> "
            f"{_fmt(en.get('member_steps_per_s'))} member-steps/s"
            + (f" ({_fmt(en['member_steps_per_s_per_device'])} per "
               "device)" if en.get("member_steps_per_s_per_device")
               else ""))
        lines.append(
            f"- batch size {_fmt(en.get('size'), '.0f')}, mean "
            f"occupancy {_fmt(en.get('occupancy_mean'), '.1%')}, "
            f"{_fmt(en.get('members_completed'), '.0f', '0')} member(s) "
            f"completed over {_fmt(en.get('runs'), '.0f')} driver "
            "run(s)")
        ch = en.get("chunks") or {}
        if ch.get("count"):
            lines.append(
                f"- {ch['count']} batched dispatch(es): p50 "
                f"{_fmt(ch.get('p50_ms'))} ms, p90 "
                f"{_fmt(ch.get('p90_ms'))} ms per chunk")
        nev = en.get("evictions") or 0
        lines.append(f"- {nev} member eviction(s)")
        for e in (en.get("eviction_records") or [])[:8]:
            lines.append(
                f"  - member {e.get('member')} (scenario "
                f"`{e.get('scenario')}`) at step {e.get('step')}: "
                f"{e.get('fields')}")
        lines.append("")
    rz = rep.get("resilience")
    if rz:
        lines += ["## Resilience", ""]
        n = rz.get("n_incidents") or 0
        lines.append(
            f"- {n} incident(s) detected, "
            f"{_fmt(rz.get('resolved'), '.0f', '0')} recovered "
            f"(MTTR mean {_fmt(rz.get('mttr_s_mean'))} s, max "
            f"{_fmt(rz.get('mttr_s_max'))} s), "
            f"{_fmt(rz.get('steps_replayed'), '.0f', '0')} step(s) "
            f"replayed over "
            f"{_fmt(rz.get('recovery_attempts'), '.0f', '0')} recovery "
            "attempt(s)")
        if rz.get("consistent") is False:
            lines.append(
                "- **INCONSISTENT**: the supervisor claims "
                f"{rz.get('claimed_incidents')} incident(s) but the "
                f"event log records {n} — the gate refuses this report")
        incs = rz.get("incidents") or []
        if incs:
            lines += ["", "| kind | detected at | restored to | MTTR s "
                          "| replayed | attempts |",
                      "|---|---|---|---|---|---|"]
            for i in incs[:12]:
                lines.append(
                    f"| {i.get('kind')} | {i.get('detected_at_step')} "
                    f"| {i.get('restored_step')} "
                    f"| {_fmt(i.get('mttr_s'))} "
                    f"| {i.get('steps_replayed')} "
                    f"| {i.get('attempts')} |")
            lines.append("")
        ck = rz.get("checkpoints") or {}
        lines.append(
            f"- checkpoints: {_fmt(ck.get('saved'), '.0f', '0')} "
            f"scheduled, {_fmt(ck.get('durable'), '.0f', '0')} durable "
            f"(cadence {_fmt(ck.get('cadence_steps'), '.0f')} steps), "
            f"{_fmt(ck.get('fallbacks'), '.0f', '0')} walk-back "
            f"fallback(s); durability barriers "
            f"{_fmt(ck.get('barrier_s'))} s"
            + (f" ({_fmt(ck.get('barrier_pct_of_wall'), '.2f')}% of "
               "supervised wall time)"
               if ck.get("barrier_pct_of_wall") is not None else ""))
        if rz.get("faults_injected"):
            lines.append(
                f"- {rz['faults_injected']} fault(s) INJECTED by the "
                "harness (a drill, not weather)")
        if rz.get("preempted"):
            lines.append("- run **preempted** (drained to a durable "
                         "checkpoint; resume with the supervisor)")
        deg = rz.get("degraded")
        if isinstance(deg, dict):
            for d in (deg.get("events") or [])[:4]:
                lines.append(f"- **degraded** at step {d.get('step')}: "
                             f"{d.get('note')}")
            if deg.get("new_mesh"):
                total = ((deg.get("devices_used") or 0)
                         + (deg.get("lost_devices") or 0))
                lines.append(
                    f"- re-mesh: {deg.get('old_mesh')} -> "
                    f"{deg.get('new_mesh')} "
                    f"({_fmt(deg.get('devices_used'), '.0f')} of "
                    f"{_fmt(total, '.0f')} devices)")
            post = deg.get("post_remesh")
            if post:
                lines.append(
                    "- post-remesh: p50 "
                    f"{_fmt(post.get('p50_ms'))} ms/step over "
                    f"{post.get('samples')} sample(s), "
                    f"{_fmt(post.get('site_updates_per_s_per_surviving_chip'), '.3e')}"
                    " site-updates/s per SURVIVING chip")
        elif deg:  # pre-remesh-library reports: a bare event list
            for d in deg[:4]:
                lines.append(f"- **degraded** at step {d.get('step')}: "
                             f"{d.get('note')}")
        lines.append("")
    sv = rep.get("service")
    if sv:
        lines += ["## Service", ""]
        ql = (sv.get("queue_latency_s") or {})
        overall = ql.get("overall") or {}
        lines.append(
            f"- {_fmt(sv.get('requests'), '.0f', '0')} request(s) "
            f"dispatched over {_fmt(sv.get('leases'), '.0f', '0')} "
            f"lease(s): {_fmt(sv.get('completed'), '.0f', '0')} "
            f"completed, {_fmt(sv.get('diverged'), '.0f', '0')} "
            f"diverged, "
            f"{_fmt(sum((sv.get('rejected') or {}).values()), '.0f', '0')}"
            f" rejected"
            + (f" ({', '.join(f'{k}: {v}' for k, v in sorted((sv.get('rejected') or {}).items()))})"
               if sv.get("rejected") else ""))
        lines.append(
            f"- queue latency: p50 {_fmt(overall.get('p50_s'))} s, "
            f"p95 {_fmt(overall.get('p95_s'))} s over "
            f"{_fmt(overall.get('count'), '.0f', '0')} dispatch(es)")
        for cls, row in sorted((ql.get("by_priority") or {}).items()):
            lines.append(
                f"  - class {cls}: p50 {_fmt(row.get('p50_s'))} s, "
                f"p95 {_fmt(row.get('p95_s'))} s "
                f"({row.get('count')} dispatch(es))")
        tf = sv.get("ttfs_s") or {}
        warm_t, cold_t = tf.get("warm") or {}, tf.get("cold") or {}
        lines.append(
            f"- time-to-first-step: warm p50 "
            f"{_fmt(warm_t.get('p50_s'))} s "
            f"({_fmt(warm_t.get('count'), '.0f', '0')} lease(s)), "
            f"cold p50 {_fmt(cold_t.get('p50_s'))} s "
            f"({_fmt(cold_t.get('count'), '.0f', '0')} lease(s))")
        lines.append(
            f"- warm path: {_fmt(sv.get('warm_leases'), '.0f', '0')} "
            f"warm lease(s), "
            f"{_fmt(sv.get('warm_lease_backend_compiles'), '.0f', '0')} "
            "backend compile(s) on them (the contract is ZERO)"
            + ("" if not sv.get("warm_lease_backend_compiles") else
               " — **dispatch-never-compile violated**"))
        bad_warm = [a for a in sv.get("warm_admissions") or []
                    if a.get("fingerprint_ok") is False]
        if bad_warm:
            lines.append(
                f"- **{len(bad_warm)} warm admission(s) over "
                "mismatched fingerprints** — the gate refuses this "
                "report")
        lines.append(
            f"- {_fmt(sv.get('preemptions'), '.0f', '0')} "
            f"preemption(s), "
            f"{_fmt(sv.get('work_lost_to_replay_member_steps'), '.0f', '0')}"
            f" member-step(s) lost to replay, "
            f"{_fmt(sv.get('lease_failures'), '.0f', '0')} lease "
            "failure(s)")
        shares = sv.get("tenant_share") or {}
        if shares:
            lines.append("- tenant occupancy: " + ", ".join(
                f"{t} {_fmt(f, '.1%')}"
                for t, f in sorted(shares.items())))
        lg = sv.get("loadgen")
        if lg:
            lines.append(
                f"- loadgen (seed {lg.get('seed')}): "
                f"{_fmt(lg.get('requests'), '.0f', '0')} request(s), "
                f"{_fmt(lg.get('warm_admissions'), '.0f', '0')} warm / "
                f"{_fmt(lg.get('cold_admissions'), '.0f', '0')} cold "
                "admission(s), preempted-resume bit-exact: "
                f"{lg.get('preempt_bitexact')}")
        lines.append("")
    lat = rep.get("latency")
    if lat:
        lines += ["## Latency (request critical path)", ""]
        wall = lat.get("wall_s") or {}
        lines.append(
            f"- {_fmt(lat.get('assembled'), '.0f', '0')} of "
            f"{_fmt(lat.get('traced'), '.0f', '0')} traced request(s) "
            f"assembled; submit→retire wall p50 "
            f"{_fmt(wall.get('p50_s'))} s, p95 {_fmt(wall.get('p95_s'))}"
            " s")
        if lat.get("unassembled"):
            n_bad = lat.get("unassembled_total")
            if not isinstance(n_bad, int):
                n_bad = len(lat["unassembled"])
            lines.append(
                f"- **{n_bad} traced request(s) "
                "failed to assemble** (coverage loss; see "
                "`latency.unassembled`)")
        chk = lat.get("phase_sum_check") or {}
        if chk.get("max_rel_err") is not None:
            lines.append(
                f"- partition audit: phases sum to the wall within "
                f"{_fmt(chk['max_rel_err'], '.2%')} worst-case "
                f"(tolerance {_fmt(chk.get('tolerance'), '.0%')}: "
                f"{'OK' if chk.get('ok') else '**VIOLATED**'})")
        phases = lat.get("phases_s") or {}
        if phases:
            lines += ["", "| phase | requests | p50 s | p95 s | max s |",
                      "|---|---|---|---|---|"]
            for name, row in sorted(
                    phases.items(),
                    key=lambda kv: -(kv[1].get("p50_s") or 0.0)):
                lines.append(
                    f"| `{name}` | {row.get('count')} "
                    f"| {_fmt(row.get('p50_s'))} "
                    f"| {_fmt(row.get('p95_s'))} "
                    f"| {_fmt(row.get('max_s'))} |")
            lines.append("")
        dom = lat.get("dominant_phase") or {}
        if dom:
            lines.append("- dominant phase: " + ", ".join(
                f"`{p}` ×{n}" for p, n in sorted(
                    dom.items(), key=lambda kv: -kv[1])))
        dl = lat.get("deadline") or {}
        if dl.get("deadlined"):
            rate = dl.get("miss_rate")
            lines.append(
                f"- deadlines: {dl.get('missed')} of "
                f"{dl.get('deadlined')} deadlined request(s) missed "
                f"({_fmt(rate, '.0%')}); margin p50 "
                f"{_fmt((dl.get('margin_s') or {}).get('p50_s'))} s")
            for cls, row in sorted((dl.get("by_priority") or {}).items()):
                lines.append(
                    f"  - class {cls}: {row.get('missed')}/"
                    f"{row.get('deadlined')} missed "
                    f"({_fmt(row.get('miss_rate'), '.0%')})")
        lines.append("")
    al = rep.get("alerts")
    if al:
        lines += ["## SLO alerts (live burn-rate monitor)", ""]
        lines.append(
            f"- {_fmt(al.get('alerts'), '.0f', '0')} alert(s) fired, "
            f"{_fmt(al.get('resolved'), '.0f', '0')} resolved, "
            f"{_fmt(al.get('flaps'), '.0f', '0')} flap(s) "
            "(re-fires after a resolve)")
        for rec in al.get("unresolved") or []:
            lines.append(
                f"- **UNRESOLVED at exit**: `{rec.get('leg')}` burning "
                f"at {_fmt(rec.get('value'))} vs bar "
                f"{_fmt(rec.get('bar'))} — the gate refuses this "
                "report if its post-hoc SLO section claims green")
        for leg, r in sorted((al.get("by_leg") or {}).items()):
            lines.append(
                f"  - `{leg}`: {r.get('alerts')} fired / "
                f"{r.get('resolved')} resolved, total "
                f"{_fmt(r.get('total_alert_s'))} s alerting"
                + (f" (max {_fmt(r.get('max_alert_s'))} s)"
                   if r.get("max_alert_s") is not None else ""))
        lines.append("")
    pf = rep.get("perf")
    if pf:
        lines += ["## Continuous performance (obs.perf)", ""]
        an = pf.get("anomalies") or {}
        lines.append(
            f"- {_fmt(an.get('alerts'), '.0f', '0')} anomaly(ies) "
            f"fired, {_fmt(an.get('resolved'), '.0f', '0')} recovered, "
            f"{_fmt(an.get('flaps'), '.0f', '0')} flap(s)")
        for rec in an.get("unresolved") or []:
            lines.append(
                f"- **UNRESOLVED at exit**: `{rec.get('leg')}` at "
                f"{_fmt(rec.get('value'))} ms vs baseline "
                f"{_fmt(rec.get('bar'))} ms — the gate refuses this "
                "report if its step-time verdict claims green")
        for sig, d in sorted((pf.get("digests") or {}).items()):
            lines.append(
                f"  - `{sig}` digest: p50 {_fmt(d.get('p50_ms'))} / "
                f"p95 {_fmt(d.get('p95_ms'))} / "
                f"p99 {_fmt(d.get('p99_ms'))} ms over "
                f"{_fmt(d.get('count'), '.0f')} step(s)")
        st = pf.get("straggler")
        if st:
            slow = st.get("slowest") or {}
            lines.append(
                f"- straggler attribution: host {slow.get('host')} at "
                f"{_fmt(slow.get('mean_ms'))} ms vs fleet median "
                f"{_fmt(st.get('median_ms'))} ms "
                f"(skew {_fmt(st.get('skew'))}"
                + (", **skewed**)" if st.get("skewed") else ")"))
        for cap in pf.get("captures") or []:
            art = cap.get("artifact")
            lines.append(
                f"- flight-recorder capture (`{cap.get('signature')}`, "
                f"{cap.get('steps')} step(s)): "
                + (f"`{art}`" if art else "no artifact ("
                   + str(cap.get("error")
                         or "profiler produced no trace") + ")"))
        sup = pf.get("captures_suppressed")
        if sup:
            lines.append(f"- {sup} capture request(s) rate-limit "
                         "suppressed (one trace per cooldown)")
        lines.append("")
    fl = rep.get("fleet")
    if fl:
        lines += ["## Fleet (replica registry + federation)", ""]
        cov = fl.get("coverage") or {}
        lines.append(
            f"- {_fmt(cov.get('replicas'), '.0f', '0')} replica(s) "
            f"seen, {_fmt(cov.get('lost'), '.0f', '0')} lost, "
            f"{_fmt(fl.get('scrapes'), '.0f', '0')} aggregation "
            f"pass(es), scrape success "
            f"{_fmt(fl.get('scrape_success_rate'), '.0%')} "
            f"({'complete' if cov.get('complete') else 'PARTIAL'} "
            "coverage)")
        rows = fl.get("replicas") or []
        if rows:
            lines += ["", "| replica | status | heartbeat age s "
                      "| queue | fingerprint |", "|---|---|---|---|---|"]
            for row in rows:
                lines.append(
                    f"| `{row.get('replica')}` | {row.get('status')} "
                    f"| {_fmt(row.get('age_s'))} "
                    f"| {_fmt(row.get('queue_depth'), '.0f')} "
                    f"| `{row.get('fingerprint') or '—'}` |")
            lines.append("")
        for rec in fl.get("replicas_lost") or []:
            lines.append(
                f"- **replica lost**: `{rec.get('replica')}` "
                f"({rec.get('reason')}) — the fleet verdict is "
                "degraded, not silently averaged over the survivors")
        legs = fl.get("legs") or {}
        if legs:
            lines += ["", "| fleet leg | value | bar | alerting |",
                      "|---|---|---|---|"]
            for name, leg in sorted(legs.items()):
                lines.append(
                    f"| `{name}` | {_fmt(leg.get('value_fast'))} "
                    f"| {_fmt(leg.get('bar'))} "
                    f"| {'YES' if leg.get('alerting') else 'no'} |")
            lines.append("")
        fal = fl.get("alerts")
        if fal:
            lines.append(
                f"- fleet alerts: {_fmt(fal.get('alerts'), '.0f', '0')} "
                f"fired, {_fmt(fal.get('resolved'), '.0f', '0')} "
                f"resolved, {_fmt(fal.get('flaps'), '.0f', '0')} "
                "flap(s)")
            for rec in fal.get("unresolved") or []:
                lines.append(
                    f"- **UNRESOLVED at exit**: fleet `{rec.get('leg')}` "
                    f"burning at {_fmt(rec.get('value'))} vs bar "
                    f"{_fmt(rec.get('bar'))}")
        skew = fl.get("skew") or {}
        if skew.get("skewed"):
            lines.append(
                f"- **version/flag SKEW**: {skew.get('stacks')} "
                "distinct compiler stacks across live replicas")
        if fl.get("divergence"):
            lines.append(
                "- **warm-fingerprint divergence**: "
                + ", ".join(f"`{s}`" for s in fl["divergence"]))
        lines.append("")
    cap = rep.get("capacity")
    if cap:
        lines += ["## Capacity & goodput (obs.capacity)", ""]
        cov = cap.get("coverage") or {}
        wm = cap.get("watermarks") or {}
        lines.append(
            f"- {_fmt(wm.get('samples'), '.0f', '0')} watermark "
            f"sample(s) over {_fmt(cov.get('leases'), '.0f')} "
            f"lease(s) ("
            + ("complete coverage" if cov.get("complete") else
               ("predicted-only — stat-less backend"
                if cov.get("predicted_only") else "PARTIAL coverage"))
            + ")")
        rec = cap.get("reconciliation")
        if rec:
            lines.append(
                f"- reconciliation: predicted "
                f"{_fmt(rec.get('predicted_bytes'), ',.0f')} B vs peak "
                f"{_fmt(rec.get('peak_bytes_in_use'), ',.0f')} B in use "
                f"(rel err {_fmt(rec.get('rel_err'), '.1%')})")
        fps = cap.get("footprints") or []
        if fps:
            lines += ["", "| program | fingerprint | predicted bytes "
                      "| source |", "|---|---|---|---|"]
            for row in fps:
                lines.append(
                    f"| `{row.get('label')}` "
                    f"| `{row.get('fingerprint') or '—'}` "
                    f"| {_fmt(row.get('predicted_bytes'), ',.0f')} "
                    f"| {row.get('source')} |")
            lines.append("")
        rej = cap.get("rejections") or {}
        if rej.get("count"):
            last = rej.get("last") or {}
            lines.append(
                f"- **{rej['count']} CapacityExceeded rejection(s)** "
                f"({', '.join(f'`{s}`' for s in rej.get('signatures') or [])}) "
                f"— last: predicted "
                f"{_fmt(last.get('predicted_bytes'), ',.0f')} B over "
                f"budget {_fmt(last.get('budget_bytes'), ',.0f')} B")
        if cap.get("evictions"):
            lines.append(
                f"- {cap['evictions']} warm-pool eviction(s) under the "
                "queue-behind-eviction policy")
        for path in cap.get("oom_bundles") or []:
            lines.append(f"- **OOM forensic bundle**: `{path}`")
        tenants = cap.get("tenants") or {}
        if tenants:
            lines += ["", "| tenant | requests | chip-s | waste chip-s "
                      "| committed steps | goodput steps/chip-s |",
                      "|---|---|---|---|---|---|"]
            for name in sorted(tenants):
                row = tenants[name]
                lines.append(
                    f"| `{name}` | {_fmt(row.get('requests'), '.0f')} "
                    f"| {_fmt(row.get('chip_s'))} "
                    f"| {_fmt(row.get('waste_chip_s'))} "
                    f"| {_fmt(row.get('committed_steps'), '.0f')} "
                    f"| {_fmt(row.get('goodput'))} |")
            lines.append("")
        if cap.get("goodput") is not None:
            lines.append(
                f"- goodput: **{_fmt(cap.get('goodput'))} committed "
                f"member-steps per chip-second** "
                f"({_fmt(cap.get('committed_steps'), '.0f', '0')} steps "
                f"/ {_fmt(cap.get('total_chip_s'))} chip-s, "
                f"{_fmt(cap.get('waste_chip_s'))} chip-s replay+drain "
                "waste)")
        lines.append("")
    ff = rep.get("fft")
    if ff:
        lines += ["## FFT / spectra", ""]
        st_f = ff.get("ms") or {}
        lines.append(
            f"- scheme `{ff.get('scheme')}`: "
            f"{_fmt(ff.get('calls'), '.0f', '0')} spectra call(s), p50 "
            f"{_fmt(st_f.get('p50_ms'))} ms (p90 "
            f"{_fmt(st_f.get('p90_ms'))}, MAD {_fmt(st_f.get('mad_ms'))})")
        mo = ff.get("model")
        if mo:
            lines.append(
                f"- flops model (5 N log₂ N × {mo.get('nfields')} "
                f"field(s) at {mo.get('grid_shape')}): "
                f"{_fmt(mo.get('model_flops'), '.3e')} flops -> "
                f"{_fmt(mo.get('achieved_gflops'))} GFLOP/s achieved")
            lines.append(
                f"- stage-traffic roofline: "
                f"{_fmt(mo.get('model_bytes'), ',.0f')} B modeled -> "
                f"{_fmt(mo.get('achieved_gbps'))} GB/s of "
                f"{_fmt(mo.get('peak_gbps'))} GB/s peak "
                f"({_fmt(mo.get('fraction_of_peak'), '.1%')} of "
                "roofline)")
        stg = ff.get("stages") or {}
        rows = [(k, v) for k, v in stg.items() if v]
        if rows:
            lines += ["", "| scope | count | total ms | per-device ms |",
                      "|---|---|---|---|"]
            for name, row in rows:
                lines.append(
                    f"| `{name}` | {row.get('count')} "
                    f"| {_fmt(row.get('total_ms'))} "
                    f"| {_fmt(row.get('total_ms_per_device'))} |")
            lines.append("")
        if ff.get("transpose_exposed_ms") is not None:
            lines.append(
                f"- transposes: {_fmt(ff.get('transpose_hidden_ms'))} "
                "ms hidden behind local FFT stages, "
                f"{_fmt(ff.get('transpose_exposed_ms'))} ms exposed "
                "(per-device)")
        lines.append("")
    lines += [
        "## Per-scope breakdown",
        "",
    ]
    scopes = rep.get("scopes") or {}
    if scopes:
        lines += ["| scope | count | total ms | mean ms |",
                  "|---|---|---|---|"]
        for name, row in sorted(
                scopes.items(),
                key=lambda kv: -kv[1].get("total_ms", 0.0)):
            lines.append(
                f"| `{name}` | {row.get('count')} "
                f"| {_fmt(row.get('total_ms'))} "
                f"| {_fmt(row.get('mean_ms'))} |")
        if rep.get("trace_file"):
            lines += ["", f"Trace: `{rep['trace_file']}`"]
    else:
        lines.append("*(no trace captured — per-scope durations "
                     "unavailable; rerun with `--profile`)*")
    lines.append("")
    return "\n".join(lines)
