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
The fingerprint is :func:`pystella_tpu.obs.memory.
environment_fingerprint`, whose versions and flags are the ones the
program fingerprints hash.
"""

from __future__ import annotations

import json
import os
import time

from pystella_tpu.obs import events as _events
from pystella_tpu.obs.memory import environment_fingerprint

__all__ = ["REPORT_SCHEMA_VERSION", "PerfLedger", "mad", "percentile",
           "step_stats"]

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
        # include_rotated: a size-rotated long-lived log (rotate_bytes=)
        # ingests as one continuous stream — the latest-run scoping
        # below then applies across the family
        all_events = _events.read_events(events_path,
                                         include_rotated=True)
        starts = [i for i, ev in enumerate(all_events)
                  if ev.get("kind") == "run_start"]
        if starts:
            all_events = all_events[starts[-1]:]
        for ev in all_events:
            kind = ev.get("kind")
            data = ev.get("data") or {}
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
