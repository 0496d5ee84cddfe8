"""Central environment-variable registry.

Every ``PYSTELLA_*`` knob the package or its drivers read
is declared here — name, default, type, and a one-line description —
and read through :func:`getenv` / the typed getters. The source-tier
lint (:mod:`pystella_tpu.lint.source`) enforces the contract: an
``os.environ`` read of a project-prefixed variable anywhere else in
``pystella_tpu/`` fails CI unless the site carries an explicit
``# env-registry: NAME`` pragma naming a variable registered here (the
escape hatch for the stdlib-only modules that must stay loadable BY
FILE in a jax-free supervisor and therefore cannot import this module
through the package).

The table in ``doc/observability.md`` ("Environment variables") is the
human rendering; the lint's ``env-doc`` check fails when a registered
variable is missing from it, so registry and doc cannot drift.

This module is stdlib-only and free of package-relative imports, so a
supervisor that must not import jax can load it by file::

    spec = importlib.util.spec_from_file_location(
        "_cfg", ".../pystella_tpu/config.py")

Reads are LIVE (no import-time caching): a test or a harness can vary
a knob between two builds in one process.
"""

from __future__ import annotations

import dataclasses
import os

__all__ = ["EnvVar", "register", "registered", "getenv", "get_int",
           "get_float", "get_bool", "snapshot"]

_UNSET = object()

@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One registered environment variable."""

    name: str
    default: str | None
    help: str
    kind: str = "str"        # str | int | float | bool | path
    #: where it is consumed: "package" (pystella_tpu/ runtime),
    #: "driver" (example scripts, CLIs), "test" (suite config), or
    #: "external" (not ours — documented because reports fingerprint it)
    scope: str = "package"

#: name -> EnvVar, in registration order
_REGISTRY: dict[str, EnvVar] = {}

def register(name, default=None, help="", kind="str", scope="package"):
    """Register a variable (idempotent for identical declarations);
    returns ``name``. Conflicting re-registration raises — two call
    sites disagreeing about a default is exactly the config drift the
    registry exists to prevent."""
    var = EnvVar(name=str(name), default=default, help=help, kind=kind,
                 scope=scope)
    existing = _REGISTRY.get(var.name)
    if existing is not None and existing != var:
        raise ValueError(
            f"env var {name!r} already registered with a different "
            f"declaration: {existing} vs {var}")
    _REGISTRY[var.name] = var
    return var.name

def registered():
    """The registry as a name -> :class:`EnvVar` dict (copy)."""
    return dict(_REGISTRY)

def getenv(name, default=_UNSET):
    """The raw string value of a REGISTERED variable (the registered
    default — or ``default`` when given — when unset). Reading an
    unregistered name raises ``KeyError``: register it first."""
    var = _REGISTRY.get(name)
    if var is None:
        raise KeyError(
            f"env var {name!r} is not registered in pystella_tpu.config "
            "— declare it there (with a default and description) before "
            "reading it")
    fallback = var.default if default is _UNSET else default
    val = os.environ.get(name)
    return fallback if val is None else val

def get_int(name, default=_UNSET):
    val = getenv(name, default)
    return None if val is None else int(float(val))

def get_float(name, default=_UNSET):
    val = getenv(name, default)
    return None if val is None else float(val)

#: accepted spellings for boolean variables (everything else is False,
#: matching ``parallel.overlap.env_setting``'s tolerant parse)
_TRUE = ("1", "true", "on", "yes")

def get_bool(name, default=_UNSET):
    val = getenv(name, default)
    if val is None:
        return None
    return str(val).strip().lower() in _TRUE

def snapshot():
    """``{name: raw value}`` for every registered variable currently
    set in the process environment (no defaults) — the config side of a
    forensic/environment fingerprint."""
    return {name: os.environ[name] for name in _REGISTRY
            if name in os.environ}

# ---------------------------------------------------------------------------
# the registry: package runtime knobs
# ---------------------------------------------------------------------------

register("PYSTELLA_EVENT_LOG", default=None, kind="path",
         help="JSONL run-event log path picked up by obs.events.get_log() "
              "when no explicit obs.configure() call was made; unset "
              "disables implicit event logging")
register("PYSTELLA_EVENT_ROTATE_MB", default=None, kind="float",
         help="size-triggered event-log rollover in MiB: when the live "
              "JSONL file reaches this size, obs.events.EventLog "
              "renames it to <stem>.<n>.jsonl and opens a fresh file, "
              "so a persistent server cannot grow one unbounded log; "
              "ledger ingestion reads the whole rotated family; unset "
              "disables rotation")
register("PYSTELLA_HALO_OVERLAP", default="auto", kind="bool",
         help="halo-exchange/compute overlap policy for sharded stencils: "
              "1/0 force on/off, unset/'auto' enables exactly when the "
              "mesh shards a lattice axis (parallel.overlap.enabled)")
register("PYSTELLA_WARMSTART_DIR", default=None, kind="path",
         help="default artifact directory for the AOT warm-start "
              "store (obs.warmstart): the export/verify CLI "
              "persists and loads matching artifacts there, skipping "
              "trace+compile for them — "
              "fingerprint mismatches fall back to the jit path and "
              "are recorded as warmstart_mismatch events")
register("PYSTELLA_ENSEMBLE_SIZE", default="8", kind="int",
         help="default member count for ensemble (batched-scenario) "
              "runs: EnsembleDriver uses it when no explicit size is "
              "given")
register("PYSTELLA_ENSEMBLE_AXIS", default="ensemble",
         help="name of the leading device-mesh axis the ensemble tier "
              "packs members along (parallel.decomp.ensemble_mesh); "
              "the lattice axes keep their x/y/z names after it")
register("PYSTELLA_ENSEMBLE_MAX_EVICTIONS", default="16", kind="int",
         help="evict-and-resample budget per ensemble run: beyond this "
              "many member evictions the EnsembleMonitor declares the "
              "whole batch diverged (SimulationDiverged) instead of "
              "resampling forever — a configuration producing that "
              "many bad draws is itself broken")
register("PYSTELLA_ENSEMBLE_RESAMPLE", default="1", kind="bool",
         help="eviction policy: 1 (default) resamples an evicted "
              "member's slot from its scenario's sampler (fresh seed), "
              "0 masks the slot out for the rest of the run instead")
register("PYSTELLA_RESILIENCE_CHECKPOINT_EVERY", default="50", kind="int",
         help="default checkpoint interval in steps for the elastic "
              "Supervisor (resilience.supervisor) — also the bound on "
              "replayed steps after a fault: recovery restores the "
              "durable last-good checkpoint and replays at most one "
              "interval")
register("PYSTELLA_RESILIENCE_MAX_RECOVERIES", default="4", kind="int",
         help="incident budget per supervised run: beyond this many "
              "recovered faults the Supervisor raises RecoveryFailed "
              "instead of replaying forever — an environment producing "
              "that many incidents needs an operator, not a retry loop")
register("PYSTELLA_RESILIENCE_BACKOFF_BASE_S", default="1.0", kind="float",
         help="first recovery-attempt backoff in seconds (jittered "
              "exponential, factor 2) for the Supervisor's per-incident "
              "retry loop (resilience.retry)")
register("PYSTELLA_RESILIENCE_BACKOFF_MAX_S", default="60", kind="float",
         help="recovery-attempt backoff ceiling in seconds")
register("PYSTELLA_RESILIENCE_RETRY_BUDGET_S", default="600",
         kind="float",
         help="wall budget in seconds for ONE incident's recovery "
              "attempts (re-dial + restore retries); exhausting it "
              "raises RecoveryFailed with the last underlying error")
register("PYSTELLA_FAULT_DEVICE_SUBSET", default=None,
         help="arm a DeviceSubsetFault from the environment "
              "(resilience.FaultInjector.from_env, consumed by drivers "
              "that opt in, e.g. the remesh drills): '<step>:<count>' "
              "loses the last <count> devices of the state's device "
              "set entering <step>; unset disables")
register("PYSTELLA_FAULT_DEVICE_SUBSET_PERSIST", default="1", kind="bool",
         help="persistence of the env-armed device-subset fault: 1 "
              "(default) models real hardware — lost devices STAY "
              "lost, and only a re-meshed program that no longer "
              "touches them replays through cleanly; 0 makes it a "
              "one-shot transient like the other fault kinds")
register("PYSTELLA_SERVICE_SLOTS", default="4", kind="int",
         help="batch slots per scenario-service lease "
              "(service.ScenarioService): each scheduler dispatch "
              "leases up to this many shape-compatible requests to one "
              "batched EnsembleStepper program")
register("PYSTELLA_SERVICE_CHUNK", default="2", kind="int",
         help="steps per batched dispatch inside a scenario-service "
              "lease; preemption and checkpointing happen at chunk "
              "boundaries, so this is also the preemption-latency "
              "granularity")
register("PYSTELLA_SERVICE_COLD_POLICY", default="compile",
         help="admission policy for a request whose (model, lattice, "
              "mesh) signature has no warm-pool entry "
              "(service.AdmissionController): 'compile' admits it "
              "queued behind the build+compile of a fresh pool entry "
              "(its time-to-first-step then pays the compile), "
              "'reject' refuses it with a typed ColdSignature verdict")
register("PYSTELLA_SERVICE_QUOTA", default="64", kind="int",
         help="per-tenant admission quota of the scenario service's "
              "fair-share scheduler: submissions beyond this many "
              "queued requests for one tenant are rejected "
              "(service_reject event, reason 'quota') instead of "
              "letting one tenant starve the others")
register("PYSTELLA_SERVICE_PREEMPT", default="1", kind="bool",
         help="priority preemption in the scenario service: 1 "
              "(default) lets a pending request of a strictly higher "
              "priority class preempt a running lease at the next "
              "chunk boundary (drain -> durable checkpoint -> "
              "requeue, no work lost); 0 runs every lease to "
              "completion")
register("PYSTELLA_LIVE_PORT", default="0", kind="int",
         help="TCP port of the opt-in in-process live telemetry "
              "endpoint (obs.live: /metrics Prometheus exposition, "
              "/healthz liveness+readiness, /slo burn-rate state), "
              "bound to 127.0.0.1 on a daemon thread around "
              "ScenarioService.serve(); 0 (default) or unset disables "
              "the live plane entirely — emit paths and event logs "
              "are then byte-identical to a build without it")
register("PYSTELLA_SLO_FAST_WINDOW_S", default="60", kind="float",
         help="fast window in seconds of the live SLO burn-rate "
              "monitor (obs.slo.SLOMonitor): an alert fires only when "
              "the windowed metric breaches its bar over BOTH the "
              "fast window (it is still happening) and the slow "
              "window (it is sustained), and resolves when the fast "
              "window recovers or empties")
register("PYSTELLA_SLO_SLOW_WINDOW_S", default="300", kind="float",
         help="slow window in seconds of the live SLO burn-rate "
              "monitor — the sustained-breach half of the fast/slow "
              "multi-window alert rule")
register("PYSTELLA_SLO_MIN_SAMPLES", default="1", kind="int",
         help="minimum samples the fast window must hold before a "
              "percentile/rate SLO leg may fire (count-kind legs are "
              "exempt — their value IS the sample count); raise it on "
              "a busy service so a single outlier dispatch cannot "
              "page")
register("PYSTELLA_PERF", default="1", kind="bool",
         help="continuous-performance plane master switch (obs.perf): "
              "1 (default) lets StepTimer and the scenario service's "
              "dispatch loop feed the process-default step-time "
              "digest + CUSUM change-point detector; 0 disables the "
              "plane entirely — observe() is a no-op and the default "
              "monitor is never constructed")
register("PYSTELLA_PERF_WINDOW", default="64", kind="int",
         help="healthy-baseline reference window (samples) of the "
              "continuous-performance CUSUM detector "
              "(obs.perf.CusumDetector): location/scale are the "
              "median/MAD over the last this-many healthy samples "
              "per program signature; the window freezes while an "
              "anomaly is open so the baseline cannot absorb the "
              "regression it is reporting")
register("PYSTELLA_PERF_MIN_SAMPLES", default="16", kind="int",
         help="samples the reference window must hold before the "
              "continuous-performance detector may fire — warmup and "
              "short runs stay quiet")
register("PYSTELLA_PERF_CUSUM_K", default="0.5", kind="float",
         help="CUSUM slack in sigmas (obs.perf): a sample only "
              "accumulates drift when it exceeds baseline + k*sigma; "
              "also the recovery band — perf_recovered needs the "
              "recent samples back below that bar")
register("PYSTELLA_PERF_CUSUM_H", default="8.0", kind="float",
         help="CUSUM fire threshold in accumulated clipped sigmas "
              "(obs.perf): per-sample increments are clipped at 4 "
              "sigma, so with the default 8.0 a single spike cannot "
              "fire — only >= 2 consecutive far-outliers (or a longer "
              "run of modest ones) accumulate past it")
register("PYSTELLA_PERF_RECOVER_N", default="5", kind="int",
         help="consecutive in-band samples (below baseline + k*sigma) "
              "after which an open perf anomaly emits perf_recovered "
              "and the CUSUM accumulator resets")
register("PYSTELLA_PERF_CAPTURE_DIR", default=None, kind="path",
         help="artifact root of the anomaly-triggered flight recorder "
              "(obs.perf.FlightRecorder): when set, a fired "
              "perf_anomaly starts a rate-limited jax.profiler "
              "capture of the next PYSTELLA_PERF_CAPTURE_STEPS steps "
              "and writes the Perfetto trace under this directory "
              "(perf_capture event carries the path); unset (default) "
              "disables automatic capture — anomalies still fire, "
              "nothing is profiled")
register("PYSTELLA_PERF_CAPTURE_STEPS", default="8", kind="int",
         help="steps the anomaly-triggered flight recorder keeps the "
              "profiler running before closing the capture and "
              "emitting perf_capture")
register("PYSTELLA_PERF_CAPTURE_COOLDOWN_S", default="600", kind="float",
         help="minimum seconds between anomaly-triggered profiler "
              "capture starts — the rate limit: an anomaly storm "
              "produces at most one trace per cooldown plus a "
              "suppression count, not a disk full of traces")
register("PYSTELLA_FLEET_DIR", default=None, kind="path",
         help="shared replica-registry directory of the fleet "
              "observability plane (service.registry / obs.fleet): "
              "when set, ScenarioService.serve() announces a "
              "heartbeated JSON record there (replica id, live URL, "
              "stack fingerprint, warm-pool fingerprints, queue "
              "depth) and withdraws it on exit; unset (default) "
              "disables the fleet plane entirely")
register("PYSTELLA_FLEET_HEARTBEAT_S", default="2.0", kind="float",
         help="cadence in seconds at which a fleet replica rewrites "
              "its registry record (service.registry.ReplicaRegistry); "
              "each beat refreshes the dynamic fields (queue depth, "
              "serving state, warm fingerprints); <= 0 announces once "
              "and never beats (tests)")
register("PYSTELLA_FLEET_EXPIRE_S", default="10", kind="float",
         help="heartbeat age in seconds past which registry readers "
              "(obs.fleet.FleetAggregator, service status --fleet) "
              "treat a replica record as stale/dead — a crashed "
              "replica cannot tombstone itself, so expiry is how the "
              "fleet notices; keep it several heartbeats wide")
register("PYSTELLA_FLEET_SCRAPE_TIMEOUT_S", default="2.0", kind="float",
         help="per-endpoint HTTP timeout in seconds for one fleet "
              "scrape of a replica's /metrics, /slo, /healthz "
              "(obs.fleet.FleetAggregator); a replica slower than "
              "this counts as a scrape failure, not a hang of the "
              "whole aggregation pass")
register("PYSTELLA_TRACE_SERVICE", default="1", kind="bool",
         help="request-scoped distributed tracing in the scenario "
              "service: 1 (default) allocates a trace id per "
              "ScenarioRequest and threads trace/span/parent fields "
              "(event schema v2) through submission, dispatch, the "
              "supervised lease loop, and retire, so obs.spans can "
              "assemble per-request critical-path latency; 0 emits "
              "v1-shaped events with no trace context")
register("PYSTELLA_TRACE_EXPORT", default=None, kind="path",
         help="default Perfetto output path for the assembled service "
              "span timeline: `python -m pystella_tpu.obs.spans` "
              "writes the request-timeline trace file there when no "
              "explicit --perfetto is given; unset writes none")
register("PYSTELLA_FFT_SCHEME", default="auto",
         help="distributed-FFT scheme the planner (fourier.plan."
              "make_dft) and the spectra/projector/Poisson consumers "
              "select: 'auto' (the shard_map pencil tier whenever the "
              "grid x/y axes divide the total device count, else the "
              "DFT reshard/partial/replicate chain), 'pencil' (force "
              "the shard_map tier; infeasible shapes raise), or 'dft' "
              "(force the legacy declarative-reshard tiering)")
register("PYSTELLA_FFT_REPLICATE_LIMIT", default="1073741824",
         kind="float",
         help="replicate-fallback size limit in bytes for transforms "
              "no distributed scheme serves: above it DFT construction "
              "raises instead of silently replicating the k-space "
              "array on every device (override per-instance with "
              "replicate_limit=/allow_replicate=)")
register("PYSTELLA_CAPACITY_HEADROOM", default="0.9", kind="float",
         help="memory-aware admission budget as a fraction of device "
              "HBM capacity (obs.capacity.CapacityMonitor): resident "
              "warm-pool programs + the candidate lease's predicted "
              "footprint must fit capacity x this, else the request is "
              "rejected with a typed CapacityExceeded verdict")
register("PYSTELLA_CAPACITY_POLICY", default="reject",
         help="what memory-aware admission does on overcommit: "
              "'reject' (default) refuses the request outright "
              "(capacity_reject event), 'evict' first drops idle "
              "warm-pool entries not backing queued work "
              "(capacity_evict events) and re-checks — "
              "queue-behind-eviction")
register("PYSTELLA_CAPACITY_BYTES", default=None, kind="int",
         help="device-capacity override in bytes for the admission "
              "budget; unset uses the allocator's bytes_limit from "
              "device.memory_stats(), and where neither exists (CPU) "
              "the capacity check skips honestly (decision reason "
              "'no-capacity-limit') instead of guessing")
register("PYSTELLA_CAPACITY_DIR",
         help="persistence directory for predicted HBM footprints "
              "(obs.capacity.FootprintLedger, *.footprint.json beside "
              "the warm-start artifacts); unset falls back to "
              "PYSTELLA_WARMSTART_DIR, and with neither set the "
              "ledger stays in-memory")

# ---------------------------------------------------------------------------
# driver knobs (examples, the gate's CLI)
# ---------------------------------------------------------------------------

register("PYSTELLA_GATE_COMM_EXCESS_PCT", default="25", kind="float",
         scope="driver",
         help="gate threshold for the modeled-vs-measured comm check: "
              "measured collective traffic exceeding the dataflow "
              "lint tier's static model by more than this percentage "
              "fails the gate (the model is an upper bound — measured "
              "above it means unattributed traffic)")

# ---------------------------------------------------------------------------
# external variables we read or set (not project-prefixed; documented
# because perf-report fingerprints and the gate's flag-mismatch warning
# depend on them)
# ---------------------------------------------------------------------------

register("XLA_FLAGS", default=None, scope="external",
         help="XLA compiler/runtime flags; scheduler-relevant entries "
              "are fingerprinted into perf reports "
              "(obs.ledger.xla_flag_fingerprint)")
register("LIBTPU_INIT_ARGS", default=None, scope="external",
         help="libtpu init flags; the package sets none, the "
              "scheduler-relevant ones present are fingerprinted into "
              "perf reports")
register("JAX_PLATFORMS", default=None, scope="external",
         help="jax backend selection; the test suite and the lint CLI "
              "default it to 'cpu', measuring scripts take what jax "
              "finds and refuse anything but a TPU")
register("JAX_COMPILATION_CACHE_DIR", default=None, kind="path",
         scope="external",
         help="jax's persistent compilation-cache directory; when set, "
              "obs.memory.ensure_compilation_cache uses it and sets no "
              "directory in code, when unset the cache is "
              "bench_results/xla_cache in the checkout")
register("JAX_ENABLE_X64", default=None, scope="external",
         help="jax 64-bit mode; the test suite enables it for "
              "reference-parity f64 tolerances")
