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
              "so a long-lived process cannot grow one unbounded log; "
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
              "(obs.memory.flags_fingerprint)")
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
