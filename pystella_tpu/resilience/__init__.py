"""Elastic runtime: retry/backoff, fault injection, and the supervisor.

A long run can lose a device link, a device or its host mid-run. This
package is the recovery layer that treats that as weather, not
catastrophe:

- :mod:`pystella_tpu.resilience.retry` — budget-aware jittered
  exponential backoff with transient-vs-deterministic triage
  (:func:`classify_exception`). Stdlib-only and loadable by file,
  like ``config.py``.
- :mod:`pystella_tpu.resilience.faults` — a deterministic
  fault-injection harness (:class:`FaultInjector`: raise-at-step /
  simulated device loss / NaN corruption / SIGTERM preemption) so
  every recovery path is testable on the CPU mesh in tier-1.
- :mod:`pystella_tpu.resilience.supervisor` — :class:`Supervisor`,
  the driver wrapper: health-checked async durable checkpoints off the
  step path, fault detection, re-dial/re-mesh, restore from the
  durable last-good checkpoint, bounded replay, clean SIGTERM
  preemption, and the incident telemetry
  (``fault_detected``/``recovery_attempt``/``run_resumed``/
  ``run_degraded``) the ledger's ``resilience`` report section and the
  gate's degraded-annotation verdicts are built from.
- :mod:`pystella_tpu.resilience.remesh` — :class:`RemeshPlanner`, the
  supervisor's DEFAULT remesh policy: solve the best feasible degraded
  mesh over the surviving devices (halo/grid/pencil-FFT feasibility,
  ensemble member-axis shrink), reshard the last durable checkpoint
  straight onto it (``Checkpointer.restore(mesh=...)`` — never
  materialized on one device), rebuild the step function through the
  original constructors, and emit the auditable ``remesh_plan``
  record. Device loss becomes a measured, gated degradation instead of
  an abort.

See ``doc/resilience.md`` for the supervisor contract, the fault
taxonomy, replay semantics, and degraded-mesh continuation.
"""

from pystella_tpu.resilience.retry import (
    Retrier, RetryPolicy, classify_exception, retry_call)
from pystella_tpu.resilience.faults import (
    DeviceSubsetFault, Fault, FaultInjector, NaNFault, RaiseFault,
    SigtermFault, device_loss_error)
from pystella_tpu.resilience.remesh import (
    RemeshPlan, RemeshPlanner, feasible_proc_shapes,
    proc_shape_candidates)
from pystella_tpu.resilience.supervisor import RecoveryFailed, Supervisor

__all__ = [
    "Retrier", "RetryPolicy", "classify_exception", "retry_call",
    "DeviceSubsetFault", "Fault", "FaultInjector", "NaNFault",
    "RaiseFault", "SigtermFault", "device_loss_error",
    "RemeshPlan", "RemeshPlanner", "feasible_proc_shapes",
    "proc_shape_candidates",
    "RecoveryFailed", "Supervisor",
]
