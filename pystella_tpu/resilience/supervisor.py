"""The elastic run supervisor: survive faults, resume from last good.

``Supervisor`` wraps any per-step driver loop (a
:class:`~pystella_tpu.Stepper`, a fused chunk dispatch, an ensemble
driver tick — anything shaped ``step_fn(state, step) -> state``) with
the recovery machinery the ROADMAP's pod-scale item calls for:

- **health-checked periodic checkpoints, async and durable** — every
  ``checkpoint_every`` steps the monitor is flushed and synchronously
  checked (a diverged state is never checkpointed), then the
  :class:`~pystella_tpu.Checkpointer` *schedules* the write and the
  loop moves on; the durability barrier for each save runs one interval
  later, off the step path, and only then does ``last_good`` advance
  (:meth:`Checkpointer.finalize`).
- **fault detection and triage** — a sentinel trip
  (:class:`~pystella_tpu.SimulationDiverged`) is a *numerics* fault;
  any other exception is triaged by
  :func:`~pystella_tpu.resilience.retry.classify_exception`:
  transient (``UNAVAILABLE``, transport drops, device loss) enters
  recovery, deterministic re-raises immediately — replaying a program
  bug burns the budget to fail identically.
- **recovery** — under a jittered-backoff
  :class:`~pystella_tpu.resilience.retry.Retrier`: re-dial the
  multi-controller runtime (:func:`pystella_tpu.parallel.multihost.
  reinit` — no longer a one-way latch), re-mesh to the surviving
  devices — by default through the
  :class:`~pystella_tpu.resilience.remesh.RemeshPlanner` given as
  ``planner=`` (solve a feasible degraded mesh, rebuild the step
  function through the original constructors, emit ``remesh_plan`` +
  ``run_degraded``), with the legacy ``remesh`` hook as an override —
  finalize pending checkpoint writes, restore from the durable
  last-good checkpoint (walking back past a torn newest one; a
  re-meshed run restores STRAIGHT onto the degraded mesh through
  :meth:`Checkpointer.restore`'s ``mesh=`` template path, never
  materializing the state on one device), and **replay at most one
  checkpoint interval** of steps. A swap also refreshes the monitor's
  decomposition-derived state (:meth:`HealthMonitor.reset`) so
  sentinel field specs and checkpoint sharding track the new mesh.
- **preemption** — SIGTERM sets a flag; at the next step boundary the
  supervisor drains the monitor, takes a synchronous durable
  checkpoint, emits ``run_preempted``, and returns cleanly so a
  restarted process resumes exactly there (``run(resume="auto")``).

Every incident is telemetry: ``fault_detected`` -> ``recovery_attempt``
(xN) -> ``run_resumed`` (with measured MTTR and replayed-step count),
plus ``run_degraded`` / ``run_preempted`` / ``supervisor_done``. The
perf ledger folds these into the report's ``resilience`` section and
the gate annotates — rather than refuses — evidence measured across a
recorded incident (``doc/resilience.md``).

Deterministic testing: pass a
:class:`~pystella_tpu.resilience.faults.FaultInjector` and every one of
these paths runs on the 8-device CPU mesh in tier-1.
"""

from __future__ import annotations

import signal
import time

from pystella_tpu import config as _config
from pystella_tpu.obs import events as _events
from pystella_tpu.obs.scope import trace_scope
from pystella_tpu.obs.sentinel import SimulationDiverged
from pystella_tpu.resilience.retry import (
    Retrier, RetryPolicy, classify_exception)

__all__ = ["Supervisor", "RecoveryFailed"]


class RecoveryFailed(RuntimeError):
    """Raised when recovery itself gives up: the per-incident retry
    budget ran out, the incident budget (``max_recoveries``) was
    exceeded, or the same fault recurred at the same step after a
    restore (a deterministic failure wearing a transient's clothes).
    ``last_error`` carries the underlying failure."""

    def __init__(self, message, last_error=None):
        super().__init__(message)
        self.last_error = last_error


def _default_retry_policy():
    return RetryPolicy(
        base_s=_config.get_float("PYSTELLA_RESILIENCE_BACKOFF_BASE_S"),
        factor=2.0,
        max_s=_config.get_float("PYSTELLA_RESILIENCE_BACKOFF_MAX_S"),
        jitter=0.1,
        budget_s=_config.get_float("PYSTELLA_RESILIENCE_RETRY_BUDGET_S"))


class Supervisor:
    """Drive ``step_fn`` for ``nsteps`` steps under fault supervision.

    :arg step_fn: ``step_fn(state, step) -> state`` — one simulation
        step; ``step`` is the 0-based index of the step being taken.
        Donation is the caller's business, but note the supervisor may
        re-dispatch from a restored state after a fault.
    :arg checkpointer: a :class:`~pystella_tpu.Checkpointer`; the
        supervisor drives its schedule/finalize split and reads its
        durable ``last_good``.
    :arg nsteps: total steps the run is complete at.
    :arg monitor: optional :class:`~pystella_tpu.HealthMonitor` (or any
        object with ``observe``/``poll``/``flush``/``discard`` and
        ``check_now``/``check_sync``): observed every step, flushed +
        synchronously checked before every checkpoint save.
    :arg checkpoint_every: checkpoint interval in steps (default: the
        ``PYSTELLA_RESILIENCE_CHECKPOINT_EVERY`` registry value). The
        replay bound after a fault is exactly this interval.
    :arg restore_fn: optional per-leaf callable applied to restored
        host arrays (e.g. ``decomp.shard``) — the placement half of a
        resume.
    :arg faults: optional :class:`~pystella_tpu.resilience.faults.
        FaultInjector`, consulted entering every step (tests, drills).
    :arg retry: :class:`~pystella_tpu.resilience.retry.RetryPolicy`
        for recovery attempts within one incident (default: the
        ``PYSTELLA_RESILIENCE_*`` registry values).
    :arg max_recoveries: incident budget for the whole run (default:
        ``PYSTELLA_RESILIENCE_MAX_RECOVERIES``); one more fault raises
        :class:`RecoveryFailed`.
    :arg remesh: optional hook ``remesh(error, attempt) -> None | dict``
        called during device-loss recovery; returning
        ``{"step_fn": ..., "restore_fn": ..., "decomp": ...,
        "monitor": ..., "note": ...}`` (any subset) swaps in a
        re-meshed program for the surviving devices and emits
        ``run_degraded``. When set it OVERRIDES ``planner``.
    :arg planner: optional
        :class:`~pystella_tpu.resilience.remesh.RemeshPlanner` — the
        DEFAULT remesh policy: on device-loss recovery (and no
        ``remesh`` hook) it resolves the survivors, solves the best
        feasible degraded mesh (emitting ``remesh_plan``), rebuilds
        the step function through the original constructors, and the
        restore lands straight on the new mesh.
    :arg redial: re-initialize the multi-controller runtime during
        device-loss recovery (default ``True``; a single-process run's
        re-dial is a no-op). A CALLABLE replaces the default
        ``multihost.reinit()`` — e.g. a multi-process drill re-dialing
        as a smaller cluster with explicit coordinator arguments.
    :arg metadata_fn: optional ``metadata_fn(step, state) -> dict``
        merged into every checkpoint's metadata.
    :arg keep_initial: keep a host-side copy of the initial state so a
        fault *before the first checkpoint* can restart from step 0
        instead of failing the run (default ``True``; skipped
        automatically for non-fully-addressable multi-host arrays —
        costs one host copy of the state).
    :arg install_sigterm: install the SIGTERM preemption handler for
        the duration of :meth:`run` (main thread only; elsewhere the
        flag can be set manually via :meth:`request_preemption`).
    :arg label: tag carried on every emitted event.
    """

    def __init__(self, step_fn, checkpointer, nsteps, *, monitor=None,
                 checkpoint_every=None, restore_fn=None, faults=None,
                 retry=None, max_recoveries=None, remesh=None,
                 planner=None, redial=True, metadata_fn=None,
                 keep_initial=True, install_sigterm=True, label=""):
        self.step_fn = step_fn
        self.checkpointer = checkpointer
        self.nsteps = int(nsteps)
        self.monitor = monitor
        self.checkpoint_every = int(
            checkpoint_every if checkpoint_every is not None
            else _config.get_int("PYSTELLA_RESILIENCE_CHECKPOINT_EVERY"))
        if self.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        self.restore_fn = restore_fn
        self.faults = faults
        self.retry_policy = retry or _default_retry_policy()
        self.max_recoveries = int(
            max_recoveries if max_recoveries is not None
            else _config.get_int("PYSTELLA_RESILIENCE_MAX_RECOVERIES"))
        self.remesh = remesh
        self.planner = planner
        #: set by a re-mesh swap: restores then land straight on this
        #: decomposition's mesh (the Checkpointer mesh= template path)
        self.restore_decomp = None
        self.redial = redial if callable(redial) else bool(redial)
        self.metadata_fn = metadata_fn
        self.keep_initial = bool(keep_initial)
        self.install_sigterm = bool(install_sigterm)
        self.label = label
        #: incident records of the last :meth:`run` (newest last)
        self.incidents = []
        self._preempt_signum = None
        self._initial = None            # (step, host-copied state)
        self._last_incident_key = None

    # -- preemption --------------------------------------------------------

    def request_preemption(self, signum=signal.SIGTERM):
        """Flag the run for a drain + durable checkpoint + clean return
        at the next step boundary (what the SIGTERM handler does)."""
        self._preempt_signum = int(signum)

    def _handler(self, signum, frame):
        self.request_preemption(signum)

    # -- the run -----------------------------------------------------------

    def run(self, state=None, start_step=0, resume="auto"):
        """Drive the run to completion (or a clean preemption point).

        :arg state: the initial state pytree; may be ``None`` when
            resuming from an existing checkpoint.
        :arg start_step: steps already completed in ``state``.
        :arg resume: ``"auto"`` restores from the newest durable
            checkpoint when one exists (walking back past a corrupt
            one) and falls back to ``state`` otherwise; ``True``
            requires a checkpoint; ``False`` ignores checkpoints.

        Returns a report dict: ``state`` (final), ``completed``,
        ``preempted``, ``final_step``, ``steps_run``,
        ``steps_replayed``, ``incidents``, ``wall_s``.
        """
        t_run0 = time.monotonic()
        self.incidents = []
        self._last_incident_key = None
        self._preempt_signum = None

        step = int(start_step)
        if resume and self.checkpointer.all_steps():
            step, state, _meta = self._restore()
            _events.emit("run_resumed", step=step, label=self.label,
                         source="restart", incident=False)
        elif resume is True:
            raise FileNotFoundError(
                f"resume=True but no checkpoints under "
                f"{self.checkpointer.directory}")
        if state is None:
            raise ValueError("no initial state and nothing to resume")
        self._snapshot_initial(step, state)

        _events.emit("supervisor_start", step=step, label=self.label,
                     nsteps=self.nsteps,
                     checkpoint_every=self.checkpoint_every,
                     max_recoveries=self.max_recoveries)

        prev_handler = None
        handler_installed = False
        if self.install_sigterm:
            try:
                prev_handler = signal.signal(signal.SIGTERM, self._handler)
                handler_installed = True
            except ValueError:
                pass  # not the main thread: preemption flag only
        steps_run = 0
        try:
            while step < self.nsteps:
                try:
                    # the preemption drain runs INSIDE fault triage: its
                    # pre-save health check can legitimately trip (NaN
                    # entered within the sentinel's maturity lag before
                    # SIGTERM arrived) — recovery then restores a clean
                    # state and the still-set flag drains THAT instead
                    # of durably checkpointing a diverged state
                    if self._preempt_signum is not None:
                        return self._preempt(step, state, steps_run,
                                             t_run0)
                    if self.faults is not None:
                        state = self.faults.apply(step, state)
                    with trace_scope("supervised_step"):
                        state = self.step_fn(state, step)
                    step += 1
                    steps_run += 1
                    if self.monitor is not None:
                        self.monitor.observe(step, state)
                        self.monitor.poll()
                    if step % self.checkpoint_every == 0 \
                            or step == self.nsteps:
                        self._checkpoint(step, state)
                except SimulationDiverged as e:
                    step, state = self._recover("numerics", e, step, state)
                except Exception as e:  # noqa: BLE001 — triaged below
                    if classify_exception(e) != "transient":
                        _events.emit(
                            "fault_detected", step=step, label=self.label,
                            fault_kind="deterministic", action="reraise",
                            error=f"{type(e).__name__}: {e}")
                        raise
                    step, state = self._recover("device_loss", e, step,
                                                state)
            if self.monitor is not None:
                self.monitor.flush()
            self.checkpointer.finalize()
            report = self._report(state, step, steps_run, t_run0,
                                  completed=True, preempted=False)
            _events.emit("supervisor_done", step=step, label=self.label,
                         **{k: v for k, v in report.items()
                            if k not in ("state", "label",
                                         "incident_records")})
            return report
        finally:
            if handler_installed:
                signal.signal(signal.SIGTERM, prev_handler)

    # -- pieces ------------------------------------------------------------

    def _snapshot_initial(self, step, state):
        if not self.keep_initial:
            return
        import jax
        import numpy as np
        leaves = jax.tree_util.tree_leaves(state)
        if any(getattr(x, "is_fully_addressable", True) is False
               for x in leaves):
            self.keep_initial = False  # multi-host: no host copy exists
            return
        self._initial = (int(step),
                         jax.tree_util.tree_map(np.array, state))

    def _metadata(self, step, state):
        meta = {"step": int(step), "label": self.label}
        if self.metadata_fn is not None:
            meta.update(self.metadata_fn(step, state) or {})
        return meta

    def _checkpoint(self, step, state):
        # a diverged state must never be checkpointed: drain the async
        # queue (trips report their true step) and check the state
        # about to be saved synchronously
        if self.monitor is not None:
            self.monitor.flush()
            check = getattr(self.monitor, "check_now", None)
            if check is not None:
                check(state, step=step)
            else:
                self.monitor.check_sync(step, state)
        # durability barrier for the PREVIOUS interval's save — it has
        # had a whole interval to land, so this is (nearly) free and
        # keeps the write itself off the step path
        self.checkpointer.finalize()
        # once something durable exists on disk, the initial-state
        # snapshot can never be needed again: release the host copy (a
        # production state is gigabytes)
        if self._initial is not None \
                and self.checkpointer.last_good is not None:
            self._initial = None
        self.checkpointer.save(step, state,
                               metadata=self._metadata(step, state))
        if step == self.nsteps:
            self.checkpointer.finalize()

    def _restore(self):
        if self.restore_decomp is not None:
            # a re-meshed run: restore straight onto the degraded mesh
            # (orbax reads each device's shard directly — the state is
            # never materialized on one device)
            step, state, meta = self.checkpointer.restore(
                mesh=self.restore_decomp)
        else:
            step, state, meta = self.checkpointer.restore(
                sharding_fn=self.restore_fn)
        return int(step), state, meta

    def _restore_or_restart(self):
        """Restore from the newest durable checkpoint, or — when no
        checkpoint exists yet, or when every on-disk checkpoint turns
        out to be torn (listed but unrestorable: a crash mid-first-
        write) — restart from the initial-state snapshot. A fault
        before the first DURABLE checkpoint must not be fatal when the
        run can simply start over; the snapshot is only released once
        something durable exists, so this fallback and the release
        policy cover each other exactly."""
        if self.checkpointer.all_steps():
            try:
                return self._restore()
            except Exception:
                if self._initial is None:
                    raise
                _events.emit("checkpoint_fallback", step=None,
                             label=self.label,
                             error="every on-disk checkpoint failed to "
                                   "restore; restarting from the "
                                   "initial-state snapshot")
        if self._initial is not None:
            import jax
            step0, host_state = self._initial
            place = self.restore_fn or (lambda x: x)
            return (step0,
                    jax.tree_util.tree_map(place, host_state), None)
        raise FileNotFoundError(
            "no checkpoint to restore and no initial-state snapshot "
            "(keep_initial=False)")

    def _redial(self):
        if callable(self.redial):
            self.redial()
            return
        from pystella_tpu.parallel import multihost
        multihost.reinit()

    def _apply_swap(self, swap, at_step):
        """Install a re-meshed program (from the ``remesh`` hook or the
        planner): swap the step function, point restores at the new
        mesh, and refresh the monitor's decomposition-derived state —
        a swapped mesh must not leave the monitor checking vectors
        (or the checkpointer placing shards) against the old
        sharding."""
        self.step_fn = swap.get("step_fn", self.step_fn)
        self.restore_fn = swap.get("restore_fn", self.restore_fn)
        if swap.get("decomp") is not None:
            self.restore_decomp = swap["decomp"]
        if "monitor" in swap:
            self.monitor = swap["monitor"]
        elif self.monitor is not None:
            reset = getattr(self.monitor, "reset", None)
            if reset is not None:
                reset()
        _events.emit("run_degraded", step=at_step, label=self.label,
                     note=swap.get("note", "re-meshed to surviving "
                                   "devices"))

    def _finalize_bounded(self, timeout_s):
        """The durability barrier, with a wall bound — ONLY for the
        recovery path. ``Checkpointer.finalize()`` blocks in orbax's
        ``wait_until_finished``; a device dying mid-async-write can
        leave that wait stuck forever, and a blocked call never raises,
        so the per-incident retry budget would never fire. Run it in a
        daemon thread and convert a timeout into a ``TimeoutError``
        (classified transient -> counted against the retry budget). On
        timeout the thread stays blocked in orbax — leaked by design;
        the process is mid-disaster-recovery and about to give up or
        re-dial anyway."""
        import threading
        box = {}
        done = threading.Event()

        def _run():
            try:
                box["ok"] = self.checkpointer.finalize()
            except BaseException as e:  # noqa: B036 — rethrown below
                box["error"] = e
            finally:
                done.set()

        th = threading.Thread(target=_run, daemon=True,
                              name="ckpt-finalize")
        th.start()
        if not done.wait(timeout_s):
            raise TimeoutError(
                f"checkpoint durability barrier timed out after "
                f"{timeout_s:.0f}s (async write wedged mid-recovery)")
        if "error" in box:
            raise box["error"]
        return box.get("ok")

    def _recover(self, kind, error, at_step, state):
        """One incident: triage happened, now re-dial / re-mesh /
        restore / bound the replay. Returns ``(step, state)`` to resume
        the loop from; raises :class:`RecoveryFailed` when recovery
        itself gives up."""
        t0 = time.monotonic()
        err_str = f"{type(error).__name__}: {error}"
        trip_step = getattr(error, "step", at_step)
        _events.emit("fault_detected", step=at_step, label=self.label,
                     fault_kind=kind, error=err_str, trip_step=trip_step)

        if len(self.incidents) >= self.max_recoveries:
            _events.emit("recovery_failed", step=at_step, label=self.label,
                         fault_kind=kind, reason="incident budget exhausted",
                         incidents=len(self.incidents))
            raise RecoveryFailed(
                f"incident budget exhausted ({len(self.incidents)} "
                f"recoveries already this run); latest: {err_str}",
                last_error=error) from error
        key = (kind, int(trip_step))
        if key == self._last_incident_key:
            # the same fault at the same step straight after a restore:
            # deterministic recurrence, replaying again cannot help
            _events.emit("recovery_failed", step=at_step, label=self.label,
                         fault_kind=kind, reason="fault recurred at the same "
                         "step after restore", trip_step=trip_step)
            raise RecoveryFailed(
                f"{kind} fault recurred at step {trip_step} after a "
                f"restore — deterministic, not retrying: {err_str}",
                last_error=error) from error

        if self.monitor is not None:
            # pending health vectors describe the corrupted trajectory
            self.monitor.discard()

        retrier = Retrier(self.retry_policy, emit=_events.emit,
                          label=self.label or "supervisor")
        attempt = 0
        while True:
            attempt += 1
            _events.emit("recovery_attempt", step=at_step,
                         label=self.label, fault_kind=kind, attempt=attempt)
            try:
                if kind == "device_loss":
                    if self.redial:
                        self._redial()
                    swap = None
                    if self.remesh is not None:       # hook overrides
                        swap = self.remesh(error, attempt)
                    elif self.planner is not None:    # default policy
                        swap = self.planner(error, attempt,
                                            faults=self.faults,
                                            step=at_step)
                    if swap:
                        self._apply_swap(swap, at_step)
                # scheduled-but-unconfirmed writes must settle before a
                # read; a torn one is walked back over by restore().
                # Bounded: a barrier wedged by the very device loss
                # being recovered from must count against the retry
                # budget, not hang recovery forever
                budget = self.retry_policy.budget_s or 600.0
                self._finalize_bounded(max(10.0, budget / 4.0))
                step, state, _meta = self._restore_or_restart()
                break
            except Exception as e2:  # noqa: BLE001 — budgeted below
                decision, reason = retrier.note_failure(
                    kind=classify_exception(e2), error=e2)
                if decision == "stop":
                    _events.emit("recovery_failed", step=at_step,
                                 label=self.label, fault_kind=kind,
                                 reason=reason, attempt=attempt,
                                 error=f"{type(e2).__name__}: {e2}")
                    raise RecoveryFailed(
                        f"recovery gave up after {attempt} attempt(s) "
                        f"({reason}); last error: "
                        f"{type(e2).__name__}: {e2}",
                        last_error=e2) from e2
                retrier.wait()

        mttr_s = time.monotonic() - t0
        steps_replayed = max(0, at_step - step)
        incident = {
            "kind": kind, "step": int(trip_step),
            "detected_at_step": int(at_step),
            "restored_step": int(step),
            "steps_replayed": int(steps_replayed),
            "attempts": int(attempt),
            "mttr_s": float(mttr_s),
            "error": err_str,
        }
        self.incidents.append(incident)
        self._last_incident_key = key
        _events.emit("run_resumed", step=step, label=self.label,
                     source="recovery", incident=True, fault_kind=kind,
                     from_step=at_step, mttr_s=round(mttr_s, 4),
                     steps_replayed=steps_replayed, attempts=attempt)
        return step, state

    def _preempt(self, step, state, steps_run, t_run0):
        """SIGTERM drain: flush + check, durable checkpoint, clean
        return — the restarted process resumes exactly here. Runs
        inside the run loop's fault triage: a trip here (corrupt state
        caught by the drain's own health check) recovers first, then
        the still-set preemption flag drains the restored state. The
        drain's wall cost lands on ``run_preempted`` as ``drain_s``:
        measured, not inferred."""
        t_drain0 = time.monotonic()
        if self.monitor is not None:
            # same contract as _checkpoint: a diverged state must
            # never be checkpointed — not even by a preemption drain
            self.monitor.flush()
            check = getattr(self.monitor, "check_now", None)
            if check is not None:
                check(state, step=step)
            else:
                self.monitor.check_sync(step, state)
        self.checkpointer.finalize()
        if self.checkpointer.latest_step != step:
            self.checkpointer.save(step, state,
                                   metadata=self._metadata(step, state))
        self.checkpointer.finalize()
        _events.emit("run_preempted", step=step, label=self.label,
                     signum=self._preempt_signum,
                     checkpoint_step=step,
                     drain_s=round(time.monotonic() - t_drain0, 6))
        report = self._report(state, step, steps_run, t_run0,
                              completed=False, preempted=True)
        _events.emit("supervisor_done", step=step, label=self.label,
                     **{k: v for k, v in report.items()
                        if k not in ("state", "label",
                                     "incident_records")})
        return report

    def _report(self, state, step, steps_run, t_run0, completed,
                preempted):
        return {
            "state": state,
            "completed": bool(completed),
            "preempted": bool(preempted),
            "final_step": int(step),
            "steps_run": int(steps_run),
            "steps_replayed": int(sum(i["steps_replayed"]
                                      for i in self.incidents)),
            "incidents": len(self.incidents),
            "incident_records": list(self.incidents),
            "wall_s": float(time.monotonic() - t_run0),
            "last_good": self.checkpointer.last_good,
            "label": self.label,
        }
